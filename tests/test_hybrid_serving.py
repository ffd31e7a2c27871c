"""The policy of window and full attention layers behind the inference
server (PR 35): prefill then decode through the mixed arena against the
reference, what the server counts of the cache and of the ring, the
flags, the refusal to train. (The core itself:
tests/test_hybrid_attention.py, whose tiny sizes these share.)
"""

import numpy as np
import pytest

import jax

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config, validate_runtime
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.models import (HybridAttentionDims,
                                      HybridAttentionStack, LatentMoEStack,
                                      PowerRetentionStack, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import hybrid_attention_reference as reference
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
THETA = 1e4
EPS = 1e-5
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = HybridAttentionDims(window=4, cache_capacity=64, prefill_chunk=8)


def _agent():
  return SequenceAgent(num_actions=VOCAB, num_layers=5, hidden_size=32,
                       num_heads=HEADS, mlp_size=48, rope_theta=THETA,
                       norm_eps=EPS, core_dims=DIMS)


def _episodes(seed, lengths=((21, 10), (11, 6))):
  """[(prompt block, decode tokens)], the block all but the prompt's
  last token, which is the first decode token."""
  rng = np.random.RandomState(seed)
  return [(rng.randint(VOCAB, size=prompt).astype(np.int32),
           rng.randint(VOCAB, size=decode).astype(np.int32))
          for prompt, decode in lengths]


def test_prefill_then_decode_is_the_references_full_forward():
  """Three sessions behind the batcher (three rows in a bucket of four:
  a padded row in every call). Each plays two episodes: a prompt handed
  over in chunks of 8 with a ragged last one (five and two rings long),
  then decoding through the arena across the ring's wrap; the second
  episode reuses the slot. log mu(a) and baseline against the
  reference's forward of each episode alone, from its first token."""
  agent = _agent()
  params = init_params(agent, jax.random.PRNGKey(0), TOKEN_OBS)
  config = Config(inference_state_cache=True, inference_timeout_ms=20,
                  inference_min_batch=1, inference_state_slots=3)
  server = InferenceServer(agent, params, config, seed=11)
  server.warmup(TOKEN_OBS, sizes=[3])
  try:
    assert server.prefill_chunk == 8
    handles = [server.initial_core_state() for _ in range(3)]
    sessions = [_episodes(seed) for seed in (1, 2, 3)]
    served = [[], [], []]  # per session [(action, log mu, baseline)]
    with HIGHEST:
      for episode in range(2):
        for handle, session in zip(handles, sessions):
          handle.prefill(session[episode][0])
        for t in range(len(sessions[0][episode][1])):
          tokens = np.array([s[episode][1][t] for s in sessions])
          out, _ = server.policy(
              np.zeros(3, np.int32),
              StepOutput(np.zeros(3, np.float32), None,
                         np.zeros(3, bool), (tokens,)), handles)
          for j in range(3):
            served[j].append((out.action[j], out.policy_logits[j],
                              out.baseline[j]))
      at = 0
      for episode in range(2):
        steps = len(sessions[0][episode][1])
        for j, session in enumerate(sessions):
          block, decode = session[episode]
          actions, log_mu, baseline = (
              np.array(x) for x in zip(*served[j][at:at + steps]))
          ref_mu, ref_base, _ = reference.forward(
              params, np.concatenate([block, decode]),
              np.concatenate([np.zeros_like(block), actions]), dims=DIMS,
              num_heads=HEADS, rope_theta=THETA, norm_eps=EPS, block=8)
          np.testing.assert_allclose(log_mu, ref_mu[len(block):], atol=2e-4)
          np.testing.assert_allclose(baseline, ref_base[len(block):],
                                     atol=2e-4)
        at += steps
    stats = server.stats()
    assert stats['prefill_tokens'] == 3 * 32
    assert stats['prefill_chunks'] == 3 * (3 + 2)
    # Every call's rows read their position and one of the cache, and
    # of the ring its 4 columns (every position here is beyond 3).
    assert stats['cache_tokens_read'] == 3 * (
        sum(range(22, 32)) + sum(range(12, 18)))
    assert stats['window_tokens_read'] == 3 * 16 * 4
    assert (stats['cache_capacity'], stats['cache_window']) == (64, 4)
    # Four routed layers, three live rows of four choices a call: the
    # padded row routes nowhere.
    calls = stats['calls'] - 1  # the warm-up's rows were all padded
    assert 0 < stats['routed_rows_held'] <= calls * 4 * 3 * 4
    assert 0 < stats['experts_hit'] <= calls * 4 * 4
    # Two kinds of leaf: four rings of 4 columns and one cache of 64, 64
    # float32 a column, and the position; a row a slot and the one
    # padded rows are written to.
    assert stats['state_bytes_per_slot'] == (4 * 4 + 64) * 64 * 4 + 4
    assert stats['arena_bytes'] == 4 * stats['state_bytes_per_slot']
  finally:
    server.close()


def _config(**kw):
  base = dict(agent='sequence', env_backend='tokens', num_actions=VOCAB,
              level_name='tokens', episode_length=24, num_action_repeats=1,
              token_prompt_length=5, token_prompt_stride=3, num_actors=2,
              unroll_length=6, inference_state_cache=True,
              inference_state_slots=2, seq_num_layers=5, seq_hidden_size=32,
              seq_num_heads=HEADS, seq_mlp_size=48, seq_rope_theta=THETA,
              seq_norm_eps=EPS, seq_layer_pattern='LLLG', seq_window=4,
              seq_expert_groups=1, seq_expert_groups_kept=1,
              seq_cache_capacity=64, mode='test', slo_engine=False,
              controller='off')
  base.update(kw)
  return Config(**base)


def test_flags_build_the_core_the_widths_name():
  config = _config()
  validate_runtime(config)
  assert config.seq_core == 'hybrid'
  agent = driver.build_agent(config, VOCAB)
  assert agent.core_dims == DIMS and agent.prefill_chunk == 8
  assert isinstance(agent.core(), HybridAttentionStack)
  latent = _config(seq_layer_pattern='', seq_kv_lora_rank=16)
  assert latent.seq_core == 'latent' and isinstance(
      driver.build_agent(latent, VOCAB).core(), LatentMoEStack)
  plain = _config(seq_layer_pattern='')
  assert plain.seq_core == 'retention' and isinstance(
      driver.build_agent(plain, VOCAB).core(), PowerRetentionStack)
  with pytest.raises(ValueError, match='give one of them'):
    validate_runtime(_config(seq_kv_lora_rank=16))
  with pytest.raises(ValueError, match='does not fit a cache'):
    validate_runtime(_config(episode_length=65))
  with pytest.raises(ValueError, match='one letter a layer'):
    driver.build_agent(_config(seq_layer_pattern='LLGX'), VOCAB)
  # The prompt goes over as a block for this core too.
  spec = factory.make_env_spec(config, 'tokens', seed=5, is_test=True)
  assert spec.constructor_kwargs['prompt_block'] == 8


def test_training_the_core_is_refused(tmp_path):
  with pytest.raises(ValueError, match='not yet trained'):
    driver.train(_config(mode='train', logdir=str(tmp_path),
                         use_py_process=False), max_steps=1)

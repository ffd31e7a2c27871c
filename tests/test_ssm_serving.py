"""The policy of Mamba-2 and full attention layers behind the inference
server: prefill then decode through the arena against the reference in
log mu and in the baseline, with a state held in bfloat16 as the
control that the same tolerances refuse; what the server counts; the
flags; the refusal to train. (The core itself: tests/test_ssm_hybrid.py,
whose tiny sizes these share.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config, validate_runtime
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.models import (HybridAttentionStack, SSMHybridDims,
                                      SSMHybridStack, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import ssm_hybrid_reference as reference
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
EPS = 1e-5
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = SSMHybridDims(
    moe_size=16, routed_experts=16, experts_held=4, experts_per_token=4,
    expert_groups=1, expert_groups_kept=1, routed_scale=1.0,
    shared_experts=2, router='softmax', embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=2.0)
# The served model is float32 here and the reference's equal to float32
# rounding: log mu and baseline within 2e-4 (the CPU's own worst, 1e-5,
# with room). A state held in bfloat16 moves them by 1e-3 and more over
# these episodes: the tolerance lies between.
TOLERANCE = 2e-4


def _episodes(seed, lengths=((21, 10), (11, 6))):
  """[(prompt block, decode tokens)], the block all but the prompt's
  last token, which is the first decode token."""
  rng = np.random.RandomState(seed)
  return [(rng.randint(VOCAB, size=prompt).astype(np.int32),
           rng.randint(VOCAB, size=decode).astype(np.int32))
          for prompt, decode in lengths]


@pytest.fixture(scope='module')
def served():
  """Two sessions behind the batcher (two rows in a bucket of three: a
  padded row in every call), each two episodes; the second reuses the
  slot -> (params, sessions, [per session [(action, log mu, baseline)]],
  stats, [per session the Mamba layers' states in the arena after the
  last call])."""
  agent = SequenceAgent(num_actions=VOCAB, num_layers=3, hidden_size=32,
                        num_heads=HEADS, norm_eps=EPS, core_dims=DIMS)
  params = init_params(agent, jax.random.PRNGKey(0), TOKEN_OBS)
  config = Config(inference_state_cache=True, inference_timeout_ms=20,
                  inference_min_batch=1, inference_state_slots=2)
  server = InferenceServer(agent, params, config, seed=11)
  server.warmup(TOKEN_OBS, sizes=[3])
  try:
    assert server.prefill_chunk == 8
    handles = [server.initial_core_state() for _ in range(2)]
    sessions = [_episodes(seed) for seed in (1, 2)]
    rows = [[], []]
    with HIGHEST:
      for episode in range(2):
        for handle, session in zip(handles, sessions):
          handle.prefill(session[episode][0])
        for t in range(len(sessions[0][episode][1])):
          tokens = np.array([s[episode][1][t] for s in sessions])
          out, _ = server.policy(
              np.zeros(2, np.int32),
              StepOutput(np.zeros(2, np.float32), None,
                         np.zeros(2, bool), (tokens,)), handles)
          for j in range(2):
            rows[j].append((out.action[j], out.policy_logits[j],
                            out.baseline[j]))
    stats = server.stats()
    arena = server._arena_now()
    states = [[np.asarray(leaf[1][handle.slot]) for leaf in arena['layers']
               if isinstance(leaf, tuple)] for handle in handles]
  finally:
    server.close()
  return params, sessions, rows, stats, states


@pytest.mark.parametrize('state_dtype', [None, jnp.bfloat16],
                         ids=['as_stated', 'bfloat16_state'])
def test_prefill_then_decode_is_the_references_full_forward(served,
                                                            state_dtype):
  """log mu(a) and baseline of every decode step against the reference's
  forward of each episode alone, from its first token: within the
  tolerance as the configuration states the model, and out of it with
  the state in bfloat16."""
  params, sessions, rows, _, _ = served
  worst = 0.0
  with HIGHEST:
    at = 0
    for episode in range(2):
      steps = len(sessions[0][episode][1])
      for j, session in enumerate(sessions):
        block, decode = session[episode]
        actions, log_mu, baseline = (
            np.array(x) for x in zip(*rows[j][at:at + steps]))
        ref_mu, ref_base, _ = reference.forward(
            params, np.concatenate([block, decode]),
            np.concatenate([np.zeros_like(block), actions]), dims=DIMS,
            num_heads=HEADS, norm_eps=EPS, block=8, state_dtype=state_dtype)
        worst = max(worst, np.abs(log_mu - ref_mu[len(block):]).max(),
                    np.abs(baseline - ref_base[len(block):]).max())
      at += steps
  if state_dtype is None:
    assert worst < TOLERANCE
  else:
    assert worst > 3 * TOLERANCE


@pytest.mark.parametrize('state_dtype', [None, jnp.bfloat16],
                         ids=['as_stated', 'bfloat16_state'])
def test_the_arena_holds_the_state_of_the_references_recurrence(
    served, state_dtype):
  """What each session's arena row holds after its second episode's last
  call, both Mamba layers' S, against the S of the reference's
  recurrence over the same tokens: equal to float32 rounding as the
  configuration states the model (the CPU's own worst, 1e-6, with room),
  and far out with the state in bfloat16."""
  params, sessions, rows, _, states = served
  steps = len(sessions[0][1][1])
  worst = 0.0
  with HIGHEST:
    for j, session in enumerate(sessions):
      block, decode = session[1]
      actions = np.array([r[0] for r in rows[j][-steps:]])
      *_, reference_states = reference.forward(
          params, np.concatenate([block, decode]),
          np.concatenate([np.zeros_like(block), actions]), dims=DIMS,
          num_heads=HEADS, norm_eps=EPS, block=8, state_dtype=state_dtype,
          states=True)
      assert len(reference_states) == len(states[j]) == 2
      for held, want in zip(states[j], reference_states):
        worst = max(worst, np.linalg.norm(held - want) /
                    np.linalg.norm(want))
  if state_dtype is None:
    assert worst < 1e-5
  else:
    assert worst > 1e-3


def test_what_the_server_counts(served):
  _, _, _, stats, _ = served
  assert stats['prefill_tokens'] == 2 * 32
  assert stats['prefill_chunks'] == 2 * (3 + 2)
  # Every call's rows read their position and one of the full layer.
  assert stats['cache_tokens_read'] == 2 * (
      sum(range(22, 32)) + sum(range(12, 18)))
  assert (stats['cache_capacity'], stats['cache_window']) == (64, 0)
  # Two live rows a call, two Mamba layers each: the padded row is
  # stepped and not counted.
  assert stats['ssm_rows_stepped'] == 16 * 2 * 2
  calls = stats['calls'] - 1  # the warm-up's rows were all padded
  assert 0 < stats['routed_rows_held'] <= calls * 3 * 2 * 4
  # A slot: two Mamba layers' conv window (3 x 288) and state (4 x 8 x
  # 128), the full layer's cache (64 x 64), all float32, the position.
  assert stats['state_bytes_per_slot'] == 4 * (
      2 * (3 * 288 + 4 * 8 * 128) + 64 * 64) + 4
  assert stats['arena_bytes'] == 3 * stats['state_bytes_per_slot']


def _config(**kw):
  base = dict(agent='sequence', env_backend='tokens', num_actions=VOCAB,
              level_name='tokens', episode_length=24, num_action_repeats=1,
              token_prompt_length=5, token_prompt_stride=3, num_actors=2,
              unroll_length=6, inference_state_cache=True,
              inference_state_slots=2, seq_num_layers=3, seq_hidden_size=32,
              seq_num_heads=HEADS, seq_norm_eps=EPS, seq_layer_pattern='MMG',
              seq_moe_size=16, seq_routed_experts=16, seq_experts_held=4,
              seq_expert_groups=1, seq_expert_groups_kept=1,
              seq_routed_scale=1.0, seq_shared_experts=2,
              seq_router='softmax', seq_embedding_multiplier=3.0,
              seq_residual_multiplier=0.5, seq_attention_multiplier=0.0625,
              seq_logits_scaling=2.0, seq_cache_capacity=64, mode='test',
              slo_engine=False, controller='off')
  base.update(kw)
  return Config(**base)


def test_flags_build_the_core_the_widths_name():
  config = _config()
  validate_runtime(config)
  assert config.seq_core == 'ssm'
  agent = driver.build_agent(config, VOCAB)
  assert agent.core_dims == DIMS and agent.prefill_chunk == 8
  assert isinstance(agent.core(), SSMHybridStack)
  attention = _config(seq_layer_pattern='LLLG', seq_router='sigmoid',
                      seq_num_layers=5)
  assert attention.seq_core == 'hybrid' and isinstance(
      driver.build_agent(attention, VOCAB).core(), HybridAttentionStack)
  with pytest.raises(ValueError, match='does not fit a cache'):
    validate_runtime(_config(episode_length=65))
  with pytest.raises(ValueError, match='one letter a layer'):
    driver.build_agent(_config(seq_layer_pattern='MLG'), VOCAB)
  with pytest.raises(ValueError, match='one group'):
    driver.build_agent(_config(seq_expert_groups=4), VOCAB)
  # The prompt goes over as a block for this core too.
  spec = factory.make_env_spec(config, 'tokens', seed=5, is_test=True)
  assert spec.constructor_kwargs['prompt_block'] == 8


def test_training_the_core_is_refused(tmp_path):
  with pytest.raises(ValueError, match='not yet trained'):
    driver.train(_config(mode='train', logdir=str(tmp_path),
                         use_py_process=False), max_steps=1)

"""The latent-attention policy behind the inference server (PR 32): the
prefill entry, positions and counters, the token env's prompt block,
the actor's hand-over, the flags, and that an agent without a chunk
form is served what the parent commit's program computed. (The core itself:
tests/test_latent_moe.py, whose tiny sizes these share.)
"""

import numpy as np
import pytest

import jax

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config, validate_runtime
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.envs.tokens import TokenEnv
from scalable_agent_tpu.models import (PowerRetentionStack, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import latent_moe
from scalable_agent_tpu.models import latent_moe_reference as reference
from scalable_agent_tpu.models.latent_moe import LatentMoEDims
from scalable_agent_tpu.runtime.actor import Actor, ActorGroup
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
THETA = 1e4
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = LatentMoEDims(cache_capacity=64, prefill_chunk=8)


def _agent(dims=DIMS, **kw):
  return SequenceAgent(num_actions=VOCAB, num_layers=3, hidden_size=32,
                       num_heads=HEADS, mlp_size=48, rope_theta=THETA,
                       core_dims=dims, **kw)


def _params(agent, seed=0):
  return init_params(agent, jax.random.PRNGKey(seed), TOKEN_OBS)


def _reference(params, tokens, actions, dims=DIMS, **kw):
  return reference.forward(params, tokens, actions, dims=dims,
                           num_heads=HEADS, rope_theta=THETA, block=8, **kw)


def _server(agent, params, **cfg):
  config = Config(inference_state_cache=True, inference_timeout_ms=20,
                  inference_min_batch=1, **cfg)
  return InferenceServer(agent, params, config, seed=11)


def _episodes(seed, lengths=((21, 10), (11, 6))):
  """[(prompt block, decode tokens)], the block all but the prompt's
  last token, which is the first decode token."""
  rng = np.random.RandomState(seed)
  return [(rng.randint(VOCAB, size=prompt).astype(np.int32),
           rng.randint(VOCAB, size=decode).astype(np.int32))
          for prompt, decode in lengths]


class TestServer:

  def test_prefill_then_decode_is_the_references_full_forward(self):
    """(a), (g): three sessions behind the batcher (three rows in a
    bucket of four: a padded row in every call). Each plays two
    episodes: a prompt handed over in chunks of 8 with a ragged last
    one, then decoding through the arena; the second episode reuses
    the slot. log mu(a) and baseline against the reference's forward
    of each episode alone, from its first token."""
    agent = _agent()
    params = _params(agent)
    server = _server(agent, params, inference_state_slots=3)
    server.warmup(TOKEN_OBS, sizes=[3])
    try:
      assert server.prefill_chunk == 8
      handles = [server.initial_core_state() for _ in range(3)]
      assert [h.prefill_chunk for h in handles] == [8, 8, 8]
      sessions = [_episodes(seed) for seed in (1, 2, 3)]
      served = [[], [], []]  # per session [(action, log mu, baseline)]
      with HIGHEST:
        for episode in range(2):
          for handle, session in zip(handles, sessions):
            handle.prefill(session[episode][0])
          steps = len(sessions[0][episode][1])
          for t in range(steps):
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
            tokens = np.concatenate([block, decode])
            forced = np.concatenate([np.zeros_like(block), actions])
            ref_mu, ref_base, _ = _reference(params, tokens, forced)
            np.testing.assert_allclose(
                log_mu, ref_mu[len(block):], atol=2e-4)
            np.testing.assert_allclose(
                baseline, ref_base[len(block):], atol=2e-4)
          at += steps
      stats = server.stats()
      # 3 sessions x (21 + 11) prompt tokens, in ceil(21/8) + ceil(11/8)
      # chunks each; every call's rows read their position and one.
      assert stats['prefill_tokens'] == 3 * 32
      assert stats['prefill_chunks'] == 3 * (3 + 2)
      assert stats['cache_tokens_read'] == 3 * (
          sum(range(22, 32)) + sum(range(12, 18)))
      assert stats['cache_capacity'] == 64
      # Two routed layers, three live rows of four choices a call: the
      # padded row routes nowhere.
      calls = stats['calls'] - 1  # the warm-up's rows were all padded
      assert 0 < stats['routed_rows_held'] <= calls * 2 * 3 * 4
      assert 0 < stats['experts_hit'] <= calls * 2 * 4
      assert stats['experts_hit'] <= stats['routed_rows_held']
      # A row a slot and the one padded rows are written to.
      assert stats['arena_bytes'] == 4 * stats['state_bytes_per_slot']
      assert stats['state_bytes_per_slot'] == 3 * 64 * 20 * 4 + 4
    finally:
      server.close()

  def test_a_slot_sees_nothing_of_the_episode_before(self):
    """(g): `done` resets a row's position, not its cache; a slot
    released and acquired again, or reset by `done` in mid-stream,
    answers as a fresh session does."""
    agent = _agent()
    params = _params(agent)
    server = _server(agent, params, inference_state_slots=2)
    server.warmup(TOKEN_OBS, sizes=[1])
    try:
      rng = np.random.RandomState(4)
      tokens = rng.randint(VOCAB, size=12).astype(np.int32)

      def play(handle, tokens, dones):
        outs = []
        for token, done in zip(tokens, dones):
          out, _ = server.policy(
              np.int32(0), StepOutput(np.float32(0), None, np.bool_(done),
                                      (np.int32(token),)), handle)
          outs.append((out.action, out.policy_logits, out.baseline))
        return [np.array(x) for x in zip(*outs)]

      with HIGHEST:
        first = server.initial_core_state()
        first.prefill(rng.randint(VOCAB, size=30).astype(np.int32))
        # `done` in mid-stream: the episode of 30 + 5 tokens is gone.
        dones = np.arange(12) == 5
        actions, log_mu, baseline = play(first, tokens, dones)
        forced = actions[5:]
        ref_mu, ref_base, _ = _reference(params, tokens[5:], forced)
      np.testing.assert_allclose(log_mu[5:], ref_mu, atol=2e-4)
      np.testing.assert_allclose(baseline[5:], ref_base, atol=2e-4)
      first.release()
      with HIGHEST:
        again = server.initial_core_state()
        assert again.slot == first.slot
        actions, log_mu, _ = play(again, tokens[5:], np.arange(7) == 0)
        ref_mu, _, _ = _reference(params, tokens[5:], actions)
      np.testing.assert_allclose(log_mu, ref_mu, atol=2e-4)
      with pytest.raises(RuntimeError, match='released'):
        first.prefill(tokens)
    finally:
      server.close()

  def test_an_agent_without_a_chunk_form_is_not_prefilled(self):
    agent = SequenceAgent(num_actions=VOCAB)
    server = _server(agent, _params(agent), inference_state_slots=1)
    try:
      handle = server.initial_core_state()
      assert server.prefill_chunk == 0 and handle.prefill_chunk == 0
      with pytest.raises(RuntimeError, match='no chunk form'):
        handle.prefill(np.zeros(3, np.int32))
      stats = server.stats()
      assert stats['cache_capacity'] == 0 and stats['prefill_chunks'] == 0
      assert 'experts_hit' not in stats
    finally:
      server.close()


def _config(**kw):
  base = dict(agent='sequence', env_backend='tokens', num_actions=VOCAB,
              level_name='tokens', episode_length=24, num_action_repeats=1,
              token_prompt_length=5, token_prompt_stride=3, num_actors=2,
              unroll_length=6, inference_state_cache=True,
              inference_state_slots=2, seq_num_layers=3, seq_hidden_size=32,
              seq_num_heads=HEADS, seq_mlp_size=48, seq_rope_theta=THETA,
              seq_kv_lora_rank=16, seq_cache_capacity=64,
              mode='test', slo_engine=False, controller='off')
  base.update(kw)
  return Config(**base)


def test_flags_build_the_core_the_widths_name():
  config = _config()
  validate_runtime(config)
  agent = driver.build_agent(config, VOCAB)
  assert agent.core_dims == DIMS and agent.prefill_chunk == 8
  assert isinstance(agent.core(), latent_moe.LatentMoEStack)
  plain = driver.build_agent(_config(seq_kv_lora_rank=0), VOCAB)
  assert plain.core_dims is None and isinstance(plain.core(),
                                             PowerRetentionStack)
  with pytest.raises(ValueError, match='does not fit a cache'):
    validate_runtime(_config(episode_length=65))
  with pytest.raises(ValueError, match='longest prompt'):
    validate_runtime(_config(token_prompt_stride=30))
  with pytest.raises(ValueError, match='kept groups'):
    driver.build_agent(_config(seq_experts_per_token=9), VOCAB)


def test_training_the_chunked_core_is_refused(tmp_path):
  with pytest.raises(ValueError, match='not yet trained'):
    driver.train(_config(mode='train', logdir=str(tmp_path),
                         use_py_process=False), max_steps=1)
  # And without the arena there is nowhere for its cache to live.
  agent = driver.build_agent(_config(), VOCAB)
  with pytest.raises(ValueError, match='--inference_state_cache'):
    InferenceServer(agent, _params(agent),
                    _config(inference_state_cache=False))


def test_token_env_offers_its_prompt_as_a_block():
  env = TokenEnv(vocab_size=VOCAB, episode_length=12, prompt_length=5,
                 seed=3, prompt_block=9, start_step=7)
  twin = TokenEnv(vocab_size=VOCAB, episode_length=12, prompt_length=5,
                  seed=3)
  prompt = [int(twin.initial()[0])] + [
      int(twin.step(0)[2][0]) for _ in range(4)]
  # The first observation is the prompt's last token; the block is
  # what lies before it, zero-padded to the fleet's longest.
  assert int(env.initial()[0]) == prompt[-1]
  with pytest.raises(RuntimeError, match='nobody took it'):
    env.step(0)
  block, n = env.prompt_block()
  assert block.shape == (9,) and block.dtype == np.int32 and n == 4
  assert block.tolist() == prompt[:4] + [0] * 5
  # 12 tokens an episode: 5 of prompt, so `done` on the 8th step.
  dones = [bool(env.step(1)[1]) for _ in range(8)]
  assert dones == [False] * 7 + [True]
  with pytest.raises(RuntimeError, match='nobody took it'):
    env.step(0)
  assert env.prompt_block()[1] == 4
  assert twin.prompt_block() is None
  spec = TokenEnv._tensor_specs('prompt_block', {}, {'prompt_block': 9})
  assert spec[0].shape == (9,) and spec[1].shape == ()
  # The factory: session i's prompt is 3 (i mod fleet) tokens longer,
  # and the block is sized for the longest.
  specs = [factory.make_env_spec(_config(), 'tokens', seed=seed,
                                 is_test=True) for seed in (4, 5)]
  assert [s.constructor_kwargs['prompt_length'] for s in specs] == [5, 8]
  assert {s.constructor_kwargs['prompt_block'] for s in specs} == {8}
  plain = factory.make_env_spec(_config(seq_kv_lora_rank=0), 'tokens',
                                seed=5, is_test=True)
  assert plain.constructor_kwargs['prompt_block'] == 0


@pytest.mark.parametrize('use_py_process', [False, True],
                         ids=['in_process', 'process_hosted'])
def test_an_actor_hands_the_prompt_over_where_an_episode_begins(
    use_py_process):
  """Two envs, one actor group, the real server: every episode's
  block reaches the server before the episode's first policy call,
  that call carries no `done`, and what the policy answers is the
  reference's forward of the episode so far."""
  config = _config(use_py_process=use_py_process)
  agent = driver.build_agent(config, VOCAB)
  params = _params(agent)
  server = InferenceServer(agent, params, config, seed=5, pad_batch_to=2)
  server.warmup(TOKEN_OBS, max_size=2)
  blocks, calls = [], []
  prefill = server.prefill

  def recording_prefill(handle, tokens):
    blocks.append((handle.slot, np.array(tokens)))
    return prefill(handle, tokens)

  def policy(prev_action, env_output, core_state):
    out, state = server.policy(prev_action, env_output, core_state)
    calls.append((np.array(env_output.observation[0]),
                  np.array(env_output.done), np.array(out.action),
                  np.array(out.policy_logits), len(blocks)))
    return out, state

  server.prefill = recording_prefill
  made = []
  try:
    for i in range(2):
      spec = factory.make_env_spec(config, 'tokens', seed=i + 1,
                                   is_test=True)
      env, process = factory.build_environment(
          spec, use_py_process=use_py_process)
      made.append((env, process))
    group = ActorGroup([
        Actor(env, policy, server.initial_core_state(), unroll_length=6)
        for env, _ in made])
    with HIGHEST:
      unrolls = group.unroll() + group.unroll() + group.unroll()
    # Prompts of 8 and 5 tokens (seeds 1, 2 of a fleet of 2): blocks of
    # 7 and 4; episodes of 24 tokens: 17 and 20 steps, so 18 steps see
    # session 0 begin again.
    assert [len(b) for _, b in blocks] == [7, 4, 7]
    assert calls[0][4] == 0 and calls[1][4] == 2  # priming: no block
    assert all(not np.any(done) for _, done, *_ in calls[1:])
    # The unroll keeps the env's `done`.
    dones = np.concatenate([u.env_outputs.done[1:] for u in unrolls[::2]])
    assert dones.tolist() == [False] * 16 + [True, False]
    with HIGHEST:
      for j, (slot, block) in enumerate(blocks[:2]):
        tokens = np.array([c[0][j] for c in calls[1:18 - j]])
        actions = np.array([c[2][j] for c in calls[1:18 - j]])
        log_mu = np.array([c[3][j] for c in calls[1:18 - j]])
        ref_mu, _, _ = _reference(
            params, np.concatenate([block, tokens]),
            np.concatenate([np.zeros_like(block), actions]))
        np.testing.assert_allclose(log_mu, ref_mu[len(block):], atol=2e-4)
    assert server.stats()['prefill_tokens'] == 18
  finally:
    for env, process in made:
      (process or env).close()
    server.close()


def test_the_retention_programs_are_the_parents():
  """(h), rewritten for PR 36 (the cache-mode case of tests/
  test_serving.py :: test_packed_step_is_the_per_array_body_bit_for_bit
  for an agent without a chunk form): `jit_cache_step` of the
  power-retention agent at the rehearsal's widths takes ONE buffer
  and gives ONE back, and what it computes is what PR 32's parent
  computed from five arrays, written out below: outputs, key and
  arena bit for bit, the padded row included. (The lowered text this
  test compared with that of commit 9c51580 changes with the step's
  arguments; the values do not.)"""
  from scalable_agent_tpu.runtime import packing
  config = Config(agent='sequence', env_backend='tokens', num_actions=VOCAB,
                  inference_state_cache=True, inference_state_slots=3,
                  inference_min_batch=1, inference_timeout_ms=20)
  agent = driver.build_agent(config, VOCAB)
  params = _params(agent)
  server = InferenceServer(agent, params, config, seed=1)
  real, calls = server._step, []
  host = lambda tree: jax.tree_util.tree_map(np.array, tree)  # noqa: E731

  def recording_step(params_, key, arena, packed, layout):
    call = (host(key), host(arena), packed.copy(), layout)
    outs = real(params_, key, arena, packed, layout)
    calls.append(call + (host(outs),))
    return outs

  @jax.jit
  def parent_cache_step(params_, key, arena, slot_ids, prev_action, reward,
                        done, token):
    key, sub = jax.random.split(key)
    env_output = StepOutput(reward=reward[None], info=None,
                            done=done[None], observation=(token[None],))
    out, arena = agent.apply(params_, prev_action[None], env_output, arena,
                             sample_rng=sub, state_slots=slot_ids)
    return key, arena, out.action[0], out.policy_logits[0], out.baseline[0]

  server._step = recording_step
  try:
    assert server.prefill_chunk == 0
    handles = [server.initial_core_state() for _ in range(3)]
    rng = np.random.RandomState(5)
    prev = np.zeros(3, np.int32)
    for t in range(4):
      out, _ = server.policy(
          prev, StepOutput(rng.randn(3).astype(np.float32), None,
                           np.array([False, t == 2, False]),
                           (rng.randint(VOCAB, size=3).astype(np.int32),)),
          handles)
      prev = np.asarray(out.action, np.int32)
    stats = server.stats()
  finally:
    server.close()
  assert len(calls) == 4
  assert stats['h2d_buffers_per_call'] == stats['d2h_buffers_per_call'] == 1
  for key, arena, packed, layout, (new_key, new_arena, packed_out) in calls:
    assert packed.shape == (layout.words,) and packed.dtype == np.uint32
    assert [shape for _, shape in layout.specs] == [(4,)] * 5  # 3 + a pad
    want = parent_cache_step(params, key, arena,
                             *packing.host_views(packed, layout))
    got = (new_key, new_arena, *packing.host_views(
        packed_out, server._out_layouts[layout]))
    want, got = (jax.tree_util.tree_leaves(x) for x in (want, got))
    assert len(want) == len(got) > 5
    for g, w in zip(got, want):
      assert g.dtype == w.dtype and g.shape == w.shape
      np.testing.assert_array_equal(g, np.asarray(w))

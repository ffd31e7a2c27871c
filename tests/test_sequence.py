"""The sequence policy (PR 27): the core protocol, power retention
against its attention-form reference, the pytree state arena of the
inference server, and the token environment end to end.

Everything runs at a tiny size in float32 on the CPU: hidden 64, 4
query / 2 key-value heads of 16, 2 layers, a vocabulary of 97.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import driver
from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config, validate_runtime
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.envs.tokens import TokenEnv
from scalable_agent_tpu.models import (ImpalaAgent, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import retention, retention_reference
from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.ops import retention_pallas
from scalable_agent_tpu.runtime import inference as inference_lib
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput, observation_leaves
from scalable_agent_tpu.testing import make_example_batch

VOCAB = 97
DIMS = dict(num_heads=4, num_kv_heads=2, head_dim=16)
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')


def _agent(**kw):
  return SequenceAgent(num_actions=VOCAB, **kw)


def _params(agent, seed=0):
  return init_params(agent, jax.random.PRNGKey(seed), TOKEN_OBS)


def _env_output(tokens, dones):
  """[B] tokens and dones as a one-step [1, B] StepOutput."""
  tokens = jnp.asarray(tokens, jnp.int32)[None]
  return StepOutput(reward=jnp.zeros(tokens.shape, jnp.float32),
                    info=None, done=jnp.asarray(dones, bool)[None],
                    observation=(tokens,))


def _chain(agent, params, tokens, dones, seed=3):
  """The single step chained over [T] for one session -> (actions,
  log mu, baselines), each [T]."""
  state = agent.initial_state(1)
  step = jax.jit(lambda state, tok, done, key: agent.apply(
      params, jnp.zeros((1, 1), jnp.int32), _env_output(tok, done),
      state, sample_rng=key))
  outs = []
  for t in range(len(tokens)):
    out, state = step(state, tokens[t:t + 1], dones[t:t + 1],
                      jax.random.fold_in(jax.random.PRNGKey(seed), t))
    outs.append(jax.tree_util.tree_map(lambda x: np.asarray(x)[0, 0],
                                       out))
  return tuple(np.stack(x) for x in zip(*outs))


def _stretch(t=40, resets=(0, 13, 29), seed=5):
  rng = np.random.RandomState(seed)
  tokens = rng.randint(VOCAB, size=t).astype(np.int32)
  dones = np.zeros(t, bool)
  dones[list(resets)] = True
  return tokens, dones


def test_phi_is_the_degree_two_embedding():
  rng = np.random.RandomState(0)
  for d in (2, 16, 128):
    a, b = rng.randn(2, 5, d).astype(np.float32)
    got = jnp.sum(retention.phi(a) * retention.phi(b), axis=(-1, -2))
    want = np.sum(a.astype(np.float64) * b, axis=-1) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    assert retention.phi(a).shape[-2:] == (d // 2 + 1, d)
    assert retention.phi_size(d) == (d // 2 + 1) * d
    # The reference's own tiling (the upper triangle) agrees.
    ref = jnp.sum(retention_reference._phi(a) *
                  retention_reference._phi(b), axis=-1)
    np.testing.assert_allclose(ref, want, rtol=2e-5, atol=1e-5)


def test_chained_step_matches_the_attention_form():
  """40 steps of the single step, two `done`s inside the stretch,
  against the reference's attention form with the tokens forced."""
  agent = _agent()
  params = _params(agent)
  tokens, dones = _stretch()
  with HIGHEST:
    actions, log_mu, baseline = _chain(agent, params, tokens, dones)
    ref_log_mu, ref_baseline = retention_reference.forward(
        params, tokens, dones, actions, **DIMS)
    rec_log_mu, rec_baseline = retention_reference.forward_recurrent(
        params, tokens, dones, actions, **DIMS)
  np.testing.assert_allclose(log_mu, ref_log_mu, atol=2e-4)
  np.testing.assert_allclose(baseline, ref_baseline, atol=2e-4)
  # The reference's two forms agree with each other too.
  np.testing.assert_allclose(rec_log_mu, ref_log_mu, atol=2e-4)
  np.testing.assert_allclose(rec_baseline, ref_baseline, atol=2e-4)
  # The retention state matters: without the resets the numbers differ.
  with HIGHEST:
    other, _ = retention_reference.forward(
        params, tokens, np.eye(1, len(tokens), 0, dtype=bool)[0],
        actions, **DIMS)
  assert np.abs(np.asarray(other) - log_mu)[14:].max() > 1e-2


def test_reference_in_a_bfloat16_state_is_told_apart():
  agent = _agent()
  params = _params(agent)
  tokens, dones = _stretch()
  with HIGHEST:
    actions, log_mu, _ = _chain(agent, params, tokens, dones)
    low, _ = retention_reference.forward_recurrent(
        params, tokens, dones, actions, state_dtype=jnp.bfloat16, **DIMS)
  assert np.abs(np.asarray(low) - log_mu).max() > 2e-3


def test_unroll_is_the_scan_of_the_step():
  """The learner's pass over [T, B] equals the chained single step:
  full logits in the learner's pass, log mu(a) when acting."""
  agent = _agent()
  params = _params(agent)
  b = 3
  streams = [_stretch(12, (0, 5), seed) for seed in range(b)]
  tokens = np.stack([s[0] for s in streams], 1)
  dones = np.stack([s[1] for s in streams], 1)
  env_outputs = StepOutput(
      reward=jnp.zeros(tokens.shape, jnp.float32), info=None,
      done=jnp.asarray(dones), observation=(jnp.asarray(tokens),))
  with HIGHEST:
    out, final = jax.jit(lambda: agent.apply(
        params, jnp.zeros(tokens.shape, jnp.int32), env_outputs,
        agent.initial_state(b)))()
    assert out.policy_logits.shape == (12, b, VOCAB)
    log_probs = jax.nn.log_softmax(out.policy_logits)
    for j in range(b):
      actions, log_mu, baseline = _chain(agent, params, tokens[:, j],
                                         dones[:, j])
      picked = np.asarray(log_probs)[np.arange(12), j, actions]
      np.testing.assert_allclose(picked, log_mu, atol=2e-4)
      np.testing.assert_allclose(out.baseline[:, j], baseline, atol=2e-4)
  assert int(final['pos'][0]) == 12 - 5


def test_core_protocol_default_arena_step():
  """The LSTM core on rows of an arena: the rows named advance as the
  carry form does, a padded row touches nothing."""
  core = core_lib.LSTMCore(8)
  x = jnp.ones((3, 5))
  done = jnp.array([False, True, False])
  carry = jax.tree_util.tree_map(
      lambda s: s + jnp.arange(3.0)[:, None] + 1, core.initial_state(3))
  params = core.init(jax.random.PRNGKey(0), carry, x, done,
                     method=core.step)
  want, _ = core.apply(params, carry, x, done, method=core.step)
  arena = jax.tree_util.tree_map(lambda a: a + 7.0, core.arena(6))
  slots = jnp.array([4, 1, 1 << 30])
  arena = core_lib.scatter_rows(arena, slots, carry)
  got, _ = core.apply(params, arena, x, done, slots=slots,
                      method=core.step)
  for g, w, a in zip(got, want, arena):
    np.testing.assert_array_equal(g[jnp.array([4, 1])], w[:2])
    for untouched in (0, 2, 3, 5):
      np.testing.assert_array_equal(g[untouched], a[untouched])


def test_kernel_updates_named_rows_in_place():
  rows, kv, dv, dk, groups, b = 5, 2, 16, 16, 2, 4
  rng = np.random.RandomState(0)
  f32 = lambda *shape: rng.randn(*shape).astype(np.float32)  # noqa: E731
  state = f32(rows, kv, dv, retention.phi_size(dk))
  ids = np.array([2, 1 << 30, 0, -1], np.int32)
  decay, q, k, v = f32(b, kv), f32(b, kv, groups, dk), f32(b, kv, dk), f32(
      b, kv, dv)
  new, num = retention_pallas.update_rows(jnp.asarray(state), ids, decay,
                                          q, k, v)
  new, num = np.asarray(new), np.asarray(num)
  phi_k = np.asarray(retention.phi(k)).reshape(b, kv, -1)
  phi_q = np.asarray(retention.phi(q)).reshape(b, kv, groups, -1)
  for row, slot in ((0, 2), (2, 0)):
    want = (decay[row][:, None, None] * state[slot] +
            v[row][:, :, None] * phi_k[row][:, None, :])
    np.testing.assert_allclose(new[slot], want, atol=1e-5)
    np.testing.assert_allclose(
        num[row], np.einsum('jnm,jgm->jgn', want, phi_q[row]),
        rtol=1e-4, atol=1e-4)
  # Padded rows (an id out of range, either side) land in the last
  # row, which is no session's; the other sessions' rows are bitwise
  # what they were.
  for slot in (1, 3):
    np.testing.assert_array_equal(new[slot], state[slot])


def _server(agent, params, **cfg):
  config = Config(inference_state_cache=True, inference_timeout_ms=20,
                  inference_min_batch=1, **cfg)
  return InferenceServer(agent, params, config, seed=11)


class TestServerArena:

  def test_state_cache_matches_the_direct_step(self):
    """Three sessions behind the batcher, each against the direct
    single step chained on its own tokens: padded rows (three rows in
    a bucket of four), a `done` in mid-stream, and a slot released
    and taken up by a new session."""
    agent = _agent()
    params = _params(agent)
    server = _server(agent, params, inference_state_slots=3)
    server.warmup(TOKEN_OBS, sizes=[3])
    try:
      assert server.slots_free() == 3
      handles = [server.initial_core_state() for _ in range(3)]
      streams = [_stretch(9, (0, 4), seed) for seed in (1, 2, 3)]

      def direct(tokens, dones):
        state = agent.initial_state(1)
        rows = []
        for t in range(len(tokens)):
          out, state = agent.apply(
              params, jnp.zeros((1, 1), jnp.int32),
              _env_output(tokens[t:t + 1], dones[t:t + 1]), state)
          rows.append(out)
        return rows, state

      def call(step_rows):
        """One 3-row request (an ActorGroup's form)."""
        tokens = np.array([s[0][t] for s, t in step_rows], np.int32)
        dones = np.array([s[1][t] for s, t in step_rows])
        out, _ = server.policy(
            np.zeros(3, np.int32),
            StepOutput(np.zeros(3, np.float32), None, dones, (tokens,)),
            handles)
        return out

      with HIGHEST:
        served = [call([(s, t) for s in streams]) for t in range(9)]
        wants = [direct(*s) for s in streams]
      for j, (rows, state) in enumerate(wants):
        # Acting output: log mu(a) is a scalar a row; the greedy
        # logits of the direct pass at the sampled action agree.
        for t in range(9):
          assert served[t].policy_logits.shape == (3,)
          action = int(served[t].action[j])
          log_probs = jax.nn.log_softmax(rows[t].policy_logits[0, 0])
          np.testing.assert_allclose(served[t].policy_logits[j],
                                     log_probs[action], atol=2e-4)
          np.testing.assert_allclose(served[t].baseline[j],
                                     rows[t].baseline[0, 0], atol=2e-4)
        snapshot = handles[j].snapshot()
        for got, want in zip(jax.tree_util.tree_leaves(snapshot),
                             jax.tree_util.tree_leaves(state)):
          assert got.shape == want.shape and got.dtype == want.dtype
          np.testing.assert_allclose(got, want, atol=2e-5)
      stats = server.stats()
      assert stats['mean_batch'] == 3.0
      assert stats['state_bytes_per_slot'] == sum(
          int(np.prod(l.shape)) * l.dtype.itemsize for l in
          jax.tree_util.tree_leaves(jax.eval_shape(
              lambda: agent.initial_state(1))))
      # One row more than there are slots in every S (the padded
      # rows' own row): 4 rows of S, 3 of z and pos.
      s_bytes = 2 * 16 * retention.phi_size(16) * 4
      assert stats['arena_bytes'] == (
          stats['state_bytes_per_slot'] * 3 + 2 * s_bytes)
      # 3 acquires, and 3 sessions x 2 `done` rows.
      assert stats['state_resets'] == 3 + 6

      # A released slot comes back zeroed: the next session's first
      # step equals a fresh session's whatever the slot held.
      handles[1].release()
      assert server.slots_free() == 1
      fresh = server.initial_core_state()
      assert fresh.slot == handles[1].slot
      for leaf in jax.tree_util.tree_leaves(fresh.snapshot()):
        assert not np.any(leaf)
      with pytest.raises(RuntimeError, match='released'):
        handles[1].snapshot()
    finally:
      server.close()

  def test_carry_mode_is_refused_above_the_size(self):
    wide = _agent(num_layers=1, num_heads=2, num_kv_heads=2, head_dim=64)
    state_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: wide.initial_state(1))))
    assert state_bytes > inference_lib.MAX_HOST_STATE_BYTES
    params = jax.eval_shape(lambda: _params(wide))
    with pytest.raises(ValueError, match='--inference_state_cache'):
      InferenceServer(wide, params, Config(inference_state_cache=False))
    # A state under it is carried, as the LSTM's is.
    small = _agent()
    server = InferenceServer(small, _params(small),
                             Config(inference_state_cache=False,
                                    inference_timeout_ms=5))
    try:
      out, state = server.policy(
          np.int32(0), StepOutput(np.float32(0), None, np.bool_(True),
                                  (np.int32(5),)),
          server.initial_core_state())
      assert np.shape(out.policy_logits) == ()
      assert int(state['pos'][0]) == 1
      assert state['layers'][0][0].dtype == np.float32
    finally:
      server.close()

  def test_a_state_above_the_size_is_never_snapshotted(self, monkeypatch):
    monkeypatch.setattr(inference_lib, 'MAX_HOST_STATE_BYTES', 1024)
    agent = _agent()
    server = _server(agent, _params(agent), inference_state_slots=2)
    try:
      handle = server.initial_core_state()
      assert handle.snapshot() is None
      handle.write(None)  # the priming undo: back to zero
      assert server.stats()['state_resets'] == 2
    finally:
      server.close()


class TestLstmThroughTheArena:
  """The paper's agent through the pytree arena computes what the
  `(arena_c, arena_h)` pair did, and its parameter tree is unchanged."""

  OBS = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}

  def test_parameter_tree_is_the_checkpointed_one(self):
    agent = ImpalaAgent(num_actions=5, torso='shallow')
    params = init_params(agent, jax.random.PRNGKey(0), self.OBS)
    paths = sorted('/'.join(str(k.key) for k in path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    lstm = 'params/_ResetCore_0/OptimizedLSTMCell_0/'
    assert [p for p in paths if p.startswith('params/_ResetCore_0')] == (
        sorted(lstm + f'h{g}/{leaf}' for g in 'fgio'
               for leaf in ('bias', 'kernel')) +
        sorted(lstm + f'i{g}/kernel' for g in 'fgio'))
    assert {p.split('/')[1] for p in paths} == {
        'InstructionEncoder_0', 'ShallowTorso_0', '_ResetCore_0',
        'baseline', 'policy_logits'}
    assert agent.observation_names == ('frame', 'instr')
    assert observation_leaves(self.OBS) == (
        ((24, 32, 3), np.dtype(np.uint8)),
        ((MAX_INSTRUCTION_LEN,), np.dtype(np.int32)))

  def test_arena_step_is_bit_equal_to_gather_apply_scatter(self):
    agent = ImpalaAgent(num_actions=5, torso='shallow',
                        use_instruction=False)
    params = init_params(agent, jax.random.PRNGKey(0), self.OBS)
    rng = np.random.RandomState(0)
    b, slots = 4, 6
    arena = tuple(jnp.asarray(rng.randn(slots, 256).astype(np.float32))
                  for _ in range(2))
    ids = jnp.array([5, 0, 1 << 30, 3])
    env_output = StepOutput(
        reward=jnp.asarray(rng.randn(1, b).astype(np.float32)), info=None,
        done=jnp.array([[False, True, False, False]]),
        observation=(jnp.asarray(rng.randint(0, 255, (1, b, 24, 32, 3)),
                                 jnp.uint8),
                     jnp.zeros((1, b, MAX_INSTRUCTION_LEN), jnp.int32)))
    prev = jnp.zeros((1, b), jnp.int32)
    key = jax.random.PRNGKey(4)

    @jax.jit
    def before(arena):  # inference.py's cache_step as it was
      out, (c, h) = agent.apply(params, prev, env_output,
                                (arena[0][ids], arena[1][ids]),
                                sample_rng=key)
      return out, (arena[0].at[ids].set(c, mode='drop'),
                   arena[1].at[ids].set(h, mode='drop'))

    @jax.jit
    def now(arena):
      return agent.apply(params, prev, env_output, arena,
                         sample_rng=key, state_slots=ids)

    for got, want in zip(jax.tree_util.tree_leaves(now(arena)),
                         jax.tree_util.tree_leaves(before(arena))):
      np.testing.assert_array_equal(got, want)
    assert jax.tree_util.tree_structure(agent.state_arena(6)) == (
        jax.tree_util.tree_structure(arena))


def test_token_env_contract():
  env = TokenEnv(vocab_size=VOCAB, episode_length=6, prompt_length=2,
                 seed=3)
  (first,) = env.initial()
  assert first.dtype == np.int32 and first.shape == ()
  seen, rewards, dones = [int(first)], [], []
  for t in range(12):
    reward, done, (token,) = env.step(40 + t)
    seen.append(int(token))
    rewards.append(float(reward))
    dones.append(bool(done))
  # Prompt token, prompt token, then the last action echoed; `done`
  # at the episode's length, with the next prompt's first token.
  assert seen[2:6] == [41, 42, 43, 44] and seen[8:12] == [47, 48, 49, 50]
  assert dones == [False] * 5 + [True] + [False] * 5 + [True]
  assert rewards[0] == 0.0  # a prompt step earns nothing
  # The rule is verifiable: replaying it earns every generated step.
  env = TokenEnv(vocab_size=VOCAB, episode_length=6, prompt_length=2,
                 seed=3)
  (token,) = env.initial()
  total = 0.0
  for _ in range(6):
    reward, _, (token,) = env.step(
        (env._a * int(token) + env._c) % VOCAB)
    total += float(reward)
  assert total == 5.0
  # Sessions with consecutive seeds start a prompt apart.
  spec = lambda seed: factory.make_env_spec(  # noqa: E731
      _config(), 'tokens', seed=seed).constructor_kwargs['start_step']
  assert spec(4) - spec(3) == 2
  assert factory.make_env_spec(_config(), 'tokens', 1).obs_spec == {
      'leaves': (((), np.dtype(np.int32)),)}


def _config(**kw):
  base = dict(agent='sequence', env_backend='tokens', num_actions=VOCAB,
              level_name='tokens', episode_length=6, token_prompt_length=2,
              num_action_repeats=1, unroll_length=5, batch_size=2,
              num_actors=2, inference_state_cache=True,
              inference_timeout_ms=20, summary_secs=0,
              checkpoint_secs=10 ** 6, slo_engine=False, controller='off',
              scan_unroll=1)
  base.update(kw)
  return Config(**base)


def test_flags_keep_agent_and_backend_together():
  validate_runtime(_config())
  with pytest.raises(ValueError, match='tokens'):
    validate_runtime(_config(env_backend='fake'))
  with pytest.raises(ValueError, match='tokens'):
    validate_runtime(Config(env_backend='tokens', num_actions=5))
  agent = driver.build_agent(_config(param_dtype='bfloat16'), VOCAB)
  assert isinstance(agent, SequenceAgent)
  assert agent.param_dtype == jnp.bfloat16
  shapes = jax.eval_shape(lambda: _params(agent))
  assert {l.dtype for l in jax.tree_util.tree_leaves(shapes)} == {
      jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}  # the value head


def test_loss_takes_log_mu_where_the_actor_kept_its_logits():
  """`learner.loss_fn` on a batch whose behaviour `policy_logits` is
  log mu(a): the importance weights are pi(a) / mu(a)."""
  config = _config()
  agent = driver.build_agent(config, VOCAB)
  params = _params(agent)
  t1, b = config.unroll_length + 1, 2
  batch = make_example_batch(t1, b, 24, 32, VOCAB, MAX_INSTRUCTION_LEN)
  rng = np.random.RandomState(0)
  tokens = jnp.asarray(rng.randint(VOCAB, size=(t1, b)), jnp.int32)
  batch = batch._replace(
      agent_state=agent.initial_state(b),
      env_outputs=batch.env_outputs._replace(observation=(tokens,)))
  logits = batch.agent_outputs.policy_logits
  as_log_mu = batch._replace(agent_outputs=batch.agent_outputs._replace(
      policy_logits=jnp.take_along_axis(
          jax.nn.log_softmax(logits), batch.agent_outputs.action[..., None],
          axis=-1)[..., 0]))
  full, _ = learner_lib.loss_fn(params, agent, batch, config)
  kept, _ = learner_lib.loss_fn(params, agent, as_log_mu, config)
  assert np.isfinite(float(full))
  np.testing.assert_allclose(float(kept), float(full), rtol=1e-5)


@pytest.mark.parametrize('use_py_process', [False, True],
                         ids=['in_process', 'process_hosted'])
def test_train_end_to_end(tmp_path, use_py_process):
  """experiment.py's flags -> build_agent -> InferenceServer with the
  state cache -> actors -> token envs -> learner steps, on the CPU."""
  config = _config(logdir=str(tmp_path), use_py_process=use_py_process,
                   total_environment_frames=10 ** 6)
  run = driver.train(config, max_steps=3, stall_timeout_secs=120)
  assert int(jax.device_get(run.state.update_steps)) == 3
  assert isinstance(run.agent, SequenceAgent)
  params = jax.device_get(run.state.params)
  assert all(np.all(np.isfinite(l))
             for l in jax.tree_util.tree_leaves(params))
  stats = run.server.stats()
  assert stats['state_cache'] and stats['state_resets'] > 0
  assert stats['arena_bytes'] > 0


def test_play_serves_without_a_checkpoint(tmp_path):
  """The serving path with seeded parameters: `driver.play`, the play
  phase `evaluate` shares, with process-hosted token envs; the
  `fleet_factory` seam and the stop event as the benchmark uses them."""
  config = _config(logdir=str(tmp_path), use_py_process=True,
                   test_num_episodes=2)
  agent = driver.build_agent(config, VOCAB)
  params = _params(agent)
  played = driver.play(config, agent, params, TOKEN_OBS, ['tokens'],
                       num_actors=2, stall_timeout_secs=120)
  assert list(played) == [0] and len(played[0]) >= 2

  seen = {}

  def fleet_factory(config, agent, policy, buffer, levels):
    seen['server'] = policy.__self__
    return driver.make_fleet(
        config, agent, policy, buffer, levels, num_actors=2,
        initial_state_fn=seen['server'].initial_core_state)

  stop = threading.Event()
  timer = threading.Timer(3.0, stop.set)
  timer.start()
  try:
    driver.play(
        dataclass_replace(config, test_num_episodes=10 ** 9), agent,
        params, TOKEN_OBS, ['tokens'], num_actors=2,
        fleet_factory=fleet_factory, stop_event=stop,
        stall_timeout_secs=120)
  finally:
    timer.cancel()
  stats = seen['server'].stats()
  assert stats['calls'] > 0 and stats['requests'] == 2 * stats['calls']


def test_training_a_state_that_cannot_leave_the_device_is_refused(
    tmp_path, monkeypatch):
  monkeypatch.setattr(inference_lib, 'MAX_HOST_STATE_BYTES', 1024)
  with pytest.raises(ValueError, match='served'):
    driver.train(_config(logdir=str(tmp_path), use_py_process=False),
                 max_steps=1)


def dataclass_replace(config, **kw):
  import dataclasses
  return dataclasses.replace(config, **kw)

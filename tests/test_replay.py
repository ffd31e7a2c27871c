"""Sample reuse (round 10, IMPACT arXiv 1912.00167): the circular
replay tier, fresh:replayed batch composition, the clipped-target
surrogate, and the target-network cadence.

The two contracts everything here pins:

- PARITY GATE (acceptance): `--surrogate=impact` with replay_k=1,
  replay_ratio=0 and target_update_interval=1 matches the V-trace
  path over a multi-step run at the existing 2e-4 sharded gate —
  single device (measured ~1e-8: the anchor forward fuses differently
  from the grad-tracked forward, so bitwise equality is not promised)
  AND through the 8-virtual-device sharded step AND through a
  multi-step driver.train run on a deterministic feed.
- NO DOUBLE COUNTING: replayed slots and re-served batches train the
  learner again but must not re-enter env-plane accounting (episode
  stats, action histograms, fresh-frame counters).
"""

import dataclasses
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config, validate_replay
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.parallel import mesh as mesh_lib
from scalable_agent_tpu.parallel import train_parallel
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.testing import make_example_batch, make_example_unroll

H, W, A, T1 = 24, 32, 4, 5
OBS = {'frame': (H, W, 3), 'instr_len': MAX_INSTRUCTION_LEN}


def _unroll(seed):
  return make_example_unroll(T1, H, W, A, MAX_INSTRUCTION_LEN,
                             seed=seed)


def _copy_tree(tree):
  return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                tree)


def _assert_close(a, b, rtol=2e-4, atol=2e-6):
  for x, y in zip(jax.tree_util.tree_leaves(a),
                  jax.tree_util.tree_leaves(b)):
    np.testing.assert_allclose(np.asarray(x, np.float32),
                               np.asarray(y, np.float32),
                               rtol=rtol, atol=atol)


class TestReplayTier:

  def test_age_eviction_at_capacity(self):
    tier = ring_buffer.ReplayTier(3)
    for i in range(5):
      tier.add(_unroll(i))
    s = tier.stats()
    assert s['replay_occupancy'] == 3
    assert s['replay_evictions_age'] == 2
    assert len(tier) == 3

  def test_circular_cursor_continues_across_calls(self):
    tier = ring_buffer.ReplayTier(4)
    added = [_unroll(i) for i in range(3)]
    for u in added:
      tier.add(u)
    # One call serves each entry AT MOST once (a 5-sample ask against
    # 3 entries caps at one lap — the remainder fills with fresh
    # production upstream)...
    out = tier.sample(5)
    assert len(out) == 3
    # ...and the cursor carries across calls IMPACT-style: the next
    # call resumes the circular scan from the top.
    out2 = tier.sample(2)
    assert out2[0] is added[0] and out2[1] is added[1]
    s = tier.stats()
    assert s['replay_reused_unrolls'] == 5
    assert s['replay_occupancy'] == 3  # sampling never consumes

  def test_version_eviction_and_mean_staleness(self):
    tier = ring_buffer.ReplayTier(8, max_staleness=2)
    tier.note_param_version(10)
    tier.add(_unroll(0))         # version 10
    tier.note_param_version(11)
    tier.add(_unroll(1))         # version 11
    tier.note_param_version(13)  # entry 0 now 3 behind → too stale
    out = tier.sample(2)
    # The stale entry evicts in passing (consuming scan budget); the
    # window-respecting one serves.
    assert len(out) == 1
    s = tier.stats()
    assert s['replay_evictions_version'] == 1
    assert s['replay_occupancy'] == 1
    assert s['replay_reused_unrolls'] == 1
    assert s['replay_mean_staleness'] == pytest.approx(2.0)

  def test_unsample_last_rewinds_cursor_and_counters(self):
    """A sampled slice whose batch never reached the learner (fresh-
    side timeout/close push-back) gives its accounting back: the
    sequential scan re-serves the same entries and the reuse/staleness
    counters only count DELIVERED serves."""
    tier = ring_buffer.ReplayTier(4)
    tier.note_param_version(5)
    added = [_unroll(i) for i in range(3)]
    for u in added:
      tier.add(u)
    tier.note_param_version(7)  # staleness 2 per entry
    out = tier.sample(2)
    assert out[0] is added[0] and out[1] is added[1]
    tier.unsample_last()
    s = tier.stats()
    assert s['replay_reused_unrolls'] == 0
    assert s['replay_mean_staleness'] == 0.0
    # The scan resumes on the SAME entries, and a second unsample
    # (nothing outstanding) is a no-op.
    tier.unsample_last()
    out2 = tier.sample(2)
    assert out2[0] is added[0] and out2[1] is added[1]
    assert tier.stats()['replay_reused_unrolls'] == 2

  def test_buffer_timeout_returns_tier_accounting(self):
    """get_unrolls composed with a short fresh side: a timeout pushes
    fresh items back AND un-counts the replayed slice."""
    tier = ring_buffer.ReplayTier(4)
    buf = ring_buffer.TrajectoryBuffer(4, replay=tier,
                                       replay_ratio=0.5)
    buf.put(_unroll(0))
    _ = buf.get()  # retained into the tier
    with pytest.raises(TimeoutError):
      buf.get_unrolls(4, timeout=0.05)  # 2 replayed wanted, 1 avail
    s = buf.stats()
    assert s['replay_reused_unrolls'] == 0
    assert s['replay_mean_staleness'] == 0.0

  def test_unbounded_without_version_window(self):
    tier = ring_buffer.ReplayTier(4, max_staleness=0)
    tier.add(_unroll(0))
    tier.note_param_version(10**6)
    assert len(tier.sample(1)) == 1
    assert tier.stats()['replay_evictions_version'] == 0


class TestBufferComposition:

  def _buffer(self, capacity=8, tier_capacity=8, ratio=0.5,
              max_staleness=0):
    tier = ring_buffer.ReplayTier(tier_capacity,
                                  max_staleness=max_staleness)
    return ring_buffer.TrajectoryBuffer(capacity, replay=tier,
                                        replay_ratio=ratio)

  def test_compose_fresh_first_then_replayed(self):
    buf = self._buffer()
    for i in range(4):
      buf.put(_unroll(i))
    # First batch: tier empty at sample time → all fresh; the fresh
    # dequeues retain into the tier on their way out.
    items, n_fresh = buf.get_unrolls(4, timeout=1)
    assert n_fresh == 4 and len(items) == 4
    assert buf.stats()['replay_occupancy'] == 4
    # Second batch: 2 replayed (ratio .5) + 2 fresh, fresh FIRST.
    fresh = [_unroll(10), _unroll(11)]
    for u in fresh:
      buf.put(u)
    items, n_fresh = buf.get_unrolls(4, timeout=1)
    assert n_fresh == 2 and len(items) == 4
    assert items[0] is fresh[0] and items[1] is fresh[1]
    s = buf.stats()
    assert s['fresh_unrolls'] == 6
    assert s['replay_reused_unrolls'] == 2

  def test_short_tier_fills_with_fresh(self):
    buf = self._buffer(ratio=0.75)
    buf.put(_unroll(0))
    items, n_fresh = buf.get_unrolls(1, timeout=1)
    assert n_fresh == 1  # floor(1 * .75) = 0 replay slots
    for i in range(1, 5):
      buf.put(_unroll(i))
    items, n_fresh = buf.get_unrolls(4, timeout=1)
    # floor(4 * .75) = 3 wanted, tier holds 1 → 1 replayed, 3 fresh.
    assert n_fresh == 3 and len(items) == 4

  def test_get_retains_into_tier(self):
    buf = self._buffer()
    buf.put(_unroll(0))
    buf.get(timeout=1)
    s = buf.stats()
    assert s['replay_occupancy'] == 1 and s['fresh_unrolls'] == 1

  def test_ratio_needs_tier(self):
    with pytest.raises(ValueError, match='ReplayTier'):
      ring_buffer.TrajectoryBuffer(4, replay_ratio=0.5)

  def test_stats_plain_buffer_has_no_replay_keys(self):
    buf = ring_buffer.TrajectoryBuffer(4)
    s = buf.stats()
    assert 'fresh_unrolls' in s and 'replay_occupancy' not in s


class TestConfigValidation:

  def test_hard_errors(self):
    for bad in (dict(surrogate='ppo'), dict(replay_k=0),
                dict(replay_ratio=1.0), dict(replay_ratio=-0.1),
                dict(target_update_interval=0),
                dict(impact_epsilon=0.0),
                dict(replay_capacity_unrolls=-1),
                dict(replay_max_staleness=-1)):
      with pytest.raises(ValueError):
        validate_replay(Config(**bad))

  def test_defaults_validate_clean(self):
    assert validate_replay(Config()) == []

  def test_reuse_with_vtrace_warns(self):
    warnings = validate_replay(Config(replay_k=2))
    assert any('surrogate=impact' in w for w in warnings)

  def test_staleness_units_cross_link(self):
    """The round-10 unit unification: replay staleness defers to the
    ingest admission window (both in published param-version deltas),
    and a narrower replay window draws the cross-link warning."""
    cfg = Config(max_unroll_staleness=7)
    assert cfg.resolved_replay_max_staleness == 7
    cfg = Config(max_unroll_staleness=7, replay_max_staleness=3)
    assert cfg.resolved_replay_max_staleness == 3
    warnings = validate_replay(cfg)
    assert any('param-version' in w for w in warnings)
    assert Config().resolved_replay_max_staleness == 0

  def test_capacity_auto(self):
    assert Config(batch_size=8).resolved_replay_capacity == 32
    assert Config(replay_capacity_unrolls=5).resolved_replay_capacity \
        == 5


def _make_states_and_steps(cfg_v, cfg_i, agent):
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  state_v = learner_lib.make_train_state(_copy_tree(params), cfg_v)
  state_i = learner_lib.make_train_state(_copy_tree(params), cfg_i)
  return (state_v, learner_lib.make_train_step(agent, cfg_v),
          state_i, learner_lib.make_train_step(agent, cfg_i))


class TestImpactSurrogate:

  def _configs(self, **common):
    base = dict(batch_size=2, unroll_length=T1 - 1,
                num_action_repeats=1, total_environment_frames=10**6,
                num_actions=A, height=H, width=W, torso='shallow',
                use_instruction=False)
    base.update(common)
    cfg_v = Config(**base)
    cfg_i = dataclasses.replace(cfg_v, surrogate='impact',
                                target_update_interval=1)
    return cfg_v, cfg_i

  def test_state_carries_target_only_under_impact(self):
    cfg_v, cfg_i = self._configs()
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    params = init_params(agent, jax.random.PRNGKey(0), OBS)
    assert learner_lib.make_train_state(params, cfg_v).target_params \
        is None
    state = learner_lib.make_train_state(_copy_tree(params), cfg_i)
    assert state.target_params is not None
    # Distinct buffers (the donated state must not alias target to
    # params), equal values.
    _assert_close(state.target_params, state.params, rtol=0, atol=0)
    p_leaves = jax.tree_util.tree_leaves(state.params)
    t_leaves = jax.tree_util.tree_leaves(state.target_params)
    assert all(p is not t for p, t in zip(p_leaves, t_leaves))

  def test_parity_gate_single_device_multi_step(self):
    """Acceptance: impact at the parity operating point matches
    vtrace over a multi-step run within the 2e-4 gate."""
    cfg_v, cfg_i = self._configs()
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    state_v, step_v, state_i, step_i = _make_states_and_steps(
        cfg_v, cfg_i, agent)
    for seed in range(4):
      batch = make_example_batch(T1, 2, H, W, A, MAX_INSTRUCTION_LEN,
                                 seed=seed, done_prob=0.1)
      state_v, metrics_v = step_v(state_v, batch)
      state_i, metrics_i = step_i(state_i, batch)
    _assert_close(state_v.params, state_i.params)
    np.testing.assert_allclose(float(metrics_v['grad_norm']),
                               float(metrics_i['grad_norm']),
                               rtol=2e-4)
    # At the anchor point the ratio never leaves the clip band.
    assert float(metrics_i['impact_clip_fraction']) == 0.0
    # interval=1: the anchor entering the next step IS the params.
    _assert_close(state_i.target_params, state_i.params, rtol=0,
                  atol=0)

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_parity_gate_sharded_step(self):
    """Acceptance: the same gate through the 8-virtual-device sharded
    step (impact-sharded vs vtrace-sharded, 2 steps)."""
    cfg_v, cfg_i = self._configs(batch_size=8)
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    mesh = mesh_lib.make_mesh(model_parallelism=1)
    example = make_example_batch(T1, 8, H, W, A, MAX_INSTRUCTION_LEN)
    params = init_params(agent, jax.random.PRNGKey(0), OBS)
    state_v = train_parallel.make_sharded_train_state(
        _copy_tree(params), cfg_v, mesh)
    state_i = train_parallel.make_sharded_train_state(
        _copy_tree(params), cfg_i, mesh)
    step_v, place_v = train_parallel.make_sharded_train_step(
        agent, cfg_v, mesh, example)
    step_i, place_i = train_parallel.make_sharded_train_step(
        agent, cfg_i, mesh, example)
    for seed in range(2):
      batch = make_example_batch(T1, 8, H, W, A, MAX_INSTRUCTION_LEN,
                                 seed=seed, done_prob=0.1)
      state_v, _ = step_v(state_v, place_v(batch))
      state_i, _ = step_i(state_i, place_i(batch))
    _assert_close(state_v.params, state_i.params, rtol=5e-4,
                  atol=5e-6)

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_target_refresh_cadence(self):
    """interval=3: the anchor holds still for 3 steps, then snapshots
    the just-updated params — the version-gated publish pattern
    in-graph."""
    cfg_v, cfg_i = self._configs()
    cfg_i = dataclasses.replace(cfg_i, target_update_interval=3)
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    params = init_params(agent, jax.random.PRNGKey(0), OBS)
    state = learner_lib.make_train_state(_copy_tree(params), cfg_i)
    step = learner_lib.make_train_step(agent, cfg_i)
    initial = _copy_tree(state.params)
    params_after = {}
    for k in range(1, 6):
      batch = make_example_batch(T1, 2, H, W, A, MAX_INSTRUCTION_LEN,
                                 seed=k, done_prob=0.1)
      state, _ = step(state, batch)
      params_after[k] = _copy_tree(state.params)
      anchor_step = (k // 3) * 3  # last refresh at a multiple of 3
      want = initial if anchor_step == 0 else params_after[anchor_step]
      _assert_close(state.target_params, want, rtol=0, atol=0)

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_popart_anchor_stats_snapshot_with_target(self):
    """impact + PopArt (interval > 1): the anchor's PopArt stats
    snapshot refreshes WITH the anchor head. Preservation rewrites
    only the LIVE value head as the stats move, so unnormalizing the
    frozen target head with CURRENT stats would mis-scale the V-trace
    values/bootstrap by the drift since the last refresh — the
    snapshot must hold the stats as of the refresh, not the live
    ones."""
    num_tasks = 2
    cfg = Config(batch_size=2, unroll_length=T1 - 1,
                 num_action_repeats=1, total_environment_frames=10**6,
                 num_actions=A, height=H, width=W, torso='shallow',
                 use_instruction=False, use_popart=True,
                 popart_beta=0.3, surrogate='impact',
                 target_update_interval=3)
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False,
                        num_popart_tasks=num_tasks)
    params = init_params(agent, jax.random.PRNGKey(0), OBS)
    state = learner_lib.make_train_state(params, cfg,
                                         num_popart_tasks=num_tasks)
    assert state.target_popart is not None
    step = learner_lib.make_train_step(agent, cfg)
    popart_after = {0: _copy_tree(state.popart)}
    for k in range(1, 6):
      batch = make_example_batch(T1, 2, H, W, A, MAX_INSTRUCTION_LEN,
                                 seed=k, done_prob=0.2)
      batch = batch._replace(level_name=np.array([0, 1], np.int32))
      state, _ = step(state, batch)
      popart_after[k] = _copy_tree(state.popart)
      anchor_step = (k // 3) * 3  # last refresh at a multiple of 3
      _assert_close(state.target_popart, popart_after[anchor_step],
                    rtol=0, atol=0)
      if k not in (0, 3):
        # The stats DO drift between refreshes — otherwise the
        # snapshot guard would be vacuous here.
        assert np.any(np.asarray(state.popart.mu) !=
                      np.asarray(state.target_popart.mu))

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_impact_changes_updates_off_the_anchor_point(self):
    """Sanity: with a LAGGING anchor (interval > 1) the surrogate is a
    different objective — updates must actually diverge from vtrace
    (parity is a property of the anchor point, not a no-op loss)."""
    cfg_v, cfg_i = self._configs()
    cfg_i = dataclasses.replace(cfg_i, target_update_interval=4,
                                impact_epsilon=0.01)
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    state_v, step_v, state_i, step_i = _make_states_and_steps(
        cfg_v, cfg_i, agent)
    for seed in range(3):
      batch = make_example_batch(T1, 2, H, W, A, MAX_INSTRUCTION_LEN,
                                 seed=seed, done_prob=0.1)
      state_v, _ = step_v(state_v, batch)
      state_i, _ = step_i(state_i, batch)
    diffs = [float(np.max(np.abs(np.asarray(x, np.float32) -
                                 np.asarray(y, np.float32))))
             for x, y in zip(jax.tree_util.tree_leaves(state_v.params),
                             jax.tree_util.tree_leaves(state_i.params))]
    assert max(diffs) > 1e-6

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_checkpoint_roundtrip_preserves_target(self, tmp_path):
    from scalable_agent_tpu import checkpoint as checkpoint_lib
    _, cfg_i = self._configs()
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    params = init_params(agent, jax.random.PRNGKey(0), OBS)
    state = learner_lib.make_train_state(params, cfg_i)
    step = learner_lib.make_train_step(agent, cfg_i)
    state, _ = step(state, make_example_batch(
        T1, 2, H, W, A, MAX_INSTRUCTION_LEN, seed=0))
    ckpt = checkpoint_lib.Checkpointer(str(tmp_path / 'ckpt'))
    try:
      ckpt.save(state, force=True)
      params2 = init_params(agent, jax.random.PRNGKey(0), OBS)
      template = learner_lib.make_train_state(params2, cfg_i)
      restored = ckpt.restore_latest(template)
    finally:
      ckpt.close()
    assert restored is not None
    _assert_close(restored.target_params, state.target_params,
                  rtol=0, atol=0)


class _DeterministicFleet:
  """Single-threaded producer putting a FIXED unroll sequence — the
  driver-level parity runs need bit-identical batch composition across
  two train() invocations (a real fleet's thread interleaving would
  not be reproducible). Implements the ActorFleet surface train()
  touches."""

  def __init__(self, buffer, unrolls):
    import threading
    self._buffer = buffer
    self._unrolls = unrolls
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._produce, daemon=True)

  def _produce(self):
    i = 0
    while not self._stop.is_set():
      try:
        self._buffer.put(self._unrolls[i % len(self._unrolls)],
                         timeout=0.2)
        i += 1
      except (TimeoutError, ring_buffer.Closed):
        continue

  def start(self):
    self._thread.start()

  def errors(self):
    return []

  def check_health(self, stall_timeout_secs=None):
    pass

  def stats(self, healthy_horizon_secs=60.0):
    return {'alive': 1, 'respawns': 0, 'healthy': 1,
            'healthy_fraction': 1.0, 'unrolls': 0}

  def stop(self, timeout=10.0):
    self._stop.set()
    self._thread.join(timeout=timeout)


class TestDriverIntegration:

  def _config(self, tmp_path, name, **kw):
    base = dict(
        logdir=str(tmp_path / name), env_backend='fake',
        num_actions=A, num_actors=0, batch_size=2,
        unroll_length=T1 - 1, num_action_repeats=1, episode_length=4,
        height=H, width=W, torso='shallow', use_py_process=False,
        use_instruction=False, total_environment_frames=10**6,
        checkpoint_secs=10**6, summary_secs=0, seed=3)
    base.update(kw)
    return Config(**base)

  def _fleet_factory(self):
    unrolls = [_unroll(i) for i in range(8)]

    def factory(config, agent, policy, buffer, levels):
      return _DeterministicFleet(buffer, unrolls)

    return factory

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_parity_gate_driver_run(self, tmp_path):
    """Acceptance: impact at the parity point vs vtrace over a
    MULTI-STEP DRIVER RUN (deterministic feed) — final params within
    the 2e-4 gate."""
    from scalable_agent_tpu import driver
    finals = {}
    for name in ('vtrace', 'impact'):
      cfg = self._config(
          tmp_path, name, surrogate=name,
          target_update_interval=1)
      run = driver.train(cfg, max_steps=3, stall_timeout_secs=60,
                         fleet_factory=self._fleet_factory())
      assert int(run.state.update_steps) == 3
      finals[name] = jax.device_get(run.state.params)
    _assert_close(finals['vtrace'], finals['impact'])

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_replay_run_telemetry_reaches_jsonl(self, tmp_path):
    """replay_k x replay_ratio through driver.train: training
    advances, re-serves and replays happen, and every round-10
    summary lands in summaries.jsonl (the satellite assertion)."""
    from scalable_agent_tpu import driver
    cfg = self._config(tmp_path, 'replay', surrogate='impact',
                       replay_k=2, replay_ratio=0.5,
                       target_update_interval=2,
                       replay_max_staleness=50)
    run = driver.train(cfg, max_steps=6, stall_timeout_secs=60,
                       fleet_factory=self._fleet_factory())
    assert int(run.state.update_steps) == 6
    pf = run.prefetcher.stats()
    assert pf['replay_k'] == 2
    assert pf['serves'] == pf['staged_batches'] * 2 or \
        pf['serves'] >= 6
    assert pf['batch_reserves'] >= 2
    with open(os.path.join(cfg.logdir, 'summaries.jsonl')) as f:
      events = [json.loads(line) for line in f]
    tags = {e['tag'] for e in events}
    for tag in ('learner_updates_per_env_frame',
                'env_frames_fresh_per_sec', 'env_plane_utilization',
                'learner_plane_utilization', 'frames_fresh',
                'frames_reused', 'replay_occupancy',
                'replay_evictions_age', 'replay_evictions_version',
                'replay_reused_unrolls', 'replay_mean_staleness',
                'impact_clip_fraction'):
      assert tag in tags, f'missing summary tag {tag}'
    # The headline metric actually reflects reuse: with replay_k=2
    # and ratio .5, updates per fresh frame must exceed the no-reuse
    # rate 1/frames_per_step over the run as a whole.
    upef = [e['value'] for e in events
            if e['tag'] == 'learner_updates_per_env_frame'
            and e['value'] > 0]
    assert upef, 'no non-zero learner_updates_per_env_frame interval'
    assert max(upef) > 1.0 / cfg.frames_per_step

  def test_frame_budget_counts_fresh_frames_under_reuse(self, tmp_path):
    """The frame budget / TrainRun.frames count FRESH env frames when
    reuse is on: with replay_k=2 each env frame buys ~2 updates, so a
    run bounded by total_environment_frames must take ~2x the updates
    the old steps x frames_per_step arithmetic would have allowed
    (which terminated the run early, overstating consumption)."""
    from scalable_agent_tpu import driver
    budget_steps = 4  # what steps-derived accounting would allow
    cfg = self._config(
        tmp_path, 'budget', surrogate='impact', replay_k=2,
        target_update_interval=2)
    cfg = dataclasses.replace(
        cfg, total_environment_frames=budget_steps * cfg.frames_per_step)
    run = driver.train(cfg, stall_timeout_secs=60,
                       fleet_factory=self._fleet_factory())
    steps = int(run.state.update_steps)
    assert steps > budget_steps, (
        f'run stopped at {steps} updates — the frame budget counted '
        f're-serves as env frames')
    # TrainRun.frames reports the fresh-frame figure, and the run ran
    # to (at least) its env-frame budget.
    assert run.frames >= cfg.total_environment_frames

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_episode_stats_not_double_counted(self, tmp_path):
    """A re-served batch must contribute ZERO episode events: with
    replay_k=2 every batch rides twice, so episode-return events must
    number the same as a replay-off run over the same fed unrolls
    would allow at most — concretely, no more than the number of
    done=True flags in the FRESH unrolls consumed."""
    from scalable_agent_tpu import driver
    unrolls = []
    for i in range(8):
      u = _unroll(i)
      done = np.zeros(T1, bool)
      done[-1] = True  # one episode end per unroll
      info = u.env_outputs.info._replace(
          episode_return=np.full(T1, float(i), np.float32))
      u = u._replace(env_outputs=u.env_outputs._replace(
          done=done, info=info))
      unrolls.append(u)

    def factory(config, agent, policy, buffer, levels):
      return _DeterministicFleet(buffer, unrolls)

    cfg = self._config(tmp_path, 'dedup', surrogate='impact',
                       replay_k=2, replay_ratio=0.5)
    run = driver.train(cfg, max_steps=6, stall_timeout_secs=60,
                       fleet_factory=factory)
    assert int(run.state.update_steps) == 6
    with open(os.path.join(cfg.logdir, 'summaries.jsonl')) as f:
      events = [json.loads(line) for line in f]
    episode_events = [e for e in events
                      if e['tag'].endswith('/episode_return')]
    # 6 updates at replay_k=2 consume at most 3 staged batches x 2
    # slots, of which at most half are... conservatively: fresh
    # unrolls consumed bound the episode count (1 done per unroll).
    # Without the double-count guards this would be ~2x higher.
    fresh = None
    for e in events:
      if e['tag'] == 'frames_fresh':
        fresh = e['value']
    assert fresh is not None
    fresh_unroll_count = fresh / (cfg.unroll_length *
                                  cfg.num_action_repeats)
    assert len(episode_events) <= fresh_unroll_count


def test_replay_tier_crc_evicts_rotted_entry():
  """Round 12: a retained unroll mutated in host memory AFTER insert
  (the tier holds by reference — rot is exactly this shape) must be
  EVICTED at sample time, never served; counted as
  replay_evictions_crc. With verify_crc=False the tier serves the
  aliased object untouched (the pre-round-12 semantics)."""
  import numpy as np
  from scalable_agent_tpu.runtime import ring_buffer
  from tests.test_remote import _tiny_unroll

  tier = ring_buffer.ReplayTier(4)
  clean = _tiny_unroll(0)
  rotten = _tiny_unroll(1)
  tier.add(clean)
  tier.add(rotten)
  # Rot: flip one byte of the retained frame stack, in place.
  np.asarray(rotten.env_outputs.observation[0]).flat[7] ^= 0x10
  out = tier.sample(4)
  assert len(out) == 1
  assert out[0] is clean
  assert tier.evictions_crc == 1
  assert len(tier) == 1
  assert tier.stats()['replay_evictions_crc'] == 1

  off = ring_buffer.ReplayTier(4, verify_crc=False)
  off.add(rotten)
  assert off.sample(1) == [rotten]
  assert off.evictions_crc == 0


class TestDynamicReplayK:
  """Round 15: the controller's set_replay_k actuator — live changes
  apply to batches staged AFTER the call; in-flight entries finish
  the K they were staged under, with first-serve accounting pinned
  to that K (never the live knob)."""

  def test_set_replay_k_applies_to_new_batches_only(self):
    buf = ring_buffer.TrajectoryBuffer(8)
    pf = ring_buffer.BatchPrefetcher(buf, batch_size=2,
                                     place_fn=lambda b: b, depth=1,
                                     replay_k=1)
    try:
      for i in range(2):
        buf.put(_unroll(i))
      deadline = time.monotonic() + 10
      while pf.stats()['staged_batches'] < 1 and \
          time.monotonic() < deadline:
        time.sleep(0.01)
      assert pf.replay_k == 1
      pf.set_replay_k(2)
      assert pf.replay_k == 2
      for i in range(2):
        buf.put(_unroll(10 + i))
      # Batch 1 was staged under k=1: exactly one serve.
      b1 = pf.get(timeout=10)
      # Batch 2 (staged under k=2): first serve + one bit-identical
      # re-serve of the SAME staged object.
      b2a = pf.get(timeout=10)
      b2b = pf.get(timeout=10)
      assert b2a is b2b and b1 is not b2a
      # Fresh accounting: 2 batches x 2 fresh slots, credited at
      # first serve only — the re-serve added nothing.
      assert pf.fresh_slots_served() == 4
      stats = pf.stats()
      assert stats['serves'] == 3
      assert stats['batch_reserves'] == 1
      with pytest.raises(TimeoutError):
        pf.get(timeout=0.1)
      # Stepping back down: the next staged batch serves once again.
      pf.set_replay_k(1)
      for i in range(2):
        buf.put(_unroll(20 + i))
      b3 = pf.get(timeout=10)
      assert b3 is not b2a
      with pytest.raises(TimeoutError):
        pf.get(timeout=0.1)
      assert pf.fresh_slots_served() == 6
    finally:
      pf.close()

  def test_set_replay_k_validates(self):
    buf = ring_buffer.TrajectoryBuffer(2)
    pf = ring_buffer.BatchPrefetcher(buf, batch_size=2,
                                     place_fn=lambda b: b, depth=1)
    try:
      with pytest.raises(ValueError):
        pf.set_replay_k(0)
    finally:
      pf.close()

"""The learner-plane feed (round 8): per-unroll device staging,
on-device batch assembly, the shard_map'ped Pallas V-trace, and the
deferred metrics readback.

The golden-parity contract everything here pins: the unroll staging
plane (`staging_mode='unroll'`) must produce batches BIT-IDENTICAL to
the host-stack path — `dynamic_update_slice` of the same values is the
same batch — on the single device AND assembled shard-wise over the
8-virtual-device pure-DP mesh; and the fused Pallas V-trace under
`shard_map` must match the single-device forms at the existing 2e-4
gate now that the driver's mesh rejection is lifted.
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import observability, vtrace
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.parallel import mesh as mesh_lib
from scalable_agent_tpu.parallel import train_parallel
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.runtime.actor import batch_unrolls
from scalable_agent_tpu.testing import make_example_batch, make_example_unroll

H, W, A, T1 = 8, 8, 4, 5


def _unrolls(n, seed0=0):
  return [make_example_unroll(T1, H, W, A, MAX_INSTRUCTION_LEN, seed=i)
          for i in range(seed0, seed0 + n)]


def _assert_tree_equal(a, b):
  for x, y in zip(jax.tree_util.tree_leaves(a),
                  jax.tree_util.tree_leaves(b)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestUnrollStagerParity:

  def test_single_device_bit_identical_to_host_stack(self):
    """The golden parity gate: on-device dynamic_update_slice assembly
    == batch_unrolls + transfer, bit for bit, dtypes included."""
    unrolls = _unrolls(3)
    stager = ring_buffer.UnrollBatchStager(3)
    for u in unrolls:
      stager.add(u)
    batch = stager.finish()
    ref = batch_unrolls(unrolls)
    _assert_tree_equal(batch, ref)
    for x, y in zip(jax.tree_util.tree_leaves(batch),
                    jax.tree_util.tree_leaves(ref)):
      assert x.dtype == y.dtype
    assert stager.stats() == {'unrolls_staged': 3,
                              'batches_assembled': 1,
                              'aborted_partials': 0,
                              'donation_fallback': False}

  def test_consecutive_batches_are_independent(self):
    """Fresh arenas per batch: emitting batch N and assembling N+1
    must not write into N's buffers (the learner reads N meanwhile)."""
    stager = ring_buffer.UnrollBatchStager(2)
    first = _unrolls(2)
    for u in first:
      stager.add(u)
    batch1 = stager.finish()
    snapshot = jax.tree_util.tree_map(
        lambda x: np.asarray(x).copy(), batch1)
    for u in _unrolls(2, seed0=7):
      stager.add(u)
    batch2 = stager.finish()
    _assert_tree_equal(batch1, snapshot)          # untouched
    _assert_tree_equal(batch2, batch_unrolls(_unrolls(2, seed0=7)))

  def test_mesh_assembly_matches_host_stack_and_shardings(self):
    """Pure-DP 8-device mesh: per-slot placement + zero-copy global
    assembly equals the host-stack batch AND lands on the exact
    data-axis shardings the sharded step's place_fn would use."""
    b = 8
    cfg = Config(batch_size=b, unroll_length=T1 - 1)
    mesh = mesh_lib.make_mesh(model_parallelism=1)
    example = make_example_batch(T1, b, H, W, A, MAX_INSTRUCTION_LEN)
    slot_devices, assemble = train_parallel.make_unroll_assembly(
        cfg, mesh, example)
    assert len(slot_devices) == b
    stager = ring_buffer.UnrollBatchStager(
        b, slot_devices=slot_devices, assemble_fn=assemble)
    unrolls = _unrolls(b)
    for u in unrolls:
      stager.add(u)
    batch = stager.finish()
    _assert_tree_equal(batch, batch_unrolls(unrolls))
    want = mesh_lib.batch_shardings(example, mesh)
    assert (batch.env_outputs.reward.sharding.spec ==
            want.env_outputs.reward.spec)
    assert (batch.agent_state[0].sharding.spec ==
            want.agent_state[0].spec)
    assert batch.env_outputs.reward.shape == (T1, b)

  def test_supports_unroll_staging_gates(self):
    mesh = mesh_lib.make_mesh(model_parallelism=1)
    assert train_parallel.supports_unroll_staging(
        Config(batch_size=8), mesh)
    # Indivisible local batch → unsupported (driver falls back).
    assert not train_parallel.supports_unroll_staging(
        Config(batch_size=6), mesh)
    # Model-axis batch sharding (TP mesh) → unsupported.
    tp_mesh = mesh_lib.make_mesh(model_parallelism=2)
    assert not train_parallel.supports_unroll_staging(
        Config(batch_size=8, model_parallelism=2), tp_mesh)
    # No mesh → always supported.
    assert train_parallel.supports_unroll_staging(
        Config(batch_size=3), None)


class TestUnrollModeFailurePaths:
  """Satellite: the staging plane's close/error paths must not leak
  staged batches or partial arenas, and must surface producer errors
  to the learner loop."""

  def test_close_mid_batch_aborts_partial_without_leak(self):
    buf = ring_buffer.TrajectoryBuffer(8)
    stager = ring_buffer.UnrollBatchStager(4)
    pf = ring_buffer.BatchPrefetcher(buf, 4, stager=stager, depth=2)
    # Two of four slots staged, then the buffer closes (the poison
    # path run_actor_loop takes on a real failure).
    for u in _unrolls(2):
      buf.put(u)
    deadline = time.monotonic() + 5
    while stager.unrolls_staged < 2 and time.monotonic() < deadline:
      time.sleep(0.01)
    assert stager.unrolls_staged == 2
    buf.close()
    with pytest.raises(ring_buffer.Closed):
      pf.get(timeout=5)
    pf.close()
    # The partial arena was dropped — no staged-batch leak past the
    # prefetcher's lifetime.
    assert stager.stats()['aborted_partials'] == 1
    assert stager._arenas is None
    assert stager._next_slot == 0
    assert len(pf._out) == 0

  def test_close_with_staged_batches_releases_them(self):
    buf = ring_buffer.TrajectoryBuffer(16)
    stager = ring_buffer.UnrollBatchStager(2)
    pf = ring_buffer.BatchPrefetcher(buf, 2, stager=stager, depth=2)
    for u in _unrolls(8):
      buf.put(u)
    deadline = time.monotonic() + 5
    while pf.stats()['staged_batches'] < 2 and \
        time.monotonic() < deadline:
      time.sleep(0.01)
    assert pf.stats()['staged_batches'] >= 2
    pf.close()
    # Full staged batches are dropped at close — a closed prefetcher
    # must not pin batch-sized device buffers.
    assert len(pf._out) == 0
    with pytest.raises(ring_buffer.Closed):
      pf.get(timeout=1)

  def test_producer_error_surfaces_to_consumer(self):
    """A failure inside the staging path itself (here: the host-view
    peel, standing in for a malformed unroll) must reach the learner's
    prefetcher.get as the original error, not a hang."""
    buf = ring_buffer.TrajectoryBuffer(8)

    def bad_view(unroll):
      raise RuntimeError('malformed unroll')

    stager = ring_buffer.UnrollBatchStager(2, host_view_fn=bad_view)
    pf = ring_buffer.BatchPrefetcher(buf, 2, stager=stager, depth=2)
    buf.put(_unrolls(1)[0])
    with pytest.raises(RuntimeError, match='malformed unroll'):
      pf.get(timeout=10)
    pf.close()
    assert stager._arenas is None  # partial state cleaned up

  def test_donation_fallback_engages_and_stays_correct(self, monkeypatch):
    """The PR-3 jaxlib donation-aliasing defect class: the first
    insert that raises an alias error flips the stager to the
    un-donated jit for the rest of the run — same batch, fallback
    recorded."""
    stager = ring_buffer.UnrollBatchStager(2)
    calls = {'n': 0}

    def raising_insert(arena, unroll, slot):
      calls['n'] += 1
      raise RuntimeError(
          'INTERNAL: Expected aliased input 3, to have the same size '
          'as output')

    monkeypatch.setattr(stager, '_insert_donated', raising_insert)
    unrolls = _unrolls(2)
    for u in unrolls:
      stager.add(u)
    batch = stager.finish()
    assert calls['n'] == 1              # tripped once, never retried
    assert stager.donation_fallback
    assert stager.stats()['donation_fallback']
    _assert_tree_equal(batch, batch_unrolls(unrolls))

  def test_non_alias_insert_error_propagates(self, monkeypatch):
    stager = ring_buffer.UnrollBatchStager(1)

    def raising_insert(arena, unroll, slot):
      raise RuntimeError('RESOURCE_EXHAUSTED: out of memory')

    monkeypatch.setattr(stager, '_insert_donated', raising_insert)
    with pytest.raises(RuntimeError, match='RESOURCE_EXHAUSTED'):
      stager.add(_unrolls(1)[0])


class TestStagedArenaReserve:
  """Satellite (round 10): the replay_k re-serve lifecycle. A staged
  batch served K times must be THE SAME device arrays every serve (no
  re-stage, no extra H2D), release its depth slot only after the Kth
  serve, and a close mid-reuse must drop it with everything else."""

  def _put(self, buf, n, seed0=0):
    for u in _unrolls(n, seed0=seed0):
      buf.put(u)

  def test_reserves_are_bit_identical_and_release_after_kth(self):
    buf = ring_buffer.TrajectoryBuffer(16)
    stager = ring_buffer.UnrollBatchStager(2)
    pf = ring_buffer.BatchPrefetcher(buf, 2, stager=stager, depth=2,
                                     replay_k=3)
    self._put(buf, 4)
    serves = [pf.get(timeout=10) for _ in range(3)]
    # The SAME staged object every serve — re-serving is a pointer
    # hand-out, not a re-stage (zero added H2D by construction).
    assert serves[1] is serves[0] and serves[2] is serves[0]
    next_batch = pf.get(timeout=10)
    assert next_batch is not serves[0]
    _assert_tree_equal(next_batch, batch_unrolls(_unrolls(2, seed0=2)))
    stats = pf.stats()
    assert stats['replay_k'] == 3
    assert stats['serves'] == 4
    assert stats['batch_reserves'] == 2
    # Exactly two batches were ever staged for the four serves.
    assert stager.stats()['batches_assembled'] == 2
    pf.close()

  def test_depth_slot_held_until_kth_serve(self):
    """A half-served batch still occupies its depth slot: with
    depth=1 and replay_k=2, the second staged batch cannot enter the
    queue until the first batch's second serve frees the slot."""
    buf = ring_buffer.TrajectoryBuffer(16)
    stager = ring_buffer.UnrollBatchStager(1)
    pf = ring_buffer.BatchPrefetcher(buf, 1, stager=stager, depth=1,
                                     replay_k=2)
    self._put(buf, 3)
    first = pf.get(timeout=10)
    deadline = time.monotonic() + 1
    while time.monotonic() < deadline:
      time.sleep(0.02)
    assert len(pf._out) == 1  # batch 2 parked outside the queue
    assert pf.get(timeout=10) is first      # second serve frees it
    second = pf.get(timeout=10)
    assert second is not first
    pf.close()

  def test_close_mid_reuse_aborts_without_leak(self):
    buf = ring_buffer.TrajectoryBuffer(16)
    stager = ring_buffer.UnrollBatchStager(2)
    pf = ring_buffer.BatchPrefetcher(buf, 2, stager=stager, depth=2,
                                     replay_k=4)
    self._put(buf, 2)
    pf.get(timeout=10)  # 3 serves still owed on this batch
    pf.close()
    # The partially-served batch was dropped with the rest — no staged
    # device arrays outlive the prefetcher.
    assert len(pf._out) == 0
    with pytest.raises(ring_buffer.Closed):
      pf.get(timeout=1)

  def test_reserve_fn_transforms_reserves_only(self):
    buf = ring_buffer.TrajectoryBuffer(16)
    seen = []

    def reserve_fn(item):
      seen.append(item)
      return {'reused': item}

    pf = ring_buffer.BatchPrefetcher(buf, 2, place_fn=lambda b: b,
                                     depth=2, replay_k=2,
                                     reserve_fn=reserve_fn)
    self._put(buf, 2)
    first = pf.get(timeout=10)
    second = pf.get(timeout=10)
    assert not isinstance(first, dict)
    assert isinstance(second, dict) and second['reused'] is first
    assert len(seen) == 1 and seen[0] is first
    pf.close()


class TestShardedPallasVtrace:
  """The lifted mesh restriction: the fused kernel under shard_map on
  the 8-virtual-device mesh vs the single-device forms, at the
  existing 2e-4 sharded-parity gate."""

  def _inputs(self, t=7, b=16, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        log_rhos=jnp.asarray(rng.randn(t, b) * 0.5, jnp.float32),
        discounts=jnp.asarray(0.9 * (rng.rand(t, b) > 0.1),
                              jnp.float32),
        rewards=jnp.asarray(rng.randn(t, b), jnp.float32),
        values=jnp.asarray(rng.randn(t, b), jnp.float32),
        bootstrap_value=jnp.asarray(rng.randn(b), jnp.float32))

  def test_sharded_matches_scan_and_single_device_pallas(self):
    mesh = mesh_lib.make_mesh(model_parallelism=1)
    kw = self._inputs()
    scan = vtrace.from_importance_weights(**kw)
    single = vtrace.from_importance_weights(use_pallas=True, **kw)
    sharded = vtrace.from_importance_weights(use_pallas=True,
                                             mesh=mesh, **kw)
    for ref in (scan, single):
      np.testing.assert_allclose(np.asarray(ref.vs),
                                 np.asarray(sharded.vs),
                                 rtol=2e-4, atol=2e-4)
      np.testing.assert_allclose(np.asarray(ref.pg_advantages),
                                 np.asarray(sharded.pg_advantages),
                                 rtol=2e-4, atol=2e-4)

  def test_sharded_under_jit_with_clip_none(self):
    mesh = mesh_lib.make_mesh(model_parallelism=1)
    kw = self._inputs(seed=3)
    ref = vtrace.from_importance_weights(
        clip_rho_threshold=None, clip_pg_rho_threshold=None, **kw)
    fn = jax.jit(lambda **k: vtrace.from_importance_weights(
        use_pallas=True, mesh=mesh, clip_rho_threshold=None,
        clip_pg_rho_threshold=None, **k))
    out = fn(**kw)
    np.testing.assert_allclose(np.asarray(ref.vs), np.asarray(out.vs),
                               rtol=2e-4, atol=2e-4)

  def test_single_device_mesh_also_takes_the_kernel(self):
    """devices=1 mesh (the bench chip's operating point): the
    shard_map path must still run and agree."""
    mesh = mesh_lib.make_mesh(jax.devices()[:1], model_parallelism=1)
    kw = self._inputs(seed=5)
    ref = vtrace.from_importance_weights(use_pallas=True, **kw)
    out = vtrace.from_importance_weights(use_pallas=True, mesh=mesh,
                                         **kw)
    np.testing.assert_allclose(np.asarray(ref.vs), np.asarray(out.vs),
                               rtol=1e-6, atol=1e-6)


class TestDeferredMetrics:

  def test_stack_and_read_roundtrip(self):
    metrics = {'total_loss': jnp.float32(1.5),
               'grad_norm': jnp.float32(0.25),
               'learning_rate': jnp.float32(0.125)}
    handle = observability.stack_metrics(metrics)
    out = observability.read_stacked_metrics(handle)
    assert out == {'total_loss': 1.5, 'grad_norm': 0.25,
                   'learning_rate': 0.125}

  def test_handle_is_one_device_array(self):
    metrics = {'a': jnp.float32(1), 'b': jnp.float32(2)}
    keys, stacked = observability.stack_metrics(metrics)
    assert keys == ('a', 'b')
    assert stacked.shape == (2,)


class TestDriverIntegration:
  """staging_mode='unroll' through the production driver: training
  works, telemetry lands, and the mode echoes in the stats."""

  def _config(self, tmp_path, **kw):
    base = dict(
        logdir=str(tmp_path), env_backend='bandit', num_actors=2,
        batch_size=2, unroll_length=5, num_action_repeats=1,
        episode_length=4, height=24, width=32, torso='shallow',
        use_py_process=False, use_instruction=False,
        total_environment_frames=10**6, inference_timeout_ms=5,
        checkpoint_secs=0, summary_secs=0, seed=3)
    base.update(kw)
    return Config(**base)

  def test_train_with_unroll_staging(self, tmp_path):
    from scalable_agent_tpu import driver
    cfg = self._config(tmp_path, staging_mode='unroll')
    run = driver.train(cfg, max_steps=3, stall_timeout_secs=60)
    assert int(run.state.update_steps) == 3
    pf = run.prefetcher.stats()
    assert pf['mode'] == 'unroll'
    assert pf['batches_assembled'] >= 3
    assert not pf['donation_fallback']
    with open(os.path.join(str(tmp_path), 'summaries.jsonl')) as f:
      events = [json.loads(line) for line in f]
    tags = {e['tag'] for e in events}
    # Round-8 staging telemetry + the deferred metrics still landing.
    assert 'staging_exposed_ms_per_step' in tags
    assert 'h2d_overlap_fraction' in tags
    assert 'total_loss' in tags
    # The actually-running mode echo (bench e2e_fed labels rows off
    # this, not off config — a topology fallback must not mislabel).
    active = [e['value'] for e in events
              if e['tag'] == 'staging_unroll_active']
    assert active and all(v == 1.0 for v in active)
    assert all(np.isfinite(e['value']) for e in events
               if e['tag'] == 'total_loss')

  def test_unknown_staging_mode_rejected_before_spinup(self, tmp_path):
    from scalable_agent_tpu import driver
    cfg = self._config(tmp_path, staging_mode='bogus')
    with pytest.raises(ValueError, match='staging_mode'):
      driver.train(cfg, max_steps=1)

  def test_unsupported_topology_falls_back_to_batch(self, tmp_path,
                                                    monkeypatch):
    """An unsupported topology (the real cases are model-axis batch
    sharding and indivisible local batches — TestUnrollStagerParity
    pins the predicate itself; the TP variant cannot run here because
    of the seed jaxlib donation bug) must WARN and train with batch
    staging, not crash."""
    from scalable_agent_tpu import driver
    monkeypatch.setattr(driver.train_parallel, 'supports_unroll_staging',
                        lambda config, mesh: False)
    cfg = self._config(tmp_path, staging_mode='unroll')
    run = driver.train(cfg, max_steps=2, stall_timeout_secs=60)
    assert run.prefetcher.stats()['mode'] == 'batch'
    assert int(run.state.update_steps) == 2

  def test_train_with_unroll_staging_on_mesh_and_pallas(self, tmp_path):
    """The acceptance composition: 8-device pure-DP mesh + unroll
    staging + the shard_map'ped Pallas V-trace, through driver.train
    (the combination the old ValueError forbade)."""
    from scalable_agent_tpu import driver
    cfg = self._config(tmp_path, staging_mode='unroll', batch_size=8,
                       use_pallas_vtrace=True)
    run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
    assert int(run.state.update_steps) == 2
    pf = run.prefetcher.stats()
    assert pf['mode'] == 'unroll'
    assert pf['unrolls_staged'] >= 16

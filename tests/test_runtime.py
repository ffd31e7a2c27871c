"""Runtime integration: InferenceServer + TrajectoryBuffer + learner.

The production topology on fake envs: N actor THREADS sharing one
batched-inference server (C++ batcher → one jitted call), unrolls
flowing through the bounded buffer with backpressure, prefetched
batches feeding the jitted train step. The reference never tests this
glue (SURVEY §4); we do.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.envs.fake import ContextualBanditEnv, FakeEnv
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.runtime.actor import Actor, run_actor_loop
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.runtime.ring_buffer import (
    BatchPrefetcher, Closed, TrajectoryBuffer)

H, W, A = 24, 32, 3
OBS = {'frame': (H, W, 3), 'instr_len': MAX_INSTRUCTION_LEN}


def _mk(num_actions=A, **cfg_kw):
  agent = ImpalaAgent(num_actions=num_actions, torso='shallow',
                      use_instruction=False)
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  cfg = Config(**cfg_kw)
  return agent, params, cfg


class TestTrajectoryBuffer:

  def test_fifo_and_backpressure(self):
    buf = TrajectoryBuffer(capacity_unrolls=2)
    buf.put('a')
    buf.put('b')
    with pytest.raises(TimeoutError):
      buf.put('c', timeout=0.05)  # full → blocks
    assert buf.get() == 'a'
    buf.put('c')  # space again
    assert buf.get() == 'b'
    assert buf.get() == 'c'

  def test_close_wakes_blocked_producer(self):
    buf = TrajectoryBuffer(capacity_unrolls=1)
    buf.put('x')
    states = []

    def producer():
      try:
        buf.put('y')  # parks: buffer full
      except Closed:
        states.append('producer-closed')

    tp = threading.Thread(target=producer)
    tp.start()
    time.sleep(0.05)
    buf.close()
    tp.join(timeout=5)
    assert not tp.is_alive()
    assert states == ['producer-closed']
    # Queued items still drain after close, then Closed.
    assert buf.get() == 'x'
    with pytest.raises(Closed):
      buf.get()

  def test_get_batch_larger_than_capacity_streams(self):
    # The reference's capacity-1 FIFOQueue feeds dequeue_many(batch):
    # dequeues free producer slots incrementally, so batch > capacity
    # must work (no atomic-residency requirement).
    from scalable_agent_tpu.structs import ActorOutput
    buf = TrajectoryBuffer(capacity_unrolls=1)
    T, B = 4, 3

    def mk(i):
      return ActorOutput(
          level_name=np.int32(0),
          agent_state=np.full((1, 2), i, np.float32),
          env_outputs=np.full((T,), i, np.float32),
          agent_outputs=np.full((T,), i, np.float32))

    def producer():
      for i in range(B):
        buf.put(mk(i))

    tp = threading.Thread(target=producer)
    tp.start()
    batch = buf.get_batch(B, timeout=10)
    tp.join(timeout=5)
    assert batch.env_outputs.shape == (T, B)
    np.testing.assert_array_equal(batch.env_outputs[0], [0, 1, 2])
    assert batch.agent_state.shape == (B, 2)

  def test_get_batch_timeout_drops_nothing(self):
    from scalable_agent_tpu.structs import ActorOutput
    buf = TrajectoryBuffer(capacity_unrolls=4)
    item = ActorOutput(np.int32(7), np.zeros((1, 2), np.float32),
                       np.zeros((4,), np.float32),
                       np.zeros((4,), np.float32))
    buf.put(item)
    with pytest.raises(TimeoutError):
      buf.get_batch(2, timeout=0.05)  # partial: pushed back, not lost
    assert len(buf) == 1
    got = buf.get()
    assert got.level_name == 7

  def test_close_wakes_blocked_consumer(self):
    buf = TrajectoryBuffer(capacity_unrolls=1)
    states = []

    def consumer():
      try:
        buf.get()  # parks: buffer empty
      except Closed:
        states.append('consumer-closed')

    tc = threading.Thread(target=consumer)
    tc.start()
    time.sleep(0.05)
    buf.close()
    tc.join(timeout=5)
    assert not tc.is_alive()
    assert states == ['consumer-closed']


class TestBatchPrefetcher:

  @staticmethod
  def _item(i=0):
    from scalable_agent_tpu.structs import ActorOutput
    return ActorOutput(np.int32(0),
                       np.full((1, 2), i, np.float32),
                       np.full((4,), i, np.float32),
                       np.full((4,), i, np.float32))

  def test_double_buffering_hides_staging(self):
    """Acceptance (ISSUE 1): with staging depth >= 2 and producers
    keeping up, no step blocks on `place_fn` (the device_put stand-in)
    once the pipeline is primed — the overlap counters must show it."""
    buf = TrajectoryBuffer(capacity_unrolls=8)
    stop = threading.Event()

    def produce():
      while not stop.is_set():
        try:
          buf.put(self._item(), timeout=0.1)
        except (TimeoutError, Closed):
          continue

    producer = threading.Thread(target=produce, daemon=True)
    producer.start()

    def slow_place(batch):  # simulated H2D: 20 ms per staged batch
      time.sleep(0.02)
      return batch

    pf = BatchPrefetcher(buf, batch_size=2, place_fn=slow_place,
                         depth=2)
    try:
      pf.get(timeout=10)  # prime the pipeline (this one MAY block)
      for _ in range(10):
        time.sleep(0.03)  # simulated step: longer than one staging
        pf.get(timeout=10)
      stats = pf.stats()
      assert stats['depth'] == 2
      assert stats['gets'] == 11
      assert stats['staged_batches'] >= 11
      # Steady state never waited: at most the priming get blocked.
      assert stats['blocked_gets'] <= 1, stats
      assert stats['h2d_overlap_fraction'] >= 0.8, stats
    finally:
      stop.set()
      pf.close()
      producer.join(timeout=5)

  def test_depth_bounds_staged_batches(self):
    """depth bounds the staged-ahead pipeline (each slot extends the
    policy-lag bound by one batch, so the prefetcher must not run
    ahead of it): `depth` queued batches plus the one the thread has
    already dispatched and is parking — never more."""
    buf = TrajectoryBuffer(capacity_unrolls=8)
    for i in range(8):
      buf.put(self._item(i))
    staged = []
    pf = BatchPrefetcher(buf, batch_size=1,
                         place_fn=lambda b: staged.append(b) or b,
                         depth=3)
    try:
      deadline = time.monotonic() + 5
      while len(staged) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
      time.sleep(0.1)  # would overfill if depth were not enforced
      assert len(staged) == 4  # 3 queued + 1 parked at the full gate
      pf.get(timeout=5)
      deadline = time.monotonic() + 5
      while len(staged) < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
      time.sleep(0.05)
      assert len(staged) == 5  # one slot freed -> exactly one more
    finally:
      pf.close()


class TestInferenceServer:

  def test_actors_share_batched_inference(self):
    agent, params, cfg = _mk(
        batch_size=4, unroll_length=8, num_action_repeats=1,
        inference_min_batch=1, inference_max_batch=8,
        inference_timeout_ms=20)
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      actors = [
          Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i),
                server.policy, agent.initial_state(1), 8)
          for i in range(4)]
      unrolls = [[] for _ in actors]

      def run(i):
        for _ in range(2):
          unrolls[i].append(actors[i].unroll())

      threads = [threading.Thread(target=run, args=(i,))
                 for i in range(4)]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=60)
      for lst in unrolls:
        assert len(lst) == 2
        for u in lst:
          assert u.env_outputs.reward.shape == (9,)
          assert np.isfinite(
              np.asarray(u.agent_outputs.policy_logits)).all()
          assert (np.asarray(u.agent_outputs.action) >= 0).all()
          assert (np.asarray(u.agent_outputs.action) < A).all()
      # Merge telemetry: all requests accounted for, and with 4
      # concurrent actors against one computation thread some calls
      # MUST have merged (calls strictly < requests) — the
      # single-machine throughput lever the stats exist to expose.
      stats = server.stats()
      assert stats['requests'] >= 4 * 2 * 8
      assert stats['calls'] < stats['requests']
      assert stats['mean_batch'] > 1.0
    finally:
      server.close()

  def test_pad_batch_to_compiles_one_bucket(self):
    """VERDICT r3 W5: with pad_batch_to set (eval), every merged
    batch pads to ONE bucket — warmup executes exactly one padded
    shape and live traffic of any size reuses it (no tail compiles
    when levels finish)."""
    agent, params, cfg = _mk(
        batch_size=4, unroll_length=4, num_action_repeats=1,
        inference_min_batch=1, inference_max_batch=64,
        inference_timeout_ms=5)
    from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
    server = InferenceServer(agent, params, cfg, seed=3,
                             pad_batch_to=6)
    # Record the FULL layout of the one buffer the step takes (PR 36:
    # every input's dtype and shape, rows included): "one compile"
    # means one layout — a batch-rows-only probe would miss a second
    # compile from any other dimension (e.g. an instr-length mismatch
    # between warmup and live traffic).
    seen_shapes = set()
    real_step = server._step

    def recording_step(params_, rng, packed, layout):
      assert packed.shape == (layout.words,)
      seen_shapes.add(tuple(shape for _, shape in layout.specs))
      return real_step(params_, rng, packed, layout)

    server._step = recording_step
    try:
      # Same call evaluate() makes: max_size = this host's level
      # count; with the pad floor every candidate size lands in ONE
      # bucket, so warmup executes exactly one padded shape.
      server.warmup({'frame': (H, W, 3),
                     'instr_len': MAX_INSTRUCTION_LEN}, max_size=6)
      assert len(seen_shapes) == 1, seen_shapes
      assert next(iter(seen_shapes))[0] == (8,)  # pow2(6) rows

      # Live batch-1 traffic pads to the same bucket — the SAME full
      # shape tuple, so no further compile.
      actor = Actor(FakeEnv(height=H, width=W, num_actions=A, seed=0),
                    server.policy, agent.initial_state(1), 4)
      actor.unroll()
      assert len(seen_shapes) == 1, seen_shapes
    finally:
      server.close()

  def test_concurrent_param_updates_under_load(self):
    """Publisher hammering update_params while actor threads infer:
    the params pointer swap, the PRNG key lock, and the batcher must
    hold up under churn (the production cadence is one publish per
    learner step against ~48 inferring actors)."""
    agent, params, cfg = _mk(
        batch_size=4, unroll_length=8, num_action_repeats=1,
        inference_min_batch=1, inference_max_batch=8,
        inference_timeout_ms=5)
    server = InferenceServer(agent, params, cfg, seed=5)
    stop = threading.Event()
    try:
      actors = [
          Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i),
                server.policy, agent.initial_state(1), 8)
          for i in range(3)]

      def publisher():
        i = 0
        while not stop.is_set():
          scale = 1.0 + (i % 5) * 0.1
          server.update_params(jax.tree_util.tree_map(
              lambda x: x * scale, params))
          i += 1
          time.sleep(0.005)

      pub = threading.Thread(target=publisher, daemon=True)
      pub.start()
      unrolls = [[] for _ in actors]

      def run(i):
        for _ in range(3):
          unrolls[i].append(actors[i].unroll())

      threads = [threading.Thread(target=run, args=(i,))
                 for i in range(3)]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=120)
      # Let the publisher pass the count gate before stopping it: on a
      # loaded 1-core host the GIL can starve the publisher thread for
      # the actors' whole (warm-cache) run — the property under test
      # is swap-safety under churn, not a publish-rate SLO.
      deadline = time.monotonic() + 30
      while (server.stats()['params_version'] <= 3
             and time.monotonic() < deadline):
        time.sleep(0.01)
      stop.set()
      pub.join(timeout=10)
      for lst in unrolls:
        assert len(lst) == 3
        for u in lst:
          assert np.isfinite(
              np.asarray(u.agent_outputs.policy_logits)).all()
      assert server.stats()['params_version'] > 3
    finally:
      stop.set()
      server.close()

  def test_update_params_is_picked_up(self):
    agent, params, cfg = _mk(inference_timeout_ms=5)
    server = InferenceServer(agent, params, cfg)
    try:
      env = FakeEnv(height=H, width=W, num_actions=A)
      actor = Actor(env, server.policy, agent.initial_state(1), 4)
      u1 = actor.unroll()
      zeroed = jax.tree_util.tree_map(lambda x: x * 0, params)
      server.update_params(zeroed)
      u2 = actor.unroll()
      # With zero params, logits collapse to a constant vector.
      logits = np.asarray(u2.agent_outputs.policy_logits[1:])
      assert np.allclose(logits, logits[..., :1], atol=1e-6)
      del u1
    finally:
      server.close()



  def test_auto_min_batch_resolves_to_fleet_size(self, batcher_options_spy):
    """inference_min_batch=0 (auto) floors the merge at the fleet
    size, clamped to max_batch (docs/PERF.md round-5 batcher sweep)."""
    agent, params, cfg = _mk(
        batch_size=4, unroll_length=8, num_action_repeats=1,
        inference_min_batch=0, inference_max_batch=8,
        inference_timeout_ms=20)
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=6)
    server.close()
    assert batcher_options_spy[-1]['minimum_batch_size'] == 6
    # Clamped at max_batch when the fleet is bigger.
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=99)
    server.close()
    assert batcher_options_spy[-1]['minimum_batch_size'] == 8
    # Explicit min_batch is untouched by fleet_size.
    agent, params, cfg = _mk(
        batch_size=4, unroll_length=8, num_action_repeats=1,
        inference_min_batch=2, inference_max_batch=8,
        inference_timeout_ms=20)
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=6)
    server.close()
    assert batcher_options_spy[-1]['minimum_batch_size'] == 2

  def test_auto_min_batch_serves_a_fleet(self):
    """Auto merge floor end-to-end: 3 actors against min_batch=0 —
    every call should carry all 3 once the fleet is in steady state,
    and the timeout must keep a lone straggler from deadlocking."""
    agent, params, cfg = _mk(
        batch_size=3, unroll_length=6, num_action_repeats=1,
        inference_min_batch=0, inference_max_batch=8,
        inference_timeout_ms=50)
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=3)
    try:
      actors = [
          Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i),
                server.policy, agent.initial_state(1), 6)
          for i in range(3)]
      results = [None] * 3

      def run(i):
        results[i] = actors[i].unroll()

      threads = [threading.Thread(target=run, args=(i,))
                 for i in range(3)]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=60)
      assert all(r is not None for r in results)
      stats = server.stats()
      assert stats['requests'] >= 3 * 6
      assert stats['calls'] >= 1
      # NOTE deliberately no merge-ratio assert: on a loaded 1-core CI
      # host thread skew can expire the 50 ms window with partial
      # batches — the floor-resolution contract is pinned by the
      # monkeypatch test above, and the steady-state merge (3.92/4)
      # was measured on the real pipeline (docs/PERF.md r5 sweep).
      # This test pins the no-deadlock property.
    finally:
      server.close()

class _Poisoned:
  """Stand-in for an array whose execution failed: any host
  materialization or readiness check raises (jax semantics for
  outputs of a failed computation)."""

  def block_until_ready(self):
    raise RuntimeError('computation failed (simulated)')

  def __array__(self, dtype=None):
    raise RuntimeError('computation failed (simulated)')


class _Interrupt(BaseException):
  """Not an Exception: what a KeyboardInterrupt is to a call."""


def _cfg_variant(**kw):
  base = dict(batch_size=2, unroll_length=6, num_action_repeats=1,
              inference_min_batch=1, inference_max_batch=8,
              inference_timeout_ms=5)
  base.update(kw)
  return base


def _scripted_inputs(steps, seed=0):
  """Deterministic per-step (frame, reward, done) script with done
  edges (t % 7 == 0 past t=0) — both servers must see byte-identical
  inputs for the golden parity gate."""
  from scalable_agent_tpu.structs import StepOutput, StepOutputInfo
  rng = np.random.RandomState(seed)
  frames = rng.randint(0, 255, (steps, H, W, 3)).astype(np.uint8)
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  instr = np.zeros((MAX_INSTRUCTION_LEN,), np.int32)

  def env_out(t):
    return StepOutput(
        reward=np.float32(0.1 * t),
        info=StepOutputInfo(np.float32(0), np.int32(0)),
        done=np.bool_(t > 0 and t % 7 == 0),
        observation=(frames[t], instr))

  return env_out


def _drive(server, env_out, steps, state=None, feedback=True):
  """Sequential policy() loop; returns the per-step (action, logits,
  baseline) plus the final carry snapshot and the state object (slot
  handle in cache mode). feedback=False pins prev_action to 0 so the
  trace depends only on (inputs, carry), not the sampling key stream
  (what the zeroed-slot-reuse parity needs)."""
  if state is None:
    state = server.initial_core_state()
  prev = np.int32(0)
  outs = []
  for t in range(steps):
    out, state = server.policy(prev, env_out(t), state)
    outs.append((int(out.action),
                 np.asarray(out.policy_logits).copy(),
                 float(out.baseline)))
    if feedback:
      prev = np.int32(out.action)
  snap = state.snapshot() if hasattr(state, 'snapshot') else state
  return outs, tuple(np.asarray(x) for x in snap), state


def _assert_traces_equal(a, b):
  assert len(a) == len(b)
  for t, (ra, rb) in enumerate(zip(a, b)):
    assert ra[0] == rb[0], f'step {t}: action {ra[0]} != {rb[0]}'
    np.testing.assert_array_equal(ra[1], rb[1], err_msg=f'step {t}')
    assert ra[2] == rb[2], f'step {t}: baseline'


class TestStateCache:
  """The round-7 tentpole's golden parity gate: the device-resident
  state arena must be numerics-IDENTICAL to the carry-passing path —
  same seeds → identical actions/logits/baselines across multiple
  unrolls, through done edges, respawn slot reuse, and the sharded
  eval mesh."""

  def _servers(self, mesh=None, **cfg_kw):
    agent, params, _ = _mk()
    carry_cfg = Config(**_cfg_variant(inference_state_cache=False,
                                      **cfg_kw))
    cache_cfg = Config(**_cfg_variant(inference_state_cache=True,
                                      **cfg_kw))
    carry = InferenceServer(agent, params, carry_cfg, seed=3, mesh=mesh)
    cache = InferenceServer(agent, params, cache_cfg, seed=3, mesh=mesh)
    return carry, cache

  def test_golden_parity_multi_unroll_with_done_edges(self):
    carry, cache = self._servers()
    try:
      env_out = _scripted_inputs(24)
      a, snap_a, _ = _drive(carry, env_out, 24)   # >= 2 unrolls of 8
      b, snap_b, _ = _drive(cache, env_out, 24)
      _assert_traces_equal(a, b)
      for x, y in zip(snap_a, snap_b):
        np.testing.assert_array_equal(x, y)
    finally:
      carry.close()
      cache.close()

  def test_slot_release_and_zeroed_reuse(self):
    """Respawn slot reuse: release → re-acquire returns the SAME slot
    ZEROED, so the replacement's trace matches the original's
    from-scratch trace — no stale carry served."""
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_state_cache=True,
                                inference_state_slots=2))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      env_out = _scripted_inputs(6)
      # feedback=False: pin prev_action so the trace depends only on
      # (inputs, carry) — the key stream advances between the two
      # drives, so sampled actions may differ, exactly as a fresh
      # carry-passing actor's would.
      outs1, snap1, handle1 = _drive(server, env_out, 6,
                                     feedback=False)
      assert np.abs(snap1[0]).max() > 0  # carry actually advanced
      assert server.slots_free() == 1
      handle1.release()
      assert server.slots_free() == 2
      handle1.release()  # idempotent
      assert server.slots_free() == 2
      # LIFO reuse: the next acquire returns the SAME slot, zeroed —
      # logits/baseline (rng-free) must replay exactly.
      outs2, snap2, handle2 = _drive(server, env_out, 6,
                                     feedback=False)
      assert handle2.slot == handle1.slot
      for x, y in zip(outs1, outs2):
        np.testing.assert_array_equal(x[1], y[1])
        assert x[2] == y[2]
      for x, y in zip(snap1, snap2):
        np.testing.assert_array_equal(x, y)
      # A released handle must not be usable (a straggler thread must
      # fail loudly, not scatter into the new owner's slot).
      with pytest.raises(RuntimeError, match='released'):
        server.policy(np.int32(0), env_out(0), handle1)
    finally:
      server.close()

  def test_actor_death_mid_call_reclaims_slot(self):
    """Satellite: batcher-timeout/slot-leak — an actor whose policy
    call dies (server closed under it / env crash) unwinds through
    run_actor_loop's finally → actor.close() → the slot returns to
    the free list."""
    agent, params, cfg = _mk(**_cfg_variant(
        inference_state_cache=True, inference_timeout_ms=5))
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=2)
    from scalable_agent_tpu.runtime.ring_buffer import TrajectoryBuffer
    buf = TrajectoryBuffer(8)
    stop = threading.Event()
    total = server.slots_free()

    class DyingEnv(FakeEnv):

      def __init__(self, **kw):
        super().__init__(**kw)
        self._steps = 0

      def step(self, action):
        self._steps += 1
        if self._steps >= 3:
          raise RuntimeError('env crashed mid-unroll')
        return super().step(action)

    failures = []
    actor = Actor(DyingEnv(height=H, width=W, num_actions=A, seed=0),
                  server.policy, server.initial_core_state(), 8)
    try:
      assert server.slots_free() == total - 1
      run_actor_loop(actor, buf, stop, on_failure=failures.append)
      assert len(failures) == 1
      # The dying actor's slot came back; a fresh acquire is zeroed.
      assert server.slots_free() == total
      snap = server.initial_core_state().snapshot()
      assert np.abs(np.asarray(snap[0])).max() == 0
      assert np.abs(np.asarray(snap[1])).max() == 0
    finally:
      stop.set()
      server.close()
      buf.close()

  def test_mid_call_close_releases_slots_via_fleet_loop(self):
    """Actors parked IN policy() when the server closes: the
    BatcherCancelled unwind must still release every slot."""
    agent, params, cfg = _mk(**_cfg_variant(
        inference_state_cache=True,
        inference_min_batch=8,          # never satisfied: callers park
        inference_timeout_ms=60_000))
    server = InferenceServer(agent, params, cfg, seed=3, fleet_size=2)
    from scalable_agent_tpu.runtime.ring_buffer import TrajectoryBuffer
    buf = TrajectoryBuffer(8)
    stop = threading.Event()
    total = server.slots_free()
    actors = [Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i),
                    server.policy, server.initial_core_state(), 8)
              for i in range(2)]
    threads = [threading.Thread(target=run_actor_loop,
                                args=(a, buf, stop), daemon=True)
               for a in actors]
    for t in threads:
      t.start()
    time.sleep(0.3)  # both park in the merge wait
    assert server.slots_free() == total - 2
    stop.set()        # stop FIRST: cancellation is then a clean exit
    server.close()
    for t in threads:
      t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert server.slots_free() == total
    buf.close()

  def test_arena_exhaustion_degrades_not_raises(self):
    """Round 9: the old `RuntimeError('state arena exhausted')` is
    UNREACHABLE — under the default (block) admission policy an
    exhausted arena parks the caller, and only the deadline produces
    a clean, counted SlotUnavailable; a freed slot unparks a waiter
    or is acquirable again."""
    from scalable_agent_tpu.runtime.inference import SlotUnavailable
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_state_cache=True,
                                inference_state_slots=1,
                                inference_admission_timeout_secs=0.2))
    assert cfg.inference_admission == 'block'  # the default policy
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      h1 = server.initial_core_state()
      with pytest.raises(SlotUnavailable, match='admission timeout'):
        server.initial_core_state()
      stats = server.stats()
      assert stats['admission_timeouts'] == 1
      assert stats['admission_waits'] == 1
      assert stats['sheds'] == 0
      h1.release()
      server.initial_core_state()  # freed slot is acquirable again
    finally:
      server.close()

  def test_state_cache_through_actor_unroll_parity(self):
    """End-to-end through the REAL Actor loop (priming call included):
    identical unrolls from a carry-passing and a state-cache server —
    including agent_state (the learner's unroll-start carry) on the
    SECOND unroll, where the cache path's once-per-unroll snapshot
    must equal the carry path's host-held state."""
    agent, params, _ = _mk()
    results = {}
    for cache in (False, True):
      cfg = Config(**_cfg_variant(inference_state_cache=cache))
      server = InferenceServer(agent, params, cfg, seed=11)
      try:
        actor = Actor(FakeEnv(height=H, width=W, num_actions=A, seed=5),
                      server.policy, server.initial_core_state(), 6)
        u1 = actor.unroll()
        u2 = actor.unroll()
        actor.close()
        results[cache] = (u1, u2)
      finally:
        server.close()
    for (ua, ub) in zip(results[False], results[True]):
      np.testing.assert_array_equal(
          np.asarray(ua.agent_outputs.action),
          np.asarray(ub.agent_outputs.action))
      np.testing.assert_array_equal(
          np.asarray(ua.agent_outputs.policy_logits),
          np.asarray(ub.agent_outputs.policy_logits))
      for sa, sb in zip(ua.agent_state, ub.agent_state):
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


class TestInferencePlaneStats:

  def test_stats_percentiles_and_echo(self):
    agent, params, cfg = _mk(**_cfg_variant(
        inference_state_cache=True, inference_pipeline_depth=2))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      env_out = _scripted_inputs(8)
      _drive(server, env_out, 8)
      stats = server.stats()
      assert stats['pipeline_depth'] == 2
      assert stats['state_cache'] is True
      assert stats['latency_p50_ms'] > 0
      assert stats['latency_p99_ms'] >= stats['latency_p50_ms']
      assert stats['inflight_peak'] >= 1
      assert stats['slots_free'] is not None
    finally:
      server.close()
    # Carry-mode echo.
    server = InferenceServer(agent, params, Config(**_cfg_variant(
        inference_pipeline_depth=1)), seed=3)
    try:
      _drive(server, _scripted_inputs(4), 4)
      stats = server.stats()
      assert stats['pipeline_depth'] == 1
      assert stats['state_cache'] is False
      assert stats['slots_free'] is None
      assert stats['inflight_peak'] == 1  # depth 1: serial dispatch
    finally:
      server.close()

  def test_pipeline_depth_bounds_inflight(self):
    """The depth semaphore is the policy-lag bound of the inference
    plane: dispatched-but-uncompleted merged calls never exceed it."""
    agent, params, cfg = _mk(**_cfg_variant(
        inference_pipeline_depth=2, inference_timeout_ms=2))
    server = InferenceServer(agent, params, cfg, seed=3)
    stop = threading.Event()
    try:
      def hammer(i):
        env_out = _scripted_inputs(1, seed=i)
        state = server.initial_core_state()
        prev = np.int32(0)
        while not stop.is_set():
          out, state = server.policy(prev, env_out(0), state)
          prev = np.int32(out.action)

      threads = [threading.Thread(target=hammer, args=(i,),
                                  daemon=True) for i in range(4)]
      for t in threads:
        t.start()
      time.sleep(1.0)
      stop.set()
      for t in threads:
        t.join(timeout=10)
      stats = server.stats()
      assert stats['calls'] > 0
      assert 1 <= stats['inflight_peak'] <= 2
    finally:
      stop.set()
      server.close()

  def test_failed_execution_recovers_key_and_arena_chain(self):
    """One failed merged execution must fail THAT batch's callers and
    nothing else: the device key (and in cache mode the arena) are
    outputs of the failed step — the server re-anchors them instead of
    serving the poisoned chain to every later call forever."""
    from scalable_agent_tpu.ops.dynamic_batching import BatcherError

    for cache in (False, True):
      agent, params, cfg = _mk(**_cfg_variant(
          inference_state_cache=cache))
      server = InferenceServer(agent, params, cfg, seed=3)
      try:
        env_out = _scripted_inputs(4)
        _drive(server, env_out, 2)  # healthy warm path
        real_step = server._step
        # key + the packed outputs; the arena between them with the
        # state cache (PR 36: one array out whatever the outputs).
        n_outs = 3 if cache else 2
        state = {'poisoned': False}

        def failing_step(*args):
          if not state['poisoned']:
            state['poisoned'] = True
            return tuple(_Poisoned() for _ in range(n_outs))
          return real_step(*args)

        server._step = failing_step
        handle = server.initial_core_state()
        with pytest.raises(BatcherError, match='failed'):
          server.policy(np.int32(0), env_out(0), handle)
        # The very next call succeeds: the chain was re-anchored.
        out, handle = server.policy(np.int32(0), env_out(1), handle)
        assert np.isfinite(np.asarray(out.policy_logits)).all()
        stats = server.stats()
        assert stats['chain_recoveries'] >= 1
      finally:
        server.close()

  def test_staging_failure_answers_callers_and_survives(self):
    """A make_buffers failure after the batch was dequeued must answer
    the parked callers with the error (not strand them) and must not
    kill the dispatch thread."""
    from scalable_agent_tpu.ops.dynamic_batching import BatcherError
    agent, params, cfg = _mk(**_cfg_variant())
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      env_out = _scripted_inputs(4)
      real = server._staging_for
      state = {'failed': False}

      def flaky(total_rows):
        if not state['failed']:
          state['failed'] = True
          raise MemoryError('no staging memory (simulated)')
        return real(total_rows)

      server._staging_for = flaky
      core = server.initial_core_state()
      with pytest.raises(BatcherError, match='MemoryError'):
        server.policy(np.int32(0), env_out(0), core)
      out, core = server.policy(np.int32(0), env_out(1), core)
      assert np.isfinite(np.asarray(out.policy_logits)).all()
    finally:
      server.close()

  def test_update_params_version_gate(self):
    """Satellite: an unchanged-version publish must skip the
    whole-tree copy (counted), a new version must land."""
    agent, params, cfg = _mk()
    server = InferenceServer(agent, params, cfg)
    try:
      server.update_params(params, version=7)
      assert server.stats()['params_version'] == 1
      server.update_params(params, version=7)  # same version: skipped
      stats = server.stats()
      assert stats['params_version'] == 1
      assert stats['publishes_skipped'] == 1
      server.update_params(params, version=8)
      assert server.stats()['params_version'] == 2
      # Unversioned publishes never gate (the safe default).
      server.update_params(params)
      server.update_params(params)
      stats = server.stats()
      assert stats['params_version'] == 4
      assert stats['publishes_skipped'] == 1
    finally:
      server.close()


class TestFullPipeline:

  def test_actors_buffer_prefetcher_learner(self):
    agent, params, cfg = _mk(
        batch_size=2, unroll_length=6, num_action_repeats=1,
        total_environment_frames=10**6,
        inference_min_batch=1, inference_max_batch=8,
        inference_timeout_ms=10)
    server = InferenceServer(agent, params, cfg, seed=1)
    buf = TrajectoryBuffer(capacity_unrolls=cfg.batch_size *
                           cfg.queue_capacity_batches * 2)
    stop = threading.Event()

    def actor_loop(i):
      actor = Actor(
          ContextualBanditEnv(height=H, width=W, num_actions=A,
                              seed=10 + i),
          server.policy, agent.initial_state(1), cfg.unroll_length)
      run_actor_loop(actor, buf, stop)

    threads = [threading.Thread(target=actor_loop, args=(i,))
               for i in range(3)]
    for t in threads:
      t.start()

    prefetcher = BatchPrefetcher(buf, cfg.batch_size)
    state = learner_lib.make_train_state(params, cfg)
    train_step = learner_lib.make_train_step(agent, cfg)
    try:
      losses = []
      for _ in range(4):
        batch = prefetcher.get(timeout=60)
        state, metrics = train_step(state, batch)
        server.update_params(state.params)
        losses.append(float(metrics['total_loss']))
      assert all(np.isfinite(l) for l in losses), losses
      assert int(state.update_steps) == 4
    finally:
      stop.set()
      prefetcher.close()
      server.close()
      for t in threads:
        t.join(timeout=10)
      assert not any(t.is_alive() for t in threads)


# --- An actor thread stepping k envs in lockstep (PR 26) ---


class _ScriptedStatePolicy:
  """A deterministic Actor-contract policy in both forms (scalar, and
  k-row for an ActorGroup), computed row by row so that a row's result
  cannot depend on the rows it travelled with. `cache=True` keeps the
  carries behind opaque handles (snapshot / write / release), like the
  InferenceServer's state-cache mode."""

  class Handle:

    def __init__(self, carries, slot):
      self._carries, self.slot, self.released = carries, slot, False

    def snapshot(self):
      return tuple(x.copy() for x in self._carries[self.slot])

    def write(self, carry):
      self._carries[self.slot] = tuple(np.array(x) for x in carry)

    def release(self):
      self.released = True

  def __init__(self, cache):
    self._cache = cache
    self._carries = {}
    self.calls = []  # rows per call

  def initial_core_state(self):
    carry = (np.zeros((1, 4), np.float32), np.ones((1, 4), np.float32))
    if not self._cache:
      return carry
    slot = len(self._carries)
    self._carries[slot] = carry
    return self.Handle(self._carries, slot)

  @staticmethod
  def _row(prev_action, reward, done, frame, carry):
    from scalable_agent_tpu.structs import AgentOutput
    c, h = carry
    if done:
      c, h = np.zeros_like(c), np.ones_like(h)
    x = np.float32(frame.astype(np.float32).mean() / 255.0)
    c = (np.float32(0.5) * c + x + np.float32(prev_action)).astype(
        np.float32)
    h = (h * np.float32(0.9) + np.float32(reward)).astype(np.float32)
    logits = np.asarray([c[0, 0], h[0, 1], c[0, 2] - h[0, 3]],
                        np.float32)
    action = np.int32(int(np.abs(c).sum() * 7) % A)
    return AgentOutput(action, logits, np.float32(h.sum())), (c, h)

  def __call__(self, prev_action, env_output, core_state):
    from scalable_agent_tpu.structs import AgentOutput
    frame = env_output.observation[0]
    if np.ndim(prev_action) == 0:
      self.calls.append(1)
      carry = core_state.snapshot() if self._cache else core_state
      out, carry = self._row(prev_action, env_output.reward,
                             env_output.done, frame, carry)
      if self._cache:
        core_state.write(carry)
        return out, core_state
      return out, carry
    k = len(prev_action)
    self.calls.append(k)
    outs, carries = [], []
    for j in range(k):
      carry = (core_state[j].snapshot() if self._cache else
               tuple(x[j:j + 1] for x in core_state))
      out, carry = self._row(prev_action[j], env_output.reward[j],
                             env_output.done[j], frame[j], carry)
      outs.append(out)
      carries.append(carry)
      if self._cache:
        core_state[j].write(carry)
    out = AgentOutput(*[np.stack(xs) for xs in zip(*outs)])
    if self._cache:
      return out, core_state
    return out, tuple(np.concatenate(xs, axis=0) for xs in zip(*carries))


def _assert_unrolls_bitwise_equal(a, b):
  la, ta = jax.tree_util.tree_flatten(a)
  lb, tb = jax.tree_util.tree_flatten(b)
  assert ta == tb
  for x, y in zip(la, lb):
    x, y = np.asarray(x), np.asarray(y)
    assert x.dtype == y.dtype and x.shape == y.shape
    np.testing.assert_array_equal(x, y)


class TestActorGroup:

  @pytest.mark.parametrize('cache', [False, True],
                           ids=['carry_passing', 'state_cache'])
  def test_group_unrolls_bitwise_equal_single_actors(self, cache):
    """An env's unroll does not depend on the group it was stepped in:
    three unrolls of T=7 over episodes of 5 steps, so episode
    boundaries fall inside and across unrolls, the overlap frame, the
    lazy priming call and the unroll-start carry included."""
    from scalable_agent_tpu.runtime.actor import ActorGroup
    k, T = 3, 7

    def make(policy):
      return [Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i,
                            episode_length=5),
                    policy, policy.initial_core_state(), T,
                    num_action_repeats=2, level_name_id=i)
              for i in range(k)]

    alone_policy = _ScriptedStatePolicy(cache)
    alone = [[a.unroll() for _ in range(3)] for a in make(alone_policy)]
    group_policy = _ScriptedStatePolicy(cache)
    group = ActorGroup(make(group_policy))
    grouped = [group.unroll() for _ in range(3)]
    # One priming call and T steps an unroll, each carrying k rows.
    assert group_policy.calls == [k] * (1 + 3 * T)
    assert alone_policy.calls == [1] * (k * (1 + 3 * T))
    for j in range(k):
      for n in range(3):
        _assert_unrolls_bitwise_equal(alone[j][n], grouped[n][j])
      dones = np.concatenate(
          [np.asarray(u.env_outputs.done[1:]) for u in alone[j]])
      assert dones.sum() >= 3  # the episodes did end inside

  def test_group_of_one_makes_the_scalar_call(self):
    """`Actor.unroll()` is the k = 1 driver of the same loop: a policy
    that only knows the scalar contract keeps working."""
    seen = []

    def scalar_only(prev_action, env_output, core_state):
      from scalable_agent_tpu.structs import AgentOutput
      seen.append(np.ndim(prev_action))
      return AgentOutput(np.int32(1), np.zeros(A, np.float32),
                         np.float32(0)), core_state

    actor = Actor(FakeEnv(height=H, width=W, num_actions=A), scalar_only,
                  (np.zeros((1, 4), np.float32),) * 2, 4)
    unroll = actor.unroll()
    assert unroll.agent_outputs.action.shape == (5,)
    assert seen == [0] * 5

  def test_failed_member_is_named_and_every_reply_collected(self):
    """One env of a group raising: the group says which, and the
    others' steps were still brought to an end."""
    from scalable_agent_tpu.runtime.actor import ActorGroup

    class Crashing(FakeEnv):
      steps = 0

      def step(self, action):
        self.steps += 1
        if self.steps == 3:
          raise RuntimeError('env crashed mid-unroll')
        return super().step(action)

    policy = _ScriptedStatePolicy(cache=False)
    envs = [FakeEnv(height=H, width=W, num_actions=A, seed=0),
            Crashing(height=H, width=W, num_actions=A, seed=1),
            FakeEnv(height=H, width=W, num_actions=A, seed=2)]
    group = ActorGroup([Actor(env, policy, policy.initial_core_state(), 6)
                        for env in envs])
    with pytest.raises(RuntimeError, match='mid-unroll'):
      group.unroll()
    assert group.failed is group.actors[1]
    assert group.waiting_on is None

  @pytest.mark.parametrize('cache', [False, True],
                           ids=['carry_passing', 'state_cache'])
  def test_members_join_and_leave_between_unrolls(self, cache):
    """A member that joins a running group (unprimed, among primed
    mates) and one that leaves it: every env's unrolls stay bitwise
    what it produces alone, and the one that left is closed."""
    from scalable_agent_tpu.runtime.actor import ActorGroup
    T = 7

    def make(policy):
      return [Actor(FakeEnv(height=H, width=W, num_actions=A, seed=i,
                            episode_length=5),
                    policy, policy.initial_core_state(), T,
                    level_name_id=i) for i in range(3)]

    alone = [[a.unroll() for _ in range(3)]
             for a in make(_ScriptedStatePolicy(cache))]
    policy = _ScriptedStatePolicy(cache)
    a, b, c = make(policy)
    group = ActorGroup([a, b], names=['a', 'b'])
    first = group.unroll()
    group.join(c, 'c')
    assert group.actors == [a, b]  # not before the rolling thread says
    group.admit()
    second = group.unroll()
    group.leave(a)
    assert (group.actors, group.names) == ([b, c], ['b', 'c'])
    third = group.unroll()
    assert policy.calls == ([2] * (1 + T) + [1] + [3] * T + [2] * T)
    for got, want in [(first[0], alone[0][0]), (second[0], alone[0][1]),
                      (first[1], alone[1][0]), (second[1], alone[1][1]),
                      (third[0], alone[1][2]),
                      (second[2], alone[2][0]), (third[1], alone[2][1])]:
      _assert_unrolls_bitwise_equal(want, got)
    if cache:
      assert a._core_state.released and not b._core_state.released
    group.join(a, 'late')
    group.close()  # a member no unroll took in is closed with the rest
    if cache:
      assert b._core_state.released and c._core_state.released


class _NoSpecEnv:
  """A hosted env that declares nothing: FakeEnv's steps, no
  `_tensor_specs`."""

  def __init__(self, **kw):
    self._env = FakeEnv(**kw)

  def initial(self):
    return self._env.initial()

  def step(self, action):
    return self._env.step(action)


class _TruncatingEnv(FakeEnv):
  """Its third step returns a frame a row short of its declared spec
  (`fault='shape'`), ends the process (`fault='die'`) or sleeps for a
  minute (`fault='sleep'`)."""

  def __init__(self, fault=None, **kw):
    super().__init__(**kw)
    self._fault, self._steps = fault, 0

  def step(self, action):
    reward, done, (frame, instr) = super().step(action)
    self._steps += 1
    if self._steps == 3 and self._fault == 'shape':
      frame = frame[:-1]
    if self._steps == 3 and self._fault == 'die':
      import os
      os._exit(1)
    if self._steps == 3 and self._fault == 'sleep':
      time.sleep(60)
    return reward, done, (frame, instr)


def _hosted_group(env_class, kwargs_list, policy, T, **process_kwargs):
  from scalable_agent_tpu.runtime import py_process
  from scalable_agent_tpu.runtime.actor import ActorGroup
  envs = [py_process.ProxyEnv(
      py_process.PyProcess(env_class, kw, **process_kwargs).start())
      for kw in kwargs_list]
  return ActorGroup([
      Actor(env, policy, policy.initial_core_state(), T,
            num_action_repeats=2, level_name_id=i)
      for i, env in enumerate(envs)])


def _block_group_cases():
  from scalable_agent_tpu.envs.tokens import TokenEnv
  image = dict(height=H, width=W, num_actions=A, episode_length=5)
  tokens = dict(vocab_size=A, episode_length=6, prompt_length=2)
  return [pytest.param(cls, kw, k, id=f'{name}-k{k}')
          for name, cls, kw in [('image', FakeEnv, image),
                                ('tokens', TokenEnv, tokens)]
          for k in (1, 3, 32)]


# Long beside what a loaded host adds to a hosted step's exchange
# (~4 ms a step seen under a full tier-1 run, where 2 ms naps failed).
_NAP_MS = 10.0


class _SleepyEnv(FakeEnv):
  """FakeEnv whose `step` takes `_NAP_MS` of its own."""

  def step(self, action):
    time.sleep(_NAP_MS / 1e3)
    return super().step(action)


class TestGroupStepRecord:
  """PR 37: a group's steps in its always-on record, three stamps a
  step and the slowest member's own time in its env; the fleet's
  `stats()` sums them and lays the long ones beside the activities of
  other threads."""

  @pytest.mark.parametrize('hosting', ['block', 'pipe', 'in_process'])
  def test_the_childs_own_step_time_reaches_the_record(self, hosting):
    from scalable_agent_tpu.runtime import fleet as fleet_lib
    from scalable_agent_tpu.runtime.actor import ActorGroup
    T, k = 5, 3
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i)
                   for i in range(k)]
    policy = _ScriptedStatePolicy(cache=False)
    if hosting == 'in_process':
      group = ActorGroup([
          Actor(_SleepyEnv(**kw), policy, policy.initial_core_state(), T)
          for kw in kwargs_list])
    else:
      group = _hosted_group(_SleepyEnv, kwargs_list, policy, T,
                            step_block=hosting == 'block')
    try:
      for _ in range(2):
        group.unroll()
      assert group._rollout.shared == (hosting == 'block')
      counts = fleet_lib._step_counts([group.steps])
      _, rows = group.steps.held()
    finally:
      group.close()
    steps = counts['group_steps']
    assert steps == 2 * T == len(rows)
    # The slowest member's own nap, every step; the step's env phase
    # holds it (the children nap at once, envs in this process in turn).
    assert (rows[:, 3] >= _NAP_MS * 1e6).all()
    assert counts['step_env_child_ms'] >= _NAP_MS * steps
    assert counts['step_env_ms'] >= counts['step_env_child_ms']
    if hosting == 'in_process':
      assert counts['step_env_ms'] >= _NAP_MS * k * steps
    else:
      assert counts['step_env_ms'] < _NAP_MS * k * steps
    assert counts['step_ms'] == pytest.approx(
        counts['step_policy_wait_ms'] + counts['step_env_ms'])
    # Within an unroll one step's last stamp is the next one's first.
    in_unroll = np.arange(1, steps) % T != 0
    assert (rows[1:, 0] == rows[:-1, 2])[in_unroll].all()
    assert (np.diff(rows[:, :3], axis=1) > 0).all()

  def test_a_fleet_lays_its_long_steps_at_the_door_of_a_publish(self):
    """One thread's fast steps, and a `learner/publish` that another
    thread spends 50 ms in without letting go of the GIL: the steps
    held up under it are the excess, and `stats()` says under what."""
    from scalable_agent_tpu import telemetry
    from scalable_agent_tpu.runtime.fleet import ActorFleet
    policy = _ScriptedStatePolicy(cache=False)

    def make_actor(i):
      env = FakeEnv(height=H, width=W, num_actions=A, seed=i)
      return env, None, Actor(env, policy, policy.initial_core_state(),
                              unroll_length=50)

    class _Sink:  # a buffer that never fills
      closed = False

      def put(self, unroll, timeout=None):
        pass

      def close(self):
        self.closed = True

    fleet = ActorFleet(make_actor, _Sink(), num_actors=1)
    first = fleet.stats()
    assert first['group_steps'] == 0 and first['step_ms'] == 0.0
    assert first['step_excess_ms_in_learner/publish'] == 0.0
    assert first['step_ms_p50'] == first['step_ms_max'] == 0.0
    fleet.start()
    try:
      time.sleep(0.2)
      opened = fleet.stats()
      with telemetry.activity('learner/publish'):
        deadline = time.perf_counter() + 0.05
        while time.perf_counter() < deadline:  # holds the GIL
          sum(range(1000))
      time.sleep(0.1)
      closed = fleet.stats()
    finally:
      fleet.stop()
    delta = lambda key: closed[key] - opened[key]  # noqa: E731
    assert delta('group_steps') > 100
    # Half of the 50 ms and more, whatever else the machine did; no
    # other activity was under way.
    assert delta('step_excess_ms_in_learner/publish') >= 15.0
    assert delta('step_excess_ms') >= delta(
        'step_excess_ms_in_learner/publish')
    assert delta('step_excess_ms_in_staging/stage') == 0.0
    assert closed['step_ms_max'] >= 4.0 > closed['step_ms_p50']
    assert closed['step_ms_p95'] >= closed['step_ms_p50'] > 0
    # A thread that ended leaves its counts behind.
    assert fleet.stats()['group_steps'] >= closed['group_steps']


class TestGroupOnASharedBlock:
  """PR 33: hosted envs of a declared `step` spec step into a block in
  shared memory that their group owns; everything else stays as it
  was, and which it is follows from what the members offer."""

  @pytest.mark.parametrize('env_class,kwargs,k', _block_group_cases())
  def test_block_and_pipe_unrolls_are_bitwise_equal(self, env_class,
                                                    kwargs, k):
    """The same envs, the same seeds, the same policy: a group on the
    block and one on the pipe produce the same ActorOutputs, which are
    what each env produces in this process, alone."""
    T = 7
    kwargs_list = [dict(kwargs, seed=i) for i in range(k)]
    policies = [_ScriptedStatePolicy(cache=False) for _ in range(3)]
    on_block = _hosted_group(env_class, kwargs_list, policies[0], T)
    on_pipe = _hosted_group(env_class, kwargs_list, policies[1], T,
                            step_block=False)
    alone = [Actor(env_class(**kw), policies[2],
                   policies[2].initial_core_state(), T,
                   num_action_repeats=2, level_name_id=i)
             for i, kw in enumerate(kwargs_list)]
    try:
      for n in range(3):
        blocked, piped = on_block.unroll(), on_pipe.unroll()
        for j in range(k):
          _assert_unrolls_bitwise_equal(blocked[j], piped[j])
          if j < 3:
            _assert_unrolls_bitwise_equal(blocked[j], alone[j].unroll())
      steps = [a._env._process.block_steps for a in on_block.actors]
      assert steps == [3 * T] * k
      assert not any(a._env._process.block_steps for a in on_pipe.actors)
      # The group's own record counts a step once, whatever k, and
      # every step carries its slowest child's own time.
      for group in (on_block, on_pipe):
        assert group.steps.cycles == 3 * T
        _, rows = group.steps.held()
        assert (rows[:, 3] > 0).all()
      # What an unroll holds is its own: the next unroll reuses the
      # group's arrays and must not show through.
      held = blocked[0].env_outputs.observation[0].copy()
      on_block.unroll()
      np.testing.assert_array_equal(
          blocked[0].env_outputs.observation[0], held)
    finally:
      on_block.close()
      on_pipe.close()

  def test_an_env_without_a_spec_stays_on_the_pipe(self):
    """Nothing declared, nothing to lay a block out from: the group
    steps by pickled calls, and the counters say so."""
    T = 4
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i)
                   for i in range(2)]
    policy = _ScriptedStatePolicy(cache=False)
    group = _hosted_group(_NoSpecEnv, kwargs_list, policy, T)
    alone = [Actor(FakeEnv(**kw), policy, policy.initial_core_state(),
                   T, num_action_repeats=2, level_name_id=i)
             for i, kw in enumerate(kwargs_list)]
    try:
      for unroll, actor in zip(group.unroll(), alone):
        _assert_unrolls_bitwise_equal(unroll, actor.unroll())
      processes = [a._env._process for a in group.actors]
      assert [p.block_steps for p in processes] == [0, 0]
      assert [p.pipe_calls for p in processes] == [1 + T] * 2
    finally:
      group.close()

  @pytest.mark.parametrize('fault,error', [
      ('shape', 'SpecMismatchError'), ('die', 'ProcessClosed')])
  def test_a_failing_member_is_named_and_its_mates_collected(
      self, fault, error):
    """A member whose step breaks its spec, or whose process dies
    between wake-up and answer: the group says which, the error is
    what the pipe gave, and no mate is left mid-call."""
    from scalable_agent_tpu.runtime import py_process
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i,
                        fault=fault if i == 1 else None)
                   for i in range(3)]
    group = _hosted_group(_TruncatingEnv, kwargs_list,
                          _ScriptedStatePolicy(cache=False), 6)
    try:
      with pytest.raises(getattr(py_process, error),
                         match='_TruncatingEnv'):
        group.unroll()
      assert group.failed is group.actors[1]
      assert group.waiting_on is None
      for actor in group.actors[0::2]:
        process = actor._env._process
        assert process._pending is None and process.block_steps == 3
        actor._env.initial()  # the pipe is in step: answered
    finally:
      group.close()

  @pytest.mark.parametrize('hosting', ['block', 'pipe', 'in_process'])
  def test_pass_steps_count_group_steps_and_block_steps_members(
      self, hosting):
    """`pass_steps` counts the GROUP steps taken as one pass
    over a shared block, every one of them where the members map one
    and none where they do not; `block_steps` still counts each
    member's own steps through the block."""
    from scalable_agent_tpu.runtime import fleet as fleet_lib
    from scalable_agent_tpu.runtime.actor import ActorGroup
    T, k = 4, 3
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i)
                   for i in range(k)]
    policy = _ScriptedStatePolicy(cache=False)
    if hosting == 'in_process':
      group = ActorGroup([
          Actor(FakeEnv(**kw), policy, policy.initial_core_state(), T)
          for kw in kwargs_list])
    else:
      group = _hosted_group(FakeEnv, kwargs_list, policy, T,
                            step_block=hosting == 'block')
    try:
      for _ in range(2):
        group.unroll()
      counts = fleet_lib._step_counts([group.steps])
      steps = [getattr(a._env, '_process', None) for a in group.actors]
      steps = [0 if p is None else p.block_steps for p in steps]
    finally:
      group.close()
    assert counts['group_steps'] == 2 * T
    on_block = hosting == 'block'
    assert counts['pass_steps'] == (2 * T if on_block else 0)
    assert steps == [2 * T if on_block else 0] * k

  def test_waiting_on_names_the_member_a_pass_is_blocked_on(self):
    """One member sleeps in its step while its mates have answered:
    the group names it (`waiting_on`, what the fleet reads to charge a
    hang to the member that hangs alone); closing it breaks the pass,
    which raises ProcessClosed for that member and collects the rest."""
    from scalable_agent_tpu.runtime import py_process
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i,
                        fault='sleep' if i == 1 else None)
                   for i in range(3)]
    group = _hosted_group(_TruncatingEnv, kwargs_list,
                          _ScriptedStatePolicy(cache=False), 6)
    raised = []

    def unroll():
      try:
        group.unroll()
      except py_process.ProcessClosed as e:
        raised.append(e)

    thread = threading.Thread(target=unroll, daemon=True)
    try:
      thread.start()
      deadline = time.monotonic() + 30
      while (group.waiting_on is not group.actors[1] and
             time.monotonic() < deadline):
        time.sleep(0.01)
      assert group.waiting_on is group.actors[1]
      time.sleep(0.2)  # asleep still; the mates long answered
      assert group.waiting_on is group.actors[1]
      block = group._rollout.block
      assert block.seq[0] == block.seq[2] == block.step_seq
      group.actors[1]._env._process.close(timeout=0.5)
      thread.join(30)
      assert not thread.is_alive() and len(raised) == 1
      assert group.failed is group.actors[1]
      assert group.waiting_on is None
      for actor in group.actors[0::2]:
        actor._env.initial()  # the pipe is in step: answered
    finally:
      group.close()

  def test_a_new_member_is_mapped_into_a_new_block(self):
    """Membership moves between unrolls: the group lays a block out
    for the members as they are, every child maps it, and the unrolls
    stay what each env produces alone."""
    from scalable_agent_tpu.runtime import py_process
    T = 5
    kwargs_list = [dict(height=H, width=W, num_actions=A, seed=i,
                        episode_length=4) for i in range(3)]
    policy = _ScriptedStatePolicy(cache=False)
    group = _hosted_group(FakeEnv, kwargs_list, policy, T)
    alone_policy = _ScriptedStatePolicy(cache=False)
    alone = [Actor(FakeEnv(**kw), alone_policy,
                   alone_policy.initial_core_state(), T,
                   num_action_repeats=2, level_name_id=i)
             for i, kw in enumerate(kwargs_list)]
    late = group.actors.pop()
    group.names = ['a', 'b']
    try:
      first = group.unroll()
      block = group._rollout.block
      assert group._rollout.shared and block.columns == 2
      group.join(late, 'c')
      group.admit()
      second = group.unroll()
      assert group._rollout.block is not block
      assert group._rollout.shared and group._rollout.block.columns == 3
      group.leave(group.actors[0])
      third = group.unroll()
      assert group._rollout.block.columns == 2
      for got, actor in [(first[0], alone[0]), (second[0], alone[0]),
                         (first[1], alone[1]), (second[1], alone[1]),
                         (third[0], alone[1]),
                         (second[2], alone[2]), (third[1], alone[2])]:
        _assert_unrolls_bitwise_equal(got, actor.unroll())
      assert not [name for name in os.listdir(py_process._BLOCK_DIR)
                  if name.startswith(f'step_block_{os.getpid()}_')]
    finally:
      group.close()


class TestGroupedPolicyCall:

  @pytest.mark.parametrize('cache', [False, True],
                           ids=['carry_passing', 'state_cache'])
  def test_k_row_call_equals_k_one_row_calls(self, cache):
    """`InferenceServer.policy` with k rows in ONE request against k
    one-row requests merged into one call in the same row order, from
    the same key and the same carries: row for row the same action,
    logits, baseline and new carry; `requests` counts rows either way
    and `batcher_requests` the calls that carried them."""
    from scalable_agent_tpu.structs import StepOutput
    k = 3
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(
        inference_state_cache=cache, inference_min_batch=k,
        inference_timeout_ms=60_000))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      key0 = jax.random.PRNGKey(17)
      rng = np.random.RandomState(5)
      env_outs = [_scripted_inputs(4, seed=j)(j + 1) for j in range(k)]
      prev = rng.randint(0, A, (k,)).astype(np.int32)
      carries = [tuple(rng.randn(1, 256).astype(np.float32)
                       for _ in range(2)) for _ in range(k)]
      handles = ([server.initial_core_state() for _ in range(k)]
                 if cache else None)

      def reset():
        with server._key_lock:
          server._key = key0
        if cache:
          for handle, carry in zip(handles, carries):
            handle.write(carry)

      def carry_of(j, new_state):
        return handles[j].snapshot() if cache else new_state

      # k requests of one row, enqueued in row order.
      reset()
      base = server.stats()['batcher_requests']
      rows = [None] * k

      def call(j):
        out, new_state = server.policy(
            prev[j], env_outs[j], handles[j] if cache else carries[j])
        rows[j] = (out, new_state)

      threads = [threading.Thread(target=call, args=(j,))
                 for j in range(k)]
      for j, t in enumerate(threads):
        t.start()
        deadline = time.monotonic() + 30
        while (server.stats()['batcher_requests'] < base + j + 1 and
               time.monotonic() < deadline):
          time.sleep(0.002)
        time.sleep(0.02)  # counted just before it enqueues
      for t in threads:
        t.join(timeout=60)
      assert all(r is not None for r in rows)
      one_row = [(r[0], carry_of(j, r[1])) for j, r in enumerate(rows)]
      stats = server.stats()
      assert stats['batcher_requests'] == base + k
      calls, requests = stats['calls'], stats['requests']

      # One request of k rows.
      reset()
      stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                       *env_outs)
      core = handles if cache else tuple(
          np.concatenate(xs, axis=0) for xs in zip(*carries))
      out, new_state = server.policy(prev, stacked, core)
      stats = server.stats()
      assert stats['batcher_requests'] == base + k + 1
      assert stats['calls'] == calls + 1
      assert stats['requests'] == requests + k
      assert np.asarray(out.action).shape == (k,)
      for j in range(k):
        alone_out, alone_carry = one_row[j]
        assert int(out.action[j]) == int(alone_out.action)
        np.testing.assert_array_equal(out.policy_logits[j],
                                      alone_out.policy_logits)
        assert out.baseline[j] == alone_out.baseline
        grouped_carry = (handles[j].snapshot() if cache else
                         tuple(x[j:j + 1] for x in new_state))
        for x, y in zip(grouped_carry, alone_carry):
          np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    finally:
      server.close()

  def test_k_row_call_checks_every_handle(self):
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_state_cache=True))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      handles = [server.initial_core_state() for _ in range(2)]
      env_out = _scripted_inputs(2)
      stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                       env_out(0), env_out(1))
      handles[1].release()
      with pytest.raises(RuntimeError, match='released'):
        server.policy(np.zeros(2, np.int32), stacked, handles)
      with pytest.raises(TypeError, match='slot handle'):
        server.policy(np.zeros(2, np.int32), stacked,
                      [handles[0], object()])
    finally:
      server.close()

  @pytest.mark.parametrize('inline', [True, False],
                           ids=['groups_inline', 'all_batched'])
  def test_mixed_row_counts_under_contention(self, inline):
    """More callers than cores, scalar and k-row requests mixed, a
    short switch interval: every caller gets ITS rows back (the row
    count and the echo of its own frame's logits shape), and the two
    counters add up: rows in `requests`, calls in `batcher_requests`.
    At a floor of 1 the groups' requests go inline; with the inline
    call taken away every request rides the batcher, which merges
    k-row requests with scalar ones."""
    import sys
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_min_batch=1,
                                inference_max_batch=16,
                                inference_timeout_ms=2))
    server = InferenceServer(agent, params, cfg, seed=3)
    if not inline:
      server._call_inline = lambda inputs: None  # the batched path
    env_out = _scripted_inputs(4)
    widths = [0, 1, 2, 3] * 3  # 0: the scalar form
    calls_each, errors = 15, []

    def caller(k):
      try:
        rows = max(k, 1)
        carry = tuple(np.zeros((rows, 256), np.float32)
                      for _ in range(2))
        for t in range(calls_each):
          if k == 0:
            out, carry = server.policy(np.int32(0), env_out(t % 4), carry)
            assert np.ndim(out.action) == 0
          else:
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *[env_out(t % 4)] * k)
            out, carry = server.policy(np.zeros(k, np.int32), stacked,
                                       carry)
            assert out.policy_logits.shape == (k, A)
            # The same input in every row: the same logits back.
            np.testing.assert_array_equal(out.policy_logits[0],
                                          out.policy_logits[-1])
          assert carry[0].shape == (rows, 256)
      except BaseException as e:  # noqa: BLE001 — reported below
        errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
      threads = [threading.Thread(target=caller, args=(k,))
                 for k in widths]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=120)
      assert not any(t.is_alive() for t in threads)
      assert not errors, errors
      stats = server.stats()
      assert stats['batcher_requests'] == len(widths) * calls_each
      assert stats['requests'] == calls_each * sum(
          max(k, 1) for k in widths)
      assert (stats['inline_calls'] > 0) == inline
    finally:
      sys.setswitchinterval(interval)
      server.close()


def _group_request(k, t=0, seed=0, done_rows=()):
  """The k-row form of `policy`'s env output: row j is
  `_scripted_inputs(.., seed + j)` at step t, `done` where listed."""
  rows = [_scripted_inputs(t + 1, seed=seed + j)(t) for j in range(k)]
  stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *rows)
  done = np.zeros((k,), bool)
  done[list(done_rows)] = True
  return stacked._replace(done=done)


class TestInlineCall:
  """PR 39: a group's request whose rows alone fill the merge floor is
  ONE call on its caller's thread (runtime/inference.py ::
  `_call_inline`), with the batched path's accounting, locks, ring and
  answers; every other request rides the batcher as before."""

  @pytest.mark.parametrize('cache', [False, True],
                           ids=['carry_passing', 'state_cache'])
  def test_inline_and_batched_paths_answer_alike(self, cache):
    """One seeded server, one 3-row request at floor 3 (a pad row in
    the bucket of 4), `done` resets on two steps: the inline path and
    the batched path give the same actions, logits, baselines, new
    carries and counts, bit for bit."""
    k = 3
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_state_cache=cache,
                                inference_min_batch=k))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      rng = np.random.RandomState(5)
      prev0 = rng.randint(0, A, (k,)).astype(np.int32)
      carries = [tuple(rng.randn(1, 256).astype(np.float32)
                       for _ in range(2)) for _ in range(k)]
      handles = ([server.initial_core_state() for _ in range(k)]
                 if cache else None)
      requests = [_group_request(k, t, done_rows=done)
                  for t, done in enumerate([(), (1,), (), (0, 2)])]

      def run():
        with server._key_lock:
          server._key = jax.random.PRNGKey(17)
        if cache:
          for handle, carry in zip(handles, carries):
            handle.write(carry)
          core = handles
        else:
          core = tuple(np.concatenate(xs) for xs in zip(*carries))
        opened, answers, prev = server.stats(), [], prev0
        for env_out in requests:
          out, core = server.policy(prev, env_out, core)
          carry = ([h.snapshot() for h in handles] if cache else core)
          answers.append(jax.tree_util.tree_map(
              np.array, (tuple(out), carry)))
          prev = np.asarray(out.action, np.int32)
        closed = server.stats()
        counts = {key: closed[key] - opened[key] for key in (
            'calls', 'requests', 'inline_calls', 'state_resets')}
        return answers, counts

      inline, inline_counts = run()
      server._call_inline = lambda inputs: None  # the batched path
      batched, batched_counts = run()
    finally:
      server.close()
    steps = len(requests)
    assert inline_counts == dict(calls=steps, requests=k * steps,
                                 inline_calls=steps, state_resets=3)
    assert batched_counts == dict(inline_counts, inline_calls=0)
    for got, want in zip(jax.tree_util.tree_leaves(inline),
                         jax.tree_util.tree_leaves(batched)):
      assert got.dtype == want.dtype and got.shape == want.shape
      np.testing.assert_array_equal(got, want)

  def test_the_floor_decides_the_path(self):
    """At the floor and above: inline. Two groups under the floor
    still merge into ONE batched call; a lone actor's row rides the
    batcher whatever the floor; a group over the merge's maximum is
    refused as the batcher refuses it."""
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_min_batch=4,
                                inference_timeout_ms=60_000))
    server = InferenceServer(agent, params, cfg, seed=3)

    def call(k, seed=0):
      carry = tuple(np.zeros((k, 256), np.float32) for _ in range(2))
      out, _ = server.policy(np.zeros(k, np.int32),
                             _group_request(k, seed=seed), carry)
      assert np.asarray(out.action).shape == (k,)

    try:
      call(4)
      call(5)
      stats = server.stats()
      assert stats['calls'] == stats['inline_calls'] == 2
      pair = [threading.Thread(target=call, args=(2, seed))
              for seed in (1, 2)]
      for t in pair:
        t.start()
      for t in pair:
        t.join(timeout=60)
      stats = server.stats()
      assert (stats['calls'], stats['inline_calls']) == (3, 2)
      assert stats['requests'] == 4 + 5 + 2 + 2
      assert stats['batcher_requests'] == 4
      with pytest.raises(ValueError, match='maximum_batch_size'):
        call(9)
      assert server.stats()['inline_calls'] == 2
    finally:
      server.close()
    cfg = Config(**_cfg_variant())  # floor 1
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      env_out = _scripted_inputs(2)
      _drive(server, env_out, 2)
      assert server.stats()['inline_calls'] == 0
      call(1)
      stats = server.stats()
      assert (stats['calls'], stats['inline_calls']) == (3, 1)
    finally:
      server.close()

  def test_groups_under_the_pad_floor_ride_the_batcher(self):
    """The eval server's case: a floor of 1 and `pad_batch_to` the
    fleet. A group's request reaches the floor but not the pad floor,
    so it rides the batcher, where groups queued together merge into a
    call padded to the fleet, as before the inline call; a request that
    fills the pad floor alone goes inline."""
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_min_batch=1))
    server = InferenceServer(agent, params, cfg, seed=3, pad_batch_to=4)

    def call(k, seed=0):
      carry = tuple(np.zeros((k, 256), np.float32) for _ in range(2))
      out, _ = server.policy(np.zeros(k, np.int32),
                             _group_request(k, seed=seed), carry)
      assert np.asarray(out.action).shape == (k,)

    try:
      call(2)
      pair = [threading.Thread(target=call, args=(2, seed))
              for seed in (1, 2)]
      for t in pair:
        t.start()
      for t in pair:
        t.join(timeout=60)
      stats = server.stats()
      assert stats['inline_calls'] == 0 and stats['requests'] == 6
      assert stats['batcher_requests'] == 3
      assert list(server._staging) == [4]  # every call padded to 4
      call(4)
      assert server.stats()['inline_calls'] == 1
    finally:
      server.close()

  @pytest.mark.parametrize('cache', [False, True],
                           ids=['carry_passing', 'state_cache'])
  def test_a_failed_inline_call_raises_in_its_caller(self, cache):
    """A failed execution re-anchors the key (and arena) and raises
    BatcherError in the caller, as the batched path answers its parked
    callers; a failed dispatch raises the same way and re-anchors
    nothing. Either way the semaphore and the staging position come
    back, and the next call is served; an interrupt (not an Exception)
    goes to the caller as it is, and gives the semaphore back too."""
    from scalable_agent_tpu.ops.dynamic_batching import BatcherError
    k = 2
    agent, params, cfg = _mk(**_cfg_variant(inference_state_cache=cache,
                                            inference_min_batch=k))
    server = InferenceServer(agent, params, cfg, seed=3)
    try:
      core = ([server.initial_core_state() for _ in range(k)] if cache
              else tuple(np.zeros((k, 256), np.float32)
                         for _ in range(2)))

      def call(t):
        out, new_core = server.policy(np.zeros(k, np.int32),
                                      _group_request(k, t), core)
        assert np.isfinite(np.asarray(out.policy_logits)).all()
        return new_core

      core = call(0)
      real_step = server._step
      failure = {}

      def failing_step(*args):
        how = failure.pop('how', None)
        if how == 'execution':
          # key + the packed outputs; the arena between them.
          return tuple(_Poisoned() for _ in range(3 if cache else 2))
        if how == 'dispatch':
          raise ValueError('refused at dispatch (simulated)')
        if how == 'interrupt':
          raise _Interrupt()
        return real_step(*args)

      server._step = failing_step
      failure['how'] = 'execution'
      with pytest.raises(BatcherError, match='RuntimeError: computation'):
        call(1)
      assert server.stats()['chain_recoveries'] == 1
      core = call(2)  # the chain was re-anchored
      failure['how'] = 'dispatch'
      with pytest.raises(BatcherError, match='ValueError: refused'):
        call(3)
      core = call(4)
      failure['how'] = 'interrupt'
      with pytest.raises(_Interrupt):
        call(5)
      core = call(6)
      stats = server.stats()
      assert stats['chain_recoveries'] == 1
      assert stats['calls'] == stats['inline_calls'] == 7
      # A row for every executed call, failed or not: none for the
      # calls refused at dispatch (the batched path's rule too).
      assert server._cycles.cycles == 5
      assert server._sem._value == cfg.inference_pipeline_depth
    finally:
      server.close()

  def test_inline_callers_at_once(self):
    """Two groups at a floor of one, calling at once under a short
    switch interval: each gets ITS rows back, `calls` is the number of
    requests, every row of the calls' record is whole, and each caller
    staged in a buffer of its own, none in the dispatch thread's rings."""
    import sys
    k, n = 2, 40
    agent, params, _ = _mk()
    cfg = Config(**_cfg_variant(inference_min_batch=k,
                                inference_pipeline_depth=2))
    server = InferenceServer(agent, params, cfg, seed=3)
    zero = tuple(np.zeros((k, 256), np.float32) for _ in range(2))
    requests = [_group_request(k, seed=10 * i) for i in range(2)]
    # Logits follow the inputs and the carry alone: these are the
    # answers each group must get back, from one call on its own.
    want = [np.array(server.policy(np.zeros(k, np.int32), r,
                                   zero)[0].policy_logits)
            for r in requests]
    errors = []

    def caller(i):
      try:
        for _ in range(n):
          out, _ = server.policy(np.zeros(k, np.int32), requests[i],
                                 zero)
          np.testing.assert_array_equal(out.policy_logits, want[i])
      except BaseException as e:  # noqa: BLE001 — reported below
        errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
      threads = [threading.Thread(target=caller, args=(i,))
                 for i in range(2)]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=120)
      assert not any(t.is_alive() for t in threads)
      assert not errors, errors
      stats = server.stats()
    finally:
      sys.setswitchinterval(interval)
      server.close()
    calls = 2 * n + 2
    assert stats['calls'] == stats['inline_calls'] == calls
    assert stats['requests'] == k * calls
    totals = server._cycles.totals()
    assert (totals['cycles'], totals['h2d'], totals['d2h']) == (
        calls, calls, calls)
    _, rows = server._cycles.held()
    assert len(rows) == calls
    assert (np.diff(rows[:, :5], axis=1) >= 0).all()
    assert (rows[:, 5:] == 1).all()
    assert server._staging == {}

"""Actor-plane overload & preemption hardening (round 9).

Covers the three degrade seams the ISSUE's acceptance criteria gate:

- slot ADMISSION (runtime/inference.py): block/shed/grow policies,
  priority classes, the waitlist's released-slot handoff, close()
  answering parked waiters, and the unreachability of the old
  raise-on-exhaustion path;
- ingest STALENESS (runtime/remote.py): version-windowed unroll
  admission with per-connection counters and the benign 'stale'
  client contract;
- preemption DRAIN/RESUME (driver.py): the deterministic
  `preempt_signal` fault drains mid-run into a verified checkpoint +
  resume manifest, and the resumed run's step sequence equals the
  uninterrupted run's (the parity gate).
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import init_params
from scalable_agent_tpu.runtime import faults as faults_lib
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.runtime.inference import (
    InferenceClosed, InferenceServer, PRIORITY_EVAL, PRIORITY_LIVE,
    SlotUnavailable)

H, W, A = 24, 32, 3


def _mk_server(**overrides):
  cfg_kwargs = dict(
      inference_state_cache=True,
      inference_min_batch=1,
      inference_timeout_ms=5,
      height=H, width=W,
      torso='shallow',
      use_instruction=False)
  cfg_kwargs.update(overrides)
  cfg = Config(**cfg_kwargs)
  agent = driver.build_agent(cfg, A)
  params = init_params(agent, jax.random.PRNGKey(0),
                       {'frame': (H, W, 3), 'instr_len': 16})
  return InferenceServer(agent, params, cfg, seed=3)


def _read_jsonl(path):
  if not os.path.exists(path):
    return []
  with open(path) as f:
    return [json.loads(line) for line in f if line.strip()]


# --- admission control -------------------------------------------------


def test_admission_denied_slots_quarantine_deterministically():
  """Round-14 regression pin for the overload-storm quarantine flake:
  slots whose every (re)spawn is denied by inference-slot admission
  must quarantine after EXACTLY quarantine_after+1 consecutive
  denials, driven purely by check_health calls — never by wall-clock
  luck. The storm used to assert `slots_quarantined == fleet - slots`
  against a fixed SIGTERM timer and lost the race to the full-jitter
  respawn backoff 7/12 seeds; the harness now gates its SIGTERM on
  the quarantine incident ledger, and THIS test pins the ladder's
  determinism the gate relies on (zero-jitter backoff: the count is a
  function of health checks alone)."""
  import random
  from scalable_agent_tpu.runtime.fleet import ActorFleet
  from scalable_agent_tpu.runtime.remote import Backoff

  class _ZeroJitter(random.Random):
    def uniform(self, a, b):
      return 0.0

  quarantine_after = 2
  spawn_attempts = {0: 0, 1: 0}

  def make_actor(i):
    spawn_attempts[i] += 1
    raise SlotUnavailable(f'arena exhausted (slot {i})')

  buffer = ring_buffer.TrajectoryBuffer(4)
  fleet = ActorFleet(make_actor, buffer, num_actors=2,
                     quarantine_after=quarantine_after)
  for slot in fleet._slots:
    slot.backoff = Backoff(base=1e-6, cap=1e-6, rng=_ZeroJitter())
  fleet.start()  # start-time denials degrade (streak 1), never raise
  assert fleet.stats()['slots_quarantined'] == 0
  checks = 0
  while fleet.stats()['slots_quarantined'] < 2:
    fleet.check_health()
    checks += 1
    assert checks <= 2 * (quarantine_after + 2), (
        'quarantine did not complete within a deterministic number '
        f'of health checks (attempts: {spawn_attempts})')
  # Exactly fleet-minus-capacity slots quarantined — the storm's SLO.
  assert fleet.stats()['slots_quarantined'] == 2
  # The ladder's arithmetic: the start denial is streak 1; each
  # respawn bumps the streak and spawns only while streak <=
  # quarantine_after; the attempt that pushes the streak past the
  # budget quits WITHOUT spawning. Total spawn attempts per slot ==
  # quarantine_after, exactly.
  assert spawn_attempts == {0: quarantine_after,
                            1: quarantine_after}
  # Quarantined slots are terminal: no further spawns ever.
  for _ in range(3):
    assert fleet.check_health() == []
  assert spawn_attempts == {0: quarantine_after,
                            1: quarantine_after}
  fleet.stop(timeout=2)
  buffer.close()


def test_block_waitlist_hands_over_released_slot():
  """block policy: an exhausted acquire PARKS; releasing a slot hands
  it to the waiter directly, and the stale handle cannot touch its
  reused slot (the released-slot-handle reuse gate)."""
  server = _mk_server(inference_state_slots=1,
                      inference_admission_timeout_secs=10.0)
  try:
    h1 = server.initial_core_state()
    got = {}

    def waiter():
      got['handle'] = server.initial_core_state()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while (server.stats()['waitlist_depth'] == 0
           and time.monotonic() < deadline):
      time.sleep(0.01)
    assert server.stats()['waitlist_depth'] == 1
    h1.release()
    t.join(timeout=5)
    assert not t.is_alive()
    h2 = got['handle']
    assert h2.slot == h1.slot  # the very slot, handed over
    # The old handle is dead: no read, no write, no policy use.
    with pytest.raises(RuntimeError, match='released'):
      h1.snapshot()
    with pytest.raises(RuntimeError, match='released'):
      h1.write((np.zeros((1, 256), np.float32),) * 2)
    # The new owner's slot is freshly zeroed.
    snap = h2.snapshot()
    assert np.abs(np.asarray(snap[0])).max() == 0
    assert server.stats()['admission_waits'] == 1
    h2.release()
  finally:
    server.close()


def test_priority_classes_order_the_waitlist():
  """A released slot goes to the LIVE-class waiter even when an
  EVAL-class waiter has been parked longer — eval/respawn churn can
  not starve live actors."""
  server = _mk_server(inference_state_slots=1,
                      inference_admission_timeout_secs=10.0)
  try:
    h1 = server.initial_core_state()
    order = []
    parked = []

    def waiter(name, priority):
      parked.append(name)
      h = server.initial_core_state(priority=priority)
      order.append(name)
      h.release()

    t_eval = threading.Thread(target=waiter,
                              args=('eval', PRIORITY_EVAL), daemon=True)
    t_eval.start()
    deadline = time.monotonic() + 5
    while (server.stats()['waitlist_depth'] < 1
           and time.monotonic() < deadline):
      time.sleep(0.01)
    t_live = threading.Thread(target=waiter,
                              args=('live', PRIORITY_LIVE), daemon=True)
    t_live.start()
    while (server.stats()['waitlist_depth'] < 2
           and time.monotonic() < deadline):
      time.sleep(0.01)
    assert server.stats()['waitlist_depth'] == 2
    h1.release()
    t_live.join(timeout=5)
    t_eval.join(timeout=5)
    assert order == ['live', 'eval']
  finally:
    server.close()


def test_shed_policy_counts_deadline_rejections():
  server = _mk_server(inference_state_slots=1,
                      inference_admission='shed',
                      inference_admission_timeout_secs=0.1)
  try:
    h1 = server.initial_core_state()
    with pytest.raises(SlotUnavailable, match='shed'):
      server.initial_core_state()
    stats = server.stats()
    assert stats['sheds'] == 1
    assert stats['admission_timeouts'] == 0
    assert stats['admission'] == 'shed'
    h1.release()
  finally:
    server.close()


def test_grow_policy_doubles_arena_and_preserves_carries():
  server = _mk_server(inference_state_slots=2,
                      inference_admission='grow')
  try:
    handles = [server.initial_core_state() for _ in range(2)]
    marker = (np.full((1, 256), 3.5, np.float32),
              np.full((1, 256), -1.25, np.float32))
    handles[0].write(marker)
    # Third acquire exhausts the 2-slot arena: grow, never park.
    handles.append(server.initial_core_state())
    stats = server.stats()
    assert stats['arena_grows'] == 1
    assert stats['admission_waits'] == 0
    # Existing carries survived the growth copy.
    snap = handles[0].snapshot()
    np.testing.assert_array_equal(np.asarray(snap[0]), marker[0])
    np.testing.assert_array_equal(np.asarray(snap[1]), marker[1])
    # The grown slot is zeroed and usable.
    snap = handles[2].snapshot()
    assert np.abs(np.asarray(snap[0])).max() == 0
    for h in handles:
      h.release()
    assert server.slots_free() == 4  # 2 doubled
  finally:
    server.close()


def test_close_answers_parked_waiters():
  """Satellite: close() must answer the waitlist with a clean error,
  never leave callers blocked forever."""
  server = _mk_server(inference_state_slots=1,
                      inference_admission_timeout_secs=60.0)
  h1 = server.initial_core_state()
  result = {}

  def waiter():
    try:
      server.initial_core_state()
      result['outcome'] = 'acquired'
    except InferenceClosed:
      result['outcome'] = 'closed'
    except Exception as e:
      result['outcome'] = f'unexpected: {e!r}'

  t = threading.Thread(target=waiter, daemon=True)
  t.start()
  deadline = time.monotonic() + 5
  while (server.stats()['waitlist_depth'] == 0
         and time.monotonic() < deadline):
    time.sleep(0.01)
  server.close()
  t.join(timeout=5)
  assert not t.is_alive()
  assert result['outcome'] == 'closed'
  assert server.stats()['unjoined_threads'] == 0
  del h1


def test_slot_exhaustion_fault_forces_contended_path():
  """The 'slot_exhaustion' site detours an acquire through the
  waitlist even with slots free; the backoff re-check admits it
  without waiting out the whole deadline."""
  server = _mk_server(inference_state_slots=4,
                      inference_admission_timeout_secs=10.0)
  plan = faults_lib.FaultPlan(
      [faults_lib.Fault('slot_exhaustion', 0, 'force')])
  faults_lib.install(plan)
  try:
    t0 = time.monotonic()
    h = server.initial_core_state()
    assert time.monotonic() - t0 < 5.0  # re-check, not deadline
    assert server.stats()['admission_waits'] == 1
    assert plan.stats()['slot_exhaustion']['fired'] == 1
    h.release()
  finally:
    faults_lib.clear()
    server.close()


# --- ingest staleness --------------------------------------------------


def test_ingest_staleness_window_rejects_and_recovers():
  from scalable_agent_tpu.runtime import remote
  buf = ring_buffer.TrajectoryBuffer(8)
  params = {'w': np.zeros((2, 2), np.float32)}
  server = remote.TrajectoryIngestServer(buf, params,
                                         max_unroll_staleness=1)
  client = None
  try:
    for _ in range(3):  # versions 2, 3, 4
      server.publish_params(params)
    client = remote.RemoteActorClient(f'127.0.0.1:{server.port}')
    unroll = {'x': np.zeros((3,), np.float32)}
    # Version 1 is 3 behind version 4: refused, benign, counted —
    # and the returned version is the CURRENT one (the refetch cue).
    got = client.send_unroll(unroll, params_version=1)
    assert got == 4
    assert client.stale_rejections == 1
    assert len(buf) == 0
    stats = server.stats()
    assert stats['stale_rejected'] == 1
    assert sum(stats['per_conn_stale_rejected'].values()) == 1
    # A fresh-enough version (and a version-less legacy frame) land.
    assert client.send_unroll(unroll, params_version=4) == 4
    assert client.send_unroll(unroll) == 4
    assert len(buf) == 2
    assert server.stats()['unrolls'] == 2
  finally:
    if client is not None:
      client.close()
    server.close()
    buf.close()


def test_buffer_occupancy_stats_track_backpressure():
  buf = ring_buffer.TrajectoryBuffer(2)
  buf.put('a')
  buf.put('b')
  blocked = threading.Event()

  def producer():
    blocked.set()
    buf.put('c', timeout=10)

  t = threading.Thread(target=producer, daemon=True)
  t.start()
  blocked.wait(timeout=5)
  time.sleep(0.1)  # let the put actually park on the full buffer
  buf.get()
  t.join(timeout=5)
  stats = buf.stats()
  assert stats['capacity'] == 2
  assert stats['high_water'] == 2
  assert stats['occupancy'] == 2
  assert stats['put_waits'] == 1
  assert stats['put_wait_secs'] > 0
  buf.close()


# --- preemption drain / resume ----------------------------------------


def _config(tmp_path, **kw):
  base = dict(
      logdir=str(tmp_path),
      env_backend='bandit',
      num_actors=2,
      batch_size=2,
      unroll_length=5,
      num_action_repeats=1,
      episode_length=4,
      height=24, width=32,
      torso='shallow',
      use_py_process=False,
      use_instruction=False,
      total_environment_frames=10 ** 6,
      inference_timeout_ms=5,
      checkpoint_secs=0,
      summary_secs=0,
      seed=3)
  base.update(kw)
  return Config(**base)


def _frame_steps(logdir, filename='summaries.jsonl'):
  """The summary step sequence of the run(s) in `logdir` — the
  'identical step sequence' the drain/resume parity gate compares."""
  return [e['step'] for e in _read_jsonl(os.path.join(logdir, filename))
          if e.get('tag') == 'env_frames_per_sec']


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_drain_resume_parity_vs_uninterrupted(tmp_path):
  """THE acceptance gate: same seeds, same frame budget — a run
  preempted mid-way (deterministic preempt_signal fault), drained and
  resumed must produce the identical learner step sequence as the
  uninterrupted run, with no frames lost or double-counted."""
  total_steps = 6
  budget = total_steps * 2 * 5  # batch 2 × unroll 5 × repeats 1

  plain_dir = tmp_path / 'plain'
  cfg_a = _config(plain_dir, total_environment_frames=budget)
  run_a = driver.train(cfg_a, stall_timeout_secs=60)
  assert int(run_a.state.update_steps) == total_steps

  drained_dir = tmp_path / 'drained'
  cfg_b = _config(drained_dir, total_environment_frames=budget)
  plan = faults_lib.FaultPlan(
      [faults_lib.Fault('preempt_signal', 3, 'drain')])
  faults_lib.install(plan)
  try:
    run_b1 = driver.train(cfg_b, stall_timeout_secs=60)
  finally:
    faults_lib.clear()
  steps_b1 = int(run_b1.state.update_steps)
  assert 3 <= steps_b1 <= total_steps  # drained at/after the fault

  manifest = driver.read_resume_manifest(str(drained_dir))
  assert manifest is not None
  assert manifest['update_steps'] == steps_b1
  assert manifest['frames'] == steps_b1 * cfg_b.frames_per_step
  assert manifest['checkpoint_verified'] is True
  assert manifest['checkpoint_step'] == steps_b1
  assert manifest['drain_latency_secs'] >= 0
  assert manifest['drain_source'] == 'fault'

  # Resume: picks up at the manifest step, consumes the manifest, and
  # finishes the identical frame budget.
  run_b2 = driver.train(cfg_b, stall_timeout_secs=60)
  assert int(run_b2.state.update_steps) == total_steps
  assert driver.read_resume_manifest(str(drained_dir)) is None
  assert os.path.exists(
      os.path.join(str(drained_dir), 'resume_manifest.json.consumed'))

  # Parity: the concatenated (drain + resume) step sequence IS the
  # uninterrupted sequence.
  assert _frame_steps(str(plain_dir)) == list(range(1, total_steps + 1))
  assert _frame_steps(str(drained_dir)) == _frame_steps(str(plain_dir))

  # Drain narration landed in the incident stream with its latency.
  incidents = _read_jsonl(os.path.join(str(drained_dir),
                                       'incidents.jsonl'))
  kinds = [e['kind'] for e in incidents]
  assert 'preempt_drain_start' in kinds
  complete = [e for e in incidents
              if e['kind'] == 'preempt_drain_complete']
  assert complete and complete[0]['drain_latency_secs'] >= 0


def test_drain_event_triggers_graceful_drain(tmp_path):
  """The SIGTERM seam: a set drain_event ends the run through the
  drain path (manifest + verified checkpoint), not an exception."""
  cfg = _config(tmp_path)
  event = threading.Event()
  event.set()  # preempted before the first step: still clean
  run = driver.train(cfg, stall_timeout_secs=60, drain_event=event)
  assert int(run.state.update_steps) >= 0
  manifest = driver.read_resume_manifest(str(tmp_path))
  assert manifest is not None
  assert manifest['drain_source'] == 'signal'
  assert manifest['update_steps'] == int(run.state.update_steps)


def test_overload_counters_reach_summaries(tmp_path):
  """Satellite: every new counter rides driver.train's summary
  stream — sheds, admission waits, quarantined slots, staleness
  rejections, buffer occupancy."""
  # Ingest on a free port so the remote_* tags (incl. the staleness
  # counter) are exercised too.
  import socket
  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  cfg = _config(tmp_path, remote_actor_port=port,
                inference_state_cache=True,
                max_unroll_staleness=2)
  driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  events = _read_jsonl(os.path.join(str(tmp_path), 'summaries.jsonl'))
  tags = {e['tag'] for e in events if 'tag' in e}
  for tag in ('inference_sheds', 'inference_admission_waits',
              'inference_arena_grows', 'slots_quarantined',
              'buffer_high_water', 'buffer_put_waits',
              'remote_stale_rejected'):
    assert tag in tags, f'summary tag {tag!r} missing'


def test_set_admission_flips_live_policy_and_counters():
  """Round 15: the controller's admission actuator — a live
  block->shed flip changes how the NEXT deadline rejection is
  counted, and ->grow lets the next exhausted acquire grow the arena
  instead of parking."""
  server = _mk_server(inference_state_slots=2,
                      inference_admission='block',
                      inference_admission_timeout_secs=0.2)
  try:
    assert server.admission == 'block'
    held = [server.initial_core_state() for _ in range(2)]
    with pytest.raises(SlotUnavailable):
      server.initial_core_state()
    assert server.stats()['admission_timeouts'] == 1
    assert server.stats()['sheds'] == 0
    # Flip to shed: the same exhaustion now counts as a shed.
    assert server.set_admission('shed') == 'block'
    assert server.admission == 'shed'
    with pytest.raises(SlotUnavailable):
      server.initial_core_state()
    assert server.stats()['sheds'] == 1
    # Flip to grow: the arena doubles instead of rejecting.
    server.set_admission('grow')
    handle = server.initial_core_state()
    assert server.stats()['arena_grows'] == 1
    handle.release()
    with pytest.raises(ValueError):
      server.set_admission('banana')
    for h in held:
      h.release()
  finally:
    server.close()

"""ActorFleet failure detection and respawn (SURVEY §5.3 greenfield —
the reference has no equivalent: a dead actor silently stops feeding)."""

import threading
import time

import os

import numpy as np
import pytest

from scalable_agent_tpu.envs.fake import FakeEnv
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.runtime.actor import Actor
from scalable_agent_tpu.runtime.fleet import ActorFleet

H, W, A = 8, 8, 3


class CrashingEnv(FakeEnv):
  """Env that dies after `crash_after` steps (first life only)."""
  crashes = 0

  def __init__(self, crash_after=3, **kw):
    super().__init__(**kw)
    self._steps = 0
    self._crash_after = crash_after

  def step(self, action):
    self._steps += 1
    if self._crash_after and self._steps >= self._crash_after:
      type(self).crashes += 1
      raise RuntimeError('env crashed')
    return super().step(action)


def _dummy_policy(prev_action, env_output, core_state):
  from scalable_agent_tpu.structs import AgentOutput
  out = AgentOutput(action=np.int32(0),
                    policy_logits=np.zeros(A, np.float32),
                    baseline=np.float32(0.0))
  return out, core_state


def _make_actor_factory(env_factory, unroll_length=4):
  def make_actor(i):
    env = env_factory(i)
    actor = Actor(env, _dummy_policy, (np.zeros((1, 4), np.float32),) * 2,
                  unroll_length=unroll_length)
    return env, None, actor
  return make_actor


def test_fleet_produces_and_stops():
  buffer = ring_buffer.TrajectoryBuffer(4)
  fleet = ActorFleet(
      _make_actor_factory(lambda i: FakeEnv(height=H, width=W,
                                            num_actions=A, seed=i)),
      buffer, num_actors=2)
  fleet.start()
  got = [buffer.get(timeout=10) for _ in range(3)]
  assert len(got) == 3
  fleet.stop()
  stats = fleet.stats()
  assert stats['unrolls'] >= 3
  # Envs in this process: no env process was reached either way.
  assert stats['block_steps'] == 0 and stats['pipe_calls'] == 0
  assert not fleet.errors()


def test_fleet_detects_and_respawns_crashed_actor():
  CrashingEnv.crashes = 0
  buffer = ring_buffer.TrajectoryBuffer(8)

  def env_factory(i):
    # First spawn crashes; respawned envs run clean.
    crash_after = 3 if CrashingEnv.crashes < 2 else 0
    return CrashingEnv(crash_after=crash_after, height=H, width=W,
                       num_actions=A, seed=i)

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=2)
  fleet.start()
  deadline = time.monotonic() + 15
  respawned = []
  while time.monotonic() < deadline and not respawned:
    respawned = fleet.check_health()
    time.sleep(0.05)
  assert respawned, 'crash never detected'
  # After respawn the fleet produces again.
  unroll = buffer.get(timeout=10)
  assert unroll.env_outputs.reward.shape[0] == 5
  fleet.stop()
  assert fleet.stats()['respawns'] >= 1


def test_stats_alive_vs_healthy_quorum():
  """Round 7 satellite: a wedged actor's thread is `alive` but must
  NOT count as `healthy` — the quorum fraction is the honest signal
  the driver logs."""
  buffer = ring_buffer.TrajectoryBuffer(8)
  stall = threading.Event()

  class StallingEnv(FakeEnv):
    def __init__(self, stall_me=False, **kw):
      super().__init__(**kw)
      self._stall_me = stall_me

    def step(self, action):
      if self._stall_me and stall.is_set():
        time.sleep(30)
      return super().step(action)

  def env_factory(i):
    return StallingEnv(stall_me=(i == 0), height=H, width=W,
                       num_actions=A, seed=i)

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=2)
  fleet.start()
  # Both healthy first: drain a couple of unrolls so heartbeats beat.
  for _ in range(2):
    buffer.get(timeout=10)
  stats = fleet.stats(healthy_horizon_secs=60.0)
  assert stats['alive'] == 2
  assert stats['healthy'] == 2
  assert stats['healthy_fraction'] == 1.0

  stall.set()
  deadline = time.monotonic() + 10
  while time.monotonic() < deadline:
    # Keep the healthy actor's heartbeat fresh by draining its output.
    try:
      buffer.get(timeout=0.2)
    except TimeoutError:
      pass
    stats = fleet.stats(healthy_horizon_secs=0.5)
    if stats['healthy'] == 1:
      break
  assert stats['alive'] == 2          # the wedged thread still runs
  assert stats['healthy'] == 1        # ...but it is not healthy
  assert stats['healthy_fraction'] == 0.5
  stall.clear()
  fleet.stop(timeout=2)


def test_fleet_detects_stalled_actor():
  buffer = ring_buffer.TrajectoryBuffer(2)

  stall = threading.Event()

  class StallingEnv(FakeEnv):
    def step(self, action):
      if stall.is_set():
        time.sleep(30)
      return super().step(action)

  made = []

  def env_factory(i):
    env = StallingEnv(height=H, width=W, num_actions=A, seed=i)
    made.append(env)
    return env

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=1)
  fleet.start()
  buffer.get(timeout=10)  # healthy first unroll
  stall.set()
  time.sleep(0.3)
  bad = fleet.check_health(stall_timeout_secs=0.2, respawn=False)
  assert bad == [0]
  stall.clear()
  fleet.stop(timeout=2)


def test_respawn_failure_contained_and_retried():
  """A respawn whose make_actor raises (env construction, exhausted
  inference state arena) must NOT propagate out of check_health into
  the learner loop: the error lands on the slot and the next health
  check retries — here successfully."""
  CrashingEnv.crashes = 0
  buffer = ring_buffer.TrajectoryBuffer(8)
  spawn_fail = {'armed': False, 'raised': 0}

  def env_factory(i):
    if spawn_fail['armed']:
      spawn_fail['armed'] = False
      spawn_fail['raised'] += 1
      raise RuntimeError('state arena exhausted (simulated)')
    crash_after = 3 if CrashingEnv.crashes < 1 else 0
    return CrashingEnv(crash_after=crash_after, height=H, width=W,
                       num_actions=A, seed=i)

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=1)
  fleet.start()  # start-time spawn succeeds
  # Wait for the first crash to land on the slot.
  deadline = time.monotonic() + 15
  while time.monotonic() < deadline and not fleet.errors():
    time.sleep(0.05)
  assert fleet.errors()
  # The respawn attempt itself fails — contained, not raised.
  spawn_fail['armed'] = True
  bad = fleet.check_health()
  assert bad == [0]
  assert spawn_fail['raised'] == 1
  assert fleet.errors()  # failure recorded on the slot
  # A later check retries (respawns are backoff-paced now — round 9)
  # and recovers: unrolls flow again.
  deadline = time.monotonic() + 15
  got = None
  while got is None and time.monotonic() < deadline:
    fleet.check_health()
    try:
      got = buffer.get(timeout=0.5)
    except TimeoutError:
      pass
  assert got is not None
  fleet.stop(timeout=5)


def test_respawn_backoff_then_quarantine():
  """Round 9 satellite: a persistently failing env is respawned on a
  jittered backoff (no hot loop) and QUARANTINED after
  `quarantine_after` consecutive respawns without a completed unroll —
  surfaced as `slots_quarantined`, with the rest of the fleet
  untouched."""
  buffer = ring_buffer.TrajectoryBuffer(8)

  class AlwaysCrashingEnv(FakeEnv):
    def step(self, action):
      raise RuntimeError('permanently broken env')

  def env_factory(i):
    if i == 0:
      return AlwaysCrashingEnv(height=H, width=W, num_actions=A, seed=i)
    return FakeEnv(height=H, width=W, num_actions=A, seed=i)

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=2, quarantine_after=2)
  # Shrink the backoff so the give-up ladder runs inside test time.
  for slot in fleet._slots:
    slot.backoff._base = 0.01
    slot.backoff._cap = 0.05
  fleet.start()
  deadline = time.monotonic() + 20
  while time.monotonic() < deadline:
    fleet.check_health()
    if fleet.stats()['slots_quarantined'] == 1:
      break
    time.sleep(0.02)
  stats = fleet.stats()
  assert stats['slots_quarantined'] == 1
  # Quarantine means give-up-after-N, not hot-loop-forever.
  assert fleet._slots[0].respawns == 3  # quarantine_after=2 -> 3rd quits
  assert fleet._slots[0].quarantined
  # The healthy actor keeps feeding.
  assert buffer.get(timeout=10) is not None
  # A quarantined slot is never acted on again.
  assert fleet.check_health() == []
  fleet.stop(timeout=2)


def test_stop_reports_unjoined_and_buffer_refuses_writes():
  """Round 9 satellite: stop() names actors that missed the join
  deadline instead of dropping them, and the buffer accepts NO writes
  after stop() returns (the '_respawn stale unroll' regression)."""
  buffer = ring_buffer.TrajectoryBuffer(8)
  stall = threading.Event()

  class StallingEnv(FakeEnv):
    def __init__(self, stall_me=False, **kw):
      super().__init__(**kw)
      self._stall_me = stall_me

    def step(self, action):
      if self._stall_me and stall.is_set():
        time.sleep(30)
      return super().step(action)

  def env_factory(i):
    return StallingEnv(stall_me=(i == 0), height=H, width=W,
                       num_actions=A, seed=i)

  fleet = ActorFleet(_make_actor_factory(env_factory), buffer,
                     num_actors=2)
  fleet.start()
  buffer.get(timeout=10)  # healthy first
  stall.set()
  # Actor 0 wedges in its next step. Drained meanwhile: an actor
  # parked on a full buffer is not in a step (and unrolls are quick
  # enough to fill these 8 slots before this thread gets here).
  deadline = time.monotonic() + 0.5
  while time.monotonic() < deadline:
    try:
      buffer.get(timeout=0.05)
    except TimeoutError:
      pass
  report = fleet.stop(timeout=1.0)
  assert report['unjoined_actors'] == [0]
  # After stop() returns, a straggler's put cannot land a stale
  # unroll: the buffer is closed.
  import pytest
  with pytest.raises(ring_buffer.Closed):
    buffer.put('stale-unroll')
  stall.clear()


def test_stats_wedged_counts_silent_alive_threads():
  """Round 11: an alive thread with a stale heartbeat and NO recorded
  error is 'wedged' — the fleet-side zero-deadlocked-threads ledger
  (blocked in env.step / parked on backpressure)."""
  import time as time_lib
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _make_actor_factory(lambda i: FakeEnv(height=H, width=W,
                                            num_actions=A, seed=i)),
      buffer, 2)
  try:
    fleet.start()
    deadline = time_lib.time() + 10
    while fleet.stats()['unrolls'] < 2 and time_lib.time() < deadline:
      time_lib.sleep(0.05)
    stats = fleet.stats(healthy_horizon_secs=60.0)
    assert stats['wedged'] == 0
    # With a zero horizon every producing-but-not-this-instant thread
    # reads as wedged — the stat is horizon-relative by design.
    stats_tight = fleet.stats(healthy_horizon_secs=0.0)
    assert stats_tight['wedged'] == stats_tight['alive']
  finally:
    fleet.stop()


# --------------------------------------------------------------------
# Elastic fleet size + quarantine rehabilitation (round 15): the
# controller's fleet_size actuator and the probation ladder.
# --------------------------------------------------------------------


def _wait(predicate, timeout=15.0, interval=0.02):
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if predicate():
      return True
    time.sleep(interval)
  return predicate()


def _pumped(fleet, cond, buffer=None):
  """Predicate that drives the respawn machinery (check_health runs
  on the learner thread in production), drains `buffer` like a
  learner would (a full buffer blocks every producer's put), and then
  evaluates `cond`."""
  def p():
    fleet.check_health()
    if buffer is not None:
      try:
        while True:
          buffer.get(timeout=0)
      except (TimeoutError, ring_buffer.Closed):
        pass
    return cond()
  return p


def test_set_target_size_parks_unparks_and_quorum_denominator():
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _make_actor_factory(lambda i: FakeEnv(height=H, width=W,
                                            num_actions=A, seed=i)),
      buffer, num_actors=4)
  try:
    fleet.start()
    assert _wait(lambda: fleet.stats()['healthy'] == 4)
    # Shrink: the two highest-index slots park; each actor exits
    # cleanly after its current unroll, and the quorum DENOMINATOR
    # shrinks with the fleet — a deliberate shed must not read as a
    # dying plane.
    report = fleet.set_target_size(2)
    assert sorted(report['parked']) == [2, 3]
    assert fleet.target_size() == 2
    assert _wait(lambda: fleet.stats()['healthy'] == 2)
    stats = fleet.stats()
    assert stats['parked'] == 2
    assert stats['healthy_fraction'] == 1.0
    # Parked slots are skipped by health checks (no respawn).
    fleet.check_health()
    assert fleet.stats()['parked'] == 2
    # Grow: unpark first — the slots respawn and produce again.
    report = fleet.set_target_size(4)
    assert sorted(report['unparked']) == [2, 3]
    assert report['rehabilitated'] == []
    assert _wait(_pumped(fleet,
                         lambda: fleet.stats()['healthy'] == 4))
  finally:
    fleet.stop()


def test_rehabilitation_probation_success_counts():
  """A quarantined slot reclaimed through probation: cool-down,
  probe spawn, ONE completed unroll clears it (slots_rehabilitated)."""
  buffer = ring_buffer.TrajectoryBuffer(64)
  fails = {1: 1}  # slot 1: the first (pre-quarantine) spawn raises

  def make_actor(i):
    if fails.get(i, 0) > 0:
      fails[i] -= 1
      raise RuntimeError(f'flaky env on slot {i}')
    env = FakeEnv(height=H, width=W, num_actions=A, seed=i)
    actor = Actor(env, _dummy_policy,
                  (np.zeros((1, 4), np.float32),) * 2,
                  unroll_length=4)
    return env, None, actor

  fleet = ActorFleet(make_actor, buffer, num_actors=2,
                     quarantine_after=1, probation_secs=0.05)
  # Zero-jitter backoff so the quarantine ladder is check-driven.
  for slot in fleet._slots:
    slot.backoff._rng = type('R', (), {'uniform':
                                       staticmethod(lambda a, b: 0.0)})
  try:
    # Slot 1's start-time spawn raises a non-admission error: start()
    # would raise it — spawn slot 0 only, then drive slot 1 through
    # the respawn ladder (thread-None counts as dead since round 15).
    fleet._slots[1].error = RuntimeError('seed: never spawned')
    fleet._spawn(fleet._slots[0])
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['slots_quarantined'] == 1))
    assert fleet.target_size() == 1
    # Before the cool-down elapses nothing is reclaimable.
    fleet._slots[1].quarantined_at = time.monotonic()
    assert fleet.set_target_size(2)['rehabilitated'] == []
    time.sleep(0.08)
    report = fleet.set_target_size(2)
    assert report['rehabilitated'] == [1]
    assert fleet.stats()['rehabilitations'] == 1
    # The quarantine-era error is a closed incident: it must not
    # surface as live through errors() mid-probation (review fix).
    assert fleet.errors() == []
    # The flake budget is spent: the probe spawn succeeds, the first
    # unroll completes, and the probation clears.
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['slots_rehabilitated'] == 1,
        buffer=buffer))
    stats = fleet.stats()
    assert stats['slots_quarantined'] == 0
    assert stats['slots_rehabilitated'] == 1
  finally:
    fleet.stop()


def test_probation_requarantines_on_repeat_failure():
  buffer = ring_buffer.TrajectoryBuffer(64)
  fails = {0: 100}  # slot 0 never spawns successfully

  def make_actor(i):
    if fails.get(i, 0) > 0:
      fails[i] -= 1
      raise RuntimeError(f'permanently broken env on slot {i}')
    env = FakeEnv(height=H, width=W, num_actions=A, seed=i)
    actor = Actor(env, _dummy_policy,
                  (np.zeros((1, 4), np.float32),) * 2,
                  unroll_length=4)
    return env, None, actor

  fleet = ActorFleet(make_actor, buffer, num_actors=1,
                     quarantine_after=1, probation_secs=0.0)
  for slot in fleet._slots:
    slot.backoff._rng = type('R', (), {'uniform':
                                       staticmethod(lambda a, b: 0.0)})
  try:
    fleet._slots[0].error = RuntimeError('seed: never spawned')
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['slots_quarantined'] == 1))
    respawns_before = fleet.stats()['respawns']
    assert fleet.set_target_size(1)['rehabilitated'] == [0]
    # The probe spawn fails -> the SECOND respawn re-quarantines
    # immediately (probation is one probe, not a fresh ladder).
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['slots_quarantined'] == 1))
    stats = fleet.stats()
    assert stats['slots_quarantined'] == 1
    assert stats['slots_rehabilitated'] == 0
    # The probation cost at most 2 respawn attempts (probe + give-up).
    assert stats['respawns'] - respawns_before <= 2
  finally:
    fleet.stop()


def test_parked_slot_errors_do_not_surface():
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _make_actor_factory(lambda i: FakeEnv(height=H, width=W,
                                            num_actions=A, seed=i)),
      buffer, num_actors=2)
  try:
    fleet.start()
    assert _wait(lambda: fleet.stats()['healthy'] == 2)
    fleet.set_target_size(1)
    # A stale error on the parked slot is a closed incident, not the
    # cause of some later stall.
    fleet._slots[1].error = RuntimeError('stale, pre-park')
    assert fleet.errors() == []
  finally:
    fleet.stop()


# --- Groups: one thread steps k process-hosted envs in lockstep (PR 26)


class FlagCrashEnv(FakeEnv):
  """Process-hosted crash fixture: raises at its third step while its
  flag file exists. `once` removes the flag first, so only the first
  life crashes (a child cannot count its lives in a class variable)."""

  def __init__(self, crash_flag=None, once=True, **kw):
    super().__init__(**kw)
    self._crash_flag, self._once, self._steps = crash_flag, once, 0

  def step(self, action):
    import os
    self._steps += 1
    if (self._steps == 3 and self._crash_flag and
        os.path.exists(self._crash_flag)):
      if self._once:
        os.remove(self._crash_flag)
      if self._crash_flag.endswith('hang'):
        time.sleep(60)  # a wedged simulator
      raise RuntimeError('hosted env crashed')
    return super().step(action)


def _group_policy(prev_action, env_output, core_state):
  """The dummy policy in both forms of the Actor contract."""
  from scalable_agent_tpu.structs import AgentOutput
  lead = np.shape(prev_action)
  return AgentOutput(action=np.zeros(lead, np.int32),
                     policy_logits=np.zeros(lead + (A,), np.float32),
                     baseline=np.zeros(lead, np.float32)), core_state


def _hosted_factory(processes, env_kwargs, policy=_group_policy,
                    state_fn=None, env_class=FlagCrashEnv,
                    step_block=True):
  """make_actor for process-hosted envs; every PyProcess it starts is
  appended to `processes`. `step_block=False` holds their steps to
  the pickled pipe."""
  from scalable_agent_tpu.runtime import py_process

  def make_actor(i):
    process = py_process.PyProcess(env_class, env_kwargs(i),
                                   step_block=step_block).start()
    processes.append(process)
    env = py_process.ProxyEnv(process)
    state = (state_fn() if state_fn else
             (np.zeros((1, 4), np.float32),) * 2)
    actor = Actor(env, policy, state, unroll_length=4, level_name_id=i)
    return env, process, actor
  return make_actor


def _none_running(processes):
  return not any(p._process.is_alive() for p in processes)


def _no_segment_left():
  from scalable_agent_tpu.runtime import py_process
  return not [name for name in os.listdir(py_process._BLOCK_DIR)
              if name.startswith(f'step_block_{os.getpid()}_')]


def _assert_stepped_by(stats, step_block, envs, spawns):
  """The fleet's counters of how its env processes were reached:
  every `step` through the block, or none; an `initial` (on the
  block, an attach too) a spawned process either way, and a `close`
  where one could still be sent."""
  if step_block:
    assert stats['block_steps'] >= 4 * envs  # an unroll each, at least
    assert 2 * spawns <= stats['pipe_calls'] <= 3 * spawns
  else:
    assert stats['block_steps'] == 0
    assert stats['pipe_calls'] >= spawns + 4 * envs


_BOTH_PATHS = pytest.mark.parametrize(
    'step_block', [True, False], ids=['block', 'pipe'])


@_BOTH_PATHS
def test_group_member_failure_respawns_the_group(monkeypatch, tmp_path,
                                                 step_block):
  """One env of a group raises: the group's thread ends through
  run_actor_loop's one failure path, the error lands on that env's
  slot alone, the slots respawn together and share a thread again
  (on a block of their own, mapped anew: the counters go on across
  the respawn), the buffer stays open, and no child and no shared
  segment outlives the fleet."""
  flag = tmp_path / 'crash'
  flag.write_text('armed')
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i,
          crash_flag=str(flag) if i == 1 else None),
          step_block=step_block),
      buffer, num_actors=3)
  fleet.start()
  try:
    stats = fleet.stats()
    assert stats['actor_threads'] == 1
    assert stats['envs_per_thread'] == 3.0
    first_thread = fleet._slots[0].thread
    assert first_thread.name.startswith('actor-')
    assert _wait(lambda: any(s.error or s.collateral
                             for s in fleet._slots))
    with fleet._lock:
      assert [s.error is not None for s in fleet._slots] == [
          False, True, False]
      assert 'hosted env crashed' in str(fleet._slots[1].error)
      assert [s.collateral for s in fleet._slots] == [True, False, True]
    assert sorted(fleet.check_health()) == [0, 1, 2]
    # Charged to the slot whose env failed, not to its mates.
    assert [s.respawn_streak for s in fleet._slots][0::2] == [0, 0]
    assert [s.respawns for s in fleet._slots] == [1, 1, 1]
    threads = {id(s.thread) for s in fleet._slots}
    assert len(threads) == 1 and fleet._slots[0].thread is not first_thread
    # The shared buffer was never closed: all three levels feed again.
    seen, deadline = set(), time.monotonic() + 30
    while len(seen) < 3 and time.monotonic() < deadline:
      seen.add(int(buffer.get(timeout=10).level_name))
    assert seen == {0, 1, 2}
    assert fleet.errors() == []
    assert fleet.stats()['slots_quarantined'] == 0
    assert len(processes) == 6
    assert _no_segment_left()
    _assert_stepped_by(fleet.stats(), step_block, envs=3, spawns=6)
  finally:
    report = fleet.stop()
  assert report['unjoined_actors'] == []
  assert _wait(lambda: _none_running(processes), timeout=10)
  assert _no_segment_left()


@_BOTH_PATHS
def test_wedged_group_member_respawns_the_group(monkeypatch, tmp_path,
                                                step_block):
  """One env of a group hangs mid-step: every member's heartbeat goes
  stale, the stall check orphans the thread and respawns the slots
  together; the children the wedged thread still held calls on are
  killed at once (not one close timeout after another), and the
  orphaned thread unwinds without touching its successors."""
  flag = tmp_path / 'hang'
  flag.write_text('armed')
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i,
          crash_flag=str(flag) if i == 0 else None),
          step_block=step_block),
      buffer, num_actors=4)
  fleet.start()
  try:
    wedged = fleet._slots[0].thread
    assert _wait(lambda: not flag.exists())
    time.sleep(0.6)
    from scalable_agent_tpu.runtime import py_process
    closes, close_all = [], py_process.close_all

    def timed_close_all(processes, **kw):
      t0 = time.monotonic()
      close_all(processes, **kw)
      closes.append((len(processes), time.monotonic() - t0))

    monkeypatch.setattr(py_process, 'close_all', timed_close_all)
    assert sorted(fleet.check_health(stall_timeout_secs=0.5)) == [
        0, 1, 2, 3]
    # (The orphan closes its own again later; a thread that an earlier
    # test of this process orphaned may end meanwhile and close none.)
    closed, took = next(c for c in closes if c[0])
    assert closed == 4 and took < 3.0  # in turn: a second each, 4 s
    # Charged to the slot the thread was waiting for, not to its mates.
    assert [s.respawn_streak for s in fleet._slots] == [1, 0, 0, 0]
    assert _wait(lambda: _none_running(processes[:4]), timeout=10)
    wedged.join(timeout=15)  # its recv broke with the killed child
    assert not wedged.is_alive()
    assert fleet.errors() == []  # the orphan wrote nothing to the slots
    seen, deadline = set(), time.monotonic() + 30
    while len(seen) < 4 and time.monotonic() < deadline:
      seen.add(int(buffer.get(timeout=10).level_name))
    assert seen == {0, 1, 2, 3}
    assert fleet.stats()['actor_threads'] == 1
    _assert_stepped_by(fleet.stats(), step_block, envs=4, spawns=8)
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)
  assert _no_segment_left()


def test_group_member_quarantine_spares_its_mates(monkeypatch, tmp_path):
  """A member that crashes in every life climbs the respawn ladder
  alone: it is quarantined, its mates are not, and they go on as a
  smaller group."""
  flag = tmp_path / 'crash'
  flag.write_text('armed')
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i, once=False,
          crash_flag=str(flag) if i == 0 else None)),
      buffer, num_actors=3, quarantine_after=1)
  for slot in fleet._slots:
    slot.backoff._rng = type('R', (), {'uniform':
                                       staticmethod(lambda a, b: 0.0)})
  fleet.start()
  try:
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['slots_quarantined'] == 1, buffer),
        timeout=60)
    assert [s.quarantined for s in fleet._slots] == [True, False, False]
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['healthy'] == 2))
    stats = fleet.stats()
    assert stats['actor_threads'] == 1 and stats['envs_per_thread'] == 2.0
    before = fleet.stats()['unrolls']
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['unrolls'] >= before + 4, buffer))
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_stop_joins_a_group_parked_in_the_batcher(monkeypatch):
  """A group parked in its ONE batcher request (a merge floor nobody
  will fill) when the run stops: cancelled like a lone caller, joined,
  every member's state slot released, no child left."""
  import jax
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.runtime.inference import InferenceServer
  h, w = 24, 32
  agent = ImpalaAgent(num_actions=A, torso='shallow',
                      use_instruction=False)
  params = init_params(agent, jax.random.PRNGKey(0), {
      'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN})
  cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
               inference_state_cache=True, inference_min_batch=8,
               inference_max_batch=8, inference_timeout_ms=60_000)
  server = InferenceServer(agent, params, cfg, seed=3, fleet_size=2)
  total = server.slots_free()
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(8)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=h, width=w, num_actions=A, seed=i),
          policy=server.policy, state_fn=server.initial_core_state),
      buffer, num_actors=2)
  try:
    fleet.start()
    assert _wait(lambda: server.stats()['batcher_requests'] == 1)
    time.sleep(0.2)  # parked in compute_wait
    assert server.slots_free() == total - 2
    assert fleet.stats()['actor_threads'] == 1
    t0 = time.monotonic()
    fleet.stop_event.set()  # stop first: the cancel is then clean
    server.close()
    report = fleet.stop(timeout=10)
    assert report['unjoined_actors'] == []
    assert time.monotonic() - t0 < 10
    assert fleet.errors() == []
    assert server.slots_free() == total
  finally:
    server.close()
    fleet.stop(timeout=1)
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_quiesce_joins_a_group_parked_on_a_full_buffer(monkeypatch):
  """A group whose second unroll finds the buffer full and nobody
  draining: quiesce() (buffer left open) joins it inside the put's
  stop grace; its first unroll landed."""
  from scalable_agent_tpu.runtime import actor as actor_lib
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(1)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i)),
      buffer, num_actors=2)
  fleet.start()
  try:
    assert _wait(lambda: fleet.stats()['unrolls'] == 1)
    time.sleep(0.2)  # the second member's put is parked
    t0 = time.monotonic()
    report = fleet.quiesce(
        timeout=actor_lib._STOP_PUT_GRACE_SECS + 5.0)
    assert report['unjoined_actors'] == []
    assert (time.monotonic() - t0 <
            actor_lib._STOP_PUT_GRACE_SECS + 3 * actor_lib._PUT_POLL_SECS)
    assert int(buffer.get(timeout=1).level_name) == 0
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_in_process_envs_keep_a_thread_each(monkeypatch):
  """Envs hosted in the actor's own process are never grouped, however
  few cores there are: `env.step` runs on the actor thread there."""
  buffer = ring_buffer.TrajectoryBuffer(16)
  fleet = ActorFleet(
      _make_actor_factory(lambda i: FakeEnv(height=H, width=W,
                                            num_actions=A, seed=i)),
      buffer, num_actors=3)
  fleet.start()
  try:
    stats = fleet.stats()
    assert stats['actor_threads'] == 3
    assert stats['envs_per_thread'] == 1.0
    assert ([s.thread.name for s in fleet._slots] ==
            ['actor-0', 'actor-1', 'actor-2'])
  finally:
    fleet.stop()


def test_mixed_spec_fleet_groups_within_a_spec(monkeypatch):
  """Envs of different observation specs never share a group (a k-row
  request needs one trailing shape), wherever they sit in the fleet."""
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  sizes = [(8, 8), (16, 8), (8, 8), (16, 8), (8, 8)]
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=sizes[i][0], width=sizes[i][1], num_actions=A, seed=i),
          env_class=FakeEnv),
      buffer, num_actors=5)
  fleet.start()
  try:
    threads = [s.thread for s in fleet._slots]
    assert threads[0] is threads[2] is threads[4]
    assert threads[1] is threads[3]
    assert threads[0] is not threads[1]
    stats = fleet.stats()
    assert stats['actor_threads'] == 2
    assert stats['envs_per_thread'] == 2.5
    seen, deadline = set(), time.monotonic() + 30
    while len(seen) < 5 and time.monotonic() < deadline:
      unroll = buffer.get(timeout=10)
      level = int(unroll.level_name)
      assert unroll.env_outputs.observation[0].shape[1:3] == sizes[level]
      seen.add(level)
    assert seen == {0, 1, 2, 3, 4}
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_set_target_size_moves_one_slot_of_a_group():
  """The shipped rule puts a small hosted fleet on ONE thread: the
  controller's step of one still parks one slot (the thread goes on
  with the rest, no respawn, the child gone) and unparks one (it
  joins the running group at its next unroll), and never the fleet."""
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i), env_class=FakeEnv),
      buffer, num_actors=4)
  fleet.start()
  try:
    thread = fleet._slots[0].thread
    assert all(s.thread is thread for s in fleet._slots)
    report = fleet.set_target_size(3)
    assert report['parked'] == [3] and fleet.target_size() == 3
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['alive'] == 3, buffer))
    assert fleet._slots[3].thread is None
    assert _wait(lambda: not processes[3]._process.is_alive())
    stats = fleet.stats()
    assert stats['actor_threads'] == 1 and stats['envs_per_thread'] == 3.0
    assert stats['respawns'] == 0 and stats['parked'] == 1
    before = [s.unrolls_done for s in fleet._slots]
    assert _wait(_pumped(fleet, lambda: all(
        s.unrolls_done >= n + 2
        for s, n in zip(fleet._slots[:3], before)), buffer))
    assert fleet._slots[3].unrolls_done == before[3]
    assert thread.is_alive()

    report = fleet.set_target_size(4)
    assert report['unparked'] == [3] and fleet.target_size() == 4
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['healthy'] == 4, buffer))
    assert _wait(_pumped(
        fleet, lambda: fleet._slots[3].unrolls_done > before[3], buffer))
    assert all(s.thread is thread for s in fleet._slots)
    assert fleet.stats()['actor_threads'] == 1
    assert [s.respawns for s in fleet._slots] == [0, 0, 0, 1]

    # All the way down, one by one, and the last slot stays.
    for n in (3, 2, 1):
      assert len(fleet.set_target_size(n)['parked']) == 1
    assert fleet.target_size() == 1
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['alive'] == 1, buffer))
    assert fleet._slots[0].thread is thread and thread.is_alive()
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_hanging_group_member_is_quarantined_alone(tmp_path):
  """A member whose env hangs in every life, before its first unroll:
  the stall check charges the member the thread is waiting for and
  respawns its mates beside it off the ladder, so it is quarantined
  alone and the rest go on feeding as a smaller group."""
  flag = tmp_path / 'hang'
  flag.write_text('armed')
  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i, once=False,
          crash_flag=str(flag) if i == 1 else None)),
      buffer, num_actors=3, quarantine_after=1)
  for slot in fleet._slots:
    slot.backoff._rng = type('R', (), {'uniform':
                                       staticmethod(lambda a, b: 0.0)})
  fleet.start()
  try:
    for life in (1, 2):
      time.sleep(0.8)  # every heartbeat is stale, slot 1's call pending
      assert sorted(fleet.check_health(stall_timeout_secs=0.5)) == [
          0, 1, 2]
      assert [s.respawn_streak for s in fleet._slots] == [0, life, 0]
      assert [s.respawns for s in fleet._slots] == [life] * 3
    assert [s.quarantined for s in fleet._slots] == [False, True, False]
    assert fleet._slots[0].thread is fleet._slots[2].thread
    before = fleet.stats()['unrolls']
    assert _wait(_pumped(
        fleet, lambda: fleet.stats()['unrolls'] >= before + 4, buffer))
    stats = fleet.stats()
    assert stats['slots_quarantined'] == 1 and stats['healthy'] == 2
    assert stats['actor_threads'] == 1 and stats['envs_per_thread'] == 2.0
    assert fleet.errors() == []
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=15)


def test_group_size_is_bounded_by_the_policy_rows():
  """A policy that takes at most two rows a call (the server's
  `inference_max_batch`; the batcher never splits a request) gets
  groups of two, not one request it would refuse."""
  rows = []

  def two_rows_at_most(prev_action, env_output, core_state):
    rows.append(np.size(prev_action))
    if rows[-1] > 2:
      raise ValueError('rows exceeds maximum_batch_size')
    return _group_policy(prev_action, env_output, core_state)

  processes = []
  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(
      _hosted_factory(processes, lambda i: dict(
          height=H, width=W, num_actions=A, seed=i), env_class=FakeEnv,
          policy=two_rows_at_most),
      buffer, num_actors=5, max_policy_rows=2)
  fleet.start()
  try:
    seen, deadline = set(), time.monotonic() + 30
    while len(seen) < 5 and time.monotonic() < deadline:
      seen.add(int(buffer.get(timeout=10).level_name))
    assert seen == {0, 1, 2, 3, 4}
    stats = fleet.stats()
    assert stats['actor_threads'] == 3
    assert fleet._slots[0].thread is fleet._slots[1].thread
    assert fleet._slots[2].thread is fleet._slots[3].thread
    assert set(rows) == {1, 2}
    assert fleet.errors() == [] and stats['respawns'] == 0
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)


def test_make_fleet_bounds_groups_by_inference_max_batch():
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  for max_batch, want in ((16, 16), (1024, 32), (1, 1)):
    fleet = driver.make_fleet(
        Config(num_actors=32, inference_max_batch=max_batch), None,
        _group_policy, ring_buffer.TrajectoryBuffer(4), ['fake'])
    assert fleet._envs_per_thread == want


def test_a_slowly_built_group_does_not_start_stalled():
  """A group's envs are built in turn before their thread starts: the
  time the later ones took must not read as the first ones' stall."""
  processes = []
  make_actor = _hosted_factory(processes, lambda i: dict(
      height=H, width=W, num_actions=A, seed=i), env_class=FakeEnv)

  def slow_make_actor(i):
    time.sleep(0.3)
    return make_actor(i)

  buffer = ring_buffer.TrajectoryBuffer(64)
  fleet = ActorFleet(slow_make_actor, buffer, num_actors=4)
  fleet.start()
  try:
    assert fleet.check_health(stall_timeout_secs=0.5) == []
    assert _wait(lambda: fleet.stats()['unrolls'] >= 4)
    assert fleet.stats()['respawns'] == 0
  finally:
    fleet.stop()
  assert _wait(lambda: _none_running(processes), timeout=10)

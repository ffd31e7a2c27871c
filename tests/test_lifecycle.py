"""The one run lifecycle (lifecycle.py): opening and closing a run, the
health ladder, the profiler window — against fakes, on the host — and
one run-level drill under both runtimes."""

import collections
import json
import os
import threading

import numpy as np
import pytest

from scalable_agent_tpu import health as health_lib
from scalable_agent_tpu import lifecycle
from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis import runtime as lock_check
from scalable_agent_tpu.config import Config

_State = collections.namedtuple('_State', 'params update_steps')


class _FakeCheckpointer:
  """What the lifecycle asks of a Checkpointer, recording the calls."""
  save_errors = restore_fallbacks = digest_fallbacks = 0

  def __init__(self, last_good=None):
    self.last_good = last_good
    self.calls = []

  def restore_last_good(self, state):
    self.calls.append('restore_last_good')
    return self.last_good

  def save(self, state, force=False):
    self.calls.append(('save', state, force))

  def close(self):
    self.calls.append('close')


def _config(tmp_path, **kw):
  base = dict(logdir=str(tmp_path), health_rollback_after=2,
              health_max_rollbacks=1)
  base.update(kw)
  return Config(**base)


def _metrics(ok=True):
  return {'step_ok': 1.0 if ok else 0.0,
          'total_loss': 1.0 if ok else float('nan'), 'grad_norm': 1.0}


def _incident_kinds(tmp_path):
  with open(os.path.join(str(tmp_path), 'incidents.jsonl')) as f:
    return [json.loads(line)['kind'] for line in f]


def _driver_gauges():
  return [n for n in telemetry.registry().names()
          if n.startswith('driver/') or n == 'slo/burning']


# --- open_run / Run.close --------------------------------------------


@pytest.mark.parametrize('failing', ['writer', 'incidents', 'slo_engine'])
def test_open_run_unwinds_what_it_built(tmp_path, monkeypatch, failing):
  def boom(*args, **kwargs):
    raise OSError(f'{failing} cannot open')
  if failing == 'writer':
    monkeypatch.setattr(lifecycle.observability, 'SummaryWriter', boom)
  elif failing == 'incidents':
    monkeypatch.setattr(lifecycle.observability, 'EventLog', boom)
  else:
    monkeypatch.setattr(lifecycle.slo_lib.SloEngine, 'start', boom)
  ckpt = _FakeCheckpointer()
  with pytest.raises(OSError, match=failing):
    lifecycle.open_run(_config(tmp_path), ckpt)
  assert lock_check._incident_sink is None
  assert not [t for t in threading.enumerate()
              if t.name == 'slo-engine']
  assert _driver_gauges() == []
  assert ckpt.calls == []  # the checkpointer stays the caller's


@pytest.mark.parametrize('how', ['clean', 'exception', 'unhealthy'])
def test_close_writes_verdict_and_applies_the_tail_save_rule(
    tmp_path, how):
  ckpt = _FakeCheckpointer()
  life = lifecycle.open_run(_config(tmp_path), ckpt)
  life.loop_gauges(update_steps=lambda: 7, env_frames=lambda: 70,
                   utilization=lambda: 1.0)
  assert len(_driver_gauges()) == 5
  state = _State(params=None, update_steps=np.int32(7))
  if how == 'unhealthy':
    for step in (1, 2):
      life.ladder.step(step, _metrics(ok=False), state)
    assert not life.ladder.healthy_now
  order = []

  def run_and_close():
    try:
      if how == 'exception':
        raise RuntimeError('the loop died')
    finally:
      life.close(state, 7, extra={'runtime': 'anakin'},
                 teardown=lambda: order.append(list(ckpt.calls)))

  if how == 'exception':
    with pytest.raises(RuntimeError):
      run_and_close()
  else:
    run_and_close()
  with open(os.path.join(str(tmp_path), 'SLO_VERDICT.json')) as f:
    verdict = json.load(f)
  assert verdict['clean_exit'] == (how != 'exception')
  assert verdict['update_steps'] == 7
  assert verdict['runtime'] == 'anakin'
  assert order == [[]]  # teardown ran, before any save or close
  if how == 'unhealthy':
    assert ckpt.calls == ['close']
  else:
    assert ckpt.calls == [('save', state, True), 'close']
  assert lock_check._incident_sink is None
  assert _driver_gauges() == []
  assert not [t for t in threading.enumerate()
              if t.name == 'slo-engine']


def test_close_without_state_saves_nothing(tmp_path):
  """The fused population's shape: no checkpointer of its own, its
  members saved at their round boundary."""
  life = lifecycle.open_run(_config(tmp_path, slo_engine=False))
  assert life.health is None  # nothing to roll back to: no watchdog
  life.close(None, 3)
  assert not os.path.exists(os.path.join(str(tmp_path),
                                         'SLO_VERDICT.json'))
  assert os.path.exists(os.path.join(str(tmp_path), 'config.json'))


def test_restore_at_start_closes_the_manager_on_a_failed_restore(
    tmp_path, monkeypatch):
  closed = []

  class _Ckpt(_FakeCheckpointer):
    def __init__(self, directory, **kwargs):
      super().__init__()
      assert directory == str(tmp_path) + '/checkpoints'

    def restore_latest(self, state):
      return None

    def close(self):
      closed.append(self)

  monkeypatch.setattr(lifecycle.checkpoint_lib, 'Checkpointer', _Ckpt)
  fresh = _State(params=None, update_steps=np.int32(0))
  ckpt, state = lifecycle.restore_at_start(_config(tmp_path), fresh)
  assert state is fresh and not closed
  handed = _State(params=None, update_steps=np.int32(5))
  _, state = lifecycle.restore_at_start(_config(tmp_path), fresh,
                                        initial_state=handed)
  assert state is handed

  def mismatch(checkpointer, state):
    raise ValueError('structure mismatch')
  with pytest.raises(ValueError, match='structure'):
    lifecycle.restore_at_start(_config(tmp_path), fresh,
                               restore=mismatch)
  assert len(closed) == 1


# --- HealthLadder -----------------------------------------------------

# (name, per-step ok flags, restorable?, incident kinds, rollbacks)
_LADDER_CASES = [
    ('all_ok', [1, 1, 1, 1, 1], True, [], 0),
    ('burst_recovers', [1, 0, 1, 1, 1], True,
     ['health_bad_burst_start', 'health_recovered'], 0),
    ('rollback_keeps_update_steps', [1, 0, 0, 1, 1], True,
     ['health_bad_burst_start', 'rollback', 'health_recovered'], 1),
    ('nothing_to_restore_halts', [1, 0, 0, 1, 1], False,
     ['health_bad_burst_start', 'health_halt'], 0),
]


@pytest.mark.parametrize('name,oks,restorable,kinds,rollbacks',
                         _LADDER_CASES,
                         ids=[c[0] for c in _LADDER_CASES])
def test_health_ladder(tmp_path, name, oks, restorable, kinds,
                       rollbacks):
  cfg = _config(tmp_path, slo_engine=False)
  good = _State(params='last good', update_steps=np.int32(1))
  ckpt = _FakeCheckpointer(last_good=good if restorable else None)
  life = lifecycle.open_run(cfg, ckpt)
  ladder = life.ladder
  state = _State(params='live', update_steps=np.int32(0))
  halted = None
  try:
    # One more step than flags: the read is one step late.
    for step, ok in enumerate(oks + [1], start=1):
      state = state._replace(update_steps=np.int32(step))
      try:
        state = ladder.step(step, _metrics(ok=bool(ok)), state)
      except health_lib.TrainingDivergence as e:
        halted = e
        break
  finally:
    life.close(None, len(oks))
  assert _incident_kinds(tmp_path) == kinds
  assert life.health.stats()['rollbacks'] == rollbacks
  if name == 'nothing_to_restore_halts':
    assert halted is not None and os.path.exists(halted.bundle_path)
    with open(halted.bundle_path) as f:
      assert 'no restorable checkpoint' in json.load(f)['reason']
    assert not ladder.healthy_now
  else:
    assert halted is None and ladder.healthy_now
  if name == 'rollback_keeps_update_steps':
    # Params reverted; the step counter did not.
    assert state.params == 'last good'
    assert int(state.update_steps) == len(oks) + 1
  elif halted is None:
    assert state.params == 'live'


def test_health_ladder_hooks_and_extra_sentinels(tmp_path):
  """What only `train` does comes in as arguments: the restore choice,
  the republish after a rollback, the extra sentinel values (the SDC
  fingerprints' shape) and the flight recorder."""
  good = _State(params='chosen', update_steps=np.int32(1))
  calls = []

  def restore(state):
    calls.append(('restore', int(state.update_steps)))
    return good

  def on_rollback(step, state):
    calls.append(('on_rollback', step, state.params,
                  int(state.update_steps)))

  def dispatch(step, state):
    calls.append(('dispatch', step))
    return step

  def read(obs_step, handle):
    calls.append(('read', obs_step, handle))
    # The third check carries a replica mismatch; the fourth again.
    return {'sdc_replica_mismatch': 1.0 if handle >= 3 else 0.0}

  flight = telemetry.FlightRecorder()
  ckpt = _FakeCheckpointer()
  life = lifecycle.open_run(
      _config(tmp_path, slo_engine=False), ckpt, flight=flight,
      rollback_restore=restore,
      on_rollback=on_rollback, extra_sentinels=(dispatch, read))
  state = _State(params='live', update_steps=np.int32(0))
  try:
    for step in range(1, 6):
      state = state._replace(update_steps=np.int32(step))
      state = life.ladder.step(step, _metrics(), state)
  finally:
    life.close(None, 5)
  assert ckpt.calls == ['close']  # the default restore was not asked
  assert [c for c in calls if c[0] == 'dispatch'] == [
      ('dispatch', s) for s in range(1, 6)]
  assert ('read', 3, 3) in calls and ('read', 4, 4) in calls
  # Checks 3 and 4 were bad (K=2): the rollback is judged at step 5.
  assert ('restore', 5) in calls
  assert ('on_rollback', 5, 'chosen', 5) in calls
  assert life.health.stats()['sdc_mismatches'] == 2
  with open(os.path.join(str(tmp_path), 'incidents.jsonl')) as f:
    rollback = [json.loads(line) for line in f][-1]
  assert rollback['kind'] == 'rollback'
  assert rollback['restored_checkpoint_step'] == 1
  assert os.path.exists(rollback['flight'])


def test_health_ladder_off_is_transparent(tmp_path):
  life = lifecycle.open_run(_config(tmp_path, health_watchdog=False),
                            _FakeCheckpointer())
  state = _State(params='live', update_steps=np.int32(0))
  try:
    assert life.health is None
    for step in (1, 2, 3, 4):
      assert life.ladder.step(step, _metrics(ok=False), state) is state
    assert life.ladder.healthy_now
  finally:
    life.close(None, 4)


# --- ProfilerWindow ---------------------------------------------------


class _FakeSloEngine:

  def __init__(self, requests=()):
    self.requests = collections.deque(requests)
    self.noted = []

  def take_profile_request(self):
    return self.requests.popleft() if self.requests else None

  def note_profile(self, name, path):
    self.noted.append((name, path))


@pytest.fixture
def captures(monkeypatch):
  """Every ProfilerCapture started, as [directory, 'open'|'stopped'];
  a second one while one is open is the fault the window prevents."""
  log = []

  class _Capture:
    def __init__(self, directory):
      assert all(state == 'stopped' for _, state in log), log
      self.entry = [directory, 'open']
      log.append(self.entry)

    def stop(self):
      self.entry[1] = 'stopped'

  monkeypatch.setattr(lifecycle.observability, 'ProfilerCapture',
                      _Capture)
  return log


def test_profiler_window_operator_alone(tmp_path, captures):
  cfg = _config(tmp_path, profile_dir=str(tmp_path / 'prof'),
                profile_start_step=2, profile_num_steps=3)
  window = lifecycle.ProfilerWindow(cfg, None)
  opened_at = {}
  for steps_done in range(8):
    window.tick(steps_done)
    for directory, state in captures:
      opened_at.setdefault((directory, state), steps_done)
  prof = str(tmp_path / 'prof')
  assert opened_at == {(prof, 'open'): 2, (prof, 'stopped'): 5}
  window.close()
  assert captures == [[prof, 'stopped']]


def test_profiler_window_slo_alone(tmp_path, captures):
  cfg = _config(tmp_path, slo_capture_steps=2)
  engine = _FakeSloEngine(['fps_floor'])
  window = lifecycle.ProfilerWindow(cfg, engine)
  window.tick(1)
  directory = os.path.join(str(tmp_path), 'diagnostics',
                           'slo_profile_fps_floor')
  assert captures == [[directory, 'open']]
  assert engine.noted == [('fps_floor', directory)]
  window.tick(2)
  assert captures[0][1] == 'open'
  window.tick(3)
  assert captures == [[directory, 'stopped']]
  # A run that ends inside a capture stops it.
  engine.requests.append('learner_stall')
  window.tick(4)
  assert captures[-1][1] == 'open'
  window.close()
  assert captures[-1][1] == 'stopped'


def test_profiler_window_operator_defers_past_slo(tmp_path, captures):
  prof = str(tmp_path / 'prof')
  cfg = _config(tmp_path, profile_dir=prof, profile_start_step=2,
                profile_num_steps=2, slo_capture_steps=3)
  engine = _FakeSloEngine(['fps_floor'])
  window = lifecycle.ProfilerWindow(cfg, engine)
  window.tick(0)  # the SLO capture takes the profiler first
  engine.requests.append('learner_stall')  # waits for both
  for steps_done in range(1, 12):
    window.tick(steps_done)
  window.close()
  assert [os.path.basename(d) for d, _ in captures] == [
      'slo_profile_fps_floor', 'prof', 'slo_profile_learner_stall']
  assert all(state == 'stopped' for _, state in captures)


# --- the run-level drill ----------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize('runtime', ['fleet', 'anakin'])
def test_every_step_non_finite_halts_the_same_under_both_runtimes(
    tmp_path, runtime):
  """An infinite entropy cost makes every update non-finite. Under
  either runtime: the burst starts, the rollback finds nothing to
  restore, the run halts with a bundle, the verdict says the exit was
  not clean, and no tail checkpoint lands on the diverged state."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu import slo
  from scalable_agent_tpu.checkpoint import Checkpointer
  cfg = Config(
      logdir=str(tmp_path), runtime=runtime, env_backend='bandit',
      num_actors=2, batch_size=2, unroll_length=5,
      num_action_repeats=1, episode_length=4, height=24, width=32,
      torso='shallow', use_py_process=False, use_instruction=False,
      total_environment_frames=10 ** 6, inference_timeout_ms=5,
      checkpoint_secs=10 ** 6, summary_secs=0, seed=3,
      entropy_cost=float('inf'), health_rollback_after=2)
  with pytest.raises(health_lib.TrainingDivergence) as exc_info:
    driver.train(cfg, max_steps=12, stall_timeout_secs=60)
  kinds = [k for k in _incident_kinds(tmp_path)
           if k.startswith(('health_', 'rollback'))]
  assert kinds == ['health_bad_burst_start', 'health_halt']
  with open(exc_info.value.bundle_path) as f:
    bundle = json.load(f)
  assert 'no restorable checkpoint' in bundle['reason']
  verdict = slo.read_verdict(str(tmp_path))
  assert verdict is not None and verdict['clean_exit'] is False
  assert verdict['update_steps'] == 3  # K=2 bad steps, read one late
  ckpt = Checkpointer(str(tmp_path) + '/checkpoints')
  try:
    assert ckpt.latest_step() is None
  finally:
    ckpt.close()
  assert _driver_gauges() == []

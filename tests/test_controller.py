"""The round-15 self-healing controller: declarative policy table,
bounded escalate/revert moves with hysteresis, observe-mode dry runs,
the CONTROLLER_LOG.json / incident / external-ledger audit trail, and
the driver end-to-end (an acting controller's moves ride the drain
manifest like slo_violation incidents).

Determinism is a tested property, not an accident: the scripted-trace
test drives `Controller.tick(now=...)` with an injected clock through
a fixed snapshot sequence and pins the EXACT action list — escalate,
hold-under-cool-down, hysteresis no-flap, revert — twice, asserting
the two traces are identical (the controller uses no randomness and
no hidden wall-clock reads beyond `now`).
"""

import json
import threading
import time

import pytest

from scalable_agent_tpu import controller as ctl
from scalable_agent_tpu import health as health_lib
from scalable_agent_tpu import observability
from scalable_agent_tpu import slo


def _obj(state=slo.OK, margin=None, value=None, severity='page',
         target=1.0, burns=0):
  return {'state': state, 'margin': margin, 'value': value,
          'severity': severity, 'target': target, 'burns': burns}


class _StubEngine:
  """control_snapshot()-shaped stand-in the tests script directly."""

  def __init__(self, **objectives):
    self.snapshot = objectives

  def control_snapshot(self):
    return {n: dict(e) for n, e in self.snapshot.items()}


class _Knob:
  """A recording numeric/enum actuator target."""

  def __init__(self, value):
    self.value = value
    self.sets = []

  def get(self):
    return self.value

  def set(self, v):
    self.sets.append(v)
    self.value = v


def _controller(engine, rules, actuators, tmp_path, mode='act',
                **kw):
  return ctl.Controller(engine, rules, actuators, str(tmp_path),
                        mode=mode, interval_secs=60.0, **kw)


# --------------------------------------------------------------------
# Policy table.
# --------------------------------------------------------------------


def test_default_rules_reference_shipped_objectives_and_actuators():
  names = {o.name for o in slo.DEFAULT_OBJECTIVES}
  for rule in ctl.DEFAULT_RULES:
    rule.validate()
    assert rule.objective in names, rule
    assert rule.actuator in ctl.KNOWN_ACTUATORS, rule


def test_load_rules_json_roundtrip_and_failures(tmp_path):
  path = tmp_path / 'policy.json'
  path.write_text(json.dumps([
      {'objective': 'fleet_healthy_fraction', 'actuator': 'fleet_size',
       'direction': 'up', 'step': 1, 'trigger_margin': 0.1,
       'clear_margin': 0.4, 'cooldown_secs': 2.0}]))
  rules = ctl.load_rules(str(path))
  assert len(rules) == 1 and rules[0].clear_margin == 0.4
  # Defaults when no path.
  assert [r.objective for r in ctl.load_rules()] == \
      [r.objective for r in ctl.DEFAULT_RULES]
  # A typo'd actuator fails at load, not silently at runtime.
  path.write_text(json.dumps([
      {'objective': 'x', 'actuator': 'warp_drive'}]))
  with pytest.raises(ValueError, match='unknown actuator'):
    ctl.load_rules(str(path))
  # The hysteresis band must be a band: clear >= trigger.
  path.write_text(json.dumps([
      {'objective': 'x', 'actuator': 'replay_k',
       'trigger_margin': 0.5, 'clear_margin': 0.1}]))
  with pytest.raises(ValueError, match='hysteresis'):
    ctl.load_rules(str(path))
  path.write_text(json.dumps({'not': 'a list'}))
  with pytest.raises(ValueError, match='non-empty JSON list'):
    ctl.load_rules(str(path))


def test_rules_over_missing_actuator_or_objective_are_dropped(
    tmp_path):
  engine = _StubEngine(known=_obj())
  knob = _Knob(1)
  rules = [
      ctl.Rule(objective='known', actuator='replay_k'),
      ctl.Rule(objective='known', actuator='publish_secs'),  # no act.
      ctl.Rule(objective='unknown', actuator='replay_k'),    # no obj.
  ]
  c = _controller(engine, rules, [
      ctl.Actuator('replay_k', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=4)], tmp_path)
  assert len(c._rules) == 1
  c.stop()


# --------------------------------------------------------------------
# The scripted deterministic trace (the ISSUE's controller-determinism
# satellite): exact action sequence, zero jitter.
# --------------------------------------------------------------------


def _scripted_trace(tmp_path, subdir):
  engine = _StubEngine(lag=_obj())
  knob = _Knob(2)
  rule = ctl.Rule(objective='lag', actuator='replay_k', step=1,
                  direction='up', cooldown_secs=10.0,
                  clear_margin=0.5)
  out = tmp_path / subdir
  out.mkdir()
  c = _controller(engine, [rule], [
      ctl.Actuator('replay_k', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=4)], out)
  trace = []

  def step(now, **obj):
    engine.snapshot['lag'] = _obj(**obj)
    for a in c.tick(now=now):
      trace.append((round(now, 1), a['kind'], a['from'], a['to']))

  step(0.0, state=slo.BURNING, margin=-0.5)    # escalate 2 -> 3
  step(5.0, state=slo.BURNING, margin=-0.5)    # hold: cool-down
  step(12.0, state=slo.BURNING, margin=-0.5)   # escalate 3 -> 4
  step(24.0, state=slo.BURNING, margin=-0.5)   # hold: at the bound
  step(36.0, state=slo.OK, margin=0.2)         # hysteresis: no flap
  step(48.0, state=slo.OK, margin=0.6)         # revert 4 -> 3
  step(53.0, state=slo.OK, margin=0.6)         # hold: cool-down
  step(60.0, state=slo.OK, margin=0.6)         # revert 3 -> 2 (done)
  step(72.0, state=slo.OK, margin=0.6)         # disengaged: idle
  c.stop()
  return trace, knob.sets, c.counts()


def test_scripted_trace_exact_action_sequence(tmp_path):
  trace, sets, counts = _scripted_trace(tmp_path, 'a')
  assert trace == [
      (0.0, 'escalate', 2, 3),
      (12.0, 'escalate', 3, 4),
      (48.0, 'revert', 4, 3),
      (60.0, 'revert', 3, 2),
  ]
  assert sets == [3, 4, 3, 2]
  assert counts == {'actions': 4, 'escalations': 2, 'reverts': 2,
                    'applied': 4, 'apply_errors': 0}
  # Zero jitter: an identical re-run produces the identical trace.
  trace2, sets2, _ = _scripted_trace(tmp_path, 'b')
  assert trace2 == trace and sets2 == sets


def test_enum_actuator_escalates_to_target_and_reverts(tmp_path):
  engine = _StubEngine(overload=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob('block')
  rule = ctl.Rule(objective='overload', actuator='admission',
                  to='shed', revert_to='block', cooldown_secs=1.0,
                  clear_margin=0.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('admission', kind='enum', get_fn=knob.get,
                   set_fn=knob.set,
                   values=('block', 'shed', 'grow'))], tmp_path)
  assert [(a['kind'], a['to']) for a in c.tick(now=0.0)] == \
      [('escalate', 'shed')]
  # Already at the target: burning keeps holding, no action spam.
  assert c.tick(now=5.0) == []
  engine.snapshot['overload'] = _obj(state=slo.OK, margin=3.0)
  assert [(a['kind'], a['to']) for a in c.tick(now=10.0)] == \
      [('revert', 'block')]
  assert knob.sets == ['shed', 'block']
  assert c.engaged_rules() == 0
  c.stop()


def test_margin_pressure_triggers_before_the_burn(tmp_path):
  """The leading-edge trigger: a page objective whose margin thinned
  to the trigger band moves the knob while the state is still OK —
  the mechanism that lets an actuated run keep its verdict green."""
  engine = _StubEngine(quorum=_obj(state=slo.OK, margin=0.05))
  knob = _Knob(2)
  rule = ctl.Rule(objective='quorum', actuator='fleet_size', step=1,
                  trigger_margin=0.1, clear_margin=0.4,
                  cooldown_secs=1.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('fleet_size', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=4)], tmp_path)
  assert [a['kind'] for a in c.tick(now=0.0)] == ['escalate']
  assert knob.value == 3
  c.stop()


def test_no_data_holds_every_knob(tmp_path):
  engine = _StubEngine(lag=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob(1)
  rule = ctl.Rule(objective='lag', actuator='replay_k', step=1,
                  cooldown_secs=0.0, clear_margin=0.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('replay_k', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=4)], tmp_path)
  c.tick(now=0.0)
  assert knob.value == 2
  # Blindness is not a reason to move a knob — in either direction.
  engine.snapshot['lag'] = _obj(state=slo.NO_DATA)
  assert c.tick(now=10.0) == []
  assert knob.value == 2
  c.stop()


# --------------------------------------------------------------------
# Observe mode: the faithful dry run.
# --------------------------------------------------------------------


def test_observe_mode_logs_whole_sequence_without_touching(tmp_path):
  engine = _StubEngine(lag=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob(1)
  rule = ctl.Rule(objective='lag', actuator='replay_k', step=1,
                  cooldown_secs=1.0, clear_margin=0.5)
  c = _controller(engine, [rule], [
      ctl.Actuator('replay_k', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=3)], tmp_path,
                  mode='observe')
  moves = []
  for t in (0.0, 2.0, 4.0, 6.0):
    moves += [(a['from'], a['to'], a['applied'])
              for a in c.tick(now=t)]
  # The virtual value walks the same 1 -> 2 -> 3 -> bound sequence an
  # acting controller would; the real knob never moves.
  assert moves == [(1, 2, False), (2, 3, False)]
  assert knob.sets == [] and knob.value == 1
  engine.snapshot['lag'] = _obj(state=slo.OK, margin=0.9)
  reverts = [(a['from'], a['to']) for a in c.tick(now=8.0)]
  assert reverts == [(3, 2)]
  assert knob.sets == []
  c.stop()
  log = ctl.read_log(str(tmp_path))
  assert log['mode'] == 'observe'
  assert all(not a['applied'] for a in log['actions'])


# --------------------------------------------------------------------
# Audit trail: log file, incidents, external ledger, failure paths.
# --------------------------------------------------------------------


def test_actions_land_in_log_incidents_and_external_ledger(tmp_path):
  engine = _StubEngine(lag=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob(1)
  incidents = observability.EventLog(str(tmp_path))
  monitor = health_lib.HealthMonitor()
  rule = ctl.Rule(objective='lag', actuator='replay_k', step=1,
                  cooldown_secs=0.0, clear_margin=0.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('replay_k', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=4)], tmp_path,
                  incidents=incidents, health=monitor)
  c.tick(now=0.0)
  c.stop()
  c.finalize()
  incidents.close()
  log = ctl.read_log(str(tmp_path))
  assert log['counts']['applied'] == 1
  (row,) = log['actions']
  assert (row['kind'], row['actuator'], row['from'], row['to'],
          row['applied']) == ('escalate', 'replay_k', 1, 2, True)
  with open(tmp_path / 'incidents.jsonl') as f:
    events = [json.loads(l) for l in f if l.strip()]
  (ev,) = [e for e in events if e['kind'] == 'controller_action']
  assert ev['action'] == 'escalate' and ev['actuator'] == 'replay_k'
  # The external-incident ledger (rides drain manifests/halt bundles).
  assert monitor.external_incidents == {'controller_replay_k': 1}


def test_failing_actuator_set_is_counted_not_fatal(tmp_path):
  engine = _StubEngine(lag=_obj(state=slo.BURNING, margin=-1.0))

  def broken_set(v):
    raise RuntimeError('knob fell off')

  rule = ctl.Rule(objective='lag', actuator='replay_k', step=1,
                  cooldown_secs=0.0, clear_margin=0.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('replay_k', kind='int', get_fn=lambda: 1,
                   set_fn=broken_set, minimum=1, maximum=4)], tmp_path)
  (action,) = c.tick(now=0.0)
  assert action['applied'] is False
  assert 'knob fell off' in action['error']
  assert c.counts()['apply_errors'] == 1
  c.stop()


def test_bounded_moves_never_leave_the_registered_range(tmp_path):
  engine = _StubEngine(p=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob(28.0)
  rule = ctl.Rule(objective='p', actuator='publish_secs', step=5.0,
                  cooldown_secs=0.0, clear_margin=0.0)
  c = _controller(engine, [rule], [
      ctl.Actuator('publish_secs', kind='float', get_fn=knob.get,
                   set_fn=knob.set, minimum=2.0, maximum=30.0)],
                  tmp_path)
  c.tick(now=0.0)
  assert knob.value == 30.0   # clamped, not 33.0
  assert c.tick(now=1.0) == []  # at the bound: holding IS the action
  c.stop()


# --------------------------------------------------------------------
# Driver end-to-end: an acting controller's moves ride the drain
# manifest (the external-incident ledger), the log lands, and the
# actuator really moved.
# --------------------------------------------------------------------


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_acting_controller_rides_drain_manifest(tmp_path):
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config

  # Window sizing: the engine thread ticks at >= 0.25 s (SloEngine's
  # floor), and a value burn needs 3 fast-window samples — 1.5 s is
  # the narrowest fast window that can burn from the thread alone
  # (steps may be scarce around compile time on a slow CI host).
  spec = [dict(name='always_burning', metric='driver/update_steps',
               comparison='<=', target=-1.0, severity='info',
               fast_window_secs=1.5, slow_window_secs=4.0)]
  policy = [dict(objective='always_burning', actuator='replay_k',
                 direction='up', step=1, cooldown_secs=0.2,
                 clear_margin=0.0)]
  spec_path = tmp_path / 'spec.json'
  policy_path = tmp_path / 'policy.json'
  spec_path.write_text(json.dumps(spec))
  policy_path.write_text(json.dumps(policy))
  cfg = Config(
      logdir=str(tmp_path), env_backend='bandit', num_actors=2,
      batch_size=2, unroll_length=5, num_action_repeats=1,
      episode_length=4, height=24, width=32, torso='shallow',
      use_py_process=False, use_instruction=False,
      total_environment_frames=10**9, inference_timeout_ms=5,
      checkpoint_secs=0, summary_secs=0, seed=5,
      controller='act', controller_policy=str(policy_path),
      controller_interval_secs=0.1, controller_replay_k_max=2,
      slo_spec=str(spec_path), slo_capture=False)
  drain = threading.Event()
  threading.Timer(7.0, drain.set).start()
  run = driver.train(cfg, stall_timeout_secs=30, drain_event=drain)
  # The actuator really moved (bounded at controller_replay_k_max).
  assert run.prefetcher.replay_k == 2
  assert run.controller is not None
  assert run.controller.counts()['applied'] >= 1
  log = ctl.read_log(str(tmp_path))
  assert log['mode'] == 'act'
  assert any(a['actuator'] == 'replay_k' and a['applied']
             for a in log['actions'])
  # The drain manifest names the controller's writes in the external
  # ledger (like slo_<name> burns) and carries the counts block.
  manifest = driver.read_resume_manifest(str(tmp_path))
  assert manifest is not None
  external = manifest['health']['external_incidents']
  assert external.get('controller_replay_k', 0) >= 1
  assert manifest['controller']['applied'] >= 1
  assert manifest['controller']['mode'] == 'act'
  # Incident stream carries the fsync'd controller_action records.
  with open(tmp_path / 'incidents.jsonl') as f:
    kinds = {json.loads(l)['kind'] for l in f if l.strip()}
  assert 'controller_action' in kinds


def test_enum_rule_without_target_fails_at_spinup(tmp_path):
  """Review fix: an enum rule missing `to` (or with a typo'd state)
  must fail at construction, not silently never fire / burn an apply
  error per cool-down."""
  engine = _StubEngine(overload=_obj())
  knob = _Knob('block')
  actuators = [ctl.Actuator('admission', kind='enum', get_fn=knob.get,
                            set_fn=knob.set,
                            values=('block', 'shed', 'grow'))]
  with pytest.raises(ValueError, match='needs a `to` target'):
    _controller(engine, [ctl.Rule(objective='overload',
                                  actuator='admission')],
                actuators, tmp_path)
  with pytest.raises(ValueError, match='not a legal state'):
    _controller(engine, [ctl.Rule(objective='overload',
                                  actuator='admission', to='shedd')],
                actuators, tmp_path)
  with pytest.raises(ValueError, match='not a legal state'):
    _controller(engine, [ctl.Rule(objective='overload',
                                  actuator='admission', to='shed',
                                  revert_to='blok')],
                actuators, tmp_path)


def test_opposing_rules_do_not_seesaw_a_shared_actuator(tmp_path):
  """Review fix: at most one engaged rule owns an actuator (first
  engaged wins, table order); a conflicting rule holds until the
  owner disengages instead of fighting it."""
  engine = _StubEngine(
      quorum=_obj(state=slo.BURNING, margin=-1.0),
      parked=_obj(state=slo.BURNING, margin=-1.0))
  knob = _Knob(4)
  grow = ctl.Rule(objective='quorum', actuator='fleet_size',
                  direction='up', step=1, cooldown_secs=1.0,
                  clear_margin=0.5)
  shrink = ctl.Rule(objective='parked', actuator='fleet_size',
                    direction='down', step=1, cooldown_secs=1.0,
                    clear_margin=0.5)
  c = _controller(engine, [grow, shrink], [
      ctl.Actuator('fleet_size', kind='int', get_fn=knob.get,
                   set_fn=knob.set, minimum=1, maximum=8)], tmp_path)
  # Both burning: only the FIRST rule (grow) moves the knob; shrink
  # holds — the knob walks monotonically up, never see-saws.
  for t in (0.0, 2.0, 4.0):
    c.tick(now=t)
  assert knob.sets == [5, 6, 7]
  # Grow clears and reverts to its baseline; shrink holds while the
  # knob is owned and may engage only once grow fully disengages (the
  # final revert releases ownership within that same tick).
  engine.snapshot['quorum'] = _obj(state=slo.OK, margin=0.9)
  for t in (6.0, 8.0, 10.0):
    c.tick(now=t)
  # The whole history is two clean monotone phases, never interleaved:
  # grow up 4->7, grow back 7->4, then shrink's first own move 4->3.
  assert knob.sets == [5, 6, 7, 6, 5, 4, 3]
  assert c.engaged_rules() == 1  # shrink owns the knob now
  # Shrink's objective clears: it reverts to ITS baseline (4).
  engine.snapshot['parked'] = _obj(state=slo.OK, margin=0.9)
  c.tick(now=12.0)
  assert knob.value == 4 and c.engaged_rules() == 0
  c.stop()


def test_validate_controller_ranges_and_crosslinks():
  from scalable_agent_tpu.config import Config, validate_controller
  with pytest.raises(ValueError):
    validate_controller(Config(controller='auto'))
  with pytest.raises(ValueError):
    validate_controller(Config(controller_replay_k_max=0))
  assert validate_controller(Config()) == []
  warned = validate_controller(Config(controller='act',
                                      slo_engine=False))
  assert any('disabled' in w for w in warned)
  warned = validate_controller(Config(controller='act'))
  assert any('clipped-target anchor' in w for w in warned)
  # Review fix: a probation cool-down longer than the idle-reaping
  # window with heartbeats off would get the cooling client reaped
  # mid-probation.
  warned = validate_controller(Config(remote_heartbeat_secs=0,
                                      remote_conn_idle_timeout_secs=20,
                                      fleet_probation_secs=60))
  assert any('mid-probation' in w for w in warned)

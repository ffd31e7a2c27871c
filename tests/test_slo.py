"""The round-14 SLO engine: declarative objectives, burn-rate
evaluation, triggered deep diagnostics, the per-run verdict, and the
scripts/slo_report.py regression gate.

The integration test is the acceptance bar: a tiny clean driver run
must land an all-pass SLO_VERDICT.json with every default objective
evaluated and ZERO captures; a run under a violating spec must land a
failing verdict naming the objective with the flight dump, trace
slice, and bounded profiler capture present in diagnostics/.
"""

import json
import os
import socket
import time

import numpy as np
import pytest

from scalable_agent_tpu import slo, telemetry
from scalable_agent_tpu.config import Config, validate_slo


def _snap(**metrics):
  return dict(metrics)


def _objective(**kw):
  base = dict(name='o', metric='t/m', comparison='<=', target=1.0,
              fast_window_secs=10.0, slow_window_secs=40.0)
  base.update(kw)
  return slo.Objective(**base)


# --------------------------------------------------------------------
# Objective spec + loading.
# --------------------------------------------------------------------


def test_default_objectives_load_and_validate():
  objectives = slo.load_objectives()
  names = [o.name for o in objectives]
  assert len(names) == len(set(names))
  assert 'policy_lag_p99' in names
  assert 'wire_crc_rejected_zero' in names
  for o in objectives:
    assert o.fast_window_secs and o.slow_window_secs
    assert o.severity in slo.SEVERITIES


def test_spec_file_roundtrip_and_window_defaults(tmp_path):
  spec = [dict(name='lag', metric='trace/policy_lag', field='p99',
               comparison='<=', target=3.0, severity='page'),
          dict(name='crc', metric='ingest/wire_crc_rejected',
               kind='rate', comparison='==', target=0.0,
               fast_window_secs=5.0, slow_window_secs=9.0)]
  path = tmp_path / 'spec.json'
  path.write_text(json.dumps(spec))
  objectives = slo.load_objectives(str(path), fast_window_secs=11.0,
                                   slow_window_secs=77.0)
  by_name = {o.name: o for o in objectives}
  assert by_name['lag'].fast_window_secs == 11.0   # default filled
  assert by_name['lag'].slow_window_secs == 77.0
  assert by_name['crc'].fast_window_secs == 5.0    # pinned wins
  assert by_name['crc'].severity == 'ticket'


@pytest.mark.parametrize('bad', [
    dict(name='x', metric='no_slash', comparison='<=', target=1.0),
    dict(name='x', metric='a/b', comparison='<', target=1.0),
    dict(name='x', metric='a/b', comparison='<=', target=1.0,
         severity='urgent'),
    dict(name='x', metric='a/b', comparison='<=', target=1.0,
         kind='delta'),
])
def test_bad_objectives_raise(tmp_path, bad):
  path = tmp_path / 'spec.json'
  path.write_text(json.dumps([bad]))
  with pytest.raises(ValueError):
    slo.load_objectives(str(path))


def test_duplicate_objective_names_raise(tmp_path):
  spec = [dict(name='x', metric='a/b', comparison='<=', target=1.0)] * 2
  path = tmp_path / 'spec.json'
  path.write_text(json.dumps(spec))
  with pytest.raises(ValueError, match='duplicate'):
    slo.load_objectives(str(path))


def test_unreadable_spec_raises(tmp_path):
  with pytest.raises(OSError):
    slo.load_objectives(str(tmp_path / 'missing.json'))
  bad = tmp_path / 'bad.json'
  bad.write_text('{}')
  with pytest.raises(ValueError):
    slo.load_objectives(str(bad))


def test_validate_slo_ranges_and_crosslinks():
  with pytest.raises(ValueError):
    validate_slo(Config(slo_fast_window_secs=0))
  with pytest.raises(ValueError):
    validate_slo(Config(slo_capture_steps=0))
  assert validate_slo(Config()) == []
  warned = validate_slo(Config(telemetry_trace=False))
  assert any('no_data' in w for w in warned)
  warned = validate_slo(Config(slo_fast_window_secs=400.0))
  assert any('slow window' in w for w in warned)
  # An explicit interval too coarse for the fast window leaves value
  # objectives structurally unable to burn.
  warned = validate_slo(Config(slo_interval_secs=30.0,
                               slo_fast_window_secs=30.0))
  assert any('unable to fire' in w for w in warned)
  warned = validate_slo(Config(slo_engine=False, slo_spec='x.json'))
  assert any('nothing will judge' in w for w in warned)


# --------------------------------------------------------------------
# Burn-rate evaluation.
# --------------------------------------------------------------------


def test_value_objective_multiwindow_burn_semantics():
  ev = slo.SloEvaluator([_objective(comparison='<=', target=1.0)],
                        min_samples=3)
  t0 = 1000.0
  # Two bad samples: below min_samples, no burn yet.
  assert ev.observe(_snap(**{'t/m': 5.0}), now=t0) == []
  assert ev.observe(_snap(**{'t/m': 5.0}), now=t0 + 2) == []
  # Third bad sample: fast window fully violating, slow >= half.
  assert ev.observe(_snap(**{'t/m': 5.0}), now=t0 + 4) == ['o']
  state = ev.verdict()['objectives']['o']
  assert state['state'] == slo.BURNING and state['burns'] == 1
  # A healthy sample inside the fast window ends the burn...
  assert ev.observe(_snap(**{'t/m': 0.5}), now=t0 + 6) == []
  assert ev.verdict()['objectives']['o']['state'] == slo.OK
  # ...and a NEW burn is a second episode, not a re-entry.
  for i in range(3):
    newly = ev.observe(_snap(**{'t/m': 9.0}), now=t0 + 20 + i)
  assert newly == ['o']
  assert ev.verdict()['objectives']['o']['burns'] == 2


def test_value_objective_blip_does_not_burn():
  """One bad sample among healthy ones must never burn (the fast
  window must be FULLY violating)."""
  ev = slo.SloEvaluator([_objective(comparison='<=', target=1.0)],
                        min_samples=3)
  t0 = 1000.0
  for i, v in enumerate([0.2, 0.3, 9.0, 0.2, 0.1]):
    assert ev.observe(_snap(**{'t/m': v}), now=t0 + i) == []
  assert ev.verdict()['pass']


def test_slow_window_confirms_sustained_burn():
  """Fast window fully violating but the slow window mostly healthy:
  not a burn yet (the multi-window gate)."""
  o = _objective(comparison='<=', target=1.0, fast_window_secs=3.0,
                 slow_window_secs=30.0)
  ev = slo.SloEvaluator([o], min_samples=2)
  t0 = 1000.0
  # 8 healthy samples fill the slow window...
  for i in range(8):
    ev.observe(_snap(**{'t/m': 0.1}), now=t0 + i)
  # ...then 2 bad samples fill the fast window: slow is 2/10 bad.
  assert ev.observe(_snap(**{'t/m': 5.0}), now=t0 + 8) == []
  assert ev.observe(_snap(**{'t/m': 5.0}), now=t0 + 9) == []
  assert ev.verdict()['objectives']['o']['state'] == slo.OK
  # The burn confirms once half the slow window is violating.
  newly = []
  for i in range(10, 22):
    newly += ev.observe(_snap(**{'t/m': 5.0}), now=t0 + i)
  assert newly == ['o']


def test_rate_objective_burns_on_counter_movement():
  o = _objective(name='crc', metric='ingest/wire_crc_rejected',
                 kind='rate', comparison='==', target=0.0)
  ev = slo.SloEvaluator([o])
  t0 = 1000.0
  assert ev.observe(_snap(**{'ingest/wire_crc_rejected': 0}),
                    now=t0) == []
  assert ev.observe(_snap(**{'ingest/wire_crc_rejected': 0}),
                    now=t0 + 1) == []
  assert ev.verdict()['objectives']['crc']['state'] == slo.OK
  # Any movement inside the fast window burns.
  assert ev.observe(_snap(**{'ingest/wire_crc_rejected': 2}),
                    now=t0 + 2) == ['crc']
  entry = ev.verdict()['objectives']['crc']
  assert entry['value'] == 2  # the window delta
  # Once the bump ages out of the fast window the burn ends, but the
  # episode stays on the ledger (the verdict still fails).
  ev.observe(_snap(**{'ingest/wire_crc_rejected': 2}), now=t0 + 30)
  ev.observe(_snap(**{'ingest/wire_crc_rejected': 2}), now=t0 + 31)
  verdict = ev.verdict()
  assert verdict['objectives']['crc']['state'] == slo.OK
  assert not verdict['pass'] and verdict['violations'] == ['crc']


def test_rate_objective_per_second_floor():
  """kind='rate' with >= judges the per-second rate (the fps floor
  shape) with slow-window confirmation: a short stall whose slow
  window still clears the floor is a blip, not a burn; a sustained
  stall burns."""
  o = _objective(name='fps', metric='driver/env_frames', kind='rate',
                 comparison='>=', target=100.0)
  ev = slo.SloEvaluator([o])
  t0 = 1000.0
  ev.observe(_snap(**{'driver/env_frames': 0}), now=t0)
  assert ev.observe(_snap(**{'driver/env_frames': 2000}),
                    now=t0 + 5) == []   # 400/s >= 100
  # Short stall: the fast window (10 s) collapses below the floor,
  # but the slow window (40 s) still averages above it — no burn
  # (a checkpoint save must not fail the run).
  assert ev.observe(_snap(**{'driver/env_frames': 2000}),
                    now=t0 + 12) == []
  assert ev.observe(_snap(**{'driver/env_frames': 2005}),
                    now=t0 + 18) == []
  assert ev.verdict()['objectives']['fps']['state'] == slo.OK
  # SUSTAINED stall: both windows' rates collapse — burn, once.
  newly = []
  for i in (24, 30, 36, 42, 48):
    newly += ev.observe(_snap(**{'driver/env_frames': 2005 + i}),
                        now=t0 + i)
  assert newly == ['fps']
  assert ev.verdict()['objectives']['fps']['state'] == slo.BURNING


def test_missing_and_nan_metrics_are_no_data():
  hist = telemetry.Histogram('t/h')  # empty -> NaN percentiles
  o1 = _objective(name='absent', metric='t/never')
  o2 = _objective(name='nan', metric='t/h', field='p99')
  ev = slo.SloEvaluator([o1, o2])
  ev.observe(_snap(**{'t/h': hist.snapshot_value()}))
  verdict = ev.verdict()
  assert verdict['objectives']['absent']['state'] == slo.NO_DATA
  assert verdict['objectives']['nan']['state'] == slo.NO_DATA
  assert verdict['pass']


def test_histogram_field_selection():
  o = _objective(metric='trace/policy_lag', field='p99',
                 comparison='<=', target=4.0)
  ev = slo.SloEvaluator([o], min_samples=2)
  h = telemetry.Histogram('trace/policy_lag')
  for v in (1, 1, 9, 9, 9, 9):
    h.observe(v)
  t0 = 1000.0
  for i in range(3):
    ev.observe(_snap(**{'trace/policy_lag': h.snapshot_value()}),
               now=t0 + i)
  entry = ev.verdict()['objectives']['o']
  assert entry['state'] == slo.BURNING and entry['value'] == 9


def test_baseline_relative_target_and_no_baseline(tmp_path):
  o = _objective(name='fps_floor', metric='driver/env_frames',
                 kind='rate', comparison='>=', target=0.5,
                 baseline='fps')
  # No baseline: evaluated, never a violation.
  ev = slo.SloEvaluator([o])
  ev.observe(_snap(**{'driver/env_frames': 0}), now=1000.0)
  ev.observe(_snap(**{'driver/env_frames': 10}), now=1001.0)
  verdict = ev.verdict()
  assert verdict['objectives']['fps_floor']['state'] == slo.NO_BASELINE
  assert verdict['pass']
  # With a baseline of 100 fps, the effective floor is 50/s.
  ev = slo.SloEvaluator([o], baseline={'fps': 100.0})
  ev.observe(_snap(**{'driver/env_frames': 0}), now=1000.0)
  assert ev.observe(_snap(**{'driver/env_frames': 10}),
                    now=1001.0) == ['fps_floor']
  assert ev.verdict()['objectives']['fps_floor']['target'] == 50.0


def test_baseline_file_roundtrip(tmp_path):
  path = str(tmp_path / 'baseline.json')
  assert slo.load_baseline(path) == {}           # absent file
  assert slo.load_baseline('') == {}             # disabled
  slo.update_baseline(path, {'fps': 123.0}, host='h1')
  slo.update_baseline(path, {'fps': 456.0}, host='h2')
  assert slo.load_baseline(path, host='h1')['fps'] == 123.0
  assert slo.load_baseline(path, host='h2')['fps'] == 456.0
  assert slo.load_baseline(path, host='h3') == {}


def test_corrupt_baseline_file_raises(tmp_path):
  """A PRESENT but unparseable baseline file must fail at spin-up,
  not silently disarm the fps_floor objective (the --slo_spec
  fail-fast rule)."""
  path = tmp_path / 'baseline.json'
  path.write_text('{not json')
  with pytest.raises(ValueError, match='baseline'):
    slo.load_baseline(str(path))


def test_info_severity_never_fails_the_verdict():
  o = _objective(name='advisory', severity='info', comparison='<=',
                 target=1.0)
  ev = slo.SloEvaluator([o], min_samples=2)
  t0 = 1000.0
  for i in range(4):
    ev.observe(_snap(**{'t/m': 9.0}), now=t0 + i)
  verdict = ev.verdict()
  assert verdict['objectives']['advisory']['burns'] >= 1
  assert verdict['pass'] and verdict['violations'] == []


# --------------------------------------------------------------------
# The engine: emission, captures, verdict file.
# --------------------------------------------------------------------


class _FakeWriter:
  def __init__(self):
    self.scalars = []

  def scalar(self, tag, value, step):
    self.scalars.append((tag, value, step))


class _FakeIncidents:
  def __init__(self):
    self.events = []

  def event(self, kind, step=None, **fields):
    self.events.append(dict(kind=kind, step=step, **fields))


def _page_objective(metric='t/page'):
  return _objective(name='page_o', metric=metric, severity='page',
                    kind='rate', comparison='==', target=0.0,
                    fast_window_secs=30.0, slow_window_secs=60.0)


def test_engine_emits_once_and_captures_once(tmp_path):
  reg = telemetry.MetricsRegistry()
  c = reg.counter('t/page')
  flight = telemetry.FlightRecorder()
  flight.record({'k': 'batch', 'step': 1})
  writer, incidents = _FakeWriter(), _FakeIncidents()
  slices = []

  def fake_slice(logdir, window, out_path, state):
    slices.append(out_path)
    with open(out_path, 'w') as f:
      json.dump({'sliced': True}, f)
    return True

  engine = slo.SloEngine([_page_objective()], str(tmp_path),
                         registry=reg, writer=writer,
                         incidents=incidents, flight=flight,
                         interval_secs=60.0,  # thread stays quiet
                         trace_slice_fn=fake_slice)
  engine.start()
  try:
    c.inc(3)
    assert engine.observe() == ['page_o']
    # Still burning on the next tick: no duplicate emission/capture.
    assert engine.observe() == []
    # Artifacts are written by the ENGINE thread's drain (or
    # finalize) — never inline on the observing (driver) thread.
    engine.flush_captures()
    kinds = [e['kind'] for e in incidents.events]
    assert kinds.count('slo_violation') == 1
    assert kinds.count('slo_capture') == 1
    assert [t for t, _, _ in writer.scalars] == ['slo_violations']
    # The capture artifacts landed.
    flight_path = tmp_path / 'diagnostics' / 'slo_flight_page_o.json'
    assert flight_path.exists()
    assert json.load(open(flight_path))['records'][0]['step'] == 1
    assert slices and os.path.exists(slices[0])
    # Exactly one queued profiler request, handed over once.
    assert engine.take_profile_request() == 'page_o'
    assert engine.take_profile_request() is None
    engine.note_profile('page_o', '/some/dir')
    verdict = engine.verdict()
    assert verdict['captures']['page_o']['profile'] == '/some/dir'
    assert not verdict['pass']
  finally:
    engine.stop()


def test_engine_feeds_health_external_ledger(tmp_path):
  from scalable_agent_tpu import health as health_lib
  reg = telemetry.MetricsRegistry()
  c = reg.counter('t/page')
  monitor = health_lib.HealthMonitor()
  engine = slo.SloEngine([_page_objective()], str(tmp_path),
                         registry=reg, health=monitor,
                         capture=False, interval_secs=60.0)
  engine.start()
  try:
    c.inc()
    engine.observe()
    assert monitor.external_incidents == {'slo_page_o': 1}
  finally:
    engine.stop()


def test_engine_registry_gauges_and_unregister(tmp_path):
  reg_global = telemetry.registry()
  engine = slo.SloEngine([_page_objective()], str(tmp_path),
                         registry=telemetry.MetricsRegistry(),
                         capture=False, interval_secs=60.0)
  assert reg_global.get('slo/burning') is not None
  engine.stop()
  assert reg_global.get('slo/burning') is None


def test_finalize_writes_verdict_json(tmp_path):
  reg = telemetry.MetricsRegistry()
  reg.counter('t/page')
  engine = slo.SloEngine([_page_objective()], str(tmp_path),
                         registry=reg, capture=False,
                         interval_secs=60.0)
  engine.start()
  time.sleep(0.05)
  engine.stop()
  verdict = engine.finalize(extra={'clean_exit': True})
  path = tmp_path / 'SLO_VERDICT.json'
  assert path.exists()
  on_disk = json.load(open(path))
  assert on_disk['pass'] == verdict['pass'] is True
  assert on_disk['clean_exit'] is True
  assert 'page_o' in on_disk['objectives']
  assert slo.read_verdict(str(tmp_path))['pass'] is True


# --------------------------------------------------------------------
# scripts/slo_report.py: the go/no-go gate.
# --------------------------------------------------------------------


def _write_verdict(tmp_path, passing=True, violations=()):
  objectives = {
      'policy_lag_p99': {'name': 'policy_lag_p99', 'severity': 'page',
                         'state': 'ok', 'value': 1.0, 'target': 8.0,
                         'margin': 7.0, 'burns': 0,
                         'metric': 'trace/policy_lag'}}
  for v in violations:
    objectives[v] = {'name': v, 'severity': 'page', 'state': 'ok',
                     'value': 3, 'target': 0.0, 'margin': -3,
                     'burns': 1, 'metric': 'x/y'}
  verdict = {'pass': passing, 'violations': sorted(violations),
             'objectives': objectives, 'captures': {}}
  with open(os.path.join(tmp_path, 'SLO_VERDICT.json'), 'w') as f:
    json.dump(verdict, f)


def test_slo_report_gates_on_verdict(tmp_path, capsys):
  from scripts import slo_report
  _write_verdict(str(tmp_path), passing=True)
  assert slo_report.main([str(tmp_path)]) == 0
  _write_verdict(str(tmp_path), passing=False,
                 violations=['wire_crc_rejected_zero'])
  assert slo_report.main([str(tmp_path)]) == 1
  out = capsys.readouterr().out
  assert 'FAIL' in out and 'wire_crc_rejected_zero' in out


def test_slo_report_missing_verdict_exits_2(tmp_path):
  from scripts import slo_report
  assert slo_report.main([str(tmp_path)]) == 2


def test_slo_report_bench_gate_against_history(tmp_path, capsys):
  from scripts import slo_report
  _write_verdict(str(tmp_path), passing=True)
  history = tmp_path / 'HISTORY.md'
  history.write_text(
      '| round | headline |\n|---|---|\n'
      '| r1 | 313,838 fps | x |\n| r2 | 299,736 fps | y |\n')
  bench = tmp_path / 'BENCH_OUT.json'
  # A real (non-SMOKE) artifact below the floor fails the gate.
  bench.write_text(json.dumps(
      {'value': 200000.0, 'unit': 'env-frames/sec (deep)'}))
  rc = slo_report.main([str(tmp_path), '--bench', str(bench),
                        '--history', str(history)])
  assert rc == 1
  assert 'regression floor' in capsys.readouterr().out
  # Within tolerance: passes (baseline = max row = 313,838).
  bench.write_text(json.dumps(
      {'value': 310000.0, 'unit': 'env-frames/sec (deep)'}))
  assert slo_report.main([str(tmp_path), '--bench', str(bench),
                          '--history', str(history)]) == 0
  # SMOKE artifacts skip the gate with a note.
  bench.write_text(json.dumps({'value': 5.0, 'unit': 'fps (SMOKE)'}))
  assert slo_report.main([str(tmp_path), '--bench', str(bench),
                          '--history', str(history)]) == 0


def test_slo_report_history_parser_and_no_default_history(tmp_path):
  from scripts import slo_report
  history = tmp_path / 'HISTORY.md'
  history.write_text(
      '# Bench history\n\n| round | headline | note |\n|---|---|---|\n'
      '| r3 | 299,736 fps | a |\n| r4 | 320,260 fps | best |\n'
      '| r6 | (driver-recorded post-round) | no number yet |\n')
  baseline, rows = slo_report.load_history_baseline(str(history))
  assert (baseline, rows) == (320260.0, 2)
  assert slo_report.load_history_baseline(
      str(tmp_path / 'absent.md')) == (None, 0)
  # No --history: the repo ships no chip history to gate against, so
  # the bench gate is skipped instead of judging against nothing.
  _write_verdict(str(tmp_path), passing=True)
  bench = tmp_path / 'BENCH_OUT.json'
  bench.write_text(json.dumps(
      {'value': 1.0, 'unit': 'env-frames/sec (deep)'}))
  assert slo_report.main([str(tmp_path), '--bench', str(bench)]) == 0


def test_slo_report_updates_fps_baseline(tmp_path, capsys):
  from scripts import slo_report
  _write_verdict(str(tmp_path), passing=True)
  with open(os.path.join(tmp_path, 'summaries.jsonl'), 'w') as f:
    for i, fps in enumerate([10.0, 100.0, 120.0, 110.0]):
      f.write(json.dumps({'tag': 'env_frames_per_sec', 'value': fps,
                          'step': i, 'wall_time': 0}) + '\n')
  baseline_path = str(tmp_path / 'baseline.json')
  assert slo_report.main([str(tmp_path), '--update-fps-baseline',
                          baseline_path]) == 0
  entry = slo.load_baseline(baseline_path)
  # The recorded floor is the median of the SECOND HALF of the
  # samples ([120, 110] -> upper median 120): warmup excluded.
  assert entry['fps'] == pytest.approx(120.0)


# --------------------------------------------------------------------
# scripts/fleet_stats.py: the live operator CLI.
# --------------------------------------------------------------------


def test_fleet_stats_cli_against_live_ingest(capsys):
  from scalable_agent_tpu.runtime import remote, ring_buffer
  from scripts import fleet_stats
  from tests.test_telemetry import _tiny_unroll
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1')
  try:
    # One real unroll so the counters are non-trivial.
    client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    client.send_unroll(_tiny_unroll(1))
    client.close()
    rc = fleet_stats.main([f'127.0.0.1:{server.port}'])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'metrics registry' in out
    assert 'ingest/unrolls' in out and 'ingest server' in out
    rc = fleet_stats.main([f'127.0.0.1:{server.port}', '--json'])
    parsed = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert parsed['ingest']['unrolls'] == 1
    assert parsed['registry']['ingest/unrolls'] == 1
  finally:
    server.close()
    buffer.close()


def test_fleet_stats_cli_unreachable_host_exits_1(capsys):
  from scripts import fleet_stats
  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  rc = fleet_stats.main([f'127.0.0.1:{port}', '--timeout', '0.5'])
  assert rc == 1
  assert 'could not fetch' in capsys.readouterr().err


# --------------------------------------------------------------------
# Acceptance: the driver writes the verdict; captures fire end to end.
# --------------------------------------------------------------------


_DRIVER_BASE = dict(
    env_backend='bandit', num_actors=2, batch_size=2, unroll_length=5,
    num_action_repeats=1, episode_length=4, height=24, width=32,
    torso='shallow', use_py_process=False, use_instruction=False,
    total_environment_frames=10**9, inference_timeout_ms=5,
    checkpoint_secs=0, summary_secs=0, seed=7)


def test_clean_driver_run_all_pass_verdict_zero_captures(tmp_path):
  from scalable_agent_tpu import driver
  driver.train(Config(logdir=str(tmp_path), **_DRIVER_BASE),
               max_steps=5, stall_timeout_secs=60)
  verdict = slo.read_verdict(str(tmp_path))
  assert verdict is not None
  assert verdict['pass'], verdict['violations']
  assert verdict['captures'] == {}
  assert set(verdict['objectives']) == {
      o.name for o in slo.DEFAULT_OBJECTIVES}
  for name, e in verdict['objectives'].items():
    # info objectives are ADVISORY leading indicators (round 15: the
    # controller's triggers) — a toy env-bound run legitimately burns
    # learner_plane_utilization without failing anything.
    assert (e['state'] in (slo.OK, slo.NO_DATA, slo.NO_BASELINE)
            or e['severity'] == 'info'), (name, e)
  assert verdict['clean_exit'] is True
  # Zero captures = an empty diagnostics footprint.
  diag = tmp_path / 'diagnostics'
  assert not diag.exists() or not any(
      p.name.startswith('slo_') for p in diag.iterdir())


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_violating_run_fails_verdict_with_triggered_capture(tmp_path):
  """A page-severity burn mid-run lands the failing verdict AND all
  three capture artifacts (flight dump, trace slice, bounded profiler
  trace) under diagnostics/ — rate-limited to one capture."""
  from scalable_agent_tpu import driver
  spec = [dict(name='impossible_floor',
               metric='driver/env_plane_utilization',
               comparison='>=', target=2.0, severity='page',
               fast_window_secs=1.0, slow_window_secs=4.0)]
  spec_path = tmp_path / 'spec.json'
  spec_path.write_text(json.dumps(spec))
  cfg = Config(logdir=str(tmp_path),
               **dict(_DRIVER_BASE, slo_spec=str(spec_path),
                      slo_interval_secs=0.25, slo_capture_steps=2))
  driver.train(cfg, max_steps=30, stall_timeout_secs=60)
  verdict = slo.read_verdict(str(tmp_path))
  assert verdict is not None and not verdict['pass']
  assert verdict['violations'] == ['impossible_floor']
  cap = verdict['captures']['impossible_floor']
  assert cap['flight'] and os.path.exists(cap['flight'])
  assert cap['trace_slice'] and os.path.exists(cap['trace_slice'])
  assert cap['profile'] and os.path.isdir(cap['profile'])
  assert any(os.scandir(cap['profile']))  # profiler wrote a trace
  sliced = json.load(open(cap['trace_slice']))
  assert sliced['slo_objective']['name'] == 'impossible_floor'
  # Structured violations reached both streams.
  with open(tmp_path / 'incidents.jsonl') as f:
    kinds = [json.loads(l)['kind'] for l in f if l.strip()]
  assert 'slo_violation' in kinds and 'slo_capture' in kinds
  with open(tmp_path / 'summaries.jsonl') as f:
    tags = {json.loads(l)['tag'] for l in f if l.strip()}
  assert 'slo_violations' in tags
  # slo_report exits nonzero on the failing verdict.
  from scripts import slo_report
  assert slo_report.main([str(tmp_path)]) == 1


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_slo_engine_off_writes_no_verdict(tmp_path):
  from scalable_agent_tpu import driver
  driver.train(Config(logdir=str(tmp_path),
                      **dict(_DRIVER_BASE, slo_engine=False)),
               max_steps=3, stall_timeout_secs=60)
  assert slo.read_verdict(str(tmp_path)) is None


# --------------------------------------------------------------------
# Round 15: the controller's locked snapshot API — burning()/margins
# read from a second thread must be self-consistent mid-evaluation.
# --------------------------------------------------------------------


def test_control_snapshot_consistent_mid_evaluation(tmp_path):
  """Two objectives judge the SAME gauge with opposite comparisons;
  a torn (unlocked) read could catch one objective re-judged against
  the new value while the other still carries the old one — the
  locked control_snapshot must never show that."""
  import threading

  from scalable_agent_tpu import telemetry

  reg = telemetry.MetricsRegistry()
  gauge = reg.gauge('ctl/x')
  objectives = [
      slo.Objective(name='low', metric='ctl/x', comparison='<=',
                    target=1.0, fast_window_secs=1.0,
                    slow_window_secs=2.0),
      slo.Objective(name='high', metric='ctl/x', comparison='>=',
                    target=1.0, fast_window_secs=1.0,
                    slow_window_secs=2.0),
  ]
  engine = slo.SloEngine(objectives, str(tmp_path), registry=reg,
                         capture=False, min_samples=2)
  stop = threading.Event()
  torn = []

  def reader():
    while not stop.is_set():
      snap = engine.control_snapshot()
      low, high = snap['low'], snap['high']
      # The one invariant a torn read would break: inside ONE
      # snapshot both objectives were judged against the SAME sample.
      if (low['value'] is not None and high['value'] is not None
          and low['value'] != high['value']):
        torn.append((low['value'], high['value']))
      if (low['state'] == slo.BURNING
          and high['state'] == slo.BURNING):
        torn.append(('both-burning', low['value'], high['value']))
      engine.burning()  # the locked list API must not deadlock

  t = threading.Thread(target=reader)
  t.start()
  try:
    now = 1000.0
    for phase in range(60):
      value = 5.0 if phase % 2 == 0 else 0.0
      gauge.set(value)
      for _ in range(8):
        now += 0.3
        engine.observe(now=now)
  finally:
    stop.set()
    t.join(timeout=10)
    engine.stop()
  assert torn == []
  # And the snapshot carries the control fields the policy table
  # reads.
  snap = engine.control_snapshot()
  for entry in snap.values():
    for key in ('state', 'value', 'margin', 'severity', 'burns'):
      assert key in entry

"""The way from a span or a scope in the program to a metric's value,
from the CPU side: the program's spans on the trace (on its clock
through the landmark, on the host's without one), the three readers on
traces made by hand, the scope line from a profile encoded by hand,
and `capture_report.py` over what `observability.ProfilerCapture`
leaves: one made here, and the `--profile_dir` window of one fleet run
at a cell's rehearsal sizes."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import (loader, program_spans, trace_reduce,
                               trace_scopes)
from benchmark.readers import (span_idle_overlap, span_stat,
                               trace_scope_share)
from benchmark import capture_report
from scalable_agent_tpu import observability, telemetry

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
US = 1_000  # rows are written in microseconds, the trace is in ns
LANDMARK = r'^jit_capture_clock_sync\b'


def _taken(rows, armed=0, taken=1000):
  """What telemetry.take_spans() hands over, for rows (name, t0_us,
  t1_us, thread)."""
  return {'clock': {'perf_ns': armed * US, 'wall_ns': 0},
          'taken_ns': taken * US, 'dropped': 0, 'threads': {},
          'spans': [(name, t0 * US, t1 * US, thread, None)
                    for name, t0, t1, thread in rows]}


ACTORS = [
    # thread 1: a step of 100 us holding a 60 us park and a 20 us
    # pipe (inside a 30 us env step); then a step of 50 us alone
    ('actor/step', 0, 100, 1), ('batcher/compute', 10, 70, 1),
    ('actor/env_step', 70, 100, 1), ('env/pipe', 75, 95, 1),
    ('actor/step', 100, 150, 1),
    # thread 2: a step of 200 us, all but 10 us of it parked; thread
    # 1's spans overlap it in time and are none of its children
    ('actor/step', 0, 200, 2), ('batcher/compute', 5, 195, 2)]


def _trace_with(rows, **kwargs):
  trace = trace_reduce.Trace({})  # a CPU has no device plane
  assert not program_spans.join(trace, _taken(rows, **kwargs), 0,
                                LANDMARK)
  return trace


def test_span_stat_p50_mean_minus_busy_share_and_per_step():
  obs = {'trace': _trace_with(ACTORS)}
  step = dict(span='actor/step')
  assert span_stat.read(obs, stat='mean', **step) == pytest.approx(
      (100 + 50 + 200) / 3 / 1e3)
  assert span_stat.read(obs, stat='p50', **step) == pytest.approx(0.1)
  minus = ['batcher/compute', 'env/pipe']
  # Self times 100-60-20, 50, 200-190: children by containment on the
  # span's OWN thread.
  assert span_stat.read(obs, stat='mean', minus=minus,
                        **step) == pytest.approx((20 + 50 + 10) / 3e3)
  assert span_stat.read(obs, stat='p50', minus=minus,
                        **step) == pytest.approx(0.02)
  # 80 us of self time in an armed interval of 1000 us.
  assert span_stat.read(obs, stat='busy_share', minus=minus,
                        **step) == pytest.approx(8.0)
  # A child listed with ITS child is taken out once (the union).
  assert span_stat.read(
      obs, stat='mean', minus=['actor/env_step', 'env/pipe'],
      **step) == pytest.approx((70 + 50 + 200) / 3e3)
  assert span_stat.read(obs, span='env/pipe', stat='per_step',
                        per='actor/step') == pytest.approx(0.02 / 3)
  assert span_stat.read(obs, span='no/such', stat='p50') is None
  assert span_stat.read(obs, span='env/pipe', stat='per_step',
                        per='no/such') is None
  assert span_stat.read({}, span='actor/step', stat='p50') is None
  with pytest.raises(ValueError):
    span_stat.read(obs, span='actor/step', stat='p51')


def test_span_idle_overlap_is_the_idle_time_inside_one_span_name():
  trace = trace_reduce.Trace.from_rows([
      (p, l, n, s * US, d * US) for p, l, n, s, d in [
          # busy 100..200 and 600..700 of a slice 0..1000: idle 800
          ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[] fusion',
           100, 100),
          ('/device:TPU:0', 'XLA Ops', '%fusion.2 = f32[] fusion',
           600, 100),
          # the landmark: host time 0 is trace time 0
          ('/device:TPU:0', 'XLA Modules', 'jit_capture_clock_sync(1)',
           0, 0)]])
  assert program_spans.join(trace, _taken([
      # waits on two threads: their UNION covers 0..150 and 400..650,
      # of which 0..100 and 400..600 are idle
      ('inference/wait_batch', 0, 150, 1),
      ('inference/wait_batch', 50, 120, 2),
      ('inference/wait_batch', 400, 650, 1),
      ('inference/dispatch', 150, 160, 1)]), 0, LANDMARK)
  obs = {'trace': trace}
  assert span_idle_overlap.read(
      obs, span='inference/wait_batch') == pytest.approx(
          100.0 * 300 / 800)
  assert span_idle_overlap.read(
      obs, span='inference/dispatch') == pytest.approx(0.0)
  assert span_idle_overlap.read(obs, span='no/such') is None
  # No device plane (a rehearsal): nothing to read.
  assert span_idle_overlap.read(
      {'trace': _trace_with(ACTORS)}, span='actor/step') is None


def test_trace_scope_share_is_self_time_by_scope_inside_one_program():
  step, other = 'jit_train_step(7)', 'jit_carry_step(9)'
  scopes = trace_scopes.SCOPES_LINE
  torso = 'jit(train_step)/jvp(ImpalaAgent)/torso/conv'
  back = 'jit(train_step)/transpose(jvp(ImpalaAgent))/torso/conv'
  rows = [
      ('XLA Modules', step, 0, 400), ('XLA Modules', other, 500, 100),
      # a while of 100 (core) whose body holds 90: self time 10
      (scopes, 'jit(train_step)/jvp(ImpalaAgent)/core/while', 0, 100),
      (scopes, 'jit(train_step)/jvp(ImpalaAgent)/core/body', 0, 90),
      (scopes, torso, 100, 150), (scopes, back, 250, 100),
      (scopes, 'jit(train_step)/jvp(vtrace)/scan', 350, 40),
      (scopes, trace_scopes.NO_SCOPE, 390, 10),
      # another program's torso does not count
      (scopes, 'jit(carry_step)/ImpalaAgent/torso/conv', 500, 100)]
  trace = trace_reduce.Trace.from_rows(
      [('/device:TPU:0', l, n, s * US, d * US) for l, n, s, d in rows])

  def share(scope):
    return trace_scope_share.read(
        {'trace': trace}, module_regex=r'^jit_train_step\b',
        scope_regex=rf'(^|[/(]){scope}([/)]|$)')

  assert share('torso') == pytest.approx(100.0 * 250 / 400)
  assert share('core') == pytest.approx(100.0 * 100 / 400)
  assert share('vtrace') == pytest.approx(100.0 * 40 / 400)
  assert share('tors') == pytest.approx(0.0)  # whole path elements
  assert trace_scope_share.read(
      {'trace': trace}, module_regex='^jit_nothing', scope_regex='x'
  ) is None
  assert trace_scope_share.read(
      {'trace': _trace_with(ACTORS)}, module_regex='.', scope_regex='.'
  ) is None


# --- From the recorder to the trace. ---


def _device_rows():
  return [
      ('/device:TPU:0', 'XLA Modules', 'jit_capture_clock_sync(9)', 400,
       100),
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[] fusion',
       1000 * US, 100 * US)]


PROGRAM = [
    ('inference/wait_batch', 7_000, 7_900, 1),
    ('inference/dispatch', 7_900, 7_950, 1),
    ('learner/wait_batch', 7_000, 8_000, 3),
    # an actor's park is the SHORTEST span over the gap's middle
    ('batcher/compute', 7_400, 7_500, 2),
    ('actor/step', 7_390, 7_510, 2)]


def test_feeding_spans_reach_idle_gaps_and_actor_spans_do_not():
  """The spans of the threads that feed the device reach the trace's
  clock through the landmark in the one add_host_spans call; every
  span goes on the `program` lines."""
  trace = trace_reduce.Trace.from_rows(_device_rows())
  taken = _taken(PROGRAM, armed=7_000, taken=8_000)
  sync_ns = 7_000 * US  # the host saw the landmark end then
  assert {name for name, _, _ in program_spans.feeding(taken)} == {
      'inference/wait_batch', 'inference/dispatch',
      'learner/wait_batch'}
  assert program_spans.join(trace, taken, sync_ns, LANDMARK)
  # The trace put the landmark's end at 500 ns.
  offset = 500 - sync_ns
  host = trace.host_spans()
  assert host['bench:inference/wait_batch'][0][0] == 500
  assert 'bench:batcher/compute' not in host
  assert 'bench:actor/step' not in host
  assert trace_reduce.traced_window(trace) == (500.0, 500.0 + 1e6)
  # The device is idle from 0.5 to 1000 us: the gap's middle lies
  # under the server's wait, the learner's longer wait and an actor's
  # shorter park. The park is not a candidate.
  gaps = dict(trace_reduce.idle_gaps(trace))
  assert list(gaps) == ['bench:inference/wait_batch']
  # Every span, actor side included, on the program lines and on the
  # trace's clock.
  assert program_spans.armed_interval(trace) == (500.0, 500.0 + 1e6)
  threads = program_spans.threads(trace)
  assert len(threads) == 3
  names = {n for ev in threads for n in ev.names}
  assert names == {name for name, _, _, _ in PROGRAM}
  (park,) = [ev for ev in threads if 'batcher/compute' in ev.names]
  starts, ends = program_spans.intervals(park, {'batcher/compute'})
  assert starts[0] == 7_400 * US + offset
  assert ends[0] == 7_500 * US + offset
  assert span_stat.read({'trace': trace}, span='actor/step',
                        stat='mean', minus=['batcher/compute']
                        ) == pytest.approx(0.02)
  # Idle from the slice's start at 0.5 us to the operation at 1000.
  assert span_idle_overlap.read(
      {'trace': trace}, span='inference/wait_batch'
  ) == pytest.approx(100.0 * 900 / 999.5)


def test_without_a_landmark_the_program_lines_are_on_the_hosts_clock():
  trace = trace_reduce.Trace({})  # a CPU has no device plane
  taken = _taken(PROGRAM, armed=7_000, taken=8_000)
  assert not program_spans.join(trace, taken, 7_000 * US, LANDMARK)
  assert not trace.chips() and not trace.host_spans()
  assert program_spans.armed_interval(trace) == (7e6, 8e6)
  assert span_stat.read({'trace': trace}, span='inference/dispatch',
                        stat='p50') == pytest.approx(0.05)


def test_a_capture_without_rows_joins_nothing():
  """Another `take_spans()` got there first: the capture's spans.json
  holds the landmark alone."""
  trace = trace_reduce.Trace.from_rows(_device_rows())
  assert not program_spans.join(trace, {}, 7_000 * US, LANDMARK)
  assert not program_spans.join(trace, None, 7_000 * US, LANDMARK)
  assert not trace.host_spans() and not program_spans.threads(trace)
  assert span_stat.read({'trace': trace}, span='actor/step',
                        stat='mean') is None


def _varint(n):
  out = bytearray()
  while True:
    out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
    n >>= 7
    if not n:
      return bytes(out)


def _bytes_field(number, payload):
  if isinstance(payload, str):
    payload = payload.encode()
  return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _int_field(number, value):
  return _varint(number << 3) + _varint(value)


def _hlo_proto(instructions):
  """HloProto{hlo_module{computations{instructions{name, opcode,
  id, metadata{op_type, op_name}}}}}."""
  body = b''.join(
      _bytes_field(2, _bytes_field(1, name) + _bytes_field(2, 'fusion') +
                   _int_field(35, 300 + i) +
                   (_bytes_field(7, _bytes_field(1, 'conv') +
                                 _bytes_field(2, op_name))
                    if op_name else b''))
      for i, (name, op_name) in enumerate(instructions))
  computation = _bytes_field(1, 'main') + body
  return _bytes_field(1, _bytes_field(1, 'jit_x') +
                      _bytes_field(3, computation))


def _device_plane(lines=None):
  """XPlane{id, name, lines{id, name, events{metadata_id, offset_ps,
  duration_ps}}, event_metadata{key, value{id, name}}} for {line name:
  [(event name, start_ns, dur_ns)]}."""
  lines = lines or {}
  ids = {name: i + 1 for i, name in enumerate(
      sorted({name for events in lines.values()
              for name, _, _ in events}))}
  body = b''.join(
      _bytes_field(3, _int_field(1, k + 1) + _bytes_field(2, line) +
                   b''.join(_bytes_field(
                       4, _int_field(1, ids[name]) +
                       _int_field(2, start * 1000) +
                       _int_field(3, dur * 1000))
                            for name, start, dur in events))
      for k, (line, events) in enumerate(lines.items()))
  metadata = b''.join(
      _bytes_field(4, _int_field(1, i) + _bytes_field(
          2, _int_field(1, i) + _bytes_field(2, name)))
      for name, i in ids.items())
  return (_int_field(1, 1) + _bytes_field(2, '/device:TPU:0') + body +
          metadata)


def _xspace(programs, device_lines=None):
  """XSpace{planes{name, event_metadata{key, value{id, name,
  stats{metadata_id, bytes_value}}}}} with a device plane before it."""
  entries = b''.join(
      _bytes_field(4, _int_field(1, 10 + i) + _bytes_field(
          2, _int_field(1, 10 + i) + _bytes_field(2, name) +
          _bytes_field(5, _int_field(1, 1) + _bytes_field(6, proto))))
      for i, (name, proto) in enumerate(programs.items()))
  device = _device_plane(device_lines)
  metadata = _int_field(1, 2) + _bytes_field(2, '/host:metadata') + entries
  return _bytes_field(1, device) + _bytes_field(1, metadata)


def test_scope_line_names_each_operation_by_its_programs_op_name(
    tmp_path):
  torso = 'jit(train_step)/jvp(ImpalaAgent)/torso/conv_general_dilated'
  path = tmp_path / 'hand.xplane.pb'
  path.write_bytes(_xspace({
      'jit_train_step(7)': _hlo_proto([
          ('fusion.1', torso), ('fusion.2', None),
          ('while.3', 'jit(train_step)/jvp(ImpalaAgent)/core/while')]),
      'jit_carry_step(9)': _hlo_proto([
          ('fusion.1', 'jit(carry_step)/ImpalaAgent/heads/dot')])}))
  assert sorted(trace_scopes.hlo_protos(str(path))) == [
      'jit_carry_step(9)', 'jit_train_step(7)']
  trace = trace_reduce.Trace.from_rows([
      ('/device:TPU:0', 'XLA Modules', 'jit_train_step(7)', 0, 300),
      ('/device:TPU:0', 'XLA Modules', 'jit_carry_step(9)', 400, 100),
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = bf16[8] fusion(%p)',
       0, 100),
      ('/device:TPU:0', 'XLA Ops', '%fusion.2 = bf16[8] fusion(%p)',
       100, 50),
      ('/device:TPU:0', 'XLA Ops', '%while.3 = () while(%t)', 150, 150),
      # the same instruction name in ANOTHER program
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[2] fusion(%q)',
       400, 100),
      # outside every execution
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[2] fusion(%q)',
       600, 10)])
  assert trace_scopes.add_scope_line(trace, str(path)) == 3
  ev = trace.chips()[0][1][trace_scopes.SCOPES_LINE]
  ops = trace.chips()[0][1]['XLA Ops']
  assert ev.names == [
      torso, trace_scopes.NO_SCOPE,
      'jit(train_step)/jvp(ImpalaAgent)/core/while',
      'jit(carry_step)/ImpalaAgent/heads/dot', trace_scopes.NO_SCOPE]
  assert list(ev.start) == list(ops.start)
  assert list(ev.dur) == list(ops.dur)
  # No existing reduction reads the line: busy time is unchanged.
  assert trace_reduce.busy(trace, (0, 700))['busy_s'] == pytest.approx(
      410e-9)
  assert trace_scope_share.read(
      {'trace': trace}, module_regex=r'^jit_train_step\b',
      scope_regex=r'(^|[/(])torso([/)]|$)') == pytest.approx(
          100.0 * 100 / 300)


def test_a_profile_without_a_programs_hlo_adds_no_scope_line(tmp_path):
  path = tmp_path / 'empty.xplane.pb'
  path.write_bytes(_xspace({}))
  trace = trace_reduce.Trace.from_rows([
      ('/device:TPU:0', 'XLA Modules', 'jit_train_step(7)', 0, 300),
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = bf16[8] fusion', 0, 9)])
  assert trace_scopes.add_scope_line(trace, str(path)) == 0
  assert trace_scopes.SCOPES_LINE not in trace.chips()[0][1]


def test_a_real_profile_carries_each_programs_hlo_with_its_scopes(
    tmp_path):
  """The decoder against the profiler's own encoder: a CPU profile of
  a jitted function under a named scope."""
  import glob

  import jax
  import jax.numpy as jnp

  @jax.jit
  def scoped_for_the_test(x):
    with jax.named_scope('torso'):
      return jnp.tanh(x) * 2

  x = jnp.ones((8, 8))
  scoped_for_the_test(x).block_until_ready()
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  options.host_tracer_level = 0
  jax.profiler.start_trace(str(tmp_path), profiler_options=options)
  scoped_for_the_test(x).block_until_ready()
  jax.profiler.stop_trace()
  (path,) = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*' /
                          '*.xplane.pb'))
  protos = trace_scopes.hlo_protos(path)
  mine = [name for name in protos
          if name.startswith('jit_scoped_for_the_test')]
  if not mine:
    pytest.skip('this platform\'s profile holds no HLO: '
                f'{sorted(protos)}')
  names = trace_scopes.op_names(protos[mine[0]])
  assert any('/torso/' in op_name for op_name in names.values()), names


# --- Through capture_report.py. ---

SPAN_METRICS, DEVICE_METRICS = set(), set()
for _file in os.listdir(os.path.join(REPO, 'benchmark', 'metrics')):
  _spec = loader.load_metric(_file[:-len('.json')])
  if _spec['reader'] in capture_report.READERS:
    (SPAN_METRICS if _spec['entry']['source'] == 'program_span'
     else DEVICE_METRICS).add(_file[:-len('.json')])


def test_the_metrics_not_yet_listed_carry_the_entry_they_would_get():
  """BENCHMARK.json is as the parent left it; each of the 16 metric
  files holds, under `entry`, what a benchmark PR appends to
  `per_layer` with the lines of context.py (PERF.md, section 7)."""
  assert len(SPAN_METRICS) == 10 and len(DEVICE_METRICS) == 6
  listed = {m['name'] for m in
            MANIFEST['end_to_end'] + MANIFEST['per_layer']}
  assert not listed & (SPAN_METRICS | DEVICE_METRICS)
  layers = {m['layer'] for m in MANIFEST['per_layer']}
  for name in SPAN_METRICS | DEVICE_METRICS:
    spec = loader.load_metric(name)
    assert spec['what'].startswith(('span ', 'scope ', 'the ')), name
    entry = spec['entry']
    assert set(entry) == {'unit', 'better', 'source', 'layer', 'moves',
                          'workloads'}, name
    assert entry['layer'] in layers, name
    assert entry['better'] in ('lower', 'higher'), name
    for cell in entry['workloads']:
      e2e = {m['name'] for m in
             loader.cell_metrics(MANIFEST, cell, 'end_to_end')}
      assert entry['moves'] in e2e, (name, cell)


def test_capture_report_reads_what_a_profiler_capture_leaves(tmp_path):
  """ProfilerCapture -> spans.json + a profile -> one Trace -> the
  span metrics; a CPU's profile has no device plane, so no metric
  whose source is the device trace and no breakdown."""
  telemetry.take_spans()
  capture = observability.ProfilerCapture(str(tmp_path))
  for batch in range(3):
    with telemetry.span('inference/dispatch', id=batch):
      pass
  with telemetry.span('actor/step'):
    with telemetry.span('env/pipe'):
      pass
  capture.stop()
  assert telemetry.take_spans() is None  # the capture disarmed it
  result = capture_report.reduce(str(tmp_path))
  assert set(result) == {'metrics'}
  assert set(result['metrics']) == {
      'inference.dispatch_ms_p50', 'actors.step_self_ms',
      'actors.python_busy_share', 'actors.env_pipe_ms_p50'}
  assert result['metrics']['actors.env_pipe_ms_p50']['unit'] == 'ms'
  assert all(m['value'] >= 0 for m in result['metrics'].values())


def test_capture_report_joins_a_device_profile_with_the_spans(
    tmp_path):
  """A capture as a chip leaves it, its profile encoded by hand: the
  landmark puts the spans on the trace's clock, so the device's idle
  time meets `inference/wait_batch`, `idle_gaps` names it, and the
  step's operations count under their scopes."""
  profile = tmp_path / 'plugins' / 'profile' / 'hand'
  profile.mkdir(parents=True)
  # The landmark ends at 500 ns on the trace; the step runs from 600.5
  # to 1000.5 us, three quarters of it in the torso.
  (profile / 'hand.xplane.pb').write_bytes(_xspace(
      {'jit_train_step(7)': _hlo_proto([
          ('fusion.1', 'jit(train_step)/jvp(ImpalaAgent)/torso/conv'),
          ('fusion.2', 'jit(train_step)/jvp(ImpalaAgent)/core/while')])},
      {'XLA Modules': [('jit_capture_clock_sync(1)', 400, 100),
                       ('jit_train_step(7)', 600_500, 400_000)],
       'XLA Ops': [('%fusion.1 = bf16[8] fusion(%p)', 600_500, 300_000),
                   ('%fusion.2 = bf16[8] fusion(%p)', 900_500,
                    100_000)]}))
  # The host saw the landmark end at 7 ms on its clock and armed then.
  taken = _taken([
      ('inference/wait_batch', 7_000, 7_500, 1),
      ('inference/dispatch', 7_500, 7_550, 1),
      ('learner/wait_batch', 7_000, 7_600, 3),
      ('actor/step', 7_100, 7_300, 2),
      ('batcher/compute', 7_110, 7_290, 2)], armed=7_000, taken=8_000)
  taken['landmark'] = {'module': 'jit_capture_clock_sync',
                       'host_perf_ns': 7_000 * US}
  (tmp_path / 'spans.json').write_text(json.dumps(taken))
  result = capture_report.reduce(str(tmp_path))
  values = {name: m['value'] for name, m in result['metrics'].items()}
  # Idle from 0.5 to 600.5 us; the server waited from 0.5 to 500.5.
  assert values.pop('inference.idle_while_waiting_share'
                    ) == pytest.approx(100.0 * 500 / 600)
  assert values.pop('learner.torso_share') == pytest.approx(75.0)
  assert values.pop('learner.core_share') == pytest.approx(25.0)
  assert values.pop('actors.step_self_ms') == pytest.approx(0.02)
  assert values.pop('actors.python_busy_share') == pytest.approx(2.0)
  assert values.pop('batcher.wait_batch_ms_p50') == pytest.approx(0.5)
  assert values.pop('inference.dispatch_ms_p50') == pytest.approx(0.05)
  assert not values  # no other span, no jit_anakin_step
  assert result['device'] == {'busy_s': pytest.approx(400e-6),
                              'window_s': pytest.approx(1e-3)}
  (gap,) = result['breakdown']['idle_gaps']
  assert gap[0] == 'bench:inference/wait_batch'
  assert gap[1] == pytest.approx(600e-6)


def test_a_fleet_runs_profile_dir_window_gives_every_span_metric(
    tmp_path):
  """From a span in the program to a metric's value: experiment.py at
  the rehearsal sizes of the cell the span metrics name, with
  --profile_dir, then capture_report.py on that directory."""
  cells = {cell for name in SPAN_METRICS
           for cell in loader.load_metric(name)['entry']['workloads']}
  (cell,) = [loader.find_cell(MANIFEST, name) for name in cells]
  flags = loader.flag_args(
      loader.load_config(MANIFEST, cell['config']),
      loader.load_traffic(cell['traffic']),
      {'seed': 5, 'logdir': str(tmp_path / 'log'),
       # 9 learner steps; the capture holds steps 3 to 5
       'total_environment_frames': 9 * 2 * 5 * 4,
       'profile_dir': str(tmp_path / 'capture'),
       'profile_start_step': 2, 'profile_num_steps': 3},
      rehearse=True)
  env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO,
             XLA_FLAGS='--xla_force_host_platform_device_count=1')
  env.pop('JAX_COMPILATION_CACHE_DIR', None)
  ran = subprocess.run(
      [sys.executable, os.path.join(REPO, 'experiment.py'), *flags],
      cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
  assert ran.returncode == 0, ran.stderr[-4000:]
  done = subprocess.run(
      [sys.executable, os.path.join(REPO, 'benchmark',
                                    'capture_report.py'),
       str(tmp_path / 'capture')],
      cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
  assert done.returncode == 0, done.stderr[-4000:]
  result = json.loads(done.stdout.strip().splitlines()[-1])
  assert set(result) == {'metrics'}  # no device plane on a CPU
  assert set(result['metrics']) == SPAN_METRICS
  for name, metric in result['metrics'].items():
    assert metric['value'] > 0, name
  assert "on the host's clock" in done.stdout

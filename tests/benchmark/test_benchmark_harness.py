"""The benchmark's yardstick from the CPU side: the files fit the
contract and each other, and the arithmetic that turns counts, clocks
and traces into metrics gives what a hand count gives. What the cells
measure, only a chip run can say."""

import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import (caller_clock, flops, ledger, loader,
                               trace_reduce, vtrace_ref, window)
from scalable_agent_tpu.config import Config

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
NAME = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$')
SOURCES = {'device_trace', 'program_span', 'program_counter',
           'host_clock'}


# --- BENCHMARK.json against the contract and the files. ---


def test_manifest_has_exactly_the_contract_keys():
  assert sorted(MANIFEST) == sorted([
      'command', 'paths', 'run_seconds', 'configs', 'workloads',
      'end_to_end', 'per_layer'])
  assert os.path.getsize(os.path.join(REPO, 'BENCHMARK.json')) < 65536
  assert 1 <= MANIFEST['run_seconds'] <= 51
  names = [e['name'] for kind in ('configs', 'workloads', 'end_to_end',
                                  'per_layer') for e in MANIFEST[kind]]
  assert all(NAME.match(n) for n in names)
  assert len(set(names)) == len(names)


def test_a_full_check_of_24_cells_fits_its_budget():
  runs = 2 + 14 * 24
  total = (runs * (MANIFEST['run_seconds'] + 60) + 24 * 2 * 90 + 1200)
  assert total <= 43200


def test_cells_name_their_files_and_at_most_a_quarter_take_four_chips():
  cells = MANIFEST['workloads']
  assert 2 <= len(cells) <= 24
  assert len({(c['config'], c['traffic']) for c in cells}) == len(cells)
  four = [c for c in cells if c['chips'] == 4]
  assert all(c['chips'] in (1, 4) for c in cells)
  assert len(four) <= max(1, len(cells) // 4)
  used = set()
  for cell in cells:
    assert len(cell['why']) <= 200
    config = loader.load_config(MANIFEST, cell['config'])
    traffic = loader.load_traffic(cell['traffic'])
    assert os.path.exists(os.path.join(
        REPO, 'benchmark', 'drivers', traffic['driver'] + '.py'))
    assert isinstance(config['flags'], dict) and 'reduced' in config
    used.add(cell['config'])
  assert used == {c['name'] for c in MANIFEST['configs']}
  for config in MANIFEST['configs']:
    assert config['file'].startswith('benchmark/configs/')
    assert len(config['why']) <= 200 and config['reduced'] == []


def test_every_metric_has_a_bound_or_a_layer_a_file_and_a_reader():
  e2e = {m['name']: m for m in MANIFEST['end_to_end']}
  assert 'setup_s' in e2e and e2e['setup_s']['bound'] == 0.1
  for m in MANIFEST['end_to_end']:
    assert 0.01 <= m['bound'] <= 0.1
    assert m['source'] in ('host_clock', 'device_trace')
  for m in MANIFEST['per_layer']:
    assert 'bound' not in m and m['moves'] in e2e and m['layer']
  cells = {c['name'] for c in MANIFEST['workloads']}
  for m in MANIFEST['end_to_end'] + MANIFEST['per_layer']:
    assert m['source'] in SOURCES and m['better'] in ('higher', 'lower')
    assert set(m.get('workloads', cells)) <= cells
    spec = loader.load_metric(m['name'])
    assert callable(loader.load_reader(spec['reader']).read)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
  for cell in MANIFEST['workloads']:
    e2e = [m['name'] for m in
           loader.cell_metrics(MANIFEST, cell['name'], 'end_to_end')]
    layers = loader.cell_metrics(MANIFEST, cell['name'], 'per_layer')
    assert 'setup_s' in e2e and len(e2e) >= 2 and layers
    # A per-layer metric is reported only where the metric it moves is.
    assert all(m['moves'] in e2e for m in layers), cell['name']


def test_no_harness_file_names_a_cell_a_config_or_a_traffic_mix():
  """The harness is driven by data: a later PR adds files and entries
  and edits nothing that is there."""
  names = {c['name'] for c in MANIFEST['workloads']}
  names |= {c['name'] for c in MANIFEST['configs']}
  names |= {c['traffic'] for c in MANIFEST['workloads']}
  for folder in ('', 'harness', 'drivers', 'readers'):
    base = os.path.join(REPO, 'benchmark', folder)
    for file in os.listdir(base):
      if file.endswith('.py'):
        with open(os.path.join(base, file)) as f:
          text = f.read()
        assert not [n for n in names if n in text], file


def test_a_name_nobody_defines_is_an_error_not_a_default():
  with pytest.raises(loader.BenchmarkError):
    loader.find_cell(MANIFEST, 'no_such.cell')
  with pytest.raises(loader.BenchmarkError):
    loader.load_traffic('no_such_traffic')
  with pytest.raises(loader.BenchmarkError):
    loader.load_reader('no_such_reader')


@pytest.mark.parametrize('kind', ['end_to_end', 'per_layer'])
def test_nothing_to_read_is_an_error_on_the_chip_left_out_in_rehearsal(
    kind):
  """A name that moved in the program must not make its metric vanish
  from a chip run in silence; a rehearsal has no device trace."""
  from benchmark import run
  for cell in MANIFEST['workloads']:
    listed = loader.cell_metrics(MANIFEST, cell['name'], kind)
    assert run._read_metrics(
        loader, MANIFEST, cell, kind, {}, 'rehearsal.', True) == {}
    with pytest.raises(loader.BenchmarkError, match=listed[0]['name']):
      run._read_metrics(loader, MANIFEST, cell, kind, {}, '', False)


def test_every_metric_file_says_what_it_reads():
  for m in MANIFEST['end_to_end'] + MANIFEST['per_layer']:
    assert len(loader.load_metric(m['name'])['what']) > 20, m['name']


def test_flags_reach_a_config_through_the_programs_own_parsing():
  cell = loader.find_cell(MANIFEST, MANIFEST['workloads'][0]['name'])
  config_file = loader.load_config(MANIFEST, cell['config'])
  traffic_file = loader.load_traffic(cell['traffic'])
  args = loader.flag_args(config_file, traffic_file,
                          {'seed': 5, 'logdir': '/nowhere'})
  config = loader.build_config(args)
  assert config.seed == 5 and config.logdir == '/nowhere'
  for key, value in {**config_file['flags'],
                     **traffic_file['flags']}.items():
    assert getattr(config, key) == value, key
  tiny = loader.build_config(loader.flag_args(
      config_file, traffic_file, {'seed': 5, 'logdir': '/nowhere'},
      rehearse=True))
  assert tiny.batch_size < config.batch_size


# --- flops.py against hand counts. ---


def test_flops_of_one_conv_one_dense_and_one_lstm_step():
  # 3x3 conv, 3 -> 16 channels at 72x96: one MAC per tap per output.
  assert flops.conv2d_flops(72, 96, 3, 3, 3, 16) == 2 * 72 * 96 * 27 * 16
  assert flops.dense_flops(3456, 256) == 2 * 3456 * 256
  # LSTM 256 over a 330-wide input: four gates of (330+256) x 256.
  assert flops.lstm_step_flops(330, 256) == 2 * 586 * 1024


def test_flops_of_the_deep_agent_by_hand():
  config = Config(torso='deep', height=72, width=96, num_actions=9,
                  use_instruction=True, batch_size=32, unroll_length=100)
  conv = flops.conv2d_flops
  torso = (conv(72, 96, 3, 3, 3, 16) + 4 * conv(36, 48, 3, 3, 16, 16) +
           conv(36, 48, 3, 3, 16, 32) + 4 * conv(18, 24, 3, 3, 32, 32) +
           conv(18, 24, 3, 3, 32, 32) + 4 * conv(9, 12, 3, 3, 32, 32) +
           2 * 9 * 12 * 32 * 256)
  assert flops.torso_forward_flops('deep', 72, 96) == torso
  frame = (torso + 16 * 2 * (20 + 64) * 4 * 64 +
           2 * (256 + 1 + 9 + 64 + 256) * 4 * 256 + 2 * 256 * 10)
  assert flops.agent_forward_flops(config) == frame
  assert flops.learner_step_flops(config) == 3 * frame * 101 * 32
  # About a teraFLOP a step at the paper's batch, as the issue says.
  assert 0.95e12 < flops.learner_step_flops(config) < 1.1e12
  assert flops.anakin_step_flops(config) == (
      frame * 100 * 32 + 3 * frame * 101 * 32)
  with pytest.raises(ValueError):
    flops.torso_forward_flops('transformer', 72, 96)
  with pytest.raises(ValueError):
    flops.agent_forward_flops(
        Config(num_actions=9, pixel_control_cost=0.1))


def test_peaks_name_their_source_and_an_unknown_kind_is_an_error():
  with open(os.path.join(REPO, 'benchmark/harness/peaks.json')) as f:
    peaks = json.load(f)
  v5e = peaks['TPU v5 lite']
  assert v5e['bf16_flops_per_s'] == 197e12
  assert v5e['hbm_bytes_per_s'] == 819e9 and 'source' in v5e
  reader = loader.load_reader('trace_mfu')
  trace = trace_reduce.Trace.from_rows([
      ('/device:TPU:0', 'XLA Modules', 'jit_train_step(1)', 0, 1e6)])
  obs = {'trace': trace, 'device': {'kind': 'TPU v9'},
         'peaks_path': os.path.join(REPO,
                                    'benchmark/harness/peaks.json'),
         'config': Config(num_actions=9, torso='deep', height=24,
                          width=32)}
  with pytest.raises(KeyError):
    reader.read(obs, module_regex='^jit_train_step', flops=
                'learner_step_flops')


# --- Window arithmetic. ---


def test_chained_rate():
  assert window.chained_rate(100, 4.0, 12800) == 320000.0
  with pytest.raises(window.WindowError):
    window.chained_rate(0, 4.0, 12800)


def test_event_rate_counts_whole_steps_between_events_in_the_window():
  events = [(100.0, 3), (103.0, 4), (106.5, 5), (109.0, 6), (112.0, 7)]
  rate, steps, seconds = window.event_rate(events, 102.0, 110.0, 1000)
  assert (steps, seconds) == (2, 6.0) and rate == 2000 / 6.0


@pytest.mark.parametrize('events', [
    [], [(105.0, 4)], [(101.0, 3), (111.0, 9)]])
def test_a_window_with_fewer_than_two_events_is_an_error_not_a_zero(
    events):
  with pytest.raises(window.WindowError):
    window.event_rate(events, 102.0, 110.0, 1000)


def test_step_events_take_the_first_line_of_each_step(tmp_path):
  path = tmp_path / 'summaries.jsonl'
  lines = [{'wall_time': 10.0, 'step': 1, 'tag': 'a', 'value': 1.0},
           {'wall_time': 10.2, 'step': 1, 'tag': 'b', 'value': 2.0},
           {'wall_time': 13.0, 'step': 2, 'tag': 'a', 'value': 3.0}]
  path.write_text('\n'.join(json.dumps(x) for x in lines) +
                  '\n{"wall_time": 14.0, "st')  # a line being written
  assert window.read_step_events(str(path)) == [(10.0, 1), (13.0, 2)]
  assert window.read_step_events(str(tmp_path / 'absent')) == []
  assert window.read_scalars(str(path), ['a'])['a'] == [
      (10.0, 1, 1.0), (13.0, 2, 3.0)]


# --- Readers over synthetic observations. ---


def test_counter_delta_and_observed_and_caller_clock_readers():
  obs = {'window_seconds': 10.0, 'setup_s': 33.5,
         'counters': {'open': {'fleet': {'unrolls': 10},
                               'server': {'requests': 100, 'calls': 10}},
                      'close': {'fleet': {'unrolls': 60},
                                'server': {'requests': 700,
                                           'calls': 40}}},
         'caller_waits': np.arange(1, 101) / 1e3}
  delta = loader.load_reader('counter_delta').read
  assert delta(obs, 'fleet', 'unrolls') == 5.0
  assert delta(obs, 'server', 'requests', per=['server', 'calls']) == 20.0
  assert delta({}, 'fleet', 'unrolls') is None
  observed = loader.load_reader('observed').read
  assert observed(obs, ['setup_s']) == 33.5
  assert observed(obs, ['counters', 'close', 'nothing']) is None
  p95 = loader.load_reader('caller_clock').read(obs, percentile=95)
  assert p95 == pytest.approx(95.05)
  assert loader.load_reader('caller_clock').read({}, percentile=95) is None


def test_caller_clock_times_each_call_and_passes_state_untouched():
  clock = caller_clock.CallerClock(capacity=2)  # forces a doubling
  state = object()

  def policy(prev_action, env_output, core_state):
    return 'out', core_state

  timed = clock.wrap(policy)
  for _ in range(5):
    assert timed(0, None, state) == ('out', state)
  waits = clock.waits(0.0, float('inf'))
  assert len(waits) == 5 and np.all(waits >= 0)
  assert len(clock.waits(0.0, 1e-9)) == 0


def test_compile_ledger_pairs_cache_events_and_splits_by_phase():
  book = ledger.CompileLedger()
  compile_event = '/jax/core/compile/backend_compile_duration'
  book._event('/jax/compilation_cache/cache_hits')
  book._duration(compile_event, 0.01, fun_name='warm')
  book._event('/jax/compilation_cache/cache_misses')
  book._duration(compile_event, 2.0, fun_name='cold')
  book._duration(compile_event, 0.5, fun_name='eager')
  book._duration('/jax/other', 9.0)
  book.phase = 'window'
  book._duration(compile_event, 1.0, fun_name='late')
  setup = book.summary('setup')
  assert (setup['requests'], setup['hits'], setup['misses'],
          setup['uncached']) == (3, 1, 1, 1)
  assert setup['secs'] == pytest.approx(2.51)
  assert setup['compiled_names'] == ['cold', 'eager']
  assert book.summary('window')['compiled_names'] == ['late']


def test_vtrace_reference_agrees_with_every_form_of_the_step():
  for flags in ({}, {'use_associative_scan': True}):
    ok, worst = vtrace_ref.check_step_form(
        Config(unroll_length=20, batch_size=4, **flags), seed=3)
    assert ok and worst < 1e-5
  inputs = vtrace_ref.seeded_inputs(3, 20, 4)
  vs, _ = vtrace_ref.ground_truth(**inputs)
  # Lower precision than the configuration states must fail it.
  coarse = {k: np.asarray(v, np.float16) for k, v in inputs.items()}
  vs16, _ = vtrace_ref.ground_truth(**coarse)
  error = np.max(np.abs(vs16 - vs) / np.maximum(1.0, np.abs(vs)))
  assert error > vtrace_ref.RELATIVE_TOLERANCE


# --- The reducer on a trace recorded on the chip. ---

TESTDATA = os.path.join(REPO, 'benchmark', 'testdata')


@pytest.fixture(scope='module')
def recorded():
  with open(os.path.join(TESTDATA, 'trace_step_dp4.expected.json')) as f:
    expected = json.load(f)
  trace = trace_reduce.Trace.from_recorded(
      os.path.join(TESTDATA, 'trace_step_dp4.json.gz'))
  return trace, expected


def test_recorded_trace_is_small_and_names_its_chips(recorded):
  trace, expected = recorded
  path = os.path.join(TESTDATA, 'trace_step_dp4.json.gz')
  assert os.path.getsize(path) < 200 * 1024
  assert [chip for chip, _ in trace.chips()] == expected['chips']


def test_recorded_trace_gives_the_expected_busy_and_idle(recorded):
  trace, expected = recorded
  busy = trace_reduce.busy(trace)
  assert busy['busy_s'] == pytest.approx(expected['busy_s'], rel=1e-9)
  assert busy['window_s'] == pytest.approx(expected['window_s'], rel=1e-9)
  assert 0 < busy['busy_s'] <= busy['window_s']
  idle = loader.load_reader('trace_idle').read({'trace': trace})
  assert idle == pytest.approx(
      100 * (1 - expected['busy_s'] / expected['window_s']))


def test_recorded_trace_gives_the_expected_module_times(recorded):
  trace, expected = recorded
  times = trace_reduce.module_times(trace, r'^jit_train_step\b')
  assert times['count'] == expected['module_count']
  assert times['seconds'] == pytest.approx(expected['module_seconds'],
                                           rel=1e-9)
  assert trace_reduce.module_times(trace, '^jit_nothing') is None
  ms = loader.load_reader('trace_module_time').read(
      {'trace': trace}, module_regex=r'^jit_train_step\b')
  assert ms == pytest.approx(
      expected['module_seconds'] / expected['module_count'] * 1e3)


def test_recorded_trace_gives_the_expected_collective_times(recorded):
  trace, expected = recorded
  times = trace_reduce.collective_times(trace)
  assert times['seconds'] == pytest.approx(
      expected['collective_seconds'], rel=1e-9)
  assert times['exposed_seconds'] == pytest.approx(
      expected['collective_exposed_seconds'], rel=1e-9)
  assert 0 < times['exposed_seconds'] <= times['seconds']


def test_recorded_trace_gives_a_breakdown_of_self_times(recorded):
  trace, expected = recorded
  ops = trace_reduce.top_ops(trace)
  assert [name for name, _ in ops[:3]] == expected['top_ops'][:3]
  assert len(ops) <= 10 and all(t > 0 for _, t in ops)
  # Self times: no operation is charged for what runs inside it, so
  # the times by name sum to no more than the busy time of a chip.
  total = sum(t for _, t in trace_reduce.top_ops(trace, limit=10 ** 6))
  assert total <= trace_reduce.busy(trace)['busy_s'] * (1 + 1e-6)
  gaps = trace_reduce.idle_gaps(trace)
  assert len(gaps) <= 10 and all(t > 0 for _, t in gaps)
  assert sum(t for _, t in gaps) == pytest.approx(
      expected['idle_chip0_s'], rel=1e-6)


def test_host_spans_reach_the_traces_clock_through_the_landmark():
  trace = trace_reduce.Trace.from_rows([
      ('/device:TPU:0', 'XLA Modules', 'jit_bench_clock_sync(9)', 400, 100),
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[] fusion', 1000, 500)])
  # The host saw the landmark end at 7,000,500 ns of ITS clock; the
  # trace put that instant at 500 ns.
  assert trace_reduce.add_host_spans(
      trace, [('trace', 7_000_500, 7_002_500),
              ('barrier', 7_001_000, 7_002_000)], 7_000_500,
      r'^jit_bench_clock_sync\b')
  spans = trace.host_spans()
  assert spans['bench:barrier'][0][0] == 1000
  assert spans['bench:barrier'][1][0] == 2000
  assert trace_reduce.traced_window(trace) == (500.0, 2500.0)


def test_spans_that_straddle_an_end_of_the_traced_slice_are_kept():
  """A learner's wait of seconds straddles the slice's ends more often
  than not; dropped, its idle time reads as `no bench: span`."""
  from benchmark.harness import context
  ctx = context.RunContext({}, {}, {}, None, 0, 1.0, True, True, 0.0,
                           None, '')
  with ctx.span('untraced'):
    pass
  before = ctx.span('began_before')
  before.__enter__()
  ctx._spans = []  # the profiler starts
  with ctx.span('inside'):
    pass
  before.__exit__(None, None, None)
  open_at_stop = ctx.span('open_at_stop')
  open_at_stop.__enter__()
  spans = ctx._take_spans(stop_ns=2**62)
  open_at_stop.__exit__(None, None, None)
  assert [name for name, _, _ in spans] == [
      'inside', 'began_before', 'open_at_stop']
  assert spans[1][1] < spans[0][1] and spans[2][2] == 2**62
  assert all(t0 <= t1 for _, t0, t1 in spans)
  assert ctx._spans is None and not ctx._open  # nothing kept afterwards


def test_interval_arithmetic_on_a_trace_made_by_hand():
  rows = [
      # chip 0: a while spanning two ops, a gap, a collective under
      # which compute runs for half of its time
      ('/device:TPU:0', 'XLA Ops', '%while.1 = () while', 0, 100),
      ('/device:TPU:0', 'XLA Ops', '%fusion.1 = f32[] fusion', 0, 40),
      ('/device:TPU:0', 'XLA Ops', '%fusion.2 = f32[] fusion', 50, 50),
      ('/device:TPU:0', 'XLA Ops', '%fusion.3 = f32[] fusion', 220, 30),
      ('/device:TPU:0', 'Async XLA Ops',
       '%all-reduce-start.1 = f32[] all-reduce-start', 200, 60),
      ('/device:TPU:0', 'XLA Modules', 'jit_train_step(7)', 0, 260),
      ('/host:CPU', 'python3', 'bench:trace', 0, 300),
      ('/host:CPU', 'python3', 'bench:barrier', 90, 200)]
  # Written in microseconds, read in the trace's nanoseconds.
  trace = trace_reduce.Trace.from_rows(
      [(p, l, n, s * 1e3, d * 1e3) for p, l, n, s, d in rows])
  busy = trace_reduce.busy(trace)
  assert busy['busy_s'] == pytest.approx(130e-6)  # async is not busy
  assert busy['window_s'] == pytest.approx(300e-6)
  coll = trace_reduce.collective_times(trace)
  assert coll['seconds'] == pytest.approx(60e-6)
  assert coll['exposed_seconds'] == pytest.approx(30e-6)
  ops = dict(trace_reduce.top_ops(trace))
  assert ops['%while.1 = () while'] == pytest.approx(10e-6)  # self
  gaps = dict(trace_reduce.idle_gaps(trace))
  # 100..220 and 250..300: both middles lie under the barrier span.
  assert gaps == {'bench:barrier': pytest.approx(170e-6)}

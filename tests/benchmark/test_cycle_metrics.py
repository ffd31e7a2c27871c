"""PR 37's per-layer metrics: thirteen entries appended to `per_layer`
that read the program's always-on cycle records (`server.stats()`
`call_*`, `fleet.stats()` `step_*`) in the window, profiler off, through
one new reader. Every one is READ in the traced rehearsal of the cell
that lists it; and the reader ends a run with a result on a program from
before the records (the parent commit, under these files) while a key
that moved in a program that has them stays an error on the chip."""

import pytest

from benchmark.harness import loader
from benchmark.readers import cycle_counter
from test_benchmark_cells import _result, _run

MANIFEST = loader.load_manifest()
CELL = 'deep_dmlab.fleet32'
SERVER = [f'inference.{phase}_ms_per_call' for phase in (
    'wait_batch', 'dispatch', 'in_flight_and_readback', 'unpark')] + [
        'inference.call_host_ms_p95', 'inference.excess_ms_per_s',
        'batcher.wait_ms_per_request']
FLEET = ['actors.step_ms_mean', 'actors.step_ms_p50',
         'actors.excess_ms_per_s', 'actors.excess_in_publish_share',
         'actors.env_step_ms_per_step', 'actors.env_child_ms_per_step']
NEW = SERVER + FLEET


def test_new_entries_are_appended_and_keep_to_the_contract():
  entries = MANIFEST['per_layer'][-len(NEW):]
  assert [m['name'] for m in entries] == NEW  # appended, in this order
  e2e = {m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')}
  layers = {m['layer'] for m in MANIFEST['per_layer'][:-len(NEW)]}
  for m in entries:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert m['source'] == 'program_counter' and m['better'] == 'lower'
    assert m['layer'] in layers  # a layer the benchmark already names
    # Listed where its reader finds something, and its `moves` is
    # reported by every cell it lists.
    assert m['workloads'] == [CELL] and m['moves'] in e2e
    spec = loader.load_metric(m['name'])
    assert set(spec) == {'reader', 'args', 'what'}  # listed: no `entry`
    assert spec['reader'] == 'cycle_counter' and len(spec['what']) > 20
  by_name = {m['name']: m for m in entries}
  for name in SERVER:
    assert by_name[name]['moves'] == 'policy_call_p95_ms'
    args = loader.load_metric(name)['args']
    assert (args['source'], args['since']) == ('server', 'call_excess_ms')
  for name in FLEET:
    assert (by_name[name]['layer'], by_name[name]['moves']) == (
        'actors', 'fleet_fps')
    args = loader.load_metric(name)['args']
    assert (args['source'], args['since']) == ('fleet', 'group_steps')
  assert by_name['batcher.wait_ms_per_request']['layer'] == 'batcher'
  assert by_name['actors.excess_in_publish_share']['unit'] == '%'


def _obs(opened, closed, seconds=10.0):
  return {'window_seconds': seconds,
          'counters': {'open': {'fleet': opened, 'server': {'calls': 0}},
                       'close': {'fleet': closed, 'server': {'calls': 5}}}}


def test_the_reader_by_the_counters_it_is_given():
  opened = {'group_steps': 100, 'step_ms': 400.0, 'step_excess_ms': 50.0,
            'step_excess_ms_in_learner/publish': 20.0, 'step_ms_p50': 3.0}
  closed = {'group_steps': 300, 'step_ms': 1400.0, 'step_excess_ms': 250.0,
            'step_excess_ms_in_learner/publish': 170.0, 'step_ms_p50': 4.5}
  obs = _obs(opened, closed)
  read = lambda key, **kw: cycle_counter.read(  # noqa: E731
      obs, 'fleet', key, 'group_steps', **kw)
  assert read('step_ms', per=['fleet', 'group_steps']) == 5.0  # the MEAN
  assert read('step_excess_ms') == 20.0  # a second of window
  assert read('step_ms_p50', per='close') == 4.5
  assert read('step_excess_ms_in_learner/publish',
              per=['fleet', 'step_excess_ms'], scale=100.0) == 75.0
  assert read('step_ms', per=['server', 'calls']) == 200.0
  # A window without excess has no share of it under anything: 0, not
  # nothing (on the chip, nothing ends the run without a result).
  still = _obs(opened, dict(closed, step_excess_ms=50.0,
                            **{'step_excess_ms_in_learner/publish': 20.0}))
  assert cycle_counter.read(
      still, 'fleet', 'step_excess_ms_in_learner/publish', 'group_steps',
      per=['fleet', 'step_excess_ms'], scale=100.0) == 0.0


def test_nothing_to_read_a_program_from_before_and_a_name_that_moved():
  args = loader.load_metric('actors.step_ms_mean')['args']
  # No counters at all (another driver's run, a rehearsal's empty obs).
  assert cycle_counter.read({}, **args) is None
  assert cycle_counter.read({'counters': {}}, **args) is None
  assert cycle_counter.read(
      {'counters': {'open': {'server': {}}, 'close': {'server': {}}}},
      **args) is None
  # The parent commit under these files: `fleet.stats()` without any of
  # the record's keys. Every new metric reads 0.0 and the run ends with
  # a result; none raises.
  parent = {'fleet': {'unrolls': 7, 'block_steps': 9},
            'server': {'calls': 3, 'batcher_requests': 3,
                       'latency_p50_ms': 2.0}}
  before = {'window_seconds': 5.0,
            'counters': {'open': parent, 'close': parent}}
  for name in NEW:
    assert cycle_counter.read(
        before, **loader.load_metric(name)['args']) == 0.0, name
  # A program that keeps the records and lost ONE key: still an error.
  moved = _obs({'group_steps': 1}, {'group_steps': 9})
  assert cycle_counter.read(moved, **args) is None


@pytest.fixture(scope='module')
def traced_rehearsal():
  return _run(loader.ROOT, '--workload', CELL, '--seed', '2147483659',
              '--seconds', '2', '--trace', '1', '--rehearse')


def test_every_new_entry_is_read_in_the_cells_traced_rehearsal(
    traced_rehearsal):
  assert not traced_rehearsal.left
  result = _result(traced_rehearsal)
  assert result['correct'] is True and result['failed'] == 0
  metrics = {name: m['value'] for name, m in result['metrics'].items()}
  for name in NEW:  # read, not left out
    assert 'rehearsal.' + name in metrics, name
    assert f'metric {name}: nothing to read' not in traced_rehearsal.stdout
  value = lambda name: metrics['rehearsal.' + name]  # noqa: E731
  phases = [value(name) for name in SERVER[:4]]
  assert all(v > 0 for v in phases)
  # The mean of the server's own latency is the three phases after the
  # wait, and its p95 is no mean of a calmer time.
  assert sum(phases[1:]) < value('batcher.wait_ms_per_request')
  assert value('inference.call_host_ms_p95') >= (
      metrics['rehearsal.inference.call_host_ms_p50'])
  assert value('actors.step_ms_mean') > 0 and value('actors.step_ms_p50') > 0
  assert 0.0 <= value('actors.excess_in_publish_share') <= 100.0
  assert value('actors.excess_ms_per_s') >= 0
  assert 0 < value('actors.env_child_ms_per_step') < value(
      'actors.env_step_ms_per_step') < value('actors.step_ms_mean')

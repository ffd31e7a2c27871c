"""PR 39's per-layer metric, `inference.inline_call_share`: the share of
a window's calls that the inference server answered on their caller's
thread (`server.stats()['inline_calls']` by `calls`), read by PR 37's
`cycle_counter` reader in `deep_dmlab.fleet32` alone. The parent commit,
under these files, has no such counter and reads 0.0; the cell's traced
rehearsal reads 100. And everything PR 37's own test of its thirteen
entries held of them by their place at the END of `per_layer`, which
this entry moves (a file under the benchmark's `paths`, not this PR's to
edit), held from their first entry."""

import pytest

from benchmark.harness import loader
from benchmark.readers import cycle_counter
from test_benchmark_cells import _result, _run
from test_cycle_metrics import FLEET, NEW, SERVER

MANIFEST = loader.load_manifest()
CELL = 'deep_dmlab.fleet32'
NAME = 'inference.inline_call_share'


def test_the_entry_is_appended_and_keeps_to_the_contract():
  entry = MANIFEST['per_layer'][-1]
  assert entry == {
      'name': NAME, 'unit': '%', 'better': 'higher',
      'source': 'program_counter', 'layer': 'inference_server',
      'moves': 'fleet_fps', 'workloads': [CELL]}
  layers = {m['layer'] for m in MANIFEST['per_layer'][:-1]}
  assert entry['layer'] in layers  # a layer the benchmark already names
  assert entry['moves'] in {
      m['name'] for m in loader.cell_metrics(MANIFEST, CELL, 'end_to_end')}
  spec = loader.load_metric(NAME)
  assert set(spec) == {'reader', 'args', 'what'} and len(spec['what']) > 20
  assert spec['reader'] == 'cycle_counter'
  assert spec['args'] == {'source': 'server', 'key': 'inline_calls',
                          'since': 'inline_calls',
                          'per': ['server', 'calls'], 'scale': 100}


def test_pr37_entries_keep_their_place_from_their_first():
  names = [m['name'] for m in MANIFEST['per_layer']]
  first = names.index(NEW[0])
  entries = MANIFEST['per_layer'][first:first + len(NEW)]
  assert [m['name'] for m in entries] == NEW  # appended, in this order
  e2e = {m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')}
  layers = {m['layer'] for m in MANIFEST['per_layer'][:first]}
  for m in entries:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert m['source'] == 'program_counter' and m['better'] == 'lower'
    assert m['layer'] in layers  # a layer the benchmark already names
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


def _obs(opened, closed):
  return {'window_seconds': 5.0,
          'counters': {'open': {'server': opened},
                       'close': {'server': closed}}}


@pytest.mark.parametrize('opened,closed,share', [
    # The parent: no `inline_calls` at all; its run ends with 0.0.
    ({'calls': 3, 'batcher_requests': 3}, {'calls': 9,
                                           'batcher_requests': 9}, 0.0),
    ({'calls': 3, 'inline_calls': 3}, {'calls': 9, 'inline_calls': 9},
     100.0),
    ({'calls': 3, 'inline_calls': 1}, {'calls': 11, 'inline_calls': 3},
     25.0),
    ({'calls': 3, 'inline_calls': 3}, {'calls': 3, 'inline_calls': 3},
     0.0),  # a window without calls
], ids=['parent', 'every_call', 'a_quarter', 'no_calls'])
def test_the_reader_by_the_counters_it_is_given(opened, closed, share):
  args = loader.load_metric(NAME)['args']
  assert cycle_counter.read(_obs(opened, closed), **args) == share


def test_the_cells_traced_rehearsal_reads_every_call_inline():
  done = _run(loader.ROOT, '--workload', CELL, '--seed', '2147483671',
              '--seconds', '2', '--trace', '1', '--rehearse')
  assert not done.left
  result = _result(done)
  assert result['correct'] is True and result['failed'] == 0
  assert result['metrics']['rehearsal.' + NAME]['value'] == 100.0
  # A call holds the group's rows (`requests` counts rows: the check
  # that calls merged, benchmark/drivers/train_loop.py, still holds).
  assert result['metrics']['rehearsal.batcher.mean_merge']['value'] > 1

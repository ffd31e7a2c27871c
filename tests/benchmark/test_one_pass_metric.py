"""The per-layer metric `actors.one_pass_step_share`: the share of a
window's group steps that went as one pass over the group's shared
block (`fleet.stats()['pass_steps']` by `group_steps`), read by the
`cycle_counter` reader in `deep_dmlab.fleet32` alone. A program without
the counter reads 0.0; the cell's traced rehearsal reads 100. And
everything the inline-call share's own test held of its entry by its
place at the END of `per_layer`, which this entry moves (that test's
file is under the benchmark's `paths`), held by the entry's name."""

import pytest

from benchmark.harness import loader
from benchmark.readers import cycle_counter
from test_benchmark_cells import _result, _run

MANIFEST = loader.load_manifest()
CELL = 'deep_dmlab.fleet32'
NAME = 'actors.one_pass_step_share'
INLINE = 'inference.inline_call_share'


def _entry(name):
  (entry,) = [m for m in MANIFEST['per_layer'] if m['name'] == name]
  return entry


@pytest.mark.parametrize('name,layer,reader_args', [
    (NAME, 'actors', {'source': 'fleet', 'key': 'pass_steps',
                      'since': 'pass_steps',
                      'per': ['fleet', 'group_steps'], 'scale': 100}),
    (INLINE, 'inference_server', {'source': 'server',
                                  'key': 'inline_calls',
                                  'since': 'inline_calls',
                                  'per': ['server', 'calls'],
                                  'scale': 100}),
], ids=['one_pass_step_share', 'inline_call_share'])
def test_the_entry_keeps_to_the_contract(name, layer, reader_args):
  entry = _entry(name)
  assert entry == {
      'name': name, 'unit': '%', 'better': 'higher',
      'source': 'program_counter', 'layer': layer,
      'moves': 'fleet_fps', 'workloads': [CELL]}
  before = MANIFEST['per_layer'][:MANIFEST['per_layer'].index(entry)]
  # A layer the benchmark named before the entry came.
  assert entry['layer'] in {m['layer'] for m in before}
  assert entry['moves'] in {
      m['name'] for m in loader.cell_metrics(MANIFEST, CELL, 'end_to_end')}
  spec = loader.load_metric(name)
  assert set(spec) == {'reader', 'args', 'what'} and len(spec['what']) > 20
  assert spec['reader'] == 'cycle_counter'
  assert spec['args'] == reader_args


def test_the_entry_is_appended_after_the_inline_share():
  names = [m['name'] for m in MANIFEST['per_layer']]
  assert names[-2:] == [INLINE, NAME]


def _obs(opened, closed):
  return {'window_seconds': 5.0,
          'counters': {'open': {'fleet': opened},
                       'close': {'fleet': closed}}}


@pytest.mark.parametrize('opened,closed,share', [
    # The parent: no `pass_steps` at all; its run ends with 0.0.
    ({'group_steps': 3, 'block_steps': 96}, {'group_steps': 9,
                                             'block_steps': 288}, 0.0),
    ({'group_steps': 3, 'pass_steps': 3}, {'group_steps': 9,
                                           'pass_steps': 9}, 100.0),
    ({'group_steps': 3, 'pass_steps': 1}, {'group_steps': 11,
                                           'pass_steps': 3}, 25.0),
    ({'group_steps': 3, 'pass_steps': 3}, {'group_steps': 3,
                                           'pass_steps': 3},
     0.0),  # a window without steps
], ids=['parent', 'every_step', 'a_quarter', 'no_steps'])
def test_the_reader_by_the_counters_it_is_given(opened, closed, share):
  args = loader.load_metric(NAME)['args']
  assert cycle_counter.read(_obs(opened, closed), **args) == share


def test_the_cells_traced_rehearsal_steps_every_group_in_one_pass():
  done = _run(loader.ROOT, '--workload', CELL, '--seed', '2147483693',
              '--seconds', '2', '--trace', '1', '--rehearse')
  assert not done.left
  result = _result(done)
  assert result['correct'] is True and result['failed'] == 0
  assert result['metrics']['rehearsal.' + NAME]['value'] == 100.0

"""Finds a cell's files by the names in BENCHMARK.json.

Nothing here lists cells, configurations, traffic mixes, drivers,
metrics or readers: each is a file of its own, found by name under the
checkout that holds this file. A later PR adds a cell with one
`workloads` entry plus new files, and edits nothing that is there.

  BENCHMARK.json workloads[]        -> {name, config, traffic, chips}
  BENCHMARK.json configs[].file     -> benchmark/configs/<config>.json
  benchmark/traffic/<traffic>.json  -> driver name + its parameters
  benchmark/drivers/<driver>.py     -> run(ctx) -> observations
  benchmark/metrics/<metric>.json   -> reader name + its arguments
  benchmark/readers/<reader>.py     -> read(observations, **args)

Which metrics a cell reports is BENCHMARK.json's to say: an entry of
`end_to_end` or `per_layer` applies to every cell, or to the cells its
optional `workloads` key names.
"""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchmarkError(Exception):
  """The benchmark's own files do not fit together (a missing file, a
  name nobody defines): fix the data, not the harness."""


def _read_json(path):
  try:
    with open(path) as f:
      return json.load(f)
  except OSError as e:
    raise BenchmarkError(f'cannot read {path}: {e}') from e
  except ValueError as e:
    raise BenchmarkError(f'{path} is not JSON: {e}') from e


def load_manifest(root=ROOT):
  return _read_json(os.path.join(root, 'BENCHMARK.json'))


def _by_name(entries, name, what):
  found = [e for e in entries if e.get('name') == name]
  if len(found) != 1:
    raise BenchmarkError(
        f'{what} {name!r}: {len(found)} entries in BENCHMARK.json '
        f'(known: {sorted(e.get("name") for e in entries)})')
  return found[0]


def find_cell(manifest, name):
  return _by_name(manifest['workloads'], name, 'workload')


def load_config(manifest, name, root=ROOT):
  """The configuration's own file, as BENCHMARK.json places it."""
  entry = _by_name(manifest['configs'], name, 'config')
  return _read_json(os.path.join(root, entry['file']))


def load_traffic(name, root=ROOT):
  return _read_json(
      os.path.join(root, 'benchmark', 'traffic', f'{name}.json'))


def load_metric(name, root=ROOT):
  return _read_json(
      os.path.join(root, 'benchmark', 'metrics', f'{name}.json'))


def _load_module(kind, name):
  try:
    return importlib.import_module(f'benchmark.{kind}.{name}')
  except ModuleNotFoundError as e:
    if e.name != f'benchmark.{kind}.{name}':
      raise  # the module exists; something IT imports does not
    raise BenchmarkError(
        f'no benchmark/{kind}/{name}.py for the name {name!r}') from e


def load_driver(name):
  return _load_module('drivers', name)


def load_reader(name):
  return _load_module('readers', name)


def cell_metrics(manifest, cell_name, kind):
  """The `end_to_end` or `per_layer` entries this cell reports."""
  return [m for m in manifest[kind]
          if 'workloads' not in m or cell_name in m['workloads']]


def flag_args(config_file, traffic_file, overrides, rehearse=False):
  """experiment.py's command line for this cell: the configuration's
  flags, then the traffic's, then the rehearsal's tiny sizes, then
  what the harness decides per run (seed, logdir). Later wins, as on
  any command line."""
  merged = {}
  for source in (config_file, traffic_file):
    merged.update(source.get('flags', {}))
  if rehearse:
    for source in (config_file, traffic_file):
      merged.update(source.get('rehearse_flags', {}))
  merged.update(overrides)
  args = []
  for key, value in merged.items():
    if isinstance(value, bool):
      value = 'true' if value else 'false'
    args.append(f'--{key}={value}')
  return args


def build_config(args):
  """A Config through the program's OWN flag parsing, so any model a
  later PR can start from the command line can be a configuration
  without a line of harness."""
  import experiment
  experiment.FLAGS.unparse_flags()
  experiment.FLAGS(['experiment.py'] + list(args))
  return experiment.config_from_flags()

"""The span and scope metrics of one capture the program made itself.

    python3 benchmark/capture_report.py <directory>

`<directory>` is what `observability.ProfilerCapture` leaves (the
`--profile_dir` window of a run, or an SLO capture): the device-only
profile under `plugins/profile/*/` and `spans.json`, the span
recorder's rows for the same interval with the capture's landmark.
Both go onto one `Trace` on one clock (`harness/program_spans.py ::
join`, `harness/trace_scopes.py :: add_scope_line`), and every metric
file of `benchmark/metrics/` whose reader is one of READERS is read
from it. The last line of standard output is one JSON object:
`metrics` ({name: {value, unit}}; a metric the capture holds nothing
for is left out: no device plane on a CPU, no `jit_anakin_step` in a
fleet run) and, where the capture has a device plane, `device`
(`busy_s`, `window_s`) and `breakdown` as `run.py` gives them, with
`idle_gaps` naming the program's `inference/*`, `staging/*` and
`learner/*` spans.

These metrics are not entries of BENCHMARK.json: the benchmark's own
traced slice would have to arm the recorder and read the profile's
file before it deletes it, in `harness/context.py`, which the PR that
added them could not edit (PERF.md, section 7). Each metric file
carries the entry it would get under `entry`.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ('span_stat', 'span_idle_overlap', 'trace_scope_share')


def reduce(directory):
  """The result dict for one capture directory."""
  from benchmark.harness import loader, program_spans, trace_reduce
  from benchmark.harness import trace_scopes
  (path,) = glob.glob(os.path.join(
      directory, 'plugins', 'profile', '*', '*.xplane.pb'))
  with open(os.path.join(directory, 'spans.json')) as f:
    taken = json.load(f)
  trace = trace_reduce.Trace.from_xplane(path)
  landmark = taken['landmark']
  on_trace_clock = program_spans.join(
      trace, taken, landmark['host_perf_ns'],
      rf'^{landmark["module"]}\b')
  scoped = trace_scopes.add_scope_line(trace, path)
  print(f'{path}: {len(taken.get("spans", ()))} program spans '
        f'({taken.get("dropped", 0)} dropped), on the '
        f'{"trace" if on_trace_clock else "host"}\'s clock; {scoped} '
        'device operations with a scope path', flush=True)
  obs, metrics = {'trace': trace}, {}
  for file in sorted(glob.glob(
      os.path.join(ROOT, 'benchmark', 'metrics', '*.json'))):
    name = os.path.basename(file)[:-len('.json')]
    spec = loader.load_metric(name)
    if spec['reader'] not in READERS:
      continue
    value = loader.load_reader(spec['reader']).read(obs, **spec['args'])
    if value is not None:
      metrics[name] = {'value': float(value),
                       'unit': spec['entry']['unit']}
  result = {'metrics': metrics}
  if trace.chips():
    busy = trace_reduce.busy(trace)
    result['device'] = {'busy_s': busy['busy_s'],
                        'window_s': busy['window_s']}
    result['breakdown'] = {
        'device_ops': trace_reduce.top_ops(trace),
        'idle_gaps': trace_reduce.idle_gaps(trace)}
  return result


if __name__ == '__main__':
  sys.path.insert(0, ROOT)
  print(json.dumps(reduce(sys.argv[1])), flush=True)

"""Model FLOP/s utilization, in percent: the FLOPs the forward and
backward passes need per execution (benchmark/harness/flops.py, from
the configuration's shapes; `flops` names the function) over the
device time of the matching XLA module (trace), over chips x peak
(benchmark/harness/peaks.json, by device_kind; an unknown kind is an
error)."""

import json

from benchmark.harness import flops as flops_lib
from benchmark.harness import trace_reduce


def read(obs, module_regex, flops):
  trace = obs.get('trace')
  if trace is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  if times is None:
    return None
  with open(obs['peaks_path']) as f:
    peaks = json.load(f)
  kind = obs['device']['kind']
  if kind not in peaks:
    raise KeyError(f'no peak for device_kind {kind!r} in peaks.json')
  needed = getattr(flops_lib, flops)(obs['config'])
  seconds = times['seconds'] / times['count']
  chips = len(trace.chips())
  return 100.0 * needed / seconds / (
      chips * peaks[kind]['bf16_flops_per_s'])

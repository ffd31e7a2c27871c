"""One kernel's share of its roofline, in percent, where bytes bound
it: the bytes it must move a call (`quantity` names the function of
benchmark/harness/brumby_counts.py, by the call's live rows) over the
kernel's device time a call, over the chip's `hbm_bytes_per_s`
(benchmark/harness/peaks.json).

The kernel's time is the sum of the durations of the device operations
it ran as, inside the executions of the XLA module matching
`module_regex`, over the number of those executions. The operations
are found by `op_regex` on their names (`XLA Ops`): a Pallas kernel is
a custom call that the v5e's trace names as the program named the
kernel. None where the trace has no such operation (a program without
the kernel)."""

import json
import re

import numpy as np

from benchmark.harness import brumby_counts, trace_reduce
from benchmark.readers.trace_call_share import live_rows


def read(obs, module_regex, quantity, op_regex):
  trace, rows = obs.get('trace'), live_rows(obs)
  if trace is None or rows is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  if times is None:
    return None
  op = re.compile(op_regex)
  seconds = []
  for _, lines in trace.chips():
    ops = lines.get(trace_reduce.OPS_LINE)
    if ops is None:
      continue
    hit = np.asarray([bool(op.search(n)) for n in ops.names])
    if hit.any():
      seconds.append(float(np.sum(ops.dur[hit])) / 1e9)
  if not seconds:
    return None
  with open(obs['peaks_path']) as f:
    peaks = json.load(f)
  kind = obs['device']['kind']
  if kind not in peaks:
    raise KeyError(f'no peak for device_kind {kind!r} in peaks.json')
  # The traced slice may cut an execution at either end; module_times
  # counts whole ones, the operations are summed over the slice: one
  # call's worth of error in some hundreds.
  per_call = float(np.mean(seconds)) / times['count']
  needed = getattr(brumby_counts, quantity)(obs['config'], rows)
  print(f'trace_kernel_roofline {op_regex}: {per_call * 1e3:.3f} ms a '
        f'call, {needed:.4g} bytes a call', flush=True)
  return 100.0 * needed / per_call / peaks[kind]['hbm_bytes_per_s']

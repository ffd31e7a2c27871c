"""The device's idle share, in percent: 1 - (union of the intervals in
which an operation ran on the device) / (the traced window), mean over
the chips, from the profiler's trace."""

from benchmark.harness import trace_reduce


def read(obs):
  trace = obs.get('trace')
  if trace is None or not trace.chips():
    return None
  busy = trace_reduce.busy(trace)
  return 100.0 * (1.0 - busy['busy_s'] / busy['window_s'])

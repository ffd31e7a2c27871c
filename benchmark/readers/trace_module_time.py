"""Device milliseconds per execution of the compiled programs (XLA
modules) whose name matches `module_regex`, from the profiler's trace,
mean over the chips."""

from benchmark.harness import trace_reduce


def read(obs, module_regex):
  trace = obs.get('trace')
  if trace is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  if times is None:
    return None
  print(f'trace_module_time {module_regex}: {times["count"]:.0f} '
        f'executions, {times["seconds"]:.4f} s on the device',
        flush=True)
  return times['seconds'] / times['count'] * 1e3

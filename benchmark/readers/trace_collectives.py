"""Collective operations in the profiler's trace, mean over the chips:
`what='ms_per_step'` is their time per execution of the XLA module
matching `module_regex`; `what='exposed_share'` is the percentage of
that time during which no other operation ran on the chip."""

from benchmark.harness import trace_reduce


def read(obs, what, module_regex):
  trace = obs.get('trace')
  if trace is None or len(trace.chips()) < 2:
    return None
  times = trace_reduce.collective_times(trace)
  if what == 'exposed_share':
    if times['seconds'] <= 0:
      return None
    return 100.0 * times['exposed_seconds'] / times['seconds']
  modules = trace_reduce.module_times(trace, module_regex)
  if modules is None:
    return None
  return times['seconds'] / modules['count'] * 1e3

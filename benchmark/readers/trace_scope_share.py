"""The share, in percent, of a compiled program's device time that ran
under one scope: the SELF time of the operations (a `while` spans its
body) that began inside an execution of the XLA module matching
`module_regex` and whose scope path (`harness/trace_scopes.py`: the
operation's `op_name`, where `jax.named_scope` and Flax's module names
end up) matches `scope_regex`, over the self time of all operations
inside those executions; forward and backward together, chips pooled.
None where the trace has no scope line (no device plane, or no stat of
the profile carried the path) or no such module."""

import re

import numpy as np

from benchmark.harness import trace_reduce, trace_scopes


def read(obs, module_regex, scope_regex):
  trace = obs.get('trace')
  if trace is None:
    return None
  module, scope = re.compile(module_regex), re.compile(scope_regex)
  total = matched = 0.0
  for _, lines in trace.chips():
    ev = lines.get(trace_scopes.SCOPES_LINE)
    modules = lines.get(trace_reduce.MODULES_LINE)
    if ev is None or modules is None:
      continue
    hit = np.asarray([bool(module.search(n)) for n in modules.names])
    m_starts, m_ends = trace_reduce._merge(
        modules.start[hit], (modules.start + modules.dur)[hit])
    if len(m_starts) == 0:
      continue
    i = np.searchsorted(m_starts, ev.start, side='right') - 1
    inside = (i >= 0) & (ev.start < m_ends[np.maximum(i, 0)])
    self_time = trace_reduce._self_times(ev)
    hits = {n: bool(scope.search(n)) for n in set(ev.names)}
    in_scope = np.asarray([hits[n] for n in ev.names])
    total += float(np.sum(self_time[inside]))
    matched += float(np.sum(self_time[inside & in_scope]))
  if total <= 0:
    return None
  print(f'trace_scope_share {scope_regex} in {module_regex}: '
        f'{matched / 1e9:.4f} of {total / 1e9:.4f} s', flush=True)
  return 100.0 * matched / total

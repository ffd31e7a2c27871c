"""One scope's share of its roofline, in percent: what the work under
the scope must move and compute a call (`bytes_quantity`,
`flops_quantity`: functions of benchmark/harness/<counts>.py, called
with the program's Config and what a merged call carried in the mean,
as `trace_counted_share.py` makes it) over the device time a call of
the operations under the scope, over the chip's `hbm_bytes_per_s` and
`bf16_flops_per_s` (benchmark/harness/peaks.json). With both
quantities the LARGER of the two shares is reported: work near the
chip's ridge is bound by whichever it is nearer to.

The scope's time is the SELF time (a `while` spans its body) of the
device operations that began inside an execution of the XLA module
matching `module_regex` and whose scope path (`harness/trace_scopes.py`:
where `jax.named_scope` ends up) matches `scope_regex`, mean over the
chips, over the number of those executions. Read BY SCOPE, it is the
same work whether XLA's own operations or a kernel do it. None where
the trace has no scope line, no such module or nothing under the
scope."""

import re

import numpy as np

from benchmark.harness import trace_reduce, trace_scopes
from benchmark.readers import trace_counted_share


def scope_seconds(trace, module_regex, scope_regex):
  """Seconds under the scope inside the module's executions, per chip
  (mean); None where there is nothing to read."""
  module, scope = re.compile(module_regex), re.compile(scope_regex)
  seconds = []
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
    hits = {n: bool(scope.search(n)) for n in set(ev.names)}
    in_scope = np.asarray([hits[n] for n in ev.names])
    seconds.append(float(np.sum(
        trace_reduce._self_times(ev)[inside & in_scope])) / 1e9)
  if not seconds or not any(seconds):
    return None
  return float(np.mean(seconds))


def read(obs, module_regex, scope_regex, counts, bytes_quantity=None,
         flops_quantity=None):
  trace = obs.get('trace')
  carried = trace_counted_share.per_call(obs)
  if trace is None or carried is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  seconds = scope_seconds(trace, module_regex, scope_regex)
  if times is None or seconds is None:
    return None
  per_call = seconds / times['count']
  shares = []
  for quantity, peak in ((bytes_quantity, 'hbm_bytes_per_s'),
                         (flops_quantity, 'bf16_flops_per_s')):
    if quantity is None:
      continue
    needed = trace_counted_share.counted(counts, quantity, obs, carried)
    shares.append(100.0 * needed / per_call /
                  trace_counted_share.peak_of(obs, peak))
    print(f'trace_scope_roofline {scope_regex}: {counts}.{quantity} '
          f'{needed:.4g} a call in {per_call * 1e3:.3f} ms: '
          f'{shares[-1]:.1f}% of {peak}', flush=True)
  return max(shares) if shares else None

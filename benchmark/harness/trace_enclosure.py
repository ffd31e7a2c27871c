"""Scope paths for the device operations that have none of their own.

`trace_scopes.add_scope_line` names each operation by its HLO
instruction's `op_name`. The compiler's own instructions carry none:
on a TPU the copies that prefetch an operation's weights into fast
memory (`copy-start` / `copy-done`) are such, and the time the device
WAITS in a `copy-done` is the time of reading those weights from HBM:
in a decode step, where reading weights is the work, a quarter of the
program's device time (PR 32, chip run). Left at `(no scope)` it is
missing from the scope that caused it, and a roofline share of that
scope, its bytes over its time, reads over 100%.

`inherit_scopes` gives every such operation a path, by two rules:

1. inside an operation that has a path and spans it in time (a
   `conditional`'s branch, a `while`'s body), that operation's path;
2. else the path of the next operation with a path that begins on the
   chip inside the same execution of its program: the scheduler puts a
   `copy-done` as late as it can, before the operation that consumes
   what was copied.

An operation with no later scoped operation in its execution keeps
`(no scope)`.
"""

import numpy as np

from benchmark.harness import trace_reduce, trace_scopes


def inherit_scopes(trace):
  """Renames in place; returns (operations renamed, left unnamed)."""
  renamed = left = 0
  for chip, lines in trace.chips():
    ev = lines.get(trace_scopes.SCOPES_LINE)
    modules = lines.get(trace_reduce.MODULES_LINE)
    if ev is None or modules is None:
      continue
    names = list(ev.names)
    ends = ev.start + ev.dur
    # The execution an operation began in (-1: none).
    execution = np.searchsorted(modules.start, ev.start, side='right') - 1
    inside = (execution >= 0) & (
        ev.start < (modules.start + modules.dur)[np.maximum(execution, 0)])
    execution = np.where(inside, execution, -1)
    # Rule 1: events arrive sorted by (start, -dur), so the open
    # enclosing events are a stack.
    stack, pending = [], []
    for i in range(len(names)):
      while stack and ends[stack[-1]] <= ev.start[i]:
        stack.pop()
      if names[i] == trace_scopes.NO_SCOPE:
        if stack and ends[i] <= ends[stack[-1]]:
          names[i] = names[stack[-1]]
          renamed += 1
        else:
          pending.append(i)
      if names[i] != trace_scopes.NO_SCOPE:
        stack.append(i)
    # Rule 2: the next scoped operation of the same execution.
    following, follows = None, {}
    for i in reversed(range(len(names))):
      if names[i] != trace_scopes.NO_SCOPE:
        following = i
      elif (following is not None and execution[i] >= 0 and
            execution[following] == execution[i]):
        follows[i] = names[following]
    for i in pending:
      if i in follows:
        names[i] = follows[i]
        renamed += 1
      else:
        left += 1
    plane = f'/device:TPU:{chip}'
    lines.update(trace_reduce.Trace.from_rows(
        (plane, trace_scopes.SCOPES_LINE, name, start, dur)
        for name, start, dur in zip(names, ev.start, ev.dur)).planes[plane])
  return renamed, left

"""How much of the device's idle time lies inside one of the program's
own spans, in percent: the idle time of the traced slice (the
complement of `trace_reduce`'s busy union: the intervals in which no
operation ran on the chip) that the union of the `span` events, on any
thread, covers / all the idle time of the slice; mean over the chips.
Needs the device plane AND the spans on its clock: None for a capture
made on a CPU, or one without the recorder's rows."""

import numpy as np

from benchmark.harness import program_spans, trace_reduce


def read(obs, span):
  trace = obs.get('trace')
  if trace is None or not trace.chips():
    return None
  pairs = [program_spans.intervals(ev, {span})
           for ev in program_spans.threads(trace)]
  if not pairs or not sum(len(starts) for starts, _ in pairs):
    return None
  span_starts = np.concatenate([starts for starts, _ in pairs])
  span_ends = np.concatenate([ends for _, ends in pairs])
  window = trace_reduce.traced_window(trace)
  shares = []
  for _, lines in trace.chips():
    gap_starts, gap_ends = trace_reduce._gaps(
        *trace_reduce._op_intervals(lines, window), window)
    idle = float(np.sum(gap_ends - gap_starts))
    if idle <= 0:
      return None
    inside = float(np.sum(program_spans.covered(
        gap_starts, gap_ends, span_starts, span_ends)))
    print(f'span_idle_overlap {span}: {len(span_starts)} spans over '
          f'{inside / 1e9:.3f} of {idle / 1e9:.3f} s idle', flush=True)
    shares.append(100.0 * inside / idle)
  return float(np.mean(shares))

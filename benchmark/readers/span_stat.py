"""A statistic of one of the program's own spans (the recorder of
`scalable_agent_tpu/telemetry.py`, armed over a profiler capture; on
the `Trace` as `harness/program_spans.py :: join` leaves them). Host
clocks, so a capture made on a CPU gives them too.

`span` names the span. `minus` names child spans whose time is taken
out of each `span` event: what they cover of it on its own thread
(containment; the union, so nested children are not taken out twice).
What is left is the span's self time where `minus` lists all its
children. `stat`:

  p50         median of that time per event, ms
  mean        mean of it per event, ms
  per_step    its sum over the slice / the number of `per` spans in
              the slice (`per` names the span that counts steps), ms
  busy_share  its sum over all threads / the armed interval, percent
              (100 = one core's worth)

None where the trace holds no such span.
"""

import numpy as np

from benchmark.harness import program_spans


def read(obs, span, stat, minus=(), per=None):
  trace = obs.get('trace')
  if trace is None:
    return None
  times = program_spans.self_times(trace, span, minus)
  if len(times) == 0:
    return None
  print(f'span_stat {span} minus {list(minus)}: {len(times)} spans, '
        f'{np.sum(times) / 1e9:.4f} s', flush=True)
  if stat == 'p50':
    return float(np.median(times)) / 1e6
  if stat == 'mean':
    return float(np.mean(times)) / 1e6
  if stat == 'per_step':
    steps = program_spans.count(trace, per)
    return float(np.sum(times)) / 1e6 / steps if steps else None
  if stat == 'busy_share':
    lo, hi = program_spans.armed_interval(trace)
    return 100.0 * float(np.sum(times)) / (hi - lo)
  raise ValueError(f'span_stat: no stat {stat!r}')

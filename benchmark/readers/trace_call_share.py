"""A compiled program's share of a peak of the chip, in percent: what
one execution must compute or move (`quantity` names the function of
benchmark/harness/brumby_counts.py, counted by the call's live rows,
from the configuration's shapes) over the device time of the XLA
module matching `module_regex` per execution (trace; the rows are the
mean the server counted a merged call over the window), over the peak
`peak` of benchmark/harness/peaks.json (by device_kind; an unknown kind
is an error). With `hbm_bytes_per_s` it is the whole step's share of
its roofline where bytes bound it (a decode step); with
`bf16_flops_per_s` the whole step's share of the arithmetic peak."""

import json

from benchmark.harness import brumby_counts, trace_reduce


def live_rows(obs):
  """Rows a merged call carried, as the server counted them over the
  window (`counters`: rows and calls at its open and close); padded
  rows are not among them. None where no call was counted."""
  counters = obs.get('counters') or {}
  try:
    opened, closed = counters['open']['server'], counters['close']['server']
    calls = closed['calls'] - opened['calls']
    return (closed['requests'] - opened['requests']) / calls
  except (KeyError, ZeroDivisionError):
    return None


def read(obs, module_regex, quantity, peak):
  trace, rows = obs.get('trace'), live_rows(obs)
  if trace is None or rows is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  if times is None:
    return None
  with open(obs['peaks_path']) as f:
    peaks = json.load(f)
  kind = obs['device']['kind']
  if kind not in peaks:
    raise KeyError(f'no peak for device_kind {kind!r} in peaks.json')
  needed = getattr(brumby_counts, quantity)(obs['config'], rows)
  seconds = times['seconds'] / times['count']
  print(f'trace_call_share {quantity}: {needed:.4g} a call, '
        f'{seconds * 1e3:.3f} ms a call over {times["count"]:.0f} calls',
        flush=True)
  return 100.0 * needed / seconds / (
      len(trace.chips()) * peaks[kind][peak])

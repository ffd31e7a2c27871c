"""A compiled program's share of a peak of the chip, in percent, by
counts that a module of benchmark/harness names: what one execution
must compute or move (`quantity` names the function of
benchmark/harness/<counts>.py, called with the program's Config and
with what a merged call carried in the mean, `per_call`) over the
device time of the XLA module matching `module_regex` per execution
(trace), over the peak `peak` of benchmark/harness/peaks.json (by
device_kind; an unknown kind is an error). `trace_call_share.py` is
the same for `brumby_counts`, which it names in its code; this reader
takes the counts module as an argument.

`per_call`: the server's numeric counters (`stats()`), each as its
delta over the calls it was counted in, divided by those calls:
`requests` is then the live rows a call, `cache_tokens_read` the
cached tokens its rows read, and so on by the counters' names. The
counters are taken around the TRACED slice where the driver read them
there (`counters.trace_open` / `trace_close`: a state that grows with
the episode makes a later call dearer than a window's), else around
the window. None where no call was counted."""

import importlib
import json
import numbers

from benchmark.harness import trace_reduce


def per_call(obs):
  counters = obs.get('counters') or {}
  for first, last in (('trace_open', 'trace_close'), ('open', 'close')):
    try:
      opened, closed = counters[first]['server'], counters[last]['server']
      calls = closed['calls'] - opened['calls']
    except KeyError:
      continue
    if calls <= 0:
      continue
    return {key: (closed[key] - opened[key]) / calls
            for key, value in closed.items()
            if isinstance(value, numbers.Number)
            and not isinstance(value, bool)
            and isinstance(opened.get(key), numbers.Number)}
  return None


def peak_of(obs, peak):
  with open(obs['peaks_path']) as f:
    peaks = json.load(f)
  kind = obs['device']['kind']
  if kind not in peaks:
    raise KeyError(f'no peak for device_kind {kind!r} in peaks.json')
  return peaks[kind][peak]


def counted(counts, quantity, obs, carried):
  module = importlib.import_module(f'benchmark.harness.{counts}')
  return getattr(module, quantity)(obs['config'], carried)


def read(obs, module_regex, counts, quantity, peak):
  trace, carried = obs.get('trace'), per_call(obs)
  if trace is None or carried is None:
    return None
  times = trace_reduce.module_times(trace, module_regex)
  if times is None:
    return None
  needed = counted(counts, quantity, obs, carried)
  seconds = times['seconds'] / times['count']
  print(f'trace_counted_share {counts}.{quantity}: {needed:.4g} a call, '
        f'{seconds * 1e3:.3f} ms a call over {times["count"]:.0f} calls',
        flush=True)
  return 100.0 * needed / seconds / (
      len(trace.chips()) * peak_of(obs, peak))

"""From a profiler trace (.xplane.pb) to numbers. The one reducer.

    python benchmark/harness/trace_reduce.py <file.xplane.pb>

prints what the trace holds (planes, lines, the names that take the
time) for a look by hand, which is where any new reader starts.

What the trace of a TPU v5e looks like (PR 22, chip run, jax 0.9.0,
libtpu 0.0.34): one plane per chip named `/device:TPU:<n>`, with the
lines `Steps`, `XLA Modules` (one event per execution of a compiled
program, named `jit_<function>(<fingerprint>)`), `XLA Ops` (one event
per HLO operation, named by the WHOLE instruction text; a `while` spans
the operations of its body, so times by name are SELF times) and `Async
XLA Ops` (copy-start..done spans that overlap the operations; not
counted as busy). All planes share one clock, in nanoseconds from the
start of the session. The other planes (`/host:CPU`, `#Chip0 Host
Interface`, `/host:metadata`, `Task Environment`, `/device:CUSTOM:
Megascale Trace`) are not read: the benchmark traces with the host
tracer off (harness/context.py says why) and brings its own host spans,
named `bench:<what>`, onto the trace's clock with `add_host_spans`.
Idle gaps on the device are attributed to the innermost such span that
covers them.
"""

import collections
import gzip
import json
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
HOST_PLANE = '/host:CPU'
MODULES_LINE = 'XLA Modules'
OPS_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'
SPAN_PREFIX = 'bench:'
TRACE_SPAN = SPAN_PREFIX + 'trace'
COLLECTIVE = re.compile(
    r'^%?(all-reduce|all-gather|reduce-scatter|collective-permute|'
    r'all-to-all|collective-broadcast)')
# Operations that only hold others: their time is their body's.
_CONTAINER = re.compile(r'^%?(while|call|conditional)')
# Gaps shorter than this are launch spacing between operations, not
# the host holding the chip back; they are summed under one name.
SHORT_GAP_NS = 10_000

Events = collections.namedtuple('Events', 'names start dur')


def _events(rows):
  rows.sort(key=lambda r: (r[1], -r[2]))
  return Events([r[0] for r in rows],
                np.asarray([r[1] for r in rows], np.float64),
                np.asarray([r[2] for r in rows], np.float64))


class Trace:
  """{plane name: {line name: Events}}, times in nanoseconds. Lines
  of one plane that share a name (host threads do) are merged."""

  def __init__(self, planes):
    self.planes = planes

  @classmethod
  def from_rows(cls, rows):
    """rows: iterable of (plane, line, name, start_ns, dur_ns)."""
    grouped = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for plane, line, name, start, dur in rows:
      grouped[plane][line].append((name, float(start), float(dur)))
    return cls({plane: {line: _events(rs) for line, rs in lines.items()}
                for plane, lines in grouped.items()})

  @classmethod
  def from_xplane(cls, path):
    """Reads the device planes of the profiler's own file with nothing
    but JAX. (The host plane is not read: see `add_host_spans`.)"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)

    def rows():
      for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
          for line in plane.lines:
            for event in line.events:
              yield (plane.name, line.name, event.name, event.start_ns,
                     event.duration_ns)

    return cls.from_rows(rows())

  @classmethod
  def from_recorded(cls, path):
    """A trace cut down and kept as rows (benchmark/testdata): gzip'd
    JSON {'rows': [[plane, line, name, start_ns, dur_ns], ...]}."""
    with gzip.open(path, 'rt') as f:
      return cls.from_rows(json.load(f)['rows'])

  def to_rows(self):
    for plane, lines in self.planes.items():
      for line, ev in lines.items():
        for name, start, dur in zip(ev.names, ev.start, ev.dur):
          yield [plane, line, name, float(start), float(dur)]

  def chips(self):
    """[(chip index, {line: Events})] in chip order."""
    found = []
    for name, lines in self.planes.items():
      match = DEVICE_PLANE.match(name)
      if match:
        found.append((int(match.group(1)), lines))
    return sorted(found, key=lambda item: item[0])

  def host_spans(self):
    """{span name: (starts, ends)} of the benchmark's own host spans,
    each sorted by start."""
    spans = collections.defaultdict(list)
    for ev in self.planes.get(HOST_PLANE, {}).values():
      for name, start, dur in zip(ev.names, ev.start, ev.dur):
        if name.startswith(SPAN_PREFIX):
          spans[name].append((start, start + dur))
    out = {}
    for name, pairs in spans.items():
      pairs.sort()
      out[name] = (np.asarray([p[0] for p in pairs]),
                   np.asarray([p[1] for p in pairs]))
    return out


def add_host_spans(trace, spans, landmark_host_ns, landmark_regex):
  """Puts the benchmark's own host spans [(name, t0_ns, t1_ns)], taken
  on the host's clock, onto the trace's clock as `bench:<name>` events
  of the host plane. The two clocks meet at a landmark: a tiny program
  whose end the host saw at `landmark_host_ns` and the trace recorded
  as the end of the first `XLA Modules` event matching
  `landmark_regex`. False, and nothing added, where the trace holds no
  such event (a CPU has no device plane)."""
  regex = re.compile(landmark_regex)
  ends = [ev.start[i] + ev.dur[i] for _, lines in trace.chips()
          for ev in [lines.get(MODULES_LINE)] if ev is not None
          for i, name in enumerate(ev.names) if regex.search(name)]
  if not ends:
    return False
  offset = min(ends) - landmark_host_ns
  rows = [(SPAN_PREFIX + name, t0 + offset, t1 - t0)
          for name, t0, t1 in spans]
  trace.planes.setdefault(HOST_PLANE, {})['benchmark'] = _events(rows)
  return True


# --- Interval arithmetic. ---


def _merge(starts, ends):
  """Union of intervals as sorted, disjoint (starts, ends)."""
  if len(starts) == 0:
    return np.zeros(0), np.zeros(0)
  order = np.argsort(starts, kind='stable')
  starts, ends = np.asarray(starts)[order], np.asarray(ends)[order]
  running_end = np.maximum.accumulate(ends)
  new = np.ones(len(starts), bool)
  new[1:] = starts[1:] > running_end[:-1]
  first = np.flatnonzero(new)
  last = np.append(first[1:] - 1, len(starts) - 1)
  return starts[first], running_end[last]


def _clip(starts, ends, window):
  lo, hi = window
  starts, ends = np.maximum(starts, lo), np.minimum(ends, hi)
  keep = ends > starts
  return starts[keep], ends[keep]


def _length(starts, ends):
  return float(np.sum(ends - starts))


def _gaps(starts, ends, window):
  """The complement of disjoint sorted intervals inside `window`."""
  lo, hi = window
  gap_starts = np.append(lo, ends)
  gap_ends = np.append(starts, hi)
  keep = gap_ends > gap_starts
  return gap_starts[keep], gap_ends[keep]


def _op_intervals(lines, window, which=None, line=OPS_LINE):
  ev = lines.get(line)
  if ev is None or len(ev.start) == 0:
    return np.zeros(0), np.zeros(0)
  starts, ends = ev.start, ev.start + ev.dur
  if which is not None:
    mask = np.asarray([bool(which(n)) for n in ev.names])
    starts, ends = starts[mask], ends[mask]
  return _merge(*_clip(starts, ends, window))


# --- Reductions. ---


def traced_window(trace):
  """(start, end) of the traced window: the benchmark's own
  `bench:trace` span if it is there, else the extent of the device
  events."""
  spans = trace.host_spans().get(TRACE_SPAN)
  if spans is not None and len(spans[0]):
    return float(spans[0][0]), float(spans[1][-1])
  lo, hi = np.inf, -np.inf
  for _, lines in trace.chips():
    for ev in lines.values():
      if len(ev.start):
        lo = min(lo, float(ev.start.min()))
        hi = max(hi, float((ev.start + ev.dur).max()))
  if not hi > lo:
    raise ValueError('the trace holds no device event')
  return lo, hi


def busy(trace, window=None):
  """{'busy_s', 'window_s', 'per_chip_busy_s'}: seconds in which an
  operation ran on the device (the union of the `XLA Ops` intervals
  inside the window), averaged over the chips traced."""
  window = window or traced_window(trace)
  per_chip = [_length(*_op_intervals(lines, window)) / 1e9
              for _, lines in trace.chips()]
  if not per_chip:
    raise ValueError('the trace holds no device plane')
  return {'busy_s': float(np.mean(per_chip)),
          'window_s': (window[1] - window[0]) / 1e9,
          'per_chip_busy_s': per_chip}


def module_times(trace, pattern, window=None):
  """{'seconds', 'count'} of the `XLA Modules` events whose name
  matches `pattern` and which lie wholly inside the traced window (an
  execution cut by the trace's start or stop is recorded shorter than
  it was), per chip (mean over chips): the device time of a compiled
  program, execution by execution. None where nothing matches."""
  if not trace.chips():
    return None
  regex = re.compile(pattern)
  lo, hi = window or traced_window(trace)
  seconds, counts = [], []
  for _, lines in trace.chips():
    ev = lines.get(MODULES_LINE)
    hit = ([i for i, n in enumerate(ev.names) if regex.search(n) and
            ev.start[i] >= lo and ev.start[i] + ev.dur[i] <= hi]
           if ev is not None else [])
    seconds.append(float(np.sum(ev.dur[hit])) / 1e9 if hit else 0.0)
    counts.append(len(hit))
  if not counts or not any(counts):
    return None
  return {'seconds': float(np.mean(seconds)),
          'count': float(np.mean(counts))}


def collective_times(trace, window=None):
  """{'seconds', 'exposed_seconds'} per chip (mean): time inside
  collective operations (on the operations line, or between an
  asynchronous one's start and done on the async line), and the part
  of it during which no other operation ran on that chip."""
  window = window or traced_window(trace)
  is_collective = lambda n: COLLECTIVE.match(n)  # noqa: E731
  total, exposed = [], []
  for _, lines in trace.chips():
    sync = _op_intervals(lines, window, is_collective)
    asyn = _op_intervals(lines, window, is_collective, ASYNC_LINE)
    c_starts, c_ends = _merge(np.concatenate([sync[0], asyn[0]]),
                              np.concatenate([sync[1], asyn[1]]))
    o_starts, o_ends = _op_intervals(
        lines, window, lambda n: not COLLECTIVE.match(n) and
        not _CONTAINER.match(n))
    total.append(_length(c_starts, c_ends))
    # Exposed = collective time minus its overlap with other work.
    overlap = 0.0
    for s, e in zip(c_starts, c_ends):
      overlap += _length(*_clip(o_starts, o_ends, (s, e)))
    exposed.append(total[-1] - overlap)
  if not total:
    raise ValueError('the trace holds no device plane')
  return {'seconds': float(np.mean(total)) / 1e9,
          'exposed_seconds': float(np.mean(exposed)) / 1e9}


def _self_times(ev):
  """Each event's duration minus that of the events nested in it (a
  `while` spans its body). Events arrive sorted by (start, -dur)."""
  self_time = ev.dur.copy()
  stack = []  # indices of the open enclosing events
  ends = ev.start + ev.dur
  for i in range(len(ev.start)):
    while stack and ends[stack[-1]] <= ev.start[i]:
      stack.pop()
    if stack and ends[i] <= ends[stack[-1]]:
      self_time[stack[-1]] -= ev.dur[i]
    stack.append(i)
  return np.maximum(self_time, 0.0)


_LAYOUT = re.compile(r'\{[^}]*\}')


def short_name(name, limit=120):
  """`%fusion.12 = bf16[64,36,48,16] fusion` from the instruction text
  the trace names an operation by (layouts and operands dropped)."""
  text = _LAYOUT.sub('', name)
  head, eq, rest = text.partition(' = ')
  if not eq:
    return text[:limit]
  if rest.startswith('('):  # a tuple of outputs
    depth, end = 0, 0
    for end, ch in enumerate(rest):
      depth += (ch == '(') - (ch == ')')
      if depth == 0:
        break
    shape, tail = rest[:end + 1], rest[end + 1:]
  else:
    shape, _, tail = rest.partition(' ')
  op = tail.strip().split('(', 1)[0]
  return f'{head} = {shape} {op}'[:limit]


def top_ops(trace, limit=10):
  """[[name, seconds]]: the device operations that took most SELF
  time, under the names the trace gives them, mean over chips."""
  totals = collections.Counter()
  chips = trace.chips()
  for _, lines in chips:
    ev = lines.get(OPS_LINE)
    if ev is None:
      continue
    for name, t in zip(ev.names, _self_times(ev)):
      totals[short_name(name)] += t
  return [[name, float(t) / 1e9 / len(chips)]
          for name, t in totals.most_common(limit)]


def idle_gaps(trace, window=None, limit=10):
  """[[what the host was doing, seconds]]: the idle time of the first
  chip inside the window, summed by the innermost `bench:` span that
  covers each gap's middle; gaps under SHORT_GAP_NS are launch spacing
  and summed apart."""
  window = window or traced_window(trace)
  chips = trace.chips()
  if not chips:
    raise ValueError('the trace holds no device plane')
  gap_starts, gap_ends = _gaps(*_op_intervals(chips[0][1], window),
                               window)
  spans = {name: pair for name, pair in trace.host_spans().items()
           if name != TRACE_SPAN}
  totals = collections.Counter()
  for start, end in zip(gap_starts, gap_ends):
    if end - start < SHORT_GAP_NS:
      totals['between operations (gaps under 10 us)'] += end - start
      continue
    middle, best, best_len = (start + end) / 2, None, np.inf
    for name, (s, e) in spans.items():
      i = int(np.searchsorted(s, middle, side='right')) - 1
      # Spans of one name may overlap across threads; look a few back.
      for j in range(i, max(i - 8, -1), -1):
        if e[j] >= middle and e[j] - s[j] < best_len:
          best, best_len = name, e[j] - s[j]
    totals[best or 'no bench: span'] += end - start
  return [[name, float(t) / 1e9] for name, t in totals.most_common(limit)]


def describe(path, top=8):
  """What a trace file holds, for a look by hand."""
  from jax.profiler import ProfileData
  out = []
  for plane in ProfileData.from_file(path).planes:
    out.append(f'PLANE {plane.name!r}')
    for line in plane.lines:
      totals, counts = collections.Counter(), collections.Counter()
      lo, hi, n, stat_keys = np.inf, -np.inf, 0, None
      for event in line.events:
        n += 1
        totals[event.name] += event.duration_ns
        counts[event.name] += 1
        lo = min(lo, event.start_ns)
        hi = max(hi, event.start_ns + event.duration_ns)
        if stat_keys is None:
          stat_keys = [str(k) for k, _ in event.stats]
      out.append(f'  LINE {line.name!r}: {n} events, '
                 f'{lo / 1e6:.3f}..{hi / 1e6:.3f} ms, '
                 f'stats {stat_keys}')
      for name, t in totals.most_common(top):
        out.append(f'    {t / 1e6:10.3f} ms  x{counts[name]:<6d} '
                   f'{name[:100]}')
  return '\n'.join(out)


if __name__ == '__main__':
  print(describe(sys.argv[1]))

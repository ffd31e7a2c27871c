"""Fleet-wide telemetry plane (round 13): unified metrics registry,
per-unroll trace spans, and the incident flight recorder.

Nine PRs in, the stack could say how FAST each plane runs (fps meters,
per-lane counters, bench rows) but not WHERE a single unroll spends its
time or what the behaviour-vs-target policy-lag distribution — the
quantity V-trace actually corrects for (IMPALA, arXiv 1802.01561) —
looks like under load. Podracer (arXiv 2104.06272) makes the same
point for pods: the scheduling story is only as good as the cross-host
telemetry behind it. This module is that layer, in three pieces:

1. **Metrics registry** — `Counter` / `Gauge` / `Histogram` objects
   that every component registers into ONE process-wide
   `MetricsRegistry` instead of keeping module-local ints with
   per-module reporting paths. `snapshot()` is the single source of
   truth the driver's drain manifest, the health halt bundle, the
   flight recorder, and the remote `stats` control-lane request all
   read. Registration is by NAME with latest-wins replacement: a
   per-run component (an ingest server, a health monitor) re-registers
   its metrics on construction and the snapshot always reflects the
   live incarnation. EVERY registration in scalable_agent_tpu/ must
   use the literal-string module helpers
   (`telemetry.counter('<component>/<name>')`, same for gauge /
   histogram) —
   scripts/ci.sh lints that each registered name appears in
   docs/OBSERVABILITY.md's inventory (and that no documented name is
   orphaned), which only works because the names are greppable
   literals.

2. **Trace spans** — a compact per-unroll trace context (actor id,
   per-actor sequence number, session epoch, behaviour params version)
   stamped with wall-clock hop timestamps as the unroll moves through
   the pipeline: env-step completion → actor send → wire receipt →
   ingest validate/commit → staging → learner serve → train step. The
   context rides the unroll's wire frame on the remote lanes
   (protocol v8, negotiated at hello — older peers simply don't
   stamp) and a bounded identity-keyed sidecar (`tag_unroll` /
   `pop_unroll`) inside a process, because trajectory pytrees cannot
   carry extra leaves without breaking the wire contract. The
   learner-side `PipelineTracer` assembles completed spans into
   `traces.jsonl` — one line per trained batch, carrying every
   member unroll's hop list and the batch's policy-lag vector
   (published version at train time minus each unroll's behaviour
   version). `scripts/trace_report.py` reconstructs per-hop latency
   and the lag distribution from this stream.

   Hop timestamps are `time.time()` (wall clock), not monotonic:
   spans cross process (and host) boundaries, where monotonic clocks
   do not compare. Within a host the deltas are exact; across hosts
   they carry NTP skew — docs/OBSERVABILITY.md documents the caveat.

3. **Flight recorder** — a bounded in-memory ring of the most recent
   trace records plus periodic registry snapshots. A halt or rollback
   then ships the last N seconds of pipeline history (what was the
   lag doing? did installs stall?) with the diagnostic bundle instead
   of a point-in-time counter dump (health.write_halt_bundle /
   driver.train's rollback incident path).

4. **Span recorder** — where pieces 1-3 count and stamp per UNROLL on
   the wall clock, the recorder times the layer boundaries of the
   fleet path per ENV STEP on `time.perf_counter_ns()`, in memory, and
   only while armed (`arm_spans` .. `take_spans`). Off is the default
   and costs a site one global read and one branch. A profiler capture
   arms it (observability.ProfilerCapture, the benchmark's traced
   slice); the clock pair read at arming joins its rows to the hop
   stamps above, a landmark program joins them to the device trace.
   Span names are listed in docs/OBSERVABILITY.md ("Spans"). Two
   things of it are ALWAYS on (PR 37), because the cycles that cost a
   mean its median does not show happen in runs nobody traced: a
   `CycleRecord` (the last N cycles of one loop, a few stamps each, on
   the same clock) and the ring of finished `activity` parks (the
   rare, heavy work of other threads); `excess` lays one over the
   other. docs/OBSERVABILITY.md "Cycle records".

Costs are measured, not assumed: the feed pipeline was run with
tracing on and off, and the always-on default is an accept/reject call
recorded in docs/PERF.md (r11); what the span recorder costs on the
chip is in the root PERF.md.

No jax imports here — actor hosts and test helpers use this module
before (or without) jax initialization.
"""

import collections
import json
import math
import operator
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

NAN = float('nan')


# --------------------------------------------------------------------
# Metrics registry.
# --------------------------------------------------------------------


class Counter:
  """Monotone (well, add-only) counter. Thread-safe."""

  def __init__(self, name: str):
    self.name = name
    self._value = 0
    self._lock = threading.Lock()

  def inc(self, n: int = 1):
    with self._lock:
      self._value += n

  @property
  def value(self):
    with self._lock:
      return self._value

  def snapshot_value(self):
    return self.value


class Gauge:
  """Point-in-time value: either `set()` by its owner, or backed by a
  zero-argument callable (`fn=`) read lazily at snapshot time — the
  adoption path for existing stats surfaces (a component registers
  `telemetry.gauge('<component>/<name>', fn=lambda: self._n)` and its
  module-local bookkeeping becomes registry-visible without rewriting
  the bookkeeping). A callback that raises reads as NaN: a torn-down
  component must never break the snapshot that is trying to describe
  the teardown."""

  def __init__(self, name: str, fn: Optional[Callable] = None):
    self.name = name
    self._fn = fn
    self._value = 0.0
    self._lock = threading.Lock()

  def set(self, value):
    with self._lock:
      self._value = value

  @property
  def value(self):
    if self._fn is not None:
      try:
        return self._fn()
      except Exception:
        return NAN
    with self._lock:
      return self._value

  def snapshot_value(self):
    return self.value


class Histogram:
  """Bounded-reservoir histogram: cumulative count/sum plus sample
  percentiles over the most recent `maxlen` observations (the
  LatencyReservoir design, promoted to a registry citizen). Empty →
  NaN percentiles — reports render '-', nothing crashes."""

  def __init__(self, name: str, maxlen: int = 4096):
    self.name = name
    self._samples = collections.deque(maxlen=maxlen)
    self._lock = threading.Lock()
    self._count = 0
    self._sum = 0.0
    self._max = NAN

  def observe(self, value):
    v = float(value)
    with self._lock:
      self._samples.append(v)
      self._count += 1
      self._sum += v
      self._max = v if math.isnan(self._max) else max(self._max, v)

  @property
  def count(self) -> int:
    with self._lock:
      return self._count

  def percentiles(self, *qs: float) -> Tuple[float, ...]:
    with self._lock:
      snap = sorted(self._samples)
    if not snap:
      return tuple(NAN for _ in qs)
    last = len(snap) - 1
    return tuple(snap[min(last, int(round(q * last)))] for q in qs)

  def snapshot_value(self) -> Dict:
    p50, p99 = self.percentiles(0.5, 0.99)
    with self._lock:
      return {'count': self._count, 'sum': round(self._sum, 6),
              'max': self._max, 'p50': p50, 'p99': p99}


class MetricsRegistry:
  """Name → metric map with a thread-safe `snapshot()`.

  Registration replaces by name (latest instance wins): components are
  per-run objects and the registry is process-global, so the snapshot
  must describe the LIVE incarnation — a test constructing ten ingest
  servers leaves the last one's counters registered, which is exactly
  the production semantics (one live server per process)."""

  def __init__(self):
    self._metrics: Dict[str, object] = {}
    self._lock = threading.Lock()

  def register(self, metric):
    with self._lock:
      self._metrics[metric.name] = metric
    return metric

  def counter(self, name: str) -> Counter:
    return self.register(Counter(name))

  def gauge(self, name: str, fn: Optional[Callable] = None) -> Gauge:
    return self.register(Gauge(name, fn=fn))

  def histogram(self, name: str, maxlen: int = 4096) -> Histogram:
    return self.register(Histogram(name, maxlen=maxlen))

  def get(self, name: str):
    with self._lock:
      return self._metrics.get(name)

  def unregister(self, name: str, metric=None):
    """Remove `name` — but when `metric` is given, only if it is the
    REGISTERED instance (identity check): a closing component must
    not evict a newer incarnation that already replaced it under the
    same name. fn-gauges close over their owner, so unregistering at
    teardown is what lets a finished run's pipeline objects be
    collected instead of pinned by the registry for the process
    lifetime."""
    with self._lock:
      if metric is None or self._metrics.get(name) is metric:
        self._metrics.pop(name, None)

  def names(self) -> List[str]:
    with self._lock:
      return sorted(self._metrics)

  def snapshot(self) -> Dict:
    """One JSON-serializable dict of every registered metric's current
    value (counters/gauges → number, histograms → {count, sum, max,
    p50, p99}). The read is point-in-time per metric, not a global
    atomic cut — consumers (drain manifest, flight recorder, fleet
    stats request) want recency, not transactional consistency."""
    with self._lock:
      metrics = list(self._metrics.values())
    out = {}
    for m in metrics:
      v = m.snapshot_value()
      if isinstance(v, (np.integer, np.floating)):
        v = v.item()
      out[m.name] = v
    return out


# The process-wide default registry. Module helpers below are the ONLY
# registration spellings used inside scalable_agent_tpu/ — the ci.sh
# metric-name lint greps for them.
_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
  return _REGISTRY


def counter(name: str) -> Counter:
  return _REGISTRY.counter(name)


def gauge(name: str, fn: Optional[Callable] = None) -> Gauge:
  return _REGISTRY.gauge(name, fn=fn)


def histogram(name: str, maxlen: int = 4096) -> Histogram:
  return _REGISTRY.histogram(name, maxlen=maxlen)


# --------------------------------------------------------------------
# Span recorder.
# --------------------------------------------------------------------

# Rows refused because the armed recorder was full (`max_spans`).
_SPANS_DROPPED = counter('trace/spans_dropped')


class _SpanRecorder:
  """One armed interval: its rows, its bound, and the clock pair."""

  def __init__(self, max_spans: int):
    self.rows: List[Tuple] = []
    self.threads: Dict[int, str] = {}  # ident -> name, as rows arrive
    self.max_spans = max_spans
    self.dropped_before = _SPANS_DROPPED.value
    self.wall_ns = time.time_ns()
    self.perf_ns = time.perf_counter_ns()

  def add(self, name, t0, t1, span_id, thread=None):
    # list.append is atomic under the GIL; the bound may be passed by
    # at most one row per racing thread.
    if len(self.rows) < self.max_spans:
      if thread is None:
        thread = threading.get_ident()
        if thread not in self.threads:  # a thread may end before take
          self.threads[thread] = threading.current_thread().name
      self.rows.append((name, t0, t1, thread, span_id))
    else:
      _SPANS_DROPPED.inc()


# The armed recorder, or None: THE global every span site reads.
_recorder: Optional[_SpanRecorder] = None
# The `id` spans of this thread inherit from their enclosing span
# (only ever touched while a recorder is armed).
_span_ids = threading.local()
# Parks under way, armed or not: a set of _Park (add/discard and
# list() are atomic under the GIL).
_open_parks = set()
# The last finished `activity` parks, armed or not: (name, t0, t1) on
# the perf clock (append and list() are atomic under the GIL). A few
# a second in a training run: minutes of them.
_ACTIVITY_RING = 4096
_activities = collections.deque(maxlen=_ACTIVITY_RING)
# The sites that are activities, so that a reader finds each name at
# zero before its first park ends. A name not listed here is taken up
# when it first appears.
ACTIVITIES = ('learner/publish', 'learner/summaries',
              'learner/checkpoint', 'staging/stage', 'inference/prefill')


class _Site:
  """What a span site holds: ended by `end()` or by leaving its `with`
  block."""
  __slots__ = ()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.end()
    return False


class _NoSpan(_Site):
  """What a span site gets while the recorder is off: one shared
  object that does nothing."""
  __slots__ = ()

  def __exit__(self, *exc):
    return False

  def end(self):
    pass


NO_SPAN = _NoSpan()
_NOTHING_TO_RESTORE = object()


class _Span(_Site):
  """A span under way. Ends once; kept if the recorder it began under
  is still the armed one."""
  __slots__ = ('_recorder', '_name', '_id', '_t0', '_outer_id')

  def __init__(self, recorder, name, span_id):
    self._recorder = recorder
    self._name = name
    outer = getattr(_span_ids, 'id', None)
    if span_id is None:
      self._id = outer
      self._outer_id = _NOTHING_TO_RESTORE
    else:
      self._id = _span_ids.id = span_id
      self._outer_id = outer
    self._t0 = time.perf_counter_ns()

  def end(self):
    t1 = time.perf_counter_ns()
    recorder, self._recorder = self._recorder, None
    if recorder is None:
      return
    if self._outer_id is not _NOTHING_TO_RESTORE:
      _span_ids.id = self._outer_id
    if recorder is _recorder:
      recorder.add(self._name, self._t0, t1, self._id)


def span(name: str, id=None):  # noqa: A002 — the row's field name
  """A span site: `with telemetry.span('actor/step'):`, or
  `s = telemetry.span(...)` .. `s.end()` where a block will not do.
  Off (the default) this is one global read and one branch: no clock
  read, no allocation, no lock. Armed, the row `(name, t0_ns, t1_ns,
  thread_ident, id)` is kept when the span ends. `id` names the
  request the span belongs to (a batcher `batch_id`, an unroll's
  `(actor, seq)`); a span given none inherits its enclosing span's on
  the same thread. A span that began before arming, or is still open
  at `take_spans`, is not kept: see `park` for the waits that must be."""
  recorder = _recorder
  if recorder is None:
    return NO_SPAN
  return _Span(recorder, name, id)


class _Park(_Site):
  __slots__ = ('name', 'id', 't0', 'thread', 'remembered')

  def __init__(self, name, span_id, remembered=False):
    self.name = name
    self.id = span_id
    self.remembered = remembered
    self.thread = threading.get_ident()
    self.t0 = time.perf_counter_ns()
    _open_parks.add(self)

  def end(self):
    if self not in _open_parks:
      return  # ended already
    _open_parks.discard(self)
    recorder = _recorder
    if recorder is None and not self.remembered:
      return
    t1 = time.perf_counter_ns()
    if self.remembered:
      _activities.append((self.name, self.t0, t1))
    if recorder is not None:
      span_id = self.id
      if span_id is None:
        span_id = getattr(_span_ids, 'id', None)
      # A wait that began before arming is kept from arming on.
      recorder.add(self.name, max(self.t0, recorder.perf_ns), t1,
                   span_id)


def park(name: str, id=None):  # noqa: A002
  """A span site for a WAIT (a thread parked on a queue, a condition,
  a full buffer) or for the one long span that holds such a wait
  (`learner/iteration`), used like `span`. Unlike `span` it reads the
  clock and notes itself as under way even while the recorder is off,
  so that a wait which straddles either end of the armed interval is
  kept, clipped to it: one that began before `arm_spans` starts at the
  arming instant, and one still parked at `take_spans` is closed there
  (its later real end, recorder off again, adds nothing). A learner's
  wait of seconds straddles an end of a 15 s capture more often than
  not. Only for the few sites that run under ~100 times a second. Its
  `id` may be set inside the block (`with park(..) as p: p.id = ..`)."""
  return _Park(name, id)


def activity(name: str, id=None):  # noqa: A002
  """A park for WORK that is rare and heavy (a parameter publish, a
  batch staged, a checkpoint written: about one a second each), used
  like `park` and kept by an armed recorder like one. Finished, it
  also leaves `(name, t0_ns, t1_ns)` in a bounded ring of the last
  few thousand, recorder on or off: what `excess` asks when a cycle of
  some OTHER thread's loop ran long. Not for waits (a wait holds
  nothing anyone else needs) and not for sites that run hundreds of
  times a second."""
  return _Park(name, id, remembered=True)


def activities(since_ns: int = 0,
               now_ns: Optional[int] = None) -> List[Tuple]:
  """The finished activities the ring still holds that ended at
  `since_ns` or later, oldest first, then those under way, each closed
  at `now_ns` (default: now): `(name, t0_ns, t1_ns)` on the perf
  clock."""
  if now_ns is None:
    now_ns = time.perf_counter_ns()
  finished = list(_activities)  # in the order they ended
  first = len(finished)
  while first and finished[first - 1][2] >= since_ns:
    first -= 1
  under_way = [(p.name, p.t0, now_ns) for p in list(_open_parks)
               if p.remembered and p.t0 < now_ns]
  return finished[first:] + under_way


def arm_spans(max_spans: int = 400_000) -> Dict:
  """Arms a fresh recorder (dropping an armed one's rows) and returns
  the clock pair `{'perf_ns', 'wall_ns'}`, both read now: span rows
  are on the first clock, the hop stamps of traces.jsonl on the
  second. Rows past `max_spans` are dropped and counted on
  'trace/spans_dropped'."""
  global _recorder
  recorder = _SpanRecorder(max_spans)
  _recorder = recorder
  return {'perf_ns': recorder.perf_ns, 'wall_ns': recorder.wall_ns}


def take_spans() -> Optional[Dict]:
  """Disarms the recorder and hands its rows over whole, or None when
  none was armed: {'clock': {'perf_ns', 'wall_ns'} (at arming),
  'taken_ns' (perf clock, now), 'spans': [(name, t0_ns, t1_ns,
  thread_ident, id), ...], 'threads': {thread_ident: name},
  'dropped': rows this interval refused}. Parks still under way are
  closed at `taken_ns`."""
  global _recorder
  recorder, _recorder = _recorder, None
  if recorder is None:
    return None
  taken_ns = time.perf_counter_ns()
  for p in list(_open_parks):
    recorder.add(p.name, max(p.t0, recorder.perf_ns), taken_ns, p.id,
                 thread=p.thread)
  for t in threading.enumerate():  # the threads still parked
    recorder.threads.setdefault(t.ident, t.name)
  return {'clock': {'perf_ns': recorder.perf_ns,
                    'wall_ns': recorder.wall_ns},
          'taken_ns': taken_ns, 'spans': recorder.rows,
          'threads': recorder.threads,
          'dropped': _SPANS_DROPPED.value - recorder.dropped_before}


# --- Cycle records: the recorder's always-on part (PR 37). ---

_NS_PER_MS = 1e6
# `excess` judges a cycle against the median of this many newest rows.
_EXCESS_REFERENCE = 4096


def nearest_rank(ascending, q):
  """runtime/inference.percentile_ms's rule, on an ascending array."""
  n = len(ascending)
  return float(ascending[min(n - 1, int(n * q))]) if n else 0.0


class CycleRecord:
  """The last `rows` cycles of ONE loop, always on: each cycle a row of
  `len(phases) + 1` stamps on `time.perf_counter_ns()` (the span
  recorder's clock: a row can be laid beside armed spans and, through
  the landmark, the device trace; phase k runs from stamp k to stamp
  k + 1) and of `extras`, further int64 numbers of the cycle (a count,
  a duration measured elsewhere). Beside the ring, what never wraps:
  the count of cycles and the sum of every phase and extra, so that a
  window's means come from their change between two readings and not
  from the rows that happen to be held.

  One thread writes (`write`, a whole row at once: no lock, no array
  made); any thread reads (`summary`, `held`, `excess`). A reader
  never takes a half-written row for a cycle: the writer announces a
  row before it overwrites its place in the ring and counts it after,
  and a reader drops what was announced while it copied.

  `cycle_from` is the stamp a cycle's LENGTH counts from, for a loop
  whose first phase is a wait for work (the length `summary` gives as
  'cycle' and `excess` judges)."""

  def __init__(self, phases, extras=(), rows=32768, cycle_from=0):
    self.phases = tuple(phases)
    self.extras = tuple(extras)
    self.cycle_from = cycle_from
    self._stamps = len(self.phases) + 1
    self._ring = np.zeros((rows, self._stamps + len(self.extras)),
                          np.int64)
    self._announced = 0  # rows begun, ever
    self._written = 0    # rows whole, ever
    # Column sums over every row ever written (Python ints: a phase's
    # total is the difference of two stamps' sums).
    self._sums = (0,) * (self._stamps + len(self.extras))
    # `excess`: the rows it has judged, and what it found, ever.
    self._excess_lock = threading.Lock()
    self._judged = 0
    self._lost = 0
    self._excess_ns = 0.0
    self._unnamed_ns = 0.0
    self._in_ns = {name: 0.0 for name in ACTIVITIES}

  def write(self, *values):
    """One cycle: its stamps in order, then its extras."""
    i = self._written
    self._announced = i + 1
    self._ring[i % len(self._ring)] = values
    self._sums = tuple(map(operator.add, self._sums, values))
    self._written = i + 1

  @property
  def cycles(self) -> int:
    return self._written

  def totals(self) -> Dict:
    """{'cycles', '<phase>_ns' a phase, '<extra>' an extra}: sums over
    every cycle ever written (read while the writer writes, the count
    and the sums may be one cycle apart)."""
    sums, cycles = self._sums, self._written
    out = {'cycles': cycles}
    for k, name in enumerate(self.phases):
      out[name + '_ns'] = sums[k + 1] - sums[k]
    for name, total in zip(self.extras, sums[self._stamps:]):
      out[name] = total
    return out

  def held(self, since=0, last=None):
    """(index of the first row returned, a copy of the whole rows
    `since` .. newest that the ring still holds; at most the `last`
    newest)."""
    ring = self._ring
    n = len(ring)
    end = self._written
    start = max(since, end - n, 0)
    if last is not None:
      start = max(start, end - last)
    rows = np.take(ring, np.arange(start, end) % n, axis=0)
    # What the writer began while the copy was made is not whole.
    start2 = max(start, self._announced - n)
    return start2, rows[start2 - start:]

  def lengths(self, rows):
    """The cycle lengths of `held` rows, ns."""
    return rows[:, self._stamps - 1] - rows[:, self.cycle_from]

  def summary(self) -> Dict:
    """Off the writing path: {'cycles' (ever), 'held', and per phase,
    and for 'cycle' (stamp `cycle_from` to the last): {'mean', 'p50',
    'p95', 'max'}} in ms over the rows held."""
    _, rows = self.held()
    out = {'cycles': self._written, 'held': len(rows)}
    columns = {name: rows[:, k + 1] - rows[:, k]
               for k, name in enumerate(self.phases)}
    columns['cycle'] = self.lengths(rows)
    for name, ns in columns.items():
      ascending = np.sort(ns)
      out[name] = {
          'mean': float(ns.mean()) / _NS_PER_MS if len(ns) else 0.0,
          'p50': nearest_rank(ascending, 0.5) / _NS_PER_MS,
          'p95': nearest_rank(ascending, 0.95) / _NS_PER_MS,
          'max': float(ascending[-1]) / _NS_PER_MS if len(ns) else 0.0}
    return out


def _covered(starts, ends, t):
  """How much of (-inf, t] the disjoint ascending intervals cover."""
  before = np.concatenate([[0], np.cumsum(ends - starts)])
  i = np.searchsorted(starts, t, side='right')  # intervals begun by t
  inside = np.minimum(t, ends[np.maximum(i - 1, 0)]) - starts[
      np.maximum(i - 1, 0)]
  return before[np.maximum(i - 1, 0)] + np.where(i > 0, inside, 0)


def _overlap(intervals, t0, t1):
  """ns of each cycle [t0, t1] that lie under the union of
  `intervals` (an [m, 2] array in any order)."""
  if not len(intervals):
    return np.zeros(len(t0), np.int64)
  order = np.argsort(intervals[:, 0])
  starts, ends = intervals[order, 0], intervals[order, 1]
  ends = np.maximum.accumulate(ends)  # the union, made disjoint:
  starts = np.maximum(starts, np.concatenate([[starts[0]], ends[:-1]]))
  ends = np.maximum(ends, starts)
  return _covered(starts, ends, t1) - _covered(starts, ends, t0)


def excess(record: CycleRecord, parks=None) -> Dict:
  """What `record`'s mean pays over its median, and under what: the
  cycles written since the last call are judged and the sums, which
  are CUMULATIVE like the record's own, returned (all ns):

    {'excess_ns', 'in_ns': {activity name: ..}, 'unnamed_ns',
     'cycles_judged', 'cycles_lost'}

  A cycle's excess is its length less the record's median (over its
  newest 4,096 rows now), where that is positive. No threshold: one
  cycle held for 145 ms and twenty cycles at twice their length both
  count, and the record's `max` tells them apart. Of a cycle's excess, the part
  charged to an activity's name is excess x (what of the cycle lay
  under that name's parks / its length); `unnamed_ns` is the part
  under no activity at all (by their union), so the names' parts and
  `unnamed_ns` sum to `excess_ns` unless two activities ran at once.
  Rows that a lap of the ring took before any call judged them are
  counted (`cycles_lost`), not guessed. `parks`: (name, t0_ns, t1_ns)
  rows to judge against instead of `activities()` (a test's)."""
  with record._excess_lock:
    first, rows = record.held(since=record._judged)
    record._lost += first - record._judged
    record._judged = first + len(rows)
    if len(rows):
      _, reference = record.held(last=_EXCESS_REFERENCE)
      held = record.lengths(reference)
      middle = min(len(held) - 1, len(held) // 2)  # `nearest_rank`'s
      median = float(np.partition(held, middle)[middle])
      t0 = rows[:, record.cycle_from]
      t1 = rows[:, record._stamps - 1]
      length = t1 - t0
      over = np.maximum(length - median, 0.0)
      share = over / np.maximum(length, 1)
      if parks is None:
        parks = activities(since_ns=int(t0.min()), now_ns=int(t1.max()))
      by_name = {}
      for name, a, b in parks:
        by_name.setdefault(name, []).append((a, b))
      every = np.asarray([(a, b) for _, a, b in parks],
                         np.int64).reshape(-1, 2)
      record._excess_ns += float(over.sum())
      record._unnamed_ns += float(
          (share * (length - _overlap(every, t0, t1))).sum())
      for name, intervals in by_name.items():
        under = _overlap(np.asarray(intervals, np.int64), t0, t1)
        record._in_ns[name] = (record._in_ns.get(name, 0.0) +
                               float((share * under).sum()))
    return {'excess_ns': record._excess_ns,
            'in_ns': dict(record._in_ns),
            'unnamed_ns': record._unnamed_ns,
            'cycles_judged': record._judged - record._lost,
            'cycles_lost': record._lost}


def excess_ms(prefix: str, *results: Dict) -> Dict:
  """`excess` results, summed, as the flat keys a `stats()` carries:
  `<prefix>excess_ms`, `<prefix>excess_ms_in_<activity>` a name,
  `<prefix>excess_ms_unnamed`, `<prefix>cycles_lost`."""
  names = sorted({name for r in results for name in r['in_ns']} |
                 set(ACTIVITIES))
  total = lambda key: sum(r[key] for r in results)  # noqa: E731
  out = {prefix + 'excess_ms': total('excess_ns') / _NS_PER_MS}
  for name in names:
    out[f'{prefix}excess_ms_in_{name}'] = sum(
        r['in_ns'].get(name, 0.0) for r in results) / _NS_PER_MS
  out[prefix + 'excess_ms_unnamed'] = total('unnamed_ns') / _NS_PER_MS
  out[prefix + 'cycles_lost'] = total('cycles_lost')
  return out


# --------------------------------------------------------------------
# Trace spans.
# --------------------------------------------------------------------

# Hop names, in pipeline order. Spans may omit hops (a local-fleet
# unroll never crosses the wire; an old-protocol peer stamps nothing) —
# scripts/trace_report.py computes deltas between the hops that ARE
# present, in this order.
HOP_DONE = 'done'        # env-step loop completed the unroll (actor)
HOP_SEND = 'send'        # remote pump handed it to the socket
HOP_WIRE = 'wire'        # ingest reader finished receiving the frame
HOP_COMMIT = 'commit'    # validate/commit worker landed the buffer put
HOP_STAGED = 'staged'    # batch assembly picked it (host stack or
                         # per-unroll device staging)
HOP_SERVE = 'serve'      # the learner's get() took the staged batch
HOP_STEP = 'step'        # the train step consuming it was dispatched
HOP_ORDER = (HOP_DONE, HOP_SEND, HOP_WIRE, HOP_COMMIT, HOP_STAGED,
             HOP_SERVE, HOP_STEP)


def make_trace(actor, seq: int, epoch=None,
               behavior_version=None) -> Dict:
  """A fresh per-unroll trace context. Compact keys on purpose — this
  dict rides every v8 unroll frame: 'a' actor id, 's' per-actor unroll
  sequence, 'e' session epoch (the learner incarnation the actor
  believes it feeds), 'bv' the params version the actor ACTED with
  (the behaviour policy — policy lag is published-at-train minus
  this), 'h' the [hop, wall_time] stamp list."""
  trace = {'a': str(actor), 's': int(seq), 'h': []}
  if epoch is not None:
    trace['e'] = int(epoch)
  if behavior_version is not None:
    trace['bv'] = int(behavior_version)
  return trace


def stamp(trace: Optional[Dict], hop: str, t: Optional[float] = None):
  """Append one [hop, wall_time] stamp. None-tolerant (call sites
  stay unconditional on untraced old-peer unrolls) AND shape-tolerant:
  a malformed context from a buggy/skewed peer — a dict missing 'h',
  or carrying a non-list there — gets a fresh stamp list instead of
  raising into whoever stamps it (the ingest READER stamps wire
  frames; a KeyError there would drop the connection outside the
  quarantine accounting every other malformed-frame path gets)."""
  if trace is None:
    return trace
  hops = trace.get('h')
  if not isinstance(hops, list):
    hops = trace['h'] = []
  hops.append([hop, round(time.time() if t is None else t, 6)])
  return trace


class _TagStore:
  """Bounded identity-keyed sidecar: unroll pytree → trace context.

  Trajectory pytrees cannot carry extra leaves (the wire contract and
  the learner's tree_flatten would both see them), so inside a process
  the trace context travels NEXT TO the unroll, keyed by `id()`. The
  store holds NO reference to the unroll itself — a tagged unroll
  that never reaches consumption (a drain drop, a fleet-stop discard)
  must cost a stale ~200-byte trace entry, not a multi-MB pytree
  pinned for the rest of the run (the soak's slow-leak shape). The
  id-only key admits one benign hazard: a freed unroll's id can be
  reused, and a LATER untraced object at the same address could pop
  the stale trace — a mislabeled span in the telemetry stream, never
  a correctness issue (and a re-tag at the same address simply
  overwrites the stale entry). Bounded: oldest entries evicted,
  counted."""

  def __init__(self, capacity: int = 8192):
    self._capacity = capacity
    self._entries: 'collections.OrderedDict' = collections.OrderedDict()
    self._lock = threading.Lock()
    self.evicted = 0

  def tag(self, obj, trace: Dict):
    with self._lock:
      self._entries[id(obj)] = trace
      while len(self._entries) > self._capacity:
        self._entries.popitem(last=False)
        self.evicted += 1

  def pop(self, obj) -> Optional[Dict]:
    with self._lock:
      return self._entries.pop(id(obj), None)

  def __len__(self):
    with self._lock:
      return len(self._entries)


_UNROLL_TAGS = _TagStore()


def tag_unroll(unroll, trace: Optional[Dict]):
  if trace is not None:
    _UNROLL_TAGS.tag(unroll, trace)


def pop_unroll(unroll) -> Optional[Dict]:
  return _UNROLL_TAGS.pop(unroll)


# --- Actor-side stamping switch. The learner process enables it by
# installing a PipelineTracer (set_tracer); a REMOTE actor host — which
# has no tracer, its spans complete learner-side — enables it
# explicitly with configure_actor_tracing. `version_fn` supplies the
# behaviour params version stamped on each fresh trace (a mutable-cell
# closure at both call sites: reading a stats surface per unroll would
# put a lock on the env loop). ---
_actor_tracing_lock = threading.Lock()
_actor_tracing: Optional[Dict] = None


def configure_actor_tracing(version_fn: Optional[Callable] = None,
                            epoch=None):
  global _actor_tracing
  with _actor_tracing_lock:
    _actor_tracing = {'version_fn': version_fn, 'epoch': epoch}


def clear_actor_tracing():
  global _actor_tracing
  with _actor_tracing_lock:
    _actor_tracing = None


def begin_unroll_trace(actor, seq: int) -> Optional[Dict]:
  """A fresh trace for one just-completed unroll, or None when
  tracing is off in this process (the actor loop's one-line seam)."""
  with _actor_tracing_lock:
    cfg = _actor_tracing
  if cfg is None:
    tracer = get_tracer()
    if tracer is None:
      return None
    cfg = {'version_fn': tracer.behavior_version,
           'epoch': tracer.epoch}
  version = None
  if cfg.get('version_fn') is not None:
    try:
      version = cfg['version_fn']()
    except Exception:
      version = None
  return make_trace(actor, seq, epoch=cfg.get('epoch'),
                    behavior_version=version)


# --------------------------------------------------------------------
# Flight recorder.
# --------------------------------------------------------------------


class FlightRecorder:
  """Bounded ring of recent telemetry: the last `capacity` trace
  records (batches, publishes, installs) plus the last `snapshots`
  registry snapshots — dumped into the health halt bundle and the
  rollback diagnostics so an incident ships the preceding pipeline
  history, not a point-in-time counter read. Thread-safe."""

  def __init__(self, capacity: int = 512, snapshots: int = 16):
    self._records = collections.deque(maxlen=max(capacity, 8))
    self._snapshots = collections.deque(maxlen=max(snapshots, 2))
    self._lock = threading.Lock()

  def record(self, rec: Dict):
    with self._lock:
      self._records.append(rec)

  def note_registry(self, snapshot: Dict):
    """Stash one registry snapshot (call on the summary cadence)."""
    with self._lock:
      self._snapshots.append({'wall_time': round(time.time(), 3),
                              'metrics': snapshot})

  def __len__(self) -> int:
    """Trace records currently retained in the ring."""
    with self._lock:
      return len(self._records)

  @property
  def snapshots_held(self) -> int:
    with self._lock:
      return len(self._snapshots)

  def dump(self) -> Dict:
    with self._lock:
      return {'wall_time': round(time.time(), 3),
              'records': list(self._records),
              'registry_snapshots': list(self._snapshots)}

  def write(self, path: str) -> str:
    """Atomic JSON dump (tmp + rename — incident artifacts must be
    complete or absent)."""
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump(self.dump(), f, indent=2, default=str)
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------------
# The learner-side pipeline tracer.
# --------------------------------------------------------------------


# Writes that raced (or followed) close() and were dropped, across
# every JSONL appender in the process: the pre-round-13 behavior was
# a ValueError from the closed file object in whatever thread lost
# the race — a respawning actor logging one last episode could take
# its fleet slot down over a log line.
_DROPPED_WRITES = counter('observability/dropped_writes')


def dropped_writes_total() -> int:
  """Process-wide silently-dropped JSONL writes (the driver's summary
  export and the SLO engine's dropped_writes objective both read this
  instead of reaching for the private counter)."""
  return _DROPPED_WRITES.value


class JsonlAppender:
  """THE thread-safe line-buffered append-only JSONL plumbing — one
  implementation behind the scalar summaries, the incident stream
  (observability._JsonlAppender subclasses this; it lives here
  because telemetry must stay importable without the observability
  module's env-suite dependency chain, and observability already
  imports telemetry), and the tracer's traces.jsonl.

  Crash-safety contract: a write AFTER close() is a silent drop,
  counted on `dropped_writes` (+ the process-wide
  'observability/dropped_writes' registry counter) — never a raise
  into the writing thread. `durable=True` flushes + fsyncs before
  returning, so records that must survive a kill -9 (halt/rollback
  incidents) reach the disk instead of dying in the userspace buffer
  with the process."""

  def __init__(self, logdir: str, filename: str):
    os.makedirs(logdir, exist_ok=True)
    self._path = os.path.join(logdir, filename)
    self._file = open(self._path, 'a', buffering=1)
    self._lock = threading.Lock()
    self._closed = False
    self.dropped_writes = 0

  @property
  def path(self):
    return self._path

  def write(self, record: Dict, durable: bool = False,
            **dumps_kwargs):
    with self._lock:
      if self._closed:
        self.dropped_writes += 1
        _DROPPED_WRITES.inc()
        return
      self._file.write(json.dumps(record, **dumps_kwargs) + '\n')
      if durable:
        try:
          self._file.flush()
          os.fsync(self._file.fileno())
        except OSError:
          pass  # best effort: the record is written either way

  def close(self):
    with self._lock:
      if self._closed:
        return
      self._closed = True
      self._file.close()


class PipelineTracer:
  """Assembles per-unroll spans into `traces.jsonl` + the flight ring.

  One per training run, installed process-globally via `set_tracer`
  (the faults_lib.install pattern — threading a tracer through every
  constructor between the driver and the prefetcher would touch ten
  signatures for one optional observer). The staged/served FIFOs
  mirror the BatchPrefetcher's own FIFO semantics: batches are staged
  in order, served in order (re-serves skip `on_serve`), and trained
  in order — so `on_step` always completes the OLDEST served batch.
  Both FIFOs are bounded: a consumer that stops calling on_step (a
  bench loop, a halted learner) must cost dropped trace records, not
  unbounded memory.

  Emitted records (one JSON object per line in traces.jsonl):
    {'k': 'batch', 'step', 'pv' (published version at train time),
     't' (step wall time), 'n_fresh', 'lag' ([pv - bv per unroll with
     a known behaviour version]), 'spans' ([{a, s, e, bv, h}, ...])}
    {'k': 'publish', 'v', 't'}
    {'k': 'install', 'a', 'v', 't' (actor-side install time),
     't_seen' (when the notice reached the learner)}
  """

  def __init__(self, logdir: str, filename: str = 'traces.jsonl',
               flight_capacity: int = 512, epoch=None,
               version_fn: Optional[Callable] = None):
    self._writer = JsonlAppender(logdir, filename)
    self.flight = FlightRecorder(capacity=flight_capacity)
    self.epoch = epoch
    self.version_fn = version_fn
    # The local publish clock: policy lag is a PUBLISH-COUNT delta
    # (the unit V-trace's staleness story is written in), so the
    # tracer counts publishes itself for locally produced unrolls.
    # Remote unrolls arrive with a behaviour version in the ingest
    # lane's OWN publish counter — the ingest worker stamps the
    # commit-time counter value ('cv') into the trace so the delta is
    # computed within one clock; two clocks never mix.
    self._publish_count = 0
    self._lock = threading.Lock()
    self._staged = collections.deque(maxlen=64)
    self._served = collections.deque(maxlen=64)
    # Registry-backed telemetry about the telemetry (meta, but the
    # overhead/coverage questions are real: untagged unrolls mean a
    # peer isn't stamping; dropped batches mean the FIFOs overflowed).
    self._m_batches = counter('trace/batches')
    self._m_unrolls = counter('trace/unrolls')
    self._m_untagged = counter('trace/untagged_unrolls')
    self._m_installs = counter('trace/param_installs')
    self._m_dropped = counter('trace/dropped_records')
    self._h_lag = histogram('trace/policy_lag')
    self._h_e2e = histogram('trace/e2e_ms')
    # Flight-recorder occupancy (round 14): fn-gauges over the ring so
    # the registry snapshot (and the driver's summary export) can say
    # how much incident history a dump would ship. Unregistered at
    # close() — they close over this per-run tracer's flight ring.
    self._flight_gauges = [
        gauge('trace/flight_records', fn=lambda: len(self.flight)),
        gauge('trace/flight_snapshots',
              fn=lambda: self.flight.snapshots_held),
    ]

  @property
  def path(self) -> str:
    return self._writer.path

  @property
  def publish_count(self) -> int:
    return self._publish_count

  def behavior_version(self) -> Optional[int]:
    """The behaviour-policy version a locally produced unroll should
    stamp: the injected version_fn when one is set, else this
    tracer's own publish count (local actors install every publish
    synchronously, so count-at-act-time IS their behaviour version)."""
    if self.version_fn is not None:
      try:
        return self.version_fn()
      except Exception:
        return None
    return self._publish_count

  # --- ingest/commit side ---

  def tag(self, unroll, trace: Optional[Dict]):
    tag_unroll(unroll, trace)

  def on_install(self, actor, version, t_install):
    rec = {'k': 'install', 'a': str(actor), 'v': int(version),
           't': float(t_install), 't_seen': round(time.time(), 6)}
    self._m_installs.inc()
    self._writer.write(rec, default=str)
    self.flight.record(rec)

  # --- feed pipeline side (BatchPrefetcher hooks) ---

  def on_batch(self, unrolls, n_fresh: int):
    """A batch's unrolls were picked for staging (in slot order,
    fresh first). Pops their sidecar tags; replayed slots (consumed
    once already) legitimately have none."""
    now = round(time.time(), 6)
    spans = []
    for u in unrolls[:n_fresh]:
      trace = pop_unroll(u)
      if trace is None:
        self._m_untagged.inc()
      else:
        stamp(trace, HOP_STAGED, now)
        spans.append(trace)
    with self._lock:
      if len(self._staged) == self._staged.maxlen:
        self._m_dropped.inc()
      self._staged.append({'spans': spans, 'n_fresh': int(n_fresh)})

  def on_serve(self):
    """The learner's get() took a batch's FIRST serve (re-serves ride
    the same staged arena and are not new pipeline traversals)."""
    now = round(time.time(), 6)
    with self._lock:
      if not self._staged:
        return
      entry = self._staged.popleft()
      if len(self._served) == self._served.maxlen:
        self._m_dropped.inc()
      self._served.append(entry)
    for trace in entry['spans']:
      stamp(trace, HOP_SERVE, now)

  def on_step(self, step: int):
    """The train step consuming the oldest served batch was
    dispatched: complete its spans, compute the policy-lag vector
    (publish-count delta, each unroll judged within ITS clock — the
    commit-time 'cv' for remote unrolls, this tracer's publish count
    for local ones), emit the batch record."""
    now = round(time.time(), 6)
    with self._lock:
      if not self._served:
        return
      entry = self._served.popleft()
    lags = []
    for trace in entry['spans']:
      stamp(trace, HOP_STEP, now)
      bv = trace.get('bv')
      current = trace.get('cv')
      if current is None:
        current = self._publish_count
      if bv is not None:
        lag = max(int(current) - int(bv), 0)
        lags.append(lag)
        self._h_lag.observe(lag)
      if trace['h']:
        self._h_e2e.observe((trace['h'][-1][1] - trace['h'][0][1])
                            * 1e3)
    self._m_batches.inc()
    self._m_unrolls.inc(len(entry['spans']))
    rec = {'k': 'batch', 'step': int(step), 't': now,
           'pv': self._publish_count,
           'n_fresh': entry['n_fresh'], 'lag': lags,
           'spans': entry['spans']}
    self._writer.write(rec, default=str)
    self.flight.record(rec)

  def on_publish(self, version: int,
                 remote_version: Optional[int] = None):
    """A param publish landed (version is the caller's label — the
    driver publishes step-stamped snapshots); bumps the local publish
    clock the policy-lag arithmetic counts in.

    `remote_version` is the INGEST LANE's version for this snapshot
    when it was also published to the remote fleet — actors' install
    notices carry ingest-lane versions (a different sequence from the
    step-stamped label), so the publish→install join in trace_report
    must key on it ('rv'). Without it, installs at production publish
    cadences would join nothing (or the wrong publish)."""
    self._publish_count += 1
    rec = {'k': 'publish', 'v': int(version),
           'count': self._publish_count, 't': round(time.time(), 6)}
    if remote_version is not None:
      rec['rv'] = int(remote_version)
    self._writer.write(rec, default=str)
    self.flight.record(rec)

  def span_percentiles(self) -> Dict[str, float]:
    """The live policy-lag / end-to-end percentiles (the summary
    export's supported surface — keeps the driver off the tracer's
    internal histogram objects). NaN until traffic flows."""
    lag_p50, lag_p99 = self._h_lag.percentiles(0.5, 0.99)
    e2e_p50, e2e_p99 = self._h_e2e.percentiles(0.5, 0.99)
    return {'policy_lag_p50': lag_p50, 'policy_lag_p99': lag_p99,
            'unroll_e2e_p50_ms': e2e_p50, 'unroll_e2e_p99_ms': e2e_p99}

  def stats(self) -> Dict:
    return {'batches': self._m_batches.value,
            'unrolls': self._m_unrolls.value,
            'untagged_unrolls': self._m_untagged.value,
            'param_installs': self._m_installs.value,
            'dropped_records': self._m_dropped.value,
            'tag_store_size': len(_UNROLL_TAGS),
            'dropped_writes': self._writer.dropped_writes}

  def close(self):
    self._writer.close()
    for g in self._flight_gauges:
      _REGISTRY.unregister(g.name, g)


_tracer_lock = threading.Lock()
_tracer: Optional[PipelineTracer] = None


def set_tracer(tracer: Optional[PipelineTracer]):
  """Install (or clear, with None) the process-global tracer. The
  driver owns the lifecycle: set before the fleet starts, cleared —
  and closed — in its teardown finally."""
  global _tracer
  with _tracer_lock:
    _tracer = tracer


def get_tracer() -> Optional[PipelineTracer]:
  return _tracer

"""The program's own spans, on a `Trace`.

The program times its layer boundaries itself (the span recorder of
`scalable_agent_tpu/telemetry.py`: rows `(name, t0_ns, t1_ns,
thread_ident, id)` on `perf_counter_ns`, kept only while armed), and
`observability.ProfilerCapture` arms it for a device-only profiler
capture and writes what it took to `<dir>/spans.json` with the
capture's landmark (`benchmark/capture_report.py` reads both). `join`
puts the rows onto the `Trace` read from that capture's profile:

- ALL of them, under the names the program gave them, as lines of the
  host plane: one line `program:<thread_ident>` per thread (so that
  which span encloses which can be told from containment, thread by
  thread) and one line `program` holding a single event
  `program:armed`, the armed interval. They sit on the trace's clock
  where the landmark is on the trace, and on the host's clock where it
  is not (a CPU has no device plane): the span readers
  (`readers/span_stat.py`) work on either,
  `readers/span_idle_overlap.py` needs the first.
- the spans of the threads that FEED the device (`inference/*`,
  `staging/*`, `learner/*`) also go through
  `trace_reduce.add_host_spans`, so `trace_reduce.idle_gaps` names
  them. The actor side (`actor/*`, `batcher/compute`, `env/pipe`) does
  not: `idle_gaps` gives a gap to the SHORTEST span over its middle,
  and of 32 actor threads one is always parked in something short,
  which would name a bystander for every gap.

The benchmark's own traced slice (`context.py :: trace_start /
trace_stop`) does not arm the recorder: that file was not the adding
PR's to edit (PERF.md, section 7, has the lines it would take).
"""

import collections

import numpy as np

from benchmark.harness import trace_reduce

LINE = 'program'
ARMED = 'program:armed'
FEEDING = ('inference/', 'staging/', 'learner/')


def feeding(taken):
  """[(name, t0_ns, t1_ns)] of the device-feeding threads' spans, in
  the form `trace_reduce.add_host_spans` takes."""
  return [(name, t0, t1) for name, t0, t1, _, _ in taken['spans']
          if name.startswith(FEEDING)]


def join(trace, taken, landmark_host_ns, landmark_regex):
  """Puts what `telemetry.take_spans()` handed over onto `trace` (see
  the module's docstring). The clocks meet at the landmark: a tiny
  program whose end the host saw at `landmark_host_ns` and the trace
  recorded as the end of the first `XLA Modules` event matching
  `landmark_regex`. False where the trace holds no such event: the
  lines are then on the host's clock and `idle_gaps` gets nothing."""
  if not taken or not taken.get('spans'):
    return False
  spans = feeding(taken)
  spans.append((trace_reduce.TRACE_SPAN[len(trace_reduce.SPAN_PREFIX):],
                landmark_host_ns, taken['taken_ns']))
  on_trace_clock = trace_reduce.add_host_spans(
      trace, spans, landmark_host_ns, landmark_regex)
  offset = 0.0
  if on_trace_clock:
    # `bench:trace` starts at `landmark_host_ns` on the host's clock.
    offset = float(trace.host_spans()[trace_reduce.TRACE_SPAN][0][0]
                   ) - landmark_host_ns
  armed = taken['clock']['perf_ns']
  rows = [(trace_reduce.HOST_PLANE, LINE, ARMED, armed + offset,
           taken['taken_ns'] - armed)]
  rows.extend(
      (trace_reduce.HOST_PLANE, f'{LINE}:{thread}', name, t0 + offset,
       t1 - t0) for name, t0, t1, thread, _ in taken['spans'])
  lines = trace_reduce.Trace.from_rows(rows).planes[
      trace_reduce.HOST_PLANE]
  trace.planes.setdefault(trace_reduce.HOST_PLANE, {}).update(lines)
  return on_trace_clock


# --- What the span readers share. ---


def armed_interval(trace):
  """(start, end) of the armed interval on the trace, or None."""
  ev = trace.planes.get(trace_reduce.HOST_PLANE, {}).get(LINE)
  if ev is None or len(ev.start) == 0:
    return None
  return float(ev.start[0]), float(ev.start[0] + ev.dur[0])


def threads(trace):
  """The per-thread lines: [Events]."""
  return [ev for line, ev in
          trace.planes.get(trace_reduce.HOST_PLANE, {}).items()
          if line.startswith(LINE + ':')]


def intervals(ev, names):
  """(starts, ends) of the events of one line named in `names`."""
  mask = np.asarray([n in names for n in ev.names], bool)
  return ev.start[mask], ev.start[mask] + ev.dur[mask]


def covered(starts, ends, by_starts, by_ends):
  """For each interval, the length of it that the union of the `by`
  intervals covers."""
  u_starts, u_ends = trace_reduce._merge(by_starts, by_ends)
  if len(u_starts) == 0:
    return np.zeros(len(starts))
  # The length of the union to the left of x: piecewise linear, rising
  # inside each of its (disjoint, sorted) intervals and flat between.
  cum = np.concatenate([[0.0], np.cumsum(u_ends - u_starts)])
  knots = np.column_stack([u_starts, u_ends]).ravel()
  lengths = np.column_stack([cum[:-1], cum[1:]]).ravel()
  return (np.interp(np.asarray(ends, float), knots, lengths) -
          np.interp(np.asarray(starts, float), knots, lengths))


def self_times(trace, span, minus=()):
  """Nanoseconds of every `span` event, less what events named in
  `minus` cover of it on its own thread (children by containment)."""
  out = []
  for ev in threads(trace):
    starts, ends = intervals(ev, {span})
    if len(starts) == 0:
      continue
    time = ends - starts
    if minus:
      time = time - covered(starts, ends, *intervals(ev, set(minus)))
    out.append(time)
  return np.concatenate(out) if out else np.zeros(0)


def count(trace, span):
  return sum(collections.Counter(ev.names)[span]
             for ev in threads(trace))

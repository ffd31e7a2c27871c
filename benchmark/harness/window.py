"""Window arithmetic: from what was counted and clocked to a rate.

Copied in substance from bench.py (`_time_step`: steps chained on the
donated state, one barrier per window; `_read_window_summaries`: frames
between two summary events over the wall time between them) and kept
here so that a PR which changes the program cannot change the
yardstick. The originals are listed for deletion in PERF.md.
"""

import json


class WindowError(Exception):
  """The window holds too little to stand a rate on. An error, never a
  zero: a zero would read as a measurement."""


def chained_rate(steps, seconds, frames_per_step):
  """Frames per second of `steps` steps chained on the donated state,
  clocked from the first dispatch to the barrier after the last."""
  if steps < 1 or seconds <= 0:
    raise WindowError(
        f'{steps} step(s) in {seconds} s: nothing to take a rate from')
  return steps * frames_per_step / seconds


def read_step_events(path):
  """[(wall_time, step)] from a summaries.jsonl, the FIRST event of
  each step count: a summary block is stamped line by line, and its
  first line is the moment the driver turned to that step."""
  events, seen = [], set()
  try:
    f = open(path)
  except OSError:
    return events  # the run has not written a summary yet
  with f:
    for line in f:
      try:
        event = json.loads(line)
        step, wall = int(event['step']), float(event['wall_time'])
      except (ValueError, KeyError, TypeError):
        continue  # a line still being written, or not an event
      if step not in seen:
        seen.add(step)
        events.append((wall, step))
  return events


def events_in(events, t_open, t_close):
  return [(t, s) for t, s in events if t_open <= t <= t_close]


def event_rate(events, t_open, t_close, frames_per_step):
  """(frames per second, steps, seconds) between the first and the
  last step event inside [t_open, t_close]: a whole number of steps
  over the time they took, never the wall time of the call."""
  inside = events_in(events, t_open, t_close)
  if len(inside) < 2:
    raise WindowError(
        f'{len(inside)} step event(s) inside the window of '
        f'{t_close - t_open:.1f} s: a rate needs two')
  (t0, s0), (t1, s1) = inside[0], inside[-1]
  if t1 <= t0 or s1 <= s0:
    raise WindowError(f'step events do not advance: {inside[0]} .. '
                      f'{inside[-1]}')
  return (s1 - s0) * frames_per_step / (t1 - t0), s1 - s0, t1 - t0


def read_scalars(path, tags):
  """{tag: [(wall_time, step, value)]} for the scalar `tags` of a
  summaries.jsonl."""
  rows = {tag: [] for tag in tags}
  with open(path) as f:
    for line in f:
      try:
        event = json.loads(line)
      except ValueError:
        continue
      if event.get('tag') in rows and 'value' in event:
        rows[event['tag']].append(
            (event['wall_time'], event['step'], event['value']))
  return rows

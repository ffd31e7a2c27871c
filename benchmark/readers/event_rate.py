"""Frames per second between the first and the last learner-step event
of the run's own summaries that lie inside the window."""

from benchmark.harness import window


def read(obs):
  if 'step_events' not in obs:
    return None
  rate, steps, seconds = window.event_rate(
      obs['step_events'], *obs['window_wall'], obs['frames_per_step'])
  inside = window.events_in(obs['step_events'], *obs['window_wall'])
  gaps = [round(b[0] - a[0], 2) for a, b in zip(inside, inside[1:])]
  print(f'event_rate: {steps} steps in {seconds:.3f} s between the '
        'first and last step event inside the window; seconds from '
        f'event to event: {gaps[:40]}', flush=True)
  return rate

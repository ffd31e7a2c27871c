"""Frames per second of the steps a driver chained on the donated
state, from the first dispatch to the barrier after the last."""

from benchmark.harness import window


def read(obs):
  steps = obs.get('steps')
  if steps is None:
    return None
  return window.chained_rate(steps['count'], steps['seconds'],
                             obs['frames_per_step'])

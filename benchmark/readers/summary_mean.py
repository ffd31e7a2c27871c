"""The mean over the window of a scalar the program writes to its own
summaries (one value per summary interval); `one_minus` turns a
utilization into the share that waited, `scale` turns it into a
percentage."""

import os

import numpy as np

from benchmark.harness import window


def read(obs, tag, one_minus=False, scale=1.0):
  path = obs.get('summaries')
  if not path or not os.path.exists(path) or 'window_wall' not in obs:
    return None
  t_open, t_close = obs['window_wall']
  values = [v for t, _, v in window.read_scalars(path, [tag])[tag]
            if t_open <= t <= t_close]
  if not values:
    return None
  mean = float(np.mean(values))
  return (1.0 - mean if one_minus else mean) * scale

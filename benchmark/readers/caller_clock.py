"""A percentile, in milliseconds, of what callers waited for one
policy call inside the window (harness/caller_clock.py). Prints the
sample count: a percentile wants at least ten samples beyond it."""

import numpy as np


def read(obs, percentile):
  waits = obs.get('caller_waits')
  if waits is None or len(waits) == 0:
    return None
  beyond = int(len(waits) * (100 - percentile) / 100)
  print(f'caller_clock: p{percentile} over {len(waits)} calls '
        f'({beyond} beyond it)', flush=True)
  return float(np.percentile(waits, percentile)) * 1e3

"""What one caller waits for one policy call, clocked on the caller's
side: a wrapper around the `policy` callable an actor is given. The
core state passes through untouched, so it keeps working when the
state cache hands out slot handles instead of carries.

Each calling thread writes into arrays of its own, allocated before it
is needed and doubled when full; nothing is shared while the calls run.
"""

import threading
import time

import numpy as np


class _ThreadLog:

  def __init__(self, capacity):
    self.end = np.empty(capacity, np.float64)
    self.wait = np.empty(capacity, np.float64)
    self.n = 0

  def add(self, end, wait):
    if self.n == len(self.end):
      self.end = np.concatenate([self.end, np.empty_like(self.end)])
      self.wait = np.concatenate([self.wait, np.empty_like(self.wait)])
    self.end[self.n] = end
    self.wait[self.n] = wait
    self.n += 1


class CallerClock:

  def __init__(self, capacity=1 << 15):
    self._capacity = capacity
    self._local = threading.local()
    self._logs = []
    self._lock = threading.Lock()

  def wrap(self, policy):
    def timed_policy(prev_action, env_output, core_state):
      log = getattr(self._local, 'log', None)
      if log is None:
        log = self._local.log = _ThreadLog(self._capacity)
        with self._lock:
          self._logs.append(log)
      t0 = time.perf_counter()
      result = policy(prev_action, env_output, core_state)
      t1 = time.perf_counter()
      log.add(t1, t1 - t0)
      return result
    return timed_policy

  def waits(self, t_open, t_close):
    """Seconds waited by every call that RETURNED inside
    [t_open, t_close] on time.perf_counter()'s clock."""
    with self._lock:
      logs = list(self._logs)
    parts = []
    for log in logs:
      n = log.n  # the writer may be a call ahead; it never rewrites
      end, wait = log.end[:n], log.wait[:n]
      parts.append(wait[(end >= t_open) & (end <= t_close)])
    return np.concatenate(parts) if parts else np.zeros(0)

"""One owner of the chip, nothing left behind.

The way chip_smoke.py does it (PR 21's first pass was refused for a
process left running): env processes are watched while the run lasts,
and on every way out whatever this process started is stopped and
waited for before the result line is printed.
"""

import os
import signal
import sys
import time


def descendants(root_pid):
  """{pid: parent pid} of every live process below root_pid."""
  parent = {}
  for name in os.listdir('/proc'):
    if not name.isdigit():
      continue
    try:
      with open(f'/proc/{name}/stat') as f:
        parent[int(name)] = int(f.read().rsplit(')', 1)[1].split()[1])
    except (OSError, IndexError, ValueError):
      continue  # the process ended while we were reading it
  found = {}
  for pid in parent:
    p = pid
    while p in parent and p != root_pid:
      p = parent[p]
    if p == root_pid and pid != root_pid:
      found[pid] = parent[pid]
  return found


def kill_and_wait(pids, timeout=10.0):
  """SIGKILL `pids` and wait until each is gone, reaping our own."""
  for pid in pids:
    try:
      os.kill(pid, signal.SIGKILL)
    except OSError:
      pass
  deadline = time.monotonic() + timeout
  left = set(pids)
  while left and time.monotonic() < deadline:
    for pid in list(left):
      try:
        os.waitpid(pid, os.WNOHANG)  # a zombie child of ours
      except OSError:
        pass  # not our child: its own parent reaps it
      if not os.path.exists(f'/proc/{pid}'):
        left.discard(pid)
    if left:
      time.sleep(0.05)
  return left


def env_processes_left(wait_secs=30.0):
  """Pids forked FROM the forkserver (env processes) that are still
  alive: the run that started them must have stopped them by now."""
  me = os.getpid()
  deadline = time.monotonic() + wait_secs
  while True:
    left = [pid for pid, ppid in descendants(me).items() if ppid != me]
    if not left or time.monotonic() > deadline:
      return left
    time.sleep(0.2)


def stop_children():
  """Stops every process this one started and waits until each is
  gone. Left to themselves the forkserver and the resource tracker end
  only AFTER this process has, and whoever looks right then finds them
  running. Returns the pids still there afterwards: none, or the
  result is withheld."""
  me = os.getpid()
  # Env processes first, while the forkserver is there to reap them;
  # it does not take its children with it.
  kill_and_wait(
      [pid for pid, ppid in descendants(me).items() if ppid != me])
  py_process = sys.modules.get('scalable_agent_tpu.runtime.py_process')
  if py_process is not None:
    py_process.stop_forkserver()
  kill_and_wait(list(descendants(me)))
  return sorted(descendants(me))


def _accelerator_fds(pid):
  """Device files of an accelerator that `pid` holds open."""
  held = []
  try:
    names = os.listdir(f'/proc/{pid}/fd')
  except OSError:
    return held
  for name in names:
    try:
      target = os.readlink(f'/proc/{pid}/fd/{name}')
    except OSError:
      continue
    if target.startswith(('/dev/accel', '/dev/vfio')):
      held.append(target)
  return held


class ChildWatch:
  """Records any descendant of this process that holds an accelerator
  device open: the chip belongs to this process alone, and every child
  must stay on the CPU. `sample()` looks once; the drivers call it
  while the fleet starts and when the window closes, never on a timer
  inside the window."""

  def __init__(self):
    self.seen = set()
    self.offenders = {}

  def sample(self):
    for pid in descendants(os.getpid()):
      self.seen.add(pid)
      held = _accelerator_fds(pid)
      if held:
        self.offenders[pid] = held

"""Where a group's env step goes on this host: the step alone, no chip.

32 process-hosted `FakeEnv` children at 72x96 stepped through a shared
block the way `ActorGroup._env_step` steps them: one `StepPass` a group
step, or, in a checkout from before `StepPass`, a send to each member
then a receive from each. Per group step: wall p50 / mean / p95, the
stepping thread's own CPU, the children's CPU, the slowest child's own
`env.step`. Beside it, a bare loop of 32 one-byte round trips to echo
children over socket pairs and over two one-way pipes: the kernel's
part alone. Prints one JSON line, prefixed `STEP0`.

    python scripts/env_step_probe.py [label]            # this checkout
    PYTHONPATH=<other checkout> python <this file> [label]

`K` and `N` in the environment set the children and the group steps
(32 and 4,000).
"""

import json
import multiprocessing
import os
import socket
import sys
import time

os.environ['JAX_PLATFORMS'] = 'cpu'
# Last: a checkout on PYTHONPATH comes first.
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from scalable_agent_tpu.envs.fake import FakeEnv  # noqa: E402
from scalable_agent_tpu.runtime import py_process  # noqa: E402

K = int(os.environ.get('K', 32))
N = int(os.environ.get('N', 4000))
WARM = 300
TICK = os.sysconf('SC_CLK_TCK')


def _cpu_ticks(pid):
  with open(f'/proc/{pid}/stat') as f:
    fields = f.read().rsplit(')', 1)[1].split()
  return int(fields[11]) + int(fields[12])


def _group_step(envs, block):
  """(form, step(row, actions)) as this checkout steps a block."""
  one_pass = getattr(py_process, 'StepPass', None)
  if one_pass is not None:
    return 'pass', one_pass(block, envs).step

  def halves(row, actions):
    block.begin_step(row)
    for env, action in zip(envs, actions.tolist()):
      env.step_send(action)
    for env in envs:
      env.step_receive()
  return 'halves', halves


def measure_group():
  kw = dict(height=72, width=96, num_actions=9, episode_length=50)
  envs = [py_process.ProxyEnv(py_process.PyProcess(
      FakeEnv, dict(kw, seed=j)).start()) for j in range(K)]
  block = py_process.StepBlock.create(envs[0].step_block_specs(), 101, K)
  for j, env in enumerate(envs):
    env.attach_block(block, j)
  block.unlink()
  for env in envs:
    env.initial()
  form, step = _group_step(envs, block)
  pids = [env._process._process.pid for env in envs]
  actions = np.zeros(K, np.int32)
  walls, slowest = [], []
  for n in range(WARM + N):
    if n == WARM:
      ticks0 = sum(_cpu_ticks(p) for p in pids)
      thread0 = time.thread_time_ns()
    actions[:] = n % 9
    t0 = time.perf_counter_ns()
    step(1 + n % 100, actions)
    walls.append(time.perf_counter_ns() - t0)
    slowest.append(int(block.busy_ns.max()))
  thread_ms = (time.thread_time_ns() - thread0) / N / 1e6
  children_ms = ((sum(_cpu_ticks(p) for p in pids) - ticks0) / TICK
                 * 1e3 / N)
  py_process.close_all([e._process for e in envs])
  w = np.asarray(walls[WARM:]) / 1e6
  return {'form': form, 'step_ms_p50': float(np.median(w)),
          'step_ms_mean': float(w.mean()),
          'step_ms_p95': float(np.percentile(w, 95)),
          'actor_thread_cpu_ms': thread_ms, 'children_cpu_ms': children_ms,
          'slowest_child_env_step_ms': float(np.mean(slowest[WARM:])) / 1e6}


def _echo(rfd, wfd):
  while os.read(rfd, 1):
    os.write(wfd, b'K')


def measure_bare(kind):
  """32 round trips of one byte, in two loops, to echo children over
  socket pairs ('sock') or two one-way pipes ('pipe')."""
  ctx = multiprocessing.get_context('fork')
  ends, procs, sockets = [], [], []
  for _ in range(K):
    if kind == 'sock':
      a, b = socket.socketpair()
      proc = ctx.Process(target=_echo, args=(b.fileno(), b.fileno()),
                         daemon=True)
      proc.start()
      b.close()
      ends.append((a.fileno(), a.fileno()))
      sockets.append(a)
    else:
      down_r, down_w = os.pipe()
      up_r, up_w = os.pipe()
      proc = ctx.Process(target=_echo, args=(down_r, up_w), daemon=True)
      proc.start()
      os.close(down_r)
      os.close(up_w)
      ends.append((down_w, up_r))
    procs.append(proc)
  walls = []
  for n in range(WARM + N):
    if n == WARM:
      thread0 = time.thread_time_ns()
    t0 = time.perf_counter_ns()
    for w, _ in ends:
      os.write(w, b'S')
    for _, r in ends:
      os.read(r, 1)
    walls.append(time.perf_counter_ns() - t0)
  thread_ms = (time.thread_time_ns() - thread0) / N / 1e6
  for proc in procs:  # each holds its elders' ends: no EOF reaches them
    proc.terminate()
    proc.join(5)
  for s in sockets:
    s.close()
  if kind == 'pipe':
    for w, r in ends:
      os.close(w)
      os.close(r)
  w = np.asarray(walls[WARM:]) / 1e6
  return {f'bare_{kind}_ms_p50': float(np.median(w)),
          f'bare_{kind}_ms_mean': float(w.mean()),
          f'bare_{kind}_thread_cpu_ms': thread_ms}


def main():
  out = {'label': sys.argv[1] if len(sys.argv) > 1 else '',
         'cores': os.cpu_count()}
  # Before the forkserver starts, so that the echo children, forked
  # from here, hold none of its descriptors.
  out.update(measure_bare('sock'))
  out.update(measure_bare('pipe'))
  py_process.warm_forkserver()
  try:
    out.update(measure_group())
  finally:
    py_process.stop_forkserver()
  print('STEP0', json.dumps(out), flush=True)


if __name__ == '__main__':
  main()

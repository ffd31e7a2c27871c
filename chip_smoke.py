"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the main path once through the entry points a user calls —
experiment.py's own flag parsing and `main` -> `driver.train` — at the
full width of the paper's agent (deep ResNet torso, bf16, 72x96 frames,
instruction encoder, 9 actions: 1,628,030 parameters) with random
weights from a seed, and checks every phase by the repo's own means:

1. kernel  the Pallas V-trace kernel COMPILED (interpret=False stated)
           at [T=100, B=32], within 1e-4 of the scan form;
2. fleet   process-hosted fake envs -> C++ batcher -> InferenceServer
           -> TrajectoryBuffer/prefetcher -> a few learner steps of
           12,800 frames each -> param publish;
3. anakin  `--runtime=anakin`: a few fused act+learn steps of the same
           agent on the jittable `procgen` core;
4. procgen `--runtime=fleet --env_backend=procgen`, process-hosted: the
           env children step their cores through JAX and must do it on
           the CPU while this process owns the chip; the one learner
           step runs with `--use_pallas_vtrace`;
5. parity  (more than one chip only) the seeded sharded-vs-single-
           device loss parity body on {data: n} and {data: n/2,
           model: 2}, with true sharded TP compute and donation on.

On a host with several chips the same fleet phase must come up on a
{data: n} mesh with the batch split n ways and state on every chip.

One process drives the chip. The first failed check raises, so the
exit code is non-zero and no later phase runs. On every way out the
script stops each process it started (env processes, the forkserver,
the resource tracker) and waits until it is gone. The last line of
standard output is the result: {"ok": true, "device": {...}}.

Without an accelerator the script exits 2 at the device check, before
any compile. `--cpu-rehearsal` runs the same phases at toy sizes on the
CPU (Pallas in interpret mode) to debug the script itself; every line
it prints says so, it proves nothing about the chip, and it prints no
result line.
"""

import argparse
import contextlib
import functools
import io
import json
import logging
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_SECS = 1150  # hard stop inside the 1200 s the contract allows
_PREFIX = ''


def say(text):
  print(_PREFIX + text, flush=True)


def check(condition, what):
  """A hard check: the first failure ends the smoke non-zero."""
  if not condition:
    raise SystemExit(_PREFIX + 'CHECK FAILED: ' + what)
  say('  ok: ' + what)


# --- Process hygiene: one owner of the chip, nothing left behind. ---


def _descendants(root_pid):
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


def check_no_child_left():
  """Whatever was forked FROM the forkserver is an env process, and the
  run that started it must have stopped it by now. (This process's own
  children, the forkserver and the resource tracker, are stopped by
  stop_children on the way out.)"""
  me = os.getpid()
  deadline = time.monotonic() + 30
  while True:
    left = [pid for pid, ppid in _descendants(me).items() if ppid != me]
    if not left or time.monotonic() > deadline:
      break
    time.sleep(0.5)
  check(not left, f'no env process outlived its run (left: {left})')


def _kill_and_wait(pids, timeout=10.0):
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


def stop_children():
  """Stops every process this one started and waits until each is gone,
  on every way out (pass, failed check, exception). Left to themselves
  the forkserver and the resource tracker end only AFTER this process
  has (the server not before its preload imports finish, seconds), and
  whoever looks right then finds them running. Returns the pids that
  had to be killed: none when every run stopped what it started."""
  me = os.getpid()
  # Env processes first, while the forkserver is there to reap them; it
  # does not take its children with it.
  killed = [pid for pid, ppid in _descendants(me).items() if ppid != me]
  _kill_and_wait(killed)
  # Present whenever a process exists to stop: run_phases imported it
  # to start the forkserver.
  py_process = sys.modules.get('scalable_agent_tpu.runtime.py_process')
  if py_process is not None:
    py_process.stop_forkserver()
  rest = list(_descendants(me))
  _kill_and_wait(rest)
  return killed + rest


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
  """Samples this process's descendants while a phase runs and records
  any that opens an accelerator device: the chip belongs to this
  process alone, and every child the smoke starts must stay on CPU."""

  def __init__(self):
    self.seen = set()
    self.offenders = {}
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._loop, daemon=True)

  def _loop(self):
    while not self._stop.wait(0.25):
      for pid in _descendants(os.getpid()):
        self.seen.add(pid)
        held = _accelerator_fds(pid)
        if held:
          self.offenders[pid] = held

  def __enter__(self):
    self._thread.start()
    return self

  def __exit__(self, *exc):
    self._stop.set()
    self._thread.join(timeout=10)


class Deadline:
  """Never outlive the time limit: a hung phase (a dead fleet parks
  driver.train forever) must end as a failure, not as a hung chip."""

  def __init__(self):
    self.phase = 'start-up'
    self._timer = threading.Timer(DEADLINE_SECS, self._expire)
    self._timer.daemon = True

  def _expire(self):
    say(f'DEADLINE: {DEADLINE_SECS} s passed in phase {self.phase!r}; '
        'killing children and exiting 3')
    _kill_and_wait(list(_descendants(os.getpid())), timeout=5.0)
    os._exit(3)

  def __enter__(self):
    self._timer.start()
    return self

  def __exit__(self, *exc):
    self._timer.cancel()


# --- The entry points. ---


def run_experiment(flag_args):
  """experiment.py's own flag parsing and `main`, exactly as `python
  experiment.py <flags>` runs them; returns the TrainRun that
  driver.train handed back to main (which drops it)."""
  import experiment
  from scalable_agent_tpu import driver
  runs = []
  real_train = driver.train

  def recording_train(*args, **kwargs):
    runs.append(real_train(*args, **kwargs))
    return runs[-1]

  driver.train = recording_train
  try:
    experiment.FLAGS.unparse_flags()
    experiment.FLAGS(['experiment.py'] + list(flag_args))
    experiment.main([])
  finally:
    driver.train = real_train
  (run,) = runs
  return run


def agent_flags(size):
  return [
      '--mode=train', f'--torso={size["torso"]}',
      '--compute_dtype=bfloat16', f'--height={size["height"]}',
      f'--width={size["width"]}', '--use_instruction=true',
      '--num_actions=9', f'--unroll_length={size["unroll_length"]}',
      f'--batch_size={size["batch_size"]}', '--summary_secs=0',
      '--seed=21']


def read_scalars(logdir, tag):
  """[(step, value)] for `tag` from the run's summaries.jsonl."""
  rows = []
  with open(os.path.join(logdir, 'summaries.jsonl')) as f:
    for line in f:
      event = json.loads(line)
      if event.get('tag') == tag:
        rows.append((event['step'], event['value']))
  return rows


def check_learner(run, logdir, steps, initial_params, platform):
  """What every training phase must show, fleet or fused."""
  import jax
  import numpy as np
  import optax

  state = run.state
  check(int(jax.device_get(state.update_steps)) == steps,
        f'update_steps == {steps}')
  # The in-graph sentinel withholds a non-finite update and the step
  # counter still advances, so a run that skipped every step would
  # exit 0. The optimizer's own count moves only with an APPLIED
  # update: equal counts mean every step's loss and gradient norm were
  # finite, the last one included (its metrics are read one step late
  # and never reach the summaries).
  applied = int(jax.device_get(
      optax.tree_utils.tree_get(state.opt_state, 'count')))
  check(applied == steps, f'optimizer applied {applied} of {steps} updates')
  losses = read_scalars(logdir, 'total_loss')
  check(len(losses) >= steps and
        all(np.isfinite(value) for _, value in losses),
        f'total_loss finite at every summary: '
        f'{[round(value, 3) for _, value in losses]}')
  health = run.health.stats()
  counters = {
      'skipped_steps': health['skipped_steps'],
      'rollbacks': health['rollbacks'],
      'checkpoint_save_errors': run.checkpointer.save_errors,
      'checkpoint_restore_fallbacks': run.checkpointer.restore_fallbacks,
  }
  if run.fleet is not None:
    fleet = run.fleet.stats()
    counters.update(respawns=fleet['respawns'],
                    slots_quarantined=fleet['slots_quarantined'],
                    quarantined=(run.ingest.stats()['quarantined']
                                 if run.ingest is not None else 0))
  check(not any(counters.values()), f'robustness counters zero: {counters}')
  with open(os.path.join(logdir, 'device.json')) as f:
    recorded = json.load(f)
  check(recorded['platform'] == platform and
        recorded['device_count'] == jax.device_count(),
        f'the run named its device beside config.json: {recorded}')

  leaves = jax.tree_util.tree_leaves(state)
  check(all(d.platform == platform for leaf in leaves
            for d in leaf.devices()),
        f'learner state ({len(leaves)} leaves) lives on {platform} devices')
  final = jax.device_get(state.params)
  check(all(np.all(np.isfinite(x))
            for x in jax.tree_util.tree_leaves(final)),
        'final parameters finite')
  paths = [jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_leaves_with_path(final)]
  unchanged = [path for path, a, b in zip(
      paths, jax.tree_util.tree_leaves(final),
      jax.tree_util.tree_leaves(initial_params))
               if not np.any(np.asarray(a) != np.asarray(b))]
  # The smoke's envs give a one-word (fake) or empty (procgen)
  # instruction, so the instruction encoder's recurrent kernels only
  # ever see a zero state: their gradient is exactly zero. Every leaf
  # anywhere else must have moved.
  stuck = [path for path in unchanged if 'InstructionEncoder' not in path]
  check(not stuck and len(unchanged) < len(paths) // 2,
        f'{len(paths) - len(unchanged)} of {len(paths)} parameter leaves '
        f'differ from their initial values; unchanged: {unchanged}')


def check_mesh(run, devices):
  """Several chips: the fleet phase must have come up sharded over all
  of them, with no workaround engaged."""
  import jax
  n = len(devices)
  check(run.mesh is not None and
        dict(run.mesh.shape) == {'data': n, 'model': 1},
        f'choose_mesh returned {{data: {n}}}, not the single-device '
        f'fallback: {run.mesh}')
  step = run.train_step
  check(step.donation_fallback is False and step.tp_gathered is False,
        'sharded step kept donation on, no gathered-TP workaround')
  batch = run.config.batch_size
  frame_sharding = step.batch_shardings.env_outputs.observation[0]
  t1 = run.config.unroll_length + 1
  shard = frame_sharding.shard_shape(
      (t1, batch, run.config.height, run.config.width, 3))
  check(shard[1] * n == batch and len(frame_sharding.device_set) == n,
        f'the batch is split {n} ways ({shard[1]} unrolls per chip)')
  # XLA:CPU reports no memory_stats (the rehearsal); a chip must.
  in_use = [(d.memory_stats() or {}).get('bytes_in_use') for d in devices]
  check(all({s.device for s in leaf.addressable_shards} == set(devices)
            for leaf in jax.tree_util.tree_leaves(run.state.params))
        and all(b > 0 if b is not None else d.platform == 'cpu'
                for b, d in zip(in_use, devices)),
        f'every parameter leaf has a shard on each of the {n} chips; '
        f'bytes_in_use {in_use}')
  spanned = run.server.stats()['devices_last_call']
  serving = {d for leaf in jax.tree_util.tree_leaves(
      run.server.live_params()) for d in leaf.devices()}
  check(spanned == len(serving),
        f'the merged inference call spanned {spanned} device(s), the '
        f'{len(serving)} its params live on')


def fleet_phase(name, size, backend, steps, num_actors, platform,
                extra_flags=()):
  """`--runtime=fleet` through experiment.main with process-hosted
  envs; returns the TrainRun after the per-phase checks."""
  import jax
  from scalable_agent_tpu.envs import factory
  from scalable_agent_tpu.models import init_params

  logdir = tempfile.mkdtemp(prefix=f'chip_smoke_{name}_')
  frames = steps * size['batch_size'] * size['unroll_length'] * 4
  flags = agent_flags(size) + [
      '--runtime=fleet', f'--env_backend={backend}',
      '--use_py_process=true', f'--num_actors={num_actors}',
      f'--total_environment_frames={frames}', f'--logdir={logdir}',
      *extra_flags]
  say(f'[{name}] experiment.py ' + ' '.join(flags))
  t0 = time.monotonic()
  with ChildWatch() as watch:
    run = run_experiment(flags)
  wall = time.monotonic() - t0
  try:
    cfg = run.config
    check(cfg.use_py_process and cfg.env_backend == backend and
          cfg.runtime == 'fleet' and cfg.torso == size['torso'],
          'the flags reached the run')
    level = factory.level_names(cfg)[0]
    obs_spec = factory.make_env_spec(cfg, level, seed=1).obs_spec
    initial = jax.device_get(init_params(
        run.agent, jax.random.PRNGKey(cfg.seed), obs_spec))
    if size['torso'] == 'deep':
      count = sum(x.size for x in jax.tree_util.tree_leaves(initial))
      check(count == 1628030, f'the paper\'s agent: {count:,} parameters')
    check_learner(run, logdir, steps, initial, platform)

    served = run.server.stats()
    calls_needed = steps * size['batch_size'] * size['unroll_length']
    check(served['requests'] >= calls_needed,
          f'inference answered {served["requests"]} policy calls '
          f'(>= {calls_needed}) in {served["calls"]} merged calls')
    check(served['mean_batch'] > 1.0,
          f'the batcher merged: mean batch {served["mean_batch"]:.2f}')
    check(served['chain_recoveries'] == 0 and served['sheds'] == 0,
          'no inference chain recovery, no shed request')
    check(all(d.platform == platform for leaf in
              jax.tree_util.tree_leaves(run.server.live_params())
              for d in leaf.devices()),
          f'inference server params live on {platform} devices')
    check(run.fleet.stats()['unrolls'] >= steps * size['batch_size'],
          f'{num_actors} actors produced '
          f'{run.fleet.stats()["unrolls"]} unrolls')
    # Env children (forked from the forkserver) must never open the
    # chip this process holds.
    check(len(watch.seen) >= num_actors,
          f'watched {len(watch.seen)} child processes')
    check(not watch.offenders,
          f'no child opened an accelerator device: {watch.offenders}')
    if platform == 'tpu':
      mine = sorted(set(_accelerator_fds(os.getpid())))
      check(mine, f'this process holds the accelerator device files {mine}')
    if jax.device_count() > 1:
      check_mesh(run, jax.devices())
    say(f'[{name}] PASS: {steps} learner steps of '
        f'{frames // steps:,} frames, {wall:.1f} s wall (set-up and '
        f'compilation included); inference p50 '
        f'{served["latency_p50_ms"]} ms per merged call')
  finally:
    shutil.rmtree(logdir, ignore_errors=True)


def anakin_phase(size, steps, platform):
  """`--runtime=anakin` behind the same entry: fused act+learn steps of
  the same agent on the jittable procgen core."""
  import jax
  from scalable_agent_tpu.parallel import anakin

  logdir = tempfile.mkdtemp(prefix='chip_smoke_anakin_')
  frames = steps * size['batch_size'] * size['unroll_length'] * 4
  flags = agent_flags(size) + [
      '--runtime=anakin', '--env_backend=procgen',
      f'--total_environment_frames={frames}', f'--logdir={logdir}']
  say('[anakin] experiment.py ' + ' '.join(flags))
  t0 = time.monotonic()
  run = run_experiment(flags)
  wall = time.monotonic() - t0
  try:
    check(run.fleet is None and run.server is None and
          run.config.runtime == 'anakin',
          'the fused runtime ran: no fleet, no inference server')
    # The same seeded construction driver.train_anakin starts from.
    initial = jax.device_get(
        anakin.build_run(run.config, mesh=run.mesh)[3].train_state.params)
    check_learner(run, logdir, steps, initial, platform)
    say(f'[anakin] PASS: {steps} fused steps of {frames // steps:,} '
        f'frames, {wall:.1f} s wall (set-up and compilation included)')
  finally:
    shutil.rmtree(logdir, ignore_errors=True)


def kernel_phase(interpret):
  """The repo's one packaged Pallas kernel, compiled by Mosaic (never
  interpreted on the chip), against the scan form on a seeded batch."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  from scalable_agent_tpu import vtrace
  from scalable_agent_tpu.ops import vtrace_pallas

  t, b = 100, 32
  keys = jax.random.split(jax.random.PRNGKey(21), 6)
  inputs = dict(
      log_rhos=0.5 * jax.random.normal(keys[0], (t, b)),
      discounts=0.99 * (jax.random.uniform(keys[1], (t, b)) > 0.02),
      rewards=jax.random.normal(keys[2], (t, b)),
      values=2.0 * jax.random.normal(keys[3], (t, b)),
      bootstrap_value=2.0 * jax.random.normal(keys[4], (b,)))
  fused = jax.jit(functools.partial(
      vtrace_pallas.from_importance_weights, interpret=interpret))
  vs, pg = jax.block_until_ready(fused(**inputs))
  ref = jax.jit(vtrace.from_importance_weights)(**inputs)
  check(vs.shape == (t, b) and pg.shape == (t, b) and
        bool(jnp.all(jnp.isfinite(vs)) & jnp.all(jnp.isfinite(pg))),
        f'kernel outputs finite, shape [T={t}, B={b}], '
        f'interpret={interpret}')
  err_vs = float(np.max(np.abs(np.asarray(vs) - np.asarray(ref.vs))))
  err_pg = float(np.max(np.abs(
      np.asarray(pg) - np.asarray(ref.pg_advantages))))
  check(err_vs < 1e-4 and err_pg < 1e-4,
        f'within 1e-4 of the scan form: max |dvs| {err_vs:.2e}, '
        f'max |dpg| {err_pg:.2e}')


def parity_phase(devices, with_tp):
  """The deterministic parity body (__graft_entry__._parity_dryrun:
  sharded vs single-device loss on one seeded batch) on the real
  chips, pure DP and DP x TP with tp_compute=auto. (The CPU rehearsal
  skips the TP half: `auto` is the gathered workaround there.)"""
  import __graft_entry__
  n = len(devices)
  meshes = [1] + ([2] if with_tp and n % 2 == 0 else [])
  for model_par in meshes:
    label = f'chip_parity(data={n // model_par}, model={model_par})'
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
      delta, step = __graft_entry__._parity_dryrun(
          devices, model_par, {'tp_compute': 'auto'}, label)
    say(printed.getvalue().strip())
    check(delta < 2e-4 and step.tp_gathered is False and
          step.donation_fallback is False,
          f'{label}: rel. delta {delta:.2e}, true sharded compute, '
          'donation on')


class CompileLedger:
  """Compilation as set-up cost: persistent-cache hits and misses and
  backend compile seconds, from jax.monitoring's own events."""

  def __init__(self):
    self.hits = self.misses = 0
    self.compile_secs = 0.0

  def _event(self, event, **kwargs):
    if event == '/jax/compilation_cache/cache_hits':
      self.hits += 1
    elif event == '/jax/compilation_cache/cache_misses':
      self.misses += 1

  def _duration(self, event, duration, **kwargs):
    if event == '/jax/core/compile/backend_compile_duration':
      self.compile_secs += duration

  def __enter__(self):
    import jax
    jax.monitoring.register_event_listener(self._event)
    jax.monitoring.register_event_duration_secs_listener(self._duration)
    return self

  def __exit__(self, *exc):
    import jax
    jax.monitoring.unregister_event_listener(self._event)
    jax.monitoring.unregister_event_duration_listener(self._duration)


def main(argv):
  global _PREFIX
  parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
  parser.add_argument(
      '--cpu-rehearsal', action='store_true',
      help='toy sizes on the CPU to debug this script; proves nothing '
           'about the chip and prints no result line')
  args = parser.parse_args(argv)
  if args.cpu_rehearsal:
    _PREFIX = '[CPU REHEARSAL, NOT A CHIP RESULT] '
  # experiment.main's own basicConfig is then a no-op: same format,
  # with the rehearsal label on the program's lines too.
  logging.basicConfig(
      level=logging.INFO,
      format=_PREFIX + '%(asctime)s %(name)s %(levelname)s %(message)s')
  # One line per finished episode would bury everything else.
  logging.getLogger('scalable_agent_tpu').addFilter(
      lambda record: not record.getMessage().startswith('episode '))
  t_start = time.monotonic()
  with Deadline() as deadline:
    try:
      code, device = run_phases(args.cpu_rehearsal, deadline, t_start)
    finally:
      deadline.phase = 'stopping children'
      killed = stop_children()
    check(not killed, 'every process the smoke started was stopped by '
          f'the run that started it (had to kill: {killed})')
    check(not _descendants(os.getpid()),
          'no process is left running behind the smoke')
  if code == 0 and not args.cpu_rehearsal:
    # The result, last: nothing is printed after it.
    print(json.dumps({'ok': True, 'device': device}), flush=True)
  return code


def run_phases(rehearsal, deadline, t_start):
  # The forkserver that env processes are forked from starts while
  # this process is still quiet, before JAX spins up (py_process.py).
  from scalable_agent_tpu.runtime.py_process import warm_forkserver
  warm_forkserver()

  # --- The device JAX gives us, with no platform override of ours. ---
  import jax
  import jaxlib
  try:
    from importlib.metadata import version
    libtpu = version('libtpu')
  except Exception:  # noqa: BLE001 — a version string, never a gate
    libtpu = 'not installed'
  devices = jax.devices()
  device = {'platform': devices[0].platform,
            'kind': devices[0].device_kind, 'count': len(devices)}
  say(f'device: platform={device["platform"]} '
      f'device_kind={device["kind"]!r} count={device["count"]} | '
      f'jax {jax.__version__} jaxlib {jaxlib.__version__} '
      f'libtpu {libtpu}')
  if rehearsal:
    if device['platform'] != 'cpu':
      say('--cpu-rehearsal is for a CPU-pinned process '
          '(JAX_PLATFORMS=cpu); on a chip run the smoke itself')
      return 2, device
  elif device['platform'] != 'tpu':
    say(f'no accelerator: JAX reports platform '
        f'{device["platform"]!r}, this smoke needs "tpu"')
    return 2, device

  # --- Built from what git would commit: the native batcher from
  # batcher.cc, never a .so found in the tree. ---
  deadline.phase = 'build'
  batcher_dir = os.path.join(REPO, 'scalable_agent_tpu', 'ops', 'batcher')
  subprocess.run(['make', '-B', '-C', batcher_dir, 'libbatcher.so'],
                 check=True, stdout=subprocess.DEVNULL)
  say('native batcher rebuilt from batcher.cc (make -B)')

  # --- The compile cache, placed by the program's one rule. ---
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.parallel import distributed
  distributed.arm_compile_cache(Config())
  say('compile cache: JAX_COMPILATION_CACHE_DIR='
      f'{os.environ.get("JAX_COMPILATION_CACHE_DIR")!r}, '
      f'jax_compilation_cache_dir={jax.config.jax_compilation_cache_dir!r}')

  platform = device['platform']
  if rehearsal:
    size = dict(torso='shallow', height=24, width=32, unroll_length=5,
                batch_size=4)
    fleet_actors, procgen_actors, steps = 4, 2, 3
  else:
    size = dict(torso='deep', height=72, width=96, unroll_length=100,
                batch_size=32)
    fleet_actors, procgen_actors, steps = 32, 8, 4

  with CompileLedger() as ledger:
    deadline.phase = 'kernel'
    say('[kernel] Pallas V-trace vs the scan form')
    kernel_phase(interpret=rehearsal)
    deadline.phase = 'fleet'
    fleet_phase('fleet', size, 'fake', steps, fleet_actors, platform)
    deadline.phase = 'anakin'
    anakin_phase(size, steps, platform)
    deadline.phase = 'procgen'
    fleet_phase('procgen', size, 'procgen', 1, procgen_actors, platform,
                extra_flags=['--use_pallas_vtrace=true'])
    if len(devices) > 1:
      deadline.phase = 'parity'
      parity_phase(devices, with_tp=not rehearsal)

  check_no_child_left()
  say(f'set-up: {ledger.misses} compile-cache misses, {ledger.hits} '
      f'hits, {ledger.compile_secs:.1f} s in backend compiles; total '
      f'wall {time.monotonic() - t_start:.1f} s')
  if rehearsal:
    say('rehearsal finished; run `python chip_smoke.py` on the chip '
        'for a result')
  return 0, device


if __name__ == '__main__':
  sys.exit(main(sys.argv[1:]))

"""The serving path with no learner behind it: `driver.play`, the play
phase `driver.evaluate` shares, given parameters made on the device
from `--seed` in the configuration's dtype (no checkpoint, no float32
master copy). Process-hosted envs -> actor group -> C++ batcher ->
inference server with the state cache -> one merged call a step.
Nothing here names a cell: the model and the traffic are the files'.

`driver.play` blocks, so a watcher thread of the benchmark opens the
window once the server has answered `warm_calls` merged calls (all
before is set-up), closes it `--seconds` later, takes the traced slice
after it and ends the run through `stop_event`, the seam `play` takes.
The fleet is built through `play`'s `fleet_factory` seam, which calls
`driver.make_fleet` as `play` does, except that the `policy` the actors
get is timed on the caller's side (harness/caller_clock.py), its first
`check_sessions` rows are recorded, and the env seeds follow `--seed`.

`correct`: for `check_sessions` sessions the recorder keeps, call by
call, the token fed, the `done` flag and what the timed path returned
(action, log mu(action), baseline). After the window the plain
reference (harness/brumby_ref.py: attention form, float32, highest
matmul precision, the served parameters widened a layer at a time, the
head in vocabulary blocks) recomputes, with the recorded tokens and
actions forced, each session's last whole episode before an episode
boundary and `check_steps / 2` steps beyond it; the `check_steps` steps
that span the boundary are compared. Tolerances: LOG_MU_TOLERANCE and
BASELINE_TOLERANCE below, with their reasons. Further: every merged
call of the window carried the whole fleet's rows; nothing compiled in
the window (run.py); no child opened the chip, none was left.
`failed` = sheds, respawns, quarantines, chain recoveries.

Traffic parameters: `warm_calls`, `trace_seconds`, `check_sessions`,
`check_steps`. This file does not set the traffic file's
`env_processes`: tests/benchmark/test_benchmark_cells.py holds every
traffic mix with that key to the training fleet's layer metrics. The
forkserver is started here instead, before the first env process; it is
a fresh interpreter either way (`spawnv_passfds`), and every child pins
itself to the CPU.
"""

import glob
import os
import shutil
import tempfile
import threading
import time

import jax
import numpy as np

from benchmark.harness import (brumby_ref, caller_clock, correct,
                               processes, trace_scopes)
from scalable_agent_tpu import driver
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.models import init_params
from scalable_agent_tpu.runtime import py_process

POLL_SECS = 0.02           # how often the watcher reads the call count
STALL_TIMEOUT_SECS = 120
VOCAB_BLOCK = 16384        # columns of the head the reference takes at once

# The two limits of `correct`, each between two readings on the chip
# at the published widths (PERF.md section 6, PR 27, has the runs).
# Largest difference from the reference this program gave, the worst
# of 192 compared steps a run: log mu 0.017-0.024, baseline 0.016-
# 0.021. That is not float32 reassociation (a layer alone agrees with
# a float64 truth to 2e-4 at worst, 1e-6 in the mean): the operands of
# every projection are rounded to bfloat16 as the configuration states,
# here and in the reference alike, and a rounding that flips on a
# difference in the last float32 bit is worth 4e-3 of its element, so
# two float32 computations of this model drift apart to the size of
# bfloat16's step within a layer or two (the reference's own recurrent
# form in float32 differs from its attention form by 0.016-0.028).
# The reference with its state held in bfloat16, the nearest precision
# below the configuration's float32 state: log mu 0.133 and 0.147,
# baseline 0.076 and 0.172. The limits lie between, more than twice
# the first reading and under the second: a bfloat16 state fails both.
LOG_MU_TOLERANCE = 0.06
BASELINE_TOLERANCE = 0.05


class _Recorder:
  """What the first `sessions` rows of every policy call were fed and
  answered, in call order; the caller's thread writes, nobody reads
  until the run is over."""

  def __init__(self, sessions):
    self.sessions = sessions
    self.rows = []  # (token, done, action, log_mu, baseline), each [sessions]

  def wrap(self, policy):
    def recorded_policy(prev_action, env_output, core_state):
      out, new_state = policy(prev_action, env_output, core_state)
      n = self.sessions
      self.rows.append(tuple(
          np.array(np.asarray(x)[:n]) for x in (
              env_output.observation[0], env_output.done, out.action,
              out.policy_logits, out.baseline)))
      return out, new_state
    return recorded_policy

  def session(self, j):
    """(tokens, dones, actions, log_mu, baselines) of session j, [T]."""
    return tuple(np.stack([row[i][j] for row in self.rows])
                 for i in range(5))


class _Seams:

  def __init__(self, sessions):
    self.server = None
    self.fleet = None
    self.clock = caller_clock.CallerClock()
    self.recorder = _Recorder(sessions)

  def counters(self):
    return {'server': self.server.stats(), 'fleet': self.fleet.stats()}


def _fleet_factory(seams, seed):
  def build(config, agent, policy, buffer, levels):
    seams.server = policy.__self__
    seams.fleet = driver.make_fleet(
        config, agent, seams.recorder.wrap(seams.clock.wrap(policy)),
        buffer, levels, seed_base=seed * 1009, is_test=True,
        initial_state_fn=seams.server.initial_core_state)
    return seams.fleet
  return build


def _stop_trace_with_scopes(ctx):
  """`ctx.trace_stop()` reads the profile and deletes it; the scope
  line (`trace_scopes.add_scope_line`) needs the file too. The context
  keeps a copy where its `keep_trace` option says, so the slice is
  stopped with that option pointing at a directory of this driver's,
  unless the caller asked for one."""
  kept = ctx._keep_trace
  folder = kept or tempfile.mkdtemp(prefix='bench_scopes_')
  ctx._keep_trace = folder
  try:
    ctx.trace_stop()
    (path,) = glob.glob(os.path.join(folder, '*.xplane.pb'))
    named = trace_scopes.add_scope_line(ctx.trace_result, path)
    ctx.mark(f'traced slice read; {named} device operations with a '
             'scope path')
  finally:
    ctx._keep_trace = kept
    if not kept:
      shutil.rmtree(folder, ignore_errors=True)


class _Watcher(threading.Thread):

  def __init__(self, ctx, seams, stop_event, watch):
    super().__init__(name='bench-watcher', daemon=True)
    self.ctx, self.seams, self.watch = ctx, seams, watch
    self.stop_event = stop_event
    self.gave_up = threading.Event()
    self.error = None
    self.obs = {}

  def run(self):
    try:
      self._run()
    except BaseException as e:  # noqa: BLE001 — re-raised by the driver
      self.error = e
    finally:
      self.stop_event.set()

  def _wait_for_calls(self, calls):
    last_sample = 0.0
    while not self.gave_up.is_set():
      server = self.seams.server
      if (server is not None and self.seams.fleet is not None
          and server.stats()['calls'] >= calls):
        return True
      if time.monotonic() - last_sample > 2.0:
        self.watch.sample()  # children open a chip, if ever, at start
        last_sample = time.monotonic()
      time.sleep(POLL_SECS)
    return False

  def _run(self):
    ctx = self.ctx
    if not self._wait_for_calls(ctx.param('warm_calls')):
      return
    ctx.open_window()
    opened = {'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    self.gave_up.wait(
        max(0.0, opened['perf'] + ctx.seconds - time.perf_counter()))
    closed = {'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    ctx.close_window()
    if ctx.trace and not self.gave_up.is_set():
      # The traced slice FOLLOWS the window, as in train_loop.py.
      ctx.trace_start()
      self.gave_up.wait(ctx.param('trace_seconds'))
      _stop_trace_with_scopes(ctx)
    self.watch.sample()
    self.obs = {'opened': opened, 'closed': closed}


def _check_against_reference(checks, ctx, cfg, params, recorder):
  """The recorded sessions against the reference, on the stretch that
  spans each session's last episode boundary."""
  half = ctx.param('check_steps') // 2
  dims = dict(num_heads=cfg.seq_num_heads,
              num_kv_heads=cfg.seq_num_kv_heads,
              head_dim=cfg.seq_head_dim, rope_theta=cfg.seq_rope_theta,
              norm_eps=cfg.seq_norm_eps,
              operand_dtype=(jax.numpy.bfloat16
                             if cfg.compute_dtype == 'bfloat16' else None),
              vocab_block=VOCAB_BLOCK)
  reference = lambda tokens, dones, actions: brumby_ref.forward(  # noqa: E731
      params, tokens, dones, actions, **dims)
  worst_mu = worst_base = 0.0
  compared = 0
  for j in range(recorder.sessions):
    tokens, dones, actions, log_mu, baseline = recorder.session(j)
    starts = np.flatnonzero(dones)
    # The last boundary with `half` steps after it and a whole episode
    # before it (the step that primes an actor repeats its first
    # observation: both carry `done`, and a stretch never starts there).
    ends = [e for e in starts if e + half <= len(tokens) and
            e - cfg.episode_length in starts and e - cfg.episode_length > 1]
    if not ends:
      checks.record(f'session {j}: a whole episode and a boundary were '
                    'recorded', False,
                    f'{len(tokens)} steps, episode starts at {starts[:8]}')
      continue
    lo, hi = ends[-1] - cfg.episode_length, ends[-1] + half
    with jax.default_matmul_precision('highest'):
      ref_mu, ref_base = jax.device_get(reference(
          tokens[lo:hi], dones[lo:hi], actions[lo:hi]))
    span = slice(hi - lo - 2 * half, hi - lo)
    worst_mu = max(worst_mu, float(np.max(np.abs(
        ref_mu[span] - log_mu[lo:hi][span]))))
    worst_base = max(worst_base, float(np.max(np.abs(
        ref_base[span] - baseline[lo:hi][span]))))
    compared += 2 * half
  print(f'reference: {compared} steps of {recorder.sessions} sessions '
        f'compared; worst |log mu - reference| {worst_mu:.3e}, worst '
        f'|baseline - reference| {worst_base:.3e}', flush=True)
  checks.record(
      'log mu(a) of the timed path agrees with the attention-form '
      'reference across an episode boundary',
      compared > 0 and worst_mu <= LOG_MU_TOLERANCE,
      f'worst {worst_mu:.3e}, tolerance {LOG_MU_TOLERANCE:.0e}, '
      f'{compared} steps')
  checks.record(
      'the baseline of the timed path agrees with the reference',
      compared > 0 and worst_base <= BASELINE_TOLERANCE,
      f'worst {worst_base:.3e}, tolerance {BASELINE_TOLERANCE:.0e}')


def run(ctx):
  cfg = ctx.config
  py_process.warm_forkserver()
  checks = correct.Checks()
  watch = processes.ChildWatch()
  seams = _Seams(ctx.param('check_sessions'))
  levels = factory.level_names(cfg)
  spec = factory.make_env_spec(cfg, levels[0], seed=1, is_test=True)
  agent = driver.build_agent(cfg, spec.num_actions)
  params = jax.jit(lambda key: init_params(agent, key, spec.obs_spec))(
      jax.random.PRNGKey(ctx.seed))
  jax.block_until_ready(params)
  ctx.mark('parameters made on the device from the seed: '
           f'{sum(x.size for x in jax.tree_util.tree_leaves(params))}')

  stop_event = threading.Event()
  watcher = _Watcher(ctx, seams, stop_event, watch)
  t_call = time.perf_counter()
  watcher.start()
  try:
    driver.play(cfg, agent, params, spec.obs_spec, levels,
                num_actors=cfg.num_actors,
                fleet_factory=_fleet_factory(seams, ctx.seed),
                stop_event=stop_event,
                stall_timeout_secs=STALL_TIMEOUT_SECS)
  finally:
    watcher.gave_up.set()
    watcher.join(timeout=60)
  if watcher.error is not None:
    raise watcher.error
  if not watcher.obs:
    raise RuntimeError(
        f'the run ended after {time.perf_counter() - t_call:.0f} s '
        f'before {ctx.param("warm_calls")} merged calls: no window')
  opened, closed = watcher.obs['opened'], watcher.obs['closed']
  server = {k: closed['counters']['server'][k] -
            opened['counters']['server'][k]
            for k in ('calls', 'requests', 'batcher_requests')}
  fleet = closed['counters']['fleet']
  print(f'window: {server["calls"]} merged calls of '
        f'{server["requests"]} rows in '
        f'{closed["perf"] - opened["perf"]:.1f} s; state '
        f'{closed["counters"]["server"]["state_bytes_per_slot"]} bytes a '
        f'slot, arena {closed["counters"]["server"]["arena_bytes"]}',
        flush=True)

  waits = seams.clock.waits(opened['perf'], closed['perf'])
  checks.record(
      'every merged call carried the whole fleet\'s rows',
      server['calls'] > 0 and
      server['requests'] == cfg.num_actors * server['calls'],
      f'{server["requests"]} rows in {server["calls"]} calls of '
      f'{cfg.num_actors} sessions')
  left = processes.env_processes_left()
  checks.record('no env process outlived the run', not left, left)
  checks.record(
      f'watched {len(watch.seen)} child processes, none opened an '
      'accelerator device',
      len(watch.seen) >= cfg.num_actors and not watch.offenders,
      watch.offenders)
  _check_against_reference(checks, ctx, cfg, params, seams.recorder)
  end = closed['counters']['server']
  return {
      'checks': checks,
      'failures': {
          'actor_respawns': fleet['respawns'],
          'slots_quarantined': fleet['slots_quarantined'],
          'sheds': end['sheds'],
          'chain_recoveries': end['chain_recoveries']},
      'attempted': len(waits),
      'caller_waits': waits,
      'window_seconds': closed['perf'] - opened['perf'],
      'counters': {'open': opened['counters'],
                   'close': closed['counters']},
  }

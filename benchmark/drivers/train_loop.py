"""The paths users run: `driver.train`, one blocking call, for the
fleet runtime (process-hosted actors -> batcher -> inference server ->
buffer -> learner) and, with `--runtime=anakin` among the traffic's
flags, the fused act+learn loop. Which one is the traffic file's
business; nothing here names a cell.

The call compiles its step on the first batch, so the window cannot
open when the call starts. A watcher thread of the benchmark tails the
run's own summaries.jsonl, opens the window once `ramp_steps` learner
steps have completed (all before is set-up), and `--seconds` later ends
the run through `drain_event`, the preemption seam `driver.train`
already takes. Rates come from the step events inside the window,
never from the wall time of the call.

Fleet runtime only: the fleet is built through `driver.train`'s
`fleet_factory` seam, which calls `driver.make_fleet` exactly as
`driver.train` does, except that the `policy` the actors get is timed
on the caller's side (harness/caller_clock.py) and the env seeds follow
`--seed`. The seam also hands the benchmark the server and the fleet,
whose counters it reads when the window opens and when it closes.

Traffic parameters: `ramp_steps`, `trace_seconds` (the slice the traced
run puts under the profiler, after its window).
"""

import contextlib
import os
import threading
import time

import jax

from benchmark.harness import caller_clock, correct, processes, window
from scalable_agent_tpu import driver
from scalable_agent_tpu.parallel import anakin as anakin_lib
from scalable_agent_tpu.runtime import ring_buffer

POLL_SECS = 0.05           # how often the watcher reads summaries.jsonl
STALL_TIMEOUT_SECS = 120   # driver.train gives up on a stalled learner


class _Seams:
  """What the benchmark holds of the running program."""

  def __init__(self):
    self.server = None
    self.fleet = None
    self.initial_params = None
    self.clock = caller_clock.CallerClock()

  def counters(self):
    """The program's own counts, now."""
    if self.server is None:
      return {}
    return {'server': self.server.stats(), 'fleet': self.fleet.stats()}


def _fleet_factory(seams, seed):
  def build(config, agent, policy, buffer, levels):
    seams.server = policy.__self__
    # The server copied the initial parameters at construction.
    seams.initial_params = jax.device_get(seams.server.live_params())
    seams.fleet = driver.make_fleet(
        config, agent, seams.clock.wrap(policy), buffer, levels,
        seed_base=seed * 1009,
        initial_state_fn=seams.server.initial_core_state)
    return seams.fleet
  return build


class _Watcher(threading.Thread):
  """Opens and closes the window from beside the blocking call."""

  def __init__(self, ctx, seams, summaries, drain_event, watch):
    super().__init__(name='bench-watcher', daemon=True)
    self.ctx, self.seams, self.watch = ctx, seams, watch
    self.summaries = summaries
    self.drain_event = drain_event
    self.gave_up = threading.Event()
    self.error = None
    self.obs = {}

  def _wait_for_step(self, step):
    last_sample = 0.0
    while not self.gave_up.is_set():
      events = window.read_step_events(self.summaries)
      if events and events[-1][1] >= step:
        return True
      if time.monotonic() - last_sample > 2.0:
        # Children open a chip, if ever, when they start.
        self.watch.sample()
        last_sample = time.monotonic()
      time.sleep(POLL_SECS)
    return False

  def run(self):
    try:
      self._run()
    except BaseException as e:  # noqa: BLE001 — re-raised by the driver
      self.error = e
      self.drain_event.set()

  def _run(self):
    ctx = self.ctx
    if not self._wait_for_step(ctx.param('ramp_steps')):
      return
    ctx.open_window()
    opened = {'wall': time.time(), 'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    self.gave_up.wait(
        max(0.0, opened['perf'] + ctx.seconds - time.perf_counter()))
    closed = {'wall': time.time(), 'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    ctx.close_window()
    if ctx.trace:
      # The traced slice FOLLOWS the window: counts, clocks and
      # summaries are read with the profiler off, like the end-to-end
      # metrics; only what is read from the device's trace comes from
      # the slice, and stopping the profiler belongs to no window.
      ctx.trace_start()
      self.gave_up.wait(ctx.param('trace_seconds'))
      ctx.trace_stop()
      ctx.mark('traced slice read')
    self.watch.sample()
    self.obs = {'opened': opened, 'closed': closed}
    self.drain_event.set()


@contextlib.contextmanager
def _wrapped(owner, name, wrap):
  """`owner.name` replaced by `wrap(the real one)` for the run: the
  benchmark's way of recording at a boundary of the program that has
  no seam, from its own files."""
  real = getattr(owner, name)
  setattr(owner, name, wrap(real))
  try:
    yield
  finally:
    setattr(owner, name, real)


def _learner_wait_span(ctx):
  """The traced run only: the learner's wait for a batch becomes a host
  span of the benchmark's, around the call into the buffer layer."""
  def wrap(real_get):
    def get(self, timeout=None):
      with ctx.span('learner_wait_batch'):
        return real_get(self, timeout=timeout)
    return get
  return _wrapped(ring_buffer.BatchPrefetcher, 'get', wrap)


def _capture_anakin_init(seams):
  """The fused runtime has no factory seam: note the parameters its
  construction starts from, as `driver.train_anakin` calls it."""
  def wrap(real_build):
    def build_run(*args, **kwargs):
      built = real_build(*args, **kwargs)
      seams.initial_params = jax.device_get(built[3].train_state.params)
      return built
    return build_run
  return _wrapped(anakin_lib, 'build_run', wrap)


def run(ctx):
  cfg = ctx.config
  fleet_runtime = cfg.runtime == 'fleet'
  seams, checks = _Seams(), correct.Checks()
  watch = processes.ChildWatch()
  drain_event = threading.Event()
  summaries = os.path.join(cfg.logdir, 'summaries.jsonl')
  watcher = _Watcher(ctx, seams, summaries, drain_event, watch)
  kwargs = {}
  t_call = time.perf_counter()
  with contextlib.ExitStack() as wrappers:
    if not fleet_runtime:
      wrappers.enter_context(_capture_anakin_init(seams))
    else:
      kwargs['fleet_factory'] = _fleet_factory(seams, ctx.seed)
      if ctx.trace:
        wrappers.enter_context(_learner_wait_span(ctx))
    watcher.start()
    try:
      run_ = driver.train(
          cfg, drain_event=drain_event,
          stall_timeout_secs=STALL_TIMEOUT_SECS, **kwargs)
    finally:
      watcher.gave_up.set()
      watcher.join(timeout=30)
  if watcher.error is not None:
    raise watcher.error
  t_return = time.perf_counter()
  if not watcher.obs:
    raise window.WindowError(
        f'the run ended after {t_return - t_call:.0f} s before '
        f'{ctx.param("ramp_steps")} learner steps had completed: no '
        'window was opened')
  opened, closed = watcher.obs['opened'], watcher.obs['closed']
  print(f'driver.train: {opened["perf"] - t_call:.1f} s of set-up and '
        f'ramp inside the call, {closed["perf"] - opened["perf"]:.1f} s '
        f'of window, {t_return - closed["perf"]:.1f} s of drain and '
        'teardown after it', flush=True)

  steps = int(jax.device_get(run_.state.update_steps))
  losses = [v for _, _, v in
            window.read_scalars(summaries, ['total_loss'])['total_loss']]
  obs = {
      'frames_per_step': cfg.frames_per_step,
      'step_events': window.read_step_events(summaries),
      'window_wall': (opened['wall'], closed['wall']),
      'window_seconds': closed['perf'] - opened['perf'],
      'counters': {'open': opened['counters'],
                   'close': closed['counters']},
  }
  inside = window.events_in(obs['step_events'], *obs['window_wall'])
  failures = {}
  if fleet_runtime:
    obs['caller_waits'] = seams.clock.waits(opened['perf'],
                                            closed['perf'])
    fleet, server = closed['counters']['fleet'], closed['counters']['server']
    failures.update(
        actor_respawns=fleet['respawns'],
        slots_quarantined=fleet['slots_quarantined'],
        sheds=server['sheds'],
        chain_recoveries=server['chain_recoveries'])
    left = processes.env_processes_left()
    checks.record('no env process outlived the run', not left, left)
    checks.record(
        f'watched {len(watch.seen)} child processes, none opened an '
        'accelerator device',
        len(watch.seen) >= cfg.num_actors and not watch.offenders,
        watch.offenders)
    checks.record('the batcher merged calls',
                  server['requests'] > server['calls'] > 0,
                  f'{server["requests"]} calls in {server["calls"]}')
  health = run_.health.stats() if run_.health is not None else {}
  withheld = correct.check_learner(
      checks, run_.state, steps, seams.initial_params, losses)
  failures.update(steps_withheld=withheld,
                  rollbacks=health.get('rollbacks', 0))
  correct.check_vtrace(checks, cfg, ctx.seed)
  obs.update(
      checks=checks, failures=failures,
      attempted=((inside[-1][1] - inside[0][1] if inside else 0) +
                 len(obs.get('caller_waits', ()))))
  return obs

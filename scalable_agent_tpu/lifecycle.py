"""What a training run does besides training, written once.

`driver.train`, `driver.train_anakin` and the fused population loop
differ in what produces a batch and what steps it. What a run does at
its start and end, on a bad step, and with the one profiler does not
differ, and lives here:

  restore_at_start   Checkpointer + restore (or a caller's state)
  open_run -> Run    summaries, incidents, lock-order sink, config
                     dump, fps meter, health monitor, SLO engine;
                     `Run.close` writes the verdict, applies the
                     tail-checkpoint rule and closes in order
  HealthLadder       delayed sentinel read -> skip / rollback / halt
  ProfilerWindow     at most one jax.profiler capture at a time
  DelayedMetrics     the one-step-late metrics read

Nothing here knows which loop calls it: what only one loop does
(SDC fingerprints, the multi-host restore choice, the republish after
a rollback, the flight recorder) comes in as an argument.
"""

import contextlib
import dataclasses
import json
import logging
import os
import sys
from typing import Callable, Dict, List, Optional, Tuple

import jax

from scalable_agent_tpu import checkpoint as checkpoint_lib
from scalable_agent_tpu import health as health_lib
from scalable_agent_tpu import observability
from scalable_agent_tpu import slo as slo_lib
from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis import runtime as lock_check
from scalable_agent_tpu.parallel import sharding as sharding_lib

log = logging.getLogger('scalable_agent_tpu')


def record_run(config, write: bool = True) -> None:
  """Reproducibility: the exact config of every run and the device it
  ran on live next to its checkpoints/summaries (the reference leaves
  flags only in shell history). The device is also one log line, so a
  run that came up on another platform than intended says so at
  start-up. `write=False` logs only (multi-host: process 0 owns the
  files)."""
  devices = jax.devices()
  device = {'platform': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'device_count': len(devices)}
  log.info('running on platform=%s device_kind=%s device_count=%d',
           device['platform'], device['device_kind'],
           device['device_count'])
  if not write:
    return
  for name, payload in (('config.json', dataclasses.asdict(config)),
                        ('device.json', device)):
    with open(os.path.join(config.logdir, name), 'w') as f:
      json.dump(payload, f, indent=2, sort_keys=True)


def restore_at_start(config, state, *, mesh=None, registry=None,
                     restore: Optional[Callable] = None,
                     initial_state=None):
  """The run's Checkpointer over `<logdir>/checkpoints` and the state
  to start from: `initial_state` when the caller hands one over (the
  population loop's on-device exploit: no disk round trip, the next
  periodic save lands it in this ladder), else the newest restorable
  checkpoint, else `state` as built.

  `restore(checkpointer, state)` replaces `restore_latest` (train's
  cross-topology route). A raise from it, a structure mismatch say,
  closes the manager (its background threads would survive a retry in
  the same process) and propagates: the caller never reaches a close
  that could tail-save a fresh state over an incompatible ladder."""
  checkpointer = checkpoint_lib.Checkpointer(
      config.logdir + '/checkpoints',
      save_interval_secs=config.checkpoint_secs,
      verify_digests=config.ckpt_digests,
      registry=registry or sharding_lib.from_config(config), mesh=mesh)
  if initial_state is not None:
    log.info('starting from caller-provided state at step %d',
             int(jax.device_get(initial_state.update_steps)))
    return checkpointer, initial_state
  try:
    restored = (restore(checkpointer, state) if restore is not None
                else checkpointer.restore_latest(state))
  except BaseException:
    checkpointer.close()
    raise
  if restored is None:
    return checkpointer, state
  log.info('restored checkpoint at step %d',
           int(jax.device_get(restored.update_steps)))
  return checkpointer, restored


class DelayedMetrics:
  """The step's scalar metrics, stacked into one device array when the
  step is dispatched and read one step later: by then the values are
  computed, so the summary's read is one transfer that never syncs the
  dispatch chain (observability.stack_metrics)."""

  def __init__(self):
    self.pending = None  # (step, handle) of the step just dispatched
    self._prev = None

  def push(self, step: int, metrics: Dict) -> None:
    self._prev = self.pending
    self.pending = (step, observability.stack_metrics(metrics))

  def older(self):
    """The (step, handle) to read now: the previous step's; only the
    very first step has no predecessor and blocks on its own."""
    return self._prev if self._prev is not None else self.pending


class ProfilerWindow:
  """The one profiler, shared by two clients: the operator's
  `--profile_dir` window of `profile_num_steps` steps from
  `profile_start_step` on, and a bounded capture into
  `diagnostics/slo_profile_<objective>/` that a page-severity SLO burn
  asks for (the engine thread queues it; the loop that dispatches
  device work has to run it). jax.profiler traces one thing at a time,
  so the operator's window DEFERS past a capture under way, never
  silently skipped, and SLO requests wait while the operator's runs.
  `tick(steps_done)` once a step; `close()` stops what is open."""

  def __init__(self, config, slo_engine=None):
    self._config = config
    self._slo_engine = slo_engine
    self._operator_pending = bool(config.profile_dir)
    self._capture = None  # the observability.ProfilerCapture under way
    self._stop_step = None
    self._slo_name = None  # the objective, while the capture is SLO's

  def tick(self, steps_done: int) -> None:
    if self._capture is not None:
      if steps_done < self._stop_step:
        return
      log.info('profiler trace and spans.json written to %s',
               self._capture.stop())
      self._capture = None
      if self._slo_name is not None:
        self._slo_name = None
        return  # the operator's window opens at the next step
    if (self._operator_pending
        and steps_done >= self._config.profile_start_step):
      self._capture = observability.ProfilerCapture(
          self._config.profile_dir)
      self._operator_pending = False
      self._stop_step = steps_done + self._config.profile_num_steps
      return
    if self._slo_engine is None:
      return
    req = self._slo_engine.take_profile_request()
    if req is None:
      return
    directory = os.path.join(self._config.logdir, 'diagnostics',
                             f'slo_profile_{req}')
    try:
      self._capture = observability.ProfilerCapture(directory)
    except Exception:
      log.exception('SLO profiler capture failed to start')
      self._slo_engine.note_profile(req, None)
      return
    self._slo_name = req
    self._stop_step = steps_done + self._config.slo_capture_steps
    self._slo_engine.note_profile(req, directory)
    log.warning('SLO page (%s): capturing a %d-step profiler trace '
                'into %s', req, self._config.slo_capture_steps,
                directory)

  def close(self) -> None:
    if self._capture is not None:
      self._capture.stop()
      self._capture = None
    elif self._operator_pending:
      log.warning(
          'profile_dir set but the run ended before the window could '
          'start (profile_start_step=%d, or an SLO capture held the '
          'profiler): no operator trace was captured',
          self._config.profile_start_step)


class HealthLadder:
  """The escalation ladder over health.HealthMonitor: skip-and-count
  (the in-graph guard already withheld a non-finite update) -> roll
  back to the last-known-good checkpoint after K consecutive bad steps
  -> halt with a diagnostic bundle instead of training through
  divergence.

  `step` is called once a learner step. The sentinel read is ONE STEP
  DELAYED: step N's stacked scalars are fetched after step N+1 was
  dispatched, so the read finds computed values and never syncs the
  dispatch pipeline. Verdicts are a deterministic function of the
  (replicated) step metrics, so multi-host processes reach rollback
  and halt in lockstep.

  `restore(state)` replaces `checkpointer.restore_last_good` (train's
  multi-host choice of one step for all hosts). `on_rollback(step,
  state)` runs after a rollback took, before its incident (train
  republishes the reverted params). `extra_sentinels` is a pair:
  `dispatch(step, state)` returns a device handle when a check is
  stashed, `read(obs_step, handle)` returns the values to add when it
  is read (train's SDC fingerprints). `flight` is the telemetry flight
  recorder, dumped beside a rollback and into the halt bundle.

  With `health` None (the watchdog off) `step` returns its state and
  `healthy_now` stays True."""

  def __init__(self, config, health, checkpointer, incidents, *,
               flight=None, restore: Optional[Callable] = None,
               on_rollback: Optional[Callable] = None,
               extra_sentinels: Optional[Tuple[Callable,
                                               Callable]] = None):
    self._config = config
    self._health = health
    self._incidents = incidents
    self._flight = flight
    self._restore = restore or (
        checkpointer.restore_last_good if health is not None else None)
    self._on_rollback = on_rollback
    self._extra = extra_sentinels
    self._steps = 0
    # (step, SentinelHandle, extra handle) awaiting its delayed read.
    self._pending = None
    # Bad steps of the current burst. Kept here, not read from the
    # monitor: its consecutive count resets on a ROLLBACK verdict, so
    # a burst whose length is a multiple of K would never end.
    self._bad_count_in_burst = 0

  @property
  def healthy_now(self) -> bool:
    """False inside a bad burst. Saves are WITHHELD then: finite
    divergence mutates params every step, and saving them would
    advance LAST_GOOD onto the diverged state and evict the healthy
    retained steps the rollback needs."""
    return self._bad_count_in_burst == 0

  def step(self, step_now: int, metrics: Dict, state):
    """Stashes this step's sentinels, judges the previous check's, and
    returns the TrainState to continue with (`state`, or the rolled
    back one with `update_steps` kept). Raises TrainingDivergence on
    HALT."""
    health = self._health
    if health is None:
      return state
    self._steps += 1
    prev, self._pending = self._pending, None
    if self._steps % self._config.health_check_every_steps == 0:
      extra = (self._extra[0](step_now, state)
               if self._extra is not None else None)
      self._pending = (step_now, health_lib.stack_sentinels(metrics),
                       extra)
    if prev is None:
      return state
    obs_step, handle, extra = prev
    values = health_lib.read_handle(handle)
    if extra is not None:
      values.update(self._extra[1](obs_step, extra))
    verdict = health.observe_values(obs_step, values)
    self._bad_count_in_burst += (verdict != health_lib.OK)
    if verdict != health_lib.OK and self._bad_count_in_burst == 1:
      self._incidents.event('health_bad_burst_start', step=obs_step,
                            reason=health.last_reason)
      log.warning('unhealthy training step %d: %s', obs_step,
                  health.last_reason)
    elif verdict == health_lib.OK and self._bad_count_in_burst > 0:
      self._incidents.event('health_recovered', step=obs_step,
                            bad_steps=self._bad_count_in_burst)
      self._bad_count_in_burst = 0
    if verdict == health_lib.ROLLBACK:
      rolled = self._restore(state)
      if rolled is None:
        verdict = health_lib.HALT
        health.rollbacks -= 1  # granted but could not be honored
        health.last_reason = (f'{health.last_reason}; rollback '
                              'requested but no restorable '
                              'checkpoint exists')
      else:
        # Keep the CURRENT update counter: frames/steps count
        # consumed env data and must stay monotone through a rollback
        # (checkpoint step numbers and the LR schedule never move
        # backwards; only params/opt/popart revert).
        restored_step = int(jax.device_get(rolled.update_steps))
        state = rolled._replace(update_steps=state.update_steps)
        if self._on_rollback is not None:
          self._on_rollback(step_now, state)
        self._incidents.event(
            'rollback', step=step_now,
            restored_checkpoint_step=restored_step,
            reason=health.last_reason,
            flight=self._dump_flight(step_now))
        log.warning(
            'health rollback at step %d: restored checkpoint step %d '
            '(params/optimizer/popart revert; step counter keeps '
            'running)', step_now, restored_step)
    if verdict == health_lib.HALT:
      bundle = health.write_halt_bundle(
          self._config.logdir, self._config, step_now,
          reason=health.last_reason,
          flight=(self._flight.dump() if self._flight is not None
                  else None))
      self._incidents.event('health_halt', step=step_now,
                            reason=health.last_reason, bundle=bundle)
      raise health_lib.TrainingDivergence(
          f'training halted at step {step_now} after '
          f'{health.rollbacks} rollback escalation(s): '
          f'{health.last_reason}. Diagnostic bundle: {bundle}',
          bundle_path=bundle)
    return state

  def _dump_flight(self, step_now: int) -> Optional[str]:
    """The last seconds of pipeline history next to the rollback
    incident: a postmortem starts from what the pipeline was DOING."""
    if self._flight is None:
      return None
    try:
      out_dir = os.path.join(self._config.logdir, 'diagnostics')
      os.makedirs(out_dir, exist_ok=True)
      return self._flight.write(os.path.join(
          out_dir, f'flight_rollback_step{step_now}.json'))
    except OSError:
      log.exception('flight-recorder dump failed')
      return None


class Run:
  """The open planes of one run (see `open_run`). Attributes: `writer`
  (SummaryWriter), `incidents` (EventLog), `fps_meter`, `health`
  (HealthMonitor, None with the watchdog off), `slo_engine` (None with
  the engine off), `slo_interval`, `ladder`, `profiler`, `metrics`,
  `process_index`, and after `close` began, `clean_exit`."""

  def __init__(self, config, checkpointer):
    self.config = config
    self.checkpointer = checkpointer
    self.process_index = jax.process_index()
    self.writer = None
    self.incidents = None
    self.fps_meter = None
    self.health = None
    self.slo_engine = None
    self.slo_interval = None
    self.ladder = None
    self.profiler = None
    self.metrics = DelayedMetrics()
    self.clean_exit = None
    self._gauges: List[telemetry.Gauge] = []
    self._unwind = contextlib.ExitStack()  # what `abort` undoes

  def _name(self, stem: str, ext: str) -> str:
    """Multi-host: every process writes its OWN streams into a shared
    logdir; process 0 keeps the canonical file names."""
    if self.process_index == 0:
      return f'{stem}.{ext}'
    return f'{stem}_p{self.process_index}.{ext}'

  def track(self, gauge: telemetry.Gauge) -> telemetry.Gauge:
    """Unregisters `gauge` when the run closes: fn-gauges close over
    the loop's locals, which must not stay pinned by the registry."""
    self._gauges.append(gauge)
    return gauge

  def loop_gauges(self, update_steps: Callable, env_frames: Callable,
                  utilization: Optional[Callable] = None) -> None:
    """The registry's view of the loop: the step and frame clocks every
    other counter is read against and, from a loop with no plane split
    of its own to measure (one fused program is busy whenever the loop
    is), the two plane utilizations."""
    self.track(telemetry.gauge('driver/update_steps', fn=update_steps))
    self.track(telemetry.gauge('driver/env_frames', fn=env_frames))
    if utilization is not None:
      self.track(telemetry.gauge('driver/env_plane_utilization',
                                 fn=utilization))
      self.track(telemetry.gauge('driver/learner_plane_utilization',
                                 fn=utilization))

  def write_health_scalars(self, step: int) -> None:
    """The learner's failure-domain counters of the summary block."""
    writer = self.writer
    if self.health is not None:
      hs = self.health.stats()
      writer.scalar('skipped_steps', hs['skipped_steps'], step)
      writer.scalar('flagged_steps', hs['flagged_steps'], step)
      writer.scalar('rollbacks', hs['rollbacks'], step)
    checkpointer = self.checkpointer
    writer.scalar('checkpoint_save_errors', checkpointer.save_errors,
                  step)
    writer.scalar('checkpoint_restore_fallbacks',
                  checkpointer.restore_fallbacks, step)
    # Restore rungs refused for a CONTENT-digest mismatch (bit rot on
    # a committed step): a subset of the fallbacks, on its own curve.
    writer.scalar('ckpt_digest_fallbacks',
                  checkpointer.digest_fallbacks, step)

  def observe_slo(self) -> None:
    """Step-synchronous SLO evaluation from the summary block: the
    engine's thread covers long gaps, this call makes detection
    deterministic wherever summaries are frequent (chaos runs at
    summary_secs=0)."""
    if self.slo_engine is not None:
      self.slo_engine.observe()

  def abort(self) -> None:
    """Unwinds a run that never started (set-up failed after
    `open_run`): no verdict, no checkpoint; newest first, and one
    failing step does not skip the rest. The checkpointer stays the
    caller's."""
    self._unwind.close()

  def _unregister_gauges(self) -> None:
    for gauge in self._gauges:
      telemetry.registry().unregister(gauge.name, gauge)
    self._gauges = []

  def close(self, state, update_steps: int,
            extra: Optional[Dict] = None,
            teardown: Optional[Callable] = None) -> None:
    """Ends the run, from the loop's `finally`, on every way out.

    In order: the SLO evaluator stops and SLO_VERDICT.json is written
    (`clean_exit`, `update_steps` and the caller's `extra`) BEFORE any
    component goes, so that the final observation still sees every
    fn-gauge; the profiler stops; `teardown()` takes down what only
    this loop has; `state` gets its tail checkpoint (None: the loop
    saved already) unless the run ended inside a bad burst or is
    unwinding an exception on several hosts; then checkpointer,
    summaries, lock-order sink, incidents and gauges close."""
    self.clean_exit = sys.exc_info()[0] is None
    if self.slo_engine is not None:
      try:
        self.slo_engine.stop()
        name = self._name('SLO_VERDICT', 'json')
        verdict = self.slo_engine.finalize(
            os.path.join(self.config.logdir, name),
            extra=dict({'clean_exit': self.clean_exit,
                        'update_steps': update_steps}, **(extra or {})))
        (log.info if verdict['pass'] else log.warning)(
            'SLO verdict: %s (%d objective(s), violations: %s) -> %s',
            'PASS' if verdict['pass'] else 'FAIL',
            len(verdict['objectives']),
            verdict['violations'] or 'none', name)
      except Exception:
        log.exception('SLO verdict write failed')
    self.profiler.close()
    try:
      if teardown is not None:
        teardown()
      # The final save is a COLLECTIVE. On a clean exit every host
      # reaches it in lockstep (termination is a function of the
      # shared step count). Unwinding a host-local exception, the
      # other hosts are still inside the collective train step:
      # entering the Orbax barrier would deadlock the job instead of
      # surfacing the error; periodic checkpoints cover the tail. An
      # UNHEALTHY exit (divergence halt, or any unwind mid-burst) must
      # not save either: it would advance LAST_GOOD onto the diverged
      # state and evict the healthy steps, and the restarted run would
      # restore the poison and halt again.
      if state is None:
        pass
      elif not self.ladder.healthy_now:
        log.warning('skipping final checkpoint: training was '
                    'unhealthy at exit (the retained last-known-good '
                    'checkpoint covers the resume)')
      elif jax.process_count() == 1 or self.clean_exit:
        self.checkpointer.save(state, force=True)
      else:
        log.warning('skipping final collective checkpoint on '
                    'exception unwind (multi-host)')
    finally:
      if self.checkpointer is not None:
        self.checkpointer.close()
      self.writer.close()
      # The sink closes over this run's incident stream: cleared
      # before the stream closes, so that a later detection in a
      # leaked daemon thread is a counted log line, not a write into
      # a closed file.
      lock_check.set_incident_sink(None)
      self.incidents.close()
      self._unregister_gauges()


def open_run(config, checkpointer=None, *, flight=None,
             rollback_restore: Optional[Callable] = None,
             on_rollback: Optional[Callable] = None,
             extra_sentinels=None) -> Run:
  """Opens what every training run has, in this order: the summary
  stream and the incident log, the lock-order incident sink (a latent
  ABBA deadlock found by a storm must survive whatever crash follows
  it), config.json and device.json, the fps meter, the health monitor
  (`config.health_watchdog`, and a `checkpointer` to roll back to)
  with its ladder, the
  SLO engine (`config.slo_engine`) and the profiler window. A raise
  part-way unwinds what was built, newest first, and propagates.

  `flight` is the telemetry flight recorder where the loop has one;
  the three hooks go to `HealthLadder`. The checkpointer is closed by
  `Run.close`, not by a failure here."""
  run = Run(config, checkpointer)
  with contextlib.ExitStack() as unwind:
    unwind.callback(run._unregister_gauges)
    run.writer = observability.SummaryWriter(
        config.logdir, filename=run._name('summaries', 'jsonl'))
    unwind.callback(run.writer.close)
    run.incidents = observability.EventLog(
        config.logdir, filename=run._name('incidents', 'jsonl'))
    unwind.callback(run.incidents.close)
    lock_check.set_incident_sink(run.incidents.event)
    unwind.callback(lock_check.set_incident_sink, None)
    record_run(config, write=run.process_index == 0)
    run.fps_meter = observability.FpsMeter()
    # No checkpointer (the fused population: one a member, saved at
    # round boundaries): nothing to roll back to, so no watchdog.
    run.health = (health_lib.monitor_from_config(config)
                  if config.health_watchdog and checkpointer is not None
                  else None)
    run.ladder = HealthLadder(
        config, run.health, checkpointer, run.incidents,
        flight=flight, restore=rollback_restore,
        on_rollback=on_rollback, extra_sentinels=extra_sentinels)
    if config.slo_engine:
      objectives = slo_lib.load_objectives(
          config.slo_spec,
          fast_window_secs=config.slo_fast_window_secs,
          slow_window_secs=config.slo_slow_window_secs)
      # Derived cadence: summary-paced, but ALWAYS at least ~4 samples
      # inside the fast burn window. Value objectives need 3
      # fast-window samples before they can burn, so an interval as
      # long as the window would leave the page objectives unable to
      # fire (validate_slo warns when an EXPLICIT interval does this).
      run.slo_interval = (
          config.slo_interval_secs if config.slo_interval_secs > 0
          else min(max(float(config.summary_secs), 1.0), 30.0,
                   config.slo_fast_window_secs / 4.0))
      run.slo_engine = slo_lib.SloEngine(
          objectives, config.logdir, writer=run.writer,
          incidents=run.incidents, flight=flight, health=run.health,
          capture=config.slo_capture, interval_secs=run.slo_interval,
          baseline=slo_lib.load_baseline(config.slo_fps_baseline))
      unwind.callback(run.slo_engine.stop)
      run.slo_engine.start()
    run.profiler = ProfilerWindow(config, run.slo_engine)
    run._unwind = unwind.pop_all()
  return run

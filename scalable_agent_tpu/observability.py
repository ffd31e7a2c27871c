"""Observability: throughput meter, episode stats, summaries.

The reference has three channels (SURVEY §5.5): tf.summary scalars from
build_learner, manual per-episode tf.Summary protos from the learner
Python loop, and tf.logging text. Episode statistics travel THROUGH the
graph as `StepOutputInfo` — no side channel (reference: environments.py
≈L165–190; experiment.py ≈L590–620). This module keeps that design: the
learner loop hands each dequeued batch to `EpisodeStats.extract`, which
reads finished episodes straight out of the trajectory pytree.

What the reference lacks and BASELINE demands is a first-class
frames/sec meter (SURVEY §5.1) — `FpsMeter` here is the north-star
metric source.

Summaries are JSONL events (one object per line: wall_time, step, tag,
value) — greppable, plotter-friendly, no TensorBoard dependency.
"""

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.envs import suites


class _JsonlAppender(telemetry.JsonlAppender):
  """Shared line-buffered append-only JSONL plumbing for the scalar
  summaries and the incident stream. THE implementation (open/lock/
  write-line/silent-counted-drop-after-close/fsync-durable) lives in
  telemetry.JsonlAppender — one copy behind this module's streams AND
  the tracer's traces.jsonl, so the round-13 crash-safety contract
  cannot drift between them."""


class SummaryWriter(_JsonlAppender):
  """Append-only JSONL scalar writer (thread-safe)."""

  def __init__(self, logdir: str, filename: str = 'summaries.jsonl'):
    super().__init__(logdir, filename)

  def scalar(self, tag: str, value, step: int):
    self.write({'wall_time': round(time.time(), 3),
                 'step': int(step), 'tag': tag, 'value': float(value)})

  def scalars(self, values: Dict[str, float], step: int):
    for tag, value in values.items():
      self.scalar(tag, value, step)

  def histogram(self, tag: str, counts, step: int, edges=None):
    """Fixed-bin histogram event (the reference's
    tf.summary.histogram channel, experiment.py ≈L395 — its one use is
    the per-update action histogram, the main policy-collapse signal).

    `counts[i]` is the count of bin i — for discrete data (actions)
    the bin IS the value; for continuous data pass `edges` (len
    = len(counts)+1, np.histogram convention)."""
    event = {'wall_time': round(time.time(), 3), 'step': int(step),
             'tag': tag, 'kind': 'histogram',
             'counts': [int(c) for c in np.asarray(counts).ravel()]}
    if edges is not None:
      event['edges'] = [float(e) for e in np.asarray(edges).ravel()]
    self.write(event)


class EventLog(_JsonlAppender):
  """Append-only JSONL of structured INCIDENT events (thread-safe).

  Scalar summaries answer 'how much'; during a failure the operator
  (and scripts/chaos.py's SLO asserts) need 'what happened when':
  bad-step bursts, checkpoint rollbacks, watchdog halts, fault
  injections. One object per line — {wall_time, kind, step, ...} —
  in `incidents.jsonl` next to the summaries. Quiet runs produce an
  empty (or absent) file; the log is written on incident, not on a
  cadence.
  """

  # Incident kinds that must survive a kill -9 landing right after
  # the event (fsync'd): the halt/rollback/SDC records ARE the
  # postmortem — a line-buffered write that dies in the page cache
  # with the process defeats the whole stream. Substring match so the
  # driver's spellings (health_halt, sdc_replica_mismatch,
  # fault_replica_divergence, actor_slots_quarantined) all qualify
  # without a fragile exact list.
  # 'slo' (round 14): an SLO violation/capture record is the page an
  # operator will be reading — it must survive the crash it may be
  # narrating.
  # 'controller' (round 15): a controller_action record is the
  # self-healing audit trail — a knob the run moved on its own must
  # survive whatever crash follows it.
  # 'lock_order' (round 18): a lock_order_inversion detection IS the
  # latent-deadlock postmortem — it must survive the deadlock/crash
  # it predicts.
  # 'host_' (round 20): host_left/host_joined membership records are
  # how an operator reconstructs the pod's shape over time — a
  # departure record that dies with the crash that caused the
  # departure defeats the audit.
  # 'reshard' (round 20): a topology_resharded record marks a restore
  # whose layout was respecified for a NEW mesh — the provenance line
  # every later numerical question starts from.
  # 'pbt' (round 22): a pbt_exploit record is the provenance of a
  # member's weights (which donor it copied, at which round, with
  # which explored hypers) — without it a population run's winner is
  # unexplainable after the fact (RUNBOOK "which replica won and
  # why").
  # The canonical marker list is contract-linted
  # (scripts/lint.py durable-markers) against the docs/OBSERVABILITY
  # .md "Durable incident markers" section AND against the kinds the
  # modules actually emit, both directions.
  _DURABLE_MARKERS = ('halt', 'rollback', 'sdc', 'quarantin', 'slo',
                      'controller', 'lock_order', 'host_', 'reshard',
                      'pbt')

  def __init__(self, logdir: str, filename: str = 'incidents.jsonl'):
    super().__init__(logdir, filename)

  def event(self, kind: str, step: Optional[int] = None, **fields):
    record = {'wall_time': round(time.time(), 3), 'kind': str(kind)}
    if step is not None:
      record['step'] = int(step)
    record.update(fields)
    durable = any(m in kind for m in self._DURABLE_MARKERS)
    self.write(record, durable=durable, default=str)


class FpsMeter:
  """Environment-frames/sec over a sliding window of learner steps.

  Frames unit matches the reference's global step: env frames AFTER
  action repeat (experiment.py ≈L390; SURVEY §6 measurement definition).
  """

  def __init__(self, window_secs: float = 30.0):
    self._window_secs = window_secs
    self._events = collections.deque()  # (t, frame_delta)
    self._total_frames = 0
    self._start = time.monotonic()

  def update(self, frames: int):
    now = time.monotonic()
    self._total_frames += frames
    self._events.append((now, frames))
    self._prune(now)

  def _prune(self, now: float):
    cutoff = now - self._window_secs
    while self._events and self._events[0][0] < cutoff:
      self._events.popleft()

  @property
  def total_frames(self) -> int:
    return self._total_frames

  def fps(self) -> float:
    """Rate over the trailing window, anchored at NOW — a stalled
    learner reads as decaying-to-zero fps, not the last healthy rate."""
    now = time.monotonic()
    self._prune(now)
    span = min(now - self._start, self._window_secs)
    if span <= 0:
      return 0.0
    return sum(delta for _, delta in self._events) / span


class ThreadWatchdog:
  """Liveness ledger for long-running service threads (round 11).

  A wedged thread — an ingest reader stuck mid-recv against a
  half-open peer, a param-lane selector loop that died, a worker
  parked forever in a send — used to leak SILENTLY: the socket stayed
  open, the thread stayed alive, and the only symptom was a slowly
  starving pipeline. Each service thread `beat()`s once per loop
  iteration (including idle poll timeouts, so an idle thread is not a
  wedged thread); `wedged(stall_secs)` names the threads that have
  made no progress past the deadline. The owner (the ingest server's
  `stats()`) surfaces the count so the driver can write the
  `ingest_threads_wedged` summary + incident instead of the operator
  discovering the leak hours later.

  Thread-safe; registration is idempotent (a beat registers)."""

  def __init__(self):
    self._beats: Dict[str, float] = {}
    self._lock = threading.Lock()

  def beat(self, name: str):
    with self._lock:
      self._beats[name] = time.monotonic()

  def unregister(self, name: str):
    with self._lock:
      self._beats.pop(name, None)

  def names(self) -> List[str]:
    with self._lock:
      return sorted(self._beats)

  def wedged(self, stall_secs: float) -> List[str]:
    """Registered threads with no beat for `stall_secs` (sorted)."""
    cutoff = time.monotonic() - stall_secs
    with self._lock:
      return sorted(n for n, t in self._beats.items() if t < cutoff)


class LatencyReservoir:
  """Bounded recent-sample reservoir for latency percentiles
  (thread-safe) — the per-lane transport counters' backing store
  (round 6): seconds in, p50/p99 out, for consumers that want the
  seconds-native API without a registry name (inference admission
  waits).

  Since round 13 this is a thin veneer over `telemetry.Histogram`
  (which IS this design promoted to a registry citizen) — ONE
  implementation of the bounded-window/nearest-rank/NaN-on-empty
  contract, so the registry's numbers and this surface can never
  drift. NaN on empty: 'no traffic yet' renders as '-' in
  bench/telemetry rows instead of masquerading as a perfect 0 ms
  latency."""

  def __init__(self, maxlen: int = 4096):
    self._hist = telemetry.Histogram('latency_reservoir',
                                     maxlen=maxlen)

  def record(self, seconds: float):
    self._hist.observe(float(seconds))

  @property
  def count(self) -> int:
    return self._hist.count

  def percentiles(self, *qs: float) -> Tuple[float, ...]:
    return self._hist.percentiles(*qs)

  def percentile_ms(self, *qs: float) -> Tuple[float, ...]:
    """`percentiles`, in rounded milliseconds — the stats()-surface
    form every reservoir consumer was hand-rolling with its own
    `round(x * 1e3, 3)`."""
    return tuple(round(v * 1e3, 3) for v in self.percentiles(*qs))


def stack_metrics(metrics: Dict) -> Tuple[Tuple[str, ...], object]:
  """Stack a step's scalar metrics into ONE device array.

  The deferred-readback half of the learner's metrics path (round 8):
  `driver.train` used to `device_get` the whole per-step metrics dict
  leaf-by-leaf at summary time — one host sync per key, against
  values the step had JUST produced, so the first sync stalled on the
  entire step. Stacking costs one tiny fused dispatch per step; the
  handle is read ONE STEP LATER (`read_stacked_metrics`), by which
  time the values are long computed and the single transfer returns
  without syncing the dispatch pipeline — the same pattern
  health.stack_sentinels proved for the watchdog scalars."""
  import jax.numpy as jnp
  keys = tuple(sorted(metrics))
  return keys, jnp.stack([jnp.asarray(metrics[k], jnp.float32)
                          for k in keys])


def read_stacked_metrics(handle) -> Dict[str, float]:
  """One transfer: (keys, stacked device array) → host float dict."""
  import jax
  keys, stacked = handle
  values = np.asarray(jax.device_get(stacked))
  return {k: float(v) for k, v in zip(keys, values)}


CAPTURE_LANDMARK = 'capture_clock_sync'
_landmark = None  # (the jitted landmark, its argument), once warmed


def _run_landmark() -> int:
  """Runs the landmark program to its end; the host's clock then."""
  global _landmark
  import jax
  if _landmark is None:
    import jax.numpy as jnp

    def capture_clock_sync(x):
      return x + 1

    _landmark = (jax.jit(capture_clock_sync), jnp.zeros((), jnp.int32))
  jax.block_until_ready(_landmark[0](_landmark[1]))
  return time.perf_counter_ns()


class ProfilerCapture:
  """One bounded `jax.profiler` capture into `directory`: THE way this
  program starts and stops the profiler.

  The profiler records the DEVICE only (`host_tracer_level` and
  `python_tracer_level` 0). With its host tracer on, a 32-actor fleet
  ran at 420 policy calls/s instead of 965 and the capture took 250 s
  to stop (PERF.md, PR 22): what it showed was the tracer. The host's
  side comes from the span recorder instead (telemetry.arm_spans),
  armed for the capture; `stop` writes it to `<directory>/spans.json`
  as `telemetry.take_spans()` gives it, plus `landmark`: the name of
  a tiny program run right after the profiler started and the host
  clock (`perf_counter_ns`) at which the host saw it end. The device
  trace records that program's end too (the first `XLA Modules` event
  named `jit_capture_clock_sync`), which puts both on one clock.
  `scripts/trace_report.py <directory>/spans.json` summarizes the
  spans. One capture at a time: the profiler and the recorder are
  both process-wide."""

  def __init__(self, directory: str):
    import jax
    self.directory = directory
    os.makedirs(directory, exist_ok=True)
    _run_landmark()  # compiled before the profiler looks
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    self._landmark_ns = _run_landmark()
    telemetry.arm_spans()

  def stop(self) -> str:
    """Stops the profiler and writes spans.json; returns its path."""
    import jax
    taken = telemetry.take_spans() or {}
    jax.profiler.stop_trace()
    taken['landmark'] = {'module': f'jit_{CAPTURE_LANDMARK}',
                         'host_perf_ns': self._landmark_ns}
    path = os.path.join(self.directory, 'spans.json')
    with open(path, 'w') as f:
      json.dump(taken, f)
    return path


def extract_episodes(batch) -> List[Tuple[int, float, int]]:
  """Finished episodes in a dequeued [T+1, B] batch.

  Returns [(level_id, episode_return, episode_frames)]. A done at
  timestep t>0 marks an episode end whose final stats ride in the
  OUTPUT info at that step (the FlowEnvironment contract). Timestep 0
  is the overlap frame — already counted in the previous batch, so
  skipped exactly like the reference's `done[1:]` (test() ≈L399 and
  the train loop ≈L590).
  """
  done = np.asarray(batch.env_outputs.done)[1:]          # [T, B]
  returns = np.asarray(batch.env_outputs.info.episode_return)[1:]
  steps = np.asarray(batch.env_outputs.info.episode_step)[1:]
  levels = np.asarray(batch.level_name)                  # [B]
  t_idx, b_idx = np.nonzero(done)
  return [(int(levels[b]), float(returns[t, b]), int(steps[t, b]))
          for t, b in zip(t_idx, b_idx)]


class EpisodeStats:
  """Accumulates per-level episode returns and periodic DMLab-30 scores.

  Mirrors the reference learner loop (experiment.py ≈L590–620): every
  finished episode logs `<level>/episode_return` and
  `<level>/episode_frames`; in benchmark mode, once EVERY level has at
  least one finished episode, emit the suite's human-normalized
  training scores over the per-level means (`dmlab30/training_no_cap`
  + `dmlab30/training_cap_100`, or `atari57/training_median` +
  `atari57/training_mean`), then reset the accumulator.

  Args:
    level_names: id → name mapping (actors carry int level ids;
      strings never enter trajectories).
    multi_task: legacy alias for benchmark='dmlab30'.
    benchmark: None | 'dmlab30' | 'atari57' — enables the suite
      scoring path (level_names must then be that suite's levels).
  """

  def __init__(self, level_names: List[str], multi_task: bool = False,
               writer: Optional[SummaryWriter] = None,
               benchmark: Optional[str] = None):
    self._level_names = list(level_names)
    if benchmark is None and multi_task:
      benchmark = 'dmlab30'
    if benchmark is not None and benchmark not in suites.SUITES:
      raise ValueError(f'unknown benchmark {benchmark!r} '
                       f'(suites: {sorted(suites.SUITES)})')
    self._multi_task = benchmark is not None
    self._suite = suites.SUITES[benchmark] if benchmark else None
    self._writer = writer
    self._level_returns: Dict[str, List[float]] = {
        name: [] for name in self._level_names}
    self.last_scores: Optional[Dict[str, float]] = None

  def record_batch(self, batch, step: int) -> List[Tuple[str, float, int]]:
    """Extract finished episodes, write summaries, maybe score.

    Returns [(level_name, episode_return, episode_frames)] for logging.
    """
    episodes = []
    for level_id, ep_return, ep_frames in extract_episodes(batch):
      name = self._level_names[level_id]
      episodes.append((name, ep_return, ep_frames))
      if self._multi_task:  # accumulator is only read by _maybe_score
        self._level_returns.setdefault(name, []).append(ep_return)
      if self._writer is not None:
        self._writer.scalar(f'{name}/episode_return', ep_return, step)
        self._writer.scalar(f'{name}/episode_frames', ep_frames, step)
    if self._multi_task:
      self._maybe_score(step)
    return episodes

  def _maybe_score(self, step: int):
    if not all(self._level_returns.get(name)
               for name in self._level_names):
      return
    self.last_scores = self._suite.training_scores(self._level_returns)
    if self._writer is not None:
      self._writer.scalars(self.last_scores, step)
    self._level_returns = {name: [] for name in self._level_names}

"""SLO engine: declarative objectives over the metrics registry,
burn-rate evaluation, and triggered deep diagnostics (round 14).

PR 10 gave the fleet rich sensors — a 47-name metrics registry, trace
spans, policy-lag attribution, a flight recorder — but nothing in the
system *judges* those numbers: every target lived in a human's head or
a chaos script's asserts. This module is the sensor-to-verdict half of
the control loop (ROADMAP item 5; PAL's resource-aware monitoring,
arXiv 2110.01101, and the per-plane accounting Podracer makes
first-class, arXiv 2104.06272):

1. **Declarative objectives** (`Objective`): named targets over
   registry metric names — `policy_lag_p99 <= N`,
   `env_plane_utilization >= x`, `wire_crc_rejected rate == 0`, an fps
   floor against a per-host baseline file — each with a comparison, a
   target, fast/slow evaluation windows, and a severity
   (info < ticket < page). `DEFAULT_OBJECTIVES` ships a set covering
   every plane PRs 1–10 instrumented; `--slo_spec` loads a custom JSON
   set. Metric names are literal strings on purpose: scripts/ci.sh
   lints every objective's metric against the registered-name
   inventory (an objective over a metric nobody registers is a CI
   failure, both directions).

2. **Burn-rate evaluation** (`SloEvaluator`): registry snapshots
   accumulate into a bounded history; each objective is judged over a
   FAST and a SLOW window (multi-window burn-rate alerting — a blip
   must not page, a sustained burn must). Value objectives burn when
   every fast-window sample violates (≥ `min_samples`) AND at least
   half the slow-window samples do; rate objectives (counters) burn on
   the windowed delta/rate. Missing or NaN metrics evaluate as
   `no_data` (present in the verdict, never a violation — a
   `--telemetry_trace=false` run must not page on its own blindness).

3. **Triggered deep diagnostics** (`SloEngine`): on the FIRST burn of
   a severity≥page objective the engine captures its own explanation —
   a flight-recorder dump and a trace_report hop-delta slice over the
   violation window land in `<logdir>/diagnostics/`, and a bounded
   `jax.profiler` capture of the next K learner steps is requested
   from the driver loop (slo.py itself never imports jax). Rate
   limited: ONE capture per objective per run. An SLO page therefore
   ships with the pipeline history that explains it.

4. **The verdict** (`SLO_VERDICT.json`): one per-run artifact —
   overall pass/fail plus per-objective state, value, target, margin,
   and burn count — consumed by scripts/chaos.py (the storms assert
   the SAME objectives production is judged by), scripts/soak.py, and
   scripts/slo_report.py (the CI/chip go-no-go gate, which also diffs
   bench headline numbers against docs/BENCH_HISTORY.md baselines).

Cost is measured, not assumed: the evaluator tick and the
profiler-capture overhead were timed, and the default-ON call is
recorded in docs/PERF.md (r12).

No jax imports here — the engine must be importable by actor hosts,
scripts, and tests without accelerator initialization (the telemetry
module's rule).
"""

import collections
import dataclasses
import json
import math
import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock

# Severity ladder. Only `page` triggers deep diagnostics; `info`
# objectives are recorded in the verdict but never fail it (advisory
# floors an operator tunes per deployment).
SEVERITIES = ('info', 'ticket', 'page')

# Objective states in the verdict.
OK = 'ok'
BURNING = 'burning'
NO_DATA = 'no_data'          # metric absent/NaN over the window
NO_BASELINE = 'no_baseline'  # baseline-relative target, no baseline

_COMPARATORS = {
    '<=': lambda v, t: v <= t,
    '>=': lambda v, t: v >= t,
    '==': lambda v, t: v == t,
}


@dataclasses.dataclass(frozen=True)
class Objective:
  """One declarative objective over a registry metric.

  Args:
    name: the objective's name (verdict key, incident label,
      diagnostics filename stem).
    metric: the registry metric name judged (ci.sh lints it against
      the registered inventory).
    comparison: '<=', '>=' or '==' — value `comparison` target holds
      when healthy.
    target: the threshold. With `baseline` set, a FRACTION of the
      per-host baseline value instead (see `fps_floor`).
    kind: 'value' (judge the sampled values in the windows) or 'rate'
      (judge the windowed counter movement: the per-second rate for
      <=/>=; the raw window delta for '==' — `rate == 0` means "this
      counter must not move").
    field: for histogram metrics, which snapshot field to judge
      ('p50' | 'p99' | 'max' | 'count' | 'sum').
    fast_window_secs / slow_window_secs: the two burn windows. None
      defers to the evaluator's configured defaults.
    severity: 'info' | 'ticket' | 'page'.
    baseline: key into the per-host baseline file; the effective
      target is baseline_value * target. No file/entry → NO_BASELINE.
    description: one line for the verdict/docs.
  """
  name: str
  metric: str
  comparison: str
  target: float
  kind: str = 'value'
  field: Optional[str] = None
  fast_window_secs: Optional[float] = None
  slow_window_secs: Optional[float] = None
  severity: str = 'ticket'
  baseline: Optional[str] = None
  description: str = ''

  def validate(self):
    if self.comparison not in _COMPARATORS:
      raise ValueError(f'objective {self.name!r}: comparison must be '
                       f'one of {sorted(_COMPARATORS)}, got '
                       f'{self.comparison!r}')
    if self.kind not in ('value', 'rate'):
      raise ValueError(f'objective {self.name!r}: kind must be '
                       f'value|rate, got {self.kind!r}')
    if self.severity not in SEVERITIES:
      raise ValueError(f'objective {self.name!r}: severity must be '
                       f'one of {SEVERITIES}, got {self.severity!r}')
    if not self.metric or '/' not in self.metric:
      raise ValueError(f'objective {self.name!r}: metric must be a '
                       f'registry name (component/name), got '
                       f'{self.metric!r}')
    return self


# The shipped default set — one named objective per plane PRs 1–10
# instrumented. Names, metrics, targets, windows and severities are
# all literals: docs/OBSERVABILITY.md carries this table verbatim and
# scripts/ci.sh lints BOTH directions (an objective over an
# unregistered metric, and a documented objective nobody ships).
# Targets are deliberately loose "is the system sane" floors — an
# operator tightens them per deployment via --slo_spec; the point of
# the defaults is that every run is judged by SOMETHING machine-read.
DEFAULT_OBJECTIVES = (
    # Policy-lag plane (PR 10): the publish-count delta V-trace
    # corrects for. The healthy bound is the feed pipeline's depth
    # (buffer + staging + in-flight batches — measured p99 ~5-8 on
    # the per-step publish cadence); p99 past 16 published versions
    # means staleness is OFF the V-trace design point — page, with
    # the trace slice as the explanation.
    Objective(name='policy_lag_p99', metric='trace/policy_lag',
              field='p99', comparison='<=', target=16.0,
              severity='page',
              description='behaviour-vs-train publish-count delta p99'),
    # Unroll end-to-end latency (PR 10 spans): done→step p99.
    Objective(name='unroll_e2e_p99_ms', metric='trace/e2e_ms',
              field='p99', comparison='<=', target=30000.0,
              severity='ticket',
              description='per-unroll done->step span p99 (ms)'),
    # Env plane (PR 5/7 utilization split): the floor detects a DEAD
    # env plane (nothing produced all window), not a backpressured
    # one — a pipeline that consumes at all keeps the ratio above it.
    Objective(name='env_plane_utilization',
              metric='driver/env_plane_utilization',
              comparison='>=', target=0.001, severity='ticket',
              description='producers not parked on backpressure'),
    # Actor plane (PR 6): the quorum fraction the fleet feeds with.
    Objective(name='fleet_healthy_fraction',
              metric='driver/fleet_healthy_fraction',
              comparison='>=', target=0.25, severity='page',
              description='healthy actor slots / fleet size'),
    # Throughput floor vs the per-host baseline file (the north-star
    # number, judged against what THIS host has shown it can do).
    Objective(name='fps_floor', metric='driver/env_frames',
              kind='rate', comparison='>=', target=0.5,
              baseline='fps', severity='ticket',
              description='env frames/sec >= 0.5x per-host baseline'),
    # Data-plane integrity (PR 9): any movement is an incident.
    Objective(name='wire_crc_rejected_zero',
              metric='ingest/wire_crc_rejected',
              kind='rate', comparison='==', target=0.0,
              severity='page',
              description='unroll frames refused for CRC mismatch'),
    Objective(name='sdc_mismatch_zero', metric='health/sdc_mismatches',
              kind='rate', comparison='==', target=0.0,
              severity='page',
              description='per-replica param fingerprint disagreements'),
    Objective(name='ckpt_digest_fallbacks_zero',
              metric='checkpoint/digest_fallbacks',
              kind='rate', comparison='==', target=0.0,
              severity='ticket',
              description='restore rungs refused for content digests'),
    # Transport plane (PR 8): quarantines/reaps/stale epochs flat at
    # zero on a healthy fleet.
    Objective(name='ingest_quarantine_zero', metric='ingest/quarantined',
              kind='rate', comparison='==', target=0.0,
              severity='ticket',
              description='connections dropped for unparseable frames'),
    Objective(name='conns_reaped_zero', metric='ingest/conns_reaped',
              kind='rate', comparison='==', target=0.0,
              severity='ticket',
              description='idle/half-open connections reaped'),
    Objective(name='stale_epoch_zero',
              metric='ingest/stale_epoch_rejected',
              kind='rate', comparison='==', target=0.0,
              severity='ticket',
              description='unrolls refused from a dead incarnation'),
    # Learner failure domain (PR 2): a rollback is the ladder working,
    # and still an incident someone should read.
    Objective(name='rollbacks_zero', metric='health/rollbacks',
              kind='rate', comparison='==', target=0.0,
              severity='ticket',
              description='automatic checkpoint rollbacks'),
    # Plane-balance leading indicator (round 15, controller.py): the
    # learner mostly parked on the feed = the env plane is the bound —
    # the controller's raise-replay_k trigger (IMPACT,
    # arXiv 1912.00167). Advisory: env-bound is a CAPACITY shape, not
    # an incident, so burning this must never fail a verdict.
    Objective(name='learner_plane_utilization',
              metric='driver/learner_plane_utilization',
              comparison='>=', target=0.05, severity='info',
              description='learner not starved by the env plane'),
    # Filler-aware variant (round 16, the hybrid filler /
    # --runtime=anakin): with the filler ON — or under the fused
    # anakin runtime — the learner plane is lifted to ~1.0 BY
    # CONSTRUCTION (idle feed slices run Anakin self-play), so this
    # stricter floor burning on such a run means the filler itself is
    # failing to fill. On a plain env-bound fleet run it burns
    # benignly (info can never fail a verdict) — that burn IS the
    # capacity-headroom signal the filler knob exists for. Filler
    # frames must NOT mask a dead env plane: env_plane_utilization
    # above stays the dead-plane signal either way
    # (config.validate_runtime cross-links the knobs).
    Objective(name='learner_plane_utilization_filler',
              metric='driver/learner_plane_utilization',
              comparison='>=', target=0.9, severity='info',
              description='hybrid filler keeps the learner plane '
                          '~fully busy'),
    # Transport-pressure leading indicator (round 15, controller.py):
    # ack service time is the end-to-end backpressure remote pumps
    # feel — the controller's stretch-publish-cadence trigger.
    Objective(name='ingest_ack_p99_ms', metric='ingest/ack_ms',
              field='p99', comparison='<=', target=5000.0,
              severity='info',
              description='ingest ack service time p99 (ms)'),
    # Telemetry self-health (PR 10 satellites): advisory only.
    Objective(name='dropped_writes_zero',
              metric='observability/dropped_writes',
              kind='rate', comparison='==', target=0.0,
              severity='info',
              description='JSONL writes dropped after close'),
    Objective(name='trace_drops_zero', metric='trace/dropped_records',
              kind='rate', comparison='==', target=0.0,
              severity='info',
              description='tracer FIFO overflows'),
    # Serving plane (round 21, multi-tenant serving): end-to-end
    # service latency of the shared inference step — every decoupled-
    # serving client (local C++ batcher callers AND v10 routed
    # cross-host batches) lands in this histogram. Burning past the
    # target is overload the admission actuator can shed (the routed
    # chaos storm asserts this objective stays green through a
    # replica kill).
    Objective(name='serving_latency_p99_ms', metric='serving/latency_ms',
              field='p99', comparison='<=', target=30000.0,
              severity='ticket',
              description='inference serve latency p99 (ms), local '
                          'and routed'),
    # Population plane (round 22, driver.train_population): the WORST
    # suite's best member return — a population whose laggard suite
    # never crosses zero is spending its frame budget on one task.
    # The gauge only exists inside a PBT run (registered after the
    # first scoring round); every other run evaluates no_data, which
    # never violates. Advisory: return scales are task-relative, so a
    # default floor can only be the "learning at all" zero line.
    Objective(name='per_task_return_floor',
              metric='population/task_return_min',
              comparison='>=', target=0.0, severity='info',
              description='worst suite best-member return >= 0'),
)


def load_objectives(spec_path: str = '',
                    fast_window_secs: float = 30.0,
                    slow_window_secs: float = 300.0
                    ) -> List[Objective]:
  """The objective set: `spec_path` (a JSON list of Objective field
  dicts) when given, else the shipped defaults — either way with the
  configured windows filled in wherever an entry didn't pin its own.
  Raises on an unreadable/invalid spec (a typo'd objective must fail
  the run at spin-up, not silently judge nothing)."""
  if spec_path:
    with open(spec_path) as f:
      raw = json.load(f)
    if not isinstance(raw, list) or not raw:
      raise ValueError(f'SLO spec {spec_path!r} must be a non-empty '
                       'JSON list of objective dicts')
    objectives = []
    for entry in raw:
      try:
        objectives.append(Objective(**entry))
      except TypeError as e:
        raise ValueError(f'SLO spec {spec_path!r}: bad objective '
                         f'entry {entry!r}: {e}') from e
  else:
    objectives = list(DEFAULT_OBJECTIVES)
  seen = set()
  resolved = []
  for o in objectives:
    o.validate()
    if o.name in seen:
      raise ValueError(f'duplicate SLO objective name {o.name!r}')
    seen.add(o.name)
    resolved.append(dataclasses.replace(
        o,
        fast_window_secs=(o.fast_window_secs
                          if o.fast_window_secs is not None
                          else fast_window_secs),
        slow_window_secs=(o.slow_window_secs
                          if o.slow_window_secs is not None
                          else slow_window_secs)))
  return resolved


# --------------------------------------------------------------------
# Per-host fps baseline file.
# --------------------------------------------------------------------


def load_baseline(path: str, host: Optional[str] = None) -> Dict:
  """The per-host baseline entry ({'fps': ...}) from a JSON file
  keyed by hostname. An ABSENT file (or entry) is {} — a host that
  never recorded a baseline evaluates its baseline-relative
  objectives as NO_BASELINE, never as a violation. A PRESENT but
  unreadable/corrupt file raises: the operator set a floor and a
  typo must not silently disarm it (the --slo_spec fail-fast rule)."""
  if not path:
    return {}
  host = host or socket.gethostname()
  try:
    with open(path) as f:
      table = json.load(f)
  except FileNotFoundError:
    return {}
  except (OSError, ValueError) as e:
    raise ValueError(
        f'SLO fps baseline file {path!r} exists but is unreadable '
        f'({e}) — fix or remove it; a corrupt baseline must not '
        'silently disarm the fps_floor objective') from e
  entry = table.get(host)
  return dict(entry) if isinstance(entry, dict) else {}


def update_baseline(path: str, values: Dict,
                    host: Optional[str] = None) -> str:
  """Merge `values` (e.g. {'fps': measured}) into the per-host entry
  (atomic tmp+rename). scripts/slo_report.py --update-fps-baseline
  uses this to record a known-good run as the floor future runs are
  judged against."""
  host = host or socket.gethostname()
  try:
    with open(path) as f:
      table = json.load(f)
  except (OSError, ValueError):
    table = {}
  entry = table.setdefault(host, {})
  entry.update(values)
  entry['wall_time'] = round(time.time(), 3)
  tmp = path + '.tmp'
  with open(tmp, 'w') as f:
    json.dump(table, f, indent=2, sort_keys=True)
  os.replace(tmp, path)
  return path


# --------------------------------------------------------------------
# Evaluation.
# --------------------------------------------------------------------


def _metric_value(snapshot: Dict, objective: Objective):
  """The judged scalar from one registry snapshot, or None when the
  metric (or its histogram field) is absent/NaN."""
  raw = snapshot.get(objective.metric)
  if raw is None:
    return None
  if isinstance(raw, dict):
    raw = raw.get(objective.field or 'p99')
  if raw is None:
    return None
  try:
    value = float(raw)
  except (TypeError, ValueError):
    return None
  if math.isnan(value):
    return None
  return value


class SloEvaluator:
  """Windowed burn-rate evaluation of a set of objectives against a
  history of registry snapshots.

  `observe(snapshot, now)` appends one sample and re-judges every
  objective; the per-objective result dicts carry
  {state, value, target, margin, burns, ...}. Burn semantics:

  - value objectives: burning when the fast window holds >=
    `min_samples` valid samples, ALL of them violate, and >= half the
    slow-window samples violate (multi-window: a single bad sample
    cannot page; a sustained burn cannot hide).
  - rate objectives: the counter's movement over each window — the
    per-second rate for <=/>= comparisons, the raw delta for '=='
    (== 0 means "this counter must not move"). Monotone counters make
    the slow window confirmation automatic.

  `burns` counts burn EPISODES (entering the burning state), so the
  verdict distinguishes "violated once, recovered" from "never
  violated"; an objective with burns > 0 fails the verdict at
  ticket/page severity.
  """

  def __init__(self, objectives: List[Objective],
               min_samples: int = 3,
               baseline: Optional[Dict] = None):
    self._objectives = list(objectives)
    self._min_samples = max(int(min_samples), 2)
    self._baseline = dict(baseline or {})
    horizon = max([o.slow_window_secs or 300.0
                   for o in self._objectives] or [300.0])
    self._horizon = horizon * 1.25
    self._samples = collections.deque()   # (t, snapshot)
    self._state: Dict[str, Dict] = {
        o.name: {'name': o.name, 'metric': o.metric,
                 'comparison': o.comparison, 'kind': o.kind,
                 'severity': o.severity, 'state': NO_DATA,
                 'value': None, 'target': o.target, 'margin': None,
                 'burns': 0, 'last_burn_wall_time': None,
                 'description': o.description}
        for o in self._objectives}

  @property
  def objectives(self) -> List[Objective]:
    return list(self._objectives)

  def _resolved_target(self, o: Objective) -> Optional[float]:
    if o.baseline is None:
      return o.target
    base = self._baseline.get(o.baseline)
    if base is None:
      return None
    return float(base) * o.target

  def _window(self, now: float, secs: float):
    cutoff = now - secs
    return [(t, snap) for t, snap in self._samples if t >= cutoff]

  def _judge_value(self, o: Objective, now: float, target: float):
    holds = _COMPARATORS[o.comparison]
    fast = [(t, v) for t, snap in self._window(now, o.fast_window_secs)
            if (v := _metric_value(snap, o)) is not None]
    if not fast:
      return NO_DATA, None
    value = fast[-1][1]
    if len(fast) < self._min_samples:
      return OK, value
    if any(holds(v, target) for _, v in fast):
      # At least one fast-window sample is healthy: not burning.
      return OK, value
    slow = [v for t, snap in self._window(now, o.slow_window_secs)
            if (v := _metric_value(snap, o)) is not None]
    bad = sum(1 for v in slow if not holds(v, target))
    if slow and bad >= max(len(slow) / 2.0, 1):
      return BURNING, value
    return OK, value

  def _rate_over(self, o: Objective, now: float, secs: float):
    """(window delta, per-second rate) of a counter metric over the
    trailing `secs`, or (None, None) below two valid samples."""
    samples = [(t, v) for t, snap in self._window(now, secs)
               if (v := _metric_value(snap, o)) is not None]
    if len(samples) < 2:
      return None, None
    (t0, v0), (t1, v1) = samples[0], samples[-1]
    dt = t1 - t0
    if dt <= 0:
      return None, None
    return v1 - v0, (v1 - v0) / dt

  def _judge_rate(self, o: Objective, now: float, target: float):
    delta, rate = self._rate_over(o, now, o.fast_window_secs)
    if delta is None:
      return NO_DATA, None
    if o.comparison == '==':
      # "rate == 0": the counter must not move inside the fast window.
      # Monotone counters need no slow-window confirmation — a
      # fast-window bump IS a slow-window bump.
      return (OK if delta == target else BURNING), delta
    if _COMPARATORS[o.comparison](rate, target):
      return OK, rate
    # Multi-window confirmation for <=/>= rate objectives (the fps
    # floor shape): one fast-window stall — a checkpoint save, a
    # transient ingest hiccup — must not fail the run; the SLOW
    # window's rate must agree the bound is broken.
    _, slow_rate = self._rate_over(o, now, o.slow_window_secs)
    if slow_rate is None or _COMPARATORS[o.comparison](slow_rate,
                                                      target):
      return OK, rate
    return BURNING, rate

  def observe(self, snapshot: Dict,
              now: Optional[float] = None) -> List[str]:
    """Append one snapshot; re-judge everything. Returns the names of
    objectives that ENTERED the burning state on this observation."""
    now = time.time() if now is None else float(now)
    self._samples.append((now, snapshot))
    while self._samples and self._samples[0][0] < now - self._horizon:
      self._samples.popleft()
    newly = []
    for o in self._objectives:
      entry = self._state[o.name]
      target = self._resolved_target(o)
      if target is None:
        entry.update(state=NO_BASELINE, value=None, margin=None)
        continue
      entry['target'] = target
      if o.kind == 'rate':
        state, value = self._judge_rate(o, now, target)
      else:
        state, value = self._judge_value(o, now, target)
      margin = None
      if value is not None:
        # Signed headroom: positive = inside the objective.
        if o.comparison == '<=':
          margin = target - value
        elif o.comparison == '>=':
          margin = value - target
        else:
          margin = -abs(value - target)
      was_burning = entry['state'] == BURNING
      entry.update(state=state, value=value, margin=margin)
      if state == BURNING and not was_burning:
        entry['burns'] += 1
        entry['last_burn_wall_time'] = round(now, 3)
        newly.append(o.name)
    return newly

  def burning(self) -> List[str]:
    return [n for n, e in self._state.items()
            if e['state'] == BURNING]

  def states(self) -> Dict[str, Dict]:
    """Deep-copied per-objective judged state ({name: {state, value,
    target, margin, severity, burns, ...}}). Each entry's fields were
    written by ONE `entry.update(...)` call, so a copy is internally
    consistent; callers needing consistency ACROSS objectives must
    hold the owning engine's lock (SloEngine.control_snapshot does)."""
    return {n: dict(e) for n, e in self._state.items()}

  def verdict(self) -> Dict:
    """The per-run verdict: overall pass/fail + every objective's
    final state and burn count. `pass` fails on any ticket/page
    objective that EVER burned; info objectives are advisory."""
    violations = sorted(
        n for n, e in self._state.items()
        if e['burns'] > 0 and e['severity'] in ('ticket', 'page'))
    return {
        'pass': not violations,
        'violations': violations,
        'wall_time': round(time.time(), 3),
        'objectives': {n: dict(e) for n, e in self._state.items()},
    }


# --------------------------------------------------------------------
# The engine: thread + emission + triggered deep diagnostics.
# --------------------------------------------------------------------


class SloEngine:
  """The driver-resident judge: snapshots the registry on a cadence
  (its own thread, PLUS `observe()` calls from the driver's summary
  block so detection is step-synchronous when summaries are frequent),
  emits structured violations into summaries.jsonl + incidents.jsonl
  (+ health.note_external — the external-incident ledger carries SLO
  burns into drain manifests and halt bundles), and on the first
  severity-page burn captures the run's own explanation into
  `<logdir>/diagnostics/`:

    slo_flight_<objective>.json   the flight-recorder dump
    slo_trace_<objective>.json    trace_report hop-delta slice over
                                  the violation window
    slo_profile_<objective>/      a bounded jax.profiler capture of
                                  the next K learner steps (requested
                                  via `take_profile_request` — the
                                  driver loop owns the profiler)

  One capture per objective per run; `finalize()` writes
  SLO_VERDICT.json (atomic) and returns the verdict."""

  # Lock discipline (round 18, guarded-by lint): the evaluator state,
  # the capture rate-limit table, and both work queues mutate only
  # under _lock — observe() runs from TWO threads (engine tick + the
  # driver's summary block), so a bare deque append here is exactly
  # the torn-coordination shape the round-15 snapshot-consistency
  # test exists for.
  _captures: guarded_by('_lock')
  _profile_queue: guarded_by('_lock')
  _capture_queue: guarded_by('_lock')

  def __init__(self, objectives: List[Objective], logdir: str,
               registry: Optional[telemetry.MetricsRegistry] = None,
               writer=None, incidents=None, flight=None, health=None,
               capture: bool = True, interval_secs: float = 5.0,
               baseline: Optional[Dict] = None,
               min_samples: int = 3,
               trace_slice_fn: Optional[Callable] = None):
    self._evaluator = SloEvaluator(objectives,
                                   min_samples=min_samples,
                                   baseline=baseline)
    self._logdir = logdir
    self._registry = registry or telemetry.registry()
    self._writer = writer
    self._incidents = incidents
    self._flight = flight
    self._health = health
    self._capture = bool(capture)
    self._interval = max(float(interval_secs), 0.25)
    self._trace_slice_fn = trace_slice_fn or _trace_slice
    self._lock = make_lock('slo.SloEngine._lock')
    self._stop = threading.Event()
    self._thread: Optional[threading.Thread] = None
    self._captures: Dict[str, Dict] = {}
    self._profile_queue: collections.deque = collections.deque()
    # Captures pending their artifact writes: (name, capture, state)
    # queued by whoever's observe() detects the burn, DRAINED on the
    # engine thread (flush_captures) — the driver's summary-block
    # observe must never pay the flight-dump + whole-trace-stream
    # slice inline on the training loop.
    self._capture_queue: collections.deque = collections.deque()
    # Registry view of the judge itself (unregistered at stop — the
    # fn-gauge closes over this per-run engine).
    self._m_violations = telemetry.counter('slo/violations')
    self._g_burning = telemetry.gauge(
        'slo/burning', fn=lambda: len(self._evaluator.burning()))

  # --- lifecycle ---

  def start(self):
    self.observe()  # t0 sample: rate objectives span the whole run
    self._thread = threading.Thread(target=self._loop,
                                    name='slo-engine', daemon=True)
    self._thread.start()

  def _loop(self):
    while not self._stop.wait(self._interval):
      try:
        self.observe()
      except Exception:  # pragma: no cover - must never kill the run
        import logging
        logging.getLogger('scalable_agent_tpu').exception(
            'SLO evaluator tick failed')
      self.flush_captures()

  def stop(self):
    self._stop.set()
    if self._thread is not None:
      self._thread.join(timeout=5.0)
      self._thread = None
    telemetry.registry().unregister(self._g_burning.name,
                                    self._g_burning)

  # --- evaluation + emission ---

  def observe(self, now: Optional[float] = None) -> List[str]:
    """One evaluation pass (thread-safe; the engine thread and the
    driver's summary block both call this). Returns newly-burning
    objective names.

    Only the evaluator-state mutation runs under the lock. The
    emission (incident/summary writes) happens after release, fully
    exception-guarded — a disk-full at the moment of a burn must not
    kill the thread that called observe (which may be the TRAINING
    loop's summary block). The heavy capture artifacts (flight dump +
    a trace_report pass over the whole traces.jsonl — seconds on a
    long run) are only QUEUED here; the engine thread (and finalize)
    drains them via flush_captures. The per-objective rate limit is
    enforced under the lock (the captures entry is reserved before
    release)."""
    snapshot = self._registry.snapshot()
    with self._lock:
      newly = self._evaluator.observe(snapshot, now=now)
      if not newly:
        return newly
      states = {name: dict(self._evaluator._state[name])
                for name in newly}
      for name in newly:
        if (self._capture and states[name]['severity'] == 'page'
            and name not in self._captures):
          capture: Dict = {
              'objective': name, 'wall_time': round(time.time(), 3),
              'flight': None, 'trace_slice': None, 'profile': None}
          self._captures[name] = capture
          self._capture_queue.append((name, capture, states[name]))
    try:
      step = int(snapshot.get('driver/update_steps') or 0)
      for name in newly:
        state = states[name]
        self._m_violations.inc()
        if self._incidents is not None:
          self._incidents.event(
              'slo_violation', step=step, objective=name,
              severity=state['severity'], metric=state['metric'],
              value=state['value'], target=state['target'],
              margin=state['margin'], burns=state['burns'])
        if self._health is not None:
          self._health.note_external(f'slo_{name}')
      if self._writer is not None:
        self._writer.scalar('slo_violations',
                            self._m_violations.value, step)
    except Exception:  # best-effort: judging survives a sick disk
      import logging
      logging.getLogger('scalable_agent_tpu').exception(
          'SLO violation emission failed')
    return newly

  # --- the control surface (round 15, controller.py) ---

  def burning(self) -> List[str]:
    """The currently-burning objective names, read under the engine
    lock (stable against a concurrent observe() — the controller
    thread's read API)."""
    with self._lock:
      return self._evaluator.burning()

  def control_snapshot(self) -> Dict[str, Dict]:
    """A locked, self-consistent copy of every objective's judged
    state ({name: {state, value, target, margin, severity, burns,
    ...}}) — the round-15 controller's control input. The lock
    guarantees the copy describes ONE evaluation pass: two objectives
    over the same metric can never disagree about its value inside a
    single snapshot (regression-pinned by
    tests/test_slo.py::test_control_snapshot_consistent_mid_evaluation).
    """
    with self._lock:
      return self._evaluator.states()

  def flush_captures(self):
    """Write queued capture artifacts (engine thread per tick;
    finalize as the backstop for burns detected after the last tick).
    Each capture is independently best-effort."""
    while True:
      # Round 18 (guarded-by lint): the queue is appended to under
      # the lock by whichever thread's observe() detects the burn —
      # the drain must pop under the same lock, not rely on deque
      # GIL-atomicity.
      with self._lock:
        if not self._capture_queue:
          return
        name, capture, state = self._capture_queue.popleft()
      try:
        self._write_capture_artifacts(name, capture, state)
      except Exception:  # the contract: never take down the run
        import logging
        logging.getLogger('scalable_agent_tpu').exception(
            'SLO capture artifacts for %r failed', name)

  # --- triggered deep diagnostics ---

  def _write_capture_artifacts(self, name: str, capture: Dict,
                               state: Dict):
    """First page-severity burn of `name` (entry already reserved
    under the lock): dump the flight recorder, slice the trace stream
    over the violation window, and queue a profiler capture for the
    driver loop. Runs on the ENGINE thread (flush_captures), outside
    the lock; every artifact is independently best-effort — a sick
    disk at page time must cost artifacts, never the run (and never
    the profiler request, which needs no disk until jax writes)."""
    out_dir = os.path.join(self._logdir, 'diagnostics')
    try:
      os.makedirs(out_dir, exist_ok=True)
    except OSError:
      out_dir = None
    if out_dir is not None and self._flight is not None:
      try:
        capture['flight'] = self._flight.write(
            os.path.join(out_dir, f'slo_flight_{name}.json'))
      except Exception:
        pass
    if out_dir is not None:
      try:
        objective = next(o for o in self._evaluator.objectives
                         if o.name == name)
        window_secs = objective.slow_window_secs or 300.0
        slice_path = os.path.join(out_dir, f'slo_trace_{name}.json')
        if self._trace_slice_fn(self._logdir, window_secs, slice_path,
                                state):
          capture['trace_slice'] = slice_path
      except Exception:
        pass
    # Round 18 (guarded-by lint): the driver loop pops this queue
    # under the lock; the engine-thread append holds it too.
    with self._lock:
      self._profile_queue.append(name)
    if self._incidents is not None:
      try:
        self._incidents.event('slo_capture', objective=name,
                              flight=capture['flight'],
                              trace_slice=capture['trace_slice'])
      except Exception:
        pass

  def take_profile_request(self) -> Optional[str]:
    """Pop the next queued profiler capture (driver loop; None when
    idle). The driver owns jax.profiler — it starts a bounded trace
    into diagnostics/slo_profile_<name>/ and reports back via
    `note_profile`."""
    with self._lock:
      return self._profile_queue.popleft() if self._profile_queue \
          else None

  def note_profile(self, name: str, path: Optional[str]):
    with self._lock:
      if name in self._captures:
        self._captures[name]['profile'] = path

  # --- the verdict ---

  def verdict(self, extra: Optional[Dict] = None) -> Dict:
    with self._lock:
      out = self._evaluator.verdict()
      out['captures'] = {n: dict(c) for n, c in self._captures.items()}
    if extra:
      out.update(extra)
    return out

  def finalize(self, path: Optional[str] = None,
               extra: Optional[Dict] = None) -> Dict:
    """Final observation + atomic SLO_VERDICT.json write. Returns the
    verdict dict (chaos/soak/slo_report read the file). Drains any
    capture still queued (a burn detected after the engine thread's
    last tick must not lose its artifacts)."""
    try:
      self.observe()
    except Exception:
      pass
    self.flush_captures()
    verdict = self.verdict(extra=extra)
    if path is None:
      path = os.path.join(self._logdir, 'SLO_VERDICT.json')
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump(verdict, f, indent=2, sort_keys=True, default=str)
    os.replace(tmp, path)
    return verdict


def _trace_slice(logdir: str, window_secs: float, out_path: str,
                 state: Dict) -> bool:
  """The violation-window hop-delta slice: trace_report.summarize over
  the records inside [burn - slow_window, now], written as JSON next
  to the other capture artifacts. Lazy script import (operator installs
  without the scripts/ tree skip the slice, never crash)."""
  try:
    from scripts import trace_report
  except ImportError:
    return False
  now = time.time()
  records = [r for r in trace_report.load_traces(logdir)
             if r.get('t') is None or r['t'] >= now - window_secs]
  summary = trace_report.summarize(records)
  summary['slo_objective'] = dict(state)
  summary['window_secs'] = window_secs
  tmp = out_path + '.tmp'
  with open(tmp, 'w') as f:
    json.dump(summary, f, indent=2, default=str)
  os.replace(tmp, out_path)
  return True


def read_verdict(logdir: str) -> Optional[Dict]:
  """The run's SLO_VERDICT.json, or None (consumed by chaos/soak/
  slo_report)."""
  try:
    with open(os.path.join(logdir, 'SLO_VERDICT.json')) as f:
      return json.load(f)
  except (OSError, ValueError):
    return None

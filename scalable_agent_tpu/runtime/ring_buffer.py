"""Trajectory transport: bounded unroll buffer + device prefetch.

Replaces the reference's learner-hosted `tf.FIFOQueue(capacity=1)` +
`StagingArea` double-buffer (reference: experiment.py ≈L470, ≈L540–560;
SURVEY §2.b "async pipeline"):

- `TrajectoryBuffer`: a bounded ring of completed unrolls. Producers
  (actor threads) block when full — capacity IS the backpressure that
  bounds policy lag, exactly the reference's capacity-1 queue semantics
  (lag ≤ capacity + in-flight unroll + staged batch).
- `BatchPrefetcher`: one thread that assembles [T+1, B] batches and
  stages the next `depth` device batches while the learner trains on
  the current one (the StagingArea role, default depth 2 —
  config.staging_depth). `place_fn` is where `jax.device_put` with
  data-axis shardings happens, so staging overlaps host→HBM transfer
  with TPU compute; with depth >= 2 consecutive transfers also
  overlap each other (the r5 fed bench measured H2D as the dominant
  feed-gap term).
- `UnrollBatchStager` (round 8, config.staging_mode='unroll'): the
  device-resident alternative to the host stack + one-burst
  `device_put`. Each completed unroll is `device_put` the moment it
  leaves the buffer — placed directly on the device owning its batch
  slot — and the [T+1, B] batch is assembled ON DEVICE by a jitted,
  donated `dynamic_update_slice` arena, so the step-boundary H2D
  burst (BENCH_r05: h2d_ms 1430.5 on a 67.5 MB batch) becomes a
  per-unroll trickle overlapped with the previous step's compute, and
  the host-side `batch_unrolls` stack (stack_ms 37.5) leaves the hot
  path entirely. Golden parity: `dynamic_update_slice` of the same
  values is bit-identical to the host-stack + transfer path
  (tests/test_learner_plane.py).

Episode stats ride inside the trajectories (StepOutputInfo), so there
is no side channel to drain — consume them from the dequeued batch
like the reference's learner loop does (≈L590–620).

Round 10 adds the sample-reuse tier (IMPACT, arXiv 1912.00167;
docs/PERF.md r9): `ReplayTier` is a circular arena of already-consumed
unrolls sitting BEHIND the TrajectoryBuffer — `get_unrolls` composes
each batch fresh:replayed per the replay ratio — and the
`BatchPrefetcher` re-serves every staged device batch `replay_k` times
before release (the staged arena is handed out AS IS: no re-stage, no
additional H2D), multiplying learner updates per env frame while the
actor/env plane stays the rate limiter it measures as.
"""

import collections
import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from scalable_agent_tpu import integrity
from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock
from scalable_agent_tpu.runtime.actor import batch_unrolls
from scalable_agent_tpu.structs import ActorOutput

log = logging.getLogger('scalable_agent_tpu')


class Closed(Exception):
  """The buffer was closed while blocking."""


class ReplayTier:
  """Circular replay arena of completed unrolls (round 10 — IMPACT's
  circular buffer, host tier).

  Consumed unrolls are retained (by reference — they are immutable
  host numpy once the actor enqueued them) with the param version
  current at retention time. `sample(n)` hands out up to n unrolls via
  a circular read cursor (IMPACT reads its buffer sequentially, not
  uniformly — recent data recurs at a bounded cadence), evicting
  entries that aged past the staleness window in passing. Eviction is
  two-fold and separately counted:

  - by AGE: the ring is full and a new unroll overwrites the oldest
    (`evictions_age`) — capacity IS the age bound;
  - by VERSION: an entry's retention-time param version has fallen
    more than `max_staleness` PUBLISHED VERSIONS behind the current
    one (`evictions_version`). The unit is the same param-version
    delta `--max_unroll_staleness` uses for ingest admission (the
    round-10 unification); 0 = no version bound.
  - by CONTENT (round 12, `verify_crc`): each entry keeps the CRC of
    its bytes at INSERT time and is re-verified at every serve — a
    retained unroll sitting in host memory for thousands of serves is
    exactly where silent RAM rot would otherwise be multiplied into
    the batch mix K times over. A mismatch evicts instead of serving
    (`evictions_crc`), the host-tier sibling of the wire CRC and the
    checkpoint digest ladder.

  Thread-safe (own lock; never calls back into the buffer).
  """

  # Lock discipline (round 18, guarded-by lint). The public eviction/
  # reuse counters stay unannotated on purpose: their fn-gauge reads
  # are lock-free by design (torn-read-benign ints, documented below).
  _entries: guarded_by('_lock')
  _cursor: guarded_by('_lock')
  _version: guarded_by('_lock')
  _staleness_sum: guarded_by('_lock')
  _staleness_samples: guarded_by('_lock')
  _last_sample: guarded_by('_lock')

  def __init__(self, capacity_unrolls: int, max_staleness: int = 0,
               verify_crc: bool = True):
    if capacity_unrolls < 1:
      raise ValueError('replay capacity must be >= 1')
    self._capacity = capacity_unrolls
    self._max_staleness = max_staleness
    self._verify_crc = bool(verify_crc)
    self._entries = collections.deque()  # (unroll, version, crc|None)
    self._cursor = 0
    self._lock = make_lock('ring_buffer.ReplayTier._lock')
    self._version = 0
    # Telemetry (summary surface via TrajectoryBuffer.stats()).
    self.evictions_age = 0
    self.evictions_version = 0
    self.evictions_crc = 0
    self.reused_unrolls = 0
    self._staleness_sum = 0
    self._staleness_samples = 0
    self._last_sample = (0, 0)  # (count, staleness_sum) — unsample_last
    # Unified-registry view (round 13): lazy gauges over the counters
    # above — the module-local bookkeeping stays authoritative (and
    # lock-guarded for mutation); the registry reads it. Lock-free
    # reads of ints are torn-read-benign. Handles kept so the owning
    # buffer's close() can unregister them (fn-gauges close over
    # `self` — an unregistered gauge is what lets a finished run's
    # tier be collected).
    self._gauges = [
        telemetry.gauge('replay/occupancy',
                        fn=lambda: len(self._entries)),
        telemetry.gauge('replay/evictions_age',
                        fn=lambda: self.evictions_age),
        telemetry.gauge('replay/evictions_version',
                        fn=lambda: self.evictions_version),
        telemetry.gauge('replay/evictions_crc',
                        fn=lambda: self.evictions_crc),
        telemetry.gauge('replay/reused_unrolls',
                        fn=lambda: self.reused_unrolls),
    ]

  def note_param_version(self, version: int):
    """Advance the current published param version (driver publish
    cadence) — the clock both staleness accounting and version
    eviction read."""
    with self._lock:
      self._version = max(self._version, int(version))

  def add(self, unroll: ActorOutput):
    # Insert-time content CRC, computed OUTSIDE the lock (one pass
    # over the unroll's bytes — ~0.1 ms/MB; the serve-side verify is
    # what catches rot accumulated while retained).
    crc = integrity.tree_digest(unroll) if self._verify_crc else None
    with self._lock:
      if len(self._entries) >= self._capacity:
        self._entries.popleft()
        self.evictions_age += 1
        if self._cursor > 0:
          self._cursor -= 1  # keep the cursor on the same entry
      self._entries.append((unroll, self._version, crc))

  def sample(self, n: int) -> List[ActorOutput]:
    """Up to `n` unrolls from the circular cursor (fewer when the
    tier is short, or when version/CRC eviction thins the pick). Each
    DELIVERED serve counts toward `reused_unrolls` and the
    mean-staleness accumulator.

    The serve-time CRC verification (a full pass over each multi-MB
    unroll) runs OUTSIDE the lock — holding it would stall every
    producer's `add()` behind milliseconds of hashing on the learner
    feed path (the same reason the insert-side CRC sits outside).
    Rotted entries found in the verify phase are evicted by IDENTITY
    on re-acquire (never by ==: tuples of numpy arrays don't
    compare), with the cursor adjusted; a rotted pick shrinks this
    call's batch instead of rescanning — the next call refills."""
    picked: List[Tuple] = []  # (entry, staleness), CRC pending
    with self._lock:
      budget = len(self._entries)  # at most one full lap per call
      while len(picked) < n and self._entries and budget > 0:
        budget -= 1
        if self._cursor >= len(self._entries):
          self._cursor = 0
        entry = self._entries[self._cursor]
        staleness = self._version - entry[1]
        if self._max_staleness and staleness > self._max_staleness:
          del self._entries[self._cursor]
          self.evictions_version += 1
          continue
        picked.append((entry, staleness))
        self._cursor += 1
    verified: List[Tuple] = []
    rotten: List[Tuple] = []
    for entry, staleness in picked:
      unroll, _, crc = entry
      if crc is not None and integrity.tree_digest(unroll) != crc:
        # Host-memory rot since insert: reuse must NEVER serve it
        # (replay would multiply the corruption into K batches).
        rotten.append(entry)
      else:
        verified.append((entry, staleness))
    with self._lock:
      for entry in rotten:
        for idx, cand in enumerate(self._entries):
          if cand is entry:
            del self._entries[idx]
            if idx < self._cursor:
              self._cursor -= 1
            self.evictions_crc += 1
            break
      sample_staleness = 0
      for _, staleness in verified:
        self.reused_unrolls += 1
        self._staleness_sum += staleness
        self._staleness_samples += 1
        sample_staleness += staleness
      self._last_sample = (len(verified), sample_staleness)
    return [entry[0] for entry, _ in verified]

  def unsample_last(self):
    """Undo the ACCOUNTING of the most recent sample() — the caller
    failed to deliver its batch (fresh-side timeout/close push-back in
    get_unrolls): the cursor steps back so the sequential scan
    re-serves the same entries next call, and the reuse/staleness
    counters forget them. Version evictions stand (the entries really
    were too stale). One outstanding sample at a time — the
    single-consumer prefetcher pattern; a repeated call is a no-op."""
    with self._lock:
      n, staleness_sum = self._last_sample
      self._last_sample = (0, 0)
      if n == 0:
        return
      if self._entries:
        self._cursor = (self._cursor - n) % len(self._entries)
      self.reused_unrolls -= n
      self._staleness_sum -= staleness_sum
      self._staleness_samples -= n

  def __len__(self):
    with self._lock:
      return len(self._entries)

  def stats(self):
    with self._lock:
      mean_staleness = (self._staleness_sum / self._staleness_samples
                        if self._staleness_samples else 0.0)
      return {
          'replay_occupancy': len(self._entries),
          'replay_capacity': self._capacity,
          'replay_evictions_age': self.evictions_age,
          'replay_evictions_version': self.evictions_version,
          'replay_evictions_crc': self.evictions_crc,
          'replay_reused_unrolls': self.reused_unrolls,
          'replay_mean_staleness': round(mean_staleness, 3),
      }


def _wait_until(cond: threading.Condition, predicate: Callable[[], bool],
                deadline: Optional[float], what: str):
  """Wait on `cond` (held) until predicate() or deadline; deadline-based
  so spurious wakeups under contention don't restart the clock."""
  while not predicate():
    remaining = None if deadline is None else deadline - time.monotonic()
    if remaining is not None and remaining <= 0:
      raise TimeoutError(f'{what} timed out')
    cond.wait(remaining)


class TrajectoryBuffer:
  """Bounded FIFO of unrolls with blocking put/get and backpressure.

  With a `ReplayTier` attached (round 10), every FRESH unroll dequeued
  is retained into the tier on its way out, and `get_unrolls` composes
  each batch's slots fresh-first:replayed per `replay_ratio`. The
  bounded FIFO semantics of the fresh path — backpressure, FIFO order,
  push-back on timeout/close — are untouched; the tier is pure
  retention behind it.
  """

  # Lock discipline (round 18, guarded-by lint): the deque, close
  # flag, and backpressure counters mutate only under _lock (the
  # conditions wrap the same mutex — the checker understands the
  # aliasing); fn-gauge reads in __init__ are exempt by convention.
  _deque: guarded_by('_lock')
  _closed: guarded_by('_lock')
  _high_water: guarded_by('_lock')
  _put_waits: guarded_by('_lock')
  _put_wait_secs: guarded_by('_lock')
  _fresh_unrolls: guarded_by('_lock')

  def __init__(self, capacity_unrolls: int,
               replay: Optional[ReplayTier] = None,
               replay_ratio: float = 0.0):
    if capacity_unrolls < 1:
      raise ValueError('capacity must be >= 1')
    if not 0.0 <= replay_ratio < 1.0:
      raise ValueError('replay_ratio must be in [0, 1)')
    if replay_ratio > 0 and replay is None:
      raise ValueError('replay_ratio > 0 needs a ReplayTier')
    self._capacity = capacity_unrolls
    self._replay = replay
    self._replay_ratio = replay_ratio
    self._deque = collections.deque()
    self._lock = make_lock('ring_buffer.TrajectoryBuffer._lock')
    self._not_full = threading.Condition(self._lock)
    self._not_empty = threading.Condition(self._lock)
    self._closed = False
    # Occupancy telemetry (round 9 — the bounded-queueing guard made
    # observable): the high-water mark (which also exposes get_batch's
    # transient push-back overshoot), and how often/long producers
    # actually blocked on the full buffer — the producer-side
    # backpressure the capacity bound exists to apply.
    self._high_water = 0
    self._put_waits = 0
    self._put_wait_secs = 0.0
    # Fresh-dequeue counter (round 10): cumulative unrolls that left
    # the FIFO toward the learner (stats()['fresh_unrolls']). NOTE
    # this runs AHEAD of training by the prefetch lookahead — frame
    # budgets and the learner_updates_per_env_frame denominator read
    # the prefetcher's serve-time fresh_slots_served instead.
    self._fresh_unrolls = 0
    # Unified-registry view (round 13): same pattern as the replay
    # tier — lazy gauges over this instance's occupancy/backpressure
    # counters, so the drain manifest / flight recorder / fleet stats
    # request read them without a stats() plumbing path. close()
    # unregisters them (identity-checked, so a newer buffer's
    # registration survives an older one's teardown).
    self._gauges = [
        telemetry.gauge('buffer/occupancy',
                        fn=lambda: len(self._deque)),
        telemetry.gauge('buffer/high_water',
                        fn=lambda: self._high_water),
        telemetry.gauge('buffer/put_waits',
                        fn=lambda: self._put_waits),
        telemetry.gauge('buffer/fresh_unrolls',
                        fn=lambda: self._fresh_unrolls),
    ]
    if replay is not None:
      self._gauges += replay._gauges

  @property
  def replay(self) -> Optional[ReplayTier]:
    return self._replay

  def note_param_version(self, version: int):
    """Driver publish cadence → the replay tier's staleness clock
    (no-op without a tier, so call sites stay unconditional)."""
    if self._replay is not None:
      self._replay.note_param_version(version)

  def put(self, unroll: ActorOutput, timeout: Optional[float] = None):
    """Block while full (backpressure). Raises Closed after close().

    The timeout bounds TOTAL blocking time (deadline-based — spurious
    wakeups under contention don't restart the clock)."""
    deadline = None if timeout is None else time.monotonic() + timeout
    with self._not_full:
      if len(self._deque) >= self._capacity and not self._closed:
        self._put_waits += 1
        t0 = time.monotonic()
        try:
          _wait_until(self._not_full,
                      lambda: (len(self._deque) < self._capacity
                               or self._closed),
                      deadline, 'TrajectoryBuffer.put')
        finally:
          self._put_wait_secs += time.monotonic() - t0
      if self._closed:
        raise Closed()
      self._deque.append(unroll)
      self._high_water = max(self._high_water, len(self._deque))
      self._not_empty.notify()

  def get(self, timeout: Optional[float] = None) -> ActorOutput:
    """Block while empty. Raises Closed after close() drains. Timeout
    bounds total blocking time (deadline-based)."""
    deadline = None if timeout is None else time.monotonic() + timeout
    with self._not_empty:
      _wait_until(self._not_empty,
                  lambda: self._deque or self._closed,
                  deadline, 'TrajectoryBuffer.get')
      if not self._deque:
        raise Closed()
      item = self._deque.popleft()
      self._fresh_unrolls += 1
      self._not_full.notify()
    if self._replay is not None:
      self._replay.add(item)
    return item

  def sample_replay(self, batch_size: int) -> List[ActorOutput]:
    """The replayed slice of one composed batch: up to
    floor(batch_size * replay_ratio) unrolls from the tier (fewer when
    it is short), [] without a tier. Sampled BEFORE the fresh fetch so
    a batch never replays an unroll it is also consuming fresh. Split
    out of get_unrolls so the unroll staging path can plan its slot
    composition while still staging each fresh unroll the moment it
    dequeues (the per-unroll trickle is the mode's whole point)."""
    if self._replay is None or self._replay_ratio == 0:
      return []
    return self._replay.sample(int(batch_size * self._replay_ratio))

  def get_unrolls(self, batch_size: int,
                  timeout: Optional[float] = None
                  ) -> Tuple[List[ActorOutput], int]:
    """Dequeue one batch's unrolls composed fresh:replayed (round 10).

    Returns `(unrolls, n_fresh)` — FRESH unrolls first (slots
    [0, n_fresh)), replayed after, so downstream stats peels can slice
    the env-plane view (episode events, action histograms) without
    double-counting replays. Replayed slots are sampled from the tier
    BEFORE the blocking fresh fetch (so a batch never replays an
    unroll it is also consuming fresh); with no tier or ratio 0 every
    slot is fresh and this is exactly the old `get_batch` dequeue.

    Fresh fetch semantics are unchanged from get_batch: incremental
    accumulation (dequeued unrolls free producer slots immediately),
    deadline-bounded blocking, and push-back to the FRONT on
    timeout/close so no trajectory is dropped (replayed samples need
    no push-back — the tier still holds them). Every completed fresh
    dequeue is retained into the replay tier."""
    replayed = self.sample_replay(batch_size)
    n_fresh = batch_size - len(replayed)
    deadline = None if timeout is None else time.monotonic() + timeout
    items: List[ActorOutput] = []
    with self._not_empty:
      try:
        while len(items) < n_fresh:
          _wait_until(self._not_empty,
                      lambda: self._deque or self._closed,
                      deadline, 'TrajectoryBuffer.get_batch')
          if not self._deque:  # closed and drained: partial batch
            raise Closed()
          while self._deque and len(items) < n_fresh:
            items.append(self._deque.popleft())
          self._not_full.notify_all()
      except (TimeoutError, Closed):
        # Push-back may transiently exceed capacity (up to capacity +
        # batch_size - 1): keeping trajectories beats the strict lag
        # bound on this error path; producers stay blocked until the
        # excess drains. Wake other consumers — the restored items are
        # consumable (lost-wakeup otherwise).
        self._deque.extendleft(reversed(items))
        self._high_water = max(self._high_water, len(self._deque))
        if items:
          self._not_empty.notify_all()
        if replayed:
          # The replayed slice never reached the learner either: give
          # its accounting back so the tier's sequential scan and the
          # reuse/staleness counters only see DELIVERED serves.
          self._replay.unsample_last()
        raise
      self._fresh_unrolls += len(items)
    if self._replay is not None:
      for item in items:
        self._replay.add(item)
    return items + replayed, n_fresh

  def get_batch(self, batch_size: int,
                timeout: Optional[float] = None) -> ActorOutput:
    """Dequeue `batch_size` unrolls and stack to a [T+1, B] batch (the
    reference's `dequeue_many` + time-major transpose). Composes
    fresh:replayed when a replay tier is attached — see get_unrolls,
    which owns the dequeue/push-back semantics."""
    items, _ = self.get_unrolls(batch_size, timeout)
    return batch_unrolls(items)

  def close(self):
    with self._lock:
      self._closed = True
      self._not_full.notify_all()
      self._not_empty.notify_all()
    # Release the registry's hold on this instance (and its replay
    # tier): the fn-gauges close over self, and a closed buffer must
    # be collectable, not pinned by telemetry for the process
    # lifetime. Identity-checked — a newer incarnation's registration
    # under the same names is left alone.
    for gauge in self._gauges:
      telemetry.registry().unregister(gauge.name, gauge)

  def stats(self):
    """Occupancy/backpressure counters (driver summary surface):
    {'occupancy', 'capacity', 'high_water', 'put_waits',
    'put_wait_secs', 'fresh_unrolls'}, plus the replay tier's
    occupancy/eviction/reuse counters when one is attached (round 10).
    high_water at (or briefly above) capacity with growing put_waits
    means producers are throttled by backpressure — the
    bounded-occupancy guarantee working, not a failure."""
    with self._lock:
      out = {
          'occupancy': len(self._deque),
          'capacity': self._capacity,
          'high_water': self._high_water,
          'put_waits': self._put_waits,
          'put_wait_secs': round(self._put_wait_secs, 4),
          'fresh_unrolls': self._fresh_unrolls,
      }
    if self._replay is not None:
      out.update(self._replay.stats())
    return out

  def __len__(self):
    with self._lock:
      return len(self._deque)


def _arena_insert(arena, unroll, slot):
  """One jitted batch-slot write: place unroll `slot`'s rows into the
  [T+1, B(, ...)] arena via `dynamic_update_slice` (bit-identical to
  `np.stack` of the same values — the golden-parity property the
  unroll staging mode rests on). Donated on the arena so the update is
  in-place in HBM."""
  import jax
  import jax.numpy as jnp
  from jax import lax

  def traj(a, x):
    # [T+1, ...] unroll leaf → arena [T+1, B, ...] at batch index slot.
    x = jnp.asarray(x)
    return lax.dynamic_update_slice(
        a, x[:, None].astype(a.dtype), (0, slot) + (0,) * (a.ndim - 2))

  def lead(a, x):
    # Leading-batch leaf: level_name scalar → arena [B]; core-state
    # [1, hidden] → arena [B, hidden].
    x = jnp.asarray(x)
    upd = x if x.ndim == a.ndim else x[None]
    return lax.dynamic_update_slice(a, upd.astype(a.dtype),
                                    (slot,) + (0,) * (a.ndim - 1))

  tree_map = jax.tree_util.tree_map
  return ActorOutput(
      level_name=lead(arena.level_name, unroll.level_name),
      agent_state=tree_map(lead, arena.agent_state, unroll.agent_state),
      env_outputs=tree_map(traj, arena.env_outputs, unroll.env_outputs),
      agent_outputs=tree_map(traj, arena.agent_outputs,
                             unroll.agent_outputs))


class UnrollBatchStager:
  """On-device [T+1, B] batch assembly from per-unroll transfers
  (config.staging_mode='unroll').

  `add(unroll)` runs the moment an unroll leaves the TrajectoryBuffer:
  the optional `host_view_fn` peels its tiny host-side stats view
  first (the batch never comes back to host), then the unroll is
  `jax.device_put` — async, directly to the device owning its batch
  slot (`slot_devices`) — and written into a zeroed per-device arena
  by the jitted, DONATED `_arena_insert`. The step-boundary H2D burst
  becomes a B-transfer trickle that overlaps the previous step's
  compute; the host `batch_unrolls` stack disappears.

  `finish()` emits the [T+1, B] batch: the arena itself on a single
  device, or `assemble_fn` (zero-copy
  `jax.make_array_from_single_device_arrays` over the data-axis
  sharding — parallel/train_parallel.make_unroll_assembly) under a
  pure-DP mesh. Fresh zero arenas back the NEXT batch, so the emitted
  arrays are never written again while the learner reads them.

  Donation-aliasing fallback: some jaxlib builds mis-pair donation
  aliases of mesh-placed leaves (the PR-3 dryrun defect — "Expected
  aliased input ... to have the same size"). The first insert that
  trips it rebuilds the insert un-donated and continues; the engaged
  fallback is visible as `stats()['donation_fallback']`.

  NOT thread-safe: owned and driven by the BatchPrefetcher loop
  thread. `abort()` (partial batch at close/error) is idempotent.
  """

  def __init__(self, batch_size: int, slot_devices=None,
               assemble_fn=None, host_view_fn=None, finalize_fn=None,
               donate: bool = True):
    import jax
    if batch_size < 1:
      raise ValueError('batch_size must be >= 1')
    if slot_devices is not None and len(slot_devices) != batch_size:
      raise ValueError(f'slot_devices must have one entry per batch '
                       f'slot ({len(slot_devices)} != {batch_size})')
    self._batch_size = batch_size
    self._slot_devices = slot_devices
    self._assemble_fn = assemble_fn
    self._host_view_fn = host_view_fn
    self._finalize_fn = finalize_fn
    self._donate = donate
    self._insert_donated = jax.jit(_arena_insert, donate_argnums=(0,))
    self._insert_plain = jax.jit(_arena_insert)
    # Slots grouped by device, in slot order: arena d holds the
    # contiguous run of slots placed on device d (the data-axis shard
    # layout make_unroll_assembly's sharding expects).
    if slot_devices is None:
      self._device_slots = [(None, batch_size)]
    else:
      groups = []
      for dev in slot_devices:
        if groups and groups[-1][0] == dev:
          groups[-1][1] += 1
        else:
          groups.append([dev, 1])
      self._device_slots = [(d, n) for d, n in groups]
    self._arenas = None   # list of per-device arenas (current batch)
    self._views = []
    self._next_slot = 0
    # Telemetry (read via stats(); single-writer, torn reads benign).
    self.unrolls_staged = 0
    self.batches_assembled = 0
    self.aborted_partials = 0
    self.donation_fallback = False

  def _zero_arena(self, unroll, slots, device):
    """Zeroed per-device arena with `slots` batch rows, shaped from a
    real unroll (no spec plumbing — the first unroll of each batch
    defines the shapes, and a shape drift fails loudly in the jit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def traj(x):
      x = np.asarray(x)
      return jnp.zeros((x.shape[0], slots) + x.shape[1:], x.dtype)

    def lead(x):
      x = np.asarray(x)
      shape = (slots,) + (x.shape[1:] if x.ndim else ())
      return jnp.zeros(shape, x.dtype)

    tree_map = jax.tree_util.tree_map
    arena = ActorOutput(
        level_name=lead(unroll.level_name),
        agent_state=tree_map(lead, unroll.agent_state),
        env_outputs=tree_map(traj, unroll.env_outputs),
        agent_outputs=tree_map(traj, unroll.agent_outputs))
    if device is not None:
      arena = jax.device_put(arena, device)
    return arena

  def _insert(self, arena, unroll_dev, local_slot):
    import numpy as np
    slot = np.int32(local_slot)
    if self._donate:
      try:
        return self._insert_donated(arena, unroll_dev, slot)
      except Exception as e:  # jaxlib XlaRuntimeError (INTERNAL)
        if 'alias' not in str(e):
          raise
        # The PR-3 jaxlib donation-aliasing defect: retry un-donated
        # for the rest of the run (correctness first; the in-place
        # update is an optimization).
        self._donate = False
        self.donation_fallback = True
    return self._insert_plain(arena, unroll_dev, slot)

  def add(self, unroll, peel_view: bool = True):
    """Stage one unroll into the current batch (called with host
    numpy, straight off the TrajectoryBuffer). `peel_view=False` skips
    the host stats peel — REPLAYED unrolls (round 10) already peeled
    their episode view on first consumption; peeling again would
    double-count episodes in the summaries."""
    import jax
    if self._next_slot >= self._batch_size:
      raise RuntimeError('batch already full; call finish()')
    if self._host_view_fn is not None and peel_view:
      self._views.append(self._host_view_fn(unroll))
    if self._arenas is None:
      self._arenas = [self._zero_arena(unroll, n, d)
                      for d, n in self._device_slots]
    # Which per-device arena owns this global slot, and where in it.
    slot = self._next_slot
    arena_idx, local_slot = 0, slot
    for i, (_, n) in enumerate(self._device_slots):
      if local_slot < n:
        arena_idx = i
        break
      local_slot -= n
    device = self._device_slots[arena_idx][0]
    unroll_dev = (jax.device_put(unroll, device) if device is not None
                  else jax.device_put(unroll))
    self._arenas[arena_idx] = self._insert(self._arenas[arena_idx],
                                           unroll_dev, local_slot)
    self._next_slot += 1
    self.unrolls_staged += 1

  def finish(self):
    """Emit the completed [T+1, B] device batch (plus the finalized
    host views when configured); resets for the next batch."""
    if self._next_slot != self._batch_size:
      raise RuntimeError(
          f'finish() with {self._next_slot}/{self._batch_size} slots '
          'staged')
    arenas, views = self._arenas, self._views
    self._arenas, self._views, self._next_slot = None, [], 0
    batch = (self._assemble_fn(arenas) if self._assemble_fn is not None
             else arenas[0])
    self.batches_assembled += 1
    if self._finalize_fn is not None:
      return self._finalize_fn(views, batch)
    return batch

  def abort(self):
    """Drop a partially staged batch (close/error path): releases the
    arena device buffers so nothing leaks past the prefetcher's
    lifetime. Idempotent."""
    if self._arenas is not None or self._next_slot:
      self.aborted_partials += 1
    self._arenas = None
    self._views = []
    self._next_slot = 0

  def stats(self):
    return {
        'unrolls_staged': self.unrolls_staged,
        'batches_assembled': self.batches_assembled,
        'aborted_partials': self.aborted_partials,
        'donation_fallback': self.donation_fallback,
    }


class BatchPrefetcher:
  """Stages upcoming device batches while the learner consumes the
  current one (the StagingArea role, generalized to `depth` slots).

  depth is the number of staged batches that may be in flight at once
  (config.staging_depth; default 2). With depth >= 2 the prefetcher
  keeps TWO `place_fn` dispatches outstanding: `jax.device_put` is
  async, so the transfers of batches N+1 and N+2 overlap each other
  AND the step computing batch N — the r5 fed-learner bench measured
  the host→device copy as the dominant feed-gap term (`h2d_ms` 1430.5
  vs `stack_ms` 37.5, BENCH_r05), and a single staged slot can hide
  at most one transfer behind one step. Raising depth trades policy
  lag (each staged batch extends the lag bound by one batch) for
  transfer overlap; keep it small.

  `stats()` reports the overlap counters the acceptance gate reads:
  `h2d_overlap_fraction` is the fraction of `get()` calls that found
  a batch already staged (the step did NOT block on staging). It
  conflates data starvation with transfer stalls by design — both are
  "the learner waited" — so read it together with `buffer_unrolls`
  (≈0 means starvation upstream of staging).

  Sample reuse (round 10): with `replay_k` > 1 each staged batch is
  SERVED `replay_k` times before its slot frees — the staged device
  arena is handed out AS IS (the same arrays; the train step donates
  only its state, and the unroll stager backs every batch with fresh
  arenas, so re-serves are bit-identical), which is `replay_k` learner
  updates per ONE stage/H2D. Serves after the first pass through
  `reserve_fn` (when given) so the caller can blank the host stats
  view — a re-serve consumes zero new env frames. A batch being
  re-served still occupies its depth slot until the Kth serve, and
  `close()` drops partially-served batches with everything else (no
  staged HBM outlives the prefetcher).

  When the buffer carries a replay tier, `place_fn` is called as
  `place_fn(batch, n_fresh)` — the composed batch's fresh slot count —
  so the driver's stats peel can exclude replayed columns; without a
  tier the one-argument contract is unchanged.
  """

  # Lock discipline (round 18, guarded-by lint): staging state, the
  # overlap telemetry, and the live replay_k knob all mutate under
  # _lock (the _ready/_space conditions wrap the same mutex).
  _out: guarded_by('_lock')
  _closed: guarded_by('_lock')
  _error: guarded_by('_lock')
  _staged: guarded_by('_lock')
  _gets: guarded_by('_lock')
  _blocked_gets: guarded_by('_lock')
  _wait_secs: guarded_by('_lock')
  _serves: guarded_by('_lock')
  _reserves: guarded_by('_lock')
  _fresh_served: guarded_by('_lock')
  _replay_k: guarded_by('_lock')

  def __init__(self, buffer: TrajectoryBuffer, batch_size: int,
               place_fn: Callable = lambda batch, n_fresh=None: batch,
               depth: int = 2,
               stager: Optional[UnrollBatchStager] = None,
               replay_k: int = 1,
               reserve_fn: Optional[Callable] = None):
    if depth < 1:
      raise ValueError('staging depth must be >= 1')
    if replay_k < 1:
      raise ValueError('replay_k must be >= 1')
    self._buffer = buffer
    self._batch_size = batch_size
    self._place_fn = place_fn
    # staging_mode='unroll': per-unroll device staging + on-device
    # assembly replaces get_batch + place_fn (which is then unused).
    self._stager = stager
    self._replay_k = replay_k
    self._reserve_fn = reserve_fn
    self._fresh_aware = buffer.replay is not None
    self._serves = 0
    self._reserves = 0
    self._fresh_served = 0
    self._out = collections.deque()
    self._lock = make_lock('ring_buffer.BatchPrefetcher._lock')
    self._ready = threading.Condition(self._lock)
    self._space = threading.Condition(self._lock)
    self._depth = depth
    self._closed = False
    self._error: Optional[BaseException] = None
    # Overlap telemetry (all under self._lock).
    self._staged = 0
    self._gets = 0
    self._blocked_gets = 0
    self._wait_secs = 0.0
    # Unified-registry view (round 13); unregistered by close().
    self._gauges = [
        telemetry.gauge('staging/staged_batches',
                        fn=lambda: self._staged),
        telemetry.gauge('staging/blocked_gets',
                        fn=lambda: self._blocked_gets),
        telemetry.gauge('staging/serves', fn=lambda: self._serves),
        telemetry.gauge('staging/fresh_slots_served',
                        fn=lambda: self._fresh_served),
    ]
    self._thread = threading.Thread(target=self._loop,
                                    name='batch-prefetcher', daemon=True)
    self._thread.start()

  def _stage_next(self):
    """Assemble + stage one batch; returns (staged, n_fresh). Batch
    mode: host stack via get_unrolls, then one place_fn burst. Unroll
    mode: each unroll is transferred the moment it dequeues and the
    batch assembles on device (UnrollBatchStager) — the transfers
    overlap the step that is computing RIGHT NOW, not just each other.
    Both modes compose fresh:replayed slots through the buffer's
    replay tier (fresh first); replayed unrolls skip the host stats
    peel."""
    tracer = telemetry.get_tracer()
    if self._stager is None:
      with telemetry.park('staging/wait_unrolls'):
        items, n_fresh = self._buffer.get_unrolls(self._batch_size)
      if tracer is not None:
        # Trace hop (round 13): this batch's fresh unrolls were
        # picked for staging — completes each sidecar span's STAGED
        # stamp and opens the batch's entry in the tracer's FIFO
        # (serve/step stamps follow in this same FIFO order).
        tracer.on_batch(items, n_fresh)
      with telemetry.activity('staging/stage'):
        batch = batch_unrolls(items)
        if self._fresh_aware:
          return self._place_fn(batch, n_fresh), n_fresh
        return self._place_fn(batch), n_fresh  # async put: overlaps
    # Unroll mode stays INCREMENTAL: each fresh unroll stages (and
    # starts its H2D) the moment it dequeues — batching the dequeue
    # would turn the trickle back into a step-boundary burst. Replayed
    # slots (available instantly) fill the tail of the batch.
    replayed = self._buffer.sample_replay(self._batch_size)
    n_fresh = self._batch_size - len(replayed)
    fresh_items = []
    # (So here the two spans alternate per UNROLL, and a batch's
    # staging is the sum of its `staging/stage` spans.)
    for _ in range(n_fresh):
      with telemetry.park('staging/wait_unrolls'):
        unroll = self._buffer.get()
      fresh_items.append(unroll)
      with telemetry.activity('staging/stage'):
        self._stager.add(unroll)
    with telemetry.activity('staging/stage'):
      for unroll in replayed:
        self._stager.add(unroll, peel_view=False)
      if tracer is not None:
        tracer.on_batch(fresh_items + replayed, n_fresh)
      return self._stager.finish(), n_fresh

  def _loop(self):
    try:
      while True:
        staged, n_fresh = self._stage_next()
        with self._space:
          while len(self._out) >= self._depth and not self._closed:
            self._space.wait()
          if self._closed:
            return
          # [staged, serves_remaining, n_fresh, staged_k]: the entry
          # leaves the deque — freeing its depth slot AND its device
          # arrays — only after the replay_k-th serve. n_fresh is
          # credited to `fresh_slots_served` at FIRST serve, so the
          # fresh-vs-serve accounting is attributed at consumption
          # time (a batch staged ahead by the prefetcher but never
          # served counts nothing — the lookahead-free invariant
          # the composition accounting relies on). staged_k pins the K
          # this entry was staged under: set_replay_k (round 15, the
          # controller's actuator) changes only FUTURE entries, and
          # first-serve detection compares against the entry's own K,
          # never the live knob.
          k = self._replay_k
          self._out.append([staged, k, n_fresh, k])
          self._staged += 1
          self._ready.notify()
    except Closed:
      if self._stager is not None:
        self._stager.abort()  # partial batch: free its arena buffers
      with self._lock:
        self._closed = True
        self._ready.notify_all()
    except BaseException as e:  # surfaced to the consumer
      if self._stager is not None:
        self._stager.abort()
      with self._lock:
        self._error = e
        self._closed = True
        self._ready.notify_all()

  def ready(self) -> bool:
    """Ready-without-dequeue probe (round 16, the hybrid filler's
    yield check): True when a `get()` right now would NOT block — a
    batch is staged, or the prefetcher is closed/errored (then get()
    raises immediately, which is the caller's signal to take its
    normal error path instead of filling forever). Never consumes,
    never counts toward the wait telemetry."""
    with self._lock:
      return bool(self._out) or self._closed

  def get(self, timeout: Optional[float] = None):
    deadline = None if timeout is None else time.monotonic() + timeout
    t0 = time.monotonic()
    with self._ready:
      self._gets += 1
      blocked = not self._out and not self._closed
      if blocked:
        self._blocked_gets += 1
        # The learner's wait as a recorder span, over exactly what
        # `_wait_secs` sums.
        with telemetry.park('learner/wait_batch'):
          while not self._out and not self._closed:
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining <= 0:
              self._wait_secs += time.monotonic() - t0
              raise TimeoutError('BatchPrefetcher.get timed out')
            self._ready.wait(remaining)
        self._wait_secs += time.monotonic() - t0
      if self._error is not None:
        raise self._error
      if not self._out:
        raise Closed()
      entry = self._out[0]
      item = entry[0]
      first_serve = entry[1] == entry[3]
      entry[1] -= 1
      if entry[1] <= 0:  # Kth serve: release the slot + the arrays
        self._out.popleft()
        self._space.notify()
      self._serves += 1
      if first_serve:
        self._fresh_served += entry[2]
        tracer = telemetry.get_tracer()
        if tracer is not None:
          # First serve = the learner picked this staged batch up
          # (re-serves ride the same arena; no new pipeline traversal).
          tracer.on_serve()
      if not first_serve:
        self._reserves += 1
        if self._reserve_fn is not None:
          item = self._reserve_fn(item)
      return item

  @property
  def replay_k(self) -> int:
    """The live re-serve count (the controller's actuator get path).
    Round 18: read under _lock like every other _replay_k access —
    the bare read was GIL-atomic but violated the declared
    guarded_by discipline (found by the lint)."""
    with self._lock:
      return self._replay_k

  def set_replay_k(self, k: int):
    """Thread-safe live replay_k change (round 15: the controller's
    sample-reuse actuator). Applies to batches staged AFTER the call;
    entries already staged finish out the K they were staged under
    (their first-serve accounting compares against that pinned K, so
    fresh-frame attribution can never double- or under-count across a
    change)."""
    k = int(k)
    if k < 1:
      raise ValueError('replay_k must be >= 1')
    with self._lock:
      if k != self._replay_k:
        log.warning('prefetcher replay_k: %d -> %d',
                    self._replay_k, k)
      self._replay_k = k

  def fresh_slots_served(self) -> int:
    """Cumulative fresh unroll slots of FIRST-served batches — the
    serve-time env-frame counter (immune to prefetch lookahead). Split
    from stats() because the driver's frame budget reads it every
    step; building the full stats dict there would add lock hold time
    the staging thread contends on."""
    with self._lock:
      return self._fresh_served

  def stats(self):
    """Staging/overlap counters: staged batches, consumer gets, how
    many blocked, total blocked seconds, and the headline
    `h2d_overlap_fraction` (1.0 = no step ever waited on staging)."""
    with self._lock:
      gets = self._gets
      # Overlap is denominated on FIRST serves: a re-serve (replay_k
      # > 1) hands back the entry already at the deque head, so it can
      # never block — counting it would dilute the fraction by 1/K and
      # mask real staging stalls on reuse configs.
      first_gets = max(gets - self._reserves, 0)
      out = {
          'depth': self._depth,
          'mode': 'unroll' if self._stager is not None else 'batch',
          'staged_batches': self._staged,
          'gets': gets,
          'blocked_gets': self._blocked_gets,
          'wait_secs': round(self._wait_secs, 4),
          'h2d_overlap_fraction': (
              (first_gets - self._blocked_gets) / first_gets
              if first_gets else 0.0),
          # Sample reuse (round 10): serves counts every batch handed
          # to the learner; batch_reserves the serves beyond each
          # batch's first (zero-H2D re-serves of the staged arena);
          # fresh_slots_served the fresh unroll slots of FIRST-served
          # batches (credited at serve time, so composition ratios
          # derived from it are immune to prefetch lookahead).
          'replay_k': self._replay_k,
          'serves': self._serves,
          'batch_reserves': self._reserves,
          'fresh_slots_served': self._fresh_served,
      }
    if self._stager is not None:
      out.update(self._stager.stats())
    return out

  def close(self):
    with self._lock:
      self._closed = True
      self._ready.notify_all()
      self._space.notify_all()
    self._buffer.close()
    self._thread.join(timeout=5)
    # Release staged device batches (and, via the loop thread's abort,
    # any partial arena): a closed prefetcher must not pin batch-sized
    # HBM buffers for the rest of the process lifetime — and neither
    # may the registry pin the prefetcher itself via its fn-gauges.
    with self._lock:
      self._out.clear()
    for gauge in self._gauges:
      telemetry.registry().unregister(gauge.name, gauge)

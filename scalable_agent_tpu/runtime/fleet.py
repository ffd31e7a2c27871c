"""Actor fleet: owns env processes, actor threads, and their health.

The reference's actor fleet is implicit — QueueRunner threads plus
PyProcessHook-started env processes, with NO failure detection: a dead
actor silently stops contributing (SURVEY §5.3). This module makes the
fleet explicit and adds what upstream lacks:

- per-actor heartbeats (last unroll completion time),
- dead/stalled-actor detection,
- respawn of the env (process) + actor thread without disturbing the
  rest of the fleet or the learner,
- capped-exponential respawn backoff with full jitter PER SLOT and a
  give-up-after-N quarantine (round 9): a persistently failing env —
  or a respawn starved by inference-slot admission under overload —
  used to hot-loop respawn attempts through every health check;
  now each failed generation pushes the slot's next attempt out on
  its own jittered backoff, and after `quarantine_after` consecutive
  respawns without ONE completed unroll the slot is quarantined
  (marked dead, surfaced as `slots_quarantined` in stats()/driver
  summaries) instead of burning the learner loop forever.

Trajectories from a respawned actor restart from a fresh episode —
consistent with the reference's crash story (unrolls straddling a
restart are lost, SURVEY §5.4).

A SLOT is the unit of env identity (index, seed, level, heartbeat,
error, quarantine, probation, parking); a GROUP is the unit of thread
(PR 26): process-hosted envs of one spec are stepped k to a thread in
lockstep (`runtime.actor.ActorGroup`), so one merged inference call
wakes one thread per group and not one per env. k follows what the
fleet observes — the env's hosting, its spec, `num_actors` and the
rows the policy takes in one call — and is 1 for envs hosted in this
process. A slot joins and leaves its group on its own, between two
unrolls: parked, it leaves and the thread goes on with the rest;
spawned, it joins a running group of its spec that has room, or
starts a thread.
"""

import collections
import logging
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock
from scalable_agent_tpu.runtime import py_process, ring_buffer
from scalable_agent_tpu.runtime.actor import Actor, ActorGroup
from scalable_agent_tpu.runtime.remote import Backoff

log = logging.getLogger('scalable_agent_tpu')


def _is_admission_error(e: BaseException) -> bool:
  """Whether a spawn failure is inference-slot admission (overload —
  degrade to pause-and-retry) rather than a setup error (raise).
  Lazy import: the fleet must not pull jax at module import."""
  from scalable_agent_tpu.runtime.inference import (InferenceClosed,
                                                    SlotUnavailable)
  return isinstance(e, (SlotUnavailable, InferenceClosed))


# Envs to an actor thread, at most: as far as the measurement goes.
# `deep_dmlab.fleet32` (32 envs, 13 cores, TPU v5e; PERF.md, PR 26)
# got faster with every thread fewer, frames/s by thread count: 32:
# 3,845; 16: 5,010; 8: 6,534; 6: 7,519; 4: 8,382; 3: 8,700; 2: 10,922;
# 1: 13,503. Under the GIL an actor thread adds no parallel work (the
# env steps run in the children, whichever thread sent them); it adds
# a wake-up per merged inference call, and the threads come back from
# that wake one after another. Past 32 nothing was measured: one
# thread's serial share of a step (a send, a receive and the
# bookkeeping per env) comes near the time the children take to step.
# What a group costs: an env that fails takes its mates' unrolls in
# flight with it, and one that hangs holds them all up until the stall
# check respawns the group (docs/ROBUSTNESS.md).
_MAX_ENVS_PER_THREAD = 32

# One env on a thread: its slot, the slot's generation at the spawn,
# its Actor and its PyProcess (None: hosted in this process).
_Member = collections.namedtuple('_Member',
                                 'slot generation actor process')

# `stats()`'s step percentiles: over this many newest steps a thread.
_RECENT_STEPS = 4096


def _step_counts(records):
  """What the groups' step records (`ActorGroup.steps`) sum to, as the
  flat cumulative keys of `ActorFleet.stats`."""
  totals = [r.totals() for r in records]
  ms = lambda key: sum(t[key] for t in totals) / 1e6  # noqa: E731
  return {
      'group_steps': sum(t['cycles'] for t in totals),
      'pass_steps': sum(t['pass_steps'] for t in totals),
      'step_ms': ms('policy_wait_ns') + ms('env_ns'),
      'step_policy_wait_ms': ms('policy_wait_ns'),
      'step_env_ms': ms('env_ns'),
      'step_env_child_ms': ms('env_child_ns'),
      **telemetry.excess_ms(
          'step_', *[telemetry.excess(r) for r in records])}


class _Group:
  """One actor thread and the slots it carries."""

  def __init__(self, key, members):
    self.key = key  # `Actor.group_key()`; None: takes no one else
    self.actors = ActorGroup(
        [m.actor for m in members],
        names=[f'actor-{m.slot.index}' for m in members])
    # Actor -> _Member, under the fleet's lock: those of `actors` and
    # those it has yet to admit.
    self.members = {m.actor: m for m in members}
    self.thread: Optional[threading.Thread] = None
    # Takes no more members (under the fleet's lock): the thread has
    # ended, failed or stalled.
    self.closed = False


class _Slot:
  """One actor's mutable runtime state (env, thread, health)."""

  def __init__(self, index):
    self.index = index
    self.env = None
    self.process = None          # PyProcess when process-hosted
    self.actor: Optional[Actor] = None
    # The thread that steps this slot's env, and the group it carries
    # (the slots of one group hold the same two).
    self.thread: Optional[threading.Thread] = None
    self.group: Optional[_Group] = None
    self.generation: int = 0     # bumped on every (re)spawn
    self.last_heartbeat: float = time.monotonic()
    self.unrolls_done: int = 0
    self.respawns: int = 0
    # What the env processes this slot had before counted (`process`
    # counts its own): env steps through a shared block, and calls
    # down the pickled pipe.
    self.block_steps: int = 0
    self.pipe_calls: int = 0
    self.error: Optional[BaseException] = None
    # The group's thread ended or hung on ANOTHER member's env: this
    # slot respawns with it, off the respawn ladder (no streak, no
    # backoff: the slot did nothing wrong).
    self.collateral: bool = False
    # Respawn pacing (round 9): consecutive respawns since the last
    # COMPLETED unroll (a spawn that crash-loops before producing is
    # still a failure), the per-slot jittered backoff, the earliest
    # next respawn attempt, and the give-up flag.
    self.respawn_streak: int = 0
    self.backoff = Backoff(base=0.5, cap=30.0)
    self.next_respawn_time: float = 0.0
    self.quarantined: bool = False
    # Elastic fleet (round 15): a PARKED slot is deliberately idle —
    # excluded from spawning, health checks, and the quorum
    # denominator (set_target_size is the controller's shrink/grow
    # seam). `quarantined_at` feeds the probation cool-down;
    # `probation` marks a rehabilitated slot whose NEXT failure
    # re-quarantines immediately (one probe, not a fresh ladder).
    self.parked: bool = False
    self.quarantined_at: float = 0.0
    self.probation: bool = False


class ActorFleet:
  """N actors producing unrolls into a shared TrajectoryBuffer.

  Args:
    make_actor: (slot_index) → (env, process_or_None, Actor). Called at
      start and again on every respawn; must build a FRESH env.
    buffer: the shared TrajectoryBuffer.
    num_actors: fleet size.
    quarantine_after: consecutive respawns without one completed
      unroll before the slot gives up and quarantines (0 = never).
    max_policy_rows: the most rows `policy` takes in one call (the
      inference server's `inference_max_batch`: the batcher never
      splits a request), so the most envs to a thread; None: no such
      limit.
  """

  # Lock discipline (round 18, checked by the guarded-by lint): slot
  # mutation and the rehabilitation counters happen under _lock; the
  # _Slot objects themselves are reached only through _slots.
  _slots_rehabilitated: guarded_by('_lock')
  _rehabilitations: guarded_by('_lock')
  _groups: guarded_by('_lock')
  _step_records: guarded_by('_lock')
  _steps_retired: guarded_by('_lock')

  def __init__(self, make_actor: Callable, buffer, num_actors: int,
               quarantine_after: int = 5,
               probation_secs: float = 30.0,
               max_policy_rows: Optional[int] = None):
    self._make_actor = make_actor
    self._buffer = buffer
    self._quarantine_after = int(quarantine_after)
    self._probation_secs = float(probation_secs)
    self._stop = threading.Event()
    self._lock = make_lock('fleet._lock')
    self._slots: List[_Slot] = [_Slot(i) for i in range(num_actors)]
    self._slots_rehabilitated = 0  # probation cleared by an unroll
    self._rehabilitations = 0      # probation attempts started
    # Process-hosted envs to a thread, at most (`_MAX_ENVS_PER_THREAD`).
    self._envs_per_thread = max(1, min(
        _MAX_ENVS_PER_THREAD, max_policy_rows or _MAX_ENVS_PER_THREAD))
    self._groups: List[_Group] = []  # running, with a key
    # The step records of the threads that run (`ActorGroup.steps`),
    # and what those of the threads that ended had counted.
    self._step_records: List[telemetry.CycleRecord] = []
    self._steps_retired = collections.Counter()

  @property
  def stop_event(self):
    return self._stop

  def start(self):
    def on_error(slot, e):
      # Overload degrade (round 9): a start-time acquire denied by
      # inference-slot admission is NOT a setup error — record it on
      # the slot and let the health loop retry on the slot's backoff
      # instead of crashing the run before it begins. Anything else
      # (env construction, bad config) still raises to the caller.
      if not _is_admission_error(e):
        raise e
      with self._lock:
        slot.error = e
        slot.thread = None
        slot.respawn_streak += 1
        slot.next_respawn_time = (time.monotonic()
                                  + slot.backoff.next_delay())
      log.warning(
          'actor %d: start-time slot admission denied (%s) — '
          'degrading to pause-and-retry', slot.index, e)

    # Parked before start: elastic fleets spin up small.
    self._spawn_slots([s for s in self._slots if not s.parked],
                      on_error)

  def _spawn(self, slot: _Slot):
    def on_error(slot, e):
      raise e
    self._spawn_slots([slot], on_error)

  def _spawn_slots(self, slots: List[_Slot], on_error: Callable):
    """Build each slot's env and actor, in turn, then give each a
    thread (`_place`): its own for an env that lives in this process,
    one shared with its `Actor.group_key`'s others for a process-hosted
    env. `on_error(slot, exc)` takes a failed build; if it raises, the
    slots built so far run and the rest is not built."""
    built = {}  # group key -> [_Member]
    try:
      for slot in slots:
        try:
          env, process, actor = self._make_actor(slot.index)
        except Exception as e:
          on_error(slot, e)
          continue
        with self._lock:
          slot.generation += 1
          generation = slot.generation
          slot.block_steps += getattr(slot.process, 'block_steps', 0)
          slot.pipe_calls += getattr(slot.process, 'pipe_calls', 0)
          slot.env, slot.process, slot.actor = env, process, actor
          slot.error = None
          slot.collateral = False
          slot.last_heartbeat = time.monotonic()
        group_key = getattr(actor, 'group_key', None)
        key = group_key() if process is not None and group_key else None
        built.setdefault(key, []).append(
            _Member(slot, generation, actor, process))
    finally:
      for key, members in built.items():
        self._place(key, members)

  def _place(self, key, members: List[_Member]):
    """A thread for each new member. With a `key` (process-hosted
    envs that can share one): room in a running group of that key
    first, where the member steps with the others from their next
    unroll on, then new threads in even shares of at most
    `_envs_per_thread`. Without: a thread of its own."""
    room = self._envs_per_thread if key is not None else 1
    with self._lock:
      self._groups = [g for g in self._groups if not g.closed]
      for group in self._groups:
        if group.key != key:
          continue
        while members and len(group.members) < room:
          member = members.pop(0)
          group.members[member.actor] = member
          self._carry(member.slot, group)
          group.actors.join(member.actor, f'actor-{member.slot.index}')
    threads = -(-len(members) // room)
    share, larger = divmod(len(members), threads or 1)
    while members:
      size = share + (larger > 0)
      larger -= 1
      self._start_thread(key, members[:size])
      members = members[size:]

  def _start_thread(self, key, members: List[_Member]):
    group = _Group(key, members)
    group.thread = threading.Thread(
        target=self._run, args=(group,),
        name=f'actor-{members[0].slot.index}', daemon=True)
    with self._lock:
      for member in members:
        self._carry(member.slot, group)
      if key is not None:
        self._groups.append(group)
      self._step_records.append(group.actors.steps)
    group.thread.start()

  @staticmethod
  def _carry(slot: _Slot, group: _Group):
    """`group`'s thread steps `slot`'s env from here on (under the
    lock). Its heartbeat starts now and not when its env was built:
    a group's envs are built in turn before their thread starts, and
    the first must not look stalled for the time the others took."""
    slot.thread, slot.group = group.thread, group
    slot.last_heartbeat = time.monotonic()

  def _run(self, group: _Group):
    """Thread body: `actor.run_actor_loop` (the one shutdown/poison
    contract) with fleet bookkeeping hooked in. Touches only ITS OWN
    actor/process objects and writes a slot's state only while it is
    still that slot's current generation — an orphaned thread
    (replaced after a stall) must not mark the healthy replacement
    dead or close its process. Failures are recorded on the slot whose
    env failed, or on every member when the failure was not one env's
    (the shared buffer stays open for the other actors); the learner
    surfaces them via errors() on its stall path."""
    from scalable_agent_tpu.runtime.actor import run_actor_loop

    def on_unroll(actor):
      with self._lock:
        slot, generation, _, process = group.members[actor]
        stays = slot.generation == generation
        if stays:
          slot.last_heartbeat = time.monotonic()
          slot.unrolls_done += 1
          # A completed unroll is the success signal that resets the
          # respawn ladder: streak, backoff, and pacing all clear — and
          # it is what clears PROBATION: a rehabilitated slot has
          # proven itself only once it lands real data (round 15,
          # counted as slots_rehabilitated).
          slot.respawn_streak = 0
          slot.backoff.reset()
          slot.next_respawn_time = 0.0
          if slot.probation:
            slot.probation = False
            self._slots_rehabilitated += 1
            log.info('actor %d REHABILITATED: probation unroll '
                     'completed; the slot rejoins the fleet',
                     slot.index)
          if slot.parked:
            # The controller shrank the fleet under us: this unroll
            # landed (already put), and the slot leaves its thread,
            # which goes on with the rest of the group.
            stays = False
            slot.thread = slot.group = None
        # Else orphaned: a replacement owns the slot now.
        if not stays:
          del group.members[actor]
      if not stays and process is not None:
        self._close_processes([process])
      return stays

    def on_failure(exc):
      failed = group.actors.failed
      with self._lock:
        group.closed = True
        for actor, (slot, generation, _, _) in group.members.items():
          if slot.generation != generation:
            continue
          if failed is None or failed is actor:
            slot.error = exc
          else:
            slot.collateral = True

    try:
      run_actor_loop(group.actors, self._buffer, self._stop,
                     on_unroll=on_unroll, on_failure=on_failure)
    finally:
      counted = _step_counts([group.actors.steps])
      with self._lock:
        self._step_records.remove(group.actors.steps)
        self._steps_retired.update(counted)
        group.closed = True
        members = list(group.members.values())
        if not self._stop.is_set():
          # Whoever is still here goes down with the thread: a slot
          # that joined as the loop ended, if the failure path above
          # charged no one.
          for slot, generation, _, _ in members:
            if slot.generation == generation and slot.error is None:
              slot.collateral = True
      self._close_processes([m.process for m in members
                             if m.process is not None], timeout=2.0)

  @staticmethod
  def _close_processes(processes, timeout=1.0):
    """Close env processes, all at once: a close that finds a call in
    flight waits out its timeout before it kills the child, and a
    stalled group's thread holds a call on each of its children."""
    try:
      py_process.close_all(processes, timeout=timeout)
    except Exception:  # the callers go on: these processes are done
      log.exception('closing %d env processes', len(processes))

  def check_health(self, stall_timeout_secs: Optional[float] = None,
                   respawn: bool = True) -> List[int]:
    """Detect failed/stalled actors; respawn them. Returns the indices
    acted upon. Call periodically from the learner loop (the reference
    has no equivalent — SURVEY §5.3 greenfield).

    A thread stalls as a whole: the members of a stalled slot's group
    are respawned with it. Where the thread hangs in an env step, the
    member whose reply it waits for is the one at fault and climbs
    the respawn ladder; the others are `collateral`."""
    if self._stop.is_set():
      return []
    now = time.monotonic()
    bad: List[_Slot] = []
    stalled_groups: List[_Group] = []
    with self._lock:
      for slot in self._slots:
        if slot.quarantined or slot.parked:
          continue  # gave up / deliberately idle; stats() carries both
        # thread-None counts as dead (round 15): a slot unparked after
        # never spawning (elastic grow) has no thread and no error —
        # it must still be picked up here and spawned.
        dead = (slot.error is not None or slot.collateral
                or slot.thread is None or not slot.thread.is_alive())
        stalled = (stall_timeout_secs is not None and
                   now - slot.last_heartbeat > stall_timeout_secs)
        # Respawn pacing: a failing slot is retried only once its
        # jittered backoff elapses — a crash-looping env (or an
        # admission-denied respawn under overload) must not hot-loop
        # the learner thread through every health check.
        if (dead or stalled) and now >= slot.next_respawn_time:
          bad.append(slot)
          if (not dead and slot.group is not None
              and slot.group not in stalled_groups):
            stalled_groups.append(slot.group)
      for group in stalled_groups if respawn else ():
        group.closed = True
        hung = group.actors.waiting_on
        for actor, (mate, generation, _, _) in group.members.items():
          if (mate.generation != generation or mate.quarantined
              or mate.parked or mate.error is not None):
            continue
          # A mate whose own heartbeat is stale is charged, as a lone
          # actor is, unless the thread waits for another's env.
          own = mate in bad
          if not own:
            bad.append(mate)
          mate.collateral = (actor is not hung and
                             (hung is not None or not own))
      bad.sort(key=lambda s: s.index)
    if respawn and bad:
      self._close_processes(
          [s.process for s in bad if s.process is not None])
      # The slots found bad together respawn together: the members of
      # a failed group share a thread again.
      self._spawn_slots([s for s in bad if self._retire(s)],
                        self._spawn_failed)
    return [s.index for s in bad]

  def _spawn_failed(self, slot: _Slot, e: Exception):
    # A failed respawn (env construction, denied inference-slot
    # admission) must not propagate into the learner loop that
    # called check_health — start()-time spawn failures still raise
    # for setup errors (admission denials degrade; see start()), but
    # a mid-run respawn records the error on the slot: the next
    # health check retries after the slot's backoff, and the learner
    # surfaces it via errors() only if the pipeline actually stalls
    # (the same containment as any other actor-side failure).
    with self._lock:
      slot.error = e
      slot.thread = None

  def _retire(self, slot: _Slot) -> bool:
    """The first half of a respawn (its env process is closed): let go
    of the slot's old thread and move it along the respawn ladder.
    False: the slot gave up (quarantined) and is not spawned again."""
    old_thread = slot.thread
    old_actor = slot.actor
    if old_thread is not None and old_thread.is_alive():
      # A stalled thread blocked in env.step can't be killed; it is
      # orphaned (daemon) and a fresh actor takes over the slot. Its
      # buffer.put may still land one stale unroll — harmless, same
      # policy-lag bound as any in-flight unroll. Its device-resident
      # inference state (a state-arena slot) stays acquired until the
      # thread unwinds through run_actor_loop's finally — the arena's
      # auto headroom (2× fleet) covers the interim; the replacement
      # gets a FRESH zeroed slot from make_actor either way.
      pass
    elif old_actor is not None:
      # Dead thread: run_actor_loop's finally normally released the
      # inference state via actor.close(); this is the idempotent
      # backstop for a thread killed before its finally ran — the
      # respawn must free the old slot, not leak it.
      try:
        old_actor.release_policy_state()
      except Exception:
        pass
    with self._lock:
      slot.respawns += 1
      if slot.collateral:
        return True  # its group's failure, not its own: no ladder
      slot.respawn_streak += 1
      # Pace the NEXT attempt now, so a spawn that fails (or succeeds
      # and immediately crash-loops) waits out the jittered backoff
      # before the health loop touches the slot again.
      slot.next_respawn_time = (time.monotonic()
                                + slot.backoff.next_delay())
      # Probation (round 15): a rehabilitated slot gets ONE probe
      # (re)spawn — streak 1 is the probe itself; a second respawn
      # without a completed unroll re-quarantines immediately instead
      # of re-running the whole give-up ladder.
      give_up = ((self._quarantine_after > 0 and
                  slot.respawn_streak > self._quarantine_after) or
                 (slot.probation and slot.respawn_streak > 1))
      if give_up:
        slot.quarantined = True
        slot.quarantined_at = time.monotonic()
        slot.probation = False
        slot.thread = None
    if give_up:
      log.error(
          'actor %d QUARANTINED after %d consecutive respawns without '
          'a completed unroll (last error: %s) — the slot is marked '
          'dead; the rest of the fleet keeps feeding', slot.index,
          slot.respawn_streak, slot.error)
    return not give_up

  # --- elastic fleet size (round 15): the controller's actuator ---

  def target_size(self) -> int:
    """Contributing slots: neither parked nor quarantined — the value
    the fleet-size actuator steps (growing past it first unparks,
    then rehabilitates)."""
    with self._lock:
      return sum(1 for s in self._slots
                 if not s.parked and not s.quarantined)

  def set_target_size(self, n: int) -> Dict[str, List[int]]:
    """Thread-safe elastic resize toward `n` contributing slots.

    Shrink parks the highest-index contributing slots (each actor
    exits cleanly after its current unroll — the on_unroll seam: a
    lone actor's thread ends, a group's goes on with its other
    members; a parked slot leaves the quorum denominator, so shedding
    load never reads as a dying fleet). Grow first UNPARKS parked
    slots, then REHABILITATES quarantined ones whose probation
    cool-down has elapsed: quarantine cleared, probation armed,
    respawn ladder reset — the next check_health runs the probe spawn
    (into a running group of the slot's spec that has room, else on a
    new thread), and ONE completed unroll clears probation
    (slots_rehabilitated); a repeat failure re-quarantines
    immediately. The fleet never grows past its constructed slot
    count (the bounded-move guarantee — the controller's actuator
    registers that as the hard max).

    Returns {'parked': [...], 'unparked': [...], 'rehabilitated':
    [...]} slot indices. May deliver fewer than requested when every
    remaining quarantined slot is still inside its cool-down — the
    caller (controller) simply retries after its own cool-down."""
    now = time.monotonic()
    report = {'parked': [], 'unparked': [], 'rehabilitated': []}
    with self._lock:
      n = max(0, min(int(n), len(self._slots)))
      contributing = [s for s in self._slots
                      if not s.parked and not s.quarantined]
      if n < len(contributing):
        for slot in reversed(contributing[n:]):
          slot.parked = True
          report['parked'].append(slot.index)
      elif n > len(contributing):
        need = n - len(contributing)
        for slot in self._slots:
          if need == 0:
            break
          if slot.parked and not slot.quarantined:
            slot.parked = False
            # Spawn-eligible immediately: a slot parked since start
            # has no thread; one parked mid-run has a finished one.
            # Any error from before the park is a closed incident —
            # it must not surface through errors() as the cause of
            # whatever stalls the pipeline next.
            slot.error = None
            slot.next_respawn_time = 0.0
            report['unparked'].append(slot.index)
            need -= 1
        if need:
          ready = sorted(
              (s for s in self._slots if s.quarantined and
               now - s.quarantined_at >= self._probation_secs),
              key=lambda s: s.quarantined_at)
          for slot in ready[:need]:
            slot.quarantined = False
            slot.probation = True
            slot.parked = False
            slot.respawn_streak = 0
            slot.backoff.reset()
            slot.next_respawn_time = 0.0
            # The quarantine-era error is a CLOSED incident: leaving
            # it would make errors() surface it as live mid-probation
            # and misdiagnose an unrelated stall (the slot stays
            # respawn-eligible — a thread-less slot counts as dead).
            slot.error = None
            self._rehabilitations += 1
            report['rehabilitated'].append(slot.index)
    for which in ('parked', 'unparked', 'rehabilitated'):
      if report[which]:
        log.warning('fleet resize -> %d contributing: %s slots %s',
                    n, which, report[which])
    return report

  def errors(self) -> List[BaseException]:
    """Errors the learner should act on NOW. A quarantined slot's
    error is a closed incident (logged, counted in stats() — the
    give-up already happened), not the cause of whatever stalls the
    pipeline hours later — surfacing it would misdiagnose the new
    incident; a PARKED slot's stale error is the same (the park was
    deliberate). Exception: when EVERY active slot is quarantined the
    fleet is dead and those errors ARE the cause, so they come back."""
    with self._lock:
      live = [s.error for s in self._slots
              if s.error is not None and not s.quarantined
              and not s.parked]
      if live:
        return live
      active = [s for s in self._slots if not s.parked]
      if active and all(s.quarantined for s in active):
        return [s.error for s in active if s.error is not None]
      return []

  def stats(self, healthy_horizon_secs: float = 60.0):
    """Fleet health counters.

    `alive` counts slots whose CURRENT thread is running — but a
    wedged actor (blocked in env.step) or one whose error hasn't been
    collected yet is alive without producing, and a stalled thread
    orphaned by respawn keeps running as a daemon invisibly. `healthy`
    is the honest signal: the slot's current-generation thread is
    alive, has no recorded error, AND heartbeat-fresh within
    `healthy_horizon_secs` (align it with the driver's stall timeout).
    `healthy_fraction` is the quorum the driver logs — the scheduler-
    facing 'how much of my fleet is actually feeding' number.
    """
    now = time.monotonic()
    with self._lock:
      alive = [s for s in self._slots
               if s.thread is not None and s.thread.is_alive()]
      healthy = [s for s in alive
                 if s.error is None and not s.quarantined and
                 not s.parked and
                 now - s.last_heartbeat <= healthy_horizon_secs]
      # Wedged = alive with NO heartbeat inside the horizon and no
      # recorded error: the thread runs but produces nothing — the
      # blocked-in-env.step / parked-on-backpressure shape the
      # zero-deadlocked-threads chaos SLO counts (an errored slot is
      # 'dead pending respawn', a different bucket; a parked slot is
      # deliberately idle, neither).
      wedged = [s for s in alive
                if s.error is None and not s.quarantined and
                not s.parked and
                now - s.last_heartbeat > healthy_horizon_secs]
      # Quorum denominator = ACTIVE (non-parked) slots (round 15): a
      # controller-shrunk fleet is smaller on purpose — parked slots
      # reading as unhealthy would make every deliberate shed look
      # like a dying plane to the fleet_healthy_fraction objective.
      active = sum(1 for s in self._slots if not s.parked)
      threads = len({id(s.thread) for s in alive})
      records = list(self._step_records)
      steps = collections.Counter(self._steps_retired)
      counts = {
          # Slots alive per running thread (PR 26): 1.0 while every
          # env has a thread of its own, k where groups of k share one.
          'actor_threads': threads,
          'envs_per_thread': len(alive) / threads if threads else 0.0,
          'unrolls': sum(s.unrolls_done for s in self._slots),
          # How the process-hosted envs were reached (PR 33): env
          # steps through a group's shared block, and calls down the
          # pickled pipe (`initial`, `prompt_block`, `close`, and
          # `step` where no block could be had).
          'block_steps': sum(
              s.block_steps + getattr(s.process, 'block_steps', 0)
              for s in self._slots),
          'pipe_calls': sum(
              s.pipe_calls + getattr(s.process, 'pipe_calls', 0)
              for s in self._slots),
          'respawns': sum(s.respawns for s in self._slots),
          'alive': len(alive),
          'healthy': len(healthy),
          'wedged': len(wedged),
          'healthy_fraction': (len(healthy) / active
                               if active else 1.0),
          # Give-up slots (round 9): respawn exhausted its budget —
          # the honest 'this much of my fleet is permanently gone'
          # number the driver surfaces as `slots_quarantined`.
          'slots_quarantined': sum(1 for s in self._slots
                                   if s.quarantined),
          # Elastic-fleet surface (round 15).
          'parked': len(self._slots) - active,
          'target_size': sum(1 for s in self._slots
                             if not s.parked and not s.quarantined),
          'rehabilitations': self._rehabilitations,
          'slots_rehabilitated': self._slots_rehabilitated,
      }
    # The group steps (PR 37; docs/OBSERVABILITY.md "Cycle records"),
    # summed over the threads, ended ones included, off the lock: the
    # count (and of those, `pass_steps`: the steps taken as one pass
    # over a shared block), the cumulative ms of a step and of its two
    # phases, of the slowest member's own time in its env's `step`,
    # and of what the steps lay over their thread's median, by the
    # activity they lay under (`telemetry.excess`); percentiles over
    # the running threads' newest steps.
    steps.update(_step_counts(records))
    recent = [r.lengths(r.held(last=_RECENT_STEPS)[1]) for r in records]
    recent = np.sort(np.concatenate(recent or [np.zeros(0, np.int64)]))
    rank = lambda q: telemetry.nearest_rank(recent, q) / 1e6  # noqa: E731
    counts.update(steps, step_ms_p50=rank(0.5), step_ms_p95=rank(0.95),
                  step_ms_max=rank(1.0))
    return counts

  def _join_all(self, timeout: float, what: str,
                consequence: str) -> Dict[str, List[int]]:
    """Deadline-join every actor thread; actors that miss it are
    NAMED in the log and the returned report instead of dropped
    silently (round 9 — the shared tail of stop() and quiesce())."""
    deadline = time.monotonic() + timeout
    unjoined: List[int] = []
    for slot in self._slots:
      if slot.thread is not None:
        slot.thread.join(max(0.0, deadline - time.monotonic()))
        if slot.thread.is_alive():
          unjoined.append(slot.index)
    if unjoined:
      log.warning('fleet %s: actors %s did not stop within %.1fs '
                  '(%s)', what, unjoined, timeout, consequence)
    return {'unjoined_actors': unjoined}

  def quiesce(self, timeout: float = 10.0) -> Dict[str, List[int]]:
    """Stop production WITHOUT closing the buffer (the preemption-
    drain path): the stop event ends each actor's loop after its
    current unroll, and the in-flight unrolls land in the trajectory
    buffer for the learner to flush. Joins actor threads up to
    `timeout`; returns {'unjoined_actors': [...]} — the slots whose
    unrolls are lost to the drain (a wedged env can't be joined; its
    unroll follows the reference's crash semantics)."""
    self._stop.set()
    return self._join_all(timeout, 'quiesce',
                          'their in-flight unrolls are lost')

  def stop(self, timeout: float = 10.0) -> Dict[str, List[int]]:
    """Stop the fleet and close the buffer. Returns the same report as
    `quiesce`. After stop() returns the buffer is CLOSED: any
    straggler thread's `put` raises `ring_buffer.Closed` instead of
    landing a stale unroll (regression-tested; the in-RUN orphan
    window documented in `_respawn` is unchanged). The buffer closes
    BEFORE the join: an actor blocked in a full buffer's put must be
    woken (Closed) or it could never exit."""
    self._stop.set()
    self._buffer.close()
    return self._join_all(timeout, 'stop',
                          'orphaned as daemon threads')

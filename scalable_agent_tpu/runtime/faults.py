"""Deterministic cross-layer fault injection.

The robustness layer (health watchdog, checkpoint integrity ladder,
fleet respawn, transport reconnect) is only trustworthy if its failure
paths EXECUTE — in CI, deterministically, not just in a post-mortem.
This module is the one place that knows how to break the pipeline on
purpose:

- `FaultPlan`: a seedable schedule of `Fault`s keyed by (site, event
  index). Each injection site keeps a monotone event counter; a fault
  fires when the counter hits its index. Same plan + same workload ⇒
  same faults, every run (`scripts/chaos.py` asserts recovery SLOs on
  top of this).
- Injection sites threaded through the real code paths (no mocks — the
  production error handling is what executes):

    env_step          FaultyEnv wrapper (driver.make_fleet wraps when a
                      plan covers the site): 'raise' kills the actor
                      (fleet must respawn), 'hang' wedges it for
                      `param` seconds (stall detection must respawn).
    transport_send    RemoteActorClient._rpc: 'drop' closes the socket,
                      'garbage'/'truncate' first ship a corrupt frame
                      the learner's ingest must survive (and
                      quarantine), then drop. All surface as OSError so
                      the actor's reconnect/backoff path runs.
    checkpoint_save   Checkpointer.save: the just-written newest step
                      is corrupted on disk and the last-known-good
                      marker is NOT advanced — a save interrupted
                      mid-write. `restore_latest` must fall back.
    nan_burst         driver.train: the staged batch's rewards become
                      NaN for the step — the loss/grads go non-finite
                      and the learner's device-side guard + watchdog
                      ladder must skip/roll back.
    slot_exhaustion   InferenceServer._acquire_slot: the acquire is
                      forced down the contended admission path (parked
                      waitlist) even when slots are free — the
                      block/shed/grow degrade machinery must execute,
                      never the old raise-on-exhaustion.
    preempt_signal    driver.train loop (one event per learner step):
                      a fired fault requests the preemption drain —
                      SIGTERM made deterministic for the chaos SLOs
                      (quiesce → flush → verified checkpoint →
                      resume_manifest.json).
    slow_learner      driver.train loop: 'hang' sleeps `param` seconds
                      in the step path, so the trajectory buffer fills
                      and producer-side backpressure (actor put
                      blocking, ingest ack delay, staleness growth)
                      must engage instead of unbounded queueing.
    conn_partition    RemoteActorClient._rpc (round 11): 'blackhole'
                      goes silent for `param` seconds WITHOUT closing
                      the socket — the half-open shape a network
                      partition/dead NAT entry produces. The learner's
                      idle reaper must reap the silent connection
                      within its budget; the client resumes after the
                      partition "heals" and its next send finds the
                      reaped socket (reconnect window runs).
    conn_delay        RemoteActorClient._rpc: 'delay' sleeps exactly
                      `param` seconds before the send; 'jitter'
                      sleeps a seeded U[0, param] — injected transport
                      latency the liveness machinery must tolerate
                      WITHOUT reaping (delay < idle window).
    learner_crash     driver.train loop, one event per consumed batch:
                      'kill' hard-aborts the process with SIGKILL — no
                      finally blocks, no drain, no 'bye' frame.
                      kill -9 / OOM made deterministic; only ever
                      scheduled against a learner running as a CHILD
                      process (scripts/chaos.py run_partition_storm),
                      which then restarts it and asserts the
                      restore-from-LAST_GOOD + fleet re-attach SLOs.
    wire_bitflip      RemoteActorClient._rpc OOB sends (round 12):
                      'flip' flips ONE seeded bit in the largest raw
                      buffer of the outgoing unroll frame AFTER the
                      v7 CRC trailer was computed — a frame that still
                      PARSES (the flip lands in the frame-stack bytes,
                      not the pickle skeleton), which is exactly the
                      silent corruption the CRC exists to catch.
                      Distinct from transport_send 'garbage' (which
                      cannot parse and trips the quarantine path).
                      The sender's own unroll is never touched (the
                      damaged segment is a copy), so the scripted
                      re-send ships clean bytes.
    publish_corrupt   TrajectoryIngestServer._make_blob (round 12):
                      flips one seeded bit in a float leaf of the
                      params snapshot AFTER the content digest was
                      computed but BEFORE serialization — host-memory
                      rot between device_get and the wire. The frame
                      CRC is consistent with the corrupted bytes (it
                      is computed over them), so only the client's
                      digest check before update_params can catch it.
    ckpt_bitrot       Checkpointer.save (round 12): flips one byte in
                      the largest file of the JUST-COMMITTED step
                      AFTER its digests were recorded and LAST_GOOD
                      advanced — disk rot on a step every marker calls
                      good. Only the restore ladder's digest
                      verification can catch it (the save already
                      verified; structure stays intact).
    replica_divergence  driver.train (round 12), one event per step:
                      perturbs ONE data-parallel replica's input to
                      the in-graph SDC param fingerprint (the probe
                      lane of train_parallel.make_sdc_fingerprint_fn).
                      A GSPMD program cannot make a logically
                      replicated array actually diverge — real SDC is
                      a hardware fault below the program — so the
                      injection perturbs the detector's per-replica
                      view instead, driving the IDENTICAL detection →
                      incident → rollback path a truly diverged
                      replica would: fingerprints disagree, health flags the
                      step, the ladder rolls back (re-replicating
                      params from the checkpoint — the real-SDC fix).

The plan is installed process-globally (`install`/`clear`); sites are
consulted via `fire(site)` which is a no-op returning None when no
plan is active (zero overhead on production paths). Multi-process
topologies (remote actor children) ship the plan through the
`SA_FAULT_PLAN` env var as JSON (`to_json`/`from_json`) and install it
themselves at startup.

Determinism note: event counters are global per site. When several
actor threads share a site ('env_step'), WHICH thread draws the firing
index depends on scheduling, but the NUMBER and KIND of faults fired
is exactly the schedule — the property the chaos SLOs assert on.
"""

import dataclasses
import json
import os
import socket
import struct
import threading
import time
from typing import Dict, List, Optional

SITES = ('env_step', 'transport_send', 'checkpoint_save', 'nan_burst',
         'slot_exhaustion', 'preempt_signal', 'slow_learner',
         'conn_partition', 'conn_delay', 'learner_crash',
         'wire_bitflip', 'publish_corrupt', 'ckpt_bitrot',
         'replica_divergence')

_LEN = struct.Struct('>Q')


class InjectedFault(RuntimeError):
  """An exception raised by fault injection (never by real code) —
  recovery paths can tell scripted damage from organic failures."""


@dataclasses.dataclass(frozen=True)
class Fault:
  site: str    # one of SITES
  index: int   # the site's event counter value at which to fire
  kind: str    # site-specific: raise|hang|drop|garbage|truncate|
               # interrupt|nan
  param: float = 0.0  # kind-specific (hang seconds, ...)

  def __post_init__(self):
    if self.site not in SITES:
      raise ValueError(f'unknown fault site {self.site!r} '
                       f'(sites: {SITES})')


class FaultPlan:
  """A deterministic schedule of faults + per-site event counters.

  Thread-safe: `fire` is called from actor threads, the learner loop,
  and checkpoint saves concurrently.
  """

  def __init__(self, faults: List[Fault], seed: int = 0):
    self._seed = int(seed)
    self._table: Dict[str, Dict[int, Fault]] = {}
    for f in faults:
      self._table.setdefault(f.site, {})[int(f.index)] = f
    self._counters: Dict[str, int] = {site: 0 for site in SITES}
    self._fired: Dict[str, int] = {site: 0 for site in SITES}
    self._lock = threading.Lock()

  @property
  def seed(self) -> int:
    return self._seed

  def faults(self) -> List[Fault]:
    return sorted((f for per in self._table.values()
                   for f in per.values()),
                  key=lambda f: (f.site, f.index))

  def covers(self, site: str) -> bool:
    """Whether any fault targets `site` (drives e.g. whether envs get
    wrapped at all — uncovered sites stay zero-cost)."""
    return bool(self._table.get(site))

  def fire(self, site: str) -> Optional[Fault]:
    """Advance `site`'s event counter; return the fault scheduled at
    the pre-advance index, if any."""
    with self._lock:
      idx = self._counters[site]
      self._counters[site] = idx + 1
      fault = self._table.get(site, {}).get(idx)
      if fault is not None:
        self._fired[site] += 1
      return fault

  def stats(self) -> Dict[str, Dict[str, int]]:
    with self._lock:
      return {site: {'events': self._counters[site],
                     'fired': self._fired[site],
                     'scheduled': len(self._table.get(site, {}))}
              for site in SITES}

  # --- serialization (cross-process: SA_FAULT_PLAN env var) ---

  def to_json(self) -> str:
    return json.dumps({'seed': self._seed,
                       'faults': [dataclasses.asdict(f)
                                  for f in self.faults()]})

  @classmethod
  def from_json(cls, payload: str) -> 'FaultPlan':
    obj = json.loads(payload)
    return cls([Fault(**f) for f in obj['faults']],
               seed=obj.get('seed', 0))

  @classmethod
  def storm(cls, seed: int,
            env_raise_at: Optional[int] = None,
            env_hang_at: Optional[int] = None,
            env_hang_secs: float = 3.0,
            transport: Optional[List[str]] = None,
            transport_start: int = 3,
            transport_stride: int = 4,
            nan_burst_at: Optional[int] = None,
            nan_burst_len: int = 0,
            checkpoint_interrupt_at: Optional[int] = None,
            slot_exhaustion_at: Optional[int] = None,
            slot_exhaustion_len: int = 0,
            preempt_at: Optional[int] = None,
            slow_learner_at: Optional[int] = None,
            slow_learner_len: int = 0,
            slow_learner_secs: float = 0.5,
            conn_partition_at: Optional[int] = None,
            conn_partition_secs: float = 3.0,
            conn_delay: Optional[List[int]] = None,
            conn_delay_secs: float = 0.2,
            learner_crash_at: Optional[int] = None,
            wire_bitflip: Optional[List[int]] = None,
            publish_corrupt_at: Optional[int] = None,
            publish_corrupt_len: int = 1,
            ckpt_bitrot_at: Optional[int] = None,
            replica_divergence_at: Optional[int] = None,
            replica_divergence_len: int = 0
            ) -> 'FaultPlan':
    """The scripted multi-fault storm chaos.py runs: one builder so
    the schedule is a pure function of its arguments (+ seed, which
    only perturbs garbage payload content, not the schedule)."""
    faults: List[Fault] = []
    if env_raise_at is not None:
      faults.append(Fault('env_step', env_raise_at, 'raise'))
    if env_hang_at is not None:
      faults.append(Fault('env_step', env_hang_at, 'hang',
                          param=env_hang_secs))
    for i, kind in enumerate(transport or []):
      faults.append(Fault('transport_send',
                          transport_start + i * transport_stride, kind))
    for i in range(nan_burst_len):
      faults.append(Fault('nan_burst', (nan_burst_at or 0) + i, 'nan'))
    if checkpoint_interrupt_at is not None:
      faults.append(Fault('checkpoint_save', checkpoint_interrupt_at,
                          'interrupt'))
    for i in range(slot_exhaustion_len):
      faults.append(Fault('slot_exhaustion',
                          (slot_exhaustion_at or 0) + i, 'force'))
    if preempt_at is not None:
      faults.append(Fault('preempt_signal', preempt_at, 'drain'))
    for i in range(slow_learner_len):
      faults.append(Fault('slow_learner', (slow_learner_at or 0) + i,
                          'hang', param=slow_learner_secs))
    if conn_partition_at is not None:
      faults.append(Fault('conn_partition', conn_partition_at,
                          'blackhole', param=conn_partition_secs))
    for idx in conn_delay or []:
      faults.append(Fault('conn_delay', idx, 'delay',
                          param=conn_delay_secs))
    if learner_crash_at is not None:
      faults.append(Fault('learner_crash', learner_crash_at, 'kill'))
    for idx in wire_bitflip or []:
      faults.append(Fault('wire_bitflip', idx, 'flip'))
    if publish_corrupt_at is not None:
      # A LENGTH, not one shot: publishes are cached per version and
      # replaced on a cadence — a single corrupt blob can be
      # superseded before any client fetches it, so the storm
      # corrupts a RUN of consecutive publishes to guarantee the
      # fleet meets one.
      for i in range(max(publish_corrupt_len, 1)):
        faults.append(Fault('publish_corrupt', publish_corrupt_at + i,
                            'flip'))
    if ckpt_bitrot_at is not None:
      faults.append(Fault('ckpt_bitrot', ckpt_bitrot_at, 'flip'))
    for i in range(replica_divergence_len):
      faults.append(Fault('replica_divergence',
                          (replica_divergence_at or 0) + i, 'perturb'))
    return cls(faults, seed=seed)


# --- process-global registry ---

_active_lock = threading.Lock()
_active: Optional[FaultPlan] = None

PLAN_ENV_VAR = 'SA_FAULT_PLAN'


def install(plan: Optional[FaultPlan]) -> None:
  global _active
  with _active_lock:
    _active = plan


def clear() -> None:
  install(None)


def active() -> Optional[FaultPlan]:
  return _active


def install_from_env() -> Optional[FaultPlan]:
  """Install the plan serialized in SA_FAULT_PLAN, if any (chaos.py's
  remote-actor child calls this before run_remote_actor)."""
  payload = os.environ.get(PLAN_ENV_VAR)
  if not payload:
    return None
  plan = FaultPlan.from_json(payload)
  install(plan)
  return plan


def fire(site: str) -> Optional[Fault]:
  """Consult the active plan; None when no plan is installed (the
  common production case — one global read, no lock)."""
  plan = _active
  if plan is None:
    return None
  return plan.fire(site)


# --- site: env_step ---


class FaultyEnv:
  """Environment wrapper consulting the plan on every step.

  'raise' propagates an InjectedFault out of env.step — exactly the
  shape of an organic env crash (the fleet's respawn path runs).
  'hang' sleeps `param` seconds while the step is in flight — the
  shape of a wedged simulator (heartbeats go stale; stall detection
  must orphan the thread and respawn the slot).
  """

  def __init__(self, env):
    self._env = env
    if hasattr(env, 'step_send'):
      # A process-hosted env stepped in two halves (py_process.ProxyEnv)
      # takes its fault where the step starts; `step_receive` reaches
      # the env through __getattr__. An env without the halves must
      # not grow one here: the actor asks by hasattr.
      self.step_send = self._step_send

  def initial(self):
    return self._env.initial()

  @staticmethod
  def _fire():
    fault = fire('env_step')
    if fault is not None:
      if fault.kind == 'raise':
        raise InjectedFault('env_step: injected crash')
      if fault.kind == 'hang':
        time.sleep(float(fault.param))
      # unknown kinds fall through: a typo'd schedule should not
      # silently change the no-fault behavior mid-run

  def step(self, action):
    self._fire()
    return self._env.step(action)

  def _step_send(self, action):
    self._fire()
    return self._env.step_send(action)

  def close(self):
    return self._env.close()

  def __getattr__(self, name):
    if name == 'attach_block':
      # A group steps the envs of a shared block in one pass, with no
      # step of a member's own to fault: an env that takes faults
      # steps by its own calls.
      raise AttributeError(name)
    return getattr(self._env, name)


def maybe_wrap_env(env):
  """Wrap `env` iff the active plan targets env_step (otherwise the
  production object is returned untouched — zero indirection)."""
  plan = _active
  if plan is not None and plan.covers('env_step'):
    return FaultyEnv(env)
  return env


# --- site: transport_send ---


def apply_transport_fault(fault: Fault, sock: socket.socket,
                          seed: int = 0) -> None:
  """Damage `sock` per `fault` and raise the OSError the caller's
  reconnect path expects. 'garbage' ships a well-framed message of
  seeded random bytes (the receiver must fail parsing and quarantine
  the connection, not crash); 'truncate' claims more bytes than it
  sends (the receiver sees EOF mid-message); 'drop' just dies
  mid-conversation."""
  import numpy as np
  try:
    if fault.kind == 'garbage':
      rng = np.random.RandomState((seed + fault.index) % (2 ** 31))
      payload = rng.bytes(256)
      sock.sendall(_LEN.pack(len(payload)) + payload)
    elif fault.kind == 'truncate':
      rng = np.random.RandomState((seed + fault.index) % (2 ** 31))
      payload = rng.bytes(128)
      sock.sendall(_LEN.pack(len(payload) * 4) + payload)
    # 'drop' and unknown kinds: no bytes, just the close below.
  except OSError:
    pass  # the peer may already be gone; the raise below still runs
  try:
    sock.close()
  except OSError:
    pass
  raise ConnectionError(
      f'injected transport fault: {fault.kind} (index {fault.index})')


# --- sites: conn_partition / conn_delay (round 11) ---


def apply_conn_partition(fault: Fault) -> None:
  """Blackhole the connection for `fault.param` seconds: the caller
  goes completely silent — no send, no recv, NO close — exactly the
  half-open shape a network partition produces (the peer's socket
  stays ESTABLISHED with nothing flowing). Returns when the partition
  'heals'; the caller then proceeds normally and discovers whatever
  the other side did meanwhile (idle reap → RST on the next send)."""
  time.sleep(float(fault.param))


def apply_conn_delay(fault: Fault, seed: int = 0) -> None:
  """Injected transport latency: 'delay' sleeps exactly `param`
  seconds (deterministic — tests assert the floor); 'jitter' sleeps a
  seeded U[0, param]."""
  if fault.kind == 'jitter':
    import numpy as np
    rng = np.random.RandomState((seed + fault.index) % (2 ** 31))
    time.sleep(float(rng.uniform(0.0, float(fault.param))))
  else:
    time.sleep(float(fault.param))


# --- site: learner_crash ---


def hard_crash(fault: Fault) -> None:
  """kill -9 the current process: no exception unwind, no finally
  blocks, no drain, no 'bye' frame — the OOM-killer/preempt shape the
  restart story (docs/RUNBOOK.md §8) must survive. Logged first so
  the chaos harness can tell a scheduled crash from an organic one."""
  import logging
  import signal
  logging.getLogger('scalable_agent_tpu').error(
      'learner_crash fault firing (index %d): hard-killing pid %d',
      fault.index, os.getpid())
  os.kill(os.getpid(), signal.SIGKILL)


# --- site: checkpoint_save ---


def corrupt_checkpoint_step(directory: str, step: int) -> List[str]:
  """Simulate a save killed mid-write: truncate every non-trivial file
  of the step's directory to half its bytes (metadata/commit markers
  are left in place, so the step still LISTS as the newest — the
  dead-end `restore_latest` used to hit). Returns the damaged paths.
  Shared by the checkpoint_save site and the checkpoint tests."""
  step_dir = None
  for name in os.listdir(directory):
    path = os.path.join(directory, name)
    if os.path.isdir(path) and name.split('.')[-1] == str(step):
      step_dir = path
      break
    if os.path.isdir(path) and name == str(step):
      step_dir = path
      break
  if step_dir is None:
    raise FileNotFoundError(
        f'no step directory for step {step} under {directory}')
  damaged = []
  for root, _, files in os.walk(step_dir):
    for fname in files:
      fpath = os.path.join(root, fname)
      size = os.path.getsize(fpath)
      if size >= 32:
        with open(fpath, 'r+b') as f:
          f.truncate(size // 2)
        damaged.append(fpath)
  return damaged


# --- site: wire_bitflip ---


def apply_wire_bitflip(fault: Fault, segments, seed: int = 0):
  """One seeded bit flip in the LARGEST raw-buffer segment of an
  outgoing OOB frame — after the CRC trailer was computed, so the
  receiver's v7 check sees exactly the silent-corruption shape: a
  frame that parses (the flip lands in array bytes, not the pickle
  skeleton) with a stale trailer. Returns a NEW segment list; the
  caller's unroll (aliased by the other segments) is never touched,
  so its scripted re-send ships clean bytes."""
  import numpy as np
  from scalable_agent_tpu import integrity
  if len(segments) < 2:
    return segments  # no raw buffers to damage (tiny frame): no-op
  idx = max(range(1, len(segments)),
            key=lambda i: memoryview(segments[i]).nbytes)
  damaged = bytearray(segments[idx])
  rng = np.random.RandomState((seed + fault.index) % (2 ** 31))
  byte, bit = integrity.flip_bit(
      damaged, int(rng.randint(0, max(len(damaged) * 8, 1))))
  import logging
  logging.getLogger('scalable_agent_tpu').warning(
      'wire_bitflip fault firing (index %d): flipped bit %d of byte '
      '%d in a %d-byte frame segment', fault.index, bit, byte,
      len(damaged))
  return segments[:idx] + [memoryview(damaged)] + segments[idx + 1:]


# --- site: publish_corrupt ---


def corrupt_params_tree(fault: Fault, params, seed: int = 0):
  """Return `params` with ONE seeded bit flipped in its largest leaf
  — host-memory rot between the digest computation and the wire
  serialization. The caller computes the content digest BEFORE this
  runs, so the shipped blob's frame CRC is self-consistent and only
  the receiving client's digest check can catch the damage. Leaves
  other than the victim alias the input (no tree copy). Dtype is NOT
  filtered on: the wire form may be ml_dtypes.bfloat16 (numpy kind
  'V'), and rot does not care what it flips."""
  import jax
  import numpy as np
  from scalable_agent_tpu import integrity
  leaves, treedef = jax.tree_util.tree_flatten(params)
  candidates = [i for i, leaf in enumerate(leaves)
                if np.asarray(leaf).size > 0]
  if not candidates:
    return params
  victim = max(candidates, key=lambda i: np.asarray(leaves[i]).nbytes)
  arr = np.array(leaves[victim], copy=True)
  raw = bytearray(arr.tobytes())
  rng = np.random.RandomState((seed + fault.index) % (2 ** 31))
  integrity.flip_bit(raw, int(rng.randint(0, len(raw) * 8)))
  leaves[victim] = np.frombuffer(
      bytes(raw), dtype=arr.dtype).reshape(arr.shape)
  import logging
  logging.getLogger('scalable_agent_tpu').warning(
      'publish_corrupt fault firing (index %d): flipped one bit in a '
      '%d-byte param leaf after digest', fault.index, len(raw))
  return jax.tree_util.tree_unflatten(treedef, leaves)


# --- site: ckpt_bitrot ---


def bitrot_checkpoint_step(directory: str, step: int,
                           seed: int = 0) -> str:
  """Flip ONE byte mid-file in the largest file of a COMMITTED step
  directory — disk rot after the save verified and LAST_GOOD advanced
  (distinct from corrupt_checkpoint_step's half-truncated
  mid-write shape, which the PR 2 ladder already catches without
  digests). Returns the damaged path."""
  import numpy as np
  step_dir = None
  for name in os.listdir(directory):
    path = os.path.join(directory, name)
    if os.path.isdir(path) and (name == str(step)
                                or name.split('.')[-1] == str(step)):
      step_dir = path
      break
  if step_dir is None:
    raise FileNotFoundError(
        f'no step directory for step {step} under {directory}')
  candidates = []
  for root, _, files in os.walk(step_dir):
    for fname in files:
      fpath = os.path.join(root, fname)
      candidates.append((os.path.getsize(fpath), fpath))
  if not candidates:
    raise FileNotFoundError(f'step {step} directory is empty')
  size, target = max(candidates)
  rng = np.random.RandomState((seed + step) % (2 ** 31))
  offset = int(rng.randint(0, max(size, 1)))
  with open(target, 'r+b') as f:
    f.seek(offset)
    byte = f.read(1) or b'\x00'
    f.seek(offset)
    f.write(bytes((byte[0] ^ (1 << int(rng.randint(0, 8))),)))
  import logging
  logging.getLogger('scalable_agent_tpu').warning(
      'ckpt_bitrot fault: flipped one bit at offset %d of %s', offset,
      target)
  return target


# --- site: nan_burst ---


def poison_batch(batch):
  """Return `batch` with its rewards replaced by NaN (device-side op:
  the batch is already staged). Drives a non-finite loss/grad through
  the REAL loss, so the watchdog sees exactly what organic divergence
  produces."""
  import jax.numpy as jnp
  env_outputs = batch.env_outputs._replace(
      reward=jnp.full_like(batch.env_outputs.reward, jnp.nan))
  return batch._replace(env_outputs=env_outputs)


def maybe_poison_batch(batch):
  """Consult the nan_burst site once (one learner step = one event);
  poison when scheduled."""
  fault = fire('nan_burst')
  if fault is not None:
    return poison_batch(batch), True
  return batch, False

"""Checkpoint / resume (Orbax-backed).

Reference semantics (reference: experiment.py ≈L570
`MonitoredTrainingSession(checkpoint_dir=logdir, save_checkpoint_secs=600)`;
SURVEY §5.4): periodically save ALL global state — network params,
optimizer slots, and the environment-frame counter — and restore the
latest on startup. Actor-local state (LSTM carries, env state) is
intentionally NOT checkpointed: unrolls straddling a restart are lost,
exactly as upstream.

The TPU build checkpoints the whole `learner.TrainState` pytree
(params, opt_state, update_steps) via Orbax. `update_steps` × frames
per step reproduces the reference's `num_environment_frames` global
step. Sharded (multi-chip) states round-trip: Orbax records shardings
and restores to the same placements when given the live state as the
abstract target.
"""

import contextlib
import functools
import importlib.metadata
import json
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

import jax


@contextlib.contextmanager
def _one_scan_of_installed_files():
  """Orbax imports google.cloud.logging, and each google.* package it
  pulls in runs api_core's `check_python_version` as it is imported:
  every call asks `importlib.metadata.packages_distributions()`, which
  stats every file of every installed distribution. On the chip
  machine's file system that is about 10 s a call, twice, before a run
  has built anything (root PERF.md section 6, PR 30: found in the
  main thread's stack samples of set-up). The answer cannot change
  between two imports of one statement, so it is computed once."""
  real = importlib.metadata.packages_distributions
  importlib.metadata.packages_distributions = functools.cache(real)
  try:
    yield
  finally:
    importlib.metadata.packages_distributions = real


with _one_scan_of_installed_files():
  import orbax.checkpoint as ocp

from scalable_agent_tpu import integrity, telemetry
from scalable_agent_tpu.learner import TrainState
from scalable_agent_tpu.runtime import faults as faults_lib

log = logging.getLogger('scalable_agent_tpu')


class CheckpointStructureError(ValueError):
  """The latest checkpoint's tree structure does not match the state
  built from the current config (see the message for likely flags)."""


class CheckpointCorruption(RuntimeError):
  """A retained step's on-disk CONTENT does not match the digests its
  verified save recorded (round 12): bit rot after commit. Orbax's own
  restore only catches partial/structural damage — a flipped byte
  inside an array file restores 'successfully' as garbage params. The
  restore ladder classifies this as per-step corruption (falls back
  to the previous retained step), never as a config mismatch.

  The message deliberately avoids every _STRUCTURE_MARKERS phrase so
  `_looks_structural` routes it down the corruption arm."""


# Markers Orbax puts in tree-STRUCTURE mismatch messages (vs corrupt/
# partial files, missing arrays, I/O errors): only these earn the
# config-flag guidance — flag advice on a genuinely corrupt checkpoint
# sends operators down the wrong path (ADVICE r3). Deliberately
# NARROW: generic words like 'missing'/'key'/'mismatch' also appear in
# partial-save messages ('missing commit file', 'checksum mismatch'),
# which must get the corruption wording. 'dict key mismatch' is the
# newer-Orbax spelling of the restore-target/on-disk tree diff
# (jax tree_util raises it before any file is read).
_STRUCTURE_MARKERS = (
    'structure', 'tree', 'pytree', 'not found in checkpoint',
    'do not match', 'dict key mismatch')


def _looks_structural(e) -> bool:
  """Whether a restore failure looks like a tree-STRUCTURE mismatch
  (config-flag guidance, no fallback — older steps share the config)
  rather than corrupt/partial files (corruption guidance, and the
  restore ladder retries the previous retained step). KeyError is
  structural by TYPE (its str is just the missing key, which need not
  contain any marker) — EXCEPT Orbax's missing-ITEM KeyError ('Item
  "default" was not found ... Available items: []'), which means the
  step directory lost its payload (partial save/eviction): that is
  per-step damage the ladder must fall back past, not a config
  mismatch."""
  msg = str(e).lower()
  if isinstance(e, KeyError):
    return 'available items' not in msg
  return any(marker in msg for marker in _STRUCTURE_MARKERS)


def _wrap_structure_error(e, directory, step):
  """Re-raise a restore failure with the likely config-flag causes.

  The agent's param-tree STRUCTURE is a function of the config
  (VERDICT r2 W7): the raw Orbax mismatch error names neither the flag
  nor the fix, so operators hitting the documented migration footgun
  (`config.use_instruction` None-auto) got a dead end. The message is
  sniffed first so non-structural failures (corrupt/partial files)
  don't get misleading flag advice."""
  base = (f'could not restore checkpoint step {step} from {directory}: '
          f'{e}\n')
  if _looks_structural(e):
    guidance = (
        'This looks like a tree-structure mismatch: the param tree is '
        'a function of the config. Usual cause: --use_instruction '
        '(default None = auto by level name — a checkpoint trained '
        'with the instruction encoder needs an explicit '
        '--use_instruction=true when resumed/evaluated on a '
        'non-language level, and vice versa). Also structure-changing: '
        '--torso, --use_popart, --pixel_control_cost. Compare your '
        "flags against the run's config.json saved next to the "
        'checkpoints.')
  else:
    guidance = (
        'This does not look like a tree-structure mismatch — the '
        'checkpoint files may be corrupt or partially written (e.g. a '
        'save interrupted mid-write). Try the previous retained step, '
        'or if the config might have changed, compare your flags '
        "against the run's config.json saved next to the checkpoints.")
  raise CheckpointStructureError(base + guidance) from e


class Checkpointer:
  """Thin lifecycle wrapper over an Orbax CheckpointManager.

  Args:
    directory: checkpoint root (the reference's --logdir).
    max_to_keep: retained checkpoints (oldest pruned).
    save_interval_secs: wall-clock throttle — `maybe_save` is a no-op
      until this many seconds passed since the last save (reference
      save_checkpoint_secs=600).
  """

  def __init__(self, directory: str, max_to_keep: int = 3,
               save_interval_secs: float = 600.0,
               verify_digests: bool = True,
               registry=None, mesh=None):
    # Sharding registry + mesh (round 19, parallel/sharding.py): when
    # provided, every verified save also records the REGISTRY's view
    # of the param placements (SHARDING_{step}.json — rule set, the
    # {path: spec} manifest, its content digest), and restores warn
    # when the on-disk manifest disagrees with what this run would
    # resolve — the checkpoint plane's sharding truth is the same
    # single source as the learner's, and the manifest is the on-disk
    # half of cross-topology resharding (ROADMAP item 3; see
    # `registry_restore_targets`).
    self._registry = registry
    self._mesh = mesh
    self._directory = os.path.abspath(directory)
    os.makedirs(self._directory, exist_ok=True)
    self._manager = ocp.CheckpointManager(
        self._directory,
        options=ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep, create=True))
    self._save_interval_secs = save_interval_secs
    self._last_save_time: Optional[float] = None
    self._last_good_path = os.path.join(self._directory, 'LAST_GOOD')
    # Content-digest ledger (round 12; config.ckpt_digests): verified
    # saves record a per-file CRC of the committed step; the restore
    # ladder re-verifies before trusting a step, extending the PR 2
    # fallback ladder from partial/structural damage to BIT ROT —
    # orbax restores a flipped byte inside an array file
    # 'successfully', as garbage params.
    self._verify_digests = bool(verify_digests)
    # Integrity-ladder observability (driver summaries + tests).
    self.save_errors = 0
    self.last_save_error: Optional[BaseException] = None
    self.restore_fallbacks = 0
    # Steps the ladder refused specifically for digest (bit-rot)
    # mismatches — counted separately from structural/partial
    # fallbacks so summaries can alarm on silent disk corruption.
    self.digest_fallbacks = 0
    # Unified-registry view (round 13, telemetry.py): lazy gauges over
    # the ladder counters — same numbers as the driver summaries, read
    # by the drain manifest / flight recorder / remote 'stats' from
    # one source of truth.
    self._gauges = [
        telemetry.gauge('checkpoint/save_errors',
                        fn=lambda: self.save_errors),
        telemetry.gauge('checkpoint/restore_fallbacks',
                        fn=lambda: self.restore_fallbacks),
        telemetry.gauge('checkpoint/digest_fallbacks',
                        fn=lambda: self.digest_fallbacks),
    ]

  def save(self, state: TrainState, step: Optional[int] = None,
           force: bool = False) -> bool:
    """Save now and VERIFY completion. `step` defaults to the state's
    own update counter.

    Returns whether a checkpoint was written and finalized. A step
    that already exists is skipped (returns False, even with
    force=True — Orbax raises StepAlreadyExistsError rather than
    overwriting); the throttle clock only resets on a real write so
    `maybe_save` stays truthful.

    This blocks on `wait_until_finished` so save-side errors surface
    HERE (logged + recorded on `save_errors`/`last_save_error`)
    instead of getting lost until close(); a failed save does not
    raise — older retained steps still cover a restore, which is the
    integrity ladder's whole point. Only a save that completed without
    error advances the LAST_GOOD marker, so 'restorable' and 'newest'
    stay distinguishable (restore_last_good reads the marker)."""
    if step is None:
      step = int(jax.device_get(state.update_steps))
    if step in self._manager.all_steps():
      return False  # force=True raises StepAlreadyExistsError otherwise
    # Seconds of the calling thread (the learner's): an activity, so
    # that an actor step held up under it can be laid at its door
    # (telemetry.excess).
    with telemetry.activity('learner/checkpoint'):
      return self._write(state, step, force)

  def _write(self, state: TrainState, step: int, force: bool) -> bool:
    """`save`, past the check that the step is new."""
    saved = bool(self._manager.save(
        step, args=ocp.args.StandardSave(state), force=force))
    if not saved:
      return False
    # Fault-injection site (runtime/faults.py 'checkpoint_save'): a
    # fired fault simulates the process dying mid-write — the step's
    # files are damaged on disk and the marker does NOT advance.
    fault = faults_lib.fire('checkpoint_save')
    try:
      self._manager.wait_until_finished()
    except Exception as e:
      # Throttle clock deliberately NOT reset on this path: the next
      # maybe_save retries immediately instead of training another
      # full save_interval_secs with no checkpoint after a transient
      # storage blip.
      self.save_errors += 1
      self.last_save_error = e
      log.exception(
          'checkpoint save at step %d FAILED to finalize (marker not '
          'advanced; older retained steps remain restorable)', step)
      return False
    self._last_save_time = time.monotonic()
    if fault is not None:
      damaged = faults_lib.corrupt_checkpoint_step(self._directory,
                                                   step)
      self.save_errors += 1
      self.last_save_error = faults_lib.InjectedFault(
          f'checkpoint_save interrupted at step {step}')
      log.warning('injected checkpoint-save interrupt at step %d '
                  '(%d files damaged, LAST_GOOD not advanced)', step,
                  len(damaged))
      return True
    digests = self._record_digests(step)
    self._record_sharding_manifest(step, state)
    self._mark_last_good(step, digests)
    # Fault site 'ckpt_bitrot' (round 12): flip one byte in a file of
    # the step JUST committed — AFTER its digests were recorded and
    # LAST_GOOD advanced. Every marker now calls this step good; only
    # the restore ladder's digest verification can catch it.
    rot = faults_lib.fire('ckpt_bitrot')
    if rot is not None:
      plan = faults_lib.active()
      faults_lib.bitrot_checkpoint_step(
          self._directory, step, seed=plan.seed if plan else 0)
    return True

  # --- content-digest ledger (round 12) ---

  def _digest_path(self, step: int) -> str:
    return os.path.join(self._directory, f'DIGEST_{int(step)}.json')

  def _step_dir(self, step: int) -> Optional[str]:
    """The on-disk directory of a retained step (orbax lays steps out
    as '<step>' or '<prefix>.<step>' depending on version)."""
    for name in os.listdir(self._directory):
      path = os.path.join(self._directory, name)
      if os.path.isdir(path) and (name == str(step)
                                  or name.split('.')[-1] == str(step)):
        return path
    return None

  def _record_digests(self, step: int) -> Optional[Dict]:
    """Digest every file of a just-verified step and persist the
    ledger (atomic, process 0). Returns the digest dict (also embedded
    in the LAST_GOOD manifest). Best-effort: a digest failure must
    not fail the save — it only costs bit-rot coverage for this
    step."""
    if not self._verify_digests:
      return None
    if jax.process_index() != 0:
      # Only process 0 writes the ledger (and the LAST_GOOD manifest
      # that embeds it) — the other hosts must not re-read and
      # checksum the whole multi-GB step from shared storage for a
      # result nothing consumes.
      return None
    try:
      step_dir = self._step_dir(step)
      if step_dir is None:
        return None
      digests = {}
      for root, _, files in os.walk(step_dir):
        for fname in files:
          fpath = os.path.join(root, fname)
          rel = os.path.relpath(fpath, step_dir)
          digests[rel] = integrity.digest_record(
              integrity.file_digest(fpath))
      if jax.process_index() == 0:
        tmp = self._digest_path(step) + '.tmp'
        with open(tmp, 'w') as f:
          json.dump({'step': int(step), 'algo': integrity.CRC_ALGO,
                     'files': digests}, f)
        os.replace(tmp, self._digest_path(step))
        self._prune_digests()
      return digests
    except OSError:
      log.exception('could not record content digests for step %d '
                    '(bit-rot coverage lost for this step)', step)
      return None

  def _prune_digests(self) -> None:
    """Drop digest/sharding ledgers of steps no longer retained."""
    retained = {str(int(s)) for s in self._manager.all_steps()}
    for name in os.listdir(self._directory):
      for prefix in ('DIGEST_', 'SHARDING_'):
        if not (name.startswith(prefix) and name.endswith('.json')):
          continue
        if name[len(prefix):-len('.json')] not in retained:
          try:
            os.remove(os.path.join(self._directory, name))
          except OSError:
            pass

  # --- sharding manifest (round 19, parallel/sharding.py) ---

  def _sharding_path(self, step: int) -> str:
    return os.path.join(self._directory, f'SHARDING_{int(step)}.json')

  def _record_sharding_manifest(self, step: int, state) -> None:
    """Record the registry's {param_path: spec} view of this save
    (process 0, atomic). Best-effort like the digest ledger: a
    manifest failure must not fail the save — it only costs drift
    detection for this step."""
    if self._registry is None or jax.process_index() != 0:
      return
    try:
      specs = self._registry.describe(state.params, self._mesh)
      mesh_shape = (dict(self._mesh.shape)
                    if self._mesh is not None else None)
      payload = {
          'step': int(step),
          'rule_set': self._registry.rule_set,
          'mesh': mesh_shape,
          'specs': specs,
          'digest': integrity.digest_record(
              integrity.spec_table_digest(specs)),
      }
      tmp = self._sharding_path(step) + '.tmp'
      with open(tmp, 'w') as f:
        json.dump(payload, f, indent=1)
      os.replace(tmp, self._sharding_path(step))
    except (OSError, TypeError, ValueError):
      log.exception('could not record sharding manifest for step %d '
                    '(resharding drift detection lost for this step)',
                    step)

  def read_sharding_manifest(self, step: int) -> Optional[Dict]:
    """The recorded sharding manifest of a retained step, or None."""
    try:
      with open(self._sharding_path(step)) as f:
        return json.load(f)
    except (OSError, ValueError):
      return None

  def _warn_sharding_drift(self, step: int, restored) -> None:
    """Compare the restored step's recorded manifest against what THIS
    run's registry resolves; a mismatch means the checkpoint was laid
    out under different rules/topology. The restore itself is still
    correct — Orbax resharded into the pinned targets — so this warns
    rather than raises; it is the observability half of cross-topology
    resharding."""
    if self._registry is None or restored is None:
      return
    manifest = self.read_sharding_manifest(step)
    if manifest is None:
      return
    try:
      current = self._registry.describe(restored.params, self._mesh)
    except Exception:
      log.exception('sharding drift check failed for step %d', step)
      return
    recorded = manifest.get('specs', {})
    if recorded == current:
      return
    changed = sorted(
        set(recorded.items()) ^ set(current.items()))
    log.warning(
        'checkpoint step %d was saved under sharding rule set %r '
        '(mesh %s) but this run resolves %r — %d spec(s) differ '
        '(first: %s); Orbax resharded into the live placements, '
        'training continues on the new layout',
        step, manifest.get('rule_set'), manifest.get('mesh'),
        self._registry.rule_set, len(changed) // 2 + len(changed) % 2,
        changed[0] if changed else '?')

  def verify_step_digests(self, step: int) -> Optional[bool]:
    """Re-digest a retained step against its recorded ledger.

    Returns True (verified), None (no ledger / foreign algorithm —
    verification SKIPPED, logged), or raises CheckpointCorruption
    naming the first rotted file. A recorded file that has gone
    MISSING is corruption too (partial eviction under the marker)."""
    if not self._verify_digests:
      return None
    try:
      with open(self._digest_path(step)) as f:
        ledger = json.load(f)
    except (OSError, ValueError):
      return None  # pre-round-12 step (or foreign writer): no ledger
    files = ledger.get('files')
    if not isinstance(files, dict):
      return None
    step_dir = self._step_dir(step)
    if step_dir is None:
      raise CheckpointCorruption(
          f'checkpoint step {step} has a digest ledger but no step '
          'directory on disk')
    for rel, record in sorted(files.items()):
      fpath = os.path.join(step_dir, rel)
      try:
        value = integrity.file_digest(fpath)
      except OSError as e:
        raise CheckpointCorruption(
            f'checkpoint step {step}: recorded file {rel!r} is '
            f'unreadable ({e}) — content verification failed')
      verdict = integrity.verify_record(record, value)
      if verdict is None:
        log.warning(
            'checkpoint step %d: digest for %r recorded with a '
            'different algorithm (%r vs local %s) — content '
            'verification skipped', step, rel, record,
            integrity.CRC_ALGO)
        return None
      if not verdict:
        raise CheckpointCorruption(
            f'checkpoint step {step}: content digest verification '
            f'failed for {rel!r} (crc {value:08x} differs from the '
            f'recorded {int(record["crc"]):08x}) — bit rot after '
            'commit; this step cannot be trusted')
    return True

  def _mark_last_good(self, step: int,
                      digests: Optional[Dict] = None) -> None:
    """Atomically advance the LAST_GOOD marker (tmp + rename): only a
    save that verifiably finished earns it. Multi-host: process 0
    writes (shared checkpoint dirs must have one writer — same
    convention as the driver's config.json). The verified save's
    content digests ride the manifest (round 12), so the marker names
    not just WHICH step is good but what its bytes looked like when
    it earned the name."""
    if jax.process_index() != 0:
      return
    tmp = self._last_good_path + '.tmp'
    try:
      manifest = {'step': int(step),
                  'wall_time': round(time.time(), 3)}
      if digests is not None:
        manifest['digest_algo'] = integrity.CRC_ALGO
        manifest['digests'] = digests
      with open(tmp, 'w') as f:
        json.dump(manifest, f)
      os.replace(tmp, self._last_good_path)
    except OSError:
      log.exception('could not write LAST_GOOD marker for step %d',
                    step)

  def last_good_step(self) -> Optional[int]:
    """The step the LAST_GOOD marker names, if it is still retained
    (pruning can outrun the marker on long runs); None otherwise."""
    try:
      with open(self._last_good_path) as f:
        step = int(json.load(f)['step'])
    except (OSError, ValueError, KeyError, TypeError):
      return None
    return step if step in self._manager.all_steps() else None

  def should_save(self) -> bool:
    """Whether the save interval has elapsed (host-local wall clock).

    Multi-host callers MUST NOT act on this independently: clocks
    differ per host, Orbax saves are collective, and disagreeing hosts
    deadlock in the barrier sync. Broadcast process 0's decision
    (driver.train does) and pass it to `maybe_save(decision=...)`.
    The first call after construction starts the clock."""
    now = time.monotonic()
    if self._last_save_time is None:
      self._last_save_time = now
      return False
    return now - self._last_save_time >= self._save_interval_secs

  def maybe_save(self, state: TrainState, step: Optional[int] = None,
                 decision: Optional[bool] = None) -> bool:
    """Save iff the save interval elapsed (call freely from the learner
    loop), matching the reference's every-N-seconds hook. `decision`
    overrides the local clock (multi-host: broadcast from process 0)."""
    if decision is None:
      decision = self.should_save()
    if not decision:
      return False
    return self.save(state, step)

  def latest_step(self) -> Optional[int]:
    return self._manager.latest_step()

  def _restore_ladder(self, steps: List[int], restore_fn
                      ) -> Tuple[Optional[object], Optional[int]]:
    """Try `restore_fn(step)` down the given step list (newest first).

    The integrity ladder: a corrupt/partial step is logged and the
    previous retained step is tried (the dead-end `restore_latest`
    used to hit on a save interrupted mid-write); a STRUCTURE mismatch
    raises immediately with the config-flag guidance — older steps
    were written by the same config, so falling back cannot help and
    would only bury the real cause. Exhausting every step raises with
    the corruption guidance for the newest failure.

    Round 12: each rung first re-verifies the step's recorded content
    digests (`verify_step_digests`) — BIT ROT on a committed step
    restores 'successfully' through orbax as garbage params, so the
    ladder must refuse it before orbax ever reads it. Digest refusals
    are counted separately (`digest_fallbacks`)."""
    last_err: Optional[Tuple[int, BaseException]] = None
    for tried, step in enumerate(steps):
      try:
        self.verify_step_digests(step)
        restored = restore_fn(step)
      except Exception as e:
        if isinstance(e, CheckpointCorruption):
          self.digest_fallbacks += 1
        elif _looks_structural(e):
          _wrap_structure_error(e, self._directory, step)
        log.warning(
            'checkpoint step %d failed to restore (%s: %s); falling '
            'back to the previous retained step', step,
            type(e).__name__, e)
        if last_err is None:
          last_err = (step, e)
        continue
      if tried:
        self.restore_fallbacks += tried
        log.warning('restored checkpoint step %d after %d newer '
                    'corrupt/partial step(s)', step, tried)
      return restored, step
    _wrap_structure_error(last_err[1], self._directory, last_err[0])

  def restore_latest(self, target: TrainState) -> Optional[TrainState]:
    """Restore the most recent RESTORABLE checkpoint, or None if none
    exists. A corrupt/partial newest step falls back through older
    retained steps (see `_restore_ladder`).

    `target` is a concrete (or abstract shape/dtype/sharding) TrainState
    matching the saved structure — build it with `make_train_state` on
    the right mesh first; restored arrays land on the same placements.
    """
    steps = sorted(self._manager.all_steps(), reverse=True)
    if not steps:
      return None
    restored, step = self._restore_ladder(
        steps, self._make_full_restore_fn(target))
    self._warn_sharding_drift(step, restored)
    return restored

  def restore_last_good(self, target: TrainState
                        ) -> Optional[TrainState]:
    """Rollback restore (health.py's escalation ladder): the step the
    LAST_GOOD marker names first — 'known restorable', not merely
    'newest' — then every other retained step, newest first. None when
    nothing is restorable at all (the driver then halts)."""
    steps = sorted(self._manager.all_steps(), reverse=True)
    good = self.last_good_step()
    if good is not None:
      steps = [good] + [s for s in steps if s != good]
    if not steps:
      return None
    try:
      restored, step = self._restore_ladder(
          steps, self._make_full_restore_fn(target))
    except CheckpointStructureError:
      log.exception('rollback restore failed on every retained step')
      return None
    log.info('rolled back to checkpoint step %d', step)
    self._warn_sharding_drift(step, restored)
    return restored

  def rollback_step_choice(self) -> int:
    """The step a rollback SHOULD restore: last-known-good, else the
    newest retained, else -1 (nothing restorable). Multi-host rollback
    coordination: process 0's choice is broadcast and every host
    restores exactly that step via `restore_step` — the per-host
    ladder could diverge on host-local I/O errors, and a sharded
    restore is a cross-process collective that deadlocks if hosts
    enter it with different steps."""
    good = self.last_good_step()
    if good is not None:
      return good
    steps = self._manager.all_steps()
    return max(steps) if steps else -1

  def restore_step(self, step: int, target: TrainState) -> TrainState:
    """Single-step restore, NO ladder (the multi-host rollback path:
    every host must attempt the SAME step; a failure raises on all
    hosts together — the same exposure as the startup restore).
    Content digests still verify first: a bit-rotted rollback target
    must fail loudly on every host, not restore as garbage."""
    try:
      self.verify_step_digests(step)
      return self._make_full_restore_fn(target)(step)
    except Exception as e:
      _wrap_structure_error(e, self._directory, step)

  def _make_full_restore_fn(self, target: TrainState):
    def to_abstract(x):
      # Pin the TARGET's sharding so restored leaves land exactly on
      # its placements (mesh-sharded or single-device alike). An
      # already-abstract leaf carrying a sharding passes through
      # unchanged (registry_restore_targets builds those).
      if isinstance(x, jax.ShapeDtypeStruct):
        return x
      if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=x.sharding)
      return ocp.utils.to_shape_dtype_struct(x)

    abstract = jax.tree_util.tree_map(to_abstract, target)
    return lambda step: self._manager.restore(
        step, args=ocp.args.StandardRestore(abstract))

  def restore_latest_params(self, params, make_state):
    """Restore ONLY params (+ the update_steps counter) from the latest
    checkpoint; returns (params, update_steps) or None.

    Eval needs the policy weights, not the optimizer moments (≈2×
    params of dead HBM if restored). The full-state target is built
    only abstractly (`jax.eval_shape` over `make_state`) so the
    moments are never materialized, and every leaf outside
    params/update_steps restores as `ocp.PLACEHOLDER` — Orbax never
    reads it. Restored leaves land on `params`' own placements (Orbax
    requires explicit shardings when process_count > 1).

    Args:
      params: CONCRETE param pytree of jax.Arrays (init_params output);
        supplies both the tree structure and the target placements.
      make_state: params → TrainState (e.g. a make_train_state
        closure); evaluated under eval_shape only.
    """
    steps = sorted(self._manager.all_steps(), reverse=True)
    if not steps:
      return None

    abstract = jax.eval_shape(make_state, params)
    as_abstract = lambda c: jax.ShapeDtypeStruct(  # noqa: E731
        c.shape, c.dtype, sharding=c.sharding)
    dev_sharding = jax.tree_util.tree_leaves(params)[0].sharding
    if hasattr(ocp, 'PLACEHOLDER'):
      placeholder = lambda t: jax.tree_util.tree_map(  # noqa: E731
          lambda _: ocp.PLACEHOLDER, t)
      target = abstract._replace(
          params=jax.tree_util.tree_map(as_abstract, params),
          update_steps=jax.ShapeDtypeStruct(
              abstract.update_steps.shape, abstract.update_steps.dtype,
              sharding=dev_sharding),
          opt_state=placeholder(abstract.opt_state),
          popart=placeholder(abstract.popart))
    else:
      # Orbax builds without PLACEHOLDER (< 0.9): restore the FULL
      # abstract state and drop everything but params/update_steps.
      # The optimizer moments materialize for the duration of the call
      # (≈2× params of transient HBM) — a documented availability-
      # over-optimization fallback: a dead eval path is a failure
      # domain too. Non-params leaves land on the params' placements.
      target = jax.tree_util.tree_map(
          lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype,
                                         sharding=dev_sharding),
          abstract)
      target = target._replace(
          params=jax.tree_util.tree_map(as_abstract, params))
    # PLACEHOLDER is a PyTreeRestore feature (StandardRestore rejects
    # it), and a manager that already did a StandardSave has its item
    # handler pinned — restore through a FRESH manager so the step
    # layout stays Orbax's concern, not ours. Same integrity ladder as
    # restore_latest: eval must survive a corrupt newest step too.
    manager = ocp.CheckpointManager(self._directory)
    try:
      restored, _ = self._restore_ladder(
          steps, lambda step: manager.restore(
              step, args=ocp.args.PyTreeRestore(target)))
    finally:
      manager.close()
    return restored.params, int(jax.device_get(restored.update_steps))

  def wait_until_finished(self):
    self._manager.wait_until_finished()

  def saved_mesh_shape(self) -> Optional[Dict[str, int]]:
    """The mesh shape dict the NEWEST retained step's sharding
    manifest recorded, or None (no steps / no manifest / pre-manifest
    writer). The driver's elastic-restore gate compares this against
    the live mesh to decide whether a restore is cross-topology."""
    steps = self._manager.all_steps()
    if not steps:
      return None
    manifest = self.read_sharding_manifest(max(steps))
    if not manifest or not isinstance(manifest.get('mesh'), dict):
      return None
    return {str(k): int(v) for k, v in manifest['mesh'].items()}

  def restore_resharded(self, abstract_state, registry, mesh,
                        strict: bool = True):
    """Restore the latest restorable step directly onto REGISTRY-
    resolved placements for `mesh` — the cross-topology resharding
    path (ROADMAP item 3): a checkpoint saved on any topology restores
    here with Orbax moving each leaf's bytes into the specs this
    registry resolves for THIS mesh, no concrete donor state needed.
    `abstract_state` is the eval_shape of the target TrainState.

    strict (the default, round 20): refuse with `ShardingLayoutError`
    when the registry resolves a cut this mesh cannot honor for a leaf
    the save had NOT already recorded as replicated (the manifest's
    spec table is the exemption list) — a topology change must never
    silently rewrite a layout the checkpoint still holds. strict=False
    accepts the divisibility guard's replicated degradation, exactly
    like a fresh spin-up on the new mesh."""
    if strict:
      steps = self._manager.all_steps()
      manifest = (self.read_sharding_manifest(max(steps))
                  if steps else None)
      saved = manifest.get('specs') if manifest else None
      registry.check_layout(abstract_state.params, mesh, what='param',
                            saved_specs=saved)
    return self.restore_latest(
        registry_restore_targets(abstract_state, registry, mesh))

  def close(self):
    self._manager.wait_until_finished()
    self._manager.close()
    # Drop the registry's fn-gauge hold on this instance (identity-
    # checked — a newer checkpointer's registration survives).
    for gauge in self._gauges:
      telemetry.registry().unregister(gauge.name, gauge)


def registry_restore_targets(abstract_state, registry, mesh):
  """Abstract restore targets whose placements the sharding REGISTRY
  resolves (parallel/sharding.py) — not copied from any live state.

  This is the primitive under cross-topology resharding (ROADMAP
  item 3): restore_latest pins each leaf to its target's sharding, so
  feeding it targets resolved by the registry FOR THE NEW MESH makes
  Orbax reshard a checkpoint saved under any topology into exactly the
  placements the current rules declare. The save-side half is the
  SHARDING_{step}.json manifest (`Checkpointer._record_sharding_
  manifest`), which records what the bytes on disk were laid out as.
  """
  shardings = registry.state_shardings(abstract_state, mesh)
  return jax.tree_util.tree_map(
      lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sh),
      abstract_state, shardings)

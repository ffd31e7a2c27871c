"""Sharded (multi-chip) training step.

One `jit` over the mesh: batch sharded on the data axis, params
replicated (or TP-sharded), optimizer state following params. XLA
inserts the gradient all-reduce (psum over ICI) — no hand-written
collectives needed for DP, which is the whole point of the design
(SURVEY §5.8: "gradient/metric reduction = jax.lax.psum over the DP
mesh axis" — jit's partitioner emits exactly that from these
shardings).
"""

import logging

import numpy as np

import jax
from jax.sharding import Mesh

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.parallel import sharding as sharding_lib

log = logging.getLogger('scalable_agent_tpu')


def make_sharded_train_state(params, config: Config, mesh: Mesh,
                             enable_tp: bool = False,
                             num_popart_tasks: int = 0,
                             registry=None):
  """Place params on the mesh and build the TrainState there, every
  placement resolved by the sharding registry (round 19): params by the
  partition rules, optimizer moments cloned leaf-wise from the matched
  param specs, `target_params` pinned identically (the IMPACT anchor's
  in-graph refresh is a leafwise select — mixed placements would force
  a resharding copy every step), and every remaining leaf (step/opt
  counters, PopArt stats) explicitly replicated — a single-device
  committed scalar next to mesh-committed params is a mixed-placement
  error under jit (bites after checkpoint restore).

  Params are placed BEFORE the optimizer state is built so the eager
  zeros_like moments materialize already-sharded (never an unsharded
  full copy in HBM); the final registry-wide device_put is then a
  no-op confirmation for them."""
  if registry is None:
    registry = sharding_lib.from_config(
        config, enable_tp=enable_tp or config.model_parallelism > 1)
  p_shard = registry.param_shardings(params, mesh)
  params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
  state = learner_lib.make_train_state(params, config, num_popart_tasks)
  shardings = registry.state_shardings(state, mesh)
  return jax.tree_util.tree_map(jax.device_put, state, shardings)


def resolve_tp_compute(config) -> str:
  """'gathered' | 'sharded' — how TP matmuls actually execute.

  'auto' resolves per backend: CPU takes the gathered workaround (this
  jaxlib's partitioner mis-computes AD graphs over model-sharded
  leaves — see make_sharded_train_step); TPU/GPU keep true sharded
  compute. Explicit values win either way."""
  mode = getattr(config, 'tp_compute', 'auto')
  if mode == 'auto':
    return 'gathered' if jax.default_backend() == 'cpu' else 'sharded'
  return mode


def make_sharded_train_step(agent, config: Config, mesh: Mesh,
                            example_batch, donate: bool = True):
  """Jit the learner step with explicit in/out shardings over the mesh.

  Returns (train_step, place_batch): `place_batch` device_puts a host
  batch with the data-axis sharding — the host→device edge of the
  trajectory transport (the reference's StagingArea role).

  donate: donate the input state for in-place HBM update (the
  production default). False exists for environments whose jaxlib
  mis-sizes donation aliases of TP-sharded leaves ("Expected aliased
  input ... to have the same size" — the pre-existing bug xfail'd in
  tests/test_parallel.py); __graft_entry__'s dryrun falls back to it
  so the parity gate still runs there.

  The step carries how the batch is split as attributes:
  `batch_shardings` (the shardings it is jitted with), `batch_shards`
  (D, the number of shards of the batch dim: `sharding.batch_shards`)
  and `rows_per_device` ((T+1)·B/D, the rows of the agent's merged
  axis a device computes: the agent lays them shard-major so that the
  merge keeps the sharding, `sharding.merge_time_batch`). That no
  device computes more is in the compiled program, not in D
  (tests/test_parallel.py reads it there).

  The mesh rides into the step fn (round 8): the Pallas V-trace has
  no SPMD partitioning rule, so under this jit it runs shard_map'ped
  over the data axis — the fused kernel is no longer single-device
  only (vtrace.py / ops/vtrace_pallas.py).

  TP compute mode (round 17): with model_parallelism > 1 this jaxlib's
  CPU backend has a SECOND defect beyond donation aliasing — the
  partitioned program computes WRONG numerics whenever any leaf is
  model-axis-sharded (measured: annotating a single bias changes the
  loss by ~0.5; GSPMD and the experimental shardy partitioner both
  produce the identical wrong value, and sharding-constraining every
  activation does not repair it — only the differentiated (AD) graph
  is affected, a forward pass with an in-graph all-gather is exact).
  `resolve_tp_compute(config)` therefore selects 'gathered' on CPU:
  params stay TP-SHARDED AT REST (the memory story and the
  cross-process collective placement are real), but each step runs as
  gather → replicated-compute → scatter, three separate compiled
  programs, so the partitioner never differentiates through a
  model-sharded leaf. Parity-gated by the tp4 multihost child and
  tests/test_parallel.py. TPU/GPU keep true sharded TP compute
  ('sharded'); config.tp_compute overrides either way.
  """
  train_step = learner_lib.make_train_step_fn(agent, config, mesh=mesh)
  registry = sharding_lib.from_config(config)
  batch_shard = registry.batch_shardings(
      example_batch, mesh,
      shard_over_model=sharding_lib.shard_batch_over_model(config))
  replicated = sharding_lib.replicated(mesh)
  # None = decide on the first call from the LIVE state: TP can arrive
  # via config.model_parallelism or via a make_sharded_train_state
  # caller passing enable_tp out-of-band (tests do) — any model-
  # sharded leaf in the state means the defect applies.
  gathered_tp = (True if (config.model_parallelism > 1 and
                          resolve_tp_compute(config) == 'gathered')
                 else None)

  def jit_step(donate_now):
    return jax.jit(
        train_step,
        in_shardings=(None, batch_shard),  # state keeps its placement
        out_shardings=(None, replicated),
        donate_argnums=(0,) if donate_now else ())

  # Donation self-heal (round 17, the ring_buffer._insert pattern):
  # this jaxlib mis-pairs donation aliases of TP-sharded leaves
  # ("Expected aliased input ... to have the same size" — the
  # seed-listed defect, xfail'd in tests/test_parallel.py). The first
  # step that trips it rebuilds the jit UN-donated and retries with
  # the same arguments (the alias check fails before any buffer is
  # consumed — proven by the arena insert's identical retry);
  # correctness first, the in-place HBM update is an optimization.
  # The engaged fallback is visible as `step.donation_fallback` —
  # multi-process callers included, which is what turns the
  # tp-across-process tests green on this jaxlib.
  compiled = {'fn': jit_step(donate), 'donate': donate}

  # The two reshard programs of the gathered path (pure layout moves
  # as their OWN compiled programs — exact, verified leaf-identical
  # round trip), built ONCE on the first step: jit caches on function
  # identity, so a fresh jit(lambda ...) per call would retrace the
  # whole state tree twice per step. The scatter captures the at-rest
  # placements from the FIRST live state (a restored checkpoint's
  # placements included) and re-establishes them every step.
  _reshard_fns = {}

  def run_step(state, batch):
    nonlocal gathered_tp
    if gathered_tp is None:
      gathered_tp = (resolve_tp_compute(config) == 'gathered' and any(
          sharding_lib.MODEL_AXIS in str(getattr(x.sharding, 'spec', ''))
          for x in jax.tree_util.tree_leaves(state)
          if isinstance(x, jax.Array)))
      step.tp_gathered = gathered_tp
      if gathered_tp:
        _log_gathered()
    if not gathered_tp:
      return compiled['fn'](state, batch)
    # gather → replicated compute → scatter.
    if 'gather' not in _reshard_fns:
      at_rest = jax.tree_util.tree_map(lambda x: x.sharding, state)
      rep = jax.tree_util.tree_map(lambda _: replicated, state)
      _reshard_fns['gather'] = jax.jit(lambda t: t, out_shardings=rep)
      _reshard_fns['scatter'] = jax.jit(lambda t: t,
                                        out_shardings=at_rest)
    new_state, metrics = compiled['fn'](
        _reshard_fns['gather'](state), batch)
    return _reshard_fns['scatter'](new_state), metrics

  def step(state, batch):
    try:
      return run_step(state, batch)
    except Exception as e:  # jaxlib XlaRuntimeError (INTERNAL)
      if not compiled['donate'] or 'alias' not in str(e):
        raise
      log.warning(
          'sharded train step: donation aliasing defect on this '
          'jaxlib (%s) — rebuilding un-donated and retrying; HBM '
          'holds one extra state copy for the rest of the run', e)
      compiled['fn'] = jit_step(False)
      compiled['donate'] = False
      step.donation_fallback = True
      return run_step(state, batch)

  # The step's program for these arguments, not run (`.compile()`
  # gives its text and memory: the tests and the compile-only fit).
  step.lower = lambda state, batch: compiled['fn'].lower(state, batch)
  step.donation_fallback = False
  step.tp_gathered = bool(gathered_tp)
  # The shardings the step is jitted with: how the batch is split.
  step.batch_shardings = batch_shard
  step.batch_shards = sharding_lib.batch_shards(config, mesh)
  t1, b = example_batch.env_outputs.reward.shape
  step.rows_per_device = t1 * b // step.batch_shards
  log.info('sharded train step on mesh %s: batch %d in %d shards, '
           '%d of the %d merged [T*B] rows on a device',
           dict(mesh.shape), b, step.batch_shards,
           step.rows_per_device, t1 * b)

  def _log_gathered():
    log.info(
        'TP compute mode: gathered (params stay model-sharded at '
        'rest; each step gathers, computes replicated, re-scatters) — '
        'the %s backend mis-computes differentiated programs over '
        'model-sharded leaves on this jaxlib (docs/PARALLELISM.md)',
        jax.default_backend())

  if gathered_tp:
    _log_gathered()

  def place_batch(host_batch):
    """Host numpy → globally-sharded device arrays. Each process passes
    its LOCAL shard of the data axis (on a single host, local == global
    and this is an ordinary sharded device_put); across hosts this is
    the whole trajectory transport — data never leaves the host that
    produced it (SURVEY §5.8)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.make_array_from_process_local_data(
            s, np.asarray(x)),
        host_batch, batch_shard)

  return step, place_batch


def supports_sdc_check(config, mesh) -> bool:
  """Whether the cross-replica SDC fingerprint check can run here:
  it compares PER-REPLICA fingerprints of the (logically replicated)
  params, which needs a pure-DP mesh (TP-sharded params give each
  device a different — legitimately different — shard) with at least
  two data replicas to compare. Single device has nothing to
  cross-check; the driver then leaves the sentinel off."""
  if mesh is None:
    return False
  # "Are params logically replicated?" is a registry question now
  # (round 19): any model-axis rule means each device legitimately
  # holds a different shard — nothing to cross-compare.
  if sharding_lib.from_config(config).model_sharded:
    return False
  if sharding_lib.shard_batch_over_model(config):
    return False
  # Multi-process meshes need the in-graph all-gather (round 17): a
  # raw readback device_gets a P('data')-sharded array, which jax
  # refuses when shards live on non-addressable devices. With
  # sdc_allgather the fingerprint vector leaves the graph REPLICATED
  # (every host reads its local copy), so the PR 9 single-controller
  # gate lifts; without it the sentinel stays off here
  # (validate_distributed warns).
  if any(d.process_index != jax.process_index()
         for d in mesh.devices.flat):
    if not getattr(config, 'sdc_allgather', True):
      return False
  return mesh.shape[sharding_lib.DATA_AXIS] >= 2


def make_sdc_fingerprint_fn(mesh: Mesh):
  """Per-replica param fingerprints for the SDC sentinel (round 12).

  Returns (fingerprint_fn, num_replicas): `fingerprint_fn(params,
  probe_host)` dispatches a shard_map over the data axis in which EACH
  replica computes `learner.param_fingerprint` from ITS OWN copy of
  the replicated params — the computation runs on every device against
  the local HBM buffers, so a silently corrupted replica copy yields a
  differing entry of the returned [num_replicas] uint32 array. The
  driver reads it one step delayed (the sentinel pattern) and any
  disagreement is deterministic-compute-violated: incident + the PR 2
  rollback ladder (the restore re-replicates params, which is exactly
  the repair real SDC needs).

  `probe_host` is the chaos lane (runtime/faults.py
  'replica_divergence'): a host uint32 vector, normally zeros, added
  per-replica to the fingerprint INSIDE the graph. A GSPMD program
  cannot make a logically replicated array truly diverge — real SDC
  is a hardware fault below the program — so the drill perturbs the
  detector's per-replica view instead, driving the identical
  detection → incident → rollback path.

  check_vma=False: params enter replicated but the per-replica
  fingerprints are deliberately per-shard — the whole point is that
  'replicated' is an assumption the hardware can break, which is not
  a claim shard_map's replication checker can express.

  The [replicas] vector leaves the graph REPLICATED via an in-graph
  all-gather over the data axis (round 17): each replica computes its
  own fingerprint from local HBM, the all-gather exchanges the one
  uint32 per replica (bytes on the wire — noise against the step's
  gradient psum), and the host read then touches only addressable
  shards — which is what lifts the PR 9 single-controller gate and
  lets the sentinel run on multi-process meshes. The collective is
  dispatched from the lockstep driver path (per health check, every
  host), so it is barrier-safe by the same argument as the step
  itself."""
  num_replicas = int(mesh.shape[sharding_lib.DATA_AXIS])
  probe_sharding = sharding_lib.data_sharding(mesh)

  def per_replica(params, probe):
    fp = learner_lib.param_fingerprint(params)
    # [1] per replica → all-gathered [replicas] on EVERY device. A
    # corrupted replica's entry differs identically in every copy of
    # the gathered vector, so any host's local read sees it.
    return jax.lax.all_gather(
        (fp + probe.reshape(())).reshape(()), sharding_lib.DATA_AXIS,
        tiled=False)

  sharded = jax.jit(jax.shard_map(
      per_replica, mesh=mesh,
      in_specs=(sharding_lib.spec_replicated(),
                sharding_lib.spec_data()),
      out_specs=sharding_lib.spec_replicated(), check_vma=False))

  def fingerprint_fn(params, probe_host=None):
    if probe_host is None:
      probe_host = np.zeros((num_replicas,), np.uint32)
    probe = jax.device_put(
        np.ascontiguousarray(probe_host, np.uint32), probe_sharding)
    return sharded(params, probe)

  return fingerprint_fn, num_replicas


def supports_unroll_staging(config, mesh) -> bool:
  """Whether staging_mode='unroll' can serve this topology.

  The per-unroll staging plane places each unroll on the device owning
  its batch slot and assembles the global batch zero-copy from the
  per-device arenas — that requires a pure-data batch sharding (no
  model-axis replication of the batch: duplicating every unroll's H2D
  across the TP width would undo the trickle win) and a local batch
  that divides this process's data width. The driver falls back to
  'batch' with a warning otherwise; None mesh (single device) always
  supports it."""
  if mesh is None:
    return True
  if sharding_lib.shard_batch_over_model(config):
    return False
  if mesh.shape[sharding_lib.MODEL_AXIS] != 1:
    return False
  local = [d for d in mesh.devices.flat
           if d.process_index == jax.process_index()]
  local_batch = config.batch_size // jax.process_count()
  return bool(local) and local_batch % len(local) == 0


def unroll_slot_owners(local_devices, local_batch: int):
  """Slot → owning device for this PROCESS's slice of the global batch
  (round 17 pulls the arithmetic out of make_unroll_assembly so the
  placement is unit-testable without spawning processes).

  Slot s of the local batch lives on local_devices[s // per_dev] — the
  contiguous data-axis shard layout batch_shardings assigns, restricted
  to THIS process's addressable devices: unroll staging is the
  host-local half of the trajectory transport, so slot ownership must
  never name another host's device."""
  n_local = len(local_devices)
  if n_local == 0 or local_batch % n_local != 0:
    raise ValueError(
        f'local batch {local_batch} does not divide over '
        f'{n_local} local device(s)')
  per_dev = local_batch // n_local
  return [local_devices[s // per_dev] for s in range(local_batch)]


def make_unroll_assembly(config, mesh, example_batch):
  """Slot placement + zero-copy global assembly for the per-unroll
  staging plane (runtime/ring_buffer.UnrollBatchStager) over a pure-DP
  mesh.

  Returns (slot_devices, assemble_fn): slot s of this process's local
  batch lives on the s·D/B-th local mesh device (the contiguous
  data-axis shard layout batch_shardings assigns), and `assemble_fn`
  stitches the per-device arenas into the globally-sharded batch via
  `jax.make_array_from_single_device_arrays` — no copy, no host
  round trip: the arena rows ARE the step's shards. Single-host this
  is the whole batch; multi-host each process supplies its
  addressable shards, exactly like make_array_from_process_local_data
  does on the batch path."""
  if not supports_unroll_staging(config, mesh):
    raise ValueError('unroll staging unsupported on this topology '
                     '(see supports_unroll_staging)')
  batch_shard = sharding_lib.from_config(config).batch_shardings(
      example_batch, mesh, shard_over_model=False)
  local_devices = [d for d in mesh.devices.flat
                   if d.process_index == jax.process_index()]
  data_width = mesh.shape[sharding_lib.DATA_AXIS]
  local_batch = config.batch_size // jax.process_count()
  slot_devices = unroll_slot_owners(local_devices, local_batch)

  def assemble(sub_arenas):
    """Per-device arenas (device order) → the global sharded batch."""

    def join(sharding, *shards):
      spec = sharding.spec
      bdim = next(i for i, ax in enumerate(spec) if ax is not None)
      # Global batch dim: per-device rows × data-axis width.
      shape = list(shards[0].shape)
      shape[bdim] = shards[0].shape[bdim] * data_width
      return jax.make_array_from_single_device_arrays(
          tuple(shape), sharding, list(shards))

    return jax.tree_util.tree_map(join, batch_shard, *sub_arenas)

  return slot_devices, assemble

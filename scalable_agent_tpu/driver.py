"""Experiment driver: wires the whole framework into train/test runs.

TPU-native counterpart of the reference's `train()` / `test()`
orchestration (reference: experiment.py ≈L430–630). The TF1 machinery
maps as:

  FIFOQueue + QueueRunner threads      → TrajectoryBuffer + ActorFleet
  StagingArea GPU prefetch             → BatchPrefetcher (device_put
                                         with data-axis shardings)
  dynamic_batching monkey-patch        → InferenceServer (C++ batcher
                                         in front of a jitted step)
  MonitoredTrainingSession checkpoints → Checkpointer (Orbax)
  tf.summary + manual Summary protos   → SummaryWriter (JSONL) +
                                         EpisodeStats
  gRPC weight fetch by actors          → host param snapshot publish
  PyProcessHook env lifecycle          → factory.build_environment +
                                         fleet-owned processes

`train()` runs until `total_environment_frames` (reference while-loop
≈L585); `evaluate()` restores the latest checkpoint and plays
`test_num_episodes` per level, with DMLab-30 human-normalized scoring
in multi-task mode (reference test() ≈L595–630).
"""

import collections
import dataclasses
import inspect
import json
import logging
import os
import shutil
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import multihost_utils

from scalable_agent_tpu import checkpoint as checkpoint_lib
from scalable_agent_tpu import controller as controller_lib
from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu import lifecycle
from scalable_agent_tpu import observability
from scalable_agent_tpu import population as population_lib
from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis import runtime as lock_check
from scalable_agent_tpu.config import (Config, validate_controller,
                                       validate_distributed,
                                       validate_integrity,
                                       validate_population,
                                       validate_replay,
                                       validate_runtime,
                                       validate_serving, validate_slo,
                                       validate_transport)
from scalable_agent_tpu.envs import factory, suites
from scalable_agent_tpu.models import (ImpalaAgent, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import hybrid_attention
from scalable_agent_tpu.models import latent_moe
from scalable_agent_tpu.parallel import mesh as mesh_lib
from scalable_agent_tpu.parallel import sharding as sharding_lib
from scalable_agent_tpu.parallel import train_parallel
from scalable_agent_tpu.runtime import faults as faults_lib
from scalable_agent_tpu.runtime import inference as inference_lib
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.runtime.actor import Actor
from scalable_agent_tpu.runtime.fleet import ActorFleet
from scalable_agent_tpu.runtime.inference import InferenceServer

log = logging.getLogger('scalable_agent_tpu')

# The preemption drain's on-disk handoff: written next to the
# checkpoints at drain time, consumed (renamed) by the resuming run.
RESUME_MANIFEST = 'resume_manifest.json'


def read_resume_manifest(logdir: str) -> Optional[Dict]:
  """The drain manifest of a previous preempted run, or None."""
  path = os.path.join(logdir, RESUME_MANIFEST)
  try:
    with open(path) as f:
      return json.load(f)
  except (OSError, ValueError):
    return None


def _write_resume_manifest(logdir: str, manifest: Dict) -> str:
  """Atomic write (tmp + rename): a manifest is either complete or
  absent — a resume must never act on a half-written one."""
  path = os.path.join(logdir, RESUME_MANIFEST)
  tmp = path + '.tmp'
  with open(tmp, 'w') as f:
    json.dump(manifest, f, indent=2, sort_keys=True)
  os.replace(tmp, path)
  return path


def _stats_only_view(level_name, info, done):
  """ActorOutput carrying ONLY what observability.extract_episodes
  reads ([T+1, B] done/info + [B] level ids) — the single place that
  encodes its input contract for both train() and evaluate()."""
  from scalable_agent_tpu.structs import ActorOutput, StepOutput
  return ActorOutput(
      level_name=level_name,
      agent_state=None,
      env_outputs=StepOutput(reward=None, info=info, done=done,
                             observation=None),
      agent_outputs=None)


def build_agent(config: Config, num_actions: int, num_tasks: int = 1):
  dtype = (jnp.bfloat16 if config.compute_dtype == 'bfloat16'
           else jnp.float32)
  if config.agent == 'sequence':
    core_dims = None
    widths = {'latent': latent_moe.LatentMoEDims,
              'hybrid': hybrid_attention.HybridAttentionDims}.get(
                  config.seq_core)
    if widths is not None:
      # Every field of a core's widths is the flag of its name.
      core_dims = widths(**{
          field.name: getattr(config, f'seq_{field.name}')
          for field in dataclasses.fields(widths)})
      core_dims.check()
    return SequenceAgent(
        core_dims=core_dims,
        num_actions=num_actions, num_layers=config.seq_num_layers,
        hidden_size=config.seq_hidden_size,
        num_heads=config.seq_num_heads,
        num_kv_heads=config.seq_num_kv_heads,
        head_dim=config.seq_head_dim, mlp_size=config.seq_mlp_size,
        rope_theta=config.seq_rope_theta, norm_eps=config.seq_norm_eps,
        scan_unroll=config.scan_unroll, dtype=dtype,
        param_dtype=(jnp.bfloat16 if config.param_dtype == 'bfloat16'
                     else jnp.float32))
  return ImpalaAgent(num_actions=num_actions, torso=config.torso,
                     use_instruction=config.resolved_use_instruction,
                     num_popart_tasks=(num_tasks if config.use_popart
                                       else 0),
                     use_pixel_control=config.pixel_control_cost > 0,
                     pixel_control_cell_size=config.pixel_control_cell_size,
                     pixel_control_head_impl=config.pixel_control_head_impl,
                     pixel_control_q_f32=config.pixel_control_q_f32,
                     scan_unroll=config.scan_unroll,
                     dtype=dtype)


def make_fleet(config: Config, agent, policy, buffer, levels,
               seed_base: int = 0, level_offset: int = 0,
               is_test: bool = False,
               num_actors: Optional[int] = None,
               initial_state_fn=None) -> ActorFleet:
  """The one env+actor+fleet construction, shared by train(),
  evaluate(), and the remote-actor role (they differ only in seeds,
  level assignment, and fleet size). Actor i plays
  levels[(level_offset + i) % len] with env seed `seed_base + i + 1`.

  Heterogeneous fleets (round 22): when config.fleet_tasks is set AND
  `levels` is exactly its task-name list (train() arranges this), the
  fleet mixes SUITES — actor i's task comes from the weighted
  largest-remainder plan (population.plan_actor_assignment), its env
  spec is built for THAT task's backend, and level_name_id is the
  task index (one PopArt slot + one EpisodeStats curve per task). The
  declared weights are the per-task frame budgets: actors produce at
  the same rate, so actor share == frame share. Callers that pass
  ordinary level lists (evaluate on one backend, remote actors) are
  untouched.

  `initial_state_fn` builds each actor's policy core state, called
  fresh at every (re)spawn — pass the InferenceServer's
  `initial_core_state` so state-cache mode hands each actor a zeroed
  arena slot (a respawned actor must never inherit a stale carry);
  None falls back to the plain numeric zero carry. A factory that
  accepts a `priority` keyword (initial_core_state does) gets the
  admission class: PRIORITY_LIVE for a slot's first spawn,
  PRIORITY_RESPAWN for respawns — so respawn churn under overload
  waits behind live traffic instead of starving it.
  """
  n = config.num_actors if num_actors is None else num_actors
  if initial_state_fn is None:
    initial_state_fn = lambda: agent.initial_state(1)  # noqa: E731
  try:
    accepts_priority = ('priority' in
                        inspect.signature(initial_state_fn).parameters)
  except (TypeError, ValueError):
    accepts_priority = False
  # Spawn count per slot (single-threaded: start() and check_health
  # respawns both run on the learner thread) — first spawn vs respawn
  # picks the admission priority class.
  spawns = collections.Counter()
  task_plan = None
  if config.fleet_tasks:
    tasks = population_lib.parse_fleet_tasks(config.fleet_tasks)
    if [name for name, _ in tasks] == list(levels):
      task_plan = population_lib.plan_actor_assignment(tasks, n)

  def make_actor(i):
    idx = level_offset + i
    if task_plan is not None:
      # Task identity is a function of the SLOT (idx), not the spawn:
      # a respawned actor rejoins its task's frame budget.
      idx = task_plan[idx % len(task_plan)]
    level = levels[idx % len(levels)]
    backend = level if task_plan is not None else None
    spec = factory.make_env_spec(config, level,
                                 seed=seed_base + i + 1,
                                 is_test=is_test, backend=backend)
    env, process = factory.build_environment(
        spec, use_py_process=config.use_py_process)
    # Fault-injection seam (runtime/faults.py): identity unless an
    # installed plan targets env_step.
    env = faults_lib.maybe_wrap_env(env)
    try:
      if accepts_priority:
        priority = (inference_lib.PRIORITY_RESPAWN if spawns[i]
                    else inference_lib.PRIORITY_LIVE)
        state = initial_state_fn(priority=priority)
      else:
        state = initial_state_fn()
    except BaseException:
      # A denied slot admission must not leak the env just built —
      # the fleet retries this spawn later with a FRESH env.
      try:
        if process is not None:
          process.close(timeout=1.0)
        else:
          env.close()
      except Exception:
        pass
      raise
    spawns[i] += 1
    actor = Actor(env, policy, state,
                  unroll_length=config.unroll_length,
                  num_action_repeats=config.num_action_repeats,
                  level_name_id=idx % len(levels))
    return env, process, actor

  return ActorFleet(make_actor, buffer, n,
                    quarantine_after=config.fleet_quarantine_after,
                    probation_secs=config.fleet_probation_secs,
                    max_policy_rows=config.inference_max_batch)


def _log_env_transport(fleet_stats):
  """The closing line on how the process-hosted envs were stepped:
  through their group's shared block, or by pickled calls."""
  steps = fleet_stats.get('block_steps', 0)
  calls = fleet_stats.get('pipe_calls', 0)
  if steps or calls:
    log.info(
        'env transport: block_steps=%d pipe_calls=%d (%.1f%% of the '
        'traffic to the env processes went through a shared block)',
        steps, calls, 100.0 * steps / (steps + calls))


def _choose_eval_mesh():
  """Inference mesh for evaluate(): LOCAL devices only (each host's
  dynamic batcher fires independently — a cross-process mesh would
  need lockstep invocation), pure data axis (inference replicates
  params; a model axis would only do redundant compute). Any
  multi-device host then runs eval inference across all its chips
  instead of leaving (n-1)/n idle (VERDICT r2 W6)."""
  devices = jax.local_devices()
  if len(devices) == 1:
    return None
  return mesh_lib.make_mesh(devices, model_parallelism=1)


def choose_mesh(config: Config):
  """Mesh over all local devices when the batch can shard; None means
  plain single-device jit (the reference's single-machine mode)."""
  devices = jax.devices()
  mp = config.model_parallelism
  if len(devices) == 1 and mp == 1:
    return None
  if mp > len(devices) or len(devices) % mp != 0:
    raise ValueError(
        f'model_parallelism={mp} does not divide the device count '
        f'{len(devices)}')
  # Multi-host TP shards the batch over BOTH mesh axes (see
  # sharding.batch_shardings), so the batch must divide the full
  # device count there; otherwise only the data width.
  if sharding_lib.shard_batch_over_model(config):
    batch_width = len(devices)
  else:
    batch_width = len(devices) // mp
  if config.batch_size % batch_width != 0:
    if jax.process_count() > 1:
      # Multi-host: the fallback would leave every host training an
      # independent, never-synchronized replica against a shared
      # logdir — silently wrong training. Refuse.
      raise ValueError(
          f'batch_size={config.batch_size} not divisible by '
          f'batch-sharding width {batch_width}; single-device '
          'fallback is only safe single-host')
    log.warning('batch_size %d not divisible by batch-sharding width '
                '%d; falling back to single-device training',
                config.batch_size, batch_width)
    return None
  return mesh_lib.make_mesh(devices, model_parallelism=mp)


class TrainRun:
  """All live objects of a training run (for inspection/tests)."""

  def __init__(self, config, agent, state, fleet, prefetcher, server,
               checkpointer, writer, stats, fps_meter, ingest=None,
               health=None):
    self.config = config
    self.agent = agent
    self.state = state
    self.fleet = fleet
    self.prefetcher = prefetcher
    self.server = server
    self.checkpointer = checkpointer
    self.writer = writer
    self.stats = stats
    self.fps_meter = fps_meter
    self.ingest = ingest
    self.health = health  # HealthMonitor (None when watchdog is off)
    self.controller = None  # controller.Controller (round 15), set
                            # by train() when --controller != off
    # Set by train()/train_anakin(): the mesh the run chose (None =
    # single device) and, for the fleet runtime, the learner step —
    # its `donation_fallback` / `tp_gathered` attributes say whether a
    # sharded run gave way to a workaround (chip_smoke.py asserts not).
    self.mesh = None
    self.train_step = None
    # Set by train() when sample reuse is on: a closure over the
    # prefetcher's serve-time fresh-slot counter, so `frames` reports
    # FRESH env frames (reuse makes update_steps × frames_per_step an
    # overcount).
    self._env_frames_fn = None

  @property
  def frames(self) -> int:
    if self._env_frames_fn is not None:
      return int(self._env_frames_fn())
    return int(jax.device_get(self.state.update_steps)) * \
        self.config.frames_per_step


def train(config: Config, max_steps: Optional[int] = None,
          stall_timeout_secs: Optional[float] = None,
          max_seconds: Optional[float] = None,
          fleet_factory=None,
          drain_event: Optional[threading.Event] = None) -> TrainRun:
  """Run IMPALA training until total_environment_frames (or max_steps
  / max_seconds — timed smoke and bench runs).

  `fleet_factory(config, agent, policy, buffer, levels)` replaces
  make_fleet when given: tests inject a synthetic producer fleet so
  THIS loop (stats extraction, publish cadence, summaries, health
  checks) runs at full feed rate without env/inference cost (VERDICT
  r4 #3); benchmark/drivers/train_loop.py wraps make_fleet to watch
  the children. Production always uses the default.

  `drain_event` is the preemption seam (experiment.py sets it from
  SIGTERM; the 'preempt_signal' fault site fires it deterministically
  for chaos): when set, the loop QUIESCES instead of dying mid-step —
  admissions stop, in-flight unrolls flush through the learner,
  a verified checkpoint lands through the integrity ladder, and
  `resume_manifest.json` (frames / update_steps / param version /
  buffer watermarks) is written next to the summaries; the next
  train() on the same logdir resumes from it. Single-host only: the
  drain checkpoint is not a collective (multi-host preemption keeps
  the periodic-checkpoint story).

  Returns the TrainRun with the final state (all machinery shut down).
  """
  # --- Runtime axis (round 16): --runtime=anakin runs the fused
  # on-device act+learn loop under the SAME lifecycle contract this
  # function provides the fleet (checkpoint ladder, health ladder,
  # SLO verdict, summaries/incidents). One entry point, two operating
  # points — callers never branch. ---
  # --- Multi-process spin-up (round 17): validate the DECLARED
  # topology first (a malformed coordinator or out-of-range
  # process_id must be a crisp ValueError, not a coordinator hanging
  # out its 300 s initialization window waiting for a process that
  # can never come), then join jax.distributed BEFORE the first
  # device op below (the backend is built with cross-process
  # collectives only if the runtime exists first). Launcher-
  # initialized topologies (the test-harness path: config fields
  # default, jax.distributed already up) get the cross-links
  # re-checked against the LIVE process count after the join. ---
  from scalable_agent_tpu.parallel import distributed
  dist_warnings = validate_distributed(config)
  distributed.maybe_initialize(config)
  live_processes = jax.process_count()
  if live_processes > max(config.num_processes, 1):
    dist_warnings = validate_distributed(
        config, live_process_count=live_processes)
  for warning in dist_warnings:
    log.warning('%s', warning)
  # Lock-order detection (round 18, analysis/runtime.py): arm BEFORE
  # any component constructs its locks — make_lock reads the armed
  # state at construction (this covers both runtimes; the anakin
  # dispatch below constructs its own checkpoint/SLO planes).
  # Arm-only (never disarm): tests/chaos arm via the LOCK_ORDER_CHECK
  # env var, and a False flag here must not silently strip their
  # instrumentation.
  if config.lock_order_check:
    lock_check.arm()
  if config.pbt_population >= 2:
    # PBT (round 22): the population loop owns the members' anakin
    # runs end to end — dispatch before any fleet machinery exists
    # (train_population validates the knob group itself, hard errors
    # included: a non-anakin runtime is rejected there).
    if fleet_factory is not None:
      raise ValueError('fleet_factory is a fleet-runtime seam; PBT '
                       'members are fused-loop anakin replicas')
    return train_population(config, max_steps=max_steps,
                            max_seconds=max_seconds,
                            drain_event=drain_event)
  if config.runtime == 'anakin':
    if fleet_factory is not None:
      raise ValueError('fleet_factory is a fleet-runtime seam; '
                       '--runtime=anakin has no fleet')
    return train_anakin(config, max_steps=max_steps,
                        max_seconds=max_seconds,
                        drain_event=drain_event)
  if max_seconds is not None and jax.process_count() > 1:
    # Wall clocks differ per host: a time-based exit is NOT a
    # deterministic function of the shared step count, so hosts would
    # leave the loop at different steps and deadlock the collective
    # final checkpoint (see the finally-block contract below).
    raise ValueError('max_seconds is single-host only; bound multi-host '
                     'runs by max_steps/total_environment_frames')
  levels = factory.level_names(config)
  fleet_tasks = population_lib.parse_fleet_tasks(config.fleet_tasks)
  if fleet_tasks:
    # Heterogeneous fleet (round 22): the task list REPLACES the level
    # list — one PopArt slot and one EpisodeStats curve per TASK, and
    # make_fleet recognizes this exact list and applies the weighted
    # actor plan. One policy head serves every task, so the per-task
    # action widths must agree (validate_population rejects the known
    # conflicts; this catches default-width drift, e.g. bandit's 3 vs
    # gridworld's 4 — pin --num_actions to resolve).
    levels = [name for name, _ in fleet_tasks]
    specs = [factory.make_env_spec(config, name, seed=1, backend=name)
             for name in levels]
    widths = sorted({s.num_actions for s in specs})
    if len(widths) > 1:
      raise ValueError(
          f'fleet_tasks suites disagree on action width {widths}: one '
          'shared policy head needs one width — set --num_actions')
    spec0 = specs[0]
  else:
    spec0 = factory.make_env_spec(config, levels[0], seed=1)
  num_actions = spec0.num_actions
  agent = build_agent(config, num_actions, num_tasks=len(levels))
  params = init_params(agent, jax.random.PRNGKey(config.seed),
                       spec0.obs_spec)
  num_popart_tasks = len(levels) if config.use_popart else 0

  # Multi-host: config.batch_size is GLOBAL; each host's fleet feeds
  # its process-local shard (SURVEY §5.8 — trajectory transport stays
  # host-local; only gradients ride ICI/DCN).
  num_processes = jax.process_count()
  if config.batch_size % num_processes != 0:
    raise ValueError(f'batch_size={config.batch_size} must divide by '
                     f'process count {num_processes}')
  local_batch_size = config.batch_size // num_processes

  if config.use_pallas_vtrace and config.use_associative_scan:
    # Fail before any env/checkpoint spin-up (vtrace re-checks at
    # trace time for library users).
    raise ValueError('use_pallas_vtrace and use_associative_scan are '
                     'mutually exclusive')
  if config.staging_mode not in ('batch', 'unroll'):
    raise ValueError(f'unknown staging_mode {config.staging_mode!r} '
                     '(batch | unroll)')
  # Sample-reuse knob group (round 10): fail on bad ranges before any
  # env/checkpoint spin-up; soft cross-link findings (vtrace-without-
  # anchor, mismatched staleness windows) are logged, not fatal.
  for warning in validate_replay(config):
    log.warning('%s', warning)
  # Transport-liveness knob group (round 11): same contract — hard
  # range errors raise, cross-links (reconnect window shorter than the
  # learner restart budget, heartbeat outside the reaping window) log.
  for warning in validate_transport(config):
    log.warning('%s', warning)
  # Data-plane integrity knob group (round 12): cross-link warnings
  # for a half-enabled integrity plane (SDC without the ladder, remote
  # ingest without wire CRC).
  for warning in validate_integrity(config):
    log.warning('%s', warning)
  # SLO knob group (round 14): hard range errors raise; cross-links
  # (engine without tracing, capture without the watchdog) log.
  for warning in validate_slo(config):
    log.warning('%s', warning)
  # Controller knob group (round 15): hard enum/range errors raise;
  # cross-links (controller without the SLO engine, act-mode replay
  # escalation without the IMPACT anchor) log.
  for warning in validate_controller(config):
    log.warning('%s', warning)
  # Runtime-axis knob group (round 16): a non-jittable filler backend
  # fails here before any env/checkpoint spin-up; cross-links (filler
  # without the IMPACT anchor, filler with the SLO engine off) log.
  for warning in validate_runtime(config):
    log.warning('%s', warning)
  # Serving-plane knob group (round 21): multi-tenant residency,
  # A/B + shadow fractions, routed-inference topology cross-links.
  for warning in validate_serving(config):
    log.warning('%s', warning)
  # Population knob group (round 22): curriculum ranges, mixed-fleet
  # composition, PBT topology — hard errors raise here (before the
  # mesh/fleet spin-up below); cross-links (curriculum on a backend
  # with no level space, multi-suite without PopArt) log.
  for warning in validate_population(config):
    log.warning('%s', warning)
  # NOTE round 8: the fused Pallas V-trace is no longer rejected under
  # a mesh — the sharded step runs it shard_map'ped over the data axis
  # (vtrace.py / ops/vtrace_pallas.sharded_from_importance_weights;
  # parity-gated on the 8-virtual-device mesh in tests/test_parallel).
  mesh = choose_mesh(config)
  # The ONE registry instance every sharding consumer of this run
  # queries (round 19, parallel/sharding.py): state placement, the
  # checkpoint manifest, and the publisher predicate all resolve from
  # the same declared rule set — private copies are a lint violation.
  registry = sharding_lib.from_config(config)
  if mesh is not None:
    from scalable_agent_tpu.testing import make_example_batch
    from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
    h, w, _ = spec0.frame_shape
    example_batch = make_example_batch(
        config.unroll_length + 1, config.batch_size, h, w, num_actions,
        MAX_INSTRUCTION_LEN)
    state = train_parallel.make_sharded_train_state(
        params, config, mesh, enable_tp=config.model_parallelism > 1,
        num_popart_tasks=num_popart_tasks, registry=registry)
    train_step, place_fn = train_parallel.make_sharded_train_step(
        agent, config, mesh, example_batch)
  else:
    state = learner_lib.make_train_state(params, config,
                                         num_popart_tasks)
    train_step = learner_lib.make_train_step(agent, config)
    # ONE tree-level async device_put (the per-leaf
    # device_put(np.asarray(x)) round trip dispatched leaf-at-a-time
    # and re-materialized already-host arrays); default-device
    # placement matches the unroll stager's steady-state slot
    # placement, so batch and unroll staging land identically.
    place_fn = jax.device_put

  # --- Checkpoint restore (reference: MonitoredTrainingSession auto-
  # restore from --logdir, ≈L570). ---
  # Elastic restore gate (round 20, elastic membership): when the
  # newest step's sharding manifest records a DIFFERENT mesh than this
  # run's (a 2-process checkpoint under a 4-process restart, or vice
  # versa), route through the registry's explicit resharding path —
  # targets respecified for the LIVE mesh, with the strict layout
  # check refusing cuts the new topology cannot honor — instead of the
  # implicit same-topology pinning. Fixed-topology restores take the
  # unchanged restore_latest path (docs/MIGRATION.md).
  elastic_restore = None

  def _restore(checkpointer, state):
    nonlocal elastic_restore
    topo_delta = (distributed.topology_delta(
        checkpointer.saved_mesh_shape(), mesh)
                  if mesh is not None else None)
    if topo_delta is None:
      return checkpointer.restore_latest(state)
    log.warning(
        'cross-topology restore: checkpoint saved on mesh %s, this '
        'run is mesh %s (%d process(es)) — resharding onto registry '
        'targets for the live topology', topo_delta['saved_mesh'],
        topo_delta['live_mesh'], topo_delta['processes'])
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = checkpointer.restore_resharded(abstract, registry, mesh)
    if restored is not None:
      elastic_restore = topo_delta
    return restored

  checkpointer, state = lifecycle.restore_at_start(
      config, state, mesh=mesh, registry=registry, restore=_restore)
  # Host-side step/frame mirror: the loop must not device_get the
  # on-device counter every iteration (that would sync the async
  # dispatch pipeline each step).
  _initial_steps = int(jax.device_get(state.update_steps))

  # --- Preemption resume: a drain manifest from a preempted run is
  # the handoff record — validate the restored step against it, then
  # CONSUME it (renamed, process 0) so a later unrelated restart does
  # not re-announce the same preemption. ---
  resume_manifest = read_resume_manifest(config.logdir)
  if resume_manifest is not None:
    manifest_steps = int(resume_manifest.get('update_steps', -1))
    if _initial_steps == manifest_steps:
      log.info('resuming from preemption drain manifest: step %d, '
               '%d frames (drain latency %.2fs, %d unroll(s) were '
               'left in the buffer)', manifest_steps,
               resume_manifest.get('frames', -1),
               resume_manifest.get('drain_latency_secs', -1.0),
               resume_manifest.get('buffer', {}).get(
                   'leftover_unrolls', 0))
    else:
      # The drain's verified checkpoint and the manifest disagree
      # (drain save failed → the ladder restored an older LAST_GOOD).
      # Resume anyway — frames between the checkpoint and the drain
      # point replay, the same at-least-once story as any crash.
      log.warning(
          'resume manifest names step %d but the restored checkpoint '
          'is step %d — resuming from the checkpoint (frames between '
          'them replay)', manifest_steps, _initial_steps)
    if jax.process_index() == 0:
      try:
        os.replace(os.path.join(config.logdir, RESUME_MANIFEST),
                   os.path.join(config.logdir,
                                RESUME_MANIFEST + '.consumed'))
      except OSError:
        log.exception('could not consume the resume manifest')

  # --- SDC sentinel (round 12): per-replica param fingerprints,
  # cross-checked host-side one step delayed. Pure-DP meshes with
  # >= 2 data replicas only — single device has nothing to compare,
  # TP-sharded params legitimately differ per device. ---
  sdc_fp_fn = None
  sdc_replicas = 0
  if (config.sdc_check and config.health_watchdog
      and train_parallel.supports_sdc_check(config, mesh)):
    sdc_fp_fn, sdc_replicas = train_parallel.make_sdc_fingerprint_fn(
        mesh)
    log.info('SDC sentinel armed: param fingerprints cross-checked '
             'across %d data replicas', sdc_replicas)

  # Multi-host TP: state.params are sharded ACROSS processes, so a
  # jit over them (the inference step) is a collective SPMD program —
  # and the batcher's computation thread invokes inference at
  # unsynchronized times per host, which deadlocks in the collective
  # (measured: device_get never returns). Actors must run on a FULL
  # host-local copy instead. process_allgather is itself a
  # collective, so every call site must be on the lockstep path
  # (same step, every host) — which publish_params_every is. The
  # predicate is the registry's (round 19): the publisher codec asks
  # the same sharding authority as the learner.
  localize_actor_params = sharding_lib.needs_host_local_params(
      config, mesh)

  def actor_params(params):
    if localize_actor_params:
      return multihost_utils.process_allgather(params, tiled=True)
    return params

  # Setup from here to the main loop's try/finally can raise (port
  # binds, env construction, 20–40 s inference compiles, fleet.start's
  # make_actor spawning env processes on this thread): the
  # already-listening ingest must not outlive a failed train() — a
  # bound zombie port serving stale v1 params would break retries in
  # the same process — and neither must the inference server (batcher
  # thread + warmed params/executables resident on the chip), the
  # prefetcher thread, a half-started fleet's env processes, or the
  # checkpoint manager's background threads.
  buffer = None
  ingest = None
  server = None
  fleet = None
  prefetcher = None
  life = None
  tracer = None
  ctrl = None
  filler = None
  # The remote-publish cadence as a mutable cell (round 15): the loop
  # below reads publish_cadence['secs'] instead of the frozen config
  # field, so the controller's publish_secs actuator can stretch it
  # live (a float store/load is GIL-atomic).
  publish_cadence = {'secs': float(config.remote_publish_secs)}
  try:
    # --- Trajectory buffer + remote ingest, BEFORE inference warmup:
    # remote actor hosts connect and fetch params while this host
    # spends its 20–40 s compiling, instead of timing out against a
    # closed port (reference's learner-hosted shared FIFOQueue that
    # remote actors enqueue into, ≈L470/SURVEY §3.4 — remote unrolls
    # land in the SAME buffer as the local fleet's, so downstream is
    # source-oblivious). ---
    capacity = max(config.queue_capacity_batches * config.batch_size,
                   config.batch_size)
    # Circular replay tier (round 10, IMPACT): retains consumed
    # unrolls behind the FIFO so get_unrolls can compose
    # fresh:replayed batches; staleness is measured in published
    # param-version deltas against the version fed by the publish
    # cadence below (the same unit --max_unroll_staleness gates
    # ingest admission with).
    replay_tier = None
    if config.replay_ratio > 0:
      replay_tier = ring_buffer.ReplayTier(
          config.resolved_replay_capacity,
          max_staleness=config.resolved_replay_max_staleness,
          verify_crc=config.replay_crc)
    buffer = ring_buffer.TrajectoryBuffer(
        capacity, replay=replay_tier, replay_ratio=config.replay_ratio)
    buffer.note_param_version(_initial_steps)
    frames_per_unroll = config.unroll_length * config.num_action_repeats
    # Serve-time fresh-frame accounting is ALSO armed whenever an
    # acting controller could raise replay_k mid-run (round 15): the
    # steps-derived arithmetic would overcount env frames the moment
    # the knob moves, and the serve-time counter is exact at
    # replay_k=1 too. The hybrid filler (round 16) arms it for the
    # same reason from the other side: filler steps are learner
    # updates that consume ZERO fresh env frames, so only the
    # serve-time counter keeps the frame budget / LR clock / fps on
    # the fleet's fresh-frame clock.
    reuse_on = (config.replay_k > 1 or config.replay_ratio > 0
                or config.controller == 'act' or config.anakin_filler)
    # ONE localization for both the ingest snapshot and the inference
    # server, UNCONDITIONALLY before the ingest branch: actor_params
    # is a cross-host collective in multi-host-TP mode, and
    # remote_actor_port legitimately differs per host (mixed
    # topologies enable ingest on some hosts only) — a collective
    # inside that branch would desync the hosts' collective sequences
    # and hang the job at startup.
    initial_pub = actor_params(state.params)
    if config.remote_actor_port:
      from scalable_agent_tpu.runtime import remote
      # device_get of the LOCALIZED copy (a raw device_get of
      # cross-process-sharded params would raise on non-addressable
      # shards; on the plain path this is the ordinary host copy).
      ingest = remote.TrajectoryIngestServer(
          buffer, jax.device_get(initial_pub),
          host=config.remote_actor_bind_host,
          port=config.remote_actor_port,
          contract=remote.trajectory_contract(config, agent,
                                              num_actions),
          wire_dtype=config.resolved_wire_dtype,
          ingest_workers=config.ingest_workers,
          max_unroll_staleness=config.max_unroll_staleness,
          heartbeat_secs=config.remote_heartbeat_secs,
          idle_timeout_secs=config.remote_conn_idle_timeout_secs,
          wire_crc=config.wire_crc,
          trace=config.telemetry_trace)
      log.info('remote-actor ingest listening on port %d '
               '(session epoch %d)', ingest.port, ingest.session_epoch)
    # --- Inference server (weights served host-side to actor
    # threads). Per-process seed offset: params/init use config.seed
    # IDENTICALLY on every host (multi-host device_put asserts
    # equality), while env and action-sampling streams must NOT repeat
    # across hosts. ---
    process_index = jax.process_index()
    process_seed_base = process_index * max(config.num_actors, 1000)
    server = InferenceServer(agent, initial_pub, config,
                             seed=config.seed + 1000 + process_seed_base,
                             fleet_size=config.num_actors)
    # update_params COPIES: the constructor stores its argument by
    # reference, and in the non-localized path that is state.params
    # itself — which the first train step DONATES. Without this copy,
    # actors would run inference on deleted buffers (real on TPU;
    # invisible on CPU tests, where jit ignores donation).
    server.update_params(initial_pub, version=_initial_steps)
    if getattr(agent, 'prefill_chunk', 0):
      server.close()
      raise ValueError(
          'this agent\'s core takes an episode\'s prompt as a block '
          'through the inference server (prefill), which no unroll '
          'records, so the learner\'s pass over the unroll would not be '
          'the actor\'s: such a policy can be served (driver.play), not '
          'yet trained')
    state_bytes = server.stats()['state_bytes_per_slot']
    if state_bytes > inference_lib.MAX_HOST_STATE_BYTES:
      server.close()
      raise ValueError(
          'the learner needs every unroll\'s starting state '
          f'(ActorOutput.agent_state), and a state of {state_bytes} '
          'bytes a session never leaves the inference server\'s '
          'arena: such a policy '
          'can be served (driver.play), not yet trained')
    # Pre-compile inference buckets up to the fleet size: a bucket's
    # first appearance otherwise stalls every parked actor for the TPU
    # compile (the reference's TF graph had dynamic batch dims). With
    # no local fleet (remote-ingest-only learners, synthetic
    # fleet_factory benches) nothing calls local inference — skip the
    # 20–40 s compile.
    if config.num_actors > 0:
      server.warmup(spec0.obs_spec, max_size=config.num_actors)
    # v10 routed serving (round 21): the ingest listener answers
    # 'infer' requests with this host's InferenceServer — actor hosts
    # running a ServingRouter spread batches across learner replicas.
    # Attached AFTER warmup so a routed batch never pays first-call
    # compile for the warm buckets.
    if ingest is not None:
      ingest.attach_serving(server.serve_remote)

    if fleet_factory is None:
      fleet = make_fleet(config, agent, server.policy, buffer, levels,
                         seed_base=process_seed_base,
                         initial_state_fn=server.initial_core_state)
    else:
      fleet = fleet_factory(config, agent, server.policy, buffer,
                            levels)

    def stage(host_batch, n_fresh=None):
      """Prefetcher stage: peel off a tiny host-side stats view (done /
      info / level ids / action counts — the batch is host numpy right
      here) BEFORE the device transfer, so the train loop never
      device_gets frames just to read episode stats.

      `n_fresh` (passed by the prefetcher when a replay tier composes
      the batch) bounds the peel to the FRESH columns — replayed slots
      already recorded their episodes/actions on first consumption, so
      peeling them again would double-count env-plane stats."""
      nf = (np.asarray(host_batch.level_name).shape[0]
            if n_fresh is None else n_fresh)
      stats_view = _stats_only_view(
          np.asarray(host_batch.level_name)[:nf],
          jax.tree_util.tree_map(lambda x: np.asarray(x)[:, :nf],
                                 host_batch.env_outputs.info),
          np.asarray(host_batch.env_outputs.done)[:, :nf])
      # Action histogram source (reference build_learner's
      # tf.summary.histogram, ≈L395): bincount of the trained-on
      # actions ([1:] drops the overlap row, like the loss shift).
      action_counts = np.bincount(
          np.asarray(host_batch.agent_outputs.action)[1:, :nf].ravel(),
          minlength=num_actions)
      return stats_view, action_counts, place_fn(host_batch)

    # --- Per-unroll host stats peel + batch finalize: the unroll
    # staging plane's split of stage() — the tiny leaves (done / info
    # / level id / action bincount) peel per unroll while it is host
    # numpy; the frames never come back, and the per-batch host work
    # is a [T+1, B]-of-scalars stack instead of the 67.5 MB frame
    # stack (BENCH_r05 stack_ms 37.5). ---
    def unroll_view(unroll):
      return (
          np.asarray(unroll.level_name),
          jax.tree_util.tree_map(np.asarray, unroll.env_outputs.info),
          np.asarray(unroll.env_outputs.done),
          np.bincount(np.asarray(unroll.agent_outputs.action)[1:],
                      minlength=num_actions))

    def finalize_views(views, batch_device):
      stats_view = _stats_only_view(
          np.stack([v[0] for v in views]),
          jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=1),
                                 *[v[1] for v in views]),
          np.stack([v[2] for v in views], axis=1))
      action_counts = np.sum([v[3] for v in views], axis=0)
      return stats_view, action_counts, batch_device

    stager = None
    if config.staging_mode == 'unroll':
      if train_parallel.supports_unroll_staging(config, mesh):
        if mesh is None:
          slot_devices, assemble_fn = None, None
        else:
          slot_devices, assemble_fn = train_parallel.make_unroll_assembly(
              config, mesh, example_batch)
        stager = ring_buffer.UnrollBatchStager(
            local_batch_size, slot_devices=slot_devices,
            assemble_fn=assemble_fn, host_view_fn=unroll_view,
            finalize_fn=finalize_views)
      else:
        log.warning(
            'staging_mode=unroll unsupported on this topology '
            '(model-axis batch sharding or local batch %d not '
            'divisible by the local data width) — falling back to '
            'batch staging', local_batch_size)
    _reserve_counts = np.zeros((num_actions,), np.int64)

    def reserve_view(item):
      """Re-serve transform (replay_k > 1): the staged device batch
      rides again untouched; the env-plane view must NOT — a re-serve
      consumes zero new env frames, so its episode stats are None and
      its action counts zero (the loop skips both)."""
      return None, _reserve_counts, item[2]

    prefetcher = ring_buffer.BatchPrefetcher(
        buffer, local_batch_size, place_fn=stage,
        depth=config.staging_depth, stager=stager,
        replay_k=config.replay_k, reserve_fn=reserve_view)

    # Env-frame accounting under sample reuse (round 10): with
    # replay_k > 1 or replay_ratio > 0 a learner step no longer
    # consumes frames_per_step FRESH env frames, so the frame budget,
    # fps meter, TrainRun.frames, and the drain manifest count fresh
    # unroll slots at SERVE time instead — the prefetcher's
    # fresh_slots_served counter, credited at each batch's first
    # serve, so the figure is immune to prefetch lookahead. The
    # pre-resume base is still approximated as steps ×
    # frames_per_step — exact for histories trained without reuse
    # (the counter does not survive the process). With reuse off this
    # stays the old steps-derived arithmetic exactly.
    env_frames_fn = None
    if reuse_on:
      # Per-host counter → global frames (local_batch_size slots per
      # host-local batch; multi-host reuse keeps the same scale-up the
      # steps-derived arithmetic applies).
      hosts_scale = max(config.batch_size // max(local_batch_size, 1),
                        1)
      resumed_frames = _initial_steps * config.frames_per_step

      def env_frames_fn():
        return (resumed_frames +
                prefetcher.fresh_slots_served() *
                frames_per_unroll * hosts_scale)

    # Telemetry plane (round 13, telemetry.py): the pipeline tracer
    # completes per-unroll trace spans (actor → wire → ingest →
    # staging → serve → step) into traces.jsonl and keeps the flight
    # recorder the halt/rollback diagnostics dump. Installed
    # process-globally BEFORE fleet.start() so the first unroll is
    # already stamped; the finally clears and closes it.
    if config.telemetry_trace:
      tracer = telemetry.PipelineTracer(
          config.logdir,
          filename=('traces.jsonl' if process_index == 0
                    else f'traces_p{process_index}.jsonl'),
          flight_capacity=config.telemetry_flight_len,
          epoch=(ingest.session_epoch if ingest is not None else None))
      telemetry.set_tracer(tracer)

    def _sdc_dispatch(step_now, state):
      # SDC fingerprints ride the ladder's delayed-read cadence: the
      # [replicas] uint32 array is dispatched NOW (before the next
      # step donates the state) and read one check later. The
      # 'replica_divergence' fault site fires here — one event per
      # health check — perturbing one replica's probe lane so the
      # real detection→rollback path executes.
      probe = np.zeros((sdc_replicas,), np.uint32)
      div = faults_lib.fire('replica_divergence')
      if div is not None:
        victim = div.index % sdc_replicas
        probe[victim] = np.uint32(1 + (div.index % 1000))
        incidents.event('fault_replica_divergence', step=step_now,
                        replica=victim)
      return sdc_fp_fn(state.params, probe)

    def _sdc_read(obs_step, fp_handle):
      fps = np.asarray(jax.device_get(fp_handle))
      sdc_mismatch = bool((fps != fps[0]).any())
      if sdc_mismatch:
        incidents.event('sdc_replica_mismatch', step=obs_step,
                        fingerprints=[int(x) for x in fps])
        log.error(
            'SDC sentinel: per-replica param fingerprints DISAGREE at '
            'step %d: %s — deterministic compute violated (suspect '
            'chip/HBM; docs/RUNBOOK.md §9)', obs_step,
            [f'{int(x):08x}' for x in fps])
      return {'sdc_replica_mismatch': 1.0 if sdc_mismatch else 0.0}

    def _rollback_restore_all_hosts(state):
      # Hosts must enter the (collective) restore with the SAME step:
      # the per-host ladder could diverge on host-local I/O errors.
      # Process 0 chooses; everyone follows — the broadcast is safe
      # here because verdicts are a deterministic function of the
      # replicated metrics, so every host reaches this in lockstep.
      choice = int(multihost_utils.broadcast_one_to_all(
          jnp.asarray(checkpointer.rollback_step_choice(), jnp.int32)))
      return (checkpointer.restore_step(choice, state)
              if choice >= 0 else None)

    def _republish_rolled_back(step_now, state):
      published = actor_params(state.params)
      server.update_params(published)
      rolled_remote_version = None
      if ingest is not None:
        rolled_remote_version = ingest.publish_params(
            jax.device_get(published))
      if tracer is not None:
        # The rollback republish is a real publish: the local lag
        # clock and the install join both see it.
        tracer.on_publish(step_now,
                          remote_version=rolled_remote_version)

    # Summaries, incidents (bad-step bursts, rollbacks, halts, fault
    # injections: what the scalars can't narrate; chaos.py reads them
    # for its SLOs), health watchdog, SLO engine: lifecycle.open_run.
    life = lifecycle.open_run(
        config, checkpointer,
        flight=(tracer.flight if tracer is not None else None),
        rollback_restore=(None if num_processes == 1
                          else _rollback_restore_all_hosts),
        on_rollback=_republish_rolled_back,
        extra_sentinels=((_sdc_dispatch, _sdc_read)
                         if sdc_fp_fn is not None else None))
    writer, incidents, fps_meter = (life.writer, life.incidents,
                                    life.fps_meter)
    health, slo_engine = life.health, life.slo_engine
    # The elastic restore above predates the incident stream: announce
    # it here so the topology change is on the record, not just in the
    # log (round 20).
    if elastic_restore is not None:
      incidents.event('topology_resharded', step=_initial_steps,
                      **elastic_restore)
    stats = observability.EpisodeStats(
        levels,
        benchmark=(config.level_name
                   if config.level_name in suites.SUITES else None),
        writer=writer)
    run = TrainRun(config, agent, state, fleet, prefetcher, server,
                   checkpointer, writer, stats, fps_meter,
                   ingest=ingest, health=health)
    run._env_frames_fn = env_frames_fn
    run.mesh = mesh
    run.train_step = train_step
    fleet.start()
    # --- Self-healing controller (round 15, controller.py): the
    # verdict-to-actuation half of the control loop. The policy table
    # maps the SLO engine's burning set + margins to bounded moves on
    # the actuators this topology exposes: the prefetcher's replay_k,
    # the inference server's admission mode, the remote publish
    # cadence (the mutable cell below — the loop reads it instead of
    # the frozen config field), and the fleet's elastic target size
    # (grow = unpark/rehabilitate quarantined slots via probation).
    # observe mode evaluates and logs every move without touching
    # anything; the finally writes CONTROLLER_LOG.json either way. ---
    if config.controller != 'off' and slo_engine is not None:
      ctrl_rules = controller_lib.load_rules(config.controller_policy)
      actuators = [
          controller_lib.Actuator(
              'replay_k', kind='int',
              get_fn=lambda: prefetcher.replay_k,
              set_fn=prefetcher.set_replay_k,
              minimum=1,
              maximum=max(config.controller_replay_k_max,
                          config.replay_k)),
          controller_lib.Actuator(
              'admission', kind='enum',
              get_fn=lambda: server.admission,
              set_fn=server.set_admission,
              values=inference_lib.ADMISSION_POLICIES),
      ]
      if ingest is not None:
        actuators.append(controller_lib.Actuator(
            'publish_secs', kind='float',
            get_fn=lambda: publish_cadence['secs'],
            set_fn=lambda v: publish_cadence.__setitem__(
                'secs', float(v)),
            minimum=float(config.remote_publish_secs),
            maximum=max(config.controller_publish_secs_max,
                        float(config.remote_publish_secs))))
      if config.num_actors > 0 and hasattr(fleet, 'set_target_size'):
        actuators.append(controller_lib.Actuator(
            'fleet_size', kind='int',
            get_fn=fleet.target_size,
            set_fn=fleet.set_target_size,
            minimum=1, maximum=config.num_actors))
      # Pod topology actuator (round 20, elastic membership): the
      # pod-level set_target_size. DECLARATIVE — the learner cannot
      # spawn hosts, so a move publishes the desired host count to
      # <logdir>/POD_TARGET.json (atomic replace) for the cluster
      # supervisor (chaos.py's elastic storm; an operator's
      # orchestration in production) to reconcile against. Process 0
      # only, per the per-actuator-ownership rule — one pod, one
      # declared target, exactly like the checkpoint manifests.
      if (ingest is not None and process_index == 0
          and config.pod_max_hosts > 0):
        pod_target = {'hosts': None}  # None = never moved: mirror live

        def _pod_target_get():
          if pod_target['hosts'] is not None:
            return pod_target['hosts']
          return max(ingest.live_hosts(), 1)

        def _pod_target_set(n):
          pod_target['hosts'] = int(n)
          payload = {'target_hosts': int(n),
                     'live_hosts': ingest.live_hosts(),
                     'membership': ingest.membership(),
                     'wall_time': round(time.time(), 3)}
          path = os.path.join(config.logdir, 'POD_TARGET.json')
          tmp = f'{path}.tmp'
          with open(tmp, 'w') as f:
            json.dump(payload, f, indent=2)
          os.replace(tmp, path)

        actuators.append(controller_lib.Actuator(
            'pod_size', kind='int',
            get_fn=_pod_target_get, set_fn=_pod_target_set,
            minimum=1, maximum=config.pod_max_hosts))
      ctrl_interval = (config.controller_interval_secs
                       if config.controller_interval_secs > 0
                       else life.slo_interval)
      ctrl = controller_lib.Controller(
          slo_engine, ctrl_rules, actuators, config.logdir,
          mode=config.controller, interval_secs=ctrl_interval,
          incidents=incidents, health=health,
          log_name=('CONTROLLER_LOG.json' if process_index == 0
                    else f'CONTROLLER_LOG_p{process_index}.json'))
      run.controller = ctrl
      ctrl.start()
      log.info('controller started in %r mode: %d rule(s) over %d '
               'actuator(s)', config.controller, len(ctrl._rules),
               len(actuators))
    elif config.controller != 'off':
      log.warning('controller=%s ignored: the SLO engine is off and '
                  'the controller has no other input',
                  config.controller)
    # --- Hybrid filler (round 16, anakin.HybridFiller): idle feed
    # slices run ONE bounded Anakin self-play step on the learner
    # chips instead of parking — the loop below consults
    # prefetcher.ready() (the ready-without-dequeue probe) so a
    # staged batch is never delayed by more than one filler step.
    # validate_runtime already rejected non-jittable backends; an
    # unsupported TOPOLOGY (model-axis mesh, indivisible filler
    # batch) degrades to plain parking with a warning like the
    # staging-mode fallback — but a genuinely bad knob combination
    # (e.g. a filler core that cannot honor the main task's
    # action-space width) RAISES here, at spin-up, like every other
    # validate_* error: an explicitly requested feature must never be
    # silently off for the whole run.
    if config.anakin_filler:
      from scalable_agent_tpu.parallel import anakin as anakin_lib
      filler_ok, filler_reason = anakin_lib.supports_filler(config,
                                                            mesh)
      if not filler_ok:
        log.warning('anakin_filler disabled on this topology: %s',
                    filler_reason)
      else:
        filler = anakin_lib.HybridFiller(agent, config, num_actions,
                                         mesh=mesh)
        log.info(
            'hybrid filler armed: %r self-play (B=%d, T=%d) fills '
            'idle learner slices; fresh-frame clocks unchanged',
            filler.backend, filler.stats()['batch_size'],
            filler.stats()['unroll_length'])
  except BaseException:
    # Best-effort bounded teardown, most-critical-first: the ingest
    # port release leads (a second interrupt landing mid-cleanup must
    # not leave the bound zombie port), slow thread joins go last, and
    # one failing step must not skip the rest.
    def _try(fn):
      try:
        fn()
      except Exception:
        log.exception('train() setup-failure cleanup step failed')
    if ingest is not None:
      # Setup failure = crash semantics: remote actors keep their
      # reconnect window for the supervisor's retry (graceful=True
      # would 'bye' them into permanent exit — see the main finally).
      _try(lambda: ingest.close(graceful=False))
    if buffer is not None:
      _try(buffer.close)
    if prefetcher is not None:
      _try(prefetcher.close)
    if server is not None:
      _try(server.close)
    if fleet is not None:
      _try(lambda: fleet.stop(timeout=2.0))
    if ctrl is not None:
      _try(ctrl.stop)  # no log finalize: the run never started
    if life is not None:
      _try(life.abort)  # no verdict: the run never started
    if tracer is not None:
      _try(lambda: telemetry.set_tracer(None))
      _try(tracer.close)
    if filler is not None:
      _try(filler.close)
    _try(checkpointer.close)
    raise

  steps_done = 0
  errors: List[BaseException] = []

  def env_frames():
    if env_frames_fn is not None:
      return env_frames_fn()
    return (_initial_steps + steps_done) * config.frames_per_step

  # The step and frame clocks in the registry: closures over the loop
  # locals (the env-frames one reaches the prefetcher).
  life.loop_gauges(update_steps=lambda: steps_done + _initial_steps,
                   env_frames=env_frames)
  # Plane-state gauges (round 14): the summary block's utilization
  # split and fleet quorum, registered into the unified registry so
  # the SLO engine (and the flight recorder / drain manifest) judge
  # the SAME numbers the summaries carry. Created lazily at the first
  # summary interval — a default 0.0 before any measurement would
  # read as a dead plane to the env_plane_utilization objective.
  _plane_gauges: Dict[str, telemetry.Gauge] = {}

  def _set_plane_gauge(name, value):
    gauge = _plane_gauges.get(name)
    if gauge is None:
      # Literal registration names (the ci.sh lint contract).
      if name == 'env':
        gauge = telemetry.gauge('driver/env_plane_utilization')
      elif name == 'learner':
        gauge = telemetry.gauge('driver/learner_plane_utilization')
      elif name == 'hosts':
        gauge = telemetry.gauge('driver/remote_live_hosts')
      else:
        gauge = telemetry.gauge('driver/fleet_healthy_fraction')
      _plane_gauges[name] = life.track(gauge)
    gauge.set(value)
  # Preemption-drain state: set once the drain is requested (SIGTERM
  # via drain_event, or the deterministic 'preempt_signal' fault);
  # the loop then flushes the already-produced feed instead of
  # breaking mid-pipeline, and the post-loop finalize takes the
  # verified checkpoint + writes the resume manifest.
  draining = False
  drain_t0 = None
  drain_deadline = None
  drain_source = None
  action_counts_acc = np.zeros((num_actions,), np.int64)
  last_publish_step = _initial_steps   # resume-manifest param version
  last_quarantined_slots = 0
  last_remote_publish = float('-inf')
  last_pf_snap = {'gets': 0, 'wait_secs': 0.0}
  # Sample-reuse / plane-utilization snapshot (round 10): per-interval
  # deltas for learner_updates_per_env_frame and the env-vs-learner
  # utilization split.
  last_reuse_snap = {'steps': 0, 'fresh_unrolls': 0,
                     'put_wait_secs': 0.0, 'time': time.monotonic()}
  last_inference_snap = {'calls': 0, 'requests': 0}
  last_ingest_snap = {'unrolls': 0, 'per_conn_unrolls': {}}
  last_ingest_time = time.monotonic()
  loop_start = time.monotonic()
  last_summary = time.monotonic()
  last_batch_time = time.monotonic()
  # Hybrid-filler loop state (round 16): liveness-check gate for the
  # filling regime + the incident edge detector for withheld
  # (non-finite) filler updates.
  last_filler_check = time.monotonic()
  last_filler_skipped = 0
  poll_secs = 10.0 if stall_timeout_secs is None else min(
      10.0, stall_timeout_secs)
  # Recorder span 'learner/iteration': one per learner STEP, from the
  # end of the last step's pass of this loop to the end of this one's,
  # whatever passes without a batch (a timed-out get, a filler slice)
  # lie between. A `park`, as the wait it holds: seconds long, so kept
  # where it straddles an end of a capture.
  iteration = None
  # The learner thread's rare, heavy work, as activities (remembered
  # with the recorder off: telemetry.excess asks them when a step of
  # an actor thread ran long). Ended in the `finally` too.
  publish = summaries = telemetry.NO_SPAN
  try:
    while True:
      if iteration is None:
        iteration = telemetry.park('learner/iteration')
      # --- Preemption drain request (SIGTERM via drain_event, or the
      # deterministic 'preempt_signal' fault site): quiesce instead of
      # dying mid-step. The fault site is consulted every loop
      # iteration (one event per step, like nan_burst). ---
      preempt_fault = faults_lib.fire('preempt_signal') is not None
      if not draining and (preempt_fault or (
          drain_event is not None and drain_event.is_set())):
        if num_processes > 1:
          # The drain checkpoint is NOT a collective save; a one-host
          # drain would deadlock the others. Exit the loop — the
          # periodic collective checkpoints cover the tail.
          log.warning('preemption requested on a multi-host run: '
                      'drain is single-host, exiting the loop')
          break
        draining = True
        drain_source = 'fault' if preempt_fault else 'signal'
        drain_t0 = time.monotonic()
        drain_deadline = drain_t0 + config.preempt_drain_timeout_secs
        incidents.event('preempt_drain_start',
                        step=steps_done + _initial_steps,
                        source=drain_source)
        log.warning(
            'preemption drain (%s): admissions stopped; flushing '
            'in-flight unrolls within %.1fs', drain_source,
            config.preempt_drain_timeout_secs)
        # Stop production WITHOUT closing the buffer: actors finish
        # their current unroll, put it, and exit — those unrolls are
        # exactly what the flush below trains on. (Custom fleet
        # factories without a stop seam still drain: the feed just
        # keeps producing until the deadline.)
        if hasattr(fleet, 'stop_event'):
          fleet.stop_event.set()
      if draining and time.monotonic() > drain_deadline:
        log.warning('preemption drain budget exhausted; finalizing')
        break
      frames = env_frames()
      if frames >= config.total_environment_frames:
        break
      if max_steps is not None and steps_done >= max_steps:
        break
      if (max_seconds is not None and
          time.monotonic() - loop_start > max_seconds):
        break
      # --- Hybrid filler slice (round 16): nothing staged right now,
      # so the learner chips run ONE bounded Anakin self-play step
      # instead of parking in prefetcher.get. fill_one BLOCKS on the
      # step's completion, so a batch staged meanwhile waits at most
      # one filler step (the yield-determinism contract,
      # tests/test_filler.py); the next iteration re-probes. Filler
      # updates mutate params but never advance update_steps — the
      # frame budget, LR schedule, and fps meter stay on the fleet's
      # fresh-frame clock (serve-time accounting, armed above). ---
      if (filler is not None and not draining
          and not prefetcher.ready()):
        run.state = filler.fill_one(run.state)
        state = run.state
        now_fill = time.monotonic()
        if now_fill - last_filler_check > poll_secs:
          # The starved branch's liveness duties, time-gated so a
          # microsecond filler step doesn't health-check every slice:
          # a dead fleet must still surface through the filler regime
          # (filler frames must not mask a dead env plane — the
          # env_plane_utilization objective pages, and the stall raise
          # below still fires).
          last_filler_check = now_fill
          errors = fleet.errors() or errors
          fleet.check_health(stall_timeout_secs=stall_timeout_secs)
          # (A respawn's rebuild on this thread: see the get below.)
          last_batch_time += time.monotonic() - now_fill
          if (stall_timeout_secs is not None and
              now_fill - last_batch_time >
              max(3 * stall_timeout_secs, 30.0)):
            raise errors[0] if errors else TimeoutError(
                'no trajectory batch despite healthy actors (hybrid '
                'filler kept the learner busy; the env plane is the '
                'incident)')
        continue
      try:
        stats_view, action_counts, batch_device = prefetcher.get(
            timeout=0.5 if draining else poll_secs)
      except TimeoutError:
        if draining:
          break  # the feed dried up: every drainable batch is trained
        # No data yet: surface actor failures instead of hanging (the
        # reference hangs silently here — SURVEY §5.3). Read errors
        # BEFORE check_health — a respawn clears the slot's error, and
        # a crash-looping actor's root cause must survive to the stall
        # raise below (same ordering as evaluate()).
        errors = fleet.errors() or errors
        checked = time.monotonic()
        fleet.check_health(stall_timeout_secs=stall_timeout_secs)
        # A respawn rebuilds envs on THIS thread (a group's k, in
        # turn): the time that took is not the fleet's silence, and
        # must not run the deadline out before the new envs can feed.
        last_batch_time += time.monotonic() - checked
        if (stall_timeout_secs is not None and
            time.monotonic() - last_batch_time >
            max(3 * stall_timeout_secs, 30.0)):
          raise errors[0] if errors else TimeoutError(
              'no trajectory batch despite healthy actors')
        continue
      except ring_buffer.Closed:
        if draining:
          break
        errors = fleet.errors() or errors
        if errors:
          raise errors[0]
        raise
      last_batch_time = time.monotonic()
      # Fault site 'learner_crash' (round 11): one event per CONSUMED
      # batch — a scheduled event hard-kills this process (SIGKILL: no
      # unwind, no drain, no 'bye'). kill -9/OOM made deterministic
      # for chaos.py's run_partition_storm, which runs the learner as
      # a child, restarts it, and asserts the restore-from-LAST_GOOD +
      # fleet re-attach SLOs.
      crash = faults_lib.fire('learner_crash')
      if crash is not None:
        faults_lib.hard_crash(crash)
      # Data is flowing again: captured errors are from a recovered
      # incident; keeping them would misattribute a much later stall.
      errors = []
      # The profiler (SURVEY §5.1 — the reference has no tracing at
      # all): the operator's window, placed after warmup so compiles
      # don't drown the timeline, or an SLO page's bounded capture.
      life.profiler.tick(steps_done)
      # Fault-injection seam (runtime/faults.py 'nan_burst'): rewards
      # become NaN on the staged device batch, driving a non-finite
      # loss through the REAL loss/grad path — what organic divergence
      # looks like to the watchdog.
      batch_device, poisoned = faults_lib.maybe_poison_batch(
          batch_device)
      if poisoned:
        incidents.event('fault_nan_burst',
                        step=steps_done + _initial_steps + 1)
      # Fault site 'slow_learner': a stalled step (device contention,
      # preempted neighbors) — the buffer must fill and producer-side
      # backpressure engage, never unbounded queueing (the overload
      # storm's occupancy SLO).
      slow = faults_lib.fire('slow_learner')
      if slow is not None and slow.kind == 'hang':
        time.sleep(float(slow.param))
      with telemetry.span('learner/step_dispatch'):
        state, metrics = train_step(run.state, batch_device)
      run.state = state
      steps_done += 1
      if env_frames_fn is None:
        fps_meter.update(config.frames_per_step)
      else:
        # `frames` is this iteration's pre-serve reading, so the delta
        # is exactly the fresh frames this batch's first serve
        # credited — 0 on a re-serve, keeping fps an ENV-frame rate.
        fps_meter.update(max(env_frames_fn() - frames, 0))
      action_counts_acc += action_counts

      # Episode stats ride in the trajectory; the prefetcher peeled a
      # host-side view before the device transfer — no device_get here.
      step_now = steps_done + _initial_steps
      # Trace spans (round 13): the step consuming the oldest served
      # batch was just dispatched — complete its spans and emit the
      # batch record with the policy-lag vector (traces.jsonl).
      if tracer is not None:
        tracer.on_step(step_now)
      # Stack this step's scalar metrics into ONE device array now —
      # BEFORE the next step is dispatched, so the tiny stack
      # computation precedes it on the device stream. The summary
      # block reads the PREVIOUS step's stack: already computed, one
      # transfer, no dispatch-pipeline sync (the health-sentinel
      # pattern applied to the whole metrics dict — round 8; the old
      # path device_get each key separately against just-dispatched
      # values).
      life.metrics.push(step_now, metrics)
      # A re-served batch (replay_k > 1) carries no env-plane view —
      # its episodes/actions were recorded on the first serve.
      if stats_view is not None:
        for name, ep_return, ep_frames in stats.record_batch(
            stats_view, step_now):
          log.info('episode %s return=%.2f frames=%d', name, ep_return,
                   ep_frames)

      # Escalation ladder (health.py): skip → rollback → halt, on a
      # one-step-delayed read.
      state = life.ladder.step(step_now, metrics, state)
      run.state = state

      if steps_done % config.publish_params_every == 0:
        publish = telemetry.activity('learner/publish')
        # actor_params is a cross-host collective in multi-host-TP
        # mode: it must run UNCONDITIONALLY here (lockstep branch),
        # never inside the per-host time-gated ingest publish below.
        # version=step_now gates the server's whole-tree copy: a
        # republish of the same step's snapshot is a counted no-op.
        published = actor_params(state.params)
        server.update_params(published, version=step_now)
        last_publish_step = step_now
        # Replay staleness clock (round 10): retained unrolls age in
        # published param versions — the same unit the ingest
        # admission window uses.
        buffer.note_param_version(step_now)
        remote_version = None
        if (ingest is not None and
            time.monotonic() - last_remote_publish >=
            publish_cadence['secs'] and
            ingest.stats()['live'] > 0):
          # Remote hosts poll-on-ack: publishing bumps the version the
          # next ack reports (the reference's per-run gRPC weight
          # fetch, as an explicit snapshot). Unlike the local pointer
          # swap above, this is a blocking device_get of the whole
          # param tree — hence the wall-clock throttle and the
          # nobody-connected gate. (Already host numpy when the
          # multi-host-TP localization ran; device_get is then a
          # pass-through.)
          last_remote_publish = time.monotonic()
          remote_version = ingest.publish_params(
              jax.device_get(published))
        # Trace record + the local publish clock policy lag counts
        # in. The INGEST-LANE version rides along when this snapshot
        # also went to the remote fleet: actors' install notices
        # carry that sequence, and trace_report's publish→install
        # join keys on it.
        if tracer is not None:
          tracer.on_publish(step_now, remote_version=remote_version)
        publish.end()

      now = time.monotonic()
      if now - last_summary >= config.summary_secs:
        last_summary = now
        summaries = telemetry.activity('learner/summaries')
        # One-step-delayed stacked read (round 8): the previous step's
        # metrics land in a single transfer of already-computed values.
        # Written at step_now — one step stale, immaterial at summary
        # cadence, and it keeps the summary step sequence monotone
        # (episode events already wrote step_now; the chaos SLO and
        # downstream readers assert non-decreasing steps). Only the
        # very first step has no predecessor — that one read blocks on
        # the fresh dispatch, like the old path always did.
        writer.scalars(
            observability.read_stacked_metrics(life.metrics.older()[1]),
            step_now)
        writer.scalar('env_frames_per_sec', fps_meter.fps(), step_now)
        # Telemetry plane (round 13): the live policy-lag and
        # end-to-end span percentiles (the trace stream's headline
        # numbers, exported on the summary cadence so a lag blow-up
        # shows without a trace_report run), and one registry
        # snapshot into the flight recorder — the "what were the
        # counters doing just before" half of an incident dump. NaN
        # until traffic flows (rendered '-', not a fake 0).
        if tracer is not None:
          for tag, value in tracer.span_percentiles().items():
            writer.scalar(tag, value, step_now)
          writer.scalar('trace_untagged_unrolls',
                        tracer.stats()['untagged_unrolls'], step_now)
          tracer.flight.note_registry(telemetry.registry().snapshot())
        fleet_stats = fleet.stats(
            healthy_horizon_secs=(stall_timeout_secs
                                  if stall_timeout_secs else 60.0))
        writer.scalar('actors_alive', fleet_stats['alive'], step_now)
        # alive vs healthy (round 7): a wedged actor is alive without
        # producing — the quorum fraction is the honest fleet signal.
        writer.scalar('actors_healthy', fleet_stats['healthy'],
                      step_now)
        # Alive-but-silent actors (blocked in env.step / parked on
        # backpressure past the horizon): the fleet-side member of
        # the zero-deadlocked-threads ledger (round 11).
        writer.scalar('actors_wedged', fleet_stats.get('wedged', 0),
                      step_now)
        writer.scalar('fleet_healthy_fraction',
                      fleet_stats['healthy_fraction'], step_now)
        writer.scalar('actor_respawns', fleet_stats['respawns'],
                      step_now)
        # Threads that carry the fleet (PR 26): process-hosted envs
        # are stepped k to a thread, one batcher request per group.
        writer.scalar('actor_threads',
                      fleet_stats.get('actor_threads', 0), step_now)
        writer.scalar('envs_per_thread',
                      fleet_stats.get('envs_per_thread', 0.0), step_now)
        # Learner failure-domain counters (health.py / checkpoint.py).
        life.write_health_scalars(step_now)
        if health is not None:
          # SDC sentinel (round 12): replica fingerprint mismatches,
          # counted separately from non-finite skips — hardware lying
          # vs math diverging are different operator responses.
          writer.scalar('sdc_replica_mismatches',
                        health.stats().get('sdc_mismatches', 0),
                        step_now)
        # Buffer occupancy: ~0 means the learner is starved (env/
        # inference bound); ~capacity means actors are throttled by
        # backpressure (learner bound).
        writer.scalar('buffer_unrolls', len(buffer), step_now)
        # Merge telemetry over THIS summary interval (a cumulative
        # mean would hide regressions late in a long run): ≈1 means
        # the batcher is not merging — the single-machine throughput
        # lever (paper Table 1).
        snap = server.stats()
        d_calls = snap['calls'] - last_inference_snap['calls']
        d_reqs = snap['requests'] - last_inference_snap['requests']
        last_inference_snap = snap
        writer.scalar('inference_mean_batch',
                      (d_reqs / d_calls) if d_calls else 0.0, step_now)
        # Rows per policy() call: 1 for lone actors, k where an actor
        # thread steps k envs. Over the whole run: rows are counted
        # when a merged call is dispatched and calls when they are
        # made, so an interval's ratio wobbles by the calls in flight.
        policy_calls = snap.get('batcher_requests', 0)
        writer.scalar('inference_rows_per_request',
                      (snap['requests'] / policy_calls) if policy_calls
                      else 0.0, step_now)
        # Staleness: how many snapshots actors have been served (the
        # reference's "actions within one unroll may span weight
        # versions" caveat, made observable).
        writer.scalar('params_version', snap['params_version'],
                      step_now)
        # Actor-plane service time (round 7): per-merged-call latency
        # percentiles over the recent window — the inference-plane
        # bench's unit, exported live so a production regression shows
        # in the same numbers the bench rows use. publishes_skipped
        # counts version-gated no-op publishes (copy avoided).
        writer.scalar('inference_latency_p50_ms',
                      snap['latency_p50_ms'], step_now)
        writer.scalar('inference_latency_p99_ms',
                      snap['latency_p99_ms'], step_now)
        writer.scalar('inference_publishes_skipped',
                      snap['publishes_skipped'], step_now)
        # Admission/overload counters (round 9): sheds are the serving
        # plane's load-shedding response; admission_waits says how
        # often acquires parked; quarantined slots are respawn's
        # give-up tally. All bounded-degradation signals — alert on
        # slope, not presence.
        writer.scalar('inference_sheds', snap.get('sheds', 0),
                      step_now)
        writer.scalar('inference_admission_waits',
                      snap.get('admission_waits', 0), step_now)
        writer.scalar('inference_arena_grows',
                      snap.get('arena_grows', 0), step_now)
        # Multi-tenant serving plane (round 21): how many policy
        # versions are resident, and — when shadow traffic is on —
        # the EWMA action-disagreement between live and shadow (0.0
        # means the candidate acts identically on real traffic).
        writer.scalar('inference_resident_versions',
                      snap.get('resident_versions', 1), step_now)
        writer.scalar('inference_shadow_divergence',
                      snap.get('shadow_divergence', 0.0), step_now)
        quarantined_slots = fleet_stats.get('slots_quarantined', 0)
        writer.scalar('slots_quarantined', quarantined_slots, step_now)
        if quarantined_slots > last_quarantined_slots:
          incidents.event('actor_slots_quarantined', step=step_now,
                          count=quarantined_slots)
          last_quarantined_slots = quarantined_slots
        # Buffer occupancy guard: high_water at capacity + put_waits
        # growing = producers throttled by backpressure (the bound
        # holding), not a failure.
        buf_stats = buffer.stats()
        writer.scalar('buffer_high_water', buf_stats['high_water'],
                      step_now)
        writer.scalar('buffer_put_waits', buf_stats['put_waits'],
                      step_now)
        # --- Sample-reuse + plane-split telemetry (round 10): the
        # measurement that motivates replay and later judges it. ---
        pf = prefetcher.stats()
        d_steps = steps_done - last_reuse_snap['steps']
        # Fresh counted at SERVE time (fresh_slots_served — credited
        # at each batch's first serve), matching bench_replay's
        # composition attribution: dequeue-time fresh_unrolls runs
        # ahead by the prefetch lookahead, reading the headline low.
        d_fresh = (pf['fresh_slots_served'] -
                   last_reuse_snap['fresh_unrolls'])
        d_fresh_frames = d_fresh * frames_per_unroll
        # Learner updates per FRESH env frame over this interval: the
        # IMPACT headline. 1/frames_per_step at replay off; scales
        # with replay_k and 1/(1-replay_ratio).
        writer.scalar('learner_updates_per_env_frame',
                      (d_steps / d_fresh_frames) if d_fresh_frames
                      else 0.0, step_now)
        interval = now - last_reuse_snap['time']
        writer.scalar('env_frames_fresh_per_sec',
                      d_fresh_frames / interval if interval > 0
                      else 0.0, step_now)
        # Utilization split: how much of the interval each plane was
        # actually working. Learner-plane = wall fraction NOT blocked
        # on the feed (prefetcher wait); env-plane = fraction its
        # producer threads were NOT parked on buffer backpressure
        # (put_wait_secs is summed across producers, hence the
        # fleet-size normalization). Learner low + env high = env
        # bound (the regime replay attacks); the reverse = learner
        # bound.
        d_feed_wait = pf['wait_secs'] - last_reuse_snap.get(
            'feed_wait_secs', 0.0)
        learner_util = (min(max(1.0 - d_feed_wait / interval, 0.0),
                            1.0) if interval > 0 else 0.0)
        writer.scalar('learner_plane_utilization', learner_util,
                      step_now)
        d_put_wait = (buf_stats['put_wait_secs'] -
                      last_reuse_snap['put_wait_secs'])
        # Producer-thread count for the normalization: local actors
        # PLUS live ingest connections — the remote topology runs
        # num_actors=0 with N connection threads summing their waits,
        # which would otherwise clamp the metric to 0.
        producers = config.num_actors
        if ingest is not None:
          producers += ingest.stats()['live']
        producers = max(producers, 1)
        env_util = (min(max(1.0 - d_put_wait / (interval * producers),
                            0.0), 1.0) if interval > 0 else 0.0)
        writer.scalar('env_plane_utilization', env_util, step_now)
        # Registry mirror of the plane split + fleet quorum (round
        # 14): the numbers the SLO engine's env_plane_utilization /
        # fleet_healthy_fraction objectives judge.
        _set_plane_gauge('env', env_util)
        _set_plane_gauge('learner', learner_util)
        _set_plane_gauge('fleet', fleet_stats['healthy_fraction'])
        # Fresh vs reused frame counters (cumulative): reused = tier
        # replays (re-staged) + whole-batch re-serves (zero-H2D).
        frames_fresh = pf['fresh_slots_served'] * frames_per_unroll
        frames_reused = (
            buf_stats.get('replay_reused_unrolls', 0) +
            pf.get('batch_reserves', 0) * local_batch_size
        ) * frames_per_unroll
        writer.scalar('frames_fresh', frames_fresh, step_now)
        writer.scalar('frames_reused', frames_reused, step_now)
        if replay_tier is not None:
          for key in ('replay_occupancy', 'replay_evictions_age',
                      'replay_evictions_version',
                      'replay_reused_unrolls',
                      'replay_mean_staleness'):
            writer.scalar(key, buf_stats[key], step_now)
        last_reuse_snap = {
            'steps': steps_done,
            'fresh_unrolls': pf['fresh_slots_served'],
            'put_wait_secs': buf_stats['put_wait_secs'],
            'feed_wait_secs': pf['wait_secs'],
            'time': now,
        }
        # Per-interval action distribution (cumulative would hide a
        # late policy collapse).
        writer.histogram('actions', action_counts_acc, step_now)
        action_counts_acc = np.zeros_like(action_counts_acc)
        # Staging overlap (round 6): fraction of steps that did NOT
        # block on the prefetcher — the H2D-hidden-behind-compute
        # gate (read with buffer_unrolls: ≈0 there means the wait is
        # starvation upstream of staging, not transfer).
        pf = prefetcher.stats()
        writer.scalar('h2d_overlap_fraction',
                      pf['h2d_overlap_fraction'], step_now)
        writer.scalar('staged_batches', pf['staged_batches'], step_now)
        # EXPOSED staging wait over this interval (round 8): ms/step
        # the learner actually blocked on the feed — the part of
        # H2D+stacking NOT hidden behind compute. The overlap fraction
        # says how often a step waited; this says how much (the
        # benchmark's `staging.exposed_ms_per_step`).
        d_gets = pf['gets'] - last_pf_snap['gets']
        d_wait = pf['wait_secs'] - last_pf_snap['wait_secs']
        writer.scalar('staging_exposed_ms_per_step',
                      (d_wait / d_gets * 1e3) if d_gets else 0.0,
                      step_now)
        last_pf_snap = pf
        # The mode ACTUALLY running (config may have asked for unroll
        # and been topology-fallback'd to batch — a bench row labeled
        # from config alone would corrupt the head-to-head record).
        writer.scalar('staging_unroll_active',
                      1.0 if pf['mode'] == 'unroll' else 0.0, step_now)
        if pf.get('donation_fallback'):
          writer.scalar('staging_donation_fallback', 1, step_now)
        if ingest is not None:
          ing = ingest.stats()
          writer.scalar('remote_unrolls', ing['unrolls'], step_now)
          writer.scalar('remote_connections', ing['connections'],
                        step_now)
          # Rejected unrolls keep their connection alive (the actor
          # decides severity), so without this counter a host whose
          # every unroll is being refused is invisible here.
          writer.scalar('remote_rejected', ing['rejected'], step_now)
          # Staleness-window refusals (round 9): benign per unroll
          # (the client refetches), but a steadily climbing count
          # means some host can't keep its params fresh.
          writer.scalar('remote_stale_rejected',
                        ing.get('stale_rejected', 0), step_now)
          # Connections dropped for unparseable/garbage frames — the
          # wire-level quarantine (a corrupting peer must not be able
          # to take the learner down, only itself).
          writer.scalar('quarantined', ing['quarantined'], step_now)
          # v7 payload integrity (round 12): unrolls refused before
          # the put for a mismatched CRC trailer; param publishes the
          # fleet refused to install (digest mismatch, reported back
          # on the retry fetch); bytes/frames the discard paths threw
          # away. Expected flat at zero — any slope is an incident.
          writer.scalar('wire_crc_rejected',
                        ing.get('wire_crc_rejected', 0), step_now)
          writer.scalar('publish_digest_rejected',
                        ing.get('publish_digest_rejected', 0),
                        step_now)
          writer.scalar('ingest_discarded_frames',
                        ing.get('discarded_frames', 0), step_now)
          writer.scalar('ingest_discarded_bytes',
                        ing.get('discarded_bytes', 0), step_now)
          if (ing.get('wire_crc_rejected', 0) >
              last_ingest_snap.get('wire_crc_rejected', 0)):
            incidents.event(
                'wire_crc_rejected', step=step_now,
                total=ing['wire_crc_rejected'],
                delta=(ing['wire_crc_rejected'] -
                       last_ingest_snap.get('wire_crc_rejected', 0)))
          if (ing.get('publish_digest_rejected', 0) >
              last_ingest_snap.get('publish_digest_rejected', 0)):
            incidents.event(
                'publish_digest_rejected', step=step_now,
                total=ing['publish_digest_rejected'])
            if health is not None:
              health.note_external('publish_digest_rejected')
          # Per-lane transport counters (round 6). Ack latency is the
          # end-to-end backpressure signal remote pumps feel; the
          # per-connection rate spread separates one starved host
          # from a uniformly slow fleet.
          writer.scalar('remote_ack_p50_ms', ing['ack_p50_ms'],
                        step_now)
          writer.scalar('remote_ack_p99_ms', ing['ack_p99_ms'],
                        step_now)
          writer.scalar('remote_param_blobs', ing['param_blobs'],
                        step_now)
          # Transport-liveness counters (round 11): reaped idle/
          # half-open connections and dropped param subscribers are
          # the fan-out shrinkage signals; heartbeat misses lead the
          # reaps; reattach count/latency is the restarted learner's
          # fleet-recovery ledger; wedged threads should be ZERO —
          # any nonzero is an incident, not a trend.
          writer.scalar('remote_conns_reaped',
                        ing.get('conns_reaped', 0), step_now)
          writer.scalar('remote_heartbeat_misses',
                        ing.get('heartbeat_misses', 0), step_now)
          writer.scalar('param_subs_dropped',
                        ing.get('param_subs_dropped', 0), step_now)
          writer.scalar('remote_stale_epoch_rejected',
                        ing.get('stale_epoch_rejected', 0), step_now)
          writer.scalar('remote_reattached',
                        ing.get('reattached', 0), step_now)
          writer.scalar('remote_reattach_latency_secs',
                        ing.get('reattach_latency_secs', 0.0),
                        step_now)
          wedged_now = ing.get('ingest_threads_wedged', 0)
          writer.scalar('ingest_threads_wedged', wedged_now, step_now)
          if (ing.get('conns_reaped', 0) >
              last_ingest_snap.get('conns_reaped', 0)):
            incidents.event(
                'remote_conn_reaped', step=step_now,
                total=ing['conns_reaped'],
                delta=(ing['conns_reaped'] -
                       last_ingest_snap.get('conns_reaped', 0)))
          if wedged_now > last_ingest_snap.get(
              'ingest_threads_wedged', 0):
            names = ing.get('wedged_thread_names', [])
            incidents.event('ingest_threads_wedged', step=step_now,
                            count=wedged_now, names=names)
            if health is not None:
              health.note_external('ingest_threads_wedged')
            log.error('ingest watchdog: %d wedged thread(s): %s',
                      wedged_now, ', '.join(names))
          # Elastic membership (round 20): the v9 host ledger. The
          # gauge is the pod-size ground truth the SLO engine and the
          # pod_size actuator read; join/leave events drain into
          # DURABLE incidents (the 'host_' marker) so survivors'
          # incident streams narrate every topology change — the
          # departure itself is benign (training continues at reduced
          # topology), which is exactly why it must be on the record.
          live_hosts = ing.get('live_hosts', 0)
          writer.scalar('remote_live_hosts', live_hosts, step_now)
          _set_plane_gauge('hosts', live_hosts)
          for member_ev in ingest.drain_membership_events():
            if member_ev.get('kind') == 'host_left':
              incidents.event('host_left', step=step_now,
                              host=member_ev.get('host'),
                              reason=member_ev.get('reason'))
              log.warning(
                  'pod membership: host %s left (%s); %d host(s) '
                  'remain — continuing at reduced topology',
                  member_ev.get('host'), member_ev.get('reason'),
                  live_hosts)
            else:
              incidents.event('host_joined', step=step_now,
                              host=member_ev.get('host'),
                              reattach=member_ev.get('reattach',
                                                     False))
              log.info('pod membership: host %s joined (%d live)',
                       member_ev.get('host'), live_hosts)
          dt_summary = now - last_ingest_time
          d_unrolls = ing['unrolls'] - last_ingest_snap['unrolls']
          writer.scalar('remote_unrolls_per_sec',
                        d_unrolls / dt_summary if dt_summary else 0.0,
                        step_now)
          per_conn = ing['per_conn_unrolls']
          prev_conn = last_ingest_snap['per_conn_unrolls']
          rates = [(per_conn[k] - prev_conn.get(k, 0)) / dt_summary
                   for k in per_conn] if dt_summary else []
          if rates:
            writer.scalar('remote_conn_unrolls_per_sec_min',
                          min(rates), step_now)
            writer.scalar('remote_conn_unrolls_per_sec_max',
                          max(rates), step_now)
          last_ingest_snap = ing
          last_ingest_time = now
        # Telemetry self-health (round 14 satellites): silently
        # dropped JSONL writes (any stream, process-wide) and the
        # flight recorder's occupancy — asserted to reach
        # summaries.jsonl by the e2e remote test alongside the trace
        # scalars.
        writer.scalar('dropped_writes',
                      telemetry.dropped_writes_total(), step_now)
        if tracer is not None:
          writer.scalar('trace_flight_records', len(tracer.flight),
                        step_now)
        # Hybrid-filler surface (round 16): filler work is a SEPARATE
        # ledger from the fresh-frame clock — updates/frames say how
        # much idle learner capacity the filler reclaimed (the
        # learner_plane_utilization lift is the headline), skipped
        # counts non-finite filler updates the in-graph guard
        # withheld (an incident on increase: a filler stream must
        # never be able to poison params silently, and a climbing
        # count means the self-play task itself is diverging).
        if filler is not None:
          fstats = filler.stats()
          writer.scalar('filler_updates', fstats['updates'], step_now)
          writer.scalar('filler_frames', fstats['frames'], step_now)
          writer.scalar('filler_skipped_updates', fstats['skipped'],
                        step_now)
          if fstats['skipped'] > last_filler_skipped:
            incidents.event('filler_skipped_updates', step=step_now,
                            total=fstats['skipped'],
                            delta=(fstats['skipped'] -
                                   last_filler_skipped))
            if health is not None:
              health.note_external('filler_skipped_updates')
            last_filler_skipped = fstats['skipped']
        # Controller surface (round 15): the action/revert counts and
        # the live actuator state, so a knob the controller moved is
        # visible in the same stream the objectives are judged from.
        if ctrl is not None:
          ctrl_counts = ctrl.counts()
          writer.scalar('controller_actions', ctrl_counts['actions'],
                        step_now)
          writer.scalar('controller_reverts', ctrl_counts['reverts'],
                        step_now)
          writer.scalar('controller_engaged', ctrl.engaged_rules(),
                        step_now)
          writer.scalar('controller_replay_k', prefetcher.replay_k,
                        step_now)
          writer.scalar('controller_publish_secs',
                        publish_cadence['secs'], step_now)
        life.observe_slo()
        summaries.end()
      # Checkpoint cadence: Orbax saves are collective across hosts;
      # clocks differ, so all hosts act on PROCESS 0's decision (a
      # host-local clock here would desync the barrier and deadlock).
      # The broadcast is a cross-host sync, so it runs only every
      # checkpoint_check_every_steps — the cadence check itself must
      # not tax the hot loop (at worst the save lands that many steps
      # late, noise against checkpoint_secs=600).
      # Saves are WITHHELD mid-burst (HealthLadder.healthy_now); the
      # gate is lockstep across hosts (verdicts are a function of the
      # replicated metrics).
      healthy_now = life.ladder.healthy_now
      if num_processes == 1:
        if healthy_now:
          checkpointer.maybe_save(state)
      elif steps_done % config.checkpoint_check_every_steps == 0:
        decision = bool(multihost_utils.broadcast_one_to_all(
            jnp.asarray(checkpointer.should_save()))) and healthy_now
        checkpointer.maybe_save(state, decision=decision)
      fleet.check_health(stall_timeout_secs=stall_timeout_secs)
      iteration.end()
      iteration = None
    if draining:
      # --- Drain finalize: quiesce → flush already happened in the
      # loop; now join the fleet (bounded), close the prefetcher
      # (pushes any partial batch's unrolls back into the buffer),
      # take a VERIFIED checkpoint through the integrity ladder, and
      # write the resume manifest. ---
      remaining = max(1.0, drain_deadline - time.monotonic())
      quiesce_report = (fleet.quiesce(timeout=remaining)
                        if hasattr(fleet, 'quiesce')
                        else {'unjoined_actors': []})
      prefetcher.close()
      step_final = _initial_steps + steps_done
      buf_stats = buffer.stats()
      # Withhold the drain save mid-bad-burst, exactly like the
      # periodic and final saves: checkpointing diverged params would
      # advance LAST_GOOD onto the poison. The manifest then names
      # the retained last-good step as the resume point.
      if life.ladder.healthy_now:
        checkpointer.save(run.state, force=True)
      else:
        log.warning('drain checkpoint withheld: training was '
                    'unhealthy at preemption (the retained last-'
                    'known-good step covers the resume)')
      ckpt_step = checkpointer.last_good_step()
      drain_latency = time.monotonic() - drain_t0
      manifest = {
          'update_steps': step_final,
          'frames': env_frames(),
          'params_version_step': last_publish_step,
          'params_publishes': server.stats()['params_version'],
          'checkpoint_step': ckpt_step,
          'checkpoint_verified': ckpt_step == step_final,
          'buffer': {
              'leftover_unrolls': buf_stats['occupancy'],
              'high_water': buf_stats['high_water'],
              'capacity': buf_stats['capacity'],
          },
          'unjoined_actors': quiesce_report['unjoined_actors'],
          # Health at preemption: consecutive_bad > 0 here explains a
          # withheld (unverified) drain checkpoint to the resume/
          # postmortem without a summaries.jsonl dig.
          'health': (health.drain_report()
                     if health is not None else None),
          # The unified telemetry snapshot (round 13): every
          # registry-backed counter at drain time, from the same
          # source of truth the flight recorder and the remote
          # 'stats' request read — the resume/postmortem gets the
          # full counter surface without a summaries.jsonl dig.
          'metrics': telemetry.registry().snapshot(),
          # SLO state at drain time (round 14): the preempted run's
          # verdict-so-far, so the resume/postmortem sees which
          # objectives were burning when the platform pulled the node.
          'slo': (slo_engine.verdict() if slo_engine is not None
                  else None),
          # Controller state at drain time (round 15): what the run
          # did to itself before the platform pulled the node —
          # alongside the health ledger's controller_<actuator>
          # entries.
          'controller': (dict(ctrl.counts(), mode=ctrl.mode)
                         if ctrl is not None else None),
          # Hybrid-filler ledger (round 16): how much idle learner
          # capacity self-play reclaimed — explicitly OUTSIDE the
          # 'frames' fresh-frame figure above.
          'filler': filler.stats() if filler is not None else None,
          'drain_source': drain_source,
          'drain_latency_secs': round(drain_latency, 3),
          'wall_time': round(time.time(), 3),
      }
      if process_index == 0:
        path = _write_resume_manifest(config.logdir, manifest)
        log.warning(
            'preemption drain complete in %.2fs: checkpoint step %s '
            '(verified=%s), %d unroll(s) left in the buffer, '
            'manifest %s', drain_latency, ckpt_step,
            manifest['checkpoint_verified'],
            buf_stats['occupancy'], path)
      incidents.event('preempt_drain_complete', step=step_final,
                      drain_latency_secs=round(drain_latency, 3),
                      checkpoint_step=ckpt_step,
                      leftover_unrolls=buf_stats['occupancy'],
                      unjoined_actors=quiesce_report['unjoined_actors'])
      writer.scalar('drain_latency_secs', round(drain_latency, 3),
                    step_final)
  finally:
    if iteration is not None:
      iteration.end()  # the loop was left mid-pass
    publish.end()
    summaries.end()
    # One robustness roll-up while the fleet still runs (stats after
    # stop() would read an all-dead fleet): what the run's failure
    # domain absorbed, in the same counters the summaries carry.
    try:
      fleet_stats = fleet.stats(
          healthy_horizon_secs=(stall_timeout_secs
                                if stall_timeout_secs else 60.0))
      hs = health.stats() if health is not None else {}
      ing_q = ingest.stats()['quarantined'] if ingest is not None else 0
      log.info(
          'robustness summary: skipped_steps=%d rollbacks=%d '
          'quarantined=%d respawns=%d fleet_healthy_fraction=%.2f '
          'checkpoint_save_errors=%d restore_fallbacks=%d',
          hs.get('skipped_steps', 0), hs.get('rollbacks', 0), ing_q,
          fleet_stats['respawns'], fleet_stats['healthy_fraction'],
          checkpointer.save_errors, checkpointer.restore_fallbacks)
      _log_env_transport(fleet_stats)
    except Exception:
      log.exception('robustness summary failed')
    # Controller (round 15): stop the actuation thread FIRST (it
    # reads the engine and moves component knobs — both about to be
    # torn down) and write CONTROLLER_LOG.json on every exit path;
    # the action log is the operator's record of what the run did to
    # itself.
    if ctrl is not None:
      try:
        ctrl.stop()
        ctrl_counts = ctrl.finalize()
        log.info('controller [%s]: %d action(s) (%d escalation(s), '
                 '%d revert(s), %d applied) -> CONTROLLER_LOG.json',
                 ctrl.mode, ctrl_counts['actions'],
                 ctrl_counts['escalations'], ctrl_counts['reverts'],
                 ctrl_counts['applied'])
      except Exception:
        log.exception('controller finalize failed')
    def _stop_planes():
      if ingest is not None:
        # v10 routed serving: flip the draining notice FIRST — every
        # infer reply from here on tells routers to shift traffic away
        # while the rest of the teardown runs.
        try:
          ingest.set_draining()
        except Exception:
          log.exception('set_draining failed')
      fleet.stop()
      prefetcher.close()
      server.close()
      if filler is not None:
        # Unregister the filler's per-run counter (identity-checked,
        # so this can never evict a newer run's registration).
        try:
          filler.close()
        except Exception:
          log.exception('filler close failed')
      if ingest is not None:
        # Clean end → 'bye' frame (remote actors exit immediately);
        # exception unwind → crash semantics (actors keep their
        # reconnect window for the supervisor's restart).
        ingest.close(graceful=life.clean_exit)

    # The verdict is written on every exit path (a crashed run's is
    # exactly what the postmortem wants; chaos/soak/slo_report read
    # the file), then the planes above stop, then the tail checkpoint.
    try:
      life.close(run.state, _initial_steps + steps_done,
                 teardown=_stop_planes)
    finally:
      if tracer is not None:
        telemetry.set_tracer(None)
        tracer.close()
  return run


def train_anakin(config: Config, max_steps: Optional[int] = None,
                 max_seconds: Optional[float] = None,
                 drain_event: Optional[threading.Event] = None,
                 initial_state=None) -> TrainRun:
  """The Anakin runtime (round 16, ROADMAP item 3): act+learn fused
  into one jitted device step (parallel/anakin.py, Podracer
  arXiv:2104.06272), run as a PRODUCTION run — the full lifecycle the
  fleet runtime gets, not the bench curiosity the r4 artifact
  measured at 1,250,181 fps:

  - checkpoint ladder (PR 2/9): verified saves with content digests,
    restore_latest at spin-up, LAST_GOOD rollback on health
    escalation, structure-mismatch refusal without overwriting;
  - health watchdog (PR 2): the in-graph non-finite guard is already
    inside the fused step (learner.make_train_step_fn); here the host
    monitor reads the one-step-delayed sentinels and escalates
    skip → rollback → halt-with-bundle exactly like the fleet loop;
  - metrics registry + SLO engine + verdict (PRs 10–11): the same
    literal gauge names, the same default objective set, the same
    SLO_VERDICT.json on every exit path, slo_violation incidents, and
    the triggered jax.profiler capture served by this loop;
  - summaries/incidents JSONL, config.json, FpsMeter — the artifact
    contract every script (chaos/soak/slo_report) already reads.

  Sharding: the mesh path shards the env batch over the data axis per
  the `test_anakin_shards_over_the_mesh` discipline (params
  replicate; jit inserts the gradient psum). Data-parallel and
  single-host only — the fused loop has no cross-host batch
  transport.

  Pipeline-plane machinery (fleet, inference server, prefetcher,
  ingest, tracer, controller) intentionally absent: there are no hops
  to trace and no actuators to drive; the SLO objectives over those
  planes evaluate no_data, which never violates. `drain_event`
  (SIGTERM via experiment.py) stops the loop at the next fused-step
  boundary — the finally's tail checkpoint + verdict are the drain.

  `initial_state` (round 23): a TrainState to start from INSTEAD of
  restore_latest — the population loop's on-device exploit seam. An
  in-process PBT loser inherits the donor's weights as a device
  pytree; round-tripping that copy through the filesystem (the old
  rmtree+copytree) cost a serialize/deserialize per exploit and a
  window where the loser's checkpoint ladder didn't exist at all.
  The ladder still records the decision durably: the loop's next
  periodic save lands the inherited state in the loser's own dir.

  Returns a TrainRun whose fleet/prefetcher/server/stats are None.
  """
  from scalable_agent_tpu.parallel import anakin as anakin_lib
  if jax.process_count() > 1:
    raise ValueError('runtime=anakin is single-host: the fused loop '
                     'has no cross-host batch transport — each '
                     'process would train an unsynchronized replica')
  if config.model_parallelism > 1:
    raise ValueError('runtime=anakin is data-parallel only; drop '
                     '--model_parallelism')
  # Knob-group validation, same contract as train(): hard errors
  # raise before any spin-up cost; cross-links log.
  for validate in (validate_runtime, validate_slo,
                   validate_population):
    for warning in validate(config):
      log.warning('%s', warning)
  if config.controller != 'off':
    log.info('controller=%s is a fleet-runtime feature: the anakin '
             'runtime has no actuators (no prefetcher/admission/'
             'publish/fleet knobs) — running without it',
             config.controller)

  mesh = choose_mesh(config)
  env_core, agent, step, carry = anakin_lib.build_run(config,
                                                      mesh=mesh)
  del env_core
  os.makedirs(config.logdir, exist_ok=True)

  checkpointer, train_state = lifecycle.restore_at_start(
      config, carry.train_state, mesh=mesh, initial_state=initial_state)
  carry = carry._replace(train_state=train_state)
  _initial_steps = int(jax.device_get(train_state.update_steps))
  try:
    life = lifecycle.open_run(config, checkpointer)
  except BaseException:
    checkpointer.close()
    raise
  writer, fps_meter = life.writer, life.fps_meter

  run = TrainRun(config, agent, carry.train_state, None, None, None,
                 checkpointer, writer, None, fps_meter,
                 health=life.health)
  run.mesh = mesh
  steps_done = 0
  # The plane split is a fleet concept; in the fused runtime env and
  # learner are the same XLA program, busy whenever the loop is, so
  # both utilization gauges pin 1.0: fps_floor is the objective that
  # catches a wedged loop. fleet_healthy_fraction stays unregistered
  # (no fleet: no_data, never a violation).
  life.loop_gauges(
      update_steps=lambda: steps_done + _initial_steps,
      env_frames=lambda: ((steps_done + _initial_steps) *
                          config.frames_per_step),
      utilization=lambda: 1.0)
  # Curriculum telemetry (round 22): the fused step already folds the
  # per-level score/visit tables and their scalar digests into the
  # stacked metrics; these registry gauges re-export the latest
  # summary-read values so the SLO engine and scripts see them under
  # registry names without an extra device sync (the dict updates at
  # the summary cadence from the one-step-delayed read the loop does
  # anyway).
  curriculum_latest: Dict[str, float] = {}
  if config.curriculum != 'uniform':
    life.track(telemetry.gauge(
        'curriculum/entropy',
        fn=lambda: curriculum_latest.get('curriculum_entropy', 0.0)))
    life.track(telemetry.gauge(
        'curriculum/levels_visited',
        fn=lambda: curriculum_latest.get('curriculum_levels_visited',
                                         0.0)))
    life.track(telemetry.gauge(
        'curriculum/score_max',
        fn=lambda: curriculum_latest.get('curriculum_score_max', 0.0)))
  sync_every = anakin_lib._cpu_mesh_sync_every(mesh)
  loop_start = time.monotonic()
  last_summary = loop_start

  def _final_artifacts():
    # Final summary flush: short runs end inside one window and would
    # otherwise ship empty curves.
    if steps_done and life.metrics.pending is not None:
      step_final, handle = life.metrics.pending
      try:
        writer.scalars(observability.read_stacked_metrics(handle),
                       step_final)
        writer.scalar('env_frames_per_sec', fps_meter.fps(),
                      step_final)
      except Exception:
        log.exception('final summary flush failed')
    # Per-level curriculum artifact (round 22): the final score /
    # visit tables plus the live sampling distribution — the
    # machine-readable answer to "which levels got the frames"
    # (scripts and the CI population lane read this, not summaries).
    if (config.curriculum != 'uniform' and
        hasattr(carry.env_state, 'level_scores')):
      try:
        scores = np.asarray(
            jax.device_get(carry.env_state.level_scores))
        visits = np.asarray(
            jax.device_get(carry.env_state.level_visits))
        probs = np.asarray(population_lib.level_probs(
            scores, config.curriculum_temperature,
            config.curriculum_eps))
        curriculum_path = os.path.join(config.logdir,
                                       'CURRICULUM_LEVELS.json')
        with open(curriculum_path, 'w') as f:
          json.dump({'curriculum': config.curriculum,
                     'temperature': config.curriculum_temperature,
                     'eps': config.curriculum_eps,
                     'scores': [float(s) for s in scores],
                     'visits': [float(v) for v in visits],
                     'probs': [float(p) for p in probs]},
                    f, indent=2)
      except Exception:
        log.exception('curriculum artifact write failed')

  try:
    while True:
      if drain_event is not None and drain_event.is_set():
        # SIGTERM: the fused loop quiesces at a step boundary — the
        # close's tail checkpoint + SLO verdict ARE the drain (no
        # buffers to flush, no fleet to join).
        life.incidents.event('anakin_stop_requested',
                             step=_initial_steps + steps_done)
        log.warning('stop requested (SIGTERM): finalizing at step %d',
                    _initial_steps + steps_done)
        break
      frames = (_initial_steps + steps_done) * config.frames_per_step
      if frames >= config.total_environment_frames:
        break
      if max_steps is not None and steps_done >= max_steps:
        break
      if (max_seconds is not None and
          time.monotonic() - loop_start > max_seconds):
        break
      carry, metrics = step(carry)
      run.state = carry.train_state
      steps_done += 1
      step_now = _initial_steps + steps_done
      fps_meter.update(config.frames_per_step)
      if sync_every is not None and steps_done % sync_every == 0:
        jax.block_until_ready(metrics['total_loss'])
      life.metrics.push(step_now, metrics)
      life.profiler.tick(steps_done)
      # The fused step's in-graph guard already withheld any
      # non-finite update on device; the ladder escalates.
      carry = carry._replace(train_state=life.ladder.step(
          step_now, metrics, carry.train_state))
      run.state = carry.train_state

      now = time.monotonic()
      if now - last_summary >= config.summary_secs:
        last_summary = now
        vals = observability.read_stacked_metrics(
            life.metrics.older()[1])
        writer.scalars(vals, step_now)
        if config.curriculum != 'uniform':
          curriculum_latest.update(
              {k: v for k, v in vals.items()
               if k.startswith('curriculum_')})
        writer.scalar('env_frames_per_sec', fps_meter.fps(), step_now)
        life.write_health_scalars(step_now)
        life.observe_slo()
      if life.ladder.healthy_now:
        checkpointer.maybe_save(carry.train_state)
  finally:
    life.close(run.state, _initial_steps + steps_done,
               extra={'runtime': 'anakin'}, teardown=_final_artifacts)
  return run


def _member_return(member_dir: str, tag: str = 'mean_reward',
                   tail: int = 5) -> float:
  """A member's fitness: the mean of its last `tail` summary values
  for `tag` (step-ordered). Summaries append across rounds, so the
  tail reflects the round just finished. Missing/empty summaries
  score 0.0 — a member that produced nothing never wins a round."""
  vals = []
  try:
    with open(os.path.join(member_dir, 'summaries.jsonl')) as f:
      for line in f:
        try:
          rec = json.loads(line)
        except ValueError:
          continue
        if rec.get('tag') == tag and 'value' in rec:
          vals.append((int(rec.get('step', 0)), float(rec['value'])))
  except OSError:
    return 0.0
  if not vals:
    return 0.0
  vals.sort(key=lambda sv: sv[0])
  return float(np.mean([v for _, v in vals[-tail:]]))


def _inherit_member_dir(donor_dir: str, loser_dir: str) -> None:
  """Cross-process PBT weight inheritance: the loser's checkpoint
  ladder becomes a copy of the donor's — via copy-then-swap, so a
  failed copy NEVER deletes the loser's own ladder (the r22 code did
  rmtree-then-copytree, which left the loser with no restorable
  checkpoint at all if the copy died mid-way). The loser's next
  restore re-verifies the donor's content digests through the PR 2
  ladder — a torn copy is refused, not trained on.

  This is the cross-process fallback only: in-process exploits hand
  the donor's state over as a device pytree (train_anakin's
  initial_state seam) and never touch the filesystem."""
  tmp = loser_dir + '.inherit_tmp'
  old = loser_dir + '.inherit_old'
  for leftover in (tmp, old):
    if os.path.isdir(leftover):
      shutil.rmtree(leftover)
  try:
    shutil.copytree(donor_dir, tmp)
  except BaseException:
    # The loser's ladder was never touched; only the partial copy
    # goes.
    shutil.rmtree(tmp, ignore_errors=True)
    raise
  if os.path.isdir(loser_dir):
    os.rename(loser_dir, old)
  os.rename(tmp, loser_dir)
  shutil.rmtree(old, ignore_errors=True)


def _population_gauges(pop_stats: Dict[str, float]) -> List:
  """The population's registry gauges over the loop's `pop_stats`.
  Registered lazily AFTER the first scoring pass: an objective over an
  absent gauge evaluates no_data (never violates), while a gauge
  registered before any member has a return would judge a
  placeholder. Member SLO engines from round 1 on DO see these (same
  process, same registry): the per-task floor is judged while the
  population still trains. The caller unregisters them."""
  return [
      telemetry.gauge(
          'population/task_return_min',
          fn=lambda: pop_stats.get('task_return_min', 0.0)),
      telemetry.gauge(
          'population/best_return',
          fn=lambda: pop_stats.get('best_return', 0.0)),
      telemetry.gauge(
          'population/exploits_total',
          fn=lambda: pop_stats.get('exploits', 0.0)),
  ]


def _train_population_fused(config: Config,
                            max_steps: Optional[int] = None,
                            max_seconds: Optional[float] = None,
                            drain_event: Optional[threading.Event] = None
                            ) -> TrainRun:
  """The vectorized population (round 23, --pbt_vectorized): all N
  members advance in ONE compiled program per round.

  The serial loop (train_population below) spins train_anakin up N
  times per round — N jit traces the first round, N spin-up/teardown
  walls every round, and a device left idle while the host replays
  lifecycle code between members. Here the member axis is a vmap
  axis instead: one stacked carry, one fused act+learn step vmapped
  over members, one dispatch per lockstep step. The PBT hypers
  (learning_rate, entropy_cost) enter the program as TRACED
  per-member scalars, so explore perturbations between rounds NEVER
  retrigger compilation — round 2 reuses round 1's executable.

  What stays host-side, by design: the decide/explore logic runs
  BETWEEN rounds (pbt_decide on the members' summary returns), and
  weight inheritance is a device-to-device stacked-index copy
  (`train_state.at[loser].set(train_state[donor])`) — no rmtree, no
  copytree, no serialize round trip. Each member still owns a real
  checkpoint ladder: its slice is force-saved at every round
  boundary AFTER exploits land, so the decision history is durable
  and any member dir resumes (fused or serial) across processes.

  Single-suite, single-device members: one vmapped program can only
  train structurally identical members (validate_population rejects
  multi-suite vectorized populations and degrades model-axis meshes
  to the serial loop). Artifacts match the serial path:
  population_summaries.jsonl, PBT_LOG.json (vectorized=true),
  pbt_exploit/pbt_winner incidents, per-member summaries.jsonl and
  checkpoints/, and the parent-logdir SLO verdict."""
  from scalable_agent_tpu.parallel import anakin as anakin_lib
  suite_list = list(config.resolved_pbt_suites)
  suite = suite_list[0]
  n = config.pbt_population
  round_frames = config.resolved_pbt_round_frames
  num_rounds = max(
      1, -(-config.total_environment_frames // round_frames))
  os.makedirs(config.logdir, exist_ok=True)
  rng = np.random.default_rng(config.seed)

  # Same hyper-init recipe as the serial loop (member 0 is the
  # unperturbed control arm) — the two paths must be comparable.
  members = []
  for k in range(n):
    hypers = {'learning_rate': config.learning_rate,
              'entropy_cost': config.entropy_cost}
    if k:
      hypers = population_lib.pbt_explore(hypers, rng,
                                          config.pbt_perturb)
    members.append({'member': k, 'suite': suite, 'hypers': hypers})

  base_config = dataclasses.replace(
      config, env_backend=suite, pbt_population=0, fleet_tasks='',
      pbt_vectorized=False)
  env_core = anakin_lib.make_env_core(base_config)
  agent = build_agent(base_config, env_core.num_actions)
  vstep = anakin_lib.make_vectorized_anakin_step(agent, env_core,
                                                 base_config)

  member_dirs = []
  member_configs = []
  checkpointers = []
  member_writers = []
  try:
    # Per-member init (each member's own PRNG stream — same seed
    # recipe as the serial member spin-up), per-member restore
    # through its own ladder, then ONE stacked carry.
    carries = []
    for k in range(n):
      member_dir = os.path.join(config.logdir, f'member_{k:02d}')
      os.makedirs(member_dir, exist_ok=True)
      member_config = dataclasses.replace(
          base_config, logdir=member_dir,
          seed=config.seed + 101 * k + 1,
          learning_rate=members[k]['hypers']['learning_rate'],
          entropy_cost=members[k]['hypers']['entropy_cost'])
      with open(os.path.join(member_dir, 'config.json'), 'w') as f:
        json.dump(dataclasses.asdict(member_config), f, indent=2,
                  sort_keys=True)
      member_dirs.append(member_dir)
      member_configs.append(member_config)
      carry_k = anakin_lib.init_carry(
          agent, env_core, base_config,
          jax.random.PRNGKey(member_config.seed))
      checkpointer, train_state = lifecycle.restore_at_start(
          member_config, carry_k.train_state)
      checkpointers.append(checkpointer)
      member_writers.append(observability.SummaryWriter(member_dir))
      carries.append(carry_k._replace(train_state=train_state))
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *carries)
    del carries
    life = lifecycle.open_run(config)
  except BaseException:
    for w in member_writers:
      w.close()
    for c in checkpointers:
      c.close()
    raise
  writer, incidents, fps_meter = (life.writer, life.incidents,
                                  life.fps_meter)

  pop_path = os.path.join(config.logdir, 'population_summaries.jsonl')
  pop_stats: Dict[str, float] = {'exploits': 0.0}
  pop_gauges: List = []

  pbt_log = {'population': n, 'suites': suite_list,
             'round_frames': round_frames, 'num_rounds': num_rounds,
             'quantile': config.pbt_quantile,
             'perturb': config.pbt_perturb, 'vectorized': True,
             'rounds': [], 'winner': None}

  def _write_pbt_log():
    path = os.path.join(config.logdir, 'PBT_LOG.json')
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump(pbt_log, f, indent=2, sort_keys=True)
    os.replace(tmp, path)

  _initial_steps = int(np.max(np.asarray(
      jax.device_get(stacked.train_state.update_steps))))
  steps_done = 0
  frames_per_step = config.frames_per_step
  life.loop_gauges(
      update_steps=lambda: steps_done + _initial_steps,
      env_frames=lambda: ((steps_done + _initial_steps) *
                          frames_per_step * n),
      utilization=lambda: 1.0)

  def _hyp_arrays():
    return {
        'learning_rate': jnp.asarray(
            [m['hypers']['learning_rate'] for m in members],
            jnp.float32),
        'entropy_cost': jnp.asarray(
            [m['hypers']['entropy_cost'] for m in members],
            jnp.float32),
    }

  def _flush_members(pending):
    step_f, (keys, stacked_vals) = pending
    vals = np.asarray(jax.device_get(stacked_vals))  # [keys, N]
    for k in range(n):
      member_writers[k].scalars(
          {key: float(vals[i, k]) for i, key in enumerate(keys)},
          step_f)

  returns = [0.0] * n
  scored = False
  loop_start = time.monotonic()
  last_summary = loop_start
  try:
    for r in range(num_rounds):
      if drain_event is not None and drain_event.is_set():
        break
      target = min((r + 1) * round_frames,
                   config.total_environment_frames)
      hyp = _hyp_arrays()
      round_steps = 0
      while True:
        if drain_event is not None and drain_event.is_set():
          incidents.event('anakin_stop_requested',
                          step=_initial_steps + steps_done, round=r)
          break
        if (_initial_steps + steps_done) * frames_per_step >= target:
          break
        if max_steps is not None and round_steps >= max_steps:
          break
        if (max_seconds is not None and
            time.monotonic() - loop_start > max_seconds):
          break
        stacked, metrics = vstep(stacked, hyp)
        steps_done += 1
        round_steps += 1
        step_now = _initial_steps + steps_done
        fps_meter.update(frames_per_step * n)
        life.metrics.push(step_now, metrics)
        now = time.monotonic()
        if now - last_summary >= config.summary_secs:
          last_summary = now
          _flush_members(life.metrics.older())
          writer.scalar('env_frames_per_sec', fps_meter.fps(),
                        step_now)
          life.observe_slo()
      # Round boundary: flush the freshest metrics so the scoring
      # pass below reads THIS round's tail, then score/decide.
      if life.metrics.pending is not None:
        _flush_members(life.metrics.pending)
      for k in range(n):
        returns[k] = _member_return(member_dirs[k])
        row = {'wall_time': round(time.time(), 3), 'round': r,
               'member': k, 'suite': suite, 'frames': target,
               'mean_return': returns[k]}
        row.update({f'hyper_{h}': float(v)
                    for h, v in sorted(members[k]['hypers'].items())})
        with open(pop_path, 'a') as f:
          f.write(json.dumps(row, sort_keys=True) + '\n')
      scored = True
      pop_stats['task_return_min'] = min(returns)
      pop_stats['best_return'] = max(returns)
      if not pop_gauges:
        pop_gauges.extend(life.track(gauge) for gauge in
                          _population_gauges(pop_stats))
      writer.scalar('population/task_return_min',
                    pop_stats['task_return_min'], target)
      writer.scalar('population/best_return',
                    pop_stats['best_return'], target)

      round_rec = {'round': r, 'target_frames': target,
                   'returns': list(returns),
                   'suites': [suite] * n,
                   'hypers': [dict(m['hypers']) for m in members],
                   'decisions': []}
      final_round = (r == num_rounds - 1 or
                     (drain_event is not None and
                      drain_event.is_set()))
      if not final_round:
        decisions = population_lib.pbt_decide(
            returns, [suite] * n, rng,
            quantile=config.pbt_quantile,
            perturb=config.pbt_perturb,
            hypers=[m['hypers'] for m in members])
        for k, decision in enumerate(decisions):
          if decision is None:
            continue
          donor = decision['donor']
          # On-device weight inheritance: a stacked-index copy of
          # the donor's train-state slice over the loser's — the
          # r22 rmtree+copytree became one device op. (Only the
          # train state transfers; the loser keeps its own env
          # stream, exactly like the serial path, where inheritance
          # never touched env state either.)
          stacked = stacked._replace(
              train_state=jax.tree_util.tree_map(
                  lambda x: x.at[k].set(x[donor]),
                  stacked.train_state))
          members[k]['hypers'] = dict(decision['hypers'])
          pop_stats['exploits'] += 1.0
          incidents.event(
              'pbt_exploit', step=target, round=r, member=k,
              donor=donor, suite=suite,
              member_return=returns[k], donor_return=returns[donor],
              hypers=decision['hypers'])
          log.info('pbt round %d: member %d (return %.3f) exploits '
                   'member %d (return %.3f), new hypers %s '
                   '[on-device]', r, k, returns[k], donor,
                   returns[donor], decision['hypers'])
          round_rec['decisions'].append(dict(decision, member=k))
      writer.scalar('population/exploits_total',
                    pop_stats['exploits'], target)
      # Durable decision record: every member's slice lands in its
      # OWN ladder after exploits, so the round's outcome (inherited
      # weights included) survives this process — any member dir
      # resumes, fused or serial.
      for k in range(n):
        checkpointers[k].save(
            jax.tree_util.tree_map(lambda x: x[k],
                                   stacked.train_state),
            force=True)
      pbt_log['rounds'].append(round_rec)
      _write_pbt_log()

    if scored:
      winner = int(np.argmax(returns))
      pbt_log['winner'] = {
          'member': winner, 'suite': suite,
          'return': returns[winner],
          'hypers': dict(members[winner]['hypers']),
          'logdir': member_dirs[winner]}
      _write_pbt_log()
      incidents.event('pbt_winner', member=winner, suite=suite,
                      final_return=returns[winner],
                      hypers=members[winner]['hypers'])
      log.info('pbt winner: member %d (%s) return %.3f hypers %s '
               '[vectorized]', winner, suite, returns[winner],
               members[winner]['hypers'])
      return TrainRun(
          member_configs[winner], agent,
          jax.tree_util.tree_map(lambda x: x[winner],
                                 stacked.train_state),
          None, None, None, checkpointers[winner],
          member_writers[winner], None, fps_meter)
    raise RuntimeError('population run trained no member (drained '
                       'before the first round scored?)')
  finally:
    def _close_members():
      for c in checkpointers:
        c.close()
      for w in member_writers:
        w.close()

    # No tail save here: every member's slice was force-saved at its
    # round boundary, after the exploits landed.
    life.close(None, _initial_steps + steps_done,
               extra={'runtime': 'anakin', 'vectorized': True,
                      'population': n},
               teardown=_close_members)


def train_population(config: Config, max_steps: Optional[int] = None,
                     max_seconds: Optional[float] = None,
                     drain_event: Optional[threading.Event] = None
                     ) -> TrainRun:
  """Population-based training over Anakin learner replicas (round
  22, PBT arXiv 1711.09846): ONE driver invocation trains
  `pbt_population` members — each a full train_anakin run in
  `<logdir>/member_<k>` with its own checkpoint ladder, summaries,
  and SLO verdict — suites assigned round-robin from
  `resolved_pbt_suites`, hypers (learning_rate, entropy_cost)
  exploit/explored between rounds.

  The schedule is round-synchronous and sequential on this host: each
  round extends every member's frame budget by
  `resolved_pbt_round_frames` (members RESUME from their own verified
  checkpoints — the round boundary is just a host-side pause), then
  the process-0-owned decision loop ranks WITHIN each suite
  (cross-suite returns are not commensurable), and bottom-quantile
  members inherit a donor's weights by copying its `checkpoints/`
  directory through the PR 2 ladder — the loser's next restore
  re-verifies the donor's content digests, so a torn copy is refused,
  not trained on. Every exploit lands as a DURABLE `pbt_exploit`
  incident (donor, returns, explored hypers) — the provenance chain
  RUNBOOK.md's "which replica won and why" walks backwards.

  Artifacts in the parent logdir: `population_summaries.jsonl` (one
  row per member per round: suite, frames, mean return, live hypers —
  the per-task return curves), `PBT_LOG.json` (the full decision
  history + final winner), and `summaries.jsonl` population/* scalars
  feeding the `per_task_return_floor` SLO objective via the
  population/* gauges (registered after the first scoring pass; other
  runs see no_data, never a violation).

  `max_steps`/`max_seconds` bound each MEMBER run (the test seam);
  `drain_event` stops cleanly at the next member/round boundary.
  Returns the winning member's TrainRun.
  """
  for warning in validate_population(config):
    log.warning('%s', warning)
  if config.pbt_population < 2:
    raise ValueError(f'train_population needs pbt_population >= 2, '
                     f'got {config.pbt_population}')
  if config.pbt_vectorized:
    # Round 23: the fused path — one vmapped program advances every
    # member in lockstep. Single-device members only: a model-axis
    # mesh degrades to the serial loop (validate_population already
    # warned).
    if config.model_parallelism <= 1:
      return _train_population_fused(config, max_steps=max_steps,
                                     max_seconds=max_seconds,
                                     drain_event=drain_event)
    log.warning('pbt_vectorized ignored (model_parallelism=%d): '
                'running the serial member loop',
                config.model_parallelism)
  suite_list = list(config.resolved_pbt_suites)
  n = config.pbt_population
  round_frames = config.resolved_pbt_round_frames
  num_rounds = max(
      1, -(-config.total_environment_frames // round_frames))
  os.makedirs(config.logdir, exist_ok=True)
  incidents = observability.EventLog(config.logdir)
  writer = observability.SummaryWriter(config.logdir)
  pop_path = os.path.join(config.logdir, 'population_summaries.jsonl')
  rng = np.random.default_rng(config.seed)

  # Member 0 carries the configured hypers unperturbed (the "control"
  # arm); the rest start from an explored neighborhood so round 0
  # already has diversity to select over.
  members = []
  for k in range(n):
    hypers = {'learning_rate': config.learning_rate,
              'entropy_cost': config.entropy_cost}
    if k:
      hypers = population_lib.pbt_explore(hypers, rng,
                                          config.pbt_perturb)
    members.append({'member': k, 'suite': suite_list[k % len(suite_list)],
                    'hypers': hypers})

  pop_stats: Dict[str, float] = {'exploits': 0.0}
  pop_gauges: List = []

  pbt_log = {'population': n, 'suites': suite_list,
             'round_frames': round_frames, 'num_rounds': num_rounds,
             'quantile': config.pbt_quantile,
             'perturb': config.pbt_perturb, 'vectorized': False,
             'rounds': [], 'winner': None}

  def _write_pbt_log():
    path = os.path.join(config.logdir, 'PBT_LOG.json')
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump(pbt_log, f, indent=2, sort_keys=True)
    os.replace(tmp, path)

  runs: Dict[int, TrainRun] = {}
  # Round 23: in-process weight inheritance. An exploited loser's
  # next spin-up starts from this device pytree instead of its own
  # checkpoint — no filesystem round trip, no window where its
  # ladder is gone.
  inherit: Dict[int, object] = {}
  returns = [0.0] * n
  try:
    for r in range(num_rounds):
      if drain_event is not None and drain_event.is_set():
        break
      target = min((r + 1) * round_frames,
                   config.total_environment_frames)
      for m in members:
        if drain_event is not None and drain_event.is_set():
          break
        k = m['member']
        member_dir = os.path.join(config.logdir, f'member_{k:02d}')
        member_config = dataclasses.replace(
            config,
            logdir=member_dir,
            # Distinct, round-stable env/init seed per member; params
            # beyond round 0 come from the member's own checkpoint.
            seed=config.seed + 101 * k + 1,
            env_backend=m['suite'],
            total_environment_frames=target,
            learning_rate=m['hypers']['learning_rate'],
            entropy_cost=m['hypers']['entropy_cost'],
            # Members are plain anakin runs: no recursive population,
            # no fleet-runtime task mixing.
            pbt_population=0,
            fleet_tasks='')
        runs[k] = train_anakin(member_config, max_steps=max_steps,
                               max_seconds=max_seconds,
                               drain_event=drain_event,
                               initial_state=inherit.pop(k, None))
        returns[k] = _member_return(member_dir)
        row = {'wall_time': round(time.time(), 3), 'round': r,
               'member': k, 'suite': m['suite'], 'frames': target,
               'mean_return': returns[k]}
        row.update({f'hyper_{h}': float(v)
                    for h, v in sorted(m['hypers'].items())})
        with open(pop_path, 'a') as f:
          f.write(json.dumps(row, sort_keys=True) + '\n')

      group_labels = [m['suite'] for m in members]
      per_suite_best = {
          s: max(returns[i] for i in range(n)
                 if group_labels[i] == s)
          for s in suite_list}
      pop_stats['task_return_min'] = min(per_suite_best.values())
      pop_stats['best_return'] = max(returns)
      if not pop_gauges:
        pop_gauges.extend(_population_gauges(pop_stats))
      writer.scalar('population/task_return_min',
                    pop_stats['task_return_min'], target)
      writer.scalar('population/best_return',
                    pop_stats['best_return'], target)

      round_rec = {'round': r, 'target_frames': target,
                   'returns': list(returns),
                   'suites': list(group_labels),
                   'hypers': [dict(m['hypers']) for m in members],
                   'decisions': []}
      final_round = (r == num_rounds - 1 or
                     (drain_event is not None and
                      drain_event.is_set()))
      if not final_round:
        # Exploit/explore only when another round will train on the
        # result — mutating weights after the last round would ship
        # an inherited-but-untrained population.
        decisions = population_lib.pbt_decide(
            returns, group_labels, rng,
            quantile=config.pbt_quantile,
            perturb=config.pbt_perturb,
            hypers=[m['hypers'] for m in members])
        for k, decision in enumerate(decisions):
          if decision is None:
            continue
          donor = decision['donor']
          if donor in runs:
            # On-device inheritance (round 23): the donor trained in
            # THIS process, so its final state is already a device
            # pytree — deep-copy it (the loser's fused step donates
            # its carry; an aliased buffer would invalidate the
            # donor's state and any sibling inheriting it too) and
            # hand it to the loser's next spin-up. The loser's own
            # ladder then records the inherited-and-trained state at
            # the normal save cadence — durable, without a
            # serialize/deserialize round trip per exploit.
            inherit[k] = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), runs[donor].state)
          else:
            # Cross-process fallback: inherit through the checkpoint
            # ladder — the loser's next restore_latest re-verifies
            # the donor's content digests (a torn copy is refused,
            # not loaded), and the copy-then-swap helper never
            # leaves the loser without a ladder.
            src = os.path.join(config.logdir, f'member_{donor:02d}',
                               'checkpoints')
            dst = os.path.join(config.logdir, f'member_{k:02d}',
                               'checkpoints')
            if os.path.isdir(src):
              _inherit_member_dir(src, dst)
          members[k]['hypers'] = dict(decision['hypers'])
          pop_stats['exploits'] += 1.0
          incidents.event(
              'pbt_exploit', step=target, round=r, member=k,
              donor=donor, suite=members[k]['suite'],
              member_return=returns[k], donor_return=returns[donor],
              hypers=decision['hypers'])
          log.info('pbt round %d: member %d (return %.3f) exploits '
                   'member %d (return %.3f), new hypers %s', r, k,
                   returns[k], donor, returns[donor],
                   decision['hypers'])
          round_rec['decisions'].append(dict(decision, member=k))
      writer.scalar('population/exploits_total', pop_stats['exploits'],
                    target)
      pbt_log['rounds'].append(round_rec)
      _write_pbt_log()

    if runs:
      winner = max(runs, key=lambda k: returns[k])
      pbt_log['winner'] = {
          'member': winner, 'suite': members[winner]['suite'],
          'return': returns[winner],
          'hypers': dict(members[winner]['hypers']),
          'logdir': os.path.join(config.logdir,
                                 f'member_{winner:02d}')}
      _write_pbt_log()
      incidents.event('pbt_winner', member=winner,
                      suite=members[winner]['suite'],
                      final_return=returns[winner],
                      hypers=members[winner]['hypers'])
      log.info('pbt winner: member %d (%s) return %.3f hypers %s',
               winner, members[winner]['suite'], returns[winner],
               members[winner]['hypers'])
      return runs[winner]
    raise RuntimeError('population run trained no member (drained '
                       'before the first member run?)')
  finally:
    for gauge in pop_gauges:
      telemetry.registry().unregister(gauge.name, gauge)
    writer.close()
    incidents.close()


def play(config: Config, agent, params, obs_spec, levels,
         num_actors: int, level_offset: int = 0, seed_base: int = 0,
         fleet_factory=None,
         stop_event: Optional[threading.Event] = None,
         stall_timeout_secs: Optional[float] = 300.0,
         drought_secs: float = 600.0) -> Dict[int, List[float]]:
  """Serve `params` to `num_actors` actors and play: the serving path
  with no learner behind it (inference server, batcher, actors, envs).

  Actor i plays `levels[(level_offset + i) % len(levels)]` with env
  seed `seed_base + i + 1`. Plays until every level id in play has
  `config.test_num_episodes` finished episodes, or `stop_event` is set
  (the preemption seam, as `train`'s `drain_event`). Returns {level
  id: [episode returns]}.

  Two callers, one path: `evaluate` passes the parameters it restored
  from a checkpoint; a serving deployment (and the benchmark's serving
  cells) pass the parameters they hold. `fleet_factory(config, agent,
  policy, buffer, levels)` replaces `make_fleet` when given, as in
  `train`: the seam a caller-side clock goes round `policy` at.

  Inference compiles exactly ONE padded bucket (`pad_batch_to`): all
  actors step concurrently, so merged batches converge to one size
  anyway, and warming every power-of-two bucket cost 6 serial 20-40 s
  compiles on dmlab30 before the first episode (VERDICT r3 W5).
  """
  ids = sorted({(level_offset + i) % len(levels)
                for i in range(num_actors)})
  returns: Dict[int, List[float]] = {level_id: [] for level_id in ids}

  def stats_view(unroll):
    """Single-unroll [T+1, 1] view — no frame stacking."""
    expand = lambda x: np.asarray(x)[:, None]  # noqa: E731
    return _stats_only_view(
        np.asarray([unroll.level_name]),
        jax.tree_util.tree_map(expand, unroll.env_outputs.info),
        expand(unroll.env_outputs.done))

  # Same setup-failure guard as train(): a make_fleet raise (env
  # construction) must not leak the warmed inference server.
  server = None
  fleet = None
  try:
    # No fleet_size here: the auto merge FLOOR (inference_min_batch
    # =0) must not apply to eval — levels retire as their episodes
    # finish, so the caller count shrinks PERMANENTLY below the
    # floor and the tail would step one timeout per batch
    # (reintroducing the W5 tail stalls pad_batch_to eliminated).
    # pad_batch_to keeps the single-compile property either way.
    server = InferenceServer(agent, params, config,
                             seed=config.seed + 2000,
                             mesh=_choose_eval_mesh(),
                             pad_batch_to=num_actors)
    server.warmup(obs_spec, max_size=num_actors)
    buffer = ring_buffer.TrajectoryBuffer(max(2 * num_actors, 2))
    if fleet_factory is not None:
      fleet = fleet_factory(config, agent, server.policy, buffer, levels)
    else:
      # Eval acquisitions carry the EVAL admission class: on a shared
      # or constrained state arena, eval churn parks behind live
      # traffic instead of starving it (the fleet's priority kwarg is
      # accepted and overridden — every eval acquire is eval-class).
      fleet = make_fleet(
          config, agent, server.policy, buffer, levels,
          seed_base=seed_base, level_offset=level_offset, is_test=True,
          num_actors=num_actors,
          initial_state_fn=lambda priority=None:
              server.initial_core_state(
                  priority=inference_lib.PRIORITY_EVAL))
  except BaseException:
    if server is not None:
      server.close()
    raise

  try:
    fleet.start()
    last_unroll_time = time.monotonic()
    errors: List[BaseException] = []
    while (any(len(returns[i]) < config.test_num_episodes for i in ids)
           and not (stop_event is not None and stop_event.is_set())):
      try:
        unroll = buffer.get(timeout=1 if stop_event is not None else 10)
      except TimeoutError:
        # Read errors BEFORE check_health — a respawn clears the
        # slot's error, and a crash-looping actor's root cause must
        # survive to the drought raise below.
        errors = fleet.errors() or errors
        # Detect dead AND stalled actors (a wedged env whose thread
        # is alive would otherwise spin this loop forever while
        # healthy levels keep producing).
        fleet.check_health(stall_timeout_secs=stall_timeout_secs)
        if time.monotonic() - last_unroll_time > drought_secs:
          raise errors[0] if errors else TimeoutError(
              f'play produced no unrolls for {drought_secs}s')
        continue
      except ring_buffer.Closed:
        errors = fleet.errors() or errors
        raise errors[0] if errors else ring_buffer.Closed()
      last_unroll_time = time.monotonic()
      errors = []  # recovered; see train()
      for level_id, ep_return, _ in observability.extract_episodes(
          stats_view(unroll)):
        returns[level_id].append(ep_return)
      fleet.check_health(stall_timeout_secs=stall_timeout_secs)
  finally:
    try:
      _log_env_transport(fleet.stats())
    except Exception:
      log.exception('env transport summary failed')
    fleet.stop()
    server.close()
  return returns


def evaluate(config: Config,
             stall_timeout_secs: Optional[float] = 300.0,
             eval_drought_secs: float = 600.0
             ) -> Dict[str, List[float]]:
  """Play test_num_episodes per level from the latest checkpoint.

  Returns {train_level_name: [episode returns]}; logs DMLab-30
  human-normalized scores in multi-task mode (reference test()
  ≈L595–630: SingularMonitoredSession restore + done[1:] extraction).

  TPU re-design over the reference: instead of stepping levels one by
  one at batch 1, ALL levels evaluate concurrently — one env+actor per
  test level feeding the same dynamic batcher, so the chip sees merged
  inference batches (30× fewer serialized device round trips on
  DMLab-30).

  Multi-host: test levels PARTITION across processes (contiguous
  slices — each host plays only its share through its local sharded
  batcher), per-level returns allgather at the end, and only process 0
  computes scores and writes the single `eval_summaries.jsonl`
  (VERDICT r3 W2: previously every process duplicated the entire
  benchmark and wrote divergent score files). Every process returns
  the same combined dict.

  The play phase itself is `play`, which any holder of parameters can
  call; this function restores them from the latest checkpoint.
  """
  from scalable_agent_tpu.parallel import distributed
  # Same contract as train(): validate the declared topology BEFORE
  # the join (crisp ValueError, not a hung initialization window).
  for warning in validate_distributed(config):
    log.warning('%s', warning)
  # Every validate_* knob group runs on the eval path too (round 18,
  # the validate-coverage lint): a hard range/enum error must fail an
  # eval exactly like a train — before this, a bad replay/transport/
  # SLO knob passed eval spin-up silently and only exploded (or was
  # silently ignored) once the same config reached train.
  for group_warnings in (validate_replay(config),
                         validate_transport(config),
                         validate_integrity(config),
                         validate_slo(config),
                         validate_controller(config),
                         validate_runtime(config),
                         validate_serving(config),
                         validate_population(config)):
    for warning in group_warnings:
      log.warning('%s', warning)
  distributed.maybe_initialize(config)
  train_levels = factory.level_names(config)
  test_levels = factory.test_level_names(config)
  num_procs = jax.process_count()
  pidx = jax.process_index()
  num_test = len(test_levels)
  base_count, rem = divmod(num_test, num_procs)
  counts = [base_count + (i < rem) for i in range(num_procs)]
  start = sum(counts[:pidx])
  my_count = counts[pidx]
  my_ids = list(range(start, start + my_count))
  if num_procs > 1:
    log.info('eval process %d/%d plays levels [%d, %d) of %d', pidx,
             num_procs, start, start + my_count, num_test)
  spec0 = factory.make_env_spec(config, test_levels[0], seed=1,
                                is_test=True)
  agent = build_agent(config, spec0.num_actions,
                      num_tasks=len(train_levels))
  params = init_params(agent, jax.random.PRNGKey(config.seed),
                       spec0.obs_spec)

  checkpointer = checkpoint_lib.Checkpointer(
      config.logdir + '/checkpoints')
  # Params-only restore: eval never materializes the RMSProp moments
  # (≈2× params) — see Checkpointer.restore_latest_params. The manager
  # closes on the raise path too (structure-mismatch guidance lives in
  # checkpoint._wrap_structure_error).
  try:
    restored = checkpointer.restore_latest_params(
        params,
        lambda p: learner_lib.make_train_state(
            p, config, len(train_levels) if config.use_popart else 0))
  finally:
    checkpointer.close()
  if restored is None:
    raise FileNotFoundError(
        f'no checkpoint under {config.logdir}/checkpoints')
  params, restored_steps = restored
  if num_procs > 1:
    # Restored leaves carry the checkpoint's GLOBAL placements (train
    # meshes span hosts — and Orbax may fall back to the sharding
    # recorded in the file). Eval inference is host-local, so localize
    # to host values first: a direct device_put of globally placed
    # leaves onto the local eval mesh is a cross-host transfer, which
    # CPU/gloo backends reject outright. Collective — every process
    # passes through here before its play phase.
    params = multihost_utils.process_allgather(params, tiled=True)

  level_returns: Dict[str, List[float]] = {
      name: [] for name in train_levels}

  # A process with no assigned levels (more hosts than test levels)
  # skips the play phase but still joins the allgather below.
  if my_count > 0:
    # level_offset keeps level ids GLOBAL (actor i plays
    # test_levels[start + i] and stamps that id on its unrolls);
    # seed_base offsets by start so env streams stay disjoint across
    # processes.
    played = play(config, agent, params, spec0.obs_spec, test_levels,
                  num_actors=my_count, level_offset=start,
                  seed_base=config.seed - 1 + start,
                  stall_timeout_secs=stall_timeout_secs,
                  drought_secs=eval_drought_secs)
    for level_id, returns in played.items():
      level_returns[train_levels[level_id]].extend(returns)

  if num_procs > 1:
    # Aggregate per-level returns: a dense [L, E] matrix (NaN = not
    # played here) allgathers to [P, L, E]; each level's row is taken
    # from its OWNER process. Every process computes the same combined
    # dict; only process 0 writes/scoring below.
    episodes = config.test_num_episodes
    mat = np.full((num_test, episodes), np.nan, np.float32)
    for lid in my_ids:
      rets = level_returns[train_levels[lid]][:episodes]
      mat[lid, :len(rets)] = rets
    gathered = np.asarray(multihost_utils.process_allgather(mat))
    owner = np.repeat(np.arange(num_procs), counts)
    for lid in range(num_test):
      row = gathered[owner[lid], lid]
      level_returns[train_levels[lid]] = [
          float(x) for x in row if not np.isnan(x)]

  if pidx != 0:
    return {name: returns[:config.test_num_episodes]
            for name, returns in level_returns.items()}

  writer = observability.SummaryWriter(config.logdir,
                                       filename='eval_summaries.jsonl')
  step = restored_steps
  for train_name, test_name in zip(train_levels, test_levels):
    returns = level_returns[train_name][:config.test_num_episodes]
    level_returns[train_name] = returns
    mean_return = float(np.mean(returns)) if returns else float('nan')
    log.info('level %s: mean return %.2f over %d episodes', test_name,
             mean_return, len(returns))
    writer.scalar(f'{test_name}/test_episode_return', mean_return,
                  step)

  if config.level_name in suites.SUITES:
    scores = suites.SUITES[config.level_name].eval_scores(level_returns)
    log.info('%s human-normalized: %s', config.level_name,
             ' '.join(f'{t.split("/")[-1]}={v:.1f}'
                      for t, v in scores.items()))
    for tag, value in scores.items():
      writer.scalar(tag, value, step)
  writer.close()
  return level_returns

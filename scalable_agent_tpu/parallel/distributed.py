"""Multi-host initialization (the reference's ClusterSpec/gRPC role).

The reference scales across machines with the TF1 distributed runtime:
`tf.train.ClusterSpec` + `tf.train.Server`, learner-hosted queue,
variables served over gRPC (reference: experiment.py ≈L435–460; SURVEY
§5.8). The TPU-native story has no parameter server and no remote queue:

- every host runs the SAME program; `jax.distributed.initialize` wires
  the processes into one runtime;
- the device mesh (parallel/mesh.py) spans all hosts' chips; gradient
  psum rides ICI within a slice and DCN across slices — XLA picks the
  transport from the mesh topology;
- trajectory transport stays host-local: each host's actor fleet feeds
  the learner shard(s) on that host (data-parallel inputs are per-host
  shards of the global batch via
  `jax.make_array_from_process_local_data`);
- weight snapshots for actors are host-local device_gets — no gRPC.

On a single host this module is a no-op; the driver works unchanged.
"""

import logging
import os
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache

log = logging.getLogger('scalable_agent_tpu')


def initialize(coordinator_address: str, num_processes: int,
               process_id: int,
               local_device_ids: Optional[list] = None,
               heartbeat_timeout_secs: Optional[int] = None) -> None:
  """Join the multi-host runtime (call before any device op).

  Cross-process collectives on the CPU backend need no arming here:
  `jax_cpu_collectives_implementation` defaults to gloo.

  Args:
    coordinator_address: 'host:port' of process 0 (the reference's
      learner address role, minus the parameter server).
    num_processes: total host process count.
    process_id: this process's index (the reference's --task).
    local_device_ids: optionally restrict this process's devices.
    heartbeat_timeout_secs: how long the coordination service waits
      for a silent process before declaring it dead. None keeps jax's
      default (100 s — right for production pods riding out GC
      pauses; the test harness passes seconds so a SIGKILL drill
      doesn't park the survivors for minutes).
  """
  kwargs = {}
  if heartbeat_timeout_secs is not None:
    kwargs['heartbeat_timeout_seconds'] = heartbeat_timeout_secs
  jax.distributed.initialize(
      coordinator_address=coordinator_address,
      num_processes=num_processes,
      process_id=process_id,
      local_device_ids=local_device_ids,
      **kwargs)
  log.info('jax.distributed: process %d/%d, %d local / %d global devices',
           process_id, num_processes, jax.local_device_count(),
           jax.device_count())


def _cpu_pinned_platform() -> bool:
  """True when this process is explicitly pinned to XLA:CPU.

  Checked WITHOUT touching `jax.devices()` — arming must never be
  what spins up the backend (that would break the
  distributed-init-before-backend ordering above). `jax_platforms`
  carries both a `jax.config.update` pin (tests/conftest.py) and a
  plain `JAX_PLATFORMS=cpu python ...` launch (jax reads the variable
  into the option at import)."""
  return (jax.config.jax_platforms or '').strip().lower() == 'cpu'


def arm_compile_cache(config) -> None:
  """Point jax's persistent compilation cache at the directory the
  config resolves (`Config.resolved_compile_cache_dir`: nothing where
  JAX_COMPILATION_CACHE_DIR places the cache from outside, else one
  fixed path inside the checkout, else the explicit flag).

  Call it before the first compile; every entry does, through
  maybe_initialize (chip_smoke.py calls it directly before its own
  kernel check). First writer wins: a directory an earlier caller in
  this process armed stays armed.

  'auto' declines to arm on a CPU-pinned process: XLA:CPU executable
  deserialization was unreliable at driver scale on jaxlib 0.4.36
  (SIGSEGV/SIGABRT reloading ~1 MB train-step executables), and with
  one fixed directory every CPU test and tool run would start sharing
  entries. Whether 0.9.0 still has the defect is ROADMAP D7's to
  re-test; an explicit --compile_cache_dir, or the environment
  variable, arms anywhere."""
  cache_dir = config.resolved_compile_cache_dir
  if not cache_dir:
    return
  if config.compile_cache_dir == 'auto' and _cpu_pinned_platform():
    log.info('persistent compilation cache: auto-arm skipped on '
             'CPU-pinned process (pass --compile_cache_dir or set '
             'JAX_COMPILATION_CACHE_DIR to override)')
    return
  if jax.config.jax_compilation_cache_dir:
    return  # first writer wins — an armed cache stays armed.
  try:
    os.makedirs(cache_dir, exist_ok=True)
  except OSError:
    # A read-only checkout costs the warm start, never the run.
    log.warning('persistent compilation cache not armed: cannot '
                'create %s', cache_dir, exc_info=True)
    return
  jax.config.update('jax_compilation_cache_dir', cache_dir)
  # Drop any cache object built against the previous (unset) option
  # so the new directory takes effect.
  compilation_cache.reset_cache()
  log.info('persistent compilation cache armed: %s', cache_dir)


def maybe_initialize(config) -> bool:
  """driver.train's spin-up seam (round 17): join the runtime the
  config names, exactly once.

  Returns True when this call initialized. No-ops (False) when the
  config names no coordinator, or when the process already joined —
  the launcher/test-harness path, where jax.distributed was
  initialized before driver.train was called.

  Also arms the persistent compilation cache (round 23) — here
  rather than in train() because this is the one seam every entry
  path (train, train_population members, evaluate) crosses before
  its first compile."""
  arm_compile_cache(config)
  if not config.coordinator_address:
    return False
  if jax.distributed.is_initialized():
    log.info('jax.distributed already initialized '
             '(%d processes) — coordinator flags are a no-op',
             jax.process_count())
    return False
  from scalable_agent_tpu.config import resolve_process_id
  initialize(config.coordinator_address,
             num_processes=config.num_processes,
             process_id=resolve_process_id(config))
  return True


def topology_delta(saved_mesh_shape, mesh) -> Optional[dict]:
  """The elastic-restart detector (round 20, elastic membership).

  Compares the mesh-shape dict a checkpoint's sharding manifest
  recorded at save time against the LIVE mesh. None = same topology
  (or nothing recorded — pre-manifest checkpoints restore on the
  unchanged fixed-topology path); else the change record the driver
  logs and writes as the durable `topology_resharded` incident, with
  the live process topology attached so a postmortem can tell a
  2→4 grow from a 4→2 shrink without cross-referencing launch logs."""
  if saved_mesh_shape is None or mesh is None:
    return None
  live = {str(axis): int(n) for axis, n in dict(mesh.shape).items()}
  saved = {str(axis): int(n) for axis, n in saved_mesh_shape.items()}
  if saved == live:
    return None
  return {'saved_mesh': saved, 'live_mesh': live,
          'processes': jax.process_count(),
          'process_index': jax.process_index()}


def global_batch_from_local(mesh, spec, local_batch):
  """Assemble a globally-sharded array from this host's local batch.

  Each host contributes its fleet's unrolls as the process-local part
  of the data-axis-sharded global batch (the reference's remote
  enqueue [NET] becomes: no transport at all — data stays where it
  was produced)."""
  return jax.tree_util.tree_map(
      lambda x, s: jax.make_array_from_process_local_data(s, x),
      local_batch, spec)

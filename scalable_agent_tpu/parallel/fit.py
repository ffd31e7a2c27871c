"""Compiled memory-fit check for the v5e-16 north-star topology.

BASELINE.json's ≥200k-fps target runs the FULL-FEATURE flagship step
(deep ResNet, T=100, B=32, DMLab 72×96, bf16, PopArt + pixel control +
instruction) data-parallel over 16 chips. Until round 6 the "fits on a
v5e-16" claim was projection arithmetic (docs/PERF.md collective
terms); this module turns it into a compiled fact: AOT-lower the
sharded train step over a pure-DP ``{'data': N}`` mesh, compile it,
and read per-device buffer sizes out of XLA's ``memory_analysis()``.

`__graft_entry__.dryrun_multichip` passes the 16 compile-only devices
of a `v5e:4x4` topology description (libtpu needs no chip for that),
so its figure is the TPU compiler's buffer assignment. Caveat for the
other callers, stated where the numbers are made: on N *virtual CPU
devices* the figure is the CPU backend's buffer assignment for the
per-device shapes — layout padding and fusion choices differ from
the TPU emitter's (CPU also computes bf16 matmuls via f32
temporaries, which *overstates* temp. vs a real v5e), so it checks
the mechanics, not the fit. The result records the backend it
compiled for.

Consumed by:
- ``__graft_entry__.dryrun_multichip`` — prints the fit figures for
  B=32 and B=16;
- ``scripts/aot_fit.py`` — the <60 s CPU CI smoke (scripts/ci.sh);
- ``tests/test_parallel.py`` — mechanics gate on the 8-device mesh.
"""

from typing import Any, Dict, Optional, Sequence

V5E_HBM_BYTES = 16 * 2**30  # 16 GiB HBM per v5e chip.
# Reserve headroom for XLA's runtime allocations the compile-time
# analysis cannot see (infeed buffers, collectives scratch, the
# framework's own arrays). 15% mirrors jax's default
# XLA_PYTHON_CLIENT_MEM_FRACTION margin.
HBM_BUDGET_FRACTION = 0.85


def full_feature_config(batch_size: int = 32, unroll_length: int = 100,
                        height: int = 72, width: int = 96):
  """The flagship full-feature learner config (the BASELINE.json
  DMLab-30 operating point)."""
  from scalable_agent_tpu.config import Config
  return Config(batch_size=batch_size, unroll_length=unroll_length,
                num_action_repeats=4, torso='deep',
                compute_dtype='bfloat16', use_popart=True,
                pixel_control_cost=0.01, use_instruction=True,
                height=height, width=width,
                total_environment_frames=int(1e9))


def aot_memory_fit(devices: Optional[Sequence[Any]] = None,
                   batch_size: int = 32, unroll_length: int = 100,
                   height: int = 72, width: int = 96,
                   num_tasks: int = 30,
                   hbm_bytes: int = V5E_HBM_BYTES) -> Dict[str, Any]:
  """AOT-compile the sharded full-feature step; return per-device fit.

  Pure-DP mesh over ``devices`` (default: all). Everything is
  abstract (``jax.eval_shape`` params, ShapeDtypeStruct batch): no
  param or batch buffer is ever materialized — this works at flagship
  shapes on any host.

  Returns a dict with per-device byte figures and ``fits`` — whether
  live bytes (arguments + outputs + temp − donation alias) stay under
  ``HBM_BUDGET_FRACTION`` of ``hbm_bytes``.
  """
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  from scalable_agent_tpu.parallel import sharding as sharding_lib
  from scalable_agent_tpu.testing import make_example_batch

  devices = list(devices) if devices is not None else jax.devices()
  n = len(devices)
  if batch_size % n:
    raise ValueError(f'batch_size={batch_size} must divide the mesh '
                     f'size {n}')
  mesh = mesh_lib.make_mesh(devices, model_parallelism=1)
  cfg = full_feature_config(batch_size, unroll_length, height, width)
  from scalable_agent_tpu import driver
  agent = driver.build_agent(cfg, num_actions=9, num_tasks=num_tasks)
  obs_spec = {'frame': (height, width, 3),
              'instr_len': MAX_INSTRUCTION_LEN}

  params_abs = jax.eval_shape(
      lambda: init_params(agent, jax.random.PRNGKey(0), obs_spec))
  state_abs = jax.eval_shape(
      lambda p: learner_lib.make_train_state(p, cfg,
                                             num_popart_tasks=num_tasks),
      params_abs)
  # Abstract batch: shapes/dtypes only (the real constructor would
  # materialize a ~67 MB frame stack for nothing). Built as an
  # eval_shape over the canonical constructor so the struct layout
  # can never drift from testing.make_example_batch.
  batch = jax.eval_shape(
      lambda: make_example_batch(unroll_length + 1, batch_size,
                                 height, width, 9,
                                 MAX_INSTRUCTION_LEN))

  # Pure-DP registry (round 19): params/state replicated, batch over
  # the data axis — the single sharding authority, not a private copy.
  registry = sharding_lib.ShardingRegistry(
      sharding_lib.RULE_SETS['replicated'], rule_set='replicated')
  batch_shard = registry.batch_shardings(batch, mesh)
  state_sh = registry.state_shardings(state_abs, mesh)
  # mesh rides in so a pallas-vtrace config lowers under shard_map
  # instead of failing the AOT fit (round 8 — the mesh restriction is
  # lifted everywhere, this path included).
  step = learner_lib.make_train_step_fn(agent, cfg, mesh=mesh)
  compiled = jax.jit(
      step, in_shardings=(state_sh, batch_shard),
      donate_argnums=(0,)).lower(state_abs, batch).compile()
  ma = compiled.memory_analysis()
  live = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
          ma.temp_size_in_bytes - ma.alias_size_in_bytes)
  budget = int(hbm_bytes * HBM_BUDGET_FRACTION)
  return {
      'mesh': {'data': n},
      'backend': devices[0].platform,
      'batch_size': batch_size,
      'per_device_batch': batch_size // n,
      'unroll_length': unroll_length,
      'argument_bytes': int(ma.argument_size_in_bytes),
      'output_bytes': int(ma.output_size_in_bytes),
      'temp_bytes': int(ma.temp_size_in_bytes),
      'alias_bytes': int(ma.alias_size_in_bytes),
      'live_bytes': int(live),
      'live_gib': round(live / 2**30, 3),
      'hbm_bytes': int(hbm_bytes),
      'hbm_budget_bytes': budget,
      'fits': bool(live <= budget),
  }


def format_fit(fit: Dict[str, Any]) -> str:
  """One tail-capture-friendly line."""
  gib = 1 / 2**30
  return (
      'aot_fit(v5e16): B=%d (per-device %d) T=%d mesh=%s live=%.3f GiB'
      ' (args %.3f + out %.3f + temp %.3f - alias %.3f) vs budget '
      '%.1f GiB [backend=%s] %s' % (
          fit['batch_size'], fit['per_device_batch'],
          fit['unroll_length'], fit['mesh'],
          fit['live_bytes'] * gib, fit['argument_bytes'] * gib,
          fit['output_bytes'] * gib, fit['temp_bytes'] * gib,
          fit['alias_bytes'] * gib, fit['hbm_budget_bytes'] * gib,
          fit['backend'], 'ok' if fit['fits'] else 'DOES NOT FIT'))

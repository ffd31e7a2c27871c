"""Child process for the two-process multi-host driver tests.

Each process joins jax.distributed (2 procs × 2 virtual CPU devices =
a 4-way data mesh) and runs the REAL driver.train. Run by
test_multihost.py — not collected by pytest itself. Modes (argv[4],
default 'run'):

- run:    3 steps, assert, exit 0 (the original two-process test).
- drill:  train indefinitely with frequent collective checkpoints —
          the failure-drill phase 1 body; the parent SIGKILLs one
          process and watches the other terminate.
- resume N: restore from the drill's checkpoints (expect step N), run
          2 more steps, exit 0 — the failure-drill phase 2 body.
- mixed P: mixed trajectory sources across the SAME mesh — process 0
          opens a remote-actor ingest on port P and runs NO local
          actors (its batch shard arrives over TCP) while process 1
          keeps a local fleet; 3 steps, assert, exit 0.
- save:   train 2 deterministic sharded steps and write a registry-
          manifested checkpoint (the elastic drill's topology-A leg).
- reshard P: restore the 'save' checkpoint onto THIS topology via
          restore_resharded, step once, dump checksums+loss to P —
          the parent parity-gates a cross-topology restore against a
          same-topology one (round 20 elastic membership).
- tp4:    4 processes × 1 device, model_parallelism=2 — the model
          axis PAIRS DEVICES FROM DIFFERENT PROCESSES (mesh rows
          [[p0,p1],[p2,p3]]), so TP matmul collectives cross the
          process boundary; 3 sharded steps on a deterministic batch
          must match a single-device reference numerically.

Topology knobs via env (the parent test sets them): MH_NPROCS
(default 2), MH_NDEV devices per process (default 2), MH_BATCH
(default 4).
"""

import os
import sys

# The env/model knobs every mode (and the mixed test's remote actor
# host) must share — the remote protocol requires learner and actor
# configs to agree exactly.
CHILD_CONFIG = dict(
    env_backend='bandit', level_name='bandit',
    num_actors=2, batch_size=4,          # GLOBAL batch; 2 per host
    unroll_length=5, num_action_repeats=1, episode_length=4,
    height=24, width=32, torso='shallow', use_py_process=False,
    use_instruction=False, total_environment_frames=10**9,
    inference_timeout_ms=5, checkpoint_secs=0, summary_secs=0,
    # Same seed on every process: model init must be IDENTICAL across
    # hosts (the driver diversifies env/sampling streams by process
    # internally).
    seed=3)


def main():
  proc = int(sys.argv[1])
  port = sys.argv[2]
  logdir = sys.argv[3]
  mode = sys.argv[4] if len(sys.argv) > 4 else 'run'
  nprocs = int(os.environ.get('MH_NPROCS', '2'))
  ndev = int(os.environ.get('MH_NDEV', '2'))
  batch = int(os.environ.get('MH_BATCH', '4'))
  os.environ['XLA_FLAGS'] = (
      f'--xla_force_host_platform_device_count={ndev}')
  import jax
  jax.config.update('jax_platforms', 'cpu')
  # The runtime's own spin-up seam (round 17), before the backend is
  # built.
  from scalable_agent_tpu.parallel import distributed
  # Tight failure detection (8 s): the SIGKILL drill's survivors must
  # abort in seconds, not jax's production default 100 s.
  distributed.initialize(f'localhost:{port}', num_processes=nprocs,
                         process_id=proc, heartbeat_timeout_secs=8)
  assert jax.device_count() == nprocs * ndev
  assert jax.local_device_count() == ndev

  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  cfg = Config(logdir=logdir, **dict(CHILD_CONFIG, batch_size=batch))

  if mode == 'run':
    # MH_MP>1 runs the FULL driver with TP (with nprocs>ndev*mp the
    # model axis crosses the process boundary — the tp4 mode proves
    # the numerics at step level; this proves driver.train end to end:
    # mesh choice, batch-width check, fleets, place_batch, train).
    mp = int(os.environ.get('MH_MP', '1'))
    if mp > 1:
      import dataclasses
      cfg = dataclasses.replace(cfg, model_parallelism=mp)
    run = driver.train(cfg, max_steps=3, stall_timeout_secs=120)
    assert int(run.state.update_steps) == 3, run.state.update_steps
    if mp > 1:
      import jax as _jax
      tp_leaves = [
          x for x in _jax.tree_util.tree_leaves(run.state.params)
          if 'model' in str(getattr(x.sharding, 'spec', ''))]
      assert tp_leaves, 'driver TP run produced no model-sharded param'
    print(f'child {proc}: ok', flush=True)
  elif mode == 'mixed':
    ingest_port = int(sys.argv[5])
    if proc == 0:
      cfg.remote_actor_port = ingest_port
      cfg.num_actors = 0
    run = driver.train(cfg, max_steps=3, stall_timeout_secs=180)
    assert int(run.state.update_steps) == 3, run.state.update_steps
    if proc == 0:
      stats = run.ingest.stats()
      assert stats['unrolls'] >= 3 * (cfg.batch_size // 2), stats
      assert run.fleet.stats()['unrolls'] == 0
    else:
      assert run.fleet.stats()['unrolls'] >= 3 * (cfg.batch_size // 2)
    print(f'child {proc}: mixed ok', flush=True)
  elif mode == 'tp4':
    import dataclasses
    import numpy as np
    import jax.numpy as jnp
    from scalable_agent_tpu import learner as learner_lib
    from scalable_agent_tpu.models import init_params
    from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
    from scalable_agent_tpu.parallel import mesh as mesh_lib
    from scalable_agent_tpu.parallel import train_parallel
    from scalable_agent_tpu.testing import make_example_batch

    assert nprocs == 4 and ndev == 1
    cfg = dataclasses.replace(cfg, batch_size=4, model_parallelism=2)
    num_actions = 3
    agent = driver.build_agent(cfg, num_actions)
    obs = {'frame': (cfg.height, cfg.width, 3),
           'instr_len': MAX_INSTRUCTION_LEN}
    params = init_params(agent, jax.random.PRNGKey(cfg.seed), obs)
    mesh = mesh_lib.make_mesh(model_parallelism=2)  # [[p0,p1],[p2,p3]]
    # The model pair (row of the mesh) must CROSS the process
    # boundary — that is the point of this mode.
    assert (mesh.devices[0, 0].process_index !=
            mesh.devices[0, 1].process_index)

    t1 = cfg.unroll_length + 1
    batch = make_example_batch(t1, cfg.batch_size, cfg.height,
                               cfg.width, num_actions,
                               MAX_INSTRUCTION_LEN, seed=7,
                               done_prob=0.1)
    state = train_parallel.make_sharded_train_state(
        params, cfg, mesh, enable_tp=True)
    # TP placements are real: some kernel shards over the model axis.
    tp_leaves = [x for x in jax.tree_util.tree_leaves(state.params)
                 if 'model' in str(getattr(x.sharding, 'spec', ''))]
    assert tp_leaves, 'no TP-sharded parameter found'
    step, place = train_parallel.make_sharded_train_step(
        agent, cfg, mesh, batch)

    # This process's single row of the global batch (batch dim sharded
    # over (data, model): shard index = data*mp + model = proc here).
    host = jax.tree_util.tree_map(np.asarray, batch)
    local = host._replace(
        level_name=host.level_name[proc:proc + 1],
        agent_state=jax.tree_util.tree_map(
            lambda x: x[proc:proc + 1], host.agent_state),
        env_outputs=jax.tree_util.tree_map(
            lambda x: x[:, proc:proc + 1], host.env_outputs),
        agent_outputs=jax.tree_util.tree_map(
            lambda x: x[:, proc:proc + 1], host.agent_outputs))
    dev_batch = place(local)
    losses = []
    for _ in range(3):
      state, metrics = step(state, dev_batch)
      losses.append(float(jax.device_get(metrics['total_loss'])))

    @jax.jit
    def checksum(p):
      return jax.tree_util.tree_reduce(
          lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))),
          p, jnp.float32(0))

    got_sum = float(jax.device_get(checksum(state.params)))

    # Single-device reference on the same (deterministic) batch: the
    # cross-process TP math must reproduce it.
    params_ref = init_params(agent, jax.random.PRNGKey(cfg.seed), obs)
    ref = learner_lib.make_train_state(params_ref, cfg)
    ref_step = learner_lib.make_train_step(agent, cfg)
    ref_losses = []
    for _ in range(3):
      ref, ref_metrics = ref_step(ref, batch)
      ref_losses.append(float(jax.device_get(
          ref_metrics['total_loss'])))
    ref_sum = float(jax.device_get(checksum(ref.params)))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(got_sum, ref_sum, rtol=2e-4)
    print(f'child {proc}: tp4 ok', flush=True)
  elif mode == 'eval':
    # Sharded multi-host eval (VERDICT r3 W2): one training step lays
    # down the collective checkpoint; evaluate() then plays only this
    # process's slice of the 30 test levels, allgathers per-level
    # returns (so BOTH processes see all 30 filled), and only process
    # 0 writes the single eval_summaries.jsonl the parent asserts on.
    cfg = Config(logdir=logdir, **dict(
        CHILD_CONFIG, batch_size=batch, level_name='dmlab30',
        unroll_length=4, episode_length=2, test_num_episodes=1))
    run = driver.train(cfg, max_steps=1, stall_timeout_secs=180)
    assert int(run.state.update_steps) == 1
    # Record which test envs THIS process actually builds — the direct
    # evidence of disjoint level coverage the parent asserts on.
    from scalable_agent_tpu.envs import factory as factory_lib
    played = []
    orig_spec = factory_lib.make_env_spec

    def recording_spec(config, level_name, seed, is_test=False):
      if is_test:
        played.append(level_name)
      return orig_spec(config, level_name, seed, is_test=is_test)

    factory_lib.make_env_spec = recording_spec
    try:
      returns = driver.evaluate(cfg, stall_timeout_secs=120)
    finally:
      factory_lib.make_env_spec = orig_spec
    assert len(returns) == 30, len(returns)
    short = {k: len(v) for k, v in returns.items() if len(v) != 1}
    assert not short, short
    # played[0] is the spec0 probe (test_levels[0] on every process);
    # the rest are this process's fleet envs.
    print(f'child {proc}: eval ok '
          f'played={",".join(sorted(set(played[1:])))}', flush=True)
  elif mode == 'sdc':
    # Round 17 satellite: the multi-process SDC sentinel end to end.
    # Both processes install the SAME fault plan, so the
    # replica_divergence probe perturbs one replica's fingerprint lane
    # at the same health check on every host (lockstep); the in-graph
    # all-gather returns the full [replicas] vector to each host, both
    # reach the SDC verdict together, and the broadcast-coordinated
    # rollback restores a pre-divergence checkpoint collectively.
    import dataclasses
    from scalable_agent_tpu.runtime import faults as faults_lib
    cfg = dataclasses.replace(cfg, checkpoint_check_every_steps=1,
                              health_rollback_after=1)
    faults_lib.install(faults_lib.FaultPlan.storm(
        seed=11, replica_divergence_at=3, replica_divergence_len=1))
    try:
      run = driver.train(cfg, max_steps=8, stall_timeout_secs=120)
    finally:
      faults_lib.clear()
    hs = run.health.stats()
    assert hs.get('sdc_mismatches', 0) >= 1, hs
    assert hs.get('rollbacks', 0) >= 1, hs
    assert int(run.state.update_steps) == 8, run.state.update_steps
    print(f'child {proc}: sdc ok mismatches={hs["sdc_mismatches"]} '
          f'rollbacks={hs["rollbacks"]}', flush=True)
  elif mode in ('save', 'reshard'):
    # Elastic resharding drill (round 20): 'save' trains 2
    # deterministic sharded steps on THIS topology and writes a
    # registry-manifested checkpoint; 'reshard' (argv[5] = result
    # JSON) restores that checkpoint onto THIS — possibly different —
    # topology via restore_resharded, takes 1 more step, and process 0
    # dumps the restored-params checksum, the step loss, and the
    # post-step checksum for the parent's cross-topology parity gate.
    import dataclasses
    import json
    import numpy as np
    import jax.numpy as jnp
    from scalable_agent_tpu import checkpoint as checkpoint_lib
    from scalable_agent_tpu import learner as learner_lib
    from scalable_agent_tpu.models import init_params
    from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
    from scalable_agent_tpu.parallel import mesh as mesh_lib
    from scalable_agent_tpu.parallel import sharding as sharding_lib
    from scalable_agent_tpu.parallel import train_parallel
    from scalable_agent_tpu.testing import make_example_batch

    mp = int(os.environ.get('MH_MP', '2'))
    cfg = dataclasses.replace(cfg, batch_size=batch,
                              model_parallelism=mp)
    num_actions = 3
    agent = driver.build_agent(cfg, num_actions)
    obs = {'frame': (cfg.height, cfg.width, 3),
           'instr_len': MAX_INSTRUCTION_LEN}
    params = init_params(agent, jax.random.PRNGKey(cfg.seed), obs)
    mesh = mesh_lib.make_mesh(model_parallelism=mp)
    registry = sharding_lib.from_config(cfg, enable_tp=mp > 1)
    t1 = cfg.unroll_length + 1
    gbatch = make_example_batch(t1, cfg.batch_size, cfg.height,
                                cfg.width, num_actions,
                                MAX_INSTRUCTION_LEN, seed=7,
                                done_prob=0.1)
    step, place = train_parallel.make_sharded_train_step(
        agent, cfg, mesh, gbatch)
    # Batch dim shards over (data, model) when TP spans hosts: with 1
    # device per process that is nprocs contiguous row blocks, this
    # process owning rows [proc*k, (proc+1)*k).
    k = cfg.batch_size // nprocs
    host = jax.tree_util.tree_map(np.asarray, gbatch)
    lo, hi = proc * k, (proc + 1) * k
    local = host._replace(
        level_name=host.level_name[lo:hi],
        agent_state=jax.tree_util.tree_map(
            lambda x: x[lo:hi], host.agent_state),
        env_outputs=jax.tree_util.tree_map(
            lambda x: x[:, lo:hi], host.env_outputs),
        agent_outputs=jax.tree_util.tree_map(
            lambda x: x[:, lo:hi], host.agent_outputs))
    dev_batch = place(local)

    @jax.jit
    def checksum(p):
      return jax.tree_util.tree_reduce(
          lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))),
          p, jnp.float32(0))

    ckpt = checkpoint_lib.Checkpointer(
        os.path.join(logdir, 'elastic_ckpt'), save_interval_secs=0,
        registry=registry, mesh=mesh)
    if mode == 'save':
      state = train_parallel.make_sharded_train_state(
          params, cfg, mesh, registry=registry)
      for _ in range(2):
        state, _ = step(state, dev_batch)
      assert ckpt.save(state, step=2)
      ckpt.wait_until_finished()
      ckpt.close()
      print(f'child {proc}: save ok', flush=True)
    else:
      out_path = sys.argv[5]
      state0 = learner_lib.make_train_state(params, cfg)
      abstract = jax.tree_util.tree_map(
          lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state0)
      saved_mesh = ckpt.saved_mesh_shape()
      delta = distributed.topology_delta(saved_mesh, mesh)
      if os.environ.get('MH_EXPECT_DELTA') == '1':
        assert delta is not None, (saved_mesh, dict(mesh.shape))
      restored = ckpt.restore_resharded(abstract, registry, mesh)
      assert restored is not None
      assert int(jax.device_get(restored.update_steps)) == 2
      restored_sum = float(jax.device_get(checksum(restored.params)))
      state, metrics = step(restored, dev_batch)
      loss = float(jax.device_get(metrics['total_loss']))
      stepped_sum = float(jax.device_get(checksum(state.params)))
      ckpt.close()
      if proc == 0:
        with open(out_path, 'w') as f:
          json.dump({'restored_sum': restored_sum, 'loss': loss,
                     'stepped_sum': stepped_sum, 'delta': delta}, f)
      print(f'child {proc}: reshard ok', flush=True)
  elif mode == 'drill':
    # Frequent collective checkpoints; runs until the parent kills this
    # process or the runtime aborts us because the peer died.
    cfg.checkpoint_check_every_steps = 2
    driver.train(cfg, stall_timeout_secs=120)
    print(f'child {proc}: train returned unexpectedly', flush=True)
  elif mode == 'resume':
    expect = int(sys.argv[5])
    run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
    got = int(run.state.update_steps)
    assert got == expect + 2, (got, expect)
    print(f'child {proc}: resumed from {expect} to {got} ok',
          flush=True)
  else:
    raise ValueError(mode)


if __name__ == '__main__':
  main()

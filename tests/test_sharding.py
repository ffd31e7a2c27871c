"""The sharding registry (parallel/sharding.py, round 19).

Resolution semantics (rule order, the scalar guard, the hard
unmatched-path error, optimizer-spec cloning), mesh binding (the
divisibility guard), the consumers-agree contract (learner state,
checkpoint restore targets, inference arena, SDC probe — identical
placements from ONE authority), the checkpoint sharding manifest +
registry resharding targets (ROADMAP item 3's enabler), and the 2D
{data, model} flagship parity gate: the deep ResNet + LSTM agent
trained 3 steps on a (data=4, model=2) mesh matches the single-device
reference at the established sharded-parity tolerances.

NOTE on PartitionSpec literals: tests are exempt from the
`sharding-registry` lint — these specs are the EXPECTED values the
registry is asserted against, not sharding decisions.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from scalable_agent_tpu import checkpoint as checkpoint_lib
from scalable_agent_tpu import integrity
from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.parallel import mesh as mesh_lib
from scalable_agent_tpu.parallel import sharding as sharding_lib
from scalable_agent_tpu.parallel import train_parallel
from scalable_agent_tpu.testing import make_example_batch

A = 4
OBS = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}


def _fake_batch(seed, t1, b):
  h, w, _ = OBS['frame']
  return make_example_batch(t1, b, h, w, A, OBS['instr_len'],
                            seed=seed, done_prob=0.1)


# --- resolution semantics ---------------------------------------------


def test_rule_order_first_match_wins():
  leaf = jnp.zeros((8, 16))
  specific_first = sharding_lib.ShardingRegistry((
      (r'special/kernel$', P(sharding_lib.MODEL_AXIS, None)),
      (r'.*kernel$', P(None, sharding_lib.MODEL_AXIS)),
      (r'.*', P()),
  ))
  assert (specific_first.spec_for('special/kernel', leaf) ==
          P(sharding_lib.MODEL_AXIS, None))
  assert (specific_first.spec_for('other/kernel', leaf) ==
          P(None, sharding_lib.MODEL_AXIS))
  assert specific_first.spec_for('other/bias', leaf) == P()
  # Same rules, generic first: the specific rule is now shadowed —
  # order IS the semantics (first re.search match wins).
  generic_first = sharding_lib.ShardingRegistry((
      (r'.*kernel$', P(None, sharding_lib.MODEL_AXIS)),
      (r'special/kernel$', P(sharding_lib.MODEL_AXIS, None)),
      (r'.*', P()),
  ))
  assert (generic_first.spec_for('special/kernel', leaf) ==
          P(None, sharding_lib.MODEL_AXIS))


def test_unmatched_path_is_a_hard_error():
  registry = sharding_lib.ShardingRegistry(((r'.*kernel$', P()),))
  # Matching path resolves; a path no rule matches names itself in the
  # error — silence is never a sharding decision.
  assert registry.spec_for('torso/kernel', jnp.zeros((4, 4))) == P()
  with pytest.raises(sharding_lib.ShardingRuleError,
                     match='torso/bias'):
    registry.spec_for('torso/bias', jnp.zeros((4, 4)))
  # And an empty rule set cannot even be constructed.
  with pytest.raises(ValueError, match='at least one rule'):
    sharding_lib.ShardingRegistry(())


def test_scalars_replicate_before_rules_run():
  registry = sharding_lib.ShardingRegistry(
      ((r'.*', P(sharding_lib.MODEL_AXIS)),))
  assert registry.spec_for('step', jnp.int32(3)) == P()
  assert registry.spec_for('one_elem', jnp.zeros((1,))) == P()
  # A real vector still takes the rule.
  assert (registry.spec_for('vec', jnp.zeros((8,))) ==
          P(sharding_lib.MODEL_AXIS))


def test_from_config_resolution():
  assert sharding_lib.from_config(
      Config(model_parallelism=1)).rule_set == 'replicated'
  assert sharding_lib.from_config(
      Config(batch_size=8, model_parallelism=2)).rule_set == 'megatron'
  # Explicit names win over the model_parallelism predicate.
  assert sharding_lib.from_config(
      Config(model_parallelism=1,
             sharding_rules='megatron')).rule_set == 'megatron'
  assert not sharding_lib.from_config(
      Config(batch_size=8, model_parallelism=2,
             sharding_rules='replicated')).model_sharded
  with pytest.raises(ValueError, match='bogus'):
    sharding_lib.from_config(Config(sharding_rules='bogus'))


def test_optimizer_specs_clone_param_specs():
  """SNIPPETS [1] semantics: moment buffers (param-shaped subtrees of
  the optax chain state) inherit the matched param specs leaf-for-leaf;
  every non-param leaf (the schedule count) is replicated."""
  agent = ImpalaAgent(num_actions=A, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  cfg = Config(batch_size=8, model_parallelism=2)
  state = learner_lib.make_train_state(params, cfg)
  registry = sharding_lib.from_config(cfg)

  pspecs = registry.param_specs(state.params)
  flat_p = jax.tree_util.tree_leaves(
      pspecs, is_leaf=lambda x: isinstance(x, P))
  assert any(sharding_lib.MODEL_AXIS in (s or ()) for s in flat_p)

  ospecs = registry.opt_specs(state.opt_state, pspecs)
  flat_o = jax.tree_util.tree_leaves(
      ospecs, is_leaf=lambda x: isinstance(x, P))
  # rmsprop-with-momentum chain: nu moments (param-shaped), the
  # schedule count (scalar), trace moments (param-shaped) — cloned
  # specs bracket exactly one replicated counter.
  assert flat_o == flat_p + [P()] + flat_p

  # The whole-state view: params and target_params by the rules,
  # opt_state as above, counters replicated.
  sspecs = registry.state_specs(state)
  assert jax.tree_util.tree_leaves(
      sspecs.params, is_leaf=lambda x: isinstance(x, P)) == flat_p
  assert sspecs.update_steps == P()


# --- mesh binding ------------------------------------------------------


def test_divisibility_guard_drops_odd_cuts():
  registry = sharding_lib.ShardingRegistry(
      sharding_lib.RULE_SETS['megatron'], rule_set='megatron')
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  params = {'Dense_0': {'kernel': jnp.zeros((4, 8)),
                        'bias': jnp.zeros((8,))},
            'Dense_1': {'kernel': jnp.zeros((4, 7)),   # 7 % 2 != 0
                        'bias': jnp.zeros((7,))}}
  sh = registry.param_shardings(params, mesh)
  assert sh['Dense_0']['kernel'].spec == P(None, sharding_lib.MODEL_AXIS)
  assert sh['Dense_0']['bias'].spec == P(sharding_lib.MODEL_AXIS)
  # The guard is applied at BINDING, identically for every consumer —
  # including the describe() manifest the checkpointer records.
  assert sh['Dense_1']['kernel'].spec == P()
  assert sh['Dense_1']['bias'].spec == P()
  manifest = registry.describe(params, mesh)
  assert manifest['Dense_1/kernel'] == str(P())
  assert manifest['Dense_0/kernel'] == str(P(None,
                                             sharding_lib.MODEL_AXIS))


@pytest.mark.parametrize('model_parallelism', [1, 2])
def test_mesh_wrappers_delegate_to_registry(model_parallelism):
  """parallel/mesh.py's param_shardings/batch_shardings are thin
  delegations now — identical output to querying the registry."""
  agent = ImpalaAgent(num_actions=A, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  mesh = mesh_lib.make_mesh(model_parallelism=model_parallelism)
  tp = model_parallelism > 1
  registry = sharding_lib.from_config(
      Config(batch_size=8, model_parallelism=model_parallelism),
      enable_tp=tp)

  via_mesh = mesh_lib.param_shardings(params, mesh, enable_tp=tp)
  via_registry = registry.param_shardings(params, mesh)
  for a, b in zip(jax.tree_util.tree_leaves(via_mesh),
                  jax.tree_util.tree_leaves(via_registry)):
    assert a == b

  batch = _fake_batch(0, 5, 8)
  bm = jax.tree_util.tree_leaves(mesh_lib.batch_shardings(batch, mesh))
  br = jax.tree_util.tree_leaves(registry.batch_shardings(batch, mesh))
  assert bm == br
  # Cross-host TP layout: the batch dim spans BOTH axes.
  over = registry.batch_specs(batch, shard_over_model=True)
  assert over.env_outputs.reward == P(
      None, (sharding_lib.DATA_AXIS, sharding_lib.MODEL_AXIS))
  assert over.level_name == P(
      (sharding_lib.DATA_AXIS, sharding_lib.MODEL_AXIS))


def test_consumers_agree_on_placements():
  """The acceptance contract: every consumer's placements ARE the
  registry's — the learner's live TrainState, the checkpoint restore
  targets, the inference arena, the SDC probe, and the manifest all
  resolve to the same shardings for the same config + mesh."""
  agent = ImpalaAgent(num_actions=A, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  cfg = Config(batch_size=8, model_parallelism=2)
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  registry = sharding_lib.from_config(cfg)

  # (1) learner: the live state's leaf shardings == state_shardings.
  state = train_parallel.make_sharded_train_state(params, cfg, mesh,
                                                  registry=registry)
  expected = registry.state_shardings(state, mesh)
  live = jax.tree_util.tree_map(lambda x: x.sharding, state)
  for a, b in zip(jax.tree_util.tree_leaves(live),
                  jax.tree_util.tree_leaves(expected)):
    assert a == b
  # TP actually engaged: at least one model-sharded param on the mesh.
  assert any(sharding_lib.MODEL_AXIS in str(s.spec)
             for s in jax.tree_util.tree_leaves(live))

  # (2) checkpoint: registry restore targets pin the SAME shardings —
  # a restore lands exactly where the learner would place (and, fed a
  # different mesh, exactly where the NEW topology's rules resolve:
  # the resharding primitive).
  abstract = jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
  targets = checkpoint_lib.registry_restore_targets(abstract, registry,
                                                    mesh)
  for t, s in zip(jax.tree_util.tree_leaves(targets),
                  jax.tree_util.tree_leaves(expected)):
    assert t.sharding == s

  # (3) inference arena + (4) SDC probe placements are the registry's
  # primitive shardings, not private constructions.
  assert sharding_lib.replicated(mesh) == NamedSharding(mesh, P())
  assert sharding_lib.data_sharding(mesh) == NamedSharding(
      mesh, P(sharding_lib.DATA_AXIS))
  from scalable_agent_tpu.runtime.inference import InferenceServer
  server = InferenceServer(agent, params, Config(), seed=0, mesh=mesh)
  try:
    assert server._replicated == sharding_lib.replicated(mesh)
    assert server._batch_sharding == sharding_lib.data_sharding(mesh)
  finally:
    server.close()

  # (5) the manifest is the bound placements, stringified.
  manifest = registry.describe(state.params, mesh)
  flat = jax.tree_util.tree_flatten_with_path(
      registry.param_shardings(state.params, mesh))[0]
  for kp, sh in flat:
    path = '/'.join(str(getattr(k, 'key', k)) for k in kp)
    assert manifest[path] == str(sh.spec)

  # (6) the SDC gate consults the registry's model_sharded predicate:
  # TP params are legitimately different per device — nothing to
  # cross-compare.
  assert registry.model_sharded
  assert not train_parallel.supports_sdc_check(cfg, mesh)
  assert train_parallel.supports_sdc_check(
      Config(batch_size=8, model_parallelism=1),
      mesh_lib.make_mesh(model_parallelism=1))


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_checkpoint_sharding_manifest_and_resharded_restore(tmp_path):
  """The save-side manifest (SHARDING_{step}.json: rule set, specs,
  digest) + the restore path onto registry-resolved placements for a
  DIFFERENT mesh — cross-topology resharding (ROADMAP item 3)."""
  agent = ImpalaAgent(num_actions=A, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  cfg = Config(batch_size=8, model_parallelism=2)
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  registry = sharding_lib.from_config(cfg)
  state = train_parallel.make_sharded_train_state(params, cfg, mesh,
                                                  registry=registry)

  ckpt = checkpoint_lib.Checkpointer(str(tmp_path / 'ckpt'),
                                     save_interval_secs=0,
                                     registry=registry, mesh=mesh)
  assert ckpt.save(state, step=1)
  ckpt.wait_until_finished()

  manifest = ckpt.read_sharding_manifest(1)
  assert manifest is not None
  assert manifest['rule_set'] == 'megatron'
  assert manifest['mesh'] == {'data': 4, 'model': 2}
  assert manifest['specs'] == registry.describe(state.params, mesh)
  assert integrity.verify_record(
      manifest['digest'], integrity.spec_table_digest(manifest['specs']))
  # On disk next to the digest ledger.
  files = os.listdir(str(tmp_path / 'ckpt'))
  assert 'SHARDING_1.json' in files

  # Restore the TP-sharded checkpoint onto a PURE-DP mesh with the
  # pure-DP registry: every restored leaf lands replicated (the new
  # rules' resolution), values identical to the saved state.
  dp_cfg = Config(batch_size=8, model_parallelism=1)
  dp_mesh = mesh_lib.make_mesh(model_parallelism=1)
  dp_registry = sharding_lib.from_config(dp_cfg)
  abstract = jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
  restored = ckpt.restore_resharded(abstract, dp_registry, dp_mesh)
  assert restored is not None
  for leaf in jax.tree_util.tree_leaves(restored.params):
    assert sharding_lib.MODEL_AXIS not in str(leaf.sharding.spec)
    assert leaf.sharding.mesh.shape == dp_mesh.shape
  for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                  jax.tree_util.tree_leaves(state.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  ckpt.close()



# --- elastic resharding edge cases (round 20) --------------------------


def _abstract(state):
  return jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)


def test_layout_violations_name_the_structural_reason():
  """The three refusal stories, each named: a spec axis the mesh does
  not carry, a cut dim past the leaf's rank, and a dim that does not
  divide the axis width."""
  registry = sharding_lib.ShardingRegistry((
      (r'.*rank$', P(None, None, sharding_lib.MODEL_AXIS)),
      (r'.*odd$', P(None, sharding_lib.MODEL_AXIS)),
      (r'.*', P()),
  ), rule_set='layout-test')
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:2])
  data_only = Mesh(devs, ('data',))
  tp_mesh = Mesh(devs.reshape(1, 2), ('data', 'model'))
  tree = {'a_rank': jnp.zeros((4, 4)),   # spec cuts dim 2, rank 2
          'b_odd': jnp.zeros((4, 7)),    # 7 % 2 != 0
          'c_fine': jnp.zeros((4, 4))}

  on_tp = dict(registry.layout_violations(tree, tp_mesh))
  assert set(on_tp) == {'a_rank', 'b_odd'}
  assert 'rank' in on_tp['a_rank']
  assert 'does not divide' in on_tp['b_odd']

  # On a mesh with no model axis at all, every model cut is refused
  # with the missing-axis story (checked before rank/width).
  on_dp = dict(registry.layout_violations(tree, data_only))
  assert set(on_dp) == {'a_rank', 'b_odd'}
  assert "'model'" in on_dp['b_odd']


def test_check_layout_exempts_leaves_saved_replicated():
  """The manifest-aware exemption: a leaf the SAVE already degraded
  to replicated (odd dims under `_guard`) must not refuse a restore —
  the restore loses nothing the checkpoint still had."""
  registry = sharding_lib.ShardingRegistry((
      (r'.*odd$', P(None, sharding_lib.MODEL_AXIS)),
      (r'.*', P()),
  ), rule_set='layout-test')
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  tree = {'w_odd': jnp.zeros((4, 7))}
  with pytest.raises(sharding_lib.ShardingLayoutError, match='w_odd'):
    registry.check_layout(tree, mesh, what='param')
  # Recorded replicated at save: exempt, no raise.
  registry.check_layout(tree, mesh, what='param',
                        saved_specs={'w_odd': str(P())})
  # Recorded SHARDED at save: the refusal stands.
  with pytest.raises(sharding_lib.ShardingLayoutError,
                     match='does not divide'):
    registry.check_layout(
        tree, mesh, what='param',
        saved_specs={'w_odd': str(P(None, sharding_lib.MODEL_AXIS))})


def test_restore_resharded_strict_refusal_and_escape(tmp_path):
  """Checkpoint-level strict gate: a leaf saved SHARDED whose cut the
  target topology cannot honor refuses with the structural error;
  strict=False accepts the documented replicated degradation."""
  registry = sharding_lib.ShardingRegistry((
      (r'.*kernel$', P(None, sharding_lib.MODEL_AXIS)),
      (r'.*', P()),
  ), rule_set='layout-test')
  params = {'Dense_0': {'kernel': jnp.ones((4, 6)),   # 6 % 2 == 0
                        'bias': jnp.zeros((6,))}}
  cfg = Config(batch_size=8)
  state = learner_lib.make_train_state(params, cfg)
  save_mesh = mesh_lib.make_mesh(model_parallelism=2)

  ckpt = checkpoint_lib.Checkpointer(str(tmp_path / 'ckpt'),
                                     save_interval_secs=0,
                                     registry=registry, mesh=save_mesh)
  assert ckpt.save(state, step=1)
  ckpt.wait_until_finished()
  manifest = ckpt.read_sharding_manifest(1)
  assert (manifest['specs']['Dense_0/kernel'] ==
          str(P(None, sharding_lib.MODEL_AXIS)))

  # model=4 cannot honor the 6-wide cut (6 % 4 != 0): strict refuses
  # with the leaf and the reason on the error.
  target_mesh = mesh_lib.make_mesh(model_parallelism=4)
  with pytest.raises(sharding_lib.ShardingLayoutError,
                     match='Dense_0/kernel'):
    ckpt.restore_resharded(_abstract(state), registry, target_mesh)

  # Non-strict: the `_guard` degradation (replicated) is accepted —
  # values intact, placement replicated on the NEW mesh.
  restored = ckpt.restore_resharded(_abstract(state), registry,
                                    target_mesh, strict=False)
  assert restored is not None
  kernel = restored.params['Dense_0']['kernel']
  assert kernel.sharding.spec == P()
  assert kernel.sharding.mesh.shape == target_mesh.shape
  np.testing.assert_array_equal(np.asarray(kernel),
                                np.asarray(params['Dense_0']['kernel']))
  ckpt.close()


def test_resharded_opt_state_follows_param_specs(tmp_path):
  """Across topologies the optimizer moments land EXACTLY where their
  params land (the round-19 cloning contract, now exercised by the
  2→4 analogue): restore a model=2 checkpoint onto a model=4 mesh and
  every param-shaped moment leaf carries the param's sharding."""
  registry = sharding_lib.ShardingRegistry((
      (r'.*kernel$', P(None, sharding_lib.MODEL_AXIS)),
      (r'.*', P()),
  ), rule_set='layout-test')
  params = {'Dense_0': {'kernel': jnp.ones((4, 8)),   # 8 % 4 == 0
                        'bias': jnp.zeros((8,))}}
  cfg = Config(batch_size=8)
  state = learner_lib.make_train_state(params, cfg)
  save_mesh = mesh_lib.make_mesh(model_parallelism=2)
  ckpt = checkpoint_lib.Checkpointer(str(tmp_path / 'ckpt'),
                                     save_interval_secs=0,
                                     registry=registry, mesh=save_mesh)
  assert ckpt.save(state, step=1)
  ckpt.wait_until_finished()

  target_mesh = mesh_lib.make_mesh(model_parallelism=4)
  restored = ckpt.restore_resharded(_abstract(state), registry,
                                    target_mesh)
  assert restored is not None
  kernel_sh = restored.params['Dense_0']['kernel'].sharding
  assert kernel_sh.spec == P(None, sharding_lib.MODEL_AXIS)
  assert dict(kernel_sh.mesh.shape) == dict(target_mesh.shape)
  # Every param-shaped moment subtree cloned the param placements.
  pdef = jax.tree_util.tree_structure(restored.params)
  expected = jax.tree_util.tree_map(lambda x: x.sharding,
                                    restored.params)
  moment_trees = [
      sub for sub in jax.tree_util.tree_leaves(
          restored.opt_state,
          is_leaf=lambda x: jax.tree_util.tree_structure(x) == pdef
          if not isinstance(x, jax.Array) else False)
      if jax.tree_util.tree_structure(sub) == pdef]
  assert moment_trees  # the rmsprop chain carries param-shaped moments
  for sub in moment_trees:
    got = jax.tree_util.tree_map(lambda x: x.sharding, sub)
    assert (jax.tree_util.tree_leaves(got) ==
            jax.tree_util.tree_leaves(expected))
  # Counters stay replicated.
  assert restored.update_steps.sharding.spec == P()
  ckpt.close()


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_same_topology_restore_stays_byte_identical(tmp_path):
  """Regression guard for the elastic gate: when the live mesh equals
  the manifest's, the driver takes the UNCHANGED restore_latest path
  and the restored bytes equal the saved bytes exactly."""
  agent = ImpalaAgent(num_actions=A, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  cfg = Config(batch_size=8, model_parallelism=2)
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  registry = sharding_lib.from_config(cfg)
  state = train_parallel.make_sharded_train_state(params, cfg, mesh,
                                                  registry=registry)
  ckpt = checkpoint_lib.Checkpointer(str(tmp_path / 'ckpt'),
                                     save_interval_secs=0,
                                     registry=registry, mesh=mesh)
  assert ckpt.save(state, step=3)
  ckpt.wait_until_finished()

  # The driver's gate reads the manifest's mesh: same topology →
  # topology_delta None → restore_latest (no resharding detour).
  from scalable_agent_tpu.parallel import distributed
  assert ckpt.saved_mesh_shape() == {'data': 4, 'model': 2}
  assert distributed.topology_delta(ckpt.saved_mesh_shape(),
                                    mesh) is None
  delta = distributed.topology_delta(
      ckpt.saved_mesh_shape(), mesh_lib.make_mesh(model_parallelism=1))
  assert delta is not None and delta['saved_mesh'] == {'data': 4,
                                                       'model': 2}

  restored = ckpt.restore_latest(state)
  assert restored is not None
  for a, b in zip(jax.tree_util.tree_leaves(restored),
                  jax.tree_util.tree_leaves(state)):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert a.sharding == b.sharding
  ckpt.close()


def test_spec_table_digest_is_content_addressed():
  specs = {'a/kernel': "PartitionSpec(None, 'model')",
           'b/bias': 'PartitionSpec()'}
  d1 = integrity.spec_table_digest(specs)
  # Order-independent (sorted paths), content-sensitive.
  d2 = integrity.spec_table_digest(dict(reversed(list(specs.items()))))
  assert d1 == d2
  changed = dict(specs, **{'a/kernel': 'PartitionSpec()'})
  assert integrity.spec_table_digest(changed) != d1


# --- the 2D {data, model} flagship parity gate -------------------------


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_2d_mesh_deep_agent_parity_gate():
  """The flagship on a real 2D mesh: the deep ResNet + LSTM agent
  (torso='deep', the reference architecture) trains 3 steps on a
  (data=4, model=2) mesh — rule set and mesh shape declared by the
  CONFIG (sharding_rules/model_parallelism), every placement resolved
  by the registry — and must match the single-device reference at the
  established sharded-parity tolerances (loss rtol 2e-4; post-update
  params rtol 5e-4 / atol 5e-6, compounding over the 3 steps). On CPU
  the tp_compute=auto gathered fallback keeps numerics exact while
  params stay model-sharded at rest (docs/PARALLELISM.md)."""
  agent = ImpalaAgent(num_actions=A, torso='deep')
  cfg = Config(batch_size=4, unroll_length=4, num_action_repeats=1,
               total_environment_frames=10**6,
               model_parallelism=2, sharding_rules='auto')
  batches = [_fake_batch(10 + i, 5, 4) for i in range(3)]

  params = init_params(agent, jax.random.PRNGKey(0), OBS)
  params2 = init_params(agent, jax.random.PRNGKey(0), OBS)

  state1 = learner_lib.make_train_state(params, cfg)
  step1 = learner_lib.make_train_step(agent, cfg)

  mesh = mesh_lib.make_mesh(model_parallelism=2)
  registry = sharding_lib.from_config(cfg)
  assert registry.rule_set == 'megatron'
  state2d = train_parallel.make_sharded_train_state(
      params2, cfg, mesh, registry=registry)
  # The 2D mesh genuinely engaged: model-sharded params at rest.
  assert any(sharding_lib.MODEL_AXIS in str(x.sharding.spec)
             for x in jax.tree_util.tree_leaves(state2d.params))
  step2d, place = train_parallel.make_sharded_train_step(
      agent, cfg, mesh, batches[0])

  losses1, losses2d = [], []
  for batch in batches:
    state1, m1 = step1(state1, batch)
    losses1.append(float(m1['total_loss']))
    state2d, m2d = step2d(state2d, place(batch))
    losses2d.append(float(m2d['total_loss']))

  np.testing.assert_allclose(losses1, losses2d, rtol=2e-4)
  for a, b in zip(jax.tree_util.tree_leaves(state1.params),
                  jax.tree_util.tree_leaves(state2d.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-4, atol=5e-6)
  # Params are STILL model-sharded after 3 steps (the gathered path
  # re-scatters to the at-rest placements every step).
  assert any(sharding_lib.MODEL_AXIS in str(x.sharding.spec)
             for x in jax.tree_util.tree_leaves(state2d.params))

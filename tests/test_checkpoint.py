"""Checkpoint round-trip: save → restore-latest reproduces the full
TrainState (params, optimizer slots, step counter) — SURVEY §5.4.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.checkpoint import Checkpointer
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.testing import make_example_batch


@pytest.fixture(scope='module')
def setup():
  cfg = Config(batch_size=2, unroll_length=3, torso='shallow',
               total_environment_frames=10**6)
  agent = ImpalaAgent(num_actions=4, torso='shallow')
  params = init_params(agent, jax.random.PRNGKey(0),
                       {'frame': (24, 32, 3),
                        'instr_len': MAX_INSTRUCTION_LEN})
  batch = make_example_batch(cfg.unroll_length + 1, cfg.batch_size,
                             24, 32, 4, MAX_INSTRUCTION_LEN)
  return cfg, agent, params, batch


def _tree_equal(a, b):
  flat_a = jax.tree_util.tree_leaves(a)
  flat_b = jax.tree_util.tree_leaves(b)
  assert len(flat_a) == len(flat_b)
  for x, y in zip(flat_a, flat_b):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_save_restore_roundtrip(setup, tmp_path):
  cfg, agent, params, batch = setup
  # Copy: the jitted step donates its state, which aliases the fixture's
  # params — other tests in this module still need them.
  params = jax.tree_util.tree_map(jnp.copy, params)
  train_step = learner_lib.make_train_step(agent, cfg)
  state = learner_lib.make_train_state(params, cfg)
  state, _ = train_step(state, batch)
  state, _ = train_step(state, batch)

  ckpt = Checkpointer(str(tmp_path / 'ckpt'), save_interval_secs=0)
  ckpt.save(state)
  ckpt.wait_until_finished()
  assert ckpt.latest_step() == 2

  # Fresh target state (different values) → restore must overwrite all.
  params2 = init_params(agent, jax.random.PRNGKey(1),
                        {'frame': (24, 32, 3),
                         'instr_len': MAX_INSTRUCTION_LEN})
  target = learner_lib.make_train_state(params2, cfg)
  restored = ckpt.restore_latest(target)
  assert restored is not None
  _tree_equal(restored, state)
  assert int(restored.update_steps) == 2
  ckpt.close()

  # Resume: training continues from the restored state identically.
  resumed, _ = train_step(restored, batch)
  again, _ = train_step(state, batch)
  _tree_equal(resumed.params, again.params)


def test_restore_latest_params_only(setup, tmp_path):
  """Eval-path restore: params + step counter come back equal, while
  the optimizer moments are never materialized (placeholder leaves) —
  VERDICT W5."""
  cfg, agent, params, batch = setup
  params = jax.tree_util.tree_map(jnp.copy, params)
  train_step = learner_lib.make_train_step(agent, cfg)
  state = learner_lib.make_train_state(params, cfg)
  state, _ = train_step(state, batch)

  ckpt = Checkpointer(str(tmp_path / 'ckpt'), save_interval_secs=0)
  ckpt.save(state)
  ckpt.wait_until_finished()

  restored = ckpt.restore_latest_params(
      state.params, lambda p: learner_lib.make_train_state(p, cfg))
  assert restored is not None
  got_params, got_steps = restored
  _tree_equal(got_params, state.params)
  assert got_steps == 1
  ckpt.close()


def test_restore_latest_params_only_none_when_empty(setup, tmp_path):
  cfg, agent, params, _ = setup
  ckpt = Checkpointer(str(tmp_path / 'empty'), save_interval_secs=0)
  assert ckpt.restore_latest_params(
      params, lambda p: learner_lib.make_train_state(p, cfg)) is None
  ckpt.close()


def test_restore_latest_none_when_empty(setup, tmp_path):
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'empty'))
  assert ckpt.restore_latest(state) is None
  assert ckpt.latest_step() is None
  ckpt.close()


def test_maybe_save_throttles(setup, tmp_path):
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'throttle'),
                      save_interval_secs=3600)
  # First call starts the clock, doesn't save.
  assert not ckpt.maybe_save(state)
  assert not ckpt.maybe_save(state)
  assert ckpt.latest_step() is None
  ckpt.close()

  fast = Checkpointer(str(tmp_path / 'fast'), save_interval_secs=0)
  assert not fast.maybe_save(state)   # starts clock
  assert fast.maybe_save(state)       # interval (0s) elapsed
  fast.wait_until_finished()
  assert fast.latest_step() == 0
  fast.close()


def test_max_to_keep_prunes(setup, tmp_path):
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'keep'), max_to_keep=2)
  for step in (1, 2, 3):
    ckpt.save(state, step=step, force=True)
  ckpt.wait_until_finished()
  assert ckpt.latest_step() == 3
  restored = ckpt.restore_latest(state)
  assert restored is not None
  ckpt.close()


def test_save_same_step_twice_reports_skip(setup, tmp_path):
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'dup'))
  assert ckpt.save(state, step=5)
  ckpt.wait_until_finished()
  assert not ckpt.save(state, step=5)  # existing step skipped → False
  ckpt.close()


def test_should_save_and_decision_override(setup, tmp_path):
  """Multi-host contract: a host whose local clock hasn't elapsed must
  still save when handed decision=True (process 0's broadcast), and
  must skip when handed False even if its own clock elapsed."""
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'decision'),
                      save_interval_secs=10**6)
  try:
    assert not ckpt.should_save()  # first call starts the clock
    assert not ckpt.maybe_save(state)          # local clock: no
    assert ckpt.maybe_save(state, decision=True)   # broadcast: yes
    state2 = state._replace(update_steps=state.update_steps + 1)
    assert not ckpt.maybe_save(state2, decision=False)
    assert ckpt.latest_step() == 0
  finally:
    ckpt.close()


def test_structure_mismatch_names_the_flag(setup, tmp_path):
  """VERDICT r2 W7: restoring a with-instruction checkpoint into a
  without-instruction state must fail with a message that points at
  --use_instruction, not a raw Orbax tree error."""
  cfg = Config(batch_size=2, unroll_length=3, torso='shallow',
               total_environment_frames=10**6)
  obs_spec = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  with_instr = ImpalaAgent(num_actions=4, torso='shallow',
                           use_instruction=True)
  params = init_params(with_instr, jax.random.PRNGKey(0), obs_spec)
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'mismatch'))
  ckpt.save(state, step=1, force=True)
  ckpt.wait_until_finished()

  without_instr = ImpalaAgent(num_actions=4, torso='shallow',
                              use_instruction=False)
  params2 = init_params(without_instr, jax.random.PRNGKey(0), obs_spec)
  target = learner_lib.make_train_state(params2, cfg)
  with pytest.raises(Exception, match='use_instruction'):
    ckpt.restore_latest(target)
  # The eval (params-only) path gets the same guidance.
  with pytest.raises(Exception, match='use_instruction'):
    ckpt.restore_latest_params(
        params2, lambda p: learner_lib.make_train_state(p, cfg))
  ckpt.close()


def test_wrap_error_sniffs_structure_vs_corruption():
  """ADVICE r3: flag guidance only on failures that look like tree-
  structure mismatches; corrupt/partial-file failures get the
  corruption wording instead of a misleading --use_instruction hunt."""
  from scalable_agent_tpu import checkpoint as ckpt_lib

  structural = [
      ValueError('User-provided restore item and on-disk value '
                 'metadata tree structures do not match.'),
      KeyError('params/instruction/embed/kernel'),  # bare key str
      TypeError('Custom PyTree node mismatch'),
      # Newer-Orbax spelling (jax tree_util raises it before any file
      # is read).
      ValueError("Dict key mismatch; expected keys: ['a']; dict: {}"),
  ]
  for e in structural:
    with pytest.raises(ckpt_lib.CheckpointStructureError,
                       match='use_instruction'):
      ckpt_lib._wrap_structure_error(e, '/ckpts', 7)

  corrupt_cases = [
      ValueError('zarr array data truncated at offset 18238'),
      # 'missing'/'key' alone must NOT count as structural — they
      # also appear in partial-save messages like this one.
      ValueError('checkpoint incomplete: missing commit file for key'),
  ]
  for e in corrupt_cases:
    with pytest.raises(ckpt_lib.CheckpointStructureError) as exc_info:
      ckpt_lib._wrap_structure_error(e, '/ckpts', 7)
    msg = str(exc_info.value)
    assert 'use_instruction' not in msg
    assert 'corrupt' in msg and 'previous retained step' in msg


def _save_steps(ckpt, state, steps):
  for step in steps:
    assert ckpt.save(state, step=step, force=True)


def test_restore_latest_falls_back_past_truncated_newest(setup,
                                                         tmp_path):
  """Integrity ladder: files of the newest step truncated (a save
  killed mid-write) → restore_latest logs, retries the previous
  retained step, and succeeds instead of dead-ending."""
  from scalable_agent_tpu.runtime import faults as faults_lib
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'ladder'), save_interval_secs=0)
  try:
    _save_steps(ckpt, state, (1, 2))
    assert ckpt.last_good_step() == 2
    faults_lib.corrupt_checkpoint_step(str(tmp_path / 'ladder'), 2)
    restored = ckpt.restore_latest(state)
    assert restored is not None
    _tree_equal(restored.params, state.params)
    assert ckpt.restore_fallbacks >= 1
  finally:
    ckpt.close()


def test_restore_latest_falls_back_past_deleted_step_files(setup,
                                                           tmp_path):
  """Same ladder for wholesale-missing array files (partial rsync,
  eviction): the newest step still LISTS but cannot restore."""
  import os
  import shutil
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  directory = str(tmp_path / 'deleted')
  ckpt = Checkpointer(directory, save_interval_secs=0)
  try:
    _save_steps(ckpt, state, (1, 2))
    step_dir = os.path.join(directory, '2')
    assert os.path.isdir(step_dir)
    # Delete the saved ARRAY payloads, keep the step dir listing.
    for root, dirs, files in os.walk(step_dir):
      for name in dirs:
        if name == 'default':
          shutil.rmtree(os.path.join(root, name))
    restored = ckpt.restore_latest(state)
    assert restored is not None
    _tree_equal(restored.params, state.params)
  finally:
    ckpt.close()


def test_restore_raises_corruption_guidance_when_all_steps_bad(
    setup, tmp_path):
  """Exhausting the ladder keeps the corruption (not flag-hunt)
  wording — the structure-vs-corruption message split stays intact."""
  from scalable_agent_tpu import checkpoint as ckpt_lib
  from scalable_agent_tpu.runtime import faults as faults_lib
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  directory = str(tmp_path / 'allbad')
  ckpt = Checkpointer(directory, save_interval_secs=0)
  try:
    _save_steps(ckpt, state, (1, 2))
    for step in (1, 2):
      from scalable_agent_tpu.runtime.faults import (
          corrupt_checkpoint_step)
      corrupt_checkpoint_step(directory, step)
    with pytest.raises(ckpt_lib.CheckpointStructureError) as exc_info:
      ckpt.restore_latest(state)
    msg = str(exc_info.value)
    assert 'use_instruction' not in msg
    assert 'corrupt' in msg
  finally:
    ckpt.close()


def test_last_good_marker_roundtrip(setup, tmp_path):
  """LAST_GOOD distinguishes 'restorable' from merely 'newest':
  advanced only by verified saves, pruned entries invalidate it, and
  restore_last_good prefers it."""
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'marker'), max_to_keep=2,
                      save_interval_secs=0)
  try:
    assert ckpt.last_good_step() is None
    _save_steps(ckpt, state, (1,))
    assert ckpt.last_good_step() == 1
    _save_steps(ckpt, state, (2, 3))   # step 1 pruned (max_to_keep=2)
    assert ckpt.last_good_step() == 3
    restored = ckpt.restore_last_good(state)
    assert restored is not None
    _tree_equal(restored.params, state.params)
  finally:
    ckpt.close()


def test_restore_last_good_none_when_empty(setup, tmp_path):
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(params, cfg)
  ckpt = Checkpointer(str(tmp_path / 'emptygood'))
  try:
    assert ckpt.restore_last_good(state) is None
  finally:
    ckpt.close()


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_sharded_state_roundtrip(setup, tmp_path):
  """The docstring's multi-chip claim: a DP-sharded TrainState saves
  and restores onto the same mesh placements (SURVEY §5.4 → Orbax)."""
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  from scalable_agent_tpu.parallel import train_parallel
  import dataclasses
  cfg, agent, params, _ = setup
  cfg = dataclasses.replace(cfg, batch_size=8)  # 8-way data axis
  batch = make_example_batch(cfg.unroll_length + 1, cfg.batch_size,
                             24, 32, 4, MAX_INSTRUCTION_LEN)
  params = jax.tree_util.tree_map(jnp.copy, params)
  mesh = mesh_lib.make_mesh(model_parallelism=1)
  state = train_parallel.make_sharded_train_state(params, cfg, mesh)
  step, place = train_parallel.make_sharded_train_step(
      agent, cfg, mesh, batch)
  state, _ = step(state, place(batch))

  ckpt = Checkpointer(str(tmp_path / 'sharded'))
  ckpt.save(state, force=True)
  ckpt.wait_until_finished()

  params2 = init_params(agent, jax.random.PRNGKey(7),
                        {'frame': (24, 32, 3),
                         'instr_len': MAX_INSTRUCTION_LEN})
  target = train_parallel.make_sharded_train_state(params2, cfg, mesh)
  restored = ckpt.restore_latest(target)
  ckpt.close()
  assert restored is not None
  _tree_equal(restored.params, state.params)
  # Placements survive: restored leaves live on the mesh like the
  # original (and training continues from them without resharding).
  leaf = jax.tree_util.tree_leaves(restored.params)[0]
  orig = jax.tree_util.tree_leaves(state.params)[0]
  assert leaf.sharding.is_equivalent_to(orig.sharding, leaf.ndim)
  resumed, _ = step(restored, place(batch))
  assert int(resumed.update_steps) == 2


# --- Round 12: content-digest ladder (bit rot) -----------------------


def test_digest_ladder_refuses_bitrot_under_last_good(setup, tmp_path):
  """The round-12 gap: a byte flipped in a COMMITTED step — digests
  recorded, LAST_GOOD advanced — restores 'successfully' through
  orbax as garbage. The ladder must refuse it on content digests
  (counted separately as digest_fallbacks) and restore the previous
  verified step; restore_last_good must make the same call."""
  from scalable_agent_tpu.runtime import faults as faults_lib
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'rot'), save_interval_secs=0)
  try:
    _save_steps(ckpt, state, (1, 2))
    assert ckpt.last_good_step() == 2
    assert ckpt.verify_step_digests(2) is True
    faults_lib.bitrot_checkpoint_step(str(tmp_path / 'rot'), 2, seed=3)
    with pytest.raises(Exception, match='digest'):
      ckpt.verify_step_digests(2)
    restored = ckpt.restore_latest(state)
    assert restored is not None
    _tree_equal(restored.params, state.params)
    assert ckpt.digest_fallbacks == 1
    assert ckpt.restore_fallbacks >= 1
    # restore_last_good: the marker NAMES the rotted step, but the
    # digests in its own manifest refuse it — the ladder lands on 1.
    rolled = ckpt.restore_last_good(state)
    assert rolled is not None
    assert ckpt.digest_fallbacks >= 2
  finally:
    ckpt.close()


def test_digest_mismatch_classified_corruption_not_structural():
  """CheckpointCorruption's message must route down the corruption
  arm of the ladder (fallback), never the structural arm (raise with
  config-flag guidance)."""
  from scalable_agent_tpu import checkpoint as checkpoint_lib
  e = checkpoint_lib.CheckpointCorruption(
      "checkpoint step 7: content digest verification failed for "
      "'default/d/abc' (crc 0000beef differs from the recorded "
      '0000dead) — bit rot after commit; this step cannot be trusted')
  assert not checkpoint_lib._looks_structural(e)


def test_ckpt_bitrot_fault_site_fires_after_commit(setup, tmp_path):
  """The 'ckpt_bitrot' site: save() verifies, records digests,
  advances LAST_GOOD — and THEN the scheduled fault rots the step, so
  every marker calls it good and only the digest ladder can tell."""
  from scalable_agent_tpu.runtime import faults as faults_lib
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'site'), save_interval_secs=0)
  faults_lib.install(faults_lib.FaultPlan(
      [faults_lib.Fault('ckpt_bitrot', 0, 'flip')], seed=9))
  try:
    assert ckpt.save(state, step=1, force=True)
    assert ckpt.last_good_step() == 1  # the marker believed the save
    with pytest.raises(Exception, match='digest'):
      ckpt.verify_step_digests(1)
  finally:
    faults_lib.clear()
    ckpt.close()


def test_digests_disabled_skips_verification(setup, tmp_path):
  """--ckpt_digests=false: no ledger recorded, verification is a
  no-op (None), and a rotted step restores exactly as pre-round-12 —
  the knob is a real escape hatch, not a silent half-state."""
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'off'), save_interval_secs=0,
                      verify_digests=False)
  try:
    _save_steps(ckpt, state, (1,))
    assert ckpt.verify_step_digests(1) is None
    import os
    assert not any(n.startswith('DIGEST_')
                   for n in os.listdir(str(tmp_path / 'off')))
  finally:
    ckpt.close()


def test_digest_ledgers_pruned_with_steps(setup, tmp_path):
  """DIGEST_<step>.json files of pruned steps are cleaned up (a long
  run must not accumulate one file per evicted checkpoint)."""
  import os
  cfg, agent, params, _ = setup
  state = learner_lib.make_train_state(
      jax.tree_util.tree_map(jnp.copy, params), cfg)
  ckpt = Checkpointer(str(tmp_path / 'prune'), max_to_keep=2,
                      save_interval_secs=0)
  try:
    _save_steps(ckpt, state, (1, 2, 3))
    names = {n for n in os.listdir(str(tmp_path / 'prune'))
             if n.startswith('DIGEST_')}
    assert names == {'DIGEST_2.json', 'DIGEST_3.json'}
  finally:
    ckpt.close()

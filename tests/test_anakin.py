"""Anakin mode (parallel/anakin.py): jittable env cores match the host
CI envs' semantics, the fused step preserves the actor's T+1 overlap
contract, the whole on-device loop learns — and (round 16) the
`--runtime=anakin` axis runs it as a production run: checkpoint
restore, health/SLO lifecycle artifacts, and the anakin-vs-fleet
return parity gate on cue_memory.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.config import Config
from scalable_agent_tpu.parallel import anakin


def _anakin_config(**kw):
  base = dict(env_backend='bandit', batch_size=4, unroll_length=5,
              num_action_repeats=1, episode_length=4, height=24,
              width=32, torso='shallow', use_instruction=False,
              use_py_process=False, learning_rate=2e-3,
              entropy_cost=3e-3, discounting=0.0,
              total_environment_frames=10**6, seed=0)
  base.update(kw)
  return Config(**base)


def test_bandit_core_matches_host_semantics():
  """Rewards/episode shape/stats mirror envs/fake.ContextualBanditEnv
  (reward iff action == dominant channel; episode_length steps per
  context; flow-style stats: emitted info carries the running totals,
  the carried state resets at done)."""
  core = anakin.BanditCore(height=8, width=8, episode_length=3,
                           num_action_repeats=2)
  state, out0 = core.init(jax.random.PRNGKey(0), batch=4)
  assert bool(out0.done.all())  # priming output starts an episode
  frame0 = np.asarray(out0.observation[0])
  assert frame0.shape == (4, 8, 8, 3) and frame0.dtype == np.uint8
  np.testing.assert_array_equal(frame0.max(axis=(1, 2)).argmax(-1),
                                np.asarray(state.context))

  returns = np.zeros(4, np.float32)
  for t in range(1, 7):
    target = np.asarray(state.context)
    action = jnp.asarray((target + (t % 2)) % 3)  # alternate hit/miss
    prev_state = state
    state, out = core.step(state, action)
    expected_reward = (np.asarray(action) == target).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(out.reward),
                                  expected_reward)
    assert bool(np.all(np.asarray(out.done) == (t % 3 == 0)))
    returns += expected_reward
    # Emitted info carries the running totals (frames = steps x repeat).
    np.testing.assert_array_equal(np.asarray(out.info.episode_return),
                                  returns)
    assert np.all(np.asarray(out.info.episode_step) ==
                  (t - 1) % 3 * 2 + 2)
    if t % 3 == 0:
      returns[:] = 0.0  # carried stats reset at done
      assert np.all(np.asarray(state.episode_return) == 0.0)
    else:
      # Context holds within an episode.
      np.testing.assert_array_equal(np.asarray(state.context),
                                    np.asarray(prev_state.context))


def test_cue_memory_core_semantics():
  core = anakin.CueMemoryCore(height=8, width=8)
  state, out0 = core.init(jax.random.PRNGKey(1), batch=3)
  # Cue visible on the first frame only.
  frame0 = np.asarray(out0.observation[0])
  assert frame0.max() == 255
  cue = np.asarray(state.context)

  # First action: fixed-action-0 bonus, independent of the cue.
  state, out1 = core.step(state, jnp.array([0, 1, 2]))
  np.testing.assert_array_equal(
      np.asarray(out1.reward), [2.0, 0.0, 0.0])
  assert not np.asarray(out1.done).any()
  assert np.asarray(out1.observation[0]).max() == 0  # blank frame

  # Second action: reward iff it matches the ORIGINAL cue; episode ends.
  action = jnp.asarray(cue)
  state, out2 = core.step(state, action)
  np.testing.assert_array_equal(np.asarray(out2.reward),
                                [1.0, 1.0, 1.0])
  assert np.asarray(out2.done).all()


def test_overlap_contract_between_fused_steps():
  """Timestep 0 of each unroll == last timestep of the previous one
  (the reference's load-bearing T+1 overlap — experiment.py ≈L285),
  and the batch is [T+1, B] time-major."""
  cfg = _anakin_config(batch_size=2, unroll_length=3)
  core = anakin.BanditCore(cfg.height, cfg.width, cfg.episode_length)
  from scalable_agent_tpu import driver
  agent = driver.build_agent(cfg, core.num_actions)
  step = anakin.make_anakin_step(agent, core, cfg, return_batch=True)
  carry = anakin.init_carry(agent, core, cfg, jax.random.PRNGKey(0))
  carry, m1 = step(carry)
  carry, m2 = step(carry)
  b1, b2 = jax.device_get((m1['batch'], m2['batch']))
  t1 = cfg.unroll_length + 1
  assert b1.env_outputs.reward.shape == (t1, cfg.batch_size)
  assert b1.agent_outputs.policy_logits.shape == (
      t1, cfg.batch_size, core.num_actions)
  for leaf1, leaf2 in zip(
      jax.tree_util.tree_leaves((b1.env_outputs, b1.agent_outputs)),
      jax.tree_util.tree_leaves((b2.env_outputs, b2.agent_outputs))):
    np.testing.assert_array_equal(leaf1[-1], leaf2[0])


def test_anakin_learns_bandit():
  """The fully fused on-device loop drives the bandit to near-optimal
  mean reward (random = 1/3, optimal = 1.0)."""
  carry, history, _ = anakin.run(_anakin_config(batch_size=8), 150)
  rewards = [float(h['mean_reward']) for h in history]
  assert all(np.isfinite(h['total_loss']) for h in history)
  assert np.mean(rewards[-10:]) > 0.8, rewards[-10:]
  assert int(carry.train_state.update_steps) == 150


def test_anakin_shards_over_the_mesh():
  """Anakin scale-out (PARALLELISM.md): env batch sharded over the
  8-device data axis, params replicated, same fused step — the
  gradient psum is inserted by jit from the placements."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import mesh as mesh_lib

  assert len(jax.devices()) == 8
  mesh = mesh_lib.make_mesh()
  cfg = _anakin_config(batch_size=16, unroll_length=3)
  core = anakin.BanditCore(cfg.height, cfg.width, cfg.episode_length)
  agent = driver.build_agent(cfg, core.num_actions)
  step = anakin.make_anakin_step(agent, core, cfg)
  carry = anakin.init_carry(agent, core, cfg, jax.random.PRNGKey(0),
                            mesh=mesh)
  # Env state genuinely spans the mesh's data axis.
  assert len(carry.env_state.context.sharding.device_set) == 8
  for _ in range(3):
    carry, metrics = step(carry)
  assert np.isfinite(float(metrics['total_loss']))
  assert int(carry.train_state.update_steps) == 3
  # The carry stays sharded across fused steps (no silent gather).
  assert len(carry.env_state.context.sharding.device_set) == 8

  import pytest
  with pytest.raises(ValueError, match='divisible'):
    anakin.init_carry(agent, core, _anakin_config(batch_size=6),
                      jax.random.PRNGKey(0), mesh=mesh)


def test_anakin_train_artifacts_and_resume(tmp_path):
  """The operator-facing loop (experiment.py --mode=train
  --runtime=anakin) produces the standard run artifacts: config dump,
  JSONL summaries, a checkpoint that a second invocation resumes
  from, and total_environment_frames termination."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import apply_overrides
  cfg = _anakin_config(
      logdir=str(tmp_path), runtime='anakin', summary_secs=0,
      checkpoint_secs=0,
      total_environment_frames=10 * 4 * 5)  # exactly 10 steps (B=4,T=5)
  run = driver.train(cfg)
  assert int(run.state.update_steps) == 10

  events = [json.loads(line) for line in
            open(str(tmp_path / 'summaries.jsonl'))]
  tags = {e['tag'] for e in events}
  assert {'total_loss', 'mean_reward',
          'env_frames_per_sec'} <= tags
  assert json.load(open(str(tmp_path / 'config.json')))[
      'env_backend'] == 'bandit'

  # Resume: frames target already met -> restores and stops at 10.
  run2 = driver.train(cfg)
  assert int(run2.state.update_steps) == 10
  # And a raised target continues from the checkpoint, not from 0.
  run3 = driver.train(
      apply_overrides(cfg, total_environment_frames=12 * 4 * 5))
  assert int(run3.state.update_steps) == 12
  # A structure mismatch on resume is refused, and the refusal leaves
  # the ladder as it was (no tail save of a fresh state over it).
  import glob
  from scalable_agent_tpu.checkpoint import CheckpointStructureError
  before = sorted(glob.glob(str(tmp_path / 'checkpoints' / '*')))
  with pytest.raises(CheckpointStructureError):
    driver.train(apply_overrides(cfg, use_instruction=True))
  assert sorted(glob.glob(str(tmp_path / 'checkpoints' / '*'))) == before


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_anakin_train_restore_mismatch_does_not_overwrite(tmp_path):
  """A structure-mismatch on resume must raise (with the flag
  guidance), not tail-save a fresh incompatible state into the logdir."""
  import glob
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.checkpoint import CheckpointStructureError
  from scalable_agent_tpu.config import apply_overrides
  cfg = _anakin_config(logdir=str(tmp_path), runtime='anakin',
                       checkpoint_secs=0,
                       total_environment_frames=2 * 4 * 5)
  driver.train(cfg)
  before = sorted(glob.glob(str(tmp_path / 'checkpoints' / '*')))
  with pytest.raises(CheckpointStructureError):
    driver.train(apply_overrides(cfg, use_instruction=True))
  assert sorted(glob.glob(str(tmp_path / 'checkpoints' / '*'))) == before


def test_run_rejects_host_only_backends_and_zero_steps():
  import pytest
  with pytest.raises(ValueError, match='jittable'):
    anakin.run(_anakin_config(env_backend='dmlab'), 1)
  with pytest.raises(ValueError, match='num_steps'):
    anakin.run(_anakin_config(), 0)
  # A core that cannot honor the requested head width raises (the
  # host CueMemoryEnv refuses the same way) ...
  with pytest.raises(ValueError, match='num_actions'):
    anakin.run(_anakin_config(env_backend='cue_memory',
                              num_actions=5), 1)
  # ... while bandit accepts wider heads exactly like its host env
  # (the hybrid filler runs it under the MAIN task's action space).
  core = anakin.make_env_core(_anakin_config(), num_actions=7)
  assert core.num_actions == 7
  state, out = core.init(jax.random.PRNGKey(0), batch=4)
  # The rewarded channel stays 0..2 regardless of head width (the
  # host env's randint(num_actions) % 3 draw, mirrored).
  assert int(np.asarray(state.context).max()) <= 2
  assert np.asarray(out.observation[0]).shape == (4, 24, 32, 3)


# --- Round 16: the pure-JAX env family (envs/jittable.py). ---


def test_jittable_registry_matches_config_backends():
  """config.JITTABLE_BACKENDS is the literal mirror of ENV_CORES
  (config.py cannot import jax-importing modules) — and every core is
  also host-registered, the dual registration the runtime-axis parity
  gate rides on."""
  from scalable_agent_tpu.config import JITTABLE_BACKENDS
  from scalable_agent_tpu.envs import jittable
  assert set(JITTABLE_BACKENDS) == set(anakin.ENV_CORES)
  assert set(jittable.HOST_ENVS) == set(jittable.JITTABLE_CORES)


def test_gridworld_core_semantics():
  """Movement clamps at borders, the goal pays +1 and ends the
  episode, the step cap ends it unpaid, flow-style stats reset at
  done, and the observation renders agent/goal cells on their own
  channels."""
  from scalable_agent_tpu.envs.jittable import GridworldCore
  core = GridworldCore(height=16, width=16, episode_length=3,
                       num_action_repeats=2, grid_size=3)
  state, out0 = core.init(jax.random.PRNGKey(0), batch=4)
  assert bool(out0.done.all())  # priming output starts an episode
  frame0 = np.asarray(out0.observation[0])
  assert frame0.shape == (4, 16, 16, 3) and frame0.dtype == np.uint8
  assert (frame0[..., 0] == 255).any()  # agent plane rendered
  assert (frame0[..., 1] == 255).any()  # goal plane rendered
  np.testing.assert_array_equal(np.asarray(state.agent_yx), 0)

  # Moving up/left from (0, 0) clamps in place.
  state1, out1 = core.step(state, jnp.array([0, 2, 0, 2]))
  at_goal = np.all(np.asarray(state.goal_yx) == 0, axis=-1)
  np.testing.assert_array_equal(np.asarray(out1.reward),
                                at_goal.astype(np.float32))
  # Non-terminal envs keep the clamped position.
  still = ~np.asarray(out1.done)
  if still.any():
    np.testing.assert_array_equal(
        np.asarray(state1.agent_yx)[still], 0)
  # Frames count action repeats; emitted stats carry running totals.
  np.testing.assert_array_equal(np.asarray(out1.info.episode_step), 2)

  # Walk right to the goal deterministically: batch=1, goal pinned by
  # re-sampling until it lands on row 0 (seeded draw is deterministic).
  core1 = GridworldCore(height=8, width=8, episode_length=8,
                        grid_size=3)
  s, _ = core1.init(jax.random.PRNGKey(3), batch=1)
  gy, gx = (int(np.asarray(s.goal_yx)[0, 0]),
            int(np.asarray(s.goal_yx)[0, 1]))
  total = 0.0
  for _ in range(gy):
    s, out = core1.step(s, jnp.array([1]))  # down
    total += float(np.asarray(out.reward)[0])
  for _ in range(gx):
    s, out = core1.step(s, jnp.array([3]))  # right
    total += float(np.asarray(out.reward)[0])
  assert total == 1.0
  assert bool(np.asarray(out.done)[0])
  # Auto-reset: agent back at origin, stats cleared in the carry.
  np.testing.assert_array_equal(np.asarray(s.agent_yx), 0)
  assert float(np.asarray(s.episode_return)[0]) == 0.0


def test_gridworld_episode_cap_ends_unpaid():
  from scalable_agent_tpu.envs.jittable import GridworldCore
  core = GridworldCore(height=8, width=8, episode_length=2,
                       grid_size=4)
  s, _ = core.init(jax.random.PRNGKey(1), batch=2)
  # Bounce up against the border twice: no goal, cap fires.
  s, out = core.step(s, jnp.array([0, 0]))
  s, out = core.step(s, jnp.array([0, 0]))
  assert bool(np.asarray(out.done).all())
  np.testing.assert_array_equal(np.asarray(out.reward), 0.0)


def test_procgen_levels_deterministic_and_walls_block():
  """The procgen-style generator: the wall layout is a pure function
  of the level id (same id -> identical walls across separate core
  instances), start/goal corners are always open, and a wall vetoes
  the move (agent stays)."""
  from scalable_agent_tpu.envs.jittable import ProcgenCore
  core_a = ProcgenCore(height=10, width=10, grid_size=4,
                       num_levels=6, wall_density=0.9)
  core_b = ProcgenCore(height=10, width=10, grid_size=4,
                       num_levels=6, wall_density=0.9)
  ids = jnp.arange(6)
  walls_a = np.asarray(core_a._walls(ids))
  walls_b = np.asarray(core_b._walls(ids))
  np.testing.assert_array_equal(walls_a, walls_b)
  assert not walls_a[:, 0, 0].any()      # start open
  assert not walls_a[:, -1, -1].any()    # goal open
  # At density 0.9 SOME interior wall must exist over 6 levels.
  assert walls_a.any()

  # A blocked move keeps the agent in place: find a level whose (0,1)
  # or (1,0) neighbor is a wall and step into it.
  state, _ = core_a.init(jax.random.PRNGKey(0), batch=6)
  walls = np.asarray(core_a._walls(state.level_id))
  right_blocked = walls[:, 0, 1]
  s1, _ = core_a.step(state, jnp.full((6,), 3))  # all step right
  moved = np.asarray(s1.agent_yx)[:, 1] == 1
  stayed = np.asarray(s1.agent_yx)[:, 1] == 0
  # done (goal/cap) resets to origin too, but with 4x4 grids and one
  # step neither can fire — so blocked <-> stayed exactly.
  np.testing.assert_array_equal(moved, ~right_blocked)
  np.testing.assert_array_equal(stayed, right_blocked)


@pytest.mark.parametrize('height,width,grid', [
    (64, 64, 5), (72, 96, 5), (10, 10, 4), (24, 32, 7)])
def test_procgen_frame_matches_a_numpy_oracle(height, width, grid):
  """The whole frame against a per-pixel NumPy loop that shares no
  code with the painter: plane 2 is 255 where the pixel's cell
  (h*G//H, w*G//W) is a wall of the env's level, planes 0 and 1 are
  the agent's and the goal's cells. H and W need not divide by G."""
  from scalable_agent_tpu.envs.jittable import ProcgenCore
  batch = 6
  core = ProcgenCore(height=height, width=width, grid_size=grid,
                     num_levels=6, wall_density=0.5)
  state, _ = core.init(jax.random.PRNGKey(3), batch)
  # Agents off the origin, a different cell each env.
  cells = np.arange(batch) * 3 % (grid * grid)
  state = state._replace(agent_yx=jnp.asarray(
      np.stack([cells // grid, cells % grid], -1), jnp.int32))
  frame = np.asarray(core._observation(state)[0])
  assert frame.dtype == np.uint8
  assert frame.shape == (batch, height, width, 3)

  walls = np.asarray(core._walls(state.level_id))
  agent = np.asarray(state.agent_yx)
  goal = np.asarray(state.goal_yx)
  assert walls.any() and not walls.all()
  want = np.zeros((batch, height, width, 3), np.uint8)
  for b in range(batch):
    for h in range(height):
      for w in range(width):
        cell = ((h * grid) // height, (w * grid) // width)
        want[b, h, w, 0] = 255 * (cell == tuple(agent[b]))
        want[b, h, w, 1] = 255 * (cell == tuple(goal[b]))
        want[b, h, w, 2] = 255 * walls[b, cell[0], cell[1]]
  np.testing.assert_array_equal(frame, want)


def _gather_result_sizes(hlo_text):
  """Element counts of every `gather` instruction's result in an
  optimised HLO module's text."""
  return [int(np.prod([int(d) for d in dims.split(',') if d] or [1]))
          for dims in re.findall(
              r'= \w+\[([\d,]*)\]\S* gather\(', hlo_text)]


# A seeded init + 5 steps of ProcgenCore(5x6, G=3, 5 levels, density
# 0.3, episode 4) at B=4, taken from the tree BEFORE the wall plane
# stopped being gathered (PR 31's parent, 82f8bf7): env 0 reaches the
# goal at step 4, env 1 walks into walls and stays, the others time
# out; every env draws a fresh level after it. Frames are six
# [4, 5, 6, 3] uint8 frames of 0/255, bit-packed.
_PROCGEN_FIXTURE_ACTIONS = [[3, 1, 0, 1], [3, 0, 3, 1], [1, 1, 2, 1],
                            [1, 3, 3, 1], [2, 1, 3, 2]]
_PROCGEN_FIXTURE_REWARD = [[0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                           [1, 0, 0, 0], [0, 0, 0, 0]]
_PROCGEN_FIXTURE_DONE = [[0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                         [1, 1, 1, 1], [0, 0, 0, 0]]
_PROCGEN_FIXTURE_LEVELS = [[3, 1, 2, 3], [3, 1, 2, 3], [3, 1, 2, 3],
                           [3, 1, 2, 3], [0, 3, 3, 2], [0, 3, 3, 2]]
_PROCGEN_FIXTURE_FRAMES = (
    '9000240000090002400004a4249909249000240000252900264009000000'
    '0002494a40009000002400090000120240009000090002400004a4249909'
    '249000240000252900264009000000000249480000000024240909000012'
    '0009000240090002400004a4249909249000240000252024240909000000'
    '0002494800000000002400090240120000000000099002640004a4249909'
    '249000240000252900264009000000000249480000000000240009024012'
    '9000240002490092400004a4000900000240009000012900024000009000'
    '2400004a4009900240000000009252900024000249009240000480000000'
    '0242409090000120240009000090002400004a4009900240000000009252')


def test_procgen_paints_walls_without_a_per_pixel_gather():
  """The wall plane is an expansion of the [B, G, G] layout along
  static pixel->cell maps, not a lookup at each of B*H*W pixels: the
  compiled `_observation` and `step` hold no gather of that size
  (`_blocked`'s [B] lookup of one cell each stays). And the expansion
  is the same work: a seeded trajectory equals the parent's, frame
  for frame."""
  from scalable_agent_tpu.envs.jittable import ProcgenCore
  batch, height, width = 8, 24, 32
  core = ProcgenCore(height=height, width=width, grid_size=5)
  state, _ = core.init(jax.random.PRNGKey(0), batch)
  action = jnp.zeros((batch,), jnp.int32)
  for fn, args in ((core._observation, (state,)),
                   (core.step, (state, action))):
    text = jax.jit(fn).lower(*args).compile().as_text()
    sizes = _gather_result_sizes(text)
    assert not [n for n in sizes if n >= batch * height * width], sizes
  # The detector sees a per-pixel gather when there is one.
  rows = jnp.asarray((np.arange(height) * 5) // height)
  gathered = jax.jit(lambda w: w[:, rows]).lower(
      jnp.zeros((batch, 5, width), bool)).compile().as_text()
  assert max(_gather_result_sizes(gathered)) >= batch * height

  core = ProcgenCore(height=5, width=6, grid_size=3, episode_length=4,
                     num_levels=5, wall_density=0.3)
  state, out = core.init(jax.random.PRNGKey(20), 4)
  frames, levels = [out.observation[0]], [state.level_id]
  rewards, dones = [], []
  step = jax.jit(core.step)
  for action in _PROCGEN_FIXTURE_ACTIONS:
    state, out = step(state, jnp.asarray(action, jnp.int32))
    frames.append(out.observation[0])
    levels.append(state.level_id)
    rewards.append(out.reward)
    dones.append(out.done)
  frames = np.stack(frames)
  assert frames.dtype == np.uint8 and frames.shape == (6, 4, 5, 6, 3)
  want = np.unpackbits(np.frombuffer(
      bytes.fromhex(_PROCGEN_FIXTURE_FRAMES), np.uint8))
  np.testing.assert_array_equal(frames,
                                255 * want.reshape(frames.shape))
  np.testing.assert_array_equal(np.stack(rewards),
                                np.asarray(_PROCGEN_FIXTURE_REWARD,
                                           np.float32))
  np.testing.assert_array_equal(np.stack(dones),
                                np.asarray(_PROCGEN_FIXTURE_DONE, bool))
  np.testing.assert_array_equal(np.stack(levels),
                                _PROCGEN_FIXTURE_LEVELS)


def test_jittable_host_envs_run_the_same_cores():
  """The fleet-runtime half of the dual registration: the host
  wrappers speak the envs/base protocol (scalar reward/done, uint8
  frame, auto-reset inside step) over the SAME core classes."""
  from scalable_agent_tpu.envs import jittable
  for name, env_cls in jittable.HOST_ENVS.items():
    env = env_cls(height=12, width=12, num_actions=4,
                  episode_length=3, seed=7, level_name=name)
    frame, instr = env.initial()
    assert frame.shape == (12, 12, 3) and frame.dtype == np.uint8
    assert instr.shape[0] > 0 and instr.dtype == np.int32
    done_seen = False
    for i in range(8):
      reward, done, (frame, instr) = env.step(i % 4)
      assert isinstance(reward, np.float32)
      assert frame.shape == (12, 12, 3)
      done_seen = done_seen or bool(done)
    assert done_seen  # the 3-step cap must have fired at least once
    env.close()


def test_factory_builds_jittable_backends():
  from scalable_agent_tpu.envs import factory
  for backend in ('gridworld', 'procgen'):
    cfg = Config(env_backend=backend, height=16, width=16,
                 episode_length=4)
    spec = factory.make_env_spec(cfg, backend, seed=3)
    assert spec.num_actions == 4
    env, process = factory.build_environment(spec,
                                             use_py_process=False)
    assert process is None
    frame, _ = env.initial()
    assert frame.shape == (16, 16, 3)
    reward, done, _ = env.step(1)
    assert reward in (np.float32(0.0), np.float32(1.0))
    env.close()


@pytest.mark.slow
def test_anakin_learns_gridworld():
  """The fused loop learns the gridworld family too: mean reward over
  the last windows beats the first windows decisively (sparse +1 at
  the goal; random walk on a 3x3 grid with an 8-step cap collects
  some reward, a learned policy much more)."""
  cfg = _anakin_config(env_backend='gridworld', batch_size=16,
                       unroll_length=8, episode_length=8,
                       discounting=0.9, entropy_cost=0.01,
                       learning_rate=3e-3)
  _, history, _ = anakin.run(cfg, 250)
  rewards = [float(h['mean_reward']) for h in history]
  early = float(np.mean(rewards[:25]))
  late = float(np.mean(rewards[-25:]))
  assert late > early + 0.05, (early, late)


# --- Round 16: the --runtime=anakin production loop
# (driver.train_anakin). ---


def _runtime_config(tmp_path, **kw):
  base = dict(logdir=str(tmp_path), runtime='anakin',
              env_backend='cue_memory', batch_size=4, unroll_length=5,
              num_action_repeats=1, height=24, width=32,
              torso='shallow', use_instruction=False,
              use_py_process=False, learning_rate=2e-3,
              summary_secs=0, checkpoint_secs=0,
              total_environment_frames=8 * 4 * 5, seed=3)
  base.update(kw)
  return Config(**base)


@pytest.mark.slow
def test_runtime_anakin_full_lifecycle(tmp_path):
  """--runtime=anakin through driver.train: the fused loop runs as a
  PRODUCTION run — checkpoint restore, green SLO verdict, summaries +
  incidents JSONL, registry gauges unwound at exit.

  Slow-marked (the heaviest anakin drill, ~20 s): the ci.sh anakin
  lane runs the whole file unfiltered, so CI still exercises it."""
  from scalable_agent_tpu import driver, slo, telemetry
  cfg = _runtime_config(tmp_path)
  run = driver.train(cfg)  # dispatches on config.runtime
  assert run.frames == 8 * 4 * 5
  assert run.fleet is None and run.prefetcher is None

  # Lifecycle artifacts: the same contract the fleet runtime ships.
  verdict = slo.read_verdict(str(tmp_path))
  assert verdict is not None and verdict['pass'], verdict
  assert verdict['objectives']  # judged by the real default set
  assert os.path.exists(str(tmp_path / 'incidents.jsonl'))
  events = [json.loads(line)
            for line in open(str(tmp_path / 'summaries.jsonl'))]
  tags = {e['tag'] for e in events}
  assert {'total_loss', 'mean_reward', 'env_frames_per_sec',
          'learning_rate'} <= tags
  assert json.load(open(str(tmp_path / 'config.json')))[
      'runtime'] == 'anakin'
  # The loop gauges were unregistered at exit (a finished run must
  # not stay registry-pinned).
  snap = telemetry.registry().snapshot()
  assert 'driver/env_plane_utilization' not in snap

  # Restore: target already met -> resumes and stops immediately; a
  # raised target continues FROM the checkpoint.
  run2 = driver.train(cfg)
  assert run2.frames == 8 * 4 * 5
  from scalable_agent_tpu.config import apply_overrides
  run3 = driver.train(apply_overrides(
      cfg, total_environment_frames=10 * 4 * 5))
  assert run3.frames == 10 * 4 * 5


def test_runtime_anakin_rejects_bad_configs(tmp_path):
  from scalable_agent_tpu import driver
  with pytest.raises(ValueError, match='jittable'):
    driver.train(_runtime_config(tmp_path, env_backend='dmlab'))
  with pytest.raises(ValueError, match='data-parallel'):
    driver.train(_runtime_config(tmp_path, model_parallelism=2))
  with pytest.raises(ValueError, match='fleet_factory'):
    driver.train(_runtime_config(tmp_path), fleet_factory=object())
  with pytest.raises(ValueError, match='runtime'):
    driver.train(_runtime_config(tmp_path, runtime='nope'))


@pytest.mark.slow
def test_runtime_parity_cue_memory(tmp_path):
  """The runtime-axis parity gate: the SAME cue_memory task trained
  through BOTH runtimes reaches comparable final returns — both must
  clear the 2.6 memory bar (memory policy 3.0, best memoryless 2.33,
  relay 5/3; see CueMemoryEnv), so both runtimes demonstrably train
  the recurrent carry, not just the reactive head."""
  from scalable_agent_tpu import driver

  # Anakin side: fused loop; mean_reward is per STEP (2-step episodes
  # -> per-episode return = 2 * mean step reward).
  anakin_cfg = Config(
      logdir=str(tmp_path / 'anakin'), runtime='anakin',
      env_backend='cue_memory', batch_size=8, unroll_length=16,
      num_action_repeats=1, height=24, width=32, torso='shallow',
      use_instruction=False, use_py_process=False,
      learning_rate=3e-3, entropy_cost=0.01, discounting=0.9,
      summary_secs=0, checkpoint_secs=10**6,
      total_environment_frames=10**9, seed=5)
  run = driver.train(anakin_cfg, max_steps=220)
  events = [json.loads(line) for line in
            open(str(tmp_path / 'anakin' / 'summaries.jsonl'))]
  step_rewards = [e['value'] for e in events
                  if e['tag'] == 'mean_reward']
  anakin_return = 2.0 * float(np.mean(step_rewards[-20:]))
  assert anakin_return > 2.6, anakin_return

  # Fleet side: the full pipeline (actors -> inference -> buffer ->
  # learner) on the same task/hyperparameters.
  fleet_cfg = Config(
      logdir=str(tmp_path / 'fleet'), runtime='fleet',
      env_backend='cue_memory', level_name='cue_memory',
      num_actors=4, batch_size=4,
      unroll_length=16, num_action_repeats=1, height=24, width=32,
      torso='shallow', use_instruction=False, use_py_process=False,
      learning_rate=3e-3, entropy_cost=0.01, discounting=0.9,
      inference_timeout_ms=5, summary_secs=0, checkpoint_secs=10**6,
      total_environment_frames=10**9, seed=5)
  driver.train(fleet_cfg, max_steps=200, stall_timeout_secs=120)
  events = [json.loads(line) for line in
            open(str(tmp_path / 'fleet' / 'summaries.jsonl'))]
  returns = [e['value'] for e in events
             if e['tag'] == 'cue_memory/episode_return']
  assert len(returns) > 30, len(returns)
  fleet_return = float(np.mean(returns[-30:]))
  assert fleet_return > 2.6, fleet_return
  # Comparable: both runtimes land in the memory-policy band
  # [2.6, 3.0], so their gap is bounded by construction.
  assert abs(fleet_return - anakin_return) < 0.4, (
      fleet_return, anakin_return)

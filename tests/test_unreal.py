"""UNREAL pixel-control tests: pseudo-rewards against hand-computed
cell deltas, the n-step Q recursion against an explicit python loop,
and the learner integration (aux loss trains, gradients reach the
torso through the aux head).

Pixel control is a TPU-build extension (SURVEY §2.12 — planned, not in
the reference); ground truth is Jaderberg et al. 2017 §3.1.
"""

import pytest

import numpy as np

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu import unreal
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.testing import make_example_batch


def test_pixel_control_rewards_hand_computed():
  # 2 frames, 1 env, 8x8, cell 4 → 2x2 cells.
  frames = np.zeros((2, 1, 8, 8, 3), np.uint8)
  frames[1, 0, :4, :4] = 255        # top-left cell fully changes
  frames[1, 0, 4:, :4, 0] = 51      # bottom-left: one channel, 51/255
  r = np.asarray(unreal.pixel_control_rewards(jnp.asarray(frames), 4))
  assert r.shape == (1, 1, 2, 2)
  np.testing.assert_allclose(r[0, 0, 0, 0], 1.0, rtol=1e-6)
  np.testing.assert_allclose(r[0, 0, 1, 0], (51 / 255.0) / 3, rtol=1e-5)
  np.testing.assert_allclose(r[0, 0, 0, 1], 0.0)
  np.testing.assert_allclose(r[0, 0, 1, 1], 0.0)


def test_pixel_control_loss_matches_python_recursion():
  rng = np.random.RandomState(0)
  t, b, hc, wc, a = 5, 2, 3, 3, 4
  q = rng.randn(t + 1, b, hc, wc, a).astype(np.float32)
  actions = rng.randint(0, a, (t, b)).astype(np.int32)
  rewards = rng.rand(t, b, hc, wc).astype(np.float32)
  done = np.zeros((t, b), bool)
  done[2, 1] = True  # cut the recursion mid-sequence for env 1
  gamma = 0.9

  # Explicit per-(t, b) python ground truth.
  targets = np.zeros((t, b, hc, wc), np.float32)
  for bi in range(b):
    acc = q[-1, bi].max(axis=-1)
    for ti in reversed(range(t)):
      if done[ti, bi]:
        acc = np.zeros_like(acc)
        r = np.zeros_like(rewards[ti, bi])
      else:
        r = rewards[ti, bi]
      acc = r + gamma * acc
      targets[ti, bi] = acc
  expected = 0.0
  for ti in range(t):
    for bi in range(b):
      q_taken = q[ti, bi, :, :, actions[ti, bi]]
      expected += 0.5 * np.square(targets[ti, bi] - q_taken).sum()
  expected /= t * b

  loss = float(unreal.pixel_control_loss(
      jnp.asarray(q), jnp.asarray(actions), jnp.asarray(rewards),
      jnp.asarray(done), discount=gamma))
  np.testing.assert_allclose(loss, expected, rtol=1e-5)


def test_head_shapes_and_sow():
  a = 4
  agent = ImpalaAgent(num_actions=a, torso='shallow',
                      use_pixel_control=True, use_instruction=False)
  obs = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  assert 'pixel_control' in params['params']
  batch = make_example_batch(3, 2, 24, 32, a, MAX_INSTRUCTION_LEN)
  ((out, _), mutables) = agent.apply(
      params, batch.agent_outputs.action, batch.env_outputs,
      batch.agent_state, compute_pixel_control=True,
      mutable=['intermediates'])
  pc_q = mutables['intermediates']['pixel_control_q'][0]
  assert pc_q.shape == (3, 2, 6, 8, a)
  # Actor path: no intermediates computed, same params work.
  out2, _ = agent.apply(params, batch.agent_outputs.action,
                        batch.env_outputs, batch.agent_state)
  assert out2.policy_logits.shape == out.policy_logits.shape


def test_learner_with_pixel_control_trains():
  a, h, w = 4, 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
               total_environment_frames=10**6, torso='shallow',
               pixel_control_cost=0.01)
  agent = ImpalaAgent(num_actions=a, torso='shallow',
                      use_pixel_control=True)
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  state = learner_lib.make_train_state(params, cfg)
  step = learner_lib.make_train_step(agent, cfg)
  batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.1)
  # Snapshot BEFORE the step: train_step donates the state, deleting
  # the original param buffers.
  before = np.asarray(
      params['params']['pixel_control']['pc_fc']['kernel']).copy()
  state, metrics = step(state, batch)
  assert np.isfinite(float(metrics['total_loss']))
  assert float(metrics['pixel_control_loss']) > 0.0
  # The aux head's params must have received gradient.
  after = state.params['params']['pixel_control']['pc_fc']['kernel']
  assert not np.allclose(before, np.asarray(after))


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_head_odd_cell_grid():
  """84x84 Atari with cell 4 → 21x21 cells (odd): the deconv stack
  rounds up and crops rather than crashing."""
  a = 4
  agent = ImpalaAgent(num_actions=a, torso='shallow',
                      use_pixel_control=True, use_instruction=False)
  obs = {'frame': (84, 84, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  batch = make_example_batch(3, 1, 84, 84, a, MAX_INSTRUCTION_LEN)
  ((_, _), mutables) = agent.apply(
      params, batch.agent_outputs.action, batch.env_outputs,
      batch.agent_state, compute_pixel_control=True,
      mutable=['intermediates'])
  assert mutables['intermediates']['pixel_control_q'][0].shape == (
      3, 1, 21, 21, a)


def test_rewards_indivisible_frame_raises():
  import pytest
  frames = jnp.zeros((2, 1, 10, 8, 3), jnp.uint8)
  with pytest.raises(ValueError, match='not divisible'):
    unreal.pixel_control_rewards(frames, 4)


# --- Round-6 fast-path parity gates (docs/PERF.md itemization). ---


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_integer_rewards_parity_with_f32_reference():
  """The integer-domain pseudo-rewards (uint8 |Δ| + int32 cell sums)
  must match the f32 reference form on random uint8 frames — including
  ODD cell grids (84x84/4 → 21x21) — and match a float64 NumPy ground
  truth to float32 rounding (the integer cell sum is exact; the single
  f32 scale is the only rounding step)."""
  rng = np.random.RandomState(7)
  for (h, w, c, cell) in [(72, 96, 3, 4), (84, 84, 3, 4), (8, 8, 1, 2),
                          (12, 20, 3, 2), (24, 32, 3, 8)]:
    frames = rng.randint(0, 256, (4, 2, h, w, c)).astype(np.uint8)
    jf = jnp.asarray(frames)
    r_int = np.asarray(
        unreal.pixel_control_rewards(jf, cell, integer_path=True))
    r_f32 = np.asarray(
        unreal.pixel_control_rewards(jf, cell, integer_path=False))
    assert r_int.shape == (3, 2, h // cell, w // cell)
    # Float64 ground truth: the exact value both forms approximate.
    f64 = frames.astype(np.float64) / 255.0
    diff = np.abs(f64[1:] - f64[:-1]).reshape(
        3, 2, h // cell, cell, w // cell, cell, c)
    truth = diff.mean(axis=(3, 5, 6))
    np.testing.assert_allclose(r_int, truth, rtol=2e-7, atol=1e-9)
    np.testing.assert_allclose(r_int, r_f32, rtol=1e-5, atol=1e-7)


def test_integer_rewards_auto_and_forced_paths():
  import pytest
  u8 = jnp.zeros((2, 1, 8, 8, 3), jnp.uint8)
  f32 = jnp.zeros((2, 1, 8, 8, 3), jnp.float32)
  # Auto: uint8 → integer path; float → f32 path. Both must run.
  assert unreal.pixel_control_rewards(u8, 4).dtype == jnp.float32
  assert unreal.pixel_control_rewards(f32, 4).dtype == jnp.float32
  # Forcing the integer path on float frames is a usage error.
  with pytest.raises(ValueError, match='uint8'):
    unreal.pixel_control_rewards(f32, 4, integer_path=True)


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_head_impl_golden_parity_fwd_and_grad():
  """`d2s` and `deconv` share ONE param tree (same names/shapes/init)
  and must produce the same Q-map AND the same gradients through it —
  the golden gate that lets the implementations swap freely on a
  checkpoint (config.pixel_control_head_impl)."""
  rng = np.random.RandomState(3)
  for (hc, wc) in [(18, 24), (21, 21), (6, 8)]:  # even + odd grids
    x = jnp.asarray(rng.randn(7, 64), jnp.float32)
    heads = {
        impl: unreal.PixelControlHead(5, (hc, wc), head_impl=impl)
        for impl in unreal.HEAD_IMPLS}
    params = heads['deconv'].init(jax.random.PRNGKey(0), x)
    params_d2s = heads['d2s'].init(jax.random.PRNGKey(0), x)
    # Identical param STRUCTURE (names + shapes) — checkpoint-
    # interchangeable by construction.
    assert (jax.tree_util.tree_structure(params) ==
            jax.tree_util.tree_structure(params_d2s))
    for a_leaf, b_leaf in zip(jax.tree_util.tree_leaves(params),
                              jax.tree_util.tree_leaves(params_d2s)):
      assert a_leaf.shape == b_leaf.shape

    def loss(p, impl):
      q = heads[impl].apply(p, x)
      return jnp.sum(jnp.sin(q * 0.1)), q  # nonlinear: grads differ
                                           # if q does anywhere

    (l_ref, q_ref), g_ref = jax.value_and_grad(
        loss, has_aux=True)(params, 'deconv')
    (l_d2s, q_d2s), g_d2s = jax.value_and_grad(
        loss, has_aux=True)(params, 'd2s')
    assert q_ref.shape == q_d2s.shape == (7, hc, wc, 5)
    np.testing.assert_allclose(np.asarray(q_ref), np.asarray(q_d2s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(l_ref), float(l_d2s), rtol=1e-5)
    for a_leaf, b_leaf in zip(jax.tree_util.tree_leaves(g_ref),
                              jax.tree_util.tree_leaves(g_d2s)):
      np.testing.assert_allclose(np.asarray(a_leaf),
                                 np.asarray(b_leaf),
                                 rtol=2e-4, atol=2e-5)


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_full_loss_parity_across_fast_paths():
  """End-to-end gate: the full learner loss with every round-6
  numerics-preserving lever ON (integer rewards + d2s head) matches
  the reference forms — the config defaults: f32 rewards + deconv
  head — on the same params and batch."""
  import dataclasses
  a, h, w = 4, 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  base = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
                total_environment_frames=10**6, torso='shallow',
                pixel_control_cost=0.05)
  batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.1)
  losses = {}
  for name, overrides in (
      ('r5_reference', dict()),
      ('r6_fast_paths', dict(pixel_control_integer_rewards=True,
                             pixel_control_head_impl='d2s'))):
    cfg = dataclasses.replace(base, **overrides)
    agent = ImpalaAgent(
        num_actions=a, torso='shallow', use_pixel_control=True,
        pixel_control_head_impl=cfg.pixel_control_head_impl,
        pixel_control_q_f32=cfg.pixel_control_q_f32)
    params = init_params(agent, jax.random.PRNGKey(0), obs)
    loss, (metrics, _) = learner_lib.loss_fn(params, agent, batch, cfg)
    losses[name] = (float(loss), float(metrics['pixel_control_loss']))
  ref, r6 = losses['r5_reference'], losses['r6_fast_paths']
  np.testing.assert_allclose(r6[0], ref[0], rtol=1e-5)
  np.testing.assert_allclose(r6[1], ref[1], rtol=1e-5)


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_bf16_q_lever_close_to_f32():
  """The opt-in pixel_control_q_f32=False lever keeps the Q-map in the
  compute dtype until the loss gather — numerics-AFFECTING by design,
  but it must stay within bf16 tolerance of the f32 head on the same
  params (and run at all)."""
  a, h, w = 4, 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
               total_environment_frames=10**6, torso='shallow',
               pixel_control_cost=0.05, compute_dtype='bfloat16',
               pixel_control_q_f32=False)
  batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.1)
  losses = {}
  for q_f32 in (True, False):
    agent = ImpalaAgent(num_actions=a, torso='shallow',
                        use_pixel_control=True, dtype=jnp.bfloat16,
                        pixel_control_q_f32=q_f32)
    params = init_params(agent, jax.random.PRNGKey(0), obs)
    loss, (metrics, _) = learner_lib.loss_fn(
        params, agent, batch, cfg)
    losses[q_f32] = float(metrics['pixel_control_loss'])
  assert np.isfinite(losses[False])
  # bf16 has ~3 decimal digits; the squared-error loss amplifies, so
  # the gate is a sanity band, not exact parity.
  np.testing.assert_allclose(losses[False], losses[True], rtol=0.05)

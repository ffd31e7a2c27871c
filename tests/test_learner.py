"""Learner tests: shift/overlap alignment (hand-indexed), reward
clipping, LR schedule, loss wiring.

SURVEY §7 "hard parts": the T+1 overlap frame, output shifting,
done-reset placement, and frame counting are where silent wrongness
lives — each gets explicit expectations here.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.structs import (
    AgentOutput, StepOutput, StepOutputInfo)


def _fake_trajectory(t_plus_1, b, a):
  """Arange-coded tensors so indices are recoverable in assertions."""
  env_outputs = StepOutput(
      reward=jnp.arange(t_plus_1 * b, dtype=jnp.float32).reshape(
          t_plus_1, b) * 0.01,
      info=StepOutputInfo(jnp.zeros((t_plus_1, b), jnp.float32),
                          jnp.zeros((t_plus_1, b), jnp.int32)),
      done=jnp.zeros((t_plus_1, b), bool),
      observation=None)
  agent_outputs = AgentOutput(
      action=jnp.arange(t_plus_1 * b, dtype=jnp.int32).reshape(
          t_plus_1, b) % a,
      policy_logits=jnp.arange(
          t_plus_1 * b * a, dtype=jnp.float32).reshape(t_plus_1, b, a),
      baseline=jnp.arange(t_plus_1 * b, dtype=jnp.float32).reshape(
          t_plus_1, b))
  learner_outputs = AgentOutput(
      action=agent_outputs.action,
      policy_logits=-agent_outputs.policy_logits,
      baseline=-agent_outputs.baseline)
  return env_outputs, agent_outputs, learner_outputs


class TestAlignBatch:

  def test_shift_semantics(self):
    """rewards[1:] pair with learner values[:-1]; bootstrap is V(o_T);
    behaviour logits/actions drop the overlap frame (experiment.py
    ≈L335–355 semantics)."""
    t1, b, a = 5, 2, 3
    env_outputs, agent_outputs, learner_outputs = _fake_trajectory(
        t1, b, a)
    cfg = Config(reward_clipping='none', discounting=0.9)
    out = learner_lib.align_batch(env_outputs, agent_outputs,
                                  learner_outputs, cfg)
    np.testing.assert_array_equal(
        np.asarray(out.rewards), np.asarray(env_outputs.reward[1:]))
    np.testing.assert_array_equal(
        np.asarray(out.behaviour_logits),
        np.asarray(agent_outputs.policy_logits[1:]))
    np.testing.assert_array_equal(
        np.asarray(out.actions), np.asarray(agent_outputs.action[1:]))
    np.testing.assert_array_equal(
        np.asarray(out.target_logits),
        np.asarray(learner_outputs.policy_logits[:-1]))
    np.testing.assert_array_equal(
        np.asarray(out.values), np.asarray(learner_outputs.baseline[:-1]))
    np.testing.assert_array_equal(
        np.asarray(out.bootstrap_value),
        np.asarray(learner_outputs.baseline[-1]))
    assert out.rewards.shape == (t1 - 1, b)

  def test_discounts_zero_at_done(self):
    t1, b, a = 4, 1, 2
    env_outputs, agent_outputs, learner_outputs = _fake_trajectory(
        t1, b, a)
    done = np.zeros((t1, b), bool)
    done[2] = True
    env_outputs = env_outputs._replace(done=jnp.asarray(done))
    cfg = Config(reward_clipping='none', discounting=0.99)
    out = learner_lib.align_batch(env_outputs, agent_outputs,
                                  learner_outputs, cfg)
    expected = np.full((t1 - 1, b), 0.99, np.float32)
    expected[1] = 0.0  # done[2] lands at shifted index 1
    np.testing.assert_allclose(np.asarray(out.discounts), expected)


class TestRewardClipping:

  def test_abs_one(self):
    r = jnp.asarray([-5.0, -0.5, 0.5, 5.0])
    np.testing.assert_allclose(
        np.asarray(learner_lib.clip_rewards(r, 'abs_one')),
        [-1.0, -0.5, 0.5, 1.0])

  def test_soft_asymmetric(self):
    """tanh(r/5) scaled x5, x0.3 on the negative side (≈L345)."""
    r = jnp.asarray([-10.0, 0.0, 10.0])
    out = np.asarray(learner_lib.clip_rewards(r, 'soft_asymmetric'))
    np.testing.assert_allclose(
        out, [0.3 * np.tanh(-2.0) * 5.0, 0.0, np.tanh(2.0) * 5.0],
        rtol=1e-6)

  def test_unknown_raises(self):
    with pytest.raises(ValueError):
      learner_lib.clip_rewards(jnp.zeros(1), 'bogus')


class TestSchedule:

  def test_linear_decay_in_env_frames(self):
    cfg = Config(batch_size=2, unroll_length=10, num_action_repeats=4,
                 total_environment_frames=800, learning_rate=0.1)
    # frames_per_step = 80; after 5 steps frames=400 → lr = 0.1 * 0.5.
    assert learner_lib.frames_per_step(cfg) == 80
    lr = learner_lib.make_schedule(cfg)(jnp.asarray(5, jnp.int32))
    np.testing.assert_allclose(float(lr), 0.05, rtol=1e-6)
    # Past the end: clamps at 0, never negative.
    lr_end = learner_lib.make_schedule(cfg)(jnp.asarray(1000, jnp.int32))
    np.testing.assert_allclose(float(lr_end), 0.0, atol=1e-9)


class TestVtraceFormsInLearner:
  """The config-selected V-trace forms must agree inside the full
  jitted train step, not just in isolation (the learner is where the
  flags are actually consumed)."""

  @pytest.mark.parametrize('variant', [
      # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
      pytest.param(dict(use_associative_scan=True),
                   marks=pytest.mark.slow),
      dict(use_pallas_vtrace=True),
  ])
  def test_matches_default_scan(self, variant):
    from scalable_agent_tpu.models import ImpalaAgent, init_params
    from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
    from scalable_agent_tpu.testing import make_example_batch
    a, h, w = 4, 24, 32
    obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
    agent = ImpalaAgent(num_actions=a, torso='shallow')
    batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                               done_prob=0.1)

    losses = []
    for overrides in ({}, variant):
      cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
                   total_environment_frames=10**6, **overrides)
      params = init_params(agent, jax.random.PRNGKey(0), obs)
      state = learner_lib.make_train_state(params, cfg)
      step = learner_lib.make_train_step(agent, cfg)
      state, metrics = step(state, batch)
      losses.append(float(metrics['total_loss']))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


@pytest.mark.slow
def test_grad_clip_norm_bounds_update():
  """config.grad_clip_norm wires optax.clip_by_global_norm into the
  update chain: a near-zero clip must shrink the first-step param
  delta by orders of magnitude vs the unclipped run."""
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.testing import make_example_batch
  a, h, w = 4, 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  agent = ImpalaAgent(num_actions=a, torso='shallow')
  batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.1)

  def delta(clip):
    cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
                 total_environment_frames=10**6, grad_clip_norm=clip)
    params = init_params(agent, jax.random.PRNGKey(0), obs)
    before = jax.tree_util.tree_map(jnp.copy, params)
    state = learner_lib.make_train_state(params, cfg)
    step = learner_lib.make_train_step(agent, cfg)
    state, _ = step(state, batch)
    return sum(
        float(jnp.sum(jnp.abs(x - y)))
        for x, y in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(before)))

  unclipped = delta(None)
  clipped = delta(1e-9)
  assert clipped < unclipped * 1e-2, (clipped, unclipped)

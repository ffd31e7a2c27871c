"""PopArt tests: statistics EMA, exact output preservation, and the
learner integration (normalized head + unnormalized V-trace).

PopArt is a TPU-build extension — the reference lists it as planned
but does not implement it (SURVEY §2.12). Ground truth here is the
PopArt definition itself (van Hasselt 2016; Hessel 2018): hand-computed
EMA updates and the preservation identity σ'·(w'x+b')+μ' == σ·(wx+b)+μ.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu import popart
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.testing import make_example_batch


def test_init_identity():
  state = popart.init(4)
  np.testing.assert_allclose(np.asarray(popart.sigma(state)),
                             np.ones(4))
  vals = jnp.array([[1.5, -2.0]])
  ids = jnp.array([0, 3])
  np.testing.assert_allclose(
      np.asarray(popart.unnormalize(state, vals, ids)),
      np.asarray(vals))


def test_update_stats_matches_hand_ema():
  state = popart.init(3)
  # Two trajectories of task 0 with targets {1,3} and {5,7}; task 2
  # with {10,10}; task 1 absent.
  targets = jnp.array([[1.0, 5.0, 10.0],
                       [3.0, 7.0, 10.0]])
  ids = jnp.array([0, 0, 2])
  beta = 0.1
  new = popart.update_stats(state, targets, ids, beta=beta)
  # Task 0: batch mean 4, second moment (1+9+25+49)/4=21.
  np.testing.assert_allclose(float(new.mu[0]), 0.9 * 0 + 0.1 * 4.0)
  np.testing.assert_allclose(float(new.nu[0]), 0.9 * 1 + 0.1 * 21.0)
  # Task 1 untouched (absent from batch).
  np.testing.assert_allclose(float(new.mu[1]), 0.0)
  np.testing.assert_allclose(float(new.nu[1]), 1.0)
  # Task 2: mean 10, second moment 100.
  np.testing.assert_allclose(float(new.mu[2]), 1.0)
  np.testing.assert_allclose(float(new.nu[2]), 0.9 + 10.0)


def test_normalize_unnormalize_roundtrip():
  state = popart.PopArtState(mu=jnp.array([2.0, -1.0]),
                             nu=jnp.array([13.0, 5.0]))
  ids = jnp.array([0, 1])
  vals = jnp.array([[4.0, -3.0], [0.0, 1.0]])
  n = popart.normalize(state, vals, ids)
  np.testing.assert_allclose(
      np.asarray(popart.unnormalize(state, n, ids)),
      np.asarray(vals), rtol=1e-6)


def test_preserve_outputs_exact():
  rng = np.random.RandomState(0)
  hidden, num_tasks = 16, 5
  kernel = jnp.asarray(rng.randn(hidden, num_tasks), jnp.float32)
  bias = jnp.asarray(rng.randn(num_tasks), jnp.float32)
  x = jnp.asarray(rng.randn(7, hidden), jnp.float32)
  old = popart.PopArtState(mu=jnp.zeros(num_tasks),
                           nu=jnp.ones(num_tasks))
  new = popart.PopArtState(
      mu=jnp.asarray(rng.randn(num_tasks), jnp.float32),
      nu=jnp.asarray(1.0 + rng.rand(num_tasks) * 10, jnp.float32))

  def unnorm_out(k, b, state):
    return (popart.sigma(state)[None, :] * (x @ k + b[None, :]) +
            state.mu[None, :])

  new_kernel, new_bias = popart.preserve_outputs(kernel, bias, old, new)
  np.testing.assert_allclose(
      np.asarray(unnorm_out(new_kernel, new_bias, new)),
      np.asarray(unnorm_out(kernel, bias, old)), rtol=1e-5, atol=1e-5)


def test_apply_preservation_flax_layout():
  agent = ImpalaAgent(num_actions=3, torso='shallow',
                      num_popart_tasks=4, use_instruction=False)
  obs = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  assert params['params']['baseline']['kernel'].shape[-1] == 4
  old = popart.init(4)
  new = popart.PopArtState(mu=jnp.full((4,), 2.0),
                           nu=jnp.full((4,), 8.0))
  rewritten = popart.apply_preservation(params, old, new)
  k0 = params['params']['baseline']['kernel']
  k1 = rewritten['params']['baseline']['kernel']
  np.testing.assert_allclose(np.asarray(k1),
                             np.asarray(k0) / 2.0, rtol=1e-6)
  # Everything else untouched.
  np.testing.assert_array_equal(
      np.asarray(rewritten['params']['policy_logits']['kernel']),
      np.asarray(params['params']['policy_logits']['kernel']))


def test_learner_with_popart_trains_and_preserves():
  num_tasks, a = 3, 4
  h, w = 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  cfg = Config(batch_size=3, unroll_length=4, num_action_repeats=1,
               total_environment_frames=10**6, use_popart=True,
               popart_beta=0.05, torso='shallow')
  agent = ImpalaAgent(num_actions=a, torso='shallow',
                      num_popart_tasks=num_tasks)
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  state = learner_lib.make_train_state(params, cfg,
                                       num_popart_tasks=num_tasks)
  assert state.popart is not None
  batch = make_example_batch(5, 3, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.1)
  batch = batch._replace(level_name=np.array([0, 1, 1], np.int32))
  step = learner_lib.make_train_step(agent, cfg)
  prev_mu = np.asarray(state.popart.mu).copy()
  for _ in range(3):
    state, metrics = step(state, batch)
  assert np.isfinite(float(metrics['total_loss']))
  new_mu = np.asarray(state.popart.mu)
  # Tasks 0 and 1 saw data; task 2 didn't.
  assert new_mu[0] != prev_mu[0]
  assert new_mu[1] != prev_mu[1]
  assert new_mu[2] == prev_mu[2]


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_popart_unnormalized_values_continuous_across_update():
  """The preservation property end-to-end in the learner: after a
  train step changes the stats, the NEW params + NEW stats must give
  (nearly) the same unnormalized values as the same params would have
  before preservation — i.e. the rewrite exactly cancels the stats
  change on the head output (up to the SGD update itself, which we
  freeze with lr=0)."""
  num_tasks, a = 2, 3
  h, w = 24, 32
  obs = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  cfg = Config(batch_size=2, unroll_length=4, num_action_repeats=1,
               total_environment_frames=10**6, use_popart=True,
               popart_beta=0.5, learning_rate=0.0, torso='shallow')
  agent = ImpalaAgent(num_actions=a, torso='shallow',
                      num_popart_tasks=num_tasks)
  params = init_params(agent, jax.random.PRNGKey(1), obs)
  state = learner_lib.make_train_state(params, cfg,
                                       num_popart_tasks=num_tasks)
  batch = make_example_batch(5, 2, h, w, a, MAX_INSTRUCTION_LEN,
                             done_prob=0.0)
  batch = batch._replace(level_name=np.array([0, 1], np.int32))
  ids = jnp.asarray(batch.level_name, jnp.int32)

  def unnorm_values(state):
    out, _ = agent.apply(state.params, batch.agent_outputs.action,
                         batch.env_outputs, batch.agent_state,
                         level_ids=ids)
    from scalable_agent_tpu import popart as popart_lib
    return np.asarray(
        popart_lib.unnormalize(state.popart, out.baseline, ids))

  before = unnorm_values(state)
  step = learner_lib.make_train_step(agent, cfg)
  state2, _ = step(state, batch)
  # Stats moved a lot (beta=0.5)…
  assert not np.allclose(np.asarray(state2.popart.mu), 0.0)
  # …but with lr=0 the unnormalized predictions are preserved.
  after = unnorm_values(state2)
  np.testing.assert_allclose(after, before, rtol=1e-4, atol=1e-4)


def test_sigma_no_nan_for_near_constant_targets():
  """Float rounding can push nu - mu² slightly negative for a
  near-constant-target task; sigma must clip the variance BEFORE the
  sqrt (a NaN here poisons the head permanently)."""
  state = popart.init(1)
  targets = jnp.full((4, 1), 1000.07, jnp.float32)
  ids = jnp.array([0])
  for _ in range(60):
    state = popart.update_stats(state, targets, ids, beta=0.1)
  s = np.asarray(popart.sigma(state))
  assert np.all(np.isfinite(s)), s
  assert s[0] >= float(state.sigma_min)
  n = popart.normalize(state, targets, ids)
  assert np.all(np.isfinite(np.asarray(n)))

"""V-trace numerics vs an independent NumPy ground truth.

Test strategy mirrors the reference's vtrace_test.py (SURVEY §4 / §2.14):
- `_ground_truth_calculation`: explicit per-(t, b) Python loops over the
  recursion, written independently of the JAX implementation.
- parameterized over batch sizes (1, 5); deterministic pseudo-random inputs
  via `_shaped_arange` / `_softmax`; log_rhos spread over [-2.5, 2.5] so
  both clip branches are exercised.
- rank-generic inputs (extra trailing dims) work; inconsistent ranks raise.
Additions over the reference: associative-scan form must match the scan
form bit-for-bit-ish (fp32 tolerance), and gradients must be blocked
through vs / pg_advantages.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import vtrace


def _shaped_arange(*shape):
  """Deterministic inputs: arange scaled into a small range."""
  return np.arange(int(np.prod(shape)), dtype=np.float32).reshape(
      *shape) / np.prod(shape)


def _softmax(logits):
  maxed = logits - logits.max(axis=-1, keepdims=True)
  e = np.exp(maxed)
  return e / e.sum(axis=-1, keepdims=True)


def _ground_truth_calculation(log_rhos, discounts, rewards, values,
                              bootstrap_value, clip_rho_threshold,
                              clip_pg_rho_threshold):
  """Explicit-loop NumPy V-trace, independent of the JAX code."""
  vs = []
  seq_len = len(discounts)
  rhos = np.exp(log_rhos)
  cs = np.minimum(rhos, 1.0)
  clipped_rhos = rhos
  if clip_rho_threshold is not None:
    clipped_rhos = np.minimum(rhos, clip_rho_threshold)
  clipped_pg_rhos = rhos
  if clip_pg_rho_threshold is not None:
    clipped_pg_rhos = np.minimum(rhos, clip_pg_rho_threshold)

  # Direct summation form: vs_t = V(x_t) + sum_{k=t}^{T-1} gamma^{k-t}
  #   * (prod_{i=t}^{k-1} c_i) * clipped_rho_k * delta_k.
  values_t_plus_1 = np.concatenate(
      [values, bootstrap_value[None, :]], axis=0)
  for s in range(seq_len):
    v_s = np.copy(values[s])  # Very important copy...
    for t in range(s, seq_len):
      v_s += (np.prod(discounts[s:t], axis=0) * np.prod(cs[s:t], axis=0) *
              clipped_rhos[t] *
              (rewards[t] + discounts[t] * values_t_plus_1[t + 1] -
               values[t]))
    vs.append(v_s)
  vs = np.stack(vs, axis=0)
  pg_advantages = (clipped_pg_rhos * (
      rewards + discounts *
      np.concatenate([vs[1:], bootstrap_value[None, :]], axis=0) - values))
  return vtrace.VTraceReturns(vs=vs, pg_advantages=pg_advantages)


def _make_inputs(batch_size, seq_len=5):
  # log_rhos spread over [-2.5, 2.5] to exercise both clip branches.
  log_rhos = _shaped_arange(seq_len, batch_size) * 5.0 - 2.5
  values = {
      'log_rhos': log_rhos,
      'discounts': np.array(
          [[0.9 if (t * batch_size + b) % 2 == 0 else 0.0
            for b in range(batch_size)] for t in range(seq_len)],
          dtype=np.float32),
      'rewards': _shaped_arange(seq_len, batch_size),
      'values': _shaped_arange(seq_len, batch_size) / batch_size,
      'bootstrap_value': _shaped_arange(batch_size) + 1.0,
      'clip_rho_threshold': 3.7,
      'clip_pg_rho_threshold': 2.2,
  }
  return values


class TestLogProbsFromLogitsAndActions:

  @pytest.mark.parametrize('batch_size', [1, 2])
  def test_log_probs_from_logits_and_actions(self, batch_size):
    seq_len = 7
    num_actions = 3
    rng = np.random.RandomState(0)
    policy_logits = _shaped_arange(seq_len, batch_size, num_actions) + 10
    actions = rng.randint(
        0, num_actions, size=(seq_len, batch_size), dtype=np.int32)

    out = vtrace.log_probs_from_logits_and_actions(
        jnp.asarray(policy_logits), jnp.asarray(actions))

    probs = _softmax(policy_logits)
    expected = np.empty((seq_len, batch_size), dtype=np.float32)
    for t in range(seq_len):
      for b in range(batch_size):
        expected[t, b] = np.log(probs[t, b, actions[t, b]])
    np.testing.assert_allclose(expected, np.asarray(out), rtol=1e-5,
                               atol=1e-5)


class TestVtrace:

  @pytest.mark.parametrize('batch_size', [1, 5])
  @pytest.mark.parametrize('use_associative_scan', [False, True])
  def test_vtrace_matches_ground_truth(self, batch_size,
                                       use_associative_scan):
    values = _make_inputs(batch_size)
    output = vtrace.from_importance_weights(
        use_associative_scan=use_associative_scan, **values)
    ground_truth = _ground_truth_calculation(**values)
    np.testing.assert_allclose(
        ground_truth.vs, np.asarray(output.vs), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        ground_truth.pg_advantages, np.asarray(output.pg_advantages),
        rtol=1e-4, atol=1e-4)

  @pytest.mark.parametrize('batch_size', [1, 2])
  def test_vtrace_from_logits(self, batch_size):
    seq_len = 5
    num_actions = 3
    clip_rho_threshold = None  # No clipping.
    clip_pg_rho_threshold = None
    rng = np.random.RandomState(1)

    behaviour_policy_logits = _shaped_arange(
        seq_len, batch_size, num_actions)
    target_policy_logits = _shaped_arange(
        seq_len, batch_size, num_actions) * 2.0 - 1.0
    actions = rng.randint(
        0, num_actions, size=(seq_len, batch_size), dtype=np.int32)
    discounts = _shaped_arange(seq_len, batch_size) * 0.9
    rewards = _shaped_arange(seq_len, batch_size) * 2 - 1
    values = _shaped_arange(seq_len, batch_size)
    bootstrap_value = _shaped_arange(batch_size) + 1.0

    out = vtrace.from_logits(
        behaviour_policy_logits=jnp.asarray(behaviour_policy_logits),
        target_policy_logits=jnp.asarray(target_policy_logits),
        actions=jnp.asarray(actions),
        discounts=jnp.asarray(discounts),
        rewards=jnp.asarray(rewards),
        values=jnp.asarray(values),
        bootstrap_value=jnp.asarray(bootstrap_value),
        clip_rho_threshold=clip_rho_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold)

    behaviour_log_probs = vtrace.log_probs_from_logits_and_actions(
        behaviour_policy_logits, actions)
    target_log_probs = vtrace.log_probs_from_logits_and_actions(
        target_policy_logits, actions)
    log_rhos = np.asarray(target_log_probs) - np.asarray(
        behaviour_log_probs)
    np.testing.assert_allclose(
        log_rhos, np.asarray(out.log_rhos), rtol=1e-5, atol=1e-5)

    ground_truth = _ground_truth_calculation(
        log_rhos=log_rhos, discounts=discounts, rewards=rewards,
        values=values, bootstrap_value=bootstrap_value,
        clip_rho_threshold=clip_rho_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold)
    np.testing.assert_allclose(
        ground_truth.vs, np.asarray(out.vs), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        ground_truth.pg_advantages, np.asarray(out.pg_advantages),
        rtol=1e-4, atol=1e-4)

  def test_higher_rank_inputs_for_importance_weights(self):
    """Extra trailing dims are supported, like the reference."""
    t, b, extra = 4, 2, 3
    out = vtrace.from_importance_weights(
        log_rhos=jnp.zeros((t, b, extra)),
        discounts=jnp.full((t, b, extra), 0.9),
        rewards=jnp.ones((t, b, extra)),
        values=jnp.ones((t, b, extra)),
        bootstrap_value=jnp.ones((b, extra)))
    assert out.vs.shape == (t, b, extra)
    assert out.pg_advantages.shape == (t, b, extra)

  def test_inconsistent_rank_inputs_for_importance_weights(self):
    with pytest.raises(Exception):
      # bootstrap_value must drop exactly the time dim.
      out = vtrace.from_importance_weights(
          log_rhos=jnp.zeros((4, 2, 3)),
          discounts=jnp.full((4, 2, 3), 0.9),
          rewards=jnp.ones((4, 2, 3)),
          values=jnp.ones((4, 2, 3)),
          bootstrap_value=jnp.ones((4,)))
      out.vs.block_until_ready()

  def test_associative_scan_matches_lax_scan(self):
    values = _make_inputs(batch_size=5, seq_len=37)
    seq = vtrace.from_importance_weights(use_associative_scan=False,
                                         **values)
    par = vtrace.from_importance_weights(use_associative_scan=True,
                                         **values)
    np.testing.assert_allclose(np.asarray(seq.vs), np.asarray(par.vs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(seq.pg_advantages), np.asarray(par.pg_advantages),
        rtol=1e-5, atol=1e-5)

  def test_outputs_are_stop_gradiented(self):
    values = _make_inputs(batch_size=2)

    def f(v):
      inputs = dict(values, values=v)
      out = vtrace.from_importance_weights(**inputs)
      return jnp.sum(out.vs) + jnp.sum(out.pg_advantages)

    grad = jax.grad(f)(jnp.asarray(values['values']))
    np.testing.assert_array_equal(np.asarray(grad),
                                  np.zeros_like(values['values']))

  def test_gradient_flows_through_from_logits_log_probs(self):
    """target_action_log_probs must remain differentiable (pg loss path)."""
    seq_len, batch_size, num_actions = 3, 2, 4
    actions = jnp.zeros((seq_len, batch_size), dtype=jnp.int32)

    def f(logits):
      out = vtrace.from_logits(
          behaviour_policy_logits=jnp.zeros(
              (seq_len, batch_size, num_actions)),
          target_policy_logits=logits,
          actions=actions,
          discounts=jnp.full((seq_len, batch_size), 0.9),
          rewards=jnp.ones((seq_len, batch_size)),
          values=jnp.zeros((seq_len, batch_size)),
          bootstrap_value=jnp.zeros((batch_size,)))
      return jnp.sum(out.target_action_log_probs)

    grad = jax.grad(f)(jnp.zeros((seq_len, batch_size, num_actions)))
    assert np.abs(np.asarray(grad)).sum() > 0


class TestVtracePallas:
  """The fused Pallas kernel (ops/vtrace_pallas.py) against the same
  NumPy ground truth — interpreter mode on CPU runs the identical
  kernel code path that compiles on TPU."""

  @pytest.mark.parametrize('batch_size', [1, 5])
  def test_matches_ground_truth(self, batch_size):
    values = _make_inputs(batch_size)
    output = vtrace.from_importance_weights(use_pallas=True, **values)
    ground_truth = _ground_truth_calculation(**values)
    np.testing.assert_allclose(
        ground_truth.vs, np.asarray(output.vs), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        ground_truth.pg_advantages, np.asarray(output.pg_advantages),
        rtol=1e-4, atol=1e-4)

  def test_matches_scan_path(self):
    """Within f32 reassociation tolerance: the kernel's pointer-
    doubling recursion reorders the accumulation relative to the
    sequential scan (~1e-5 absolute at T=100 on-chip)."""
    values = _make_inputs(5)
    seq = vtrace.from_importance_weights(use_pallas=False, **values)
    fused = vtrace.from_importance_weights(use_pallas=True, **values)
    np.testing.assert_allclose(np.asarray(seq.vs),
                               np.asarray(fused.vs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(seq.pg_advantages),
                               np.asarray(fused.pg_advantages),
                               rtol=1e-5, atol=1e-5)

  def test_higher_rank_and_wide_batch(self):
    """Trailing dims flatten into lanes; >128 lanes exercises the
    multi-block grid."""
    t, b, extra = 6, 70, 3  # 210 lanes → 2 blocks
    rng = np.random.RandomState(0)
    kwargs = dict(
        log_rhos=jnp.asarray(rng.randn(t, b, extra) * 0.5),
        discounts=jnp.full((t, b, extra), 0.9),
        rewards=jnp.asarray(rng.randn(t, b, extra)),
        values=jnp.asarray(rng.randn(t, b, extra)),
        bootstrap_value=jnp.asarray(rng.randn(b, extra)))
    out = vtrace.from_importance_weights(use_pallas=True, **kwargs)
    ref = vtrace.from_importance_weights(**kwargs)
    assert out.vs.shape == (t, b, extra)
    np.testing.assert_allclose(np.asarray(ref.vs), np.asarray(out.vs),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.pg_advantages),
                               np.asarray(out.pg_advantages),
                               rtol=1e-5, atol=1e-6)

  def test_wide_batch_matches_scan(self):
    t, b = 7, 300
    rng = np.random.RandomState(3)
    kwargs = dict(
        log_rhos=jnp.asarray(rng.randn(t, b) * 0.8),
        discounts=jnp.asarray(0.9 * (rng.rand(t, b) > 0.1)),
        rewards=jnp.asarray(rng.randn(t, b)),
        values=jnp.asarray(rng.randn(t, b)),
        bootstrap_value=jnp.asarray(rng.randn(b)))
    seq = vtrace.from_importance_weights(**kwargs)
    fused = vtrace.from_importance_weights(use_pallas=True, **kwargs)
    np.testing.assert_allclose(np.asarray(seq.vs), np.asarray(fused.vs),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(seq.pg_advantages),
                               np.asarray(fused.pg_advantages),
                               rtol=1e-5, atol=1e-6)

  def test_composes_under_jit(self):
    values = _make_inputs(2)

    @jax.jit
    def f(**kw):
      return vtrace.from_importance_weights(use_pallas=True, **kw).vs

    np.testing.assert_allclose(
        np.asarray(f(**values)),
        np.asarray(vtrace.from_importance_weights(**values).vs),
        rtol=1e-5)

  def test_grad_through_loss_with_pallas(self):
    """The production integration: value_and_grad over a loss that
    calls the Pallas path must trace (inputs are stop-gradiented
    before the kernel)."""
    values = _make_inputs(2)

    def loss(v):
      out = vtrace.from_importance_weights(
          **{**values, 'values': v}, use_pallas=True)
      # Outputs are stop-grad; gradient flows via the direct term only.
      return jnp.sum((out.vs - v) ** 2)

    g = jax.grad(loss)(values['values'])
    assert np.all(np.isfinite(np.asarray(g)))

  def test_pallas_and_associative_scan_mutually_exclusive(self):
    values = _make_inputs(1)
    with pytest.raises(ValueError, match='mutually exclusive'):
      vtrace.from_importance_weights(use_pallas=True,
                                     use_associative_scan=True,
                                     **values)

  def test_interprets_on_cpu_only_and_rejects_unknown_platforms(
      self, monkeypatch):
    """Compiled on tpu, interpreted on cpu; a platform that is neither
    must raise instead of silently running the interpreter."""
    from scalable_agent_tpu.ops import vtrace_pallas
    assert vtrace_pallas._interpret_on('tpu') is False
    assert vtrace_pallas._interpret_on('cpu') is True
    monkeypatch.setattr(jax, 'default_backend', lambda: 'gpu')
    with pytest.raises(RuntimeError, match="platform 'gpu'"):
      vtrace.from_importance_weights(use_pallas=True, **_make_inputs(1))


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_associative_scan_long_sequence():
  """Long-T readiness (SURVEY §5.7): the associative-scan V-trace is
  the sequence-scaling door — verify it matches the sequential scan at
  T=4096 (far beyond the T=100 unrolls of the reference)."""
  t, b = 4096, 4
  rng = np.random.RandomState(0)
  kwargs = dict(
      log_rhos=jnp.asarray(rng.randn(t, b) * 0.3),
      discounts=jnp.asarray(0.99 * (rng.rand(t, b) > 0.01)),
      rewards=jnp.asarray(rng.randn(t, b)),
      values=jnp.asarray(rng.randn(t, b)),
      bootstrap_value=jnp.asarray(rng.randn(b)))
  seq = vtrace.from_importance_weights(**kwargs)
  par = vtrace.from_importance_weights(use_associative_scan=True,
                                       **kwargs)
  np.testing.assert_allclose(np.asarray(seq.vs), np.asarray(par.vs),
                             rtol=2e-4, atol=2e-4)
  np.testing.assert_allclose(np.asarray(seq.pg_advantages),
                             np.asarray(par.pg_advantages),
                             rtol=2e-4, atol=2e-4)

"""V-trace in plain NumPy, the benchmark's own reference.

A COPY of tests/test_vtrace.py::_ground_truth_calculation (an explicit
double loop over time in float64, independent of the JAX code), kept
here so that a PR which changes the program or its tests cannot change
the yardstick. It is the only plain reference the repository has: a
float32 reference of the whole agent does not exist yet (ROADMAP R1),
and until it does `correct` is this much weaker.
"""

import numpy as np


def ground_truth(log_rhos, discounts, rewards, values, bootstrap_value,
                 clip_rho_threshold=1.0, clip_pg_rho_threshold=1.0):
  """(vs, pg_advantages), each [T, B] float64, by direct summation:
  vs_s = V(x_s) + sum_{t>=s} gamma^{t-s} (prod_{i<t} c_i) rho_t delta_t."""
  log_rhos, discounts, rewards, values, bootstrap_value = (
      np.asarray(x, np.float64) for x in
      (log_rhos, discounts, rewards, values, bootstrap_value))
  seq_len = len(discounts)
  rhos = np.exp(log_rhos)
  cs = np.minimum(rhos, 1.0)
  clipped_rhos = rhos
  if clip_rho_threshold is not None:
    clipped_rhos = np.minimum(rhos, clip_rho_threshold)
  clipped_pg_rhos = rhos
  if clip_pg_rho_threshold is not None:
    clipped_pg_rhos = np.minimum(rhos, clip_pg_rho_threshold)
  values_t_plus_1 = np.concatenate(
      [values, bootstrap_value[None, :]], axis=0)
  vs = []
  for s in range(seq_len):
    v_s = np.copy(values[s])
    for t in range(s, seq_len):
      v_s += (np.prod(discounts[s:t], axis=0) * np.prod(cs[s:t], axis=0) *
              clipped_rhos[t] *
              (rewards[t] + discounts[t] * values_t_plus_1[t + 1] -
               values[t]))
    vs.append(v_s)
  vs = np.stack(vs, axis=0)
  pg_advantages = clipped_pg_rhos * (
      rewards + discounts *
      np.concatenate([vs[1:], bootstrap_value[None, :]], axis=0) - values)
  return vs, pg_advantages


def seeded_inputs(seed, unroll_length, batch_size):
  """A [T, B] sample that exercises both clip branches and episode
  ends: log_rhos ~ 0.5 N(0,1), 2% of discounts zero, values of a few
  units (the shape chip_smoke.py's kernel check uses)."""
  rng = np.random.RandomState(seed)
  t, b = unroll_length, batch_size
  return dict(
      log_rhos=(0.5 * rng.randn(t, b)).astype(np.float32),
      discounts=(0.99 * (rng.rand(t, b) > 0.02)).astype(np.float32),
      rewards=rng.randn(t, b).astype(np.float32),
      values=(2.0 * rng.randn(t, b)).astype(np.float32),
      bootstrap_value=(2.0 * rng.randn(b)).astype(np.float32))


# Why 1e-3 x max(1, |reference|): the step computes V-trace in float32
# (no matrix product, so the chip's reduced matmul precision does not
# enter), and a float32 recursion over T=100 steps on values of a few
# units lands within ~1e-5 relative of the float64 sum (1.4e-6 between
# two float32 forms on the chip, PR 21). bfloat16 anywhere in it would
# miss by ~1e-2, a dropped clip or a shifted index by O(1). 1e-3 sits
# two decades above the first and one below the second.
RELATIVE_TOLERANCE = 1e-3


def check_step_form(config, seed):
  """The V-trace form the configured step uses (scan, associative scan
  or the Pallas kernel) against `ground_truth` on a seeded sample of
  the cell's own [T, min(B, 32)]. Returns (ok, worst relative error)."""
  import jax
  from scalable_agent_tpu import vtrace
  inputs = seeded_inputs(seed, config.unroll_length,
                         min(config.batch_size, 32))
  form = jax.jit(lambda kw: vtrace.from_importance_weights(
      use_associative_scan=config.use_associative_scan,
      use_pallas=config.use_pallas_vtrace, **kw))
  got = form(inputs)
  ref_vs, ref_pg = ground_truth(**inputs)
  worst = 0.0
  for have, want in ((got.vs, ref_vs), (got.pg_advantages, ref_pg)):
    error = np.abs(np.asarray(have, np.float64) - want)
    worst = max(worst, float(np.max(error / np.maximum(1.0, np.abs(want)))))
  return worst <= RELATIVE_TOLERANCE, worst

"""V-trace off-policy actor-critic return estimator, TPU-native (pure JAX).

Re-expresses the reference's V-trace library (reference: vtrace.py —
`log_probs_from_logits_and_actions` ≈L60, `from_logits` ≈L80,
`from_importance_weights` ≈L130) with the same namedtuple API, clip
semantics and time-major [T, B, ...] layout, but built for XLA:

- The backward recursion ``acc <- delta_t + gamma_t * c_t * acc`` (the
  reference runs it as a reversed `tf.scan` with `parallel_iterations=1`
  explicitly placed on CPU) is a `jax.lax.scan` here — it compiles into a
  single fused XLA loop living on-device, so there is no host round trip.
- Because the recursion is a first-order *linear* recurrence, we also offer
  a work-parallel `jax.lax.associative_scan` formulation
  (``use_associative_scan=True``) which is O(log T) depth on TPU and is the
  door to sequence-parallel V-trace for long unrolls.

All math is float32; shapes are rank-generic like the reference (tested
with extra trailing dimensions).
"""

import collections

import jax
import jax.numpy as jnp
from jax import lax

VTraceFromLogitsReturns = collections.namedtuple(
    'VTraceFromLogitsReturns',
    ['vs', 'pg_advantages', 'log_rhos',
     'behaviour_action_log_probs', 'target_action_log_probs'])

VTraceReturns = collections.namedtuple('VTraceReturns', 'vs pg_advantages')


def log_probs_from_logits_and_actions(policy_logits, actions):
  """log pi(a|x) for the given actions.

  Mirrors the reference's `-sparse_softmax_cross_entropy` formulation
  (reference: vtrace.py ≈L60) — rank generic: `policy_logits` is
  [T, B, ..., NUM_ACTIONS] and `actions` is [T, B, ...].
  """
  policy_logits = jnp.asarray(policy_logits, jnp.float32)
  log_probs = jax.nn.log_softmax(policy_logits, axis=-1)
  return jnp.take_along_axis(
      log_probs, actions[..., None].astype(jnp.int32), axis=-1).squeeze(-1)


def from_logits(behaviour_policy_logits, target_policy_logits, actions,
                discounts, rewards, values, bootstrap_value,
                clip_rho_threshold=1.0, clip_pg_rho_threshold=1.0,
                use_associative_scan=False, use_pallas=False,
                mesh=None, batch_axis='data'):
  """V-trace for softmax policies (reference: vtrace.py ≈L80).

  Shapes (time-major): logits [T, B, NUM_ACTIONS], actions [T, B],
  discounts/rewards/values [T, B], bootstrap_value [B]. Extra trailing
  dimensions are supported everywhere the reference supports them.

  `mesh` (with `batch_axis`) only matters for the Pallas form: inside
  a sharded step the kernel runs under `shard_map` over the batch
  axis (ops/vtrace_pallas.sharded_from_importance_weights) — V-trace
  is per-batch-column independent, so the mapping is exact. The pure
  JAX forms partition under GSPMD without help and ignore it.

  `target_policy_logits` need not be the differentiated policy: the
  IMPACT surrogate (learner.loss_fn with config.surrogate='impact';
  arXiv 1912.00167) passes the TARGET-NETWORK logits here, so the IS
  ratios become pi_target/mu — clipped at the same rho-bar — and the
  returned `target_action_log_probs` double as the anchor log-probs
  the clipped surrogate's pi_theta/pi_target ratio is built from.
  Nothing differentiates through this function's outputs either way
  (vs/pg_advantages are stop-gradient'ed below).

  `behaviour_policy_logits` of the ACTIONS' rank ([T, B]) are the
  actions' log-probabilities under the behaviour policy, log mu(a):
  what an agent records whose logits stay on the device
  (structs.AgentOutput). The importance weights need nothing more.
  """
  if jnp.ndim(behaviour_policy_logits) == jnp.ndim(actions):
    behaviour_action_log_probs = jnp.asarray(behaviour_policy_logits,
                                             jnp.float32)
  else:
    behaviour_action_log_probs = log_probs_from_logits_and_actions(
        behaviour_policy_logits, actions)
  target_action_log_probs = log_probs_from_logits_and_actions(
      target_policy_logits, actions)
  log_rhos = target_action_log_probs - behaviour_action_log_probs
  vtrace_returns = from_importance_weights(
      log_rhos=log_rhos,
      discounts=discounts,
      rewards=rewards,
      values=values,
      bootstrap_value=bootstrap_value,
      clip_rho_threshold=clip_rho_threshold,
      clip_pg_rho_threshold=clip_pg_rho_threshold,
      use_associative_scan=use_associative_scan,
      use_pallas=use_pallas,
      mesh=mesh, batch_axis=batch_axis)
  return VTraceFromLogitsReturns(
      log_rhos=log_rhos,
      behaviour_action_log_probs=behaviour_action_log_probs,
      target_action_log_probs=target_action_log_probs,
      **vtrace_returns._asdict())


def _vs_minus_v_xs_scan(deltas, discounts_cs):
  """Sequential backward recursion via lax.scan (single fused XLA loop)."""

  def body(acc, x):
    delta_t, discount_c_t = x
    acc = delta_t + discount_c_t * acc
    return acc, acc

  init = jnp.zeros_like(deltas[0])
  _, out = lax.scan(body, init, (deltas, discounts_cs), reverse=True)
  return out


def _vs_minus_v_xs_associative(deltas, discounts_cs):
  """Same recurrence as `_vs_minus_v_xs_scan` but O(log T) depth.

  y_t = delta_t + (gamma_t c_t) y_{t+1} is a linear first-order recurrence;
  over reversed time it is y_i = a_i y_{i-1} + b_i which composes
  associatively as (a, b) ∘ (a', b') = (a a', a' b + b').
  """

  def combine(x, y):
    a_x, b_x = x
    a_y, b_y = y
    return a_y * a_x, a_y * b_x + b_y

  _, out = lax.associative_scan(combine, (discounts_cs, deltas),
                                reverse=True)
  return out


def from_importance_weights(log_rhos, discounts, rewards, values,
                            bootstrap_value, clip_rho_threshold=1.0,
                            clip_pg_rho_threshold=1.0,
                            use_associative_scan=False,
                            use_pallas=False,
                            mesh=None, batch_axis='data'):
  """V-trace from log importance weights (reference: vtrace.py ≈L130).

  rhos = exp(log_rhos); clipped at `clip_rho_threshold` (rho-bar) for the
  value fixpoint and `clip_pg_rho_threshold` for the policy-gradient
  advantage; cs = min(1, rhos). Outputs are stop-gradient'ed exactly like
  the reference.

  `use_pallas=True` runs the whole computation as one fused Pallas TPU
  kernel (ops/vtrace_pallas.py) — no HBM intermediates; interpreter
  mode on CPU keeps CI on the same code path. Under a sharded step,
  pass the step's `mesh`: pallas_call has no SPMD partitioning rule,
  so the kernel is shard_map'ped over `batch_axis` instead (exact —
  each batch column is an independent recursion).
  """
  if use_pallas and use_associative_scan:
    raise ValueError('use_pallas and use_associative_scan are mutually '
                     'exclusive — pick one V-trace form')
  if use_pallas:
    from scalable_agent_tpu.ops import vtrace_pallas
    # Stop gradients on the INPUTS: the outputs are stop-gradiented
    # anyway (below and in the reference), and pallas_call has no jvp
    # rule — tangents reaching the kernel under value_and_grad would
    # fail at trace time.
    (log_rhos, discounts, rewards, values,
     bootstrap_value) = jax.tree_util.tree_map(
         lax.stop_gradient,
         (log_rhos, discounts, rewards, values, bootstrap_value))
    if mesh is not None:
      vs, pg_advantages = vtrace_pallas.sharded_from_importance_weights(
          mesh, log_rhos, discounts, rewards, values, bootstrap_value,
          clip_rho_threshold=clip_rho_threshold,
          clip_pg_rho_threshold=clip_pg_rho_threshold,
          batch_axis=batch_axis)
    else:
      vs, pg_advantages = vtrace_pallas.from_importance_weights(
          log_rhos, discounts, rewards, values, bootstrap_value,
          clip_rho_threshold=clip_rho_threshold,
          clip_pg_rho_threshold=clip_pg_rho_threshold)
    return VTraceReturns(vs=lax.stop_gradient(vs),
                         pg_advantages=lax.stop_gradient(pg_advantages))
  log_rhos = jnp.asarray(log_rhos, jnp.float32)
  discounts = jnp.asarray(discounts, jnp.float32)
  rewards = jnp.asarray(rewards, jnp.float32)
  values = jnp.asarray(values, jnp.float32)
  bootstrap_value = jnp.asarray(bootstrap_value, jnp.float32)

  rhos = jnp.exp(log_rhos)
  if clip_rho_threshold is not None:
    clipped_rhos = jnp.minimum(clip_rho_threshold, rhos)
  else:
    clipped_rhos = rhos
  cs = jnp.minimum(1.0, rhos)

  # V(x_{t+1}) with the bootstrap appended.
  values_t_plus_1 = jnp.concatenate(
      [values[1:], bootstrap_value[None]], axis=0)
  deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)

  scan_fn = (_vs_minus_v_xs_associative if use_associative_scan
             else _vs_minus_v_xs_scan)
  vs_minus_v_xs = scan_fn(deltas, discounts * cs)

  vs = vs_minus_v_xs + values

  # Advantage for the policy gradient; vs_{t+1} uses the bootstrap at the end.
  vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
  if clip_pg_rho_threshold is not None:
    clipped_pg_rhos = jnp.minimum(clip_pg_rho_threshold, rhos)
  else:
    clipped_pg_rhos = rhos
  pg_advantages = clipped_pg_rhos * (
      rewards + discounts * vs_t_plus_1 - values)

  return VTraceReturns(
      vs=lax.stop_gradient(vs),
      pg_advantages=lax.stop_gradient(pg_advantages))

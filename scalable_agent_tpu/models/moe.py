"""The feed-forward half of a block that the attention cores share
(models/latent_moe.py, models/hybrid_attention.py, models/ssm_hybrid.py):
the SwiGLU FFN, the router and this chip's share of the routed experts.

`FFN_w(x) = W_down(silu(W_gate x) * W_up x)`. A routed layer's router
is one of two (`router`). `sigmoid`: it scores `s = sigmoid(x W_g)`
over ALL `routed_experts` (float32 operands), chooses on `s + bias`
within the `expert_groups_kept` best of `expert_groups` groups (a group
scores the sum of its two largest; one group: no limit), the
`experts_per_token` largest among them; weights `routed_scale s_e /
sum_chosen s`. `softmax` (Granite's): the `experts_per_token` largest
logits `x W_g` are chosen, weights `routed_scale softmax(chosen
logits)`; no bias, one group. The layer is told which experts it
holds (`experts_held` from `expert_offset`): it computes their part of
the result and the shared expert's, `y = FFN_shared(x) + sum_{e chosen
and held} g_e FFN_e(x)`; what the absent experts would add is left out,
as one chip of an expert-parallel deployment leaves it to the others.
An expert nobody routed to in a call is skipped, weights unread; no
token is dropped whatever the load.

`dims` is the core's own widths (`LatentMoEDims`, `HybridAttentionDims`),
which begin with this layer's: `RoutingDims`.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# The per-call counters a routed layer sows (collection 'counters');
# the inference server sums them over a call's layers.
COUNTERS = ('routed_rows_held', 'experts_hit')
ROUTERS = ('sigmoid', 'softmax')
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RoutingDims:
  """The routed layer's sizes, the first fields of a core's widths; the
  defaults are the tiny size the CPU tests run."""
  moe_size: int = 32               # an expert's width
  routed_experts: int = 16         # the router's outputs
  experts_held: int = 4            # of them computed here ...
  expert_offset: int = 0           # ... from this one on
  experts_per_token: int = 4
  expert_groups: int = 1           # one group: no limit
  expert_groups_kept: int = 1
  routed_scale: float = 2.5
  shared_experts: int = 1
  router: str = 'sigmoid'          # ROUTERS

  def check_routing(self):
    """The router's and the share's sizes fit together, or
    ValueError."""
    d = self
    group = d.routed_experts // max(d.expert_groups, 1)
    if (d.expert_groups < 1 or d.routed_experts % d.expert_groups
        or group < 2 or d.expert_groups_kept > d.expert_groups
        or d.expert_groups_kept * group < d.experts_per_token):
      raise ValueError(
          'the router chooses experts_per_token among the kept groups '
          'of at least two experts each: '
          f'{d.routed_experts} experts, {d.expert_groups} groups, '
          f'{d.expert_groups_kept} kept, {d.experts_per_token} a '
          'token')
    if d.router not in ROUTERS or (
        d.router == 'softmax' and d.expert_groups != 1):
      raise ValueError(f'router {d.router!r}: one of {ROUTERS}; the '
                       'softmax router chooses in one group')
    if not (0 <= d.expert_offset and 0 < d.experts_held and
            d.expert_offset + d.experts_held <= d.routed_experts):
      raise ValueError('the experts held lie among the routed ones')


def route(scores, bias, d):
  """scores f32 [N, routed_experts], the sigmoid outputs -> (chosen i32
  [N, k], weights f32 [N, k]). The bias moves the choice and not the
  weight; ties go to the lower index (`lax.top_k`)."""
  n, e = scores.shape
  groups = d.expert_groups
  biased = scores + bias.astype(jnp.float32)
  by_group = biased.reshape(n, groups, e // groups)
  group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
  _, kept = jax.lax.top_k(group_score, d.expert_groups_kept)
  keep = jnp.zeros((n, groups), bool).at[
      jnp.arange(n)[:, None], kept].set(True)
  among = jnp.where(jnp.repeat(keep, e // groups, axis=1), biased,
                    -jnp.inf)
  _, chosen = jax.lax.top_k(among, d.experts_per_token)
  picked = jnp.take_along_axis(scores, chosen, axis=1)
  weights = d.routed_scale * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
  return chosen.astype(jnp.int32), weights


def route_softmax(logits, d):
  """logits f32 [N, routed_experts] -> (chosen i32 [N, k], weights f32
  [N, k]): the k largest logits (ties to the lower index), their
  softmax times `routed_scale`."""
  top, chosen = jax.lax.top_k(logits, d.experts_per_token)
  return chosen.astype(jnp.int32), d.routed_scale * jax.nn.softmax(
      top, axis=-1)


def _dot(a, kernel, dtype):
  return jnp.dot(a.astype(dtype), kernel.astype(dtype),
                 preferred_element_type=jnp.float32)


def ffn(x, weights, dtype):
  gate, up, down = weights
  return _dot(jax.nn.silu(_dot(x, gate, dtype)) * _dot(x, up, dtype),
              down, dtype)


class _Kernel(nn.Module):
  """A matrix the caller multiplies itself (inside a `lax.cond`, or
  reassociated), initialised as `_Linear`'s: N(0, `variance` / fan_in)."""
  shape: Any
  param_dtype: Any = jnp.float32
  variance: float = 1.0

  @nn.compact
  def __call__(self):
    return self.param(
        'kernel', nn.initializers.variance_scaling(
            self.variance, 'fan_in', 'normal'), tuple(self.shape),
        self.param_dtype)


class _FFNWeights(nn.Module):
  hidden_size: int
  width: int
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self):
    h, w = self.hidden_size, self.width
    return (_Kernel((h, w), self.param_dtype, name='gate_proj')(),
            _Kernel((h, w), self.param_dtype, name='up_proj')(),
            _Kernel((w, h), self.param_dtype, name='down_proj')())


class RoutedExperts(nn.Module):
  """The shared expert and this chip's share of the routed ones."""
  dims: Any
  hidden_size: int
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32
  # The mean square of an element of `x` that the router's seeded
  # kernel is drawn for, N(0, 1 / (hidden x_square)): the router's
  # outputs are then of order one and its sigmoid tells the experts
  # apart. One where a norm stands before the layer.
  x_square: float = 1.0

  @nn.compact
  def __call__(self, x, live):
    """x f32 [N, hidden]; live bool [N]: the rows that are some
    session's token (a padded row routes nowhere)."""
    d = self.dims
    with jax.named_scope('moe'):
      with jax.named_scope('router'):
        w_g = _Kernel((self.hidden_size, d.routed_experts),
                      self.param_dtype, 1.0 / self.x_square,
                      name='router')()
        logits = jnp.dot(x, w_g.astype(jnp.float32), precision=HIGHEST)
        if d.router == 'softmax':
          chosen, weights = route_softmax(logits, d)
        else:
          # Seeded small and non-zero, so that the choice it moves is
          # exercised [assumed: a trained model's is learned].
          bias = self.param('e_score_correction_bias',
                            nn.initializers.normal(0.02),
                            (d.routed_experts,), jnp.float32)
          chosen, weights = route(jax.nn.sigmoid(logits), bias, d)
        local = chosen - d.expert_offset
        held = (local >= 0) & (local < d.experts_held) & live[:, None]
        gates = jnp.sum(
            jax.nn.one_hot(local, d.experts_held, dtype=jnp.float32) *
            jnp.where(held, weights, 0.0)[..., None], axis=1)  # [N, held]
        hit = jnp.any(gates > 0, axis=0)
        self.sow('counters', 'routed_rows_held',
                 jnp.sum(held).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        self.sow('counters', 'experts_hit',
                 jnp.sum(hit).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
      with jax.named_scope('shared'):
        y = ffn(x, _FFNWeights(self.hidden_size,
                               d.moe_size * d.shared_experts,
                               self.param_dtype, name='shared_expert')(),
                self.dtype)
      with jax.named_scope('experts'):
        for e in range(d.experts_held):
          expert = _FFNWeights(self.hidden_size, d.moe_size,
                               self.param_dtype, name=f'expert_{e}')()
          y = y + jax.lax.cond(
              hit[e],
              lambda w=expert, g=gates[:, e]: ffn(x, w, self.dtype) *
              g[:, None],
              lambda: jnp.zeros_like(x))
    return y



"""Power retention (degree 2) as a recurrent core.

A power-retention layer is linear attention whose similarity is
`(q.k)^2`: with `phi` the symmetric degree-2 embedding, `phi(a).phi(b) =
(a.b)^2`, so a session's whole past is a fixed state

    S_j <- g_j S_j + v_j phi(k_j)^T      Z_j <- g_j Z_j + k_j k_j^T
    o_i = S_j phi(q_i) / (q_i^T Z_j q_i + eps)

per key-value head `j` (`g_j` a sigmoid gate, `i` the query heads of
group `j`), float32, and the same in attention form is
`o_t = sum_{s<=t} G_ts (q_t.k_s)^2 v_s / (sum_{s<=t} G_ts (q_t.k_s)^2 +
eps)`, `G_ts = prod_{r=s+1..t} g_r`, which `retention_reference.py`
computes for a whole episode. A block is that layer between RMSNorms
with a SwiGLU MLP after it, both residual; `PowerRetentionStack` is N
blocks and answers `models/core.py`'s protocol: its carry is the
per-layer `(S, Z)` and the session's position (RoPE on q and k). The
normaliser `sum_s G (q.k_s)^2` is the quadratic form of `Z = sum_s G
k_s k_s^T`: the same 8,256 symmetric terms as `phi`-space's `z`, held
as the whole 128 x 128 matrix (half a megabyte a layer beside S's 34),
so that reading it needs no `phi(q)` outside the kernel.

`phi` here is tiled by wrapped diagonals instead of the upper
triangle: `phi(a)[d, i] = w_d a_i a_{(i+d) mod D}` for `d = 0..D/2`,
`w_0 = w_{D/2} = 1`, else sqrt(2). Every unordered pair appears once
(the half-way diagonal twice, at weight 1 where the triangle has one
sqrt(2)), so `phi(a).phi(b) = (a.b)^2` exactly; the layout is
`(D/2 + 1) x D`, lane-aligned at D = 128 (8,320 terms for the
triangle's 8,256, 0.8% more bytes), and a row of it is `a * roll(a)`.

Precision, as the configuration states it: parameters in `param_dtype`
(bfloat16 when served), the operands of every projection rounded to
`dtype`, accumulation, norms, RoPE, `phi`, the state and the residual
stream in float32.
"""

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.ops import retention_pallas

# The normaliser's epsilon [assumed]. Against weights (q.k)^2 of order
# one it is a hundredth; it is not smaller because the state form
# computes a weight as a float32 sum of thousands of signed products,
# exact to about 1e-7: under a smaller epsilon a head whose whole past
# weighs next to nothing (the first step of an episode, q.k near zero)
# would return that rounding, amplified, in place of next to nothing.
EPS = 1e-2


def phi(a):
  """The degree-2 embedding, `[..., D] -> [..., D/2 + 1, D]`, float32:
  `sum(phi(a) * phi(b)) = (a . b)^2`."""
  d = a.shape[-1]
  assert d % 2 == 0, d
  a = a.astype(jnp.float32)
  rows = []
  for shift in range(d // 2 + 1):
    weight = 1.0 if shift in (0, d // 2) else math.sqrt(2.0)
    rows.append(weight * a * jnp.roll(a, -shift, axis=-1))
  return jnp.stack(rows, axis=-2)


def phi_size(head_dim):
  return (head_dim // 2 + 1) * head_dim


def rope(x, pos, theta):
  """Rotary embedding of `x [B, heads, D]` at positions `pos [B]`
  (the half-rotation form of the config's Qwen3-shaped keys)."""
  d = x.shape[-1]
  inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
  cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
  sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
  x1, x2 = x[..., :d // 2], x[..., d // 2:]
  return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def rms_norm(x, scale, eps):
  x = x.astype(jnp.float32)
  var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
  return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


class _Scale(nn.Module):
  """An RMSNorm's weight."""
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, eps):
    scale = self.param('scale', nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
    return rms_norm(x, scale, eps)


class _Linear(nn.Module):
  """`x @ kernel`, no bias: operands rounded to `dtype`, accumulated
  and returned in float32."""
  features: int
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    kernel = self.param(
        'kernel', nn.initializers.variance_scaling(
            1.0, 'fan_in', 'normal'),
        (x.shape[-1], self.features), self.param_dtype)
    return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                   preferred_element_type=jnp.float32)


def _advance(s, decay, phi_k, v, phi_q):
  """The carry-form update of S, plain jax.numpy -> (s, num)."""
  s = decay[..., None, None] * s + v[..., :, None] * phi_k[..., None, :]
  num = jnp.einsum('bjnm,bjgm->bjgn', s, phi_q,
                   precision=jax.lax.Precision.HIGHEST)
  return s, num


class RetentionBlock(nn.Module):
  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  mlp_size: int
  rope_theta: float
  norm_eps: float
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, state, pos, keep, slots=None):
    """x f32 [B, hidden]; state (S, Z): rows `[B, ...]`, or with
    `slots` the arena leaves; pos i32 [B] (already reset); keep f32
    [B]: 0 where the episode just began -> (x, (s, z))."""
    b = x.shape[0]
    kv, hd = self.num_kv_heads, self.head_dim
    groups = self.num_heads // kv
    linear = lambda n, name: _Linear(  # noqa: E731
        n, self.dtype, self.param_dtype, name=name)
    norm = lambda name: _Scale(self.param_dtype, name=name)  # noqa: E731
    with jax.named_scope('retention'):
      with jax.named_scope('proj'):
        h = norm('input_norm')(x, self.norm_eps)
        q = linear(self.num_heads * hd, 'q_proj')(h)
        k = linear(kv * hd, 'k_proj')(h)
        v = linear(kv * hd, 'v_proj')(h).reshape(b, kv, hd)
        gate = jax.nn.sigmoid(linear(kv, 'g_proj')(h))
        # A scale on q.k cancels under the normaliser (up to eps); it
        # keeps (q.k)^2 of order one. [assumed]
        scale = hd ** -0.25
        q = rope(norm('q_norm')(q.reshape(b, self.num_heads, hd),
                                self.norm_eps), pos,
                 self.rope_theta) * scale
        k = rope(norm('k_norm')(k.reshape(b, kv, hd), self.norm_eps),
                 pos, self.rope_theta) * scale
        q = q.reshape(b, kv, groups, hd)
        decay = gate * keep[:, None]
      with jax.named_scope('state'):
        s, z = state
        kk = k[..., :, None] * k[..., None, :]            # [B, KV, D, D]
        if slots is None:
          s, num = _advance(s, decay, phi(k).reshape(b, kv, -1), v,
                            phi(q).reshape(b, kv, groups, -1))
          z = z_rows = decay[..., None, None] * z + kk
        else:
          # The normaliser is half a megabyte a session and layer:
          # gathered and scattered. S is 34 MB: updated where it lies,
          # by a kernel that makes phi(q) and phi(k) itself.
          z_rows = decay[..., None, None] * z[slots] + kk
          z = z.at[slots].set(z_rows, mode='drop')
          s, num = retention_pallas.update_rows(s, slots, decay, q, k, v)
        den = jnp.einsum('bjgd,bjde,bjge->bjg', q, z_rows, q,
                         precision=jax.lax.Precision.HIGHEST)
        o = num / (den[..., None] + EPS)
      with jax.named_scope('out'):
        x = x + linear(self.hidden_size, 'o_proj')(
            o.reshape(b, self.num_heads * hd))
    with jax.named_scope('mlp'):
      n = norm('post_norm')(x, self.norm_eps)
      act = jax.nn.silu(linear(self.mlp_size, 'gate_proj')(n)) * linear(
          self.mlp_size, 'up_proj')(n)
      x = x + linear(self.hidden_size, 'down_proj')(act)
    return x, (s, z)


class PowerRetentionStack(core_lib.RecurrentCore):
  """N retention blocks as one recurrent core. Carry: `{'pos': i32
  [B], 'layers': ((S f32 [B, KV, D, M], Z f32 [B, KV, D, D]), ...)}`."""
  num_layers: int
  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  mlp_size: int
  rope_theta: float = 1e6
  norm_eps: float = 1e-6
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  def _zeros(self, rows, state_rows):
    kv, hd, m = self.num_kv_heads, self.head_dim, phi_size(self.head_dim)
    return {
        'pos': jnp.zeros((rows,), jnp.int32),
        'layers': tuple(
            (jnp.zeros((state_rows, kv, hd, m), jnp.float32),
             jnp.zeros((rows, kv, hd, hd), jnp.float32))
            for _ in range(self.num_layers))}

  def initial_state(self, batch):
    return self._zeros(batch, batch)

  def arena(self, num_slots):
    """One row more in every S than there are slots: the row that
    padded rows of a merged call land in (ops/retention_pallas.py)."""
    return self._zeros(num_slots, num_slots + 1)

  @nn.compact
  def step(self, carry, x, done, slots=None):
    keep = 1.0 - done.astype(jnp.float32)
    pos = carry['pos'] if slots is None else carry['pos'][slots]
    pos = jnp.where(done, 0, pos)
    layers = []
    for i, state in enumerate(carry['layers']):
      x, state = RetentionBlock(
          self.hidden_size, self.num_heads, self.num_kv_heads,
          self.head_dim, self.mlp_size, self.rope_theta, self.norm_eps,
          self.dtype, self.param_dtype, name=f'block_{i}')(
              x, state, pos, keep, slots)
      layers.append(state)
    if slots is None:
      new_pos = pos + 1
    else:
      new_pos = carry['pos'].at[slots].set(pos + 1, mode='drop')
    return {'pos': new_pos, 'layers': tuple(layers)}, x

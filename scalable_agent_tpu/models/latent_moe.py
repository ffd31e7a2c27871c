"""Latent attention (MLA) over a per-session latent cache, with dense
and routed-expert feed-forward layers, as a recurrent core.

A block is `x + Attention(RMSNorm(x))`, then `x + FFN(RMSNorm(x))`,
the residual stream float32 (as models/retention.py keeps it).

Latent attention, token t of an episode, head i:

    c_q = RMSNorm(h W_dq)                    [q_a_proj, q_a_layernorm]
    [q_c,i ; q_r,i] = c_q W_uq               [q_b_proj]  (nope + rope)
    [c_kv ; k_r] = h W_dkv, c_kv <- RMSNorm  [kv_a_proj_with_mqa,
                                              kv_a_layernorm]
    q_r,i and k_r (one for all heads) rotated at position t (YaRN)

The cache keeps `(c_kv, k_r)` of every token of the episode: `kv_lora_
rank + qk_rope_head_dim` numbers a token and layer, in the parameters'
dtype, one `[rows, 576, capacity]` leaf a layer of the carry (of the
inference server's arena), written in place at each row's position: a
token is a COLUMN, the positions run along the lanes, which is how a
TPU lays 576-wide rows out whatever the shape says (ops/mla_pallas.py
has the reason and the price of the other way round).
`done` resets a row's POSITION; what the row held stays where it is and
is never read again, because a row reads its cache only as far as its
position.

One attention, two forms of it:

- the prefill form (`chunk`: C tokens of one session at once, causal
  inside the chunk, the cache before it) expands keys and values per
  head, `[k_c,s,i ; v_s,i] = c_kv,s W_ukv,i` [kv_b_proj], and takes
  `softmax_s<=t((q_c,i . k_c,s,i + q_r,i . k_r,s) scale) v_s,i`;
- the decode form (`step`: one token a row) is the same function
  reassociated: `q~_i = W_uk,i q_c,i` is taken to the latent's width,
  scores are `(q~_i . c_kv,s + q_r,i . k_r,s) scale`, the weighted sum
  is over the latents, `o~_i = sum_s a c_kv,s`, and `o_i = W_uv,i^T
  o~_i`, so the 576 numbers are all that is read a cached token.

`scale = (nope + rope)^-1/2 m^2`, `m = 0.1 mscale_all_dim ln(factor) +
1`. Both forms walk the cache in blocks under a running softmax: the
prefill form in XLA's own operations as far as the chunk's last token,
the decode form in a kernel that reads each row's cache as far as that
row's position (ops/mla_pallas.py).

Feed-forward (models/moe.py, shared with models/hybrid_attention.py):
the first `first_dense_layers` blocks have the dense SwiGLU width, the
others the shared expert and this chip's share of the routed ones.

Precision, as the configuration states it: parameters and cache in
`param_dtype`, the operands of every product rounded to `dtype`,
accumulated in float32; norms, rotary, the router, softmax and the
residual stream in float32.
"""

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.core import running_softmax, write_chunk
from scalable_agent_tpu.models.moe import (
    COUNTERS, RoutedExperts, RoutingDims, _FFNWeights, _Kernel, ffn)
from scalable_agent_tpu.models.retention import _Linear, _Scale
from scalable_agent_tpu.ops import mla_pallas

# Cached tokens a pass of the running softmax takes: of a row in the
# decode form (a grid step of the kernel), of the one session in the
# prefill form.
DECODE_BLOCK = 1024
PREFILL_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class LatentMoEDims(RoutingDims):
  """What the latent core adds to `SequenceAgent`'s own widths (after
  the routed layer's: models/moe.py :: RoutingDims); the defaults are
  the tiny size the CPU tests run."""
  expert_groups: int = 4
  expert_groups_kept: int = 2
  q_lora_rank: int = 24
  kv_lora_rank: int = 16
  qk_nope_head_dim: int = 8
  qk_rope_head_dim: int = 4
  v_head_dim: int = 8
  first_dense_layers: int = 1
  rope_factor: float = 40.0        # YaRN
  rope_original_max: int = 4096
  rope_beta_fast: float = 32.0
  rope_beta_slow: float = 1.0
  rope_mscale: float = 1.0
  rope_mscale_all_dim: float = 1.0
  cache_capacity: int = 64         # tokens of an episode a row holds
  prefill_chunk: int = 8           # tokens a `chunk` call takes

  @property
  def cache_width(self):
    return self.kv_lora_rank + self.qk_rope_head_dim

  def stack(self, **fields):
    """The core these widths name, for `SequenceAgent.core`."""
    return LatentMoEStack(dims=self, **fields)

  def check(self):
    self.check_routing()
    if self.qk_rope_head_dim % 2:
      raise ValueError('rotary dimensions come in pairs')
    for block in (DECODE_BLOCK, PREFILL_BLOCK):
      if self.cache_capacity % min(block, self.cache_capacity):
        raise ValueError(
            f'cache_capacity {self.cache_capacity} is not a multiple of '
            f'the attention block {block}')


def _yarn_mscale(factor, mscale):
  return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(d):
  m = _yarn_mscale(d.rope_factor, d.rope_mscale_all_dim)
  return (d.qk_nope_head_dim + d.qk_rope_head_dim) ** -0.5 * m * m


def yarn_inv_freq(d, theta):
  """The rotary frequency of each of the `qk_rope_head_dim / 2` pairs,
  float32: `theta^(-2j/dim)` for the pairs that turn more than
  `beta_fast` times in the original context, the same over `factor`
  for those that turn less than `beta_slow` times, a linear ramp
  between the two correction dimensions."""
  dim = d.qk_rope_head_dim
  extra = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
  inter = extra / d.rope_factor

  def correction_dim(rotations):
    return (dim * math.log(d.rope_original_max /
                           (rotations * 2 * math.pi)) /
            (2 * math.log(theta)))

  low = max(math.floor(correction_dim(d.rope_beta_fast)), 0)
  high = min(math.ceil(correction_dim(d.rope_beta_slow)), dim - 1)
  ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                 0.0, 1.0)
  return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate(x, pos, d, theta):
  """x f32 [N, ..., rope] at positions pos [N]: half-rotation pairing
  (dimension j with j + rope/2), YaRN frequencies and magnitude."""
  ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(d, theta)
  ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
  magnitude = (_yarn_mscale(d.rope_factor, d.rope_mscale) /
               _yarn_mscale(d.rope_factor, d.rope_mscale_all_dim))
  cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * magnitude
  sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * magnitude
  half = x.shape[-1] // 2
  return x * cos + jnp.concatenate(
      [-x[..., half:], x[..., :half]], -1) * sin


def attend_prefill(q_c, q_r, cache, slot, pos0, n_valid, w_ukv, scale,
                   dtype):
  """The prefill form for C tokens of the session in row `slot`, at
  positions `pos0..`, over the cache's columns up to each token's own.

  q_c f32 [C, H, nope], q_r f32 [C, H, rope] (rotated); cache [S,
  rank + rope, capacity]; w_ukv [rank, H, nope + v]. Returns f32 [C, H,
  v]."""
  c, heads, nope = q_c.shape
  rank = w_ukv.shape[0]
  v_dim = w_ukv.shape[2] - nope
  capacity = cache.shape[2]
  block = min(PREFILL_BLOCK, capacity)
  q_pos = pos0 + jnp.arange(c)
  last = jnp.clip(pos0 + n_valid - 1, 0, capacity - 1)
  q_c, q_r = q_c.astype(dtype), q_r.astype(dtype)

  def body(j, carry):
    tokens = jax.lax.dynamic_slice(
        cache, (slot, 0, j * block), (1, cache.shape[1], block))[0]
    expanded = jnp.einsum(
        'rs,rx->sx', tokens[:rank].astype(dtype),
        w_ukv.reshape(rank, -1).astype(dtype),
        preferred_element_type=jnp.float32).reshape(
            block, heads, nope + v_dim)
    k_c = expanded[..., :nope].astype(dtype)
    values = expanded[..., nope:].astype(dtype)
    scores = scale * (
        jnp.einsum('thd,shd->hts', q_c, k_c,
                   preferred_element_type=jnp.float32) +
        jnp.einsum('thr,rs->hts', q_r, tokens[rank:].astype(dtype),
                   preferred_element_type=jnp.float32))
    columns = j * block + jnp.arange(block)
    valid = columns[None, :] <= q_pos[:, None]
    scores = jnp.where(valid[None], scores, -jnp.inf)
    return running_softmax(
        carry, scores, lambda p: jnp.einsum(
            'hts,shv->htv', p.astype(dtype), values,
            preferred_element_type=jnp.float32))

  init = (jnp.full((heads, c), -jnp.inf, jnp.float32),
          jnp.zeros((heads, c), jnp.float32),
          jnp.zeros((heads, c, v_dim), jnp.float32))
  _, l, acc = jax.lax.fori_loop(0, last // block + 1, body, init)
  return jnp.swapaxes(acc / l[..., None], 0, 1)


class LatentMoEBlock(nn.Module):
  dims: LatentMoEDims
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float
  norm_eps: float
  dense: bool
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, cache, slots, pos, live, prefill):
    """x f32 [N, hidden]: token n lies at position `pos[n]` of the
    cache row `slots[n]` (both in range; `live[n]` false: the row is
    no session's, and routes nowhere); cache [S, rank + rope,
    capacity]. `prefill` None: N rows of N sessions, the decode form;
    `(slot, pos0, n_valid)`: N tokens of one session, the prefill
    form -> (x, cache)."""
    d = self.dims
    n, heads = x.shape[0], self.num_heads
    nope, rope, rank = d.qk_nope_head_dim, d.qk_rope_head_dim, d.kv_lora_rank
    linear = lambda f, name: _Linear(  # noqa: E731
        f, self.dtype, self.param_dtype, name=name)
    norm = lambda name: _Scale(self.param_dtype, name=name)  # noqa: E731
    with jax.named_scope('mla'):
      with jax.named_scope('proj'):
        h = norm('input_norm')(x, self.norm_eps)
        c_q = norm('q_a_layernorm')(linear(d.q_lora_rank, 'q_a_proj')(h),
                                    self.norm_eps)
        q = linear(heads * (nope + rope), 'q_b_proj')(c_q).reshape(
            n, heads, nope + rope)
        q_c = q[..., :nope]
        q_r = rotate(q[..., nope:], pos, d, self.rope_theta)
        kv = linear(rank + rope, 'kv_a_proj_with_mqa')(h)
        c_kv = norm('kv_a_layernorm')(kv[:, :rank], self.norm_eps)
        k_r = rotate(kv[:, rank:], pos, d, self.rope_theta)
        w_ukv = _Kernel((rank, heads * (nope + d.v_head_dim)),
                        self.param_dtype, name='kv_b_proj')().reshape(
                            rank, heads, nope + d.v_head_dim)
        scale = softmax_scale(d)
        if prefill is None:
          # Absorbed: the query goes to the latent's width.
          q_lat = jnp.einsum(
              'nhd,rhd->nhr', q_c.astype(self.dtype),
              w_ukv[..., :nope].astype(self.dtype),
              preferred_element_type=jnp.float32)
          q_abs = jnp.concatenate([q_lat, q_r], -1) * scale
      with jax.named_scope('cache_write'):
        entry = jnp.concatenate([c_kv, k_r], -1).astype(cache.dtype)
        if prefill is None:
          cache = mla_pallas.write_rows(cache, entry, slots, pos)
        else:
          cache = write_chunk(cache, entry, *prefill, live)
      with jax.named_scope('attend'):
        if prefill is None:
          # Each row's own cache, as far as its position and no
          # further (ops/mla_pallas.py) -> the weighted latents.
          o_lat = mla_pallas.attend_rows(
              q_abs.astype(self.dtype), cache, slots, pos, rank=rank,
              block=min(DECODE_BLOCK, cache.shape[2]))
        else:
          o = attend_prefill(q_c, q_r, cache, *prefill, w_ukv, scale,
                             self.dtype)
      with jax.named_scope('out'):
        if prefill is None:
          o = jnp.einsum('nhr,rhv->nhv', o_lat.astype(self.dtype),
                         w_ukv[..., nope:].astype(self.dtype),
                         preferred_element_type=jnp.float32)
        x = x + linear(self.hidden_size, 'o_proj')(
            o.reshape(n, heads * d.v_head_dim))
    h = norm('post_norm')(x, self.norm_eps)
    if self.dense:
      with jax.named_scope('mlp'):
        x = x + ffn(h, _FFNWeights(self.hidden_size, self.mlp_size,
                                   self.param_dtype, name='mlp')(),
                    self.dtype)
    else:
      x = x + RoutedExperts(d, self.hidden_size, self.dtype,
                            self.param_dtype, name='moe')(h, live)
    return x, cache


class LatentMoEStack(core_lib.PositionedCore):
  """N latent-attention blocks as one recurrent core. Carry: `{'pos':
  i32 [B], 'layers': (cache [B, rank + rope, capacity] param-dtype,
  ...)}`; the arena is the same with a row a slot and one more,
  advanced in place (models/core.py :: PositionedCore)."""
  num_layers: int
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float = 1e4
  norm_eps: float = 1e-6
  dims: LatentMoEDims = LatentMoEDims()
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  counters = COUNTERS

  @property
  def chunk_size(self):
    return self.dims.prefill_chunk

  @property
  def cache_capacity(self):
    return self.dims.cache_capacity

  def initial_state(self, batch):
    d = self.dims
    return {
        'pos': jnp.zeros((batch,), jnp.int32),
        'layers': tuple(
            jnp.zeros((batch, d.cache_width, d.cache_capacity),
                      self.param_dtype)
            for _ in range(self.num_layers))}

  @nn.compact
  def _blocks(self, x, caches, slots, pos, live, prefill):
    """THE compact method: both forms run the same blocks."""
    new = []
    for i, cache in enumerate(caches):
      x, cache = LatentMoEBlock(
          self.dims, self.hidden_size, self.num_heads, self.mlp_size,
          self.rope_theta, self.norm_eps,
          i < self.dims.first_dense_layers, self.dtype, self.param_dtype,
          name=f'block_{i}')(x, cache, slots, pos, live, prefill)
      new.append(cache)
    return x, tuple(new)

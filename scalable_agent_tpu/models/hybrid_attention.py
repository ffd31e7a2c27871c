"""Grouped-query attention in a pattern of window layers and full
layers, two kinds of cache a session, with dense and routed-expert
feed-forward layers, as a recurrent core.

A block is `x + RMSNorm(Attention(x))`, then `x + RMSNorm(FFN(x))`: the
two norms sit on the OUTPUTS, the residual stream float32.

Attention, token t of an episode, query head i in key-value group
`g = i // (heads / kv_heads)`:

    q_i = RMSNorm_q(h W_q,i)   k_g = RMSNorm_k(h W_k,g)   v_g = h W_v,g
    window layer:  q_i, k_g rotated at position t (half-rotation);
                   o_i = sum over s in (t - window, t] of
                         softmax_s(q_i . k_g,s / sqrt(head_dim)) v_g,s
    full layer:    no rotation; the same sum over every s <= t
    attention(h) = concat_i(o_i) W_o

The kind of layer i is letter `i mod len` of `layer_pattern`: `L` a
window layer, `G` a full one. A session's state is one leaf a layer of
the carry (of the inference server's arena), `[rows, 2 G D, columns]`
in the parameters' dtype, a token a COLUMN (the G keys of D numbers,
then the G values), the positions along the lanes (ops/gqa_pallas.py):

- a full layer keeps every token of the episode, `cache_capacity`
  columns, token t in column t;
- a window layer keeps a ring of `window` columns, token t in column
  `t mod window`: whatever the episode's length, its last `window`
  tokens. A ring read before it is full, or after a reset, sees only
  the columns its own episode wrote, because which columns count
  follows from the row's position alone.

`done` resets a row's POSITION, never the rows (models/core.py ::
PositionedCore). One attention, two forms of it: the decode form
(`step`: one token a row) writes the token's column and reads the
row's cache in a kernel, as far as the row's position (the ring: as far
as `min(position, window - 1)`); the prefill form (`chunk`: C tokens of
one session) walks a full layer's cache in blocks under a running
softmax as far as the chunk's last token, and in a window layer attends
to the ring's columns as they were before the chunk and to the chunk
itself, causal and windowed, then leaves the chunk's last `window`
tokens in the ring.

Feed-forward (models/moe.py, shared with models/latent_moe.py): the
first `first_dense_layers` blocks have the dense SwiGLU width, the
others the shared expert and this chip's share of the routed ones.

Precision, as the configuration states it: parameters and both caches
in `param_dtype`, the operands of every product rounded to `dtype`,
accumulated in float32; norms, rotary, the router, softmax and the
residual stream in float32.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.core import running_softmax, write_chunk
from scalable_agent_tpu.models.moe import (
    COUNTERS, RoutedExperts, RoutingDims, _FFNWeights, ffn)
from scalable_agent_tpu.models.retention import _Linear, _Scale, rope
from scalable_agent_tpu.ops import gqa_pallas

# Cached tokens a pass of the running softmax takes: of a row in the
# decode form (a grid step of the kernel), of the one session in the
# prefill form.
DECODE_BLOCK = 1024
PREFILL_BLOCK = 1024
KINDS = 'LG'  # a window layer, a full layer


@dataclasses.dataclass(frozen=True)
class HybridAttentionDims(RoutingDims):
  """What this core adds to `SequenceAgent`'s own widths (after the
  routed layer's: models/moe.py :: RoutingDims); the defaults are the
  tiny size the CPU tests run."""
  num_kv_heads: int = 2
  head_dim: int = 16
  layer_pattern: str = 'LLLG'      # one letter a layer, repeated
  window: int = 8                  # the token and the window - 1 before
  first_dense_layers: int = 1
  cache_capacity: int = 64         # tokens of an episode a full layer holds
  prefill_chunk: int = 8           # tokens a `chunk` call takes

  @property
  def cache_width(self):
    return 2 * self.num_kv_heads * self.head_dim

  def kind(self, layer):
    return self.layer_pattern[layer % len(self.layer_pattern)]

  def stack(self, **fields):
    """The core these widths name, for `SequenceAgent.core`."""
    return HybridAttentionStack(dims=self, **fields)

  def check(self):
    self.check_routing()
    if not self.layer_pattern or set(self.layer_pattern) - set(KINDS):
      raise ValueError(
          f'layer_pattern {self.layer_pattern!r}: one letter a layer, '
          '`L` a window layer, `G` a full one')
    if self.head_dim % 2:
      raise ValueError('rotary dimensions come in pairs')
    for columns in (self.cache_capacity, self.window):
      for block in (128, DECODE_BLOCK, PREFILL_BLOCK):
        if columns < 1 or columns % min(block, columns):
          raise ValueError(
              f'cache_capacity {self.cache_capacity} and window '
              f'{self.window} are whole blocks of {block} columns, or '
              'fewer')


def attend_chunk_full(q, cache, slot, pos0, n_valid, scale, dtype):
  """The prefill form of a full layer for C tokens of the session in
  row `slot`, at positions `pos0..`, over the cache's columns up to
  each token's own (the chunk's are written already).

  q f32 [C, G, H / G, D]; cache [S, 2 G D, capacity]. Returns f32 [C,
  G, H / G, D]."""
  c, groups, per, dim = q.shape
  capacity = cache.shape[2]
  block = min(PREFILL_BLOCK, capacity)
  q_pos = pos0 + jnp.arange(c)
  last = jnp.clip(pos0 + n_valid - 1, 0, capacity - 1)
  q = q.astype(dtype)

  def body(j, carry):
    tokens = jax.lax.dynamic_slice(
        cache, (slot, 0, j * block), (1, cache.shape[1], block))[0]
    tokens = tokens.reshape(2, groups, dim, block).astype(dtype)
    scores = scale * jnp.einsum('tgid,gds->gits', q, tokens[0],
                                preferred_element_type=jnp.float32)
    columns = j * block + jnp.arange(block)
    valid = columns[None, :] <= q_pos[:, None]
    scores = jnp.where(valid[None, None], scores, -jnp.inf)
    return running_softmax(
        carry, scores, lambda p: jnp.einsum(
            'gits,gds->gitd', p.astype(dtype), tokens[1],
            preferred_element_type=jnp.float32))

  init = (jnp.full((groups, per, c), -jnp.inf, jnp.float32),
          jnp.zeros((groups, per, c), jnp.float32),
          jnp.zeros((groups, per, c, dim), jnp.float32))
  _, l, acc = jax.lax.fori_loop(0, last // block + 1, body, init)
  return jnp.moveaxis(acc / l[..., None], 2, 0)


def attend_chunk_window(q, entry, ring, pos0, scale, dtype):
  """The prefill form of a window layer: C tokens at positions `pos0..`
  attend to the ring's columns as they were BEFORE the chunk (column c
  holds the newest position below `pos0` that is c modulo the window,
  if the episode has one) and to the chunk itself, causal and inside
  the window.

  q f32 [C, G, H / G, D]; entry [C, 2 G D], the chunk's keys and values
  as the cache would hold them; ring [2 G D, window]. Returns f32 [C, G,
  H / G, D]."""
  c, groups, _, dim = q.shape
  window = ring.shape[1]
  before = pos0 - 1 - jnp.mod(pos0 - 1 - jnp.arange(window), window)
  key_pos = jnp.concatenate([before, pos0 + jnp.arange(c)])
  q_pos = pos0 + jnp.arange(c)
  tokens = jnp.concatenate([ring, entry.T], axis=1).reshape(
      2, groups, dim, window + c).astype(dtype)
  scores = scale * jnp.einsum('tgid,gds->gits', q.astype(dtype), tokens[0],
                              preferred_element_type=jnp.float32)
  valid = ((key_pos[None, :] >= 0) & (key_pos[None, :] <= q_pos[:, None]) &
           (q_pos[:, None] - key_pos[None, :] < window))
  a = jax.nn.softmax(jnp.where(valid[None, None], scores, -jnp.inf),
                     axis=-1)
  return jnp.einsum('gits,gds->tgid', a.astype(dtype), tokens[1],
                    preferred_element_type=jnp.float32)


def write_ring(cache, entry, slot, pos0, n_valid, live):
  """The ring of row `slot` after the chunk: column c takes the newest
  of the chunk's first `n_valid` tokens (`live [C]`) whose position is
  c modulo the window, and keeps what it held where there is none; the
  row written back whole, in place."""
  c, window = entry.shape[0], cache.shape[2]
  end = pos0 + n_valid
  newest = end - 1 - jnp.mod(end - 1 - jnp.arange(window), window)
  token = jnp.clip(newest - pos0, 0, c - 1)
  take = (newest >= pos0) & live[token]
  at = (slot, 0, 0)
  ring = jax.lax.dynamic_slice(cache, at, (1,) + cache.shape[1:])
  new = jnp.take(entry, token, axis=0).T
  return jax.lax.dynamic_update_slice(
      cache, jnp.where(take[None, None, :], new[None], ring), at)


class HybridAttentionBlock(nn.Module):
  dims: HybridAttentionDims
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float
  norm_eps: float
  full: bool
  dense: bool
  x_square: float  # of the stream the router reads (models/moe.py)
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, cache, slots, pos, live, prefill):
    """x f32 [N, hidden]: token n lies at position `pos[n]` of the
    cache row `slots[n]` (models/core.py :: PositionedCore._blocks);
    cache [S, 2 G D, capacity or window] -> (x, cache)."""
    d = self.dims
    n, heads, groups, dim = (x.shape[0], self.num_heads, d.num_kv_heads,
                             d.head_dim)
    linear = lambda f, name: _Linear(  # noqa: E731
        f, self.dtype, self.param_dtype, name=name)
    norm = lambda name: _Scale(self.param_dtype, name=name)  # noqa: E731
    scale = dim ** -0.5
    with jax.named_scope('gqa'):
      with jax.named_scope('proj'):
        q = norm('q_norm')(linear(heads * dim, 'q_proj')(x).reshape(
            n, heads, dim), self.norm_eps)
        k = norm('k_norm')(linear(groups * dim, 'k_proj')(x).reshape(
            n, groups, dim), self.norm_eps)
        v = linear(groups * dim, 'v_proj')(x)
        if not self.full:
          q = rope(q, pos, self.rope_theta)
          k = rope(k, pos, self.rope_theta)
        q = q.reshape(n, groups, heads // groups, dim)
        entry = jnp.concatenate([k.reshape(n, groups * dim), v],
                                -1).astype(cache.dtype)
      if prefill is None:
        # Each row's own cache, written at its column and read as far
        # as its position and no further (ops/gqa_pallas.py).
        with jax.named_scope('cache_write'):
          cache = gqa_pallas.write_rows(
              cache, entry, slots, pos if self.full else pos % d.window)
        with jax.named_scope('attend_full' if self.full
                             else 'attend_window'):
          o = gqa_pallas.attend_rows(
              q.astype(self.dtype), cache, slots,
              pos if self.full else jnp.minimum(pos, d.window - 1),
              scale=scale, block=min(DECODE_BLOCK, cache.shape[2]))
      elif self.full:
        with jax.named_scope('cache_write'):
          cache = write_chunk(cache, entry, *prefill, live)
        with jax.named_scope('attend_full'):
          o = attend_chunk_full(q, cache, *prefill, scale, self.dtype)
      else:
        slot, pos0, n_valid = prefill
        with jax.named_scope('attend_window'):
          ring = jax.lax.dynamic_slice(
              cache, (slot, 0, 0), (1,) + cache.shape[1:])[0]
          o = attend_chunk_window(q, entry, ring, pos0, scale, self.dtype)
        with jax.named_scope('cache_write'):
          cache = write_ring(cache, entry, slot, pos0, n_valid, live)
      with jax.named_scope('out'):
        x = x + norm('post_attention_norm')(
            linear(self.hidden_size, 'o_proj')(o.reshape(n, heads * dim)),
            self.norm_eps)
    if self.dense:
      with jax.named_scope('mlp'):
        y = ffn(x, _FFNWeights(self.hidden_size, self.mlp_size,
                               self.param_dtype, name='mlp')(), self.dtype)
    else:
      y = RoutedExperts(d, self.hidden_size, self.dtype, self.param_dtype,
                        self.x_square, name='moe')(x, live)
    return x + norm('post_ffn_norm')(y, self.norm_eps), cache


class HybridAttentionStack(core_lib.PositionedCore):
  """N blocks as one recurrent core. Carry: `{'pos': i32 [B], 'layers':
  (leaf, ...)}`, a full layer's leaf `[B, 2 G D, cache_capacity]`, a
  window layer's `[B, 2 G D, window]`, both in the parameters' dtype;
  the arena is the same with a row a slot and one more, advanced in
  place (models/core.py :: PositionedCore)."""
  num_layers: int
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float = 1e6
  norm_eps: float = 1e-5
  dims: HybridAttentionDims = HybridAttentionDims()
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  counters = COUNTERS

  @property
  def chunk_size(self):
    return self.dims.prefill_chunk

  @property
  def cache_capacity(self):
    return self.dims.cache_capacity

  @property
  def cache_window(self):
    return self.dims.window

  def initial_state(self, batch):
    d = self.dims
    return {
        'pos': jnp.zeros((batch,), jnp.int32),
        'layers': tuple(
            jnp.zeros((batch, d.cache_width,
                       d.cache_capacity if d.kind(i) == 'G' else d.window),
                      self.param_dtype)
            for i in range(self.num_layers))}

  @nn.compact
  def _blocks(self, x, caches, slots, pos, live, prefill):
    """THE compact method: both forms run the same blocks. The norms
    sit on the outputs, so block i's router reads the stream as it is:
    as seeded (embedding N(0, 1), norm weights 1) the embedding and
    2 i + 1 normed outputs, a mean square of 2 i + 2."""
    d = self.dims
    new = []
    for i, cache in enumerate(caches):
      x, cache = HybridAttentionBlock(
          d, self.hidden_size, self.num_heads, self.mlp_size,
          self.rope_theta, self.norm_eps, d.kind(i) == 'G',
          i < d.first_dense_layers, 2.0 * i + 2.0, self.dtype,
          self.param_dtype, name=f'block_{i}')(
              x, cache, slots, pos, live, prefill)
      new.append(cache)
    return x, tuple(new)

"""Mamba-2 state-space layers beside full grouped-query attention layers
in a per-layer pattern, with routed experts in every layer, as a
recurrent core: Granite 4.0-H's hybrid.

A block is pre-norm with a residual multiplier `r` on each sublayer:

    h = x + r Mixer(RMSNorm(x))
    out = h + r (Shared(RMSNorm(h)) + Routed(RMSNorm(h)))

The kind of layer i is letter `i mod len` of `layer_pattern`: `M` a
Mamba-2 mixer (models/mamba2.py), `G` full attention without position
encoding: grouped-query, scores `attention_multiplier q . k`, over every
token of the episode, in the two forms and by the kernel that the core
of window and full layers runs (models/hybrid_attention.py ::
attend_chunk_full, ops/gqa_pallas.py), imported. The feed-forward is
models/moe.py's routed layer with its softmax router (`router`): the
shared expert and this chip's share of the routed ones. The embedding
is scaled by `embedding_multiplier`, and the head is the embedding
itself (tied) with its logits divided by `logits_scaling`: the agent
reads those from these widths (models/sequence.py).

A session's state is, in one row of the carry (of the inference
server's arena), of fixed size in an `M` layer, `(conv window [rows,
K - 1, H P + 2 N] in the parameters' dtype, S [rows, H, P, N] float32)`,
and growing in a `G` layer, `[rows, 2 G D, cache_capacity]` in the
parameters' dtype, a token a column (ops/gqa_pallas.py). `done` resets
the row's position; a Mamba layer takes a row at position 0 as empty,
so its leaves start again from zero (models/core.py :: PositionedCore).

Precision, as the configuration states it: parameters and the cache in
`param_dtype`, the operands of every product rounded to `dtype`,
accumulated in float32; norms, the conv, the state-space recurrence
and its state, the router, softmax and the residual stream in float32.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.core import write_chunk
from scalable_agent_tpu.models.hybrid_attention import (
    DECODE_BLOCK, PREFILL_BLOCK, attend_chunk_full)
from scalable_agent_tpu.models.mamba2 import COUNTER, Mamba2Mixer
from scalable_agent_tpu.models.moe import COUNTERS, RoutedExperts, RoutingDims
from scalable_agent_tpu.models.retention import _Linear, _Scale
from scalable_agent_tpu.ops import gqa_pallas

KINDS = 'MG'  # a Mamba-2 layer, a full attention layer


@dataclasses.dataclass(frozen=True)
class SSMHybridDims(RoutingDims):
  """What this core adds to `SequenceAgent`'s own widths (after the
  routed layer's: models/moe.py :: RoutingDims); the defaults are the
  tiny size the CPU tests run."""
  num_kv_heads: int = 2
  head_dim: int = 16
  layer_pattern: str = 'MMG'     # one letter a layer, repeated
  ssm_heads: int = 4
  ssm_head_dim: int = 8
  ssm_state: int = 128
  conv_width: int = 4
  ssm_block: int = 4             # tokens the chunk form takes at once
  embedding_multiplier: float = 1.0
  residual_multiplier: float = 1.0
  attention_multiplier: float = 0.0625
  logits_scaling: float = 1.0
  cache_capacity: int = 64       # tokens of an episode a `G` layer holds
  prefill_chunk: int = 8         # tokens a `chunk` call takes

  # The head is the embedding (models/sequence.py), seeded N(0, 0.02^2)
  # [assumed: Transformers' default `initializer_range`]. Drawn N(0, 1),
  # the stream of `embedding_multiplier` E[token] would outweigh every
  # layer's output, and the tied head would give the token its own id
  # back with probability one, a policy that echoes its input.
  tied_embeddings = True
  embedding_init = 0.02

  @property
  def cache_width(self):
    return 2 * self.num_kv_heads * self.head_dim

  @property
  def conv_channels(self):
    return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_state

  def kind(self, layer):
    return self.layer_pattern[layer % len(self.layer_pattern)]

  def stack(self, **fields):
    """The core these widths name, for `SequenceAgent.core`."""
    return SSMHybridStack(dims=self, **fields)

  def check(self):
    self.check_routing()
    if not self.layer_pattern or set(self.layer_pattern) - set(KINDS):
      raise ValueError(
          f'layer_pattern {self.layer_pattern!r}: one letter a layer, '
          '`M` a Mamba-2 layer, `G` a full attention layer')
    if self.prefill_chunk % min(self.ssm_block, self.prefill_chunk):
      raise ValueError(f'a chunk of {self.prefill_chunk} tokens is whole '
                       f'blocks of {self.ssm_block}')
    columns = self.cache_capacity
    for block in (128, DECODE_BLOCK, PREFILL_BLOCK):
      if columns < 1 or columns % min(block, columns):
        raise ValueError(f'cache_capacity {columns} is whole blocks of '
                         f'{block} columns, or fewer')


class SSMHybridBlock(nn.Module):
  dims: SSMHybridDims
  hidden_size: int
  num_heads: int
  norm_eps: float
  mamba: bool
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  def _attention(self, h, cache, rows, pos, live, prefill):
    """Full grouped-query attention without position encoding."""
    d = self.dims
    n, heads = h.shape[0], self.num_heads
    groups, dim = d.num_kv_heads, d.head_dim
    linear = lambda f, name: _Linear(  # noqa: E731
        f, self.dtype, self.param_dtype, name=name)
    with jax.named_scope('gqa'):
      with jax.named_scope('proj'):
        q = linear(heads * dim, 'q_proj')(h).reshape(
            n, groups, heads // groups, dim)
        entry = jnp.concatenate([linear(groups * dim, 'k_proj')(h),
                                 linear(groups * dim, 'v_proj')(h)],
                                -1).astype(cache.dtype)
      with jax.named_scope('cache_write'):
        cache = (gqa_pallas.write_rows(cache, entry, rows, pos)
                 if prefill is None else
                 write_chunk(cache, entry, *prefill, live))
      with jax.named_scope('attend_full'):
        if prefill is None:
          o = gqa_pallas.attend_rows(
              q.astype(self.dtype), cache, rows, pos,
              scale=d.attention_multiplier,
              block=min(DECODE_BLOCK, cache.shape[2]))
        else:
          o = attend_chunk_full(q, cache, *prefill, d.attention_multiplier,
                                self.dtype)
      with jax.named_scope('out'):
        out = linear(self.hidden_size, 'o_proj')(o.reshape(n, heads * dim))
    return out, cache

  @nn.compact
  def __call__(self, x, leaf, rows, pos, live, prefill):
    """x f32 [N, hidden]: token n lies at position `pos[n]` of the arena
    row `rows[n]` (models/core.py :: PositionedCore._blocks); leaf: the
    layer's (conv, S) or cache -> (x, leaf)."""
    d = self.dims
    r = d.residual_multiplier
    norm = lambda name: _Scale(self.param_dtype, name=name)  # noqa: E731
    h = norm('input_norm')(x, self.norm_eps)
    if self.mamba:
      out, conv, ssm = Mamba2Mixer(
          d, self.hidden_size, self.norm_eps, self.dtype, self.param_dtype,
          name='mamba')(h, *leaf, rows, pos, live, prefill)
      leaf = (conv, ssm)
    else:
      out, leaf = self._attention(h, leaf, rows, pos, live, prefill)
    x = x + r * out
    y = RoutedExperts(d, self.hidden_size, self.dtype, self.param_dtype,
                      name='moe')(norm('post_norm')(x, self.norm_eps), live)
    return x + r * y, leaf


class SSMHybridStack(core_lib.PositionedCore):
  """N blocks as one recurrent core. Carry: `{'pos': i32 [B], 'layers':
  (leaf, ...)}`, an `M` layer's leaf `(conv [B, K - 1, H P + 2 N],
  S f32 [B, H, P, N])`, a `G` layer's `[B, 2 G D, cache_capacity]`; the
  arena is the same with a row a slot and one more, advanced in place
  (models/core.py :: PositionedCore)."""
  num_layers: int
  hidden_size: int
  num_heads: int
  mlp_size: int = 0          # no dense layer: every block routes
  rope_theta: float = 0.0    # no position encoding
  norm_eps: float = 1e-5
  dims: SSMHybridDims = SSMHybridDims()
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  counters = COUNTERS + (COUNTER,)

  @property
  def chunk_size(self):
    return self.dims.prefill_chunk

  @property
  def cache_capacity(self):
    return self.dims.cache_capacity

  def initial_state(self, batch):
    d = self.dims

    def leaf(kind):
      if kind == 'G':
        return jnp.zeros((batch, d.cache_width, d.cache_capacity),
                         self.param_dtype)
      return (jnp.zeros((batch, d.conv_width - 1, d.conv_channels),
                        self.param_dtype),
              jnp.zeros((batch, d.ssm_heads, d.ssm_head_dim, d.ssm_state),
                        jnp.float32))

    return {'pos': jnp.zeros((batch,), jnp.int32),
            'layers': tuple(leaf(d.kind(i))
                            for i in range(self.num_layers))}

  @nn.compact
  def _blocks(self, x, leaves, rows, pos, live, prefill):
    """THE compact method: both forms run the same blocks."""
    d = self.dims
    new = []
    for i, leaf in enumerate(leaves):
      x, leaf = SSMHybridBlock(
          d, self.hidden_size, self.num_heads, self.norm_eps,
          d.kind(i) == 'M', self.dtype, self.param_dtype,
          name=f'block_{i}')(x, leaf, rows, pos, live, prefill)
      new.append(leaf)
    return x, tuple(new)

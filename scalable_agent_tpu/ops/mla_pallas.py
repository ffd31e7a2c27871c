"""Latent attention's decode form over the rows of a cache arena: each
live row of a merged call writes its token's latent into ITS session's
cache (`write_rows`) and reads that cache as far as its own position
and no further (`attend_rows`).

For row `n` (a session whose cache is row `slots[n]` of the arena),
with `q[n]` each head's query already taken to the latent's width,
rotary part appended and scaled (models/latent_moe.py):

    a_h,s = softmax_{s <= pos[n]}(q[n, h] . cache[slots[n], :, s])
    out[n, h] = sum_s a_h,s cache[slots[n], :rank, s]

The arena leaf is `[slots, W, capacity]`: a session's cache is a matrix
with the TOKENS along the lanes, the latent's `W = rank + rope`
numbers along the sublanes. That is how a TPU lays a `[slots, capacity,
576]` array out anyway (576 is no multiple of the 128 lanes, 16,384
is), and a program that indexes it the other way round pays a copy of
the whole leaf in and out, 1.2 GB a layer and call (PERF.md section 6,
PR 32: the compiler's own text). Here a token is a column, a block of
tokens `[W, block]` is whole tiles, the scores `q [H, W] x block` are a
plain product and the weighted sum contracts over the lanes.

The cache is the largest thing a call touches after the weights (1.1 KB
a token and layer; gigabytes over 32 sessions), and the rows differ in
length several-fold. XLA's form of this walk gathers a block of every
row's cache into a buffer and reads the buffer twice, as far as the
LONGEST row needs. The kernel takes the arena as it lies: the grid is
(rows, blocks of the capacity), the block a step reads is picked by the
scalar-prefetched slot id and position, a step beyond the row's
position names the block the step before it read (so nothing is
fetched for it) and does no arithmetic. All heads share the one latent
a token, so a block fetched once serves 128 heads: scores `[H, block]`
and the weighted sum `[H, rank]` are two MXU products a block under a
running softmax held in fast memory.

`write_rows` puts a call's new tokens where they belong, a token one
column of 576 numbers: ops/cache_columns.py's column write, which knows
no width, under this cache's name in the trace.

On the CPU the same kernel runs interpreted (the tests' path).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scalable_agent_tpu.ops import cache_columns

# The kernels' names in the device trace's operation names.
KERNEL_NAME = 'mla_decode_attend'
WRITE_KERNEL_NAME = 'mla_cache_write'
_LANES = cache_columns.LANES


def _kernel(slots_ref, pos_ref, q_ref, cache_ref, out_ref, m_ref, l_ref,
            acc_ref, *, block, rank):
  del slots_ref  # used by the index maps only
  n, j = pl.program_id(0), pl.program_id(1)
  last = pos_ref[n]

  @pl.when(j == 0)
  def _():
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

  @pl.when(j * block <= last)
  def _():
    q = q_ref[0]                                     # [H, W]
    tokens = cache_ref[0].astype(q.dtype)            # [W, block]
    scores = jnp.dot(q, tokens,
                     preferred_element_type=jnp.float32)  # [H, block]
    columns = j * block + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(columns <= last, scores, -jnp.inf)
    m_prev = m_ref[...]                              # [H, LANES], lanes alike
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = corr[:, :1] * acc_ref[...] + jax.lax.dot_general(
        p.astype(tokens.dtype), tokens[:rank], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [H, rank]
    m_ref[...] = m_new

  @pl.when(j == pl.num_programs(1) - 1)
  def _():
    out_ref[0] = acc_ref[...] / l_ref[...][:, :1]


# The column write under the latent cache's name in the trace.
write_rows = functools.partial(cache_columns.write_rows,
                               name=WRITE_KERNEL_NAME)


@functools.partial(jax.jit, static_argnames=('rank', 'block'))
def attend_rows(q, cache, slots, pos, *, rank, block):
  """q [N, H, W], in the dtype the products' operands are rounded to;
  cache [S, W, capacity]; slots i32 [N], every id IN RANGE; pos i32
  [N], the last column row n reads (below the capacity). Returns f32
  [N, H, rank]."""
  n, heads, width = q.shape
  capacity = cache.shape[2]
  assert capacity % block == 0, (capacity, block)

  def cache_block(i, j, slots_ref, pos_ref):
    # Beyond the row's position: the block already in hand.
    return slots_ref[i], 0, jnp.minimum(j, pos_ref[i] // block)

  return pl.pallas_call(
      functools.partial(_kernel, block=block, rank=rank),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(n, capacity // block),
          in_specs=[
              pl.BlockSpec((1, heads, width), lambda i, j, *_: (i, 0, 0)),
              pl.BlockSpec((1, width, block), cache_block)],
          out_specs=pl.BlockSpec((1, heads, rank),
                                 lambda i, j, *_: (i, 0, 0)),
          scratch_shapes=[pltpu.VMEM((heads, _LANES), jnp.float32),
                          pltpu.VMEM((heads, _LANES), jnp.float32),
                          pltpu.VMEM((heads, rank), jnp.float32)]),
      out_shape=jax.ShapeDtypeStruct((n, heads, rank), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'arbitrary')),
      interpret=cache_columns.interpret_on(jax.default_backend()),
      name=KERNEL_NAME,
  )(slots, pos, q, cache)

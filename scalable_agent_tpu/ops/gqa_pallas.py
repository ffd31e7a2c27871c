"""Grouped-query attention's decode form over the rows of a cache
arena: each live row of a merged call writes its token's keys and
values into ITS session's cache (`write_rows`) and reads that cache as
far as its own position and no further (`attend_rows`).

For row `n` (a session whose cache is row `slots[n]` of the arena),
query head `i` of key-value group `g = i // (H / G)`:

    a_i,s = softmax_{s <= last[n]}(scale q[n, i] . K[slots[n], g, :, s])
    out[n, i] = sum_s a_i,s V[slots[n], g, :, s]

The arena leaf is `[slots, 2 G D, capacity]`: a session's cache is a
matrix with the TOKENS along the lanes, a token a column: the G keys of
D numbers, then the G values, along the sublanes (ops/mla_pallas.py has
the reason and the price of the other way round). The same leaf serves
a layer that keeps every token of the episode (capacity columns, `last`
the row's position) and a layer that keeps a ring of its last W tokens
(W columns, the token at position p in column p mod W, `last = min(pos,
W - 1)`: before the ring is full, or after a reset, only the columns
this episode wrote): keys are rotated before they are cached, so a
softmax over the ring needs no order.

It is not `mla_decode_attend` with other numbers. There one 576-wide
latent a token serves 128 heads and is its own value: 228 FLOP a byte,
at the chip's ridge. Here 8 pairs of K and V a token serve 8 query
heads each: 8 FLOP a byte, bound by bytes alone, and the products are 8
rows tall. The grid is (rows, blocks of the capacity); the block a
step reads is picked by the scalar-prefetched slot id and position, a
step beyond the row's position names the block the step before it read
(so nothing is fetched for it) and does no arithmetic. A step fetches
one block of ALL G keys and one of all G values (two views of the one
leaf), so that a step moves megabytes, and takes the groups in turn:
scores `[H / G, block]` and the weighted sum `[H / G, D]` are two
products a group under a running softmax held in fast memory.

`write_rows` is ops/cache_columns.py's column write under this cache's
name in the trace: a token is one column, `2 G D` numbers at a stride
of the capacity.

On the CPU the same kernels run interpreted (the tests' path).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scalable_agent_tpu.ops import cache_columns

# The kernels' names in the device trace's operation names.
KERNEL_NAME = 'gqa_decode_attend'
WRITE_KERNEL_NAME = 'gqa_cache_write'
_LANES = cache_columns.LANES

write_rows = functools.partial(cache_columns.write_rows,
                               name=WRITE_KERNEL_NAME)


def _kernel(slots_ref, last_ref, q_ref, k_ref, v_ref, out_ref, m_ref,
            l_ref, acc_ref, *, block, groups, scale):
  del slots_ref  # used by the index maps only
  n, j = pl.program_id(0), pl.program_id(1)
  last = last_ref[n]
  per, dim = q_ref.shape[2], q_ref.shape[3]

  @pl.when(j == 0)
  def _():
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

  @pl.when(j * block <= last)
  def _():
    columns = j * block + jax.lax.broadcasted_iota(
        jnp.int32, (per, block), 1)
    for g in range(groups):
      heads = slice(g * per, (g + 1) * per)
      q = q_ref[0, g]                                  # [per, D]
      keys = k_ref[0, g * dim:(g + 1) * dim, :].astype(q.dtype)
      values = v_ref[0, g * dim:(g + 1) * dim, :].astype(q.dtype)
      scores = scale * jnp.dot(
          q, keys, preferred_element_type=jnp.float32)  # [per, block]
      scores = jnp.where(columns <= last, scores, -jnp.inf)
      m_prev = m_ref[heads]                   # [per, LANES], lanes alike
      m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
      p = jnp.exp(scores - m_new[:, :1])
      corr = jnp.exp(m_prev - m_new)
      l_ref[heads] = corr * l_ref[heads] + jnp.sum(p, axis=1,
                                                   keepdims=True)
      acc_ref[heads] = corr[:, :1] * acc_ref[heads] + jax.lax.dot_general(
          p.astype(values.dtype), values, (((1,), (1,)), ((), ())),
          preferred_element_type=jnp.float32)          # [per, D]
      m_ref[heads] = m_new

  @pl.when(j == pl.num_programs(1) - 1)
  def _():
    out_ref[0] = acc_ref[...] / l_ref[...][:, :1]


@functools.partial(jax.jit, static_argnames=('scale', 'block'))
def attend_rows(q, cache, slots, last, *, scale, block):
  """q [N, G, H / G, D], in the dtype the products' operands are rounded
  to; cache [S, 2 G D, capacity]; slots i32 [N], every id IN RANGE; last
  i32 [N], the last column row n reads (below the capacity). Returns
  f32 [N, H, D]."""
  n, groups, per, dim = q.shape
  capacity = cache.shape[2]
  assert cache.shape[1] == 2 * groups * dim, (cache.shape, q.shape)
  assert capacity % block == 0, (capacity, block)

  def block_of(half):
    def index(i, j, slots_ref, last_ref):
      # Beyond the row's position: the block already in hand.
      return slots_ref[i], half, jnp.minimum(j, last_ref[i] // block)
    return pl.BlockSpec((1, groups * dim, block), index)

  heads = groups * per
  return pl.pallas_call(
      functools.partial(_kernel, block=block, groups=groups, scale=scale),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(n, capacity // block),
          in_specs=[
              pl.BlockSpec((1, groups, per, dim),
                           lambda i, j, *_: (i, 0, 0, 0)),
              block_of(0), block_of(1)],
          out_specs=pl.BlockSpec((1, heads, dim),
                                 lambda i, j, *_: (i, 0, 0)),
          scratch_shapes=[pltpu.VMEM((heads, _LANES), jnp.float32),
                          pltpu.VMEM((heads, _LANES), jnp.float32),
                          pltpu.VMEM((heads, dim), jnp.float32)]),
      out_shape=jax.ShapeDtypeStruct((n, heads, dim), jnp.float32),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'arbitrary')),
      interpret=cache_columns.interpret_on(jax.default_backend()),
      name=KERNEL_NAME,
  )(slots, last, q, cache, cache)

"""What the kernels over a cache arena share (ops/mla_pallas.py,
ops/gqa_pallas.py): the column write, which knows no width, and the
choice between the compiled and the interpreted kernel.

An arena leaf is `[slots, W, capacity]`: a session's cache is a matrix
with the TOKENS along the lanes, a token a column of W numbers at a
stride of the capacity (ops/mla_pallas.py has the reason). `write_rows`
puts a call's new tokens where they belong: a row's step takes the
128-lane block that holds its column, sets the column and puts the
block back, the arena aliased to the output. (XLA's own scatter or
`dynamic_update_slice` of a column asks for the W numbers along the
lanes and copies the whole leaf there and back to get them.)
Consecutive steps must not touch one block, or the pipeline fetches it
for the second before the first has written it: live rows differ in
their slot, and every padded row of a merged call (slot id out of
range) goes to the arena's LAST row, which belongs to no session and
exists to absorb them (models/core.py :: PositionedCore.arena), as in
ops/retention_pallas.py.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def interpret_on(platform):
  if platform == 'tpu':
    return False
  if platform == 'cpu':
    return True
  raise NotImplementedError(
      'the cache kernels run compiled on tpu or interpreted on cpu; no '
      f'path for {platform!r}')


def _write_kernel(slots_ref, pos_ref, entry_ref, cache_ref, out_ref, *,
                  lanes):
  del slots_ref  # used by the index maps only
  lane = pos_ref[pl.program_id(0)] % lanes
  block = cache_ref[0]                               # [W, lanes]
  at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
  out_ref[0] = jnp.where(at == lane, entry_ref[0], block)


@functools.partial(jax.jit, static_argnames=('name',))
def write_rows(cache, entry, slots, pos, *, name):
  """`entry [N, W]` written as column `pos[n]` of row `slots[n]` of
  `cache [S, W, capacity]`, in place (donate the cache); every id and
  position IN RANGE, no two rows on one block (module docstring).
  `name` is the kernel's in the device trace, which the caller's
  metrics find it by."""
  n, width = entry.shape
  capacity = cache.shape[2]
  lanes = min(LANES, capacity)
  assert capacity % lanes == 0, capacity

  def cache_block(i, slots_ref, pos_ref):
    return slots_ref[i], 0, pos_ref[i] // lanes

  return pl.pallas_call(
      functools.partial(_write_kernel, lanes=lanes),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(n,),
          in_specs=[
              pl.BlockSpec((1, width, 1), lambda i, *_: (i, 0, 0)),
              pl.BlockSpec((1, width, lanes), cache_block)],
          out_specs=pl.BlockSpec((1, width, lanes), cache_block)),
      out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
      # Operands 0 and 1 are the scalar prefetch; the arena is operand 3.
      input_output_aliases={3: 0},
      interpret=interpret_on(jax.default_backend()),
      name=name,
  )(slots, pos, entry.astype(cache.dtype)[..., None], cache)

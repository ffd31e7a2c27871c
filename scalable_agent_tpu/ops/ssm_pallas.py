"""The Mamba-2 state update on rows of an arena, in place.

For every row `b` of a merged call (a session whose state sits in row
`rows[b]` of the arena) and every head `h`, with the scalar decay
`a = exp(dt A_h)` (0 where the session's episode begins: the state is
taken as zero), `u = dt x_h` and the row's `B` and `C`:

    S <- a S + u B^T          S in R^{P x N}, float32
    y = S C                   (the skip term D x is the caller's)

`S` is a leaf of the inference server's state arena, `[rows, H, P, N]`:
4.19 MB a session and layer at the published widths (128 heads of 64 by
a state of 128), 134 MB for 32 sessions, so the kernel takes the arena
aliased to its output (`input_output_aliases`) and picks each grid
step's block by the scalar-prefetched row id: a block is read once,
updated on the VPU in float32 and written back to where it came from.
The rows a call does not name are never touched. The decays ride in
scalar memory beside the row ids; a step reads its heads' own.

Layout: N (the state size) on the lanes, P (a head's channels) on the
sublanes. The grid is (rows, blocks of `heads` heads); a step holds
`heads x P x N` floats (1 MB at 32 heads), and for each head makes the
rank-1 update from `u` as a column (P on the sublanes, broadcast along
the lanes: the caller hands `u` over transposed) and `B` as a row,
then `y` as the lane sum of `S * C`, one column a head of the output
block. The MXU is not used: a float32 product at full precision costs it
six passes, more than the block's time in HBM.

Padded rows of a merged call go to the arena's LAST row, which belongs
to no session (`models/core.py :: PositionedCore.arena`): the caller
maps them there, as for the cache kernels.

On the CPU the same kernel runs interpreted (the tests' path).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scalable_agent_tpu.ops import cache_columns

# The kernel's name in the device trace's operation names.
KERNEL_NAME = 'ssm_state_update'
HEADS_PER_STEP = 32   # a grid step's heads: 1 MB of state at P=64, N=128


def _kernel(rows_ref, decay_ref, s_ref, u_ref, b_ref, c_ref, s_out_ref,
            y_ref, *, heads):
  del rows_ref  # used by the index maps only
  n, j = pl.program_id(0), pl.program_id(1)
  b = b_ref[0]                                   # [1, N]
  c = c_ref[0]                                   # [1, N]
  u = u_ref[0, 0]                                # [P, heads]
  lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
  y = jnp.zeros(u.shape, jnp.float32)
  for i in range(heads):
    decay = decay_ref[(n * pl.num_programs(1) + j) * heads + i]
    s = decay * s_ref[0, i] + u[:, i:i + 1] * b  # [P, N]
    s_out_ref[0, i] = s
    y = jnp.where(lane == i, jnp.sum(s * c, axis=1, keepdims=True), y)
  y_ref[0, 0] = y


@functools.partial(jax.jit, static_argnames=('interpret',))
def update_rows(state, rows, decay, u, b, c, interpret=None):
  """Updates the rows `rows` of `state` in place.

  Args:
    state: f32 [R, H, P, N], the arena leaf; donate it (the server's
      step does) and the update costs no copy.
    rows: i32 [B], every id IN RANGE; live ones distinct.
    decay: f32 [B, H], `exp(dt A)`, 0 where the state restarts.
    u: f32 [B, H, P], `dt x`; b, c: f32 [B, N].

  Returns:
    (state', y f32 [B, H, P]) with y = S' C.
  """
  if interpret is None:
    interpret = cache_columns.interpret_on(jax.default_backend())
  _, h, p, n = state.shape
  batch = u.shape[0]
  heads = min(HEADS_PER_STEP, h)
  if h % heads or p % 8 or n % 128:
    raise ValueError(
        f'state {state.shape}: heads in blocks of {heads}, a head\'s '
        'channels in rows of 8 and the state in lanes of 128')
  blocks = h // heads
  # A block's heads side by side, their channels down: u as columns.
  u = u.reshape(batch, blocks, heads, p).swapaxes(2, 3)

  def state_block(i, j, rows_ref, decay_ref):
    del decay_ref
    return rows_ref[i], j, 0, 0

  def per_row(i, j, *_):
    return i, j, 0, 0

  state, y = pl.pallas_call(
      functools.partial(_kernel, heads=heads),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(batch, blocks),
          in_specs=[
              pl.BlockSpec((1, heads, p, n), state_block),
              pl.BlockSpec((1, 1, p, heads), per_row),
              pl.BlockSpec((1, 1, n), lambda i, j, *_: (i, 0, 0)),
              pl.BlockSpec((1, 1, n), lambda i, j, *_: (i, 0, 0)),
          ],
          out_specs=[
              pl.BlockSpec((1, heads, p, n), state_block),
              pl.BlockSpec((1, 1, p, heads), per_row),
          ]),
      out_shape=[
          jax.ShapeDtypeStruct(state.shape, jnp.float32),
          jax.ShapeDtypeStruct((batch, blocks, p, heads), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('arbitrary', 'arbitrary')),
      # Operands 0 and 1 are the scalar prefetch; the arena is operand 2.
      input_output_aliases={2: 0},
      name=KERNEL_NAME,
      interpret=interpret,
  )(rows.astype(jnp.int32), decay.astype(jnp.float32).reshape(-1), state,
    u.astype(jnp.float32), b.astype(jnp.float32)[:, None],
    c.astype(jnp.float32)[:, None])
  return state, y.swapaxes(2, 3).reshape(batch, h, p)

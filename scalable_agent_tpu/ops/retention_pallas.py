"""The power-retention state update on rows of an arena, in place.

For every live row `b` of a merged call (a session whose state sits in
row `slot_ids[b]` of the arena), every key-value head `j` and the `G`
query heads of its group:

    S <- decay * S + v phi(k)^T        S in R^{Dv x M}, float32
    num_i = S phi(q_i)                 i = 1..G

with `phi` the degree-2 embedding, made inside the kernel from q and k.

`S` is a leaf of the inference server's state arena, `[rows, KV, Dv, M]`
(`models/retention.py` says what M is). A gathered copy of the live
rows would be as large as the arena itself (4.4 GB at the published
widths and 32 sessions), so the kernel takes the arena aliased to its
output (`input_output_aliases`) and picks each grid step's block by the
scalar-prefetched slot id: a block is read once, updated on the VPU in
float32 and written back to where it came from. The rows a call does
not name are never touched.

Padded rows of a merged call (slot id out of range) all go to the
arena's LAST row, which belongs to no session: it exists to absorb
them (`models/core.py :: RecurrentCore.arena`). A padded row therefore
cannot touch a live slot, whatever the pipeline prefetches.

Layout: M (the expanded key dimension) on the lanes, Dv on the
sublanes. A column block of 128 lanes is one wrapped diagonal of
`phi`: its row for the key and for every query head is one lane
rotation of the register holding q and k, times itself. The rank-1
update then needs `v` broadcast along lanes (once a block) and the
key's row broadcast along sublanes; the products `S * phi(q_i)`
accumulate over column blocks in `G` block-sized accumulators and are
reduced along the lanes once at the end. The MXU is not used: a float32 state times
float32 `phi` at full precision costs it six passes, more than the
block's time in HBM.

On the CPU the same kernel runs interpreted (the tests' path).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The kernel's name in the device trace's operation names: the metric
# `retention.state_roofline_share` finds the kernel by it.
KERNEL_NAME = 'retention_state_update'
_LANES = 128       # lane width of the per-head output block
_ROW_BLOCK = 32    # sublane rows of S a grid step holds (1 MB at M=8320)
_QK_ROWS = 8       # one vector register: the group's queries, then the key


def _interpret_on(platform):
  if platform == 'tpu':
    return False
  if platform == 'cpu':
    return True
  raise NotImplementedError(
      'the retention kernel runs compiled on tpu or interpreted on '
      f'cpu; no path for {platform!r}')


def _kernel(ids_ref, s_ref, decay_ref, qk_ref, v_ref, s_out_ref, num_ref,
            *, groups):
  del ids_ref  # used by the index maps only
  rows = s_ref.shape[2]
  dk = qk_ref.shape[3]
  decay = decay_ref[0, 0]                    # [1, 1]
  v_col = jnp.broadcast_to(v_ref[0, 0], (rows, dk))
  # Rows 0..G-1: the group's query heads; row G: the key. phi is made
  # here, a wrapped diagonal at a time (models/retention.py :: phi):
  # one lane rotation and two products of ONE vector register give the
  # diagonal's row for the key and every query head, where reading
  # phi from HBM would add 6% to the state's own traffic.
  qk = qk_ref[0, 0]                          # [8, dk]
  accs = [jnp.zeros((rows, dk), jnp.float32) for _ in range(groups)]
  for d in range(dk // 2 + 1):
    weight = 1.0 if d in (0, dk // 2) else math.sqrt(2.0)
    # roll(x, dk - d)[i] = x[(i + d) % dk]
    shifted = qk if d == 0 else pltpu.roll(qk, dk - d, 1)
    phi = (weight * qk) * shifted            # [8, dk]
    cols = slice(d * dk, (d + 1) * dk)
    s = decay * s_ref[0, 0, :, cols] + v_col * phi[groups:groups + 1, :]
    s_out_ref[0, 0, :, cols] = s
    for i in range(groups):
      accs[i] = accs[i] + s * phi[i:i + 1, :]
  lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
  out = jnp.zeros((rows, _LANES), jnp.float32)
  for i in range(groups):
    out = jnp.where(lane == i,
                    jnp.sum(accs[i], axis=1, keepdims=True), out)
  num_ref[0, 0] = out


@functools.partial(jax.jit, static_argnames=('interpret',))
def update_rows(state, slot_ids, decay, q, k, v, interpret=None):
  """Updates the rows `slot_ids` of `state` in place.

  Args:
    state: f32 [rows, KV, Dv, M], the arena leaf, M = (Dk / 2 + 1) Dk;
      donate it (the server's step does) and the update costs no copy.
    slot_ids: i32 [B]; the live ids are distinct, anything outside
      [0, rows - 1) is a padded row and lands in row `rows - 1`.
    decay: f32 [B, KV], the gate (0 where the session's episode just
      began: the state is reset before the step).
    q: f32 [B, KV, G, Dk], the group's query heads; k: f32 [B, KV, Dk];
    v: f32 [B, KV, Dv].

  Returns:
    (state', num f32 [B, KV, G, Dv]) with num_i = S' phi(q_i).
  """
  if interpret is None:
    interpret = _interpret_on(jax.default_backend())
  arena_rows, kv, dv, m = state.shape
  b, _, groups, dk = q.shape
  block = min(_ROW_BLOCK, dv)
  if (dk // 2 + 1) * dk != m or groups >= _QK_ROWS or dv % block:
    raise ValueError(
        f'state {state.shape} does not hold phi of heads of {dk} for '
        f'{groups} query heads a group (at most {_QK_ROWS - 1}), or its '
        f'{dv} value rows do not divide into blocks of {block}')
  rows = jnp.where((slot_ids >= 0) & (slot_ids < arena_rows - 1),
                   slot_ids, arena_rows - 1).astype(jnp.int32)
  qk = jnp.concatenate(
      [q, k[:, :, None, :],
       jnp.zeros((b, kv, _QK_ROWS - groups - 1, dk), jnp.float32)], axis=2)

  def per_call(bi, j, nb, ids):
    del nb, ids
    return (bi, j, 0, 0)

  state, num = pl.pallas_call(
      functools.partial(_kernel, groups=groups),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1,
          grid=(b, kv, dv // block),
          in_specs=[
              pl.BlockSpec((1, 1, block, m),
                           lambda bi, j, nb, ids: (ids[bi], j, nb, 0)),
              pl.BlockSpec((1, 1, 1, 1), per_call),
              pl.BlockSpec((1, 1, _QK_ROWS, dk), per_call),
              pl.BlockSpec((1, 1, block, 1),
                           lambda bi, j, nb, ids: (bi, j, nb, 0)),
          ],
          out_specs=[
              pl.BlockSpec((1, 1, block, m),
                           lambda bi, j, nb, ids: (ids[bi], j, nb, 0)),
              pl.BlockSpec((1, 1, block, _LANES),
                           lambda bi, j, nb, ids: (bi, j, nb, 0)),
          ]),
      out_shape=[
          jax.ShapeDtypeStruct(state.shape, jnp.float32),
          jax.ShapeDtypeStruct((b, kv, dv, _LANES), jnp.float32),
      ],
      # Operand 0 is the scalar prefetch; the arena is operand 1.
      input_output_aliases={1: 0},
      name=KERNEL_NAME,
      interpret=interpret,
  )(rows, state, decay[:, :, None, None], qk, v[..., None])
  return state, jnp.swapaxes(num[..., :groups], -1, -2)

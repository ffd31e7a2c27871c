"""The recurrent-core protocol: what an agent's memory has to offer the
learner's unroll, the actor's single step and the inference server's
state arena.

A core is a flax module with

- `initial_state(batch)`: the zeroed carry, a pytree of `[batch, ...]`
  leaves (any dtypes: the LSTM's two float32 matrices; a retention
  stack's float32 states and an int32 position);
- `step(carry, x, done, slots=None)`: ONE step. The carry is zeroed
  wherever `done` is set BEFORE the step (`done[t]` marks the first
  observation of a new episode), then advanced with `x`. Returns
  `(carry, out)`. The one `@nn.compact` method: the parameters live
  here;
- `unroll(core, carry, xs, dones)`: the scan of that step over time.
  A function of this file and not a method, so every core's unroll IS
  the scan of its step (the learner and the actors then compute the
  same thing by construction);
- `chunk(carry, xs, n_valid, reset, slot=None)`: ONE session advanced
  by the first `n_valid` of the `C` inputs `xs [C, ...]` (the rest are
  padding: they change nothing), its state zeroed first where `reset`
  says the chunk begins an episode. The default IS the scan of `step`,
  so every core has it; a core whose step over `C` tokens at once is
  cheaper than `C` steps (attention over a cache) overrides it and
  says so in `chunk_size`, which is what makes the serving path hand a
  session's prompt over as blocks (runtime/inference.py).

With `slots` (i32 `[B]` slot ids) the carry handed to `step` is the
inference server's ARENA instead: per state leaf one `[rows, ...]`
array holding every session's state, of which this call advances the
rows `slots` and returns the arena. A padded row of a merged call
carries an id out of range and must never touch a live row. The
default (`step_in_arena`) gathers the rows, steps and scatters them
back, dropping the padded ones: right for a state of kilobytes. A core
whose state is megabytes a session updates its arena in place instead
and may lay the arena out for that (`arena`).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp


def reset_where_done(carry, done):
  """`carry` with every leaf zeroed in the rows where `done` is set."""
  def reset(s):
    mask = done.reshape(done.shape + (1,) * (s.ndim - 1))
    return jnp.where(mask, jnp.zeros_like(s), s)
  return jax.tree_util.tree_map(reset, carry)


def gather_rows(arena, slots):
  """The rows `slots` of every arena leaf (an id out of range clamps:
  a padded row computes on some live row's state and is dropped)."""
  return jax.tree_util.tree_map(lambda a: a[slots], arena)


def scatter_rows(arena, slots, rows):
  """`arena` with `rows` written at `slots`; ids out of range are
  DROPPED, which is what keeps a padded row off a live slot."""
  return jax.tree_util.tree_map(
      lambda a, r: a.at[slots].set(r.astype(a.dtype), mode='drop'),
      arena, rows)


class RecurrentCore(nn.Module):
  """Base of the cores; see the module docstring for the protocol."""

  def initial_state(self, batch):
    raise NotImplementedError

  def step(self, carry, x, done, slots=None):
    raise NotImplementedError

  def arena(self, num_slots):
    """The zeroed state arena for `num_slots` sessions: per leaf of
    `initial_state` one `[num_slots, ...]` array. Row `i` of every
    leaf is session `i`'s state; a core may append rows that belong
    to no session."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((num_slots,) + s.shape[1:], s.dtype),
        jax.eval_shape(lambda: self.initial_state(1)))

  def step_in_arena(self, step, arena, slots, x, done):
    """`step` (a carry-form step of this core) applied to the rows
    `slots` of `arena`: gather, step, scatter."""
    carry, out = step(gather_rows(arena, slots), x, done)
    return scatter_rows(arena, slots, carry), out

  # What the agent and the inference server ask of a core; the
  # defaults are a state of fixed size that counts nothing.
  # Tokens a `chunk` call takes where the core computes a chunk at
  # once; 0: the core has no form of its own, and nobody chunks for it.
  chunk_size = 0
  # Tokens of an episode a session's state holds where it is written
  # at a position (a cache attention reads); 0: a state of fixed size.
  cache_capacity = 0
  # Tokens a layer that keeps only its episode's last ones holds (a
  # ring beside the cache); 0: no such layer.
  cache_window = 0
  # The per-call counters the core's layers sow (collection
  # 'counters'), which the server reads with a call's outputs.
  counters = ()

  def chunk(self, carry, xs, n_valid, reset, slot=None):
    """The chunk form by the scan of `step`: `carry` is the session's
    own (`[1, ...]` leaves) or, with `slot` (i32 scalar), the arena, of
    which row `slot` is advanced -> (carry, outs [C, ...])."""
    rows = carry if slot is None else gather_rows(carry, slot[None])

    def one(core, rows, inputs):
      x, t = inputs
      new, out = core.step(rows, x[None], (reset & (t == 0))[None])
      keep = t < n_valid
      rows = jax.tree_util.tree_map(
          lambda n, o: jnp.where(keep, n, o), new, rows)
      return rows, out[0]

    scan = nn.scan(one, variable_broadcast='params',
                   split_rngs={'params': False})
    rows, outs = scan(self, rows, (xs, jnp.arange(xs.shape[0])))
    if slot is None:
      return rows, outs
    return scatter_rows(carry, slot[None], rows), outs


class PositionedCore(RecurrentCore):
  """A stack of layers whose state is written AT A POSITION: carry
  `{'pos': i32 [B], 'layers': (leaf [B, ...], ...)}`, one leaf a layer,
  which the layer reads as far as the row's position and no further.
  `done` resets a row's POSITION; what the row held stays where it is
  and is never read again. The arena is the carry with a row a slot
  and one more, advanced in place. A subclass gives `initial_state`,
  `cache_capacity`, `chunk_size` and THE compact method

      _blocks(x, leaves, rows, pos, live, prefill) -> (x, leaves)

  for token n at position `pos[n]` of the row `rows[n]` (both in range;
  `live[n]` false: the row is no session's). `prefill` None: N rows of
  N sessions, one token each; `(row, pos0, n_valid)`: N tokens of one
  session."""

  def arena(self, num_slots):
    """One row more than there are slots: the row that padded rows of
    a merged call (and a chunk for no session: the warm-up's) are
    written to (ops/mla_pallas.py)."""
    return self.initial_state(num_slots + 1)

  @staticmethod
  def _rows(slots, sessions):
    """(rows to read and write, which of them are some session's):
    an id out of range goes to the arena's last row."""
    live = slots < sessions
    return jnp.where(live, slots, sessions), live

  def step(self, carry, x, done, slots=None):
    """One token a row: the carry's own rows, or with `slots` the rows
    of the arena they name. A position beyond the capacity overwrites
    the last column (the configuration keeps episodes inside it)."""
    if slots is None:
      slots = rows = jnp.arange(x.shape[0])
      live = jnp.ones(x.shape[:1], bool)
    else:
      rows, live = self._rows(slots, carry['pos'].shape[0] - 1)
    pos = jnp.where(done, 0, carry['pos'][rows])
    x, layers = self._blocks(
        x, carry['layers'], rows,
        jnp.minimum(pos, self.cache_capacity - 1), live, None)
    new_pos = carry['pos'].at[slots].set(pos + 1, mode='drop')
    return {'pos': new_pos, 'layers': layers}, x

  def chunk(self, carry, xs, n_valid, reset, slot=None):
    """C tokens of one session at once."""
    c = xs.shape[0]
    if slot is None:
      slot = row = jnp.zeros((), jnp.int32)
      live = jnp.ones((), bool)
    else:
      row, live = self._rows(slot, carry['pos'].shape[0] - 1)
    pos0 = jnp.where(reset, 0, carry['pos'][row])
    x, layers = self._blocks(
        xs, carry['layers'], jnp.full((c,), row), pos0 + jnp.arange(c),
        (jnp.arange(c) < n_valid) & live, (row, pos0, n_valid))
    new_pos = carry['pos'].at[slot].set(pos0 + n_valid, mode='drop')
    return {'pos': new_pos, 'layers': layers}, x


def running_softmax(carry, scores, values_fn):
  """One block of a softmax taken in blocks: `scores [..., S]` (masked
  columns at -inf), `values_fn(p)` the block's weighted values."""
  m, l, acc = carry
  m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
  p = jnp.exp(scores - m_new[..., None])
  corr = jnp.exp(m - m_new)
  return (m_new, l * corr + jnp.sum(p, axis=-1),
          acc * corr[..., None] + values_fn(p))


def write_chunk(cache, entry, slot, pos0, n_valid, live):
  """`entry [C, W]`: its first `n_valid` tokens written as the columns
  `pos0..` of row `slot`, where `live [C]` and as far as the capacity;
  in place, as one window of C columns (shifted back where `pos0 + C`
  would pass the capacity, the tokens rolled to their columns)."""
  c, capacity = entry.shape[0], cache.shape[2]
  start = jnp.clip(pos0, 0, capacity - c)
  at = (slot, 0, start)
  window = jax.lax.dynamic_slice(cache, at, (1, cache.shape[1], c))
  token = jnp.arange(c) - (pos0 - start)   # the token a column takes
  rolled = jnp.roll(entry.T, pos0 - start, axis=1)
  write = (token >= 0) & (token < n_valid) & jnp.roll(live, pos0 - start)
  return jax.lax.dynamic_update_slice(
      cache, jnp.where(write[None, None, :], rolled[None], window), at)


def unroll(core, carry, xs, dones, scan_unroll=1):
  """The scan of `core.step` over the leading (time) axis of `xs` and
  `dones` -> (final carry, outs [T, ...])."""
  scan = nn.scan(
      lambda c, carry, x: c.step(carry, x[0], x[1]),
      variable_broadcast='params', split_rngs={'params': False},
      in_axes=0, out_axes=0, unroll=scan_unroll)
  return scan(core, carry, (xs, dones))


class LSTMCore(RecurrentCore):
  """The paper's LSTM (reference: experiment.py ≈L195–205), carry
  `(c, h)` of float32 `[B, hidden]`."""
  hidden_size: int
  dtype: jnp.dtype = jnp.float32

  def initial_state(self, batch):
    shape = (batch, self.hidden_size)
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

  @nn.compact
  def step(self, carry, x, done, slots=None):
    cell = nn.OptimizedLSTMCell(self.hidden_size, dtype=self.dtype)

    def carry_step(carry, x, done):
      return cell(reset_where_done(carry, done), x)

    if slots is None:
      return carry_step(carry, x, done)

    def arena_step(rows, x, done):
      # The arena is float32 whatever the compute dtype: the rows are
      # cast on the way in, and `scatter_rows` casts them back.
      rows = jax.tree_util.tree_map(lambda s: s.astype(self.dtype), rows)
      return carry_step(rows, x, done)

    return self.step_in_arena(arena_step, carry, slots, x, done)

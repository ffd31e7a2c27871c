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

  # Tokens a `chunk` call takes where the core computes a chunk at
  # once; 0: the core has no form of its own, and nobody chunks for it.
  chunk_size = 0

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

"""The benchmark's copy of the plain reference of the sequence policy
with Mamba-2 layers beside full attention layers and routed experts
(Granite 4.0-H's hybrid; the program's copy:
scalable_agent_tpu/models/ssm_hybrid_reference.py, the same text from
here on, which tests/benchmark/test_granite_cell.py holds): the whole
forward pass of one episode in float32 jax.numpy, no cache, no arena,
no chunking, no kernel. It imports nothing of the program.

For an episode's tokens `x_0..x_{T-1}` (position t = index t), with
`x <- embedding_multiplier E[x_t]`, per layer (letter `i mod len` of
`layer_pattern`), pre-norm with the residual multiplier `r`:

    h = x + r Mixer(RMSNorm(x));   x <- h + r FFN(RMSNorm(h))

Mamba-2 layer (letter `M`), by its defining recurrence over time:

    [z | xBC | dt] = n W_in
    xBC_t <- silu(sum_k w_k xBC_{t-K+1+k} + b)   (zeros before t = 0)
    [x | B | C] = xBC;  dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t,h B_t^T   (S_{-1} = 0)
    y_t,h = S_t C_t + D_h x_t,h
    Mixer = RMSNorm(y * silu(z)) W_out

a `lax.scan` over the tokens, one step a token. Full attention layer
(letter `G`), no position encoding, query head i in key-value group
`g = i // (heads / kv_heads)`:

    a_t,s,i = softmax_{s <= t}(attention_multiplier q_t,i . k_s,g)
    Mixer = concat_i(sum_s a_t,s,i v_s,g) W_o

as a [block, T] product a key-value group and a block of queries at a
time. FFN: the shared expert plus, in a plain loop over the experts this
share holds (`experts_held` from `expert_offset`), `g_e FFN_e(n)` for
the tokens whose router chose expert e: the `experts_per_token` largest
of the logits `n W_g` (sorted, ties to the lower index), `g =
routed_scale softmax(chosen logits)`. What the experts held elsewhere
would add is left out, as in the program. Then the final norm, the
logits `n E^T / logits_scaling` over the vocabulary slice (in column
blocks; the head is the embedding), `log softmax`, and the value head.

`dims` carries the configuration's sizes by the names of the program's
`SSMHybridDims` (any object with those attributes). The parameters are
used as given (the served copy's bfloat16-rounded values, widened to
float32 one matrix at a time). `operand_dtype` is the configuration's
stated precision of the products' operands: where the served model
rounds an activation to bfloat16 before a matrix product, so does the
reference, by the same round-to-nearest; all arithmetic stays float32
at `Precision.HIGHEST`. `conv_dtype` rounds the conv's inputs as the
conv window that holds them does, `cache_dtype` the keys and values as
the cache does; `state_dtype` rounds the state S after every step, which
the configuration states in float32: a control, never the served model.
None rounds nothing. The projections are taken a block of tokens at a
time (`block`); the arithmetic of a token does not depend on the blocks.

With `states` it also returns the state S of each Mamba layer after
the episode's last token, as the recurrence leaves it (rows of padding
after the episode leave it as it is): what a served session's arena row
holds after its last call, which the comparison reads without the head
in between.

Besides `(log pi(actions), baseline)` the reference reports, per
token, the smallest MARGIN of any top-k choice its routers made for
it: over the layers, the gap between the last expert chosen and the
first rejected, in units of the logits, counting a gap only where the
other outcome would change what THIS share computes (`_router`). A
choice is discontinuous: a token whose margin is under the rounding of
the logits may route otherwise in the served model, and the comparison
sets it aside.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rounded(x, dtype):
  """x rounded to `dtype`'s precision, kept in float32
  (`reduce_precision`, which the compiler may not take out)."""
  if dtype is None:
    return x
  info = jnp.finfo(dtype)
  return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _project(x, kernel, operand_dtype):
  return jnp.dot(_rounded(x, operand_dtype), kernel.astype(jnp.float32),
                 precision=HIGHEST)


def _rms_norm(x, scale, eps):
  var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
  return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _blocks(fn, block, *rows):
  """`fn` over the leading axis a block of rows at a time."""
  t = rows[0].shape[0]
  split = lambda x: x.reshape((t // block, block) + x.shape[1:])  # noqa: E731
  out = jax.lax.map(lambda xs: fn(*xs), tuple(split(x) for x in rows))
  return jax.tree_util.tree_map(
      lambda x: x.reshape((t,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=(
    'dims', 'eps', 'operand_dtype', 'conv_dtype', 'state_dtype', 'block'))
def _mamba(x, valid, p, dims, eps, operand_dtype, conv_dtype, state_dtype,
           block):
  """x [T, hidden], the layer's normed input; valid bool [T], the
  episode's own rows -> (the mixer's output, S after the last valid
  row)."""
  t = x.shape[0]
  heads, dim, size = dims.ssm_heads, dims.ssm_head_dim, dims.ssm_state
  inner = heads * dim
  zxbcdt = _blocks(lambda r: _project(r, p['in_proj']['kernel'],
                                      operand_dtype), block, x)
  z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * size], axis=-1)
  xbc = _rounded(xbc, conv_dtype)
  w = p['conv_kernel'].astype(jnp.float32)
  width = w.shape[0]
  padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
  xbc = jax.nn.silu(sum(padded[k:k + t] * w[k] for k in range(width)) +
                    p['conv_bias'].astype(jnp.float32))
  xs, b, c = jnp.split(xbc, [inner, inner + size], axis=-1)
  xs = xs.reshape(t, heads, dim)
  dt = jax.nn.softplus(dt + p['dt_bias'].astype(jnp.float32))
  dt = jnp.where(valid[:, None], dt, 0.0)  # exp(0) S + 0: S as it was
  a = -jnp.exp(p['A_log'].astype(jnp.float32))

  def step(s, inputs):
    x_t, dt_t, b_t, c_t = inputs
    s = _rounded(jnp.exp(dt_t * a)[:, None, None] * s +
                 (dt_t[:, None] * x_t)[:, :, None] * b_t, state_dtype)
    return s, jnp.einsum('hpn,n->hp', s, c_t, precision=HIGHEST)

  s, y = jax.lax.scan(step, jnp.zeros((heads, dim, size), jnp.float32),
                      (xs, dt, b, c))
  y = y + p['D'].astype(jnp.float32)[:, None] * xs
  y = _rms_norm(y.reshape(t, inner) * jax.nn.silu(z), p['norm'], eps)
  return _blocks(lambda r: _project(r, p['out_proj']['kernel'],
                                    operand_dtype), block, y), s


@functools.partial(jax.jit, static_argnames=(
    'dims', 'heads', 'operand_dtype', 'cache_dtype', 'block'))
def _attention(x, w_q, w_k, w_v, w_o, dims, heads, operand_dtype,
               cache_dtype, block):
  """x [T, hidden], the layer's normed input -> the attention's output
  after W_o, [T, hidden]. A key-value group at a time from the
  projections to the output projection's rows, and of a group a block
  of queries at a time."""
  t = x.shape[0]
  groups, dim = dims.num_kv_heads, dims.head_dim
  per = heads // groups
  pos = jnp.arange(t)

  def group_of(out, weights):
    w_q, w_k, w_v, w_out = weights  # this group's columns x 3, rows
    q = _project(x, w_q, operand_dtype).reshape(t, per, dim)
    k = _rounded(_project(x, w_k, operand_dtype), cache_dtype)
    v = _rounded(_project(x, w_v, operand_dtype), cache_dtype)

    def queries(q, q_pos):
      scores = dims.attention_multiplier * jnp.einsum(
          'tid,sd->its', _rounded(q, operand_dtype),
          _rounded(k, operand_dtype), precision=HIGHEST)
      mask = pos[None, :] <= q_pos[:, None]
      a = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
      return jnp.einsum('its,sd->tid', _rounded(a, operand_dtype),
                        _rounded(v, operand_dtype), precision=HIGHEST)

    o = _blocks(queries, block, q, pos).reshape(t, per * dim)
    return out + _project(o, w_out, operand_dtype), None

  by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
      w.reshape(w.shape[:axis] + (groups, -1) + w.shape[axis + 1:]),
      axis, 0)
  out, _ = jax.lax.scan(
      group_of, jnp.zeros((t, w_o.shape[1]), jnp.float32),
      (by_group(w_q, 1), by_group(w_k, 1), by_group(w_v, 1),
       by_group(w_o, 0)))
  return out


def _ffn(x, weights, operand_dtype):
  act = jax.nn.silu(_project(x, weights['gate_proj']['kernel'],
                             operand_dtype)) * _project(
                                 x, weights['up_proj']['kernel'],
                                 operand_dtype)
  return _project(act, weights['down_proj']['kernel'], operand_dtype)


@functools.partial(jax.jit, static_argnames=('operand_dtype', 'block'))
def _ffn_blocks(x, weights, gate, operand_dtype, block):
  """gate [T] times FFN(x), a block of tokens at a time."""
  return _blocks(lambda x, g: _ffn(x, weights, operand_dtype) * g[:, None],
                 block, x, gate)


@functools.partial(jax.jit, static_argnames=('dims',))
def _router(x, w_g, dims):
  """-> (weight of every expert for every token [T, E], 0 where not
  chosen; margin [T])."""
  t = x.shape[0]
  e, k = dims.routed_experts, dims.experts_per_token
  logits = jnp.dot(x, w_g.astype(jnp.float32), precision=HIGHEST)
  order = jnp.argsort(-logits, axis=-1, stable=True)
  ranked = jnp.take_along_axis(logits, order, axis=-1)
  rows = jnp.arange(t)[:, None]
  weights = jnp.zeros((t, e), jnp.float32).at[rows, order[:, :k]].set(
      dims.routed_scale * jax.nn.softmax(ranked[:, :k], axis=-1))

  # The margin: by how little the choice could have fallen otherwise,
  # where otherwise means another result HERE: the first expert
  # rejected taking the place of the last chosen counts if an expert
  # this share holds is chosen before or after (membership, or the sum
  # the softmax is taken over, then changes; a token none of whose
  # experts are held gets nothing from the routed part either way).
  held = ((jnp.arange(e) >= dims.expert_offset) &
          (jnp.arange(e) < dims.expert_offset + dims.experts_held))
  either = jnp.zeros((t, e), bool).at[rows, order[:, :k + 1]].set(True)
  counts = jnp.any(either & held, axis=-1)
  margin = jnp.where(counts, ranked[:, k - 1] - ranked[:, k], jnp.inf)
  return weights, margin


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, scale, eps):
  return _rms_norm(x, scale, eps)


def _layer(p, x, valid, i, dims, heads, eps, operand_dtype, conv_dtype,
           cache_dtype, state_dtype, block):
  """One layer over the whole episode, a matrix at a time -> (x,
  margin, S of a Mamba layer or None)."""
  r = dims.residual_multiplier
  n = _norm(x, p['input_norm']['scale'], eps)
  state = None
  if dims.layer_pattern[i % len(dims.layer_pattern)] == 'M':
    out, state = _mamba(n, valid, p['mamba'], dims, eps, operand_dtype,
                        conv_dtype, state_dtype, block)
  else:
    kernel = lambda name: p[name]['kernel']  # noqa: E731
    out = _attention(n, kernel('q_proj'), kernel('k_proj'),
                     kernel('v_proj'), kernel('o_proj'), dims, heads,
                     operand_dtype, cache_dtype, block)
  x = x + r * out
  n = _norm(x, p['post_norm']['scale'], eps)
  moe = p['moe']
  weights, margin = _router(n, moe['router']['kernel'], dims)
  y = _ffn_blocks(n, moe['shared_expert'],
                  jnp.ones((x.shape[0],), jnp.float32), operand_dtype, block)
  for e in range(dims.experts_held):  # the dense loop over the share
    # One expert at a time ON THE DEVICE too: dispatched ahead, every
    # expert's output would be allocated at once.
    y = jax.block_until_ready(y + _ffn_blocks(
        n, moe[f'expert_{e}'], weights[:, dims.expert_offset + e],
        operand_dtype, block))
  return x + r * y, margin, state


@functools.partial(jax.jit, static_argnames=('scaling',))
def _head_block(n, rows, lo, actions, lse, picked, scaling):
  logits = jnp.dot(n, rows.astype(jnp.float32).T,
                   precision=HIGHEST) / scaling
  width = rows.shape[0]
  here = (actions >= lo) & (actions < lo + width)
  column = jnp.clip(actions - lo, 0, width - 1)
  return (jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1)),
          jnp.where(here, jnp.take_along_axis(
              logits, column[:, None], axis=1)[:, 0], picked))


def _log_probs(n, table, actions, scaling, operand_dtype, vocab_block):
  n = _rounded(n, operand_dtype)
  lse = jnp.full((n.shape[0],), -jnp.inf, jnp.float32)
  picked = jnp.zeros((n.shape[0],), jnp.float32)
  for lo in range(0, table.shape[0], vocab_block):
    lse, picked = _head_block(n, table[lo:lo + vocab_block], lo, actions,
                              lse, picked, scaling)
  return picked - lse


def forward(params, tokens, actions, *, dims, num_heads, norm_eps=1e-5,
            operand_dtype=None, conv_dtype=None, cache_dtype=None,
            state_dtype=None, vocab_block=None, block=None, logits=False,
            states=False):
  """One episode from its first token.

  params: the agent's parameter tree; tokens i32 [T] as fed, prompt
  and all; actions i32 [T] as taken (anything where none was).
  Returns (log pi(actions) f32 [T], baseline f32 [T], margin f32 [T]),
  with `logits` the [T, vocabulary] logits too, and with `states` the
  list of each Mamba layer's S f32 [heads, head_dim, state] after token
  T - 1 last. Call it outside
  `jax.jit`: it runs a matrix, and a block of rows, at a time.
  """
  p = params['params']
  t = len(tokens)
  block = min(block or t, t)
  padded = -(-t // block) * block  # rows beyond t come after: unread
  tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, padded - t))
  actions = jnp.pad(jnp.asarray(actions, jnp.int32), (0, padded - t))
  table = p['embedding']
  x = jnp.take(table, tokens, axis=0).astype(jnp.float32) * float(
      dims.embedding_multiplier)
  margin = jnp.full((padded,), jnp.inf)
  valid = jnp.arange(padded) < t
  held = []
  for i in range(len(p['core'])):
    x, layer_margin, state = _layer(
        p['core'][f'block_{i}'], x, valid, i, dims, num_heads,
        float(norm_eps), operand_dtype, conv_dtype, cache_dtype,
        state_dtype, block)
    margin = jnp.minimum(margin, layer_margin)
    if state is not None:
      held.append(state)
  n = _norm(x, p['final_norm']['scale'], float(norm_eps))
  baseline = (jnp.dot(n, p['baseline']['kernel'].astype(jnp.float32),
                      precision=HIGHEST)[:, 0] +
              p['baseline']['bias'].astype(jnp.float32)[0])
  log_probs = _log_probs(n, table, actions, float(dims.logits_scaling),
                         operand_dtype, vocab_block or table.shape[0])
  out = (log_probs[:t], baseline[:t], margin[:t])
  if logits:
    out += (jnp.dot(_rounded(n, operand_dtype), table.astype(jnp.float32).T,
                    precision=HIGHEST)[:t] / float(dims.logits_scaling),)
  if states:
    out += (held,)
  return out

"""The plain reference of the sequence policy with grouped-query
attention in a pattern of window and full layers and routed experts:
the whole forward pass of one episode in float32 jax.numpy, no cache, no
ring, no batching, no kernel.

For an episode's tokens `x_0..x_{T-1}` (position t = index t), per
layer, query head i in key-value group `g = i // (heads / kv_heads)`:

    q_i = RMSNorm_q(x W_q,i);  k_g = RMSNorm_k(x W_k,g);  v_g = x W_v,g
    window layer (letter `L` of `layer_pattern`, read `i mod len`):
        q_i, k_g rotated at t (theta^(-2j/D), half-rotation);
        mask_t,s = s <= t and t - s < window
    full layer (letter `G`): no rotation;  mask_t,s = s <= t
    a_t,s,i = softmax over the mask of (q_t,i . k_s,g / sqrt(D))
    x <- x + RMSNorm(concat_i(sum_s a_t,s,i v_s,g) W_o)

then `x <- x + RMSNorm(FFN(x))`, both norms on the OUTPUTS: the dense
SwiGLU in the first `first_dense_layers` layers; in the others the
shared expert plus, in a plain loop over the experts this share holds
(`experts_held` from `expert_offset`), `g_e FFN_e(x)` for the tokens
whose router chose expert e. The router: `s = sigmoid(x W_g)` over all
`routed_experts`; the `experts_per_token` largest of `s + bias` are
chosen (sorted, ties to the lower index; one group, no limit); `g_e =
routed_scale s_e / sum_chosen s`. What the experts held elsewhere would
add is left out, as in the program. Then the final norm, `log softmax`
over the vocabulary slice (in column blocks) and the value head.

`dims` carries the configuration's sizes by the names of
`models/hybrid_attention.py :: HybridAttentionDims` (any object with
those attributes). The parameters are used as given (the served copy's
bfloat16-rounded values, widened to float32 one matrix at a time).
`operand_dtype` is the configuration's stated precision of the
products' operands: where the served model rounds an activation to
bfloat16 before a matrix product, so does the reference, by the same
round-to-nearest; all arithmetic stays float32 at
`Precision.HIGHEST`. `cache_dtype` rounds the keys (after norm and
rotation) and the values as the two caches that hold them do.
`router_dtype` rounds the router's two operands, which the
configuration states in float32: a control, never the served model.
None rounds nothing. To fit beside the served parameters at an episode's
full length (28 thousand tokens of the published widths), attention is
taken a key-value group at a time from the projections to the output
projection's rows, and of a group a block of queries at a time against
a `[block, T]` mask; the feed-forward a block of tokens at a time
(`block`). The arithmetic of a token does not depend on the blocks
(the output projection's sum over heads is taken group by group).

Besides `(log pi(actions), baseline)` the reference reports, per
token, the smallest MARGIN of any top-k choice its routers made for
it: over the layers, the gap between the last expert chosen and the
first rejected, in units of the scores, counting a gap only where the
other outcome would change what THIS share computes (`_router`). A
choice is discontinuous: a token whose margin is under the rounding of
the scores may route otherwise in the served model, and the comparison
sets it aside.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def _rope(x, pos, theta):
  """x [T, ..., D] at positions pos [T], half-rotation (dimension j
  turns with j + D/2), frequencies theta^(-2j/D)."""
  dim = x.shape[-1]
  inv_freq = (float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) /
                               dim)).astype(np.float32)
  ang = pos.astype(jnp.float32)[:, None] * inv_freq
  ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
  cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
  sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
  half = dim // 2
  return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _blocks(fn, block, *rows):
  """`fn` over the leading axis a block of rows at a time."""
  t = rows[0].shape[0]
  split = lambda x: x.reshape((t // block, block) + x.shape[1:])  # noqa: E731
  out = jax.lax.map(lambda xs: fn(*xs), tuple(split(x) for x in rows))
  return jax.tree_util.tree_map(
      lambda x: x.reshape((t,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=(
    'dims', 'heads', 'theta', 'eps', 'full', 'operand_dtype', 'cache_dtype',
    'block'))
def _attention(x, w_q, w_k, w_v, w_o, q_scale, k_scale, pos, dims, heads,
               theta, eps, full, operand_dtype, cache_dtype, block):
  """x [T, hidden] -> the attention's output after W_o, [T, hidden]. A
  key-value group at a time from the projections to the output
  projection's rows (so no array of all heads' queries exists), and of
  a group a block of queries at a time."""
  t = x.shape[0]
  groups, dim = dims.num_kv_heads, dims.head_dim
  per = heads // groups
  scale = dim ** -0.5

  def group_of(out, weights):
    w_q, w_k, w_v, w_out = weights  # this group's columns x 3, rows
    q = _rms_norm(_project(x, w_q, operand_dtype).reshape(t, per, dim),
                  q_scale, eps)
    k = _rms_norm(_project(x, w_k, operand_dtype), k_scale, eps)
    v = _project(x, w_v, operand_dtype)
    if not full:
      q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k, v = _rounded(k, cache_dtype), _rounded(v, cache_dtype)

    def queries(q, q_pos):
      scores = scale * jnp.einsum(
          'tid,sd->its', _rounded(q, operand_dtype),
          _rounded(k, operand_dtype), precision=HIGHEST)
      mask = pos[None, :] <= q_pos[:, None]
      if not full:
        mask &= q_pos[:, None] - pos[None, :] < dims.window
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


def _top(values, count):
  """The `count` largest along the last axis, sorted stably (ties to
  the lower index) -> (mask of them, the sorted values, the order)."""
  order = jnp.argsort(-values, axis=-1, stable=True)
  mask = jnp.zeros(values.shape, bool).at[
      jnp.arange(values.shape[0])[:, None], order[:, :count]].set(True)
  return mask, jnp.take_along_axis(values, order, axis=-1), order


@functools.partial(jax.jit, static_argnames=('dims', 'router_dtype'))
def _router(x, w_g, bias, dims, router_dtype=None):
  """-> (weight of every expert for every token [T, E], 0 where not
  chosen; margin [T])."""
  t = x.shape[0]
  e, k = dims.routed_experts, dims.experts_per_token
  s = jax.nn.sigmoid(jnp.dot(
      _rounded(x, router_dtype),
      _rounded(w_g.astype(jnp.float32), router_dtype), precision=HIGHEST))
  chosen, ranked, order = _top(s + bias.astype(jnp.float32), k)
  picked = jnp.where(chosen, s, 0.0)
  weights = dims.routed_scale * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

  # The margin: by how little the choice could have fallen otherwise,
  # where otherwise means another result HERE: the first expert
  # rejected taking the place of the last chosen counts if an expert
  # this share holds is chosen before or after (membership, or the sum
  # the weights are normalised by, then changes; a token none of whose
  # experts are held gets nothing from the routed part either way).
  held = ((jnp.arange(e) >= dims.expert_offset) &
          (jnp.arange(e) < dims.expert_offset + dims.experts_held))
  rows = jnp.arange(t)
  near = chosen.at[rows, order[:, k - 1]].set(False).at[
      rows, order[:, k]].set(True)
  counts = jnp.any((near | chosen) & held, axis=-1)
  margin = jnp.where(counts, ranked[:, k - 1] - ranked[:, k], jnp.inf)
  return weights, margin


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, scale, eps):
  return _rms_norm(x, scale, eps)


def _layer(p, x, pos, i, dims, heads, theta, eps, operand_dtype,
           cache_dtype, router_dtype, block):
  """One layer over the whole episode, a matrix at a time (each is
  widened to float32 while it is used and no longer) -> (x, margin)."""
  kernel = lambda name: p[name]['kernel']  # noqa: E731
  kind = dims.layer_pattern[i % len(dims.layer_pattern)]
  x = x + _norm(
      _attention(x, kernel('q_proj'), kernel('k_proj'), kernel('v_proj'),
                 kernel('o_proj'), p['q_norm']['scale'],
                 p['k_norm']['scale'], pos, dims, heads, theta, eps,
                 kind == 'G', operand_dtype, cache_dtype, block),
      p['post_attention_norm']['scale'], eps)
  ones = jnp.ones((x.shape[0],), jnp.float32)
  margin = jnp.full((x.shape[0],), jnp.inf)
  if i < dims.first_dense_layers:
    y = _ffn_blocks(x, p['mlp'], ones, operand_dtype, block)
  else:
    moe = p['moe']
    weights, margin = _router(x, moe['router']['kernel'],
                              moe['e_score_correction_bias'], dims,
                              router_dtype)
    y = _ffn_blocks(x, moe['shared_expert'], ones, operand_dtype, block)
    for e in range(dims.experts_held):  # the dense loop over the share
      # One expert at a time ON THE DEVICE too: dispatched ahead, every
      # expert's output would be allocated at once, gigabytes of them.
      y = jax.block_until_ready(y + _ffn_blocks(
          x, moe[f'expert_{e}'], weights[:, dims.expert_offset + e],
          operand_dtype, block))
  return x + _norm(y, p['post_ffn_norm']['scale'], eps), margin


@jax.jit
def _head_block(n, columns, lo, actions, lse, picked):
  logits = jnp.dot(n, columns.astype(jnp.float32), precision=HIGHEST)
  width = columns.shape[1]
  here = (actions >= lo) & (actions < lo + width)
  column = jnp.clip(actions - lo, 0, width - 1)
  return (jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1)),
          jnp.where(here, jnp.take_along_axis(
              logits, column[:, None], axis=1)[:, 0], picked))


def _log_probs(n, kernel, actions, operand_dtype, vocab_block):
  n = _rounded(n, operand_dtype)
  lse = jnp.full((n.shape[0],), -jnp.inf, jnp.float32)
  picked = jnp.zeros((n.shape[0],), jnp.float32)
  for lo in range(0, kernel.shape[1], vocab_block):
    lse, picked = _head_block(n, kernel[:, lo:lo + vocab_block], lo,
                              actions, lse, picked)
  return picked - lse


def forward(params, tokens, actions, *, dims, num_heads, rope_theta=1e6,
            norm_eps=1e-5, operand_dtype=None, cache_dtype=None,
            router_dtype=None, vocab_block=None, block=None, logits=False):
  """One episode from its first token.

  params: the agent's parameter tree; tokens i32 [T] as fed, prompt
  and all; actions i32 [T] as taken (anything where none was).
  Returns (log pi(actions) f32 [T], baseline f32 [T], margin f32 [T]),
  and with `logits` the [T, vocabulary] logits too. Call it outside
  `jax.jit`: it runs a matrix, and a block of rows, at a time.
  """
  p = params['params']
  t = len(tokens)
  block = min(block or t, t)
  padded = -(-t // block) * block  # rows beyond t attend, unattended
  tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, padded - t))
  actions = jnp.pad(jnp.asarray(actions, jnp.int32), (0, padded - t))
  pos = jnp.arange(padded)
  x = jnp.take(p['embedding'], tokens, axis=0).astype(jnp.float32)
  margin = jnp.full((padded,), jnp.inf)
  for i in range(len(p['core'])):
    x, layer_margin = _layer(
        p['core'][f'block_{i}'], x, pos, i, dims, num_heads,
        float(rope_theta), float(norm_eps), operand_dtype, cache_dtype,
        router_dtype, block)
    margin = jnp.minimum(margin, layer_margin)
  n = _norm(x, p['final_norm']['scale'], float(norm_eps))
  baseline = (jnp.dot(n, p['baseline']['kernel'].astype(jnp.float32),
                      precision=HIGHEST)[:, 0] +
              p['baseline']['bias'].astype(jnp.float32)[0])
  head = p['policy_logits']['kernel']
  log_probs = _log_probs(n, head, actions, operand_dtype,
                         vocab_block or head.shape[1])
  out = (log_probs[:t], baseline[:t], margin[:t])
  if logits:
    out += (_project(n, head, operand_dtype)[:t],)
  return out

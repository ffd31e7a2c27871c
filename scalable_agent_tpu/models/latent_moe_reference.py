"""The plain reference of the sequence policy with latent attention and
routed experts: the whole forward pass of one episode in float32
jax.numpy, prefill form only, no cache, no batching, no kernel.

For an episode's tokens `x_0..x_{T-1}` (position t = index t), per
layer, `h = RMSNorm(x)`:

    c_q = RMSNorm(h W_dq);  [q_c,i ; q_r,i] = c_q W_uq   per head i
    [c_kv ; k_r] = h W_dkv;  c_kv <- RMSNorm(c_kv)
    q_r,i and k_r rotated at t (YaRN frequencies, half-rotation)
    [k_c,s,i ; v_s,i] = c_kv,s W_ukv,i
    a_t,s,i = softmax_{s<=t}((q_c,i . k_c,s,i + q_r,i . k_r,s) scale)
    x <- x + concat_i(sum_s a_t,s,i v_s,i) W_o

then `x <- x + FFN(RMSNorm(x))`: the dense SwiGLU in the first
`first_dense_layers` layers; in the others the shared expert plus, in
a plain loop over the experts this share holds (`experts_held` from
`expert_offset`), `g_e FFN_e(x)` for the tokens whose router chose
expert e. The router: `s = sigmoid(x W_g)` over all `routed_experts`;
choice on `s + bias`: a group's score is the sum of its two largest,
the `expert_groups_kept` best groups stay, the `experts_per_token`
largest among them are chosen (sorted, ties to the lower index);
`g_e = routed_scale s_e / sum_chosen s`. What the experts held
elsewhere would add is left out, as in the program. Then the final
norm, `log softmax` over the vocabulary slice (in column blocks) and
the value head.

`dims` carries the configuration's sizes by the names of
`models/latent_moe.py :: LatentMoEDims` (any object with those
attributes). The parameters are used as given (the served copy's
bfloat16-rounded values, widened to float32 one matrix at a time).
`operand_dtype` is the configuration's stated precision of the
products' operands: where the served model rounds an activation to
bfloat16 before a matrix product, so does the reference, by the same
round-to-nearest; all arithmetic stays float32 at
`Precision.HIGHEST`. `cache_dtype` rounds `(c_kv, k_r)` as the cache
that holds them does. None rounds nothing. To fit beside the served
parameters at an episode's full length (16,384 tokens of the
published widths: every head's queries alone would be 1.6 GB),
attention is taken a group of heads at a time from the query's
up-projection to the output projection's rows, and of a group a block
of queries at a time; the feed-forward a block of tokens at a time
(`block`). The arithmetic of a token does not depend on the blocks
(the output projection's sum over heads is taken group by group).

Besides `(log pi(actions), baseline)` the reference reports, per
token, the smallest MARGIN of any top-k choice its routers made for
it: over the layers, the gap between the last group kept and the
first rejected, and between the last expert chosen and the first
rejected, in units of the scores, counting a gap only where the other
outcome would change what THIS share computes (`_router`). A choice is
discontinuous: a token whose margin is under the rounding of the
scores may route otherwise in the served model, and the comparison
sets it aside.
"""

import functools
import math

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


def _mscale(factor, m):
  return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(dims, theta):
  """YaRN's frequency of each rotary pair, float64 numpy -> float32."""
  dim = dims.qk_rope_head_dim
  j = np.arange(dim // 2, dtype=np.float64)
  plain = float(theta) ** (-2.0 * j / dim)
  stretched = plain / dims.rope_factor

  def turns_at(rotations):  # the pair that turns so often in the context
    return dim * math.log(dims.rope_original_max /
                          (rotations * 2 * math.pi)) / (2 * math.log(theta))

  low = max(math.floor(turns_at(dims.rope_beta_fast)), 0)
  high = min(math.ceil(turns_at(dims.rope_beta_slow)), dim - 1)
  ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
  return ((1.0 - ramp) * plain + ramp * stretched).astype(np.float32)


def _rope(x, pos, dims, theta):
  """x [T, ..., rope] at positions pos [T], half-rotation."""
  ang = pos.astype(jnp.float32)[:, None] * inv_freq(dims, theta)
  ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
  magnitude = (_mscale(dims.rope_factor, dims.rope_mscale) /
               _mscale(dims.rope_factor, dims.rope_mscale_all_dim))
  cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * magnitude
  sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * magnitude
  half = x.shape[-1] // 2
  x1, x2 = x[..., :half], x[..., half:]
  return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _blocks(fn, block, *rows):
  """`fn` over the leading axis a block of rows at a time."""
  t = rows[0].shape[0]
  split = lambda x: x.reshape((t // block, block) + x.shape[1:])  # noqa: E731
  out = jax.lax.map(lambda xs: fn(*xs), tuple(split(x) for x in rows))
  return jax.tree_util.tree_map(
      lambda x: x.reshape((t,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=('operand_dtype',))
def _linear(x, kernel, operand_dtype):
  return _project(x, kernel, operand_dtype)


@functools.partial(jax.jit, static_argnames=(
    'dims', 'heads', 'theta', 'eps', 'operand_dtype', 'cache_dtype',
    'block'))
def _attention(c_q, kv, w_uq, w_ukv, w_o, norms, pos, dims, heads, theta,
               eps, operand_dtype, cache_dtype, block):
  """The query latent c_q [T, q_rank] and kv [T, rank + rope] as
  projected -> the attention's output after W_o, [T, hidden]. A group
  of heads at a time from the query's up-projection to the output
  projection's rows (so no array of all heads' queries exists), and of
  a group a block of queries at a time."""
  t = c_q.shape[0]
  nope, rope, rank = (dims.qk_nope_head_dim, dims.qk_rope_head_dim,
                      dims.kv_lora_rank)
  v_dim = dims.v_head_dim
  group = math.gcd(heads, 16)
  c_kv = _rounded(_rms_norm(kv[:, :rank], norms, eps), cache_dtype)
  k_r = _rounded(_rope(kv[:, rank:], pos, dims, theta), cache_dtype)
  m = _mscale(dims.rope_factor, dims.rope_mscale_all_dim)
  scale = (nope + rope) ** -0.5 * m * m

  def heads_of(out, weights):
    w_q, w_kv, w_out = weights  # this group's columns, columns, rows
    q = _project(c_q, w_q, operand_dtype).reshape(t, group, nope + rope)
    q_c, q_r = q[..., :nope], _rope(q[..., nope:], pos, dims, theta)
    expanded = _project(c_kv, w_kv.reshape(rank, -1),
                        operand_dtype).reshape(t, group, nope + v_dim)
    k_c, v = expanded[..., :nope], expanded[..., nope:]

    def queries(q_c, q_r, q_pos):
      scores = scale * (
          jnp.einsum('thd,shd->hts', _rounded(q_c, operand_dtype),
                     _rounded(k_c, operand_dtype), precision=HIGHEST) +
          jnp.einsum('thr,sr->hts', _rounded(q_r, operand_dtype),
                     _rounded(k_r, operand_dtype), precision=HIGHEST))
      causal = pos[None, :] <= q_pos[:, None]
      a = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                         axis=-1)
      return jnp.einsum('hts,shv->thv', _rounded(a, operand_dtype),
                        _rounded(v, operand_dtype), precision=HIGHEST)

    o = _blocks(queries, block, q_c, q_r, pos).reshape(t, group * v_dim)
    return out + _project(o, w_out, operand_dtype), None

  groups = heads // group
  by_group = lambda w, axis: jnp.moveaxis(  # noqa: E731
      w.reshape(w.shape[:axis] + (groups, -1) + w.shape[axis + 1:]),
      axis, 0)
  out, _ = jax.lax.scan(
      heads_of, jnp.zeros((t, w_o.shape[1]), jnp.float32),
      (by_group(w_uq, 1), by_group(w_ukv.reshape(rank, heads, -1), 1),
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


@functools.partial(jax.jit, static_argnames=('dims',))
def _router(x, w_g, bias, dims):
  """-> (weight of every expert for every token [T, E], 0 where not
  chosen; margin [T])."""
  t = x.shape[0]
  e, groups, k = (dims.routed_experts, dims.expert_groups,
                  dims.experts_per_token)
  kept = dims.expert_groups_kept
  s = jax.nn.sigmoid(jnp.dot(x, w_g.astype(jnp.float32),
                             precision=HIGHEST))
  biased = s + bias.astype(jnp.float32)
  by_group = jnp.sort(biased.reshape(t, groups, e // groups), axis=-1)
  group_score = by_group[..., -1] + by_group[..., -2]

  def choose(keep):  # among the experts of the groups kept
    among = jnp.where(jnp.repeat(keep, e // groups, axis=1), biased,
                      -jnp.inf)
    return _top(among, k)

  keep, group_ranked, group_order = _top(group_score, kept)
  chosen, ranked, order = choose(keep)
  picked = jnp.where(chosen, s, 0.0)
  weights = dims.routed_scale * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

  # The margin: by how little the choice could have fallen otherwise,
  # where otherwise means another result HERE. The two near misses are
  # the first group rejected taking the place of the last kept, and the
  # first expert rejected that of the last chosen; either counts if it
  # changes the chosen set and an expert this share holds is chosen
  # before or after (membership, or the sum the weights are normalised
  # by, then changes; a token none of whose experts are held gets
  # nothing from the routed part either way).
  held = ((jnp.arange(e) >= dims.expert_offset) &
          (jnp.arange(e) < dims.expert_offset + dims.experts_held))
  rows = jnp.arange(t)

  def counts(other):
    return (jnp.any(other != chosen, axis=-1) &
            jnp.any((other | chosen) & held, axis=-1))

  near = chosen.at[rows, order[:, k - 1]].set(False).at[
      rows, order[:, k]].set(True)
  margin = jnp.where(counts(near) & jnp.isfinite(ranked[:, k]),
                     ranked[:, k - 1] - ranked[:, k], jnp.inf)
  if kept < groups:
    near = choose(keep.at[rows, group_order[:, kept - 1]].set(False).at[
        rows, group_order[:, kept]].set(True))[0]
    margin = jnp.minimum(margin, jnp.where(
        counts(near), group_ranked[:, kept - 1] - group_ranked[:, kept],
        jnp.inf))
  return weights, margin


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(x, scale, eps):
  return _rms_norm(x, scale, eps)


def _layer(p, x, pos, i, dims, heads, theta, eps, operand_dtype,
           cache_dtype, block):
  """One layer over the whole episode, a matrix at a time (each is
  widened to float32 while it is used and no longer) -> (x, margin)."""
  kernel = lambda name: p[name]['kernel']  # noqa: E731
  h = _norm(x, p['input_norm']['scale'], eps)
  c_q = _norm(_linear(h, kernel('q_a_proj'), operand_dtype),
              p['q_a_layernorm']['scale'], eps)
  kv = _linear(h, kernel('kv_a_proj_with_mqa'), operand_dtype)
  x = x + _attention(c_q, kv, kernel('q_b_proj'), kernel('kv_b_proj'),
                     kernel('o_proj'), p['kv_a_layernorm']['scale'], pos,
                     dims, heads, theta, eps, operand_dtype, cache_dtype,
                     block)
  h = _norm(x, p['post_norm']['scale'], eps)
  ones = jnp.ones((x.shape[0],), jnp.float32)
  if i < dims.first_dense_layers:
    return (x + _ffn_blocks(h, p['mlp'], ones, operand_dtype, block),
            jnp.full((x.shape[0],), jnp.inf))
  moe = p['moe']
  weights, margin = _router(h, moe['router']['kernel'],
                            moe['e_score_correction_bias'], dims)
  y = _ffn_blocks(h, moe['shared_expert'], ones, operand_dtype, block)
  for e in range(dims.experts_held):  # the dense loop over the share
    # One expert at a time ON THE DEVICE too: dispatched ahead, every
    # expert's output would be allocated at once, gigabytes of them.
    y = jax.block_until_ready(y + _ffn_blocks(
        h, moe[f'expert_{e}'], weights[:, dims.expert_offset + e],
        operand_dtype, block))
  return x + y, margin


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


def forward(params, tokens, actions, *, dims, num_heads, rope_theta=1e4,
            norm_eps=1e-6, operand_dtype=None, cache_dtype=None,
            vocab_block=None, block=None, logits=False):
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
        block)
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

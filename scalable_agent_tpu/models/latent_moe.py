"""Latent attention (MLA) over a per-session latent cache, with dense
and routed-expert feed-forward layers, as a recurrent core.

A block is `x + Attention(RMSNorm(x))`, then `x + FFN(RMSNorm(x))`,
the residual stream float32 (as models/retention.py keeps it).

Latent attention, token t of an episode, head i:

    c_q = RMSNorm(h W_dq)                    [q_a_proj, q_a_layernorm]
    [q_c,i ; q_r,i] = c_q W_uq               [q_b_proj]  (nope + rope)
    [c_kv ; k_r] = h W_dkv, c_kv <- RMSNorm  [kv_a_proj_with_mqa,
                                              kv_a_layernorm]
    q_r,i and k_r (one for all heads) rotated at position t (YaRN)

The cache keeps `(c_kv, k_r)` of every token of the episode: `kv_lora_
rank + qk_rope_head_dim` numbers a token and layer, in the parameters'
dtype, one `[rows, 576, capacity]` leaf a layer of the carry (of the
inference server's arena), written in place at each row's position: a
token is a COLUMN, the positions run along the lanes, which is how a
TPU lays 576-wide rows out whatever the shape says (ops/mla_pallas.py
has the reason and the price of the other way round).
`done` resets a row's POSITION; what the row held stays where it is and
is never read again, because a row reads its cache only as far as its
position.

One attention, two forms of it:

- the prefill form (`chunk`: C tokens of one session at once, causal
  inside the chunk, the cache before it) expands keys and values per
  head, `[k_c,s,i ; v_s,i] = c_kv,s W_ukv,i` [kv_b_proj], and takes
  `softmax_s<=t((q_c,i . k_c,s,i + q_r,i . k_r,s) scale) v_s,i`;
- the decode form (`step`: one token a row) is the same function
  reassociated: `q~_i = W_uk,i q_c,i` is taken to the latent's width,
  scores are `(q~_i . c_kv,s + q_r,i . k_r,s) scale`, the weighted sum
  is over the latents, `o~_i = sum_s a c_kv,s`, and `o_i = W_uv,i^T
  o~_i`, so the 576 numbers are all that is read a cached token.

`scale = (nope + rope)^-1/2 m^2`, `m = 0.1 mscale_all_dim ln(factor) +
1`. Both forms walk the cache in blocks under a running softmax: the
prefill form in XLA's own operations as far as the chunk's last token,
the decode form in a kernel that reads each row's cache as far as that
row's position (ops/mla_pallas.py).

Feed-forward: `FFN_w(x) = W_down(silu(W_gate x) * W_up x)`. The first
`first_dense_layers` blocks have the dense width. The others route:
`s = sigmoid(x W_g)` over ALL `routed_experts` (float32 operands),
choice on `s + bias` within the `expert_groups_kept` best of
`expert_groups` groups (a group scores the sum of its two largest),
the `experts_per_token` largest among them; weights `routed_scale s_e
/ sum_chosen s`. The layer is told which experts it holds
(`experts_held` from `expert_offset`): it computes their part of the
result and the shared expert's, `y = FFN_shared(x) + sum_{e chosen and
held} g_e FFN_e(x)`; what the absent experts would add is left out, as
one chip of an expert-parallel deployment leaves it to the others. An
expert nobody routed to in a call is skipped, weights unread; no token
is dropped whatever the load.

Precision, as the configuration states it: parameters and cache in
`param_dtype`, the operands of every product rounded to `dtype`,
accumulated in float32; norms, rotary, the router, softmax and the
residual stream in float32.
"""

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.retention import _Linear, _Scale
from scalable_agent_tpu.ops import mla_pallas

# Cached tokens a pass of the running softmax takes: of a row in the
# decode form (a grid step of the kernel), of the one session in the
# prefill form.
DECODE_BLOCK = 1024
PREFILL_BLOCK = 1024
# The per-call counters a routed layer sows (collection 'counters');
# the inference server sums them over a call's layers.
COUNTERS = ('routed_rows_held', 'experts_hit')
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LatentMoEDims:
  """What the latent core adds to `SequenceAgent`'s own widths; the
  defaults are the tiny size the CPU tests run."""
  q_lora_rank: int = 24
  kv_lora_rank: int = 16
  qk_nope_head_dim: int = 8
  qk_rope_head_dim: int = 4
  v_head_dim: int = 8
  first_dense_layers: int = 1
  moe_size: int = 32               # an expert's width
  routed_experts: int = 16         # the router's outputs
  experts_held: int = 4            # of them computed here ...
  expert_offset: int = 0           # ... from this one on
  experts_per_token: int = 4
  expert_groups: int = 4
  expert_groups_kept: int = 2
  routed_scale: float = 2.5
  shared_experts: int = 1
  rope_factor: float = 40.0        # YaRN
  rope_original_max: int = 4096
  rope_beta_fast: float = 32.0
  rope_beta_slow: float = 1.0
  rope_mscale: float = 1.0
  rope_mscale_all_dim: float = 1.0
  cache_capacity: int = 64         # tokens of an episode a row holds
  prefill_chunk: int = 8           # tokens a `chunk` call takes

  @property
  def cache_width(self):
    return self.kv_lora_rank + self.qk_rope_head_dim

  def check(self):
    group = self.routed_experts // max(self.expert_groups, 1)
    if (self.routed_experts % self.expert_groups or group < 2
        or self.expert_groups_kept > self.expert_groups
        or self.expert_groups_kept * group < self.experts_per_token):
      raise ValueError(
          'the router chooses experts_per_token among the kept groups '
          'of at least two experts each: '
          f'{self.routed_experts} experts, {self.expert_groups} groups, '
          f'{self.expert_groups_kept} kept, {self.experts_per_token} a '
          'token')
    if not (0 <= self.expert_offset and 0 < self.experts_held and
            self.expert_offset + self.experts_held <= self.routed_experts):
      raise ValueError('the experts held lie among the routed ones')
    if self.qk_rope_head_dim % 2:
      raise ValueError('rotary dimensions come in pairs')
    for block in (DECODE_BLOCK, PREFILL_BLOCK):
      if self.cache_capacity % min(block, self.cache_capacity):
        raise ValueError(
            f'cache_capacity {self.cache_capacity} is not a multiple of '
            f'the attention block {block}')


def _yarn_mscale(factor, mscale):
  return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(d):
  m = _yarn_mscale(d.rope_factor, d.rope_mscale_all_dim)
  return (d.qk_nope_head_dim + d.qk_rope_head_dim) ** -0.5 * m * m


def yarn_inv_freq(d, theta):
  """The rotary frequency of each of the `qk_rope_head_dim / 2` pairs,
  float32: `theta^(-2j/dim)` for the pairs that turn more than
  `beta_fast` times in the original context, the same over `factor`
  for those that turn less than `beta_slow` times, a linear ramp
  between the two correction dimensions."""
  dim = d.qk_rope_head_dim
  extra = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
  inter = extra / d.rope_factor

  def correction_dim(rotations):
    return (dim * math.log(d.rope_original_max /
                           (rotations * 2 * math.pi)) /
            (2 * math.log(theta)))

  low = max(math.floor(correction_dim(d.rope_beta_fast)), 0)
  high = min(math.ceil(correction_dim(d.rope_beta_slow)), dim - 1)
  ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                 0.0, 1.0)
  return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate(x, pos, d, theta):
  """x f32 [N, ..., rope] at positions pos [N]: half-rotation pairing
  (dimension j with j + rope/2), YaRN frequencies and magnitude."""
  ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(d, theta)
  ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
  magnitude = (_yarn_mscale(d.rope_factor, d.rope_mscale) /
               _yarn_mscale(d.rope_factor, d.rope_mscale_all_dim))
  cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * magnitude
  sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * magnitude
  half = x.shape[-1] // 2
  return x * cos + jnp.concatenate(
      [-x[..., half:], x[..., :half]], -1) * sin


def route(scores, bias, d):
  """scores f32 [N, routed_experts], the sigmoid outputs -> (chosen i32
  [N, k], weights f32 [N, k]). The bias moves the choice and not the
  weight; ties go to the lower index (`lax.top_k`)."""
  n, e = scores.shape
  groups = d.expert_groups
  biased = scores + bias.astype(jnp.float32)
  by_group = biased.reshape(n, groups, e // groups)
  group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
  _, kept = jax.lax.top_k(group_score, d.expert_groups_kept)
  keep = jnp.zeros((n, groups), bool).at[
      jnp.arange(n)[:, None], kept].set(True)
  among = jnp.where(jnp.repeat(keep, e // groups, axis=1), biased,
                    -jnp.inf)
  _, chosen = jax.lax.top_k(among, d.experts_per_token)
  picked = jnp.take_along_axis(scores, chosen, axis=1)
  weights = d.routed_scale * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
  return chosen.astype(jnp.int32), weights


def _dot(a, kernel, dtype):
  return jnp.dot(a.astype(dtype), kernel.astype(dtype),
                 preferred_element_type=jnp.float32)


def ffn(x, weights, dtype):
  gate, up, down = weights
  return _dot(jax.nn.silu(_dot(x, gate, dtype)) * _dot(x, up, dtype),
              down, dtype)


class _Kernel(nn.Module):
  """A matrix the caller multiplies itself (inside a `lax.cond`, or
  reassociated), initialised as `_Linear`'s."""
  shape: Any
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self):
    return self.param(
        'kernel', nn.initializers.variance_scaling(
            1.0, 'fan_in', 'normal'), tuple(self.shape), self.param_dtype)


class _FFNWeights(nn.Module):
  hidden_size: int
  width: int
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self):
    h, w = self.hidden_size, self.width
    return (_Kernel((h, w), self.param_dtype, name='gate_proj')(),
            _Kernel((h, w), self.param_dtype, name='up_proj')(),
            _Kernel((w, h), self.param_dtype, name='down_proj')())


def _running_softmax(carry, scores, values_fn):
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


def attend_prefill(q_c, q_r, cache, slot, pos0, n_valid, w_ukv, scale,
                   dtype):
  """The prefill form for C tokens of the session in row `slot`, at
  positions `pos0..`, over the cache's columns up to each token's own.

  q_c f32 [C, H, nope], q_r f32 [C, H, rope] (rotated); cache [S,
  rank + rope, capacity]; w_ukv [rank, H, nope + v]. Returns f32 [C, H,
  v]."""
  c, heads, nope = q_c.shape
  rank = w_ukv.shape[0]
  v_dim = w_ukv.shape[2] - nope
  capacity = cache.shape[2]
  block = min(PREFILL_BLOCK, capacity)
  q_pos = pos0 + jnp.arange(c)
  last = jnp.clip(pos0 + n_valid - 1, 0, capacity - 1)
  q_c, q_r = q_c.astype(dtype), q_r.astype(dtype)

  def body(j, carry):
    tokens = jax.lax.dynamic_slice(
        cache, (slot, 0, j * block), (1, cache.shape[1], block))[0]
    expanded = jnp.einsum(
        'rs,rx->sx', tokens[:rank].astype(dtype),
        w_ukv.reshape(rank, -1).astype(dtype),
        preferred_element_type=jnp.float32).reshape(
            block, heads, nope + v_dim)
    k_c = expanded[..., :nope].astype(dtype)
    values = expanded[..., nope:].astype(dtype)
    scores = scale * (
        jnp.einsum('thd,shd->hts', q_c, k_c,
                   preferred_element_type=jnp.float32) +
        jnp.einsum('thr,rs->hts', q_r, tokens[rank:].astype(dtype),
                   preferred_element_type=jnp.float32))
    columns = j * block + jnp.arange(block)
    valid = columns[None, :] <= q_pos[:, None]
    scores = jnp.where(valid[None], scores, -jnp.inf)
    return _running_softmax(
        carry, scores, lambda p: jnp.einsum(
            'hts,shv->htv', p.astype(dtype), values,
            preferred_element_type=jnp.float32))

  init = (jnp.full((heads, c), -jnp.inf, jnp.float32),
          jnp.zeros((heads, c), jnp.float32),
          jnp.zeros((heads, c, v_dim), jnp.float32))
  _, l, acc = jax.lax.fori_loop(0, last // block + 1, body, init)
  return jnp.swapaxes(acc / l[..., None], 0, 1)


class RoutedExperts(nn.Module):
  """The shared expert and this chip's share of the routed ones."""
  dims: LatentMoEDims
  hidden_size: int
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, live):
    """x f32 [N, hidden] (normed); live bool [N]: the rows that are
    some session's token (a padded row routes nowhere)."""
    d = self.dims
    with jax.named_scope('moe'):
      with jax.named_scope('router'):
        w_g = _Kernel((self.hidden_size, d.routed_experts),
                      self.param_dtype, name='router')()
        # Seeded small and non-zero, so that the choice it moves is
        # exercised [assumed: a trained model's is learned].
        bias = self.param('e_score_correction_bias',
                          nn.initializers.normal(0.02),
                          (d.routed_experts,), jnp.float32)
        scores = jax.nn.sigmoid(jnp.dot(
            x, w_g.astype(jnp.float32), precision=HIGHEST))
        chosen, weights = route(scores, bias, d)
        local = chosen - d.expert_offset
        held = (local >= 0) & (local < d.experts_held) & live[:, None]
        gates = jnp.sum(
            jax.nn.one_hot(local, d.experts_held, dtype=jnp.float32) *
            jnp.where(held, weights, 0.0)[..., None], axis=1)  # [N, held]
        hit = jnp.any(gates > 0, axis=0)
        self.sow('counters', 'routed_rows_held',
                 jnp.sum(held).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        self.sow('counters', 'experts_hit',
                 jnp.sum(hit).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
      with jax.named_scope('shared'):
        y = ffn(x, _FFNWeights(self.hidden_size,
                               d.moe_size * d.shared_experts,
                               self.param_dtype, name='shared_expert')(),
                self.dtype)
      with jax.named_scope('experts'):
        for e in range(d.experts_held):
          expert = _FFNWeights(self.hidden_size, d.moe_size,
                               self.param_dtype, name=f'expert_{e}')()
          y = y + jax.lax.cond(
              hit[e],
              lambda w=expert, g=gates[:, e]: ffn(x, w, self.dtype) *
              g[:, None],
              lambda: jnp.zeros_like(x))
    return y


class LatentMoEBlock(nn.Module):
  dims: LatentMoEDims
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float
  norm_eps: float
  dense: bool
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x, cache, slots, pos, live, prefill):
    """x f32 [N, hidden]: token n lies at position `pos[n]` of the
    cache row `slots[n]` (both in range; `live[n]` false: the row is
    no session's, and routes nowhere); cache [S, rank + rope,
    capacity]. `prefill` None: N rows of N sessions, the decode form;
    `(slot, pos0, n_valid)`: N tokens of one session, the prefill
    form -> (x, cache)."""
    d = self.dims
    n, heads = x.shape[0], self.num_heads
    nope, rope, rank = d.qk_nope_head_dim, d.qk_rope_head_dim, d.kv_lora_rank
    linear = lambda f, name: _Linear(  # noqa: E731
        f, self.dtype, self.param_dtype, name=name)
    norm = lambda name: _Scale(self.param_dtype, name=name)  # noqa: E731
    with jax.named_scope('mla'):
      with jax.named_scope('proj'):
        h = norm('input_norm')(x, self.norm_eps)
        c_q = norm('q_a_layernorm')(linear(d.q_lora_rank, 'q_a_proj')(h),
                                    self.norm_eps)
        q = linear(heads * (nope + rope), 'q_b_proj')(c_q).reshape(
            n, heads, nope + rope)
        q_c = q[..., :nope]
        q_r = rotate(q[..., nope:], pos, d, self.rope_theta)
        kv = linear(rank + rope, 'kv_a_proj_with_mqa')(h)
        c_kv = norm('kv_a_layernorm')(kv[:, :rank], self.norm_eps)
        k_r = rotate(kv[:, rank:], pos, d, self.rope_theta)
        w_ukv = _Kernel((rank, heads * (nope + d.v_head_dim)),
                        self.param_dtype, name='kv_b_proj')().reshape(
                            rank, heads, nope + d.v_head_dim)
        scale = softmax_scale(d)
        if prefill is None:
          # Absorbed: the query goes to the latent's width.
          q_lat = jnp.einsum(
              'nhd,rhd->nhr', q_c.astype(self.dtype),
              w_ukv[..., :nope].astype(self.dtype),
              preferred_element_type=jnp.float32)
          q_abs = jnp.concatenate([q_lat, q_r], -1) * scale
      with jax.named_scope('cache_write'):
        entry = jnp.concatenate([c_kv, k_r], -1).astype(cache.dtype)
        if prefill is None:
          cache = mla_pallas.write_rows(cache, entry, slots, pos)
        else:
          cache = write_chunk(cache, entry, *prefill, live)
      with jax.named_scope('attend'):
        if prefill is None:
          # Each row's own cache, as far as its position and no
          # further (ops/mla_pallas.py) -> the weighted latents.
          o_lat = mla_pallas.attend_rows(
              q_abs.astype(self.dtype), cache, slots, pos, rank=rank,
              block=min(DECODE_BLOCK, cache.shape[2]))
        else:
          o = attend_prefill(q_c, q_r, cache, *prefill, w_ukv, scale,
                             self.dtype)
      with jax.named_scope('out'):
        if prefill is None:
          o = jnp.einsum('nhr,rhv->nhv', o_lat.astype(self.dtype),
                         w_ukv[..., nope:].astype(self.dtype),
                         preferred_element_type=jnp.float32)
        x = x + linear(self.hidden_size, 'o_proj')(
            o.reshape(n, heads * d.v_head_dim))
    h = norm('post_norm')(x, self.norm_eps)
    if self.dense:
      with jax.named_scope('mlp'):
        x = x + ffn(h, _FFNWeights(self.hidden_size, self.mlp_size,
                                   self.param_dtype, name='mlp')(),
                    self.dtype)
    else:
      x = x + RoutedExperts(d, self.hidden_size, self.dtype,
                            self.param_dtype, name='moe')(h, live)
    return x, cache


class LatentMoEStack(core_lib.RecurrentCore):
  """N latent-attention blocks as one recurrent core. Carry: `{'pos':
  i32 [B], 'layers': (cache [B, rank + rope, capacity] param-dtype,
  ...)}`; the arena is the same with a row a slot and one more,
  advanced in place."""
  num_layers: int
  hidden_size: int
  num_heads: int
  mlp_size: int
  rope_theta: float = 1e4
  norm_eps: float = 1e-6
  dims: LatentMoEDims = LatentMoEDims()
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @property
  def chunk_size(self):
    return self.dims.prefill_chunk

  def initial_state(self, batch):
    d = self.dims
    return {
        'pos': jnp.zeros((batch,), jnp.int32),
        'layers': tuple(
            jnp.zeros((batch, d.cache_width, d.cache_capacity),
                      self.param_dtype)
            for _ in range(self.num_layers))}

  @nn.compact
  def _blocks(self, x, caches, slots, pos, live, prefill):
    """THE compact method: both forms run the same blocks."""
    new = []
    for i, cache in enumerate(caches):
      x, cache = LatentMoEBlock(
          self.dims, self.hidden_size, self.num_heads, self.mlp_size,
          self.rope_theta, self.norm_eps,
          i < self.dims.first_dense_layers, self.dtype, self.param_dtype,
          name=f'block_{i}')(x, cache, slots, pos, live, prefill)
      new.append(cache)
    return x, tuple(new)

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
    capacity = self.dims.cache_capacity
    if slots is None:
      slots = rows = jnp.arange(x.shape[0])
      live = jnp.ones(x.shape[:1], bool)
    else:
      rows, live = self._rows(slots, carry['pos'].shape[0] - 1)
    pos = jnp.where(done, 0, carry['pos'][rows])
    x, layers = self._blocks(x, carry['layers'], rows,
                             jnp.minimum(pos, capacity - 1), live, None)
    new_pos = carry['pos'].at[slots].set(pos + 1, mode='drop')
    return {'pos': new_pos, 'layers': layers}, x

  def chunk(self, carry, xs, n_valid, reset, slot=None):
    """The prefill form: C tokens of one session at once."""
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

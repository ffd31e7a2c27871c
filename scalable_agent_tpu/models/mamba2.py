"""The Mamba-2 mixer (state-space duality, scalar decay a head) in the
two forms a served session needs: a decode step of one token a row
over the rows of the inference server's arena, and the chunk form that
takes C tokens of one session at once.

For the mixer's input `h` (the block's normed stream), per token:

    [z | xBC | dt] = h W_in
    xBC <- silu(causal depthwise conv of width K over time, with bias)
    [x | B | C] = xBC        x: H heads of P; B, C: N (one group)
    dt <- softplus(dt + dt_bias);   A = -exp(A_log)
    per head h:  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
                 y_t = S_t C_t + D_h x_t
    out = RMSNorm(y * silu(z)) W_out     (the norm over all H P)

A session's state is two leaves of the carry (of the arena): the conv
window's last `K - 1` inputs `[rows, K - 1, H P + 2 N]` in the
parameters' dtype (the conv reads its inputs as that window holds
them, in both forms), and `S` `[rows, H, P, N]` in float32. A row whose
position is 0 begins its episode: both are taken as zero. That is how
`done` and a new prompt reset a session (models/core.py ::
PositionedCore), so a released slot's old state is never read.

The decode form gathers and scatters the conv window (50 KB a row) and
updates `S` in place by the kernel of ops/ssm_pallas.py. The chunk form
runs the conv over the window and the chunk's tokens, keeps the last
`K - 1` VALID inputs, and takes the recurrence in blocks of `block`
tokens by its quadratic (attention) form within a block and the state
across blocks: for a block with `a = dt A`, `c = cumsum(a)`,

    y_t = sum_{s <= t} exp(c_t - c_s) (C_t . B_s) dt_s x_s
          + exp(c_t) S_0 C_t + D x_t
    S_Q = exp(c_Q) S_0 + sum_s exp(c_Q - c_s) dt_s x_s B_s^T

float32 at `Precision.HIGHEST`. A padding token (beyond `n_valid`) has
`dt = 0`: it neither decays nor adds, so the state leaves the chunk as
its last valid token left it.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.models.retention import _Linear, rms_norm
from scalable_agent_tpu.ops import ssm_pallas

HIGHEST = jax.lax.Precision.HIGHEST
# The per-call counter the mixer sows (collection 'counters'): live rows
# a decode step advanced, summed by the server over a call's layers.
COUNTER = 'ssm_rows_stepped'


def _dt_bias_init(key, shape, dtype):
  """dt's bias as Mamba-2 seeds it: softplus^-1 of dt drawn log-uniform
  in [1e-3, 1e-1] [assumed]."""
  dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                  np.log(1e-3), np.log(1e-1)))
  return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
  """A_log as Mamba-2 seeds it: log of A drawn uniform in [1, 16]."""
  return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                    16.0)).astype(dtype)


def ssd_chunk(x, dt, a, b, c, state, block):
  """The recurrence over C tokens in blocks of `block` (module
  docstring). x f32 [C, H, P]; dt f32 [C, H] (0 at padding); a f32
  [H] (= -exp(A_log)); b, c f32 [C, N]; state f32 [H, P, N] -> (y f32
  [C, H, P] without the skip term, state)."""
  steps = x.shape[0]
  block = min(block, steps)
  split = lambda v: v.reshape((steps // block, block) + v.shape[1:])  # noqa: E731
  causal = np.tril(np.ones((block, block), bool))

  def one(s0, inputs):
    x, dt, b, c = inputs
    cum = jnp.cumsum(dt * a, axis=0)                    # [Q, H]
    seg = cum.T[:, :, None] - cum.T[:, None, :]         # [H, t, s]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.dot(c, b.T, precision=HIGHEST)         # [t, s]
    u = dt[..., None] * x                               # [Q, H, P]
    y = jnp.einsum('hts,shp->thp', decay * scores, u, precision=HIGHEST)
    y += jnp.exp(cum)[..., None] * jnp.einsum(
        'tn,hpn->thp', c, s0, precision=HIGHEST)
    to_end = jnp.exp(cum[-1][None] - cum)               # [Q, H]
    s = jnp.exp(cum[-1])[:, None, None] * s0 + jnp.einsum(
        'shp,sn->hpn', to_end[..., None] * u, b, precision=HIGHEST)
    return s, y

  state, y = jax.lax.scan(one, state, (split(x), split(dt), split(b),
                                       split(c)))
  return y.reshape((steps,) + y.shape[2:]), state


class Mamba2Mixer(nn.Module):
  """One Mamba-2 mixer; `dims` carries `ssm_heads`, `ssm_head_dim`,
  `ssm_state`, `conv_width`, `ssm_block`."""
  dims: Any
  hidden_size: int
  norm_eps: float
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, h, conv, ssm, rows, pos, live, prefill):
    """h f32 [N, hidden]: token n at position `pos[n]` of the arena row
    `rows[n]` (models/core.py :: PositionedCore._blocks); conv, ssm:
    the layer's two leaves -> (out f32 [N, hidden], conv, ssm)."""
    d = self.dims
    heads, dim, state = d.ssm_heads, d.ssm_head_dim, d.ssm_state
    inner = heads * dim
    channels = inner + 2 * state
    width = d.conv_width
    n = h.shape[0]
    dt_bias = self.param('dt_bias', _dt_bias_init, (heads,), jnp.float32)
    a = -jnp.exp(self.param('A_log', _a_log_init, (heads,), jnp.float32))
    skip = self.param('D', nn.initializers.ones, (heads,), jnp.float32)
    # N(0, 1 / K): a channel's K taps are its fan-in [assumed].
    kernel = self.param('conv_kernel', nn.initializers.normal(width ** -0.5),
                        (width, channels), self.param_dtype).astype(
                            jnp.float32)
    bias = self.param('conv_bias', nn.initializers.zeros, (channels,),
                      self.param_dtype).astype(jnp.float32)
    with jax.named_scope('mamba'):
      with jax.named_scope('in_proj'):
        zxbcdt = _Linear(2 * inner + 2 * state + heads, self.dtype,
                         self.param_dtype, name='in_proj')(h)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + channels], axis=-1)
        xbc = xbc.astype(conv.dtype)
        dt = jax.nn.softplus(dt + dt_bias)
      with jax.named_scope('conv'):
        if prefill is None:
          fresh = pos == 0
          window = jnp.concatenate([
              jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype),
                        conv[rows]), xbc[:, None]], axis=1)  # [N, K, Ch]
          conv = conv.at[rows].set(window[:, 1:])
          xbc = sum(window[:, k].astype(jnp.float32) * kernel[k]
                    for k in range(width))
        else:
          row, pos0, n_valid = prefill
          seq = jnp.concatenate([
              jnp.where(pos0 == 0, jnp.zeros((), conv.dtype), conv[row]),
              xbc])                                     # [K - 1 + C, Ch]
          kept = jax.lax.dynamic_slice(seq, (n_valid, 0),
                                       (width - 1, channels))
          conv = jax.lax.dynamic_update_slice(conv, kept[None], (row, 0, 0))
          xbc = sum(seq[k:k + n].astype(jnp.float32) * kernel[k]
                    for k in range(width))
        xbc = jax.nn.silu(xbc + bias)
        x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
        x = x.reshape(n, heads, dim)
      with jax.named_scope('state_update'):
        if prefill is None:
          decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt * a))
          ssm, y = ssm_pallas.update_rows(ssm, rows, decay, dt[..., None] * x,
                                          b, c)
          self.sow('counters', COUNTER, jnp.sum(live).astype(jnp.int32),
                   reduce_fn=lambda p, q: p + q,
                   init_fn=lambda: jnp.zeros((), jnp.int32))
        else:
          dt = jnp.where(live[:, None], dt, 0.0)
          s0 = jnp.where(pos0 == 0, 0.0, ssm[row])
          y, s = ssd_chunk(x, dt, a, b, c, s0, d.ssm_block)
          ssm = jax.lax.dynamic_update_slice(ssm, s[None], (row, 0, 0, 0))
        y = y + skip[:, None] * x
      with jax.named_scope('gate_norm'):
        norm = self.param('norm', nn.initializers.ones, (inner,),
                          self.param_dtype)
        y = rms_norm(y.reshape(n, inner) * jax.nn.silu(z), norm,
                     self.norm_eps)
      with jax.named_scope('out_proj'):
        out = _Linear(self.hidden_size, self.dtype, self.param_dtype,
                      name='out_proj')(y)
    return out, conv, ssm

"""Conv-torso byte/time levers, measured (VERDICT r4 next-round #1).

The flagship step is HBM-bound and the conv torso's backward is ~87%
of all bytes (docs/PERF.md byte attribution); the section-1 pre-pool
activation ([3232, 72, 96, 16] bf16 = 715 MB) is the single biggest
tensor. This script measures each candidate lever in isolation at
flagship shapes — step time via async chains with one value-readback
barrier, bytes/FLOPs via XLA cost_analysis — so each can be taken or
rejected with numbers, remat-style:

  s1_baseline      conv3x3(3->16) + maxpool3x3/2 (the parity model)
  s1_strided       conv3x3/2 (the 'deep_fast' section form)
  s1_argmax_idx    custom-VJP conv+pool: backward rebuilds the sparse
                   pool gradient from stored uint8 argmax indices
                   instead of re-reading the 715 MB pre-pool tensor
  torso_baseline   full deep torso fwd+bwd
  torso_deep_fast  full strided-conv torso fwd+bwd
  torso_nchw       full deep torso computed in NCHW dimension numbers
                   (layout sweep: does XLA's TPU emitter prefer it?)

Usage: python scripts/conv_levers.py          # real chip
       SMOKE=1 python scripts/conv_levers.py  # CPU mechanics check

Prints one JSON line per variant + a summary table.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMOKE = os.environ.get('SMOKE') == '1'

import jax  # noqa: E402

if SMOKE:
  jax.config.update('jax_platforms', 'cpu')

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402


def _timed(fn, args, n=None):
  """(seconds/call, bytes, flops) for a jitted fn — one value
  readback as the barrier."""
  n = n if n is not None else (2 if SMOKE else 20)
  jfn = jax.jit(fn)
  out = jfn(*args)
  float(jax.tree_util.tree_leaves(out)[0].ravel()[0])  # compile+sync
  t0 = time.perf_counter()
  for _ in range(n):
    out = jfn(*args)
  float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
  dt = (time.perf_counter() - t0) / n
  cost = jfn.lower(*args).compile().cost_analysis()
  if isinstance(cost, list):  # older jax returns [dict]
    cost = cost[0]
  return dt, cost.get('bytes accessed', float('nan')), cost.get(
      'flops', float('nan'))


def _loss_grad(apply_fn, params, x):
  """Scalar-loss fwd+bwd through apply_fn, grads w.r.t. params — the
  shape of traffic the train step's backward produces."""

  def loss(p):
    y = apply_fn(p, x)
    return jnp.sum(y.astype(jnp.float32) ** 2)

  return jax.grad(loss)(params)


# --- Section-1 variants (conv 3->16 at 72x96 + 2x spatial reduction) --

def _conv(x, w, b, strides=(1, 1)):
  # Plain bf16 conv, exactly like flax nn.Conv(dtype=bf16) in the
  # torso (a preferred_element_type=f32 accumulate makes the conv's
  # transpose rule mix dtypes under grad).
  y = lax.conv_general_dilated(
      x, w, window_strides=strides, padding='SAME',
      dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
  return y + b


def s1_baseline(params, frames):
  x = frames.astype(jnp.bfloat16) / 255.0
  y = _conv(x, params['w'], params['b'])
  return lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1),
                           (1, 2, 2, 1), 'SAME')


def s1_strided(params, frames):
  x = frames.astype(jnp.bfloat16) / 255.0
  return _conv(x, params['w'], params['b'], strides=(2, 2))


# Custom-VJP conv+pool: save (frames, w, argmax idx) — NOT the 715 MB
# pre-pool tensor. Backward scatters the pooled gradient through the
# stored indices and runs the conv wgrad against that sparse tensor.
@jax.custom_vjp
def s1_argmax(w, b, frames):
  pooled, _ = _s1_argmax_fwd_impl(w, b, frames)
  return pooled


def _pool_views(y):
  """The 9 shifted strided views of SAME-padded y: [9, N, Ho, Wo, C].

  XLA's SAME padding for (window 3, stride 2, even size) is
  ASYMMETRIC — pad_lo=0, pad_hi=1 (total pad = (Ho-1)*2+3-H = 1), so
  window i covers rows 2i..2i+2."""
  n, h, wd, c = y.shape
  ho, wo = h // 2, wd // 2
  yp = jnp.pad(y, ((0, 0), (0, 1), (0, 1), (0, 0)),
               constant_values=-jnp.inf)
  views = []
  for dy in range(3):
    for dx in range(3):
      views.append(lax.slice(yp, (0, dy, dx, 0),
                             (n, dy + 2 * (ho - 1) + 1,
                              dx + 2 * (wo - 1) + 1, c),
                             (1, 2, 2, 1)))
  return jnp.stack(views)


def _s1_argmax_fwd_impl(w, b, frames):
  x = frames.astype(jnp.bfloat16) / 255.0
  y = _conv(x, w, b)
  views = _pool_views(y)
  idx = jnp.argmax(views, axis=0).astype(jnp.uint8)
  pooled = jnp.max(views, axis=0)
  return pooled, idx


def _s1_argmax_fwd(w, b, frames):
  pooled, idx = _s1_argmax_fwd_impl(w, b, frames)
  return pooled, (w, b, frames, idx)


def _s1_argmax_bwd(res, g):
  w, b, frames, idx = res
  n, ho, wo, c = g.shape
  h, wd = 2 * ho, 2 * wo
  # Rebuild the sparse pre-pool gradient from the indices: for each of
  # the 9 window taps, the pooled grad lands at that tap's strided
  # position iff it was the argmax. Strided writes are expressed as
  # interior-dilated pads (stride-2 grid), offset by (dy, dx); the 9
  # planes sum into the conv-output gradient.
  planes = []
  for k in range(9):
    dy, dx = divmod(k, 3)
    contrib = jnp.where(idx == k, g, 0)
    # Tap (dy, dx) of window (i, j) sits at row 2i+dy, col 2j+dx in
    # the (0, 1)-padded frame (see _pool_views): interior-dilate by 2
    # and offset by (dy, dx) into the [h+1, w+1] padded grid.
    dilated = lax.pad(contrib, jnp.zeros((), g.dtype),
                      ((0, 0, 0),
                       (dy, (h + 1) - (dy + 2 * (ho - 1) + 1), 1),
                       (dx, (wd + 1) - (dx + 2 * (wo - 1) + 1), 1),
                       (0, 0, 0)))
    planes.append(dilated)
  dyp = functools.reduce(jnp.add, planes)
  dy_conv = dyp[:, :h, :wd, :]
  # Conv wgrad/bias-grad against the sparse gradient (frames are
  # integer — no dgrad exists for the input).
  x = frames.astype(jnp.bfloat16) / 255.0
  _, vjp = jax.vjp(lambda w_, b_: _conv(x, w_, b_), w, b)
  dw, db = vjp(dy_conv)
  return dw, db, None


s1_argmax.defvjp(_s1_argmax_fwd, _s1_argmax_bwd)


def s1_argmax_apply(params, frames):
  return s1_argmax(params['w'], params['b'], frames)


# --- Full-torso variants ---------------------------------------------

def _torso_apply(torso_name):
  from scalable_agent_tpu.models.torsos import TORSOS

  def apply_fn(params, frames):
    return TORSOS[torso_name](dtype=jnp.bfloat16).apply(params, frames)

  return apply_fn


def _torso_params(torso_name, frames):
  from scalable_agent_tpu.models.torsos import TORSOS
  return TORSOS[torso_name](dtype=jnp.bfloat16).init(
      jax.random.PRNGKey(0), frames)


def _nchw_full_apply(params, frames):
  """NCHW deep torso using the NHWC-initialized param tree (flax
  names: Conv_0..2 are the section convs, ResidualBlock_0..5 each hold
  Conv_0/Conv_1, Dense_0 is the projection)."""
  p = params['params']
  x = frames.astype(jnp.bfloat16) / 255.0
  x = jnp.transpose(x, (0, 3, 1, 2))

  def conv(x, cp, strides=(1, 1)):
    y = lax.conv_general_dilated(
        x, cp['kernel'].astype(x.dtype), window_strides=strides,
        padding='SAME',
        dimension_numbers=('NCHW', 'HWIO', 'NCHW'))
    return y + cp['bias'].astype(x.dtype)[None, :, None, None]

  rb = 0
  for section in range(3):
    x = conv(x, p[f'Conv_{section}'])
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), 'SAME')
    for _ in range(2):
      y = jax.nn.relu(x)
      y = conv(y, p[f'ResidualBlock_{rb}']['Conv_0'])
      y = jax.nn.relu(y)
      y = conv(y, p[f'ResidualBlock_{rb}']['Conv_1'])
      x = x + y
      rb += 1
  x = jax.nn.relu(x)
  # Match NHWC flatten order so Dense_0 weights mean the same thing.
  x = jnp.transpose(x, (0, 2, 3, 1))
  x = x.reshape((x.shape[0], -1))
  d = p['Dense_0']
  x = (x @ d['kernel'] + d['bias']).astype(jnp.bfloat16)
  return jax.nn.relu(x)


def main():
  merged = 404 if SMOKE else 3232  # (T+1)*B at flagship = 101*32
  h, w = (24, 32) if SMOKE else (72, 96)
  rng = np.random.RandomState(0)
  frames = jnp.asarray(
      rng.randint(0, 255, (merged, h, w, 3)), jnp.uint8)

  key = jax.random.PRNGKey(0)
  s1_params = {
      'w': jax.random.normal(key, (3, 3, 3, 16), jnp.bfloat16) * 0.1,
      'b': jnp.zeros((16,), jnp.bfloat16),
  }

  results = {}

  def measure(name, apply_fn, params):
    dt, nbytes, flops = _timed(
        lambda p, x: _loss_grad(apply_fn, p, x), (params, frames))
    results[name] = {
        'ms': round(dt * 1e3, 2),
        'gb': round(nbytes / 1e9, 2),
        'tflop': round(flops / 1e12, 3),
    }
    print(json.dumps({'variant': name, **results[name]}), flush=True)

  # Parity check first (SMOKE and chip): the argmax-idx backward must
  # match autodiff through the baseline exactly (same max-tie policy:
  # argmax picks the first max, like reduce_window's select).
  g_base = _loss_grad(s1_baseline, s1_params, frames)
  g_idx = _loss_grad(s1_argmax_apply, s1_params, frames)
  dw_err = float(jnp.max(jnp.abs(
      g_base['w'].astype(jnp.float32) - g_idx['w'].astype(jnp.float32))))
  scale = float(jnp.max(jnp.abs(g_base['w'].astype(jnp.float32))))
  print(json.dumps({'check': 's1_argmax_vjp_parity',
                    'max_abs_err': dw_err, 'grad_scale': scale}),
        flush=True)
  # Gate, not just telemetry (ADVICE r5 — CI runs the SMOKE path and
  # previously only PRINTED this number): same tolerance discipline as
  # scripts/pallas_conv_pool.py — bit-exact in SMOKE (both paths share
  # the same max-tie policy and CPU lowering; measured 0.0), a few
  # bf16 ulps relative to the gradient's own scale on the chip.
  tol = 1e-6 if SMOKE else 0.02 * scale
  assert dw_err <= tol, (
      f's1_argmax VJP parity broke: max_abs_err {dw_err} > tol {tol} '
      f'(grad_scale {scale})')

  measure('s1_baseline', s1_baseline, s1_params)
  measure('s1_strided', s1_strided, s1_params)
  measure('s1_argmax_idx', s1_argmax_apply, s1_params)

  deep_params = _torso_params('deep', frames)
  measure('torso_baseline', _torso_apply('deep'), deep_params)
  measure('torso_nchw', _nchw_full_apply, deep_params)
  from scalable_agent_tpu.models.torsos import TORSOS
  if 'deep_fast' in TORSOS:
    fast_params = _torso_params('deep_fast', frames)
    measure('torso_deep_fast', _torso_apply('deep_fast'), fast_params)

  print(json.dumps({'summary': results}))


if __name__ == '__main__':
  main()

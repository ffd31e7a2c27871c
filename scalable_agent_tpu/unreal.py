"""UNREAL pixel-control auxiliary task.

NOT in the reference — a planned extension (SURVEY §2.12 / BASELINE
config ladder). Implements the pixel-control auxiliary objective of
UNREAL ("Reinforcement Learning with Unsupervised Auxiliary Tasks",
Jaderberg et al. 2017 §3.1):

- pseudo-rewards: the frame is divided into `cell_size`×`cell_size`
  cells; the reward for a cell at step t is the mean absolute pixel
  change within the cell between consecutive observations;
- an auxiliary dueling Q-head (deconv from the LSTM output) predicts,
  per cell and per action, the discounted pseudo-return of maximally
  changing that cell;
- the loss is n-step Q-learning over the unroll, bootstrapped from
  max_a Q at the final frame (the same backward-recursion shape as
  V-trace — `lax.scan` over reversed time).

Everything here is pure JAX over [T, B] time-major tensors; the head
itself lives in models/agent.py (it needs the LSTM features).

Round 6 (the full-feature 20%, docs/PERF.md): the pixel-control path
got the step-cost treatment. Two numerics-preserving fast paths ship
behind config (defaults stay at the reference forms until the chip
rows land — see config.py), each parity-gated in tests/test_unreal.py,
neither measured on a chip yet:

- `pixel_control_rewards` has an INTEGER-DOMAIN form (uint8 frames
  only): |Δ| in int16, per-cell sum in int32, one float32 scale at
  the tiny [T, B, Hc, Wc] output — where the f32 reference form
  leaves it to the backend's fusion whether a full-resolution float
  copy of the [T+1, B, H, W, C] frame stack materializes (a real
  risk in a step that is ~72% HBM-bound). Mathematically identical:
  the integer sum is exact; one correctly-rounded division replaces
  a 48-term float mean.
- the stride-2 4×4 `ConvTranspose` of the Q-head can run as a
  depth-to-space decomposition (`_DeconvD2S`): one dense VALID 2×2
  conv over the zero-padded input producing all four output phases as
  channels, then a pixel-shuffle interleave. Parameter-identical to
  the deconv (same names, shapes, and init — checkpoints are
  interchangeable) and algebraically the same map; it removes the
  zero-stuffed fractionally-strided conv (75% wasted taps at stride
  2) that XLA's TPU emitter otherwise lowers the deconv to.

One numerics-AFFECTING lever is gated OFF by default:
`out_f32=False` keeps the Q-map in the compute dtype (bfloat16 on
TPU) until the loss's gather/max — the [N, Hc, Wc, A] f32
materialization halves — at the cost of bf16-rounding the Q values
the loss sees (config.pixel_control_q_f32).
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

DEFAULT_CELL_SIZE = 4
DEFAULT_DISCOUNT = 0.9

HEAD_IMPLS = ('deconv', 'd2s')


def pixel_control_rewards(frames, cell_size: int = DEFAULT_CELL_SIZE,
                          integer_path: bool = None):
  """Per-cell mean |Δpixel| between consecutive frames.

  Args:
    frames: uint8/float [T+1, B, H, W, C] observations (H, W divisible
      by cell_size).
    integer_path: None (auto) → use the integer-domain form exactly
      when `frames` is uint8; True forces it (uint8 required); False
      forces the f32 reference form. Both forms compute the same
      quantity — the integer form is the byte lever (no full-res
      float temporaries), the f32 form is the golden reference the
      parity test pins it to.
  Returns:
    f32 [T, B, H/cell, W/cell] pseudo-rewards; entry t covers the
    transition from frame t to frame t+1.
  """
  t1, b, h, w, c = frames.shape
  if h % cell_size or w % cell_size:
    raise ValueError(
        f'frame {h}x{w} not divisible by pixel-control cell_size '
        f'{cell_size}')
  hc, wc = h // cell_size, w // cell_size
  is_uint8 = frames.dtype == jnp.uint8
  if integer_path is None:
    integer_path = is_uint8
  if integer_path and not is_uint8:
    raise ValueError(
        f'integer-domain pixel_control_rewards needs uint8 frames, '
        f'got {frames.dtype}')
  if integer_path:
    # |a - b| exactly in int16 (uint8 range fits), per-cell sum in
    # int32 (≤ 255·cell²·C per cell — far inside i32), ONE f32 scale
    # at the [T, B, Hc, Wc] output. No [T, B, H, W, C] float
    # temporary exists at any point.
    d = jnp.abs(frames[1:].astype(jnp.int16) -
                frames[:-1].astype(jnp.int16))
    d = d.reshape(t1 - 1, b, hc, cell_size, wc, cell_size, c)
    cell_sum = d.astype(jnp.int32).sum(axis=(3, 5, 6))
    scale = 1.0 / (255.0 * cell_size * cell_size * c)
    return cell_sum.astype(jnp.float32) * jnp.float32(scale)
  f = frames.astype(jnp.float32) / 255.0
  diff = jnp.abs(f[1:] - f[:-1])  # [T, B, H, W, C]
  diff = diff.reshape(t1 - 1, b, hc, cell_size, wc, cell_size, c)
  return diff.mean(axis=(3, 5, 6))


class _DeconvD2S(nn.Module):
  """Stride-2 4×4 SAME ConvTranspose as conv + depth-to-space.

  Parameter-identical to `nn.ConvTranspose(features, (4, 4),
  strides=(2, 2), padding='SAME')`: a `kernel` [4, 4, in, out] and a
  `bias` [out] under the same names with the same initializers, so
  the two implementations are interchangeable on one checkpoint (the
  golden parity test applies both to shared params).

  Derivation: flax's ConvTranspose lowers to a correlation over the
  stride-dilated input with padding (2, 2). Output row 2i+r only
  meets kernel taps with row index ≡ r (mod 2) — the kernel splits
  into four 2×2 phase kernels w[r::2, c::2]. Computing all four
  phases as output channels of ONE VALID 2×2 conv over the
  (1, 1)-padded input yields every output pixel; phase (r, c) lives
  at window offset (r, c), and a reshape/transpose interleaves them
  back into the [2H, 2W] grid. Same multiply count as the dense view
  of the deconv, but as a standard conv (an [N·H·W, 2·2·in] @
  [2·2·in, 4·out] contraction) with no zero-stuffed rows.
  """
  features: int
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    n, h, w, cin = x.shape
    f = self.features
    kernel = self.param('kernel', nn.initializers.lecun_normal(),
                        (4, 4, cin, f), jnp.float32)
    bias = self.param('bias', nn.initializers.zeros_init(), (f,),
                      jnp.float32)
    x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                              dtype=self.dtype)
    # Phase kernels stacked on the output-channel dim, order
    # (r, c) ∈ [(0,0), (0,1), (1,0), (1,1)].
    phased = jnp.concatenate(
        [kernel[r::2, c::2] for r in (0, 1) for c in (0, 1)], axis=-1)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    y = jax.lax.conv_general_dilated(
        xp, phased, window_strides=(1, 1), padding='VALID',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))  # [n, h+1, w+1, 4f]
    parts = []
    for i, (r, c) in enumerate(((0, 0), (0, 1), (1, 0), (1, 1))):
      parts.append(y[:, r:r + h, c:c + w, i * f:(i + 1) * f])
    y = jnp.stack(parts, axis=-1)          # [n, h, w, f, (r·2+c)]
    y = y.reshape(n, h, w, f, 2, 2)        # [n, h, w, f, r, c]
    y = y.transpose(0, 1, 4, 2, 5, 3)      # [n, h, r, w, c, f]
    y = y.reshape(n, 2 * h, 2 * w, f)
    return y + bias


class PixelControlHead(nn.Module):
  """Dueling deconv Q-head: LSTM features → [Hc, Wc, A] Q-values.

  UNREAL §3.1 architecture shape: FC → spatial map → deconv ×2 → dueling
  value/advantage maps. `target_cells` = (H/cell, W/cell) of the frame.

  head_impl: 'deconv' (the stride-2 nn.ConvTranspose reference form)
  or 'd2s' (the parameter-identical depth-to-space recast — see
  _DeconvD2S). The stride-1 3×3 value/advantage ConvTransposes are
  already plain convolutions in disguise (SAME, no dilation) and stay
  shared between the impls.

  out_f32: cast the Q-map to float32 at the head (the r5 form). False
  keeps it in `dtype` until the loss gathers/maxes it — the byte
  lever behind config.pixel_control_q_f32.
  """
  num_actions: int
  target_cells: Any  # (hc, wc)
  dtype: Any = jnp.float32
  head_impl: str = 'deconv'
  out_f32: bool = True

  @nn.compact
  def __call__(self, core_out):
    if self.head_impl not in HEAD_IMPLS:
      raise ValueError(f'head_impl must be one of {HEAD_IMPLS}, got '
                       f'{self.head_impl!r}')
    hc, wc = self.target_cells
    # Round the base grid UP so the stride-2 deconv covers the target;
    # crop after (odd cell grids — e.g. 84x84/4 → 21x21 — just work).
    base_h, base_w, ch = (hc + 1) // 2, (wc + 1) // 2, 32
    x = nn.Dense(base_h * base_w * ch, dtype=self.dtype,
                 name='pc_fc')(core_out)
    x = nn.relu(x)
    x = x.reshape(x.shape[0], base_h, base_w, ch)
    if self.head_impl == 'd2s':
      x = _DeconvD2S(ch, dtype=self.dtype, name='pc_deconv')(x)
    else:
      x = nn.ConvTranspose(ch, (4, 4), strides=(2, 2), padding='SAME',
                           dtype=self.dtype, name='pc_deconv')(x)
    x = nn.relu(x)[:, :hc, :wc]
    value = nn.ConvTranspose(1, (3, 3), padding='SAME',
                             dtype=self.dtype, name='pc_value')(x)
    advantage = nn.ConvTranspose(self.num_actions, (3, 3),
                                 padding='SAME', dtype=self.dtype,
                                 name='pc_advantage')(x)
    advantage = advantage - advantage.mean(axis=-1, keepdims=True)
    q = value + advantage  # [N, hc, wc, A]
    return q.astype(jnp.float32) if self.out_f32 else q


def pixel_control_loss(q_values, actions, rewards, done,
                       discount: float = DEFAULT_DISCOUNT):
  """n-step Q loss for the pixel-control head.

  Args:
    q_values: f32 or bf16 [T+1, B, Hc, Wc, A] — Q at every
      observation; the last frame provides the max-Q bootstrap. A
      non-f32 Q-map (config.pixel_control_q_f32=False) is cast to
      f32 only AFTER the gather/max, so the full [T+1, B, Hc, Wc, A]
      float32 tensor never materializes.
    actions: i32 [T, B] — action taken on the t→t+1 transition.
    rewards: f32 [T, B, Hc, Wc] pseudo-rewards (pixel_control_rewards).
    done: bool [T, B] — done[t] True ⇒ the t'th transition crosses an
      episode reset (frame t+1 starts a new episode): no reward flows
      and the return recursion cuts.
  Returns:
    scalar loss: 0.5·Σ_cells (target − Q[a])², meaned over T and B.
  """
  not_done = (~done).astype(jnp.float32)[..., None, None]  # [T,B,1,1]
  rewards = rewards * not_done
  bootstrap = q_values[-1].max(axis=-1).astype(jnp.float32)  # [B,Hc,Wc]

  def step(carry, inputs):
    r, nd = inputs
    ret = r + discount * nd * carry
    return ret, ret

  _, targets = jax.lax.scan(
      step, bootstrap, (jnp.flip(rewards, 0), jnp.flip(not_done, 0)))
  targets = jnp.flip(targets, 0)  # [T, B, Hc, Wc]
  targets = jax.lax.stop_gradient(targets)

  q_taken = jnp.take_along_axis(
      q_values[:-1], actions[:, :, None, None, None], axis=-1
      )[..., 0].astype(jnp.float32)
  per_step = 0.5 * jnp.square(targets - q_taken).sum(axis=(2, 3))
  return per_step.mean()

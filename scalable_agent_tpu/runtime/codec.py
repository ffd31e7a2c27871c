"""Quantized publish codec (round 21): int8 absmax param snapshots.

The PR 1 wire codec stopped at bf16 — a 2x cut of the publish blob
with ~3 decimal digits kept, safe for behaviour policies (the bench's
param_fanout rows priced it). This module is the next rung: INT8 with
a per-leaf absmax scale, for both the in-process publish copy (the
serving plane's version table holds ~4x more resident versions under
the same HBM budget) and the cross-host fan-out (wire kind
'params_int8', protocol v10 — negotiated off for v<=9 peers, which
keep getting the bf16/f32 blob).

Shape of the encoding: each float32 leaf x becomes
`Int8Leaf(q=round(x/scale) in [-127,127], scale=max|x|/127)`. The q
array keeps the ORIGINAL shape, which is what makes the codec
`ShardingRegistry`-aware: a quantized leaf's placement spec is the
original leaf's spec applied to q plus a replicated scalar scale
(`parallel.sharding.quantized_specs`), so registry rules written
against param paths keep matching. Non-f32 leaves (ints, bools,
already-bf16 trees) pass through untouched — the same f32-only rule
the bf16 codec ships.

`Int8Leaf` is a registered jax pytree node: a quantized tree jits,
device_puts, and digests (`integrity.tree_digest` walks q AND scale)
exactly like a plain tree, and `dequantize_tree` runs in-graph — the
serving step traces the dequant into the compiled program, so serving
an int8-resident version costs one fused multiply, not a host round
trip.

Quantization is LOSSY (max per-leaf error = scale/2). It therefore
ships parity-GATED: `greedy_agreement` scores argmax-action agreement
of the quantized policy against fp32 on the same inputs, and
tests/test_serving.py holds the gate. docs/PERF.md records the wire-bytes/blackout rows per the
accept/reject discipline.
"""

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

# q = clip(round(x / scale), -QMAX, QMAX); scale = absmax / QMAX.
QMAX = 127


class Int8Leaf:
  """One quantized leaf: `q` (int8, the original leaf's shape) and
  `scale` (float32 scalar). Registered as a jax pytree node so
  quantized trees flow through jit / device_put / tree_digest like
  plain trees; `dequantize_tree` maps it back to float32."""

  __slots__ = ('q', 'scale')

  def __init__(self, q, scale):
    self.q = q
    self.scale = scale

  def __repr__(self):
    shape = getattr(self.q, 'shape', None)
    return f'Int8Leaf(shape={shape}, scale={self.scale!r})'

  # __slots__ classes need explicit pickle state (the wire blob is a
  # pickled tree of these; protocol-5 OOB buffers still extract the
  # arrays zero-copy — numpy provides the buffers, not the container).
  def __getstate__(self):
    return (self.q, self.scale)

  def __setstate__(self, state):
    self.q, self.scale = state


jax.tree_util.register_pytree_node(
    Int8Leaf,
    lambda leaf: ((leaf.q, leaf.scale), None),
    lambda _, children: Int8Leaf(*children))


def _is_q(x):
  return isinstance(x, Int8Leaf)


def _is_f32(x):
  return getattr(x, 'dtype', None) in (np.float32, jnp.float32)


def quantize_np(tree):
  """Host-side (wire) absmax int8 quantization: every float32 leaf →
  Int8Leaf(np.int8 q, np.float32 scalar scale); everything else
  passes through. An all-zero leaf gets scale 0 (dequantizes to
  exact zeros)."""

  def one(x):
    if not _is_f32(x):
      return x
    x = np.asarray(x)
    absmax = float(np.max(np.abs(x))) if x.size else 0.0
    scale = np.float32(absmax / QMAX)
    if scale == 0.0:
      return Int8Leaf(np.zeros(x.shape, np.int8), scale)
    q = np.clip(np.rint(x / scale), -QMAX, QMAX).astype(np.int8)
    return Int8Leaf(q, scale)

  return jax.tree_util.tree_map(one, tree)


def quantize_device(tree):
  """Device-side quantization for the in-process publish copy (the
  version table's int8-resident entries): same absmax scheme with
  jnp ops, so the copy stays on device. `jnp.where` keeps the
  all-zero-leaf case graph-safe (no host read of the scale)."""

  def one(x):
    if not _is_f32(x):
      return x
    x = jnp.asarray(x)
    scale = (jnp.max(jnp.abs(x)) / QMAX).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(x / safe), -QMAX, QMAX).astype(jnp.int8)
    return Int8Leaf(q, scale)

  return jax.tree_util.tree_map(one, tree)


def dequantize_tree(tree):
  """Int8Leaf leaves → float32 (jnp ops — traces in-graph, so a
  serving step over an int8-resident version fuses the dequant into
  the compiled program). Identity for trees with no quantized
  leaves."""

  def one(x):
    if not _is_q(x):
      return x
    return jnp.asarray(x.q, jnp.float32) * x.scale

  return jax.tree_util.tree_map(one, tree, is_leaf=_is_q)


def dequantize_np(tree):
  """Host-side decode (the v10 client's 'params_int8' install path):
  Int8Leaf → np.float32. The actor's agent/contract only ever sees
  f32, exactly like the bf16 upcast path."""

  def one(x):
    if not _is_q(x):
      return x
    return (np.asarray(x.q, np.float32)
            * np.float32(x.scale)).astype(np.float32)

  return jax.tree_util.tree_map(one, tree, is_leaf=_is_q)


def is_quantized(tree) -> bool:
  """True if any leaf of `tree` is an Int8Leaf."""
  found = []
  jax.tree_util.tree_map(
      lambda x: found.append(True) if _is_q(x) else None, tree,
      is_leaf=_is_q)
  return bool(found)


def tree_nbytes(tree) -> int:
  """Total leaf bytes (Int8Leaf counts q + scale) — the version
  table's HBM-budget accounting and the bench's wire-bytes rows."""
  total = 0
  for leaf in jax.tree_util.tree_leaves(tree):
    total += int(np.asarray(leaf).nbytes)
  return total


def max_abs_error(tree) -> float:
  """Upper bound on the per-element absolute quantization error of an
  encoded tree: max over leaves of scale/2 (rounding half-step)."""
  worst = 0.0

  def one(x):
    nonlocal worst
    if _is_q(x):
      worst = max(worst, float(x.scale) / 2.0)

  jax.tree_util.tree_map(one, tree, is_leaf=_is_q)
  return worst


def greedy_agreement(logits_a, logits_b) -> float:
  """Fraction of rows whose greedy (argmax) action agrees — the
  parity gate's score. Greedy, not sampled: sampled actions differ by
  RNG alone, so only the argmax comparison isolates the codec's
  effect on the policy."""
  a = np.argmax(np.asarray(logits_a), axis=-1)
  b = np.argmax(np.asarray(logits_b), axis=-1)
  if a.size == 0:
    return 1.0
  return float(np.mean(a == b))


def wire_sizes(params) -> Tuple[int, int, int]:
  """(f32, bf16, int8) leaf-byte totals for one tree — the bench's
  wire-bytes arithmetic without building three real blobs."""
  f32 = tree_nbytes(params)
  bf16 = 0
  for leaf in jax.tree_util.tree_leaves(params):
    arr = np.asarray(leaf)
    bf16 += arr.nbytes // 2 if arr.dtype == np.float32 else arr.nbytes
  int8 = tree_nbytes(quantize_np(params))
  return f32, bf16, int8

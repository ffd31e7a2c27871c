"""Declarative sharding registry — the ONE source of sharding truth.

Until round 19 the sharding decision was hand-copied across seven
consumers: the learner step and AOT fit carried their own
param/batch/replicated constructions, the mesh builder owned a private
regex rule table, the publisher codec and `target_params` re-derived
"are params cross-host sharded" from config arithmetic, the inference
arena built its own replicated/data shardings, the SDC fingerprint
encoded "params are logically replicated" as a config predicate, the
checkpoint restore specs were whatever the live state happened to
carry, and the multi-host placement arithmetic re-assumed the
contiguous data layout. Every new consumer was a "forgot to shard it"
bug waiting to land (ROADMAP item 1).

This module is the single authority they all query now:

- **Rule sets** (`RULE_SETS`): ordered (regex-over-param-path →
  `PartitionSpec`) tables, first match wins — the fmengine/EasyLM
  partition-rule pattern (SNIPPETS.md [2]). Scalars resolve replicated
  before the rules run; a param NO rule matches is a hard spin-up
  error (rule sets therefore end with an explicit catch-all — silence
  is never a sharding decision).
- **Optimizer-state specs** are cloned leaf-wise from the matched
  param specs (SNIPPETS.md [1]): any subtree of the optimizer state
  whose tree structure equals the params' (moment buffers) inherits
  the param specs; every other leaf (GA/schedule counters, scalars)
  is replicated.
- **Mesh binding** (`ShardingRegistry.param_shardings` /
  `state_shardings` / `batch_shardings`): resolved specs become
  `NamedSharding`s on a concrete mesh, with the divisibility guard —
  a model-axis cut whose dim does not divide the mesh's model width
  drops to replicated (odd feature sizes), applied HERE so every
  consumer sees the identical post-guard placement.

Consumers (each converted in round 19; the `sharding-registry` lint
pins that no new inline `PartitionSpec(...)` creeps in elsewhere):
`parallel/train_parallel.py` (learner step + SDC fingerprint
dispatch), `parallel/fit.py` (AOT fit), `parallel/mesh.py`
(delegating wrappers), `runtime/inference.py` (arena placements),
`driver.py` (publisher localization predicate), `checkpoint.py`
(save-side sharding manifest + registry restore targets),
`integrity.py` (spec-table digest), and the multi-host placement
arithmetic (`train_parallel.make_unroll_assembly`,
`distributed.global_batch_from_local` — both consume
`batch_shardings`).

The registry is deliberately mesh-independent at the resolution layer
(specs are pure data) — respecifying the same rule set against a new
mesh is exactly what checkpoint resharding across topologies needs
(ROADMAP item 3; the manifest `describe()` writes is its on-disk
record).
"""

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = 'data'
MODEL_AXIS = 'model'


class ShardingRuleError(ValueError):
  """A param path no rule matches — a hard spin-up error: silence is
  never a sharding decision (the registry's core contract)."""


class ShardingLayoutError(ShardingRuleError):
  """A resolved spec the TARGET mesh cannot honor — the axis is not on
  the mesh, the cut dim is out of rank, or the dim does not divide the
  axis width. Where live binding silently degrades such a cut to
  replicated (`_guard`), the strict layout check cross-topology restore
  runs (round 20, elastic membership) refuses with the structural story
  instead: a topology change must never silently rewrite a layout the
  checkpoint still holds."""


def shard_batch_over_model(config) -> bool:
  """Whether the learner batch must shard over the model axis too.

  True exactly when TP spans hosts: trajectory transport is host-local
  (each process supplies only its own fleet's rows), so model-axis
  batch replication would demand bit-identical batches from different
  hosts. The ONE predicate the batch-divisibility check
  (driver.choose_mesh), the sharding choice (batch_shardings callers),
  and the publisher localization (needs_host_local_params) consult —
  they must never drift."""
  return config.model_parallelism > 1 and jax.process_count() > 1


def needs_host_local_params(config, mesh) -> bool:
  """Whether actor-facing param consumers (the publisher codec, the
  inference server, ingest snapshots) must run on a host-LOCAL copy
  (process_allgather) instead of the learner's at-rest placements.

  True exactly when params are model-sharded ACROSS processes: a jit
  over cross-process-sharded params is a collective SPMD program, and
  the batcher invokes inference at unsynchronized times per host —
  which deadlocks in the collective (round 17's measured hang)."""
  return mesh is not None and shard_batch_over_model(config)


# --- rule sets --------------------------------------------------------

# Megatron-style TP cut (moved verbatim from parallel/mesh.py round 19
# — the rules themselves are unchanged, only their home): the bulk of
# the params shard their OUTPUT-feature dim over the model axis:
# - anonymous Dense kernels (torso projections),
# - every OptimizedLSTMCell gate kernel (i{i,f,g,o} input-to-gate and
#   h{i,f,g,o} hidden-to-gate) — the recurrent carry then propagates
#   model-sharded through the time scan, the Megatron-style LSTM cut,
# - Conv kernels ([kh, kw, in, out]) on their out-channel dim.
# The named heads (policy_logits, baseline) stay replicated — they are
# tiny and their outputs feed cross-replica math; no rule names them,
# so they fall to the mandatory catch-all. At IMPALA scale TP is
# headroom, not a necessity; the mechanism is real and parity-gated
# (tests/test_sharding.py, tests/test_parallel.py).
_TP_RULES: Tuple[Tuple[str, P], ...] = (
    (r'.*Dense_\d+/kernel$', P(None, MODEL_AXIS)),
    (r'.*Dense_\d+/bias$', P(MODEL_AXIS)),
    (r'.*OptimizedLSTMCell_\d+/[ih][ifgo]/kernel$', P(None, MODEL_AXIS)),
    (r'.*OptimizedLSTMCell_\d+/[ih][ifgo]/bias$', P(MODEL_AXIS)),
    (r'.*Conv_\d+/kernel$', P(None, None, None, MODEL_AXIS)),
    (r'.*Conv_\d+/bias$', P(MODEL_AXIS)),
    (r'.*', P()),
)

# Named rule sets a config can declare (--sharding_rules). 'auto'
# resolves at registry construction: 'megatron' when the mesh has a
# model axis to cut, 'replicated' (pure DP) otherwise.
RULE_SETS: Dict[str, Tuple[Tuple[str, P], ...]] = {
    'replicated': ((r'.*', P()),),
    'megatron': _TP_RULES,
}


class ShardingRegistry:
  """Ordered partition rules + every derived sharding decision.

  Resolution (`spec_for`, `param_specs`, `opt_specs`, `state_specs`)
  is pure data — specs, no mesh. Binding (`*_shardings`) takes the
  concrete mesh and applies the divisibility guard. Consumers never
  construct a `PartitionSpec` themselves (the `sharding-registry`
  lint enforces it)."""

  def __init__(self, rules: Sequence[Tuple[str, P]],
               rule_set: str = '<custom>'):
    if not rules:
      raise ValueError('a sharding registry needs at least one rule '
                       '(a catch-all (".*", PartitionSpec()) is the '
                       'minimal pure-DP set)')
    self.rule_set = rule_set
    self.rules: Tuple[Tuple[Any, P], ...] = tuple(
        (re.compile(pattern), spec) for pattern, spec in rules)

  # --- resolution (mesh-independent) ---------------------------------

  @property
  def model_sharded(self) -> bool:
    """Whether this rule set cuts ANY param over the model axis — the
    predicate the SDC sentinel gate and the publisher consult ('are
    params logically replicated?')."""
    return any(MODEL_AXIS in (s or ()) for _, s in self.rules)

  def spec_for(self, path: str, leaf) -> P:
    """First matching rule's spec for one param. Scalars (rank 0 or
    one element) are replicated before the rules run (SNIPPETS [2]);
    an unmatched path is a hard error, not a silent replication."""
    shape = tuple(getattr(leaf, 'shape', ()) or ())
    if len(shape) == 0 or int(np.prod(shape)) == 1:
      return P()
    for pattern, spec in self.rules:
      if pattern.search(path):
        return spec
    raise ShardingRuleError(
        f'no partition rule matches param {path!r} (rule set '
        f'{self.rule_set!r}) — every param must resolve; add a rule '
        'or end the set with a catch-all (".*", PartitionSpec())')

  def param_specs(self, params):
    """Pytree of `PartitionSpec` over a param (or abstract
    shape/dtype) tree, keyed on the '/'-joined key path."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: self.spec_for(_path_str(kp), leaf), params)

  def opt_specs(self, opt_state, param_specs):
    """Optimizer-state specs cloned leaf-wise from the matched param
    specs (SNIPPETS [1]): subtrees whose structure equals the params'
    (first/second moment buffers) inherit `param_specs`; every other
    leaf (GA steps, schedule counts, scalars) is replicated."""
    pdef = jax.tree_util.tree_structure(param_specs)

    def is_param_shaped(x):
      try:
        return jax.tree_util.tree_structure(x) == pdef
      except Exception:
        return False

    def per_node(x):
      return param_specs if is_param_shaped(x) else P()

    return jax.tree_util.tree_map(per_node, opt_state,
                                  is_leaf=is_param_shaped)

  def state_specs(self, state):
    """Specs for a whole TrainState-like NamedTuple: `params` by the
    rules, `target_params` cloned from them (the IMPACT anchor shards
    EXACTLY like the params — mixed placements would force a
    resharding copy every step), `opt_state` via `opt_specs`, every
    other field (step counter, PopArt stats) replicated."""
    pspecs = self.param_specs(state.params)
    fields = {}
    for name, value in state._asdict().items():
      if name == 'params':
        fields[name] = pspecs
      elif name == 'target_params' and value is not None:
        fields[name] = pspecs
      elif name == 'opt_state':
        fields[name] = self.opt_specs(value, pspecs)
      else:
        fields[name] = jax.tree_util.tree_map(lambda _: P(), value)
    return type(state)(**fields)

  def describe(self, params, mesh: Optional[Mesh] = None
               ) -> Dict[str, str]:
    """{param_path: spec_string} — the on-disk manifest form
    (checkpoint.py records it per save; integrity.py digests it).
    With a mesh, the divisibility guard is applied first so the
    record names the placements that actually hold."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for kp, leaf in flat:
      path = _path_str(kp)
      spec = self.spec_for(path, leaf)
      if mesh is not None:
        spec = self._guard(spec, leaf, mesh)
      out[path] = str(spec)
    return out

  # --- binding (mesh-dependent) --------------------------------------

  def _guard(self, spec: P, leaf, mesh: Mesh) -> P:
    """Drop cuts that don't divide the leaf (odd feature sizes) —
    applied at binding so every consumer sees the same post-guard
    placement."""
    if not any(ax is not None for ax in spec):
      return spec
    width = int(mesh.shape.get(MODEL_AXIS, 1))
    for dim, ax in enumerate(spec):
      if ax is not None and (dim >= leaf.ndim
                             or leaf.shape[dim] % width != 0):
        return P()
    return spec

  def layout_violations(self, tree, mesh: Mesh):
    """[(path, reason)] for every leaf whose RESOLVED spec this mesh
    cannot honor — the structural half of the divisibility guard.
    Where `_guard` silently degrades such a binding to replicated,
    this names the leaf and the reason; cross-topology restore
    consults it (`check_layout`) so a topology change never silently
    rewrites the declared layout (round 20, elastic membership)."""
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
      path = _path_str(kp)
      spec = self.spec_for(path, leaf)
      shape = tuple(getattr(leaf, 'shape', ()) or ())
      for dim, ax in enumerate(spec):
        if ax is None:
          continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        missing = sorted(set(axes) - set(mesh.shape))
        if missing:
          out.append((path, (
              f'spec {spec} names mesh axis {missing[0]!r} but the '
              f'target mesh only has {dict(mesh.shape)}')))
          continue
        width = 1
        for a in axes:
          width *= int(mesh.shape[a])
        if dim >= len(shape):
          out.append((path, (
              f'spec {spec} cuts dim {dim} but the leaf is rank '
              f'{len(shape)} {shape}')))
        elif shape[dim] % width != 0:
          out.append((path, (
              f'dim {dim} (size {shape[dim]}) does not divide mesh '
              f'axis {"*".join(axes)} width {width} (spec {spec})')))
    return out

  def check_layout(self, tree, mesh: Mesh, what: str = 'state',
                   saved_specs: Optional[Dict[str, str]] = None
                   ) -> None:
    """Raise `ShardingLayoutError` unless every leaf's resolved spec
    can bind on `mesh` exactly as resolved — the refusal gate of
    strict cross-topology restore. A leaf the SAVE already recorded
    as replicated (`saved_specs`: the checkpoint sharding manifest's
    {path: spec} table) is exempt: its cut was degraded before the
    topology changed, so the restore loses nothing the save still
    had."""
    replicated = str(P())
    violations = [
        (path, reason)
        for path, reason in self.layout_violations(tree, mesh)
        if saved_specs is None or saved_specs.get(path) != replicated]
    if not violations:
      return
    shown = '\n'.join(f'  - {p}: {r}' for p, r in violations[:8])
    more = ('' if len(violations) <= 8
            else f'\n  ... and {len(violations) - 8} more')
    raise ShardingLayoutError(
        f'{len(violations)} {what} leaf/leaves cannot be laid out on '
        f'the target mesh {dict(mesh.shape)} under rule set '
        f'{self.rule_set!r}:\n{shown}{more}\n'
        'Fix the target topology (every cut dim must divide its axis '
        'width), pick a rule set the mesh can honor, or restore '
        'non-strict to accept replicated degradation.')

  def param_shardings(self, params, mesh: Mesh):
    """NamedShardings for a param pytree on this mesh."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh,
            self._guard(self.spec_for(_path_str(kp), leaf), leaf, mesh)),
        params)

  def state_shardings(self, state, mesh: Mesh):
    """NamedShardings for a whole TrainState (optimizer moments cloned
    from param placements, everything else replicated)."""
    pshard = self.param_shardings(state.params, mesh)
    pspecs = jax.tree_util.tree_map(lambda s: s.spec, pshard)
    specs = self.state_specs(state)._replace(
        params=pspecs,
        target_params=(pspecs if state.target_params is not None
                       else None),
        opt_state=self.opt_specs(state.opt_state, pspecs))
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  specs)

  def batch_specs(self, batch_pytree, shard_over_model: bool = False):
    """PartitionSpecs for the learner batch: data axis on the batch
    dim. Trajectory tensors are time-major [T+1, B, ...] → dim 1;
    level_name/agent_state are [B, ...] → dim 0 (keyed on the
    ActorOutput structural position).

    shard_over_model: shard the batch dim over BOTH axes instead of
    replicating it across the model axis — required when TP spans
    hosts (see `shard_batch_over_model`): every host then feeds
    distinct rows and GSPMD inserts the model-axis all-gather where
    the TP matmuls need the full data shard."""
    from scalable_agent_tpu.structs import ActorOutput

    axes = _batch_axes(shard_over_model)
    traj = lambda _: P(None, axes)  # noqa: E731
    lead = lambda _: P(axes)        # noqa: E731
    return ActorOutput(
        level_name=lead(None),
        agent_state=jax.tree_util.tree_map(lead,
                                           batch_pytree.agent_state),
        env_outputs=jax.tree_util.tree_map(traj,
                                           batch_pytree.env_outputs),
        agent_outputs=jax.tree_util.tree_map(
            traj, batch_pytree.agent_outputs))

  def batch_shardings(self, batch_pytree, mesh: Mesh,
                      shard_over_model: bool = False):
    """NamedShardings for the learner batch on this mesh."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        self.batch_specs(batch_pytree,
                         shard_over_model=shard_over_model))


def _batch_axes(shard_over_model: bool):
  """The mesh axes `batch_specs` puts on the batch dim."""
  return (DATA_AXIS, MODEL_AXIS) if shard_over_model else (DATA_AXIS,)


def batch_shards(config, mesh: Optional[Mesh]) -> int:
  """D: into how many shards `batch_specs` cuts the batch dim on this
  mesh: the data width, times the model width where
  `shard_batch_over_model` holds; 1 with no mesh. The learner hands it
  to the agent's `[T, B] -> [T*B]` merge (`merge_time_batch`)."""
  if mesh is None:
    return 1
  return int(np.prod([
      mesh.shape[axis]
      for axis in _batch_axes(shard_batch_over_model(config))]))


def _path_str(kp) -> str:
  return '/'.join(str(getattr(k, 'key', getattr(k, 'idx', k)))
                  for k in kp)


def from_config(config, enable_tp: Optional[bool] = None
                ) -> ShardingRegistry:
  """The registry a config declares: `config.sharding_rules` names a
  RULE_SETS entry; 'auto' resolves to 'megatron' when a model axis
  exists to cut ('replicated' otherwise). `enable_tp` overrides the
  model_parallelism predicate for callers that arm TP out-of-band
  (tests pass a TP mesh against a default config)."""
  name = getattr(config, 'sharding_rules', 'auto') or 'auto'
  if enable_tp is None:
    enable_tp = config.model_parallelism > 1
  if name == 'auto':
    name = 'megatron' if enable_tp else 'replicated'
  if name not in RULE_SETS:
    raise ValueError(
        f'unknown sharding_rules {name!r}; known: '
        f"auto, {', '.join(sorted(RULE_SETS))}")
  return ShardingRegistry(RULE_SETS[name], rule_set=name)


# --- shared primitive shardings (the non-param placements) ------------
#
# These are sharding decisions too — inference arenas, SDC probe
# vectors, Anakin carries, shard_map specs. One home for them keeps
# the `sharding-registry` lint meaningful: a consumer importing these
# provably made no private layout choice.


def spec_replicated() -> P:
  """The replicated PartitionSpec (shard_map in/out specs)."""
  return P()


def spec_data() -> P:
  """One vector sharded over the data axis (SDC probe lanes,
  per-replica shard_map inputs)."""
  return P(DATA_AXIS)


def spec_time_major(ndim: int, axis=DATA_AXIS) -> P:
  """[T, B, ...] tensors: batch dim 1 over `axis` (the shard_map
  boundary spec of the Pallas V-trace)."""
  return P(*((None, axis) + (None,) * (ndim - 2)))


def spec_batch_lead(ndim: int, axis=DATA_AXIS) -> P:
  """[B, ...] tensors: batch dim 0 over `axis`."""
  return P(*((axis,) + (None,) * (ndim - 1)))


def replicated(mesh: Mesh) -> NamedSharding:
  """Replicated placement on a mesh (params at inference, scalars,
  gathered outputs)."""
  return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh) -> NamedSharding:
  """Leading-dim data-axis placement (inference batch rows, SDC probe
  vectors)."""
  return NamedSharding(mesh, P(DATA_AXIS))


# --- row order of the merged [T*B] axis --------------------------------
#
# The agent runs its torso and heads over time merged into the batch.
# In a plain `[T, B] -> [T*B]` reshape B is the MINOR factor of the
# merged axis, so the rows of one batch shard lie strided through it
# and no tiled sharding of `[T*B]` can name them: the partitioner
# all-gathers, and every device computes every row. With the shards of
# B outermost (`[T, D, B/D] -> [D, T, B/D] -> [D*T*B/D]`) a shard's
# rows are one contiguous block, the merged axis stays sharded over the
# batch axes, and nothing moves. Rows are independent in everything
# that runs on the merged axis, so their order changes no value.


def _rows_per_shard(b: int, shards: int) -> int:
  if b % shards:
    raise ValueError(f'batch {b} does not divide into {shards} shards')
  return b // shards


def merge_time_batch(x, shards: int = 1, trailing=None):
  """`[T, B, ...] -> [T*B, ...]`, the `shards` shards of B outermost:
  row `(d*T + t) * B/D + j` holds `x[t, d*B/D + j]`. `shards` = 1 and
  T = 1 are the plain reshape. `trailing`: the result's shape after
  its first axis where that is not `x.shape[2:]` (a [T, B] array
  merged into a column)."""
  t, b = x.shape[:2]
  merged = (t * b,) + tuple(x.shape[2:] if trailing is None
                            else trailing)
  if shards == 1 or t == 1:
    return x.reshape(merged)
  x = x.reshape((t, shards, _rows_per_shard(b, shards)) + x.shape[2:])
  return jnp.swapaxes(x, 0, 1).reshape(merged)


def split_time_batch(y, t: int, b: int, shards: int = 1):
  """The inverse of `merge_time_batch`: `[T*B, ...] -> [T, B, ...]` in
  the caller's order."""
  split = (t, b) + y.shape[1:]
  if shards == 1 or t == 1:
    return y.reshape(split)
  y = y.reshape((shards, t, _rows_per_shard(b, shards)) + y.shape[1:])
  return jnp.swapaxes(y, 0, 1).reshape(split)


def quantized_specs(quantized_tree, plain_specs):
  """Specs for an int8-quantized param tree (round 21 publish codec),
  cloned from the PLAIN tree's registry specs: each `codec.Int8Leaf`
  keeps the original leaf's spec on `q` (same shape, so the rule that
  matched the f32 leaf is still the right placement) and replicates
  the scalar `scale` — the codec stays inside the registry's
  one-source-of-truth contract instead of inventing placements.

  `quantized_tree` is the encoded tree (Int8Leaf nodes where f32
  leaves were); `plain_specs` is `registry.param_specs(params)` over
  the ORIGINAL tree. Registry rules key on the plain tree's paths, so
  the clone — not a re-match against the deeper quantized paths — is
  what keeps regex rules working unchanged."""
  from scalable_agent_tpu.runtime import codec

  def one(leaf, spec):
    if isinstance(leaf, codec.Int8Leaf):
      return codec.Int8Leaf(spec, P())
    return spec

  return jax.tree_util.tree_map(
      one, quantized_tree, plain_specs,
      is_leaf=lambda x: isinstance(x, codec.Int8Leaf))


def quantized_shardings(quantized_tree, plain_specs, mesh: Mesh):
  """`quantized_specs` resolved to NamedShardings on `mesh` (the
  device_put placement of an int8-resident version-table entry on a
  sharded serving mesh)."""
  return jax.tree_util.tree_map(
      lambda spec: NamedSharding(mesh, spec),
      quantized_specs(quantized_tree, plain_specs))

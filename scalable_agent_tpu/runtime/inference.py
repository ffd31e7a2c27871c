"""Batched inference server: many actor threads, one jitted TPU call.

The reference reaches ~3× single-machine throughput by transparently
merging ~48 concurrent batch-1 `Agent._build` calls into one GPU call
via the C++ Batcher op (reference: experiment.py ≈L470–482 monkey-patch
+ dynamic_batching.py). This is the TPU-native equivalent:

- actor threads call `policy(prev_action, env_output, core_state)`
  (the `runtime.actor.Actor` contract) and block;
- the C++ batcher (ops/batcher) merges concurrent calls;
- a dispatch thread runs the jitted single-step agent on the merged
  batch on TPU; a completion thread reads results back and unparks
  the callers.

XLA needs static shapes, so merged batches are padded up to the next
power of two (capped at maximum_batch_size) before the jitted call and
sliced after — a handful of compiled shapes total, no recompiles in
steady state (the reference's TF graph handled dynamic batch dims
natively; bucketing is the XLA-idiomatic trade).

Round 7 overhaul (docs/INFERENCE.md) — three independent levers:

1. Device-resident core-state cache (config.inference_state_cache):
   instead of shipping the recurrent carry host→device and the new
   carry device→host on EVERY env step, each actor owns a slot in an
   on-device state arena, a pytree with one `[rows, ...]` array per
   leaf of the agent's state in the leaf's dtype (PR 27; the LSTM's
   two `[slots, hidden]` float32 matrices, a retention stack's
   per-layer float32 states and an int32 position); the jitted step
   hands the arena and the slot ids to the agent's core, which gathers
   the carries, computes and scatters them back in-graph (Podracer,
   arXiv:2104.06272) or, for a state of megabytes a session, updates
   the named rows in place (models/core.py; the arena is donated).
   The per-step wire drops to
   (action, reward, done, frame, instr, slot_id); the carry crosses
   the host boundary only once per unroll (the learner needs the
   unroll-start state — `_SlotHandle.snapshot()`). Numerics-identical
   to the carry-passing path (golden parity gate in
   tests/test_runtime.py, done edges + respawn slot reuse + the
   sharded-eval mesh included); done-reset stays in-graph via the
   agent's `_ResetCore`.
2. Pipelined dispatch (config.inference_pipeline_depth, default 2):
   dispatch and completion are separate threads with a depth
   semaphore between them, so merged batch k+1 assembles and lands on
   device while batch k computes — the actor-plane mirror of
   `BatchPrefetcher`'s H2D/compute overlap. Depth 1 reproduces the
   old serialized assemble→dispatch→device_get loop.
   The inline call (PR 39): a k-row request (an `ActorGroup`'s) whose
   rows alone reach the merge floor and the pad floor (`pad_batch_to`),
   with no shadow version live, has nothing to wait for and nothing to
   overlap (its group cannot step until its actions are back), so its
   caller's own thread stages it in a buffer of its own, dispatches it
   under the same semaphore and locks and reads it back:
   no batcher, no dispatch or completion thread, no copy of the
   results (stats()['inline_calls']; docs/INFERENCE.md section 2).
3. Zero-copy merge staging: the C++ batcher's merge-copy lands
   directly in preallocated per-bucket padded staging buffers
   (`Batcher.get_batch_into`) — no per-call np.concatenate, no
   per-call allocation — and the PRNG key lives on device, split
   in-graph by the jitted step instead of per-call on the host.
   Since PR 36 a staging position is ONE flat buffer, the per-input
   arrays views of it, and a merged call crosses the host boundary as
   one transfer each way: the step unpacks its batch inputs from that
   buffer and packs its outputs into one array (runtime/packing.py;
   docs/INFERENCE.md "One buffer each way"; stats()
   ['h2d_buffers_per_call'], ['d2h_buffers_per_call']).

Weights: the server holds a params snapshot updated via
`update_params` (the reference's gRPC weight fetch becomes an on-host
pointer swap; the same "actions within one unroll may span weight
versions" caveat applies — reference ≈L472 comment).

Round 9 (actor-plane overload hardening, docs/ROBUSTNESS.md): slot
ADMISSION CONTROL replaces raise-on-exhaustion. `_acquire_slot` parks
callers on a priority-ordered bounded waitlist instead of raising
`RuntimeError('state arena exhausted')` — exhaustion now DEGRADES per
`config.inference_admission`:

  block  (default) wait (deadline-bounded, capped-jitter re-check via
         runtime.remote.Backoff) for a released slot; raise
         `SlotUnavailable` only at the deadline.
  shed   same parked wait, but the deadline REJECTION is the intended
         steady-state response to overload: counted in
         stats()['sheds'] and the driver's `inference_sheds` summary —
         the serving-plane load-shedding seam (TorchBeast's decoupled
         actor/server split, arXiv:1910.03552).
  grow   never park: the arena doubles in place (one recompile per
         growth, counted in stats()['arena_grows']).

Waiters carry a PRIORITY class (PRIORITY_LIVE < PRIORITY_RESPAWN <
PRIORITY_EVAL): releases hand the freed slot to the best-priority
waiter directly, so eval/respawn churn can never starve live actor
traffic. `close()` answers every parked waiter with `InferenceClosed`
(never leaves them blocked forever) and counts worker threads that
missed their join deadline (stats()['unjoined_threads']).

Round 21 (multi-tenant serving plane, docs/INFERENCE.md): the single
resident params snapshot generalizes to a VERSION TABLE —
`config.serving_resident_versions` policy versions resident
concurrently (LRU eviction of unpinned, non-live entries under the
count cap and the optional `serving_hbm_budget_mb` byte budget), with
per-version serve counters, A/B assignment
(`serving_ab_fraction` of merged calls served by the newest non-live
candidate — assignment is at merged-call granularity because the C++
batcher merges rows from many actors into one call), and SHADOW
traffic: `serving_shadow_fraction` of merged calls are ALSO replayed
against a shadow version through a PURE step (no key chain, no arena
scatter) and scored against live on GREEDY action agreement — the
`serving/shadow_divergence` gauge (sampled actions would differ by
RNG alone, so only argmax isolates the version delta). A version
re-published while still resident flips live WITHOUT a tree copy
(stats()['version_flips']); `publish_codec=int8` stores table entries
quantized (runtime/codec.py — ~4x more resident versions per byte,
dequantized in-graph by the serving step). `serving_aot=True`
pre-compiles serving steps per (batch-bucket, params-structure) at
publish time via the jit lower/compile seam (parallel/fit.py's AOT
pattern), so a version flip to a new dtype structure — or a warmed
bucket under a flipped structure — never pays first-call compile on
the serve path (misses fall back to the jit cache and count
stats()['aot_misses']). `serve_remote` serves carry-passing batches
from the same table for the wire-v10 routed inference service
(runtime/routing.py).

PR 32 (a state that grows with the episode): an agent whose core
computes a chunk of tokens at once (`agent.prefill_chunk`; a cache of
latents read by attention, models/latent_moe.py) gets a second compiled
program beside the step, `prefill_chunk`: ONE session's slot advanced
by up to that many tokens, on the same donated arena. `prefill(handle,
tokens)` (an actor calls it through its slot handle where an episode
begins) dispatches ceil(n / chunk) of them under `_arena_lock`, so
they take their place in the arena's chain among the merged calls in
flight; `warmup` compiles it. Such a state is written AT A POSITION:
`done` resets the position in-graph, not the row, and the server
follows every slot's position on the host (it sees each call's `done`
rows and each block's length) to count the cached tokens the calls
read: `cache_tokens_read`, each live row's position and one, and beside
it, where the agent's core keeps a ring of an episode's last
`cache_window` tokens in some layers (PR 35: models/
hybrid_attention.py), `window_tokens_read`, the same capped at the
ring. Counters an agent's layers sow a call (`agent.call_counters`)
come back with the call's own readback.
"""

import collections
import logging
import queue
import threading
import time
import types

import numpy as np

import flax.traverse_util
import jax
import jax.numpy as jnp

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock
from scalable_agent_tpu.observability import LatencyReservoir
from scalable_agent_tpu.ops import dynamic_batching
from scalable_agent_tpu.runtime import codec as codec_lib
from scalable_agent_tpu.runtime import faults as faults_lib
from scalable_agent_tpu.runtime import packing
from scalable_agent_tpu.runtime.remote import Backoff
from scalable_agent_tpu.structs import (AgentOutput, StepOutput,
                                        observation_leaves)

log = logging.getLogger('scalable_agent_tpu')

# Serving-plane telemetry (round 21; docs/OBSERVABILITY.md inventory).
# Merged-call service latency also feeds the serving_latency_p99_ms
# SLO objective — admission is its actuator (controller.DEFAULT_RULES).
_SERVE_LATENCY = telemetry.histogram('serving/latency_ms')
_SHADOW_DIVERGENCE = telemetry.gauge('serving/shadow_divergence')
# Every other count of the serving plane is a key of `stats()` and
# nowhere else (PR 37: the registry's copies had no reader).
# The per-call counters an agent's layers may sow
# (`agent.call_counters` names some of these), summed in `stats()`.
_CALL_COUNTERS = ('routed_rows_held', 'experts_hit', 'ssm_rows_stepped')

# A merged call's stamps (`InferenceServer._cycles`; docs/
# OBSERVABILITY.md "Cycle records") and what else is kept of it.
_CALL_PHASES = ('wait_batch', 'dispatch', 'in_flight_and_readback',
                'unpark')
_CALL_EXTRAS = ('h2d', 'd2h')
# The stamps a call's latency lies between.
_IN_HAND, _UNPARKED = 1, len(_CALL_PHASES)
# `latency_p*_ms`, `*_buffers_per_call`: over this many newest calls.
_RECENT_CALLS = 512

# Admission priority classes (lower = served first): a released slot
# is handed to the best-priority parked waiter, so background churn
# (respawns, eval fleets sharing a server) cannot starve live actors.
PRIORITY_LIVE = 0
PRIORITY_RESPAWN = 1
PRIORITY_EVAL = 2

ADMISSION_POLICIES = ('block', 'shed', 'grow')

# Padded merge rows scatter/gather with this slot id: ALWAYS out of
# range (gather clamps, scatter mode='drop' discards), and — unlike
# the old `num_slots` stamp — still out of range after a 'grow'
# admission doubles the arena between staging and dispatch.
_PAD_SLOT_ID = np.int32(1 << 30)

# The largest recurrent state of ONE session that may cross the host
# boundary: with every call in carry-passing mode, with every unroll
# as a slot's snapshot. At 1 MiB a merged call of 32 already moves
# 32 MiB each way, more than ten times the 1.2 ms the whole readback
# of such a call takes today (PERF.md section 5); a retention state is
# 136 MB a session. Above it the state lives in the server's arena
# and nowhere else: carry-passing mode is refused at construction,
# and a slot's snapshot is None.
MAX_HOST_STATE_BYTES = 1 << 20


class SlotUnavailable(RuntimeError):
  """No state-arena slot could be admitted before the deadline (shed
  policy: the intended overload response; block policy: the bounded-
  wait backstop). Fleet respawn treats this as pause-and-retry, never
  as a learner-loop crash."""


class InferenceClosed(RuntimeError):
  """The server closed while the caller was parked on the admission
  waitlist — a clean shutdown answer, not an overload signal."""


class _Waiter:
  """One parked `_acquire_slot` caller: priority + FIFO tiebreak, an
  event the release path sets on direct slot handoff, and the closed
  flag `close()` answers parked callers with."""

  __slots__ = ('priority', 'seq', 'event', 'slot', 'closed')

  def __init__(self, priority, seq):
    self.priority = priority
    self.seq = seq
    self.event = threading.Event()
    self.slot = None
    self.closed = False


def _next_power_of_two(n):
  p = 1
  while p < n:
    p *= 2
  return p


def _tree_nbytes(tree):
  """Leaf-byte total WITHOUT a device transfer (jax and numpy arrays
  both expose .nbytes) — the version table's HBM-budget accounting
  runs on every publish, so it must not device_get the tree."""
  total = 0
  for leaf in jax.tree_util.tree_leaves(tree):
    nbytes = getattr(leaf, 'nbytes', None)
    if nbytes is None:
      nbytes = np.asarray(leaf).nbytes
    total += int(nbytes)
  return total


def _params_fingerprint(params):
  """Hashable structure key for the AOT executable table: treedef +
  per-leaf dtypes. Two versions with the same fingerprint share
  compiled steps (the common case: every fp32 publish); an int8
  publish (Int8Leaf nodes change the treedef AND the dtypes) maps to
  its own executables."""
  leaves, treedef = jax.tree_util.tree_flatten(params)
  return (treedef,
          tuple(str(getattr(l, 'dtype', type(l).__name__))
                for l in leaves))


def _wire_dtype(dtype):
  """The dtype an observation leaf crosses in: the one jit would give
  the array were it an argument of its own (int64 from an env is int32
  on the device), because its BYTES are what the step reads now."""
  return np.dtype(jax.dtypes.canonicalize_dtype(dtype))


def percentile_ms(sorted_secs_or_ms, q, scale=1.0):
  """q-th percentile of an ascending list (nearest-rank, clamped) ×
  scale — the ONE implementation behind stats() and the bench rows, so
  the accept/reject numbers are computed identically everywhere."""
  if not sorted_secs_or_ms:
    return 0.0
  n = len(sorted_secs_or_ms)
  return sorted_secs_or_ms[min(n - 1, int(n * q))] * scale


def _planar_bytes():
  """Whether a step takes an image's interleaved bytes apart on their
  way in (runtime/packing.py): where its program runs on a TPU."""
  return jax.default_backend() == 'tpu'


def step_functions(agent, state_cache, planar=False, note_outputs=None,
                   rows_sharding=None):
  """The programs a server runs for `agent`, as plain functions (the
  server jits them; tests/test_tpu_compile.py compiles them for a
  described chip without a server):

  `step`: one merged call. Its batch inputs come as ONE buffer and its
  outputs leave as ONE (runtime/packing.py, PR 36): `packed` is a
  staging position's flat buffer as it lies on the host, `layout`
  (static) says which `[padded, ...]` array lies where.
  `carry_step(params, key, packed, layout) -> (key, packed outputs)`
  where the state rides with the call, `cache_step(params, key, arena,
  packed, layout) -> (key, arena, packed outputs)` where it lives in
  the (donated) arena; the program is named for which.
  `note_outputs(layout, output layout)` is told, as a step is traced,
  which arrays it packs, for the host to read them by.
  `shadow_step`: the same arguments but the key, the logits alone.
  `carry_rows`: the carry-passing body, an argument an array.
  `prefill_chunk`: models with a chunk form (module docstring, PR 32).

  `planar`: `packing.unpack`'s. `rows_sharding`: on a mesh; one buffer
  cannot be split by rows, so it is placed whole on every device and
  each array takes the rows' sharding as it is unpacked."""
  num_obs = len(agent.observation_names)
  state_treedef = jax.tree_util.tree_structure(
      jax.eval_shape(lambda: agent.initial_state(1)))
  counter_names = tuple(getattr(agent, 'call_counters', ()))

  def _apply(params, sub, prev_action, reward, done, obs, core_state,
             slots=None, counters=False):
    # Int8-resident versions (publish_codec=int8) dequantize HERE,
    # in-graph: XLA fuses the per-leaf multiply into the step, so
    # serving a quantized version costs no host round trip. Identity
    # for plain trees.
    params = codec_lib.dequantize_tree(params)
    env_output = StepOutput(
        reward=reward[None], info=None, done=done[None],
        observation=tuple(o[None] for o in obs))
    # With `slots` the agent's core advances those rows of the arena
    # (`core_state`) and hands the arena back: models/core.py.
    kwargs = {} if slots is None else {'state_slots': slots}
    if counters:
      # What the agent's layers sowed this call, summed by name over
      # the layers, goes out with the call's outputs.
      (out, new_state), sown = agent.apply(
          params, prev_action[None], env_output, core_state,
          sample_rng=sub, mutable=['counters'], **kwargs)
      totals = dict.fromkeys(counter_names, 0)
      for path, value in flax.traverse_util.flatten_dict(
          sown.get('counters', {})).items():
        totals[path[-1]] += value
      return (out.action[0], out.policy_logits[0], out.baseline[0],
              new_state, *[jnp.asarray(totals[name], jnp.int32)
                           for name in counter_names])
    out, new_state = agent.apply(
        params, prev_action[None], env_output, core_state,
        sample_rng=sub, **kwargs)
    return (out.action[0], out.policy_logits[0], out.baseline[0],
            new_state)

  def unflatten(leaves):
    return jax.tree_util.tree_unflatten(state_treedef, leaves)

  def carry_rows(params, key, prev_action, reward, done, *rest):
    key, sub = jax.random.split(key)
    action, logits, baseline, new_state = _apply(
        params, sub, prev_action, reward, done, rest[:num_obs],
        unflatten(rest[num_obs:]))
    return (key, action, logits, baseline,
            *jax.tree_util.tree_leaves(new_state))

  def unpack(packed, layout):
    inputs = packing.unpack(packed, layout, planar)
    if rows_sharding is not None:
      inputs = [jax.lax.with_sharding_constraint(x, rows_sharding)
                for x in inputs]
    return inputs

  def pack(layout, outputs):
    packed, out_layout = packing.pack(outputs)
    if note_outputs is not None:
      note_outputs(layout, out_layout)
    return packed

  def carry_step(params, key, packed, layout):
    key, *outputs = carry_rows(params, key, *unpack(packed, layout))
    return key, pack(layout, outputs)

  def cache_step(params, key, arena, packed, layout):
    slot_ids, prev_action, reward, done, *obs = unpack(packed, layout)
    key, sub = jax.random.split(key)
    # Each row's state is the arena's row `slot_ids[row]`. Padded
    # rows carry _PAD_SLOT_ID (out of range for any arena size,
    # grown or not) and never touch a live slot: the default core
    # gathers with a clamp (their compute is sliced away) and
    # scatters with mode='drop'; a core that updates its arena in
    # place sends them to a row of their own.
    action, logits, baseline, arena, *counts = _apply(
        params, sub, prev_action, reward, done, obs, arena,
        slots=slot_ids, counters=bool(counter_names))
    return key, arena, pack(layout, [action, logits, baseline, *counts])

  # Shadow step (round 21): PURE — no key split chained back, no
  # arena scatter — so replaying a merged call against a shadow
  # version can never perturb the live fleet's RNG stream or
  # carries. Scored on GREEDY agreement downstream, so the fixed
  # sample key is irrelevant to the gauge.
  def shadow_carry(params, packed, layout):
    prev_action, reward, done, *rest = unpack(packed, layout)
    sub = jax.random.PRNGKey(0)
    _, logits, _, _ = _apply(params, sub, prev_action, reward, done,
                             rest[:num_obs], unflatten(rest[num_obs:]))
    return logits

  def shadow_cache(params, arena, packed, layout):
    slot_ids, prev_action, reward, done, *obs = unpack(packed, layout)
    sub = jax.random.PRNGKey(0)
    _, logits, _, _ = _apply(params, sub, prev_action, reward, done,
                             obs, arena, slots=slot_ids)
    return logits

  def prefill_chunk(params, arena, slot, tokens, n_valid, reset):
    # One session's slot advanced by the first `n_valid` of `tokens`
    # (from an empty state where `reset`): embedding and core, no
    # head. The arena is donated, as to the step.
    return agent.apply(
        codec_lib.dequantize_tree(params), tokens, arena, slot,
        n_valid, reset, method=agent.prefill)

  return types.SimpleNamespace(
      step=cache_step if state_cache else carry_step,
      shadow_step=shadow_cache if state_cache else shadow_carry,
      carry_rows=carry_rows, prefill_chunk=prefill_chunk)


class _Staging(list):
  """One position of a bucket's staging ring: the per-input arrays the
  batcher writes through (this list) are VIEWS of ONE flat buffer,
  `words`, which is what crosses to the device; `layout` says where
  each lies in it (runtime/packing.py)."""

  __slots__ = ('words', 'layout')

  def __init__(self, layout):
    self.layout = layout
    self.words = np.zeros((layout.words,), packing.WORD)
    super().__init__(packing.host_views(self.words, layout))


class _SlotHandle:
  """An actor's claim on one state-arena slot (state-cache mode).

  Opaque under the `runtime.actor.Actor` core-state contract; the
  actor only touches the duck-typed surface:

  - `snapshot()`: the slot's carry as host numpy, `[1, ...]` leaves
    (the LSTM's `(c[1,H], h[1,H])`) — the once-per-unroll read the
    learner's `agent_state` needs. None for a state above
    MAX_HOST_STATE_BYTES, which never leaves the device.
  - `write(carry)`: overwrite the slot (the actor's priming-call
    undo); `write(None)` zeroes it, which is what a slot held when
    nothing could be snapshotted from it.
  - `release()`: return the slot to the free list (idempotent). The
    slot is zeroed again on the NEXT acquire, so a reclaimed slot can
    never serve a stale carry.
  - `prefill_chunk`, `prefill(tokens)`: where the agent's core
    computes a chunk at once, an episode's prompt goes in as a block
    before the session's next policy call.
  """

  __slots__ = ('_server', 'slot', 'released')

  def __init__(self, server, slot):
    self._server = server
    self.slot = slot
    self.released = False

  def snapshot(self):
    if self.released:
      # A released slot may already be serving its next owner (the
      # waitlist hands freed slots over directly): a straggler thread
      # must fail here, not read someone else's carry.
      raise RuntimeError('snapshot() on a released state slot')
    return self._server._read_slot(self.slot)

  def write(self, carry):
    if self.released:
      raise RuntimeError('write() on a released state slot')
    self._server._write_slot(self.slot, carry)

  def release(self):
    if not self.released:
      self.released = True
      self._server._release_slot(self.slot)

  @property
  def prefill_chunk(self):
    """Tokens one chunk program takes; 0: the agent's core has no
    chunk form of its own, and a prompt comes a token a policy call."""
    return self._server.prefill_chunk

  def prefill(self, tokens):
    """Begin an episode in this slot with `tokens` i32 [n] behind it
    (`InferenceServer.prefill`)."""
    if self.released:
      raise RuntimeError('prefill() on a released state slot')
    self._server.prefill(self, tokens)

  def __repr__(self):
    return (f'_SlotHandle(slot={self.slot}, '
            f'released={self.released})')


class _VersionEntry:
  """One resident policy version in the serving table: the (owned,
  possibly int8-quantized) params copy, its publish key, the pin
  flag eviction honours, the per-version serve counter, its leaf
  bytes (the HBM-budget accounting) and the LRU tick."""

  __slots__ = ('key', 'params', 'pinned', 'serves', 'nbytes', 'tick')

  def __init__(self, key, params, nbytes, tick):
    self.key = key
    self.params = params
    self.pinned = False
    self.serves = 0
    self.nbytes = nbytes
    self.tick = tick

  def label(self):
    """Stable stats() key: the numeric publish version, 'anon-N' for
    None-version publishes (the dedup-less always-publish path), or
    '<seed>' for the constructor's by-reference sentinel entry."""
    if isinstance(self.key, int):
      return self.key
    if isinstance(self.key, tuple) and self.key and self.key[0] == 'anon':
      return f'anon-{self.key[1]}'
    return '<seed>'


class InferenceServer:
  """Serves a batched policy for host actor threads.

  Args:
    agent: ImpalaAgent (flax module).
    params: initial parameter pytree (host or device).
    config: Config (uses inference_* knobs).
    seed: PRNG seed for action sampling.
    mesh: optional jax.sharding.Mesh — merged inference batches shard
      over its data axis (params replicated), so concurrent eval of
      many envs uses every chip instead of one (VERDICT r2 W6: the
      reference's test() is batch-1 serial; sharded batched eval is
      TPU headroom it never had). Padded batch sizes round up to a
      multiple of the data width.
    pad_batch_to: optional floor on the padded batch size — every
      merged batch pads up to (at least) this bucket, so the server
      compiles exactly ONE program instead of one per power-of-two
      bucket (VERDICT r3 W5: eval warmed 6 buckets ≈ 2–4 min of
      serial 20–40 s compiles before the first episode). The padding
      FLOPs are noise next to one avoided compile; use where the
      steady-state merged size is known (eval: all levels step
      concurrently), not for training fleets whose merge size is the
      tuning signal.
    fleet_size: number of actor threads this server will serve —
      consulted when config.inference_min_batch == 0 (AUTO merge
      floor; see the constructor comment) and when sizing the state
      arena (config.inference_state_slots == 0).
  """

  # Lock discipline (round 18; enforced by the guarded-by lint and,
  # armed, by OrderedLock's inversion detector). Documented order
  # where nested: _slot_lock -> _arena_lock and _slot_lock ->
  # _stats_lock (the admission path), _key_lock -> _arena_lock
  # (dispatch), _params_lock -> _stats_lock (publish-skip). Nothing
  # takes _slot_lock after any other lock.
  # Round 21: the version table and its A/B + shadow assignment state
  # live under _params_lock (the picker runs where the old single-
  # snapshot read ran); the AOT executable table under _aot_lock; the
  # routed-serving key counter under _remote_lock. None of the new
  # locks nests inside (or outside) another serving lock.
  _versions: guarded_by('_params_lock')
  _live_key: guarded_by('_params_lock')
  _serve_tick: guarded_by('_params_lock')
  _anon_seq: guarded_by('_params_lock')
  _ab_fraction: guarded_by('_params_lock')
  _ab_key: guarded_by('_params_lock')
  _ab_acc: guarded_by('_params_lock')
  _shadow_fraction: guarded_by('_params_lock')
  _shadow_key: guarded_by('_params_lock')
  _shadow_acc: guarded_by('_params_lock')
  _aot: guarded_by('_aot_lock')
  _warm_meta: guarded_by('_aot_lock')
  _warm_buckets: guarded_by('_aot_lock')
  _remote_calls: guarded_by('_remote_lock')
  _key: guarded_by('_key_lock')
  _arena: guarded_by('_arena_lock')
  _free: guarded_by('_slot_lock')
  _waiters: guarded_by('_slot_lock')
  _waiter_seq: guarded_by('_slot_lock')
  _closed: guarded_by('_slot_lock')
  _admission: guarded_by('_slot_lock')
  # The grow path swaps the arena (and its size) holding BOTH
  # _slot_lock and _arena_lock, so readers under either are safe.
  _num_slots: guarded_by('_slot_lock', '_arena_lock')
  # PR 39: the calls' cycle record is written by the completion thread
  # and by inline callers, each under _cycles_lock, which nests with no
  # other lock.
  _call_end: guarded_by('_cycles_lock')
  _calls: guarded_by('_stats_lock')
  _inline_calls: guarded_by('_stats_lock')
  _merged_requests: guarded_by('_stats_lock')
  _params_version: guarded_by('_stats_lock')
  _publishes_skipped: guarded_by('_stats_lock')
  _devices_last_call: guarded_by('_stats_lock')
  _inflight: guarded_by('_stats_lock')
  _inflight_peak: guarded_by('_stats_lock')
  _acquires: guarded_by('_stats_lock')
  _admission_waits: guarded_by('_stats_lock')
  _sheds: guarded_by('_stats_lock')
  _admission_timeouts: guarded_by('_stats_lock')
  _arena_grows: guarded_by('_stats_lock')
  _unjoined_threads: guarded_by('_stats_lock')
  _chain_recoveries: guarded_by('_stats_lock')
  _state_resets: guarded_by('_stats_lock')
  _version_flips: guarded_by('_stats_lock')
  _evictions: guarded_by('_stats_lock')
  _ab_calls: guarded_by('_stats_lock')
  _shadow_calls: guarded_by('_stats_lock')
  _shadow_divergence: guarded_by('_stats_lock')
  _aot_misses: guarded_by('_stats_lock')
  _slot_pos: guarded_by('_stats_lock')
  _prefill_tokens: guarded_by('_stats_lock')
  _prefill_chunks: guarded_by('_stats_lock')
  _cache_tokens_read: guarded_by('_stats_lock')
  _window_tokens_read: guarded_by('_stats_lock')
  _call_counts: guarded_by('_stats_lock')

  def __init__(self, agent, params, config, seed=0, mesh=None,
               pad_batch_to=None, fleet_size=None):
    self._pad_floor = pad_batch_to
    # inference_min_batch == 0 means AUTO: floor the merge at the
    # local fleet size, so every inference call carries the whole
    # fleet and per-call dispatch amortizes fully (measured +53% e2e
    # fps at the bench operating point — docs/PERF.md round-5 batcher
    # sweep). inference_timeout_ms bounds the wait when an actor is
    # mid-unroll-publish or being respawned, so the floor degrades to
    # a latency cap, never a deadlock.
    min_batch = config.inference_min_batch
    if min_batch == 0:
      min_batch = max(fleet_size or 1, 1)
    self._min_batch = min(min_batch, config.inference_max_batch)
    # A request of this many rows fills both the merge floor and the
    # padded bucket's floor alone: no other caller's rows could join
    # its call without a wait or a larger bucket, so its caller serves
    # it (`_call_inline`). Groups under it ride the batcher and merge:
    # an eval fleet's groups under `pad_batch_to`, a fleet's groups
    # under the auto floor.
    self._inline_rows = min(max(self._min_batch, pad_batch_to or 1),
                            config.inference_max_batch)
    self._agent = agent
    # One observation is `num_obs` arrays (the agent says which); one
    # session's recurrent state is a pytree of `[1, ...]` leaves.
    num_obs = len(agent.observation_names)
    self._state_spec = jax.eval_shape(lambda: agent.initial_state(1))
    state_leaves, self._state_treedef = jax.tree_util.tree_flatten(
        self._state_spec)
    self._state_dtypes = [l.dtype for l in state_leaves]
    self._state_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize for l in state_leaves)
    self._mesh = mesh
    self._state_cache = bool(config.inference_state_cache)
    if (not self._state_cache
        and self._state_bytes > MAX_HOST_STATE_BYTES):
      raise ValueError(
          f'a recurrent state of {self._state_bytes} bytes a session '
          'cannot ride with every policy call (carry-passing mode '
          f'moves it both ways; the limit is {MAX_HOST_STATE_BYTES}): '
          'keep it on the device with --inference_state_cache')
    self._depth = max(1, int(config.inference_pipeline_depth))
    # --- Slot admission policy (overload hardening; module docstring).
    self._admission = getattr(config, 'inference_admission', 'block')
    if self._admission not in ADMISSION_POLICIES:
      raise ValueError(
          f'unknown inference_admission {self._admission!r} '
          f'(policies: {ADMISSION_POLICIES})')
    self._admission_timeout = float(
        getattr(config, 'inference_admission_timeout_secs', 10.0))
    if mesh is not None:
      # Arena placements come from the sharding registry's primitive
      # helpers (round 19): params replicated over the acting mesh,
      # batch rows over the data axis — no private layout choice here.
      from scalable_agent_tpu.parallel import sharding as sharding_lib
      self._dp = int(mesh.shape[sharding_lib.DATA_AXIS])
      self._replicated = sharding_lib.replicated(mesh)
      self._batch_sharding = sharding_lib.data_sharding(mesh)
      params = jax.device_put(params, self._replicated)
    else:
      self._dp = 1
    self._params_lock = make_lock('inference._params_lock')
    # --- Serving version table (round 21; module docstring). The
    # constructor's params enter BY REFERENCE under a sentinel key no
    # caller-supplied version can equal, so the FIRST update_params
    # always lands a fresh owned copy (donation safety — see
    # update_params; the sentinel is process memory on purpose and
    # does NOT survive a checkpoint restore, tests/test_serving.py
    # pins why).
    self._resident_cap = max(1, int(
        getattr(config, 'serving_resident_versions', 1)))
    self._hbm_budget_bytes = int(
        float(getattr(config, 'serving_hbm_budget_mb', 0.0)) * 1e6)
    self._quantize_resident = (
        getattr(config, 'publish_codec', 'bf16') == 'int8')
    self._serve_tick = 0
    self._anon_seq = 0
    self._versions = collections.OrderedDict()
    seed_key = object()
    self._live_key = seed_key
    self._versions[seed_key] = _VersionEntry(
        seed_key, params, _tree_nbytes(params), 0)
    # A/B + shadow assignment (merged-call granularity — the batcher
    # merges many actors into one call, so per-request assignment
    # does not exist at this layer).
    self._ab_fraction = float(
        getattr(config, 'serving_ab_fraction', 0.0))
    self._ab_key = None      # None = auto: newest non-live resident
    self._ab_acc = 0.0
    self._shadow_fraction = float(
        getattr(config, 'serving_shadow_fraction', 0.0))
    self._shadow_key = None  # None = auto: newest non-live resident
    self._shadow_acc = 0.0
    # Per-bucket AOT serving executables (round 21): (the padded
    # bucket's layout, params-structure fingerprint) -> compiled step
    # (a step takes the layout as a static argument). Populated by
    # _precompile_params at publish/warmup time; _dispatch falls back
    # to the jit cache (and counts the miss) when absent.
    self._serving_aot = bool(getattr(config, 'serving_aot', False))
    self._aot_lock = make_lock('inference._aot_lock')
    self._aot = {}
    self._warm_meta = None
    self._warm_buckets = set()
    # Routed-serving (wire v10) RNG: a dedicated per-call fold chain,
    # so cross-host requests never perturb the local fleet's key.
    self._remote_lock = make_lock('inference._remote_lock')
    self._remote_calls = 0
    self._remote_base_key = jax.random.PRNGKey(seed + 424_243)
    self._stats_lock = make_lock('inference._stats_lock')
    self._version_flips = 0
    self._evictions = 0
    self._ab_calls = 0
    self._shadow_calls = 0
    self._shadow_divergence = 0.0
    self._aot_misses = 0
    self._calls = 0
    self._inline_calls = 0
    self._merged_requests = 0
    self._batcher_requests = 0
    self._params_version = 0
    self._publishes_skipped = 0
    self._devices_last_call = 0
    self._inflight = 0
    self._inflight_peak = 0
    # Admission counters (stats(); the driver's summary surface).
    self._acquires = 0
    self._admission_waits = 0      # acquires that had to park
    self._sheds = 0                # shed policy: deadline rejections
    self._admission_timeouts = 0   # block policy: deadline rejections
    self._arena_grows = 0
    self._unjoined_threads = 0
    self._admission_wait_reservoir = LatencyReservoir(maxlen=1024)
    # The merged calls' cycle record: five stamps a call (the phases of
    # `_CALL_PHASES` between them) and what crossed the host boundary
    # each way, written whole by the completion thread. THE source of
    # stats()'s latency percentiles (over its newest `_RECENT_CALLS`
    # rows: recent service time, not a week's history), of the
    # cumulative `call_*_ms` and of `serving/latency_ms`.
    self._cycles = telemetry.CycleRecord(
        _CALL_PHASES, extras=_CALL_EXTRAS, cycle_from=_IN_HAND)
    # It has one writer at a time: the completion thread and inline
    # callers write under this lock. `_call_end`: the newest row's
    # last stamp, where the next call's wait began at the earliest.
    self._cycles_lock = make_lock('inference._cycles_lock')
    self._call_end = 0
    # Callers' time parked in `policy`'s `compute`, ns (unlocked, as
    # `_batcher_requests` is).
    self._batcher_wait_ns = 0
    # _key is a DEVICE array chained through the jitted step (split
    # in-graph); the lock orders warmup (caller thread) against the
    # dispatch thread. Same split sequence as the old host-side
    # jax.random.split — numerics unchanged.
    self._key_lock = make_lock('inference._key_lock')
    self._key = jax.random.PRNGKey(seed)
    self._base_seed = seed
    self._chain_recoveries = 0
    self._state_resets = 0
    self._max_batch = config.inference_max_batch
    # A state written at a position (module docstring, PR 32).
    self.prefill_chunk = int(getattr(agent, 'prefill_chunk', 0))
    self._cache_capacity = int(getattr(agent, 'cache_capacity', 0))
    self._cache_window = int(getattr(agent, 'cache_window', 0))
    self._counter_names = tuple(getattr(agent, 'call_counters', ()))
    self._call_counts = {name: 0 for name in self._counter_names
                         if name in _CALL_COUNTERS}
    if len(self._call_counts) != len(self._counter_names):
      raise ValueError(f'call counters {self._counter_names}: the '
                       f'server knows {sorted(_CALL_COUNTERS)}')
    self._prefill_tokens = 0
    self._prefill_chunks = 0
    self._cache_tokens_read = 0
    self._window_tokens_read = 0
    self._slot_pos = np.zeros((0,), np.int64)
    if (self.prefill_chunk or self._counter_names) and not (
        self._state_cache):
      raise ValueError(
          'an agent whose core computes chunks or counts its calls '
          'keeps its state in the arena: --inference_state_cache')

    # --- Device-resident state arena (state-cache mode). ---
    # Lock order where nested: _slot_lock -> _arena_lock (the grow
    # path swaps the arena while holding the free list); _key_lock ->
    # _arena_lock (dispatch). Nothing takes _slot_lock after either.
    self._arena_lock = make_lock('inference._arena_lock')
    self._slot_lock = make_lock('inference._slot_lock')
    self._waiters = []          # parked _acquire_slot callers
    self._waiter_seq = 0
    if self._state_cache:
      num_slots = int(config.inference_state_slots)
      if num_slots <= 0:
        # Auto: 2× the fleet (respawn headroom — a wedged actor's slot
        # frees only when its orphaned thread unwinds) with a floor,
        # covering eval servers sized by pad_batch_to instead of
        # fleet_size.
        num_slots = max(2 * max(fleet_size or 0, pad_batch_to or 0), 8)
      self._num_slots = num_slots
      self._free = list(range(num_slots))
      self._arena = self._new_arena(num_slots)
      self._slot_pos = np.zeros((num_slots,), np.int64)
    else:
      self._num_slots = 0
      self._free = []
      self._arena = None
    if mesh is not None:
      self._key = jax.device_put(self._key, self._replicated)

    # The programs (`step_functions`): `self._out_layouts` is where a
    # step notes, as it is traced, the arrays it packs, for the host
    # to read them by.
    self._out_layouts = {}
    steps = step_functions(
        agent, self._state_cache, planar=_planar_bytes(),
        note_outputs=self._out_layouts.__setitem__,
        rows_sharding=None if mesh is None else self._batch_sharding)

    self._prefill_step = (
        jax.jit(steps.prefill_chunk, donate_argnums=(1,))
        if self.prefill_chunk else None)

    num_batch_args = 3 + num_obs + (
        1 if self._state_cache else len(state_leaves))
    # The arena is DONATED: the step's output takes its buffers, so a
    # core that updates rows in place costs no second arena (4.4 GB
    # for 32 retention sessions). Whoever reads `self._arena`
    # therefore dispatches its read under `_arena_lock`, before the
    # next step can take the buffers (`_read_slot`).
    donate = (2,) if self._state_cache else ()
    static = (4,) if self._state_cache else (3,)
    if mesh is None:
      self._step = jax.jit(steps.step, donate_argnums=donate,
                           static_argnums=static)
    else:
      # params keep their (replicated) placement; the key, the state
      # arena and the one buffer each way are replicated (`unpack`
      # shards the rows).
      self._step = jax.jit(
          steps.step,
          out_shardings=(self._replicated,) * (2 + len(donate)),
          donate_argnums=donate, static_argnums=static)
    # The shadow step takes the live step's arguments but the key.
    self._shadow_step = jax.jit(steps.shadow_step,
                                static_argnums=(static[0] - 1,))
    # Routed-serving step (serve_remote): always carry-passing — the
    # remote caller owns its carry; a cross-host request must never
    # consume a local arena slot. It comes as a dict of arrays off the
    # wire, not through the staging ring: the per-array form.
    self._remote_step = jax.jit(steps.carry_rows)
    # AOT lower/compile inputs (see _precompile_params): the key's
    # spec is fixed at construction; _step is the jit object lowered.
    self._key_spec = jax.ShapeDtypeStruct(
        np.shape(jax.random.PRNGKey(0)),
        np.asarray(jax.random.PRNGKey(0)).dtype)

    # --- Pipelined dispatch plane: the C++ batcher merges concurrent
    # policy() calls; the dispatch thread copies each merged batch
    # into a padded staging buffer (zero-copy via get_batch_into),
    # dispatches the jitted step (async), and moves on to assemble
    # the next batch; the completion thread reads results back in
    # FIFO order and unparks the callers. The semaphore bounds
    # dispatched-but-uncompleted batches at `depth`. ---
    self._staging = {}        # padded size -> ring of buffer lists
    self._inline_staging = threading.local()  # padded size -> _Staging
    self._staging_calls = {}  # padded size -> calls (ring index)
    self._batcher = dynamic_batching.Batcher(
        num_tensors=num_batch_args,
        minimum_batch_size=self._min_batch,
        maximum_batch_size=config.inference_max_batch,
        timeout_ms=config.inference_timeout_ms)
    self._sem = threading.Semaphore(self._depth)
    self._completion_q = queue.Queue()
    self._closed = False
    self._dispatch_thread = threading.Thread(
        target=self._dispatch_loop, name='inference-dispatch',
        daemon=True)
    self._completion_thread = threading.Thread(
        target=self._completion_loop, name='inference-completion',
        daemon=True)
    self._dispatch_thread.start()
    self._completion_thread.start()

  # -- state arena (state-cache mode) --

  def initial_core_state(self, priority=PRIORITY_LIVE):
    """Per-actor policy-state factory (driver.make_fleet's
    initial_state_fn): zeroed host carry in carry-passing mode, a
    freshly acquired (zeroed) arena slot in state-cache mode. Called
    at actor (re)spawn — a respawned actor starts from a clean slot
    either way. `priority` is the admission class of the acquire
    (PRIORITY_LIVE / PRIORITY_RESPAWN / PRIORITY_EVAL — released
    slots go to the best-priority parked waiter first)."""
    if not self._state_cache:
      return jax.tree_util.tree_map(
          lambda l: np.zeros(l.shape, l.dtype), self._state_spec)
    return self._acquire_slot(priority=priority)

  @property
  def admission(self) -> str:
    """The live admission policy (the controller's actuator get
    path). Round 18: read under _slot_lock like every other
    _admission access — the bare read was GIL-atomic but violated
    the declared guarded_by discipline (found by the lint)."""
    with self._slot_lock:
      return self._admission

  def set_admission(self, mode: str) -> str:
    """Thread-safe live admission-policy flip (round 15: the
    controller's overload actuator). Takes effect for every acquire
    that has not yet chosen its path; callers already PARKED on the
    waitlist keep their original deadline semantics (block→shed
    mid-park changes only how their deadline rejection is counted;
    →grow lets the next arriving acquire grow the arena, which then
    hands slots to the parked waiters through the normal release
    path). Returns the previous mode."""
    if mode not in ADMISSION_POLICIES:
      raise ValueError(f'unknown inference_admission {mode!r} '
                       f'(policies: {ADMISSION_POLICIES})')
    with self._slot_lock:
      old = self._admission
      self._admission = mode
    if old != mode:
      log.warning('inference admission policy: %s -> %s', old, mode)
    return old

  def _acquire_slot(self, priority=PRIORITY_LIVE):
    """Admit one slot acquisition under the configured policy (module
    docstring): fast-path pop when slots are free and nobody is parked
    ahead of us, else grow (grow policy) or park on the priority
    waitlist (block/shed) with a deadline. Raises SlotUnavailable at
    the deadline, InferenceClosed when the server shuts down — never
    the old bare 'state arena exhausted' RuntimeError."""
    # Fault site 'slot_exhaustion' (runtime/faults.py): a fired fault
    # forces this acquire down the contended path even when slots are
    # free — the parked waiter re-checks the real free list on its
    # next backoff tick, so the forced detour is bounded and the
    # waitlist machinery executes under test.
    forced = faults_lib.fire('slot_exhaustion') is not None
    waiter = None
    with self._slot_lock:
      if self._closed:
        raise InferenceClosed('inference server is closed')
      with self._stats_lock:
        self._acquires += 1
      if not forced and self._free and not self._waiters:
        slot = self._free.pop()
      elif self._admission == 'grow':
        if forced or not self._free:
          self._grow_arena_locked()
        slot = self._free.pop()
      else:
        self._waiter_seq += 1
        waiter = _Waiter(priority, self._waiter_seq)
        self._waiters.append(waiter)
        with self._stats_lock:
          self._admission_waits += 1
    if waiter is not None:
      slot = self._wait_for_slot(waiter)
    self._zero_slot(slot)
    return _SlotHandle(self, slot)

  def _best_waiter_locked(self):
    """Called with _slot_lock held; waitlists are fleet-sized."""
    return min(self._waiters, key=lambda w: (w.priority, w.seq))

  def _wait_for_slot(self, waiter):
    """Park until a released slot is handed over, the server closes,
    or the admission deadline passes. The event wait is capped-jitter
    (runtime.remote.Backoff) so a missed wake — or a fault-forced park
    with slots actually free — re-checks the free list instead of
    blocking until the deadline."""
    t0 = time.monotonic()
    deadline = t0 + self._admission_timeout
    backoff = Backoff(base=0.02, cap=0.5)
    while True:
      remaining = deadline - time.monotonic()
      if remaining > 0:
        waiter.event.wait(timeout=min(backoff.next_delay() + 1e-3,
                                      remaining))
      with self._slot_lock:
        if waiter.slot is not None:
          slot = waiter.slot  # direct handoff from _release_slot
          break
        if waiter.closed or self._closed:
          if waiter in self._waiters:
            self._waiters.remove(waiter)
          raise InferenceClosed(
              'inference server closed while waiting for a state slot')
        if self._free and self._best_waiter_locked() is waiter:
          self._waiters.remove(waiter)
          slot = self._free.pop()
          break
        if time.monotonic() >= deadline:
          self._waiters.remove(waiter)
          shed = self._admission == 'shed'
          with self._stats_lock:
            if shed:
              self._sheds += 1
            else:
              self._admission_timeouts += 1
          raise SlotUnavailable(
              f'{"shed" if shed else "admission timeout"}: no state-'
              f'arena slot free within {self._admission_timeout:.1f}s '
              f'({self._num_slots} slots, {len(self._waiters)} other '
              'waiter(s)) — overload; raise --inference_state_slots, '
              'or pick --inference_admission=grow')
    self._admission_wait_reservoir.record(time.monotonic() - t0)
    return slot

  def _grow_arena_locked(self):
    """Double the state arena in place (grow admission; called with
    _slot_lock held). Existing slot ids and carries are preserved; the
    new rows are zeroed and appended to the free list. One XLA
    recompile per growth (new arena shape) — rare by construction."""
    old = self._num_slots
    new = 2 * old if old else 8
    with self._arena_lock:
      self._arena = jax.tree_util.tree_map(
          lambda grown, a: grown.at[:old].set(a[:old]),
          self._new_arena(new), self._arena)
      self._num_slots = new
    with self._stats_lock:
      self._slot_pos = np.concatenate(
          [self._slot_pos, np.zeros((new - old,), np.int64)])
    self._free.extend(range(old, new))
    # Cache-mode AOT executables bake the arena shape into their
    # compiled programs — all stale after a grow. Drop them; the next
    # publish/warmup repopulates at the new shape. Lock order:
    # _slot_lock -> _aot_lock (this path only).
    with self._aot_lock:
      self._aot.clear()
    with self._stats_lock:
      self._arena_grows += 1
    log.warning(
        'inference state arena grown %d -> %d slots '
        '(--inference_admission=grow; one recompile per growth)',
        old, new)

  def _release_slot(self, slot):
    with self._slot_lock:
      if self._waiters:
        # Direct handoff to the best-priority waiter: the slot never
        # touches the free list, so a lower-priority waiter (or a
        # fresh fast-path acquire) cannot steal it.
        w = self._best_waiter_locked()
        self._waiters.remove(w)
        w.slot = slot
        w.event.set()
      else:
        self._free.append(slot)

  def _new_arena(self, num_slots):
    """The agent's zeroed state arena for `num_slots` sessions: per
    state leaf one `[rows, ...]` array in the leaf's dtype."""
    arena = self._agent.state_arena(num_slots)
    if self._mesh is not None:
      arena = jax.device_put(arena, self._replicated)
    return arena

  # One slot's row of every leaf, rewritten IN PLACE (the arena is
  # donated to these as it is to the step): `a.at[slot].set(...)`
  # outside a donating program would copy a whole arena of gigabytes
  # to change one row.
  _set_slot = staticmethod(jax.jit(
      lambda arena, slot, rows: jax.tree_util.tree_map(
          lambda a, r: a.at[slot].set(r[0]), arena, rows),
      donate_argnums=0))
  _clear_slot = staticmethod(jax.jit(
      lambda arena, slot: jax.tree_util.tree_map(
          lambda a: a.at[slot].set(jnp.zeros((), a.dtype)), arena),
      donate_argnums=0))

  def _zero_slot(self, slot):
    with self._arena_lock:
      self._arena = self._clear_slot(self._arena, np.int32(slot))
    with self._stats_lock:
      self._state_resets += 1
      self._slot_pos[slot] = 0

  def prefill(self, handle, tokens):
    """Begin an episode in `handle`'s slot with `tokens` i32 [n] behind
    it: ceil(n / prefill_chunk) chunk programs on the donated arena,
    each dispatched under `_arena_lock` like every other writer of the
    arena, so they run in order among the merged calls in flight (the
    caller is the slot's one actor, between two of its policy calls).
    The first chunk resets the slot's position; an empty block is that
    reset alone. Nothing is read back."""
    if not self.prefill_chunk:
      raise RuntimeError('this agent\'s core has no chunk form')
    tokens = np.asarray(tokens, np.int32)
    size, slot = self.prefill_chunk, np.int32(handle.slot)
    params = self.live_params()
    chunks = 0
    for lo in range(0, max(len(tokens), 1), size):
      block = np.zeros((size,), np.int32)
      valid = min(size, len(tokens) - lo)
      block[:valid] = tokens[lo:lo + valid]
      with telemetry.activity('inference/prefill', id=int(slot)):
        with self._arena_lock:
          self._arena = self._prefill_step(
              params, self._arena, slot, block, np.int32(valid),
              np.bool_(lo == 0))
      chunks += 1
    with self._stats_lock:
      self._slot_pos[handle.slot] = len(tokens)
      self._prefill_tokens += len(tokens)
      self._prefill_chunks += chunks

  def _read_slot(self, slot):
    if self._state_bytes > MAX_HOST_STATE_BYTES:
      return None  # never leaves the device
    with self._arena_lock:
      # Dispatched under the lock: the next step DONATES the arena,
      # and a read enqueued before it still sees these buffers. Only
      # the owning actor writes this slot, and it is parked here.
      rows = jax.tree_util.tree_map(lambda a: a[slot], self._arena)
    return jax.tree_util.tree_map(lambda r: np.asarray(r)[None], rows)

  def _write_slot(self, slot, carry):
    if carry is None:
      return self._zero_slot(slot)
    rows = jax.tree_util.tree_map(
        lambda c, l: np.asarray(c, l.dtype), carry, self._state_spec)
    with self._arena_lock:
      self._arena = self._set_slot(self._arena, np.int32(slot), rows)

  def _arena_now(self):
    with self._arena_lock:
      return self._arena

  def release_state(self):
    """Give the arena's device memory back; for a server that has been
    closed and is kept for its stats (gigabytes, where the state is a
    cache: a benchmark's reference needs the room)."""
    with self._arena_lock:
      self._arena = None

  def slots_free(self):
    with self._slot_lock:
      return len(self._free)

  # -- dispatch plane --

  def _staging_for(self, total_rows):
    """Padded staging buffers for a merged batch of total_rows rows.

    Per padded bucket, a ring of depth+1 preallocated positions, each
    ONE flat buffer and the per-input `[padded, ...]` views of it the
    batcher's merge-copy lands in (`_Staging`): with at most `depth`
    calls dispatched-but-uncompleted (the semaphore, which inline calls
    take too) and merged calls completed in FIFO order, a ring slot is
    reused only after the batch that last used it has completed — its
    host buffer is free to overwrite. Only the dispatch thread takes
    from the rings."""
    padded = self._padded_size(total_rows)
    ring = self._staging.get(padded)
    if ring is None:
      layout = packing.Layout.of_rows(self._batcher.input_meta(), padded)
      ring = [_Staging(layout) for _ in range(self._depth + 1)]
      self._staging[padded] = ring
      self._staging_calls[padded] = 0
    i = self._staging_calls[padded] % len(ring)
    self._staging_calls[padded] += 1
    return ring[i]

  def _inline_staging_for(self, rows):
    """The calling thread's own staging for an inline call of `rows`
    rows, one a padded bucket. A caller has one call at a time and
    reads it back before it returns, so its buffer is free to overwrite
    at its next call: no ring, no lock, and inline calls that complete
    out of order never touch the dispatch thread's rings."""
    padded = self._padded_size(rows)
    own = self._inline_staging.__dict__
    staging = own.get(padded)
    if staging is None:
      staging = own[padded] = _Staging(packing.Layout.of_rows(
          self._batcher.input_meta(), padded))
    return staging

  def _aot_lookup(self, params, layout):
    """The pre-compiled serving executable for this (padded bucket's
    layout, params structure), or None — in which case _dispatch falls
    back to the jit cache and the miss is counted (a miss on the serve
    path is exactly the first-call compile stall the AOT table exists
    to remove)."""
    k = (layout, _params_fingerprint(params))
    with self._aot_lock:
      compiled = self._aot.get(k)
    if compiled is None:
      with self._stats_lock:
        self._aot_misses += 1
    return compiled

  def _dispatch(self, params, staging, shadow_params=None):
    """Dispatch one padded batch (a `_Staging`: its flat buffer is the
    step's ONE batch argument) through the jitted step, chaining the
    device-resident key (and arena) — returns the (async) packed
    outputs, the shadow version's logits (or None) and how many host
    arrays the step was handed. The shadow step runs BEFORE the live
    step so both read the same pre-step arena carries."""
    step = self._step  # read per call: tests monkeypatch it
    packed, layout = staging.words, staging.layout
    crossing = sum(isinstance(leaf, np.ndarray)
                   for leaf in jax.tree_util.tree_leaves(packed))
    if self._mesh is not None:
      # Explicit placement: under multi-process JAX, jit refuses
      # numpy args with non-trivial shardings — and the local eval
      # mesh is exactly that. All its devices are process-local,
      # so the transfer itself is ordinary.
      packed = jax.device_put(packed, self._replicated)
    compiled = (self._aot_lookup(params, layout)
                if self._serving_aot else None)
    # A compiled executable has its static argument inside it.
    static = () if compiled is not None else (layout,)
    fn = compiled if compiled is not None else step
    with self._key_lock:
      if self._state_cache:
        with self._arena_lock:
          shadow_out = None
          if shadow_params is not None:
            shadow_out = self._shadow_step(
                shadow_params, self._arena, packed, layout)
          self._key, self._arena, out = fn(
              params, self._key, self._arena, packed, *static)
          return out, shadow_out, crossing
      shadow_out = None
      if shadow_params is not None:
        shadow_out = self._shadow_step(shadow_params, packed, layout)
      self._key, out = fn(params, self._key, packed, *static)
      return out, shadow_out, crossing

  def _account(self, staging, n, inline=False):
    """A call's accounting, whichever path runs it: its pad rows
    pointed out of range, then the call, its rows, the `done` rows
    that reset their session's state in-graph and the cached tokens
    its rows read."""
    if self._state_cache:
      # The staging ring reuses buffers: rows [n:] may hold slot
      # ids from an earlier (larger) merge — point them out of
      # range so the in-graph scatter drops them. The sentinel is
      # a constant (not num_slots): a concurrent 'grow' admission
      # must not turn a just-stamped pad id into a live slot.
      staging[0][n:] = _PAD_SLOT_ID
    resets = int(np.count_nonzero(
        staging[3 if self._state_cache else 2][:n]))
    with self._stats_lock:
      self._calls += 1
      self._inline_calls += inline
      self._merged_requests += n
      self._state_resets += resets
      if self._cache_capacity:
        # Each live row reads its cache up to the token this call
        # writes: its position (0 again where `done`) and one.
        slots = staging[0][:n]
        reads = np.where(staging[3][:n], 0, self._slot_pos[slots]) + 1
        self._slot_pos[slots] = reads
        cache_reads = int(np.sum(np.minimum(
            reads, self._cache_capacity)))
        self._cache_tokens_read += cache_reads
        # A layer that keeps a ring of the episode's last tokens
        # beside the cache reads that many of them at most.
        window_reads = int(np.sum(np.minimum(
            reads, self._cache_window)))
        self._window_tokens_read += window_reads

  def _note_dispatched(self):
    with self._stats_lock:
      self._inflight += 1
      self._inflight_peak = max(self._inflight_peak, self._inflight)

  @staticmethod
  def _fetched(payload):
    """(devices the call spanned, arrays to fetch) of a call's outputs,
    read before `device_get` turns them into host numpy: the
    sharded-eval contract's observability, and the d2h column."""
    try:
      devices = len(payload.sharding.device_set)
    except Exception:
      devices = 1
    return devices, len(jax.tree_util.tree_leaves(payload))

  def _read_back(self, payload, layout, call_id=None):
    """A dispatched call's outputs as host views, its counters summed.
    ONE device_get of ONE array: the step packed its outputs (each
    separate device→host readback is a full round trip); the host
    reads them as views of it, by the layout the program noted when it
    was traced."""
    with telemetry.span('inference/readback', id=call_id):
      host = packing.host_views(jax.device_get(payload),
                                self._out_layouts[layout])
    if self._counter_names:
      # The call's counters rode its readback, behind its outputs.
      split = len(host) - len(self._counter_names)
      host, counts = host[:split], host[split:]
      with self._stats_lock:
        for name, count in zip(self._counter_names, counts):
          self._call_counts[name] += int(count)
    return host

  def _fail_call(self, error, answer):
    """A failed execution poisons everything CHAINED from its outputs —
    the device key, and in cache mode the arena — which _dispatch
    already swapped in. Re-anchor them BEFORE `answer(message)` reaches
    the call's callers: an answered caller retries immediately, and
    that retry's dispatch must never inherit the poisoned chain (on a
    loaded 1-core host the retry used to win the race and fail on the
    poisoned key). The answer is in the finally so a recovery failure
    can't strand callers."""
    try:
      self._recover_chain()
    finally:
      answer(f'{type(error).__name__}: {error}')

  def _close_call(self, t_wait, t_hand, t_dispatched, t_read, t_unparked,
                  crossing, fetched, devices):
    """A dispatched call's end, whichever path ran it: its row of
    `_cycles` (five stamps: its wait's begin, batch in hand, jitted call
    returned, host views in hand, callers answered), its latency, the
    in-flight count. The wait's begin is moved up to the newest row's
    end where it began before that (which it always does at pipeline
    depth 1; an inline call gives 0: its wait begins there): the four
    phases of consecutive calls then do not overlap, and sum to the
    cycle."""
    with self._cycles_lock:
      began = max(t_wait, self._call_end) or t_hand
      self._cycles.write(min(began, t_hand), t_hand, t_dispatched, t_read,
                         t_unparked, crossing, fetched)
      self._call_end = max(self._call_end, t_unparked)
    _SERVE_LATENCY.observe((t_unparked - t_hand) / 1e6)
    with self._stats_lock:
      self._inflight -= 1
      self._devices_last_call = devices

  def _dispatch_loop(self):
    while True:
      try:
        # Late-bound: _staging_for is resolved per batch, after the
        # (long) park in get_batch — not captured at loop entry.
        with telemetry.park('inference/wait_batch') as wait:
          item = self._batcher.get_batch_into(
              lambda rows: self._staging_for(rows))
          if item is not None:
            wait.id = item[0]  # the batch this wait ended with
      except Exception:
        # Staging-buffer construction failed; get_batch_into answers
        # the batch's callers with the error before re-raising (its
        # rc-assert path cannot, so this stays loud). The dispatch
        # plane must survive — a dead dispatch thread hangs every
        # future policy call — but never silently: a persistent error
        # here would otherwise be an undiagnosable busy-spin.
        log.exception('inference dispatch: merged-batch staging failed')
        continue
      if item is None:
        self._completion_q.put(None)
        return
      batch_id, n, bufs = item
      t_hand = time.perf_counter_ns()
      dispatch = telemetry.span('inference/dispatch', id=batch_id)
      try:
        self._account(bufs, n)
        with self._params_lock:
          params, _ = self._pick_live_locked()
          shadow_params = self._pick_shadow_locked()
        self._sem.acquire()
        try:
          payload, shadow_out, crossing = self._dispatch(
              params, bufs, shadow_params)
          # The jitted call has returned: the `dispatch` phase ends.
          t_dispatched = time.perf_counter_ns()
          self._note_dispatched()
        except BaseException:
          self._sem.release()
          raise
        self._completion_q.put(
            (batch_id, n, (wait.t0, t_hand, t_dispatched), crossing,
             payload, shadow_out, bufs))
      except Exception as e:  # propagate to the parked callers
        self._batcher.set_error(batch_id, f'{type(e).__name__}: {e}')
      finally:
        dispatch.end()

  def _completion_loop(self):
    """Reads each dispatched call's result back, in order, unparks
    its callers and closes the call (`_close_call`) with the dispatch
    thread's three stamps and this thread's two. ONE phase from the
    jitted call's return to the host views: a `block_until_ready`
    before the `device_get` would part the launch and the device's
    time from the copy's, and was measured (PERF.md section 6, PR 37:
    in_flight 1.79 ms and readback 0.51 of `fleet32`'s mean call) at
    one more wake-up a call, 4.8% of `policy_call_p50_ms`: not kept."""
    while True:
      item = self._completion_q.get()
      if item is None:
        return
      (batch_id, n, (t_wait, t_hand, t_dispatched), crossing, payload,
       shadow_out, staging) = item
      t_read = None
      devices, fetched = self._fetched(payload)
      try:
        host = self._read_back(payload, staging.layout, batch_id)
        t_read = time.perf_counter_ns()
        with telemetry.span('inference/unpark', id=batch_id):
          self._batcher.set_outputs(batch_id, [o[:n] for o in host])
        t_unparked = time.perf_counter_ns()
        if shadow_out is not None:
          # Shadow scoring AFTER the callers are answered: the gauge
          # must never add device_get latency to the live path. Logits
          # sit at payload index 1 in both step modes.
          try:
            live_logits = host[1][:n]
            shadow_logits = np.asarray(jax.device_get(shadow_out))[:n]
            divergence = 1.0 - codec_lib.greedy_agreement(
                live_logits, shadow_logits)
            with self._stats_lock:
              self._shadow_calls += 1
              if self._shadow_calls == 1:
                self._shadow_divergence = divergence
              else:
                # EWMA: the gauge tracks RECENT divergence, so a
                # shadow flip mid-run shows up within ~10 samples.
                self._shadow_divergence = (
                    0.9 * self._shadow_divergence + 0.1 * divergence)
              ewma = self._shadow_divergence
            _SHADOW_DIVERGENCE.set(ewma)
          except Exception:
            log.exception('inference: shadow scoring failed')
      except Exception as e:

        def answer(message):
          try:
            self._batcher.set_error(batch_id, message)
          except Exception:
            pass

        self._fail_call(e, answer)
        # A failed call's row ends where its callers had their error.
        t_unparked = time.perf_counter_ns()
        t_read = t_read or t_unparked
      finally:
        self._sem.release()
      self._close_call(t_wait, t_hand, t_dispatched, t_read, t_unparked,
                       crossing, fetched, devices)

  def _call_inline(self, inputs):
    """One k-row request whose rows alone fill the merge, served on its
    caller's thread (module docstring, 2.): staged into the caller's
    own buffer (`_inline_staging_for`), accounted, dispatched under the same semaphore and
    locks as a merged call, read back with one `device_get`. Returns
    views of what it fetched, or None where a shadow version is live:
    shadow scoring follows the callers' answer, on the batched path.
    Any failure raises `BatcherError`, the batched path's answer to its
    callers; a failed execution re-anchors the chain first."""
    n = self._batcher.check(inputs)
    with self._params_lock:
      if self._shadow_entry_locked() is not None:
        return None
      params, _ = self._pick_live_locked()
    self._sem.acquire()
    try:
      staging = self._inline_staging_for(n)
      for view, rows in zip(staging, inputs):
        view[:n] = rows
      t_hand = time.perf_counter_ns()
      self._account(staging, n, inline=True)
      with telemetry.span('inference/dispatch'):
        payload, _, crossing = self._dispatch(params, staging)
      t_dispatched = time.perf_counter_ns()
    except BaseException as e:
      self._sem.release()
      if not isinstance(e, Exception):
        raise
      raise dynamic_batching.BatcherError(
          f'{type(e).__name__}: {e}') from e
    self._note_dispatched()
    devices, fetched = self._fetched(payload)
    t_read = None
    try:
      host = self._read_back(payload, staging.layout)
      t_read = time.perf_counter_ns()
      with telemetry.span('inference/unpark'):  # the answer: no copy
        return [o[:n] for o in host]
    except Exception as e:

      def answer(message):
        raise dynamic_batching.BatcherError(message) from e

      self._fail_call(e, answer)
    finally:
      t_unparked = time.perf_counter_ns()
      self._sem.release()
      self._close_call(0, t_hand, t_dispatched, t_read or t_unparked,
                       t_unparked, crossing, fetched, devices)

  def _recover_chain(self):
    """Re-anchor the device-chained state after a failed execution.

    The key (and state arena) are outputs of every dispatched step, so
    a failed step leaves poisoned arrays in the chain and every
    later dispatch would inherit the failure (the old host-side split
    survived transient failures — this restores that property). The
    key re-seeds deterministically from (base_seed, recovery count);
    the arena, if poisoned, can only be zeroed — its carry values
    passed through the failed step — which resets the fleet's
    episodes-in-flight, the same degraded class as a respawn's fresh
    episode."""
    recovered = False
    with self._key_lock:
      try:
        jax.block_until_ready(self._key)
      except Exception:
        recovered = True
        # Round 18 (guarded-by lint + review): read the recovery
        # count under _stats_lock NESTED in _key_lock — two racing
        # recoveries serialize on _key_lock, and each must see the
        # previous one's increment (below, same nesting) or both
        # would reseed with the identical (base_seed, count) key and
        # silently replay the same inference RNG stream. Lock order
        # _key_lock -> _stats_lock; nothing takes them inverted.
        with self._stats_lock:
          recoveries = self._chain_recoveries
        key = jax.random.PRNGKey(
            self._base_seed + 100_003 * (recoveries + 1))
        if self._mesh is not None:
          key = jax.device_put(key, self._replicated)
        self._key = key
      if self._state_cache:
        with self._arena_lock:
          try:
            jax.block_until_ready(self._arena)
          except Exception:
            recovered = True
            self._arena = self._new_arena(self._num_slots)
            # Every session begins again: so do the positions the
            # host follows (_key_lock -> _arena_lock -> _stats_lock).
            with self._stats_lock:
              self._slot_pos[:] = 0
      if recovered:
        # Still inside _key_lock: the count advance is part of the
        # recovery's critical section, not an afterthought a second
        # recoverer can sneak past.
        with self._stats_lock:
          self._chain_recoveries += 1

  def _padded_size(self, n):
    """Bucket size for a merged batch of n: next power of two (capped
    at max_batch), rounded up to a multiple of the mesh's data width
    so every shard is non-empty. Note the rounding can EXCEED
    max_batch when the data width doesn't divide it: max_batch caps
    how many real requests merge (the batcher enforces that); the
    padded compute shape must still be shardable."""
    if self._pad_floor is not None:
      n = max(n, self._pad_floor)
    padded = min(_next_power_of_two(n), self._max_batch)
    if self._dp > 1:
      padded = ((padded + self._dp - 1) // self._dp) * self._dp
    return padded

  def warmup(self, obs_spec, sizes=None, max_size=None):
    """Pre-compile the jitted step for the padded bucket sizes.

    XLA compiles one program per padded batch shape (powers of two up
    to max_batch). Without this, each new bucket's first appearance
    stalls EVERY parked actor thread for the 20–40 s TPU compile; the
    reference's TF graph had no such stall (dynamic batch dims). Call
    before starting the fleet.

    Args:
      obs_spec: the env's observation spec
        (structs.observation_leaves).
      sizes: iterable of *unpadded* sizes to warm. Default: every
        power-of-two bucket up to `max_size` (capped at
        maximum_batch_size) — pass max_size=fleet size so only
        reachable buckets compile.
      max_size: see `sizes`; None means maximum_batch_size.
    """
    obs_leaves = observation_leaves(obs_spec)
    if sizes is None:
      cap = self._max_batch if max_size is None else min(
          _next_power_of_two(max_size), self._max_batch)
      sizes, s = [], 1
      while s <= cap:
        sizes.append(s)
        s *= 2
      if sizes[-1] != cap:
        # A non-power-of-two max_batch cap is itself a reachable
        # padded size (merged batches pad to min(pow2, max_batch)).
        sizes.append(cap)
    if self._prefill_step is not None:
      # The chunk program too, on a slot out of range: it writes
      # nowhere (same program: values are not what XLA specializes on).
      with self._arena_lock:
        self._arena = self._prefill_step(
            self.live_params(), self._arena, _PAD_SLOT_ID,
            np.zeros((self.prefill_chunk,), np.int32),
            np.int32(self.prefill_chunk), np.bool_(True))
        jax.block_until_ready(self._arena)
    padded_done = set()
    for size in sizes:
      padded = self._padded_size(size)
      if padded in padded_done:
        continue
      padded_done.add(padded)
      params = self.live_params()
      # What a policy call's rows will be (`policy`), zeroed.
      meta = [(np.dtype(np.int32), ()), (np.dtype(np.float32), ()),
              (np.dtype(bool), ())] + [
                  (_wire_dtype(dtype), tuple(shape))
                  for shape, dtype in obs_leaves]
      if self._state_cache:
        meta.insert(0, (np.dtype(np.int32), ()))
      else:
        meta += [(np.dtype(l.dtype), tuple(l.shape[1:]))
                 for l in jax.tree_util.tree_leaves(self._state_spec)]
      staging = _Staging(packing.Layout.of_rows(meta, padded))
      if self._state_cache:
        # Warmup must not touch live carries: out-of-range slot ids
        # make every scatter a drop (same compiled program — shapes
        # and dtypes are what XLA specializes on, not values).
        staging[0][:] = _PAD_SLOT_ID
      # Record the input meta + warmed bucket for the AOT table —
      # _precompile_params re-derives argument specs from these when a
      # NEW params structure publishes later (the version-flip-
      # without-compile guarantee needs exactly this memo).
      with self._aot_lock:
        if self._warm_meta is None:
          self._warm_meta = tuple(meta)
        self._warm_buckets.add(padded)
      if self._serving_aot:
        # Pre-compile BEFORE dispatching, so warmup itself serves
        # from the AOT table (aot_misses stays 0 end to end).
        self._precompile_params(params)
      payload, _, _ = self._dispatch(params, staging)
      jax.block_until_ready(payload)

  def stats(self):
    """Merge + service telemetry, flat; docs/OBSERVABILITY.md "Cycle
    records" and docs/INFERENCE.md list every key. By group:

    - the merge: `calls`, `requests` (rows), `batcher_requests`
      (policy() calls), `inline_calls` (calls answered on their
      caller's thread, PR 39), `mean_batch`, `pipeline_depth`,
      `inflight_peak`, `devices_last_call`, `chain_recoveries`,
      `unjoined_threads`; `batcher_wait_ms` (cumulative: the callers'
      time parked in the batcher);
    - a merged call's time, from `_cycles`: cumulative
      `call_wait_batch_ms`, `call_dispatch_ms`,
      `call_in_flight_and_readback_ms`, `call_unpark_ms` (their change
      over a window by the change of `calls` is the window's mean);
      `latency_p50_ms`, `latency_p95_ms`, `latency_p99_ms` (batch in
      hand -> callers unparked, over the newest 512 calls: the
      benchmark's `inference.call_host_ms_p50` reads the first);
      `h2d_buffers_per_call`, `d2h_buffers_per_call` (the same calls);
      cumulative `call_excess_ms` (what the calls' latencies lay over
      their median, `telemetry.excess`), `call_excess_ms_in_<activity>`
      a name of `telemetry.ACTIVITIES`, `call_excess_ms_unnamed`,
      `call_cycles_lost`;
    - parameters: `params_version`, `publishes_skipped`,
      `resident_versions`, `live_version`, `serve_counts`,
      `version_flips`, `evictions`, `ab_calls`, `shadow_calls`,
      `shadow_divergence`, `aot_misses`, `aot_compiled`;
    - the sessions' state: `state_cache`, `slots_free`,
      `state_bytes_per_slot`, `arena_bytes`, `state_resets`,
      `prefill_tokens`, `prefill_chunks`, `cache_tokens_read`,
      `cache_capacity`, `window_tokens_read`, `cache_window`, and the
      agent's per-call counters by their names;
    - admission: `admission`, `acquires`, `admission_waits`, `sheds`,
      `admission_timeouts`, `admission_wait_p99_ms`, `arena_grows`,
      `waitlist_depth`.

    mean_batch near 1.0 means the batcher is not merging (the
    reference's ~3x single-machine win comes precisely from this
    number being high, paper Table 1); watch it when tuning
    inference_{min_batch,timeout_ms}.
    """
    _, recent = self._cycles.held(last=_RECENT_CALLS)
    lat = sorted(((recent[:, _UNPARKED] - recent[:, _IN_HAND])
                  / 1e6).tolist())
    h2d, d2h = recent[:, _UNPARKED + 1], recent[:, _UNPARKED + 2]
    call_totals = self._cycles.totals()
    with self._stats_lock:
      calls, reqs = self._calls, self._merged_requests
      inline_calls = self._inline_calls
      devices = self._devices_last_call
      version = self._params_version
      skipped = self._publishes_skipped
      peak = self._inflight_peak
      recoveries = self._chain_recoveries
      state_resets = self._state_resets
      acquires = self._acquires
      admission_waits = self._admission_waits
      sheds = self._sheds
      admission_timeouts = self._admission_timeouts
      arena_grows = self._arena_grows
      unjoined = self._unjoined_threads
      version_flips = self._version_flips
      evictions = self._evictions
      ab_calls = self._ab_calls
      shadow_calls = self._shadow_calls
      shadow_divergence = self._shadow_divergence
      aot_misses = self._aot_misses
      prefill_tokens = self._prefill_tokens
      prefill_chunks = self._prefill_chunks
      cache_tokens_read = self._cache_tokens_read
      window_tokens_read = self._window_tokens_read
      call_counts = dict(self._call_counts)
    with self._params_lock:
      resident = len(self._versions)
      live_label = self._versions[self._live_key].label()
      serve_counts = {str(e.label()): e.serves
                      for e in self._versions.values()}
    with self._aot_lock:
      aot_compiled = len(self._aot)
    with self._slot_lock:
      waitlist_depth = len(self._waiters)
      admission = self._admission
    (wait_p99_ms,) = self._admission_wait_reservoir.percentile_ms(0.99)
    return {
        'calls': calls,
        'requests': reqs,
        # `requests` counts ROWS (one per env step); this counts the
        # policy() calls that carried them: rows per call is 1 for
        # lone actors, k for an ActorGroup of k.
        'batcher_requests': self._batcher_requests,
        # Calls answered on their caller's thread, of `calls`: a
        # group's request that alone filled the merge and pad floors.
        'inline_calls': inline_calls,
        'mean_batch': (reqs / calls) if calls else 0.0,
        'params_version': version,
        'publishes_skipped': skipped,
        'devices_last_call': devices,
        'batcher_wait_ms': self._batcher_wait_ns / 1e6,
        **{f'call_{phase}_ms': call_totals[phase + '_ns'] / 1e6
           for phase in _CALL_PHASES},
        'latency_p50_ms': round(percentile_ms(lat, 0.5), 3),
        'latency_p95_ms': round(percentile_ms(lat, 0.95), 3),
        'latency_p99_ms': round(percentile_ms(lat, 0.99), 3),
        # Over the same newest calls: host arrays handed to the step
        # a call, arrays fetched from it a call (one each since PR 36;
        # counted where they cross).
        'h2d_buffers_per_call': float(h2d.mean()) if len(h2d) else 0.0,
        'd2h_buffers_per_call': float(d2h.mean()) if len(d2h) else 0.0,
        **telemetry.excess_ms('call_', telemetry.excess(self._cycles)),
        'pipeline_depth': self._depth,
        'state_cache': self._state_cache,
        'inflight_peak': peak,
        'chain_recoveries': recoveries,
        'slots_free': self.slots_free() if self._state_cache else None,
        # The recurrent state (PR 27): one session's bytes, the whole
        # arena's (0 in carry-passing mode), and how often a state was
        # zeroed: a `done` row of a merged call, or a slot cleared on
        # acquire or by an actor's priming undo.
        'state_bytes_per_slot': self._state_bytes,
        'arena_bytes': (_tree_nbytes(self._arena_now())
                        if self._state_cache else 0),
        'state_resets': state_resets,
        # A state written at a position (PR 32): prompt tokens handed
        # over in blocks and the chunk programs they took; the cached
        # tokens the merged calls' live rows read (host arithmetic on
        # each call's `done` rows and each block's length); what a
        # slot's cache holds at most (0: a state of fixed size); the
        # same of a ring of the episode's last `cache_window` tokens
        # that some layers keep beside it (0: none does); and the
        # per-call counters the agent's layers sow, summed.
        'prefill_tokens': prefill_tokens,
        'prefill_chunks': prefill_chunks,
        'cache_tokens_read': cache_tokens_read,
        'cache_capacity': self._cache_capacity,
        'window_tokens_read': window_tokens_read,
        'cache_window': self._cache_window,
        **call_counts,
        # Admission/overload telemetry (round 9): the shed fraction is
        # sheds / acquires — the serving-plane overload SLO number.
        'admission': admission,
        'acquires': acquires,
        'admission_waits': admission_waits,
        'sheds': sheds,
        'admission_timeouts': admission_timeouts,
        'admission_wait_p99_ms': wait_p99_ms,
        'arena_grows': arena_grows,
        'waitlist_depth': waitlist_depth,
        'unjoined_threads': unjoined,
        # Serving version table (round 21): per-version counters keyed
        # by entry label, plus the A/B + shadow + AOT planes.
        'resident_versions': resident,
        'live_version': live_label,
        'serve_counts': serve_counts,
        'version_flips': version_flips,
        'evictions': evictions,
        'ab_calls': ab_calls,
        'shadow_calls': shadow_calls,
        'shadow_divergence': round(shadow_divergence, 6),
        'aot_misses': aot_misses,
        'aot_compiled': aot_compiled,
    }

  # -- serving version table (round 21) --

  def live_params(self):
    """The params tree the next merged call serves (inspection: where
    it lives, which devices it spans)."""
    with self._params_lock:
      return self._versions[self._live_key].params

  def _newest_nonlive_locked(self):
    """The most recently PUBLISHED non-live resident entry (insertion
    order, not serve recency) — the auto A/B candidate and the auto
    shadow version. Called with _params_lock held."""
    for key in reversed(self._versions):
      if key != self._live_key:
        return self._versions[key]
    return None

  def _entry_for_locked(self, key_or_none):
    if key_or_none is None:
      return self._newest_nonlive_locked()
    return self._versions.get(key_or_none)

  def _pick_live_locked(self):
    """Pick this merged call's serving params under _params_lock: the
    live entry, or — serving_ab_fraction of calls, via a deterministic
    accumulator — the A/B candidate (set_ab's key, else the newest
    non-live resident). Bumps the entry's serve counter + LRU tick.
    Returns (params, entry key)."""
    self._serve_tick += 1
    entry = self._versions[self._live_key]
    if self._ab_fraction > 0.0:
      cand = self._entry_for_locked(self._ab_key)
      if cand is not None and cand.key != self._live_key:
        self._ab_acc += self._ab_fraction
        if self._ab_acc >= 1.0:
          self._ab_acc -= 1.0
          entry = cand
          with self._stats_lock:
            self._ab_calls += 1
    entry.serves += 1
    entry.tick = self._serve_tick
    return entry.params, entry.key

  def _shadow_entry_locked(self):
    """The shadow version's entry while one is live, else None: a
    fraction is set and there is a version to replay against,
    set_shadow's key, else the newest non-live resident; never the
    live entry (zero divergence by construction would only dilute the
    gauge)."""
    if self._shadow_fraction <= 0.0:
      return None
    entry = self._entry_for_locked(self._shadow_key)
    if entry is None or entry.key == self._live_key:
      return None
    return entry

  def _pick_shadow_locked(self):
    """The shadow version's params for this merged call, or None —
    sampled at serving_shadow_fraction by the same accumulator
    scheme."""
    entry = self._shadow_entry_locked()
    if entry is None:
      return None
    self._shadow_acc += self._shadow_fraction
    if self._shadow_acc < 1.0:
      return None
    self._shadow_acc -= 1.0
    return entry.params

  def _install_locked(self, key, params):
    """Insert an OWNED params copy as the live entry, then evict LRU
    unpinned non-live entries past the count cap / byte budget.
    Called with _params_lock held."""
    self._serve_tick += 1
    self._versions[key] = _VersionEntry(
        key, params, _tree_nbytes(params), self._serve_tick)
    self._versions.move_to_end(key)
    self._live_key = key
    self._evict_locked()

  def _evict_locked(self):
    while True:
      over_count = len(self._versions) > self._resident_cap
      over_bytes = (
          self._hbm_budget_bytes > 0 and len(self._versions) > 1
          and sum(e.nbytes for e in self._versions.values())
          > self._hbm_budget_bytes)
      if not (over_count or over_bytes):
        return
      victim = None
      for e in self._versions.values():
        if e.key == self._live_key or e.pinned:
          continue
        if victim is None or e.tick < victim.tick:
          victim = e
      if victim is None:
        # Every resident entry is live or pinned: the budget cannot
        # be honoured without breaking a pin — keep them and say so.
        log.warning(
            'serving version table over budget (%d resident) but '
            'every entry is live/pinned — nothing evictable',
            len(self._versions))
        return
      del self._versions[victim.key]
      with self._stats_lock:
        self._evictions += 1
      log.info('serving: evicted resident version %s (LRU; %d left)',
               victim.label(), len(self._versions))

  def _precompile_params(self, params):
    """AOT-compile the serving step for `params`' structure across
    every warmed bucket (the jit .lower(...).compile() seam —
    parallel/fit.py's AOT pattern), so a later flip to this version
    never pays first-call compile on the serve path. Runs on the
    PUBLISHER's thread; a no-op before the first warmup() (no input
    meta recorded yet) and for already-compiled (bucket, structure)
    keys."""
    with self._aot_lock:
      meta = self._warm_meta
      buckets = sorted(self._warm_buckets)
    if meta is None:
      return
    fingerprint = _params_fingerprint(params)
    params_sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), l.dtype), params)
    arena_sds = ()
    if self._state_cache:
      arena_sds = (jax.tree_util.tree_map(
          lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
          self._arena_now()),)
    for padded in buckets:
      layout = packing.Layout.of_rows(meta, padded)
      cache_key = (layout, fingerprint)
      with self._aot_lock:
        if cache_key in self._aot:
          continue
      packed_sds = jax.ShapeDtypeStruct((layout.words,), packing.WORD)
      try:
        compiled = self._step.lower(
            params_sds, self._key_spec, *arena_sds, packed_sds,
            layout).compile()
      except Exception:
        log.exception(
            'serving AOT compile failed (bucket %d) — the jit cache '
            'covers it at first-call cost', padded)
        return
      with self._aot_lock:
        self._aot[cache_key] = compiled

  def update_params(self, params, version=None):
    """Publish a weight snapshot into the serving version table.

    Copy semantics: a NEW entry copies each leaf — the learner's train
    step DONATES its state, so the caller's buffers will be
    invalidated by the next update; a zero-copy swap would hand actors
    deleted buffers ("Buffer has been deleted or donated"). The copy
    is dispatched before any subsequent donation, so it's race-free.
    On the mesh path the explicit copy also matters: device_put alone
    is a NO-OP (aliased buffers) when the input already carries the
    target sharding.

    Version semantics (round 21):
      - version == the LIVE entry's key: skipped entirely (counted in
        stats()['publishes_skipped']) — republishing an unchanged
        snapshot must not cost a tree copy.
      - version RESIDENT but not live: flips live to that entry with
        NO copy (stats()['version_flips']) — the rollback/promote
        path the table exists for.
      - otherwise: copy (quantize first when publish_codec=int8),
        AOT-precompile if enabled (BEFORE the flip, off the serve
        path), install as live, evict LRU past the caps.
      - version=None: always a fresh anonymous entry (the safe
        default for callers with no version).

    Restore caveat (round 21 satellite; tests/test_serving.py pins
    it): the table — dedup keys included — is process memory BY
    DESIGN. A server rebuilt after a checkpoint restore re-copies on
    the first publish of any version, including a numeric version it
    published before the restart: the constructor holds its params by
    reference under a sentinel key, and the first publish must land
    an owned copy for the donation safety above. A dedup key that
    survived restore would skip that copy and hand actors the
    learner's donated buffers.
    """
    if version is not None:
      with self._params_lock:
        if version == self._live_key:
          with self._stats_lock:
            self._publishes_skipped += 1
          return
        if version in self._versions:
          self._serve_tick += 1
          entry = self._versions[version]
          entry.tick = self._serve_tick
          self._versions.move_to_end(version)
          self._live_key = version
          with self._stats_lock:
            self._version_flips += 1
            self._params_version += 1
          return
    params = jax.tree_util.tree_map(jnp.copy, params)
    if self._quantize_resident:
      params = codec_lib.quantize_device(params)
    if self._mesh is not None:
      params = jax.device_put(params, self._replicated)
    if self._serving_aot:
      # Compile for this structure BEFORE the entry goes live: the
      # publisher's thread eats the compile, never a serving call.
      self._precompile_params(params)
    with self._params_lock:
      key = version
      if key is None:
        self._anon_seq += 1
        key = ('anon', self._anon_seq)
      self._install_locked(key, params)
    with self._stats_lock:
      self._params_version += 1

  def pin_version(self, version, pinned=True):
    """Pin (or unpin) a resident version: pinned entries are exempt
    from LRU eviction — the rollback anchor. Returns True if the
    version was resident."""
    with self._params_lock:
      entry = self._versions.get(version)
      if entry is None:
        return False
      entry.pinned = bool(pinned)
      return True

  def set_live(self, version):
    """Flip serving to an already-resident version without a publish
    (stats()['version_flips']). Raises KeyError if not resident."""
    with self._params_lock:
      if version not in self._versions:
        raise KeyError(f'version {version!r} is not resident')
      if version == self._live_key:
        return
      self._serve_tick += 1
      entry = self._versions[version]
      entry.tick = self._serve_tick
      self._versions.move_to_end(version)
      self._live_key = version
      with self._stats_lock:
        self._version_flips += 1
        self._params_version += 1

  def set_ab(self, version, fraction):
    """Route `fraction` of merged calls to `version` (None = the
    newest non-live resident). Fraction 0 disables A/B."""
    fraction = float(fraction)
    if not 0.0 <= fraction <= 1.0:
      raise ValueError(f'ab fraction {fraction} outside [0, 1]')
    with self._params_lock:
      self._ab_key = version
      self._ab_fraction = fraction
      self._ab_acc = 0.0

  def set_shadow(self, version, fraction):
    """Replay `fraction` of merged calls against `version` (None =
    the newest non-live resident) and score greedy agreement vs live
    into the serving/shadow_divergence gauge. Fraction 0 disables."""
    fraction = float(fraction)
    if not 0.0 <= fraction <= 1.0:
      raise ValueError(f'shadow fraction {fraction} outside [0, 1]')
    with self._params_lock:
      self._shadow_key = version
      self._shadow_fraction = fraction
      self._shadow_acc = 0.0

  def resident_versions(self):
    """[(label, serves, pinned, live?)] for every resident entry, in
    publish order — the bench's per-version counter rows."""
    with self._params_lock:
      return [(e.label(), e.serves, e.pinned, e.key == self._live_key)
              for e in self._versions.values()]

  _REMOTE_ORDER = ('prev_action', 'reward', 'done', 'frame', 'instr',
                   'core_c', 'core_h')

  def serve_remote(self, payload):
    """Serve one CARRY-PASSING batch for the wire-v10 routed inference
    service (runtime/remote.py 'infer' requests — the driver attaches
    this as the ingest server's serving seam).

    `payload` is a dict of batch-leading arrays: prev_action [B]
    int32, reward [B] f32, done [B] bool, frame [B,H,W,C] uint8,
    instr [B,L] int32, core_c/core_h [B,H] f32. Returns the result
    dict (action, logits, baseline, core_c, core_h, version label).

    Carry-passing even on a state-cache server: the remote caller
    owns its carry — a cross-host request must never consume a local
    arena slot. RNG is a per-call fold_in of a dedicated base key, so
    routed traffic never perturbs the local fleet's key chain. One
    compiled program per distinct batch size: route fixed-size
    batches, or accept the first-call compile."""
    t0 = time.perf_counter()
    inputs = tuple(np.asarray(payload[k]) for k in self._REMOTE_ORDER)
    with self._params_lock:
      params, key = self._pick_live_locked()
      label = self._versions[key].label()
    with self._remote_lock:
      self._remote_calls += 1
      count = self._remote_calls
    sub = jax.random.fold_in(self._remote_base_key, count)
    if self._mesh is not None:
      inputs = jax.device_put(inputs, self._replicated)
    outs = self._remote_step(params, sub, *inputs)
    action, logits, baseline, new_c, new_h = jax.device_get(outs[1:])
    _SERVE_LATENCY.observe((time.perf_counter() - t0) * 1e3)
    return {
        'action': np.asarray(action),
        'logits': np.asarray(logits),
        'baseline': np.asarray(baseline),
        'core_c': np.asarray(new_c),
        'core_h': np.asarray(new_h),
        'version': label,
    }

  def policy(self, prev_action, env_output, core_state):
    """`runtime.actor.Actor`-contract policy, in both of its forms.

    Scalar form (one env): scalars in, scalars out. k-row form (an
    `ActorGroup` of k envs; told by `prev_action` being i32[k]): every
    leaf of `env_output` and of the returned AgentOutput has a leading
    axis of k, and the k rows ride the batcher as ONE request (one
    park, one wake, one copy of the results), never split across
    merged calls; where they alone fill the merge and pad floors they
    are ONE call on this thread, and the outputs are read-only views of
    its readback. Row by row the two forms compute the same.

    Carry-passing mode: core_state is the numeric carry, a pytree of
    `[1, ...]` leaves (`[k, ...]` for k rows; the LSTM's `(c, h)`),
    and the new carry rides the wire back. State-cache mode: core_state is a `_SlotHandle` (a list
    of k for k rows) and only the slot ids ride the wire — the carries
    advance in-graph on the device."""
    grouped = np.ndim(prev_action) > 0

    def rows(x, dtype=None):
      x = np.asarray(x, dtype)
      if dtype is None:  # an observation leaf, as its env made it
        x = x.astype(_wire_dtype(x.dtype), copy=False)
      return x if grouped else x[None]

    inputs = [
        rows(prev_action, np.int32),
        rows(env_output.reward, np.float32),
        rows(env_output.done, bool),
        *[rows(leaf) for leaf in env_output.observation]]
    if self._state_cache:
      handles = core_state if grouped else [core_state]
      for handle in handles:
        if not isinstance(handle, _SlotHandle):
          raise TypeError(
              'state-cache mode: core_state must be the slot handle '
              'from initial_core_state(), got '
              f'{type(handle).__name__}')
        if handle.released:
          # A respawned actor owns this slot's successor; a straggler
          # thread must fail here, not scatter into someone else's slot.
          raise RuntimeError(
              'policy() called with a released state slot')
      inputs.insert(0, np.asarray([h.slot for h in handles], np.int32))
      new_state = core_state
    else:
      inputs += [np.asarray(leaf, dtype) for leaf, dtype in zip(
          jax.tree_util.tree_leaves(core_state), self._state_dtypes)]
    # No lock for a counter on every caller's path: a count lost to
    # two callers' race is within what stats() promises of it.
    self._batcher_requests += 1
    t0 = time.perf_counter_ns()
    with telemetry.span('batcher/compute'):
      # A group's request that alone fills the merge and pad floors has
      # nothing to merge with or wait for: its own thread serves it
      # (module docstring, 2.). A lone actor's row, a group under either
      # floor and any call while a shadow version is live ride the
      # batcher.
      outs = None
      if (grouped and self._inline_rows <= len(inputs[0]) <= self._max_batch
          and not self._batcher.closed):
        outs = self._call_inline(inputs)
      if outs is None:
        outs = self._batcher.compute(inputs)
    self._batcher_wait_ns += time.perf_counter_ns() - t0
    if not self._state_cache:
      new_state = jax.tree_util.tree_unflatten(self._state_treedef,
                                               outs[3:])
    action, logits, baseline = outs[:3]
    if not grouped:
      action, logits, baseline = action[0], logits[0], baseline[0]
    return AgentOutput(action=action, policy_logits=logits,
                       baseline=baseline), new_state

  def close(self):
    with self._slot_lock:
      if self._closed:
        return
      self._closed = True
      # Parked admission waiters get a CLEAN InferenceClosed answer —
      # a caller waiting out an overload must not block forever on a
      # server that is going away.
      waiters, self._waiters = self._waiters, []
      for w in waiters:
        w.closed = True
        w.event.set()
    # Close wakes the dispatch thread's get_batch (None) and cancels
    # parked callers; the dispatch thread forwards the sentinel so the
    # completion thread drains in-flight batches first.
    self._batcher.close()
    unjoined = []
    for t in (self._dispatch_thread, self._completion_thread):
      if t is not None:
        t.join(timeout=10)
        if t.is_alive():
          unjoined.append(t.name)
    if unjoined:
      # Leaked threads used to vanish silently; a wedged dispatch/
      # completion thread pins device buffers and a staging ring for
      # the rest of the process lifetime — say so, and count it.
      with self._stats_lock:
        self._unjoined_threads = len(unjoined)
      log.warning(
          'InferenceServer.close(): %d thread(s) missed the join '
          'deadline and leak as daemons: %s', len(unjoined),
          ', '.join(unjoined))

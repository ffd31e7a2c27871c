"""Multi-tenant serving plane (round 21): version table, int8 codec,
AOT serving, wire-v10 routed inference, and the ServingRouter.

The serving PR's contract surface: N resident policy versions with
LRU/pinned eviction and per-version serve counters, A/B + shadow
traffic, an int8 publish codec (in-process resident copies AND the
cross-host fan-out blob, parity-gated in the bench), per-bucket AOT
compilation so a version flip never pays first-call compile on the
serve path, and actor-side request routing over v10 replicas.
"""

import pickle
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import ImpalaAgent, init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.runtime import codec
from scalable_agent_tpu.runtime import packing
from scalable_agent_tpu.runtime import remote
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.runtime import routing
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput, StepOutputInfo

H, W, A = 24, 32, 3
OBS = {'frame': (H, W, 3), 'instr_len': MAX_INSTRUCTION_LEN}

_AGENT = ImpalaAgent(num_actions=A, torso='shallow',
                     use_instruction=False)
_PARAMS = init_params(_AGENT, jax.random.PRNGKey(0), OBS)
_PARAMS_B = init_params(_AGENT, jax.random.PRNGKey(1), OBS)


def _server(**cfg_kw):
  cfg = Config(inference_min_batch=0, inference_max_batch=8,
               inference_timeout_ms=5, inference_state_cache=False,
               **cfg_kw)
  return InferenceServer(_AGENT, _PARAMS, cfg, seed=7,
                         pad_batch_to=1, fleet_size=1)


def _fresh(tree=None):
  return jax.tree_util.tree_map(lambda a: a + 0, tree or _PARAMS)


def _labels(server):
  return {label for label, _, _, _ in server.resident_versions()}


def _payload(server, b=2, seed=0):
  rng = np.random.RandomState(seed)
  sizes = [int(np.shape(c)[-1]) for c in server.initial_core_state()]
  return {
      'prev_action': np.zeros((b,), np.int32),
      'reward': np.zeros((b,), np.float32),
      'done': np.zeros((b,), np.bool_),
      'frame': rng.randint(0, 255, (b, H, W, 3)).astype(np.uint8),
      'instr': np.zeros((b, MAX_INSTRUCTION_LEN), np.int32),
      'core_c': np.zeros((b, sizes[0]), np.float32),
      'core_h': np.zeros((b, sizes[1]), np.float32),
  }


class TestInt8Codec:

  def test_roundtrip_error_bounded_by_scale(self):
    tree = {'w': np.linspace(-3.0, 3.0, 101).astype(np.float32),
            'b': np.zeros((7,), np.float32)}
    q = codec.quantize_np(tree)
    back = codec.dequantize_np(q)
    # Per-leaf absmax scaling: error <= scale/2 (rounding half-step).
    assert np.max(np.abs(back['w'] - tree['w'])) <= (3.0 / 127) / 2 + 1e-7
    # The all-zero leaf must round-trip EXACTLY (scale 0, not NaN).
    np.testing.assert_array_equal(back['b'], tree['b'])
    assert codec.is_quantized(q)
    assert not codec.is_quantized(tree)

  def test_device_and_host_quantize_agree(self):
    tree = {'w': np.linspace(-1.0, 2.0, 64).astype(np.float32)}
    q_np = codec.quantize_np(tree)
    q_dev = jax.device_get(codec.quantize_device(
        jax.tree_util.tree_map(jnp.asarray, tree)))
    np.testing.assert_array_equal(q_np['w'].q, np.asarray(q_dev['w'].q))
    assert np.isclose(float(q_np['w'].scale), float(q_dev['w'].scale))

  def test_dequantize_tree_traces_through_jit(self):
    # The in-graph dequant the serving step leans on: Int8Leaf is a
    # registered pytree node, so a quantized tree crosses the jit
    # boundary and dequantizes inside the compiled program.
    tree = codec.quantize_np({'w': np.arange(8, dtype=np.float32)})

    @jax.jit
    def f(t):
      return jax.tree_util.tree_reduce(
          lambda acc, x: acc + jnp.sum(x), codec.dequantize_tree(t), 0.0)

    assert float(f(tree)) == pytest.approx(float(np.sum(np.round(
        codec.dequantize_np(tree)['w']))), abs=0.2)

  def test_wire_sizes_and_agreement(self):
    tree = {'w': np.zeros((1000,), np.float32)}
    f32, bf16, int8 = codec.wire_sizes(tree)
    assert f32 > bf16 > int8
    a = np.array([[0.1, 0.9], [0.8, 0.2]], np.float32)
    b = np.array([[0.2, 0.7], [0.1, 0.6]], np.float32)
    assert codec.greedy_agreement(a, a) == 1.0
    assert codec.greedy_agreement(a, b) == 0.5
    assert codec.greedy_agreement(np.zeros((0, 2), np.float32),
                                  np.zeros((0, 2), np.float32)) == 1.0


class TestVersionTable:

  def test_resident_lru_eviction(self):
    server = _server(serving_resident_versions=2)
    try:
      for v in (1, 2, 3):
        server.update_params(_fresh(), version=v)
      assert _labels(server) == {2, 3}
      snap = server.stats()
      assert snap['resident_versions'] == 2
      assert snap['live_version'] == 3
      assert snap['evictions'] >= 2  # the seed entry, then v1
    finally:
      server.close()

  def test_pinned_version_survives_eviction(self):
    server = _server(serving_resident_versions=2)
    try:
      server.update_params(_fresh(), version=1)
      assert server.pin_version(1)
      server.update_params(_fresh(), version=2)
      server.update_params(_fresh(), version=3)
      # v1 is pinned: the LRU victim had to be v2 instead.
      assert 1 in _labels(server)
      assert 2 not in _labels(server)
      server.set_live(1)
      assert server.stats()['live_version'] == 1
      with pytest.raises(KeyError):
        server.set_live(99)
    finally:
      server.close()

  def test_hbm_budget_evicts_down_to_live(self):
    # A byte budget far below one snapshot: everything but the live
    # entry must go (the evictor never evicts live, budget or not).
    server = _server(serving_resident_versions=4,
                     serving_hbm_budget_mb=0.001)
    try:
      for v in (1, 2, 3):
        server.update_params(_fresh(), version=v)
      assert _labels(server) == {3}
    finally:
      server.close()

  def test_same_version_dedup_and_none_always_publishes(self):
    server = _server(serving_resident_versions=4)
    try:
      server.update_params(_fresh(), version=1)
      before = server.stats()
      server.update_params(_fresh(), version=1)  # same as live: no-op
      snap = server.stats()
      assert snap['params_version'] == before['params_version']
      assert snap['publishes_skipped'] == before['publishes_skipped'] + 1
      # None-version publishes NEVER dedup (no identity to dedup on),
      # and each gets a distinct anon label.
      server.update_params(_fresh())
      server.update_params(_fresh())
      snap = server.stats()
      assert snap['params_version'] == before['params_version'] + 2
      anon = [l for l in _labels(server)
              if isinstance(l, str) and l.startswith('anon-')]
      assert len(anon) == 2
    finally:
      server.close()

  def test_resident_version_flip_without_copy(self):
    server = _server(serving_resident_versions=3)
    try:
      server.update_params(_fresh(), version=1)
      server.update_params(_fresh(), version=2)
      before = server.stats()
      # v1 is RESIDENT: publishing it again is a live-pointer flip —
      # no copy, no install, no eviction churn.
      server.update_params(_fresh(_PARAMS_B), version=1)
      snap = server.stats()
      assert snap['live_version'] == 1
      assert snap['version_flips'] == before['version_flips'] + 1
      assert snap['params_version'] == before['params_version'] + 1
      assert snap['resident_versions'] == before['resident_versions']
    finally:
      server.close()

  def test_dedup_sentinel_is_process_memory_across_restore(self):
    """The documented restore caveat (update_params docstring): the
    version table — and with it the same-version dedup — is process
    memory BY DESIGN. A restarted learner restoring to step N and
    re-publishing version N must PUBLISH (copy: donation safety),
    not dedup against a table it no longer has."""
    server = _server()
    try:
      server.update_params(_fresh(), version=7)
      assert server.stats()['publishes_skipped'] == 0
    finally:
      server.close()
    restored = _server()  # the restarted process
    try:
      restored.update_params(_fresh(_PARAMS_B), version=7)
      snap = restored.stats()
      assert snap['publishes_skipped'] == 0   # NOT deduped
      assert snap['params_version'] == 1
      assert snap['live_version'] == 7
    finally:
      restored.close()

  def test_concurrent_update_params_vs_stats(self):
    server = _server(serving_resident_versions=3)
    errors = []
    stop = threading.Event()

    def publisher(base):
      try:
        for k in range(10):
          server.update_params(_fresh(), version=base + k)
      except Exception as e:  # pragma: no cover - the assertion
        errors.append(e)

    def reader():
      try:
        while not stop.is_set():
          server.stats()
          server.resident_versions()
      except Exception as e:  # pragma: no cover - the assertion
        errors.append(e)

    try:
      pubs = [threading.Thread(target=publisher, args=(100 * i,))
              for i in range(4)]
      readers = [threading.Thread(target=reader) for _ in range(2)]
      for t in pubs + readers:
        t.start()
      for t in pubs:
        t.join(timeout=60)
      stop.set()
      for t in readers:
        t.join(timeout=10)
      assert not errors
      # Every version was distinct: no dedup, 40 real publishes.
      assert server.stats()['params_version'] == 40
      assert server.stats()['resident_versions'] <= 3
    finally:
      stop.set()
      server.close()


class TestServingTraffic:

  def test_serve_counts_and_ab_assignment(self):
    server = _server(serving_resident_versions=3,
                     serving_ab_fraction=0.5)
    try:
      server.update_params(_fresh(), version=1)
      server.update_params(_fresh(), version=2)
      pay = _payload(server)
      seen = {server.serve_remote(pay)['version'] for _ in range(8)}
      snap = server.stats()
      counts = snap['serve_counts']
      assert sum(counts.values()) == 8
      # A/B fraction 0.5: every other call serves the candidate (the
      # newest non-live version) — both versions MUST have served.
      assert seen == {1, 2}
      assert counts['1'] == 4 and counts['2'] == 4
      assert snap['ab_calls'] == 4
      # Per-version serve counters ride resident_versions() too.
      by_label = {label: serves for label, serves, _, _
                  in server.resident_versions()}
      assert by_label[1] == 4 and by_label[2] == 4
    finally:
      server.close()


class TestShadowAndAot:

  def _drive(self, server, n):
    frame = np.random.RandomState(3).randint(
        0, 255, (H, W, 3)).astype(np.uint8)
    instr = np.zeros((MAX_INSTRUCTION_LEN,), np.int32)
    state = server.initial_core_state()
    prev = np.int32(0)
    for _ in range(n):
      env_out = StepOutput(
          reward=np.float32(0.0),
          info=StepOutputInfo(np.float32(0), np.int32(0)),
          done=np.bool_(False),
          observation=(frame, instr))
      out, state = server.policy(prev, env_out, state)
      prev = np.int32(out.action)

  def _wait_shadow(self, server, count, timeout=10.0):
    # Shadow scoring runs on the completion thread AFTER the parked
    # callers are answered (the gauge must never add device_get
    # latency to the live path), so the tally can trail the last
    # returned policy() call — bounded poll.
    deadline = time.monotonic() + timeout
    while (server.stats()['shadow_calls'] < count
           and time.monotonic() < deadline):
      time.sleep(0.01)
    assert server.stats()['shadow_calls'] >= count

  def test_shadow_divergence_zero_then_positive(self):
    server = _server(serving_resident_versions=3,
                     serving_shadow_fraction=1.0)
    try:
      server.update_params(_fresh(), version=1)
      server.update_params(_fresh(), version=2)  # shadow = v1, equal
      self._drive(server, 8)
      self._wait_shadow(server, 8)
      assert server.stats()['shadow_divergence'] == 0.0
      # A genuinely different network as live; shadow (v2) now
      # disagrees on argmax for a fraction of real traffic.
      server.update_params(_fresh(_PARAMS_B), version=3)
      self._drive(server, 8)
      self._wait_shadow(server, 16)
      assert server.stats()['shadow_divergence'] > 0.0
    finally:
      server.close()

  def test_a_live_shadow_sends_a_groups_call_through_the_batcher(self):
    """PR 39: shadow scoring follows the callers' answer, on the
    completion thread. While a shadow version is live a group's call
    that fills the merge floor rides the batcher and is scored; with
    none it is answered on its caller's thread."""
    server = _server(serving_resident_versions=2)
    try:
      _group_traffic(server, 2, steps=2)
      assert server.stats()['inline_calls'] == 2
      server.update_params(_fresh(), version=1)  # the seed: the shadow
      server.set_shadow(None, 1.0)
      _group_traffic(server, 2, steps=3)
      self._wait_shadow(server, 3)
      stats = server.stats()
      assert (stats['calls'], stats['inline_calls']) == (5, 2)
      server.set_shadow(None, 0.0)
      _group_traffic(server, 2, steps=1)
      stats = server.stats()
      assert (stats['calls'], stats['inline_calls']) == (6, 3)
      assert stats['shadow_calls'] == 3
    finally:
      server.close()

  @pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
  def test_aot_flip_serves_without_recompile(self):
    # int8-resident publishes change the params leaf DTYPES — without
    # AOT the first post-flip serve pays a full retrace. serving_aot
    # pre-compiles at publish (off the serve path): zero aot misses.
    server = _server(publish_codec='int8', serving_aot=True)
    try:
      server.warmup(OBS, sizes=[1])
      server.update_params(_fresh(), version=1)
      self._drive(server, 3)
      server.update_params(_fresh(), version=2)
      self._drive(server, 3)
      snap = server.stats()
      assert snap['aot_misses'] == 0
      assert snap['aot_compiled'] >= 1
    finally:
      server.close()


def _record_calls(server):
  """Wraps the server's step: of every merged call, what it was given
  (key, arena, the ONE buffer as it lay on the host, its layout) and
  what it gave back, all as host copies (the arena is donated)."""
  real, calls = server._step, []
  host = lambda tree: jax.tree_util.tree_map(np.array, tree)  # noqa: E731

  def recording_step(params, key, *rest):
    *arena, packed, layout = rest
    assert isinstance(packed, np.ndarray) and packed.ndim == 1
    call = {'params': params, 'key': host(key), 'arena': host(arena),
            'packed': packed.copy(), 'layout': layout}
    outs = real(params, key, *rest)
    call['outs'] = host(outs)
    calls.append(call)
    return outs

  server._step = recording_step
  return calls


def _rows_step(agent, state_cache):
  """The per-array body a merged call computed before PR 36 (and
  `serve_remote` still does), written out: one argument an input, one
  result an output."""

  def step(params, key, *rest):
    rest = list(rest)
    arena = rest.pop(0) if state_cache else None
    slots = rest.pop(0) if state_cache else None
    prev_action, reward, done, frame, instr, *carry = rest
    key, sub = jax.random.split(key)
    env_output = StepOutput(reward=reward[None], info=None,
                            done=done[None],
                            observation=(frame[None], instr[None]))
    if state_cache:
      out, arena = agent.apply(params, prev_action[None], env_output,
                               arena, sample_rng=sub, state_slots=slots)
      return key, arena, out.action[0], out.policy_logits[0], out.baseline[0]
    out, carry = agent.apply(params, prev_action[None], env_output,
                             tuple(carry), sample_rng=sub)
    return (key, out.action[0], out.policy_logits[0], out.baseline[0],
            *carry)

  return jax.jit(step)


def _group_traffic(server, k, steps=3, seed=0):
  """One caller, `k` rows a request (scalar form for k = 1)."""
  rng = np.random.RandomState(seed)
  grouped = k > 1
  shape = (k,) if grouped else ()
  if server.stats()['state_cache']:
    state = [server.initial_core_state() for _ in range(k)]
    state = state if grouped else state[0]
  else:
    state = jax.tree_util.tree_map(
        lambda l: np.repeat(l, k, axis=0), server.initial_core_state())
  prev = np.zeros(shape, np.int32)
  for t in range(steps):
    env_output = StepOutput(
        reward=np.asarray(rng.standard_normal(shape), np.float32),
        info=StepOutputInfo(np.float32(0), np.int32(0)),
        done=np.asarray(rng.random_sample(shape) < 0.3),
        observation=(
            rng.randint(0, 255, shape + (H, W, 3)).astype(np.uint8),
            rng.randint(0, 9, shape + (MAX_INSTRUCTION_LEN,)).astype(
                np.int32)))
    out, state = server.policy(prev, env_output, state)
    prev = np.asarray(out.action, np.int32)


def _merged_traffic(server, callers=3, steps=2):
  """`callers` threads of one row each, in step: one merge a step."""
  barrier = threading.Barrier(callers)
  errors = []

  def run(i):
    try:
      barrier.wait(timeout=60)
      _group_traffic(server, 1, steps=steps, seed=10 + i)
    except Exception as e:  # noqa: BLE001 — reported below
      errors.append(e)

  threads = [threading.Thread(target=run, args=(i,))
             for i in range(callers)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=120)
  assert not errors, errors


class TestPackedCall:
  """PR 36: a merged call's inputs cross as ONE buffer and its outputs
  come back as ONE, unpacked and packed inside the jitted step."""

  @pytest.mark.parametrize('traffic,rows,padded', [
      ('one_row', 1, 1), ('group_of_3', 3, 4), ('merge_of_3', 3, 4)])
  @pytest.mark.parametrize('state_cache', [False, True],
                           ids=['carry', 'cache'])
  def test_packed_step_is_the_per_array_body_bit_for_bit(
      self, state_cache, traffic, rows, padded):
    """Every merged call's packed outputs (padded rows included), its
    new key and, with the state cache, its new arena against the
    per-array body on the same key and the same staged arrays."""
    cfg = Config(inference_min_batch=3 if traffic == 'merge_of_3' else 1,
                 inference_max_batch=8, inference_timeout_ms=2000,
                 inference_state_cache=state_cache,
                 inference_state_slots=4)
    server = InferenceServer(_AGENT, _PARAMS, cfg, seed=7)
    calls = _record_calls(server)
    try:
      if traffic == 'merge_of_3':
        _merged_traffic(server)
      else:
        _group_traffic(server, rows)
      stats = server.stats()
    finally:
      server.close()
    assert calls and stats['mean_batch'] == rows
    reference = _rows_step(_AGENT, state_cache)
    for call in calls:
      layout = call['layout']
      assert all(shape[0] == padded for _, shape in layout.specs)
      staged = packing.host_views(call['packed'], layout)
      want = reference(call['params'], call['key'], *call['arena'],
                       *staged)
      *chained, packed_out = call['outs']
      got = chained + packing.host_views(
          packed_out, server._out_layouts[layout])
      want = jax.tree_util.tree_leaves(want)
      got = jax.tree_util.tree_leaves(got)
      assert len(got) == len(want)
      for g, w in zip(got, want):
        w = np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)

  def test_staging_views_are_one_buffer_and_a_ring(self):
    """The per-input arrays the batcher writes through are views of
    ONE flat buffer a ring position, at offsets aligned to 128 B; with
    completions held, at most depth + 1 positions are handed out, all
    distinct: none is reused before its call completes."""
    cfg = Config(inference_min_batch=1, inference_max_batch=8,
                 inference_timeout_ms=5, inference_pipeline_depth=2)
    # One bucket whatever merges: one ring.
    server = InferenceServer(_AGENT, _PARAMS, cfg, seed=7, pad_batch_to=4)
    handed, release = [], threading.Event()
    real_staging, real_outputs = (server._staging_for,
                                  server._batcher.set_outputs)

    def staging_for(total_rows):
      staging = real_staging(total_rows)
      handed.append(staging)
      return staging

    def held_outputs(batch_id, arrays):
      release.wait(timeout=60)
      return real_outputs(batch_id, arrays)

    server._staging_for = staging_for
    try:
      _group_traffic(server, 1, steps=1)
      staging = handed[0]
      assert staging.words.dtype == np.uint32 and staging.words.ndim == 1
      raw = staging.words.view(np.uint8)
      for view, (offset, nbytes) in zip(staging, staging.layout.regions[0]):
        assert np.shares_memory(view, staging.words)
        assert offset % packing.ALIGN == 0 and view.nbytes == nbytes
        assert (view.ctypes.data - raw.ctypes.data) == offset
      staging[3][...] = 7  # the frame: written through to the buffer
      offset, nbytes = staging.layout.regions[0][3]
      assert (raw[offset:offset + nbytes] == 7).all()
      # Hold every completion: the semaphore stops the third dispatch,
      # whose batch is already staged in the ring's last position.
      del handed[:]
      server._batcher.set_outputs = held_outputs
      callers = [threading.Thread(target=_group_traffic,
                                  args=(server, 1), kwargs={'steps': 1})
                 for _ in range(5)]
      for t in callers:
        t.start()
        time.sleep(0.2)  # one merge a caller
      time.sleep(0.5)
      in_flight = list(handed)
      assert len(in_flight) == cfg.inference_pipeline_depth + 1
      assert len({id(s.words) for s in in_flight}) == len(in_flight)
      release.set()
      for t in callers:
        t.join(timeout=60)
      assert not any(t.is_alive() for t in callers)
      # The callers still queued were served from the same positions.
      assert len(handed) > len(in_flight)
      assert len({id(s.words) for s in handed}) == len(in_flight)
    finally:
      release.set()
      server.close()

  @pytest.mark.parametrize('state_cache', [False, True],
                           ids=['carry', 'cache'])
  def test_one_buffer_crosses_each_way(self, state_cache):
    """The counters that say the mechanism engages are observed where
    the arrays cross: numpy leaves handed to the step, arrays fetched
    from it."""
    cfg = Config(inference_min_batch=1, inference_max_batch=8,
                 inference_timeout_ms=5,
                 inference_state_cache=state_cache)
    server = InferenceServer(_AGENT, _PARAMS, cfg, seed=7)
    try:
      assert server.stats()['h2d_buffers_per_call'] == 0.0
      server.warmup(OBS, sizes=[1])
      _group_traffic(server, 1, steps=4)
      _group_traffic(server, 3, steps=4)
      stats = server.stats()
    finally:
      server.close()
    assert stats['calls'] == 8
    assert stats['h2d_buffers_per_call'] == 1.0
    assert stats['d2h_buffers_per_call'] == 1.0
    # Two columns of the calls' cycle record (the warm-up call is no
    # merged call and writes no row).
    totals = server._cycles.totals()
    assert (totals['cycles'], totals['h2d'], totals['d2h']) == (8, 8, 8)


class TestCallCycleRecord:
  """PR 37: every merged call leaves a row of five stamps in the
  server's always-on record, THE source of its latency percentiles."""

  @pytest.mark.parametrize('state_cache', [False, True],
                           ids=['carry', 'cache'])
  def test_four_phases_close_the_call_and_the_loop(self, state_cache):
    from scalable_agent_tpu.runtime import inference
    cfg = Config(inference_min_batch=1, inference_max_batch=8,
                 inference_timeout_ms=5, inference_pipeline_depth=1,
                 inference_state_cache=state_cache)
    server = InferenceServer(_AGENT, _PARAMS, cfg, seed=7)
    try:
      first = server.stats()
      # Every key a metric reads is there before the first call.
      for phase in inference._CALL_PHASES:
        assert first[f'call_{phase}_ms'] == 0.0
      assert first['call_excess_ms'] == first['batcher_wait_ms'] == 0.0
      assert first['call_excess_ms_in_learner/publish'] == 0.0
      assert first['call_excess_ms_unnamed'] == 0.0
      assert first['latency_p95_ms'] == 0.0
      def settled():
        # A call's row is written after its callers are unparked.
        stats = server.stats()
        while server._cycles.cycles < stats['calls']:
          time.sleep(0.001)
        return server.stats()

      server.warmup(OBS, sizes=[3])
      _group_traffic(server, 3, steps=5)  # whatever the first calls pay
      opened, t0 = settled(), time.perf_counter()
      _group_traffic(server, 3, steps=200, seed=1)  # a closed loop
      wall_ms = (time.perf_counter() - t0) * 1e3
      closed = settled()
      _, rows = server._cycles.held()
    finally:
      server.close()
    assert closed['calls'] - opened['calls'] == 200
    stamps = rows[:, :5]
    assert (np.diff(stamps, axis=1) >= 0).all()  # no phase is negative
    # The three phases after `wait_batch` ARE the latency, call by call
    # and in the sums; and the percentiles are the parent's arithmetic
    # (`percentile_ms` of the ascending last 512) on these stamps.
    lat_ms = (stamps[:, 4] - stamps[:, 1]) / 1e6
    delta = lambda key: closed[key] - opened[key]  # noqa: E731
    served = sum(delta(f'call_{phase}_ms')
                 for phase in inference._CALL_PHASES[1:])
    assert served == pytest.approx(lat_ms[-200:].sum(), rel=1e-9)
    for q, key in [(0.5, 'latency_p50_ms'), (0.95, 'latency_p95_ms'),
                   (0.99, 'latency_p99_ms')]:
      assert closed[key] == round(inference.percentile_ms(
          sorted(lat_ms.tolist()), q), 3)
    # At depth 1 with one caller a call's wait begins where the call
    # before was unparked (or where its own batch was in hand, if the
    # caller came back before that stamp was taken): rows abut, and
    # phases + wait_batch are the loop's wall time.
    assert (stamps[1:, 0] >= np.minimum(stamps[:-1, 4],
                                        stamps[1:, 1])).all()
    assert served + delta('call_wait_batch_ms') == pytest.approx(
        wall_ms, rel=0.05)
    # What the caller sees of it, in `policy`'s park, lies between.
    assert served < delta('batcher_wait_ms') < wall_ms
    assert closed['call_excess_ms'] >= closed['call_excess_ms_unnamed'] >= 0
    assert closed['call_cycles_lost'] == 0


class _FakeChannel:

  def __init__(self, name, fail=False, draining=False):
    self.name = name
    self.fail = fail
    self.draining = draining
    self.closed = False

  def supports_infer(self):
    return True

  def remote_infer(self, payload):
    if self.fail:
      raise ConnectionError(f'{self.name} down')
    return {'who': self.name}, {'draining': self.draining}

  def close(self):
    self.closed = True


class TestServingRouter:

  def test_round_robin_interleaves_equal_replicas(self):
    chans = {'a': _FakeChannel('a'), 'b': _FakeChannel('b')}
    router = routing.ServingRouter(['a', 'b'], lambda a: chans[a])
    seen = [router.infer({})[0]['who'] for _ in range(6)]
    assert seen == ['a', 'b', 'a', 'b', 'a', 'b']

  def test_failover_marks_down_and_probation_expires(self):
    t = [0.0]
    chans = {'a': _FakeChannel('a', fail=True), 'b': _FakeChannel('b')}
    router = routing.ServingRouter(['a', 'b'], lambda a: chans[a],
                                   probation_secs=5.0,
                                   clock=lambda: t[0])
    # The failed pick costs one failover, lands on the survivor.
    assert router.infer({})[0]['who'] == 'b'
    assert router.stats()['route_failovers'] == 1
    # Inside probation: every pick avoids the corpse.
    assert {router.infer({})[0]['who'] for _ in range(4)} == {'b'}
    # Probation over + replica healthy again: back in rotation.
    chans['a'].fail = False
    t[0] = 6.0
    assert 'a' in {router.infer({})[0]['who'] for _ in range(4)}

  def test_poisoned_ewma_never_exiles_a_replica(self):
    # The measured storm failure: one replica's warm-up reply ate the
    # ~470ms first-call compile, its inverse-latency weight collapsed
    # to ~0.002 vs ~0.4, and at ~1/180 of the picks its EWMA never
    # saw enough traffic to recover. The pick floors every weight at
    # 1/_MAX_SPREAD of the fastest: the slow replica keeps ~1/11 of
    # the share and re-earns its weight in a handful of replies.
    chans = {'a': _FakeChannel('a'), 'b': _FakeChannel('b')}
    router = routing.ServingRouter(['a', 'b'], lambda a: chans[a])
    with router._lock:
      router._replicas['a'].ewma_ms = 470.0
      router._replicas['a'].weight = 1.0 / 470.0
      router._replicas['b'].ewma_ms = 2.5
      router._replicas['b'].weight = 1.0 / 2.5
    picks = [router.infer({})[0]['who'] for _ in range(44)]
    assert picks.count('a') >= 3

  def test_all_down_raises_no_replicas(self):
    chans = {'a': _FakeChannel('a', fail=True)}
    router = routing.ServingRouter(['a'], lambda a: chans[a])
    with pytest.raises(routing.NoReplicasAvailable):
      router.infer({})

  def test_draining_notice_drains_share(self):
    chans = {'a': _FakeChannel('a', draining=True),
             'b': _FakeChannel('b')}
    router = routing.ServingRouter(['a', 'b'], lambda a: chans[a])
    # The draining reply is still a VALID result — drain is advisory.
    results = [router.infer({})[0]['who'] for _ in range(6)]
    assert results[0] == 'a'
    # But after the notice, no NEW picks land on the drainer.
    assert set(results[1:]) == {'b'}
    by_addr = {r['address']: r for r in router.stats()['replicas']}
    assert by_addr['a']['draining']

  def test_membership_events_reshape_the_pool(self):
    chans = {'a': _FakeChannel('a'), 'b': _FakeChannel('b')}
    router = routing.ServingRouter(['a'], lambda a: chans[a])
    router.apply_membership([{'kind': 'host_joined', 'host': 'b'}])
    assert {router.infer({})[0]['who'] for _ in range(4)} == {'a', 'b'}
    router.apply_membership([{'kind': 'host_left', 'host': 'a'}])
    assert {router.infer({})[0]['who'] for _ in range(4)} == {'b'}
    assert router.stats()['available'] == 1


def _decode_blob(segments):
  """Decode one cached param-blob OOB frame back to the tuple the
  client sees (kind, version, tree, info...) — the inverse of
  remote._oob_frame_segments, for asserting blob KINDS per protocol."""
  head = memoryview(segments[0])
  off = remote._LEN.size + 1
  nraws, sklen = remote._OOB_META.unpack_from(head, off)
  off += remote._OOB_META.size
  skeleton = bytes(head[off:off + sklen])
  return pickle.loads(skeleton,
                      buffers=[memoryview(r) for r in segments[1:]])


class TestWireV10:

  def _setup(self, wire_dtype):
    cfg = Config(env_backend='bandit', unroll_length=2, height=4,
                 width=6, torso='shallow', use_instruction=False,
                 num_actions=A)
    agent = ImpalaAgent(num_actions=A, torso='shallow',
                        use_instruction=False)
    contract = remote.trajectory_contract(cfg, agent, A)
    buffer = ring_buffer.TrajectoryBuffer(2)
    rng = np.random.RandomState(0)
    params = {'w': rng.randn(64, 8).astype(np.float32),
              'b': np.zeros((8,), np.float32)}
    server = remote.TrajectoryIngestServer(
        buffer, params, host='127.0.0.1', contract=contract,
        wire_dtype=wire_dtype)
    return buffer, params, server, contract

  def test_int8_blob_roundtrips_and_old_peer_gets_compat(self):
    buffer, params, server, contract = self._setup('int8')
    client = None
    try:
      client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                        connect_timeout_secs=10)
      client.handshake(contract)
      version, tree = client.fetch_params()
      assert version == 1
      # The v10 lane ships 'params_int8'; the client dequantizes —
      # exactly the quantize→dequantize round-trip of the original.
      expect = codec.dequantize_np(codec.quantize_np(params))
      np.testing.assert_array_equal(tree['w'], expect['w'])
      # One pickle per VERSION even though int8 publishes build the
      # compat blob too (the serializations test-hook contract).
      assert server.serializations == 1
      server.publish_params(params)
      assert server.serializations == 2
      # Per-subscriber negotiation: a v9 peer is served the bf16
      # compat blob, a v10 peer the int8 blob.
      lane_blob_fn = server._param_lane._blob_fn
      old_segments, _ = lane_blob_fn(9)
      new_segments, _ = lane_blob_fn(10)
      assert _decode_blob(old_segments)[0] == 'params_bf16'
      assert _decode_blob(new_segments)[0] == 'params_int8'
    finally:
      if client is not None:
        client.close()
      server.close()
      buffer.close()

  def test_infer_requires_attach_then_serves_with_drain_notice(self):
    buffer, params, server, contract = self._setup(None)
    client = None
    try:
      client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                        connect_timeout_secs=10)
      client.handshake(contract)
      assert client.supports_infer()
      with pytest.raises(RuntimeError, match='serving not attached'):
        client.remote_infer({'x': np.ones((2,), np.float32)})
      server.attach_serving(
          lambda payload: {'echo': payload['x'] + 1})
      result, notice = client.remote_infer(
          {'x': np.ones((2,), np.float32)})
      np.testing.assert_array_equal(result['echo'],
                                    np.full((2,), 2.0, np.float32))
      assert not notice.get('draining')
      server.set_draining()
      _, notice = client.remote_infer(
          {'x': np.ones((2,), np.float32)})
      assert notice.get('draining')
    finally:
      if client is not None:
        client.close()
      server.close()
      buffer.close()


@pytest.mark.slow
def test_routed_storm_smoke(tmp_path):
  """The 3-process drill end to end: two real serving replicas, a
  SIGKILL mid-pump, the router fails over with zero starvation and a
  green routed-latency verdict (scripts/chaos.py owns the harness —
  the CI serving lane runs the same storm)."""
  from scripts import chaos
  results, errors = chaos.run_routed_storm(str(tmp_path), smoke=True)
  assert errors == [], (errors, results)
  assert results['served']['post_kill'] > 0

"""Remote-actor tests (SURVEY §3.4 / VERDICT r1 Missing #2).

The reference's distributed actor topology — dedicated actor machines
streaming unrolls into the learner-hosted queue over gRPC — is tested
here at protocol level (in-process server+client) and end-to-end: a
SEPARATE OS process with no accelerator (cpu-forced jax) feeds a real
training learner through the TCP ingest path. Upstream never tests its
distributed mode at all (SURVEY §4).
"""

import socket
import threading
import time

import numpy as np
import pytest

from scalable_agent_tpu.runtime import remote, ring_buffer
from scalable_agent_tpu.structs import (
    ActorOutput, AgentOutput, StepOutput, StepOutputInfo)


def _tiny_unroll(seed=0, t1=3, num_actions=3):
  rng = np.random.RandomState(seed)
  return ActorOutput(
      level_name=np.int32(0),
      agent_state=(np.zeros((1, 4), np.float32),
                   np.ones((1, 4), np.float32)),
      env_outputs=StepOutput(
          reward=rng.randn(t1).astype(np.float32),
          info=StepOutputInfo(np.zeros(t1, np.float32),
                              np.zeros(t1, np.int32)),
          done=np.zeros(t1, bool),
          observation=(
              rng.randint(0, 255, (t1, 4, 6, 3)).astype(np.uint8),
              np.zeros((t1, 5), np.int32))),
      agent_outputs=AgentOutput(
          action=rng.randint(0, num_actions, t1).astype(np.int32),
          policy_logits=rng.randn(t1, num_actions).astype(np.float32),
          baseline=rng.randn(t1).astype(np.float32)))


def _assert_trees_equal(a, b):
  import jax
  la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
  assert len(la) == len(lb)
  for x, y in zip(la, lb):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _contract_setup(num_actions=3, **overrides):
  """A (config, agent, contract) triple for handshake tests."""
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent
  cfg = Config(env_backend='bandit', unroll_length=2, height=4,
               width=6, torso='shallow', use_instruction=False,
               num_actions=num_actions, **overrides)
  agent = ImpalaAgent(num_actions=num_actions, torso='shallow',
                      use_instruction=False)
  return cfg, agent, remote.trajectory_contract(cfg, agent,
                                                num_actions)


def _conforming_unroll(cfg, agent, num_actions, seed=0):
  """An unroll matching `trajectory_contract(cfg, agent, ...)` — the
  one canonical constructor, so tests can't drift from the bench."""
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.testing import make_example_unroll
  return make_example_unroll(cfg.unroll_length + 1, cfg.height,
                             cfg.width, num_actions,
                             MAX_INSTRUCTION_LEN, seed=seed,
                             hidden_size=agent.hidden_size)


def test_oob_frame_roundtrip():
  """VERDICT r3 #6b: unrolls ship as a pickle-5 skeleton + raw
  out-of-band buffers (the 2.11 MB frame stack must not be copied
  through the pickler). Round trip is bit-exact, interleaves with
  plain frames on one socket, and handles zero-size arrays."""
  a, b = socket.socketpair()
  try:
    unroll = _tiny_unroll(3)
    remote._send_oob(a, ('unroll', unroll))
    kind, got = remote._recv_msg(b)
    assert kind == 'unroll'
    _assert_trees_equal(got, unroll)

    # Plain and OOB frames interleave on the same connection.
    remote._send_msg(a, ('ack', 7))
    assert remote._recv_msg(b) == ('ack', 7)
    weird = {'empty': np.zeros((0, 4), np.float32),
             'scalar': np.float64(1.5),
             'text': 'plain python rides in the skeleton'}
    remote._send_oob(a, weird)
    got = remote._recv_msg(b)
    assert got['empty'].shape == (0, 4)
    assert got['scalar'] == 1.5
    assert got['text'] == weird['text']
  finally:
    a.close()
    b.close()


def test_version_skewed_peer_dropped_cleanly():
  """A pre-v4 peer sends UNTAGGED pickle frames (first byte = pickle
  opcode 0x80 = 'frame kind 128'). The server must drop just that
  connection with a logged protocol error — not crash the handler
  thread — and keep serving healthy clients; the client side must
  surface a terminal ProtocolError instead of burning its reconnect
  window."""
  import pickle
  import pytest

  buffer = ring_buffer.TrajectoryBuffer(2)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(1)},
                                         host='127.0.0.1')
  try:
    legacy = socket.create_connection(('127.0.0.1', server.port))
    legacy.settimeout(10)
    payload = pickle.dumps(('hello', None),
                           protocol=pickle.HIGHEST_PROTOCOL)
    legacy.sendall(remote._LEN.pack(len(payload)) + payload)  # no tag
    # Server closed OUR conn, not itself. A clean FIN (b'') or an RST
    # (ECONNRESET — the v5 reader aborts on the bogus tag byte with
    # the rest of the frame unread) both prove the drop; the server's
    # own survival is asserted via the healthy client below.
    try:
      assert legacy.recv(1) == b''
    except ConnectionResetError:
      pass
    legacy.close()

    healthy = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                       connect_timeout_secs=10)
    try:
      assert healthy.fetch_params()[0] == 1  # server survived
    finally:
      healthy.close()
  finally:
    server.close()
    buffer.close()

  # Client side: an untagged (pre-v4 style) reply raises ProtocolError.
  # The v5 client fetches over a SECOND (param lane) connection, so
  # the fake legacy peer accepts both and answers the fetch untagged.
  with socket.create_server(('127.0.0.1', 0)) as srv:
    port = srv.getsockname()[1]

    def serve_legacy():
      main_conn, _ = srv.accept()       # the trajectory connection
      param_conn, _ = srv.accept()      # the client's param lane
      remote._recv_msg(param_conn)      # tagged 'hello_params' parses
      remote._recv_msg(param_conn)      # tagged 'get_params' parses
      reply = pickle.dumps(('params', 1, {}),
                           protocol=pickle.HIGHEST_PROTOCOL)
      param_conn.sendall(remote._LEN.pack(len(reply)) + reply)  # no tag
      param_conn.recv(1)
      param_conn.close()
      main_conn.close()

    t = threading.Thread(target=serve_legacy, daemon=True)
    t.start()
    client = remote.RemoteActorClient(f'127.0.0.1:{port}',
                                      connect_timeout_secs=10)
    try:
      import pytest
      with pytest.raises(remote.ProtocolError, match='version'):
        client.fetch_params()
    finally:
      client.close()
      t.join(timeout=5)


def test_handshake_rejects_skewed_config():
  """VERDICT r2 Missing #2: an actor host running a skewed config is
  rejected AT CONNECT with an error naming the offending fields —
  not accepted into the buffer to fail far away later."""
  import dataclasses
  cfg, agent, learner_contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      contract=learner_contract)
  try:
    skewed_cfg = dataclasses.replace(cfg, height=8,
                                     num_action_repeats=2)
    skewed = remote.trajectory_contract(skewed_cfg, agent, 3)
    client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
    try:
      import pytest
      with pytest.raises(remote.ContractMismatch) as exc_info:
        client.handshake(skewed)
      msg = str(exc_info.value)
      # Both the semantic knob and the shape-bearing field are named.
      assert 'config.height' in msg
      assert 'config.num_action_repeats' in msg
      assert 'learner=4' in msg and 'actor=8' in msg
    finally:
      client.close()
    assert len(buffer) == 0
  finally:
    server.close()
    buffer.close()


def test_unroll_validation_guards_the_buffer():
  """Per-unroll leaf validation: a malformed unroll is rejected with a
  path-naming error and never reaches the buffer; the connection and
  subsequent valid unrolls survive."""
  import dataclasses
  import pytest
  cfg, agent, contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    version, _ = client.handshake(contract)
    assert version == 1

    good = _conforming_unroll(cfg, agent, 3, seed=1)
    assert client.send_unroll(good) == 1
    assert len(buffer) == 1

    # Wrong frame shape (an actor host whose --height drifted after
    # the handshake, or a corrupt frame): named leaf, no buffer entry.
    bad = good._replace(env_outputs=good.env_outputs._replace(
        observation=(np.zeros((3, 8, 6, 3), np.uint8),
                     good.env_outputs.observation[1])))
    with pytest.raises(RuntimeError, match='observation'):
      client.send_unroll(bad)
    assert len(buffer) == 1
    assert server.stats()['rejected'] == 1

    # Out-of-range actions (would previously blow up the learner's
    # bincount stats path with a shape error pointing nowhere).
    bad_actions = good._replace(agent_outputs=good.agent_outputs._replace(
        action=np.array([0, 1, 7], np.int32)))
    with pytest.raises(RuntimeError, match='out of range'):
      client.send_unroll(bad_actions)
    assert len(buffer) == 1

    # The connection survived both rejections.
    assert client.send_unroll(
        _conforming_unroll(cfg, agent, 3, seed=2)) == 1
    assert len(buffer) == 2
    assert server.stats()['unrolls'] == 2
  finally:
    client.close()
    server.close()
    buffer.close()


def test_out_of_range_level_id_rejected():
  """ADVICE r3 (medium): a remote host past the handshake must not be
  able to ship an out-of-range level id — positive overflow crashes
  the learner's EpisodeStats record with IndexError, and NEGATIVE ids
  silently alias another level's episode stats and PopArt per-task
  statistics. Both directions are rejected at the wire."""
  import pytest
  cfg, agent, contract = _contract_setup()
  assert contract['fields']['num_levels'] == 1  # bandit: single level
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake(contract)
    good = _conforming_unroll(cfg, agent, 3, seed=1)

    overflow = good._replace(level_name=np.int32(5))
    with pytest.raises(RuntimeError, match='level_name 5 out of range'):
      client.send_unroll(overflow)
    aliasing = good._replace(level_name=np.int32(-1))
    with pytest.raises(RuntimeError, match='level_name -1 out of'):
      client.send_unroll(aliasing)
    assert len(buffer) == 0
    assert server.stats()['rejected'] == 2

    assert client.send_unroll(good) == 1  # in-range still lands
    assert len(buffer) == 1
  finally:
    client.close()
    server.close()
    buffer.close()


def test_fast_validator_matches_slow_path():
  """VERDICT r3 W4: the precompiled fast-path validator must agree
  with `unroll_violations` on both clean and malformed unrolls — and a
  legacy contract without signature_tree must still validate (via the
  slow path)."""
  cfg, agent, contract = _contract_setup()
  validator = remote.FastUnrollValidator(contract)
  assert validator._fast is not None  # fast path engaged

  good = _conforming_unroll(cfg, agent, 3, seed=3)
  cases = [
      good,
      # Wrong dtype on one leaf.
      good._replace(agent_outputs=good.agent_outputs._replace(
          baseline=good.agent_outputs.baseline.astype(np.float64))),
      # Wrong shape on the frame stack.
      good._replace(env_outputs=good.env_outputs._replace(
          observation=(np.zeros((3, 8, 6, 3), np.uint8),
                       good.env_outputs.observation[1]))),
      # Structure mismatch (missing agent_state half).
      good._replace(agent_state=good.agent_state[0]),
      # Value violations on a structurally clean unroll.
      good._replace(agent_outputs=good.agent_outputs._replace(
          action=np.array([0, 1, 9], np.int32))),
      good._replace(level_name=np.int32(3)),
      # Not a trajectory at all.
      'garbage',
  ]
  for case in cases:
    fast = validator(case)
    slow = remote.unroll_violations(case, contract)
    assert fast == slow, (fast, slow)
  assert validator(good) == []
  assert validator(cases[-2]) != []

  # The clean case must actually take the fast path — if the treedef
  # comparison silently stopped matching, every unroll would fall back
  # to the keystr diff and the measured ~12% would quietly return.
  from unittest import mock
  with mock.patch.object(
      remote, 'unroll_violations',
      side_effect=AssertionError('slow path taken for a clean unroll')):
    assert validator(good) == []

  legacy = {k: v for k, v in contract.items() if k != 'signature_tree'}
  legacy_validator = remote.FastUnrollValidator(legacy)
  assert legacy_validator._fast is None
  assert legacy_validator(good) == []
  assert legacy_validator(cases[2]) != []


def test_bf16_wire_dtype_halves_blob_and_upcasts():
  """The measured egress lever (docs/PERF.md): wire_dtype='bfloat16'
  ships float32 leaves as bf16 (≈half the bytes) and the client
  upcasts transparently — callers always see float32 trees; non-float
  leaves ride through untouched bit-exact."""
  import pickle
  buffer = ring_buffer.TrajectoryBuffer(4)
  params = {'w': np.arange(4096, dtype=np.float32) / 7.0,
            'steps': np.int64(123),
            'mask': np.array([True, False])}
  server = remote.TrajectoryIngestServer(buffer, params,
                                         host='127.0.0.1',
                                         wire_dtype='bfloat16')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    version, got = client.fetch_params()
    assert version == 1
    assert got['w'].dtype == np.float32
    # bf16 keeps ~3 decimal digits; the cast is the only error source.
    np.testing.assert_allclose(got['w'], params['w'], rtol=1e-2)
    assert got['steps'] == 123 and got['steps'].dtype == np.int64
    np.testing.assert_array_equal(got['mask'], params['mask'])

    exact_blob = pickle.dumps(('params', 1, params),
                              protocol=pickle.HIGHEST_PROTOCOL)
    assert server.snapshot_nbytes() < 0.65 * len(exact_blob)

    # Version bumps keep working through the cast path.
    assert server.publish_params({'w': np.full(8, 2.5, np.float32),
                                  'steps': np.int64(124),
                                  'mask': params['mask']}) == 2
    version, got = client.fetch_params()
    assert version == 2
    np.testing.assert_allclose(got['w'], 2.5, rtol=1e-2)
  finally:
    client.close()
    server.close()
    buffer.close()


def test_param_lane_chunked_blob_roundtrip_and_concurrency():
  """Round 6 param-lane contract: `fetch_params` rides a SECOND
  connection served by the chunked non-blocking publisher. A blob much
  larger than the lane's 128 KiB chunk must round-trip bit-exact,
  version bumps must propagate, the subscriber/blob counters must
  account for the traffic, and the unroll pump must keep making
  progress while subscribers poll (the r5 starvation shape)."""
  buffer = ring_buffer.TrajectoryBuffer(8)
  params = {'w': np.arange(1 << 20, dtype=np.float64)}  # 8 MB >> chunk
  server = remote.TrajectoryIngestServer(buffer, params,
                                         host='127.0.0.1')
  clients = [remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
             for _ in range(3)]
  stop = threading.Event()
  drained = []
  try:
    for c in clients:
      version, got = c.fetch_params()
      assert version == 1
      np.testing.assert_array_equal(got['w'], params['w'])
    stats = server.stats()
    assert stats['param_subscribers'] == 3
    assert stats['param_blobs'] == 3
    assert stats['connections'] == 3   # three trajectory conns
    assert server.publish_params({'w': np.full(4, 2.0)}) == 2
    for c in clients:
      version, got = c.fetch_params()
      assert version == 2
      np.testing.assert_array_equal(got['w'], np.full(4, 2.0))

    # Pump + polling subscribers concurrently: both lanes progress.
    def drain():
      while not stop.is_set():
        try:
          drained.append(buffer.get(timeout=0.2))
        except (TimeoutError, ring_buffer.Closed):
          continue

    fetches = [0]

    def fetch_loop():
      while not stop.is_set():
        clients[1].fetch_params()
        fetches[0] += 1

    threads = [threading.Thread(target=drain, daemon=True),
               threading.Thread(target=fetch_loop, daemon=True)]
    for t in threads:
      t.start()
    pumped = 0
    deadline = time.monotonic() + 0.8
    while time.monotonic() < deadline:
      clients[0].send_unroll(_tiny_unroll(pumped))
      pumped += 1
    stop.set()
    for t in threads:
      t.join(timeout=5)
    assert pumped > 0 and fetches[0] > 0
    assert server.stats()['unrolls'] == pumped
  finally:
    stop.set()
    for c in clients:
      c.close()
    server.close()
    buffer.close()


def test_multi_connection_ingest_preserves_per_conn_order():
  """Round 6 multi-reader ingest: per-connection reader threads hand
  unrolls to the validate/commit worker pool. Every unroll from N
  concurrent connections must land exactly once, in per-connection
  FIFO order (cross-connection interleaving is free), with the
  per-connection counters accounting for all of them — and the
  bounded buffer en route exercises the backpressure path."""
  buffer = ring_buffer.TrajectoryBuffer(4)  # << total: puts must block
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(1)},
                                         host='127.0.0.1',
                                         ingest_workers=2)
  n_conns, per_conn = 3, 15
  landed = []
  landed_done = threading.Event()

  def drain():
    while len(landed) < n_conns * per_conn:
      try:
        landed.append(buffer.get(timeout=5))
      except (TimeoutError, ring_buffer.Closed):
        return
    landed_done.set()

  drainer = threading.Thread(target=drain, daemon=True)
  drainer.start()

  def pump(conn_id, errors):
    client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
    try:
      for seq in range(per_conn):
        unroll = _tiny_unroll(seq)
        unroll.env_outputs.reward[0] = conn_id * 1000 + seq  # tag
        client.send_unroll(unroll)
    except Exception as e:
      errors.append(e)
    finally:
      client.close()

  errors: list = []
  pumps = [threading.Thread(target=pump, args=(i, errors), daemon=True)
           for i in range(n_conns)]
  try:
    for t in pumps:
      t.start()
    for t in pumps:
      t.join(timeout=60)
    assert not errors, errors
    assert landed_done.wait(30)
    tags = [int(u.env_outputs.reward[0]) for u in landed]
    assert len(tags) == n_conns * per_conn
    assert len(set(tags)) == len(tags)  # exactly once
    for conn_id in range(n_conns):
      seqs = [t % 1000 for t in tags if t // 1000 == conn_id]
      assert seqs == sorted(seqs), (conn_id, seqs)  # per-conn FIFO
    stats = server.stats()
    assert stats['unrolls'] == n_conns * per_conn
    assert stats['ack_p99_ms'] > 0.0
  finally:
    server.close()
    buffer.close()
    drainer.join(timeout=5)


def test_publish_codec_resolution_and_rounding():
  """The bf16 publish codec is the DEFAULT (r5 measured: ratio 0.5 for
  ~5 ms vs zlib-1's 0.926 for 209 ms); 'f32' opts out; the legacy
  remote_params_dtype spelling still wins when set. The round trip
  through the default codec is exact-to-bf16-rounding (rel err ≤
  2^-8 — one bf16 ulp)."""
  import pytest
  from scalable_agent_tpu.config import Config
  assert Config().resolved_wire_dtype == 'bfloat16'
  assert Config(publish_codec='f32').resolved_wire_dtype == ''
  assert Config(publish_codec='f32',
                remote_params_dtype='bfloat16'
                ).resolved_wire_dtype == 'bfloat16'
  with pytest.raises(ValueError, match='publish_codec'):
    _ = Config(publish_codec='zstd').resolved_wire_dtype

  buffer = ring_buffer.TrajectoryBuffer(2)
  params = {'w': (np.random.RandomState(0).randn(4096)
                  .astype(np.float32))}
  server = remote.TrajectoryIngestServer(
      buffer, params, host='127.0.0.1',
      wire_dtype=Config().resolved_wire_dtype)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    _, got = client.fetch_params()
    assert got['w'].dtype == np.float32
    rel = np.abs(got['w'] - params['w']) / np.maximum(
        np.abs(params['w']), 1e-30)
    assert float(rel.max()) <= 2.0 ** -8
  finally:
    client.close()
    server.close()
    buffer.close()


def test_publish_swap_is_version_guarded():
  """ADVICE r3: two concurrent publishers may finish pickling out of
  order — the version-guarded swap must never let a slower, OLDER
  blob overwrite a newer one (clients would be served a permanently
  stale snapshot whose embedded version also lags)."""
  import threading as th
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(1)},
                                         host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  gate = th.Event()
  orig_make_blob = server._make_blob

  def slow_make_blob(version, params):
    blob = orig_make_blob(version, params)
    if version == 2:
      assert gate.wait(10)  # hold v2's swap until v3 has landed
    return blob

  server._make_blob = slow_make_blob
  try:
    t = th.Thread(
        target=lambda: server.publish_params({'w': np.full(1, 2.0)}),
        daemon=True)
    t.start()
    deadline = time.time() + 10
    while server._version < 2:  # v2 bumped, its swap now parked
      assert time.time() < deadline
      time.sleep(0.01)
    assert server.publish_params({'w': np.full(1, 3.0)}) == 3
    gate.set()  # v2's stale swap attempt runs AFTER v3's
    t.join(timeout=10)
    assert not t.is_alive()
    version, params = client.fetch_params()
    assert version == 3
    np.testing.assert_array_equal(params['w'], np.full(1, 3.0))
  finally:
    client.close()
    server.close()
    buffer.close()


def test_unroll_before_handshake_rejected():
  cfg, agent, contract = _contract_setup()
  import pytest
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    # Plain 'error' frame (RuntimeError), NOT 'reject': legacy clients
    # only special-case 'bye'/'error' — they must fail loudly too.
    with pytest.raises(RuntimeError, match='handshake'):
      client.send_unroll(_conforming_unroll(cfg, agent, 3))
    assert len(buffer) == 0
    # The connection survives; a handshake afterwards unblocks it.
    client.handshake(contract)
    assert client.send_unroll(_conforming_unroll(cfg, agent, 3)) == 1
    assert len(buffer) == 1
  finally:
    client.close()
    server.close()
    buffer.close()


def test_one_serialization_per_version_under_many_clients():
  """VERDICT r2 W2: N concurrent clients fetching params must not
  trigger N pickles — the snapshot serializes once per published
  version and handlers ship cached bytes."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  params = {'w': np.arange(10000.0)}  # big enough to matter
  server = remote.TrajectoryIngestServer(buffer, params,
                                         host='127.0.0.1')
  n_clients = 8
  clients = [remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
             for _ in range(n_clients)]
  try:
    assert server.serializations == 1  # v1, at construction
    barrier = threading.Barrier(n_clients)
    results = [None] * n_clients

    def fetch(i):
      barrier.wait()
      results[i] = clients[i].fetch_params()

    threads = [threading.Thread(target=fetch, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=30)
    assert all(r is not None and r[0] == 1 for r in results)
    _assert_trees_equal(results[0][1], params)
    assert server.serializations == 1  # N fetches, still one pickle

    server.publish_params({'w': np.zeros(3)})
    assert server.serializations == 2
    for c in clients:
      v, _ = c.fetch_params()
      assert v == 2
    assert server.serializations == 2
  finally:
    for c in clients:
      c.close()
    server.close()
    buffer.close()


def test_ingest_protocol_roundtrip():
  """Unrolls land bit-identical in the learner buffer; params flow back
  with version bumps piggybacked on the acks."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  params_v1 = {'w': np.arange(6.0).reshape(2, 3)}
  server = remote.TrajectoryIngestServer(buffer, params_v1,
                                         host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    version, got = client.fetch_params()
    assert version == 1
    _assert_trees_equal(got, params_v1)

    unroll = _tiny_unroll(7)
    assert client.send_unroll(unroll) == 1
    landed = buffer.get(timeout=5)
    _assert_trees_equal(landed, unroll)

    params_v2 = {'w': np.full((2, 3), 9.0)}
    assert server.publish_params(params_v2) == 2
    assert client.send_unroll(_tiny_unroll(8)) == 2  # ack reports bump
    version, got = client.fetch_params()
    assert version == 2
    _assert_trees_equal(got, params_v2)
    assert server.stats()['unrolls'] == 2
    assert server.stats()['connections'] == 1
  finally:
    client.close()
    server.close()
  buffer.close()


def test_ingest_backpressure_blocks_ack():
  """A full learner buffer must delay the ack — the end-to-end
  backpressure that bounds policy lag (reference capacity-1 remote
  enqueue)."""
  buffer = ring_buffer.TrajectoryBuffer(1)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(1)},
                                         host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  done = threading.Event()

  def pump():
    client.send_unroll(_tiny_unroll(1))
    client.send_unroll(_tiny_unroll(2))  # blocks: buffer full
    done.set()

  t = threading.Thread(target=pump, daemon=True)
  try:
    t.start()
    assert not done.wait(0.6)  # second unroll is being held back
    buffer.get(timeout=5)      # drain one slot
    assert done.wait(10)       # ...and the ack goes through
    buffer.get(timeout=5)
  finally:
    client.close()
    server.close()
    t.join(timeout=5)
  buffer.close()


def _run_learner_with_remote_child(tmp_path, base, child_actors,
                                   max_steps):
  """Shared body of the end-to-end remote-actor tests: spawn the
  no-accelerator child actor process, train the learner exclusively on
  its unrolls (num_actors=0 locally), assert the wire fed every
  consumed trajectory and the child exited cleanly. Returns the
  TrainRun."""
  import _remote_actor_child
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config

  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  learner_cfg = Config(logdir=str(tmp_path), num_actors=0,
                       remote_actor_port=port, **base)
  child = _remote_actor_child.spawn(f'127.0.0.1:{port}',
                                    dict(base, num_actors=child_actors))
  try:
    run = driver.train(learner_cfg, max_steps=max_steps,
                       stall_timeout_secs=120)
    assert int(run.state.update_steps) == max_steps
    # Every consumed trajectory came over the wire.
    assert run.ingest is not None
    assert run.ingest.stats()['unrolls'] >= \
        max_steps * learner_cfg.batch_size
    assert run.fleet.stats()['unrolls'] == 0
    # Round-11 liveness counters reach the driver summaries, and a
    # healthy run reaps/wedges nothing.
    import json as json_lib
    import os as os_lib
    summaries_path = os_lib.path.join(str(tmp_path), 'summaries.jsonl')
    with open(summaries_path) as f:
      tags = {json_lib.loads(line)['tag'] for line in f
              if line.strip() and 'tag' in line}
    for tag in ('remote_conns_reaped', 'remote_heartbeat_misses',
                'param_subs_dropped', 'ingest_threads_wedged',
                'remote_reattached', 'remote_stale_epoch_rejected',
                'actors_wedged'):
      assert tag in tags, tag
    # Round-12 integrity telemetry reaches summaries.jsonl too, and a
    # clean run shows ZERO violations (CRC is negotiated ON by
    # default — every one of these unrolls was trailer-verified).
    for tag in ('wire_crc_rejected', 'publish_digest_rejected',
                'ckpt_digest_fallbacks', 'sdc_replica_mismatches',
                'ingest_discarded_frames', 'ingest_discarded_bytes'):
      assert tag in tags, tag
    stats = run.ingest.stats()
    assert stats['stale_epoch_rejected'] == 0
    assert stats['ingest_threads_wedged'] == 0
    assert stats['wire_crc_rejected'] == 0
    assert stats['publish_digest_rejected'] == 0
    assert stats['discarded_frames'] == 0
    out, _ = child.communicate(timeout=120)
    assert child.returncode == 0, out[-2000:]
    assert 'CHILD_OK' in out, out[-2000:]
    return run
  finally:
    if child.poll() is None:
      child.kill()
      child.communicate()


def test_remote_actor_feeds_training(tmp_path):
  """The VERDICT bar: a separate OS process with no accelerator runs
  the actor role end-to-end (envs → CPU inference → TCP) and a real
  learner trains exclusively on its unrolls."""
  base = dict(
      env_backend='bandit', batch_size=2, unroll_length=5,
      num_action_repeats=1, episode_length=4, height=24, width=32,
      torso='shallow', use_py_process=False, use_instruction=False,
      total_environment_frames=10**6, inference_timeout_ms=5,
      checkpoint_secs=0, summary_secs=0, seed=11)
  _run_learner_with_remote_child(tmp_path, base, child_actors=2,
                                 max_steps=3)


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_remote_actor_feeds_sharded_training(tmp_path):
  """Remote ingest composed with the 8-device mesh path: remote-fed
  host unrolls flow through make_array_from_process_local_data into
  the pjit-sharded train step (batch_size=8 triggers the mesh)."""
  import jax
  assert len(jax.devices()) == 8
  base = dict(
      env_backend='bandit', batch_size=8, unroll_length=4,
      num_action_repeats=1, episode_length=4, height=24, width=32,
      torso='shallow', use_py_process=False, use_instruction=False,
      total_environment_frames=10**6, inference_timeout_ms=5,
      checkpoint_secs=0, summary_secs=0, seed=13)
  _run_learner_with_remote_child(tmp_path, base, child_actors=3,
                                 max_steps=2)


def test_remote_actor_reconnects_after_learner_restart():
  """Elasticity: when the learner (ingest server) CRASHES and comes
  back on the same port, an actor host with actor_reconnect_secs > 0
  keeps its envs alive, reconnects, refetches params, and resumes
  feeding. Delivery is at-least-once: the in-flight unroll is resent
  (an acked unroll sitting in the dead learner's buffer is lost with
  it, like any consumed-but-untrained batch)."""
  import threading as th
  import jax
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import init_params

  cfg = Config(env_backend='bandit', num_actors=1, batch_size=1,
               unroll_length=3, num_action_repeats=1, episode_length=4,
               height=24, width=32, torso='shallow',
               use_py_process=False, use_instruction=False,
               inference_timeout_ms=5, seed=21,
               actor_reconnect_secs=30.0)
  # The server must hold REAL agent params (the actor runs inference
  # with whatever it fetches) — same construction as the actor's.
  from scalable_agent_tpu.envs import factory
  spec0 = factory.make_env_spec(cfg, factory.level_names(cfg)[0],
                                seed=1)
  agent = driver.build_agent(cfg, spec0.num_actions)
  params = jax.device_get(
      init_params(agent, jax.random.PRNGKey(cfg.seed), spec0.obs_spec))

  # Bind on port 0 (no pick-then-close race); the restart reuses A's
  # actual port so the actor's reconnect target stays valid.
  buffer_a = ring_buffer.TrajectoryBuffer(2)
  server_a = remote.TrajectoryIngestServer(
      buffer_a, params, host='127.0.0.1')
  port = server_a.port

  result = {}

  def actor_main():
    result['sent'] = remote.run_remote_actor(
        cfg, f'127.0.0.1:{port}', task=0, stop_after_unrolls=6)

  t = th.Thread(target=actor_main, daemon=True)
  t.start()
  try:
    got_a = [buffer_a.get(timeout=120) for _ in range(2)]
    assert len(got_a) == 2
    # Crash, not clean shutdown: no 'bye' frame, so the actor enters
    # its reconnect window instead of exiting.
    server_a.close(graceful=False)
    buffer_a.close()

    # Learner restarts on the SAME port with a fresh buffer/params.
    # Bind-retry: the actor's reconnect attempts can transiently hold
    # the port (ephemeral-source reuse / TIME_WAIT) right after A's
    # close.
    buffer_b = ring_buffer.TrajectoryBuffer(8)
    deadline_b = time.time() + 60
    while True:
      try:
        server_b = remote.TrajectoryIngestServer(
            buffer_b, params, host='127.0.0.1', port=port)
        break
      except OSError:
        assert time.time() < deadline_b, 'port never freed'
        time.sleep(0.5)
    try:
      # The actor stops after 6 ACKED unrolls. Server A may have acked
      # up to 2 extra unrolls in the close race (they died with
      # buffer_a), so B receives 2–4: drain until the actor exits and
      # assert its own ledger completed and the reconnect fed B.
      got_b = []
      deadline = time.time() + 120
      while t.is_alive() and time.time() < deadline:
        try:
          got_b.append(buffer_b.get(timeout=2))
        except TimeoutError:
          pass
      t.join(timeout=10)
      assert not t.is_alive()
      # Drain whatever the actor parked before exiting (the alive-
      # gated loop above may stop with items still buffered).
      while True:
        try:
          got_b.append(buffer_b.get(timeout=0.5))
        except TimeoutError:
          break
      assert result['sent'] == 6
      assert len(got_b) >= 2, len(got_b)
    finally:
      server_b.close()
      buffer_b.close()
  finally:
    t.join(timeout=10)


# --- Round 11: transport liveness, partition tolerance, session
# epochs (protocol v6). ---


def _poll_until(predicate, timeout=8.0, interval=0.05):
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if predicate():
      return True
    time.sleep(interval)
  return predicate()


def test_half_open_peer_reaped_within_deadline():
  """The regression the round-11 deadlines exist for: a half-open peer
  (partial frame, then silence) used to pin its ingest reader in
  recv FOREVER. Now the reader/reaper pair closes it within the idle
  budget, counts the reap, and the server keeps serving."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      idle_timeout_secs=0.5)
  try:
    raw = socket.create_connection(('127.0.0.1', server.port))
    t0 = time.monotonic()
    # A frame header promising 1000 bytes, then 20, then silence.
    raw.sendall(remote._LEN.pack(1000) + b'\x00' + b'x' * 20)
    assert _poll_until(lambda: server.stats()['conns_reaped'] >= 1)
    reap_secs = time.monotonic() - t0
    assert reap_secs < 5.0, reap_secs
    # The reaped socket is actually closed (recv sees EOF/RST).
    raw.settimeout(5.0)
    try:
      assert raw.recv(1) == b''
    except ConnectionResetError:
      pass
    raw.close()
    assert _poll_until(lambda: server.stats()['live'] == 0)
    # No wedged threads: the reader unwound instead of leaking.
    assert server.stats()['ingest_threads_wedged'] == 0
    # The server survived: a healthy client still round-trips.
    healthy = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                       connect_timeout_secs=10)
    try:
      assert healthy.fetch_params()[0] == 1
    finally:
      healthy.close()
  finally:
    server.close()
    buffer.close()
  assert server.stats()['unjoined_threads'] == 0


def test_reaped_partial_unroll_discarded_without_buffer_corruption():
  """A peer reaped mid-unroll: the partial OOB frame never reached the
  handoff queue, so it is discarded WITH the connection — the buffer
  holds exactly the healthy client's unrolls afterwards, bit-exact."""
  cfg, agent, contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract,
      idle_timeout_secs=0.5)
  try:
    # Handshake a raw socket, then ship HALF an unroll and go silent.
    raw = socket.create_connection(('127.0.0.1', server.port))
    remote._send_msg(raw, ('hello', contract))
    reply = remote._recv_msg(raw)
    assert reply[0] in ('params', 'params_bf16')
    partial = _conforming_unroll(cfg, agent, 3, seed=5)
    segments = remote._oob_frame_segments(('unroll', partial))
    raw.sendall(bytes(segments[0]))          # head only: frame is
    raw.sendall(bytes(segments[1][:10]))     # forever incomplete
    assert _poll_until(lambda: server.stats()['conns_reaped'] >= 1)
    raw.close()

    # The buffer is untouched and a healthy unroll lands bit-exact.
    assert len(buffer) == 0
    client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                      connect_timeout_secs=10)
    try:
      client.handshake(contract)
      good = _conforming_unroll(cfg, agent, 3, seed=6)
      assert client.send_unroll(good) == 1
      landed = buffer.get(timeout=5)
      _assert_trees_equal(landed, good)
      assert len(buffer) == 0
      assert server.stats()['unrolls'] == 1
      assert server.stats()['rejected'] == 0
    finally:
      client.close()
  finally:
    server.close()
    buffer.close()


def test_heartbeat_v6_interop_with_v5_client():
  """A v5 client against a v6 heartbeat-enabled learner: the hello is
  ACCEPTED (compatible protocols), heartbeats negotiate OFF for that
  connection — no busy keepalives reach it mid-backpressure, and its
  silence never counts heartbeat misses — while a v6 connection on
  the same server does accrue misses when it goes silent."""
  cfg, agent, contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract,
      heartbeat_secs=0.15, idle_timeout_secs=5.0)
  v5_contract = dict(contract, protocol=5)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    version, params = client.handshake(v5_contract)
    assert version == 1
    # The v6 server-info rode the reply (harmless to a real v5 client,
    # which never reads element 3), so the epoch is visible here —
    # but the SERVER treats the conn as v5.
    unroll = _conforming_unroll(cfg, agent, 3, seed=7)
    # v5 wire shape: no epoch stamp (clear what the client learned).
    client.session_epoch = None
    assert client.send_unroll(unroll, params_version=1) == 1
    buffer.get(timeout=5)
    # Silence well past 2x the heartbeat cadence: a v5 conn must not
    # count misses (it never promised to ping).
    time.sleep(0.6)
    assert server.stats()['heartbeat_misses'] == 0

    # A v6 handshake on a second connection DOES accrue misses.
    v6 = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                  connect_timeout_secs=10)
    try:
      v6.handshake(contract)
      assert v6.session_epoch == server.session_epoch
      assert _poll_until(
          lambda: server.stats()['heartbeat_misses'] >= 1, timeout=5)
    finally:
      v6.close()
  finally:
    client.close()
    server.close()
    buffer.close()


def test_idle_client_pings_survive_reaping_window():
  """A v6 client pinging at the negotiated cadence stays connected
  through many idle windows (the pong also reports publishes), while
  the ping itself round-trips the current params version."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.1, idle_timeout_secs=0.5)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10,
                                    io_timeout_secs=5.0)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    assert client.ping() == 1
    server.publish_params({'w': np.ones(2)})
    deadline = time.monotonic() + 1.5  # 3x the idle window
    while time.monotonic() < deadline:
      assert client.ping() == 2
      time.sleep(0.1)
    stats = server.stats()
    assert stats['conns_reaped'] == 0
    assert stats['live'] == 1
  finally:
    client.close()
    server.close()
    buffer.close()


def test_busy_keepalive_distinguishes_slow_from_dead():
  """While buffer backpressure holds an ack, a v6 client sees
  ('busy',) keepalives at the heartbeat cadence — so its I/O deadline
  can be TIGHTER than the worst-case ack delay without false drops."""
  buffer = ring_buffer.TrajectoryBuffer(1)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.1, idle_timeout_secs=5.0)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10,
                                    io_timeout_secs=0.6)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    buffer.put(_tiny_unroll(0))  # full: the next ack is held back
    acked = threading.Event()

    def pump():
      client.send_unroll(_tiny_unroll(1))
      acked.set()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    # Longer than the client's 0.6s I/O deadline: only the busy
    # keepalives keep the connection alive through the wait.
    time.sleep(1.0)
    assert not acked.is_set()
    buffer.get(timeout=5)
    assert acked.wait(10)
    t.join(timeout=5)
    assert client.busy_frames >= 2, client.busy_frames
  finally:
    client.close()
    server.close()
    buffer.close()


def test_session_epoch_reattach_and_stale_epoch_refusal():
  """The hard-crash restart contract: a restarted learner's epoch
  differs; a hello carrying the PRIOR epoch counts as a fleet
  re-attach (timed), and an unroll stamped with the dead incarnation's
  epoch is refused with 'stale_epoch' — counted, never buffered."""
  import pytest
  buffer = ring_buffer.TrajectoryBuffer(4)
  server_a = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.2, idle_timeout_secs=5.0)
  client = remote.RemoteActorClient(f'127.0.0.1:{server_a.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    epoch_a = client.session_epoch
    assert epoch_a == server_a.session_epoch
  finally:
    client.close()
    server_a.close(graceful=False)  # crash semantics

  server_b = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.2, idle_timeout_secs=5.0)
  assert server_b.session_epoch != epoch_a
  client_b = remote.RemoteActorClient(f'127.0.0.1:{server_b.port}',
                                      connect_timeout_secs=10)
  try:
    # Reattaching hello: prior epoch rides along -> counted + timed.
    client_b.handshake({'protocol': remote.PROTOCOL_VERSION},
                       prior_epoch=epoch_a)
    stats = server_b.stats()
    assert stats['reattached'] == 1
    assert stats['reconnected'] == 0
    assert stats['reattach_latency_secs'] >= 0.0

    # An unroll stamped with the DEAD incarnation's epoch is refused.
    client_b.session_epoch = epoch_a
    with pytest.raises(remote.SessionEpochMismatch):
      client_b.send_unroll(_tiny_unroll(1))
    assert len(buffer) == 0
    assert server_b.stats()['stale_epoch_rejected'] == 1

    # Re-stamped with the live epoch it lands fine.
    client_b.session_epoch = server_b.session_epoch
    assert client_b.send_unroll(_tiny_unroll(2)) == 1
    assert len(buffer) == 1
    # A same-epoch re-hello counts as reconnect, not reattach.
    client_c = remote.RemoteActorClient(f'127.0.0.1:{server_b.port}',
                                        connect_timeout_secs=10)
    try:
      client_c.handshake({'protocol': remote.PROTOCOL_VERSION},
                         prior_epoch=server_b.session_epoch)
      assert server_b.stats()['reconnected'] == 1
      assert server_b.stats()['reattached'] == 1
    finally:
      client_c.close()
  finally:
    client_b.close()
    server_b.close()
    buffer.close()


def test_param_lane_drop_counter_and_graceful_bye():
  """Round-11 satellites: every dropped param-lane subscriber is
  counted (param_subs_dropped — silent fan-out shrinkage made
  visible), an idle subscriber is reaped by the lane itself, and a
  graceful close answers live subscribers with a clean 'bye' that the
  client surfaces as LearnerShutdown."""
  import pytest
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1')
  try:
    # A garbage subscriber is dropped AND counted.
    bad = socket.create_connection(('127.0.0.1', server.port))
    remote._send_msg(bad, ('hello_params',))
    bad.sendall(remote._LEN.pack(8) + b'garbage!')
    assert _poll_until(
        lambda: server.stats()['param_subs_dropped'] >= 1)
    bad.close()
  finally:
    server.close()
    buffer.close()

  # Idle-reaping on the lane: a quiet subscriber past the window.
  buffer2 = ring_buffer.TrajectoryBuffer(4)
  server2 = remote.TrajectoryIngestServer(
      buffer2, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.1, idle_timeout_secs=0.4)
  try:
    quiet = socket.create_connection(('127.0.0.1', server2.port))
    remote._send_msg(quiet, ('hello_params',))
    assert _poll_until(
        lambda: server2.stats()['param_subs_reaped'] >= 1, timeout=5)
    quiet.close()
  finally:
    server2.close()
    buffer2.close()

  # Graceful close answers a live subscriber with 'bye' ->
  # LearnerShutdown at the client.
  buffer3 = ring_buffer.TrajectoryBuffer(4)
  server3 = remote.TrajectoryIngestServer(
      buffer3, {'w': np.zeros(1)}, host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server3.port}',
                                    connect_timeout_secs=10)
  try:
    assert client.fetch_params()[0] == 1  # opens + caches the lane
    server3.close(graceful=True)
    with pytest.raises(remote.LearnerShutdown):
      client.fetch_params()
  finally:
    client.close()
    buffer3.close()


def test_fetch_params_retries_once_on_reaped_lane():
  """A cached param-lane subscriber reaped between fetches must cost
  ONE transparent retry, not a whole trajectory-lane reconnect."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.arange(8.0)}, host='127.0.0.1',
      heartbeat_secs=0.1, idle_timeout_secs=0.4)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    assert client.fetch_params()[0] == 1
    # Wait out the idle window: the lane reaps the quiet subscriber.
    assert _poll_until(
        lambda: server.stats()['param_subs_reaped'] >= 1, timeout=5)
    # The next fetch silently reopens and succeeds.
    version, params = client.fetch_params()
    assert version == 1
    np.testing.assert_array_equal(params['w'], np.arange(8.0))
  finally:
    client.close()
    server.close()
    buffer.close()


def test_validate_transport_cross_links():
  """validate_transport: hard range errors raise; the
  reconnect-vs-restart-budget and heartbeat-vs-window cross-links
  warn (round 11 satellite)."""
  import pytest
  from scalable_agent_tpu import config as config_lib

  assert config_lib.validate_transport(config_lib.Config()) == []
  with pytest.raises(ValueError, match='remote_heartbeat_secs'):
    config_lib.validate_transport(
        config_lib.Config(remote_heartbeat_secs=-1.0))
  with pytest.raises(ValueError, match='actor_reconnect_secs'):
    config_lib.validate_transport(
        config_lib.Config(actor_reconnect_secs=-5.0))

  short = config_lib.validate_transport(
      config_lib.Config(actor_reconnect_secs=10.0))
  assert any('restart budget' in w for w in short)
  inverted = config_lib.validate_transport(
      config_lib.Config(remote_heartbeat_secs=30.0,
                        remote_conn_idle_timeout_secs=5.0))
  assert any('reaping window' in w for w in inverted)
  no_hb = config_lib.validate_transport(
      config_lib.Config(remote_heartbeat_secs=0.0))
  assert any('heartbeats disabled' in w for w in no_hb)
  # The flipped default itself clears the budget cross-link.
  assert config_lib.Config().actor_reconnect_secs >= \
      config_lib.LEARNER_RESTART_BUDGET_SECS


def test_close_counts_unjoined_threads_clean_case():
  """Parity with InferenceServer.close(): join results are counted,
  and a clean shutdown reports zero leaked threads."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.2, idle_timeout_secs=1.0)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    assert client.send_unroll(_tiny_unroll(0)) == 1
  finally:
    client.close()
    server.close()
    buffer.close()
  assert server.stats()['unjoined_threads'] == 0
  assert server.stats()['ingest_threads_wedged'] == 0


def test_backpressured_conn_not_reaped_past_idle_window():
  """Review fix (round 11): a lockstep client parked awaiting its ack
  behind buffer backpressure sends NOTHING — by protocol. The reaper
  must exempt conns with an in-flight unroll even when the silence
  exceeds the idle window (reaping there would kill an obedient peer
  and duplicate its unroll on reconnect)."""
  buffer = ring_buffer.TrajectoryBuffer(1)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1',
      heartbeat_secs=0.1, idle_timeout_secs=0.4)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10,
                                    io_timeout_secs=2.0)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    buffer.put(_tiny_unroll(0))  # full: the ack will be held back
    acked = threading.Event()

    def pump():
      client.send_unroll(_tiny_unroll(1))
      acked.set()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    # 3x the idle window of client-side silence while parked.
    time.sleep(1.2)
    assert server.stats()['conns_reaped'] == 0
    assert server.stats()['heartbeat_misses'] == 0
    buffer.get(timeout=5)
    assert acked.wait(10)
    t.join(timeout=5)
    # Ack delivered on the ORIGINAL connection; exactly one copy of
    # the unroll landed.
    assert server.stats()['unrolls'] == 1
    assert len(buffer) == 1
  finally:
    client.close()
    server.close()
    buffer.close()


# --- Round 12: protocol v7 payload integrity -------------------------


def test_v7_crc_negotiation_and_clean_roundtrip():
  """The production default: a v7 client against a v7 wire_crc server
  negotiates CRC at hello; every subsequent frame both ways carries a
  verified trailer, unrolls land, params fetch over the lane, and the
  integrity counters stay zero. The hello reply itself carries a
  params content digest the client verifies before install."""
  cfg, agent, contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.arange(64, dtype=np.float32)}, host='127.0.0.1',
      contract=contract, wire_crc=True)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    version, params = client.handshake(contract)
    assert version == 1
    assert client._crc, 'CRC did not negotiate on for a v7 pair'
    assert client.server_info.get('wire_crc') is True
    assert 'params_digest' in client.server_info
    unroll = _conforming_unroll(cfg, agent, 3, seed=3)
    assert client.send_unroll(unroll, params_version=1) == 1
    got = buffer.get(timeout=5)
    _assert_trees_equal(got, unroll)
    # Ping (trailer both ways) and a lane fetch (trailered blob).
    assert client.ping() == 1
    server.publish_params({'w': np.full(8, 2.0, np.float32)})
    v2, tree2 = client.fetch_params()
    assert v2 == 2
    np.testing.assert_array_equal(tree2['w'],
                                  np.full(8, 2.0, np.float32))
    stats = server.stats()
    assert stats['wire_crc_rejected'] == 0
    assert stats['quarantined'] == 0
    assert client.crc_rejected == 0
    assert client.digest_rejected == 0
  finally:
    client.close()
    server.close()
    buffer.close()


def test_wire_bitflip_refused_before_put_then_resent_clean():
  """The tentpole contract: a single bit flip that still PARSES is
  refused by the worker BEFORE the buffer put with the benign
  ('corrupt', crc) reply — the buffer provably never sees it, the
  connection survives, and the re-send (clean bytes: the fault damages
  a COPY) lands bit-exact. Counted as wire_crc_rejected, never as a
  quarantine."""
  import pytest
  from scalable_agent_tpu.runtime import faults as faults_lib

  cfg, agent, contract = _contract_setup()
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  plan = faults_lib.FaultPlan(
      [faults_lib.Fault('wire_bitflip', 0, 'flip')], seed=3)
  try:
    client.handshake(contract)
    unroll = _conforming_unroll(cfg, agent, 3, seed=5)
    faults_lib.install(plan)
    try:
      with pytest.raises(remote.UnrollCorrupt):
        client.send_unroll(unroll, params_version=1)
    finally:
      faults_lib.clear()
    assert client.crc_rejected == 1
    assert len(buffer) == 0, 'corrupt unroll reached the buffer'
    stats = server.stats()
    assert stats['wire_crc_rejected'] == 1
    assert stats['quarantined'] == 0
    assert stats['unrolls'] == 0
    # The re-send (no fault armed) ships clean bytes on the SAME
    # connection and lands bit-exact.
    assert client.send_unroll(unroll, params_version=1) == 1
    _assert_trees_equal(buffer.get(timeout=5), unroll)
    assert server.stats()['connections'] == 1
  finally:
    client.close()
    server.close()
    buffer.close()


def test_v7_v6_interop_crc_negotiated_off_both_directions():
  """Interop both ways (the acceptance gate): a v6 client against a
  v7 server, and a v7 client against a CRC-disabled server, both
  negotiate the trailers OFF and move unrolls exactly like the v6
  wire — no stray trailer bytes, no phantom corruption."""
  cfg, agent, contract = _contract_setup()

  # (a) v6 peer against a v7 wire_crc server.
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract,
      wire_crc=True)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake(dict(contract, protocol=6))
    assert not client._crc
    client.session_epoch = None  # v6 wire shape
    unroll = _conforming_unroll(cfg, agent, 3, seed=7)
    assert client.send_unroll(unroll, params_version=1) == 1
    _assert_trees_equal(buffer.get(timeout=5), unroll)
    assert server.stats()['wire_crc_rejected'] == 0
    assert server.stats()['quarantined'] == 0
  finally:
    client.close()
    server.close()
    buffer.close()

  # (b) v7 client against a server running --wire_crc=false.
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1', contract=contract,
      wire_crc=False)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake(contract)
    assert not client._crc
    unroll = _conforming_unroll(cfg, agent, 3, seed=9)
    assert client.send_unroll(unroll, params_version=1) == 1
    _assert_trees_equal(buffer.get(timeout=5), unroll)
    # The lane fetch works trailer-free too.
    server.publish_params({'w': np.ones(2)})
    assert client.fetch_params()[0] == 2
    # Digest verification runs INDEPENDENT of lane CRC (digests ship
    # whenever the server is v7), and the rejection notice must reach
    # the wire_crc=False server too — the review-round regression.
    import pytest
    from scalable_agent_tpu.runtime import faults as faults_lib
    faults_lib.install(faults_lib.FaultPlan(
        [faults_lib.Fault('publish_corrupt', 0, 'flip')], seed=11))
    try:
      server.publish_params({'w': np.arange(64, dtype=np.float32)})
    finally:
      faults_lib.clear()
    with pytest.raises(remote.ParamsCorrupt):
      client.fetch_params()
    with pytest.raises(remote.ParamsCorrupt):
      client.fetch_params()  # the retry carries the nack
    assert server.stats()['publish_digest_rejected'] >= 1
    assert server.stats()['quarantined'] == 0
  finally:
    client.close()
    server.close()
    buffer.close()


def test_publish_digest_rejected_before_install_with_nack():
  """A publish corrupted AFTER its digest (host-memory rot — the
  frame CRC is self-consistent) must be refused BEFORE install:
  fetch_params raises ParamsCorrupt, the retry fetch carries the
  digest-rejected notice (the learner's publish_digest_rejected
  ledger), and the next CLEAN publish fetches fine."""
  import pytest
  from scalable_agent_tpu.runtime import faults as faults_lib

  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.arange(128, dtype=np.float32)},
      host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    assert client._crc
    # Corrupt the NEXT blob build (the plan is installed after the
    # constructor's blob, so the coming publish is site event 0).
    faults_lib.install(faults_lib.FaultPlan(
        [faults_lib.Fault('publish_corrupt', 0, 'flip')], seed=5))
    try:
      server.publish_params({'w': np.arange(128, dtype=np.float32)})
    finally:
      faults_lib.clear()
    with pytest.raises(remote.ParamsCorrupt):
      client.fetch_params()
    assert client.digest_rejected == 1
    # The retry carries the nack; the blob is STILL corrupt (cached),
    # so it is refused again — but the server now knows.
    with pytest.raises(remote.ParamsCorrupt):
      client.fetch_params()
    assert server.stats()['publish_digest_rejected'] >= 1
    # A clean publish supersedes the rot; the fetch installs.
    server.publish_params({'w': np.full(4, 3.0, np.float32)})
    v, tree = client.fetch_params()
    assert v == 3
    np.testing.assert_array_equal(tree['w'],
                                  np.full(4, 3.0, np.float32))
  finally:
    client.close()
    server.close()
    buffer.close()


def test_quarantine_reports_discarded_bytes_and_frames():
  """Round-12 regression (the satellite fix): the unparseable-frame
  quarantine used to count the CONNECTION but drop the partial batch
  accounting — the discard path must now report how many bytes/frames
  died with it."""
  buffer = ring_buffer.TrajectoryBuffer(2)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(1)},
                                         host='127.0.0.1')
  try:
    rogue = socket.create_connection(('127.0.0.1', server.port))
    rogue.settimeout(10)
    # A well-framed message whose tag byte is garbage: parses the
    # header, fails the frame kind — the quarantine path.
    payload = b'\xee' + b'x' * 499
    rogue.sendall(remote._LEN.pack(len(payload)) + payload)
    try:
      assert rogue.recv(1) == b''
    except ConnectionResetError:
      pass
    rogue.close()
    deadline = time.monotonic() + 5
    while (server.stats()['quarantined'] < 1
           and time.monotonic() < deadline):
      time.sleep(0.05)
    stats = server.stats()
    assert stats['quarantined'] == 1
    assert stats['discarded_frames'] == 1
    # Header (8) + however much of the body was consumed before the
    # parse failed — at least the header plus the tag byte.
    assert stats['discarded_bytes'] >= remote._LEN.size + 1

    # Review-round regression: a GOOD frame followed by an oversized
    # length header must charge ~8 discarded bytes, not the good
    # frame's size (the ledger resets before the bound check raises).
    rogue2 = socket.create_connection(('127.0.0.1', server.port))
    rogue2.settimeout(10)
    remote._send_msg(rogue2, ('ping',))
    assert remote._recv_msg(rogue2)[0] == 'pong'
    rogue2.sendall(remote._LEN.pack(remote._MAX_MSG + 1))
    try:
      while rogue2.recv(4096):
        pass
    except ConnectionResetError:
      pass
    rogue2.close()
    deadline = time.monotonic() + 5
    while (server.stats()['quarantined'] < 2
           and time.monotonic() < deadline):
      time.sleep(0.05)
    stats2 = server.stats()
    assert stats2['quarantined'] == 2
    delta = stats2['discarded_bytes'] - stats['discarded_bytes']
    assert delta == remote._LEN.size, delta
  finally:
    server.close()
    buffer.close()


def test_validate_integrity_cross_links():
  """The round-12 knob-group validation: half-enabled integrity
  planes warn, the default config is silent."""
  from scalable_agent_tpu.config import Config, validate_integrity

  assert validate_integrity(Config()) == []
  warnings = validate_integrity(Config(sdc_check=True,
                                       health_watchdog=False))
  assert any('never escalated' in w for w in warnings)
  warnings = validate_integrity(Config(wire_crc=False,
                                       remote_actor_port=1234))
  assert any('no detection' in w for w in warnings)
  warnings = validate_integrity(Config(wire_crc=False,
                                       replay_ratio=0.5))
  assert any('already-rotten' in w for w in warnings)


def test_crc_probation_ladder():
  """Round 15: the client-side CRC self-quarantine grew a probation
  rung — resend, then ONE cooled-down probe, then terminal
  quarantine; a later double-refusal after the probation is spent is
  terminal immediately."""
  p = remote.CrcProbation(cooldown_secs=0.0)
  # Unroll A: refusal -> resend; second refusal -> the probation probe.
  assert p.on_refusal() == remote.CrcProbation.RESEND
  assert p.on_refusal() == remote.CrcProbation.PROBE
  assert (p.crc_resends, p.probations) == (1, 1)
  # The probe is ACKED: recovered, the host stays in the fleet.
  assert p.on_ack() is True
  assert p.recoveries == 1
  # Unroll B: the resend budget is per-unroll (resets)...
  p.next_unroll()
  assert p.on_refusal() == remote.CrcProbation.RESEND
  # ...but the probation budget is per-run: terminal this time.
  assert p.on_refusal() == remote.CrcProbation.QUARANTINE


def test_crc_probation_probe_failure_is_terminal():
  p = remote.CrcProbation(cooldown_secs=0.0)
  assert p.on_refusal() == remote.CrcProbation.RESEND
  assert p.on_refusal() == remote.CrcProbation.PROBE
  # The probe itself is refused: re-quarantine on repeat failure.
  assert p.on_refusal() == remote.CrcProbation.QUARANTINE
  assert p.recoveries == 0
  # An ordinary ack after quarantine-verdict changes nothing.
  assert p.on_ack() is False


def _wait_for(predicate, timeout=5.0, what='condition'):
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if predicate():
      return
    time.sleep(0.02)
  raise AssertionError(f'timed out waiting for {what}')


def test_membership_join_reconnect_and_drain():
  """v9 elastic membership: the FIRST hello carrying a host identity
  is a join (event + counter); a re-hello with the SAME identity is a
  reconnect, not a second join; a 'leave'-announced exit unwinds as
  host_left(reason='drain'). Events drain exactly once."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1')
  addr = f'127.0.0.1:{server.port}'
  host_id = 'hostA:111:task0'
  try:
    c1 = remote.RemoteActorClient(addr, connect_timeout_secs=10)
    c1.handshake({'protocol': remote.PROTOCOL_VERSION}, host=host_id)
    assert server.live_hosts() == 1
    assert server.membership() == [host_id]
    events = server.drain_membership_events()
    assert events == [{'kind': 'host_joined', 'host': host_id,
                       'reattach': False}]
    assert server.drain_membership_events() == []  # exactly once

    # Same identity, second connection: the ledger re-points, no event.
    c2 = remote.RemoteActorClient(addr, connect_timeout_secs=10)
    c2.handshake({'protocol': remote.PROTOCOL_VERSION}, host=host_id)
    assert server.live_hosts() == 1
    assert server.drain_membership_events() == []
    # The superseded connection closing must NOT evict the live one.
    c1.close()
    time.sleep(0.3)
    assert server.live_hosts() == 1
    assert server.drain_membership_events() == []

    # Announced drain: bye_ack, then the unwind records 'drain'.
    assert c2.send_leave() is True
    c2.close()
    _wait_for(lambda: server.live_hosts() == 0, what='drain unwind')
    events = server.drain_membership_events()
    assert events == [{'kind': 'host_left', 'host': host_id,
                       'reason': 'drain'}]
    stats = server.stats()
    assert stats['live_hosts'] == 0
    assert stats['hosts_joined'] == 1
    assert stats['hosts_left'] == 1
  finally:
    server.close()
    buffer.close()


def test_membership_unannounced_death_is_lost():
  """A host that dies without a leave announcement unwinds as
  host_left(reason='lost') — the signal the driver turns into the
  durable incident an operator pages on."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1')
  try:
    c = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                 connect_timeout_secs=10)
    c.handshake({'protocol': remote.PROTOCOL_VERSION},
                host='hostB:222:task1')
    assert server.live_hosts() == 1
    c.close()  # abrupt: no leave frame, socket just goes away
    _wait_for(lambda: server.live_hosts() == 0, what='loss unwind')
    events = server.drain_membership_events()
    assert [e['kind'] for e in events] == ['host_joined', 'host_left']
    assert events[1]['reason'] == 'lost'
  finally:
    server.close()
    buffer.close()


def test_membership_hostless_hello_and_legacy_leave():
  """Compat floor: a hello WITHOUT a host identity (v8-and-older
  actors) never enters the ledger, and send_leave against a server
  that answers ('error', unknown kind) returns False instead of
  raising — the drain path is best-effort by contract."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(
      buffer, {'w': np.zeros(1)}, host='127.0.0.1')
  try:
    c = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                 connect_timeout_secs=10)
    c.handshake({'protocol': remote.PROTOCOL_VERSION})  # no host=
    assert server.live_hosts() == 0
    assert server.drain_membership_events() == []
    c.close()
  finally:
    server.close()
    buffer.close()

  # An "old learner" that doesn't know the 'leave' kind: the client
  # swallows the error-reply RuntimeError and reports not-acked.
  lis = socket.socket()
  lis.bind(('127.0.0.1', 0))
  lis.listen(1)
  port = lis.getsockname()[1]

  def _legacy_server():
    conn, _ = lis.accept()
    try:
      kind, _ = remote._recv_msg(conn)
      assert kind == 'leave'
      remote._send_msg(conn, ('error', "unknown message kind 'leave'"))
    finally:
      conn.close()

  t = threading.Thread(target=_legacy_server, daemon=True)
  t.start()
  c = remote.RemoteActorClient(f'127.0.0.1:{port}',
                               connect_timeout_secs=10)
  try:
    assert c.send_leave() is False
  finally:
    c.close()
    lis.close()
    t.join(timeout=5)

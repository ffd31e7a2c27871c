"""Remote actors: actor-only hosts feeding a learner over TCP.

The reference runs dedicated actor processes/machines against the
learner through the TF1 gRPC runtime: actors hold their own env +
inference graph, fetch the learner-pinned weights per run, and their
`queue.enqueue` is a remote op into the learner-hosted FIFOQueue
(reference: experiment.py ≈L435–460 ClusterSpec/Server wiring, ≈L625
actor loop; SURVEY §3.4 — paper configs used 150–500 actor CPUs per
learner). A TPU host cannot step enough DMLab envs by itself to feed
200k frames/sec, so this scale-out path is load-bearing for the north
star.

TPU-native re-design (SURVEY §5.8 "shared memory / RPC to actor
processes"):

- The learner host runs a `TrajectoryIngestServer` next to its
  `TrajectoryBuffer`: remote unrolls land in the SAME buffer the local
  fleet feeds, so the learner pipeline (batcher → prefetcher → sharded
  step) is oblivious to where trajectories come from.
- Each actor-only host runs `run_remote_actor()`: a normal `ActorFleet`
  + CPU `InferenceServer` (inference on the actor host, exactly like
  the reference's distributed mode — NOT request/response inference
  against the learner), a local buffer, and a pump thread that ships
  unrolls to the learner and pulls fresh params when the learner's
  version advances.
- Weights flow learner → actor piggybacked on the unroll acks: each ack
  carries the learner's current params version; a stale client fetches
  the new snapshot. This is the gRPC variable-read replaced by an
  explicit snapshot protocol, with the same staleness story (actions
  within one unroll may span weight versions).

Wire protocol: length-prefixed pickled messages over TCP, strict
request→reply lockstep per socket (no concurrent writes per socket).
Backpressure is end-to-end: a full learner buffer blocks the ingest
worker's `put`, which delays the ack, which blocks the actor's pump —
the reference's capacity-1 remote enqueue semantics.

Transport planes (round 6 — BENCH_r05 measured all three pathologies):

- **Trajectory lane** (the hot path): one reader thread per connection
  does ONLY recv+parse and hands the unroll to a small validate/commit
  worker pool via a GIL-atomic queue; the worker validates, lands the
  unroll in the shared buffer (backpressure lives here) and sends the
  ack. Readers never touch the buffer lock, so N connections scale by
  overlapping socket copies instead of fighting over one
  recv→validate→put→ack critical path (r5: 4 connections measured
  SLOWER than 1).
- **Param lane** (weight fan-out): subscribers open a SECOND
  connection (`hello_params`) served by one selector thread with
  chunked non-blocking sends. r5 measured 8 polling fetchers
  collapsing the unroll pump 838.6 → 29.9 unrolls/s (ack p99 1.18 →
  95.8 ms): 8 handler threads each mid-sendall of a 6.5 MB blob
  starve the tiny acks. One multiplexing thread writing bounded
  chunks caps the blob plane at one runnable thread regardless of
  subscriber count. Snapshots ship bf16-cast by default
  (config.publish_codec; measured ratio 0.5 for ~5 ms vs zlib-1's
  0.926 for 209 ms).

Transport fault tolerance (round 11 — docs/TRANSPORT.md v6,
docs/ROBUSTNESS.md transport rows): every blocking socket path now
carries a deadline. Server readers poll with short timeouts
(`_ConnLiveness` — a half-open peer stalling MID-frame is reaped
instead of pinning its reader forever), sends are progress-bounded
(`_sendall_bounded` — a non-reading peer can't wedge a worker in
sendall), an idle reaper closes connections silent past
`remote_conn_idle_timeout_secs` on both lanes, v6 clients heartbeat
('ping'/'pong' with the current params version) to stay inside the
window, ingest workers emit ('busy',) keepalives while backpressure
holds an ack (slow learner ≠ dead learner), and a per-run SESSION
EPOCH rides the handshake so a hard-crashed-and-restarted learner
tells reattaching clients from fresh ones, times the fleet re-attach,
and provably accepts zero stale-incarnation unrolls
(`scripts/chaos.py run_partition_storm` asserts the SLOs). A
`ThreadWatchdog` surfaces any service thread that still wedges
(stats()['ingest_threads_wedged'] → driver summaries + incidents).

Data-plane integrity (round 12 — docs/TRANSPORT.md v7,
docs/ROBUSTNESS.md integrity rows): protocol v7 adds end-to-end
payload verification on both lanes. Every frame on a CRC-negotiated
connection carries a CRC32C trailer (integrity.py); the ingest
validate/commit worker verifies it BEFORE the buffer put and answers
`('corrupt', crc)` — the client re-sends once, then quarantines
ITSELF (persistent CRC failures mean a bad NIC/host, docs/RUNBOOK.md
§9). Param publishes additionally carry a CONTENT digest computed
from the snapshot at publish time: the client verifies it before
`update_params` installs anything into the inference arena, so a
publish corrupted between device_get and the wire (where the frame
CRC is self-consistent) is rejected fleet-wide without a version bump
and refetched on backoff — and the rejection is reported back on the
next `get_params`, so the learner's summaries see
`publish_digest_rejected` without a client-side side channel. All of
it negotiates OFF for v5/v6 peers at hello, the same extension
pattern as every protocol bump since round 9.

Trust model: pickle over cluster-internal sockets — identical trust to
the reference's unauthenticated TF gRPC runtime. Never expose the
ingest port outside the job's network. The CRC is an INTEGRITY check
against accidental corruption, not authentication.
"""

import logging
import os
import pickle
import queue
import random
import selectors
import signal
import socket
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

from scalable_agent_tpu.observability import ThreadWatchdog

import numpy as np

from scalable_agent_tpu import integrity
from scalable_agent_tpu import telemetry
from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock
from scalable_agent_tpu.runtime import faults as faults_lib
from scalable_agent_tpu.runtime import ring_buffer

log = logging.getLogger('scalable_agent_tpu')

_LEN = struct.Struct('>Q')
_MAX_MSG = 1 << 32  # 4 GiB sanity bound
# v7 per-frame CRC32C trailer: 4 big-endian bytes AFTER the payload on
# connections that negotiated CRC at hello. The length prefix keeps
# counting tag+payload only, so the framing stays v4-compatible — a
# receiver that negotiated CRC simply reads 4 more bytes per frame.
_CRC = struct.Struct('>I')
# Frame kinds (one tag byte after the length prefix). PLAIN frames
# carry one pickled object. OOB frames carry a pickle-protocol-5
# skeleton plus the arrays' raw buffers out of band — pickling a
# 2.11 MB flagship unroll inline costs ~600 µs of pure copying per
# direction on the ingest path, the skeleton+buffers form ~66 µs
# (measured, docs/PERF.md): the frames are the bytes, so don't copy
# them through the pickler.
_FRAME_PLAIN = 0
_FRAME_OOB = 1
_OOB_META = struct.Struct('>II')    # (num buffers, skeleton length)
_OOB_BUFLEN = struct.Struct('>Q')
# Remote-actor seed namespace: far above any learner host's
# process_index * max(num_actors, 1000) base (a 16M+ learner stride
# would need thousands of processes), so cross-role streams never
# collide.
_REMOTE_SEED_SPACE = 1 << 24


def _plain_frame(payload: bytes, crc: bool = False) -> bytes:
  """One complete PLAIN wire frame for pre-pickled payload bytes,
  with the v7 CRC trailer when `crc` (the trailer covers tag+payload
  — everything the length prefix counts)."""
  body = bytes((_FRAME_PLAIN,)) + payload
  frame = _LEN.pack(len(body)) + body
  if crc:
    frame += _CRC.pack(integrity.crc_bytes(body))
  return frame


def _send_msg(sock: socket.socket, obj, crc: bool = False) -> None:
  sock.sendall(_plain_frame(
      pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL), crc=crc))


# Buffers at or below this coalesce into one sendall with their
# neighbors: an unroll carries ~11 OOB buffers of which only the
# frame stack is big, and a syscall per 400-byte reward array costs
# more than copying it (round 6 — the per-message syscall count was
# one of the two costs keeping multi-connection ingest from scaling).
_OOB_COALESCE = 128 * 1024


def _oob_frame_segments(obj) -> List:
  """The complete OOB wire frame for `obj`, as segments ready for
  sendall: [head (length prefix + tag + meta + skeleton + buffer
  table), raw buffer memoryview, ...]. The ONE place the OOB frame
  layout is built — `_send_oob` streams these per message, the ingest
  server caches them per published param version."""
  buffers = []
  skeleton = pickle.dumps(obj, protocol=5,
                          buffer_callback=buffers.append)
  raws = [b.raw() for b in buffers]
  lens = b''.join(_OOB_BUFLEN.pack(r.nbytes) for r in raws)
  total = (1 + _OOB_META.size + len(skeleton) + len(lens)
           + sum(r.nbytes for r in raws))
  head = (_LEN.pack(total) + bytes((_FRAME_OOB,))
          + _OOB_META.pack(len(raws), len(skeleton))
          + skeleton + lens)
  return [head] + raws


def _segments_crc(segments) -> int:
  """CRC32C over a complete OOB frame's CONTENT (everything the
  length prefix counts: tag + meta + skeleton + table + raw buffers —
  i.e. segment 0 minus its 8-byte length prefix, then every raw)."""
  acc = integrity.Crc()
  acc.update(memoryview(segments[0])[_LEN.size:])
  for raw in segments[1:]:
    acc.update(raw)
  return acc.value


def _send_segments(sock: socket.socket, segments,
                   trailer: Optional[bytes] = None) -> None:
  """Stream a pre-built frame's segments with small-buffer coalescing
  (`_OOB_COALESCE`); big ones go as bare sendalls on their memoryview
  — no 2 MB join. `trailer` (the v7 CRC bytes) rides the final
  flush."""
  pending = [segments[0]]

  def flush():
    if not pending:
      return
    sock.sendall(pending[0] if len(pending) == 1
                 else b''.join(pending))
    pending.clear()

  for raw in segments[1:]:
    if memoryview(raw).nbytes <= _OOB_COALESCE:
      pending.append(raw)
      if sum(len(p) for p in pending) > _OOB_COALESCE:
        flush()
    else:
      flush()
      sock.sendall(raw)
  if trailer is not None:
    pending.append(trailer)
  flush()


def _send_oob(sock: socket.socket, obj, crc: bool = False) -> None:
  """Ship `obj` with its array buffers OUT of the pickle stream: the
  skeleton + per-buffer lengths go in the frame head, then each raw
  buffer is sent directly (no pickler copy). The receiver
  reconstructs with zero-copy views. With `crc`, the v7 trailer is
  computed over the frame content BEFORE the wire_bitflip fault site
  runs — an injected flip ships with a stale trailer, exactly the
  silent-corruption shape the check exists to catch."""
  segments = _oob_frame_segments(obj)
  trailer = _CRC.pack(_segments_crc(segments)) if crc else None
  plan = faults_lib.active()
  fault = faults_lib.fire('wire_bitflip')
  if fault is not None:
    segments = faults_lib.apply_wire_bitflip(
        fault, segments, seed=plan.seed if plan else 0)
  _send_segments(sock, segments, trailer)


class _CrcContext:
  """Per-frame CRC ledger for a receive on a v7 CRC connection:
  `_recv_msg` accumulates the computed CRC over every frame piece as
  it lands and records the wire trailer; the CALLER compares (the
  ingest worker does it just before the buffer put, so a corrupt
  unroll is refused with the benign ('corrupt', crc) reply instead of
  a connection drop — the reader only hard-fails frames whose very
  parse is untrustworthy)."""

  __slots__ = ('computed', 'wire')

  def __init__(self):
    self.computed = 0
    self.wire: Optional[int] = None

  def reset(self):
    self.computed = 0
    self.wire = None

  def update(self, data):
    self.computed = integrity.crc_bytes(data, self.computed)

  @property
  def ok(self) -> bool:
    return self.wire is not None and self.wire == self.computed


class _FrameStall(OSError):
  """A peer stopped sending MID-frame past the stall deadline (a
  half-open connection trickling to silence) — the reader reaps the
  connection instead of pinning itself on the partial frame forever."""


class _ServerClosing(ConnectionError):
  """The server's close() began while this reader was parked in its
  poll loop. The reader exits WITHOUT closing or unlisting its
  connection: close() already holds the shutdown sequence ('bye' →
  half-close → close) for every listed conn, and a reader racing it
  with its own close() would discard the buffered 'bye' with an RST
  (legacy blocking readers never woke here, so the bye always won)."""


class _SendStall(OSError):
  """A send made no progress past the stall deadline (a blackholed /
  non-reading peer with a full TCP window) — the sender gives up on
  the connection instead of wedging its thread in sendall forever."""


class _ConnLiveness:
  """Per-connection recv liveness for the server's reader threads
  (round 11). The socket runs in timeout mode (short poll); every poll
  expiry lands here:

  - `progress(n)` on received bytes: refreshes the connection's
    last-recv clock (the reaper's idle measure) and beats the server's
    thread watchdog.
  - `idle(got)` on a poll timeout: beats the watchdog (an idle reader
    is NOT a wedged reader), aborts cleanly when the server is
    closing, raises `_FrameStall` when the timeout fired MID-frame
    past the stall deadline (a half-open peer must not pin the reader
    on a partial frame — `in_frame` spans the WHOLE frame, set by
    _recv_msg once the header lands, so the deadline cannot reset at
    sub-frame read boundaries), and emits the ('busy',) backpressure
    keepalive for a conn whose unroll is in flight — the READER owns
    the keepalive, so it flows whether the job is held by a worker or
    still parked in the handoff queue (workers < connections under
    load). Idle BETWEEN frames with nothing in flight is legal here;
    the reaper owns that budget (it closes the socket, which surfaces
    as an OSError in the reader).
  """

  def __init__(self, conn, closed_event, stall_secs, watchdog=None,
               name='', heartbeat_secs: float = 0.0):
    self._conn = conn
    self._closed = closed_event
    self._stall_secs = stall_secs
    self._watchdog = watchdog
    self._name = name
    self._heartbeat_secs = heartbeat_secs
    self._last_busy = time.monotonic()
    self.in_frame = False  # header received, frame body outstanding
    # Bytes of the CURRENT frame received so far (header included):
    # the discard ledger — when a frame is thrown away (quarantine on
    # an unparseable frame, a mid-frame stall reap), the reader
    # reports HOW MUCH was discarded instead of dropping the partial
    # accounting on the floor (round 12 fix).
    self.frame_bytes = 0

  def beat(self):
    if self._watchdog is not None:
      self._watchdog.beat(self._name)

  def progress(self, nbytes):
    if self.in_frame:
      self.frame_bytes += nbytes
    self._conn.last_recv = time.monotonic()
    self._conn.hb_missed = False
    self.beat()

  def idle(self, got):
    self.beat()
    if self._closed.is_set():
      raise _ServerClosing('server closing')
    now = time.monotonic()
    if (got or self.in_frame) and (now - self._conn.last_recv
                                   > self._stall_secs):
      raise _FrameStall(
          f'peer silent mid-frame for more than {self._stall_secs}s')
    if (self._conn.heartbeat and self._conn.is_waiting_on_us()
        and self._heartbeat_secs > 0
        and now - self._last_busy >= self._heartbeat_secs):
      # Backpressure keepalive: the peer is parked lockstep awaiting
      # our reply (worker blocked in put OR job still queued) — tell
      # it we're slow, not dead, at the heartbeat cadence.
      self._last_busy = now
      try:
        self._conn.send(('busy',))
      except OSError:
        pass  # peer gone; the recv path will notice


def _recv_into(sock: socket.socket, view, n: int, liveness=None) -> int:
  """Fill view[:n] from the socket; returns bytes received (< n only
  on EOF). With `liveness`, the socket is expected to be in timeout
  mode: poll expiries route to liveness.idle (which may raise to abort
  a stalled frame) and received bytes to liveness.progress."""
  got = 0
  while got < n:
    try:
      r = sock.recv_into(view[got:n])
    except socket.timeout:
      if liveness is None:
        raise
      liveness.idle(got)
      continue
    if r == 0:
      return got  # EOF
    got += r
    if liveness is not None:
      liveness.progress(r)
  return got


def _recv_exact(sock: socket.socket, n: int, liveness=None):
  """n bytes as a bytearray (writable — OOB array views alias it), or
  None on clean EOF."""
  buf = bytearray(n)
  got = _recv_into(sock, memoryview(buf), n, liveness)
  if got == 0:
    return None  # clean EOF
  if got < n:
    return None  # EOF mid-read; callers map non-header Nones to errors
  return buf


def _sendall_bounded(sock: socket.socket, data, stall_secs: float,
                     beat=None) -> None:
  """sendall with a NO-PROGRESS deadline, for sockets in timeout mode:
  a live-but-slow peer keeps the transfer going chunk by chunk (each
  successful send resets the clock — a big snapshot over a thin pipe
  is fine), while a blackholed/non-reading peer whose TCP window
  filled makes no progress and aborts with `_SendStall` instead of
  wedging the sending thread forever."""
  view = memoryview(data)
  last_progress = time.monotonic()
  while view.nbytes:
    try:
      sent = sock.send(view)
    except socket.timeout:
      if beat is not None:
        beat()
      if time.monotonic() - last_progress > stall_secs:
        raise _SendStall(
            f'send made no progress for more than {stall_secs}s '
            f'({view.nbytes} byte(s) unsent)')
      continue
    view = view[sent:]
    last_progress = time.monotonic()


def _recv_msg(sock: socket.socket, liveness=None, crc_ctx=None):
  """One message (either frame kind), or None on clean EOF.

  OOB frames recv each array buffer straight into its own
  UNINITIALIZED storage (np.empty + recv_into): one 2.11 MB unroll
  used to land in a zero-filled bytearray first — ~95 µs of memset
  holding the GIL per message, one of the two per-message costs that
  kept multi-connection ingest from scaling (round 6).

  `crc_ctx` (v7 CRC-negotiated connections): the computed CRC over
  every frame piece and the 4-byte wire trailer land on the context;
  the CALLER compares (a mismatched unroll earns a benign 'corrupt'
  reply, not a drop). The trailer read happens inside the in_frame
  window — a peer stalling mid-trailer is still a mid-frame stall."""
  header = _recv_exact(sock, _LEN.size, liveness)
  if header is None:
    return None
  # The discard ledger resets the moment a new header lands — BEFORE
  # the length sanity check below can raise, or an oversized-length
  # quarantine would charge the PREVIOUS (successfully committed)
  # frame's byte count to the discard accounting.
  if liveness is not None:
    liveness.frame_bytes = _LEN.size
  (length,) = _LEN.unpack(header)
  if length > _MAX_MSG:
    raise ValueError(f'message length {length} exceeds bound')
  if crc_ctx is not None:
    crc_ctx.reset()
  if liveness is not None:
    # The frame has begun: from here to return, peer silence past the
    # stall window is a half-open MID-frame stall — the flag spans
    # every sub-frame read, so the deadline cannot reset at
    # _recv_exact boundaries.
    liveness.in_frame = True
  try:
    msg = _recv_msg_body(sock, length, liveness, crc_ctx)
    if crc_ctx is not None:
      trailer = _recv_exact(sock, _CRC.size, liveness)
      if trailer is None:
        raise ConnectionError('EOF mid-message (CRC trailer)')
      crc_ctx.wire = _CRC.unpack(trailer)[0]
    return msg
  finally:
    if liveness is not None:
      liveness.in_frame = False


def _recv_msg_body(sock: socket.socket, length: int, liveness,
                   crc_ctx=None):
  def feed(data):
    if crc_ctx is not None:
      crc_ctx.update(data)
    return data

  tag = _recv_exact(sock, 1, liveness)
  if tag is None:
    raise ConnectionError('EOF mid-message')
  feed(tag)
  kind = tag[0]
  if kind == _FRAME_PLAIN:
    payload = _recv_exact(sock, length - 1, liveness)
    if payload is None:
      raise ConnectionError('EOF mid-message')
    return pickle.loads(memoryview(feed(payload)))
  if kind == _FRAME_OOB:
    head_len = _OOB_META.size
    head = _recv_exact(sock, head_len, liveness)
    if head is None:
      raise ConnectionError('EOF mid-message')
    nbufs, skel_len = _OOB_META.unpack(feed(head))
    # Bound the header-derived sizes by the ALREADY-validated frame
    # length BEFORE allocating or recv'ing anything sized by them: a
    # corrupt peer can put 2^32-1 in either meta field independently
    # of `length`, and the consistency check below runs too late to
    # stop a ~38 GB table allocation.
    if 1 + head_len + skel_len + _OOB_BUFLEN.size * nbufs > length:
      raise ValueError(
          f'OOB header inconsistent with frame length {length}: '
          f'{nbufs} buffers, skeleton {skel_len}')
    table = _recv_exact(sock, skel_len + _OOB_BUFLEN.size * nbufs,
                        liveness)
    if table is None:
      raise ConnectionError('EOF mid-message')
    view = memoryview(feed(table))
    skeleton = view[:skel_len]
    sizes = [_OOB_BUFLEN.unpack_from(view,
                                     skel_len + _OOB_BUFLEN.size * i)[0]
             for i in range(nbufs)]
    consumed = (1 + head_len + len(table) + sum(sizes))
    if consumed != length:
      raise ValueError(
          f'OOB frame length mismatch: parsed {consumed} of {length}')
    buffers = []
    for size in sizes:
      buf = memoryview(np.empty(int(size), np.uint8))
      if _recv_into(sock, buf, int(size), liveness) < size:
        raise ConnectionError('EOF mid-message')
      buffers.append(feed(buf))
    return pickle.loads(skeleton, buffers=buffers)
  raise ValueError(f'unknown frame kind {kind}')


class LearnerShutdown(Exception):
  """The learner announced a CLEAN shutdown ('bye' frame): end of
  training, not a crash — actors must exit instead of reconnecting."""


class ContractMismatch(RuntimeError):
  """The learner rejected this actor host's handshake: the config/
  signature the actor offered does not match the learner's."""


class ProtocolError(RuntimeError):
  """The peer sent bytes this protocol version cannot parse — almost
  always a version-skewed peer (e.g. a pre-v4 role whose frames are
  untagged). Terminal: retrying against the same peer cannot succeed,
  so actors surface this instead of burning their reconnect window."""


class SessionEpochMismatch(ConnectionError):
  """The learner refused an unroll stamped with a FOREIGN session
  epoch ('stale_epoch' reply): this client's handshake belongs to a
  learner incarnation that no longer exists. A ConnectionError on
  purpose — the reconnect path (full re-handshake, fresh epoch +
  params) is exactly the right response."""


class UnrollCorrupt(RuntimeError):
  """The learner's v7 CRC check refused this unroll ('corrupt' reply):
  the bytes that arrived are not the bytes that were sent. The
  connection is FINE (the reply proves it) — the pump re-sends the
  same unroll once; a second refusal for the same unroll means the
  corruption is on this host's own path (NIC/RAM) and the host
  quarantines itself instead of feeding the learner garbage."""

  def __init__(self, message: str, crc: Optional[int] = None):
    super().__init__(message)
    self.crc = crc


class CrcProbation:
  """Client-side CRC self-quarantine ladder, with a probation rung
  (round 15). PR 9 made a double CRC refusal of the same unroll
  terminal — the host took itself out of the fleet for good, so the
  controller's grow-fleet move had nothing to reclaim on the remote
  side. The rehabilitation path mirrors the fleet-slot probation:

    refusal #1 of an unroll  -> RESEND (wire noise; at-least-once)
    refusal #2 (same unroll) -> PROBE, once per run: cool down
                                `cooldown_secs`, then re-send the
                                SAME unroll as a single probe
    probe refused (or a later unroll double-refused after the
    probation was spent)     -> QUARANTINE (terminal, as before)
    probe acked              -> recovered; the host keeps feeding

  Pure decision state (no I/O) so the ladder is unit-testable; the
  pump owns the sleep and the sends. Counters feed the
  INTEGRITY_REPORT line chaos.py and operators grep."""

  RESEND = 'resend'
  PROBE = 'probe'
  QUARANTINE = 'quarantine'

  def __init__(self, cooldown_secs: float = 30.0):
    self.cooldown_secs = max(float(cooldown_secs), 0.0)
    self.crc_resends = 0
    self.probations = 0
    self.recoveries = 0
    self._probation_used = False
    self._probe_pending = False
    self._resent = False  # current unroll already re-sent once?

  def next_unroll(self):
    """A new unroll is being sent: the per-unroll resend budget
    resets (the probation budget is per-RUN and does not)."""
    self._resent = False

  def on_refusal(self) -> str:
    """The learner's CRC refused the current unroll — what now?"""
    if not self._resent:
      self._resent = True
      self.crc_resends += 1
      return self.RESEND
    if self._probe_pending or self._probation_used:
      self._probe_pending = False  # the probe chapter is closed
      return self.QUARANTINE
    self._probation_used = True
    self._probe_pending = True
    self.probations += 1
    return self.PROBE

  def on_ack(self) -> bool:
    """An unroll was accepted; True when it was the probation probe
    (the host just recovered instead of quarantining)."""
    if self._probe_pending:
      self._probe_pending = False
      self.recoveries += 1
      return True
    return False


class ParamsCorrupt(RuntimeError):
  """A fetched param snapshot failed its content digest: the blob the
  learner published is not the tree the learner digested at publish
  time (host-memory rot between device_get and serialization — the
  frame CRC is self-consistent, only the digest can see this). The
  snapshot must NOT be installed; the caller keeps its current params
  and refetches on backoff (a corrupt blob stays corrupt until the
  next publish)."""

  def __init__(self, message: str, version: Optional[int] = None):
    super().__init__(message)
    self.version = version


class Backoff:
  """Capped exponential backoff with FULL jitter for retry loops.

  The fixed `time.sleep(0.3)` the connect/reconnect loops used to run
  meant a learner restart got the whole actor fleet back in lockstep:
  every host lost its connection at the same instant, so every host
  retried at the same instant, forever 0.3 s apart — a thundering herd
  against a listener with a finite accept backlog. Full jitter
  (delay ~ U[0, min(cap, base·2^attempt)]) decorrelates the fleet
  while still backing off a learner that stays down.

  The client loops construct a FRESH Backoff per incident (each
  connect/reconnect window starts from the fast end by construction);
  `reset()` exists for callers that hold one instance across
  incidents. `rng` is injectable for deterministic tests.
  """

  def __init__(self, base: float = 0.2, cap: float = 5.0, rng=None):
    if base <= 0 or cap <= 0:
      raise ValueError('base and cap must be > 0')
    self._base = base
    self._cap = cap
    self._rng = rng if rng is not None else random
    self._attempt = 0

  @property
  def attempt(self) -> int:
    return self._attempt

  def next_delay(self) -> float:
    ceiling = min(self._cap, self._base * (2 ** self._attempt))
    # Attempts stop growing once the cap is the binding term (2^n
    # would overflow floats long before a long outage ends).
    if self._base * (2 ** self._attempt) < self._cap:
      self._attempt += 1
    return self._rng.uniform(0.0, ceiling)

  def sleep(self) -> float:
    delay = self.next_delay()
    time.sleep(delay)
    return delay

  def reset(self) -> None:
    self._attempt = 0


# Bumped whenever the wire format or the handshake contract changes.
# v3: fields gained num_levels (level-id range validation) and the
# contract gained signature_tree (server-side fast-path validation).
# v4: tagged frames — unrolls ship as pickle-5 skeleton + out-of-band
# raw buffers instead of one inline pickle (~530 µs/unroll of pure
# copying removed from the hot ingest path).
# v5: the param lane — clients fetch weight snapshots over a SECOND
# connection opened with 'hello_params' (served by the chunked
# non-blocking publisher, isolating blob traffic from unroll acks);
# 'get_params' on the trajectory lane stays answered for the
# handshake and protocol-level tests.
# v5 extension (round 9, no version bump — compatible both ways):
# 'unroll' frames MAY carry a third element, the params version the
# client currently acts with; servers running a staleness window
# (--max_unroll_staleness) answer too-stale unrolls with a benign
# ('stale', current_version) reply instead of an ack. Old servers
# ignore the extra element; old clients read 'stale' as an ack whose
# version triggers exactly the refetch the reply intends.
# v6 (round 11): connection liveness + the hard-crash restart story,
# v5-COMPATIBLE both ways (the handshake accepts any protocol in
# _COMPATIBLE_PROTOCOLS and negotiates the new machinery OFF for v5
# peers — the same extension pattern as the round-9 staleness field):
#   - params replies carry a 4th element, the server-info dict
#     {'protocol', 'session_epoch', 'heartbeat_secs',
#     'idle_timeout_secs'} (old clients index [0..2] and never see
#     it); 'hello' MAY carry a 3rd element, the client-info dict
#     {'epoch': last-known session epoch} — a restarted learner tells
#     REATTACHING clients (prior epoch != current) from fresh ones and
#     records the fleet re-attach latency.
#   - 'ping' on either lane answers ('pong', current_version) — the
#     application-level heartbeat idle clients send so the server's
#     idle reaper can tell live-but-quiet from half-open/dead (and an
#     idle fleet still learns about new publishes from the pong).
#   - ('busy',) keepalives: while an ack is held back by buffer
#     backpressure the server emits 'busy' at the heartbeat cadence to
#     v6 peers — a slow learner stays tellable from a dead one, so the
#     client's I/O deadline can be tight without breaking the
#     backpressure contract. v6 clients skip them; v5 peers never get
#     them.
#   - 'unroll' frames MAY carry a 4th element, the session epoch the
#     client handshook under; a server seeing a FOREIGN epoch refuses
#     with ('stale_epoch', current_epoch) — the client re-handshakes.
#     Structurally unreachable over plain TCP (the connection dies
#     with the learner process), but it makes "zero stale-epoch
#     unrolls accepted across a restart" an asserted invariant instead
#     of an assumption (chaos.py run_partition_storm).
# v7 (round 12): end-to-end payload integrity, v5/v6-COMPATIBLE both
# ways (the same negotiation pattern — every v7 mechanism turns OFF
# per connection for older peers):
#   - the client-info dict MAY carry {'crc': True, 'crc_algo': <name>}
#     in the hello; a v7 server running wire_crc answers with
#     {'crc': True, 'crc_algo': ...} in its server-info — from the
#     NEXT frame on, every frame BOTH ways on that connection carries
#     a 4-byte CRC32C trailer after the payload (the length prefix
#     still counts tag+payload only). Algorithms must MATCH (a host
#     without the crc32c extension falls back to zlib-crc32;
#     cross-algorithm pairs negotiate the check off instead of
#     reporting phantom corruption).
#   - an unroll whose trailer does not match earns ('corrupt',
#     computed_crc) — verified by the ingest worker BEFORE the buffer
#     put, counted in stats()['wire_crc_rejected'], connection kept.
#     The client re-sends the unroll ONCE; a second corrupt reply for
#     the same unroll means the damage is on THIS host's path (NIC/
#     RAM) and the client quarantines itself (docs/RUNBOOK.md §9).
#   - params replies' server-info carries 'params_digest' — a content
#     CRC of the (wire-form) snapshot computed at publish time. The
#     client verifies it BEFORE update_params installs anything; a
#     mismatch (corruption upstream of frame serialization, where the
#     frame CRC is self-consistent) rejects the install without a
#     version bump, and the client's next 'get_params' carries a
#     {'digest_rejected': version} notice so the learner's
#     publish_digest_rejected counter sees the fleet-side refusal.
#   - 'hello_params' MAY carry the same client-info dict; the param
#     lane then appends the cached trailer to its blob replies and
#     verifies trailers on requests.
# v8 (round 13): per-unroll trace spans, v5/v6/v7-COMPATIBLE both
# ways (the same negotiation pattern — everything turns OFF per
# connection for older peers):
#   - the server-info dict carries 'trace' (a server-wide fact: the
#     learner runs a telemetry tracer); a v8 client seeing it stamps
#     each unroll frame with a 5th element — the compact trace
#     context (telemetry.make_trace: actor id, unroll seq, session
#     epoch, behaviour params version, [hop, wall_time] stamps). Old
#     servers never index it; old clients never send it.
#   - the trace context MAY carry 'pi' = [version, wall_time], the
#     client's most recent params-install event — how the
#     publish→installed-at-actor hop reaches the learner's
#     traces.jsonl without a dedicated side channel (the same
#     piggyback pattern as the v7 digest_rejected notice).
#   - 'stats' on the trajectory lane answers ('stats', {...}) — the
#     on-demand fleet telemetry request: the learner's unified
#     metrics-registry snapshot plus its ingest stats, served over
#     the existing control lane so operators (and tests) can read the
#     single source of truth remotely.
# v9 (round 20): elastic pod membership, v5..v8-COMPATIBLE both ways:
#   - the 'hello' client-info dict MAY carry 'host' — a stable host
#     identity string. The server keys its membership ledger on it:
#     a hello for an unknown host records a host_joined event, and
#     the connection's unwind records host_left with the reason
#     (drain/reaped/lost). Old servers ignore the extra key; old
#     clients simply never appear in the ledger (membership events
#     degrade to nothing, exactly like heartbeats on a v5 peer).
#   - 'leave' on the trajectory lane announces a DELIBERATE exit
#     (SIGTERM drain): ('leave', info) → ('bye_ack',). The server
#     marks the connection draining so its unwind records
#     host_left(reason='drain') instead of 'lost'. Old servers answer
#     ('error', unknown kind) — the draining client tolerates that
#     and closes anyway (the exit is best-effort-announced, never
#     gated on the server's vintage).
# v10 (round 21): multi-tenant serving plane, v5..v9-COMPATIBLE both
# ways (the same negotiation pattern — everything turns OFF per
# connection for older peers):
#   - blob kind 'params_int8': with --publish_codec=int8 the param
#     lane serves absmax-quantized snapshots (runtime/codec.py
#     Int8Leaf trees — ~4x smaller than f32 on the wire; the v7
#     params_digest covers the WIRE form, q and scales). Negotiated
#     PER SUBSCRIBER: 'hello_params' client-info now always carries
#     'protocol', and a v<=9 subscriber keeps receiving the cached
#     bf16 blob — both encodings are built once per publish, never
#     per subscriber.
#   - 'infer' on the trajectory lane: ('infer', payload) → ('infer_ok',
#     result, notice) serves one carry-passing inference batch from
#     the learner's resident version table (InferenceServer
#     .serve_remote — the TorchBeast decoupled-serving seam,
#     arXiv:1910.03552) when the learner attached a serving fn;
#     ('error', 'serving not attached') otherwise. The notice dict
#     carries {'draining': bool} so routers (runtime/routing.py)
#     drain a replica's share BEFORE the connection dies. Old servers
#     answer ('error', unknown kind) — the router treats that peer as
#     not routable, exactly like a v<=9 handshake.
PROTOCOL_VERSION = 10

# Handshakes accepted without negotiation failure: v5 peers get the
# round-9 wire exactly (no heartbeats, no busy keepalives, no epoch
# checks), v6 peers the round-11 wire (no CRC trailers, no digest
# checks), v7 peers the round-12 wire (no trace stamps), v8 peers the
# round-13 wire (no membership ledger entries), v9 peers the round-20
# wire (bf16 param blobs, no routed inference); everything else about
# the lanes is unchanged.
_COMPATIBLE_PROTOCOLS = (5, 6, 7, 8, 9, 10)

# Bound on the reader→worker handoff queue. The request→reply
# lockstep already implies at most one in-flight unroll per live
# connection, but that bound is a CLIENT property — a misbehaving
# peer pipelining unrolls without awaiting acks could otherwise grow
# the handoff queue without limit. A blocked reader is the correct
# backpressure: the peer's sendall stalls against the unread socket.
_INGEST_QUEUE_DEPTH = 256


def _is_signature_leaf(x) -> bool:
  """Leaves of a signature tree are (shape-tuple, dtype-name) pairs —
  they must stay leaves under tree_flatten, not flatten as tuples."""
  return (isinstance(x, tuple) and len(x) == 2
          and isinstance(x[1], str))


def trajectory_contract(config, agent, num_actions: int):
  """The wire contract both roles derive from their own config: the
  config fields the trajectory semantics depend on, plus the
  shape/dtype signature of one unroll.

  The reference's transport was graph-typed end to end — the shared
  FIFOQueue declares dtypes/shapes at construction (reference:
  experiment.py ≈L462–470 throwaway-graph spec capture) and py_process
  enforces `_tensor_specs`. This is that role for the TCP wire: the
  server compares the client's offered contract at `hello` and rejects
  mismatches naming the offending fields; each received unroll is then
  validated against the agreed signature before it can reach the
  buffer (VERDICT r2 Missing #2).

  `fields` carries semantic knobs even when they don't change shapes
  (`num_action_repeats` corrupts frame accounting silently; `torso` /
  `compute_dtype` make the served param snapshots unusable), so skew
  fails at connect instead of mid-training.
  """
  import jax
  from scalable_agent_tpu.envs import factory
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.structs import (
      ActorOutput, AgentOutput, StepOutput, StepOutputInfo)

  t1 = config.unroll_length + 1
  h, w = config.height, config.width

  def leaf(shape, dtype):
    return (tuple(int(s) for s in shape), np.dtype(dtype).name)

  # Core-state leaves come from the agent itself (the actor ships
  # `agent.initial_state(1)`-structured carries), heads are f32 by
  # the model contract (models/agent.py casts logits/baseline).
  state_sig = jax.tree_util.tree_map(
      lambda x: leaf(np.shape(x), np.asarray(jax.device_get(x)).dtype),
      agent.initial_state(1))
  example = ActorOutput(
      level_name=leaf((), np.int32),
      agent_state=state_sig,
      env_outputs=StepOutput(
          reward=leaf((t1,), np.float32),
          info=StepOutputInfo(
              episode_return=leaf((t1,), np.float32),
              episode_step=leaf((t1,), np.int32)),
          done=leaf((t1,), np.bool_),
          observation=(leaf((t1, h, w, 3), np.uint8),
                       leaf((t1, MAX_INSTRUCTION_LEN), np.int32))),
      agent_outputs=AgentOutput(
          action=leaf((t1,), np.int32),
          policy_logits=leaf((t1, int(num_actions)), np.float32),
          baseline=leaf((t1,), np.float32)))
  paths = jax.tree_util.tree_flatten_with_path(
      example, is_leaf=_is_signature_leaf)[0]
  signature = {jax.tree_util.keystr(p): v for p, v in paths}
  fields = {
      'env_backend': config.env_backend,
      # Level list must agree: unroll level ids index the learner's
      # list (and PopArt's per-task statistics) by position.
      'level_name': config.level_name,
      # Unroll level ids must index that list: an out-of-range id
      # crashes (or for negative ids silently ALIASES) the learner's
      # per-level episode stats and PopArt per-task statistics, so
      # each received unroll is range-checked against this.
      'num_levels': len(factory.level_names(config)),
      'height': int(config.height),
      'width': int(config.width),
      'unroll_length': int(config.unroll_length),
      'num_actions': int(num_actions),
      'num_action_repeats': int(config.num_action_repeats),
      'use_instruction': bool(config.resolved_use_instruction),
      'torso': config.torso,
      'compute_dtype': config.compute_dtype,
      # Shape-invisible but distribution/structure-changing knobs:
      # skew here silently shifts the data distribution (sticky
      # actions, fake-env episode length) or breaks the actor's use
      # of fetched params far from the cause (popart/pixel-control
      # change the param tree).
      'sticky_action_prob': float(config.sticky_action_prob),
      'episode_length': int(config.episode_length),
      'use_popart': bool(config.use_popart),
      'pixel_control_cost': float(config.pixel_control_cost),
  }
  # signature_tree carries the SAME leaves as `signature` but in pytree
  # form: the server flattens it once per connection into a
  # (treedef, flat leaves) pair so per-unroll validation compares
  # leaf-by-leaf instead of re-deriving a keystr dict per unroll
  # (measured ~12% of ingest throughput, VERDICT r3 W4). The keystr
  # dict stays the wire-compared form (order-insensitive, and its keys
  # name offending leaves in mismatch messages).
  return {'protocol': PROTOCOL_VERSION, 'fields': fields,
          'signature': signature, 'signature_tree': example}


def contract_mismatch_message(expected, offered) -> Optional[str]:
  """Human-readable diff of two contracts, or None when they agree.
  Names every offending field/leaf (the whole point — the raw
  failure used to surface nowhere near the offending host)."""
  if offered is None:
    return ('actor sent a legacy hello with no contract (protocol < '
            f'{PROTOCOL_VERSION}); upgrade the actor host')
  problems = []
  # v6 is v5-compatible: a peer offering any protocol in the
  # compatible set handshakes fine (the v6-only machinery — heartbeat
  # pings, busy keepalives, epoch stamps — negotiates OFF per
  # connection for v5 peers); anything else is a true skew.
  offered_protocol = offered.get('protocol')
  if (offered_protocol != expected['protocol']
      and offered_protocol not in _COMPATIBLE_PROTOCOLS):
    problems.append(f"protocol: learner={expected['protocol']} "
                    f"actor={offered_protocol}")
  for key in sorted(set(expected['fields']) |
                    set(offered.get('fields', {}))):
    e = expected['fields'].get(key, '<missing>')
    o = offered.get('fields', {}).get(key, '<missing>')
    if e != o:
      problems.append(f'config.{key}: learner={e!r} actor={o!r}')
  exp_sig = expected['signature']
  off_sig = offered.get('signature', {})
  for key in sorted(set(exp_sig) | set(off_sig)):
    e, o = exp_sig.get(key), off_sig.get(key)
    if e != o:
      problems.append(f'unroll{key}: learner={e} actor={o}')
  if not problems:
    return None
  return ('config/signature mismatch between learner and actor host: '
          + '; '.join(problems))


def _value_violations(unroll, fields) -> List[str]:
  """Range checks on a structurally valid unroll: values a corrupt
  actor could ship that blow up (actions — driver.py's bincount) or
  silently corrupt (level ids — per-level episode stats and PopArt
  per-task statistics index the learner's level list by position;
  negative ids ALIAS another level's slot) the learner's stats path."""
  problems = []
  num_actions = fields['num_actions']
  actions = np.asarray(unroll.agent_outputs.action)
  if actions.size and (actions.min() < 0 or
                       actions.max() >= num_actions):
    problems.append(
        f'actions out of range [0, {num_actions}): '
        f'min={actions.min()} max={actions.max()}')
  num_levels = fields.get('num_levels')
  if num_levels is not None:
    level = int(np.asarray(unroll.level_name))
    if not 0 <= level < num_levels:
      problems.append(
          f'level_name {level} out of range [0, {num_levels})')
  return problems


def unroll_violations(unroll, contract) -> List[str]:
  """Validate one received unroll's leaves against the agreed
  signature (+ action/level ranges, so a corrupt actor cannot blow up
  or alias the learner's stats path). Returns problems ([] = clean).

  This is the slow, leaf-NAMING path (keystr diff); the server's hot
  loop runs `FastUnrollValidator` and only falls back here to produce
  the error message once something already failed."""
  import jax
  signature = contract['signature']
  try:
    paths = jax.tree_util.tree_flatten_with_path(unroll)[0]
    got = {jax.tree_util.keystr(p): (tuple(np.shape(x)),
                                     np.asarray(x).dtype.name)
           for p, x in paths}
  except Exception as e:  # not even a pytree of arrays
    return [f'unroll is not a valid trajectory pytree: {e!r}']
  problems = []
  for key in sorted(set(signature) | set(got)):
    e, o = signature.get(key), got.get(key)
    if e is None:
      problems.append(f'unexpected leaf unroll{key}={o}')
    elif o is None:
      problems.append(f'missing leaf unroll{key} (expected {e})')
    elif e != o:
      problems.append(f'unroll{key}: expected {e}, got {o}')
  if not problems:
    problems = _value_violations(unroll, contract['fields'])
  return problems


class FastUnrollValidator:
  """Per-connection precompiled validation (VERDICT r3 W4).

  The expected signature is static per connection, so the treedef and
  the flat (shape, dtype-name) list are computed ONCE here; each unroll
  then costs one `tree_flatten` + a leaf-by-leaf compare instead of
  `tree_flatten_with_path` + keystr + dict building per unroll
  (measured ~12% of ingest throughput). Any failure falls back to
  `unroll_violations` for the leaf-naming diff — the slow path only
  runs when an error message is about to be produced anyway.

  Contracts from protocol < 3 peers lack `signature_tree`; the
  validator then just delegates to the slow path (correctness first)."""

  def __init__(self, contract):
    import jax
    self._contract = contract
    self._fast = None
    tree = contract.get('signature_tree')
    if tree is not None:
      leaves, treedef = jax.tree_util.tree_flatten(
          tree, is_leaf=_is_signature_leaf)
      self._fast = (treedef, leaves)

  def __call__(self, unroll) -> List[str]:
    if self._fast is None:
      return unroll_violations(unroll, self._contract)
    import jax
    treedef, expected = self._fast
    try:
      leaves, got_def = jax.tree_util.tree_flatten(unroll)
      if got_def == treedef:
        for (eshape, edtype), x in zip(expected, leaves):
          if (np.shape(x) != eshape
              or np.asarray(x).dtype.name != edtype):
            break
        else:
          return _value_violations(unroll, self._contract['fields'])
    except Exception:
      pass  # fall through: the slow path names the problem
    return unroll_violations(unroll, self._contract)


class _Conn:
  """One actor connection: socket + send lock (worker threads and
  close()'s 'bye' frame must not interleave writes mid-message).

  Liveness fields (round 11): `last_recv` is the reaper's idle clock
  (refreshed on EVERY received byte, so a trickling half-open peer is
  distinguishable from a live slow one); `protocol`/`heartbeat` are
  negotiated at hello (v5 peers get no busy keepalives and no
  heartbeat-miss accounting); `reaped` marks a reaper-initiated close
  so the reader's unwind logs/counts it once. When `send_stall_secs`
  is set (liveness mode — the socket runs short poll timeouts), every
  send path is progress-bounded: a non-reading peer aborts the send
  with `_SendStall` instead of wedging the sending thread."""

  # Lock discipline (round 18, guarded-by lint): the in-flight count
  # is the only _Conn field shared between the reader, the worker
  # pool, and the reaper; send_lock serializes writers on the socket.
  inflight: guarded_by('inflight_lock')

  def __init__(self, sock: socket.socket, addr=None,
               send_stall_secs: Optional[float] = None,
               base_timeout: Optional[float] = None):
    self.sock = sock
    self.addr = addr
    self.send_lock = make_lock('remote._Conn.send_lock')
    self.send_stall_secs = send_stall_secs
    # The socket timeout try_send must RESTORE (None = blocking legacy
    # mode; the reader's poll interval in liveness mode — restoring
    # None there would silently turn the reader's bounded recv
    # back into an unbounded one).
    self.base_timeout = base_timeout
    # Per-connection ingest ledger (observability: the driver reports
    # unrolls/sec per connection from deltas of these; stale
    # rejections are counted per connection so one starved/lagging
    # host is tellable from a uniformly stale fleet).
    self.unrolls = 0
    self.stale_rejected = 0
    # Liveness state.
    self.last_recv = time.monotonic()
    self.protocol = 5          # until a hello says otherwise
    self.heartbeat = False     # negotiated: v6 peer + server heartbeat
    self.hb_missed = False     # current silence window already counted
    self.reaped = False        # reaper-initiated close in progress
    # v7 payload integrity, negotiated at hello: when True, every
    # frame BOTH ways on this connection carries the CRC32C trailer
    # (the hello reply itself is pre-negotiation and ships per the
    # conn's PRIOR state, so a re-handshake stays parseable).
    self.crc = False
    self.crc_rejected = 0      # unrolls refused with ('corrupt', crc)
    # v9 elastic membership: the host identity the hello's client-info
    # carried (None for pre-v9 peers — they never enter the ledger),
    # and whether a 'leave' announced a deliberate drain (the unwind
    # then records host_left(reason='drain') instead of 'lost').
    self.host_id = None
    self.draining = False
    # Unrolls handed to the worker pool whose ack has not gone out
    # yet. A LOCKSTEP client is silent BY PROTOCOL while its unroll is
    # in flight (it may be parked for minutes behind buffer
    # backpressure) — the reaper and the heartbeat-miss counter must
    # exempt such conns or they would reap/flag protocol-obedient
    # peers exactly when the learner is slowest.
    self.inflight = 0
    self.inflight_lock = make_lock('remote._Conn.inflight_lock')

  def job_started(self):
    with self.inflight_lock:
      self.inflight += 1

  def job_finished(self):
    with self.inflight_lock:
      self.inflight -= 1

  def is_waiting_on_us(self) -> bool:
    with self.inflight_lock:
      return self.inflight > 0

  def _write(self, data) -> None:
    """One bounded-or-legacy write; callers hold send_lock."""
    if self.send_stall_secs is not None:
      _sendall_bounded(self.sock, data, self.send_stall_secs)
    else:
      self.sock.sendall(data)

  def send(self, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    with self.send_lock:
      self._write(_plain_frame(payload, crc=self.crc))

  def send_bytes(self, payload: bytes) -> None:
    """Ship pre-serialized bytes (a cached plain frame): handler
    threads must not re-pickle the whole tree per request."""
    with self.send_lock:
      self._write(_plain_frame(payload, crc=self.crc))

  def send_segments(self, segments,
                    trailer: Optional[bytes] = None) -> None:
    """Ship a pre-built wire frame as its segments (the cached param
    snapshot frame: head + raw buffers) without joining them into one
    giant bytes object first. `trailer`: the frame's cached CRC bytes
    — passed ONLY when this send should carry one (the caller knows
    whether the peer expects v7 trailers on this frame)."""
    with self.send_lock:
      for seg in segments:
        self._write(seg)
      if trailer is not None:
        self._write(trailer)

  def send_oob(self, obj) -> None:
    """Ship `obj` as an out-of-band frame (pickle-5 skeleton + raw
    array buffers — arrays never pass through the pickler): the v10
    routed-inference reply path, whose payload is batch arrays. The
    trailer rides only when this conn negotiated v7 CRC, mirroring
    the cached-blob convention (_make_blob)."""
    segments = _oob_frame_segments(obj)
    trailer = (_CRC.pack(_segments_crc(segments))
               if self.crc else None)
    self.send_segments(segments, trailer)

  def try_send(self, obj, timeout: float = 2.0) -> bool:
    """Bounded best-effort send: never blocks shutdown behind a stuck
    peer (a handler mid-sendall of a large snapshot holds send_lock;
    a non-reading client stalls sendall itself)."""
    if not self.send_lock.acquire(timeout=timeout):
      return False
    try:
      self.sock.settimeout(timeout)
      _send_msg(self.sock, obj, crc=self.crc)
      return True
    except OSError:
      return False
    finally:
      try:
        self.sock.settimeout(self.base_timeout)
      except OSError:
        pass
      self.send_lock.release()


class _ParamLane:
  """The weight fan-out plane: every `hello_params` subscriber socket,
  multiplexed by ONE selector thread with chunked non-blocking sends.

  Why not a thread per subscriber (the r5 design): 8 polling fetchers
  measured the unroll pump at 29.9 unrolls/s against 838.6 alone (ack
  p99 1.18 → 95.8 ms) — each fetch handler monopolizes the core in
  blob-sized `sendall` slices and the tiny acks queue behind up to 8
  of them. Here each ready subscriber advances at most `chunk_bytes`
  per poll round, so the blob plane is one runnable thread with
  bounded GIL holds no matter how many hosts subscribe, and the
  trajectory lane's acks never wait behind a blob mid-send.

  Requests are tiny (`get_params` frames); replies are the server's
  cached per-version blob — the lane never pickles, it only slices
  memoryviews of bytes the publisher already built.
  """

  def __init__(self, blob_fn, chunk_bytes: int = 128 * 1024,
               idle_timeout_secs: float = 0.0,
               watchdog: Optional[ThreadWatchdog] = None):
    # (subscriber protocol) -> (cached frame segments, trailer): the
    # v10 codec negotiation — an int8 publisher still hands v<=9
    # subscribers the cached bf16 blob.
    self._blob_fn = blob_fn
    self._chunk = chunk_bytes
    self._idle_timeout = float(idle_timeout_secs)
    self._watchdog = watchdog
    self._selector = selectors.DefaultSelector()
    self._lock = make_lock('remote._ParamLane._lock')  # adopt vs close
    self._closed = False
    self._blobs_served = 0
    self._bytes_sent = 0
    # Fan-out shrinkage ledger (round 11): EVERY dropped subscriber is
    # counted — a param lane that quietly loses hosts used to be
    # invisible until the fleet's params went uniformly stale.
    self._subs_dropped = 0
    self._subs_reaped = 0   # the idle/half-open subset of the drops
    # Integrity ledger (round 12): digest-rejected notices subscribers
    # attach to their retry fetches — the learner-side visibility of
    # "a corrupt publish was refused fleet-wide" — and requests whose
    # own v7 trailer failed (a corrupting subscriber loses its sub).
    self._digest_rejected = 0
    self._req_crc_dropped = 0
    # Self-pipe: adopt()/close() must wake a parked select().
    self._wake_r, self._wake_w = socket.socketpair()
    self._wake_r.setblocking(False)
    self._selector.register(self._wake_r, selectors.EVENT_READ, None)
    self._pending_adopts: List[socket.socket] = []
    self._thread = threading.Thread(target=self._loop,
                                    name='param-lane', daemon=True)
    self._thread.start()

  class _Sub:
    """Per-subscriber state: request parse buffer + outgoing chunks."""

    def __init__(self, sock, crc: bool = False, proto: int = 5):
      self.sock = sock
      self.crc = crc  # v7: trailers on replies, verified on requests
      self.proto = proto  # v10: which cached blob encoding it gets
      self.rbuf = bytearray()
      self.out: List[memoryview] = []  # remaining reply bytes
      self.last_recv = time.monotonic()  # idle-reaping clock

  def adopt(self, sock: socket.socket, crc: bool = False,
            proto: int = 5) -> bool:
    """Hand a connected socket to the lane (called from the accept
    handler once the peer said 'hello_params'). False if closing.
    `crc`: the hello_params negotiation — this subscriber's replies
    carry the blob's cached v7 trailer and its requests are
    trailer-verified. `proto`: the subscriber's offered protocol —
    selects which cached blob encoding it fetches (v10: int8)."""
    with self._lock:
      if self._closed:
        return False
      self._pending_adopts.append((sock, crc, proto))
    try:
      self._wake_w.send(b'x')
    except OSError:
      pass
    return True

  def stats(self):
    with self._lock:
      return {'blobs': self._blobs_served, 'bytes': self._bytes_sent,
              'subs_dropped': self._subs_dropped,
              'subs_reaped': self._subs_reaped,
              'digest_rejected': self._digest_rejected,
              'req_crc_dropped': self._req_crc_dropped}

  def _drop(self, sub, reaped: bool = False):
    with self._lock:
      self._subs_dropped += 1
      if reaped:
        self._subs_reaped += 1
    try:
      self._selector.unregister(sub.sock)
    except (KeyError, ValueError):
      pass
    sub.sock.close()

  def _queue_segments(self, sub, segments):
    """Queue a pre-built wire frame (its segments verbatim)."""
    sub.out.extend(memoryview(s) for s in segments)
    self._selector.modify(sub.sock,
                          selectors.EVENT_READ | selectors.EVENT_WRITE,
                          sub)

  def _queue_reply(self, sub, payload: bytes):
    header = _LEN.pack(len(payload) + 1) + bytes((_FRAME_PLAIN,))
    if sub.crc:
      self._queue_segments(sub, (header, payload, _CRC.pack(
          integrity.crc_bytes(payload, integrity.crc_bytes(
              bytes((_FRAME_PLAIN,)))))))
    else:
      self._queue_segments(sub, (header, payload))

  def _on_readable(self, sub) -> bool:
    """Drain request bytes; False = connection is gone."""
    try:
      data = sub.sock.recv(4096)
    except BlockingIOError:
      return True
    except OSError:
      return False
    if not data:
      return False
    sub.last_recv = time.monotonic()
    sub.rbuf += data
    while True:
      if len(sub.rbuf) < _LEN.size:
        return True
      (length,) = _LEN.unpack_from(sub.rbuf)
      if length > 1 << 20:  # param requests are tiny frames
        log.warning('param lane: oversized request frame (%d bytes); '
                    'dropping subscriber', length)
        return False
      # v7 subscribers append a 4-byte CRC trailer to every request.
      want = _LEN.size + length + (_CRC.size if sub.crc else 0)
      if len(sub.rbuf) < want:
        return True
      frame = bytes(sub.rbuf[_LEN.size:_LEN.size + length])
      if sub.crc:
        (wire_crc,) = _CRC.unpack_from(sub.rbuf, _LEN.size + length)
        if wire_crc != integrity.crc_bytes(frame):
          # A request this tiny failing its CRC means the subscriber's
          # send path corrupts — nothing it asks for can be trusted.
          with self._lock:
            self._req_crc_dropped += 1
          log.warning('param lane: request failed its CRC trailer; '
                      'dropping subscriber')
          return False
      del sub.rbuf[:want]
      try:
        if frame[0] != _FRAME_PLAIN:
          raise ValueError(f'unexpected frame kind {frame[0]}')
        msg = pickle.loads(frame[1:])
        kind = msg[0]
      except Exception as e:  # version-skewed peer: drop just it
        log.warning('param lane: unparseable request (%r); dropping '
                    'subscriber', e)
        return False
      if kind in ('get_params', 'hello_params', 'ping'):
        # hello_params may arrive here when the peer pipelined it with
        # its first fetch; it needs no reply of its own (but a v7 info
        # dict still upgrades the sub's CRC negotiation).
        if kind == 'hello_params' and len(msg) > 1 and \
            isinstance(msg[1], dict):
          sub.crc = bool(msg[1].get('crc')) and \
              msg[1].get('crc_algo') == integrity.CRC_ALGO
          sub.proto = int(msg[1].get('protocol') or sub.proto)
        if kind == 'get_params':
          # v7 retry fetches MAY carry a digest-rejected notice: the
          # subscriber refused to install version N because its
          # content digest failed — the learner-side ledger of a
          # corrupt publish being rejected fleet-wide.
          if len(msg) > 1 and isinstance(msg[1], dict) and \
              msg[1].get('digest_rejected') is not None:
            with self._lock:
              self._digest_rejected += 1
            log.error(
                'param lane: subscriber refused params v%s — content '
                'digest mismatch (corrupt publish); it keeps its '
                'prior snapshot and refetches on backoff',
                msg[1]['digest_rejected'])
          with self._lock:
            self._blobs_served += 1
          segments, trailer = self._blob_fn(sub.proto)
          self._queue_segments(
              sub, tuple(segments) + ((trailer,) if sub.crc else ()))
        elif kind == 'ping':
          # The v6 keepalive: an idle subscriber pings inside the
          # reaping window; the pong keeps the conversation protocol-
          # shaped (and last_recv above already refreshed the clock).
          self._queue_reply(sub, pickle.dumps(
              ('pong',), protocol=pickle.HIGHEST_PROTOCOL))
      else:
        self._queue_reply(sub, pickle.dumps(
            ('error', f'param lane only serves get_params, got '
             f'{kind!r}'), protocol=pickle.HIGHEST_PROTOCOL))
    return True

  def _on_writable(self, sub) -> bool:
    """Send at most one chunk; False = connection is gone."""
    while sub.out:
      view = sub.out[0]
      try:
        sent = sub.sock.send(view[:self._chunk])
      except BlockingIOError:
        return True
      except OSError:
        return False
      with self._lock:
        self._bytes_sent += sent
      if sent < len(view):
        sub.out[0] = view[sent:]
      else:
        sub.out.pop(0)
      # ONE bounded write per poll round: fairness across subscribers
      # and a bounded GIL hold are the whole point of the lane.
      return True
    self._selector.modify(sub.sock, selectors.EVENT_READ, sub)
    return True

  def _loop(self):
    try:
      self._loop_body()
    except Exception:
      # A dead lane must be loud: every subscriber would silently
      # hang on its next fetch otherwise.
      log.exception('param lane died; subscribers will see drops')

  def _loop_body(self):
    while True:
      if self._watchdog is not None:
        self._watchdog.beat('param-lane')
      with self._lock:
        if self._closed:
          return
        adopts, self._pending_adopts = self._pending_adopts, []
      for sock, crc, proto in adopts:
        sock.setblocking(False)
        try:
          self._selector.register(sock, selectors.EVENT_READ,
                                  self._Sub(sock, crc=crc, proto=proto))
        except (KeyError, ValueError, OSError):
          sock.close()
      # Idle/half-open subscriber reaping (round 11): a silent sub
      # past the window is dropped HERE, on the lane thread — selector
      # mutation must never race the select loop. A live v6 client
      # pings inside the window; a sub mid-reply (pending out) is
      # making progress on the write side and is left alone.
      if self._idle_timeout > 0:
        cutoff = time.monotonic() - self._idle_timeout
        stale = [key.data for key in self._selector.get_map().values()
                 if key.data is not None and not key.data.out
                 and key.data.last_recv < cutoff]
        for sub in stale:
          log.warning('param lane: reaping idle subscriber (silent '
                      'for > %.1fs)', self._idle_timeout)
          self._drop(sub, reaped=True)
      for key, events in self._selector.select(timeout=0.5):
        if key.data is None:  # wake pipe
          try:
            self._wake_r.recv(4096)
          except OSError:
            pass
          continue
        sub = key.data
        ok = True
        if events & selectors.EVENT_READ:
          ok = self._on_readable(sub)
        if ok and events & selectors.EVENT_WRITE:
          ok = self._on_writable(sub)
        if not ok:
          self._drop(sub)

  def close(self, graceful: bool = True) -> int:
    """Shut the lane down; returns the join-deadline-missed thread
    count (0 or 1 — the selector thread), which the owning server
    folds into its `unjoined_threads` stat instead of dropping.

    graceful=True answers every live subscriber with a ('bye',) frame
    before the close (best-effort, non-blocking — the sockets are
    already non-blocking): a subscriber parked in recv gets a clean
    LearnerShutdown instead of a raw EOF it must diagnose. Crash-path
    closes (graceful=False) skip it — actors must keep their reconnect
    window."""
    with self._lock:
      if self._closed:
        return 0
      self._closed = True
    try:
      self._wake_w.send(b'x')
    except OSError:
      pass
    self._thread.join(timeout=5.0)
    unjoined = 1 if self._thread.is_alive() else 0
    if unjoined:
      # The leaked thread still OWNS the selector and its sockets: a
      # teardown here would race its select loop (use-after-close on
      # the selector, corrupted mid-chunk replies). Leak the lot with
      # the thread — counted and named; the process is going away.
      log.warning('param lane close: selector thread missed the join '
                  'deadline and leaks as a daemon (selector/sockets '
                  'leaked with it)')
      return unjoined
    if graceful:
      bye = pickle.dumps(('bye',), protocol=pickle.HIGHEST_PROTOCOL)
      frame = (_LEN.pack(len(bye) + 1) + bytes((_FRAME_PLAIN,)) + bye)
      frame_crc = frame + _CRC.pack(
          integrity.crc_bytes(frame[_LEN.size:]))
      for key in list(self._selector.get_map().values()):
        # Only subscribers with NO partially-sent reply: appending the
        # bye where a client expects the rest of a chunked params
        # frame would corrupt the stream mid-message (that sub gets
        # the EOF path instead — indistinguishable from a crash, which
        # its half-fetched state already is).
        if key.data is not None and not key.data.out:
          try:
            # v7 subs expect a trailer on every frame, the bye too.
            key.fileobj.send(frame_crc if key.data.crc else frame)
          except OSError:
            pass
    for key in list(self._selector.get_map().values()):
      if key.data is not None:
        key.fileobj.close()
    self._selector.close()
    self._wake_r.close()
    self._wake_w.close()
    if self._watchdog is not None:
      self._watchdog.unregister('param-lane')
    return unjoined


class TrajectoryIngestServer:
  """Learner-side: accepts remote-actor connections, lands their
  unrolls in the shared TrajectoryBuffer, serves param snapshots.

  Args:
    buffer: the learner's TrajectoryBuffer (shared with the local
      fleet).
    params: initial host (numpy) param pytree; version 1.
    host/port: bind address; port 0 picks a free port (see `.port`).
      Loopback-only by default (the wire is unauthenticated pickle) —
      real actor-host topologies must opt in to a cluster-internal
      interface, mirroring config.remote_actor_bind_host.
    contract: `trajectory_contract(...)` of the learner's config.
      When given, clients must open with a matching `hello` before
      any unroll is accepted, and every received unroll is validated
      against the signature before it can reach the buffer. None
      disables both checks (protocol-level tests).
    wire_dtype: 'bfloat16' casts float32 leaves of each published
      snapshot for the wire (config.publish_codec resolves here — bf16
      is the production default; 'f32' opts out) — the blob kind
      becomes 'params_bf16' and RemoteActorClient upcasts on receipt,
      halving the egress term of the feed arithmetic (docs/PERF.md,
      docs/TRANSPORT.md). ''/None ships exact float32.
    ingest_workers: size of the validate/commit pool that drains the
      reader threads' handoff queue (validation + buffer.put + ack off
      the reader thread). 0 = auto (min(4, cpu count)). The handoff
      queue is bounded (`_INGEST_QUEUE_DEPTH`): well-behaved clients
      are request→reply lockstep (one in-flight unroll per live
      connection), and a misbehaving pipelined peer blocks its own
      reader instead of growing server memory.
    max_unroll_staleness: admit an unroll only when the client's
      params version is within this many published versions of the
      current one (0 = no window). Too-stale unrolls get a benign
      ('stale', current_version) reply — the client drops the unroll
      and refetches — counted per connection and in
      stats()['stale_rejected']. Off-policy V-trace tolerates bounded
      lag; this bounds it at the ADMISSION seam instead of letting a
      lagging host poison the batch mix (IMPACT's staleness window,
      arXiv:1912.00167, applied at ingest).
    heartbeat_secs: v6 connection-liveness cadence (round 11;
      config.remote_heartbeat_secs): v6 clients ping at this interval
      when idle, and ingest workers emit ('busy',) keepalives at this
      cadence to v6 peers while an ack is held back by buffer
      backpressure. 0 disables (v5 wire exactly).
    idle_timeout_secs: idle/half-open reaping window (round 11;
      config.remote_conn_idle_timeout_secs): a connection — either
      lane — that received NO bytes for this long is reaped
      (stats()['conns_reaped']), and it doubles as the mid-frame
      recv stall and send no-progress deadline on every blocking
      socket path. 0 disables reaping AND deadlines (pre-round-11
      behavior: a half-open peer pins its reader forever).
    wire_crc: v7 payload integrity (round 12; config.wire_crc): offer
      per-frame CRC32C trailers to v7 clients at hello. A mismatched
      unroll is refused with ('corrupt', crc) BEFORE the buffer put —
      counted in stats()['wire_crc_rejected'] — and the connection is
      kept (the client re-sends once, then quarantines itself). False
      negotiates every connection down to the v6 wire (the bench's
      CRC-off row, and the escape hatch for CPU-bound ingest hosts).
  """

  # Lock discipline (round 18, guarded-by lint). Three planes, three
  # locks, no nesting between them: the published snapshot + its
  # serialization clock under _params_lock, the connection/reattach
  # counters under _stats_lock, the live conn/thread lists under
  # _conns_lock. The registry counters (ingest/unrolls etc.) carry
  # their own per-counter locks and stay unannotated.
  _version: guarded_by('_params_lock')
  _blob_version: guarded_by('_params_lock')
  _params_frame: guarded_by('_params_lock')
  _params_frame_compat: guarded_by('_params_lock')
  _serving_fn: guarded_by('_params_lock')
  _draining: guarded_by('_params_lock')
  _serializations: guarded_by('_params_lock')
  _connections: guarded_by('_stats_lock')
  _param_subscribers: guarded_by('_stats_lock')
  _reattached: guarded_by('_stats_lock')
  _reconnected: guarded_by('_stats_lock')
  _reattach_latency: guarded_by('_stats_lock')
  _unjoined_threads: guarded_by('_stats_lock')
  _threads: guarded_by('_conns_lock')
  _conns: guarded_by('_conns_lock')
  _members: guarded_by('_conns_lock')
  _member_events: guarded_by('_conns_lock')

  def __init__(self, buffer, params, host: str = '127.0.0.1',
               port: int = 0, contract=None,
               wire_dtype: Optional[str] = None,
               ingest_workers: int = 0,
               max_unroll_staleness: int = 0,
               heartbeat_secs: float = 0.0,
               idle_timeout_secs: float = 0.0,
               wire_crc: bool = True,
               trace: bool = True):
    if wire_dtype not in (None, '', 'bfloat16', 'int8'):
      raise ValueError(f'unsupported wire_dtype {wire_dtype!r}')
    self._wire_bf16 = wire_dtype == 'bfloat16'
    # v10 int8 codec (round 21): the cached blob pair — int8 for v10
    # subscribers, bf16 for v<=9 (which cannot parse Int8Leaf trees
    # reliably across codec revisions and never negotiated the lossy
    # codec). Both built ONCE per publish.
    self._wire_int8 = wire_dtype == 'int8'
    self._wire_crc = bool(wire_crc)
    # v8 trace spans (round 13; config.telemetry_trace): advertised as
    # a server-wide fact in the hello reply's server-info — v8 clients
    # then stamp each unroll frame with its trace context, which the
    # reader/worker complete learner-side (telemetry.PipelineTracer).
    self._trace = bool(trace)
    self._buffer = buffer
    self._contract = contract
    self._max_staleness = int(max_unroll_staleness)
    self._validate = (FastUnrollValidator(contract)
                      if contract is not None else None)
    # --- Connection liveness (round 11). A per-run session epoch
    # rides every params reply: a restarted learner's epoch differs,
    # so reattaching clients are tellable from fresh ones (and from
    # clients of a DIFFERENT learner incarnation — the stale-epoch
    # unroll guard). Wall-clock microseconds + pid: unique across
    # restarts of the same port without any on-disk state.
    self.session_epoch = ((int(time.time() * 1e6) << 10)
                          ^ (os.getpid() & 0x3ff))
    self._t_start = time.monotonic()
    self._heartbeat_secs = float(heartbeat_secs)
    self._idle_timeout = float(idle_timeout_secs)
    self._liveness_on = (self._heartbeat_secs > 0
                         or self._idle_timeout > 0)
    # Mid-frame/send stall deadline: the idle window when set, else a
    # heartbeat-derived floor (a frame should never trickle longer
    # than a few missed heartbeats).
    self._stall_secs = (self._idle_timeout if self._idle_timeout > 0
                        else max(3 * self._heartbeat_secs, 10.0))
    # Reader/reaper poll interval: short enough that fast test windows
    # (idle 0.5 s) resolve, bounded below so we never spin.
    polls = [1.0]
    if self._idle_timeout > 0:
      polls.append(self._idle_timeout / 4)
    if self._heartbeat_secs > 0:
      polls.append(self._heartbeat_secs / 2)
    self._poll_secs = max(min(polls), 0.05)
    self._watchdog = ThreadWatchdog()
    self._params_lock = make_lock('remote.IngestServer._params_lock')
    self._version = 1
    self._blob_version = 1
    # One pickle per version (VERDICT r2 W2): handler threads send
    # these cached bytes instead of re-serializing the tree per
    # get_params — at the advertised 150+-actor-host topology every
    # version bump otherwise costs O(hosts × tree) pickles.
    self._serializations = 0
    self._params_frame = self._make_blob(self._version, params)
    self._params_frame_compat = (
        self._make_blob(self._version, params, compat=True)
        if self._wire_int8 else None)
    # Routed inference (v10): the learner attaches a serving fn
    # (InferenceServer.serve_remote) via attach_serving; 'infer'
    # requests answer ('error', ...) until then. set_draining flips
    # the notice routers drain on.
    self._serving_fn = None
    self._draining = False
    self._stats_lock = make_lock('remote.IngestServer._stats_lock')
    # Round 13: the scattered per-module ints moved into the unified
    # metrics registry (telemetry.Counter — each has its own lock;
    # cross-counter atomicity was never relied on). stats() keeps its
    # exact key surface by reading .value; the drain manifest, halt
    # bundle, flight recorder, and the remote 'stats' request read the
    # same objects through registry.snapshot().
    self._unrolls = telemetry.counter('ingest/unrolls')
    self._rejected = telemetry.counter('ingest/rejected')
    self._stale_rejected = telemetry.counter('ingest/stale_rejected')
    self._quarantined = telemetry.counter('ingest/quarantined')
    # Integrity ledger (round 12): unrolls refused because their v7
    # CRC trailer mismatched (verified before the put — the buffer
    # never saw them), and the discard accounting of thrown-away
    # partial/unparseable frames (the round-12 fix: the quarantine
    # path used to count the CONN but drop how much data died with
    # it).
    self._wire_crc_rejected = telemetry.counter(
        'ingest/wire_crc_rejected')
    self._discarded_frames = telemetry.counter(
        'ingest/discarded_frames')
    self._discarded_bytes = telemetry.counter(
        'ingest/discarded_bytes')
    self._connections = 0
    self._param_subscribers = 0  # cumulative hello_params adoptions
    # Liveness/restart counters (round 11).
    self._conns_reaped = telemetry.counter('ingest/conns_reaped')
    self._heartbeat_misses = telemetry.counter(
        'ingest/heartbeat_misses')
    self._stale_epoch_rejected = telemetry.counter(
        'ingest/stale_epoch_rejected')
    self._reattached = 0         # hellos carrying a FOREIGN prior epoch
    self._reconnected = 0        # hellos carrying OUR epoch (same run)
    self._reattach_latency = 0.0  # last reattach: secs since start
    self._unjoined_threads = 0   # close()-time join-deadline misses
    # Ack service-time percentiles read straight from the registry
    # histogram (round 13: telemetry.Histogram IS the
    # LatencyReservoir design promoted to a registry citizen — a
    # second reservoir would be the same samples bookkept twice).
    self._ack_hist = telemetry.histogram('ingest/ack_ms')
    self._closed = threading.Event()
    # Threads/conns are appended by the accept loop, pruned as peers
    # disconnect, snapshotted by close() — all under one lock (flapping
    # actor hosts over a long run must not accumulate dead entries).
    self._threads: List[threading.Thread] = []
    self._conns: List[_Conn] = []
    # Elastic membership ledger (round 20): host identity -> the conn
    # currently carrying it, plus the pending join/leave events the
    # driver drains into durable incidents. Keyed on the v9 hello's
    # 'host' string, so a RECONNECT of a known host (new conn, same
    # identity) is a non-event while a fresh host records host_joined
    # and a dead conn still owning its identity records host_left.
    self._members: Dict[str, _Conn] = {}
    self._member_events: List[Dict] = []
    self._hosts_joined = telemetry.counter('ingest/hosts_joined')
    self._hosts_left = telemetry.counter('ingest/hosts_left')
    self._conns_lock = make_lock('remote.IngestServer._conns_lock')
    # Trajectory-lane handoff: readers push (conn, unroll, t_recv,
    # client_version); the worker pool validates, commits
    # (backpressure lives in the blocking put) and acks. BOUNDED
    # (see _INGEST_QUEUE_DEPTH): a reader blocked in put is socket-
    # level backpressure on its peer, not unbounded server memory.
    self._ingest_q: 'queue.Queue' = queue.Queue(
        maxsize=_INGEST_QUEUE_DEPTH)
    if ingest_workers <= 0:
      ingest_workers = max(1, min(4, os.cpu_count() or 1))
    self._workers = [
        threading.Thread(target=self._ingest_worker,
                         args=(f'ingest-worker-{i}',),
                         name=f'ingest-worker-{i}', daemon=True)
        for i in range(ingest_workers)]
    for w in self._workers:
      w.start()
    self._param_lane = _ParamLane(self._snapshot_frame,
                                  idle_timeout_secs=self._idle_timeout,
                                  watchdog=self._watchdog)
    self._listener = socket.create_server((host, port))
    self.port = self._listener.getsockname()[1]
    self._accept_thread = threading.Thread(
        target=self._accept_loop, name='ingest-accept', daemon=True)
    self._accept_thread.start()
    # Idle/half-open reaper (round 11): the one thread that owns the
    # between-frames idle budget — it closes a silent peer's socket,
    # which wakes the blocked reader with an OSError and runs the
    # normal disconnect cleanup. Mid-frame stalls abort faster on the
    # reader itself (_ConnLiveness).
    self._reaper_thread = None
    if self._idle_timeout > 0:
      self._reaper_thread = threading.Thread(
          target=self._reap_loop, name='ingest-reaper', daemon=True)
      self._reaper_thread.start()

  def _make_blob(self, version, params,
                 compat: bool = False) -> Tuple[List[bytes], bytes]:
    """One published version as (wire frame segments, CRC trailer):
    [head (length prefix + OOB tag + skeleton + buffer table), raw
    buffer, raw buffer, ...] plus the 4 trailer bytes v7 subscribers
    get appended (cached WITH the blob — one CRC per publish, not per
    fetch).

    Out-of-band framing in the params direction too (round 6 — the
    same lesson the r4 unroll framing measured at +90%): the frame IS
    the arrays, so neither the server (per send) nor the client (per
    fetch) copies them through the pickler — the client's
    `_recv_msg` reconstructs zero-copy views, which matters doubly on
    the param lane where 8 polling fetchers' unpickles used to share
    the core with the unroll pump's acks.

    Integrity (round 12): the info dict carries 'params_digest' — a
    content CRC of the WIRE-form tree (post-bf16-cast, pre-upcast:
    the client verifies the exact bytes it received) computed HERE,
    at publish time, before serialization. The 'publish_corrupt'
    fault site fires between the digest and the pickle: the shipped
    frame is then self-consistent (its CRC trailer matches its bytes)
    and only the client's digest check can catch the damage — the
    host-memory-rot shape.

    v10 (round 21): with wire_dtype='int8' the primary blob is the
    absmax-quantized tree (kind 'params_int8'; runtime/codec.py —
    the digest covers the WIRE form, q arrays and scales, exactly
    like the bf16 digest covers the cast tree). `compat=True` builds
    the bf16 blob served to v<=9 subscribers instead — each publish
    builds both ONCE; `compat` builds don't advance the
    serializations clock (its contract is one count per VERSION, the
    per-version cost the test hook watches)."""
    if not compat:
      with self._params_lock:
        self._serializations += 1  # test hook: once per version
    wire_int8 = self._wire_int8 and not compat
    wire_bf16 = self._wire_bf16 or (self._wire_int8 and compat)
    if wire_int8:
      from scalable_agent_tpu.runtime import codec as codec_lib
      params = codec_lib.quantize_np(params)
    elif wire_bf16:
      import jax
      import ml_dtypes
      params = jax.tree_util.tree_map(
          lambda x: x.astype(ml_dtypes.bfloat16)
          if getattr(x, 'dtype', None) == np.float32 else x, params)
    digest = integrity.tree_digest(params)
    plan = faults_lib.active()
    fault = faults_lib.fire('publish_corrupt')
    if fault is not None:
      params = faults_lib.corrupt_params_tree(
          fault, params, seed=plan.seed if plan else 0)
    # v6: server info rides every params reply as a 4th element (old
    # clients index [0..2] and never see it). The hello reply IS a
    # params reply, so this is also how a client learns the session
    # epoch and the negotiated heartbeat cadence — no extra frame, no
    # extra version field on the wire.
    # 'wire_crc'/'crc_algo' are SERVER-WIDE facts (the blob is cached
    # per version, not per connection): each side derives the same
    # per-conn negotiation from (peer protocol >= 7) AND (server
    # wire_crc) AND (client offered crc) AND (algorithms match), so
    # no per-connection state needs to ride the cached frame.
    info = {'protocol': PROTOCOL_VERSION,
            'session_epoch': self.session_epoch,
            'heartbeat_secs': self._heartbeat_secs,
            'idle_timeout_secs': self._idle_timeout,
            'wire_crc': self._wire_crc,
            'crc_algo': integrity.CRC_ALGO,
            # v8: a server-wide fact like wire_crc — a v8 client
            # seeing it stamps trace contexts on its unroll frames.
            'trace': self._trace,
            'params_digest': integrity.digest_record(digest)}
    if wire_int8:
      kind = 'params_int8'
    elif wire_bf16:
      kind = 'params_bf16'
    else:
      kind = 'params'
    segments = _oob_frame_segments((kind, version, params, info))
    return segments, _CRC.pack(_segments_crc(segments))

  def publish_params(self, params) -> int:
    """Swap in a new host param snapshot; returns the new version.
    Call with numpy trees (device_get first). Serializes ONCE, here
    on the caller (learner-loop) thread — handler threads only ship
    the cached bytes. The pickle runs OUTSIDE the lock (handlers'
    acks/get_params must not stall behind it); a handler reading the
    previous blob between the version bump and the swap just triggers
    one redundant client refetch. Safe under concurrent publishers:
    the swap is version-guarded, so a slow pickle of version N can
    never overwrite version N+1's blob (ADVICE r3)."""
    with self._params_lock:
      self._version += 1
      version = self._version
    blob = self._make_blob(version, params)
    compat = (self._make_blob(version, params, compat=True)
              if self._wire_int8 else None)
    with self._params_lock:
      if version > self._blob_version:
        self._params_frame = blob
        self._params_frame_compat = compat
        self._blob_version = version
    return version

  @property
  def serializations(self) -> int:
    """How many times a param snapshot was pickled (== versions
    published, independent of client count)."""
    with self._params_lock:
      return self._serializations

  def live_hosts(self) -> int:
    """Hosts currently in the membership ledger (v9 peers only —
    pre-v9 connections never name a host identity and so never
    count here; use stats()['live'] for raw connection counts)."""
    with self._conns_lock:
      return len(self._members)

  def membership(self) -> List[str]:
    """Sorted host identities currently attached."""
    with self._conns_lock:
      return sorted(self._members)

  def drain_membership_events(self) -> List[Dict]:
    """Pop-all of the pending join/leave events, oldest first. The
    driver turns these into durable host_joined/host_left incidents
    at the summary cadence; each event is delivered exactly once."""
    with self._conns_lock:
      events, self._member_events = self._member_events, []
    return events

  def stats(self):
    with self._conns_lock:
      live = len(self._conns)
      live_hosts = len(self._members)
      per_conn = {f'{c.addr}': c.unrolls for c in self._conns}
      per_conn_stale = {f'{c.addr}': c.stale_rejected
                        for c in self._conns if c.stale_rejected}
    lane = self._param_lane.stats()
    wedged = self._wedged_threads()
    p50, p99 = self._ack_hist.percentiles(0.5, 0.99)
    ack_p50_ms, ack_p99_ms = round(p50, 3), round(p99, 3)
    with self._stats_lock:
      return {'unrolls': self._unrolls.value,
              'rejected': self._rejected.value,
              # Staleness-window rejections (round 9): unrolls refused
              # because the client's params version fell behind the
              # admission window — benign for the client (it refetches
              # and keeps its connection), but a host whose EVERY
              # unroll is stale is starving; the per-conn map names it.
              'stale_rejected': self._stale_rejected.value,
              'per_conn_stale_rejected': per_conn_stale,
              # Connections dropped after an unparseable/garbage frame
              # (protocol error path): the wire-level quarantine — a
              # corrupting peer loses its connection, the server and
              # every other connection keep going.
              'quarantined': self._quarantined.value,
              # v7 payload integrity (round 12): unrolls refused for a
              # mismatched CRC trailer (verified before the put — the
              # buffer provably never saw them), the param-lane ledger
              # of digest-refused publishes, and the discard
              # accounting of thrown-away partial/unparseable frames.
              'wire_crc_rejected': self._wire_crc_rejected.value,
              'publish_digest_rejected': lane['digest_rejected'],
              'discarded_frames': self._discarded_frames.value,
              'discarded_bytes': self._discarded_bytes.value,
              'connections': self._connections,  # cumulative
              'live': live,
              # Elastic membership (round 20): hosts currently in the
              # v9 ledger and the cumulative join/leave traffic — the
              # pod-size ground truth the driver gauges and the
              # controller's pod_size actuator read.
              'live_hosts': live_hosts,
              'hosts_joined': self._hosts_joined.value,
              'hosts_left': self._hosts_left.value,
              # Per-lane transport counters (round 6): the driver
              # turns these into summary-interval rates/latencies.
              'per_conn_unrolls': per_conn,
              'ack_p50_ms': ack_p50_ms,
              'ack_p99_ms': ack_p99_ms,
              'param_blobs': lane['blobs'],
              'param_bytes': lane['bytes'],
              'param_subscribers': self._param_subscribers,
              # Fan-out shrinkage (round 11 satellite): EVERY dropped
              # param-lane subscriber — disconnects, protocol errors,
              # idle reaps — so a quietly shrinking fleet is visible
              # in the driver summaries, not just in missing hosts.
              'param_subs_dropped': lane['subs_dropped'],
              'param_subs_reaped': lane['subs_reaped'],
              # Liveness/restart counters (round 11): reaped
              # idle/half-open connections, v6 peers silent past 2x
              # their heartbeat (the leading indicator before a
              # reap), unrolls refused for carrying a dead
              # incarnation's epoch (asserted ZERO by the partition
              # storm), and the fleet re-attach ledger a restarted
              # learner reports (count + seconds from server start to
              # the latest cross-epoch hello).
              'conns_reaped': self._conns_reaped.value,
              'heartbeat_misses': self._heartbeat_misses.value,
              'stale_epoch_rejected': self._stale_epoch_rejected.value,
              'reattached': self._reattached,
              'reconnected': self._reconnected,
              'reattach_latency_secs': round(self._reattach_latency, 3),
              'session_epoch': self.session_epoch,
              # Wedged-thread watchdog: service threads (readers,
              # workers, param lane, reaper) that made no progress
              # past the stall deadline — the silent-leak failure the
              # round-11 deadlines exist to prevent, surfaced instead
              # of assumed away.
              'ingest_threads_wedged': len(wedged),
              'wedged_thread_names': wedged,
              'unjoined_threads': self._unjoined_threads}

  def _wedged_threads(self) -> List[str]:
    """Service threads with no watchdog beat past the stall deadline.
    Liveness mode only: without poll timeouts an idle reader
    legitimately never beats, so the watchdog would cry wolf."""
    if not self._liveness_on:
      return []
    return self._watchdog.wedged(max(3 * self._stall_secs, 15.0))

  def _reap_loop(self):
    """Close connections (either lane handles its own sockets — this
    covers the trajectory lane) that received nothing inside the idle
    window; count heartbeat misses on v6 conns as the leading
    indicator. The close wakes the connection's blocked reader with an
    OSError; its normal unwind prunes the conn list."""
    while not self._closed.wait(max(self._poll_secs / 2, 0.05)):
      self._watchdog.beat('ingest-reaper')
      now = time.monotonic()
      with self._conns_lock:
        conns = list(self._conns)
      for conn in conns:
        if conn.is_waiting_on_us():
          # An unroll is in flight on this conn: the peer is parked
          # awaiting OUR ack (lockstep) — its silence is the protocol
          # working, not a half-open link. Backpressure can hold the
          # ack far past any idle window; reaping here would kill a
          # protocol-obedient peer and duplicate its unroll on
          # reconnect (the 'slow learner != dead learner' contract).
          continue
        # v5 peers CANNOT ping (no heartbeat machinery), so a
        # live-but-slow v5 actor (long episodes, mixed-version fleet
        # mid-upgrade) would be indistinguishable from half-open at
        # the v6 window — give them a generous multiple: half-open v5
        # conns still reap (bounded leak, not forever), slow live
        # ones survive any sane unroll cadence.
        idle_window = (self._idle_timeout if conn.heartbeat
                       else 5 * self._idle_timeout)
        silent = now - conn.last_recv
        if (conn.heartbeat and not conn.hb_missed
            and silent > 2 * self._heartbeat_secs):
          conn.hb_missed = True
          self._heartbeat_misses.inc()
          log.warning('remote actor %s missed its heartbeat window '
                      '(silent %.1fs, cadence %.1fs)', conn.addr,
                      silent, self._heartbeat_secs)
        if silent > idle_window and not conn.reaped:
          conn.reaped = True
          self._conns_reaped.inc()
          log.warning('reaping idle/half-open connection %s (silent '
                      '%.1fs > %.1fs window)', conn.addr, silent,
                      self._idle_timeout)
          try:
            conn.sock.shutdown(socket.SHUT_RDWR)
          except OSError:
            pass
          try:
            conn.sock.close()
          except OSError:
            pass
    self._watchdog.unregister('ingest-reaper')

  def _ingest_worker(self, name: str = 'ingest-worker'):
    """Validate/commit/ack loop — the trajectory lane's half that must
    not run on the reader thread (r5: recv + validate + put + ack
    serialized per connection made 4 connections slower than 1)."""
    try:
      self._ingest_worker_loop(name)
    finally:
      # EVERY exit path (sentinel, closed flag, Closed mid-put) must
      # retire the watchdog entry, or a cleanly-exited worker reads
      # as wedged forever in post-close stats.
      self._watchdog.unregister(name)

  def _ingest_worker_loop(self, name: str):
    while True:
      self._watchdog.beat(name)
      try:
        job = self._ingest_q.get(timeout=1.0)
      except queue.Empty:
        if self._closed.is_set():
          return
        continue
      if job is None:
        return
      (conn, unroll, t_recv, client_version, client_epoch, crc_pair,
       trace) = job
      try:
        if crc_pair is not None and crc_pair[0] != crc_pair[1]:
          # v7 payload integrity: the frame's bytes are not the bytes
          # the client sent — refuse BEFORE the staleness/epoch/
          # validation checks (every field parsed from a corrupt
          # frame is untrustworthy) and before the buffer put. The
          # benign ('corrupt', computed) reply keeps the connection:
          # the client re-sends once, then quarantines itself.
          computed, wire = crc_pair
          self._wire_crc_rejected.inc()
          conn.crc_rejected += 1
          log.warning(
              'unroll from %s failed its CRC trailer (computed '
              '%08x, wire %08x) — refused before the buffer put',
              conn.addr, computed, wire)
          conn.send(('corrupt', computed))
          continue
        if (client_epoch is not None
            and client_epoch != self.session_epoch):
          # A dead incarnation's unroll (v6 epoch stamp): refuse it
          # WITHOUT touching the buffer. Structurally unreachable over
          # plain TCP — the counter is the partition storm's proof
          # that zero stale-epoch unrolls crossed a restart, and the
          # guard that keeps that true if a proxy/load-balancer ever
          # sits in front of the port.
          self._stale_epoch_rejected.inc()
          conn.send(('stale_epoch', self.session_epoch))
          continue
        if self._max_staleness and client_version is not None:
          with self._params_lock:
            current = self._version
          if current - int(client_version) > self._max_staleness:
            # Version-windowed admission: refuse the unroll BEFORE
            # validation or the buffer put, but keep the connection —
            # the 'stale' reply carries the current version, so the
            # client's refetch-on-newer-version path fires and the
            # next unroll arrives fresh.
            self._stale_rejected.inc()
            conn.stale_rejected += 1
            conn.send(('stale', current))
            continue
        if self._validate is not None:
          problems = self._validate(unroll)
          if problems:
            # Reject WITHOUT touching the buffer (a malformed unroll
            # must not poison training) but keep the connection: the
            # actor decides whether this is fatal.
            self._rejected.inc()
            conn.send(('error', 'unroll rejected: '
                       + '; '.join(problems)))
            continue
        # Trace span (round 13, v8): this unroll passed every check —
        # stamp COMMIT (admitted; the buffer put below may still wait
        # on backpressure, which the commit→staged hop then shows as
        # queue time) and tag the unroll BEFORE the put so the
        # prefetcher can never consume it ahead of its tag. The
        # piggybacked params-install notice ('pi') becomes its own
        # trace record here — the publish→installed-at-actor hop.
        tracer = telemetry.get_tracer()
        if trace is not None and tracer is not None:
          telemetry.stamp(trace, telemetry.HOP_COMMIT)
          # Commit-time publish counter in the INGEST clock ('cv'):
          # policy lag for this unroll is cv - bv, a publish-count
          # delta judged within the clock its behaviour version was
          # stamped in (the tracer's local clock counts driver
          # publishes — a different sequence).
          with self._params_lock:
            trace['cv'] = self._version
          install = trace.pop('pi', None)
          if install is not None:
            try:
              tracer.on_install(trace.get('a', conn.addr),
                                install[0], install[1])
            except (TypeError, IndexError):
              pass  # malformed notice from a buggy peer: drop it
          tracer.tag(unroll, trace)
        # Blocking put IS the backpressure: the delayed ack holds the
        # remote pump exactly like the reference's remote enqueue
        # into the capacity-1 queue. Poll so close() can interrupt.
        # The ('busy',) keepalive that tells a v6 peer "slow, not
        # dead" meanwhile is the READER's job (_ConnLiveness.idle) —
        # it covers this wait AND a job still parked in the handoff
        # queue behind other connections (workers < connections under
        # load), which no worker-side emission could.
        while True:
          try:
            self._buffer.put(unroll, timeout=1.0)
            break
          except TimeoutError:
            if self._closed.is_set():
              return
            self._watchdog.beat(name)
        self._unrolls.inc()
        conn.unrolls += 1
        with self._params_lock:
          version = self._version
        conn.send(('ack', version))
        self._ack_hist.observe((time.monotonic() - t_recv) * 1e3)
      except ring_buffer.Closed:
        return  # learner shut down; readers see their conns drop
      except (ConnectionError, OSError):
        pass  # peer gone mid-ack; its reader notices and cleans up
      except Exception:
        log.exception('ingest worker failed on an unroll')
      finally:
        # The reply (ack/stale/reject/busy-abandon) is out, or the
        # conn is dead either way: this unroll is no longer in flight,
        # so the conn's silence becomes a liveness signal again.
        conn.job_finished()

  def _accept_loop(self):
    while not self._closed.is_set():
      try:
        conn, addr = self._listener.accept()
      except OSError:
        return  # listener closed
      conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
      if self._liveness_on:
        # Timeout mode: the reader polls (so stalls are detectable and
        # the watchdog sees beats) and every send is progress-bounded.
        conn.settimeout(self._poll_secs)
        wrapped = _Conn(conn, addr=addr,
                        send_stall_secs=self._stall_secs,
                        base_timeout=self._poll_secs)
      else:
        wrapped = _Conn(conn, addr=addr)
      t = threading.Thread(target=self._serve, args=(wrapped, addr),
                           name=f'ingest-{addr}', daemon=True)
      with self._conns_lock:
        if self._closed.is_set():
          conn.close()
          return
        self._conns.append(wrapped)
        self._threads = [x for x in self._threads if x.is_alive()]
        self._threads.append(t)
        # Started under the lock: close() snapshots `_threads` under
        # it and joins them, and an unstarted thread cannot be joined.
        t.start()
      with self._stats_lock:
        self._connections += 1

  def _snapshot_frame(
      self, proto: int = PROTOCOL_VERSION) -> Tuple[List[bytes], bytes]:
    """(cached frame segments, cached CRC trailer) of the current
    published version — the trailer ships only to v7 CRC peers.
    `proto` selects the encoding (v10 codec negotiation): a v<=9
    peer of an int8 publisher gets the cached bf16 compat blob."""
    with self._params_lock:
      if self._wire_int8 and proto < 10:
        return self._params_frame_compat
      return self._params_frame

  def snapshot_nbytes(self, proto: int = PROTOCOL_VERSION) -> int:
    """Wire size of the current cached snapshot frame (bench +
    egress-arithmetic hook; the 4 trailer bytes are noise)."""
    return sum(len(s) for s in self._snapshot_frame(proto)[0])

  def attach_serving(self, fn) -> None:
    """Attach the routed-inference seam (v10): `fn(payload dict) ->
    result dict`, normally InferenceServer.serve_remote. 'infer'
    requests answer ('error', 'serving not attached') until this is
    called; None detaches."""
    with self._params_lock:
      self._serving_fn = fn

  def set_draining(self, draining: bool = True) -> None:
    """Flip the drain notice 'infer' replies carry — routers
    (runtime/routing.py) shift a replica's share away BEFORE its
    connections die (the PR 17 leave convention, serving-plane
    edition)."""
    with self._params_lock:
      self._draining = bool(draining)

  def _serve(self, conn: _Conn, addr):
    log.info('remote actor connected from %s', addr)
    # Handshake is per-connection: with a contract set, no unroll is
    # accepted until this client's hello matched (a reconnecting
    # client re-handshakes — cheap, and it re-verifies after learner
    # restarts that may have changed the config).
    handshaken = self._contract is None
    adopted = False
    leave_to_close = False  # close() owns the socket/list teardown
    thread_name = f'ingest-reader-{addr}'
    # The liveness ledger exists on EVERY connection now (round 12):
    # besides the round-11 stall/keepalive machinery (armed only in
    # liveness mode — on a blocking legacy socket its timeout paths
    # simply never fire), it carries the per-frame byte count the
    # discard accounting reports when a frame is thrown away.
    liveness = _ConnLiveness(
        conn, self._closed, self._stall_secs,
        watchdog=self._watchdog if self._liveness_on else None,
        name=thread_name, heartbeat_secs=self._heartbeat_secs)
    liveness.beat()
    crc_ctx = None  # armed once the hello negotiates v7 CRC
    try:
      while not self._closed.is_set():
        msg = _recv_msg(conn.sock, liveness, crc_ctx)
        if msg is None:
          return  # client went away
        kind = msg[0]
        if kind == 'hello':
          offered = msg[1] if len(msg) > 1 else None
          if self._contract is not None:
            problem = contract_mismatch_message(self._contract, offered)
            if problem is not None:
              log.warning('rejecting actor %s: %s', addr, problem)
              conn.send(('reject', problem))
              return
            handshaken = True
          # v6 negotiation (contract or not — protocol tests handshake
          # against contract-less servers too): the offered protocol
          # decides whether this conn gets busy keepalives and
          # heartbeat-miss accounting; the client-info dict's prior
          # epoch tells a reattaching client (cross-epoch — a learner
          # RESTART behind it) from a same-run reconnect.
          if isinstance(offered, dict):
            conn.protocol = int(offered.get('protocol') or 5)
          conn.heartbeat = (conn.protocol >= 6
                            and self._heartbeat_secs > 0)
          client_info = msg[2] if len(msg) > 2 else None
          # v7 CRC negotiation: peer protocol, server knob, client
          # offer, and algorithm must ALL agree (a zlib-fallback host
          # paired with a crc32c host negotiates OFF — phantom
          # corruption would be worse than no check). Takes effect
          # AFTER the hello reply below: the reply ships per the
          # conn's PRIOR crc state, because the client cannot know
          # the outcome until it has parsed this very frame.
          crc_next = (conn.protocol >= 7 and self._wire_crc
                      and isinstance(client_info, dict)
                      and bool(client_info.get('crc'))
                      and client_info.get('crc_algo') ==
                      integrity.CRC_ALGO)
          prior_epoch = (client_info or {}).get('epoch') \
              if isinstance(client_info, dict) else None
          try:
            prior_epoch = (None if prior_epoch is None
                           else int(prior_epoch))
          except (TypeError, ValueError):
            prior_epoch = None  # garbage epoch: treat as a fresh hello
          if prior_epoch is not None:
            with self._stats_lock:
              if prior_epoch != self.session_epoch:
                self._reattached += 1
                self._reattach_latency = (time.monotonic()
                                          - self._t_start)
                log.info(
                    'remote actor %s REATTACHED across a learner '
                    'restart (prior epoch %d -> %d) %.2fs after '
                    'server start', addr, prior_epoch,
                    self.session_epoch, self._reattach_latency)
              else:
                self._reconnected += 1
          # v9 membership: a hello naming a host identity enters the
          # ledger. Only a NEW identity is a join event — a reconnect
          # of a known host just re-points its entry at this conn
          # (the old conn's unwind sees it no longer owns the
          # identity and stays silent).
          host_id = (client_info.get('host')
                     if isinstance(client_info, dict) else None)
          if isinstance(host_id, str) and host_id:
            conn.host_id = host_id
            with self._conns_lock:
              fresh = host_id not in self._members
              self._members[host_id] = conn
              if fresh:
                self._member_events.append(
                    {'kind': 'host_joined', 'host': host_id,
                     'reattach': prior_epoch is not None})
            if fresh:
              self._hosts_joined.inc()
              log.info('host %s JOINED the pod (%s)', host_id, addr)
          segments, trailer = self._snapshot_frame(conn.protocol)
          conn.send_segments(segments,
                             trailer if conn.crc else None)
          conn.crc = crc_next
          crc_ctx = _CrcContext() if conn.crc else None
        elif kind == 'ping':
          # Application-level heartbeat (v6): refreshes last_recv by
          # arriving; the pong carries the current params version so
          # an idle fleet still notices publishes without traffic.
          with self._params_lock:
            version = self._version
          conn.send(('pong', version))
        elif kind == 'hello_params':
          # Re-route this whole connection to the param lane: the
          # reader thread hands the raw socket over and exits — blob
          # traffic must never share a thread (or a socket) with the
          # trajectory lane's acks. Re-categorize the connection count
          # ('connections' means ACTOR connections; subscribers get
          # their own counter).
          with self._stats_lock:
            self._connections -= 1
            self._param_subscribers += 1
          # v7: the hello_params MAY carry the client-info dict — the
          # lane then appends the cached trailer to its replies and
          # verifies trailers on requests from this subscriber.
          sub_info = msg[1] if len(msg) > 1 else None
          sub_crc = (self._wire_crc and isinstance(sub_info, dict)
                     and bool(sub_info.get('crc'))
                     and sub_info.get('crc_algo') ==
                     integrity.CRC_ALGO)
          # v10: the subscriber's offered protocol picks its blob
          # encoding; absent (v<=9 hello_params, or the bare legacy
          # tuple), fall back to the trajectory-lane handshake's
          # protocol, else to the conservative bf16/f32 blob.
          sub_proto = conn.protocol
          if isinstance(sub_info, dict) and sub_info.get('protocol'):
            sub_proto = int(sub_info['protocol'])
          adopted = self._param_lane.adopt(conn.sock, crc=sub_crc,
                                           proto=sub_proto)
          return
        elif kind == 'get_params':
          # Legacy/in-band path (pre-v5 peers, protocol tests): served,
          # but production clients fetch over the param lane.
          segments, trailer = self._snapshot_frame(conn.protocol)
          conn.send_segments(segments,
                             trailer if conn.crc else None)
        elif kind == 'unroll':
          if not handshaken:
            # 'error', not 'reject': legacy (protocol-1) clients only
            # special-case 'bye'/'error' — a 'reject' here would parse
            # as a successful ack and they would silently drop every
            # unroll forever instead of failing loudly.
            conn.send(('error',
                       'unroll before a successful hello handshake — '
                       'upgrade/fix the actor host'))
            continue
          # Reader half of the trajectory lane ends here: validation,
          # the backpressure put and the ack all happen on the worker
          # pool, so this thread is back inside recv for the next
          # frame immediately. msg[2] (when present) is the client's
          # params version for the staleness window (v5 extension);
          # msg[3] (v6) is the session epoch the client handshook
          # under — the stale-incarnation guard.
          # Mark the unroll in flight BEFORE the enqueue: from here
          # until the worker's reply, this conn's silence is lockstep
          # protocol (reaper-exempt), not a liveness signal. On a v7
          # CRC conn the (computed, wire) pair rides the job: the
          # WORKER compares just before the put, so a corrupt frame
          # earns its benign reply without ever touching the buffer.
          conn.job_started()
          # msg[4] (v8) is the unroll's trace context: stamp WIRE here
          # (frame fully received) — the worker stamps COMMIT and the
          # rest of the pipeline completes the span.
          trace = msg[4] if len(msg) > 4 else None
          if isinstance(trace, dict):
            telemetry.stamp(trace, telemetry.HOP_WIRE)
          else:
            trace = None
          self._ingest_q.put((conn, msg[1], time.monotonic(),
                              msg[2] if len(msg) > 2 else None,
                              msg[3] if len(msg) > 3 else None,
                              (crc_ctx.computed, crc_ctx.wire)
                              if crc_ctx is not None else None,
                              trace))
        elif kind == 'leave':
          # v9 drain announcement: the host is exiting DELIBERATELY
          # (SIGTERM quiesce), so its unwind records
          # host_left(reason='drain') — survivors tell a planned
          # departure from a crash without any out-of-band channel.
          conn.draining = True
          conn.send(('bye_ack',))
          log.info('host %s announced drain from %s',
                   conn.host_id or '<unnamed>', addr)
          return  # the finally block runs the membership unwind
        elif kind == 'stats':
          # On-demand fleet telemetry (round 13): the unified
          # metrics-registry snapshot + this server's ingest stats,
          # served over the existing control lane — operators, tests,
          # and fleet tooling read the SAME source of truth the drain
          # manifest and flight recorder use, remotely.
          conn.send(('stats', {
              'registry': telemetry.registry().snapshot(),
              'ingest': self.stats(),
          }))
        elif kind == 'infer':
          # v10 routed inference: one carry-passing batch served from
          # the learner's resident version table (attach_serving).
          # Runs ON the reader thread — the request→reply lockstep
          # means one in-flight infer per connection, and routers open
          # a dedicated connection per replica, so the trajectory
          # lane's acks never queue behind a forward pass here. The
          # notice dict's 'draining' flag is how a replica's share
          # drains BEFORE its socket dies.
          with self._params_lock:
            serving_fn = self._serving_fn
            draining = self._draining
          if serving_fn is None:
            conn.send(('error', 'serving not attached'))
          else:
            try:
              result = serving_fn(msg[1])
            except Exception as e:
              log.exception('routed inference request failed')
              conn.send(('error',
                         f'infer failed: {type(e).__name__}: {e}'))
            else:
              conn.send_oob(('infer_ok', result,
                             {'draining': draining}))
        else:
          conn.send(('error', f'unknown message kind {kind!r}'))
      # Loop-condition exit on a closing server: same contract as
      # _ServerClosing below — close() owns the bye/teardown.
      leave_to_close = True
    except ring_buffer.Closed:
      pass  # learner shut down; dropping the conn tells the actor
    except _ServerClosing:
      # close() owns this connection's shutdown from here: leave the
      # socket open and the conn listed so the 'bye' sequence finds
      # it (closing here would race the bye into an RST).
      leave_to_close = True
    except _FrameStall as e:
      # Half-open peer caught MID-frame by the reader's own stall
      # deadline (faster than the reaper's idle window): reap it here
      # — the partial frame never reached the handoff queue, so the
      # buffer cannot be corrupted by it; it is simply discarded with
      # the connection.
      conn.reaped = True
      self._conns_reaped.inc()
      self._discarded_frames.inc()
      self._discarded_bytes.inc(liveness.frame_bytes)
      log.warning('reaping half-open connection %s: %s (partial '
                  'frame discarded: %d byte(s))', addr, e,
                  liveness.frame_bytes)
    except (ValueError, struct.error, pickle.UnpicklingError,
            EOFError) as e:
      # Unparseable frame — a version-skewed peer (a pre-v4 client's
      # untagged pickle starts with opcode 0x80 = "frame kind 128") or
      # garbage on the wire. Must not kill the handler thread
      # silently: log the likely cause and QUARANTINE just this
      # connection (counted — chaos.py's SLO asserts corrupt peers
      # get dropped while the learner keeps training). The discarded
      # frame's size rides the ledger too (round-12 fix: the conn was
      # counted but the thrown-away data never was — an operator
      # could not tell a dropped 40-byte hello from a dropped 2 MB
      # unroll burst).
      self._quarantined.inc()
      self._discarded_frames.inc()
      self._discarded_bytes.inc(liveness.frame_bytes)
      log.warning(
          'protocol/frame error from %s — connection quarantined '
          '(version-skewed peer? this learner speaks v%d; %d byte(s) '
          'discarded): %s', addr, PROTOCOL_VERSION,
          liveness.frame_bytes, e)
    except (ConnectionError, OSError) as e:
      if conn.reaped:
        log.info('remote actor %s reader unwound after reap', addr)
      elif not self._closed.is_set():
        log.warning('remote actor %s dropped: %s', addr, e)
    finally:
      if liveness is not None:
        self._watchdog.unregister(thread_name)
      if not adopted and not leave_to_close:
        conn.sock.close()
      if not leave_to_close:
        left_as = None
        with self._conns_lock:
          if conn in self._conns:
            self._conns.remove(conn)
          # Membership unwind: only the conn CURRENTLY owning the
          # identity records the departure — a reconnect re-pointed
          # the entry before the old reader unwound, so the old
          # conn's exit is a non-event.
          if (conn.host_id is not None
              and self._members.get(conn.host_id) is conn):
            del self._members[conn.host_id]
            reason = ('drain' if conn.draining
                      else 'reaped' if conn.reaped else 'lost')
            left_as = reason
            self._member_events.append(
                {'kind': 'host_left', 'host': conn.host_id,
                 'reason': reason})
        if left_as is not None:
          self._hosts_left.inc()
          log.warning('host %s LEFT the pod (%s)', conn.host_id,
                      left_as)
      if not adopted and not leave_to_close:
        log.info('remote actor %s disconnected', addr)

  def close(self, graceful: bool = True):
    """Shut the server down.

    graceful=True announces a CLEAN end ('bye' frame) so actors exit
    immediately instead of burning their reconnect window against a
    port that will never come back. Pass graceful=False when the
    learner intends to RESTART (exception unwind before a supervisor
    respawn) — actors then keep retrying and resume feeding.

    Graceful shutdown half-closes (SHUT_WR) before the hard close so
    the 'bye' is not discarded by an RST when the client's next
    request races the close; every step is time-bounded (a stuck peer
    cannot hang the learner's teardown).
    """
    self._closed.set()
    # shutdown() BEFORE close(): a thread blocked in accept() holds
    # the open file description, so close() alone leaves the port
    # LISTENing (owner-less) until some stray connection completes
    # the accept — shutdown wakes the blocked accept immediately and
    # releases the port deterministically.
    try:
      self._listener.shutdown(socket.SHUT_RDWR)
    except OSError:
      pass
    try:
      self._listener.close()
    except OSError:
      pass
    # Drain the worker pool (one sentinel per worker) and the param
    # lane before touching the trajectory conns: a worker mid-commit
    # may still send one last ack, which try_send below tolerates.
    # The handoff queue is bounded now: a full queue must not hang
    # close() — workers that miss their sentinel still exit with the
    # closed flag on their next buffer-put poll, or leak as daemons.
    for _ in self._workers:
      try:
        self._ingest_q.put(None, timeout=2.0)
      except queue.Full:
        log.warning('ingest close: handoff queue full; worker will '
                    'exit via the closed flag or leak as a daemon')
        break
    unjoined: List[str] = []
    if self._param_lane.close(graceful=graceful):
      unjoined.append('param-lane')
    with self._conns_lock:
      conns = list(self._conns)
      threads = list(self._threads)
    for conn in conns:
      if graceful:
        conn.try_send(('bye',))
        try:
          # FIN only: the client still reads the buffered 'bye' even
          # if it was mid-send; a full RDWR shutdown + close here can
          # turn into an RST that discards it.
          conn.sock.shutdown(socket.SHUT_WR)
        except OSError:
          pass
      else:
        try:
          conn.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
          pass
        conn.sock.close()
    for t in threads:
      t.join(timeout=2.0)
      if t.is_alive():
        unjoined.append(t.name)
    if graceful:
      for conn in conns:
        conn.sock.close()
    for w in self._workers:
      w.join(timeout=2.0)
      if w.is_alive():
        unjoined.append(w.name)
    self._accept_thread.join(timeout=2.0)
    if self._accept_thread.is_alive():
      unjoined.append('ingest-accept')
    if self._reaper_thread is not None:
      self._reaper_thread.join(timeout=2.0)
      if self._reaper_thread.is_alive():
        unjoined.append('ingest-reaper')
    # Join-deadline misses used to vanish silently (the InferenceServer
    # close parity, round 11 satellite): a leaked reader/worker pins
    # its buffers and a socket for the rest of the process lifetime —
    # count it and NAME it.
    with self._stats_lock:
      self._unjoined_threads = len(unjoined)
    if unjoined:
      log.warning(
          'TrajectoryIngestServer.close(): %d thread(s) missed the '
          'join deadline and leak as daemons: %s', len(unjoined),
          ', '.join(unjoined))


class RemoteActorClient:
  """Actor-side connection to the learner's ingest server.

  Two sockets, one per lane: unrolls/acks ride the trajectory
  connection opened here; `fetch_params` lazily opens a second
  connection onto the server's param lane (`hello_params`) so blob
  transfers never queue behind — or in front of — unroll acks.

  Strict request→reply per socket; NOT thread-safe — one pump thread
  owns it.

  Liveness (round 11): `io_timeout_secs` > 0 arms a recv/send deadline
  on both sockets — a silent learner (partition, hard crash behind a
  live NAT entry) surfaces as a ConnectionError within the window
  instead of pinning the pump forever. The deadline composes with the
  server's ('busy',) keepalives: a slow-but-alive learner emits busy
  frames at the heartbeat cadence while backpressure holds an ack, so
  `_rpc` keeps waiting (each frame is progress); only true silence
  trips the deadline. `session_epoch`/`server_info` are learned at
  handshake; the epoch stamps every unroll so a restarted learner can
  prove zero stale-incarnation unrolls crossed its restart.
  """

  def __init__(self, address: str, connect_timeout_secs: float = 60.0,
               io_timeout_secs: float = 0.0, wire_crc: bool = True):
    host, port = address.rsplit(':', 1)
    self._addr = (host, int(port))
    self._io_timeout = (float(io_timeout_secs)
                        if io_timeout_secs and io_timeout_secs > 0
                        else None)
    self._param_sock: Optional[socket.socket] = None
    # Unrolls the learner's staleness window refused (benign: dropped
    # + refetch; the pump reads this for its logs).
    self.stale_rejections = 0
    # v6 liveness/restart state: the server-info dict from the last
    # params reply, the session epoch this connection handshook under,
    # and how many ('busy',) backpressure keepalives were absorbed.
    self.server_info: Dict = {}
    self.session_epoch: Optional[int] = None
    self.busy_frames = 0
    # v7 payload integrity: offer CRC at hello (`wire_crc`); `_crc`
    # flips on when the handshake reply's server-info confirms the
    # negotiation — from then on every frame both ways carries the
    # trailer. `crc_rejected` counts ('corrupt', crc) refusals of OUR
    # unrolls (a climbing count implicates THIS host's NIC/RAM);
    # `digest_rejected` counts param snapshots refused before install.
    self._wire_crc = bool(wire_crc)
    self._crc = False
    self._param_sock_crc = False  # the cached sub's pinned CRC state
    self.crc_rejected = 0
    self.digest_rejected = 0
    self._digest_nack: Optional[int] = None  # rides the retry fetch
    # v8 trace spans: `trace_ok` flips on when the handshake reply's
    # server-info advertises a tracing learner — unroll frames then
    # carry their trace context as a 5th element, and the most recent
    # params-install event piggybacks on the next one ('pi' notice —
    # the publish→installed-at-actor hop, same pattern as the digest
    # nack).
    self.trace_ok = False
    self._pending_install: Optional[List] = None
    deadline = time.monotonic() + connect_timeout_secs
    last_err = None
    # Capped exponential backoff + full jitter: after a learner
    # restart, hundreds of actor hosts all lose their connection at
    # the same instant — fixed-interval retries would hammer the new
    # listener in lockstep (thundering herd).
    backoff = Backoff(base=0.2, cap=5.0)
    while True:
      try:
        self._sock = socket.create_connection((host, int(port)),
                                              timeout=10.0)
        if self._sock.getsockname() == self._sock.getpeername():
          # Localhost self-connect: while the learner's port is down,
          # the kernel can hand our outbound socket that very port as
          # its ephemeral source, and TCP simultaneous-open "succeeds"
          # against ourselves — a phantom learner that both occupies
          # the port and never replies. Drop it and retry.
          self._sock.close()
          raise OSError('self-connect while learner port is down')
        break
      except OSError as e:  # learner may not be up yet: retry
        last_err = e
        if time.monotonic() > deadline:
          raise ConnectionError(
              f'could not reach learner at {address}: {e}') from e
        backoff.sleep()
    self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    self._sock.settimeout(self._io_timeout)
    log.info('connected to learner at %s (after %s)', address, last_err)

  def _rpc(self, msg, oob: bool = False):
    # Scripted partition/latency (runtime/faults.py round 11): delay
    # sleeps before the send; blackhole goes COMPLETELY silent for its
    # window without closing — the learner-side idle reaper must see
    # half-open silence, and this client then discovers the reaped
    # socket when the partition "heals".
    plan = faults_lib.active()
    delay = faults_lib.fire('conn_delay')
    if delay is not None:
      faults_lib.apply_conn_delay(delay, seed=plan.seed if plan else 0)
    partition = faults_lib.fire('conn_partition')
    if partition is not None:
      faults_lib.apply_conn_partition(partition)
    fault = faults_lib.fire('transport_send')
    if fault is not None:
      # Scripted transport damage (runtime/faults.py): ship garbage/
      # truncated bytes the learner must survive, then surface the
      # OSError this client's reconnect path expects.
      faults_lib.apply_transport_fault(
          fault, self._sock, seed=plan.seed if plan else 0)
    if oob:
      _send_oob(self._sock, msg, crc=self._crc)
    else:
      _send_msg(self._sock, msg, crc=self._crc)
    crc_ctx = _CrcContext() if self._crc else None
    while True:
      try:
        reply = _recv_msg(self._sock, crc_ctx=crc_ctx)
      except socket.timeout as e:
        raise ConnectionError(
            f'learner silent past the {self._io_timeout}s I/O '
            'deadline (no ack, no busy keepalive) — treating the '
            'connection as dead') from e
      except (ValueError, struct.error, pickle.UnpicklingError,
              EOFError) as e:
        raise ProtocolError(
            f'unparseable reply from the learner ({e!r}) — likely a '
            f'protocol-version skew (this client speaks '
            f'v{PROTOCOL_VERSION}); upgrade both roles together') from e
      if reply is None:
        raise ConnectionError('learner closed the connection')
      if crc_ctx is not None and not crc_ctx.ok:
        # A reply failing ITS trailer means the learner→actor
        # direction corrupts: nothing parsed from it can be trusted.
        # ConnectionError on purpose — a fresh connection (and a
        # re-handshake) is the recovery; persistent failures land in
        # the reconnect window where the operator can see them.
        raise ConnectionError(
            f'learner reply failed its CRC trailer (computed '
            f'{crc_ctx.computed:08x}, wire {crc_ctx.wire:08x})')
      if reply[0] == 'busy':
        # Backpressure keepalive (v6): the ack is held back by a full
        # learner buffer, not a dead learner — keep waiting (each
        # frame refreshes the per-recv deadline by arriving).
        self.busy_frames += 1
        continue
      break
    if reply[0] == 'bye':
      raise LearnerShutdown('learner finished training')
    if reply[0] == 'reject':
      raise ContractMismatch(reply[1])
    if reply[0] == 'corrupt':
      # v7: the learner's CRC check refused our unroll — the frame
      # was damaged AFTER we computed its trailer (wire, NIC, or this
      # host's own memory). The connection itself is fine.
      self.crc_rejected += 1
      raise UnrollCorrupt(
          f'learner refused the unroll: payload CRC mismatch (its '
          f'computed crc {reply[1]:08x}) — re-send once, then treat '
          'this host as suspect', crc=reply[1])
    if reply[0] == 'stale_epoch':
      raise SessionEpochMismatch(
          f'learner refused this client\'s session epoch '
          f'{self.session_epoch} (its current epoch: {reply[1]}) — '
          'the learner restarted; re-handshake required')
    if reply[0] == 'error':
      raise RuntimeError(f'learner rejected request: {reply[1]}')
    return reply

  def _decode_params(self, reply, negotiate: bool = False,
                     offered_protocol: Optional[int] = None
                     ) -> Tuple[int, object]:
    """(version, tree) from a params reply; 'params_bf16' blobs
    (learner running remote_params_dtype=bfloat16) upcast back to
    float32 here — the actor's agent/contract only ever sees f32.
    v6 replies carry a 4th element, the server-info dict (protocol,
    session epoch, heartbeat cadence) — recorded here; absent from v5
    servers, in which case the liveness state stays empty.

    v7: the server-info's 'params_digest' is verified against the
    WIRE-form tree (before the upcast — the exact bytes received)
    BEFORE this snapshot can reach update_params. A mismatch raises
    ParamsCorrupt: the caller must NOT install, keeps its prior
    params, and refetches on backoff — a corrupt publish is rejected
    fleet-wide without a version bump. The v7 CRC negotiation resolves
    here ONLY for handshake replies (`negotiate=True`): the server
    pins its side at the hello, so flipping on a mid-stream params
    reply (a lane fetch without a handshake) would desynchronize the
    framing."""
    version, tree = reply[1], reply[2]
    if len(reply) > 3 and isinstance(reply[3], dict):
      self.server_info = reply[3]
      epoch = reply[3].get('session_epoch')
      if epoch is not None:
        self.session_epoch = epoch
      if negotiate:
        self._crc = (self._wire_crc
                     and int(self.server_info.get('protocol') or 0)
                     >= 7
                     and bool(self.server_info.get('wire_crc'))
                     and self.server_info.get('crc_algo') ==
                     integrity.CRC_ALGO)
      if offered_protocol is not None:
        # v8: stamp traces only when BOTH sides speak v8 — keyed on
        # the protocol this client OFFERED (like the CRC negotiation:
        # a forged older contract must land the same negotiation on
        # both sides) AND the server's advertised tracing fact.
        self.trace_ok = (int(offered_protocol) >= 8
                         and int(self.server_info.get('protocol')
                                 or 0) >= 8
                         and bool(self.server_info.get('trace')))
      record = self.server_info.get('params_digest')
      if record is not None:
        verdict = integrity.verify_record(
            record, integrity.tree_digest(tree))
        if verdict is False:
          self.digest_rejected += 1
          self._digest_nack = int(version)
          raise ParamsCorrupt(
              f'params v{version} failed its content digest '
              f'(recorded {record}) — snapshot NOT installed; keep '
              'the prior params and refetch on backoff',
              version=int(version))
        if verdict is None:
          log.warning(
              'params digest not comparable (recorded %r, local algo '
              '%s) — content verification skipped', record,
              integrity.CRC_ALGO)
    if reply[0] == 'params_bf16':
      import jax
      import ml_dtypes
      tree = jax.tree_util.tree_map(
          lambda x: x.astype(np.float32)
          if getattr(x, 'dtype', None) == ml_dtypes.bfloat16 else x,
          tree)
    elif reply[0] == 'params_int8':
      # v10 int8 blobs (runtime/codec.py): the digest above covered
      # the WIRE form (q arrays + scales); the host decode to f32
      # happens only after it verified.
      from scalable_agent_tpu.runtime import codec as codec_lib
      tree = codec_lib.dequantize_np(tree)
    return version, tree

  def handshake(self, contract, prior_epoch: Optional[int] = None,
                host: Optional[str] = None) -> Tuple[int, object]:
    """Offer this host's trajectory contract; returns (version,
    params) on agreement, raises ContractMismatch (naming the
    offending fields) when the learner refuses. The handshake blob
    rides the trajectory connection (once per connect — before any
    unroll is in flight, so there is no ack to starve).

    `prior_epoch` (v6): the session epoch of the learner this host was
    attached to before the drop, if any — a RESTARTED learner sees a
    foreign epoch and counts/times the fleet re-attach; old servers
    ignore the extra hello element. The same client-info dict carries
    the v7 CRC offer (algorithm included — mixed-fallback pairs must
    negotiate the check OFF, not miscompare) and the v9 `host`
    identity for the learner's elastic membership ledger."""
    # Offer CRC only when the CONTRACT itself speaks v7: tests (and
    # mixed fleets mid-upgrade) legitimately offer an older protocol
    # through a forged contract, and the negotiation must then land
    # identically on both sides — the server keys on the offered
    # protocol, so the client must too.
    offered_protocol = (contract.get('protocol')
                        if isinstance(contract, dict) else None)
    # A non-dict contract reaches the server as a legacy hello (its
    # reader keys protocol 5) — never offer CRC there.
    offer_crc = (self._wire_crc and offered_protocol is not None
                 and int(offered_protocol) >= 7)
    info: Dict = {}
    if prior_epoch is not None:
      info['epoch'] = int(prior_epoch)
    if offer_crc:
      info['crc'] = True
      info['crc_algo'] = integrity.CRC_ALGO
    if host is not None:
      # v9 membership: a stable host identity enters the learner's
      # ledger (join/leave events, live-host gauge). Old servers
      # ignore the extra key — offering it costs nothing.
      info['host'] = str(host)
    msg = ('hello', contract, info) if info else ('hello', contract)
    if not offer_crc:
      self._crc = False
    self.trace_ok = False  # re-negotiated per handshake below
    return self._decode_params(
        self._rpc(msg), negotiate=offer_crc,
        offered_protocol=(int(offered_protocol)
                          if offered_protocol is not None else None))

  def ping(self) -> int:
    """Application-level heartbeat on the trajectory lane (v6): keeps
    an idle connection inside the learner's reaping window and returns
    the learner's CURRENT params version from the pong — so an idle
    fleet still notices publishes. Raises like any rpc on a dead
    learner (the pump's reconnect path runs)."""
    reply = self._rpc(('ping',))
    if reply[0] != 'pong':
      raise ProtocolError(f'expected pong, got {reply[0]!r}')
    return reply[1]

  def fetch_params(self) -> Tuple[int, object]:
    """(version, host param pytree) — the current learner snapshot,
    fetched over the dedicated param lane. A lane failure closes just
    the param socket and surfaces as ConnectionError/OSError; the
    caller's reconnect path rebuilds both lanes. A CACHED lane that
    died between fetches (the learner's idle reaper legitimately reaps
    a long-quiet subscriber) retries ONCE on a fresh param socket
    before surfacing — a reaped sub must not cost the whole
    trajectory connection a reconnect cycle."""
    had_cached_lane = self._param_sock is not None
    try:
      return self._fetch_params_once()
    except (ConnectionError, OSError) as e:
      if not had_cached_lane:
        raise
      log.info('param lane died between fetches (%s); retrying once '
               'on a fresh subscriber connection', e)
      return self._fetch_params_once()

  def _fetch_params_once(self) -> Tuple[int, object]:
    if self._param_sock is None:
      try:
        sock = socket.create_connection(self._addr, timeout=10.0)
      except OSError:
        raise ConnectionError(
            f'could not open the param lane to {self._addr}')
      sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
      sock.settimeout(self._io_timeout)
      # The hello_params itself is pre-negotiation (no trailer); with
      # CRC already negotiated on the trajectory lane (handshake),
      # the info dict turns the same machinery on for this subscriber
      # — every subsequent frame on the lane carries trailers both
      # ways. The lane's state is PINNED at open: a later handshake
      # flipping self._crc must not desynchronize a cached sub.
      # v10: the info dict ALWAYS carries 'protocol' — the lane picks
      # this subscriber's blob encoding from it (an int8 publisher
      # hands v<=9 subscribers the bf16 compat blob); a v<=9 server
      # reads only the crc keys and ignores the rest.
      if self._crc:
        _send_msg(sock, ('hello_params',
                         {'protocol': PROTOCOL_VERSION, 'crc': True,
                          'crc_algo': integrity.CRC_ALGO}))
      else:
        _send_msg(sock, ('hello_params',
                         {'protocol': PROTOCOL_VERSION}))
      self._param_sock = sock
      self._param_sock_crc = self._crc
    lane_crc = self._param_sock_crc
    try:
      # A digest-rejected notice from a prior corrupt fetch rides the
      # retry, so the learner's publish_digest_rejected ledger sees
      # the fleet-side refusal without a dedicated side channel.
      # Independent of lane CRC: digests ship (and verify) whenever
      # the server is v7 — which is the only way _digest_nack gets
      # set — and the lane's parser reads the notice regardless of
      # its own trailer negotiation (a wire_crc=False server must not
      # be blind to fleet-side refusals).
      if self._digest_nack is not None:
        req = ('get_params', {'digest_rejected': self._digest_nack})
      else:
        req = ('get_params',)
      self._digest_nack = None
      _send_msg(self._param_sock, req, crc=lane_crc)
      crc_ctx = _CrcContext() if lane_crc else None
      reply = _recv_msg(self._param_sock, crc_ctx=crc_ctx)
      if reply is not None and crc_ctx is not None and not crc_ctx.ok:
        self._close_param_sock()
        raise ConnectionError(
            f'param blob failed its CRC trailer (computed '
            f'{crc_ctx.computed:08x}, wire {crc_ctx.wire:08x}) — '
            'wire corruption; refetching on a fresh subscriber')
    except socket.timeout as e:
      self._close_param_sock()
      raise ConnectionError(
          f'param lane silent past the {self._io_timeout}s I/O '
          'deadline') from e
    except (ValueError, struct.error, pickle.UnpicklingError,
            EOFError) as e:
      self._close_param_sock()
      raise ProtocolError(
          f'unparseable param-lane reply ({e!r}) — likely a '
          f'protocol-version skew (this client speaks '
          f'v{PROTOCOL_VERSION}); upgrade both roles together') from e
    except OSError:
      self._close_param_sock()
      raise
    if reply is None:
      self._close_param_sock()
      raise ConnectionError('learner closed the param lane')
    if reply[0] == 'bye':
      # Graceful lane shutdown (round 11): a clean end-of-training
      # answer instead of a raw EOF the client must diagnose.
      self._close_param_sock()
      raise LearnerShutdown('learner finished training (param lane)')
    if reply[0] == 'error':
      raise RuntimeError(f'learner rejected param fetch: {reply[1]}')
    return self._decode_params(reply)

  def _close_param_sock(self):
    if self._param_sock is not None:
      try:
        self._param_sock.close()
      except OSError:
        pass
      self._param_sock = None

  def note_install(self, version: int):
    """Record a params install (update_params completed actor-side);
    the event piggybacks on the NEXT traced unroll frame ('pi'
    notice) so the learner's traces.jsonl carries the
    publish→installed-at-actor hop without a dedicated side channel.
    Only the latest install is kept — the hop of interest is the
    freshest version's propagation."""
    self._pending_install = [int(version), round(time.time(), 6)]

  def send_unroll(self, unroll,
                  params_version: Optional[int] = None,
                  trace: Optional[Dict] = None) -> int:
    """Ship one ActorOutput; returns the learner's params version.
    Uses the out-of-band frame: the unroll's frame stacks ARE the
    message, so they go raw instead of through the pickler.

    `params_version` (when known) rides the frame so a learner running
    a staleness window (--max_unroll_staleness) can judge admission. A
    ('stale', current) reply means the unroll was REFUSED benignly:
    counted on `stale_rejections`, and the returned (newer) version
    makes the caller's refetch-on-newer path fire — the same contract
    as an ack, minus the landed unroll.

    When this client handshook with a v6 learner, the SESSION EPOCH
    stamps the frame too (4th element, ignored by old servers): a
    learner incarnation this unroll does not belong to refuses it
    with 'stale_epoch' → SessionEpochMismatch (ConnectionError — the
    reconnect/re-handshake path is the response).

    `trace` (v8, when tracing negotiated): the unroll's trace context
    — stamped HOP_SEND here and shipped as the 5th frame element so
    the learner completes the span. A pending params-install notice
    rides it ('pi'); on a refusal/resend the SAME context ships again
    (the duplicate hop stamps tell the report a resend happened)."""
    if trace is not None and self.trace_ok:
      telemetry.stamp(trace, telemetry.HOP_SEND)
      if self._pending_install is not None:
        trace['pi'] = self._pending_install
        self._pending_install = None
      msg = ('unroll', unroll,
             None if params_version is None else int(params_version),
             None if self.session_epoch is None
             else int(self.session_epoch),
             trace)
    elif self.session_epoch is not None:
      msg = ('unroll', unroll,
             None if params_version is None else int(params_version),
             int(self.session_epoch))
    elif params_version is None:
      msg = ('unroll', unroll)
    else:
      msg = ('unroll', unroll, int(params_version))
    reply = self._rpc(msg, oob=True)
    if reply[0] == 'stale':
      self.stale_rejections += 1
    return reply[1]

  def fetch_stats(self) -> Dict:
    """The learner's on-demand telemetry snapshot (v8 'stats' request
    on the trajectory lane): {'registry': <unified metrics-registry
    snapshot>, 'ingest': <ingest server stats>}. Raises like any rpc
    against a dead/old learner (old servers answer 'error' → the
    RuntimeError path)."""
    reply = self._rpc(('stats',))
    if reply[0] != 'stats':
      raise ProtocolError(f'expected stats, got {reply[0]!r}')
    return reply[1]

  def supports_infer(self) -> bool:
    """True when the handshaken server advertised protocol >= 10 —
    the routed-inference capability gate (routing.py skips pre-v10
    replicas instead of burning a request on the 'error' reply)."""
    return int(self.server_info.get('protocol') or 0) >= 10

  def remote_infer(self, payload: dict) -> Tuple[dict, dict]:
    """One routed inference batch (v10): ship `payload` (the
    InferenceServer.serve_remote dict — batch-leading numpy arrays)
    out-of-band, return (result dict, notice dict). The notice
    carries 'draining' — routing.py drains this replica's share when
    it flips. Raises RuntimeError against a server with no serving
    attached (or a pre-v10 server: 'error', unknown kind)."""
    reply = self._rpc(('infer', payload), oob=True)
    if reply[0] == 'error':
      raise RuntimeError(f'routed inference refused: {reply[1]}')
    if reply[0] != 'infer_ok':
      raise ProtocolError(f'expected infer_ok, got {reply[0]!r}')
    notice = reply[2] if len(reply) > 2 and isinstance(reply[2], dict) \
        else {}
    return reply[1], notice

  def send_leave(self) -> bool:
    """Announce a DELIBERATE exit (v9 drain): the learner records
    host_left(reason='drain') instead of 'lost' when this connection
    unwinds. Best-effort by design — True when the learner
    acknowledged, False against an old server (('error', unknown
    kind) → RuntimeError) or a dead connection; the caller closes
    and exits either way, never gated on the announcement."""
    try:
      reply = self._rpc(('leave', {}))
    except (RuntimeError, OSError, LearnerShutdown):
      return False
    return reply[0] == 'bye_ack'

  def close(self):
    self._close_param_sock()
    try:
      self._sock.close()
    except OSError:
      pass


def run_remote_actor(config, learner_address: str, task: int = 0,
                     stop_after_unrolls: Optional[int] = None,
                     platform: Optional[str] = 'cpu',
                     connect_timeout_secs: float = 120.0,
                     reconnect_secs: Optional[float] = None) -> int:
  """Actor-only host main loop (reference --job_name=actor --task=N).

  Builds a CPU inference server + actor fleet against params fetched
  from the learner, pumps unrolls to the learner's ingest server, and
  refreshes params whenever an ack reports a newer version. Returns the
  number of unrolls shipped. Runs until the learner closes the
  connection (normal end of training) or `stop_after_unrolls`.

  Args:
    config: the SAME Config the learner runs with (env/model knobs must
      agree — the reference shares one flag set across jobs too).
    learner_address: host:port of the learner's ingest server.
    task: this actor host's index; offsets env seeds so hosts explore
      independently (reference --task).
    stop_after_unrolls: optional unroll budget (tests).
    platform: force this jax platform BEFORE first jax use ('cpu' for
      actor hosts — they have no accelerator; None = leave as-is).
    reconnect_secs: elasticity (defaults to
      config.actor_reconnect_secs): when > 0 and the connection drops,
      keep retrying the learner for this many seconds — the fleet
      pauses on buffer backpressure meanwhile — then resume feeding
      with freshly fetched params. This is how actor hosts survive a
      learner restart-from-checkpoint (SURVEY §5.3 is greenfield; the
      reference's actors just die). 0 = exit on disconnect.
      Delivery is at-least-once: an unroll whose ack was lost in the
      drop is resent on the new connection — a duplicate trajectory at
      the learner, harmless to the off-policy math (same class as any
      stale in-flight unroll).
  """
  if platform:
    import jax
    jax.config.update('jax_platforms', platform)

  from scalable_agent_tpu import config as config_lib
  from scalable_agent_tpu import driver as driver_lib
  from scalable_agent_tpu.envs import factory
  from scalable_agent_tpu.runtime.inference import InferenceServer

  if reconnect_secs is None:
    reconnect_secs = getattr(config, 'actor_reconnect_secs', 0.0)
  for warning in config_lib.validate_transport(config):
    log.warning('%s', warning)
  for warning in config_lib.validate_integrity(config):
    log.warning('%s', warning)
  # Round 15: the probation cool-down vs idle-reaping cross-link (the
  # CRC probation sleep happens on THIS host's pump).
  for warning in config_lib.validate_controller(config):
    log.warning('%s', warning)
  # Client-side I/O deadline: the idle window doubles as "how long do
  # I wait on a silent learner" — symmetric with the server's reaping
  # of silent clients. Busy keepalives keep a backpressured-but-alive
  # learner inside it.
  io_timeout = getattr(config, 'remote_conn_idle_timeout_secs', 0.0)
  wire_crc = bool(getattr(config, 'wire_crc', True))
  levels = factory.level_names(config)
  spec0 = factory.make_env_spec(config, levels[0], seed=1)
  agent = driver_lib.build_agent(config, spec0.num_actions,
                                 num_tasks=len(levels))

  contract = trajectory_contract(config, agent, spec0.num_actions)
  # v9 membership identity: stable for THIS host process's lifetime
  # (reconnects keep it — a reconnect is a non-event in the learner's
  # ledger), unique across hosts and across restarts of the same task
  # slot (the pid) — a replacement host for the same task is a fresh
  # join, which is exactly what the elastic storm asserts.
  host_id = f'{socket.gethostname()}:{os.getpid()}:task{task}'
  client = RemoteActorClient(learner_address,
                             connect_timeout_secs=connect_timeout_secs,
                             io_timeout_secs=io_timeout,
                             wire_crc=wire_crc)
  unrolls_sent = 0
  # SIGTERM drain (round 20, riding the PR 6 quiesce idiom): the
  # handler only flips an event — the pump notices at its next wake,
  # quiesces the fleet, ANNOUNCES the departure ('leave' → the
  # learner records host_left(reason='drain') instead of 'lost') and
  # exits cleanly. Registered best-effort: under a non-main thread
  # (tests drive this function directly) signal.signal raises
  # ValueError and the drain stays externally triggerable only.
  drain = threading.Event()

  def _on_sigterm(signum, frame):
    del signum, frame
    log.warning('remote actor task=%d received SIGTERM — draining '
                '(quiesce fleet, announce leave, exit)', task)
    drain.set()

  try:
    signal.signal(signal.SIGTERM, _on_sigterm)
  except ValueError:
    pass  # not the main thread: no signal-driven drain
  # Integrity ledger across reconnects (client objects are replaced):
  # CRC refusals of our unrolls (with the round-15 probation rung),
  # digest-refused publishes, and whether this host took itself out
  # of the fleet.
  probation = CrcProbation(
      cooldown_secs=getattr(config, 'fleet_probation_secs', 30.0))
  digest_rejections = 0
  self_quarantined = False
  try:
    # The hello reply IS a cached params frame, so the STARTUP
    # handshake can meet a corrupt publish exactly like a mid-run
    # refetch — and must get the same bounded-backoff retries (the
    # corrupt blob is superseded at the next publish cadence), not a
    # fleet-shrinking crash.
    backoff = Backoff(base=0.3, cap=3.0)
    for attempt in range(5):
      try:
        version, params = client.handshake(contract, host=host_id)
        break
      except LearnerShutdown:
        # Connected just as training ended: a clean no-op, not a
        # crash.
        log.info('learner already finished training; remote actor '
                 'exiting')
        return 0
      except ParamsCorrupt as e:
        digest_rejections += 1
        log.error('remote actor task=%d: handshake params failed '
                  'their digest (%s) — attempt %d/5', task, e,
                  attempt + 1)
        if attempt == 4:
          raise
        backoff.sleep()
    known_epoch = client.session_epoch  # None against a v5 learner
    # Heartbeat cadence is the SERVER's call (negotiated via its
    # hello-reply info dict): 0 / absent (v5 learner) = no pings.
    heartbeat_secs = float(
        client.server_info.get('heartbeat_secs') or 0.0)
    log.info('remote actor task=%d got params v%d (epoch=%s, '
             'heartbeat=%.1fs)', task, version, known_epoch,
             heartbeat_secs)

    # Seed space DISJOINT from the learner hosts' (driver.train uses
    # process_index * max(num_actors, 1000) for env streams and
    # config.seed + 1000/2000 + base for sampling): a mixed topology
    # (local fleet + remote hosts) must not run bit-identical RNG
    # streams in the same training batch.
    seed_base = _REMOTE_SEED_SPACE + task * max(config.num_actors, 1000)
    server = InferenceServer(agent, params, config,
                             seed=config.seed + seed_base,
                             fleet_size=config.num_actors)
    server.warmup(spec0.obs_spec, max_size=config.num_actors)
    buffer = ring_buffer.TrajectoryBuffer(
        max(2 * config.num_actors, 2))
    # Trace-span stamping (round 13, v8): this host stamps HOP_DONE on
    # each completed unroll with the behaviour params version it acted
    # with (`version` is the pump's live binding — reads see every
    # refresh); the pump ships the context on the wire and the learner
    # completes the span. Negotiated: against a non-tracing/older
    # learner the pump pops the tags and drops them.
    if getattr(config, 'telemetry_trace', True):
      telemetry.configure_actor_tracing(version_fn=lambda: version,
                                        epoch=known_epoch)
    client.note_install(version)  # the handshake install IS the first
    fleet = driver_lib.make_fleet(
        config, agent, server.policy, buffer, levels,
        seed_base=seed_base, level_offset=task * config.num_actors,
        initial_state_fn=server.initial_core_state)
    fleet.start()

    def reconnect():
      """New client + fresh params after a drop; False = gave up.

      Retries the WHOLE connect+fetch cycle until the deadline: a
      connection that resets right after connecting (learner mid-
      restart, listener backlog races) must not end the actor."""
      nonlocal client, version, known_epoch, heartbeat_secs
      client.close()
      deadline = time.monotonic() + reconnect_secs
      # Jittered backoff between whole connect+handshake cycles: the
      # fleet must not re-handshake against a restarting learner in
      # lockstep (the constructor's connect loop jitters its own
      # retries; this covers handshake-level failures).
      backoff = Backoff(base=0.2, cap=5.0)
      while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
          log.info('remote actor task=%d gave up reconnecting', task)
          return False
        try:
          new_client = RemoteActorClient(learner_address,
                                         connect_timeout_secs=remaining,
                                         io_timeout_secs=io_timeout,
                                         wire_crc=wire_crc)
        except ConnectionError:
          continue  # connect window exhausted → loop exits above
        try:
          # The prior epoch rides the hello: a RESTARTED learner (new
          # epoch) counts this as a fleet re-attach and times it.
          v, new_params = new_client.handshake(contract,
                                               prior_epoch=known_epoch,
                                               host=host_id)
        except ContractMismatch:
          # The restarted learner runs an INCOMPATIBLE config: retrying
          # cannot succeed — surface it instead of burning the window.
          new_client.close()
          raise
        except (OSError, RuntimeError):
          new_client.close()
          backoff.sleep()
          continue
        client = new_client
        version = v
        if (known_epoch is not None
            and new_client.session_epoch != known_epoch):
          log.warning(
              'remote actor task=%d RE-ATTACHED to a restarted '
              'learner (epoch %s -> %s); params refreshed to v%d',
              task, known_epoch, new_client.session_epoch, version)
        known_epoch = new_client.session_epoch
        heartbeat_secs = float(
            new_client.server_info.get('heartbeat_secs') or 0.0)
        server.update_params(new_params)
        new_client.note_install(v)
        if getattr(config, 'telemetry_trace', True):
          # Fresh epoch on every (re)handshake: spans must name the
          # learner incarnation their unrolls actually fed.
          telemetry.configure_actor_tracing(
              version_fn=lambda: version, epoch=known_epoch)
        log.info('remote actor task=%d reconnected, params v%d',
                 task, version)
        return True

    elastic = bool(reconnect_secs) and reconnect_secs > 0

    def resume_after_drop():
      """True to keep going after a dropped connection (crash path);
      False = give up and exit."""
      if elastic and reconnect():
        return True
      log.info('learner connection closed; remote actor exiting')
      return False

    def refresh_params():
      """Fetch + install the current snapshot (version-gated on the
      server side against redundant copies).

      v7 integrity: a snapshot failing its content digest is NOT
      installed — the inference arena keeps the prior params. Retried
      on backoff a bounded number of times (the corrupt blob is
      CACHED learner-side, so it stays corrupt until the next
      publish); giving up keeps training on the old snapshot and the
      next ack's newer version triggers the refetch of a clean one.
      The rejection itself is reported to the learner on the retry's
      get_params (publish_digest_rejected)."""
      nonlocal version, params, digest_rejections
      backoff = Backoff(base=0.2, cap=2.0)
      for attempt in range(3):
        try:
          v, p = client.fetch_params()
        except ParamsCorrupt as e:
          digest_rejections += 1
          log.error('remote actor task=%d: %s (attempt %d/3)', task,
                    e, attempt + 1)
          if attempt == 2:
            log.error(
                'remote actor task=%d: giving up on params v%s — '
                'keeping v%d; the next publish will be refetched',
                task, e.version, version)
            return
          backoff.sleep()
          continue
        version, params = v, p
        server.update_params(params, version=version)
        # The install event (the publish→installed-at-actor hop)
        # piggybacks on the next traced unroll frame.
        client.note_install(version)
        log.info('remote actor task=%d refreshed params to v%d',
                 task, version)
        return

    try:
      unroll = None  # a drop mid-send must not lose the unroll
      unroll_trace = None  # its trace context rides every (re)send
      last_io = time.monotonic()
      while (not drain.is_set() and
             (stop_after_unrolls is None or
              unrolls_sent < stop_after_unrolls)):
        if unroll is None:
          probation.next_unroll()
          try:
            # With heartbeats negotiated, wake often enough to ping an
            # idle trajectory lane inside the learner's reaping window.
            get_timeout = (min(10.0, heartbeat_secs)
                           if heartbeat_secs > 0 else 10.0)
            unroll = buffer.get(timeout=get_timeout)
            unroll_trace = telemetry.pop_unroll(unroll)
          except TimeoutError:
            fleet.check_health(stall_timeout_secs=300.0)
            errors = fleet.errors()
            if errors:
              raise errors[0]
            if (heartbeat_secs > 0 and
                time.monotonic() - last_io >= heartbeat_secs):
              # Idle heartbeat: keeps the conn out of the reaper's
              # window AND learns about publishes while quiet (the
              # pong carries the current version).
              try:
                pong_version = client.ping()
                last_io = time.monotonic()
                if pong_version > version:
                  refresh_params()
              except OSError:
                if not resume_after_drop():
                  break
                last_io = time.monotonic()
            continue
        try:
          # The current params version rides along so a staleness-
          # windowed learner can judge admission; a 'stale' refusal
          # still returns the newer version, so the refetch below
          # fires and the NEXT unroll ships fresh.
          ack_version = client.send_unroll(unroll,
                                           params_version=version,
                                           trace=unroll_trace)
        except UnrollCorrupt as e:
          # The learner's CRC refused our frame. Once is wire noise:
          # re-send the SAME unroll (at-least-once, like any lost
          # ack). Twice for the same unroll means the corruption is
          # on THIS host's path (NIC/RAM — the learner verified
          # against the trailer WE computed). Round 15: before the
          # terminal self-quarantine, ONE probation rung — cool down,
          # re-send the same unroll as a single probe, and only
          # quarantine on repeat failure (docs/RUNBOOK.md §9) — so a
          # transient (an overheated NIC, a since-replaced DIMM)
          # doesn't cost the fleet this host forever.
          last_io = time.monotonic()
          verdict = probation.on_refusal()
          if verdict == CrcProbation.QUARANTINE:
            self_quarantined = True
            log.error(
                'remote actor task=%d SELF-QUARANTINED: the same '
                'unroll failed the learner CRC twice (%s) — suspect '
                'NIC/memory on this host; exiting the fleet', task, e)
            break
          if verdict == CrcProbation.PROBE:
            log.error(
                'remote actor task=%d: CRC PROBATION — the same '
                'unroll failed the learner CRC twice (%s); cooling '
                'down %.1fs then sending ONE probe (repeat failure '
                'quarantines this host)', task, e,
                probation.cooldown_secs)
            # Cool down WITHOUT going silent: a cool-down longer than
            # the learner's idle window would otherwise get this conn
            # reaped as half-open mid-probation — ping at the
            # heartbeat cadence (best-effort; a reap/drop surfaces on
            # the probe send, which owns the reconnect path).
            cool_end = time.monotonic() + probation.cooldown_secs
            while True:
              remaining = cool_end - time.monotonic()
              if remaining <= 0:
                break
              time.sleep(min(remaining, heartbeat_secs)
                         if heartbeat_secs > 0 else remaining)
              if heartbeat_secs > 0 and \
                 time.monotonic() < cool_end:
                try:
                  client.ping()
                except OSError:
                  break  # dropped mid-cool-down: probe send handles it
            last_io = time.monotonic()
            continue
          log.warning('remote actor task=%d: unroll failed the '
                      'learner CRC (%s); re-sending once', task, e)
          continue
        except OSError:
          # OSError, not just ConnectionError: a blackholed learner
          # host surfaces as ETIMEDOUT — or the round-11 client-side
          # I/O deadline fired on pure silence — and both must
          # trigger the reconnect window. SessionEpochMismatch (the
          # learner restarted under us) rides the same path: the
          # reconnect IS the re-handshake.
          if resume_after_drop():
            last_io = time.monotonic()
            continue  # resend the SAME unroll on the new connection
          break
        last_io = time.monotonic()
        if probation.on_ack():
          log.warning(
              'remote actor task=%d: CRC probation probe ACCEPTED — '
              'host recovered; staying in the fleet', task)
        unroll = None
        unroll_trace = None
        unrolls_sent += 1
        if ack_version > version:
          try:
            # Version-gated on the server side: a refetch racing the
            # publish cadence can hand back the version already being
            # served — the whole-tree copy is skipped for it (stats:
            # publishes_skipped).
            refresh_params()
            last_io = time.monotonic()
          except OSError:
            # Dropped between ack and refresh; reconnect() refetches.
            if not resume_after_drop():
              break
            last_io = time.monotonic()
      if drain.is_set():
        # Quiesce first (no more unrolls can be produced against the
        # announced-gone connection), then tell the learner this is a
        # DELIBERATE exit — best-effort: an old/dead learner just
        # records 'lost' when the socket closes below.
        fleet.stop()
        acked = client.send_leave()
        log.warning('remote actor task=%d drained cleanly after %d '
                    'unroll(s) (leave %s)', task, unrolls_sent,
                    'acked' if acked else 'not acked — old learner?')
    except LearnerShutdown:
      # Clean end of training ('bye'): no reconnect window to burn.
      log.info('learner finished training; remote actor exiting')
    except ring_buffer.Closed:
      log.info('local buffer closed; remote actor exiting')
    finally:
      telemetry.clear_actor_tracing()
      fleet.stop()
      server.close()
  finally:
    client.close()
  log.info('remote actor task=%d shipped %d unrolls', task,
           unrolls_sent)
  if (probation.crc_resends or probation.probations
      or digest_rejections or self_quarantined):
    # Greppable one-liner for harnesses (chaos.py) and operators: the
    # client-side half of the integrity ledger (the learner's stats
    # carry the server-side half).
    log.warning(
        'INTEGRITY_REPORT task=%d crc_resends=%d digest_rejections=%d '
        'crc_probations=%d crc_probation_recoveries=%d '
        'self_quarantined=%s', task, probation.crc_resends,
        digest_rejections, probation.probations, probation.recoveries,
        self_quarantined)
  return unrolls_sent

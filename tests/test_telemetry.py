"""The round-13 telemetry plane: unified metrics registry, per-unroll
trace spans (v8 wire negotiation + learner-side completion), the
flight recorder, and trace_report reconstruction.

The e2e test is the acceptance bar: a 2-process fleet run (learner +
no-accelerator remote child) whose traces.jsonl reconstructs
per-unroll hop-by-hop latency and the per-batch policy-lag histogram
through scripts/trace_report.py.
"""

import json
import math
import os
import socket
import threading
import time

import numpy as np
import pytest

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.runtime import remote, ring_buffer
from scalable_agent_tpu.structs import (ActorOutput, AgentOutput,
                                        StepOutput, StepOutputInfo)
from scripts import trace_report


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


# --------------------------------------------------------------------
# Metrics registry.
# --------------------------------------------------------------------


def test_registry_counter_gauge_histogram_snapshot():
  reg = telemetry.MetricsRegistry()
  c = reg.counter('t/c')
  c.inc()
  c.inc(4)
  g = reg.gauge('t/g')
  g.set(2.5)
  backing = {'n': 7}
  reg.gauge('t/lazy', fn=lambda: backing['n'])
  h = reg.histogram('t/h')
  for v in (1.0, 2.0, 3.0):
    h.observe(v)
  snap = reg.snapshot()
  assert snap['t/c'] == 5
  assert snap['t/g'] == 2.5
  assert snap['t/lazy'] == 7
  assert snap['t/h']['count'] == 3
  assert snap['t/h']['p50'] == 2.0
  backing['n'] = 9  # lazy gauges read live values
  assert reg.snapshot()['t/lazy'] == 9


def test_registry_replaces_by_name_latest_wins():
  reg = telemetry.MetricsRegistry()
  old = reg.counter('t/c')
  old.inc(10)
  new = reg.counter('t/c')  # a new component incarnation
  new.inc(1)
  assert reg.snapshot()['t/c'] == 1  # the live incarnation


def test_gauge_callback_failure_reads_nan():
  reg = telemetry.MetricsRegistry()
  reg.gauge('t/boom', fn=lambda: 1 / 0)
  assert math.isnan(reg.snapshot()['t/boom'])


def test_histogram_empty_percentiles_are_nan():
  h = telemetry.Histogram('t/h')
  p50, p99 = h.percentiles(0.5, 0.99)
  assert math.isnan(p50) and math.isnan(p99)
  assert math.isnan(h.snapshot_value()['p50'])


# --------------------------------------------------------------------
# Trace contexts + sidecar tag store.
# --------------------------------------------------------------------


def test_make_trace_and_stamp():
  tr = telemetry.make_trace('a-0', 3, epoch=7, behavior_version=2)
  telemetry.stamp(tr, telemetry.HOP_DONE, t=1.0)
  telemetry.stamp(tr, telemetry.HOP_SEND, t=2.0)
  assert tr['a'] == 'a-0' and tr['s'] == 3
  assert tr['e'] == 7 and tr['bv'] == 2
  assert tr['h'] == [['done', 1.0], ['send', 2.0]]
  assert telemetry.stamp(None, telemetry.HOP_WIRE) is None  # tolerant


def test_tag_store_identity_keyed_and_bounded():
  store = telemetry._TagStore(capacity=2)
  a, b, c = _tiny_unroll(1), _tiny_unroll(2), _tiny_unroll(3)
  store.tag(a, {'a': 'x'})
  store.tag(b, {'a': 'y'})
  store.tag(c, {'a': 'z'})  # evicts the oldest (a)
  assert store.pop(a) is None
  assert store.evicted == 1
  assert store.pop(b) == {'a': 'y'}
  assert store.pop(b) is None  # popped once


# --------------------------------------------------------------------
# PipelineTracer: staged/served FIFOs, lag clocks, traces.jsonl.
# --------------------------------------------------------------------


def _read_jsonl(path):
  with open(path) as f:
    return [json.loads(line) for line in f if line.strip()]


def test_tracer_completes_spans_and_batch_records(tmp_path):
  tracer = telemetry.PipelineTracer(str(tmp_path))
  try:
    tracer.on_publish(10)  # local publish clock -> 1
    u1, u2 = _tiny_unroll(1), _tiny_unroll(2)
    for i, u in enumerate((u1, u2)):
      tr = telemetry.make_trace('local-0', i, behavior_version=0)
      telemetry.stamp(tr, telemetry.HOP_DONE)
      tracer.tag(u, tr)
    tracer.on_batch([u1, u2], n_fresh=2)
    tracer.on_serve()
    tracer.on_step(5)
    records = _read_jsonl(tracer.path)
  finally:
    tracer.close()
  kinds = [r['k'] for r in records]
  assert kinds == ['publish', 'batch']
  batch = records[-1]
  assert batch['step'] == 5 and batch['n_fresh'] == 2
  # Local clock: publish count 1 - behaviour version 0 = lag 1.
  assert batch['lag'] == [1, 1]
  for span in batch['spans']:
    hops = [h[0] for h in span['h']]
    assert hops == ['done', 'staged', 'serve', 'step']
  assert tracer.stats()['batches'] == 1
  assert tracer.stats()['unrolls'] == 2


def test_tracer_remote_clock_uses_commit_version(tmp_path):
  tracer = telemetry.PipelineTracer(str(tmp_path))
  try:
    u = _tiny_unroll(1)
    tr = telemetry.make_trace('r0', 0, behavior_version=4)
    tr['cv'] = 9  # what the ingest worker stamps at commit
    tracer.tag(u, tr)
    tracer.on_batch([u], n_fresh=1)
    tracer.on_serve()
    tracer.on_step(1)
    records = _read_jsonl(tracer.path)
  finally:
    tracer.close()
  assert records[-1]['lag'] == [5]  # 9 - 4, ingest clock


def test_tracer_untagged_unrolls_counted(tmp_path):
  tracer = telemetry.PipelineTracer(str(tmp_path))
  try:
    u = _tiny_unroll(1)  # never tagged
    # The id-keyed sidecar documents one benign hazard: a freed
    # unroll from an earlier test can leave a stale tag at this
    # object's reused address. Drop any alias so 'never tagged' holds.
    telemetry.pop_unroll(u)
    tracer.on_batch([u], n_fresh=1)
    assert tracer.stats()['untagged_unrolls'] == 1
  finally:
    tracer.close()


def test_flight_recorder_ring_and_registry_snapshots():
  flight = telemetry.FlightRecorder(capacity=8, snapshots=2)
  for i in range(20):
    flight.record({'k': 'batch', 'step': i})
  flight.note_registry({'a': 1})
  flight.note_registry({'a': 2})
  flight.note_registry({'a': 3})
  dump = flight.dump()
  assert len(dump['records']) == 8
  assert dump['records'][-1]['step'] == 19
  assert [s['metrics']['a'] for s in dump['registry_snapshots']] == \
      [2, 3]


def test_flight_recorder_write_is_json(tmp_path):
  flight = telemetry.FlightRecorder()
  flight.record({'k': 'publish', 'v': 1})
  path = flight.write(str(tmp_path / 'flight.json'))
  with open(path) as f:
    dump = json.load(f)
  assert dump['records'][0]['v'] == 1


# --------------------------------------------------------------------
# v8 wire negotiation + remote stamping.
# --------------------------------------------------------------------


def test_v8_trace_negotiated_and_span_stamped_across_wire(tmp_path):
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1')
  tracer = telemetry.PipelineTracer(str(tmp_path))
  telemetry.set_tracer(tracer)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    assert client.trace_ok
    client.note_install(1)
    unroll = _tiny_unroll(1)
    tr = telemetry.make_trace('child-0', 0, behavior_version=1)
    telemetry.stamp(tr, telemetry.HOP_DONE)
    client.send_unroll(unroll, params_version=1, trace=tr)
    landed = buffer.get(timeout=5)
    span = telemetry.pop_unroll(landed)
    assert span is not None
    hops = [h[0] for h in span['h']]
    assert hops == ['done', 'send', 'wire', 'commit']
    assert span['cv'] == 1  # ingest publish clock at commit
    assert 'pi' not in span  # install notice consumed server-side
    assert tracer.stats()['param_installs'] == 1
    records = _read_jsonl(tracer.path)
    installs = [r for r in records if r['k'] == 'install']
    assert installs and installs[0]['a'] == 'child-0'
    assert installs[0]['v'] == 1
  finally:
    telemetry.set_tracer(None)
    tracer.close()
    client.close()
    server.close()
    buffer.close()


def test_v8_v7_interop_trace_negotiated_off(tmp_path):
  """A forged v7 contract keeps the old wire exactly: trace_ok stays
  off and unroll frames carry no 5th element (the server parses them
  as v7)."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1')
  tracer = telemetry.PipelineTracer(str(tmp_path))
  telemetry.set_tracer(tracer)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': 7})
    assert not client.trace_ok
    tr = telemetry.make_trace('old-0', 0)
    client.send_unroll(_tiny_unroll(1), params_version=1, trace=tr)
    landed = buffer.get(timeout=5)
    assert telemetry.pop_unroll(landed) is None
    assert tracer.stats()['untagged_unrolls'] == 0  # just no span
  finally:
    telemetry.set_tracer(None)
    tracer.close()
    client.close()
    server.close()
    buffer.close()


def test_trace_off_server_negotiates_off(tmp_path):
  """--telemetry_trace=false learner: server-info advertises no
  tracing, the client doesn't stamp."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1', trace=False)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    assert not client.trace_ok
  finally:
    client.close()
    server.close()
    buffer.close()


def test_stats_request_serves_registry_snapshot():
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1')
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    client.send_unroll(_tiny_unroll(1))
    stats = client.fetch_stats()
    assert stats['ingest']['unrolls'] == 1
    # The registry view of the same counter — one source of truth.
    assert stats['registry']['ingest/unrolls'] == 1
    assert 'ingest/ack_ms' in stats['registry']
  finally:
    client.close()
    server.close()
    buffer.close()


# --------------------------------------------------------------------
# Prefetcher integration: spans complete through the real feed path.
# --------------------------------------------------------------------


def test_prefetcher_completes_spans_through_feed(tmp_path):
  tracer = telemetry.PipelineTracer(str(tmp_path))
  telemetry.set_tracer(tracer)
  buffer = ring_buffer.TrajectoryBuffer(8)
  try:
    for i in range(4):
      u = _tiny_unroll(i)
      tr = telemetry.begin_unroll_trace('local-0', i)
      assert tr is not None  # tracer installed -> tracing on
      telemetry.stamp(tr, telemetry.HOP_DONE)
      telemetry.tag_unroll(u, tr)
      buffer.put(u)
    prefetcher = ring_buffer.BatchPrefetcher(buffer, 4,
                                             place_fn=lambda b: b)
    prefetcher.get(timeout=10)
    tracer.on_step(1)
    records = _read_jsonl(tracer.path)
    batch = [r for r in records if r['k'] == 'batch'][-1]
    assert len(batch['spans']) == 4
    for span in batch['spans']:
      assert [h[0] for h in span['h']] == ['done', 'staged', 'serve',
                                           'step']
    # Behaviour version defaulted to the tracer's publish clock (0).
    assert batch['lag'] == [0, 0, 0, 0]
    prefetcher.close()
  finally:
    telemetry.set_tracer(None)
    tracer.close()
    buffer.close()


# --------------------------------------------------------------------
# trace_report reconstruction.
# --------------------------------------------------------------------


def test_trace_report_summarize_hops_and_lag(tmp_path):
  tracer = telemetry.PipelineTracer(str(tmp_path))
  # Backdated synthetic stamps: the staged/serve/step hops are
  # stamped at REAL now by the tracer, and the round-14 skew rule
  # drops negative deltas — future-dated done/send/wire stamps would
  # read as clock skew.
  t0 = time.time() - 30.0
  tracer.on_publish(1)
  for step in range(3):
    u = _tiny_unroll(step)
    tr = telemetry.make_trace('a0', step, behavior_version=0)
    telemetry.stamp(tr, telemetry.HOP_DONE, t0 + step)
    telemetry.stamp(tr, telemetry.HOP_SEND, t0 + step + 0.010)
    telemetry.stamp(tr, telemetry.HOP_WIRE, t0 + step + 0.030)
    tracer.tag(u, tr)
    tracer.on_batch([u], n_fresh=1)
    tracer.on_serve()
    tracer.on_step(step)
  # Install AFTER the publish in record order (summarize sorts by t).
  tracer.on_install('a0', 1, time.time() + 0.5)
  tracer.close()

  records = trace_report.load_traces(str(tmp_path))
  summary = trace_report.summarize(records)
  assert summary['batches'] == 3 and summary['unrolls'] == 3
  hops = {row['hop']: row for row in summary['hops']}
  assert hops['done->send']['count'] == 3
  assert abs(hops['done->send']['p50_ms'] - 10.0) < 2.0
  assert abs(hops['send->wire']['p50_ms'] - 20.0) < 2.0
  assert 'wire->staged' in hops and 'serve->step' in hops
  assert summary['policy_lag']['histogram'] == {1: 3}
  assert summary['publish_to_install_secs']['count'] == 1
  # The text renderer never crashes on the summary (NaN -> '-').
  text = trace_report.render(summary)
  assert 'policy lag' in text


def test_trace_report_render_handles_empty():
  summary = trace_report.summarize([])
  text = trace_report.render(summary)
  assert '-' in text  # NaN percentiles render as '-'


def test_span_hop_deltas_duplicate_resend_stamps():
  """A resend re-stamps send/wire; the FIRST stamp per hop is the
  latency story (round-14 satellite: pinned on a pathological
  stream, not just documented)."""
  span = {'h': [['done', 10.0], ['send', 10.5], ['wire', 11.0],
                ['send', 13.0], ['wire', 14.0], ['commit', 11.2]]}
  deltas, e2e = trace_report.span_hop_deltas(span)
  assert dict(((a, b), ms) for (a, b), ms in deltas) == {
      ('done', 'send'): pytest.approx(500.0),
      ('send', 'wire'): pytest.approx(500.0),
      ('wire', 'commit'): pytest.approx(200.0, abs=1e-6)}
  assert e2e == pytest.approx(1200.0)


def test_span_hop_deltas_clock_skew_renders_dash_never_zero():
  """Cross-host wall clocks can skew past each other (NTP): a
  negative hop delta must surface as '-' (None), never a laundered
  0 ms — and never a crash."""
  span = {'h': [['done', 100.0], ['send', 100.2], ['wire', 99.8],
                ['commit', 100.4]]}
  deltas, e2e = trace_report.span_hop_deltas(span)
  by_pair = dict(deltas)
  assert by_pair[('send', 'wire')] is None          # skewed: no number
  assert by_pair[('wire', 'commit')] == pytest.approx(600.0)
  assert e2e == pytest.approx(400.0)                # done <= commit
  # A span whose LAST hop skews before its first: no e2e either.
  skewed = {'h': [['done', 100.0], ['send', 99.0]]}
  deltas, e2e = trace_report.span_hop_deltas(skewed)
  assert deltas == [(('done', 'send'), None)] and e2e is None
  # summarize() skips the skewed hops instead of polluting p50 with
  # zeros, and the renderer stays crash-free.
  rec = {'k': 'batch', 'step': 1, 't': 100.0, 'lag': [],
         'spans': [span, skewed]}
  summary = trace_report.summarize([rec])
  hops = {row['hop']: row for row in summary['hops']}
  assert 'send->wire' not in hops  # only skewed observations existed
  assert hops['wire->commit']['count'] == 1
  assert trace_report.render(summary)


def test_span_hop_deltas_malformed_stamps_never_crash():
  for h in (None, 'junk', [['done']], [['done', 'not-a-time']],
            [[1, 2, 3]], [None]):
    deltas, e2e = trace_report.span_hop_deltas({'h': h})
    assert deltas == [] and e2e is None


def test_trace_report_main_empty_traces_file(tmp_path, capsys):
  """An empty traces.jsonl (a run that died before its first batch)
  exits 1 with the how-to hint, never a crash."""
  (tmp_path / 'traces.jsonl').write_text('')
  assert trace_report.main([str(tmp_path)]) == 1
  assert 'no traces' in capsys.readouterr().err


def test_to_tensorboard_skips_skewed_hop_points():
  """to_tensorboard consumes the same span_hop_deltas: a skewed hop
  contributes NO scalar point (round-14 satellite — the two views
  keep agreeing)."""
  from scripts import to_tensorboard
  event = {'k': 'batch', 'step': 3, 'lag': [1],
           'spans': [{'h': [['done', 100.0], ['send', 99.0],
                            ['wire', 100.5]]}]}
  rows = to_tensorboard._trace_events(event)
  tags = [t for t, _, _ in rows]
  assert 'trace/hop_done_send_ms' not in tags  # skewed: skipped
  assert 'trace/hop_send_wire_ms' in tags
  assert 'trace/policy_lag_mean' in tags


# --------------------------------------------------------------------
# Acceptance: 2-process fleet run -> trace_report reconstruction.
# --------------------------------------------------------------------


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_e2e_remote_fleet_traces_and_report(tmp_path):
  """The acceptance bar: a learner + a no-accelerator remote actor
  child (2 OS processes) train with tracing on; traces.jsonl then
  reconstructs per-unroll hop-by-hop latency across the wire
  (done→send→wire→commit→staged→serve→step) and the per-batch
  policy-lag histogram, and the summary scalars carry the live
  policy-lag percentiles."""
  import _remote_actor_child
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config

  base = dict(
      env_backend='bandit', batch_size=2, unroll_length=5,
      num_action_repeats=1, episode_length=4, height=24, width=32,
      torso='shallow', use_py_process=False, use_instruction=False,
      total_environment_frames=10**6, inference_timeout_ms=5,
      checkpoint_secs=0, summary_secs=0, seed=17,
      publish_params_every=1)
  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  learner_cfg = Config(logdir=str(tmp_path), num_actors=0,
                       remote_actor_port=port, **base)
  child = _remote_actor_child.spawn(f'127.0.0.1:{port}',
                                    dict(base, num_actors=2))
  try:
    run = driver.train(learner_cfg, max_steps=4,
                       stall_timeout_secs=120)
    assert int(run.state.update_steps) == 4
    out, _ = child.communicate(timeout=120)
    assert child.returncode == 0, out[-2000:]
  finally:
    if child.poll() is None:
      child.kill()
      child.communicate()

  records = trace_report.load_traces(str(tmp_path))
  summary = trace_report.summarize(
      records, trace_report.load_incidents(str(tmp_path)))
  assert summary['batches'] >= 3
  assert summary['unrolls'] >= 6
  hops = {row['hop'] for row in summary['hops']}
  # The full remote pipeline, hop by hop, across both processes.
  for hop in ('done->send', 'send->wire', 'wire->commit',
              'commit->staged', 'staged->serve', 'serve->step'):
    assert hop in hops, (hop, hops)
  assert summary['e2e_ms']['count'] >= 6
  assert not math.isnan(summary['e2e_ms']['p99'])
  # Policy lag: behaviour versions rode the wire; the histogram is
  # the publish-delta distribution (≥0, small on a healthy loopback).
  lag_hist = summary['policy_lag']['histogram']
  assert lag_hist and sum(lag_hist.values()) >= 6
  assert all(int(k) >= 0 for k in lag_hist)
  # Publish→install joins: the child reported at least its handshake
  # install, and versions joined against publish records.
  assert summary['publish_to_install_secs']['count'] >= 1
  # The report renders end to end.
  text = trace_report.render(summary)
  assert 'per-hop latency' in text
  # Live summary export: the lag percentiles reached summaries.jsonl.
  with open(os.path.join(str(tmp_path), 'summaries.jsonl')) as f:
    tags = {json.loads(line)['tag'] for line in f if line.strip()}
  for tag in ('policy_lag_p50', 'policy_lag_p99', 'unroll_e2e_p50_ms',
              'unroll_e2e_p99_ms', 'trace_untagged_unrolls',
              # Round-14 satellites: the flight-recorder ring and the
              # JSONL dropped-writes ledger reach summaries.jsonl end
              # to end (before, only the trace scalars were asserted).
              'trace_flight_records', 'dropped_writes'):
    assert tag in tags, tag


def test_halt_bundle_carries_flight_dump(tmp_path):
  from scalable_agent_tpu import health as health_lib
  monitor = health_lib.HealthMonitor()
  flight = telemetry.FlightRecorder()
  flight.record({'k': 'batch', 'step': 7, 'lag': [2]})
  flight.note_registry({'ingest/unrolls': 5})
  path = monitor.write_halt_bundle(str(tmp_path), None, step=7,
                                   reason='test', flight=flight.dump())
  with open(path) as f:
    bundle = json.load(f)
  assert bundle['flight']['records'][0]['step'] == 7
  assert bundle['flight']['registry_snapshots'][0]['metrics'] == \
      {'ingest/unrolls': 5}


def test_health_counters_reach_registry():
  from scalable_agent_tpu import health as health_lib
  monitor = health_lib.HealthMonitor()
  monitor.observe_values(1, {'step_ok': 0.0})
  snap = telemetry.registry().snapshot()
  assert snap['health/skipped_steps'] == 1
  assert snap['health/flagged_steps'] == 1


def test_flight_recorder_gauges_registered_and_unregistered(tmp_path):
  """Round-14 satellite: the tracer registers fn-gauges over its
  flight ring (trace/flight_records, trace/flight_snapshots) and
  unregisters them at close — identity-checked like every other
  per-run fn-gauge."""
  reg = telemetry.registry()
  tracer = telemetry.PipelineTracer(str(tmp_path))
  try:
    tracer.flight.record({'k': 'batch', 'step': 1})
    tracer.flight.note_registry({'a': 1})
    snap = reg.snapshot()
    assert snap['trace/flight_records'] == 1
    assert snap['trace/flight_snapshots'] == 1
    assert len(tracer.flight) == 1
  finally:
    tracer.close()
  assert reg.get('trace/flight_records') is None
  assert reg.get('trace/flight_snapshots') is None


def test_dropped_writes_total_counts_post_close_writes(tmp_path):
  before = telemetry.dropped_writes_total()
  writer = telemetry.JsonlAppender(str(tmp_path), 'x.jsonl')
  writer.close()
  writer.write({'late': True})
  assert telemetry.dropped_writes_total() == before + 1


def test_trace_report_hop_order_matches_telemetry():
  """trace_report keeps its own literal HOP_ORDER (operator machines
  run it without the package's dependency chain) — this is the pin
  that keeps the two in sync."""
  assert tuple(trace_report.HOP_ORDER) == telemetry.HOP_ORDER


def test_closed_components_unregister_their_gauges():
  """fn-gauges close over their owner: close() must drop the
  registry's hold (identity-checked — a newer incarnation's
  registration survives an older one's teardown)."""
  reg = telemetry.registry()
  buffer = ring_buffer.TrajectoryBuffer(4)
  assert reg.get('buffer/occupancy') is not None
  buffer2 = ring_buffer.TrajectoryBuffer(4)  # replaces the names
  buffer.close()  # older instance: must NOT evict buffer2's gauges
  assert reg.get('buffer/occupancy') is buffer2._gauges[0]
  buffer2.close()
  assert reg.get('buffer/occupancy') is None


def test_malformed_trace_context_does_not_kill_the_reader(tmp_path):
  """A buggy v8 peer shipping a trace dict without a stamp list must
  not crash the ingest reader outside the quarantine accounting —
  stamp() repairs the shape and the unroll still lands + acks."""
  assert telemetry.stamp({'a': 'x'}, telemetry.HOP_WIRE)['h']
  assert telemetry.stamp({'a': 'x', 'h': 'junk'},
                         telemetry.HOP_WIRE)['h']
  buffer = ring_buffer.TrajectoryBuffer(4)
  server = remote.TrajectoryIngestServer(buffer, {'w': np.zeros(2)},
                                         host='127.0.0.1')
  tracer = telemetry.PipelineTracer(str(tmp_path))
  telemetry.set_tracer(tracer)
  client = remote.RemoteActorClient(f'127.0.0.1:{server.port}',
                                    connect_timeout_secs=10)
  try:
    client.handshake({'protocol': remote.PROTOCOL_VERSION})
    # Bypass send_unroll's stamping: ship the malformed context raw.
    reply = client._rpc(('unroll', _tiny_unroll(1), None, None,
                         {'a': 'buggy-peer'}), oob=True)
    assert reply[0] == 'ack'
    landed = buffer.get(timeout=5)
    span = telemetry.pop_unroll(landed)
    assert [h[0] for h in span['h']] == ['wire', 'commit']
    stats = server.stats()
    assert stats['unrolls'] == 1 and stats['quarantined'] == 0
  finally:
    telemetry.set_tracer(None)
    tracer.close()
    client.close()
    server.close()
    buffer.close()


def test_publish_install_join_uses_ingest_lane_version(tmp_path):
  """Install notices carry the ingest lane's version sequence; the
  join must key on the publish record's 'rv', not the step-stamped
  label (which is a different clock at production cadences)."""
  tracer = telemetry.PipelineTracer(str(tmp_path))
  t0 = time.time()
  # Step-stamped label 100, ingest-lane version 2 (the sequences
  # diverge immediately at publish_params_every > 1).
  tracer.on_publish(100, remote_version=2)
  tracer.on_install('a0', 2, t0 + 0.25)
  tracer.on_publish(200)  # local-only publish: no 'rv', no join key
  tracer.close()
  summary = trace_report.summarize(
      trace_report.load_traces(str(tmp_path)))
  assert summary['publish_to_install_secs']['count'] == 1


# --------------------------------------------------------------------
# Span recorder.
# --------------------------------------------------------------------


@pytest.fixture
def recorder_off():
  """Whatever a test arms, the next one starts with the recorder off."""
  telemetry.take_spans()
  yield
  telemetry.take_spans()


def test_recorder_off_records_nothing_and_a_site_allocates_nothing(
    recorder_off):
  import tracemalloc
  assert telemetry.take_spans() is None
  assert telemetry.span('actor/step') is telemetry.NO_SPAN
  assert telemetry.span('x', id=('a', 1)) is telemetry.NO_SPAN

  def sites(n):
    for _ in range(n):
      with telemetry.span('actor/step'):
        pass
      s = telemetry.span('learner/publish')
      s.end()

  sites(100)  # whatever the first pass caches
  only = [tracemalloc.Filter(True, telemetry.__file__)]
  tracemalloc.start()
  try:
    before = tracemalloc.take_snapshot().filter_traces(only)
    sites(1000)
    after = tracemalloc.take_snapshot().filter_traces(only)
  finally:
    tracemalloc.stop()
  grown = [s for s in after.compare_to(before, 'lineno')
           if s.size_diff > 0]
  assert not grown, grown
  assert telemetry.take_spans() is None


def test_armed_rows_nest_and_share_their_id(recorder_off):
  clock = telemetry.arm_spans()
  with telemetry.span('actor/unroll', id=('actor-0', 7)):
    with telemetry.span('actor/step'):
      with telemetry.span('batcher/compute'):
        time.sleep(0.001)
    explicit = telemetry.span('inference/dispatch', id=41)
    explicit.end()
    explicit.end()  # ends once
    with telemetry.span('actor/assemble'):
      pass
  with telemetry.span('learner/publish'):
    pass
  taken = telemetry.take_spans()
  assert taken['clock'] == clock and taken['dropped'] == 0
  assert taken['taken_ns'] > clock['perf_ns']
  rows = {row[0]: row for row in taken['spans']}
  assert [row[0] for row in taken['spans']] == [
      'batcher/compute', 'actor/step', 'inference/dispatch',
      'actor/assemble', 'actor/unroll', 'learner/publish']
  # Inner spans inherit the enclosing span's id; one given its own
  # keeps it and hands the outer one back; outside, none.
  for name in ('actor/unroll', 'actor/step', 'batcher/compute',
               'actor/assemble'):
    assert rows[name][4] == ('actor-0', 7), name
  assert rows['inference/dispatch'][4] == 41
  assert rows['learner/publish'][4] is None
  outer, inner = rows['actor/unroll'], rows['batcher/compute']
  assert outer[1] <= rows['actor/step'][1] <= inner[1]
  assert inner[2] <= rows['actor/step'][2] <= outer[2]
  assert inner[2] - inner[1] >= 1_000_000
  me = threading.get_ident()
  assert {row[3] for row in taken['spans']} == {me}
  assert taken['threads'][me] == threading.current_thread().name
  # The clock pair puts a row on the wall clock of traces.jsonl.
  wall = (outer[1] - clock['perf_ns'] + clock['wall_ns']) / 1e9
  assert abs(wall - time.time()) < 5.0
  # Taken means off: nothing is kept afterwards.
  with telemetry.span('late'):
    pass
  assert telemetry.take_spans() is None


def test_a_span_open_across_an_end_of_the_armed_interval_is_dropped(
    recorder_off):
  telemetry.arm_spans()
  straddles_stop = telemetry.span('a')
  telemetry.take_spans()
  telemetry.arm_spans()
  straddles_stop.end()  # began under another arming
  assert telemetry.take_spans()['spans'] == []


def test_a_park_that_straddles_either_end_is_kept_and_clipped(
    recorder_off):
  """A learner's wait of seconds straddles an end of a capture more
  often than not (PERF.md: seed 57 lost 8.4 of 14.7 idle seconds)."""
  began_before = telemetry.park('learner/wait_batch')
  never_armed = telemetry.park('staging/wait_unrolls')
  never_armed.end()
  time.sleep(0.002)
  clock = telemetry.arm_spans()
  with telemetry.park('inference/wait_batch') as inside:
    inside.id = 9  # known only once the wait is over
  began_before.end()
  open_at_take = telemetry.park('actor/put', id=('actor-1', 3))
  taken = telemetry.take_spans()
  open_at_take.end()  # its real end, recorder off: adds nothing
  rows = {row[0]: row for row in taken['spans']}
  assert sorted(rows) == ['actor/put', 'inference/wait_batch',
                          'learner/wait_batch']
  assert rows['learner/wait_batch'][1] == clock['perf_ns']  # clipped
  assert rows['learner/wait_batch'][2] > clock['perf_ns']
  assert rows['inference/wait_batch'][4] == 9
  assert rows['actor/put'][2] == taken['taken_ns']  # closed at take
  assert rows['actor/put'][4] == ('actor-1', 3)
  assert all(t0 <= t1 for _, t0, t1, _, _ in taken['spans'])
  assert not telemetry._open_parks
  # Still parked when the next capture arms: kept again, from there.
  long_wait = telemetry.park('learner/wait_batch')
  telemetry.arm_spans()
  telemetry.take_spans()
  second = telemetry.arm_spans()
  long_wait.end()
  (row,) = telemetry.take_spans()['spans']
  assert row[0] == 'learner/wait_batch' and row[1] == second['perf_ns']


def test_the_bound_drops_rows_and_counts_them(recorder_off):
  counter = telemetry.registry().get('trace/spans_dropped')
  before = counter.value
  telemetry.arm_spans(max_spans=3)
  for _ in range(5):
    with telemetry.span('actor/step'):
      pass
  parked = telemetry.park('actor/put')
  taken = telemetry.take_spans()
  parked.end()
  assert len(taken['spans']) == 3 and taken['dropped'] == 3
  assert counter.value == before + 3
  assert 'trace/spans_dropped' in telemetry.registry().snapshot()


def test_spans_of_other_threads_carry_their_thread(recorder_off):
  telemetry.arm_spans()

  def work():
    with telemetry.span('actor/step'):
      pass

  threads = [threading.Thread(target=work, name=f'actor-{i}')
             for i in range(3)]
  for t in threads:
    t.start()
  idents = {t.ident for t in threads}
  for t in threads:
    t.join()
  taken = telemetry.take_spans()
  assert {row[3] for row in taken['spans']} == idents


def test_trace_report_summarizes_a_span_capture(tmp_path, capsys):
  ms = 1_000_000
  taken = {
      'clock': {'perf_ns': 0, 'wall_ns': 0}, 'taken_ns': 100 * ms,
      'dropped': 0, 'threads': {'1': 'actor-0', '2': 'learner'},
      'spans': [
          # thread 1: a step of 10 ms holding a 6 ms park and, in a
          # 3 ms env step, a 2 ms pipe; then a step of 20 ms alone
          ['batcher/compute', 1 * ms, 7 * ms, 1, ['actor-0', 0]],
          ['env/pipe', 7 * ms, 9 * ms, 1, ['actor-0', 0]],
          ['actor/env_step', 7 * ms, 10 * ms, 1, ['actor-0', 0]],
          ['actor/step', 0, 10 * ms, 1, ['actor-0', 0]],
          ['actor/step', 10 * ms, 30 * ms, 1, ['actor-0', 0]],
          # thread 2 overlaps thread 1 in time: not its child
          ['learner/publish', 2 * ms, 6 * ms, 2, None]]}
  summary = trace_report.summarize_spans(taken)
  rows = {row['name']: row for row in summary['spans']}
  assert summary['seconds'] == pytest.approx(0.1)
  assert rows['actor/step']['count'] == 2
  assert rows['actor/step']['total_s'] == pytest.approx(0.030)
  # 30 ms less the park's 6 and the env step's 3 (its DIRECT children).
  assert rows['actor/step']['self_s'] == pytest.approx(0.021)
  assert rows['actor/env_step']['self_s'] == pytest.approx(0.001)
  assert rows['env/pipe']['self_s'] == pytest.approx(0.002)
  assert rows['learner/publish']['self_s'] == pytest.approx(0.004)
  assert rows['actor/step']['p50_ms'] in (10.0, 20.0)
  assert rows['actor/step']['per_s'] == pytest.approx(20.0)
  path = tmp_path / 'spans.json'
  path.write_text(json.dumps(taken))
  assert trace_report.main([str(path)]) == 0
  out = capsys.readouterr().out
  assert 'span report' in out and 'batcher/compute' in out


def test_the_feed_pipeline_records_its_waits_and_its_staging(
    recorder_off):
  """`learner/wait_batch` covers what `wait_secs` sums; the staging
  thread's wait for unrolls, parked since before the arming, is kept
  from the arming on."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  prefetcher = ring_buffer.BatchPrefetcher(buffer, 2)
  try:
    time.sleep(0.01)  # the staging thread parks on the empty buffer
    clock = telemetry.arm_spans()
    with pytest.raises(TimeoutError):
      prefetcher.get(timeout=0.02)
    for seed in range(2):
      buffer.put(_tiny_unroll(seed))
    batch = prefetcher.get(timeout=5)
    assert batch.env_outputs.reward.shape == (3, 2)
    taken = telemetry.take_spans()
  finally:
    prefetcher.close()
    buffer.close()
  rows = taken['spans']
  waits = [r for r in rows if r[0] == 'learner/wait_batch']
  assert len(waits) == 2 and waits[0][2] - waits[0][1] >= 20_000_000
  waited = sum(t1 - t0 for _, t0, t1, _, _ in waits) / 1e9
  assert waited == pytest.approx(prefetcher.stats()['wait_secs'],
                                 abs=0.01)
  staging = [r for r in rows if r[0].startswith('staging/')]
  assert [r[0] for r in staging[:2]] == ['staging/wait_unrolls',
                                         'staging/stage']
  assert staging[0][1] == clock['perf_ns']  # parked before the arming
  assert staging[0][3] == staging[1][3] != waits[0][3]
  # Its next wait was still under way at the take: closed there.
  assert staging[-1][0] == 'staging/wait_unrolls'
  assert staging[-1][2] == taken['taken_ns']


# --------------------------------------------------------------------
# Cycle records, activities and the mean's excess (PR 37).
# --------------------------------------------------------------------


def test_a_cycle_record_wraps_and_keeps_its_cumulative_sums():
  record = telemetry.CycleRecord(('wait', 'work'), extras=('rows',),
                                 rows=8, cycle_from=1)
  for c in range(21):  # two laps and five
    t = 1_000 * c
    record.write(t, t + 10, t + 10 + c, 3)
  totals = record.totals()
  assert totals == {'cycles': 21, 'wait_ns': 210,
                    'work_ns': sum(range(21)), 'rows': 63}
  first, rows = record.held()
  assert first == 13 and len(rows) == 8  # the ring's, oldest first
  assert rows[:, 0].tolist() == [1_000 * c for c in range(13, 21)]
  assert record.held(since=19)[0] == 19 and len(record.held(last=2)[1]) == 2
  summary = record.summary()
  assert (summary['cycles'], summary['held']) == (21, 8)
  # A cycle's length counts from `cycle_from`: the work, not the wait.
  assert summary['cycle'] == summary['work']
  assert summary['work']['max'] == pytest.approx(20 / 1e6)
  assert summary['work']['p50'] == pytest.approx(17 / 1e6)  # nearest rank
  assert summary['wait']['mean'] == pytest.approx(10 / 1e6)
  empty = telemetry.CycleRecord(('a',)).summary()
  assert empty['held'] == 0 and empty['cycle']['p95'] == 0.0


def test_a_reader_never_takes_a_half_written_row_for_a_cycle():
  """The writer laps a ring of 16 rows thousands of times while a
  reader copies it: every row the reader gets is one the writer wrote
  whole (its three stamps 7 apart, its extra their sum)."""
  record = telemetry.CycleRecord(('a', 'b'), extras=('check',), rows=16)
  stop = threading.Event()

  def write():
    c = 0
    while not stop.is_set():
      record.write(c, c + 7, c + 14, 3 * c + 21)
      c += 1

  writer = threading.Thread(target=write)
  writer.start()
  try:
    seen = 0
    deadline = time.monotonic() + 20
    while seen < 20_000 and time.monotonic() < deadline:
      first, rows = record.held()
      assert (rows[:, 1] - rows[:, 0] == 7).all()
      assert (rows[:, 2] - rows[:, 1] == 7).all()
      assert (rows[:, 3] == rows[:, :3].sum(axis=1)).all()
      assert (np.diff(rows[:, 0]) == 1).all()  # consecutive cycles
      assert not len(rows) or rows[0, 0] == first
      summary = record.summary()
      if summary['held']:  # (a lap during the copy may leave none)
        assert summary['a']['max'] == summary['b']['p50'] == 7 / 1e6
      seen += len(rows)
  finally:
    stop.set()
    writer.join()
  assert seen >= 20_000 and record.cycles > 16


def _ten_short_one_long(t0, t1):
  """Ten cycles of 10 ns, then the cycle [t0, t1]: median 10."""
  record = telemetry.CycleRecord(('step',), rows=64)
  for c in range(10):
    record.write(100 * c, 100 * c + 10)
  record.write(t0, t1)
  return record


@pytest.mark.parametrize('parks,named,unnamed', [
    # The long cycle wholly inside a park: all of its excess is there.
    ([('learner/publish', 4_900, 5_500)], {'learner/publish': 100.0}, 0.0),
    # A park wholly inside the long cycle: its share of the cycle.
    ([('learner/publish', 5_022, 5_055)], {'learner/publish': 30.0}, 70.0),
    # Two parks at once: each is charged what lay under it, `unnamed`
    # what lay under neither (by their union: 5,011..5,077).
    ([('learner/publish', 5_011, 5_066), ('staging/stage', 5_044, 5_077)],
     {'learner/publish': 50.0, 'staging/stage': 30.0}, 40.0),
    # None: the excess has no name.
    ([], {}, 100.0),
    # A park that ended before the cycle began, and one of a short
    # cycle (no excess there to charge).
    ([('learner/publish', 4_000, 4_990), ('staging/stage', 300, 310)],
     {}, 100.0),
], ids=['cycle_in_park', 'park_in_cycle', 'two_parks', 'none', 'elsewhere'])
def test_excess_on_intervals_made_by_hand(parks, named, unnamed):
  record = _ten_short_one_long(5_000, 5_110)  # 110 long: 100 over
  found = telemetry.excess(record, parks=parks)
  assert found['excess_ns'] == pytest.approx(100.0)
  assert found['unnamed_ns'] == pytest.approx(unnamed)
  for name in telemetry.ACTIVITIES:  # every known name, at zero too
    assert found['in_ns'][name] == pytest.approx(named.get(name, 0.0))
  assert (found['cycles_judged'], found['cycles_lost']) == (11, 0)
  flat = telemetry.excess_ms('step_', found)
  assert flat['step_excess_ms'] == pytest.approx(100.0 / 1e6)
  assert flat['step_excess_ms_unnamed'] == pytest.approx(unnamed / 1e6)
  assert flat['step_excess_ms_in_learner/publish'] == pytest.approx(
      named.get('learner/publish', 0.0) / 1e6)
  # Cumulative, and each cycle judged once: a second call adds nothing.
  again = telemetry.excess(record, parks=parks)
  assert again == found


def test_excess_is_cumulative_and_counts_the_rows_a_lap_took():
  record = telemetry.CycleRecord(('step',), rows=8)
  for c in range(8):
    record.write(100 * c, 100 * c + 10)
  assert telemetry.excess(record, parks=[])['excess_ns'] == 0.0
  record.write(1_000, 1_030)  # 20 over the median, under a park of its own
  first = telemetry.excess(record, parks=[('x/y', 0, 2_000)])
  assert first['excess_ns'] == first['in_ns']['x/y'] == pytest.approx(20.0)
  # Twenty more before anyone asks: twelve are gone with the lap.
  for c in range(20):
    record.write(2_000 + 100 * c, 2_010 + 100 * c)
  later = telemetry.excess(record, parks=[])
  assert (later['cycles_lost'], later['cycles_judged']) == (12, 17)
  assert later['excess_ns'] == pytest.approx(20.0)  # kept from before
  assert telemetry.excess_ms('a_', first, later)['a_cycles_lost'] == 12


def test_a_finished_activity_is_remembered_with_the_recorder_off(
    recorder_off):
  assert telemetry.take_spans() is None
  before = len(telemetry.activities())
  with telemetry.activity('learner/publish', id=7):
    time.sleep(0.002)
    under_way = telemetry.activities()[-1]
    assert under_way[0] == 'learner/publish'  # closed at `now` meanwhile
  with telemetry.park('learner/wait_batch'):  # a wait is not work
    pass
  with telemetry.span('actor/step'):
    pass
  kept = telemetry.activities()
  assert len(kept) == before + 1
  name, t0, t1 = kept[-1]
  assert name == 'learner/publish' and t1 - t0 >= 2_000_000
  assert t0 == under_way[1] and under_way[2] <= t1
  # Ended twice (a `finally` after the block's own end): kept once.
  site = telemetry.activity('learner/summaries')
  site.end()
  site.end()
  assert [a[0] for a in telemetry.activities()[before:]] == [
      'learner/publish', 'learner/summaries']


def test_an_armed_capture_sees_an_activity_as_the_span_it_was(
    recorder_off):
  telemetry.arm_spans()
  with telemetry.span('learner/step_dispatch', id=('learner', 3)):
    with telemetry.activity('learner/publish'):
      time.sleep(0.001)
  with telemetry.activity('inference/prefill', id=5):
    pass
  taken = telemetry.take_spans()
  rows = {row[0]: row for row in taken['spans']}
  assert set(rows) == {'learner/step_dispatch', 'learner/publish',
                       'inference/prefill'}
  outer, publish = rows['learner/step_dispatch'], rows['learner/publish']
  # Name, both stamps inside its parent's, thread, and the id its
  # enclosing span gave it; its own id where it was given one.
  assert outer[1] <= publish[1] < publish[2] <= outer[2]
  assert publish[3] == threading.get_ident()
  assert publish[4] == ('learner', 3) and rows['inference/prefill'][4] == 5
  # And the ring has the same two, on the same clock.
  assert telemetry.activities()[-2][1:] == (publish[1], publish[2])

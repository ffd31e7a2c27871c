"""Bench mechanics smoke: the transport-ceiling bench must keep
working on CPU (its numbers feed docs/PERF.md's scaling arithmetic).
The full-size run is the driver's job (`python bench.py` on the real
chip); here we only pin the contract: all stages run, report the
expected keys, and produce positive rates.
"""

import pytest

import bench


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_transport_bench_smoke():
  results = bench.bench_transport(smoke=True)
  assert results['unroll_mb'] > 0
  bp = results['buffer_prefetcher']
  assert bp['batches_per_sec'] > 0
  assert bp['unrolls_per_sec'] > 0
  assert results['batcher_requests_per_sec']['threads_4'] > 0
  ingest = results['ingest_1conn']
  assert ingest['unrolls_per_sec'] > 0
  assert ingest['mb_per_sec'] > 0


def test_emit_writes_artifact_and_prints_headline_last(tmp_path,
                                                       capsys):
  """Satellite (VERDICT r5 weak #1): the round artifact must survive
  the driver's tail capture — the FULL result goes to BENCH_OUT.json
  and stdout ENDS with a compact, complete JSON headline line."""
  import json
  out = {
      'metric': 'learner_env_frames_per_sec_per_chip',
      'value': 123.4, 'vs_baseline': 0.01,
      # Round-6 itemization: the popart/pc/instruction split must ride
      # the clip-safe last line (ISSUE-3 satellite).
      'no_instruction_fps': 130.0,
      'popart_only_fps': 125.0,
      'pc_only_fps': 110.0,
      'full_feature_fps': 100.0,
      'deep_fast_fps': 180.0,
      'pc_levers': {
          'r5_reference': {'median': 100.0},
          'int_rewards_d2s': {'median': 120.0},
          'default': 'int_rewards_d2s'},
      'e2e_fed': {'fps': 9000.0, 'h2d_overlap_fraction': 0.9},
      'transport': {'ingest_1conn': {'unrolls_per_sec': 900.0},
                    'ingest_4conn': {'unrolls_per_sec': 1500.0}},
      'param_fanout': {
          'pump_alone': {'unrolls_per_sec': 800.0, 'ack_p99_ms': 2.0},
          'pump_with_8_fetchers': {'unrolls_per_sec': 400.0,
                                   'ack_p99_ms': 5.0}},
  }
  path = tmp_path / 'BENCH_OUT.json'
  bench._emit(out, path=str(path))
  assert json.load(open(path)) == out          # full, self-contained
  lines = capsys.readouterr().out.strip().splitlines()
  assert json.loads(lines[0]) == out           # full line for humans
  head = json.loads(lines[-1])                 # compact line LAST
  assert head['artifact'] == 'BENCH_OUT.json'
  assert head['value'] == 123.4
  assert head['ingest_4conn'] == 1500.0
  assert head['pump_contended_unrolls_per_sec'] == 400.0
  assert head['pump_contended_ack_p99_ms'] == 5.0
  assert head['h2d_overlap_fraction'] == 0.9
  # The itemized split survives the clip-safe line.
  assert head['full_feature_fps'] == 100.0
  assert head['popart_only_fps'] == 125.0
  assert head['pc_only_fps'] == 110.0
  assert head['pc_levers'] == {'r5_reference': 100.0,
                               'int_rewards_d2s': 120.0}
  assert len(lines[-1]) < 1000  # compact: survives tail truncation


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_inference_plane_bench_smoke():
  """The round-7 actor-plane instrument: all cache×depth variants run
  and report calls/s + latency percentiles (the accept/reject rows for
  the state-cache and pipeline-depth defaults)."""
  results = bench.bench_inference_plane(smoke=True)
  fleet = results['fleet_sizes'][0]
  for cache in ('carry', 'cache'):
    for depth in (1, 2):
      row = results[f'{cache}_d{depth}_f{fleet}']
      assert row['policy_calls_per_sec'] > 0
      assert row['lat_p50_ms'] > 0
      assert row['lat_p99_ms'] >= row['lat_p50_ms']
      assert row['mean_batch'] > 0
      # The depth semaphore held.
      assert row['inflight_peak'] <= depth


def test_headline_carries_inference_plane_rows(tmp_path, capsys):
  """Acceptance: the clip-safe last line itemizes calls/s + p50/p99
  for the cache×pipeline variants at the largest fleet size."""
  import json
  out = {
      'metric': 'learner_env_frames_per_sec_per_chip',
      'value': 1.0, 'vs_baseline': 0.0,
      'inference_plane': {
          'fleet_sizes': [8, 32],
          'carry_d1_f8': {'policy_calls_per_sec': 10.0,
                          'lat_p50_ms': 1.0, 'lat_p99_ms': 2.0},
          'carry_d1_f32': {'policy_calls_per_sec': 100.0,
                           'lat_p50_ms': 3.0, 'lat_p99_ms': 6.0},
          'cache_d2_f32': {'policy_calls_per_sec': 150.0,
                           'lat_p50_ms': 2.0, 'lat_p99_ms': 4.0},
      },
  }
  bench._emit(out, path=str(tmp_path / 'BENCH_OUT.json'))
  lines = capsys.readouterr().out.strip().splitlines()
  head = json.loads(lines[-1])
  # Only the largest fleet's rows ride the compact line.
  assert head['inference_plane'] == {
      'carry_d1_f32': {'cps': 100.0, 'p50': 3.0, 'p99': 6.0},
      'cache_d2_f32': {'cps': 150.0, 'p50': 2.0, 'p99': 4.0}}
  assert len(lines[-1]) < 1000


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_anakin_bench_smoke():
  """The round-16 stage shape: per-{backend, devices} fps rows, the
  fed-fleet reference + ratio, and the hybrid filler off/on rows with
  fresh-frame parity."""
  results = bench.bench_anakin(smoke=True)
  for backend in ('bandit', 'cue_memory', 'gridworld'):
    row = results[f'{backend}_1dev']
    assert row['env_frames_per_sec'] > 0, (backend, row)
  assert 0 <= results['bandit_1dev']['mean_reward_last'] <= 1.0
  assert results['fed_reference']['fps'] > 0
  assert results['anakin_vs_fed'] > 0
  # The acceptance reference: the REAL fleet path (acting included)
  # at the same shape/batch — the fused loop must beat it soundly
  # even on the CPU build host (it deletes the batcher round trips).
  assert results['fleet_reference']['fps'] > 0
  assert results['anakin_vs_fleet'] > 1.0, results['anakin_vs_fleet']
  hybrid = results['hybrid']
  # The filler lifts learner-plane utilization under the throttled
  # feed while the fresh-frame ledger stays the fleet's own (filler
  # frames ride their separate counters).
  assert (hybrid['filler_on']['learner_plane_utilization'] >
          hybrid['filler_off']['learner_plane_utilization'])
  assert hybrid['filler_on']['filler_updates'] > 0
  assert hybrid['filler_off']['filler_updates'] == 0


def test_read_window_summaries_counts_frames_over_window(tmp_path):
  """The e2e instrument (round 5): fps = step deltas between the first
  and last summary event over their wall-time span — NOT the last
  FpsMeter sample (which quantizes in whole batches per meter window)."""
  import json
  lines = [
      # tag, value, step, wall_time
      ('env_frames_per_sec', 100.0, 10, 1000.0),
      ('inference_mean_batch', 3.5, 10, 1000.0),
      ('env_frames_per_sec', 999.0, 20, 1004.0),  # meter lies; steps don't
      ('buffer_unrolls', 2.0, 20, 1004.0),
  ]
  with open(tmp_path / 'summaries.jsonl', 'w') as f:
    for tag, value, step, wall in lines:
      f.write(json.dumps({'tag': tag, 'value': value, 'step': step,
                          'wall_time': wall}) + '\n')
  fps, span, last = bench._read_window_summaries(str(tmp_path),
                                                 frames_per_step=40)
  # (20-10) steps * 40 frames / (1004-1000) s = 100 fps — the meter's
  # bogus 999 sample must not leak into the result.
  assert fps == 100.0
  assert span == 4.0
  assert last['inference_mean_batch'] == 3.5
  assert last['buffer_unrolls'] == 2.0


def test_read_window_summaries_single_event_falls_back(tmp_path):
  import json
  with open(tmp_path / 'summaries.jsonl', 'w') as f:
    f.write(json.dumps({'tag': 'env_frames_per_sec', 'value': 77.0,
                        'step': 5, 'wall_time': 1.0}) + '\n')
  fps, span, _ = bench._read_window_summaries(str(tmp_path),
                                              frames_per_step=40)
  assert fps == 77.0 and span == 0.0


def test_fed_learner_smoke_via_fleet_factory(tmp_path):
  """driver.train(fleet_factory=...) — the injection point the fed
  bench stands on: a synthetic producer fleet feeds the real loop with
  no envs/inference; the run trains and terminates on max_steps."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.testing import make_example_unroll
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN

  cfg = Config(logdir=str(tmp_path), env_backend='fake', num_actions=9,
               num_actors=0, batch_size=2, unroll_length=5,
               num_action_repeats=1, height=24, width=32,
               torso='shallow', use_py_process=False,
               use_instruction=False,
               total_environment_frames=10**9,
               checkpoint_secs=10**6, summary_secs=10**6)
  unroll = make_example_unroll(6, 24, 32, 9, MAX_INSTRUCTION_LEN)

  def fleet_factory(config, agent, policy, buffer, levels):
    return bench._SyntheticFleet(buffer, unroll)

  run = driver.train(cfg, max_steps=3, fleet_factory=fleet_factory)
  assert run.frames == 3 * cfg.frames_per_step


def test_telemetry_bench_smoke():
  """The round-13 stage: registry/span micro rows + the tracing
  on/off feed pair that carries the always-on accept call
  (docs/PERF.md r11)."""
  results = bench.bench_telemetry(smoke=True)
  assert results['registry_ns_per_op'] > 0
  assert results['span_ns'] > 0
  assert results['feed_trace_off']['unrolls_per_sec'] > 0
  on = results['feed_trace_on']
  assert on['unrolls_per_sec'] > 0
  # The traced run actually traced: batch records were emitted and
  # every produced unroll carried its span.
  assert on['tracer']['batches'] > 0
  assert on['tracer']['untagged_unrolls'] == 0
  assert results['overhead_fraction'] is not None

"""Benchmark: learner env-frames/sec on one chip, flagship config.

Two measurements, one JSON line:

1. `value` (headline, reference unit): the jitted IMPALA train step on
   a synthetic resident batch (deep ResNet, T=100, B=32, DMLab 72x96
   frames, bfloat16) — the chip's ceiling, comparable across rounds.
   Round 6 itemizes the feature matrix around it (no_instruction /
   popart_only / pc_only / full_feature, each with step_ms +
   cost_analysis bytes) and sweeps the pixel-control fast-path
   variants (`pc_levers`) so the full-feature 20% keeps named,
   re-measured owners (docs/PERF.md r6).
2. `e2e`: the REAL pipeline — process-hosted fake envs at 72x96 → C++
   dynamic batcher → TrajectoryBuffer → BatchPrefetcher → learner on
   chip — reporting the learner consumption rate (the reference's
   unit, SURVEY §6) as median/min/max over 3 independent ~45 s
   windows, with per-window pipeline telemetry. The gap between (1)
   and (2) is the tuning target.

Plus two host-transport stages feeding docs/PERF.md's scaling
arithmetic: `transport` (buffer→prefetcher, C++ batcher, TCP unroll
ingest) and `param_fanout` (the learner's param-snapshot egress to
actor hosts — the reverse direction).

vs_baseline: BASELINE.json's north star is >=200k env-frames/sec on a
v5e-16 ⇒ 12,500 frames/sec/chip. vs_baseline = value / 12500.

Artifact protocol (round 6): the FULL result is written to
BENCH_OUT.json (self-contained — the driver's tail capture used to
clip the one giant JSON line mid-object, VERDICT r5 weak #1); stdout
gets the full JSON line for humans, then a compact headline line LAST
so a clipped tail still ends on one complete object.
"""

import json
import os
import tempfile
import threading
import time


def _time_step(cfg, use_instruction, smoke, h, w, num_tasks=1):
  """Median/min/max env-frames/sec of the jitted train step over ≥3
  independent timing windows (VERDICT r4 W1: a single-sample headline
  made the r1→r4 −6.4% drift unattributable). Each window is n steps
  async-chained on the donated state with ONE value readback as the
  barrier.

  Round 6: every row also carries the compiled step's
  `cost_analysis()` bytes/FLOPs and the median step time in ms — the
  per-feature itemization (VERDICT r5 weak #3) needs owners in BYTES,
  not just fps, because the step is ~72% HBM-bound."""
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.testing import make_example_batch

  num_actions = 9  # DMLab DEFAULT_ACTION_SET
  t1, b = cfg.unroll_length + 1, cfg.batch_size
  agent = ImpalaAgent(num_actions=num_actions, torso=cfg.torso,
                      use_instruction=use_instruction,
                      num_popart_tasks=(num_tasks if cfg.use_popart
                                        else 0),
                      use_pixel_control=cfg.pixel_control_cost > 0,
                      pixel_control_cell_size=cfg.pixel_control_cell_size,
                      pixel_control_head_impl=cfg.pixel_control_head_impl,
                      pixel_control_q_f32=cfg.pixel_control_q_f32,
                      scan_unroll=cfg.scan_unroll, dtype=jnp.bfloat16)
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
  batch = make_example_batch(t1, b, h, w, num_actions,
                             MAX_INSTRUCTION_LEN, done_prob=0.01)
  state = learner_lib.make_train_state(
      params, cfg, num_popart_tasks=(num_tasks if cfg.use_popart
                                     else 0))
  train_step = learner_lib.make_train_step(agent, cfg)

  # One explicit AOT compile serves both the timing loop and the
  # cost/bytes attribution (compiling a second executable just for
  # cost_analysis would double every row's compile time on the chip).
  compiled = train_step.lower(state, batch).compile()
  cost = {}
  try:
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):  # some jax versions return [dict]
      analysis = analysis[0]
    cost = {
        'bytes_gb': round(analysis.get('bytes accessed', float('nan'))
                          / 1e9, 2),
        'tflops': round(analysis.get('flops', float('nan')) / 1e12, 3),
    }
  except Exception:  # noqa: BLE001 — cost analysis is best-effort
    pass            # (backend-dependent); the timing rows still land.

  # Warmup / compile. The sync barrier is a HOST READBACK of the loss
  # (float(...)): the value cannot exist before the step that
  # computes it has finished. Whether block_until_ready is an equally
  # sound barrier on the chip machine is ROADMAP S0's to settle.
  state, metrics = compiled(state, batch)
  float(metrics['total_loss'])

  num_windows = 3 if not smoke else 1
  n = 20 if not smoke else 3
  window_fps = []
  for _ in range(num_windows):
    t0 = time.perf_counter()
    for _ in range(n):
      state, metrics = compiled(state, batch)
    float(metrics['total_loss'])
    dt = (time.perf_counter() - t0) / n
    window_fps.append(cfg.frames_per_step / dt)
  window_fps.sort()
  median = window_fps[len(window_fps) // 2]
  return {
      'median': round(median, 1),
      'min': round(window_fps[0], 1),
      'max': round(window_fps[-1], 1),
      'windows': [round(f, 1) for f in window_fps],
      'step_ms': round(cfg.frames_per_step / median * 1e3, 2),
      **({'cost': cost} if cost else {}),
  }


# The pixel-control lever grid (round 6, docs/PERF.md): each variant
# of the full-feature config is timed + cost-analyzed head-to-head so
# the accept/reject call is MEASURED every round on whatever backend
# runs the bench — config defaults stay at the r5 reference forms
# until the chip rows justify a flip (config.py rationale). Order:
# the reference forms, then each lever cumulatively, then the opt-in
# numerics-affecting bf16-Q lever.
PC_LEVER_GRID = (
    # == the config default (r5 reference forms):
    ('r5_reference', dict(pixel_control_integer_rewards=False,
                          pixel_control_head_impl='deconv',
                          pixel_control_q_f32=True)),
    ('int_rewards', dict(pixel_control_integer_rewards=True,
                         pixel_control_head_impl='deconv',
                         pixel_control_q_f32=True)),
    ('int_rewards_d2s', dict(pixel_control_integer_rewards=True,
                             pixel_control_head_impl='d2s',
                             pixel_control_q_f32=True)),
    ('int_rewards_d2s_bf16_q', dict(
        pixel_control_integer_rewards=True,
        pixel_control_head_impl='d2s',
        pixel_control_q_f32=False)),
)


def bench_synthetic(smoke):
  """Headline + the per-feature itemization (VERDICT r5 weak #3): the
  full-feature 20% gets named owners. Base = deep/no-features; each
  feature then rides the base ALONE (instruction via the headline row,
  popart_only, pc_only) so fps and cost_analysis bytes attribute the
  plain→full_feature gap term by term. In smoke mode the itemized
  rows run at tiny shapes (mechanics gate for CI); chip numbers come
  from the driver's run."""
  import dataclasses
  from scalable_agent_tpu.config import Config

  cfg = Config(batch_size=32 if not smoke else 2,
               unroll_length=100 if not smoke else 4,
               num_action_repeats=4,
               total_environment_frames=int(1e9),
               torso='deep', compute_dtype='bfloat16')
  h, w = (72, 96) if not smoke else (24, 32)
  rows = {'config': cfg}
  # Headline: the full flagship model (language encoder ON — dmlab30
  # parity, comparable across rounds). Against the no-instruction
  # base this IS the instruction-only itemized row.
  rows['synthetic'] = _time_step(cfg, True, smoke, h, w)
  # The plain base (docs/PERF.md): single-task levels auto-skip the
  # encoder.
  rows['no_instruction'] = _time_step(cfg, False, smoke, h, w)
  # Itemized rows: one feature at a time on the plain base.
  popart_cfg = dataclasses.replace(cfg, use_popart=True)
  rows['popart_only'] = _time_step(popart_cfg, False, smoke, h, w,
                                   num_tasks=30)
  pc_cfg = dataclasses.replace(cfg, pixel_control_cost=0.01)
  rows['pc_only'] = _time_step(pc_cfg, False, smoke, h, w)
  # North-star operating point (VERDICT r4 W5): the config
  # BASELINE.json's DMLab-30 target actually runs — PopArt + UNREAL
  # pixel control + instruction encoder, 30 tasks.
  ns_cfg = dataclasses.replace(cfg, use_popart=True,
                               pixel_control_cost=0.01)
  rows['full_feature'] = _time_step(ns_cfg, True, smoke, h, w,
                                    num_tasks=30)
  # The pixel-control lever grid at the full-feature operating point
  # (the surface being attacked): accept/reject stays measured.
  levers = {}
  for name, overrides in PC_LEVER_GRID:
    lcfg = dataclasses.replace(ns_cfg, **overrides)
    if lcfg == ns_cfg:
      # This variant IS the full_feature row's config (the current
      # defaults) — reuse its measurement instead of paying a second
      # flagship compile + timing windows for the same program.
      levers[name] = rows['full_feature']
      levers['default'] = name
      continue
    levers[name] = _time_step(lcfg, True, smoke, h, w, num_tasks=30)
  levers.setdefault('default', '(config defaults not in grid)')
  rows['pc_levers'] = levers
  # deep_fast operating point (docs/PERF.md round 5): stride-2 convs
  # replace the max-pools — the measured HBM-bandwidth lever (−37%
  # step bytes). Same param tree as deep, different function; reported
  # alongside the parity headline, not in its place. NOTE: throughput
  # variant with UNVALIDATED RETURNS beyond bandit grade (README §
  # Performance / scripts/compare_torsos.py).
  fast_cfg = dataclasses.replace(cfg, torso='deep_fast')
  rows['deep_fast'] = _time_step(fast_cfg, True, smoke, h, w)
  return rows


def _read_window_summaries(logdir, frames_per_step):
  """Steady-state fps + telemetry from a run's summaries.jsonl.

  fps = frames counted between the FIRST and LAST summary event / the
  wall time between them (VERDICT r4 W4: the old instrument read the
  last FpsMeter sample, which quantizes in whole unroll-batches per
  30 s window — ±33% resolution at the sandbox operating point;
  step-counter deltas resolve to one batch over the whole window).
  The first event lands one summary interval after the first
  completed train step, so the compile/ramp phase is excluded.
  """
  last = {}
  fps_events = []
  with open(os.path.join(logdir, 'summaries.jsonl')) as f:
    for line in f:
      e = json.loads(line)
      if 'value' in e:
        last[e['tag']] = e['value']  # keep the latest per tag
        if e['tag'] == 'env_frames_per_sec':
          fps_events.append((e['wall_time'], e['step']))
  if len(fps_events) >= 2:
    (t0, s0), (t1, s1) = fps_events[0], fps_events[-1]
    fps = (s1 - s0) * frames_per_step / (t1 - t0) if t1 > t0 else 0.0
    span = t1 - t0
  else:
    # One event: no counting window — fall back to its meter sample.
    fps = last.get('env_frames_per_sec', 0.0)
    span = 0.0
  return fps, span, last


def _e2e_window_config(smoke, seed, **overrides):
  from scalable_agent_tpu.config import Config
  cfg = Config(
      logdir=tempfile.mkdtemp(prefix='bench_e2e_'),
      env_backend='fake',
      num_actions=9,
      num_actors=4 if not smoke else 2,
      batch_size=4 if not smoke else 2,
      unroll_length=100 if not smoke else 5,
      num_action_repeats=4,
      episode_length=50,
      height=72 if not smoke else 24,
      width=96 if not smoke else 32,
      torso='deep' if not smoke else 'shallow',
      compute_dtype='bfloat16' if not smoke else 'float32',
      use_py_process=not smoke,   # smoke: in-process envs (CI speed)
      use_instruction=False,
      total_environment_frames=int(1e9),
      inference_timeout_ms=20,
      checkpoint_secs=10**6,     # no checkpoint traffic in the window
      summary_secs=5 if not smoke else 1,
      seed=seed)
  import dataclasses
  return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _run_e2e_window(cfg, smoke, label):
  """One fresh driver.train window; returns the window telemetry dict.

  65 s per window: the first ~25 s are compile/ramp (excluded by the
  summaries-based instrument, but the steady span must still be long
  enough for ≥2 summary events). A fully cold process can spend the
  WHOLE first window compiling (observed once: window 1 = 0 frames);
  such a window measures compile time, not throughput, so it is
  retried once against the now-warm in-process jit cache."""
  import dataclasses
  from scalable_agent_tpu import driver
  for attempt in range(2):
    run = driver.train(cfg, max_seconds=65 if not smoke else 8,
                       stall_timeout_secs=120)
    if run.frames > 0:
      break
    if attempt == 1:
      raise RuntimeError(
          f'e2e window {label}: zero frames in both attempts — even '
          'the warm-cache retry spent the whole window before the '
          'first train step; the window would measure compile, not '
          'throughput')
    cfg = dataclasses.replace(
        cfg, logdir=tempfile.mkdtemp(prefix='bench_e2e_'))
  fps, span, last = _read_window_summaries(cfg.logdir,
                                           cfg.frames_per_step)
  return {
      'fps': round(fps, 1),
      'steady_secs': round(span, 1),
      'inference_mean_batch': round(
          last.get('inference_mean_batch', 0.0), 2),
      # Per-merged-call service latency (round 7 summaries): read with
      # mean_batch — merge going up while p99 explodes means the floor
      # is buying batch size with actor stall time.
      'inference_p99_ms': round(
          last.get('inference_latency_p99_ms', 0.0), 2),
      'buffer_unrolls': last.get('buffer_unrolls', 0.0),
      # Staging overlap (round 8 satellite): how often the step found
      # its batch already staged — read with buffer_unrolls (≈0 there
      # means starvation upstream of staging, not transfer).
      'h2d_overlap_fraction': round(
          last.get('h2d_overlap_fraction', 0.0), 3),
      'frames': int(run.frames),
  }


def bench_e2e(smoke):
  """Sustained FPS through the full real pipeline (driver.train on
  process-hosted fake envs): ≥3 independent windows (fresh envs per
  window) with median/min/max, fps counted over each window's whole
  steady span (see _read_window_summaries), plus a batcher-knob sweep
  at the same operating point (VERDICT r4 #6: inference_mean_batch
  sat at 2.65–2.72 of 4 with no tuning recorded)."""
  windows = []
  num_windows = 3 if not smoke else 1
  for i in range(num_windows):
    cfg = _e2e_window_config(smoke, seed=1 + i)
    windows.append(_run_e2e_window(cfg, smoke, str(i)))

  fps_sorted = sorted(w['fps'] for w in windows)
  result = {
      'fps_median': fps_sorted[len(fps_sorted) // 2],
      'fps_min': fps_sorted[0],
      'fps_max': fps_sorted[-1],
      'windows': windows,
      'actors': cfg.num_actors,
      'batch_size': cfg.batch_size,
  }
  if not smoke:
    # Batcher tuning sweep, one window per setting: can a floor under
    # the merge (min_batch) or a longer merge window (timeout) push
    # mean_batch toward 4/4 — and does fps follow or does the added
    # latency eat the gain? (paper Table 1's single-machine ~3×
    # lever; since round 6 the default row above runs min_batch=0 =
    # AUTO, i.e. the fleet-size floor this sweep motivated.)
    sweep = []
    for min_batch, timeout_ms in ((2, 20), (4, 60)):
      scfg = _e2e_window_config(
          smoke, seed=101 + min_batch,
          inference_min_batch=min_batch,
          inference_timeout_ms=timeout_ms)
      w = _run_e2e_window(scfg, smoke,
                          f'min{min_batch}/t{timeout_ms}')
      w['inference_min_batch'] = min_batch
      w['inference_timeout_ms'] = timeout_ms
      sweep.append(w)
    result['batcher_sweep'] = sweep
  return result


def bench_inference_plane(smoke):
  """The actor-plane instrument (round 7): drive the InferenceServer
  with a synthetic actor fleet — threads looping policy() on canned
  observations, NO env stepping — and itemize policy-calls/s plus
  per-call latency p50/p99 across {carry-passing vs state-cache} ×
  {pipeline depth 1 vs 2} × fleet size. The e2e bench showed the
  pipeline actor/inference-bound (`inference_mean_batch` the governing
  knob); these rows isolate the server itself so the cache and
  pipeline defaults are accepted/rejected on measurement, per the
  repo's discipline (config.py inference_state_cache rationale).

  Every cell runs pad_batch_to=fleet (ONE compiled bucket per server —
  the merge floor is AUTO'd to the fleet anyway, so steady merges land
  in that bucket) and the flagship inference shapes (deep torso, 72x96
  uint8 frames, bf16 compute; tiny shallow shapes in smoke).
  Latencies are client-side (submit → answer, batcher wait included);
  the server-side merged-call latency rides along from stats().
  """
  import threading
  import numpy as np
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.ops.dynamic_batching import BatcherCancelled
  from scalable_agent_tpu.runtime.inference import (InferenceServer,
                                                    percentile_ms)
  from scalable_agent_tpu.structs import StepOutput, StepOutputInfo

  h, w = (72, 96) if not smoke else (24, 32)
  torso = 'deep' if not smoke else 'shallow'
  dtype = jnp.bfloat16 if not smoke else jnp.float32
  dur = 5.0 if not smoke else 0.6
  fleet_sizes = (8, 32) if not smoke else (3,)
  num_actions = 9
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  agent = ImpalaAgent(num_actions=num_actions, torso=torso,
                      use_instruction=False, dtype=dtype)
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
  rng = np.random.RandomState(0)
  frame = rng.randint(0, 255, (h, w, 3)).astype(np.uint8)
  instr = np.zeros((MAX_INSTRUCTION_LEN,), np.int32)

  def run_cell(fleet, cache, depth):
    cfg = Config(inference_min_batch=0, inference_max_batch=max(64, fleet),
                 inference_timeout_ms=20, inference_state_cache=cache,
                 inference_pipeline_depth=depth)
    server = InferenceServer(agent, params, cfg, seed=7,
                             pad_batch_to=fleet, fleet_size=fleet)
    server.warmup(obs_spec, sizes=[fleet])
    counts = [0] * fleet
    lats = [[] for _ in range(fleet)]
    measuring = threading.Event()
    stop = threading.Event()

    def run(i):
      state = server.initial_core_state()
      prev = np.int32(i % num_actions)
      step = 0
      try:
        while not stop.is_set():
          env_out = StepOutput(
              reward=np.float32(0.1),
              info=StepOutputInfo(np.float32(0), np.int32(0)),
              done=np.bool_(step > 0 and step % 23 == 0),
              observation=(frame, instr))
          t0 = time.perf_counter()
          out, state = server.policy(prev, env_out, state)
          dt = time.perf_counter() - t0
          counts[i] += 1
          if measuring.is_set():
            lats[i].append(dt)
          prev = np.int32(out.action)
          step += 1
      except BatcherCancelled:
        pass
      finally:
        if hasattr(state, 'release'):
          state.release()

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(fleet)]
    for t in threads:
      t.start()
    # Warm until every thread is feeding (startup must not eat the
    # window — same rule as the transport stages).
    deadline = time.perf_counter() + (60 if not smoke else 120)
    while (not all(c > 0 for c in counts)
           and time.perf_counter() < deadline):
      time.sleep(0.05)
    base = sum(counts)
    measuring.set()
    dt = _count_window(lambda: sum(counts), base, dur,
                       min_count=fleet * 4)
    got = sum(counts) - base
    measuring.clear()
    stop.set()
    for t in threads:
      t.join(timeout=15)
    stats = server.stats()
    server.close()
    for t in threads:
      t.join(timeout=5)
    if got == 0:
      raise RuntimeError(
          f'inference_plane moved no calls (cache={cache} depth='
          f'{depth} fleet={fleet})')
    window = sorted(x for lat in lats for x in lat)
    return {
        'policy_calls_per_sec': round(got / dt, 1),
        'lat_p50_ms': round(percentile_ms(window, 0.5, 1e3), 2),
        'lat_p99_ms': round(percentile_ms(window, 0.99, 1e3), 2),
        'mean_batch': round(stats['mean_batch'], 2),
        'merged_call_p50_ms': stats['latency_p50_ms'],
        'merged_call_p99_ms': stats['latency_p99_ms'],
        'inflight_peak': stats['inflight_peak'],
    }

  results = {'fleet_sizes': list(fleet_sizes)}
  for fleet in fleet_sizes:
    for cache in (False, True):
      for depth in (1, 2):
        name = f"{'cache' if cache else 'carry'}_d{depth}_f{fleet}"
        results[name] = run_cell(fleet, cache, depth)
  return results


def bench_overload(smoke):
  """The overload instrument (round 9, docs/ROBUSTNESS.md actor-plane
  rows): tail latency and shed rate of the serving plane when the
  actor population exceeds the state arena — the regime admission
  control exists for. Three rows run the fleet at {1x, 2x, 4x} slot
  capacity under the SHED policy (deadline rejection is the intended
  steady-state overload answer); each actor holds its slot for a
  burst of policy calls, releases, and re-acquires, so the admission
  seam churns continuously:

  - `policy_calls_per_sec` + client-side `lat_p50_ms`/`lat_p99_ms` of
    the calls that DID run — what overload does to the served tail;
  - `shed_fraction` (sheds / acquires, the SLO number the chaos storm
    bounds) with the raw acquire/shed/wait counters and the parked-
    wait p99 from stats() riding along.

  The 1x row is the control (shed_fraction ≈ 0 — admission must cost
  nothing when capacity suffices); 2x matches the chaos overload
  storm's pressure; 4x is the headroom probe.
  """
  import threading
  import numpy as np
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.ops.dynamic_batching import BatcherCancelled
  from scalable_agent_tpu.runtime.inference import (
      InferenceClosed, InferenceServer, SlotUnavailable, percentile_ms)
  from scalable_agent_tpu.structs import StepOutput, StepOutputInfo

  h, w = (72, 96) if not smoke else (24, 32)
  torso = 'deep' if not smoke else 'shallow'
  dtype = jnp.bfloat16 if not smoke else jnp.float32
  dur = 4.0 if not smoke else 0.6
  slots = 8 if not smoke else 2
  pressures = (1, 2, 4)
  hold_calls = 25 if not smoke else 8
  num_actions = 9
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  agent = ImpalaAgent(num_actions=num_actions, torso=torso,
                      use_instruction=False, dtype=dtype)
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
  rng = np.random.RandomState(0)
  frame = rng.randint(0, 255, (h, w, 3)).astype(np.uint8)
  instr = np.zeros((MAX_INSTRUCTION_LEN,), np.int32)

  def run_cell(pressure):
    fleet = pressure * slots
    cfg = Config(inference_min_batch=0,
                 inference_max_batch=max(64, slots),
                 inference_timeout_ms=20,
                 inference_state_cache=True,
                 inference_state_slots=slots,
                 inference_admission='shed',
                 # Short deadline: a shed row must measure steady-state
                 # rejection rate, not one long parked wait per actor.
                 inference_admission_timeout_secs=0.05)
    server = InferenceServer(agent, params, cfg, seed=7,
                             pad_batch_to=slots, fleet_size=slots)
    server.warmup(obs_spec, sizes=[slots])
    counts = [0] * fleet
    lats = [[] for _ in range(fleet)]
    measuring = threading.Event()
    stop = threading.Event()

    def run(i):
      prev = np.int32(i % num_actions)
      step = 0
      try:
        while not stop.is_set():
          try:
            state = server.initial_core_state()
          except SlotUnavailable:
            # Shed: the intended overload answer — back off briefly
            # and retry (server.stats() counts it).
            time.sleep(0.005)
            continue
          except InferenceClosed:
            return
          try:
            for _ in range(hold_calls):
              if stop.is_set():
                return
              env_out = StepOutput(
                  reward=np.float32(0.1),
                  info=StepOutputInfo(np.float32(0), np.int32(0)),
                  done=np.bool_(step > 0 and step % 23 == 0),
                  observation=(frame, instr))
              t0 = time.perf_counter()
              out, state = server.policy(prev, env_out, state)
              dt = time.perf_counter() - t0
              counts[i] += 1
              if measuring.is_set():
                lats[i].append(dt)
              prev = np.int32(out.action)
              step += 1
          finally:
            if hasattr(state, 'release'):
              state.release()
      except BatcherCancelled:
        pass

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(fleet)]
    for t in threads:
      t.start()
    deadline = time.perf_counter() + (60 if not smoke else 120)
    # Under pressure > 1x a given actor may legitimately never get a
    # slot inside the warm window — warm until the FLEET moves, not
    # until every member does.
    while (sum(counts) < slots * 2
           and time.perf_counter() < deadline):
      time.sleep(0.05)
    base = sum(counts)
    measuring.set()
    dt = _count_window(lambda: sum(counts), base, dur,
                       min_count=slots * 4)
    got = sum(counts) - base
    measuring.clear()
    stop.set()
    stats = server.stats()
    server.close()
    for t in threads:
      t.join(timeout=15)
    if got == 0:
      raise RuntimeError(f'overload moved no calls (pressure='
                         f'{pressure}x, slots={slots})')
    acquires = stats['acquires']
    window = sorted(x for lat in lats for x in lat)
    return {
        'fleet': fleet,
        'slots': slots,
        'policy_calls_per_sec': round(got / dt, 1),
        'lat_p50_ms': round(percentile_ms(window, 0.5, 1e3), 2),
        'lat_p99_ms': round(percentile_ms(window, 0.99, 1e3), 2),
        'acquires': acquires,
        'sheds': stats['sheds'],
        'shed_fraction': round(stats['sheds'] / acquires, 4)
        if acquires else 0.0,
        'admission_waits': stats['admission_waits'],
        'admission_wait_p99_ms': stats['admission_wait_p99_ms'],
    }

  results = {'slots': slots, 'pressures': list(pressures)}
  for pressure in pressures:
    results[f'{pressure}x'] = run_cell(pressure)
  return results


def bench_learner_plane(smoke):
  """The learner-feed instrument (round 8): itemize the batch boundary
  the tentpole attacks. BENCH_r05 measured it as ONE burst per step —
  stack_ms 37.5 host-stacking a 67.5 MB batch, then h2d_ms 1430.5
  transferring it — while the compiled step is HBM-bound, so headline
  growth must come from removing exposed overheads. Four cells run
  the REAL feed machinery ({batch, unroll} staging × depth {1, 2}:
  synthetic producers → TrajectoryBuffer → BatchPrefetcher →
  the compiled flagship train step) and report, per cell:

  - `exposed_feed_ms_per_step`: time the step actually BLOCKED on the
    feed (prefetcher wait — H2D + assembly not hidden behind compute);
  - `step_gap_ms`: fed wall-clock per step minus the bare compiled
    step (everything the loop adds, overlapped or not);
  - `h2d_overlap_fraction` and `stack_ms` (the host stack is 0 by
    construction in unroll mode — it left the hot path).

  Plus two one-off rows: `vtrace_sharded` (the shard_map'ped Pallas
  kernel vs the lax.scan form over a mesh of ALL local devices — 1 on
  the bench chip, so the row exercises the shard_map path trivially
  there; the scripts/ci.sh smoke lane forces 8 virtual CPU devices so
  the multi-shard path runs too, and the numeric multi-device parity
  gates live in tests/) and `metrics_readback` (leaf-by-leaf
  device_get vs the round-8 stacked read, stack dispatch itemized
  separately — the driver pays it a step before the read).
  The cells share ONE compiled executable; the accept/reject call for
  `--staging_mode` rides these rows into BENCH_r08.
  """
  import threading
  import numpy as np
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu import observability, vtrace
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  from scalable_agent_tpu.runtime import ring_buffer
  from scalable_agent_tpu.runtime.actor import batch_unrolls

  h, w = (72, 96) if not smoke else (24, 32)
  b = 32 if not smoke else 2
  t = 100 if not smoke else 4
  steps = 12 if not smoke else 3
  cfg = Config(batch_size=b, unroll_length=t, num_action_repeats=4,
               total_environment_frames=int(1e9),
               torso='deep' if not smoke else 'shallow',
               compute_dtype='bfloat16' if not smoke else 'float32',
               use_instruction=False)
  agent = ImpalaAgent(num_actions=9, torso=cfg.torso,
                      use_instruction=False,
                      scan_unroll=cfg.scan_unroll,
                      dtype=(jnp.bfloat16 if not smoke
                             else jnp.float32))
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
  state = learner_lib.make_train_state(params, cfg)
  train_step = learner_lib.make_train_step(agent, cfg)
  unroll = _transport_unroll(t + 1, h, w)
  rows = [unroll] * b
  host_batch = batch_unrolls(rows)
  placed = jax.device_put(host_batch)
  compiled = train_step.lower(state, placed).compile()
  # Warm + bare step (same value-readback barrier as _time_step).
  state, metrics = compiled(state, placed)
  float(metrics['total_loss'])
  t0 = time.perf_counter()
  for _ in range(steps):
    state, metrics = compiled(state, placed)
  float(metrics['total_loss'])
  bare_step_ms = (time.perf_counter() - t0) / steps * 1e3

  # Host stack cost (the batch-mode term unroll mode deletes).
  t0 = time.perf_counter()
  n_stack = 3 if not smoke else 1
  for _ in range(n_stack):
    batch_unrolls(rows)
  stack_ms = (time.perf_counter() - t0) / n_stack * 1e3

  def run_cell(mode, depth):
    nonlocal state
    buffer = ring_buffer.TrajectoryBuffer(2 * b)
    stop = threading.Event()

    def produce():
      while not stop.is_set():
        try:
          buffer.put(unroll, timeout=0.2)
        except (TimeoutError, ring_buffer.Closed):
          continue

    producers = [threading.Thread(target=produce, daemon=True)
                 for _ in range(4)]
    for p in producers:
      p.start()
    stager = (ring_buffer.UnrollBatchStager(b) if mode == 'unroll'
              else None)
    pf = ring_buffer.BatchPrefetcher(buffer, b,
                                     place_fn=jax.device_put,
                                     depth=depth, stager=stager)
    try:
      # Prime: the first get covers the insert-jit compile (unroll
      # mode) and the pipeline fill; excluded from the window.
      batch = pf.get(timeout=300)
      state, m = compiled(state, batch)
      float(m['total_loss'])
      base = pf.stats()
      t0 = time.perf_counter()
      for _ in range(steps):
        batch = pf.get(timeout=300)
        state, m = compiled(state, batch)
      float(m['total_loss'])
      fed_ms = (time.perf_counter() - t0) / steps * 1e3
      stats = pf.stats()
    finally:
      stop.set()
      pf.close()
      for p in producers:
        p.join(timeout=2)
    d_gets = stats['gets'] - base['gets']
    d_wait = stats['wait_secs'] - base['wait_secs']
    d_blocked = stats['blocked_gets'] - base['blocked_gets']
    return {
        'mode': mode,
        'depth': depth,
        'fed_step_ms': round(fed_ms, 2),
        'step_gap_ms': round(fed_ms - bare_step_ms, 2),
        'exposed_feed_ms_per_step': round(
            d_wait / d_gets * 1e3 if d_gets else 0.0, 2),
        'h2d_overlap_fraction': round(
            1.0 - d_blocked / d_gets if d_gets else 0.0, 3),
        'stack_ms': round(stack_ms, 1) if mode == 'batch' else 0.0,
        'donation_fallback': stats.get('donation_fallback', False),
    }

  results = {
      'batch_size': b,
      'unroll_length': t,
      'bare_step_ms': round(bare_step_ms, 2),
      'batch_mb': round(sum(x.nbytes for x in
                            jax.tree_util.tree_leaves(host_batch))
                        / 1e6, 1),
  }
  for mode in ('batch', 'unroll'):
    for depth in (1, 2):
      results[f'{mode}_d{depth}'] = run_cell(mode, depth)

  # --- Sharded Pallas-vs-scan V-trace (the lifted mesh restriction,
  # timed standalone over a mesh of ALL local devices — 1 on the
  # bench chip, 8 virtual in the CI smoke; both exercise the
  # shard_map path the flagship sharded step now takes). ---
  mesh = mesh_lib.make_mesh(jax.local_devices(), model_parallelism=1)
  tb, bb = (100, 32) if not smoke else (6, 8)
  bb = max(bb, len(jax.local_devices()))
  rng = np.random.RandomState(0)
  vkw = dict(
      log_rhos=jnp.asarray(rng.randn(tb, bb) * 0.5, jnp.float32),
      discounts=jnp.full((tb, bb), 0.9, jnp.float32),
      rewards=jnp.asarray(rng.randn(tb, bb), jnp.float32),
      values=jnp.asarray(rng.randn(tb, bb), jnp.float32),
      bootstrap_value=jnp.asarray(rng.randn(bb), jnp.float32))

  def time_vtrace(fn):
    out = fn(**vkw)
    float(np.asarray(out[0, 0]))  # readback barrier
    n = 20 if not smoke else 3
    t0 = time.perf_counter()
    for _ in range(n):
      out = fn(**vkw)
    float(np.asarray(out[0, 0]))
    return round((time.perf_counter() - t0) / n * 1e3, 3)

  results['vtrace_sharded'] = {
      'devices': len(jax.local_devices()),
      'pallas_ms': time_vtrace(jax.jit(
          lambda **k: vtrace.from_importance_weights(
              use_pallas=True, mesh=mesh, **k).vs)),
      'scan_ms': time_vtrace(jax.jit(
          lambda **k: vtrace.from_importance_weights(**k).vs)),
  }

  # --- Metrics readback, measured as the DRIVER actually pays it.
  # The round-8 path splits into two independently-timed pieces:
  # the per-step stack DISPATCH (async, returns immediately — rides
  # alongside the next step's dispatch) and the summary-time READ of
  # an already-computed stack (one transfer). Timing
  # read(stack(metrics)) as one unit would charge the deferred path a
  # serialize-on-fresh-dispatch sync it never pays in the driver,
  # where the stack was dispatched a whole step earlier. The per-leaf
  # row is the pre-round-8 summary path: one device_get per key
  # (computed values here too, so both rows measure transfer/dispatch
  # cost, not step-completion waits). ---
  n = 10 if not smoke else 2
  t0 = time.perf_counter()
  for _ in range(n):
    _ = {k: float(jax.device_get(v)) for k, v in metrics.items()}
  per_leaf_ms = (time.perf_counter() - t0) / n * 1e3
  t0 = time.perf_counter()
  handles = [observability.stack_metrics(metrics) for _ in range(n)]
  stack_dispatch_ms = (time.perf_counter() - t0) / n * 1e3
  observability.read_stacked_metrics(handles[-1])  # all computed now
  t0 = time.perf_counter()
  for h in handles:
    _ = observability.read_stacked_metrics(h)
  stacked_read_ms = (time.perf_counter() - t0) / n * 1e3
  results['metrics_readback'] = {
      'keys': len(metrics),
      'per_leaf_ms': round(per_leaf_ms, 2),
      'stacked_read_ms': round(stacked_read_ms, 2),
      'stack_dispatch_ms': round(stack_dispatch_ms, 2),
  }
  return results


def bench_replay(smoke):
  """Sample-reuse instrument (round 10, IMPACT arXiv 1912.00167):
  step_ms and learner-updates/env-frame across replay_k x replay_ratio
  through the REAL feed machinery (synthetic producers →
  TrajectoryBuffer + ReplayTier → BatchPrefetcher with staged-arena
  re-serve → ONE compiled impact-surrogate step), plus the
  driver-level return-vs-wallclock run on cue_memory that the
  accept/reject call is made on (PERF.md discipline: defaults stay at
  replay_k=1 until the curves justify a flip).

  Per cell:
  - `fed_step_ms`: fed wall-clock per learner update;
  - `fresh_unrolls_per_batch`: measured batch composition, attributed
    at SERVE time (`fresh_slots_served` / first serves — a batch the
    prefetcher staged ahead but never served counts nothing, so the
    ratio is immune to prefetch lookahead);
  - `reuse_factor`: learner updates per env frame relative to the
    no-reuse baseline (= replay_k * B / fresh_unrolls_per_batch;
    steady-state exact). The k2_r0 cell's >= 1.8x is the acceptance
    gate;
  - `h2d_unrolls_per_update`: device transfers per update — re-serves
    add NONE (the staged arena rides again), so this halves at
    replay_k=2.
  """
  import threading
  import numpy as np
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.config import Config, validate_replay
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.runtime import ring_buffer
  from scalable_agent_tpu.runtime.actor import batch_unrolls

  h, w = (72, 96) if not smoke else (24, 32)
  b = 32 if not smoke else 2
  t = 100 if not smoke else 4
  steps = 12 if not smoke else 4
  cfg = Config(batch_size=b, unroll_length=t, num_action_repeats=4,
               total_environment_frames=int(1e9),
               torso='deep' if not smoke else 'shallow',
               compute_dtype='bfloat16' if not smoke else 'float32',
               use_instruction=False, surrogate='impact',
               target_update_interval=2)
  validate_replay(cfg)
  agent = ImpalaAgent(num_actions=9, torso=cfg.torso,
                      use_instruction=False,
                      scan_unroll=cfg.scan_unroll,
                      dtype=(jnp.bfloat16 if not smoke
                             else jnp.float32))
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)

  def fresh_state():
    return learner_lib.make_train_state(
        jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                               params), cfg)

  train_step = learner_lib.make_train_step(agent, cfg)
  unroll = _transport_unroll(t + 1, h, w)
  placed = jax.device_put(batch_unrolls([unroll] * b))
  state = fresh_state()
  compiled = train_step.lower(state, placed).compile()
  state, metrics = compiled(state, placed)  # warm (impact compile)
  float(metrics['total_loss'])

  def run_cell(k, ratio):
    state = fresh_state()
    tier = (ring_buffer.ReplayTier(4 * b) if ratio > 0 else None)
    buffer = ring_buffer.TrajectoryBuffer(2 * b, replay=tier,
                                          replay_ratio=ratio)
    stop = threading.Event()

    def produce():
      while not stop.is_set():
        try:
          buffer.put(unroll, timeout=0.2)
        except (TimeoutError, ring_buffer.Closed):
          continue

    producers = [threading.Thread(target=produce, daemon=True)
                 for _ in range(4)]
    for p in producers:
      p.start()
    stager = ring_buffer.UnrollBatchStager(b)
    pf = ring_buffer.BatchPrefetcher(buffer, b, depth=2,
                                     stager=stager, replay_k=k)
    try:
      # Prime: pipeline fill + the insert-jit compile; excluded.
      batch = pf.get(timeout=300)
      state, m = compiled(state, batch)
      float(m['total_loss'])
      base_pf = pf.stats()
      t0 = time.perf_counter()
      for _ in range(steps):
        batch = pf.get(timeout=300)
        state, m = compiled(state, batch)
      float(m['total_loss'])
      fed_ms = (time.perf_counter() - t0) / steps * 1e3
      pf_stats = pf.stats()
    finally:
      stop.set()
      pf.close()
      for p in producers:
        p.join(timeout=2)
    # Serve-attributed composition (lookahead-free): fresh slots and
    # first serves are both credited when a batch is SERVED, so
    # batches the prefetcher staged ahead of the measured window (or
    # left half-served at its edge) cancel out exactly.
    d_serves = pf_stats['serves'] - base_pf['serves']
    d_reserves = (pf_stats['batch_reserves'] -
                  base_pf['batch_reserves'])
    d_first = d_serves - d_reserves
    d_fresh_served = (pf_stats['fresh_slots_served'] -
                      base_pf['fresh_slots_served'])
    fresh_per_batch = (d_fresh_served / d_first if d_first
                       else float(b))
    reuse = k * b / fresh_per_batch if fresh_per_batch else 0.0
    frames_per_batch = fresh_per_batch * t * cfg.num_action_repeats
    return {
        'replay_k': k,
        'replay_ratio': ratio,
        'fed_step_ms': round(fed_ms, 2),
        'fresh_unrolls_per_batch': round(fresh_per_batch, 2),
        'reuse_factor': round(reuse, 3),
        'updates_per_env_frame': round(
            k / frames_per_batch if frames_per_batch else 0.0, 6),
        # Unroll mode device_puts every slot of a first-served batch
        # (replayed slots re-stage too); re-serves transfer nothing.
        'h2d_unrolls_per_update': round(
            b * d_first / d_serves if d_serves else 0.0, 2),
        'batch_reserves': d_reserves,
    }

  results = {
      'batch_size': b,
      'unroll_length': t,
      'surrogate': 'impact',
  }
  for k in (1, 2, 4):
    for ratio in (0.0, 0.5, 0.75):
      results[f'k{k}_r{int(ratio * 100)}'] = run_cell(k, ratio)

  results['return_vs_wallclock'] = _bench_replay_return_curves(smoke)
  return results


def _bench_replay_return_curves(smoke):
  """The accept/reject instrument: driver.train on cue_memory (the CI
  task with a known learnability gap — memory policy 3.0 vs best
  memoryless 2.33), baseline vs reuse config, episode returns against
  WALLCLOCK (reuse buys updates per env second; only a wallclock axis
  can show whether they convert to faster learning or to staleness
  churn). Written into the artifact so the PERF.md r9 accept/reject
  record cites curves, not vibes."""
  import dataclasses
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config

  def base_config(name, **kw):
    cfg = Config(
        logdir=tempfile.mkdtemp(prefix=f'bench_replay_{name}_'),
        env_backend='cue_memory', num_actions=3,
        num_actors=4 if not smoke else 2,
        batch_size=4 if not smoke else 2,
        unroll_length=16 if not smoke else 8,
        num_action_repeats=1,
        height=72 if not smoke else 24,
        width=96 if not smoke else 32,
        torso='shallow', compute_dtype='float32',
        use_py_process=False, use_instruction=False,
        learning_rate=0.003, entropy_cost=0.01, discounting=0.9,
        total_environment_frames=10**8,
        checkpoint_secs=10**6, summary_secs=2 if not smoke else 1,
        seed=17)
    return dataclasses.replace(cfg, **kw)

  variants = [
      ('baseline_k1', base_config('k1')),
      ('reuse_k2', base_config(
          'k2', surrogate='impact', replay_k=2, replay_ratio=0.5,
          target_update_interval=5, replay_max_staleness=100)),
  ]
  out = {'task': 'cue_memory'}
  for name, cfg in variants:
    run = driver.train(cfg, max_seconds=60 if not smoke else 6,
                       stall_timeout_secs=120)
    points = []
    t0 = None
    with open(os.path.join(cfg.logdir, 'summaries.jsonl')) as f:
      for line in f:
        e = json.loads(line)
        if e.get('tag', '').endswith('/episode_return'):
          if t0 is None:
            t0 = e['wall_time']
          points.append((round(e['wall_time'] - t0, 2), e['value']))
    # Downsample to <= 20 curve points (mean per wallclock bucket).
    curve = []
    if points:
      span = max(points[-1][0], 1e-9)
      buckets = {}
      for wt, v in points:
        buckets.setdefault(min(int(wt / span * 20), 19),
                           []).append(v)
      curve = [{'t_secs': round(i / 20 * span, 1),
                'mean_return': round(sum(vs) / len(vs), 3)}
               for i, vs in sorted(buckets.items())]
    _, _, last = _read_window_summaries(cfg.logdir,
                                        cfg.frames_per_step)
    out[name] = {
        'steps': int(run.state.update_steps),
        'episodes': len(points),
        'curve': curve,
        'updates_per_env_frame': last.get(
            'learner_updates_per_env_frame', 0.0),
    }
  return out


class _SyntheticFleet:
  """Producer 'fleet' for the fed-learner stage: threads put canned
  unrolls into the trajectory buffer as fast as it accepts them —
  actors/inference/envs out of the loop, driver.train's own machinery
  (stats peel, publish cadence, summaries, health checks, checkpoint
  decisions) fully in it. Implements the ActorFleet surface train()
  touches."""

  def __init__(self, buffer, unroll, num_threads=2):
    import threading
    self._buffer = buffer
    self._unroll = unroll
    self._stop = threading.Event()
    self._threads = [
        threading.Thread(target=self._produce, daemon=True)
        for _ in range(num_threads)]

  def _produce(self):
    from scalable_agent_tpu.runtime import ring_buffer
    while not self._stop.is_set():
      try:
        self._buffer.put(self._unroll, timeout=0.2)
      except (TimeoutError, ring_buffer.Closed):
        continue

  def start(self):
    for t in self._threads:
      t.start()

  def errors(self):
    return []

  def check_health(self, stall_timeout_secs=None):
    pass

  def stats(self, healthy_horizon_secs: float = 60.0):
    # Synthetic producers never wedge: healthy == alive by definition.
    alive = len(self._threads)
    return {'alive': alive, 'respawns': 0, 'healthy': alive,
            'healthy_fraction': 1.0, 'unrolls': 0}

  def stop(self, timeout=10.0):
    self._stop.set()
    for t in self._threads:
      t.join(timeout=timeout)


def bench_e2e_fed(smoke):
  """Fed-learner measurement (VERDICT r4 Missing #2): driver.train's
  REAL loop — per-step stats extraction, publish-every-step cadence,
  summary writes, health checks, prefetcher staging + H2D — consuming
  synthetic unrolls at full rate, at the flagship learner shape
  (B=32, T=100, deep, bf16). 'The learner loop sustains ~NNNk fps
  when fed' becomes a measurement; the remaining gap to the synthetic
  headline is the loop+transfer overhead, itemized by the window
  telemetry."""
  import dataclasses
  from scalable_agent_tpu import driver

  cfg = _e2e_window_config(
      smoke, seed=7,
      num_actors=0,            # no env fleet; feed is synthetic
      batch_size=32 if not smoke else 2,
      use_py_process=False)
  t1 = cfg.unroll_length + 1
  unroll = _transport_unroll(t1, cfg.height, cfg.width)

  def fleet_factory(config, agent, policy, buffer, levels):
    return _SyntheticFleet(buffer, unroll)

  for attempt in range(2):
    run = driver.train(cfg, max_seconds=65 if not smoke else 8,
                       stall_timeout_secs=120,
                       fleet_factory=fleet_factory)
    if run.frames > 0:
      break
    if attempt == 1:
      raise RuntimeError('e2e_fed: zero frames in both attempts')
    cfg = dataclasses.replace(
        cfg, logdir=tempfile.mkdtemp(prefix='bench_fed_'))
  fps, span, last = _read_window_summaries(cfg.logdir,
                                           cfg.frames_per_step)

  # Gap itemization (the VERDICT r4 #3 contract: fed fps within ~10%
  # of synthetic OR the gap itemized): measure the two stage costs the
  # fed loop adds over the bare step — host-side batch stacking and
  # the host→device transfer of the stacked batch, barriered by a
  # value readback.
  import jax
  import numpy as np
  from scalable_agent_tpu.runtime.actor import batch_unrolls
  rows = [unroll] * cfg.batch_size
  t0 = time.perf_counter()
  n_itemize = 3 if not smoke else 1
  for _ in range(n_itemize):
    stacked = batch_unrolls(rows)
  stack_ms = (time.perf_counter() - t0) / n_itemize * 1e3
  batch_mb = sum(x.nbytes for x in
                 jax.tree_util.tree_leaves(stacked)) / 1e6
  # Barrier discipline: readback ONE element of the LARGEST leaf (the
  # 66 MB frame stack) — transfers are not ordered across arrays, so a
  # small-leaf readback could stop the clock before the dominant
  # transfer lands; a full-leaf np.asarray would add its own 66 MB D2H
  # to the timing. Residual error is bounded by the small leaves.
  def place_and_barrier(batch):
    placed = jax.tree_util.tree_map(jax.device_put, batch)
    biggest = max(jax.tree_util.tree_leaves(placed),
                  key=lambda x: x.nbytes)
    return lambda: float(biggest.ravel()[0].astype(np.float32))

  def h2d_once():
    place_and_barrier(stacked)()
  h2d_once()  # warm path
  t0 = time.perf_counter()
  for _ in range(n_itemize):
    h2d_once()
  h2d_ms = (time.perf_counter() - t0) / n_itemize * 1e3
  # Pipelined variant (round 6, staging_depth>=2): TWO transfers in
  # flight, barriered together, amortized per batch. This is what the
  # prefetcher's double-buffering actually issues; serial-vs-
  # pipelined is the measured overlap win of the transfers
  # themselves, independent of compute overlap (which the run's
  # h2d_overlap_fraction summary below reports).
  stacked2 = batch_unrolls(rows)  # distinct host buffers
  t0 = time.perf_counter()
  for _ in range(n_itemize):
    barriers = [place_and_barrier(stacked), place_and_barrier(stacked2)]
    for b in barriers:
      b()
  h2d_pipelined_ms = ((time.perf_counter() - t0) / n_itemize / 2) * 1e3
  # Exposed vs overlapped H2D (round 8 satellite): the run's own
  # telemetry says how much of the transfer the step actually WAITED
  # on (`staging_exposed_ms_per_step`, last steady interval); the
  # remainder of the serially-measured burst was hidden behind
  # compute/pipelining. The window-total h2d_ms alone could not tell
  # a fully-hidden transfer from a fully-exposed one.
  exposed_ms = round(last.get('staging_exposed_ms_per_step', 0.0), 1)
  return {
      'fps': round(fps, 1),
      'steady_secs': round(span, 1),
      'buffer_unrolls': last.get('buffer_unrolls', 0.0),
      # Fraction of steps that never blocked on staging (driver
      # summary; the ISSUE-1 acceptance counter).
      'h2d_overlap_fraction': last.get('h2d_overlap_fraction', 0.0),
      'staging_depth': cfg.staging_depth,
      # The mode the run ACTUALLY used (driver echo) — config alone
      # would mislabel a topology fallback to batch staging.
      'staging_mode': ('unroll'
                       if last.get('staging_unroll_active') else
                       'batch'),
      'frames': int(run.frames),
      'batch_size': cfg.batch_size,
      # Sample-reuse motivation split (round 10): updates per fresh
      # env frame (1/frames_per_step with replay off) and how busy
      # each plane actually was — learner low + env high is the
      # env-bound regime the replay knobs attack (driver summaries;
      # the same numbers judge the flip later).
      'learner_updates_per_env_frame': last.get(
          'learner_updates_per_env_frame', 0.0),
      'env_plane_utilization': round(
          last.get('env_plane_utilization', 0.0), 3),
      'learner_plane_utilization': round(
          last.get('learner_plane_utilization', 0.0), 3),
      'gap_itemization': {
          'batch_mb': round(batch_mb, 1),
          'stack_ms': round(stack_ms, 1),
          'h2d_ms': round(h2d_ms, 1),
          'h2d_pipelined_ms': round(h2d_pipelined_ms, 1),
          'h2d_exposed_ms': exposed_ms,
          'h2d_overlapped_ms': round(max(h2d_ms - exposed_ms, 0.0), 1),
      },
  }


def _transport_unroll(t1, h, w, num_actions=9):
  """One realistic host-side unroll (numpy, flagship shapes)."""
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.testing import make_example_unroll
  return make_example_unroll(t1, h, w, num_actions,
                             MAX_INSTRUCTION_LEN)


def _ingest_pump_child(port, smoke, validate, duration):
  """Ingest-bench pump, run in a CHILD process (spawn): one actor
  host's connection at full tilt. Exits 0 when the duration lapses or
  the learner goes away (the parent tears the server down mid-pump)."""
  from scalable_agent_tpu.runtime.py_process import pin_process_to_cpu
  pin_process_to_cpu()
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent
  from scalable_agent_tpu.runtime import remote
  t1 = 101 if not smoke else 6
  h, w = (72, 96) if not smoke else (24, 32)
  unroll = _transport_unroll(t1, h, w)
  client = remote.RemoteActorClient(f'127.0.0.1:{port}',
                                    connect_timeout_secs=30)
  try:
    if validate:
      cfg = Config(env_backend='fake', num_actions=9,
                   unroll_length=t1 - 1, height=h, width=w,
                   use_instruction=False)
      agent = ImpalaAgent(num_actions=9, use_instruction=False)
      client.handshake(remote.trajectory_contract(cfg, agent, 9))
    end = time.monotonic() + duration
    while time.monotonic() < end:
      client.send_unroll(unroll)
  except (OSError, remote.LearnerShutdown):
    pass  # parent closed the server: clean end of the window
  finally:
    client.close()


def _fanout_fetch_child(port, duration, counter):
  """Fan-out bench fetcher, run in a CHILD process: one actor host
  polling get_params at full tilt (worst case — production clients
  are version-gated), decoding each blob like a real host would."""
  from scalable_agent_tpu.runtime.py_process import pin_process_to_cpu
  pin_process_to_cpu()
  from scalable_agent_tpu.runtime import remote
  client = remote.RemoteActorClient(f'127.0.0.1:{port}',
                                    connect_timeout_secs=30)
  try:
    end = time.monotonic() + duration
    while time.monotonic() < end:
      client.fetch_params()
      counter.value += 1
  except (OSError, RuntimeError, remote.LearnerShutdown):
    pass  # parent closed the server: end of the window
  finally:
    client.close()


def _fanout_pump_child(port, smoke, duration, counter, lat_queue):
  """Fan-out bench unroll pump (the hot ingest path), run in a CHILD
  process; ships per-send ack latencies back for the p50/p99 rows."""
  from scalable_agent_tpu.runtime.py_process import pin_process_to_cpu
  pin_process_to_cpu()
  from scalable_agent_tpu.runtime import remote
  t1 = 101 if not smoke else 6
  h, w = (72, 96) if not smoke else (24, 32)
  unroll = _transport_unroll(t1, h, w)
  client = remote.RemoteActorClient(f'127.0.0.1:{port}',
                                    connect_timeout_secs=30)
  try:
    end = time.monotonic() + duration
    while time.monotonic() < end:
      t0 = time.perf_counter()
      client.send_unroll(unroll)
      lat_queue.put(time.perf_counter() - t0)
      counter.value += 1
  except (OSError, RuntimeError, remote.LearnerShutdown):
    pass  # parent closed the server: end of the window
  finally:
    client.close()


def _count_window(count_fn, base, min_dur, min_count=8, max_dur=30.0):
  """Measure a completion-counter window robustly.

  Sleeps at least `min_dur`, then keeps extending (in 50 ms slices, up
  to `max_dur`) until at least `min_count` completions landed. A loaded
  CI host can legitimately finish zero requests inside a 0.4 s
  smoke window — that starvation is scheduling noise, not a pipeline
  rate, and publishing 0.0 into the scaling arithmetic (or a smoke
  assert) is wrong. A genuinely dead stage still terminates: after
  `max_dur` we return whatever was counted (possibly 0) and the
  caller's zero-checks fire with their diagnostics.
  """
  t0 = time.perf_counter()
  time.sleep(min_dur)
  while (count_fn() - base < min_count
         and time.perf_counter() - t0 < max_dur):
    time.sleep(0.05)
  return time.perf_counter() - t0


def bench_transport(smoke):
  """Host-transport ceiling with the device and the envs OUT of
  the loop (VERDICT r2 Missing #1 / W4): what the host-side pipeline
  pieces can sustain by themselves, at flagship row sizes (72x96x3
  frames, T+1=101). Three stages, measured independently:

  a) synthetic producer threads → TrajectoryBuffer → BatchPrefetcher
     with a no-op place_fn (batch assembly/stacking included);
  b) the C++ dynamic batcher standalone: concurrent batch-1 callers
     through merge/split with a no-op computation, vs thread count;
  c) TrajectoryIngestServer loopback: pickle TCP ingest, 1 and 4
     connections.

  All numbers are for THIS host (the docs' scaling arithmetic divides
  by them); GIL contention is part of the measurement, deliberately.
  """
  import threading
  import numpy as np
  from scalable_agent_tpu.ops import dynamic_batching
  from scalable_agent_tpu.runtime import remote, ring_buffer

  t1 = 101 if not smoke else 6
  h, w = (72, 96) if not smoke else (24, 32)
  dur = 6.0 if not smoke else 0.8
  unroll = _transport_unroll(t1, h, w)
  import jax
  unroll_mb = sum(x.nbytes for x in jax.tree_util.tree_leaves(unroll)
                  ) / 1e6
  results = {'unroll_mb': round(unroll_mb, 2)}

  # --- (a) buffer → prefetcher (batch assembly + staging thread). ---
  batch_size = 4
  buffer = ring_buffer.TrajectoryBuffer(2 * batch_size)
  stop = threading.Event()

  def produce():
    while not stop.is_set():
      try:
        buffer.put(unroll, timeout=0.2)
      except (TimeoutError, ring_buffer.Closed):
        continue

  producers = [threading.Thread(target=produce, daemon=True)
               for _ in range(4)]
  for p in producers:
    p.start()
  prefetcher = ring_buffer.BatchPrefetcher(buffer, batch_size,
                                           place_fn=lambda b: b)
  prefetcher.get(timeout=30)  # warm
  n = 0
  t0 = time.perf_counter()
  while time.perf_counter() - t0 < dur:
    prefetcher.get(timeout=30)
    n += 1
  dt = time.perf_counter() - t0
  stop.set()
  prefetcher.close()
  for p in producers:
    p.join(timeout=2)
  results['buffer_prefetcher'] = {
      'batches_per_sec': round(n / dt, 1),
      'unrolls_per_sec': round(n * batch_size / dt, 1),
      'mb_per_sec': round(n * batch_size * unroll_mb / dt, 1),
  }

  # --- (b) C++ batcher standalone (merge/split machinery only). ---
  frame_row = np.zeros((1, h, w, 3), np.uint8)
  action_row = np.zeros((1,), np.int32)
  batcher_results = {}
  for nthreads in ((4, 16, 48) if not smoke else (4,)):
    fn = dynamic_batching.batch_fn_with_options(
        maximum_batch_size=1024, timeout_ms=2)(
            lambda frame, action: action)
    counts = [0] * nthreads
    stop_b = threading.Event()

    def worker(i):
      while not stop_b.is_set():
        fn(frame_row, action_row)
        counts[i] += 1

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(nthreads)]
    for t in threads:
      t.start()
    time.sleep(0.3)  # warm
    base = sum(counts)
    dt = _count_window(lambda: sum(counts), base, dur / 2)
    got = sum(counts) - base
    # Join BEFORE close: close() cancels in-flight requests, which
    # raises BatcherCancelled out of any worker still inside fn().
    stop_b.set()
    for t in threads:
      t.join(timeout=2)
    fn.close()
    batcher_results[f'threads_{nthreads}'] = round(got / dt, 1)
  results['batcher_requests_per_sec'] = batcher_results

  # --- (c) ingest loopback (tagged TCP wire), with the production
  # contract: the measured constant must include the handshake and the
  # per-unroll signature/action-range validation every real ingest
  # pays (driver.train always passes a contract). Pumps run in CHILD
  # PROCESSES (round 6): the real topology is actor HOSTS feeding the
  # learner, so the measured quantity must be the learner-side ingest
  # capacity — in-process pump threads shared the server's GIL and
  # measured the bench's own client cost as much as the server (the
  # r5 "4 connections lose to 1" was partly that artifact, partly the
  # reader-thread critical path the worker-pool handoff removed). ---
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent
  ingest_cfg = Config(env_backend='fake', num_actions=9,
                      unroll_length=t1 - 1, height=h, width=w,
                      use_instruction=False)
  ingest_agent = ImpalaAgent(num_actions=9, use_instruction=False)
  contract = remote.trajectory_contract(ingest_cfg, ingest_agent, 9)

  def run_ingest(nclients, validate, wire_crc=True):
    import multiprocessing
    ctx = multiprocessing.get_context('spawn')
    buf = ring_buffer.TrajectoryBuffer(16)
    server = remote.TrajectoryIngestServer(
        buf, {'w': np.zeros(1)}, host='127.0.0.1',
        contract=contract if validate else None,
        wire_crc=wire_crc)
    stop_c = threading.Event()

    def drain():
      while not stop_c.is_set():
        try:
          buf.get(timeout=0.2)
        except (TimeoutError, ring_buffer.Closed):
          continue

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    # Children pump for a fixed wall budget that comfortably covers
    # their own startup plus the measuring window; the count is read
    # on the SERVER side.
    child_secs = dur * 2 + (30.0 if not smoke else 20.0)
    pumps = [ctx.Process(target=_ingest_pump_child,
                         args=(server.port, smoke, validate,
                               child_secs), daemon=True)
             for _ in range(nclients)]
    for p in pumps:
      p.start()
    # Warm until every connection is live and feeding (child startup
    # pays a jax import; do not let it eat the window).
    deadline = time.perf_counter() + (60 if not smoke else 120)
    while (server.stats()['unrolls'] < nclients
           and time.perf_counter() < deadline):
      if any(p.exitcode not in (None, 0) for p in pumps):
        break
      time.sleep(0.1)
    base = server.stats()['unrolls']
    dt = _count_window(lambda: server.stats()['unrolls'], base,
                       dur / 2)
    got = server.stats()['unrolls'] - base
    server_stats = server.stats()
    stop_c.set()
    for p in pumps:
      p.terminate()
      p.join(timeout=10)
    server.close()
    buf.close()
    drainer.join(timeout=2)
    if got == 0:
      raise RuntimeError(
          f'ingest bench moved no unrolls ({nclients} conns); child '
          f'exitcodes: {[p.exitcode for p in pumps]}')
    return {
        'unrolls_per_sec': round(got / dt, 1),
        'mb_per_sec': round(got * unroll_mb / dt, 1),
        # Server-side ack service time (recv-complete → ack-sent):
        # the per-lane counter the driver also exports.
        'ack_p50_ms': round(server_stats['ack_p50_ms'], 2),
        'ack_p99_ms': round(server_stats['ack_p99_ms'], 2),
    }

  for nclients in ((1, 4) if not smoke else (1,)):
    results[f'ingest_{nclients}conn'] = run_ingest(nclients, True)
  # The validation-cost delta (VERDICT r3 W4): production always
  # validates, so the headline ingest numbers above include it; this
  # pair quantifies what the precompiled fast path left on the table.
  results['ingest_1conn_novalidate'] = run_ingest(1, False)
  # The v7 CRC-cost delta (round 12): the headline rows run the
  # production default (CRC negotiated ON — the clients handshake, so
  # every unroll pays sender CRC + receiver verify); this row
  # negotiates it OFF server-side, making the trailer overhead a
  # measured fact (docs/PERF.md r10 records the accept call — the
  # gate is <5% frames/s).
  results['ingest_1conn_crc_off'] = run_ingest(1, True,
                                               wire_crc=False)
  on = results['ingest_1conn']['unrolls_per_sec']
  off = results['ingest_1conn_crc_off']['unrolls_per_sec']
  results['crc_overhead_fraction'] = (round(1.0 - on / off, 4)
                                      if off else None)
  return results


def bench_param_fanout(smoke):
  """Learner param-snapshot EGRESS ceiling (VERDICT r3 Missing #1).

  The other half of the reference's scaling story: weights served to
  150–500 actor machines (reference: experiment.py ≈L415–455
  `pin_global_variables` — variables pinned to the learner CPU because
  serving them is a real cost; SURVEY §5.8). Every connected actor
  host refetches the snapshot once per version bump, so worst-case
  learner egress is hosts × blob_bytes / remote_publish_secs — this
  stage measures the serving side of that term with the REAL flagship
  blob (deep ResNet + instruction encoder, the tree every dmlab30
  actor host fetches):

  a) serving ceiling: N loopback CHILD-PROCESS clients looping
     get_params over the PARAM LANE (round 6: one selector thread,
     chunked non-blocking sends, bf16 codec default, out-of-band
     blob frames) — aggregate blobs/s and MB/s vs N. Clients decode
     on their own processes, matching the actor-host topology; the
     serving side is the per-core constant the PERF.md arithmetic
     divides by, same methodology as the ingest stage.
  b) ack-latency impact: one unroll pump (the hot ingest path) alone
     vs sharing the server with 8 param fetchers. r5 measured the
     shared-thread design collapsing the pump 838.6 → 29.9 unrolls/s
     (ack p99 95.8 ms); the lane isolation is accepted or rejected on
     this row.
  c) wire-shrink levers, measured one-off on the real blob: zlib-1
     compression (ratio + CPU cost) and a bfloat16 cast (exactly
     halves the float32 payload) — the bf16 numbers justify the
     publish_codec='bf16' default (docs/TRANSPORT.md).
  """
  import pickle
  import threading
  import zlib
  import numpy as np
  import jax
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.runtime import remote, ring_buffer

  h, w = (72, 96) if not smoke else (24, 32)
  dur = 6.0 if not smoke else 0.8
  agent = ImpalaAgent(num_actions=9,
                      torso='deep' if not smoke else 'shallow',
                      use_instruction=not smoke)
  params = jax.device_get(init_params(
      agent, jax.random.PRNGKey(0),
      {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}))
  blob = pickle.dumps(('params', 1, params),
                      protocol=pickle.HIGHEST_PROTOCOL)
  blob_mb = len(blob) / 1e6
  # The production default codec (config.publish_codec='bf16') is the
  # measured configuration; the f32 blob size is kept for the ratio.
  wire_dtype = Config().resolved_wire_dtype
  results = {
      'blob_mb': round(blob_mb, 2),
      'wire_dtype': wire_dtype or 'float32',
      'num_params': int(sum(
          x.size for x in jax.tree_util.tree_leaves(params))),
  }

  def run_fanout(nfetchers, with_pump):
    """nfetchers get_params loops (+ optionally one unroll pump)
    against one server; returns (blobs/s, pump stats or None). Like
    the ingest stage, the clients run in CHILD processes (round 6):
    real actor hosts fetch and decode on their own CPUs, so the
    measured quantity must be the learner-side serving/ack capacity,
    not the bench's own in-process client decode sharing the server's
    GIL."""
    import multiprocessing
    ctx = multiprocessing.get_context('spawn')
    buf = ring_buffer.TrajectoryBuffer(16)
    server = remote.TrajectoryIngestServer(buf, params,
                                           host='127.0.0.1',
                                           wire_dtype=wire_dtype)
    stop = threading.Event()

    def drain():
      while not stop.is_set():
        try:
          buf.get(timeout=0.2)
        except (TimeoutError, ring_buffer.Closed):
          continue

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    child_secs = dur * 2 + (30.0 if not smoke else 20.0)
    # One counter PER child (each Value has a single writer — a shared
    # lock-free Value across N processes would lose increments to the
    # non-atomic read-modify-write and understate the ceiling).
    fetch_counts = [ctx.Value('q', 0, lock=False)
                    for _ in range(nfetchers)]
    pump_count = ctx.Value('q', 0, lock=False)
    lat_queue = ctx.Queue()
    procs = [ctx.Process(target=_fanout_fetch_child,
                         args=(server.port, child_secs,
                               fetch_counts[i]), daemon=True)
             for i in range(nfetchers)]
    if with_pump:
      procs.append(ctx.Process(
          target=_fanout_pump_child,
          args=(server.port, smoke, child_secs, pump_count,
                lat_queue), daemon=True))
    for p in procs:
      p.start()

    pump_latencies = []

    def drain_latencies():
      while True:
        try:
          pump_latencies.append(lat_queue.get(timeout=0.1))
        except Exception:
          if stop.is_set():
            return

    lat_drainer = threading.Thread(target=drain_latencies, daemon=True)
    lat_drainer.start()

    def total_fetched():
      return sum(c.value for c in fetch_counts)

    def progress():
      vals = []
      if nfetchers:
        vals.append(total_fetched() - fetch_base)
      if with_pump:
        vals.append(pump_count.value - pump_base)
      return min(vals) if vals else 1 << 30

    # Warm until every role is live (children pay a jax import).
    deadline = time.perf_counter() + (60 if not smoke else 120)
    while time.perf_counter() < deadline:
      if ((not nfetchers or total_fetched() > 0)
          and (not with_pump or pump_count.value > 0)):
        break
      if any(p.exitcode not in (None, 0) for p in procs):
        break
      time.sleep(0.1)
    fetch_base, pump_base = total_fetched(), pump_count.value
    lat_base = len(pump_latencies)
    dt = _count_window(progress, 0, dur / 2)
    fetched = total_fetched() - fetch_base
    pumped = pump_count.value - pump_base
    window_lat = sorted(pump_latencies[lat_base:])
    # MB/s must count the bytes actually on the wire (bf16 codec
    # halves the f32 pickle this stage used to multiply by).
    wire_mb = server.snapshot_nbytes() / 1e6
    results.setdefault('wire_blob_mb', round(wire_mb, 2))
    stop.set()
    for p in procs:
      p.terminate()
      p.join(timeout=10)
    server.close()
    buf.close()
    drainer.join(timeout=2)
    lat_drainer.join(timeout=2)
    if nfetchers and fetched == 0:
      raise RuntimeError(
          f'param fan-out moved no blobs ({nfetchers} fetchers); '
          f'child exitcodes: {[p.exitcode for p in procs]}')
    if with_pump and pumped == 0:
      # Same no-silent-zero rule as the ingest stage: a dead pump must
      # fail the bench, not publish a null latency row.
      raise RuntimeError(
          f'fan-out pump moved no unrolls; child exitcodes: '
          f'{[p.exitcode for p in procs]}')
    fanout = {'blobs_per_sec': round(fetched / dt, 1),
              'mb_per_sec': round(fetched * wire_mb / dt, 1)}
    pump_stats = None
    if with_pump and window_lat:
      # The shared nearest-rank percentile (runtime.inference): the
      # bench rows and the live stats() must compute identically.
      from scalable_agent_tpu.runtime.inference import percentile_ms
      pump_stats = {
          'unrolls_per_sec': round(pumped / dt, 1),
          'ack_p50_ms': round(percentile_ms(window_lat, 0.5, 1e3), 2),
          'ack_p99_ms': round(percentile_ms(window_lat, 0.99, 1e3), 2),
      }
    return fanout, pump_stats

  for nfetchers in ((1, 8, 32) if not smoke else (1,)):
    fanout, _ = run_fanout(nfetchers, with_pump=False)
    results[f'fanout_{nfetchers}host'] = fanout
  _, pump_alone = run_fanout(0, with_pump=True)
  contenders = 8 if not smoke else 1
  _, pump_contended = run_fanout(contenders, with_pump=True)
  results['pump_alone'] = pump_alone
  results[f'pump_with_{contenders}_fetchers'] = pump_contended

  # --- (c) wire-shrink levers, one-off on the real blob. ---
  t0 = time.perf_counter()
  z = zlib.compress(blob, 1)
  z_secs = time.perf_counter() - t0
  results['zlib1'] = {'ratio': round(len(z) / len(blob), 3),
                      'compress_ms': round(z_secs * 1e3, 1)}
  import ml_dtypes
  t0 = time.perf_counter()
  cast = jax.tree_util.tree_map(
      lambda x: x.astype(ml_dtypes.bfloat16)
      if x.dtype == np.float32 else x, params)
  bblob = pickle.dumps(('params', 1, cast),
                       protocol=pickle.HIGHEST_PROTOCOL)
  b_secs = time.perf_counter() - t0
  results['bf16_cast'] = {'ratio': round(len(bblob) / len(blob), 3),
                          'cast_ms': round(b_secs * 1e3, 1)}
  return results


class _ThrottledFleet(_SyntheticFleet):
  """Rate-limited synthetic producer: one unroll per `period` seconds
  across the fleet — the ENV-BOUND regime (BENCH r9: ~150 fps feed vs
  ~300k fps learner capacity) the hybrid filler exists for. Single
  producer thread so the offered rate is the period, not its
  multiple."""

  def __init__(self, buffer, unroll, period):
    super().__init__(buffer, unroll, num_threads=1)
    self._period = period

  def _produce(self):
    import time as _time
    from scalable_agent_tpu.runtime import ring_buffer
    while not self._stop.is_set():
      _time.sleep(self._period)
      try:
        self._buffer.put(self._unroll, timeout=0.2)
      except (TimeoutError, ring_buffer.Closed):
        continue


def bench_anakin(smoke):
  """The Anakin runtime axis (round 16; parallel/anakin.py,
  driver.train_anakin, docs/PARALLELISM.md):

  1. Fused-loop fps rows over the jittable env family ({bandit,
     cue_memory, gridworld} × {1 device, all local devices}) — the
     all-device rows shard the env batch over the data mesh axis per
     the `test_anakin_shards_over_the_mesh` discipline.
  2. TWO references at the SAME model/shape, batch size, and device
     set as the anakin bandit row (driver.choose_mesh shards the fed
     learner over all local devices exactly like the all-device
     anakin row):
     - `fleet_reference` — the REAL fleet path (actors -> inference
       server -> buffer -> learner), acting cost included:
       `anakin_vs_fleet` is the end-to-end fusion win the >=3x
       acceptance gate reads (the r4 chip artifact: 1.25M fused vs
       the fed flagship's ~300k).
     - `fed_reference` — a full-rate SYNTHETIC feed through the same
       driver loop: the learner-loop ceiling with acting excluded.
       `anakin_vs_fed` can legitimately read < 1 on a CPU build host
       (synthetic data is free there and the fused loop still pays
       its T sequential acting passes); on the chip the fed path's
       transport/H2D terms return and the ratio shows the fusion win.
       Reported so the two effects (acting amortization vs transport
       deletion) stay separable.
  3. The HYBRID row: driver.train under an env-THROTTLED synthetic
     feed with --anakin_filler off vs on — learner-plane utilization
     must be strictly higher with the filler ON while fleet
     fresh-frame accounting (frame budget, fps) is unchanged at
     filler-OFF parity. This is the accept/reject evidence for the
     filler default (docs/PERF.md r13)."""
  import dataclasses
  import numpy as np
  import jax
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.parallel import anakin
  from scalable_agent_tpu.parallel import mesh as mesh_lib

  n_dev = len(jax.devices())
  steps = 200 if not smoke else 3
  t = 20 if not smoke else 3
  base = dict(
      unroll_length=t, num_action_repeats=1,
      height=24, width=32, torso='shallow',
      compute_dtype='bfloat16' if not smoke else 'float32',
      use_instruction=False, use_py_process=False,
      learning_rate=2e-3, entropy_cost=3e-3,
      total_environment_frames=10**9, seed=0)
  episode_lengths = {'bandit': 5, 'cue_memory': 2, 'gridworld': 12}

  out = {'devices': n_dev}
  for backend in ('bandit', 'cue_memory', 'gridworld'):
    for devices in sorted({1, n_dev}):
      b = 256 if not smoke else 8
      b = max(b - b % devices, devices)  # shardable batch
      cfg = Config(env_backend=backend, batch_size=b,
                   episode_length=episode_lengths[backend],
                   discounting=0.0 if backend == 'bandit' else 0.9,
                   **base)
      mesh = (mesh_lib.make_mesh() if devices > 1 else None)
      _, history, fps = anakin.run(cfg, steps, mesh=mesh)
      rewards = [float(h['mean_reward']) for h in history]
      tail = max(len(rewards) // 10, 1)
      out[f'{backend}_{devices}dev'] = {
          'env_frames_per_sec': round(fps, 1),
          'batch_size': b,
          'mean_reward_first': round(float(np.mean(rewards[:tail])),
                                     3),
          'mean_reward_last': round(float(np.mean(rewards[-tail:])),
                                    3),
      }
  out['config'] = ('shallow, 24x32, T=%d, %d step(s)' % (t, steps))

  # --- Fed-fleet reference + hybrid filler rows: driver.train's REAL
  # loop at the SAME model/shape, fed synthetically. ---
  unroll = _transport_unroll(t + 1, 24, 32, num_actions=3)

  def run_fed(tag, filler, period, seconds, batch_size,
              real_fleet=False):
    cfg = Config(env_backend='bandit', level_name='bandit',
                 num_actors=4 if real_fleet else 0,
                 batch_size=batch_size,
                 episode_length=5, discounting=0.0,
                 logdir=tempfile.mkdtemp(prefix=f'bench_anakin_{tag}_'),
                 anakin_filler=filler,
                 inference_timeout_ms=5,
                 queue_capacity_batches=2, summary_secs=0,
                 checkpoint_secs=10**6, slo_engine=False,
                 controller='off',
                 **{k: v for k, v in base.items()
                    if k not in ('seed',)}, seed=13)

    def fleet_factory(config, agent, policy, buffer, levels):
      if period is None:
        return _SyntheticFleet(buffer, unroll)
      return _ThrottledFleet(buffer, unroll, period)

    run = driver.train(cfg, max_seconds=seconds,
                       stall_timeout_secs=120,
                       fleet_factory=(None if real_fleet
                                      else fleet_factory))
    fps, _, last = _read_window_summaries(cfg.logdir,
                                          cfg.frames_per_step)
    return {
        'fps': round(fps, 1),
        'frames': int(run.frames),
        'learner_plane_utilization': round(
            last.get('learner_plane_utilization', 0.0), 3),
        'filler_updates': int(last.get('filler_updates', 0)),
        'filler_frames': int(last.get('filler_frames', 0)),
    }

  seconds = 20 if not smoke else 6
  # Apples to apples: both references run the SAME batch as the
  # anakin bandit rows, and choose_mesh shards them over all local
  # devices — so the ratios' numerator is the matching-device anakin
  # row, never a B-or-device artifact. The fleet reference uses the
  # 4-actor CI-scale local fleet (acting through the real batcher).
  anakin_ref = (out.get(f'bandit_{n_dev}dev')
                or out['bandit_1dev'])
  fleet_ref = run_fed('fleet', filler=False, period=None,
                      seconds=seconds,
                      batch_size=anakin_ref['batch_size'],
                      real_fleet=True)
  out['fleet_reference'] = dict(fleet_ref,
                                batch_size=anakin_ref['batch_size'],
                                num_actors=4)
  if fleet_ref['fps'] > 0:
    out['anakin_vs_fleet'] = round(
        anakin_ref['env_frames_per_sec'] / fleet_ref['fps'], 2)
  fed = run_fed('fed', filler=False, period=None, seconds=seconds,
                batch_size=anakin_ref['batch_size'])
  out['fed_reference'] = dict(fed, batch_size=anakin_ref['batch_size'])
  if fed['fps'] > 0:
    out['anakin_vs_fed'] = round(
        anakin_ref['env_frames_per_sec'] / fed['fps'], 2)

  # Hybrid: the SAME throttled env-bound feed, filler off vs on. The
  # off row is the parity baseline (fresh-frame fps/frames must match
  # the on row's fresh accounting — filler frames ride a separate
  # ledger). Small batch on purpose: the rows measure utilization
  # under a trickle feed, not throughput.
  period = 0.25 if not smoke else 0.4
  hybrid_b = 8 if not smoke else 2
  hybrid_off = run_fed('off', filler=False, period=period,
                       seconds=seconds, batch_size=hybrid_b)
  hybrid_on = run_fed('on', filler=True, period=period,
                      seconds=seconds, batch_size=hybrid_b)
  out['hybrid'] = {
      'feed_period_secs': period,
      'filler_off': hybrid_off,
      'filler_on': hybrid_on,
      'utilization_lift': round(
          hybrid_on['learner_plane_utilization'] -
          hybrid_off['learner_plane_utilization'], 3),
  }
  return out


def bench_telemetry(smoke):
  """Tracing/registry overhead (round 13; docs/PERF.md r11): the cost
  of the always-on telemetry plane, measured so the default is an
  accept/reject call with numbers. Three rows:

  a) registry micro: Counter.inc + Histogram.observe, ns/op — the
     per-event cost every converted module counter now pays;
  b) span micro: the full per-unroll trace lifecycle (make + 4 hop
     stamps + sidecar tag + pop), ns/span;
  c) feed pipeline head-to-head: synthetic producer threads →
     TrajectoryBuffer → BatchPrefetcher at flagship unroll sizes,
     tracer ON (spans stamped + tagged by producers, batch records
     written to a real traces.jsonl) vs OFF — unrolls/s both ways and
     the headline overhead fraction.
  """
  import shutil
  import tempfile
  import threading
  from scalable_agent_tpu import telemetry
  from scalable_agent_tpu.runtime import ring_buffer

  t1 = 101 if not smoke else 6
  h, w = (72, 96) if not smoke else (24, 32)
  dur = 4.0 if not smoke else 0.8
  unroll = _transport_unroll(t1, h, w)
  results = {}

  # --- (a) registry micro. ---
  n = 200_000 if not smoke else 20_000
  c = telemetry.counter('bench/telemetry_counter')
  hist = telemetry.histogram('bench/telemetry_hist')
  t0 = time.perf_counter()
  for i in range(n):
    c.inc()
    hist.observe(i)
  dt = time.perf_counter() - t0
  results['registry_ns_per_op'] = round(dt / (2 * n) * 1e9, 1)

  # --- (b) span micro. ---
  n = 50_000 if not smoke else 5_000
  t0 = time.perf_counter()
  for i in range(n):
    tr = telemetry.make_trace('bench', i, behavior_version=i)
    for hop in (telemetry.HOP_DONE, telemetry.HOP_WIRE,
                telemetry.HOP_STAGED, telemetry.HOP_STEP):
      telemetry.stamp(tr, hop)
    telemetry.tag_unroll(unroll, tr)
    telemetry.pop_unroll(unroll)
  dt = time.perf_counter() - t0
  results['span_ns'] = round(dt / n * 1e9, 1)

  # --- (c) feed pipeline, tracing on vs off. ---
  def run_feed(tracing):
    batch_size = 4
    tmpdir = tempfile.mkdtemp(prefix='bench_telemetry_')
    tracer = None
    if tracing:
      tracer = telemetry.PipelineTracer(tmpdir)
      telemetry.set_tracer(tracer)
    buffer = ring_buffer.TrajectoryBuffer(2 * batch_size)
    stop = threading.Event()

    def produce(name):
      seq = 0
      while not stop.is_set():
        # _replace: a fresh pytree object per put — the sidecar tag
        # store keys by identity, so re-putting ONE object would
        # alias every in-flight tag (production unrolls are always
        # distinct objects).
        item = unroll._replace()
        trace = telemetry.begin_unroll_trace(name, seq)
        if trace is not None:
          telemetry.stamp(trace, telemetry.HOP_DONE)
          telemetry.tag_unroll(item, trace)
        seq += 1
        try:
          buffer.put(item, timeout=0.2)
        except (TimeoutError, ring_buffer.Closed):
          continue

    producers = [threading.Thread(target=produce, args=(f'p{i}',),
                                  daemon=True) for i in range(4)]
    for p in producers:
      p.start()
    prefetcher = ring_buffer.BatchPrefetcher(buffer, batch_size,
                                             place_fn=lambda b: b)
    prefetcher.get(timeout=30)
    if tracer is not None:
      tracer.on_step(0)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < dur:
      prefetcher.get(timeout=30)
      n += 1
      if tracer is not None:
        # The driver's per-step completion call — batch record +
        # policy-lag arithmetic + the traces.jsonl write.
        tracer.on_step(n)
    dt = time.perf_counter() - t0
    stop.set()
    prefetcher.close()
    for p in producers:
      p.join(timeout=2)
    row = {'unrolls_per_sec': round(n * batch_size / dt, 1)}
    if tracer is not None:
      row['tracer'] = tracer.stats()
      telemetry.set_tracer(None)
      tracer.close()
    shutil.rmtree(tmpdir, ignore_errors=True)
    return row

  results['feed_trace_off'] = run_feed(False)
  results['feed_trace_on'] = run_feed(True)
  on = results['feed_trace_on']['unrolls_per_sec']
  off = results['feed_trace_off']['unrolls_per_sec']
  results['overhead_fraction'] = (round(1.0 - on / off, 4)
                                  if off else None)
  return results


def bench_slo(smoke):
  """SLO-engine overhead (round 14; docs/PERF.md r12): the cost of
  judging every run continuously, measured so the default is an
  accept/reject call with numbers. Three rows:

  a) evaluator tick: one SloEvaluator.observe over a registry-scale
     snapshot (default objective set + ~50 synthetic metric names),
     µs/tick — the per-cadence cost the engine thread pays;
  b) verdict: SloEvaluator.verdict() µs (the finalize-path cost);
  c) profiler-capture overhead: a tiny jitted step loop timed bare vs
     wrapped in a bounded jax.profiler trace (what a triggered
     page-capture costs the K steps it covers), plus the trace write
     wall time.
  """
  import shutil
  import tempfile
  import jax
  import jax.numpy as jnp
  from scalable_agent_tpu import slo as slo_lib
  from scalable_agent_tpu import telemetry

  results = {}
  objectives = slo_lib.load_objectives()
  results['objectives'] = len(objectives)

  # --- (a) evaluator tick over a registry-scale snapshot. ---
  reg = telemetry.MetricsRegistry()
  for i in range(40):
    c = reg.counter(f'bench/slo_c{i}')
    c.inc(i)
  h = reg.histogram('trace/policy_lag')
  h2 = reg.histogram('trace/e2e_ms')
  for i in range(512):
    h.observe(i % 7)
    h2.observe(50.0 + i % 31)
  g = reg.gauge('driver/env_plane_utilization')
  g.set(0.8)
  crc = reg.counter('ingest/wire_crc_rejected')
  evaluator = slo_lib.SloEvaluator(objectives, min_samples=2)
  n = 2_000 if not smoke else 200
  t_base = time.time()
  t0 = time.perf_counter()
  for i in range(n):
    crc.inc(0)  # snapshot stays cheap-but-live
    evaluator.observe(reg.snapshot(), now=t_base + i * 0.5)
  dt = time.perf_counter() - t0
  results['evaluator_tick_us'] = round(dt / n * 1e6, 2)

  # --- (b) verdict cost. ---
  n = 2_000 if not smoke else 200
  t0 = time.perf_counter()
  for _ in range(n):
    evaluator.verdict()
  dt = time.perf_counter() - t0
  results['verdict_us'] = round(dt / n * 1e6, 2)

  # --- (c) profiler-capture overhead around a tiny jitted loop. ---
  steps = 20 if not smoke else 6
  x = jnp.ones((256, 256), jnp.float32)

  @jax.jit
  def step(x):
    return jnp.tanh(x @ x) * 0.5

  def run_steps():
    y = x
    t0 = time.perf_counter()
    for _ in range(steps):
      y = step(y)
    jax.block_until_ready(y)
    return time.perf_counter() - t0

  run_steps()  # compile
  bare = min(run_steps() for _ in range(3))
  tmpdir = tempfile.mkdtemp(prefix='bench_slo_prof_')
  t0 = time.perf_counter()
  jax.profiler.start_trace(tmpdir)
  traced = run_steps()
  jax.profiler.stop_trace()
  capture_wall = time.perf_counter() - t0
  shutil.rmtree(tmpdir, ignore_errors=True)
  results['profiled_steps'] = steps
  results['bare_steps_ms'] = round(bare * 1e3, 3)
  results['traced_steps_ms'] = round(traced * 1e3, 3)
  results['capture_wall_ms'] = round(capture_wall * 1e3, 3)
  results['capture_overhead_fraction'] = (
      round(traced / bare - 1.0, 4) if bare > 0 else None)
  return results


def bench_controller(smoke):
  """Self-healing-controller overhead (round 15; controller.py): the
  cost of the verdict-to-actuation loop, measured so the default
  observe-mode thread is an accept/reject call with numbers. Rows:

  a) idle tick: one Controller.tick over a healthy snapshot with the
     default rule set — the steady-state cost the controller thread
     pays every interval (nothing burning, nothing engaged);
  b) acting tick: the same tick while a rule is escalating — includes
     the actuator set, the CONTROLLER_LOG.json rewrite, and the
     incident emission (paid only when a knob actually moves);
  c) full escalate->revert cycle wall time through a real SloEngine
     snapshot path (the engine lock + deep copy included).
  """
  import shutil
  from scalable_agent_tpu import controller as controller_lib
  from scalable_agent_tpu import slo as slo_lib
  from scalable_agent_tpu import telemetry

  results = {}
  tmpdir = tempfile.mkdtemp(prefix='bench_ctrl_')

  class _Engine:
    def __init__(self, snap):
      self.snap = snap

    def control_snapshot(self):
      return {n: dict(e) for n, e in self.snap.items()}

  def _entry(state, margin):
    return {'state': state, 'margin': margin, 'value': margin,
            'severity': 'page', 'target': 1.0, 'burns': 0}

  rules = controller_lib.load_rules()
  results['rules'] = len(rules)
  healthy = {r.objective: _entry(slo_lib.OK, 10.0) for r in rules}
  knobs = {'replay_k': 1, 'admission': 'block', 'publish_secs': 2.0,
           'fleet_size': 4}

  def _actuators():
    acts = []
    for name, lo, hi in (('replay_k', 1, 4), ('publish_secs', 2.0,
                                              30.0),
                         ('fleet_size', 1, 64)):
      acts.append(controller_lib.Actuator(
          name, kind='float' if name == 'publish_secs' else 'int',
          get_fn=lambda n=name: knobs[n],
          set_fn=lambda v, n=name: knobs.__setitem__(n, v),
          minimum=lo, maximum=hi))
    acts.append(controller_lib.Actuator(
        'admission', kind='enum',
        get_fn=lambda: knobs['admission'],
        set_fn=lambda v: knobs.__setitem__('admission', v),
        values=('block', 'shed', 'grow')))
    return acts

  # --- (a) idle tick over the default table. ---
  engine = _Engine(healthy)
  ctrl = controller_lib.Controller(engine, rules, _actuators(),
                                   tmpdir, mode='act',
                                   interval_secs=3600.0)
  n = 20_000 if not smoke else 1_000
  t0 = time.perf_counter()
  for i in range(n):
    ctrl.tick(now=float(i))
  dt = time.perf_counter() - t0
  results['idle_tick_us'] = round(dt / n * 1e6, 2)
  ctrl.stop()

  # --- (b) acting tick: one rule escalating every tick (cooldown 0,
  # bounded knob reset each round so a set really happens). ---
  burning = dict(healthy)
  burning['fleet_healthy_fraction'] = _entry(slo_lib.BURNING, -0.5)
  hot_rule = controller_lib.Rule(
      objective='fleet_healthy_fraction', actuator='fleet_size',
      direction='up', step=1, cooldown_secs=0.0, clear_margin=0.5)
  ctrl = controller_lib.Controller(_Engine(burning), [hot_rule],
                                   _actuators(), tmpdir, mode='act',
                                   interval_secs=3600.0)
  n = 300 if not smoke else 50
  t0 = time.perf_counter()
  for i in range(n):
    knobs['fleet_size'] = 4
    ctrl.tick(now=float(i))
  dt = time.perf_counter() - t0
  results['acting_tick_us'] = round(dt / n * 1e6, 2)
  ctrl.stop()

  # --- (c) escalate->revert cycle through a REAL SloEngine. ---
  reg = telemetry.MetricsRegistry()
  gauge = reg.gauge('driver/fleet_healthy_fraction')
  gauge.set(1.0)
  objective = slo_lib.Objective(
      name='fleet_healthy_fraction',
      metric='driver/fleet_healthy_fraction', comparison='>=',
      target=0.6, severity='page', fast_window_secs=2.0,
      slow_window_secs=8.0)
  engine2 = slo_lib.SloEngine([objective], tmpdir, registry=reg,
                              capture=False, min_samples=2)
  cycle_rule = controller_lib.Rule(
      objective='fleet_healthy_fraction', actuator='fleet_size',
      direction='up', step=1, trigger_margin=0.2, clear_margin=0.3,
      cooldown_secs=0.0)
  knobs['fleet_size'] = 4
  ctrl = controller_lib.Controller(engine2, [cycle_rule],
                                   _actuators(), tmpdir, mode='act',
                                   interval_secs=3600.0)
  t0 = time.perf_counter()
  now = 1000.0
  gauge.set(0.5)
  for _ in range(4):
    now += 1.0
    engine2.observe(now=now)
  actions = ctrl.tick(now=now)
  gauge.set(1.0)
  for _ in range(4):
    now += 1.0
    engine2.observe(now=now)
  actions += ctrl.tick(now=now)
  results['cycle_wall_ms'] = round((time.perf_counter() - t0) * 1e3,
                                   3)
  results['cycle_actions'] = len(actions)
  ctrl.stop()
  engine2.stop()
  shutil.rmtree(tmpdir, ignore_errors=True)
  return results


def _multihost_child_main():
  """Child body of the multihost stage: one process of the 2-process
  jax.distributed drill (or the 1-process reference when
  BENCH_MH_NPROCS=1 — then no distributed runtime at all, the true
  single-controller baseline). Runs the REAL driver.train and reports
  the steady-state env-frames/sec (median of the back half of the
  summary stream's fps curve, so compile time and ramp-up don't
  pollute the row)."""
  proc = int(os.environ['BENCH_MH_PROC'])
  nprocs = int(os.environ['BENCH_MH_NPROCS'])
  steps = int(os.environ['BENCH_MH_STEPS'])
  batch_per = int(os.environ['BENCH_MH_BATCH_PER'])
  logdir = os.environ['BENCH_MH_DIR']
  os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'
  import jax
  jax.config.update('jax_platforms', 'cpu')
  if nprocs > 1:
    from scalable_agent_tpu.parallel import distributed
    distributed.initialize(
        f"localhost:{os.environ['BENCH_MH_PORT']}",
        num_processes=nprocs, process_id=proc,
        heartbeat_timeout_secs=8)
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.config import Config
  cfg = Config(
      logdir=logdir, env_backend='bandit', level_name='bandit',
      num_actors=2, batch_size=batch_per * nprocs,
      unroll_length=10, num_action_repeats=1, episode_length=8,
      height=24, width=32, torso='shallow', use_py_process=False,
      use_instruction=False, total_environment_frames=10**9,
      inference_timeout_ms=5, checkpoint_secs=600, summary_secs=0,
      seed=5)
  run = driver.train(cfg, max_steps=steps, stall_timeout_secs=120)
  assert int(run.state.update_steps) == steps
  fname = ('summaries.jsonl' if proc == 0
           else f'summaries_p{proc}.jsonl')
  fps = []
  with open(os.path.join(logdir, fname)) as f:
    for line in f:
      event = json.loads(line)
      if event['tag'] == 'env_frames_per_sec' and event['value'] > 0:
        fps.append(event['value'])
  back = fps[len(fps) // 2:] or [0.0]
  back.sort()
  print(f'BENCH_MH proc={proc} fps={back[len(back) // 2]:.1f}',
        flush=True)


def bench_multihost(smoke):
  """The multi-process runtime (round 17): per-process fps through the
  REAL spin-up path (distributed.initialize with gloo collectives,
  per-host fleets feeding process-local shards, the cross-process
  gradient psum) vs the single-process row at the SAME per-process
  shape — `scaling_fraction` = multihost global fps / (nprocs x the
  single-process fps), the weak-scaling headline ROADMAP item 1 asks
  for as "a recorded number instead of a hope".

  This host runs the drill as 2 OS processes x 1 virtual CPU device
  (the mechanism and its overheads: gloo collectives, coordination
  heartbeats, per-host summary streams). Real pod rows come from
  running bench on the pod itself with the coordinator flags —
  recorded in docs/PERF.md when chip artifacts land."""
  import socket
  import subprocess
  import sys
  nprocs = 2
  steps = 20 if smoke else 120
  batch_per = 4

  def run_topology(n):
    tmpdir = tempfile.mkdtemp(prefix=f'bench_mh_{n}proc_')
    sock = socket.socket()
    sock.bind(('localhost', 0))
    port = sock.getsockname()[1]
    sock.close()
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env.update(BENCH_MH_CHILD='1', BENCH_MH_NPROCS=str(n),
               BENCH_MH_PORT=str(port), BENCH_MH_DIR=tmpdir,
               BENCH_MH_STEPS=str(steps),
               BENCH_MH_BATCH_PER=str(batch_per))
    import shutil
    procs = []
    fps = {}
    try:
      for i in range(n):
        env_i = dict(env, BENCH_MH_PROC=str(i))
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env_i, text=True))
      for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=600)
        assert p.returncode == 0, (
            f'multihost bench child {i}/{n} failed:\n{out[-2000:]}')
        for line in out.splitlines():
          if line.startswith('BENCH_MH '):
            parts = dict(kv.split('=') for kv in line.split()[1:])
            fps[int(parts['proc'])] = float(parts['fps'])
    finally:
      # One child failing (or timing out) must not orphan its
      # siblings holding CPU and the coordinator port, nor leak the
      # scratch dir.
      for p in procs:
        if p.poll() is None:
          p.kill()
        p.communicate()
      shutil.rmtree(tmpdir, ignore_errors=True)
    return fps

  single = run_topology(1)[0]
  multi = run_topology(nprocs)
  # Every process reports the GLOBAL frame rate (frames_per_step is
  # the global batch); the honest aggregate is the minimum — the
  # slowest host paces the collective step.
  mh_fps = min(multi.values())
  results = {
      'nprocs': nprocs,
      'steps': steps,
      'global_batch': batch_per * nprocs,
      'single_1proc': {'env_frames_per_sec': round(single, 1)},
      f'multihost_{nprocs}proc': {
          'env_frames_per_sec': round(mh_fps, 1),
          'per_process': round(mh_fps / nprocs, 1),
          'per_process_fps': {str(k): round(v, 1)
                              for k, v in multi.items()},
      },
      # Weak scaling: n processes each carry the single-process
      # per-host load; 1.0 = the runtime added zero overhead.
      'scaling_fraction': (round(mh_fps / (nprocs * single), 3)
                           if single > 0 else None),
  }
  return results


def bench_mesh2d(smoke):
  """The 2D {data, model} mesh vs pure-DP at the SAME global batch
  (round 19, parallel/sharding.py): what does cutting the params over
  the model axis buy, and what does it cost?

  Two rows through the PRODUCTION sharded path (registry-resolved
  placements, make_sharded_train_step — the exact code the driver
  runs):

  - `dp` — mesh {data: N}, `sharding_rules` resolves to 'replicated';
  - `mesh2d` — mesh {data: N/2, model: 2}, rules 'megatron' (TP on
    Dense/LSTM-gate/Conv kernels).

  Per row: measured `step_ms` (value-readback barrier), and the
  per-device memory split the registry's placements actually produce —
  `state_bytes_per_device` (params + optimizer moments, summed from
  the live state's addressable shards: the at-rest HBM story TP
  exists for) and `batch_bytes_per_device` — plus XLA's static
  `live_bytes_per_device` from the AOT memory analysis of the same
  step under the same shardings (parallel/fit.py's instrument) when
  the backend exposes it.

  Headline: `state_bytes_ratio` (mesh2d/dp, ≈0.5 + replicated-head
  remainder when the cut engages) and both step_ms. CPU rows carry
  the gathered-TP caveat: tp_compute=auto resolves 'gathered' there
  (docs/PARALLELISM.md), so mesh2d step_ms prices gather → replicated
  compute → scatter, NOT true sharded TP compute — per-device step
  time is a TPU question, the memory split is exact everywhere."""
  import numpy as np  # noqa: F401  (parity with sibling stages)
  import jax
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  from scalable_agent_tpu.parallel import sharding as sharding_lib
  from scalable_agent_tpu.parallel import train_parallel
  from scalable_agent_tpu.testing import make_example_batch

  h, w = (72, 96) if not smoke else (24, 32)
  b = 32 if not smoke else 8
  t = 20 if not smoke else 4
  steps = 10 if not smoke else 2
  torso = 'deep' if not smoke else 'shallow'
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}

  def run_variant(mp):
    cfg = Config(batch_size=b, unroll_length=t, num_action_repeats=1,
                 total_environment_frames=int(1e9),
                 model_parallelism=mp, sharding_rules='auto',
                 torso=torso, use_instruction=False)
    agent = ImpalaAgent(num_actions=9, torso=torso,
                        use_instruction=False)
    params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
    mesh = mesh_lib.make_mesh(model_parallelism=mp)
    registry = sharding_lib.from_config(cfg)
    state = train_parallel.make_sharded_train_state(
        params, cfg, mesh, registry=registry)
    batch = make_example_batch(t + 1, b, h, w, 9, obs_spec['instr_len'],
                               seed=0, done_prob=0.05)
    step, place = train_parallel.make_sharded_train_step(
        agent, cfg, mesh, batch)
    placed = place(batch)

    def bytes_per_device(tree):
      return int(sum(
          x.addressable_shards[0].data.nbytes
          for x in jax.tree_util.tree_leaves(tree)
          if isinstance(x, jax.Array)))

    state_bytes = bytes_per_device(state)
    batch_bytes = bytes_per_device(placed)

    # Static per-device live bytes of the SAME step under the SAME
    # registry shardings (the fit.py instrument; donation off — the
    # jaxlib TP donation defect xfail'd in tests/test_parallel.py).
    live_bytes = None
    try:
      raw_step = learner_lib.make_train_step_fn(agent, cfg, mesh=mesh)
      state_sh = registry.state_shardings(state, mesh)
      batch_sh = registry.batch_shardings(batch, mesh)
      ma = jax.jit(
          raw_step, in_shardings=(state_sh, batch_sh),
          out_shardings=(state_sh, sharding_lib.replicated(mesh)),
      ).lower(state, placed).compile().memory_analysis()
      live_bytes = int(ma.argument_size_in_bytes +
                       ma.output_size_in_bytes +
                       ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    except Exception as e:  # backend without memory_analysis
      log_note = f'memory_analysis unavailable: {e}'
      live_bytes = None
      del log_note

    state, metrics = step(state, placed)  # warm/compile
    float(metrics['total_loss'])
    t0 = time.perf_counter()
    for _ in range(steps):
      state, metrics = step(state, placed)
    float(metrics['total_loss'])
    step_ms = (time.perf_counter() - t0) / steps * 1e3

    model_cut = any(
        sharding_lib.MODEL_AXIS in str(x.sharding.spec)
        for x in jax.tree_util.tree_leaves(state.params))
    return {
        'mesh': {k: int(v) for k, v in dict(mesh.shape).items()},
        'rule_set': registry.rule_set,
        'global_batch': b,
        'step_ms': round(step_ms, 2),
        'state_bytes_per_device': state_bytes,
        'batch_bytes_per_device': batch_bytes,
        'live_bytes_per_device': live_bytes,
        'model_sharded': bool(model_cut),
        'tp_gathered': bool(getattr(step, 'tp_gathered', False)),
    }

  dp = run_variant(1)
  mesh2d = run_variant(2)
  ratio = (round(mesh2d['state_bytes_per_device'] /
                 dp['state_bytes_per_device'], 3)
           if dp['state_bytes_per_device'] else None)
  return {
      'dp': dp,
      'mesh2d': mesh2d,
      # The memory headline: TP's reason to exist at IMPALA scale.
      'state_bytes_ratio': ratio,
      'step_ms_ratio': (round(mesh2d['step_ms'] / dp['step_ms'], 3)
                        if dp['step_ms'] else None),
  }


def bench_serving(smoke):
  """The multi-tenant serving-plane instrument (round 21): price every
  lever the serving PR added, so its defaults are accepted/rejected on
  measurement (the repo's discipline).

  Rows:
  - codec: wire bytes f32/bf16/int8 (the publish fan-out payload),
    int8 quantize/dequantize round-trip error, and the PARITY GATE —
    greedy action agreement between fp32 serving and int8-resident
    serving on identical inputs + identical RNG (the gate the int8
    default flip will be judged by).
  - publish blackout: update_params wall time per codec — int8 pays
    an on-device quantize per publish; the row says what that costs.
  - resident versions: an N=3-resident server under A/B traffic —
    per-version serve counters prove ≥2 versions SERVED (not merely
    stored).
  - shadow: divergence gauge ~0.0 when the shadow IS the live params,
    > 0 when the shadow is a different network (sanity both ways — a
    gauge that can't move is not a gauge).
  - version-flip blackout: first policy call after an int8 publish,
    AOT-cold vs AOT-warm. The quantized tree changes leaf dtypes, so
    the cold flip pays a full retrace ON the serve path; serving_aot
    pre-compiles at publish time and the flip serves warm.
  - routed: ServingRouter over two in-process replicas (channel =
    serve_remote, no sockets — prices the ROUTER, not the wire), plus
    a kill-one failover check.
  """
  import numpy as np
  import jax
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.runtime import codec
  from scalable_agent_tpu.runtime.inference import (InferenceServer,
                                                    percentile_ms)
  from scalable_agent_tpu.runtime.routing import ServingRouter
  from scalable_agent_tpu.structs import StepOutput, StepOutputInfo

  h, w = (72, 96) if not smoke else (24, 32)
  torso = 'deep' if not smoke else 'shallow'
  reps = 200 if not smoke else 30
  batch = 8 if not smoke else 4
  num_actions = 9
  obs_spec = {'frame': (h, w, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  agent = ImpalaAgent(num_actions=num_actions, torso=torso,
                      use_instruction=False)
  params = init_params(agent, jax.random.PRNGKey(0), obs_spec)
  params_b = init_params(agent, jax.random.PRNGKey(1), obs_spec)
  rng = np.random.RandomState(0)

  def payload(server, b=batch):
    sizes = [int(np.shape(c)[-1])
             for c in server.initial_core_state()]
    return {
        'prev_action': rng.randint(0, num_actions, (b,)).astype(np.int32),
        'reward': np.zeros((b,), np.float32),
        'done': np.zeros((b,), np.bool_),
        'frame': rng.randint(0, 255, (b, h, w, 3)).astype(np.uint8),
        'instr': np.zeros((b, MAX_INSTRUCTION_LEN), np.int32),
        'core_c': np.zeros((b, sizes[0]), np.float32),
        'core_h': np.zeros((b, sizes[1]), np.float32),
    }

  def make_server(**over):
    cfg = Config(inference_min_batch=0, inference_max_batch=max(16, batch),
                 inference_timeout_ms=5, inference_state_cache=False,
                 **over)
    return InferenceServer(agent, params, cfg, seed=7, pad_batch_to=1,
                           fleet_size=1)

  results = {}

  # --- codec rows: wire bytes, round-trip error, publish blackout.
  f32_b, bf16_b, int8_b = codec.wire_sizes(jax.device_get(params))
  q = codec.quantize_np(jax.device_get(params))
  results['wire_bytes'] = {
      'f32': f32_b, 'bf16': bf16_b, 'int8': int8_b,
      'int8_vs_f32': round(int8_b / f32_b, 3),
      'int8_vs_bf16': round(int8_b / bf16_b, 3),
      'roundtrip_max_abs_err': float(codec.max_abs_error(q)),
  }

  def publish_blackout(codec_name):
    server = make_server(publish_codec=codec_name)
    times = []
    for k in range(8 if smoke else 32):
      fresh = jax.tree_util.tree_map(lambda a: a + 0, params)
      t0 = time.perf_counter()
      server.update_params(fresh, version=k + 1)
      times.append(time.perf_counter() - t0)
    server.close()
    return {'p50_ms': round(percentile_ms(sorted(times), 0.5, 1e3), 2),
            'p99_ms': round(percentile_ms(sorted(times), 0.99, 1e3), 2)}

  results['publish_blackout'] = {name: publish_blackout(name)
                                 for name in ('f32', 'int8')}

  # --- parity gate: fp32 vs int8-resident serving, same inputs, same
  # per-call RNG (both servers fold the same dedicated base key).
  s_f32 = make_server()
  s_int8 = make_server(publish_codec='int8')
  s_f32.update_params(params, version=1)
  s_int8.update_params(params, version=1)
  pay = payload(s_f32)
  out_a = s_f32.serve_remote(pay)
  out_b = s_int8.serve_remote(pay)
  results['int8_parity'] = {
      'greedy_agreement': round(float(codec.greedy_agreement(
          out_a['logits'], out_b['logits'])), 4),
      'logits_max_abs_err': float(np.max(np.abs(
          out_a['logits'] - out_b['logits']))),
  }

  # --- routed: two in-process replicas; price the router itself.
  class _LocalChannel:
    def __init__(self, server):
      self._server = server
      self.dead = False

    def supports_infer(self):
      return True

    def remote_infer(self, req):
      if self.dead:
        raise ConnectionError('replica killed')
      return self._server.serve_remote(req), {}

    def close(self):
      pass

  channels = {'a:0': _LocalChannel(s_f32), 'b:0': _LocalChannel(s_int8)}
  router = ServingRouter(['a:0', 'b:0'],
                         connect_fn=lambda addr: channels[addr])
  direct = []
  for _ in range(reps):
    t0 = time.perf_counter()
    s_f32.serve_remote(pay)
    direct.append(time.perf_counter() - t0)
  routed = []
  for _ in range(reps):
    t0 = time.perf_counter()
    router.infer(pay)
    routed.append(time.perf_counter() - t0)
  channels['a:0'].dead = True
  # Several requests so the rotation is GUARANTEED to pick the dead
  # replica at least once — the row must price the failover path, not
  # a lucky pick of the survivor.
  survived = all(router.infer(pay) is not None for _ in range(4))
  rstats = router.stats()
  router.close()
  results['routed'] = {
      'direct_p50_ms': round(percentile_ms(sorted(direct), 0.5, 1e3), 3),
      'routed_p50_ms': round(percentile_ms(sorted(routed), 0.5, 1e3), 3),
      'failover_survived': bool(survived),
      'route_failovers': rstats['route_failovers'],
      'serves': {r['address']: r['serves'] for r in rstats['replicas']},
  }
  s_f32.close()
  s_int8.close()

  # --- resident versions under A/B + shadow traffic.
  server = make_server(serving_resident_versions=3,
                       serving_ab_fraction=0.25,
                       serving_shadow_fraction=1.0)
  server.update_params(params, version=1)
  server.update_params(jax.tree_util.tree_map(lambda a: a + 0, params),
                       version=2)  # live v2, shadow auto = v1 (equal)
  frame = rng.randint(0, 255, (h, w, 3)).astype(np.uint8)
  instr = np.zeros((MAX_INSTRUCTION_LEN,), np.int32)

  def drive_policy(n):
    state = server.initial_core_state()
    prev = np.int32(0)
    for step in range(n):
      env_out = StepOutput(
          reward=np.float32(0.0),
          info=StepOutputInfo(np.float32(0), np.int32(0)),
          done=np.bool_(False),
          observation=(frame, instr))
      out, state = server.policy(prev, env_out, state)
      prev = np.int32(out.action)

  drive_policy(reps)
  div_equal = server.stats()['shadow_divergence']
  server.update_params(params_b, version=3)  # live v3, shadow = v2
  drive_policy(reps)
  snap = server.stats()
  results['resident'] = {
      'resident_versions': snap['resident_versions'],
      'live_version': snap['live_version'],
      'serve_counts': snap['serve_counts'],
      'ab_calls': snap['ab_calls'],
      'shadow_calls': snap['shadow_calls'],
      'shadow_divergence_equal': div_equal,
      'shadow_divergence_different': snap['shadow_divergence'],
  }
  server.close()

  # --- version-flip blackout: int8 publish flips the resident leaf
  # dtypes; cold pays the retrace on the first serve, warm (AOT
  # pre-compile at publish) does not.
  def flip_blackout(aot):
    server = make_server(publish_codec='int8', serving_aot=aot)
    server.warmup(obs_spec, sizes=[1])
    times = []
    for k in range(3):
      server.update_params(
          jax.tree_util.tree_map(lambda a: a + 0, params_b),
          version=k + 1)
      t0 = time.perf_counter()
      state = server.initial_core_state()
      env_out = StepOutput(
          reward=np.float32(0.0),
          info=StepOutputInfo(np.float32(0), np.int32(0)),
          done=np.bool_(False),
          observation=(frame, instr))
      server.policy(np.int32(0), env_out, state)
      times.append((time.perf_counter() - t0) * 1e3)
    stats = server.stats()
    server.close()
    return {'first_flip_ms': round(times[0], 2),
            'steady_p99_ms': round(max(times[1:]), 2),
            'aot_misses': stats['aot_misses'],
            'aot_compiled': stats['aot_compiled']}

  results['flip_blackout'] = {'cold': flip_blackout(False),
                              'warm': flip_blackout(True)}
  return results


def bench_population(smoke):
  """The population engine (round 22; population.py, docs/PERF.md
  r22). Two measured claims:

  1. Curriculum tax: the SAME fused Anakin procgen run with
     --curriculum=uniform vs --curriculum=regret — the prioritized
     sampler, per-level EMA fold, and score-table carry all live
     INSIDE the jitted step (zero host round trips per level
     decision), so the acceptance gate is fps within 5% of uniform.
     The regret row also reports the per-level telemetry (entropy,
     levels visited) so the row shows the curriculum actually DROVE
     the distribution, not just cost nothing.
  2. Padding waste: a mixed-suite request stream (16x16 cue-scale
     frames + 24x32 gridworld-scale frames, 2:1) through the REAL
     C++ batcher behind ops/dynamic_batching.FamilyBatcher —
     per-obs-spec-family queues merge rows at their exact shape, so
     padded bytes == useful bytes; the reported waste_ratio is what
     the SAME stream would have paid under naive pad-to-fleet-max
     (the measured elimination claim).
  """
  import numpy as np
  import jax
  from scalable_agent_tpu.config import Config
  from scalable_agent_tpu.ops import dynamic_batching
  from scalable_agent_tpu.parallel import anakin
  from scalable_agent_tpu.parallel import mesh as mesh_lib

  n_dev = len(jax.devices())
  steps = 200 if not smoke else 3
  t = 20 if not smoke else 3
  b = 256 if not smoke else 8
  b = max(b - b % n_dev, n_dev)
  mesh = mesh_lib.make_mesh() if n_dev > 1 else None
  out = {'devices': n_dev,
         'config': 'procgen, shallow, 24x32, T=%d, B=%d, %d step(s)'
                   % (t, b, steps)}

  for mode in ('uniform', 'regret'):
    cfg = Config(env_backend='procgen', batch_size=b,
                 unroll_length=t, num_action_repeats=1,
                 episode_length=12, height=24, width=32,
                 torso='shallow',
                 compute_dtype='bfloat16' if not smoke else 'float32',
                 use_instruction=False, use_py_process=False,
                 learning_rate=2e-3, entropy_cost=3e-3,
                 discounting=0.9, total_environment_frames=10**9,
                 curriculum=mode, procgen_num_levels=8, seed=0)
    _, history, fps = anakin.run(cfg, steps, mesh=mesh)
    row = {'env_frames_per_sec': round(fps, 1), 'batch_size': b}
    if mode != 'uniform':
      last = history[-1]
      row.update({
          'curriculum_entropy': round(
              float(last['curriculum_entropy']), 3),
          'levels_visited': int(last['curriculum_levels_visited']),
          'score_max': round(float(last['curriculum_score_max']), 4),
      })
    out[mode] = row
  overhead = 1.0 - (out['regret']['env_frames_per_sec'] /
                    max(out['uniform']['env_frames_per_sec'], 1e-9))
  out['curriculum_overhead_fraction'] = round(overhead, 4)
  out['curriculum_gate'] = {'threshold': 0.05,
                            'pass': bool(overhead <= 0.05)}

  # --- Mixed-suite padding waste through the real batcher. ---
  def make_fn(key):
    def handler(*arrays):
      # Row-wise reduce: enough work to exercise the padded staging
      # without turning the row into a compute bench.
      return [np.ascontiguousarray(
          arrays[0].reshape(arrays[0].shape[0], -1).sum(-1))]
    return handler

  fb = dynamic_batching.FamilyBatcher(
      make_fn, minimum_batch_size=1, maximum_batch_size=256,
      timeout_ms=2)
  small = np.zeros((1, 16, 16, 3), np.uint8)
  large = np.zeros((1, 24, 32, 3), np.uint8)
  requests = 600 if not smoke else 60
  workers = 6
  errors = []

  def pump(worker):
    try:
      for i in range(requests // workers):
        # 2:1 small:large — the heterogeneous composition a mixed
        # cue+gridworld fleet produces.
        fb(small if (worker + i) % 3 else large)
    except Exception as exc:  # pragma: no cover - surfaced below
      errors.append(exc)

  threads = [threading.Thread(target=pump, args=(w,))
             for w in range(workers)]
  start = time.perf_counter()
  for th in threads:
    th.start()
  for th in threads:
    th.join()
  elapsed = time.perf_counter() - start
  stats = fb.padding_stats()
  fb.close()
  if errors:
    raise errors[0]
  out['padding'] = {
      'requests': requests,
      'families': int(stats['families']),
      'rows_per_sec': round(stats['rows'] / max(elapsed, 1e-9), 1),
      'useful_bytes': stats['useful_bytes'],
      'bucketed_bytes': stats['bucketed_bytes'],
      'max_shape_bytes': stats['max_shape_bytes'],
      'waste_ratio': round(stats['waste_ratio'], 4),
  }

  # --- Round 23: fused (vmapped) population vs serial round-robin.
  # N single-device members at IDENTICAL per-member shapes. The
  # serial side pays what the r22 population loop pays every round:
  # a fresh make_anakin_step trace + spin-up per member, members
  # stepped one after another. The fused side builds ONE vmapped
  # program and advances all members in lockstep. Wall INCLUDES
  # trace/compile on both sides — amortizing N traces into one IS
  # the claim (docs/PERF.md r23; gate: >= 2x aggregate fps). ---
  import dataclasses as _dc
  import jax.numpy as jnp
  from scalable_agent_tpu import driver as driver_lib

  n_members = 4
  psteps = 40 if not smoke else 3
  pcfg = Config(env_backend='bandit',
                batch_size=16 if not smoke else 4,
                unroll_length=10 if not smoke else 5,
                num_action_repeats=1, episode_length=5,
                torso='shallow', use_instruction=False,
                use_py_process=False, learning_rate=2e-3,
                entropy_cost=3e-3, discounting=0.9,
                total_environment_frames=10**9, seed=0)
  member_frames = psteps * pcfg.frames_per_step

  start = time.perf_counter()
  for k in range(n_members):
    anakin.run(_dc.replace(pcfg, seed=pcfg.seed + 101 * k + 1),
               psteps)
  serial_wall = time.perf_counter() - start
  serial_fps = n_members * member_frames / max(serial_wall, 1e-9)

  start = time.perf_counter()
  env_core = anakin.make_env_core(pcfg)
  agent = driver_lib.build_agent(pcfg, env_core.num_actions)
  vstep = anakin.make_vectorized_anakin_step(agent, env_core, pcfg)
  stacked = anakin.init_stacked_carry(
      agent, env_core, pcfg,
      [pcfg.seed + 101 * k + 1 for k in range(n_members)])
  hyp = {'learning_rate': jnp.full((n_members,), pcfg.learning_rate,
                                   jnp.float32),
         'entropy_cost': jnp.full((n_members,), pcfg.entropy_cost,
                                  jnp.float32)}
  metrics = None
  for _ in range(psteps):
    stacked, metrics = vstep(stacked, hyp)
  jax.block_until_ready(metrics['total_loss'])
  fused_wall = time.perf_counter() - start
  fused_fps = n_members * member_frames / max(fused_wall, 1e-9)
  speedup = fused_fps / max(serial_fps, 1e-9)
  out['fused_population'] = {
      'members': n_members, 'steps_per_member': psteps,
      'member_config': 'bandit, shallow, T=%d, B=%d'
                       % (pcfg.unroll_length, pcfg.batch_size),
      'serial_wall_secs': round(serial_wall, 3),
      'serial_env_frames_per_sec': round(serial_fps, 1),
      'fused_wall_secs': round(fused_wall, 3),
      'fused_env_frames_per_sec': round(fused_fps, 1),
      'speedup': round(speedup, 2),
      'gate': {'threshold': 2.0, 'pass': bool(speedup >= 2.0)},
  }
  return out


def main():
  # Child half of the multihost stage: a fresh interpreter dispatched
  # by bench_multihost — must run before any jax/backend setup below.
  if os.environ.get('BENCH_MH_CHILD'):
    _multihost_child_main()
    return
  # BENCH_SMOKE=1: tiny shapes on CPU — validates bench mechanics in CI
  # without the chip. The driver runs the real thing (no env var, TPU).
  smoke = os.environ.get('BENCH_SMOKE') == '1'
  if smoke:
    import jax
    jax.config.update('jax_platforms', 'cpu')

  # BENCH_ONLY=learner_plane: run just the learner-feed stage (the
  # scripts/ci.sh smoke — same rationale as the inference_plane lane).
  if os.environ.get('BENCH_ONLY') == 'learner_plane':
    plane = bench_learner_plane(smoke)
    _emit({
        'metric': 'learner_plane_exposed_feed_ms_per_step',
        'value': min(row['exposed_feed_ms_per_step']
                     for row in plane.values()
                     if isinstance(row, dict)
                     and 'exposed_feed_ms_per_step' in row),
        'unit': ('exposed feed ms/step, best staging variant%s'
                 % (' (SMOKE)' if smoke else '')),
        'learner_plane': plane,
    })
    return

  # BENCH_ONLY=inference_plane: run just the actor-plane stage (the
  # scripts/ci.sh smoke — the full bench's compile budget doesn't fit
  # a CI lane; the stage's mechanics must still be exercised there).
  if os.environ.get('BENCH_ONLY') == 'inference_plane':
    infer = bench_inference_plane(smoke)
    best = max((row['policy_calls_per_sec']
                for row in infer.values() if isinstance(row, dict)),
               default=0.0)
    _emit({
        'metric': 'inference_plane_policy_calls_per_sec',
        'value': best,
        'unit': ('policy calls/sec, best variant%s'
                 % (' (SMOKE)' if smoke else '')),
        'inference_plane': infer,
    })
    return

  # BENCH_ONLY=replay: just the sample-reuse rows (the scripts/ci.sh
  # smoke — replay_k x ratio mechanics + the cue_memory curve run).
  if os.environ.get('BENCH_ONLY') == 'replay':
    replay = bench_replay(smoke)
    k2 = replay.get('k2_r0') or {}
    _emit({
        'metric': 'replay_k2_reuse_factor',
        'value': k2.get('reuse_factor', 0.0),
        'unit': ('learner updates per env frame vs no-reuse baseline '
                 'at replay_k=2%s' % (' (SMOKE)' if smoke else '')),
        'replay': replay,
    })
    return

  # BENCH_ONLY=anakin: just the runtime-axis rows (the scripts/ci.sh
  # anakin lane — fused-loop fps over the jittable env family, the
  # fed-fleet reference ratio, and the hybrid filler off/on
  # utilization rows).
  if os.environ.get('BENCH_ONLY') == 'anakin':
    anakin_rows = bench_anakin(smoke)
    _emit({
        'metric': 'anakin_env_frames_per_sec',
        'value': (anakin_rows.get('bandit_1dev') or {}).get(
            'env_frames_per_sec'),
        'unit': ('env-frames/sec, fused act+learn, bandit, 1 device%s'
                 % (' (SMOKE)' if smoke else '')),
        'anakin': anakin_rows,
    })
    return

  # BENCH_ONLY=telemetry: just the tracing/registry overhead rows
  # (the scripts/ci.sh telemetry smoke — the on/off accept gate).
  if os.environ.get('BENCH_ONLY') == 'telemetry':
    tele = bench_telemetry(smoke)
    _emit({
        'metric': 'telemetry_overhead_fraction',
        'value': tele.get('overhead_fraction'),
        'unit': ('feed-throughput fraction lost with tracing on%s'
                 % (' (SMOKE)' if smoke else '')),
        'telemetry': tele,
    })
    return

  # BENCH_ONLY=slo: just the SLO-engine overhead rows (the
  # scripts/ci.sh slo lane — evaluator tick + triggered-capture cost).
  if os.environ.get('BENCH_ONLY') == 'slo':
    slo_rows = bench_slo(smoke)
    _emit({
        'metric': 'slo_evaluator_tick_us',
        'value': slo_rows.get('evaluator_tick_us'),
        'unit': ('microseconds per SLO evaluator tick, default '
                 'objective set%s' % (' (SMOKE)' if smoke else '')),
        'slo': slo_rows,
    })
    return

  # BENCH_ONLY=multihost: just the 2-process runtime rows (the
  # scripts/ci.sh multihost lane — per-process fps + the scaling
  # fraction vs the single-process row).
  if os.environ.get('BENCH_ONLY') == 'multihost':
    mh = bench_multihost(smoke)
    _emit({
        'metric': 'multihost_scaling_fraction',
        'value': mh.get('scaling_fraction'),
        'unit': ('multihost global fps / (nprocs x single-process '
                 'fps), 2 procs x 1 CPU device%s'
                 % (' (SMOKE)' if smoke else '')),
        'multihost': mh,
    })
    return

  # BENCH_ONLY=controller: just the controller-loop rows (the
  # scripts/ci.sh controller lane — idle/acting tick + cycle cost).
  if os.environ.get('BENCH_ONLY') == 'controller':
    ctrl_rows = bench_controller(smoke)
    _emit({
        'metric': 'controller_idle_tick_us',
        'value': ctrl_rows.get('idle_tick_us'),
        'unit': ('microseconds per idle controller tick, default '
                 'rule table%s' % (' (SMOKE)' if smoke else '')),
        'controller': ctrl_rows,
    })
    return

  # BENCH_ONLY=mesh2d: just the 2D {data, model} mesh rows (the
  # scripts/ci.sh sharding-lane smoke — registry-resolved DP vs
  # DP+TP at the same global batch, step time + per-device bytes).
  if os.environ.get('BENCH_ONLY') == 'mesh2d':
    mesh2d = bench_mesh2d(smoke)
    _emit({
        'metric': 'mesh2d_state_bytes_ratio',
        'value': mesh2d['state_bytes_ratio'],
        'unit': ('per-device state bytes, {data,model} mesh / pure-DP '
                 'mesh, same global batch%s'
                 % (' (SMOKE)' if smoke else '')),
        'mesh2d': mesh2d,
    })
    return

  # BENCH_ONLY=overload: just the overload rows (the scripts/ci.sh
  # chaos-adjacent smoke — shed-rate/tail-latency mechanics on CPU).
  if os.environ.get('BENCH_ONLY') == 'overload':
    overload = bench_overload(smoke)
    worst = max((row['shed_fraction']
                 for row in overload.values() if isinstance(row, dict)),
                default=0.0)
    _emit({
        'metric': 'overload_worst_shed_fraction',
        'value': worst,
        'unit': ('sheds/acquires at 4x slot pressure, shed admission%s'
                 % (' (SMOKE)' if smoke else '')),
        'overload': overload,
    })
    return

  # BENCH_ONLY=population: just the population-engine rows (the
  # scripts/ci.sh population lane — curriculum on/off fused fps with
  # the <=5% gate, and the mixed-suite padding-waste row).
  if os.environ.get('BENCH_ONLY') == 'population':
    pop = bench_population(smoke)
    _emit({
        'metric': 'curriculum_overhead_fraction',
        'value': pop.get('curriculum_overhead_fraction'),
        'unit': ('fused-loop fps fraction lost with the in-graph '
                 'regret curriculum on, gate <= 0.05%s'
                 % (' (SMOKE)' if smoke else '')),
        'population': pop,
    })
    return

  # BENCH_ONLY=serving: just the multi-tenant serving-plane rows (the
  # scripts/ci.sh serving lane — resident versions, int8 parity +
  # wire bytes, flip blackout AOT warm/cold, router overhead).
  if os.environ.get('BENCH_ONLY') == 'serving':
    serving = bench_serving(smoke)
    _emit({
        'metric': 'serving_int8_greedy_agreement',
        'value': serving['int8_parity']['greedy_agreement'],
        'unit': ('argmax action agreement, int8-resident vs fp32 '
                 'serving, identical inputs+RNG%s'
                 % (' (SMOKE)' if smoke else '')),
        'serving': serving,
    })
    return

  rows = bench_synthetic(smoke)
  cfg = rows['config']
  stats = rows['synthetic']
  e2e = None
  e2e_fed = None
  if os.environ.get('BENCH_SKIP_E2E') != '1':
    e2e = bench_e2e(smoke)
    e2e_fed = bench_e2e_fed(smoke)
  transport = None
  if os.environ.get('BENCH_SKIP_TRANSPORT') != '1':
    transport = bench_transport(smoke)
  fanout = None
  if os.environ.get('BENCH_SKIP_FANOUT') != '1':
    fanout = bench_param_fanout(smoke)
  anakin = None
  if os.environ.get('BENCH_SKIP_ANAKIN') != '1':
    anakin = bench_anakin(smoke)
  infer = None
  if os.environ.get('BENCH_SKIP_INFERENCE') != '1':
    infer = bench_inference_plane(smoke)
  overload = None
  if os.environ.get('BENCH_SKIP_OVERLOAD') != '1':
    overload = bench_overload(smoke)
  plane = None
  if os.environ.get('BENCH_SKIP_LEARNER_PLANE') != '1':
    plane = bench_learner_plane(smoke)
  replay = None
  if os.environ.get('BENCH_SKIP_REPLAY') != '1':
    replay = bench_replay(smoke)
  tele = None
  if os.environ.get('BENCH_SKIP_TELEMETRY') != '1':
    tele = bench_telemetry(smoke)
  slo_rows = None
  if os.environ.get('BENCH_SKIP_SLO') != '1':
    slo_rows = bench_slo(smoke)
  ctrl_rows = None
  if os.environ.get('BENCH_SKIP_CONTROLLER') != '1':
    ctrl_rows = bench_controller(smoke)
  mh_rows = None
  if os.environ.get('BENCH_SKIP_MULTIHOST') != '1':
    mh_rows = bench_multihost(smoke)
  mesh2d_rows = None
  if os.environ.get('BENCH_SKIP_MESH2D') != '1':
    mesh2d_rows = bench_mesh2d(smoke)
  serving_rows = None
  if os.environ.get('BENCH_SKIP_SERVING') != '1':
    serving_rows = bench_serving(smoke)
  pop_rows = None
  if os.environ.get('BENCH_SKIP_POPULATION') != '1':
    pop_rows = bench_population(smoke)

  baseline_per_chip = 200_000.0 / 16.0  # north star / v5e-16 chips
  out = {
      'metric': 'learner_env_frames_per_sec_per_chip',
      'value': stats['median'],  # median of ≥3 windows (VERDICT r4 W1)
      'unit': ('env-frames/sec (deep ResNet, T=%d, B=%d, bf16, 1 chip%s)'
               % (cfg.unroll_length, cfg.batch_size,
                  ', SMOKE' if smoke else '')),
      'vs_baseline': round(stats['median'] / baseline_per_chip, 3),
      'synthetic': stats,
  }
  # The per-feature itemization + lever grid (round 6, VERDICT r5
  # weak #3): no_instruction is the plain base; popart_only/pc_only
  # ride it one feature at a time; the headline row doubles as the
  # instruction-only row; pc_levers re-measures the pixel-control
  # fast-path variants head-to-head at the full-feature point.
  for key in ('no_instruction', 'popart_only', 'pc_only',
              'full_feature', 'deep_fast'):
    if rows.get(key) is not None:
      out[key] = rows[key]
      out[f'{key}_fps'] = rows[key]['median']
  if rows.get('pc_levers') is not None:
    out['pc_levers'] = rows['pc_levers']
  if e2e is not None:
    out['e2e'] = e2e
  if e2e_fed is not None:
    out['e2e_fed'] = e2e_fed
  if transport is not None:
    out['transport'] = transport
  if fanout is not None:
    out['param_fanout'] = fanout
  if anakin is not None:
    out['anakin'] = anakin
  if infer is not None:
    out['inference_plane'] = infer
  if overload is not None:
    out['overload'] = overload
  if plane is not None:
    out['learner_plane'] = plane
  if replay is not None:
    out['replay'] = replay
  if tele is not None:
    out['telemetry'] = tele
  if slo_rows is not None:
    out['slo'] = slo_rows
  if ctrl_rows is not None:
    out['controller'] = ctrl_rows
  if mh_rows is not None:
    out['multihost'] = mh_rows
  if mesh2d_rows is not None:
    out['mesh2d'] = mesh2d_rows
  if serving_rows is not None:
    out['serving'] = serving_rows
  if pop_rows is not None:
    out['population'] = pop_rows
  _emit(out)


def _headline(out):
  """The compact last line: the handful of gate numbers a clipped tail
  must still carry (VERDICT r5 weak #1 — the full JSON line got cut
  mid-object by the driver's tail capture)."""
  head = {
      'metric': out['metric'],
      'value': out['value'],
      'vs_baseline': out.get('vs_baseline'),
      'artifact': 'BENCH_OUT.json',
  }
  # The full-feature itemization (round 6): the popart/pc/instruction
  # split must ride the clip-safe last line — BENCH_rN's tail is the
  # round's record and must carry the 20%'s named owners by itself.
  for key in ('no_instruction_fps', 'popart_only_fps', 'pc_only_fps',
              'full_feature_fps', 'deep_fast_fps'):
    if out.get(key) is not None:
      head[key] = out[key]
  levers = out.get('pc_levers')
  if levers:
    head['pc_levers'] = {
        name: stats['median'] for name, stats in levers.items()
        if isinstance(stats, dict) and 'median' in stats}
  fed = out.get('e2e_fed')
  if fed:
    head['e2e_fed_fps'] = fed['fps']
    head['h2d_overlap_fraction'] = fed.get('h2d_overlap_fraction')
    gap = fed.get('gap_itemization') or {}
    head['h2d_exposed_ms'] = gap.get('h2d_exposed_ms')
    # Sample-reuse motivation row (round 10): the measurement that
    # justifies replay (learner idling on an env-bound pipeline) and
    # later judges it — must survive a clipped tail.
    head['learner_updates_per_env_frame'] = fed.get(
        'learner_updates_per_env_frame')
    head['plane_utilization'] = {
        'env': fed.get('env_plane_utilization'),
        'learner': fed.get('learner_plane_utilization')}
  transport = out.get('transport')
  if transport:
    head['ingest_1conn'] = transport['ingest_1conn']['unrolls_per_sec']
    if 'ingest_4conn' in transport:
      head['ingest_4conn'] = (
          transport['ingest_4conn']['unrolls_per_sec'])
  fanout = out.get('param_fanout')
  if fanout:
    for key, value in fanout.items():
      if key.startswith('pump_with_') and value:
        head['pump_contended_unrolls_per_sec'] = (
            value['unrolls_per_sec'])
        head['pump_contended_ack_p99_ms'] = value['ack_p99_ms']
    if fanout.get('pump_alone'):
      head['pump_alone_unrolls_per_sec'] = (
          fanout['pump_alone']['unrolls_per_sec'])
  # The actor-plane itemization (round 7): the cache×pipeline call
  # — calls/s + latency p50/p99 at the largest fleet — must ride the
  # clip-safe last line (any state-cache / pipeline-depth default flip
  # is justified by exactly these rows).
  infer = out.get('inference_plane')
  if infer:
    fmax = max(infer.get('fleet_sizes') or [0])
    head['inference_plane'] = {
        name: {'cps': row['policy_calls_per_sec'],
               'p50': row['lat_p50_ms'], 'p99': row['lat_p99_ms']}
        for name, row in infer.items()
        if isinstance(row, dict) and name.endswith(f'_f{fmax}')}
  # The overload rows (round 9): shed fraction + served tail latency
  # at 1x/2x/4x slot pressure — the clip-safe record of what the
  # admission policy does under the load the chaos storm drills.
  overload = out.get('overload')
  if overload:
    head['overload'] = {
        name: {'p99': row['lat_p99_ms'],
               'shed_fraction': row['shed_fraction']}
        for name, row in overload.items() if isinstance(row, dict)}
  # The learner-feed itemization (round 8): the {batch, unroll} ×
  # depth rows plus the sharded pallas-vs-scan call must ride the
  # clip-safe last line — BENCH_r08 carries the --staging_mode and
  # Pallas-under-mesh accept/reject on exactly these numbers.
  plane = out.get('learner_plane')
  if plane:
    head['learner_plane'] = {
        name: {'exposed': row['exposed_feed_ms_per_step'],
               'gap': row['step_gap_ms'],
               'overlap': row['h2d_overlap_fraction']}
        for name, row in plane.items()
        if isinstance(row, dict) and 'exposed_feed_ms_per_step' in row}
    head['learner_plane']['bare_step_ms'] = plane['bare_step_ms']
    if plane.get('vtrace_sharded'):
      head['learner_plane']['vtrace_sharded'] = plane['vtrace_sharded']
  # The sample-reuse rows (round 10): reuse factor + step cost per
  # replay_k x ratio cell — the clip-safe record the replay_k default
  # flip is judged on (k2_r0 >= 1.8x is the acceptance gate).
  replay = out.get('replay')
  if replay:
    head['replay'] = {
        name: {'reuse': row['reuse_factor'],
               'step_ms': row['fed_step_ms'],
               'h2d_per_update': row['h2d_unrolls_per_update']}
        for name, row in replay.items()
        if isinstance(row, dict) and 'reuse_factor' in row}
    curves = replay.get('return_vs_wallclock') or {}
    if curves.get('reuse_k2'):
      head['replay']['cue_memory_updates_per_env_frame'] = (
          curves['reuse_k2'].get('updates_per_env_frame'))
  # The runtime-axis rows (round 16): single-device fused fps, the
  # real-fleet ratio the >=3x acceptance gate reads (vs_fed is the
  # acting-free learner ceiling, documented in docs/PERF.md r13), and
  # the hybrid filler's utilization lift — the clip-safe record the
  # --anakin_filler default flip is judged on.
  anakin_rows = out.get('anakin')
  if anakin_rows:
    hybrid = anakin_rows.get('hybrid') or {}
    head['anakin'] = {
        'fps_1dev': (anakin_rows.get('bandit_1dev') or {}).get(
            'env_frames_per_sec'),
        'vs_fleet': anakin_rows.get('anakin_vs_fleet'),
        'vs_fed': anakin_rows.get('anakin_vs_fed'),
        'hybrid_utilization': {
            'off': (hybrid.get('filler_off') or {}).get(
                'learner_plane_utilization'),
            'on': (hybrid.get('filler_on') or {}).get(
                'learner_plane_utilization'),
            'lift': hybrid.get('utilization_lift')},
    }
  # The telemetry-plane cost (round 13): the on/off feed overhead the
  # always-on tracing default is accepted/rejected on (docs/PERF.md
  # r11) — clip-safe like every other default-flip record.
  tele = out.get('telemetry')
  if tele:
    head['telemetry'] = {
        'overhead_fraction': tele.get('overhead_fraction'),
        'span_ns': tele.get('span_ns'),
        'registry_ns_per_op': tele.get('registry_ns_per_op')}
  # The SLO-engine cost (round 14): evaluator tick + triggered-
  # capture overhead — the numbers the always-on judging default is
  # accepted/rejected on (docs/PERF.md r12), clip-safe like every
  # other default-flip record.
  slo_rows = out.get('slo')
  if slo_rows:
    head['slo'] = {
        'evaluator_tick_us': slo_rows.get('evaluator_tick_us'),
        'verdict_us': slo_rows.get('verdict_us'),
        'capture_overhead_fraction':
            slo_rows.get('capture_overhead_fraction')}
  # The controller-loop cost (round 15): idle/acting tick + the full
  # escalate->revert cycle — the numbers the default observe-mode
  # thread is accepted/rejected on, clip-safe like every other
  # default-flip record.
  ctrl_rows = out.get('controller')
  if ctrl_rows:
    head['controller'] = {
        'idle_tick_us': ctrl_rows.get('idle_tick_us'),
        'acting_tick_us': ctrl_rows.get('acting_tick_us'),
        'cycle_wall_ms': ctrl_rows.get('cycle_wall_ms')}
  # The multi-process runtime (round 17): per-process fps + the weak-
  # scaling fraction vs the single-process row — ROADMAP item 1's
  # "recorded number instead of a hope", clip-safe.
  mh = out.get('multihost')
  if mh:
    nprocs = mh.get('nprocs')
    mh_row = mh.get(f'multihost_{nprocs}proc') or {}
    head['multihost'] = {
        'scaling_fraction': mh.get('scaling_fraction'),
        'fps': mh_row.get('env_frames_per_sec'),
        'fps_per_process': mh_row.get('per_process'),
        'single_fps': (mh.get('single_1proc') or {}).get(
            'env_frames_per_sec')}
  # The 2D {data, model} mesh rows (round 19): the per-device memory
  # split the registry's TP rules buy + both step times — the numbers
  # the mesh shape is accepted/rejected on (docs/PERF.md), clip-safe.
  m2d = out.get('mesh2d')
  if m2d:
    head['mesh2d'] = {
        'state_bytes_ratio': m2d.get('state_bytes_ratio'),
        'step_ms_ratio': m2d.get('step_ms_ratio'),
        'dp_step_ms': (m2d.get('dp') or {}).get('step_ms'),
        'mesh2d_step_ms': (m2d.get('mesh2d') or {}).get('step_ms')}
  # The population-engine rows (round 22): curriculum tax vs the <=5%
  # gate + the mixed-suite padding-waste elimination — the clip-safe
  # record the --curriculum default flip is judged on.
  pop = out.get('population')
  if pop:
    head['population'] = {
        'curriculum_overhead_fraction':
            pop.get('curriculum_overhead_fraction'),
        'curriculum_gate_pass': (pop.get('curriculum_gate')
                                 or {}).get('pass'),
        'uniform_fps': (pop.get('uniform') or {}).get(
            'env_frames_per_sec'),
        'regret_fps': (pop.get('regret') or {}).get(
            'env_frames_per_sec'),
        'padding_waste_ratio': (pop.get('padding') or {}).get(
            'waste_ratio'),
        'fused_speedup': (pop.get('fused_population')
                          or {}).get('speedup'),
        'fused_gate_pass': ((pop.get('fused_population')
                             or {}).get('gate') or {}).get('pass')}
  return head


def _emit(out, path=None):
  """Self-contained artifact protocol: write the FULL result to
  BENCH_OUT.json, print the full JSON line (for humans tailing the
  log), then print the compact headline LAST so the driver's tail
  capture always ends on one complete, parseable object."""
  if path is None:
    path = os.environ.get('BENCH_OUT', os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'BENCH_OUT.json'))
  with open(path, 'w') as f:
    json.dump(out, f, indent=1, sort_keys=True)
  print(json.dumps(out))
  print(json.dumps(_headline(out)), flush=True)


if __name__ == '__main__':
  # Before any JAX initialization, but inside the main guard: the
  # forkserver preloads __main__, so a module-level call would
  # recursively spawn a second server (see runtime/py_process.py).
  from scalable_agent_tpu.runtime.py_process import warm_forkserver
  warm_forkserver()
  main()

"""Flagship stability soak (VERDICT r2 item 6; churn mode r3 W3).

The composition test proves the full extension stack RUNS; this proves
it is STABLE AND LEARNING over a sustained run: the real driver
pipeline (process-hosted envs → C++ batcher → buffer → prefetcher →
chip) with every flagship feature on at once — deep ResNet, 72×96
frames, bfloat16 compute, instruction encoder, PopArt, UNREAL pixel
control — on the contextual-bandit task, asserting over the whole run:

  - every logged total_loss is finite,
  - PopArt σ stays inside its clip bounds (a diverging value scale
    shows up there long before NaNs),
  - episode return IMPROVES (last-third mean > first-third mean) and
    beats the random baseline (~1/3 on 3-arm bandit).

SOAK_CHURN=1 additionally exercises the elasticity machinery under
sustained failure — the greenfield feature the reference never had
(its actors just die, SURVEY §5.3), so this is its proof of life
(VERDICT r3 W3):

  - every ~60 s one env process is SIGKILLed (fleet must respawn it
    and keep training),
  - a remote actor host (the production `--job_name=actor` CLI)
    feeds the learner over TCP; mid-run it is killed and a
    replacement spawned (ingest must accept the reconnect and remote
    unrolls must resume),
  - trimmed-RSS / thread-count / open-fd / python-allocated-block
    curves are sampled throughout; the Python-side curves must stay
    flat and per-step RSS growth must stay within 2× the measured
    ambient of the no-churn control (`_AMBIENT_RSS_MB_PER_STEP`) —
    a slow leak
    in the respawn/reconnect paths would be invisible in short
    targeted tests.

Writes SOAK.json at the repo root (a git-ignored working artifact).
Invocation (on the chip, through the chip tool):

    SOAK_CHURN=1 python scripts/soak.py        # ~20 min churn soak
    python scripts/soak.py                      # 10 min steady-state
    SOAK_SECONDS=1500 SOAK_CHURN=1 python scripts/soak.py
    SOAK_SMOKE=1 [SOAK_CHURN=1] python scripts/soak.py  # CPU mechanics

Learning hyperparameters: lr 5e-4 (≈ the paper's tuned 4.8e-4),
entropy 3e-3, γ=0 (the task is one-step). The smoke test's hotter
lr 2e-3 works for the SHALLOW torso but drives the deep ResNet into
a premature near-deterministic policy that solves only 2 of the 3
cues (measured: plateau at ~0.66 reward/step vs 1.0 at 5e-4) — the
flagship stack is what is under test, and at the paper-ish lr it
learns to optimal.
"""

import json
import multiprocessing
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _file_tail(path, n):
  """Last n bytes of a possibly large file, without slurping it."""
  if not os.path.exists(path):
    return ''
  with open(path, 'rb') as f:
    f.seek(0, os.SEEK_END)
    size = f.tell()
    f.seek(max(0, size - n))
    return f.read().decode('utf-8', errors='replace')


def _rss_mb():
  """Resident set AFTER malloc_trim: the churn pipeline allocates and
  frees multi-MB blocks (2.11 MB unrolls, 3.25 MB snapshot blobs, per
  publish/unroll) and glibc retains freed arena pages, so raw RSS
  creeps for minutes without any live-object growth. Trimming first
  makes the curve measure LIVE bytes — the thing a leak check is
  for — instead of allocator retention."""
  try:
    import ctypes
    ctypes.CDLL('libc.so.6').malloc_trim(0)
  except OSError:
    pass
  with open('/proc/self/status') as f:
    for line in f:
      if line.startswith('VmRSS:'):
        return int(line.split()[1]) / 1024.0
  return float('nan')


def _num_fds():
  return len(os.listdir('/proc/self/fd'))


def _spawn_remote_actor(cfg, port, log_path):
  """The production actor-host CLI (`--job_name=actor`), loopback.
  Flags cover every trajectory-contract field the soak config sets;
  both roles then derive identical contracts. Output goes to a FILE,
  not a PIPE: over a long soak the actor logs every param refresh and
  an undrained 64 KB pipe buffer would eventually block it inside a
  log write — a wedged feed misreported as an elasticity bug."""
  cmd = [
      sys.executable, os.path.join(REPO, 'experiment.py'),
      '--job_name=actor', '--task=0',
      f'--learner_address=127.0.0.1:{port}',
      f'--logdir={cfg.logdir}',
      '--env_backend=bandit', '--num_actors=2',
      f'--batch_size={cfg.batch_size}',
      f'--unroll_length={cfg.unroll_length}',
      '--num_action_repeats=1',
      f'--episode_length={cfg.episode_length}',
      f'--height={cfg.height}', f'--width={cfg.width}',
      f'--torso={cfg.torso}', f'--compute_dtype={cfg.compute_dtype}',
      '--use_instruction=true', '--use_popart=true',
      f'--pixel_control_cost={cfg.pixel_control_cost}',
      '--discounting=0.0',
      f'--inference_timeout_ms={cfg.inference_timeout_ms}',
      '--actor_reconnect_secs=120',
      f'--seed={cfg.seed + 50}',
  ]
  env = {k: v for k, v in os.environ.items()
         if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
  existing = env.get('PYTHONPATH', '')
  env['PYTHONPATH'] = (REPO + os.pathsep + existing if existing
                       else REPO)
  log_file = open(log_path, 'a')
  try:
    return subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log_file,
                            stderr=subprocess.STDOUT, text=True)
  finally:
    log_file.close()  # the child holds its own descriptor


def _wait_port(port, deadline, stop):
  """Block until the learner's ingest port accepts (it binds BEFORE
  the inference compile, so this resolves early). Bails out
  when `stop` is set — a learner that fails during setup must not
  leave this probing for the whole run duration."""
  while time.monotonic() < deadline and not stop.is_set():
    try:
      with socket.create_connection(('127.0.0.1', port), timeout=2):
        return True
    except OSError:
      stop.wait(1.0)
  return False


class Churn:
  """Background failure injector + resource sampler.

  Runs beside driver.train in the learner process: SIGKILLs one env
  child every `kill_every` seconds, drops and replaces the remote
  actor host once at ~55% of the run, samples trimmed-RSS/threads/
  fds/python-blocks every `sample_every` seconds. `stop()` ends it
  and reaps the child."""

  def __init__(self, cfg, port, seconds, smoke):
    self._cfg = cfg
    self._port = port
    self._seconds = seconds
    self._smoke = smoke
    self._stop = threading.Event()
    self.events = []
    self.samples = []  # (t, rss_mb, threads, fds, py_blocks)
    self.env_kills = 0
    self.port_probes = 0  # each probe counts in the server's conns
    self.actor_log = os.path.join(cfg.logdir, 'remote_actor.log')
    self._actor = None
    self._thread = threading.Thread(target=self._run,
                                    name='churn', daemon=True)

  def start(self):
    self._thread.start()

  def _event(self, what):
    self.events.append({'t': round(time.monotonic() - self._t0, 1),
                        'wall_time': round(time.time(), 3),
                        'event': what})

  def _kill_one_env(self):
    # Env processes are the mp (forkserver) children of THIS process;
    # the remote actor is a subprocess.Popen and so not in this list.
    children = multiprocessing.active_children()
    if not children:
      self._event('no env child to kill')
      return
    victim = random.choice(children)
    try:
      os.kill(victim.pid, signal.SIGKILL)
      self.env_kills += 1
      self._event(f'SIGKILL env pid {victim.pid}')
    except (OSError, AttributeError) as e:
      self._event(f'env kill failed: {e!r}')

  def _reap_actor(self):
    if self._actor is None:
      return
    try:
      self._actor.wait(timeout=10)
    except subprocess.TimeoutExpired:
      self._actor.kill()
      self._actor.wait()
    self._actor = None

  def _run(self):
    self._t0 = time.monotonic()
    grace = 20 if self._smoke else 120       # past compile/warmup
    kill_every = 8 if self._smoke else 60
    sample_every = 2 if self._smoke else 15
    use_remote = not self._smoke            # CLI child ~2 min to boot
    drop_at = self._seconds * 0.55
    next_kill = grace
    next_sample = 0.0
    dropped = False
    if use_remote:
      if _wait_port(self._port, self._t0 + self._seconds, self._stop):
        self.port_probes = 1
        self._actor = _spawn_remote_actor(self._cfg, self._port,
                                          self.actor_log)
        self._event('remote actor spawned')
      else:
        self._event('ingest port never opened')
    while not self._stop.wait(0.5):
      t = time.monotonic() - self._t0
      if t >= next_sample:
        self.samples.append((round(t, 1), round(_rss_mb(), 1),
                             threading.active_count(), _num_fds(),
                             sys.getallocatedblocks()))
        next_sample = t + sample_every
      if t >= next_kill:
        self._kill_one_env()
        next_kill = t + kill_every
      if use_remote and not dropped and t >= drop_at:
        dropped = True
        if self._actor is not None and self._actor.poll() is None:
          self._actor.kill()
          self._event('SIGKILL remote actor host')
        self._reap_actor()
        self._actor = _spawn_remote_actor(self._cfg, self._port,
                                          self.actor_log)
        self.drop_wall_time = time.time()
        self._event('replacement remote actor spawned')

  def stop(self):
    self._stop.set()
    self._thread.join(timeout=10)
    if self._actor is not None and self._actor.poll() is None:
      # Learner is down by now; the child's reconnect window would
      # just burn — end it.
      self._actor.kill()
    self._reap_actor()


# Ambient RSS growth of the PLAIN train path, from a 420 s
# no-churn/no-remote control run (same flagship config, RSS sampled
# after malloc_trim): 151 steps, ~840 MB post-warmup growth
# ≈ 5.6 MB/step, while sys.getallocatedblocks() stayed flat (+1%) —
# native buffers, not Python objects, with the elasticity machinery
# entirely idle. The leak gate bounds per-step RSS growth at 2× this
# constant (a churn-added leak of even a few MB/step trips it) and
# requires the PYTHON-side curves — allocated blocks, threads, fds —
# to stay genuinely flat. The control run predates the current chip
# machine: not re-measured there, so re-take the constant with a
# no-churn run before reading a soak's RSS verdict.
_AMBIENT_RSS_MB_PER_STEP = 5.6


def _flatness_problems(samples, steps, smoke):
  """Fail on growth that looks like a leak in OUR machinery: flat
  Python blocks/threads/fds, and per-step RSS growth bounded by 2×
  the ambient (native, churn-independent) constant. On CPU (smoke,
  ambient ≈ 0) the RSS allowance drops to a small absolute bound so
  the CI smoke keeps real leak sensitivity."""
  problems = []
  if len(samples) < 8:
    problems.append(f'only {len(samples)} resource samples')
    return problems
  body = samples[len(samples) // 4:]          # drop warmup quarter
  ref = body[:max(len(body) // 2, 1)]
  tail = body[-3:]
  ref_thr = max(s[2] for s in ref)
  ref_fds = max(s[3] for s in ref)
  ref_blocks = max(s[4] for s in ref)
  for name, idx, bound in (('threads', 2, ref_thr + 4),
                           ('fds', 3, ref_fds + 16),
                           ('python blocks', 4, ref_blocks * 1.10)):
    worst = max(s[idx] for s in tail)
    if worst > bound:
      problems.append(
          f'{name} grew: tail max {worst} vs reference {bound:.1f} '
          f'(post-warmup ref max × tolerance)')
  rss_growth = max(s[1] for s in tail) - body[0][1]
  # Steps inside the sampled window, estimated time-proportionally.
  # Steps concentrate AFTER the excluded compile/warmup quarter, so
  # the time fraction UNDERcounts window steps — the computed
  # MB/step is an overestimate, i.e. the gate errs strict.
  span = samples[-1][0] - samples[0][0]
  window_frac = (tail[-1][0] - body[0][0]) / span if span > 0 else 1.0
  window_steps = max(steps * window_frac, 1.0)
  allowance = 0.5 if smoke else 2 * _AMBIENT_RSS_MB_PER_STEP
  if rss_growth / window_steps > allowance:
    problems.append(
        f'rss grew {rss_growth:.0f} MB over ~{window_steps:.0f} '
        f'post-warmup steps ({rss_growth / window_steps:.1f} '
        f'MB/step) — above the {allowance} MB/step allowance '
        f'({"CPU smoke" if smoke else "2x the measured ambient of the no-churn control"}); '
        'suspect a real leak')
  return problems


def _downsample(samples, n=40):
  if len(samples) <= n:
    return samples
  step = len(samples) / n
  return [samples[int(i * step)] for i in range(n)] + [samples[-1]]


def main():
  smoke = os.environ.get('SOAK_SMOKE') == '1'
  churn = os.environ.get('SOAK_CHURN') == '1'
  default_secs = ('40' if smoke else '1200' if churn else '600')
  seconds = float(os.environ.get('SOAK_SECONDS', default_secs))
  if smoke:
    import jax
    jax.config.update('jax_platforms', 'cpu')
  import numpy as np
  from scalable_agent_tpu import driver
  from scalable_agent_tpu import popart as popart_lib
  from scalable_agent_tpu.config import Config

  logdir = tempfile.mkdtemp(prefix='soak_')
  ingest_port = 0
  if churn:
    with socket.create_server(('127.0.0.1', 0)) as s:
      ingest_port = s.getsockname()[1]
  cfg = Config(
      logdir=logdir,
      env_backend='bandit',
      num_actors=8 if not smoke else 2,
      batch_size=4 if not smoke else 2,
      unroll_length=20 if not smoke else 5,
      num_action_repeats=1,
      episode_length=5,
      height=72 if not smoke else 24,
      width=96 if not smoke else 32,
      torso='deep' if not smoke else 'shallow',
      compute_dtype='bfloat16' if not smoke else 'float32',
      # Churn needs real processes to kill — also in smoke.
      use_py_process=(not smoke) or churn,
      use_instruction=True,
      use_popart=True,
      pixel_control_cost=0.01,
      learning_rate=0.0005,
      entropy_cost=0.003,
      discounting=0.0,
      reward_clipping='abs_one',
      total_environment_frames=int(1e9),
      inference_timeout_ms=20,
      checkpoint_secs=10**6,
      summary_secs=10 if not smoke else 2,
      remote_actor_port=ingest_port,
      # Churn runs the egress lever end-to-end: snapshots ship bf16
      # over the wire, the actor host upcasts, and the run still has
      # to learn to optimal (docs/PERF.md "Param-snapshot egress").
      remote_params_dtype='bfloat16' if churn else '',
      seed=7)

  churner = None
  if churn:
    churner = Churn(cfg, ingest_port, seconds, smoke)
    churner.start()
  try:
    run = driver.train(cfg, max_seconds=seconds,
                       stall_timeout_secs=180)
  finally:
    if churner is not None:
      churner.stop()

  losses, sigmas_min, sigmas_max, returns = [], [], [], []
  remote_unrolls = []  # (wall_time, cumulative unrolls over the wire)
  remote_conns = 0
  # Integrity counters over the soak window (round 12): final value
  # of each — all asserted ZERO below, so long-run rot shows up as a
  # red soak with a named counter instead of a mystery return dip.
  integrity_final = {'wire_crc_rejected': 0,
                     'publish_digest_rejected': 0,
                     'ckpt_digest_fallbacks': 0,
                     'sdc_replica_mismatches': 0}
  with open(os.path.join(logdir, 'summaries.jsonl')) as f:
    for line in f:
      e = json.loads(line)
      if 'value' not in e:
        continue
      if e['tag'] == 'total_loss':
        losses.append(e['value'])
      elif e['tag'] == 'popart_sigma_min':
        sigmas_min.append(e['value'])
      elif e['tag'] == 'popart_sigma_max':
        sigmas_max.append(e['value'])
      elif e['tag'] == 'remote_unrolls':
        remote_unrolls.append((e['wall_time'], e['value']))
      elif e['tag'] == 'remote_connections':
        remote_conns = max(remote_conns, int(e['value']))
      elif e['tag'] in integrity_final:
        integrity_final[e['tag']] = int(e['value'])
      elif e['tag'].endswith('/episode_return'):
        returns.append(e['value'])

  steps = int(run.state.update_steps)
  problems = []
  # --- Integrity SLO: ZERO violations over the soak window. Unlike
  # the chaos storm (which INJECTS corruption and asserts detection),
  # the soak runs clean hardware — any nonzero here is real rot on
  # this host, and a long soak is exactly where it accumulates. The
  # health counter covers local training too (no remote needed); the
  # wire counters only move in churn mode (remote feed on). ---
  if run.health is not None:
    integrity_final['sdc_replica_mismatches'] = max(
        integrity_final['sdc_replica_mismatches'],
        run.health.stats().get('sdc_mismatches', 0))
  integrity_final['ckpt_digest_fallbacks'] = max(
      integrity_final['ckpt_digest_fallbacks'],
      run.checkpointer.digest_fallbacks)
  if run.ingest is not None:
    ing = run.ingest.stats()
    integrity_final['wire_crc_rejected'] = max(
        integrity_final['wire_crc_rejected'],
        ing.get('wire_crc_rejected', 0))
    integrity_final['publish_digest_rejected'] = max(
        integrity_final['publish_digest_rejected'],
        ing.get('publish_digest_rejected', 0))
  # Round 13: the unified metrics registry is the SAME source of
  # truth the drain manifest / flight recorder / fleet 'stats' read —
  # cross-check the summaries-derived integrity counters against the
  # registrations that OUTLIVE the run (ingest Counters, the health
  # monitor's gauges; a disagreement means a reporting path rotted,
  # itself a soak finding). checkpoint/* gauges are deliberately
  # absent here: Checkpointer.close() unregisters them inside
  # driver.train's finally, and the direct
  # run.checkpointer.digest_fallbacks read above already covers that
  # counter.
  from scalable_agent_tpu import telemetry
  registry_snap = telemetry.registry().snapshot()
  registry_integrity = {
      'wire_crc_rejected': registry_snap.get('ingest/wire_crc_rejected'),
      'sdc_replica_mismatches': registry_snap.get(
          'health/sdc_mismatches'),
  }
  for name, reg_value in registry_integrity.items():
    if reg_value is None:
      continue
    integrity_final[name] = max(integrity_final[name], int(reg_value))
  for name, value in sorted(integrity_final.items()):
    if value:
      problems.append(
          f'integrity violation over the soak window: {name}={value} '
          '(expected 0 on clean hardware — suspect this host\'s '
          'NIC/RAM/disk; docs/RUNBOOK.md §9)')
  # Telemetry-plane liveness (round 13): with tracing on (default),
  # the soak window must have produced a parseable trace stream with
  # span coverage — a silent tracer over a long run is a telemetry
  # regression, not a shrug.
  telemetry_block = {'registry_names': len(registry_snap)}
  if cfg.telemetry_trace:
    sys.path.insert(0, REPO)
    from scripts import trace_report
    trace_summary = trace_report.summarize(
        trace_report.load_traces(logdir))
    telemetry_block.update({
        'trace_batches': trace_summary['batches'],
        'trace_unrolls': trace_summary['unrolls'],
        'policy_lag_p99': trace_summary['policy_lag']['p99'],
        'e2e_ms_p99': trace_summary['e2e_ms']['p99'],
    })
    if trace_summary['batches'] == 0:
      problems.append('telemetry_trace on but traces.jsonl carries '
                      'zero batch records over the soak window')
  # Round 14: the run judged itself continuously (slo.py default
  # objective set) — a soak whose SLO verdict fails is a red soak
  # naming the objective, and the soak artifact carries the verdict
  # so chip-run triage starts from margins, not raw counters.
  from scalable_agent_tpu import slo as slo_lib
  slo_verdict = slo_lib.read_verdict(logdir)
  slo_block = None
  if cfg.slo_engine:
    if slo_verdict is None:
      problems.append('slo_engine on but the run wrote no '
                      'SLO_VERDICT.json')
    else:
      slo_block = {
          'pass': slo_verdict.get('pass'),
          'violations': slo_verdict.get('violations') or [],
          'captures': sorted(slo_verdict.get('captures') or {}),
          'margins': {
              name: e.get('margin')
              for name, e in
              (slo_verdict.get('objectives') or {}).items()},
      }
      # Round 16 (ROADMAP item 3): the learner-plane utilization SLO
      # row, explicit in the soak artifact — the number the hybrid
      # filler exists to lift. With --anakin_filler the filler floor
      # objective must read ok (~1.0 by construction; burning means
      # the filler failed to fill); without it the plain row is the
      # env-bound capacity-headroom measurement. env stays alongside
      # as the dead-plane signal filler frames must never mask.
      objs = slo_verdict.get('objectives') or {}
      slo_block['plane_utilization'] = {
          'learner': (objs.get('learner_plane_utilization')
                      or {}).get('value'),
          'learner_filler_floor_state': (
              objs.get('learner_plane_utilization_filler')
              or {}).get('state'),
          'env': (objs.get('env_plane_utilization') or {}).get(
              'value'),
      }
      if not slo_verdict.get('pass'):
        problems.append(
            'SLO verdict FAILED over the soak window: '
            + ', '.join(slo_verdict.get('violations') or ['?']))
  # Round 15: the controller's action log rides the soak artifact —
  # a long run that moved its own knobs must say so (and in act mode
  # an apply error over the window is a soak finding).
  from scalable_agent_tpu import controller as controller_lib
  controller_log = controller_lib.read_log(logdir)
  controller_block = None
  if cfg.controller != 'off':
    if controller_log is None:
      problems.append('controller=%s but the run wrote no '
                      'CONTROLLER_LOG.json' % cfg.controller)
    else:
      counts = controller_log.get('counts') or {}
      controller_block = {
          'mode': controller_log.get('mode'),
          'counts': counts,
          'last_actions': [
              {k: a.get(k) for k in ('kind', 'objective', 'actuator',
                                     'from', 'to', 'applied')}
              for a in (controller_log.get('actions') or [])[-8:]],
      }
      if counts.get('apply_errors'):
        problems.append(
            'controller recorded %d actuator apply error(s) over the '
            'soak window' % counts['apply_errors'])
  if steps < (20 if not smoke else 2):
    problems.append(f'only {steps} learner steps in {seconds:.0f}s')
  if not losses or not np.all(np.isfinite(losses)):
    problems.append(f'non-finite or missing losses: {losses[-3:]}')
  # σ is clipped to [DEFAULT_SIGMA_MIN, DEFAULT_SIGMA_MAX] by design:
  # LANDING ON either bound means the value scale collapsed/diverged
  # (×1.01/÷1.01 so the check can actually fire at the clip).
  sigma_lo = float(popart_lib.DEFAULT_SIGMA_MIN)
  sigma_hi = float(popart_lib.DEFAULT_SIGMA_MAX)
  if not sigmas_max or not np.all(np.isfinite(sigmas_max)):
    problems.append('missing/non-finite popart sigma')
  elif (max(sigmas_max) >= sigma_hi / 1.01 or
        min(sigmas_min) <= sigma_lo * 1.01):
    problems.append(
        f'popart sigma hit its clip bounds: [{min(sigmas_min)}, '
        f'{max(sigmas_max)}]')
  third = max(len(returns) // 3, 1)
  early = float(np.mean(returns[:third])) if returns else float('nan')
  late = float(np.mean(returns[-third:])) if returns else float('nan')
  # Random play on the 3-arm bandit: 5-step episodes × 1/3 ≈ 1.67.
  random_baseline = cfg.episode_length / 3.0
  if not smoke:
    if len(returns) < 12:
      problems.append(f'only {len(returns)} episode returns logged')
    elif not (late > early):
      problems.append(f'return did not improve: early={early:.3f} '
                      f'late={late:.3f}')
    elif late <= 1.5 * random_baseline:
      problems.append(
          f'return does not clear the random baseline '
          f'({random_baseline:.2f}): late={late:.3f}')

  churn_artifact = None
  if churner is not None:
    respawns = run.fleet.stats()['respawns']
    if churner.env_kills == 0:
      problems.append('churn mode killed no env process')
    elif respawns == 0:
      problems.append(
          f'{churner.env_kills} env kills but fleet recorded 0 '
          'respawns')
    if not smoke:
      # The remote host was dropped and replaced: cumulative ingest
      # connections must show BOTH actors beyond the churn thread's
      # own port probe (which the server also counts), and unrolls
      # must keep landing AFTER the replacement connected.
      needed = 2 + churner.port_probes
      if remote_conns < needed:
        problems.append(
            f'expected >={needed} cumulative remote connections '
            f'({churner.port_probes} probe + original + replacement), '
            f'saw {remote_conns}')
      drop_wall = getattr(churner, 'drop_wall_time', None)
      if drop_wall is None:
        problems.append('remote actor was never dropped/replaced')
      else:
        before = max((v for w, v in remote_unrolls
                      if w <= drop_wall), default=0)
        after = max((v for w, v in remote_unrolls), default=0)
        if after <= before:
          problems.append(
              f'remote unrolls did not resume after the drop: '
              f'{before} before vs {after} final')
    problems.extend(_flatness_problems(churner.samples, steps, smoke))
    churn_artifact = {
        'env_kills': churner.env_kills,
        'fleet_respawns': respawns,
        'remote_connections': remote_conns,
        'remote_unrolls_final': (remote_unrolls[-1][1]
                                 if remote_unrolls else 0),
        'events': churner.events,
        'resource_curve': [
            {'t': t, 'rss_mb': r, 'threads': th, 'fds': fd,
             'py_blocks': bl}
            for t, r, th, fd, bl in _downsample(churner.samples)],
        'rss_note': (
            'the leak gate bounds per-step RSS growth at 2x the '
            'ambient constant of a NO-churn control (native '
            'buffers; python blocks flat) plus flat '
            'blocks/threads/fds; see _AMBIENT_RSS_MB_PER_STEP'),
        'actor_tail': _file_tail(churner.actor_log, 400),
    }

  n_chunks = 8
  chunk = max(len(returns) // n_chunks, 1)
  curve = [round(float(np.mean(returns[i:i + chunk])), 3)
           for i in range(0, len(returns), chunk)]
  artifact = {
      'ok': not problems,
      'problems': problems,
      'seconds': seconds,
      'steps': steps,
      'frames': int(run.frames),
      'episodes_logged': len(returns),
      'return_early_third': round(early, 3),
      'return_late_third': round(late, 3),
      'return_curve': curve,
      'loss_first': round(float(losses[0]), 4) if losses else None,
      'loss_last': round(float(losses[-1]), 4) if losses else None,
      'popart_sigma_range': ([round(float(min(sigmas_min)), 5),
                              round(float(max(sigmas_max)), 5)]
                             if sigmas_max else None),
      'integrity': integrity_final,
      'telemetry': telemetry_block,
      'slo': slo_block,
      'controller': controller_block,
      'churn': churn_artifact,
      'stack': {
          'torso': cfg.torso, 'compute_dtype': cfg.compute_dtype,
          'frames': [cfg.height, cfg.width],
          'use_instruction': True, 'use_popart': True,
          'pixel_control_cost': cfg.pixel_control_cost,
          'unroll_length': cfg.unroll_length,
          'batch_size': cfg.batch_size, 'num_actors': cfg.num_actors,
          'use_py_process': cfg.use_py_process,
      },
      'smoke': smoke,
  }
  out_path = os.path.join(REPO, 'SOAK.json')
  if smoke:
    out_path = os.path.join(logdir, 'SOAK_smoke.json')
  with open(out_path, 'w') as f:
    json.dump(artifact, f, indent=1)
  print(json.dumps(artifact))
  if problems:
    sys.exit(1)


if __name__ == '__main__':
  from scalable_agent_tpu.runtime.py_process import warm_forkserver
  warm_forkserver()
  main()

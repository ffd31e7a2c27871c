"""Driver integration tests: the full train/test wiring on fake envs.

The reference has NO test of experiment.py (SURVEY §4 — a gap not to
copy). These run the real driver end to end on CPU: actor fleet +
inference batcher + prefetcher + (sharded) train step + checkpointing +
episode stats, then test-mode eval restoring the checkpoint.
"""

import glob
import json
import os

import numpy as np
import pytest

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config


def _config(tmp_path, **kw):
  base = dict(
      logdir=str(tmp_path),
      env_backend='bandit',
      num_actors=2,
      batch_size=2,
      unroll_length=5,
      num_action_repeats=1,
      episode_length=4,
      height=24, width=32,
      torso='shallow',
      use_py_process=False,          # in-process: fast, no fork noise
      use_instruction=False,
      total_environment_frames=10**6,
      inference_timeout_ms=5,
      checkpoint_secs=0,             # save on every maybe_save window
      summary_secs=0,
      seed=3)
  base.update(kw)
  return Config(**base)


def test_train_smoke_and_checkpoint_roundtrip(tmp_path):
  cfg = _config(tmp_path)
  run = driver.train(cfg, max_steps=3, stall_timeout_secs=60)
  assert int(run.state.update_steps) == 3
  assert run.frames == 3 * cfg.frames_per_step

  # Checkpoint written; resume continues the step count.
  run2 = driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  assert int(run2.state.update_steps) == 5

  # Summaries exist and are valid JSONL.
  files = glob.glob(os.path.join(str(tmp_path), 'summaries.jsonl'))
  assert files
  with open(files[0]) as f:
    events = [json.loads(line) for line in f]
  assert any(e['tag'] == 'env_frames_per_sec' for e in events)
  # Action histogram (reference ≈L395): counts over the action space,
  # summing to the trained-on actions of the interval's batches.
  hists = [e for e in events if e.get('kind') == 'histogram'
           and e['tag'] == 'actions']
  assert hists
  num_actions = 3  # bandit backend default
  assert all(len(h['counts']) == num_actions for h in hists)
  assert sum(sum(h['counts']) for h in hists) <= \
      5 * cfg.unroll_length * cfg.batch_size


def test_train_total_frames_termination(tmp_path):
  cfg = _config(tmp_path,
                total_environment_frames=2 * 2 * 5)  # exactly 2 steps
  run = driver.train(cfg, stall_timeout_secs=60)
  assert int(run.state.update_steps) == 2


def test_evaluate_from_checkpoint(tmp_path):
  cfg = _config(tmp_path)
  driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  returns = driver.evaluate(cfg)
  assert set(returns) == {cfg.level_name}
  assert len(returns[cfg.level_name]) == cfg.test_num_episodes
  for r in returns[cfg.level_name]:
    assert 0.0 <= r <= cfg.episode_length
  # Eval scores land in their own summary stream.
  with open(os.path.join(str(tmp_path), 'eval_summaries.jsonl')) as f:
    tags = {json.loads(line)['tag'] for line in f}
  assert f'{cfg.level_name}/test_episode_return' in tags


def test_sharded_train_path(tmp_path):
  """batch 8 over the 8 virtual CPU devices → the pjit path."""
  import jax
  assert len(jax.devices()) == 8
  cfg = _config(tmp_path, batch_size=8, num_actors=4)
  run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
  assert int(run.state.update_steps) == 2


def test_evaluate_without_checkpoint_raises(tmp_path):
  cfg = _config(tmp_path)
  with pytest.raises(FileNotFoundError):
    driver.evaluate(cfg)


def test_setup_failure_releases_everything_and_retry_works(tmp_path):
  """The setup guard's contract (ADVICE r2 medium): a make_actor
  failure during fleet.start() — after the ingest port is already
  bound and inference is warmed — must release the port and every
  background resource, and a same-process retry on the SAME port must
  then succeed (the 'bound zombie port serving stale v1 params'
  scenario the guard's comment describes)."""
  import socket
  import threading
  from scalable_agent_tpu.envs import factory

  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  cfg = _config(tmp_path, remote_actor_port=port,
                remote_actor_bind_host='127.0.0.1')

  real_build = factory.build_environment
  calls = {'n': 0}

  def failing_build(spec, use_py_process=False):
    calls['n'] += 1
    raise RuntimeError('injected env-construction failure')

  factory.build_environment = failing_build
  try:
    with pytest.raises(RuntimeError, match='injected'):
      driver.train(cfg, max_steps=1, stall_timeout_secs=30)
  finally:
    factory.build_environment = real_build
  assert calls['n'] >= 1
  # The ingest port was released (a leaked listener would EADDRINUSE).
  probe = socket.create_server(('127.0.0.1', port))
  probe.close()
  # No stray non-daemon machinery keeping the process alive.
  assert all(t.daemon or t is threading.main_thread() or
             not t.is_alive() for t in threading.enumerate())

  # Same-process retry on the SAME port trains fine.
  run = driver.train(cfg, max_steps=1, stall_timeout_secs=60)
  assert int(run.state.update_steps) == 1


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_train_with_popart_and_pixel_control(tmp_path):
  """The extension stack end-to-end through the driver: PopArt state
  lives in the TrainState, checkpoints, and restores; the aux loss
  contributes."""
  cfg = _config(tmp_path, use_popart=True, pixel_control_cost=0.01,
                height=24, width=32)
  run = driver.train(cfg, max_steps=3, stall_timeout_secs=60)
  assert run.state.popart is not None
  mu = np.asarray(run.state.popart.mu)
  assert mu.shape == (1,)  # single level
  assert np.all(np.isfinite(mu))

  # Resume restores the PopArt stats alongside params (max_steps=0:
  # the returned state is exactly the restored checkpoint).
  run2 = driver.train(cfg, max_steps=0, stall_timeout_secs=60)
  assert int(run2.state.update_steps) == 3
  np.testing.assert_allclose(np.asarray(run2.state.popart.mu)[0],
                             mu[0], rtol=1e-6)


def test_train_with_process_hosted_envs(tmp_path, caplog):
  """The production env-hosting path (use_py_process=True): each env in
  its own OS process behind the spec protocol, through the full driver.
  Every `step` goes through the group's shared block (PR 33), only
  `initial` and the attaching down the pickled pipe, and the run's
  closing lines say so.

  Also the fork-hazard regression (VERDICT r2 W1): the driver builds
  env processes AFTER inference warmup, i.e. from a JAX-multithreaded
  parent — under the forkserver default this must raise no
  multi-threaded-fork warnings (py 3.12's deadlock deprecation)."""
  import warnings
  cfg = _config(tmp_path, use_py_process=True, num_actors=2)
  with warnings.catch_warnings(record=True) as caught, caplog.at_level(
      'INFO', logger='scalable_agent_tpu'):
    warnings.simplefilter('always')
    run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
  fork_warnings = [w for w in caught
                   if 'fork' in str(w.message).lower()]
  assert not fork_warnings, [str(w.message) for w in fork_warnings]
  assert int(run.state.update_steps) == 2
  stats = run.fleet.stats()
  assert stats['unrolls'] >= 2
  assert stats['block_steps'] >= stats['unrolls'] * cfg.unroll_length
  # `initial`, the attaching and `close`, an env each.
  assert stats['pipe_calls'] == 3 * cfg.num_actors
  assert any(r.getMessage().startswith('env transport: block_steps=')
             for r in caplog.records)


def test_evaluate_multitask_parallel(tmp_path):
  """Batched eval: all 30 dmlab30 levels evaluate concurrently through
  the shared dynamic batcher (bandit stand-in envs); every level
  reaches test_num_episodes and the human-normalized scores compute."""
  cfg = _config(tmp_path, level_name='dmlab30', num_actors=2,
                unroll_length=4, episode_length=2,
                test_num_episodes=1)
  driver.train(cfg, max_steps=1, stall_timeout_secs=120)
  returns = driver.evaluate(cfg)
  assert len(returns) == 30
  for name, rs in returns.items():
    assert len(rs) == 1, name


def test_profiler_trace_capture(tmp_path, monkeypatch):
  """jax.profiler hooks (SURVEY §5.1 — absent upstream): a capture
  window writes a trace the standard tooling can open. Every capture
  goes through observability.ProfilerCapture: the profiler records
  the device only (its host tracer halves a fleet's speed), and the
  host's side is the span recorder's, in spans.json."""
  import jax
  started = []
  real_start = jax.profiler.start_trace

  def start_trace(log_dir, **kwargs):
    started.append((log_dir, kwargs.get('profiler_options')))
    return real_start(log_dir, **kwargs)

  monkeypatch.setattr(jax.profiler, 'start_trace', start_trace)
  prof_dir = str(tmp_path / 'profile')
  cfg = _config(tmp_path, profile_dir=prof_dir, profile_start_step=1,
                profile_num_steps=1)
  driver.train(cfg, max_steps=3, stall_timeout_secs=60)
  traces = glob.glob(os.path.join(prof_dir, '**', '*.xplane.pb'),
                     recursive=True)
  assert traces, f'no trace under {prof_dir}'
  ((log_dir, options),) = started
  assert log_dir == prof_dir
  assert options.host_tracer_level == 0
  assert options.python_tracer_level == 0
  with open(os.path.join(prof_dir, 'spans.json')) as f:
    spans = json.load(f)
  assert spans['landmark']['module'] == 'jit_capture_clock_sync'
  clock = spans['clock']
  assert clock['perf_ns'] >= spans['landmark']['host_perf_ns']
  names = {row[0] for row in spans['spans']}
  # One learner step's worth (the actors may sit on a full buffer).
  assert {'learner/iteration', 'learner/step_dispatch'} <= names
  assert all(clock['perf_ns'] <= t0 <= t1 <= spans['taken_ns']
             for _, t0, t1, _, _ in spans['spans'])
  from scalable_agent_tpu import telemetry
  assert telemetry.take_spans() is None  # the capture disarmed it


SPAN_NAMES = {
    'actor/unroll', 'actor/step', 'actor/policy_call', 'batcher/compute',
    'actor/env_step', 'env/pipe', 'actor/assemble', 'actor/put',
    'inference/wait_batch', 'inference/dispatch', 'inference/readback',
    'inference/unpark', 'staging/wait_unrolls', 'staging/stage',
    'learner/wait_batch', 'learner/iteration', 'learner/step_dispatch',
    'learner/publish', 'learner/summaries',
    # An activity since PR 37: these runs checkpoint on the learner's
    # thread (`Checkpointer.save`).
    'learner/checkpoint'}


def test_a_fleet_run_records_every_span_at_its_boundary(tmp_path,
                                                        monkeypatch):
  """The recorder armed over one short fleet run with process-hosted
  envs: every span name of docs/OBSERVABILITY.md's table at least
  once, nested as the table says, ids shared as it says. A thread per
  env here (the grouped nesting is the next test's)."""
  from scalable_agent_tpu import telemetry
  from scalable_agent_tpu.runtime import fleet as fleet_lib
  monkeypatch.setattr(fleet_lib, '_MAX_ENVS_PER_THREAD', 1)
  # (Batches of 4 from 2 actors, and steps enough to outrun what the
  # actors made while the first step compiled: the learner waits.)
  cfg = _config(tmp_path, use_py_process=True, num_actors=2,
                batch_size=4)
  telemetry.arm_spans()
  try:
    run = driver.train(cfg, max_steps=8, stall_timeout_secs=120)
  finally:
    taken = telemetry.take_spans()
  assert int(run.state.update_steps) == 8
  assert taken['dropped'] == 0
  rows = taken['spans']
  assert {row[0] for row in rows} == SPAN_NAMES
  by_thread = {}
  for row in rows:
    by_thread.setdefault(row[3], []).append(row)

  def children(parent, name):
    return [r for r in by_thread[parent[3]] if r[0] == name and
            parent[1] <= r[1] and r[2] <= parent[2]]

  def all_of(name):
    return [row for row in rows if row[0] == name]

  # actor/unroll > actor/step > (actor/policy_call > batcher/compute,
  # actor/env_step > env/pipe); actor/assemble beside the steps.
  for unroll in all_of('actor/unroll'):
    steps = children(unroll, 'actor/step')
    assert len(steps) == cfg.unroll_length
    assert len(children(unroll, 'actor/assemble')) == 1
    actor, seq = unroll[4]
    assert actor == taken['threads'][unroll[3]] and seq >= 0
    for step in steps:
      (call,) = children(step, 'actor/policy_call')
      (env_step,) = children(step, 'actor/env_step')
      assert len(children(call, 'batcher/compute')) == 1
      assert len(children(env_step, 'env/pipe')) == 1
      assert call[2] <= env_step[1]
      assert step[4] == call[4] == env_step[4] == unroll[4]
  # The hand-over carries the id of the unroll it hands over.
  unroll_ids = {tuple(r[4]) for r in all_of('actor/unroll')}
  assert {tuple(r[4]) for r in all_of('actor/put')} <= unroll_ids
  # One merged call: dispatch -> readback -> unpark, one batch_id.
  by_batch = {}
  for row in rows:
    if row[0].startswith('inference/') and row[4] is not None:
      by_batch.setdefault(row[4], {})[row[0]] = row
  complete = [b for b in by_batch.values() if len(b) == 4]
  assert complete
  for batch in complete:
    assert (batch['inference/wait_batch'][2] <=
            batch['inference/dispatch'][1] <=
            batch['inference/dispatch'][2] <=
            batch['inference/readback'][1] <=
            batch['inference/readback'][2] <=
            batch['inference/unpark'][1])
  # The learner thread: everything per step inside the iteration.
  iterations = all_of('learner/iteration')
  assert len({r[3] for r in iterations}) == 1
  whole = [it for it in iterations
           if len(children(it, 'learner/step_dispatch')) == 1]
  assert len(whole) == 8
  for iteration in whole:
    assert len(children(iteration, 'learner/publish')) == 1
    assert len(children(iteration, 'learner/summaries')) == 1
  waits = all_of('learner/wait_batch')
  assert all(any(it[1] <= w[1] and w[2] <= it[2] for it in iterations)
             for w in waits)
  # Staging is a thread of its own: wait, then stage, per batch.
  assert len({r[3] for r in all_of('staging/stage')}) == 1
  assert len(all_of('staging/stage')) >= 8


@pytest.mark.parametrize('state_cache', [False, True],
                         ids=['carry_passing', 'state_cache'])
def test_a_grouped_fleet_run_counts_and_spans(tmp_path, state_cache):
  """Process-hosted envs share an actor thread (PR 26):
  `driver.train` end to end through the k-row policy
  call, with the counters that say the mechanism engaged and the
  spans at their granularity: per env (`actor/unroll`, `env/pipe`,
  `actor/assemble`, `actor/put`) or per group step (the rest)."""
  from scalable_agent_tpu import telemetry
  cfg = _config(tmp_path, use_py_process=True, num_actors=2,
                batch_size=4, inference_state_cache=state_cache)
  telemetry.arm_spans()
  try:
    run = driver.train(cfg, max_steps=6, stall_timeout_secs=120)
  finally:
    taken = telemetry.take_spans()
  assert int(run.state.update_steps) == 6
  server, fleet = run.server.stats(), run.fleet.stats()
  assert server['requests'] == 2 * server['batcher_requests'] > 0
  assert server['mean_batch'] == 2.0  # rows, as before
  assert fleet['respawns'] == 0 and fleet['slots_quarantined'] == 0
  tags = {}
  with open(os.path.join(cfg.logdir, 'summaries.jsonl')) as f:
    for line in f:
      row = json.loads(line)
      if 'value' in row:  # histograms carry none
        tags.setdefault(row['tag'], []).append(row['value'])
  assert set(tags['actor_threads']) == {1.0}
  assert set(tags['envs_per_thread']) == {2.0}
  # (Rows are counted at dispatch, calls when made: one in flight.)
  assert tags['inference_rows_per_request'][-1] == pytest.approx(
      2.0, rel=0.05)
  # (0.0: a summary interval in which no merged call was dispatched.)
  assert set(tags['inference_mean_batch']) - {0.0} == {2.0}

  rows = taken['spans']
  assert {row[0] for row in rows} == SPAN_NAMES
  actor_threads = {r[3] for r in rows if r[0].startswith('actor/')}
  assert [taken['threads'][t] for t in actor_threads] == ['actor-0']

  def inside(parent, name):
    return [r for r in rows if r[0] == name and r[3] == parent[3] and
            parent[1] <= r[1] and r[2] <= parent[2]]

  unrolls = [r for r in rows if r[0] == 'actor/unroll']
  assert {r[4][0] for r in unrolls} == {'actor-0', 'actor-1'}
  for unroll in unrolls:
    steps = inside(unroll, 'actor/step')
    assert len(steps) == cfg.unroll_length
    # Both members' assemblies fall inside either member's unroll.
    assert ({r[4][0] for r in inside(unroll, 'actor/assemble')} ==
            {'actor-0', 'actor-1'})
    for step in steps:
      (call,) = inside(step, 'actor/policy_call')
      assert len(inside(call, 'batcher/compute')) == 1
      (env_step,) = inside(step, 'actor/env_step')
      pipes = inside(env_step, 'env/pipe')
      assert len(pipes) == 2
      # Both children had their action before either reply was read.
      assert max(p[1] for p in pipes) <= min(p[2] for p in pipes)
  puts = [r for r in rows if r[0] == 'actor/put']
  assert {tuple(r[4]) for r in puts} <= {tuple(r[4]) for r in unrolls}
  assert {r[4][0] for r in puts} == {'actor-0', 'actor-1'}


def test_groups_stay_within_inference_max_batch(tmp_path):
  """`--inference_max_batch` below the fleet size: the batcher refuses
  a larger request, so the hosted fleet runs as groups of that many
  rows (here two threads of two) and not as one group whose every
  policy call raises."""
  cfg = _config(tmp_path, use_py_process=True, num_actors=4,
                batch_size=4, inference_max_batch=2)
  run = driver.train(cfg, max_steps=4, stall_timeout_secs=120)
  assert int(run.state.update_steps) == 4
  server, fleet = run.server.stats(), run.fleet.stats()
  assert server['requests'] == 2 * server['batcher_requests'] > 0
  assert fleet['respawns'] == 0 and fleet['slots_quarantined'] == 0
  with open(os.path.join(cfg.logdir, 'summaries.jsonl')) as f:
    rows = [json.loads(line) for line in f]
  assert {r['value'] for r in rows if r['tag'] == 'actor_threads'} == {2.0}
  assert {r['value'] for r in rows
          if r['tag'] == 'envs_per_thread'} == {2.0}


def test_a_slow_rebuild_does_not_run_out_the_no_batch_deadline(tmp_path):
  """A respawn rebuilds envs on the learner's own thread (a group's k,
  in turn). Where that outlasts the no-batch deadline, the time is
  the rebuild's and not the fleet's silence: the run goes on once the
  new envs feed, instead of raising on return from the rebuild."""
  import time

  class SlowRebuild:
    """The fleet, with every slot parked after the first learner step
    and a 31 s rebuild (deadline: 30 s) once the feed has run dry."""

    def __init__(self, fleet):
      self._fleet, self.calls, self.rebuilt = fleet, 0, False
      self.last_call = time.monotonic()

    def __getattr__(self, name):
      return getattr(self._fleet, name)

    def check_health(self, **kw):
      self.calls += 1
      # A second since the last call: the learner's get timed out (the
      # call after a step follows the one before it at once).
      dry = time.monotonic() - self.last_call > 0.9
      if self.calls == 1:
        self._fleet.set_target_size(0)
      elif (dry and not self.rebuilt and
            self._fleet.stats()['alive'] == 0):
        self.rebuilt = True
        time.sleep(31)
        self._fleet.set_target_size(2)
      self.last_call = time.monotonic()
      return self._fleet.check_health(**kw)

  fleets = []

  def fleet_factory(config, agent, policy, buffer, levels):
    fleets.append(SlowRebuild(driver.make_fleet(
        config, agent, policy, buffer, levels)))
    return fleets[-1]

  cfg = _config(tmp_path)
  run = driver.train(cfg, max_steps=12, stall_timeout_secs=1,
                     fleet_factory=fleet_factory)
  assert fleets[0].rebuilt
  assert int(run.state.update_steps) == 12


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_flagship_multitask_sharded(tmp_path):
  """The headline configuration in one run: dmlab30 multi-task (bandit
  stand-ins), PopArt, pixel control, instruction encoder, batch 8 over
  the 8-device mesh — the exact composition the paper's flagship uses,
  previously only covered piecewise."""
  import jax
  assert len(jax.devices()) == 8
  cfg = _config(tmp_path, level_name='dmlab30', batch_size=8,
                num_actors=4, unroll_length=4, episode_length=2,
                use_popart=True, pixel_control_cost=0.01,
                use_instruction=True)
  run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
  assert int(run.state.update_steps) == 2
  assert run.state.popart is not None
  assert np.asarray(run.state.popart.mu).shape == (30,)
  # Instruction encoder params exist and trained on the mesh.
  flat = run.state.params['params']
  assert 'InstructionEncoder_0' in flat


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
@pytest.mark.parametrize('ambient_platforms', [None, 'tpu'])
def test_dryrun_multichip_self_provisions_on_cpu(ambient_platforms):
  """Import the module and call dryrun_multichip(8) programmatically,
  with NO device provisioning in the environment. Round 1 failed here
  because the XLA_FLAGS setup lived only under __main__ (VERDICT
  Missing #1). The dry-run is a CPU virtual-device check: whatever
  platform the environment names, it pins CPU itself and never takes
  a chip."""
  import subprocess
  import sys
  env = {k: v for k, v in os.environ.items()
         if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
  if ambient_platforms is not None:
    env['JAX_PLATFORMS'] = ambient_platforms
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  out = subprocess.run(
      [sys.executable, '-c',
       'import __graft_entry__; __graft_entry__.dryrun_multichip(8); '
       'import jax; print("RAN_ON", jax.devices()[0].platform, '
       'jax.config.jax_platforms)'],
      cwd=repo, env=env, capture_output=True, text=True, timeout=600)
  assert out.returncode == 0, out.stderr[-2000:]
  assert 'parity_rel_delta' in out.stdout and 'ok' in out.stdout
  assert 'RAN_ON cpu cpu' in out.stdout, out.stdout


def test_pallas_vtrace_accepted_under_mesh(tmp_path):
  """Round 8: the mesh rejection is LIFTED — pallas_call still has no
  SPMD partitioning rule, but the sharded step now runs the kernel
  shard_map'ped over the data axis (vtrace.py), so the 8-device mesh
  trains with the fused V-trace instead of raising. The mutual
  exclusion with the associative scan stays a config error."""
  cfg = _config(tmp_path, batch_size=8, use_pallas_vtrace=True)
  run = driver.train(cfg, max_steps=2, stall_timeout_secs=120)
  assert int(run.state.update_steps) == 2
  cfg2 = _config(tmp_path, use_pallas_vtrace=True,
                 use_associative_scan=True)
  with pytest.raises(ValueError, match='mutually exclusive'):
    driver.train(cfg2, max_steps=1)


def test_default_min_batch_is_auto_for_train_only(tmp_path,
                                                  batcher_options_spy):
  """Satellite (VERDICT r5 weak #4): the DEFAULT inference_min_batch
  is 0 (auto) since round 6 — a train run with NO batching flags
  floors the merge at the fleet size (the measured 201.7-vs-146.4 fps
  lever from the r5 sweep), while eval still resolves to 1 (its
  retiring levels must not stall the tail one timeout per batch)."""
  from scalable_agent_tpu.config import Config
  assert Config().inference_min_batch == 0
  cfg = _config(tmp_path, num_actors=2)  # no inference_min_batch set
  driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  assert batcher_options_spy[-1]['minimum_batch_size'] == 2  # fleet
  # Eval's opt-out is structural: evaluate() builds its server WITHOUT
  # fleet_size (test_eval_ignores_auto_merge_floor pins the full
  # evaluate() path) — the auto default must resolve that construction
  # to a floor of 1.
  import jax
  from scalable_agent_tpu.models import init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.runtime.inference import InferenceServer
  agent = driver.build_agent(cfg, 4)
  params = init_params(agent, jax.random.PRNGKey(0),
                       {'frame': (cfg.height, cfg.width, 3),
                        'instr_len': MAX_INSTRUCTION_LEN})
  server = InferenceServer(agent, params, cfg, seed=0)
  server.close()
  assert batcher_options_spy[-1]['minimum_batch_size'] == 1  # opt-out


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_train_with_state_cache_end_to_end(tmp_path):
  """Round-9 tentpole through the REAL driver: training with the
  device-resident state cache on (slot handles flow make_fleet →
  Actor → policy; agent_state snapshots feed the learner) must train,
  checkpoint, and resume exactly like the carry-passing path."""
  cfg = _config(tmp_path, inference_state_cache=True)
  run = driver.train(cfg, max_steps=3, stall_timeout_secs=60)
  assert int(run.state.update_steps) == 3
  stats = run.server.stats()
  assert stats['state_cache'] is True
  # Every fleet actor released its slot on shutdown — no leak.
  assert run.server.slots_free() == run.server._num_slots
  # Resume from the checkpoint, still cached.
  run2 = driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  assert int(run2.state.update_steps) == 5
  # evaluate() restores and plays through the cache path too.
  returns = driver.evaluate(_config(
      tmp_path, inference_state_cache=True, test_num_episodes=1))
  assert all(len(v) == 1 for v in returns.values())


def test_transport_telemetry_written(tmp_path):
  """Round 6 per-lane counters land in summaries: the staging overlap
  fraction always, the remote ack/ingest rows when ingest is on."""
  import socket
  with socket.create_server(('127.0.0.1', 0)) as s:
    port = s.getsockname()[1]
  cfg = _config(tmp_path, summary_secs=0, remote_actor_port=port)
  driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  with open(os.path.join(str(tmp_path), 'summaries.jsonl')) as f:
    tags = {json.loads(line)['tag'] for line in f}
  assert 'h2d_overlap_fraction' in tags
  assert 'staged_batches' in tags
  assert 'remote_ack_p50_ms' in tags
  assert 'remote_ack_p99_ms' in tags
  assert 'remote_unrolls_per_sec' in tags
  # Round 7 actor-plane service telemetry (satellite: summaries/JSONL
  # export the percentiles alongside the merge telemetry).
  assert 'inference_latency_p50_ms' in tags
  assert 'inference_latency_p99_ms' in tags
  assert 'inference_publishes_skipped' in tags


def test_eval_ignores_auto_merge_floor(tmp_path, batcher_options_spy):
  """--inference_min_batch=0 (auto fleet-size floor, round 5) must NOT
  apply to evaluate(): levels retire as their episodes finish, so a
  floor would make the tail step one batcher-timeout per batch (the
  W5 tail stalls pad_batch_to eliminated). Train resolves the floor;
  eval resolves to 1."""
  cfg = _config(tmp_path, inference_min_batch=0,
                inference_timeout_ms=50, num_actors=2)
  driver.train(cfg, max_steps=2, stall_timeout_secs=60)
  assert batcher_options_spy[-1]['minimum_batch_size'] == 2  # train: fleet
  driver.evaluate(cfg)
  assert batcher_options_spy[-1]['minimum_batch_size'] == 1  # eval: no floor

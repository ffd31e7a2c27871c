"""Hybrid filler fleets (round 16, anakin.HybridFiller + the driver's
ready-probe yield loop): idle learner slices run bounded Anakin
self-play, fresh/filler frame accounting stays split, and a staged
batch is never delayed by more than one filler step.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from scalable_agent_tpu.config import Config, validate_runtime
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.parallel import anakin
from scalable_agent_tpu.runtime import ring_buffer
from scalable_agent_tpu.testing import make_example_unroll


def _filler_config(tmp_path, **kw):
  base = dict(logdir=str(tmp_path), env_backend='bandit',
              num_actors=0, batch_size=2, unroll_length=5,
              num_action_repeats=1, episode_length=4, height=24,
              width=32, torso='shallow', use_py_process=False,
              use_instruction=False, anakin_filler=True,
              filler_batch_size=2, filler_unroll_length=5,
              total_environment_frames=10**9,
              checkpoint_secs=10**6, summary_secs=0, seed=11)
  base.update(kw)
  return Config(**base)


class _ThrottledFleet:
  """Synthetic producer at a fixed trickle: the env-bound regime the
  filler exists for (BENCH r9: ~150 fps feed vs ~300k fps learner)."""

  def __init__(self, buffer, unroll, period=0.35):
    self._buffer, self._unroll, self._period = buffer, unroll, period
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._produce, daemon=True)

  def _produce(self):
    while not self._stop.is_set():
      time.sleep(self._period)
      try:
        self._buffer.put(self._unroll, timeout=0.2)
      except (TimeoutError, ring_buffer.Closed):
        continue

  def start(self):
    self._thread.start()

  def errors(self):
    return []

  def check_health(self, stall_timeout_secs=None):
    pass

  def stats(self, healthy_horizon_secs=60.0):
    return {'alive': 1, 'respawns': 0, 'healthy': 1,
            'healthy_fraction': 1.0, 'unrolls': 0}

  def stop(self, timeout=None):
    self._stop.set()


def _unroll(t1=6):
  return make_example_unroll(t1, 24, 32, 3, MAX_INSTRUCTION_LEN)


def _summary_tags(logdir):
  tags = {}
  for line in open(os.path.join(logdir, 'summaries.jsonl')):
    e = json.loads(line)
    if 'value' in e:
      tags[e['tag']] = e['value']
  return tags


# --- Unit layer. ---


def test_prefetcher_ready_probe():
  """ready() is a pure probe: False while nothing is staged, True
  once a batch is, True again after close (get() then raises — the
  caller's signal to stop filling), and it never consumes."""
  buffer = ring_buffer.TrajectoryBuffer(4)
  pf = ring_buffer.BatchPrefetcher(buffer, 2)
  try:
    assert not pf.ready()
    buffer.put(_unroll())
    buffer.put(_unroll())
    deadline = time.monotonic() + 5
    while not pf.ready() and time.monotonic() < deadline:
      time.sleep(0.01)
    assert pf.ready()
    assert pf.ready()  # probing twice consumed nothing
    pf.get(timeout=1)  # the staged batch is still there to dequeue
  finally:
    pf.close()
  assert pf.ready()
  with pytest.raises((ring_buffer.Closed, TimeoutError)):
    pf.get(timeout=0.1)


def test_validate_runtime_knob_group():
  ok = Config(anakin_filler=True, env_backend='bandit')
  warnings = validate_runtime(ok)
  assert any('vtrace' in w for w in warnings)  # IMPACT cross-link
  assert not any('vtrace' in w for w in validate_runtime(
      Config(anakin_filler=True, surrogate='impact')))
  # Filler + SLO engine off: the masking cross-link.
  assert any('env_plane' in w for w in validate_runtime(
      Config(anakin_filler=True, surrogate='impact',
             slo_engine=False)))
  with pytest.raises(ValueError, match='runtime'):
    validate_runtime(Config(runtime='bogus'))
  with pytest.raises(ValueError, match='jittable'):
    validate_runtime(Config(runtime='anakin', env_backend='dmlab'))
  with pytest.raises(ValueError, match='jittable'):
    validate_runtime(Config(anakin_filler=True,
                            filler_backend='dmlab'))
  # anakin runtime: the filler knob is a no-op worth a warning.
  assert any('no-op' in w for w in validate_runtime(
      Config(runtime='anakin', env_backend='bandit',
             anakin_filler=True)))
  # Auto backend: jittable runs self-play their OWN task; host-only
  # backends fall back to bandit.
  assert Config(env_backend='gridworld').resolved_filler_backend == \
      'gridworld'
  assert Config(env_backend='dmlab').resolved_filler_backend == \
      'bandit'


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_hybrid_filler_freezes_the_fleet_clocks():
  """The clock contract (the PR 7 serve-time attribution, extended):
  a filler update mutates params but never advances update_steps — so
  the frame budget, LR schedule, and checkpoint numbering all stay on
  the fleet's fresh-frame count. Each fill_one is synchronous (the
  one-filler-step delay bound) and feeds the separate filler ledger."""
  from scalable_agent_tpu import driver, learner, telemetry
  cfg = _filler_config('/tmp/unused', env_backend='dmlab')
  agent = driver.build_agent(cfg, num_actions=9)
  from scalable_agent_tpu.models import init_params
  obs = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, jax.random.PRNGKey(0), obs)
  state = learner.make_train_state(params, cfg)

  filler = anakin.HybridFiller(agent, cfg, num_actions=9)
  assert filler.backend == 'bandit'  # dmlab auto-falls back
  before = jax.device_get(state.params)
  for i in range(3):
    state = filler.fill_one(state)
    assert int(jax.device_get(state.update_steps)) == 0  # frozen
  after = jax.device_get(state.params)
  changed = any(
      not np.array_equal(a, b)
      for a, b in zip(jax.tree_util.tree_leaves(before),
                      jax.tree_util.tree_leaves(after)))
  assert changed  # the updates were real
  assert filler.updates == 3
  assert filler.frames == 3 * filler.frames_per_update
  assert filler.stats()['skipped'] == 0
  # The registry counter rode along (the name-lint contract) ...
  assert telemetry.registry().snapshot()[
      'driver/filler_updates'] >= 3
  # ... and close() unwinds it (the teardown contract: a later run in
  # the same process must not snapshot this run's tally).
  filler.close()
  assert 'driver/filler_updates' not in telemetry.registry().snapshot()


def test_filler_width_mismatch_fails_at_spinup(tmp_path):
  """An explicitly requested filler that cannot honor the main task's
  action-space width must FAIL the run at spin-up (like every
  validate_* error) — never be silently disabled behind a 'topology'
  warning. gridworld needs >= 4 actions; bandit is a 3-action task."""
  from scalable_agent_tpu import driver
  cfg = _filler_config(tmp_path, filler_backend='gridworld')
  with pytest.raises(ValueError, match='num_actions'):
    driver.train(cfg, max_steps=1, stall_timeout_secs=30)


def test_hybrid_filler_rejects_model_axis_mesh():
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  cfg = _filler_config('/tmp/unused')
  agent = driver.build_agent(cfg, num_actions=3)
  mesh = mesh_lib.make_mesh(model_parallelism=2)
  with pytest.raises(ValueError, match='data-parallel'):
    anakin.HybridFiller(agent, cfg, num_actions=3, mesh=mesh)


# --- Driver integration. ---


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_filler_yield_and_frame_accounting(tmp_path):
  """Under an env-throttled feed: every staged batch still trains
  (max_steps reached — the filler never starves the real stream), the
  fresh-frame budget matches the no-filler arithmetic exactly, filler
  work lands on its own summary curves, and learner-plane utilization
  is lifted ~1.0 by construction."""
  from scalable_agent_tpu import driver
  unroll = _unroll()
  cfg = _filler_config(tmp_path)

  def fleet_factory(config, agent, policy, buffer, levels):
    return _ThrottledFleet(buffer, unroll)

  run = driver.train(cfg, max_steps=4, stall_timeout_secs=60,
                     fleet_factory=fleet_factory)
  # Fresh-frame clock: 4 real batches x B=2 x T=5 x repeat=1 — the
  # filler added NOTHING here despite running throughout the stalls.
  assert run.frames == 4 * 2 * 5
  tags = _summary_tags(str(tmp_path))
  assert tags['filler_updates'] >= 1
  assert tags['filler_frames'] == tags['filler_updates'] * 2 * 5
  assert tags['filler_skipped_updates'] == 0
  assert tags['frames_fresh'] <= 4 * 2 * 5
  assert tags['learner_plane_utilization'] > 0.9
  # The run unregistered its filler counter at teardown.
  from scalable_agent_tpu import telemetry
  assert ('driver/filler_updates'
          not in telemetry.registry().snapshot())
  # env_plane_utilization stays the honest env-side signal (the
  # throttled producer is mostly idle-by-choice here, so it reads
  # high; the point is the filler did not overwrite it with 1.0-by-
  # construction semantics — it keeps its own formula).
  assert 'env_plane_utilization' in tags


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_filler_off_parity(tmp_path):
  """Filler OFF under the same throttled feed: identical fresh-frame
  accounting (the budget/LR/fps clocks are invariant to the knob) and
  no filler curves in the summaries."""
  from scalable_agent_tpu import driver
  unroll = _unroll()
  cfg = _filler_config(tmp_path, anakin_filler=False)

  def fleet_factory(config, agent, policy, buffer, levels):
    return _ThrottledFleet(buffer, unroll)

  run = driver.train(cfg, max_steps=4, stall_timeout_secs=60,
                     fleet_factory=fleet_factory)
  assert run.frames == 4 * 2 * 5  # same fresh clock as filler ON
  tags = _summary_tags(str(tmp_path))
  assert 'filler_updates' not in tags

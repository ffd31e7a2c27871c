"""Population engine (round 22): in-graph curriculum math
(population.py + the fused Anakin fold), heterogeneous-fleet
composition (parse/plan + the obs-spec FamilyBatcher), and PBT
exploit/explore with weight inheritance through the checkpoint
ladder. Slow marks carry the learning-curve gate and the
one-invocation population driver run.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu import population
from scalable_agent_tpu.config import Config, validate_population
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.ops import dynamic_batching as db


# --- Curriculum sampler math. ---


def test_level_probs_closed_form():
  scores = jnp.asarray([0.0, 1.0, 2.0])
  probs = np.asarray(population.level_probs(scores, temperature=1.0,
                                            eps=0.1))
  # Scores are max-normalized before the softmax (scale-free
  # prioritization): [0, 1, 2] / 2 -> [0, 0.5, 1].
  e = np.exp([0.0, 0.5, 1.0])
  expected = 0.9 * e / e.sum() + 0.1 / 3
  np.testing.assert_allclose(probs, expected, rtol=1e-6)
  assert abs(probs.sum() - 1.0) < 1e-6


def test_level_probs_scale_free():
  # The same skew at reward scales 1e-2 and 1e2 samples identically —
  # without max-normalization the small-scale softmax is
  # indistinguishable from uniform (the early-training regime where
  # prioritization matters most).
  small = np.asarray(population.level_probs(
      jnp.asarray([0.001, 0.02]), temperature=1.0, eps=0.1))
  large = np.asarray(population.level_probs(
      jnp.asarray([10.0, 200.0]), temperature=1.0, eps=0.1))
  np.testing.assert_allclose(small, large, rtol=1e-6)
  assert small[1] / small[0] > 2.0  # genuinely prioritized
  # All-zero scores (nothing learned yet) stay exactly uniform.
  flat = np.asarray(population.level_probs(
      jnp.zeros(4), temperature=1.0, eps=0.1))
  np.testing.assert_allclose(flat, 0.25, rtol=1e-6)


def test_level_probs_eps_floor_bounds_collapse():
  # One dominant score: without the eps floor the rest would starve.
  scores = jnp.asarray([100.0, 0.0, 0.0, 0.0])
  probs = np.asarray(population.level_probs(scores, temperature=1.0,
                                            eps=0.2))
  assert probs.min() >= 0.2 / 4 - 1e-9
  assert probs.argmax() == 0


def test_sample_levels_prefers_high_scores_and_is_deterministic():
  scores = jnp.asarray([0.0, 0.0, 4.0, 0.0])
  key = jax.random.PRNGKey(7)
  ids = np.asarray(population.sample_levels(key, scores, batch=2048,
                                            temperature=1.0, eps=0.1))
  expected = np.asarray(population.level_probs(scores, 1.0, 0.1))
  freq = np.bincount(ids, minlength=4) / ids.size
  np.testing.assert_allclose(freq, expected, atol=0.05)
  again = np.asarray(population.sample_levels(key, scores, batch=2048,
                                              temperature=1.0,
                                              eps=0.1))
  np.testing.assert_array_equal(ids, again)


def test_score_signal_modes():
  delta = jnp.asarray([-2.0, 0.5, 3.0])
  np.testing.assert_allclose(
      np.asarray(population.score_signal(delta, 'regret')),
      [0.0, 0.5, 3.0])
  np.testing.assert_allclose(
      np.asarray(population.score_signal(delta, 'td')),
      [2.0, 0.5, 3.0])
  with pytest.raises(ValueError, match='unknown curriculum mode'):
    population.score_signal(delta, 'uniform')


def test_update_scores_ema_for_visited_decay_for_stale():
  scores = jnp.asarray([1.0, 2.0, 3.0])
  visits = jnp.zeros(3, jnp.float32)
  # Level 0 visited twice (signals 4 and 6 -> mean 5), level 2 twice
  # (signal 9 twice), level 1 never.
  level_ids = jnp.asarray([[0, 2], [0, 2]])
  signals = jnp.asarray([[4.0, 9.0], [6.0, 9.0]])
  new_scores, new_visits = population.update_scores(
      scores, visits, level_ids, signals, alpha=0.5, decay=0.9)
  new_scores = np.asarray(new_scores)
  assert abs(new_scores[0] - (0.5 * 1.0 + 0.5 * 5.0)) < 1e-6
  assert abs(new_scores[1] - 0.9 * 2.0) < 1e-6   # stale: decayed
  assert abs(new_scores[2] - (0.5 * 3.0 + 0.5 * 9.0)) < 1e-6
  np.testing.assert_allclose(np.asarray(new_visits), [2.0, 0.0, 2.0])


def test_curriculum_metrics_keys_and_entropy():
  scores = jnp.zeros(6, jnp.float32)
  visits = jnp.asarray([1.0, 0.0, 2.0, 0.0, 0.0, 3.0])
  m = population.curriculum_metrics(scores, visits, temperature=1.0,
                                    eps=0.1)
  assert set(m) == {'curriculum_entropy', 'curriculum_score_mean',
                    'curriculum_score_max',
                    'curriculum_levels_visited'}
  # Flat scores -> uniform distribution -> entropy log(n).
  assert abs(float(m['curriculum_entropy']) - np.log(6)) < 1e-5
  assert float(m['curriculum_levels_visited']) == 3.0


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_fused_anakin_step_folds_curriculum_in_graph():
  """The tentpole mechanics at unit scale: one fused procgen step with
  --curriculum=regret carries the per-level tables in the env state,
  emits the curriculum metrics, and accounts exactly (T-1)*B
  transitions per step — with ZERO extra host round trips (the step
  is the same single jitted callable)."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import anakin
  cfg = Config(env_backend='procgen', batch_size=4, unroll_length=4,
               num_action_repeats=1, episode_length=6, height=24,
               width=32, torso='shallow', use_instruction=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=10**6,
               curriculum='regret', procgen_num_levels=5, seed=0)
  core = anakin.make_env_core(cfg)
  agent = driver.build_agent(cfg, core.num_actions)
  step = anakin.make_anakin_step(agent, core, cfg)
  carry = anakin.init_carry(agent, core, cfg, jax.random.PRNGKey(0))
  for expected_steps in (1, 2, 3):
    carry, metrics = step(carry)
    assert 'curriculum_entropy' in metrics
    visits = np.asarray(carry.env_state.level_visits)
    assert visits.shape == (5,)
    assert visits.sum() == expected_steps * (cfg.unroll_length - 1) * \
        cfg.batch_size
  assert np.isfinite(np.asarray(carry.env_state.level_scores)).all()


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_uniform_curriculum_emits_no_curriculum_metrics():
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import anakin
  cfg = Config(env_backend='procgen', batch_size=2, unroll_length=3,
               num_action_repeats=1, episode_length=6, height=24,
               width=32, torso='shallow', use_instruction=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=10**6,
               curriculum='uniform', procgen_num_levels=4, seed=0)
  core = anakin.make_env_core(cfg)
  agent = driver.build_agent(cfg, core.num_actions)
  step = anakin.make_anakin_step(agent, core, cfg)
  carry = anakin.init_carry(agent, core, cfg, jax.random.PRNGKey(0))
  _, metrics = step(carry)
  assert not any(k.startswith('curriculum') for k in metrics)


# --- Heterogeneous fleet composition. ---


def test_parse_fleet_tasks():
  assert population.parse_fleet_tasks('') == []
  assert population.parse_fleet_tasks('gridworld:2,procgen') == [
      ('gridworld', 2.0), ('procgen', 1.0)]
  with pytest.raises(ValueError, match='twice'):
    population.parse_fleet_tasks('a:1,a:2')
  with pytest.raises(ValueError, match='weight'):
    population.parse_fleet_tasks('a:0')
  with pytest.raises(ValueError, match='weight'):
    population.parse_fleet_tasks('a:soon')


def test_plan_actor_assignment_weights_and_floor():
  tasks = [('a', 3.0), ('b', 1.0)]
  plan = population.plan_actor_assignment(tasks, 8)
  counts = {i: plan.count(i) for i in (0, 1)}
  assert counts == {0: 6, 1: 2}
  # Round-robin interleave: both tasks appear early, not in one block.
  assert set(plan[:3]) == {0, 1}
  # >= 1 actor per task even under extreme weights.
  plan = population.plan_actor_assignment([('a', 1000.0), ('b', 1.0)],
                                          2)
  assert sorted(plan) == [0, 1]
  with pytest.raises(ValueError, match='cannot cover'):
    population.plan_actor_assignment(tasks, 1)


def test_padding_report_math():
  # 8 frames of 16x16x3 and 2 frames of 24x32x3 (uint8): bucketed
  # bytes == useful bytes; naive pads everything to 24x32x3.
  report = population.padding_report({(16, 16, 3): 8, (24, 32, 3): 2})
  assert report['useful_bytes'] == 8 * 768 + 2 * 2304
  assert report['bucketed_bytes'] == report['useful_bytes']
  assert report['max_shape_bytes'] == 10 * 2304
  waste = 1.0 - report['useful_bytes'] / report['max_shape_bytes']
  assert abs(report['waste_ratio'] - waste) < 1e-9


def test_popart_stats_summary_names_fleet_tasks():
  from scalable_agent_tpu import popart
  state = popart.init(2)
  state = popart.update_stats(
      state, jnp.full((4, 3), 10.0), jnp.asarray([0, 0, 0]), beta=0.5)
  tasks = [n for n, _ in population.parse_fleet_tasks(
      'gridworld:3,procgen:1')]
  out = popart.stats_summary(state, task_names=tasks)
  assert out['tasks'] == ['gridworld', 'procgen']
  # Only task 0 saw a batch: its mu moved, task 1 stayed identity.
  assert out['mu'][0] > 0.0 and out['mu'][1] == 0.0
  assert out['sigma'][1] == pytest.approx(1.0)


def test_make_env_spec_backend_override():
  cfg = Config(env_backend='gridworld', procgen_num_levels=6,
               total_environment_frames=10**6)
  spec = factory.make_env_spec(cfg, 'procgen', seed=1,
                               backend='procgen')
  assert spec.env_class.__name__ == 'ProcgenEnv'
  assert spec.constructor_kwargs['num_levels'] == 6
  # Default path unchanged.
  spec = factory.make_env_spec(cfg, 'gridworld', seed=1)
  assert spec.env_class.__name__ == 'GridworldEnv'


def test_family_batcher_routes_families_and_accounts_padding():
  def make_fn(key):
    def handler(x):
      return [x.reshape(x.shape[0], -1).sum(-1)]
    return handler

  fb = db.FamilyBatcher(make_fn, minimum_batch_size=1,
                        maximum_batch_size=64, timeout_ms=5)
  small = np.full((2, 16, 16, 3), 1, np.uint8)
  large = np.full((1, 24, 32, 3), 1, np.uint8)
  out_small = fb(small)
  out_large = fb(large)
  np.testing.assert_array_equal(out_small[0], [768, 768])
  np.testing.assert_array_equal(out_large[0], [2304])
  fb(small)  # same family again: routed, not a new queue
  stats = fb.padding_stats()
  assert stats['families'] == 2
  assert stats['rows'] == 5
  # Family bucketing pads nothing; naive max-shape pads the 16x16
  # rows up to 24x32 — the measured waste the bench row reports.
  assert stats['bucketed_bytes'] == stats['useful_bytes'] == \
      4 * 768 + 1 * 2304
  assert stats['max_shape_bytes'] == 5 * 2304
  assert stats['waste_ratio'] > 0.4
  fb.close()
  with pytest.raises(db.BatcherCancelled):
    fb(small)


def test_family_batcher_composition_matches_actor_plan():
  """Bucket composition end to end: the actor plan's per-task shares
  drive the request mix, and the accounting sees exactly that mix."""
  tasks = [('cue_memory', 2.0), ('gridworld', 1.0)]
  plan = population.plan_actor_assignment(tasks, 6)
  frames = {0: np.zeros((1, 16, 16, 3), np.uint8),
            1: np.zeros((1, 24, 32, 3), np.uint8)}
  fb = db.FamilyBatcher(
      lambda key: (lambda x: [x[:, 0, 0, 0]]),
      timeout_ms=5)
  for task in plan:
    fb(frames[task])
  stats = fb.padding_stats()
  fb.close()
  expected = population.padding_report(
      {(16, 16, 3): plan.count(0), (24, 32, 3): plan.count(1)})
  assert stats['useful_bytes'] == expected['useful_bytes']
  assert abs(stats['waste_ratio'] - expected['waste_ratio']) < 1e-9


def test_validate_population_rules():
  base = dict(total_environment_frames=10**6)
  with pytest.raises(ValueError, match='curriculum'):
    validate_population(Config(curriculum='nope', **base))
  with pytest.raises(ValueError, match='temperature'):
    validate_population(Config(curriculum_temperature=0.0, **base))
  with pytest.raises(ValueError, match='mixed fleets'):
    validate_population(Config(fleet_tasks='atari', **base))
  with pytest.raises(ValueError, match='policy head'):
    validate_population(Config(fleet_tasks='cue_memory,gridworld',
                               **base))
  with pytest.raises(ValueError, match='anakin'):
    validate_population(Config(pbt_population=2, **base))
  # Curriculum on a level-space-free backend: warning, not an error.
  warnings = validate_population(
      Config(env_backend='bandit', curriculum='regret', **base))
  assert any('level-id space' in w or 'inert' in w for w in warnings)
  assert validate_population(
      Config(env_backend='procgen', curriculum='regret',
             runtime='anakin', pbt_population=4,
             pbt_suites='gridworld,procgen', **base)) == []


# --- PBT exploit/explore. ---


def test_pbt_explore_multiplies_or_divides_deterministically():
  hypers = {'learning_rate': 1e-3, 'entropy_cost': 0.01}
  out = population.pbt_explore(hypers, np.random.default_rng(3),
                               perturb=1.2)
  for k, v in out.items():
    assert (abs(v - hypers[k] * 1.2) < 1e-12 or
            abs(v - hypers[k] / 1.2) < 1e-12)
  again = population.pbt_explore(hypers, np.random.default_rng(3),
                                 perturb=1.2)
  assert out == again


def test_pbt_decide_ranks_within_group_only():
  returns = [0.0, 10.0, 50.0, 60.0]
  groups = ['a', 'a', 'b', 'b']
  hypers = [{'learning_rate': 1e-3}] * 4
  decisions = population.pbt_decide(
      returns, groups, np.random.default_rng(0), quantile=0.5,
      perturb=1.2, hypers=hypers)
  # Bottom of each suite exploits its own suite's top — member 0's
  # donor must be 1 (never the higher-return cross-suite members).
  assert decisions[0] is not None and decisions[0]['donor'] == 1
  assert decisions[2] is not None and decisions[2]['donor'] == 3
  assert decisions[1] is None and decisions[3] is None
  lr = decisions[0]['hypers']['learning_rate']
  assert (abs(lr - 1.2e-3) < 1e-12 or abs(lr - 1e-3 / 1.2) < 1e-12)


def test_pbt_decide_equal_returns_keep():
  decisions = population.pbt_decide(
      [1.0, 1.0], ['a', 'a'], np.random.default_rng(0))
  assert decisions == [None, None]


def test_pbt_exploit_inherits_weights_through_checkpoint_ladder(
    tmp_path):
  """The CROSS-PROCESS exploit fallback IS a checkpoint-directory
  copy: the loser's next restore_latest loads the donor's verified
  state (digests re-checked on the copied files). Round 23 moved the
  in-process exploit on device (driver hands the donor's live
  TrainState to the loser's next run); this copy-then-swap helper
  remains the path for populations whose members span processes."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.checkpoint import Checkpointer
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN

  cfg = Config(batch_size=2, unroll_length=3, torso='shallow',
               total_environment_frames=10**6)
  agent = ImpalaAgent(num_actions=4, torso='shallow')
  obs_spec = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  donor_state = learner_lib.make_train_state(
      init_params(agent, jax.random.PRNGKey(0), obs_spec), cfg)
  donor_state = donor_state._replace(
      update_steps=jnp.asarray(7, jnp.int32))
  loser_state = learner_lib.make_train_state(
      init_params(agent, jax.random.PRNGKey(1), obs_spec), cfg)

  donor_dir = str(tmp_path / 'member_00' / 'checkpoints')
  loser_dir = str(tmp_path / 'member_01' / 'checkpoints')
  donor = Checkpointer(donor_dir, save_interval_secs=0)
  donor.save(donor_state, force=True)
  donor.wait_until_finished()
  donor.close()
  loser = Checkpointer(loser_dir, save_interval_secs=0)
  loser.save(loser_state, force=True)
  loser.wait_until_finished()
  loser.close()

  # The exploit: donor's ladder replaces the loser's wholesale —
  # through the hardened helper (a failed copy never deletes the
  # loser's ladder; see the regression test below).
  driver._inherit_member_dir(donor_dir, loser_dir)

  fresh = Checkpointer(loser_dir, save_interval_secs=0)
  restored = fresh.restore_latest(loser_state)
  fresh.close()
  assert restored is not None
  assert int(restored.update_steps) == 7
  for got, want in zip(jax.tree_util.tree_leaves(restored.params),
                       jax.tree_util.tree_leaves(donor_state.params)):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- Slow gates: learning curve + the one-invocation population. ---


@pytest.mark.slow
def test_regret_curriculum_reaches_bar_in_fewer_frames():
  """The learning-curve gate (ISSUE r22 acceptance): on a skewed
  procgen level set (wall density 0.35 -> 6 of 8 layouts solvable, 2
  goal-unreachable by BFS), the regret curriculum reaches the return
  bar in fewer total frames than uniform sampling AND shifts
  visitation toward the solvable levels — the PLR mechanism
  (arXiv 2010.03934): dead levels' relu(TD) scores decay to zero, so
  the sampler stops paying the 2/8 of every uniform batch they cost.
  Runs are deterministic per seed on the CPU backend; three seeds are
  aggregated so one lucky gradient stream cannot decide the gate."""
  from scalable_agent_tpu.parallel import anakin

  BAR, WINDOW, MAX_STEPS, SEEDS = 0.02, 20, 400, (3, 0, 11)
  SOLVABLE = [2, 3, 4, 5, 6, 7]   # BFS ground truth at density 0.35

  def run(mode, seed):
    cfg = Config(env_backend='procgen', batch_size=16,
                 unroll_length=8, num_action_repeats=1,
                 episode_length=16, height=24, width=32,
                 torso='shallow', use_instruction=False,
                 learning_rate=3e-3, entropy_cost=3e-3,
                 discounting=0.95, total_environment_frames=10**9,
                 curriculum=mode, procgen_num_levels=8,
                 procgen_wall_density=0.35, seed=seed)
    carry, history, _ = anakin.run(cfg, MAX_STEPS)
    rewards = np.array([float(h['mean_reward']) for h in history])
    windowed = np.convolve(rewards, np.ones(WINDOW) / WINDOW,
                           mode='valid')
    hit = (int(np.argmax(windowed >= BAR)) + WINDOW
           if (windowed >= BAR).any() else MAX_STEPS + 1)
    visits = np.asarray(jax.device_get(carry.env_state.level_visits))
    return hit, float(visits[SOLVABLE].sum() / visits.sum())

  uniform_steps = regret_steps = regret_hits = 0
  for seed in SEEDS:
    u_hit, _ = run('uniform', seed)
    r_hit, r_share = run('regret', seed)
    uniform_steps += u_hit
    regret_steps += r_hit
    regret_hits += r_hit <= MAX_STEPS
    # The mechanism, per seed: visitation moved toward the solvable
    # levels (uniform sits at 6/8 by construction).
    assert r_share > 6 / 8, (seed, r_share)
  assert regret_hits >= 2, regret_hits
  assert regret_steps < uniform_steps, (regret_steps, uniform_steps)


@pytest.mark.slow
def test_population_one_invocation_trains_two_suites(tmp_path,
                                                     monkeypatch):
  """ONE driver.train call, pbt_population=2 across
  {gridworld, procgen}: per-task return rows land in
  population_summaries.jsonl, PBT_LOG.json carries rounds + winner,
  and a forced rank gap exercises the exploit path end to end
  (weights through the ladder + the durable pbt_exploit incident)."""
  from scalable_agent_tpu import driver

  # Deterministic fitness: member 1 always dominates member 0, so
  # with a single comparability group the exploit fires every
  # non-final round regardless of tiny-run reward noise.
  monkeypatch.setattr(
      driver, '_member_return',
      lambda member_dir, tag='mean_reward', tail=5:
          1.0 if 'member_01' in member_dir else 0.0)

  cfg = Config(env_backend='gridworld', runtime='anakin',
               batch_size=4, unroll_length=5, num_action_repeats=1,
               episode_length=8, height=24, width=32, torso='shallow',
               use_instruction=False, use_py_process=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=800,
               seed=0, curriculum='regret', procgen_num_levels=4,
               pbt_population=2, pbt_suites='gridworld',
               pbt_round_frames=400, pbt_quantile=0.5,
               summary_secs=0, checkpoint_secs=0,
               logdir=str(tmp_path))
  run = driver.train(cfg, max_steps=10)
  assert run is not None

  with open(tmp_path / 'PBT_LOG.json') as f:
    log = json.load(f)
  assert len(log['rounds']) == 2
  assert log['winner']['member'] == 1
  exploits = [d for r in log['rounds'] for d in r['decisions']]
  assert exploits and exploits[0]['member'] == 0
  assert exploits[0]['donor'] == 1

  rows = [json.loads(line)
          for line in open(tmp_path / 'population_summaries.jsonl')]
  assert {(r['round'], r['member']) for r in rows} == {
      (0, 0), (0, 1), (1, 0), (1, 1)}
  assert all('hyper_learning_rate' in r for r in rows)

  incidents = [json.loads(line)
               for line in open(tmp_path / 'incidents.jsonl')]
  kinds = [i['kind'] for i in incidents]
  assert 'pbt_exploit' in kinds and 'pbt_winner' in kinds
  # Member 0's round-1 hypers are the donor's, explored again: the
  # donor (member != 0) started from an explored neighborhood, so the
  # inherited value is the base times an INTEGER power of 1.2 in
  # {-2, 0, 2} (init x-or-/ then exploit x-or-/).
  exploited_lr = exploits[0]['hypers']['learning_rate']
  power = np.log(exploited_lr / 2e-3) / np.log(1.2)
  assert abs(power - round(power)) < 1e-6 and round(power) in (-2, 0, 2)


@pytest.mark.slow
def test_population_two_suites_per_task_curves(tmp_path):
  """Two suites, no monkeypatching: the real one-invocation run emits
  one return row per (round, member) with both suites represented —
  the per-task return curves the ISSUE deliverable names."""
  from scalable_agent_tpu import driver
  cfg = Config(env_backend='gridworld', runtime='anakin',
               batch_size=4, unroll_length=5, num_action_repeats=1,
               episode_length=8, height=24, width=32, torso='shallow',
               use_instruction=False, use_py_process=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=400,
               seed=0, curriculum='regret', procgen_num_levels=4,
               pbt_population=2, pbt_suites='gridworld,procgen',
               pbt_round_frames=400,
               summary_secs=0, checkpoint_secs=0,
               logdir=str(tmp_path))
  driver.train(cfg, max_steps=6)
  rows = [json.loads(line)
          for line in open(tmp_path / 'population_summaries.jsonl')]
  assert {r['suite'] for r in rows} == {'gridworld', 'procgen'}
  assert all(isinstance(r['mean_return'], float) for r in rows)
  # The procgen member ran the curriculum fully in-graph: its member
  # dir carries the per-level artifact.
  with open(tmp_path / 'member_01' / 'CURRICULUM_LEVELS.json') as f:
    levels = json.load(f)
  assert len(levels['visits']) == 4 and sum(levels['visits']) > 0


# --- Round 23: fused (vmapped) population, on-device inheritance. ---


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_vectorized_anakin_member0_matches_serial_step():
  """The parity contract behind --pbt_vectorized: member 0 of the
  vmapped N=2 program, fed the config's own hypers as traced scalars,
  reproduces the plain (baked-constant) fused step from the same seed
  — same params, same metrics — while a second member with
  learning_rate=0 proves the traced scalars are real per-member
  inputs (its params stay frozen at init)."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import anakin

  cfg = Config(env_backend='bandit', batch_size=4, unroll_length=5,
               num_action_repeats=1, episode_length=5, height=24,
               width=32, torso='shallow', use_instruction=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=10**9,
               seed=0)
  env_core = anakin.make_env_core(cfg)
  agent = driver.build_agent(cfg, env_core.num_actions)

  serial_step = anakin.make_anakin_step(agent, env_core, cfg)
  serial = anakin.init_carry(agent, env_core, cfg,
                             jax.random.PRNGKey(11))

  vstep = anakin.make_vectorized_anakin_step(agent, env_core, cfg)
  stacked = anakin.init_stacked_carry(agent, env_core, cfg, (11, 12))
  frozen_init = jax.tree_util.tree_map(
      lambda x: np.asarray(x[1]), stacked.train_state.params)
  hypers = {
      'learning_rate': jnp.asarray([cfg.learning_rate, 0.0],
                                   jnp.float32),
      'entropy_cost': jnp.asarray([cfg.entropy_cost, cfg.entropy_cost],
                                  jnp.float32)}
  for _ in range(3):
    serial, m_serial = serial_step(serial)
    stacked, m_vec = vstep(stacked, hypers)

  assert np.asarray(m_vec['mean_reward']).shape == (2,)
  np.testing.assert_allclose(float(np.asarray(m_vec['mean_reward'])[0]),
                             float(m_serial['mean_reward']),
                             rtol=1e-5, atol=1e-6)
  np.testing.assert_allclose(
      float(np.asarray(m_vec['learning_rate'])[0]),
      float(m_serial['learning_rate']), rtol=1e-5)
  assert float(np.asarray(m_vec['learning_rate'])[1]) == 0.0
  for got, want in zip(
      jax.tree_util.tree_leaves(stacked.train_state.params),
      jax.tree_util.tree_leaves(serial.train_state.params)):
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want),
                               rtol=1e-5, atol=1e-6)
  # lr=0 member: three updates applied nothing.
  for got, want in zip(
      jax.tree_util.tree_leaves(stacked.train_state.params),
      jax.tree_util.tree_leaves(frozen_init)):
    np.testing.assert_array_equal(np.asarray(got)[1], want)
  assert int(np.asarray(stacked.train_state.update_steps)[1]) == 3


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_inherit_member_dir_failed_copy_preserves_loser_ladder(
    tmp_path, monkeypatch):
  """ISSUE r23 satellite: an exploit whose filesystem copy FAILS must
  not have deleted the loser's checkpoint dir first. The fallback is
  copy-then-swap — the donor lands in a sibling tmp dir and only a
  complete copy replaces the loser."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.checkpoint import Checkpointer
  from scalable_agent_tpu import learner as learner_lib
  from scalable_agent_tpu.models import ImpalaAgent, init_params
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN

  cfg = Config(batch_size=2, unroll_length=3, torso='shallow',
               total_environment_frames=10**6)
  agent = ImpalaAgent(num_actions=4, torso='shallow')
  obs_spec = {'frame': (24, 32, 3), 'instr_len': MAX_INSTRUCTION_LEN}
  donor_state = learner_lib.make_train_state(
      init_params(agent, jax.random.PRNGKey(0), obs_spec), cfg)
  loser_state = learner_lib.make_train_state(
      init_params(agent, jax.random.PRNGKey(1), obs_spec), cfg)
  loser_state = loser_state._replace(
      update_steps=jnp.asarray(5, jnp.int32))

  donor_dir = str(tmp_path / 'member_00' / 'checkpoints')
  loser_dir = str(tmp_path / 'member_01' / 'checkpoints')
  for d, state in ((donor_dir, donor_state), (loser_dir, loser_state)):
    ckpt = Checkpointer(d, save_interval_secs=0)
    ckpt.save(state, force=True)
    ckpt.wait_until_finished()
    ckpt.close()

  import shutil as shutil_lib

  def boom(src, dst, *args, **kwargs):
    raise OSError('disk full mid-copy')

  monkeypatch.setattr(driver.shutil, 'copytree', boom)
  with pytest.raises(OSError):
    driver._inherit_member_dir(donor_dir, loser_dir)
  monkeypatch.undo()

  # No half-copied tmp dir left behind, and the loser's OWN ladder is
  # intact and restorable.
  assert not os.path.exists(loser_dir + '.inherit_tmp')
  fresh = Checkpointer(loser_dir, save_interval_secs=0)
  restored = fresh.restore_latest(loser_state)
  fresh.close()
  assert restored is not None
  assert int(restored.update_steps) == 5
  for got, want in zip(jax.tree_util.tree_leaves(restored.params),
                       jax.tree_util.tree_leaves(loser_state.params)):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
  del shutil_lib


def test_validate_population_vectorized_rules():
  base = dict(runtime='anakin', env_backend='gridworld',
              pbt_population=2, pbt_round_frames=400,
              total_environment_frames=800)
  # One vmapped program trains ONE suite: a multi-suite population
  # cannot vectorize (member programs would differ structurally).
  with pytest.raises(ValueError, match='vectorized'):
    validate_population(Config(pbt_vectorized=True,
                               pbt_suites='gridworld,procgen', **base))
  # A model-axis mesh degrades to the serial member loop with a
  # warning, not an error (members are single-device programs).
  warnings = validate_population(
      Config(pbt_vectorized=True, pbt_suites='gridworld',
             model_parallelism=2, **base))
  assert any('serial' in w for w in warnings)
  # Vectorized without a population is inert, flagged.
  warnings = validate_population(
      Config(runtime='anakin', env_backend='gridworld',
             pbt_vectorized=True))
  assert any('pbt_vectorized' in w for w in warnings)
  # The happy path is silent about vectorization.
  assert validate_population(
      Config(pbt_vectorized=True, pbt_suites='gridworld',
             **base)) == []


@pytest.mark.slow
def test_population_fused_one_program_two_members(tmp_path,
                                                 monkeypatch):
  """ONE driver.train call with --pbt_vectorized: both members train
  inside one vmapped Anakin program per round, exploit is the
  on-device stacked-slice copy (no member checkpoint dir is ever
  rmtree'd), and the artifact contract matches the serial engine —
  PBT_LOG.json (now with vectorized=true), population_summaries
  rows, pbt_exploit/pbt_winner incidents, per-member summaries and
  checkpoint ladders, and a parent-logdir SLO verdict."""
  import shutil as shutil_lib
  from scalable_agent_tpu import driver
  from scalable_agent_tpu import slo as slo_lib

  monkeypatch.setattr(
      driver, '_member_return',
      lambda member_dir, tag='mean_reward', tail=5:
          1.0 if 'member_01' in member_dir else 0.0)
  removed = []
  real_rmtree = shutil_lib.rmtree

  def spy_rmtree(path, *args, **kwargs):
    removed.append(str(path))
    return real_rmtree(path, *args, **kwargs)

  monkeypatch.setattr(driver.shutil, 'rmtree', spy_rmtree)

  cfg = Config(env_backend='gridworld', runtime='anakin',
               batch_size=4, unroll_length=5, num_action_repeats=1,
               episode_length=8, height=24, width=32, torso='shallow',
               use_instruction=False, use_py_process=False,
               learning_rate=2e-3, entropy_cost=3e-3,
               discounting=0.9, total_environment_frames=800,
               seed=0, pbt_population=2, pbt_vectorized=True,
               pbt_suites='gridworld', pbt_round_frames=400,
               pbt_quantile=0.5, summary_secs=0, checkpoint_secs=0,
               logdir=str(tmp_path))
  run = driver.train(cfg, max_steps=10)
  assert run is not None

  with open(tmp_path / 'PBT_LOG.json') as f:
    log = json.load(f)
  assert log['vectorized'] is True
  assert len(log['rounds']) == 2
  assert log['winner']['member'] == 1
  exploits = [d for r in log['rounds'] for d in r['decisions']]
  assert exploits and exploits[0]['member'] == 0
  assert exploits[0]['donor'] == 1

  rows = [json.loads(line)
          for line in open(tmp_path / 'population_summaries.jsonl')]
  assert {(r['round'], r['member']) for r in rows} == {
      (0, 0), (0, 1), (1, 0), (1, 1)}
  assert all('hyper_learning_rate' in r for r in rows)

  incidents = [json.loads(line)
               for line in open(tmp_path / 'incidents.jsonl')]
  kinds = [i['kind'] for i in incidents]
  assert 'pbt_exploit' in kinds and 'pbt_winner' in kinds

  # On-device inheritance: the exploit never deleted a member ladder.
  assert not [p for p in removed
              if 'member_' in p and p.rstrip('/').endswith('checkpoints')]
  # Durable per-member ladders exist anyway (round-boundary saves).
  for k in range(2):
    member_ckpts = tmp_path / f'member_{k:02d}' / 'checkpoints'
    assert member_ckpts.is_dir() and any(member_ckpts.iterdir())
    assert (tmp_path / f'member_{k:02d}' / 'summaries.jsonl').exists()
  verdict = slo_lib.read_verdict(str(tmp_path))
  assert verdict is not None and verdict['pass']


@pytest.mark.slow
def test_fused_member0_learning_curve_matches_serial(tmp_path):
  """The r23 parity slow gate: member 0 of a fused N=4 bandit
  population (member 0 carries the unperturbed control hypers;
  members 1-3 explored, exactly the train_population recipe) learns
  like a plain serial anakin run from the same seed. The comparison
  is outcome-level (windowed mean reward), not bitwise — the gate is
  that vmapping members changes THROUGHPUT, not what any member
  learns."""
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.parallel import anakin

  STEPS, WINDOW = 120, 30
  base = dict(env_backend='bandit', batch_size=8, unroll_length=5,
              num_action_repeats=1, episode_length=5, height=24,
              width=32, torso='shallow', use_instruction=False,
              learning_rate=2e-3, entropy_cost=3e-3, discounting=0.9,
              total_environment_frames=10**9)

  # Serial reference: the plain fused loop at member 0's seed (the
  # population assigns member k seed = config.seed + 101*k + 1).
  serial_cfg = Config(seed=0 + 101 * 0 + 1, **base)
  _, history, _ = anakin.run(serial_cfg, STEPS)
  serial_tail = float(np.mean(
      [float(h['mean_reward']) for h in history][-WINDOW:]))

  # Fused N=4, same per-member shapes, member 0 unperturbed.
  cfg = Config(seed=0, **base)
  env_core = anakin.make_env_core(cfg)
  agent = driver.build_agent(cfg, env_core.num_actions)
  vstep = anakin.make_vectorized_anakin_step(agent, env_core, cfg)
  seeds = [cfg.seed + 101 * k + 1 for k in range(4)]
  stacked = anakin.init_stacked_carry(agent, env_core, cfg, seeds)
  rng = np.random.default_rng(cfg.seed)
  lrs, ecs = [], []
  for k in range(4):
    h = {'learning_rate': cfg.learning_rate,
         'entropy_cost': cfg.entropy_cost}
    if k:
      h = population.pbt_explore(h, rng, 1.2)
    lrs.append(h['learning_rate'])
    ecs.append(h['entropy_cost'])
  hypers = {'learning_rate': jnp.asarray(lrs, jnp.float32),
            'entropy_cost': jnp.asarray(ecs, jnp.float32)}
  fused_rewards = []
  for _ in range(STEPS):
    stacked, metrics = vstep(stacked, hypers)
    fused_rewards.append(float(np.asarray(
        jax.device_get(metrics['mean_reward']))[0]))
  fused_tail = float(np.mean(fused_rewards[-WINDOW:]))

  # Bandit mean reward lives in [0, 1]; both runs must have learned
  # (chance is 1/3) and member 0 must track the serial curve.
  assert serial_tail > 0.5, serial_tail
  assert fused_tail > 0.5, fused_tail
  assert abs(fused_tail - serial_tail) < 0.15, (fused_tail,
                                                serial_tail)

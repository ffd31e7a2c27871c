"""Atari-57 metadata + scoring (envs/atari57.py) and its wiring.

Like tests/test_dmlab30.py, the anchor tables are reconstructed
constants that cannot be proven here (docs/RUNBOOK.md mandates
re-verification against the published table before any reported
score); these tests bound the damage — well-formed suite, sane
values — and pin the scoring math and the driver-facing wiring.
"""

import numpy as np
import pytest

from scalable_agent_tpu import observability as obs
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.envs import atari57, factory
from scalable_agent_tpu.envs.atari import gym_game_id
from scalable_agent_tpu.structs import (ActorOutput, StepOutput,
                                        StepOutputInfo)


def test_table_is_the_57_game_suite():
  assert len(atari57.ALL_GAMES) == 57
  assert set(atari57.HUMAN_SCORES) == set(atari57.ALL_GAMES)
  assert set(atari57.RANDOM_SCORES) == set(atari57.ALL_GAMES)
  for game in atari57.ALL_GAMES:
    # snake_case rom ids (the adapter contract for both backends)
    assert game == game.lower() and ' ' not in game
    human, random = atari57.HUMAN_SCORES[game], atari57.RANDOM_SCORES[game]
    assert np.isfinite(human) and np.isfinite(random)
    # The normalization divides by (human - random): must be positive.
    assert human > random, game


def test_anchor_returns_score_0_and_100():
  at_random = {g: [atari57.RANDOM_SCORES[g]] for g in atari57.ALL_GAMES}
  at_human = {g: [atari57.HUMAN_SCORES[g]] for g in atari57.ALL_GAMES}
  for agg in ('median', 'mean'):
    assert atari57.compute_human_normalized_score(
        at_random, aggregate=agg) == pytest.approx(0.0)
    assert atari57.compute_human_normalized_score(
        at_human, aggregate=agg) == pytest.approx(100.0)


def test_median_vs_mean_and_cap():
  # One game at 10x human, the rest at random: the median is immune to
  # the outlier (this is WHY the suite reports median), the mean is not.
  returns = {g: [atari57.RANDOM_SCORES[g]] for g in atari57.ALL_GAMES}
  star = atari57.ALL_GAMES[0]
  human, random = atari57.HUMAN_SCORES[star], atari57.RANDOM_SCORES[star]
  returns[star] = [random + 10.0 * (human - random)]
  assert atari57.compute_human_normalized_score(
      returns, aggregate='median') == pytest.approx(0.0)
  assert atari57.compute_human_normalized_score(
      returns, aggregate='mean') == pytest.approx(1000.0 / 57)
  assert atari57.compute_human_normalized_score(
      returns, aggregate='mean', per_game_cap=100.0
      ) == pytest.approx(100.0 / 57)


def test_missing_game_raises():
  returns = {g: [atari57.HUMAN_SCORES[g]] for g in atari57.ALL_GAMES}
  del returns['pong']
  with pytest.raises(ValueError, match='pong'):
    atari57.compute_human_normalized_score(returns)
  returns['pong'] = []
  with pytest.raises(ValueError, match='pong'):
    atari57.compute_human_normalized_score(returns)
  with pytest.raises(ValueError, match='aggregate'):
    atari57.compute_human_normalized_score(
        {g: [0.0] for g in atari57.ALL_GAMES}, aggregate='max')


def test_factory_expands_atari57():
  cfg = Config(level_name='atari57', env_backend='atari')
  assert tuple(factory.level_names(cfg)) == atari57.ALL_GAMES
  # No held-out variants: eval plays the training games.
  assert factory.test_level_names(cfg) == factory.level_names(cfg)


def test_gym_game_id_conversion():
  assert gym_game_id('pong') == 'Pong'
  assert gym_game_id('kung_fu_master') == 'KungFuMaster'
  assert gym_game_id('up_n_down') == 'UpNDown'
  assert gym_game_id('ms_pacman') == 'MsPacman'
  assert gym_game_id('MsPacman') == 'MsPacman'  # passthrough


def _batch_for(level_id, ep_return):
  done = np.zeros((2, 1), bool)
  done[1, 0] = True
  rets = np.full((2, 1), ep_return, np.float32)
  return ActorOutput(
      level_name=np.array([level_id], np.int32),
      agent_state=None,
      env_outputs=StepOutput(
          reward=np.zeros((2, 1), np.float32),
          info=StepOutputInfo(rets, np.ones((2, 1), np.int32)),
          done=done,
          observation=None),
      agent_outputs=None)


def test_episode_stats_atari57_benchmark(tmp_path):
  games = list(atari57.ALL_GAMES)
  writer = obs.SummaryWriter(str(tmp_path))
  stats = obs.EpisodeStats(games, benchmark='atari57', writer=writer)
  for i in range(len(games) - 1):
    stats.record_batch(_batch_for(i, 5.0), step=i)
    assert stats.last_scores is None
  stats.record_batch(_batch_for(len(games) - 1, 5.0), step=99)
  writer.close()
  assert stats.last_scores is not None
  expected_median = atari57.compute_human_normalized_score(
      {g: [5.0] for g in games}, aggregate='median')
  assert np.isclose(stats.last_scores['atari57/training_median'],
                    expected_median)
  assert 'atari57/training_mean' in stats.last_scores


def test_episode_stats_rejects_unknown_benchmark():
  with pytest.raises(ValueError, match='benchmark'):
    obs.EpisodeStats(['x'], benchmark='atari58')


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_evaluate_atari57_scores(tmp_path):
  """Full evaluate() wiring for the 57-game suite (bandit stand-in
  envs, mirroring test_driver's dmlab30 eval test): every game reaches
  test_num_episodes and the median/mean human-normalized scores land
  in the single eval summary file."""
  import glob
  import json
  from scalable_agent_tpu import driver

  cfg = Config(
      logdir=str(tmp_path), level_name='atari57', env_backend='bandit',
      num_actors=2, batch_size=2, unroll_length=4, episode_length=2,
      num_action_repeats=1, height=24, width=32, torso='shallow',
      use_py_process=False, use_instruction=False,
      inference_timeout_ms=5, checkpoint_secs=0, summary_secs=0,
      test_num_episodes=1, seed=3)
  driver.train(cfg, max_steps=1, stall_timeout_secs=120)
  returns = driver.evaluate(cfg)
  assert set(returns) == set(atari57.ALL_GAMES)
  for name, rs in returns.items():
    assert len(rs) == 1, name
  events = [json.loads(line) for line in open(
      glob.glob(str(tmp_path / 'eval_summaries.jsonl'))[0])]
  tags = {e['tag'] for e in events}
  assert 'atari57/test_median' in tags and 'atari57/test_mean' in tags
  for e in events:
    assert np.isfinite(e['value']), e

"""A token environment for sequence policies: the observation is one
int32 token id, the action space is the vocabulary.

An episode is `prompt_length` seeded prompt tokens, then generation up
to `episode_length` steps in all: while the prompt lasts the
observation is the next prompt token, after it the agent's last action
echoed back (the policy reads its own output, as a decoder does). The
reward is a verifiable rule seeded per episode: a generated token earns
1 when it equals `(a * previous observation + c) mod vocabulary`, the
episode's own linear congruence; prompt steps earn 0. `done` comes at
the episode's length, and the observation with it is the next
episode's first prompt token.

Nothing image-sized crosses the pipe: `StepOutput.observation` is the
1-tuple `(token,)` (the agent's `observation_names`).
"""

import numpy as np

from scalable_agent_tpu.envs import base


def observation_specs():
  return (base.ArraySpec((), np.dtype(np.int32)),)


class TokenEnv(base.Environment):

  def __init__(self, vocab_size=97, episode_length=32, prompt_length=4,
               seed=0, start_step=0, level_name='tokens',
               num_action_repeats=1):
    del level_name, num_action_repeats
    if not 0 < prompt_length < episode_length:
      raise ValueError(
          f'prompt_length {prompt_length} must lie inside the episode '
          f'of {episode_length} steps')
    self._vocab = int(vocab_size)
    self._episode_length = int(episode_length)
    self._prompt_length = int(prompt_length)
    self._rng = np.random.RandomState(seed % (1 << 32))
    self._new_episode()
    # A session may begin part-way into its first episode, so that a
    # fleet's episode ends (and state resets) are spread over time.
    self._t = int(start_step) % self._episode_length
    self._last = np.int32(self._rng.randint(self._vocab))

  def _new_episode(self):
    self._t = 0
    self._prompt = self._rng.randint(
        self._vocab, size=self._prompt_length).astype(np.int32)
    self._a = int(self._rng.randint(1, self._vocab))
    self._c = int(self._rng.randint(self._vocab))

  def _observation(self):
    if self._t < self._prompt_length:
      token = self._prompt[self._t]
    else:
      token = self._last
    return (np.int32(token),)

  def initial(self):
    return self._observation()

  def step(self, action):
    action = int(action) % self._vocab
    (seen,) = self._observation()
    generating = self._t >= self._prompt_length - 1
    reward = np.float32(
        generating and action == (self._a * int(seen) + self._c)
        % self._vocab)
    self._last = np.int32(action)
    self._t += 1
    done = self._t >= self._episode_length
    if done:
      self._new_episode()
    return reward, np.bool_(done), self._observation()

  @staticmethod
  def _tensor_specs(method_name, unused_kwargs, constructor_kwargs):
    if method_name == 'initial':
      return observation_specs()
    if method_name == 'step':
      return (base.ArraySpec((), np.dtype(np.float32)),
              base.ArraySpec((), np.dtype(bool)),
              observation_specs())
    return None

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

With `prompt_block` (the longest prompt of the fleet, which fixes the
shape) the prompt is handed over AS A BLOCK instead: an episode's first
observation is its prompt's LAST token, and `prompt_block()` offers all
the tokens before it, zero-padded to `prompt_block`, with their number.
The block must be fetched before the episode's first step (the actor
hands it to an inference server whose core computes a chunk at once);
an episode whose block nobody took would run without its prompt, so
its first step refuses. Every session then begins at an episode's
start (`start_step` is unused).

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
               num_action_repeats=1, prompt_block=0):
    del level_name, num_action_repeats
    if prompt_block and prompt_block < prompt_length:
      raise ValueError(
          f'a prompt of {prompt_length} tokens does not fit the block '
          f'of {prompt_block}')
    if not 0 < prompt_length < episode_length:
      raise ValueError(
          f'prompt_length {prompt_length} must lie inside the episode '
          f'of {episode_length} steps')
    self._vocab = int(vocab_size)
    self._episode_length = int(episode_length)
    self._prompt_length = int(prompt_length)
    self._prompt_block = int(prompt_block)
    self._rng = np.random.RandomState(seed % (1 << 32))
    self._new_episode()
    if not self._prompt_block:
      # A session may begin part-way into its first episode, so that a
      # fleet's episode ends (and state resets) are spread over time.
      self._t = int(start_step) % self._episode_length
    self._last = np.int32(self._rng.randint(self._vocab))

  def _new_episode(self):
    self._prompt = self._rng.randint(
        self._vocab, size=self._prompt_length).astype(np.int32)
    self._a = int(self._rng.randint(1, self._vocab))
    self._c = int(self._rng.randint(self._vocab))
    # Handed over as a block, the prompt is behind the first
    # observation but for its last token.
    self._t = self._prompt_length - 1 if self._prompt_block else 0
    self._block_taken = False

  def prompt_block(self):
    """(tokens i32 [prompt_block], n): the current episode's prompt
    without its last token, zero-padded, and how many tokens that is.
    None where the prompt comes a token a step."""
    if not self._prompt_block:
      return None
    n = self._prompt_length - 1
    block = np.zeros((self._prompt_block,), np.int32)
    block[:n] = self._prompt[:n]
    self._block_taken = True
    return block, np.int32(n)

  def _observation(self):
    if self._t < self._prompt_length:
      token = self._prompt[self._t]
    else:
      token = self._last
    return (np.int32(token),)

  def initial(self):
    return self._observation()

  def step(self, action):
    if (self._prompt_block and not self._block_taken
        and self._t == self._prompt_length - 1):
      raise RuntimeError(
          'this episode\'s prompt is offered as a block '
          '(prompt_block()), and nobody took it before the first step')
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
    if method_name == 'prompt_block':
      return (base.ArraySpec((constructor_kwargs.get('prompt_block', 0),),
                             np.dtype(np.int32)),
              base.ArraySpec((), np.dtype(np.int32)))
    return None

"""Core data structures shared across the framework.

Mirrors the reference's namedtuple contracts (reference: environments.py
≈L120 `StepOutput`/`StepOutputInfo`; experiment.py ≈L52 `ActorOutput`,
≈L55 `AgentOutput`) so that a user of the reference finds the same shapes
in the same places. All are plain pytrees — they cross the host/device
boundary and jit untouched.
"""

from typing import NamedTuple, Any

import jax.numpy as jnp
import numpy as np


class StepOutputInfo(NamedTuple):
  """Episode statistics that flow *through* the trajectory (no side channel).

  On `done`, the emitted output carries the final episode stats while the
  carried state resets them to zero — the reference's FlowEnvironment design
  (environments.py ≈L165–190), kept here as part of the trajectory pytree.
  """
  episode_return: Any  # f32 []
  episode_step: Any    # i32 []


class StepOutput(NamedTuple):
  """One environment step (reference: environments.py ≈L120)."""
  reward: Any       # f32 []
  info: Any         # StepOutputInfo
  done: Any         # bool []
  observation: Any  # a tuple of arrays, as the agent's
                    # `observation_names` has them: (frame uint8
                    # [H, W, 3], instruction ids int32 [L]), or
                    # (token int32 [],) for a sequence policy


class AgentOutput(NamedTuple):
  """One agent step (reference: experiment.py ≈L55)."""
  action: Any         # i32 [] — sampled (actor) or argmax (learner unroll)
  policy_logits: Any  # f32 [num_actions]; or f32 [], the action's
                      # log-probability, from an agent that keeps
                      # the logits of a large action space on the
                      # device (models/sequence.py): told by its shape
  baseline: Any       # f32 []


class ActorOutput(NamedTuple):
  """One actor unroll as enqueued for the learner (experiment.py ≈L52).

  Time-major with the 1-frame overlap: T+1 timesteps where timestep 0 is
  the previous unroll's last frame (load-bearing for learner alignment —
  see losses.py).
  """
  level_name: Any    # bytes/str or int level id
  agent_state: Any   # LSTM state at the *start* of the unroll
  env_outputs: Any   # StepOutput of [T+1] tensors
  agent_outputs: Any # AgentOutput of [T+1] tensors


def zeros_like_spec(spec):
  """Build a zeroed pytree from a (shape, dtype) spec pytree."""
  import jax
  return jax.tree_util.tree_map(
      lambda s: jnp.zeros(s.shape, s.dtype), spec)


def observation_leaves(obs_spec):
  """((shape, dtype), ...) of one observation, in the order of the
  agent's `observation_names`, from an env's `obs_spec`: its 'leaves'
  where it states them, else the image contract's 'frame' (H, W, C)
  uint8 and 'instr_len' L int32."""
  if 'leaves' in obs_spec:
    return tuple((tuple(shape), np.dtype(dtype))
                 for shape, dtype in obs_spec['leaves'])
  return ((tuple(obs_spec['frame']), np.dtype(np.uint8)),
          ((int(obs_spec['instr_len']),), np.dtype(np.int32)))

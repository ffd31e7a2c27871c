"""Shared test/bench fixtures: synthetic trajectory batches.

One canonical constructor for a random learner batch so tests, the
driver entry points, and the benchmark can't drift apart when the
trajectory structs change.
"""

import numpy as np

import jax.numpy as jnp

from scalable_agent_tpu.structs import (
    ActorOutput, AgentOutput, StepOutput, StepOutputInfo)


def make_example_unroll(t1, h, w, num_actions, instr_len, seed=0,
                        hidden_size=256):
  """One random host-side ActorOutput unroll ([T+1] numpy, batch dim 1
  on the core state) — what a single actor ships over the wire."""
  rng = np.random.RandomState(seed)
  return ActorOutput(
      level_name=np.int32(0),
      agent_state=(np.zeros((1, hidden_size), np.float32),
                   np.zeros((1, hidden_size), np.float32)),
      env_outputs=StepOutput(
          reward=rng.randn(t1).astype(np.float32),
          info=StepOutputInfo(np.zeros(t1, np.float32),
                              np.zeros(t1, np.int32)),
          done=np.zeros(t1, bool),
          observation=(
              rng.randint(0, 255, (t1, h, w, 3)).astype(np.uint8),
              np.zeros((t1, instr_len), np.int32))),
      agent_outputs=AgentOutput(
          action=rng.randint(0, num_actions, t1).astype(np.int32),
          policy_logits=rng.randn(t1, num_actions).astype(np.float32),
          baseline=rng.randn(t1).astype(np.float32)))


def make_example_batch(t1, b, h, w, num_actions, instr_len, seed=0,
                       done_prob=0.05, hidden_size=256):
  """Random ActorOutput batch: [T+1=t1, B=b] time-major trajectory."""
  rng = np.random.RandomState(seed)
  return ActorOutput(
      level_name=jnp.zeros((b,), jnp.int32),
      agent_state=(jnp.zeros((b, hidden_size), jnp.float32),
                   jnp.zeros((b, hidden_size), jnp.float32)),
      env_outputs=StepOutput(
          reward=jnp.asarray(rng.randn(t1, b), jnp.float32),
          info=StepOutputInfo(jnp.zeros((t1, b), jnp.float32),
                              jnp.zeros((t1, b), jnp.int32)),
          done=jnp.asarray(rng.rand(t1, b) < done_prob),
          observation=(
              jnp.asarray(rng.randint(0, 255, (t1, b, h, w, 3)),
                          jnp.uint8),
              jnp.asarray(rng.randint(0, 1000, (t1, b, instr_len)),
                          jnp.int32))),
      agent_outputs=AgentOutput(
          action=jnp.asarray(rng.randint(0, num_actions, (t1, b)),
                             jnp.int32),
          policy_logits=jnp.asarray(rng.randn(t1, b, num_actions),
                                    jnp.float32),
          baseline=jnp.asarray(rng.randn(t1, b), jnp.float32)))

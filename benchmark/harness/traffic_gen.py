"""The one generator of learner traffic: a [T+1, B] batch of actor
unrolls made ON the device, in one jitted call, from the seed and the
parameters a traffic file states. Same seed, same batch.

The distributions are those of `scalable_agent_tpu.testing.
make_example_batch` (which draws on the host with NumPy and ships
tens of MB leaf by leaf): standard-normal rewards, behaviour logits and
baselines, uniform uint8 frames, uniform instruction ids and actions,
Bernoulli(done_prob) episode ends, zero core state.
"""

import jax
import jax.numpy as jnp

from scalable_agent_tpu.models.instruction import (MAX_INSTRUCTION_LEN,
                                                   VOCAB_SIZE)
from scalable_agent_tpu.structs import (ActorOutput, AgentOutput,
                                        StepOutput, StepOutputInfo)


def resident_batch_fn(config, done_prob, hidden_size=256):
  """key -> ActorOutput of [T+1, B] arrays for `config`'s geometry."""
  t1, b = config.unroll_length + 1, config.batch_size
  h, w, a = config.height, config.width, config.num_actions

  def make(key):
    k = jax.random.split(key, 7)
    return ActorOutput(
        level_name=jnp.zeros((b,), jnp.int32),
        agent_state=(jnp.zeros((b, hidden_size), jnp.float32),
                     jnp.zeros((b, hidden_size), jnp.float32)),
        env_outputs=StepOutput(
            reward=jax.random.normal(k[0], (t1, b), jnp.float32),
            info=StepOutputInfo(jnp.zeros((t1, b), jnp.float32),
                                jnp.zeros((t1, b), jnp.int32)),
            done=jax.random.bernoulli(k[1], done_prob, (t1, b)),
            observation=(
                jax.random.randint(k[2], (t1, b, h, w, 3), 0, 255,
                                   jnp.uint8),
                jax.random.randint(k[3], (t1, b, MAX_INSTRUCTION_LEN),
                                   0, VOCAB_SIZE, jnp.int32))),
        agent_outputs=AgentOutput(
            action=jax.random.randint(k[4], (t1, b), 0, a, jnp.int32),
            policy_logits=jax.random.normal(k[5], (t1, b, a),
                                            jnp.float32),
            baseline=jax.random.normal(k[6], (t1, b), jnp.float32)))

  return make


def resident_batch(config, seed, done_prob, shardings=None):
  """The batch, resident on the chip (split over the mesh as
  `shardings` says, where the step is sharded)."""
  make = resident_batch_fn(config, done_prob)
  key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
  if shardings is None:
    return jax.jit(make)(key)
  return jax.jit(make, out_shardings=shardings)(key)

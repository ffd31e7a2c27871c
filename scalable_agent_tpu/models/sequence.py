"""A sequence policy: token embedding -> recurrent core -> norm ->
policy head over the vocabulary and a value head. One of three cores:
the stack of power-retention blocks (models/retention.py), or the one
`core_dims` names by its type: latent attention over a latent cache
with routed experts (models/latent_moe.py :: LatentMoEDims), or
grouped-query attention in a pattern of window and full layers, two
kinds of cache a session, with routed experts (models/
hybrid_attention.py :: HybridAttentionDims).

It answers the same call as `ImpalaAgent` (`prev_actions, env_outputs,
core_state, sample_rng`), so the inference server, the actors and
`learner.loss_fn` run it as they run the paper's agent. What differs is
declared by the agent and read off its output, never switched by a
flag:

- the observation is one leaf, an int32 token id (`observation_names`);
- acting (`sample_rng` given), `AgentOutput.policy_logits` is a SCALAR a
  step: `log mu(action)`. Logits over a vocabulary of 151,936 are 19 MB
  a merged call of 32; the actor needs the action, its log-probability
  and the baseline, so the logits stay on the device and no unroll
  stores them. The learner forms `log_rhos = log pi(a) - log mu(a)`
  from it (`learner.loss_fn`);
- in the learner's pass (`sample_rng` None) `policy_logits` is the full
  `[T, B, vocabulary]`, inside the step's program only.

- a core that computes a chunk of tokens at once says so
  (`prefill_chunk`, from `RecurrentCore.chunk_size`), and the serving
  path then hands a session's prompt over in blocks through
  `prefill`: embedding and core, no head;
- a core whose state grows with the episode says how far
  (`cache_capacity`), one that keeps a ring of an episode's last tokens
  beside it how many (`cache_window`), and the server follows what a
  call's rows read of each;
- a core that counts what a call did (`call_counters`) sows the
  counts, and the inference server reads them with the call's outputs.

The model has no value head of its own: `baseline` is this system's
`w.x + b` on the final norm's output [assumed].
"""

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models import retention
from scalable_agent_tpu.parallel.sharding import (
    merge_time_batch, split_time_batch)
from scalable_agent_tpu.structs import AgentOutput


class SequenceAgent(nn.Module):
  num_actions: int           # the vocabulary
  num_layers: int = 2
  hidden_size: int = 64
  num_heads: int = 4
  num_kv_heads: int = 2
  head_dim: int = 16
  mlp_size: int = 128
  rope_theta: float = 1e6
  norm_eps: float = 1e-6
  scan_unroll: int = 1
  dtype: Any = jnp.float32        # the projections' operands
  param_dtype: Any = jnp.float32  # bfloat16 when served at full width
  # Given: the widths of another core than the retention stack's, which
  # builds it (`core_dims.stack`); `num_kv_heads` and `head_dim` are the
  # retention stack's.
  core_dims: Any = None

  # The leaves of `StepOutput.observation`, in order.
  observation_names = ('token',)

  def core(self, **placement):
    """The core the widths name: detached (for its shapes), or named
    in `__call__`."""
    placement = placement or {'parent': None}
    if self.core_dims is not None:
      return self.core_dims.stack(
          num_layers=self.num_layers, hidden_size=self.hidden_size,
          num_heads=self.num_heads, mlp_size=self.mlp_size,
          rope_theta=self.rope_theta, norm_eps=self.norm_eps,
          dtype=self.dtype, param_dtype=self.param_dtype, **placement)
    return retention.PowerRetentionStack(
        self.num_layers, self.hidden_size, self.num_heads,
        self.num_kv_heads, self.head_dim, self.mlp_size, self.rope_theta,
        self.norm_eps, self.dtype, self.param_dtype, **placement)

  def initial_state(self, batch_size):
    return self.core().initial_state(batch_size)

  def state_arena(self, num_slots):
    return self.core().arena(num_slots)

  @property
  def prefill_chunk(self):
    """Tokens a `prefill` call takes; 0: the prompt comes a token a
    policy call, as every other observation."""
    return self.core().chunk_size

  @property
  def cache_capacity(self):
    """Tokens of an episode a session's state holds; 0: a state of
    fixed size, whatever the episode's length."""
    return self.core().cache_capacity

  @property
  def cache_window(self):
    """Tokens a layer that keeps a ring of the episode's last ones
    holds; 0: the core has no such layer."""
    return self.core().cache_window

  @property
  def call_counters(self):
    return self.core().counters

  def prefill(self, tokens, core_state, slot, n_valid, reset):
    """The chunk call without the head: session `slot` of the arena
    `core_state` advanced by the first `n_valid` of `tokens` i32 [C],
    from an empty state where `reset` -> the arena."""
    return self(None, tokens, core_state, chunk=(slot, n_valid, reset))

  @nn.compact
  def __call__(self, prev_actions, env_outputs, core_state,
               sample_rng=None, level_ids=None,
               compute_pixel_control=False, state_slots=None,
               batch_shards=1, chunk=None):
    """Unroll over a [T, B] trajectory of tokens; see `ImpalaAgent` for
    the arguments (`batch_shards`: the heads' merged rows lie
    shard-major, as there). With `state_slots` (i32 [B]; T must be 1)
    `core_state` is the server's state arena and is returned advanced
    in the rows `state_slots`. With `chunk` the call is `prefill`'s:
    `env_outputs` is the token block, and the arena is all it
    returns."""
    del prev_actions, level_ids, compute_pixel_control  # the token says it
    token = (env_outputs if chunk is not None
             else env_outputs.observation[0])
    with jax.named_scope('embed'):
      table = self.param('embedding', nn.initializers.normal(1.0),
                         (self.num_actions, self.hidden_size),
                         self.param_dtype)
      x = jnp.take(table, token, axis=0).astype(jnp.float32)
    core = self.core(name='core')
    if chunk is not None:  # `prefill`
      slot, n_valid, reset = chunk
      return core.chunk(core_state, x, n_valid, reset, slot)[0]
    done = env_outputs.done
    t, b = token.shape
    if state_slots is None:
      new_state, out = core_lib.unroll(core, core_state, x, done,
                                       self.scan_unroll)
    else:
      assert t == 1, 'the arena form is one step'
      new_state, out = core.step(core_state, x[0], done[0],
                                 slots=state_slots)
    split = functools.partial(split_time_batch, t=t, b=b,
                              shards=batch_shards)
    # (The arena step is one step: its output is [B, hidden] already.)
    flat = (out if state_slots is not None
            else merge_time_batch(out, batch_shards))
    with jax.named_scope('lm_head'):
      n = retention._Scale(self.param_dtype, name='final_norm')(
          flat, self.norm_eps)
      logits = retention._Linear(
          self.num_actions, self.dtype, self.param_dtype,
          name='policy_logits')(n)
      # float32 at full precision: on a TPU a float32 product is one
      # bfloat16 pass unless told otherwise, and 5,120 terms are cheap.
      baseline = nn.Dense(1, dtype=jnp.float32, name='baseline',
                          precision=jax.lax.Precision.HIGHEST)(n)
      baseline = split(baseline[:, 0])
    with jax.named_scope('sample'):
      if sample_rng is None:
        action = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        policy = split(logits)
      else:
        action = jax.random.categorical(
            sample_rng, logits, axis=-1).astype(jnp.int32)
        policy = (jnp.take_along_axis(logits, action[:, None], axis=-1)[
            :, 0] - jax.nn.logsumexp(logits, axis=-1))
        policy = split(policy)
    return AgentOutput(split(action), policy, baseline), new_state

"""The IMPALA agent network (flax.linen), TPU-first.

Re-designs the reference's `class Agent(snt.RNNCore)` (reference:
experiment.py ≈L85–210) for XLA:

- The torso (conv net) is applied to the WHOLE [T, B] unroll at once by
  merging time into the batch dimension — one big MXU-friendly conv batch
  instead of per-step calls (the reference gets this via
  `snt.BatchApply`).
- The recurrent core is a `nn.scan` (lax.scan under jit) over time with
  the per-step done-reset expressed as `jnp.where(done, 0, state)` on the
  carry — the reference does this with a *Python* loop over `tf.unstack`
  + `tf.where` (experiment.py ≈L195–205), which it comments precludes
  fused RNN kernels; the scan form compiles to a single fused XLA loop.
- Heads (policy logits, baseline) again run over the merged [T*B] batch.
- The merged rows lie shard-major (`parallel/sharding.merge_time_batch`):
  with the batch cut into D shards on a mesh, shard d's T × B/D rows
  are one contiguous block, time-major inside it, so the merged axis
  keeps the batch's sharding and each device runs torso and heads over
  its own rows. With D = 1 (one device, T = 1) that is the plain
  time-major reshape. The core's scan and every output see [T, B].

Inputs each step, matching the reference contract: `(last_action,
StepOutput(reward, info, done, (frame, instruction_ids)))`. Rewards are
clipped to [-1, 1] and concatenated with the one-hot last action and the
instruction encoding before the core (reference `_torso` ≈L120).
"""

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from scalable_agent_tpu.structs import AgentOutput, observation_leaves
from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models.torsos import TORSOS
from scalable_agent_tpu.models.instruction import InstructionEncoder
from scalable_agent_tpu.parallel.sharding import (
    merge_time_batch, split_time_batch)
from scalable_agent_tpu.unreal import PixelControlHead


class ImpalaAgent(nn.Module):
  """IMPALA agent: torso → LSTM core → policy/baseline heads."""
  num_actions: int
  torso: str = 'deep'        # 'deep' (reference) | 'shallow' (paper)
  hidden_size: int = 256
  use_instruction: bool = True
  # PopArt (popart.py): >0 ⇒ the value head emits one NORMALIZED value
  # column per task and `level_ids` selects each trajectory's column.
  num_popart_tasks: int = 0
  # UNREAL pixel control (unreal.py): adds the auxiliary deconv Q-head.
  use_pixel_control: bool = False
  pixel_control_cell_size: int = 4
  # Q-head deconv implementation ('deconv' | 'd2s') and output dtype —
  # the round-6 fast-path knobs (config.pixel_control_head_impl /
  # pixel_control_q_f32; parity-gated in tests/test_unreal.py). Both
  # impls share one param tree, so checkpoints are interchangeable.
  pixel_control_head_impl: str = 'deconv'
  pixel_control_q_f32: bool = True
  # Partial unrolling of the LSTM time scan (XLA loop unroll factor):
  # amortizes per-iteration loop overhead on TPU; must divide nothing
  # (lax.scan handles remainders). 1 = plain scan.
  scan_unroll: int = 1
  dtype: jnp.dtype = jnp.float32

  # The leaves of `StepOutput.observation`, in order.
  observation_names = ('frame', 'instr')

  def core(self, **placement):
    """The recurrent core (models/core.py's protocol): detached from
    any parameter tree, for its shapes; or, in `__call__`, under the
    name it had when it was this file's `_ResetCore`, so checkpoints
    keep loading."""
    placement = placement or {'parent': None}
    return core_lib.LSTMCore(self.hidden_size, dtype=self.dtype,
                             **placement)

  def initial_state(self, batch_size):
    """Zeroed LSTM carry (c, h), each [B, hidden] (reference ≈L90)."""
    return self.core().initial_state(batch_size)

  def state_arena(self, num_slots):
    """The inference server's state arena for `num_slots` sessions."""
    return self.core().arena(num_slots)

  @nn.compact
  def __call__(self, prev_actions, env_outputs, core_state,
               sample_rng=None, level_ids=None,
               compute_pixel_control=False, state_slots=None,
               batch_shards=1):
    """Unroll over a [T, B] trajectory.

    Torso, instruction encoder and heads run over the merged [T*B]
    axis, whose rows lie shard-major: row (d*T + t) * B/D + j is
    [t, d*B/D + j] (D = `batch_shards`; D = 1: row t*B + b). Inputs and
    outputs are [T, B, ...] in the caller's order whatever D is.

    Args:
      prev_actions: i32 [T, B] — action taken *before* each timestep.
      env_outputs: StepOutput of [T, B, ...] tensors; observation is
        (frame uint8 [T, B, H, W, C], instruction ids i32 [T, B, L]).
      core_state: LSTM carry (c, h) each [B, hidden] at unroll start.
      sample_rng: PRNG key → actions are sampled from the policy
        (actor/eval path, reference `tf.multinomial` ≈L165); None →
        argmax (learner path, where the action output is unused).
      level_ids: i32 [B] task ids (PopArt only) — selects each
        trajectory's value column. None → task 0 (the act-time path,
        where the recorded baseline is unused by the learner).
      compute_pixel_control: run the auxiliary pixel-control Q-head
        and sow its output as intermediates['pixel_control_q']
        ([T, B, Hc, Wc, A]) — learner path only; actors skip the
        deconv cost. Params exist either way (created at init).
      state_slots: i32 [B] slot ids (the inference server's state
        cache; T must be 1): `core_state` is then the server's state
        ARENA, of which the rows `state_slots` are advanced and which
        is returned in place of the carry (models/core.py).
      batch_shards: static int D, the number of shards the batch dim
        is cut into on the mesh the caller's step was built with
        (`parallel/sharding.batch_shards`); it changes where rows lie
        in the merged axis and no value.

    Returns:
      (AgentOutput([T, B, ...]), final core_state).
    """
    reward, _, done, (frame, instr_ids) = env_outputs
    t, b = reward.shape[0], reward.shape[1]

    # --- Torso over merged time+batch (one big MXU batch). ---
    # (Torso rematerialization was tried and REJECTED: +20% step time
    # at [T=100, B=32] — XLA's remat re-reads more bytes than it
    # saves here. Measurements in docs/PERF.md.)
    # (`torso`, `core` and `heads` are scopes in the compiled program's
    # operation names, which the device trace's per-scope shares read:
    # Flax names its modules by class inside them, and not the scan's
    # own slicing or the reshapes around the heads.)
    merge = functools.partial(merge_time_batch, shards=batch_shards)
    split = functools.partial(split_time_batch, t=t, b=b,
                              shards=batch_shards)
    flat_frame = merge(frame)
    with jax.named_scope('torso'):
      torso_out = TORSOS[self.torso](dtype=self.dtype)(flat_frame)

    clipped_reward = merge(jnp.clip(reward, -1.0, 1.0), trailing=(1,))
    one_hot_action = jax.nn.one_hot(
        merge(prev_actions), self.num_actions, dtype=torso_out.dtype)
    parts = [torso_out, clipped_reward.astype(torso_out.dtype),
             one_hot_action]
    if self.use_instruction:
      flat_ids = merge(instr_ids)
      parts.append(InstructionEncoder(dtype=self.dtype)(flat_ids))
    core_input = split(jnp.concatenate(parts, axis=-1))

    # --- Recurrent core: scan over time with done-reset on the carry. ---
    core = self.core(name='_ResetCore_0')
    with jax.named_scope('core'):
      if state_slots is None:
        core_state = jax.tree_util.tree_map(
            lambda s: s.astype(self.dtype), core_state)
        new_state, core_out = core_lib.unroll(
            core, core_state, core_input, done, self.scan_unroll)
        new_state = jax.tree_util.tree_map(
            lambda s: s.astype(jnp.float32), new_state)
      else:
        assert t == 1, 'the arena form is one step'
        new_state, core_out = core.step(core_state, core_input[0],
                                        done[0], slots=state_slots)

    # --- Heads over merged time+batch. ---
    # (The arena step is one step: its output is [B, hidden] already.)
    flat_core = core_out if state_slots is not None else merge(core_out)
    if self.use_pixel_control and (compute_pixel_control or
                                   self.is_initializing()):
      cell = self.pixel_control_cell_size
      hc, wc = frame.shape[2] // cell, frame.shape[3] // cell
      pc_q = PixelControlHead(self.num_actions, (hc, wc),
                              dtype=self.dtype,
                              head_impl=self.pixel_control_head_impl,
                              out_f32=self.pixel_control_q_f32,
                              name='pixel_control')(flat_core)
      self.sow('intermediates', 'pixel_control_q', split(pc_q))
    with jax.named_scope('heads'):
      policy_logits = nn.Dense(self.num_actions, dtype=self.dtype,
                               name='policy_logits')(flat_core)
      num_values = max(self.num_popart_tasks, 1)
      baseline = nn.Dense(num_values, dtype=self.dtype,
                          name='baseline')(flat_core)
      policy_logits = split(policy_logits.astype(jnp.float32))
      baseline = split(baseline.astype(jnp.float32))
      if self.num_popart_tasks:
        if level_ids is None:
          level_ids = jnp.zeros((b,), jnp.int32)
        baseline = jnp.take_along_axis(
            baseline, level_ids[None, :, None].astype(jnp.int32),
            axis=2)
      baseline = baseline[..., 0]

      if sample_rng is not None:
        action = jax.random.categorical(sample_rng, policy_logits, axis=-1)
      else:
        action = jnp.argmax(policy_logits, axis=-1)
      action = action.astype(jnp.int32)

    return AgentOutput(action, policy_logits, baseline), new_state


def make_step_fn(agent):
  """Single-step (T=1) policy for actors: batch-shaped, no time axis.

  Returns f(params, rng, prev_action [B], env_output of [B, ...],
  core_state) → (AgentOutput of [B, ...], new_state). Jit this and serve
  it behind the dynamic batcher.
  """

  @functools.partial(jax.jit, static_argnums=())
  def step(params, rng, prev_action, env_output, core_state):
    env_output_t = jax.tree_util.tree_map(lambda x: x[None], env_output)
    out, new_state = agent.apply(
        params, prev_action[None], env_output_t, core_state,
        sample_rng=rng)
    return jax.tree_util.tree_map(lambda x: x[0], out), new_state

  return step


def init_params(agent, rng, obs_spec, batch_size=1):
  """Initialize parameters from an observation spec.

  obs_spec: dict with 'frame' (H, W, C) uint8 and 'instr_len' L, or
  with the observation's 'leaves' (structs.observation_leaves).
  """
  t, b = 2, batch_size
  from scalable_agent_tpu.structs import StepOutput, StepOutputInfo
  dummy = StepOutput(
      reward=jnp.zeros((t, b), jnp.float32),
      info=StepOutputInfo(jnp.zeros((t, b), jnp.float32),
                          jnp.zeros((t, b), jnp.int32)),
      done=jnp.zeros((t, b), bool),
      observation=tuple(jnp.zeros((t, b) + tuple(shape), dtype)
                        for shape, dtype in observation_leaves(obs_spec)))
  prev_actions = jnp.zeros((t, b), jnp.int32)
  return agent.init(rng, prev_actions, dummy,
                    agent.initial_state(b))

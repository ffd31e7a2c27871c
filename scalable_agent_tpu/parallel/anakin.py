"""Anakin mode: acting + learning fused into ONE jitted device step.

The production path (driver.py) is Sebulba-shaped (Podracer
architectures, arXiv:2104.06272): C++/CPU simulators on the host feed
a TPU learner through the batcher/buffer pipeline, because DMLab/ALE
can only ever be host processes (reference: environments.py ≈L60
PyProcessDmLab). But the framework's CI tasks (envs/fake.py bandit /
cue-memory) are pure state machines — for these, the TPU-idiomatic
architecture is Podracer's *Anakin*: put the environment INSIDE the
jitted step, `lax.scan` the act→env→act rollout on device, and feed
the trajectory straight into the same learner update, with zero host
transport, zero inference servers, zero Python in the loop.

What this buys:
- research-mode throughput on the CI tasks (no host round trips; the
  whole unroll+update is one XLA program), and
- a one-file demonstration that acting and learning are the SAME
  functional pieces everywhere: this module reuses `ImpalaAgent`
  unchanged (T=1 apply for acting, [T+1, B] apply inside the update)
  and `learner.make_train_step_fn` unchanged — there is exactly one
  IMPALA loss/update in the codebase.

Semantics mirror the host actor loop (runtime/actor.py) exactly:
T+1 overlap frame (timestep 0 of an unroll = last timestep of the
previous one), `agent_state` = LSTM carry at unroll start, flow-style
episode stats (the emitted StepOutputInfo carries final stats at done;
the carried state resets), initial env_output has done=True with a
zero/priming agent_output. Because acting uses the pre-update params
of the same step, behaviour == target at loss time and V-trace's rhos
are 1 for the T timesteps acted THIS step — the on-policy special
case (the correction machinery still runs; tests pin this). The one
exception is the t=0 overlap timestep: its behaviour logits came from
the PREVIOUS fused step's pre-update params, so it carries exactly
one update of policy lag (same as the host pipeline's overlap frame).

Scale-out: `init_carry(..., mesh=...)` / `run(..., mesh=...)` shard
every batch-leading leaf over the mesh's data axis — each device steps
its slice of the environments and the learner locally, params
replicate, and jit inserts the gradient psum over ICI (same placement
discipline as train_parallel.py; `test_anakin_shards_over_the_mesh`).

Round 16 promoted this module to a FIRST-CLASS RUNTIME
(`--runtime=anakin` → driver.train_anakin: the fused loop under the
full production lifecycle — checkpoint ladder, health ladder, SLO
verdict, summaries/incidents), widened the jittable env family
(envs/jittable.py gridworld + procgen cores, registered in ENV_CORES
below AND as host envs so the same task runs under both runtimes),
and added the HYBRID FILLER (`HybridFiller` at the bottom: Anakin
self-play on the fleet runtime's idle learner slices, bounded to one
step per feed probe, with every fleet clock left on the fresh-frame
count). docs/PARALLELISM.md and RUNBOOK §13 carry the operator story.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from scalable_agent_tpu import learner
from scalable_agent_tpu import population
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.structs import (ActorOutput, AgentOutput,
                                        StepOutput, StepOutputInfo)


class EnvCoreState(NamedTuple):
  """Batched functional env state (all [B] unless noted)."""
  rng: Any            # PRNG key []
  context: Any        # i32 [B] — bandit target / memory cue
  step_in_episode: Any  # i32 [B]
  episode_return: Any   # f32 [B] — flow-style carried stats
  episode_frames: Any   # i32 [B]


def _frame_from_channel(channel, batch, height, width, visible=None):
  """uint8 [B, H, W, 3] with `channel`'s plane at 255 (optionally
  masked per-env by `visible`)."""
  plane = jax.nn.one_hot(channel, 3, dtype=jnp.float32) * 255.0
  if visible is not None:
    plane = plane * visible[:, None].astype(jnp.float32)
  plane = plane.astype(jnp.uint8)  # [B, 3]
  return jnp.broadcast_to(plane[:, None, None, :],
                          (batch, height, width, 3))


def _zero_instr(batch):
  return jnp.zeros((batch, MAX_INSTRUCTION_LEN), jnp.int32)


class BanditCore:
  """Jittable ContextualBanditEnv (envs/fake.py): the frame's dominant
  color channel is the rewarded action; `episode_length` steps per
  context. Same rewards, episode shape, and stats semantics as the
  host version — property-tested side by side.

  `num_actions` widens the policy head exactly like the host env does
  (the target stays `randint(num_actions) % 3`, the host's own draw):
  the hybrid filler (HybridFiller) runs this core under the MAIN
  task's action space, so a dmlab fleet's idle learner slices can
  self-play without a second policy head."""

  def __init__(self, height=24, width=32, episode_length=5,
               num_action_repeats=1, num_actions=3):
    if num_actions < 1:
      raise ValueError(f'num_actions must be >= 1, got {num_actions}')
    self.height, self.width = height, width
    self.episode_length = episode_length
    self.num_action_repeats = num_action_repeats
    self.num_actions = num_actions

  def _observation(self, state, visible=None):
    frame = _frame_from_channel(state.context, state.context.shape[0],
                                self.height, self.width, visible)
    return (frame, _zero_instr(state.context.shape[0]))

  def _sample_context(self, rng, shape):
    # Mirrors the host env exactly: randint(num_actions) % 3 — the
    # rewarded channel is always 0..2 regardless of head width.
    return jax.random.randint(rng, shape, 0, self.num_actions) % 3

  def init(self, rng, batch) -> Tuple[EnvCoreState, StepOutput]:
    rng, sub = jax.random.split(rng)
    state = EnvCoreState(
        rng=rng,
        context=self._sample_context(sub, (batch,)),
        step_in_episode=jnp.zeros((batch,), jnp.int32),
        episode_return=jnp.zeros((batch,), jnp.float32),
        episode_frames=jnp.zeros((batch,), jnp.int32))
    # Mirrors runtime/actor.py's priming output: done=True (first obs
    # starts an episode), zero reward/stats.
    output = StepOutput(
        reward=jnp.zeros((batch,), jnp.float32),
        info=StepOutputInfo(jnp.zeros((batch,), jnp.float32),
                            jnp.zeros((batch,), jnp.int32)),
        done=jnp.ones((batch,), bool),
        observation=self._observation(state))
    return state, output

  def step(self, state: EnvCoreState, action
           ) -> Tuple[EnvCoreState, StepOutput]:
    reward = (action == state.context).astype(jnp.float32)
    step_count = state.step_in_episode + 1
    done = step_count >= self.episode_length

    ep_return = state.episode_return + reward
    ep_frames = state.episode_frames + self.num_action_repeats
    info = StepOutputInfo(ep_return, ep_frames)  # emitted: incl. done
    zero_f = jnp.zeros_like(ep_return)
    zero_i = jnp.zeros_like(ep_frames)

    rng, sub = jax.random.split(state.rng)
    fresh = self._sample_context(sub, action.shape)
    new_state = EnvCoreState(
        rng=rng,
        context=jnp.where(done, fresh, state.context),
        step_in_episode=jnp.where(done, 0, step_count),
        episode_return=jnp.where(done, zero_f, ep_return),
        episode_frames=jnp.where(done, zero_i, ep_frames))
    output = StepOutput(reward=reward, info=info, done=done,
                        observation=self._observation(new_state))
    return new_state, output


class CueMemoryCore:
  """Jittable CueMemoryEnv (envs/fake.py): two-step episodes, cue
  visible only on the first frame, fixed-action-0 bonus on the first
  step (relay-proof), match-the-cue reward on the second."""

  def __init__(self, height=16, width=16, episode_length=2,
               num_action_repeats=1, num_actions=3):
    del episode_length  # fixed two-step episodes, like the host env
    if num_actions != 3:
      # Mirrors the host CueMemoryEnv: one action per RGB cue channel.
      raise ValueError('CueMemoryCore is a 3-action task (one action '
                       'per RGB cue channel); got num_actions='
                       f'{num_actions}')
    self.height, self.width = height, width
    self.num_action_repeats = num_action_repeats
    self.num_actions = 3

  def _observation(self, state):
    visible = state.step_in_episode == 0  # cue only pre-first-action
    frame = _frame_from_channel(state.context, state.context.shape[0],
                                self.height, self.width, visible)
    return (frame, _zero_instr(state.context.shape[0]))

  def init(self, rng, batch) -> Tuple[EnvCoreState, StepOutput]:
    rng, sub = jax.random.split(rng)
    state = EnvCoreState(
        rng=rng,
        context=jax.random.randint(sub, (batch,), 0, 3),
        step_in_episode=jnp.zeros((batch,), jnp.int32),
        episode_return=jnp.zeros((batch,), jnp.float32),
        episode_frames=jnp.zeros((batch,), jnp.int32))
    output = StepOutput(
        reward=jnp.zeros((batch,), jnp.float32),
        info=StepOutputInfo(jnp.zeros((batch,), jnp.float32),
                            jnp.zeros((batch,), jnp.int32)),
        done=jnp.ones((batch,), bool),
        observation=self._observation(state))
    return state, output

  def step(self, state: EnvCoreState, action
           ) -> Tuple[EnvCoreState, StepOutput]:
    first = state.step_in_episode == 0
    reward = jnp.where(
        first,
        jnp.where(action == 0, 2.0, 0.0),              # info-free bonus
        (action == state.context).astype(jnp.float32))  # recall
    done = ~first

    ep_return = state.episode_return + reward
    ep_frames = state.episode_frames + self.num_action_repeats
    info = StepOutputInfo(ep_return, ep_frames)

    rng, sub = jax.random.split(state.rng)
    fresh = jax.random.randint(sub, action.shape, 0, 3)
    new_state = EnvCoreState(
        rng=rng,
        context=jnp.where(done, fresh, state.context),
        step_in_episode=jnp.where(done, 0, 1),
        episode_return=jnp.where(done, jnp.zeros_like(ep_return),
                                 ep_return),
        episode_frames=jnp.where(done, jnp.zeros_like(ep_frames),
                                 ep_frames))
    output = StepOutput(reward=reward, info=info, done=done,
                        observation=self._observation(new_state))
    return new_state, output


# The jittable env registry: the two CI cores above plus the round-16
# pure-JAX family (gridworld + the procgen-style parameterized
# generator — envs/jittable.py, which also registers the SAME cores as
# host environments through envs/factory.py: the dual registration the
# runtime-axis parity gate rides on). config.JITTABLE_BACKENDS mirrors
# these keys as literals (config.py cannot import this module);
# tests/test_anakin.py pins the two in sync.
from scalable_agent_tpu.envs import jittable as _jittable  # noqa: E402

ENV_CORES = {'bandit': BanditCore, 'cue_memory': CueMemoryCore,
             **_jittable.JITTABLE_CORES}


def make_env_core(config: Config, num_actions: Optional[int] = None):
  """Construct the jittable core a config names. `num_actions`
  overrides the head width (the hybrid filler passes the MAIN task's);
  falls back to config.num_actions, then the core's default. A core
  that cannot honor the width raises (CueMemoryCore is fixed at 3)."""
  if config.env_backend not in ENV_CORES:
    raise ValueError(
        f'anakin needs a jittable env core, got '
        f'{config.env_backend!r} (available: {sorted(ENV_CORES)}); '
        'real simulators use the host pipeline (driver.train)')
  core_cls = ENV_CORES[config.env_backend]
  kwargs = dict(height=config.height, width=config.width,
                episode_length=config.episode_length,
                num_action_repeats=config.num_action_repeats)
  if config.env_backend == 'procgen':
    # The level-set + curriculum knobs (round 22) are procgen-only:
    # the finite level-id space is what the prioritized sampler
    # drives. The hybrid filler reaches here through its own config
    # copy, so a procgen filler runs the same curriculum.
    kwargs.update(
        num_levels=config.procgen_num_levels,
        wall_density=config.procgen_wall_density,
        curriculum=config.curriculum,
        curriculum_temperature=config.curriculum_temperature,
        curriculum_eps=config.curriculum_eps)
  width = num_actions if num_actions is not None else config.num_actions
  if width is not None:
    kwargs['num_actions'] = width
  return core_cls(**kwargs)


class AnakinCarry(NamedTuple):
  """Everything that persists across fused steps (all device-side)."""
  train_state: Any   # learner.TrainState
  env_state: Any     # EnvCoreState
  env_output: Any    # StepOutput [B] — the pending overlap timestep
  agent_output: Any  # AgentOutput [B] — ditto
  core_state: Any    # LSTM carry (c, h) [B, hidden]
  rng: Any


class EnvCarry(NamedTuple):
  """The non-learner half of AnakinCarry: everything the fused loop
  threads BESIDES the train state. Split out (round 16) so the hybrid
  filler can persist its env-side state across fill slices while
  borrowing the LIVE fleet TrainState at each slice."""
  env_state: Any
  env_output: Any
  agent_output: Any
  core_state: Any
  rng: Any


def init_env_carry(agent, env_core, config: Config, rng,
                   mesh=None) -> EnvCarry:
  """Initial env/agent-side carry for `make_anakin_step` (no params —
  see `init_carry` for the composed whole).

  With `mesh`, every [B]-leading leaf (env state, pending outputs,
  LSTM carry) shards over the data axis. Core states are NamedTuples
  whose `rng` field is the one replicated-by-name leaf ([2]u32 —
  shape-sniffing would misplace it at b=2); every other leaf is
  [B]-leading by the ENV_CORES protocol."""
  b = config.batch_size
  if mesh is not None:
    from scalable_agent_tpu.parallel import mesh as mesh_lib
    if b % mesh.shape[mesh_lib.DATA_AXIS] != 0:
      # Before any init work — a full env init would be wasted.
      raise ValueError(
          f'batch_size={b} not divisible by the data axis '
          f'({mesh.shape[mesh_lib.DATA_AXIS]} devices)')
  rng, env_rng = jax.random.split(rng)
  env_state, env_output = env_core.init(env_rng, b)
  agent_output = AgentOutput(  # actor.py's priming output
      action=jnp.zeros((b,), jnp.int32),
      policy_logits=jnp.zeros((b, env_core.num_actions), jnp.float32),
      baseline=jnp.zeros((b,), jnp.float32))
  core_state = agent.initial_state(b)
  if mesh is None:
    return EnvCarry(env_state, env_output, agent_output, core_state,
                    rng)

  from scalable_agent_tpu.parallel import sharding as sharding_lib
  data = sharding_lib.data_sharding(mesh)
  replicated = sharding_lib.replicated(mesh)

  def place(x):
    x = jnp.asarray(x)
    batch_leading = x.ndim >= 1 and x.shape[0] == b
    return jax.device_put(x, data if batch_leading else replicated)

  # The core's PRNG key is pinned replicated BY NAME (the ENV_CORES
  # state protocol — every jittable core's state is a NamedTuple with
  # an `rng` field; gridworld/procgen ride the same rule). Captured
  # BEFORE the shape-sniffing placement, which would mis-shard the
  # [2]u32 key whenever b == 2. The procgen curriculum accumulators
  # ([num_levels] leaves, round 22) are replicated by name for the
  # same reason: num_levels == b would shape-sniff them onto the data
  # axis, splitting the one global score table the sampler reads.
  by_name = {'rng': env_state.rng}
  for field in ('level_scores', 'level_visits'):
    if hasattr(env_state, field):
      by_name[field] = getattr(env_state, field)
  env_state = jax.tree_util.tree_map(place, env_state)
  env_state = env_state._replace(
      **{k: jax.device_put(v, replicated) for k, v in by_name.items()})
  env_output, agent_output, core_state = jax.tree_util.tree_map(
      place, (env_output, agent_output, core_state))
  return EnvCarry(env_state, env_output, agent_output, core_state,
                  jax.device_put(rng, replicated))


def init_carry(agent, env_core, config: Config, rng,
               mesh=None) -> AnakinCarry:
  """Initial params/opt/env/agent state for `make_anakin_step`.

  With `mesh`, this IS Anakin's scale-out story: every [B]-leading
  leaf (env state, pending outputs, LSTM carry) shards over the data
  axis — each device runs its slice of the environments AND the
  learner locally; params/opt replicate and only the gradient psum
  crosses ICI (inserted by jit from these placements, exactly like
  parallel/train_parallel.py)."""
  from scalable_agent_tpu.models import init_params
  rng, params_rng = jax.random.split(rng)
  env = init_env_carry(agent, env_core, config, rng, mesh=mesh)
  obs_spec = {'frame': (env_core.height, env_core.width, 3),
              'instr_len': MAX_INSTRUCTION_LEN}
  params = init_params(agent, params_rng, obs_spec)
  if mesh is None:
    train_state = learner.make_train_state(params, config)
  else:
    from scalable_agent_tpu.parallel import train_parallel
    train_state = train_parallel.make_sharded_train_state(
        params, config, mesh)
  return AnakinCarry(train_state, *env)


def make_anakin_step(agent, env_core, config: Config,
                     return_batch: bool = False,
                     train_step_fn=None,
                     advance_steps: bool = True,
                     mesh=None,
                     traced_hypers: bool = False,
                     jit: bool = True):
  """One fused device step: scan T acting steps, then the SGD update.

  Returns jitted `f(carry) -> (carry, metrics)` (donating the carry);
  with `return_batch` the assembled [T+1, B] ActorOutput is added to
  the metrics dict under 'batch' (alignment tests).

  `train_step_fn` (round 16, the hybrid filler): an externally built
  raw train step — the filler passes the FLEET config's, so the loss
  hyperparameters, the in-graph health guard, and the LR schedule all
  stay exactly the fleet's while this `config` only shapes the
  on-device rollout (filler backend / batch / unroll).

  `advance_steps=False` pins `update_steps` across the fused step (the
  filler contract: filler updates must not advance the frame budget,
  the LR clock, or the checkpoint step numbering — every clock the
  run exposes stays on the fleet's fresh-frame count; IMPACT's
  staleness tolerance, arXiv 1912.00167, is why an off-cadence update
  against the frozen clock is a legal move).

  `mesh` (round 22): only consulted by the curriculum block — the
  updated [num_levels] score table is constrained back to REPLICATED
  so the carry's placement is a fixed point (without the constraint
  the partitioner shards the segment-sum output over data, and the
  sharding flip forces a second compile at step 2).

  `traced_hypers` / `jit` (round 23, the vectorized population): with
  traced_hypers the step becomes f(carry, hypers) — hypers a dict of
  traced {'learning_rate', 'entropy_cost'} scalars threaded into the
  learner's traced-hypers train step. jit=False returns the RAW
  function instead of jitting it, so make_vectorized_anakin_step can
  jax.vmap it over a leading member axis before the one jit."""
  if train_step_fn is None:
    train_step_fn = learner.make_train_step_fn(
        agent, config, traced_hypers=traced_hypers)
  t = config.unroll_length
  # Python-level gate (round 22): the curriculum block only traces for
  # cores with a finite level-id space (procgen). The sampler itself
  # lives in the core's _fresh_episode; THIS side accumulates the
  # per-level priority EMAs from the unroll's own TD errors — acting
  # baselines are already in the batch (AgentOutput.baseline), so the
  # whole loop (score → sample → act → score) is one XLA program with
  # zero host round trips per level decision.
  use_curriculum = (config.curriculum != 'uniform'
                    and hasattr(env_core, 'num_levels'))

  def anakin_step(carry: AnakinCarry, hypers=None):
    initial_core_state = carry.core_state
    params = carry.train_state.params  # pre-update: behaviour == target

    def acting_step(acting_carry, _):
      env_state, env_output, agent_output, core_state, rng = (
          acting_carry)
      rng, sample_rng = jax.random.split(rng)
      # T=1 apply of the SAME agent the learner unrolls — one model.
      # (`acting`, `env` and `learn` are scopes in the fused program's
      # operation names: the device trace's per-scope shares tell the
      # environment from the agent by them.)
      with jax.named_scope('acting'):
        out_t, new_core = agent.apply(
            params, agent_output.action[None],
            jax.tree_util.tree_map(lambda x: x[None], env_output),
            core_state, sample_rng=sample_rng)
      new_agent_output = jax.tree_util.tree_map(lambda x: x[0], out_t)
      with jax.named_scope('env'):
        new_env_state, new_env_output = env_core.step(
            env_state, new_agent_output.action)
      # Pre-step level ids: the level each transition was PLAYED in
      # (step resamples at done, so the post-step id may already be
      # next episode's).
      ys = (new_env_output, new_agent_output)
      if use_curriculum:
        ys = ys + (env_state.level_id,)
      return ((new_env_state, new_env_output, new_agent_output,
               new_core, rng), ys)

    (env_state, env_output, agent_output, core_state, rng), tail = (
        jax.lax.scan(
            acting_step,
            (carry.env_state, carry.env_output, carry.agent_output,
             carry.core_state, carry.rng),
            None, length=t))
    # T+1 assembly with the overlap frame (actor.py unroll()).
    batch = ActorOutput(
        level_name=jnp.zeros((config.batch_size,), jnp.int32),
        agent_state=initial_core_state,
        env_outputs=jax.tree_util.tree_map(
            lambda first, rest: jnp.concatenate([first[None], rest]),
            carry.env_output, tail[0]),
        agent_outputs=jax.tree_util.tree_map(
            lambda first, rest: jnp.concatenate([first[None], rest]),
            carry.agent_output, tail[1]))
    with jax.named_scope('learn'):
      if traced_hypers:
        new_train_state, metrics = train_step_fn(carry.train_state,
                                                 batch, hypers)
      else:
        new_train_state, metrics = train_step_fn(carry.train_state,
                                                 batch)
    if not advance_steps:
      new_train_state = new_train_state._replace(
          update_steps=carry.train_state.update_steps)
    metrics['mean_reward'] = jnp.mean(batch.env_outputs.reward[1:])
    if use_curriculum:
      # In-graph per-level score update from this unroll's own TD
      # errors. Alignment (learner.py): baseline[i] = V(o_{i-1}),
      # reward[i]/done[i] describe the o_{i-1} -> o_i transition, so
      # delta_i = r[i] + gamma*(1-d[i])*V(o_i) - V(o_{i-1}) needs
      # baseline[i+1] — the T-1 transitions i in [1, T). tail[2][j]
      # is the PRE-step level of the transition that produced
      # env_output j+1, so transition i maps to tail[2][i-1].
      # unroll_length=1 yields an empty update (pure decay) —
      # validate_population warns at spin-up.
      v = batch.agent_outputs.baseline                  # [T+1, B]
      r = batch.env_outputs.reward
      d = batch.env_outputs.done.astype(jnp.float32)
      delta = (r[1:t] + config.discounting * (1.0 - d[1:t]) * v[2:]
               - v[1:t])                                # [T-1, B]
      signal = population.score_signal(delta, config.curriculum)
      scores, visits = population.update_scores(
          env_state.level_scores, env_state.level_visits,
          tail[2][:t - 1], signal, config.curriculum_alpha,
          config.curriculum_decay)
      if mesh is not None:
        # Pin the table back to replicated (see the docstring): the
        # carry's placement must be a fixed point of the step.
        from scalable_agent_tpu.parallel import sharding as \
            sharding_lib
        rep = sharding_lib.replicated(mesh)
        scores = jax.lax.with_sharding_constraint(scores, rep)
        visits = jax.lax.with_sharding_constraint(visits, rep)
      env_state = env_state._replace(
          level_scores=scores, level_visits=visits)
      metrics.update(population.curriculum_metrics(
          scores, visits, config.curriculum_temperature,
          config.curriculum_eps))
    if return_batch:
      metrics['batch'] = batch
    return (AnakinCarry(new_train_state, env_state, env_output,
                        agent_output, core_state, rng),
            metrics)

  if not jit:
    return anakin_step
  return jax.jit(anakin_step, donate_argnums=(0,))


def make_vectorized_anakin_step(agent, env_core, config: Config):
  """One compiled program that advances N PBT members in lockstep.

  vmaps the *raw* (unjitted) fused act+learn step over a leading
  member axis of both the carry and the per-member hyper dict, then
  jits the vmapped function once with the stacked carry donated.
  Member programs must be structurally identical (same suite, same
  shapes) — only (learning_rate, entropy_cost) vary, and those enter
  as traced scalars so PBT explore never retriggers compilation.

  Returns a function `step(stacked_carry, hypers) -> (stacked_carry,
  stacked_metrics)` where `hypers` is a dict of f32[N] arrays with
  keys 'learning_rate' and 'entropy_cost', and every metric leaf
  gains a leading member axis.
  """
  raw_step = make_anakin_step(agent, env_core, config,
                              traced_hypers=True, jit=False)
  return jax.jit(jax.vmap(raw_step), donate_argnums=(0,))


def init_stacked_carry(agent, env_core, config: Config, seeds):
  """Stacks per-member initial carries along a leading member axis.

  Each member gets its own PRNG stream (and therefore its own env
  reset and weight init) from its entry in `seeds`; the results are
  tree-stacked so a single vmapped step advances all members.
  """
  carries = [init_carry(agent, env_core, config, jax.random.PRNGKey(s))
             for s in seeds]
  return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)


def build_run(config: Config, mesh=None,
              rng_seed: Optional[int] = None):
  """Shared construction for run()/train()/driver.train_anakin():
  validated env core, agent, jitted fused step, initial carry."""
  from scalable_agent_tpu import driver
  # The core honors config.num_actions the way the host factory does
  # (wider heads are legal where the host env accepts them: bandit,
  # gridworld, procgen); a core that cannot (CueMemoryCore is a fixed
  # 3-action task) raises here — silently building a differently-
  # shaped policy head than driver.train would for the same Config
  # would make params/checkpoints incompatible between the runtimes.
  env_core = make_env_core(config)
  agent = driver.build_agent(config, env_core.num_actions)
  step = make_anakin_step(agent, env_core, config, mesh=mesh)
  seed = config.seed if rng_seed is None else rng_seed
  carry = init_carry(agent, env_core, config, jax.random.PRNGKey(seed),
                     mesh=mesh)
  return env_core, agent, step, carry


def _cpu_mesh_sync_every(mesh) -> Optional[int]:
  """CPU-emulated meshes (xla_force_host_platform_device_count) run one
  thread per virtual device; on an oversubscribed host a long async
  chain can starve one device >40 s behind its peers at a collective,
  tripping XLA's rendezvous watchdog (observed at ~60 queued sharded
  steps on an oversubscribed CI host). Periodic syncs bound the queue
  there; real chips keep pace and skip them (a sync stalls the async
  dispatch chain)."""
  return 8 if (mesh is not None
               and jax.default_backend() == 'cpu') else None


def run(config: Config, num_steps: int, rng_seed: Optional[int] = None,
        env_backend: Optional[str] = None, mesh=None):
  """Convenience runner: build agent + env core, run `num_steps` fused
  steps, return (carry, list-of-metrics, env_frames_per_sec). Pass
  `mesh` to shard the env batch over the data axis (multi-chip).

  rng_seed=None (the default) honors config.seed, matching
  build_run()/driver.train_anakin — it used to pin seed 0, which made
  two configs differing only in `seed` produce identical runs."""
  import dataclasses
  import time
  if num_steps < 1:
    raise ValueError(f'num_steps must be >= 1, got {num_steps}')
  if env_backend is not None and env_backend != config.env_backend:
    config = dataclasses.replace(config, env_backend=env_backend)
  _, _, step, carry = build_run(config, mesh=mesh, rng_seed=rng_seed)

  carry, metrics = step(carry)  # compile + step 1
  history = [metrics]
  float(jax.device_get(metrics['total_loss']))  # compile barrier
  sync_every = _cpu_mesh_sync_every(mesh)
  t0 = time.perf_counter()
  for i in range(num_steps - 1):
    carry, metrics = step(carry)
    history.append(metrics)  # async — no per-step readback
    if sync_every is not None and i % sync_every == sync_every - 1:
      jax.block_until_ready(metrics['total_loss'])
  # ONE value readback as the timing barrier: the value cannot exist
  # before the last step has finished.
  float(jax.device_get(history[-1]['total_loss']))
  dt = time.perf_counter() - t0
  # First (compile) step excluded from timing; num_steps=1 has no
  # timed window at all.
  frames = (num_steps - 1) * config.frames_per_step
  fps = frames / dt if num_steps > 1 and dt > 0 else float('nan')
  return carry, [jax.device_get(m) for m in history], fps


def supports_filler(config: Config, mesh=None) -> Tuple[bool, str]:
  """Whether THIS topology can run the hybrid filler: (ok, reason).

  Topology limits degrade to plain parking with a warning (the
  staging-mode fallback pattern — the run is still correct, just
  unfilled); everything else about the knob group (a non-jittable
  backend, a filler core that cannot honor the main task's
  action-space width) is a CONFIG error and fails at spin-up instead:
  the driver only consults this gate, it never swallows construction
  errors."""
  if jax.process_count() > 1:
    # Fill decisions are per-host (each host's prefetcher idles on its
    # own schedule) but a filler step over a multi-process mesh is a
    # COLLECTIVE — unsynchronized invocation deadlocks, synchronized
    # invocation would stall the busy hosts. Park instead.
    return False, ('multi-process topology: filler steps are '
                   'collectives but idle slices are per-host')
  if mesh is None:
    return True, ''
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  if mesh.shape[mesh_lib.MODEL_AXIS] > 1:
    return False, 'the anakin filler is data-parallel only (model-' \
                  'axis mesh in use)'
  data = mesh.shape[mesh_lib.DATA_AXIS]
  if config.resolved_filler_batch_size % data != 0:
    return False, (f'filler batch {config.resolved_filler_batch_size} '
                   f'not divisible by the data axis ({data} devices)')
  return True, ''


class HybridFiller:
  """Anakin self-play as a FILLER workload on the learner chips
  (round 16, ROADMAP item 3's creative step).

  The regime: BENCH r9 measured an env-bound feed at ~150 fps against
  ~300k fps of learner capacity — >99% of the learner plane idles
  whenever the env plane is the bound. The driver's fleet loop
  (driver.train) consults `fill_one` exactly when the prefetcher has
  NO staged batch ready (the ready-without-dequeue probe): one fused
  Anakin self-play step runs on the learner chips, then the feed is
  re-probed — so a staged batch is never delayed by more than one
  filler step (`fill_one` BLOCKS on the step's completion; the bound
  is structural, not statistical). IMPACT's staleness tolerance
  (arXiv 1912.00167) is why interleaving off-cadence updates from a
  different data stream is a legal move — and why
  config.validate_runtime cross-links the knob with
  `--surrogate=impact`.

  Clock discipline (the PR 7 serve-time attribution, extended): the
  filler's train step is built from the FLEET config
  (`make_anakin_step(train_step_fn=...)`) and runs with
  `advance_steps=False`, so the frame budget, the LR schedule, the
  checkpoint step numbering, and the fps meter all stay on the
  fleet's fresh-frame clock; filler work is accounted SEPARATELY
  (`updates`/`frames` here, the `driver/filler_updates` registry
  counter, and the driver's filler_updates/filler_frames summary
  scalars).

  Pure-DP only: the fused step shards the env batch over the data
  axis exactly like init_env_carry; a model-axis mesh raises and the
  driver falls back to plain parking with a warning.
  """

  def __init__(self, agent, config: Config, num_actions: int,
               mesh=None):
    import dataclasses
    from scalable_agent_tpu import telemetry
    backend = config.resolved_filler_backend
    if backend not in ENV_CORES:
      raise ValueError(
          f'filler backend {backend!r} is not a jittable env core '
          f'(available: {sorted(ENV_CORES)})')
    if mesh is not None:
      from scalable_agent_tpu.parallel import mesh as mesh_lib
      if mesh.shape[mesh_lib.MODEL_AXIS] > 1:
        raise ValueError('the anakin filler is data-parallel only '
                         '(model-axis mesh in use)')
    self._config = dataclasses.replace(
        config,
        env_backend=backend,
        batch_size=config.resolved_filler_batch_size,
        unroll_length=config.resolved_filler_unroll_length,
        num_actions=None)
    core = make_env_core(self._config, num_actions=num_actions)
    # The FLEET config's raw train step: loss hyperparameters, the
    # in-graph non-finite guard, and the LR schedule stay the fleet's
    # (the schedule reads update_steps, which advance_steps=False
    # freezes at the fleet's count — filler updates apply at the LR
    # the fleet is currently training at).
    train_fn = learner.make_train_step_fn(agent, config)
    self._step = make_anakin_step(agent, core, self._config,
                                  train_step_fn=train_fn,
                                  advance_steps=False, mesh=mesh)
    self._env = init_env_carry(
        agent, core, self._config,
        jax.random.PRNGKey(config.seed + 7777), mesh=mesh)
    self.backend = backend
    self.updates = 0
    self.skipped = 0
    self.frames_per_update = (self._config.batch_size *
                              self._config.unroll_length *
                              config.num_action_repeats)
    self._counter = telemetry.counter('driver/filler_updates')

  @property
  def frames(self) -> int:
    """Cumulative FILLER env frames — never mixed into the fleet's
    fresh-frame budget/fps; the separate summary curve."""
    return self.updates * self.frames_per_update

  def fill_one(self, train_state):
    """One bounded self-play slice: run a fused Anakin step on the
    live train state and BLOCK until it completes (the one-filler-step
    delay bound a just-staged batch sees). Returns the updated train
    state; env-side carry persists here across slices."""
    carry = AnakinCarry(train_state, *self._env)
    carry, metrics = self._step(carry)
    # The completion barrier IS the yield bound: a staged batch that
    # landed while this step ran is picked up immediately after.
    step_ok = metrics.get('step_ok')
    if step_ok is not None:
      loss_ok = jax.device_get(step_ok)
      if float(loss_ok) < 0.5:
        # The in-graph guard already withheld the non-finite update
        # (params carried over); count it — a filler stream must
        # never be able to poison the fleet's params silently.
        self.skipped += 1
    else:
      jax.block_until_ready(metrics['total_loss'])
    self.updates += 1
    self._counter.inc()
    self._env = EnvCarry(carry.env_state, carry.env_output,
                         carry.agent_output, carry.core_state,
                         carry.rng)
    return carry.train_state

  def stats(self):
    return {'updates': self.updates, 'frames': self.frames,
            'skipped': self.skipped, 'backend': self.backend,
            'batch_size': self._config.batch_size,
            'unroll_length': self._config.unroll_length}

  def close(self):
    """Unregister the per-run counter (the registry teardown contract
    every driver-owned metric follows): a later run in the same
    process must not snapshot a dead run's filler tally. Identity-
    checked, so closing an old filler never evicts a newer one's
    registration."""
    from scalable_agent_tpu import telemetry
    telemetry.registry().unregister(self._counter.name, self._counter)

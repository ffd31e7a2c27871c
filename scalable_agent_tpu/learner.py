"""Learner: one jitted SGD step over a batch of actor unrolls.

Re-expresses the reference's `build_learner` (reference: experiment.py
≈L330–410) as a pure function over (TrainState, batch):

- the whole step — agent unroll over [T+1, B], V-trace, losses, RMSProp
  update — is ONE jit; V-trace runs on-device (the reference pins it to
  CPU with a comment that XLA could do better; here XLA does).
- the global step counts update steps on device; environment frames are
  `steps * batch * unroll * num_action_repeats` (reference counts frames
  directly, ≈L390) — same unit, computed host-side for reporting and
  in-schedule for the polynomial LR decay.
- the shift/overlap alignment (the 1-frame overlap between consecutive
  unrolls, reference ≈L285 + ≈L340) is factored into `align_batch` so it
  can be unit-tested against hand-indexed expectations.

Trajectory layout reminder (time-major [T+1, B]):
  env_outputs[i]  = o_i  (o_0 is the previous unroll's last frame)
  agent_outputs[i].action = a_{i-1} (action *before* o_i)
so rewards[1:] pair with values[:-1] and the bootstrap is V(o_T).
"""

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from scalable_agent_tpu import losses as losses_lib
from scalable_agent_tpu import popart as popart_lib
from scalable_agent_tpu import telemetry
from scalable_agent_tpu import unreal
from scalable_agent_tpu import vtrace
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.parallel import sharding as sharding_lib
from scalable_agent_tpu.structs import ActorOutput

# Unified-registry telemetry (round 13): registered once at import —
# the registry replaces by name, so a per-call registration would
# reset the cumulative build count.
_STEP_FN_BUILDS = telemetry.counter('learner/step_fn_builds')
_FRAMES_PER_STEP = telemetry.gauge('learner/frames_per_step')


class TrainState(NamedTuple):
  params: Any
  opt_state: Any
  update_steps: Any  # i32 [] — device-side; frames derived host-side.
  popart: Any = None  # PopArtState when config.use_popart
  # IMPACT clipped-target anchor (config.surrogate='impact', round
  # 10): an on-device param copy refreshed every
  # config.target_update_interval steps by an in-graph select. None
  # (the vtrace default) is an empty pytree subtree, so existing
  # checkpoints and the vtrace state structure are unchanged.
  target_params: Any = None
  # PopArt stats snapshot taken WITH target_params at each refresh
  # (impact + use_popart only): apply_preservation rewrites only the
  # LIVE value head as the stats move, so the frozen anchor head must
  # be unnormalized with the stats it was frozen under — current
  # stats would mis-scale the V-trace values by the drift since the
  # last refresh.
  target_popart: Any = None


class VTraceInputs(NamedTuple):
  behaviour_logits: Any  # [T, B, A] — actor's logits at acting time
  target_logits: Any     # [T, B, A] — learner's logits, same steps
  actions: Any           # [T, B]    — actions actually taken
  discounts: Any         # [T, B]
  rewards: Any           # [T, B]    — clipped
  values: Any            # [T, B]    — learner baseline V(o_i)
  bootstrap_value: Any   # [B]       — V(o_T)


def clip_rewards(rewards, mode):
  """Reference reward clipping (experiment.py ≈L345)."""
  if mode == 'abs_one':
    return jnp.clip(rewards, -1.0, 1.0)
  elif mode == 'soft_asymmetric':
    squeezed = jnp.tanh(rewards / 5.0)
    return jnp.where(rewards < 0, 0.3 * squeezed, squeezed) * 5.0
  elif mode == 'none':
    return rewards
  raise ValueError(f'unknown reward clipping: {mode!r}')


def align_batch(env_outputs, agent_outputs, learner_outputs, config):
  """Shift the [T+1] trajectory into aligned [T] V-trace inputs.

  Mirrors reference build_learner ≈L335–355: bootstrap from the last
  learner baseline, actor/env tensors drop the overlap frame ([1:]),
  learner tensors drop the last frame ([:-1])."""
  bootstrap_value = learner_outputs.baseline[-1]
  actor_t = jax.tree_util.tree_map(lambda t: t[1:], agent_outputs)
  rewards = env_outputs.reward[1:]
  done = env_outputs.done[1:]
  learner_t = jax.tree_util.tree_map(lambda t: t[:-1], learner_outputs)

  clipped_rewards = clip_rewards(rewards, config.reward_clipping)
  discounts = (~done).astype(jnp.float32) * config.discounting
  return VTraceInputs(
      behaviour_logits=actor_t.policy_logits,
      target_logits=learner_t.policy_logits,
      actions=actor_t.action,
      discounts=discounts,
      rewards=clipped_rewards,
      values=learner_t.baseline,
      bootstrap_value=bootstrap_value)


def loss_fn(params, agent, batch: ActorOutput, config: Config,
            popart_state=None, mesh=None, target_params=None,
            target_popart=None, entropy_cost=None):
  """Total IMPALA loss for one batch; returns (loss, (metrics, aux)).

  `mesh` is the sharded step's mesh (train_parallel passes it; None on
  the single-device path). The agent is told into how many shards the
  mesh cuts the batch (`sharding.batch_shards`), so that its merged
  [T*B] rows keep that sharding; and the Pallas V-trace form runs under
  shard_map over the mesh's data axis — pallas_call has no SPMD
  partitioning rule of its own (vtrace.py).

  With PopArt (popart_state not None): the agent's baseline is the
  NORMALIZED per-task value; V-trace runs on the unnormalized σ·n + μ,
  the baseline loss in normalized space with the CURRENT statistics
  (the stats/preservation update happens in train_step, one step
  behind — standard PopArt ordering). aux carries the vs targets for
  that update.

  `target_params` (config.surrogate='impact', round 10 — IMPACT,
  arXiv 1912.00167): the clipped-target anchor. The V-trace IS ratios
  and value estimates then come from a SECOND forward pass through the
  anchor (rho = pi_target/mu, values/bootstrap from the target
  critic — both clipped exactly like the reference's rho-bar), the
  baseline loss regresses the CURRENT critic toward those vs targets,
  and the policy gradient is the PPO-style clipped
  pi_theta/pi_target surrogate (losses.compute_impact_surrogate_loss)
  instead of -log pi * A. Behavior-vs-target staleness is therefore
  handled per the paper: mu may lag arbitrarily (V-trace corrects it
  against the anchor), and theta may run ahead of the anchor only as
  far as the clip band allows.

  `entropy_cost` (round 23, the vectorized population): an optional
  TRACED override of config.entropy_cost — vmapping PBT members over
  one program needs the per-member hypers as array inputs, not baked
  constants. None (every non-population caller) keeps the config's
  compile-time constant, bit-identical to before."""
  task_ids = jnp.asarray(batch.level_name).astype(jnp.int32)
  batch_shards = sharding_lib.batch_shards(config, mesh)
  use_pc = config.pixel_control_cost > 0
  if use_pc:
    ((learner_outputs, _), mutables) = agent.apply(
        params, batch.agent_outputs.action, batch.env_outputs,
        batch.agent_state, level_ids=task_ids,
        compute_pixel_control=True, batch_shards=batch_shards,
        mutable=['intermediates'])
    pc_q = mutables['intermediates']['pixel_control_q'][0]
  else:
    learner_outputs, _ = agent.apply(
        params, batch.agent_outputs.action, batch.env_outputs,
        batch.agent_state, level_ids=task_ids,
        batch_shards=batch_shards)

  if popart_state is not None:
    normalized = learner_outputs.baseline  # [T+1, B]
    unnormalized = popart_lib.unnormalize(popart_state, normalized,
                                          task_ids)
    learner_for_align = learner_outputs._replace(baseline=unnormalized)
  else:
    learner_for_align = learner_outputs
  inputs = align_batch(batch.env_outputs, batch.agent_outputs,
                       learner_for_align, config)

  use_impact = config.surrogate == 'impact' and target_params is not None
  metrics_extra = {}
  if use_impact:
    # Anchor forward pass: the target network's logits and baseline
    # over the same batch. target_params is a constant of this loss
    # (refreshed by train_step's cadence select), so no gradient
    # flows — stop_gradient makes that explicit for readers and for
    # any jvp reaching the anchor subtree.
    target_outputs, _ = agent.apply(
        target_params, batch.agent_outputs.action, batch.env_outputs,
        batch.agent_state, level_ids=task_ids,
        batch_shards=batch_shards)
    target_outputs = jax.lax.stop_gradient(target_outputs)
    if popart_state is not None:
      # Unnormalize with the stats snapshotted AT the anchor's refresh
      # (target_popart): preservation only rewrites the LIVE head as
      # stats drift, so current stats would mis-scale the frozen head
      # by sigma_now/sigma_refresh between refreshes.
      anchor_stats = (target_popart if target_popart is not None
                      else popart_state)
      target_for_align = target_outputs._replace(
          baseline=popart_lib.unnormalize(
              anchor_stats, target_outputs.baseline, task_ids))
    else:
      target_for_align = target_outputs
    # V-trace anchored on the target network: IS ratios pi_target/mu
    # (clipped at rho-bar like the reference) and the target critic's
    # values/bootstrap. The vs targets train the CURRENT critic below.
    vtrace_src = align_batch(batch.env_outputs, batch.agent_outputs,
                             target_for_align, config)
  else:
    vtrace_src = inputs
  # (`vtrace`, `loss` and `optimizer` are scopes in the compiled step's
  # operation names, beside the agent's `torso`, `core` and `heads`.)
  with jax.named_scope('vtrace'):
    vtrace_returns = vtrace.from_logits(
        behaviour_policy_logits=vtrace_src.behaviour_logits,
        target_policy_logits=vtrace_src.target_logits,
        actions=vtrace_src.actions,
        discounts=vtrace_src.discounts,
        rewards=vtrace_src.rewards,
        values=vtrace_src.values,
        bootstrap_value=vtrace_src.bootstrap_value,
        use_associative_scan=config.use_associative_scan,
        use_pallas=config.use_pallas_vtrace,
        mesh=mesh)
  with jax.named_scope('loss'):
    if use_impact:
      log_ratio = (vtrace.log_probs_from_logits_and_actions(
          inputs.target_logits, inputs.actions) -
          vtrace_returns.target_action_log_probs)
      pg_loss = losses_lib.compute_impact_surrogate_loss(
          log_ratio, vtrace_returns.pg_advantages, config.impact_epsilon)
      metrics_extra['impact_clip_fraction'] = losses_lib.\
          impact_clip_fraction(log_ratio, config.impact_epsilon)
    else:
      pg_loss = losses_lib.compute_policy_gradient_loss(
          inputs.target_logits, inputs.actions,
          vtrace_returns.pg_advantages)
    if popart_state is not None:
      # Regress the normalized head toward normalized targets.
      norm_targets = popart_lib.normalize(
          popart_state, vtrace_returns.vs, task_ids)
      baseline_loss = losses_lib.compute_baseline_loss(
          jax.lax.stop_gradient(norm_targets) -
          learner_outputs.baseline[:-1])
    else:
      baseline_loss = losses_lib.compute_baseline_loss(
          vtrace_returns.vs - inputs.values)
    entropy_loss = losses_lib.compute_entropy_loss(inputs.target_logits)

    ec = config.entropy_cost if entropy_cost is None else entropy_cost
    total_loss = (pg_loss + config.baseline_cost * baseline_loss +
                  ec * entropy_loss)
  metrics = {
      'total_loss': total_loss,
      'pg_loss': pg_loss,
      'baseline_loss': baseline_loss,
      'entropy_loss': entropy_loss,
  }
  metrics.update(metrics_extra)
  if use_pc:
    # UNREAL pixel control (unreal.py): pseudo-rewards from frame
    # deltas; action on the t→t+1 transition is agent_outputs[t+1]
    # (the [1:] slice — same alignment as the policy inputs).
    frames = batch.env_outputs.observation[0]
    # The opt-in integer-domain rewards need uint8 frames; any float
    # observation source falls back to the f32 reference form.
    use_int = (config.pixel_control_integer_rewards and
               frames.dtype == jnp.uint8)
    pc_rewards = unreal.pixel_control_rewards(
        frames, config.pixel_control_cell_size, integer_path=use_int)
    pc_loss = unreal.pixel_control_loss(
        pc_q, inputs.actions, pc_rewards,
        jnp.asarray(batch.env_outputs.done)[1:],
        discount=config.pixel_control_discount)
    total_loss = total_loss + config.pixel_control_cost * pc_loss
    metrics['pixel_control_loss'] = pc_loss
    metrics['total_loss'] = total_loss
  aux = {'vs': vtrace_returns.vs, 'task_ids': task_ids}
  return total_loss, (metrics, aux)


def param_fingerprint(params):
  """Cheap in-graph content fingerprint of a param tree: every leaf
  bit-cast to its same-width unsigned integer view and summed with
  uint32 wraparound (round 12 — the device half of the SDC sentinel).

  Properties the cross-replica check rests on:
  - EXACT: integer addition mod 2^32 is associative/commutative, so
    the value is independent of reduction order — two replicas holding
    bit-identical params ALWAYS produce equal fingerprints (a float
    reduction could not promise that).
  - SENSITIVE: any single flipped bit in any leaf changes the sum
    (one term changes by a power of two; collisions need a second
    compensating corruption).
  - CHEAP: one pass over the params, no host sync — it rides the
    step's dispatch stream and is read one step later with the other
    sentinels.

  8-byte leaves bitcast to uint32 PAIRS (trailing dim 2) so the graph
  never needs x64; bool leaves go through uint8."""
  total = jnp.zeros((), jnp.uint32)
  for leaf in jax.tree_util.tree_leaves(params):
    a = jnp.asarray(leaf)
    if a.size == 0:
      continue
    if a.dtype == jnp.bool_:
      bits = a.astype(jnp.uint8)
    else:
      itemsize = a.dtype.itemsize
      target = {1: jnp.uint8, 2: jnp.uint16}.get(itemsize, jnp.uint32)
      bits = jax.lax.bitcast_convert_type(a, target)
    total = total + jnp.sum(bits.astype(jnp.uint32))
  return total


def frames_per_step(config: Config):
  """Env frames consumed per SGD step (reference ≈L390)."""
  return config.frames_per_step


def make_schedule(config: Config):
  """Polynomial (linear) LR decay to 0 over total env frames, driven by
  the update-step count × frames-per-step (reference ≈L380–390). The
  single source of truth for the LR — used by both the optimizer and
  the logged `learning_rate` metric.

  Under sample reuse (round 10) the frame clock is FRESH env frames:
  each update consumes frames_per_step × (1 − replay_ratio)/replay_k
  of them at steady state, and the driver's frame budget counts fresh
  frames too — without this the schedule would hit zero at ~1/reuse
  of the run and train the rest at lr=0. Identical to frames_per_step
  with reuse off (the parity-gate operating point). The (1−ratio)/K
  factor assumes the tier sustains the configured composition: a
  chronically under-filled tier (tight staleness windows, cold start)
  serves extra fresh slots, so such a run exhausts its fresh-frame
  budget with the schedule only partly decayed — watch
  `replay_occupancy` vs capacity in summaries (RUNBOOK §5)."""
  fps = (float(config.frames_per_step) *
         (1.0 - config.replay_ratio) / config.replay_k)

  def schedule(count):
    frames = jnp.asarray(count).astype(jnp.float32) * fps
    frac = jnp.minimum(frames / float(config.total_environment_frames),
                       1.0)
    return config.learning_rate * (1.0 - frac)

  return schedule


def make_optimizer(config: Config):
  """RMSProp (+ optional global-norm clipping) with the frame-driven
  polynomial decay schedule."""
  opt = optax.rmsprop(
      learning_rate=make_schedule(config), decay=config.decay,
      eps=config.epsilon, momentum=config.momentum)
  if config.grad_clip_norm is not None:
    opt = optax.chain(
        optax.clip_by_global_norm(config.grad_clip_norm), opt)
  return opt


def make_train_state(params, config: Config,
                     num_popart_tasks: int = 0) -> TrainState:
  optimizer = make_optimizer(config)
  target = None
  if config.surrogate == 'impact':
    # DISTINCT buffers: the state is donated every step, and a target
    # leaf aliasing its param leaf would be donated twice. The copy
    # preserves the params' placement/sharding (eager copy follows its
    # input); make_sharded_train_state re-pins it explicitly anyway.
    target = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                    params)
  popart = (popart_lib.init(max(num_popart_tasks, 1))
            if config.use_popart else None)
  return TrainState(
      params=params,
      opt_state=optimizer.init(params),
      update_steps=jnp.zeros((), jnp.int32),
      popart=popart,
      target_params=target,
      target_popart=(jax.tree_util.tree_map(
          lambda x: jnp.array(x, copy=True), popart)
          if target is not None and popart is not None else None))


def make_train_step_fn(agent, config: Config, mesh=None,
                       traced_hypers: bool = False):
  """The raw (unjitted) train step: (TrainState, batch) → (state,
  metrics). Single source of truth — jitted plain here and with explicit
  shardings in parallel/train_parallel.py (which passes its mesh so the
  Pallas V-trace can shard_map over the data axis).

  `traced_hypers` (round 23, the vectorized population): the step
  becomes (state, batch, hypers) with hypers a dict of traced scalars
  {'learning_rate', 'entropy_cost'} — what lets jax.vmap carry N PBT
  members through ONE compiled program with per-member hypers as
  array inputs. The optimizer is built at unit learning rate (the
  schedule keeps its shape, so opt_state structure — and therefore
  checkpoints — interchange exactly with the baked-constant step) and
  the traced lr post-scales the updates. Exact for the config default
  momentum=0, and for any constant-lr run (optax.trace is linear);
  with momentum AND mid-round decay the lr applies one multiply later
  than the baked form — same first-order update, not bit-identical."""
  # Unified-registry telemetry (round 13): each build corresponds to
  # one XLA (re)compile of the step — a climbing count mid-run means
  # shape churn recompiling the hot path; frames_per_step is the
  # constant trace_report's throughput arithmetic divides by.
  _STEP_FN_BUILDS.inc()
  _FRAMES_PER_STEP.set(frames_per_step(config))
  if traced_hypers:
    # Unit-lr optimizer/schedule: schedule(count) is the pure decay
    # fraction; the member's traced lr multiplies it back in.
    unit_config = dataclasses.replace(config, learning_rate=1.0)
    optimizer = make_optimizer(unit_config)
    schedule = make_schedule(unit_config)
  else:
    optimizer = make_optimizer(config)
    schedule = make_schedule(config)

  def train_step(state: TrainState, batch: ActorOutput, hypers=None):
    if traced_hypers:
      lr = jnp.asarray(hypers['learning_rate'], jnp.float32)
      ec = jnp.asarray(hypers['entropy_cost'], jnp.float32)
    else:
      lr = None
      ec = None
    (total_loss, (metrics, aux)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params, agent, batch, config,
                               state.popart, mesh, state.target_params,
                               state.target_popart, ec)
    # Pre-clip norm: explosions must stay visible even with clipping on.
    metrics['grad_norm'] = optax.global_norm(grads)
    with jax.named_scope('optimizer'):
      updates, new_opt_state = optimizer.update(
          grads, state.opt_state, state.params)
      if traced_hypers:
        updates = jax.tree_util.tree_map(lambda u: lr * u, updates)
      new_params = optax.apply_updates(state.params, updates)
    new_popart = state.popart
    if state.popart is not None:
      # PopArt: EMA the per-task moments toward this batch's targets,
      # then rewrite the value head so unnormalized outputs are
      # preserved exactly (popart.py).
      new_popart = popart_lib.update_stats(
          state.popart, aux['vs'], aux['task_ids'],
          beta=config.popart_beta)
      new_params = popart_lib.apply_preservation(
          new_params, state.popart, new_popart)
      # Stability observability (the soak asserts these stay bounded):
      # a diverging value scale shows up here long before NaNs.
      sig = popart_lib.sigma(new_popart)
      metrics['popart_sigma_min'] = jnp.min(sig)
      metrics['popart_sigma_max'] = jnp.max(sig)
    if config.health_watchdog:
      # Device-side sentinel + skip (health.py): a non-finite loss or
      # grad norm means this update would poison the params — keep the
      # old state wholesale instead. One `where` per leaf; identity on
      # healthy steps, no host sync. The step counter still advances
      # (the batch's frames were consumed either way), so the
      # step/frame accounting stays monotone through skips.
      step_ok = (jnp.isfinite(total_loss) &
                 jnp.isfinite(metrics['grad_norm']))

      def keep(new, old):
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(step_ok, n, o), new, old)

      new_params = keep(new_params, state.params)
      new_opt_state = keep(new_opt_state, state.opt_state)
      if new_popart is not None:
        new_popart = keep(new_popart, state.popart)
      metrics['step_ok'] = step_ok.astype(jnp.float32)
    new_target = state.target_params
    new_target_popart = state.target_popart
    if state.target_params is not None:
      # Target-network refresh on its own cadence (IMPACT round 10):
      # an in-graph select — the version-gated publish pattern applied
      # to the on-device anchor (a non-refresh step copies nothing; a
      # refresh is one select per leaf, no host round trip). Runs
      # AFTER the watchdog keep() so a skipped step's anchor snapshots
      # the kept (old) params, never a withheld non-finite update.
      # With interval=1 the anchor entering step N+1 IS the params
      # entering step N+1 — the parity-gate operating point.
      refresh = ((state.update_steps + 1) %
                 config.target_update_interval) == 0
      new_target = jax.tree_util.tree_map(
          lambda p, t: jnp.where(refresh, p, t),
          new_params, state.target_params)
      if state.target_popart is not None:
        # The stats snapshot refreshes WITH the anchor head — the pair
        # is what unnormalizes the frozen baseline exactly (loss_fn).
        new_target_popart = jax.tree_util.tree_map(
            lambda p, t: jnp.where(refresh, p, t),
            new_popart, state.target_popart)
    new_state = TrainState(new_params, new_opt_state,
                           state.update_steps + 1, new_popart,
                           new_target, new_target_popart)
    metrics['learning_rate'] = (
        lr * schedule(state.update_steps) if traced_hypers
        else schedule(state.update_steps))
    return new_state, metrics

  return train_step


def make_train_step(agent, config: Config):
  """Jitted single-device train step; donates the state for in-place
  HBM update. `batch` is an ActorOutput pytree of [T+1, B] time-major
  arrays (plus agent_state [B, ...])."""
  return jax.jit(make_train_step_fn(agent, config), donate_argnums=(0,))

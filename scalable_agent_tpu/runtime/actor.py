"""Host-side actor: rolls environments into learner-ready unrolls.

Re-expresses the reference's `build_actor` (reference: experiment.py
≈L215–300) outside the graph: on TPU the env loop is host Python while
inference runs on-device (directly jitted, or via the dynamic batcher) —
there is no in-graph `tf.scan` over env steps to port.

Faithfully preserved semantics:
- persistent cross-unroll state (env output, agent output, LSTM state) —
  the reference's local TF variables (≈L235);
- the 1-frame overlap: each `ActorOutput` has T+1 timesteps, timestep 0
  being the previous unroll's last (env_output, agent_output) (≈L285);
- `agent_state` in the output is the LSTM state at the *start* of the
  unroll;
- episode statistics flow *through* the trajectory as `StepOutputInfo`
  (the reference's FlowEnvironment state machine, environments.py
  ≈L165–190): the output at a done step carries the finished episode's
  stats while the carried state resets to zero.
"""

import collections
import contextlib
import threading
import time
from typing import Callable, Optional

import numpy as np

from scalable_agent_tpu import telemetry
from scalable_agent_tpu.runtime import py_process
from scalable_agent_tpu.structs import (
    ActorOutput, AgentOutput, StepOutput, StepOutputInfo)

# run_actor_loop's put is a POLL (not one unbounded block): each
# timeout re-checks the stop event, so a stopping/quiescing fleet can
# join producers parked on a full buffer even when nobody closes it.
_PUT_POLL_SECS = 0.5
# After stop is requested, how long a parked producer keeps trying to
# land its completed unroll before dropping it and exiting (the drain
# path WANTS the unroll — the learner is flushing and room appears;
# this bound only fires when nothing is draining, where the old
# behavior was an unjoinable thread).
_STOP_PUT_GRACE_SECS = 5.0


class Actor:
  """One environment + its rollout state.

  Args:
    env: an `envs.base.Environment`.
    policy: callable `(prev_action i32[], env_output StepOutput of
      scalars, core_state) -> (AgentOutput of scalars, new_core_state)`.
      This is where inference plugs in — a direct jitted call for tests,
      the dynamic-batching client in production.
    initial_core_state: zeroed LSTM state for one env (no batch dim or
      batch dim 1, policy-defined — the actor treats it opaquely).
    unroll_length: T (the output carries T+1 with the overlap frame).
    num_action_repeats: frames per env step, for episode_step accounting
      (frames unit matches the reference's global step).
    level_name_id: int id standing in for the reference's level-name
      string (strings don't cross the device boundary; the mapping lives
      in dmlab30.py / the driver).
  """

  def __init__(self, env, policy: Callable, initial_core_state,
               unroll_length: int, num_action_repeats: int = 1,
               level_name_id: int = 0):
    self._env = env
    self._policy = policy
    self._unroll_length = unroll_length
    self._num_action_repeats = num_action_repeats
    self._level_name_id = np.int32(level_name_id)

    observation = env.initial()
    self._env_output = StepOutput(
        reward=np.float32(0.0),
        info=StepOutputInfo(np.float32(0.0), np.int32(0)),
        done=np.bool_(True),  # first obs starts an episode, like reference
        observation=observation)
    self._core_state = initial_core_state
    self._zero_core_state = initial_core_state
    self._agent_output: Optional[AgentOutput] = None
    self._episode_return = np.float32(0.0)
    self._episode_step = np.int32(0)
    self._solo: Optional['ActorGroup'] = None  # `unroll`'s group of one

  def group_key(self):
    """What two Actors must agree on to be stepped as one ActorGroup:
    the policy, the unroll length and the observation's shapes and
    dtypes (a k-row policy call needs one trailing shape)."""
    import jax
    leaves = jax.tree_util.tree_leaves(self._env_output.observation)
    return (self._policy, self._unroll_length,
            tuple((np.shape(x), np.asarray(x).dtype.str) for x in leaves))

  def unroll(self, span_id=None) -> ActorOutput:
    """Produce one ActorOutput of [T+1] time-major numpy arrays: a
    group of one (`ActorGroup.unroll` is THE loop). `span_id` is the
    `id` its recorder spans carry (telemetry.span): the actor loop
    passes the unroll's `(actor, seq)`."""
    if self._solo is None:
      self._solo = ActorGroup([self])
    return self._solo.unroll([span_id])[0]

  def _begin_unroll(self):
    """Note the numeric carry at the unroll start."""
    # Device-resident policy state (InferenceServer state-cache mode)
    # is an opaque handle: the learner still needs the NUMERIC carry
    # at the unroll start, so snapshot it here — the once-per-unroll
    # host read that replaces the old once-per-step carry round trip.
    core0 = self._core_state
    if hasattr(core0, 'snapshot'):
      self._initial_core_state = core0.snapshot()
    else:
      self._initial_core_state = core0

  def _primed(self, logits):
    """After the priming policy call (made lazily, so num_actions is
    known from its logits)."""
    core0 = self._core_state
    if hasattr(core0, 'write'):
      # The carry-passing path DISCARDS the priming call's new state;
      # a device-resident state advanced in-graph must be put back,
      # or the cache path would start the unroll one step ahead
      # (parity gate in tests/test_runtime.py).
      core0.write(self._initial_core_state)
    self._agent_output = AgentOutput(
        action=np.int32(0),
        policy_logits=np.zeros_like(np.asarray(logits)),
        baseline=np.float32(0.0))

  def release_policy_state(self):
    """Return device-resident policy state (a state-arena slot) to its
    server; no-op for plain numeric carries. Idempotent — called from
    close() on every exit path and defensively by the fleet's respawn
    (a thread killed before its finally ran must not leak the slot)."""
    state = self._core_state
    if hasattr(state, 'release'):
      try:
        state.release()
      except Exception:
        pass

  def close(self):
    self.release_policy_state()
    self._env.close()


class _Rollout:
  """A group's unroll as it is made: the T+1 steps of its k envs in
  [T+1, k, ...] arrays, so that step t is the policy's request as it
  stands (the contiguous row `x[t]`, no stacking) and a member's
  unroll is the column `x[:, j]`. The env's side (reward, done, the
  observation's leaves) is a `py_process.StepBlock`: in shared memory
  where every member is a hosted env that can map it, and then each
  child writes its own column and a group step is one
  `py_process.StepPass` over the block, a byte each way a pipe;
  otherwise in this process's memory, written here from what each
  `step` returns. Which it is follows from what the members offer
  (`step_block_specs`, `attach_block`), never from a setting.

  The members keep their own state BETWEEN unrolls (`begin` reads it,
  `finish` writes it back), so the arrays are reused from unroll to
  unroll and a change of membership just builds another Rollout.
  """

  def __init__(self, group):
    import jax
    self.actors = actors = list(group.actors)
    self.rows = actors[0]._unroll_length + 1
    k = len(actors)
    leaves, self.treedef = jax.tree_util.tree_flatten(
        actors[0]._env_output.observation)
    leaf_specs = [(np.shape(x), np.asarray(x).dtype.str) for x in leaves]
    self.step_pass = group._step_pass(leaf_specs, self.rows)
    self.shared = self.step_pass is not None
    self.block = (self.step_pass.block if self.shared else
                  py_process.StepBlock.private(leaf_specs, self.rows, k))
    self.episode_return = np.zeros((self.rows, k), np.float32)
    self.episode_step = np.zeros((self.rows, k), np.int32)
    self.repeats = np.asarray([a._num_action_repeats for a in actors],
                              np.int32)
    self.handles = hasattr(actors[0]._core_state, 'snapshot')
    # Off a shared block, hosted envs step in two halves, the others
    # in one piece.
    envs = [a._env for a in actors]
    self.sends = [getattr(env, 'step_send', None) for env in envs]
    self.receives = [getattr(env, 'step_receive', None) for env in envs]
    # A hosted env's own time in the step last received, off a block.
    self.busy = [getattr(env, 'step_busy_ns', None) for env in envs]
    self.agent = None  # AgentOutput of [T+1, k, ...]: see `begin`

  def begin(self):
    """Row 0 is each member's last step of the unroll before."""
    actors = self.actors
    for j, actor in enumerate(actors):
      reward, info, done, observation = actor._env_output
      self._write(0, j, reward, done, observation)
      self.episode_return[0, j], self.episode_step[0, j] = info
    self._return = np.asarray([a._episode_return for a in actors],
                              np.float32)
    self._step = np.asarray([a._episode_step for a in actors], np.int32)
    first = [a._agent_output for a in actors]
    if self.agent is None:
      self.agent = AgentOutput(*[
          np.empty((self.rows, len(actors)) + np.shape(y),
                   np.asarray(y).dtype) for y in first[0]])
    for j, out in enumerate(first):
      for x, y in zip(self.agent, out):
        x[0, j] = y
    self.states = _states_of(actors)

  def _write(self, t, j, reward, done, observation):
    import jax
    block = self.block
    block.reward[t, j] = reward
    block.done[t, j] = done
    for leaf, x in zip(block.leaves,
                       jax.tree_util.tree_leaves(observation)):
      leaf[t, j] = x

  def env_output(self, t, done):
    """Step t of every member as the k-row policy call takes it, its
    `done` as `hand_over_prompts` left it."""
    block = self.block
    return StepOutput(
        block.reward[t],
        StepOutputInfo(self.episode_return[t], self.episode_step[t]),
        done, self.treedef.unflatten([leaf[t] for leaf in block.leaves]))

  def hand_over_prompts(self, t):
    """`done` of step t as the policy is to see it. Where an episode
    begins, the policy state lives with a server whose core computes
    a chunk of tokens at once (`prefill_chunk`) and the env offers the
    episode's prompt as a block (`prompt_block`: all but its last
    token, which is the observation at hand), the block goes to the
    server first. The call that follows must not reset what the block
    built: the policy sees that member's `done` cleared, the unroll
    keeps it."""
    done = self.block.done[t]
    if not (self.handles and done.any()):
      return done
    for j in np.flatnonzero(done):
      state = self.states[j]
      if not getattr(state, 'prefill_chunk', 0):
        continue
      fetch = getattr(self.actors[j]._env, 'prompt_block', None)
      prompt = fetch() if fetch is not None else None
      if prompt is None:
        continue
      tokens, n = prompt
      state.prefill(np.asarray(tokens)[:int(n)])
      if done.base is not None:
        done = done.copy()
      done[j] = False
    return done

  def act(self, t):
    """The policy call of step t -> the k actions (row t + 1 of
    `agent.action`, as are the call's other outputs)."""
    out, self.states = _call_policy(
        self.actors[0]._policy, self.agent.action[t],
        self.env_output(t, self.hand_over_prompts(t)), self.states)
    if any(np.asarray(y).dtype != x.dtype for x, y in zip(self.agent, out)):
      # A policy whose outputs are wider than the priming zeros: widen
      # as np.stack would have.
      self.agent = AgentOutput(*[
          x.astype(np.result_type(x, np.asarray(y)))
          for x, y in zip(self.agent, out)])
    for x, y in zip(self.agent, out):
      x[t + 1] = y
    return self.agent.action[t + 1]

  def record(self, t):
    """Flow-style episode accounting of the step now in row t (the
    output carries the final stats at done; the carried state
    resets)."""
    done = self.block.done[t]
    self._return += self.block.reward[t]
    self._step += self.repeats
    self.episode_return[t] = self._return
    self.episode_step[t] = self._step
    if done.any():
      self._return[done] = 0
      self._step[done] = 0

  def finish(self, span_ids):
    """The members' ActorOutputs, each a copy of its column (the
    arrays go on to the next unroll), and their state for it."""
    import jax
    block, outputs = self.block, []
    for j, (actor, span_id) in enumerate(zip(self.actors, span_ids)):
      with telemetry.span('actor/assemble', id=span_id):
        env_outputs = StepOutput(
            block.reward[:, j].copy(),
            StepOutputInfo(self.episode_return[:, j].copy(),
                           self.episode_step[:, j].copy()),
            block.done[:, j].copy(),
            self.treedef.unflatten(
                [leaf[:, j].copy() for leaf in block.leaves]))
        agent_outputs = AgentOutput(*[x[:, j].copy() for x in self.agent])
      last = lambda x: x[-1]  # noqa: E731
      actor._env_output = jax.tree_util.tree_map(last, env_outputs)
      actor._agent_output = AgentOutput(*map(last, agent_outputs))
      if not self.handles:
        actor._core_state = jax.tree_util.tree_map(
            lambda x, j=j: x[j:j + 1], self.states)
      actor._episode_return = self._return[j]
      actor._episode_step = self._step[j]
      outputs.append(ActorOutput(
          level_name=actor._level_name_id,
          agent_state=actor._initial_core_state,
          env_outputs=env_outputs,
          agent_outputs=agent_outputs))
    return outputs


def _states_of(actors):
  """The members' policy states as one k-row call takes them: opaque
  handles (`snapshot`) as a list, numeric carries concatenated on
  axis 0."""
  states = [a._core_state for a in actors]
  if hasattr(states[0], 'snapshot'):
    return states
  import jax
  return jax.tree_util.tree_map(
      lambda *xs: np.concatenate(xs, axis=0), *states)


def _call_policy(policy, prev_actions, env_output, states):
  """One policy call for k rows -> (AgentOutput of [k, ...] arrays,
  the new states). A single row makes the scalar call of the Actor
  contract; `states` is a list of k opaque handles or a numeric carry
  of [k, ...] leaves."""
  if len(prev_actions) > 1:
    out, states = policy(prev_actions, env_output, states)
    return AgentOutput(*[np.asarray(x) for x in out]), states
  import jax
  handles = isinstance(states, list)
  out, state = policy(
      prev_actions[0], jax.tree_util.tree_map(lambda x: x[0], env_output),
      states[0] if handles else states)
  return (AgentOutput(*[np.asarray(x)[None] for x in out]),
          [state] if handles else state)


class ActorGroup:
  """k Actors that ONE thread steps in lockstep: per step one policy
  call with a leading axis of k, then all k env steps at once.

  The unit that parks in the batcher is the group, so a merged
  inference call wakes one thread per group instead of one per env
  (PERF.md, PR 26: 32 threads took 26 of the 33 ms cycle to come back
  one by one). What an unroll contains does not depend on the group:
  each member keeps its own episode accounting, overlap frame, carry
  and priming, and for the same policy outputs its ActorOutput is
  bitwise what it produces alone. A group of one IS the single-actor
  loop, with the scalar policy call.

  With k > 1 the members' shared `policy` takes the k-row form of the
  Actor contract: `(prev_action i32[k], env_output with [k, ...]
  leaves, core_state for k) -> (AgentOutput with [k, ...] leaves,
  core_state for k)`, where a numeric core state is the members'
  concatenated on axis 0 and opaque handles (`snapshot`) go as a list
  (`InferenceServer.policy` honours both). The request's leaves are
  rows of the group's `_Rollout` (views: the policy must have done
  with them when it returns). Where every member can map a shared
  block (process-hosted: `py_process.ProxyEnv`) a group step is
  one pass over it (`py_process.StepPass`); otherwise members whose env
  has `step_send`/`step_receive` step concurrently, and any other env
  steps in turn on this thread.

  Membership moves only between unrolls, on the rolling thread: a
  member `leave`s (and is closed) there, and an Actor that any thread
  hands to `join` is taken in by the next `admit`. Because each member
  keeps its own state, the others' unrolls do not see either.
  """

  def __init__(self, actors, names=None):
    self.actors = list(actors)
    # The members' actor ids in trace contexts and span ids (the fleet
    # gives `actor-<slot>`); None: from the rolling thread's name.
    self.names = list(names) if names is not None else None
    # The member whose env failed the group's last unroll, if the
    # failure was one env's (else None): the fleet charges that slot.
    self.failed: Optional[Actor] = None
    # Off a pass (`waiting_on`): the member whose env this thread is
    # blocked on right now.
    self._waiting_on: Optional[Actor] = None
    self._joining = collections.deque()  # (actor, name), any thread
    self._rollout: Optional[_Rollout] = None  # of the members as they are
    # The group's steps, always on (telemetry.CycleRecord; docs/
    # OBSERVABILITY.md "Cycle records"): three stamps a step (begin,
    # policy returned, envs stepped and accounted: one step's last is
    # the next one's first within an unroll), the slowest member's
    # own time in its env's `step` (`StepBlock.busy_ns`), and 1 where
    # the envs stepped in one pass over a shared block.
    self.steps = telemetry.CycleRecord(
        ('policy_wait', 'env'), extras=('env_child_ns', 'pass_steps'))

  @property
  def waiting_on(self) -> Optional[Actor]:
    """The member whose env this thread is blocked on right now, if it
    is: of a group that stalls, the one that hangs."""
    rollout = self._rollout
    if rollout is not None and rollout.shared:
      j = rollout.step_pass.waiting
      if j is not None:
        return rollout.actors[j]
    return self._waiting_on

  def join(self, actor, name):
    """Hand the group (one that has `names`) one more member, from
    any thread: it steps with the others from the rolling thread's
    next `admit` on."""
    self._joining.append((actor, name))

  def admit(self):
    """Take in what `join` brought (the rolling thread, between
    unrolls)."""
    while self._joining:
      actor, name = self._joining.popleft()
      self.actors.append(actor)
      self.names.append(name)
      self._rollout = None

  def leave(self, actor):
    """Drop a member and close it (the rolling thread, between
    unrolls); the others go on."""
    j = self.actors.index(actor)
    del self.actors[j], self.names[j]
    self._rollout = None
    _close_quietly(actor)

  def unroll(self, span_ids=None):
    """One unroll of every member -> a list of k ActorOutputs.
    `span_ids` are the members' `(actor, seq)` ids: `actor/unroll`,
    `actor/assemble` and `env/pipe` are recorded per env, the spans of
    a step (`actor/step`, `actor/policy_call`, `batcher/compute`,
    `actor/env_step`) once per GROUP step."""
    actors = self.actors
    if span_ids is None:
      span_ids = [None] * len(actors)
    self.failed = None
    with contextlib.ExitStack() as spans:
      for span_id in span_ids:
        spans.enter_context(telemetry.span('actor/unroll', id=span_id))
      for actor in actors:
        actor._begin_unroll()
      self._prime([a for a in actors if a._agent_output is None])
      rollout = self._rollout
      if rollout is None or rollout.actors != actors:
        rollout = self._rollout = _Rollout(self)
      rollout.begin()
      busy_ns, write_step = rollout.block.busy_ns, self.steps.write
      one_pass = int(rollout.shared)
      t_begin = time.perf_counter_ns()
      for t in range(rollout.rows - 1):
        with telemetry.span('actor/step'):
          with telemetry.span('actor/policy_call'):
            actions = rollout.act(t)
          t_policy = time.perf_counter_ns()
          with telemetry.span('actor/env_step'):
            self._env_step(rollout, t + 1, actions)
          rollout.record(t + 1)
        t_end = time.perf_counter_ns()
        write_step(t_begin, t_policy, t_end, int(busy_ns.max()), one_pass)
        t_begin = t_end
      return rollout.finish(span_ids)

  @staticmethod
  def _prime(actors):
    """The priming call of members that have made no policy call yet
    (its state is put back afterwards, so no prompt is handed over for
    it): their last step stacked, which happens once a member."""
    if not actors:
      return
    import jax
    out, _ = _call_policy(
        actors[0]._policy, np.zeros(len(actors), np.int32),
        jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                               *[a._env_output for a in actors]),
        _states_of(actors))
    for j, actor in enumerate(actors):
      actor._primed(out.policy_logits[j])

  def _step_pass(self, leaf_specs, rows):
    """A StepPass over a StepBlock in shared memory that every
    member's env has mapped, if every member is a hosted env whose
    `step` reply is declared as what the members' observations are,
    and the machine has the memory to share; else None. A member that
    fails here fails the unroll, as it would in a step."""
    envs = [a._env for a in self.actors]
    if not all(hasattr(env, 'attach_block') and
               env.step_block_specs() == leaf_specs for env in envs):
      return None
    try:
      block = py_process.StepBlock.create(leaf_specs, rows, len(envs))
    except OSError:
      return None
    try:
      for j, (actor, env) in enumerate(zip(self.actors, envs)):
        self._waiting_on = actor
        try:
          env.attach_block(block, j)
        except BaseException:
          self.failed = actor
          raise
    finally:
      self._waiting_on = None
      block.unlink()  # mapped by all, or of no more use
    return py_process.StepPass(block, envs)

  def _env_step(self, rollout, t, actions):
    """Step every member's env into row `t` with `actions`: in one
    pass over the shared block where there is one, else member by
    member, every send out before the first reply is waited for, so
    that hosted envs step at once. Either way every reply sent for is
    collected, whatever failed, so no child is left mid-call, and the
    first failure is raised."""
    actors = rollout.actors
    if rollout.shared:
      try:
        rollout.step_pass.step(t, actions)
      except BaseException:
        j = rollout.step_pass.failed
        self.failed = None if j is None else actors[j]
        raise
      return
    actions, sent, failure = actions.tolist(), [], None
    for j, (actor, send) in enumerate(zip(actors, rollout.sends)):
      self._waiting_on = actor
      try:
        if send is None:
          t0 = time.perf_counter_ns()
          step = actor._env.step(actions[j])
          rollout.block.busy_ns[j] = time.perf_counter_ns() - t0
          rollout._write(t, j, *step)
        else:
          send(actions[j])
          sent.append(j)
      except BaseException as e:
        failure = (actor, e)
        break
    for j in sent:
      self._waiting_on = actors[j]
      try:
        rollout._write(t, j, *rollout.receives[j]())
        if rollout.busy[j] is not None:
          rollout.block.busy_ns[j] = rollout.busy[j]()
      except BaseException as e:
        failure = failure or (actors[j], e)
    self._waiting_on = None
    if failure is not None:
      self.failed, exc = failure
      raise exc

  def close(self):
    """Close every member, those `join` brought and no unroll took in
    among them."""
    self._rollout = None
    for actor in self.actors + [actor for actor, _ in self._joining]:
      _close_quietly(actor)


def _close_quietly(actor):
  try:
    actor.close()
  except Exception:
    pass


def run_actor_loop(actor, buffer, stop_event,
                   on_unroll: Optional[Callable[[Actor], bool]] = None,
                   on_failure: Optional[Callable] = None) -> None:
  """Produce unrolls into `buffer` until stopped (thread target).

  THE actor loop — the fleet (`runtime.fleet.ActorFleet`) and
  standalone threads both run this, so there is exactly one
  shutdown/poison contract:

  - Clean shutdown: a closed buffer or a cancelled inference call
    (batcher closed) while `stop_event` is set is normal termination,
    mirroring the reference's closed-pipe → StopIteration convention
    (reference: py_process.py ≈L72).
  - Real failure (the same exceptions while NOT stopping, or any other
    exception): by default the buffer is poisoned — closed, so the
    learner's next get raises instead of hanging — and the exception
    surfaces on this thread. `on_failure(exc)` overrides this (the
    fleet records the error on its slot and keeps the shared buffer
    open for the other actors).

  Args:
    actor: the Actor to roll, or an ActorGroup whose members this
      thread rolls in lockstep (closed on exit, always). A group's
      unrolls are put one by one, each with its own trace context.
    buffer: TrajectoryBuffer receiving unrolls.
    stop_event: threading.Event signalling shutdown.
    on_unroll: called after each successful put with the Actor whose
      unroll it was; on a False that member leaves the loop (it is
      closed; the fleet's orphaned-slot and parked-slot check), and
      the loop ends when none is left. None = run forever.
    on_failure: called with the failure exception instead of the
      default poison-and-raise.
  """
  from scalable_agent_tpu.ops.dynamic_batching import BatcherCancelled
  from scalable_agent_tpu.runtime import ring_buffer

  group = actor if isinstance(actor, ActorGroup) else ActorGroup([actor])

  def fail(exc):
    if on_failure is None:
      buffer.close()
      raise exc
    on_failure(exc)

  def put(unroll, span_id):
    """False: stopping, and nobody drained the buffer within the
    grace: the unroll is dropped."""
    # Poll-put with a stop-aware grace (round 11): an actor parked
    # on a full buffer used to block UNBOUNDED — quiesce() (which
    # deliberately keeps the buffer open so in-flight unrolls land)
    # could never join it unless the learner drained. Now the park
    # re-checks the stop event every poll; once stopping, the unroll
    # gets a bounded grace to land (the drain path drains, so it
    # normally does) and is then dropped — a joined thread with a
    # named lost unroll beats a wedged one.
    stop_deadline = None
    with telemetry.park('actor/put', id=span_id):
      while True:
        try:
          buffer.put(unroll, timeout=_PUT_POLL_SECS)
          return True
        except TimeoutError:
          if not stop_event.is_set():
            continue
          if stop_deadline is None:
            stop_deadline = time.monotonic() + _STOP_PUT_GRACE_SECS
          elif time.monotonic() > stop_deadline:
            return False

  # Trace-span stamping (round 13, telemetry.py): when tracing is on
  # in this process, each completed unroll gets a fresh trace context
  # — actor id (the group's `names`; without them the thread's name,
  # a group's further members `<thread>+1`, ...), the actor's sequence
  # in this loop, the behaviour params version — stamped HOP_DONE
  # here at env-step completion (every member's when the group's
  # unroll returns, before the first put can park) and carried beside
  # the unroll (identity-keyed sidecar; the pytree itself cannot grow
  # a leaf without breaking the wire contract). Downstream hops stamp
  # at ingest/staging/step; a remote pump pops the tag and ships it
  # on the v8 wire.
  if group.names is None:
    thread_name = threading.current_thread().name
    group.names = [thread_name if j == 0 else f'{thread_name}+{j}'
                   for j in range(len(group.actors))]
  unroll_seqs = collections.Counter()  # by actor id

  try:
    while not stop_event.is_set():
      group.admit()
      actors = list(group.actors)
      if not actors:
        return  # every member left
      # The recorder's spans of an unroll carry the (actor, seq) of
      # its trace context, so spans and traces.jsonl hops join.
      span_ids = [(name, unroll_seqs[name]) for name in group.names]
      unrolls = group.unroll(span_ids)
      for unroll, span_id in zip(unrolls, span_ids):
        trace = telemetry.begin_unroll_trace(*span_id)
        if trace is not None:
          telemetry.stamp(trace, telemetry.HOP_DONE)
          telemetry.tag_unroll(unroll, trace)
        unroll_seqs[span_id[0]] += 1
      for actor, unroll, span_id in zip(actors, unrolls, span_ids):
        if not put(unroll, span_id):
          return  # stopping and nobody is draining: drop + exit
        if on_unroll is not None and not on_unroll(actor):
          group.leave(actor)  # e.g. a replacement owns its slot now
  except (ring_buffer.Closed, BatcherCancelled) as e:
    if not stop_event.is_set():
      fail(e)
  except BaseException as e:
    fail(e)
  finally:
    group.close()


def batch_unrolls(unrolls):
  """Stack B ActorOutputs into a learner batch: time-major [T+1, B] for
  the trajectory, [B, ...] for level_name/agent_state (no time axis)."""
  import jax
  env_outputs = jax.tree_util.tree_map(
      lambda *xs: np.stack(xs, axis=1), *[u.env_outputs for u in unrolls])
  agent_outputs = jax.tree_util.tree_map(
      lambda *xs: np.stack(xs, axis=1),
      *[u.agent_outputs for u in unrolls])
  level = np.stack([u.level_name for u in unrolls])
  # Per-actor core states carry batch dim 1 ([1, hidden] leaves);
  # concatenating gives the learner's [B, hidden].
  agent_state = jax.tree_util.tree_map(
      lambda *xs: np.concatenate(xs, axis=0),
      *[u.agent_state for u in unrolls])
  return ActorOutput(level, agent_state, env_outputs, agent_outputs)

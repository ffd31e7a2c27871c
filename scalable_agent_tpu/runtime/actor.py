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


def _tree_stack(items):
  """Stack a list of identically-structured pytrees of np arrays."""
  import jax
  return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *items)


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
    return ActorGroup([self]).unroll([span_id])[0]

  def _begin_unroll(self):
    """The numeric carry at the unroll start, and the lists the
    unroll's T+1 steps collect in (the overlap frame first)."""
    # Device-resident policy state (InferenceServer state-cache mode)
    # is an opaque handle: the learner still needs the NUMERIC carry
    # at the unroll start, so snapshot it here — the once-per-unroll
    # host read that replaces the old once-per-step carry round trip.
    core0 = self._core_state
    if hasattr(core0, 'snapshot'):
      self._initial_core_state = core0.snapshot()
    else:
      self._initial_core_state = core0
    self._env_outputs = [self._env_output]
    self._agent_outputs = []

  def _primed(self, out):
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
        policy_logits=np.zeros_like(np.asarray(out.policy_logits)),
        baseline=np.float32(0.0))

  def _hand_over_prompt(self):
    """The env output the next policy call sees. Where an episode
    begins, the policy state lives with a server whose core computes
    a chunk of tokens at once (`prefill_chunk`) and the env offers the
    episode's prompt as a block (`prompt_block`: all but its last
    token, which is the observation at hand), the block goes to the
    server first. The call that follows must not reset what the block
    built: the policy sees this step's `done` cleared, the unroll
    keeps it."""
    out, state = self._env_output, self._core_state
    if not (out.done and getattr(state, 'prefill_chunk', 0)):
      return out
    fetch = getattr(self._env, 'prompt_block', None)
    block = fetch() if fetch is not None else None
    if block is None:
      return out
    tokens, n = block
    state.prefill(np.asarray(tokens)[:int(n)])
    return out._replace(done=np.bool_(False))

  def _record_step(self, agent_output, core_state, reward, done,
                   observation):
    # Flow-style episode accounting (output carries final stats at
    # done; carried state resets).
    self._episode_return = np.float32(self._episode_return + reward)
    self._episode_step = np.int32(
        self._episode_step + self._num_action_repeats)
    info = StepOutputInfo(self._episode_return, self._episode_step)
    if done:
      self._episode_return = np.float32(0.0)
      self._episode_step = np.int32(0)

    env_output = StepOutput(np.float32(reward), info, np.bool_(done),
                            observation)
    self._env_outputs.append(env_output)
    self._agent_outputs.append(agent_output)
    self._env_output = env_output
    self._agent_output = agent_output
    self._core_state = core_state

  def _assemble(self, span_id=None) -> ActorOutput:
    with telemetry.span('actor/assemble', id=span_id):
      env_outputs = _tree_stack(self._env_outputs)
      agent_outputs = _tree_stack(self._agent_outputs)
    self._env_outputs = self._agent_outputs = None
    return ActorOutput(
        level_name=self._level_name_id,
        agent_state=self._initial_core_state,
        env_outputs=env_outputs,
        agent_outputs=agent_outputs)

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
  (`InferenceServer.policy` honours both). Members whose env has
  `step_send`/`step_receive` (process-hosted: `py_process.ProxyEnv`)
  step concurrently; any other env steps in turn on this thread.

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
    # The member whose env this thread is blocked on right now, if it
    # is: of a group that stalls, the one that hangs.
    self.waiting_on: Optional[Actor] = None
    self._joining = collections.deque()  # (actor, name), any thread

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

  def leave(self, actor):
    """Drop a member and close it (the rolling thread, between
    unrolls); the others go on."""
    j = self.actors.index(actor)
    del self.actors[j], self.names[j]
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
      unprimed = [a for a in actors if a._agent_output is None]
      if unprimed:
        outs, _ = self._policy_call(
            unprimed, [np.int32(0)] * len(unprimed), priming=True)
        for actor, out in zip(unprimed, outs):
          actor._primed(out)
      for actor in actors:
        actor._agent_outputs.append(actor._agent_output)

      for _ in range(actors[0]._unroll_length):
        with telemetry.span('actor/step'):
          with telemetry.span('actor/policy_call'):
            agent_outputs, core_states = self._policy_call(
                actors, [a._agent_output.action for a in actors])
          with telemetry.span('actor/env_step'):
            steps = self._env_step(
                [int(out.action) for out in agent_outputs])
          for actor, out, core_state, step in zip(
              actors, agent_outputs, core_states, steps):
            actor._record_step(out, core_state, *step)

      return [actor._assemble(span_id)
              for actor, span_id in zip(actors, span_ids)]

  @staticmethod
  def _policy_call(actors, prev_actions, priming=False):
    """One policy call for `actors` -> (their AgentOutputs of numpy
    scalars, their new core states). The priming call's state is put
    back afterwards, so no prompt is handed over for it."""
    policy = actors[0]._policy
    env_outputs = [a._env_output if priming else a._hand_over_prompt()
                   for a in actors]
    if len(actors) == 1:
      out, core_state = policy(prev_actions[0], env_outputs[0],
                               actors[0]._core_state)
      return [AgentOutput(*[np.asarray(x) for x in out])], [core_state]
    import jax
    states = [a._core_state for a in actors]
    handles = hasattr(states[0], 'snapshot')
    out, new_states = policy(
        np.asarray(prev_actions, np.int32),
        _tree_stack(env_outputs),
        states if handles else jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *states))
    out = [np.asarray(x) for x in out]
    rows = range(len(actors))
    if not handles:
      new_states = [jax.tree_util.tree_map(lambda x, j=j: x[j:j + 1],
                                           new_states) for j in rows]
    return ([AgentOutput(*[np.asarray(x[j]) for x in out]) for j in rows],
            new_states)

  def _env_step(self, actions):
    """Step every member's env -> [(reward, done, observation)]. Every
    send goes out before the first reply is waited for, so hosted envs
    step at once; every reply sent for is collected, whatever failed,
    so no child is left mid-call. The first failure is raised."""
    results, sent, failure = [None] * len(actions), [], None
    for j, (actor, action) in enumerate(zip(self.actors, actions)):
      self.waiting_on = actor
      try:
        send = getattr(actor._env, 'step_send', None)
        if send is None:
          results[j] = actor._env.step(action)
        else:
          send(action)
          sent.append(j)
      except BaseException as e:
        failure = (actor, e)
        break
    for j in sent:
      self.waiting_on = self.actors[j]
      try:
        results[j] = self.actors[j]._env.step_receive()
      except BaseException as e:
        failure = failure or (self.actors[j], e)
    self.waiting_on = None
    if failure is not None:
      self.failed, exc = failure
      raise exc
    return results

  def close(self):
    """Close every member, those `join` brought and no unroll took in
    among them."""
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

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

  def unroll(self, span_id=None) -> ActorOutput:
    """Produce one ActorOutput of [T+1] time-major numpy arrays.
    `span_id` is the `id` its recorder spans carry (telemetry.span):
    the actor loop passes the unroll's `(actor, seq)`."""
    with telemetry.span('actor/unroll', id=span_id):
      return self._unroll()

  def _unroll(self) -> ActorOutput:
    # Device-resident policy state (InferenceServer state-cache mode)
    # is an opaque handle: the learner still needs the NUMERIC carry
    # at the unroll start, so snapshot it here — the once-per-unroll
    # host read that replaces the old once-per-step carry round trip.
    core0 = self._core_state
    if hasattr(core0, 'snapshot'):
      initial_core_state = core0.snapshot()
    else:
      initial_core_state = core0
    env_outputs = [self._env_output]
    if self._agent_output is None:
      # Prime lazily so we know num_actions from the first policy call.
      out, _ = self._policy(np.int32(0), self._env_output,
                            self._core_state)
      if hasattr(core0, 'write'):
        # The carry-passing path DISCARDS the priming call's new state;
        # a device-resident state advanced in-graph must be put back,
        # or the cache path would start the unroll one step ahead
        # (parity gate in tests/test_runtime.py).
        core0.write(initial_core_state)
      self._agent_output = AgentOutput(
          action=np.int32(0),
          policy_logits=np.zeros_like(np.asarray(out.policy_logits)),
          baseline=np.float32(0.0))
    agent_outputs = [self._agent_output]

    for _ in range(self._unroll_length):
      with telemetry.span('actor/step'):
        with telemetry.span('actor/policy_call'):
          agent_output, core_state = self._policy(
              self._agent_output.action, self._env_output,
              self._core_state)
        agent_output = AgentOutput(
            *[np.asarray(x) for x in agent_output])
        with telemetry.span('actor/env_step'):
          reward, done, observation = self._env.step(
              int(agent_output.action))

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
        env_outputs.append(env_output)
        agent_outputs.append(agent_output)
        self._env_output = env_output
        self._agent_output = agent_output
        self._core_state = core_state

    with telemetry.span('actor/assemble'):
      env_outputs = _tree_stack(env_outputs)
      agent_outputs = _tree_stack(agent_outputs)
    return ActorOutput(
        level_name=self._level_name_id,
        agent_state=initial_core_state,
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


def run_actor_loop(actor: Actor, buffer, stop_event,
                   on_unroll: Optional[Callable[[], bool]] = None,
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
    actor: the Actor to roll (closed on exit, always).
    buffer: TrajectoryBuffer receiving unrolls.
    stop_event: threading.Event signalling shutdown.
    on_unroll: called after each successful put; returning False ends
      the loop (the fleet's orphaned-slot check). None = run forever.
    on_failure: called with the failure exception instead of the
      default poison-and-raise.
  """
  from scalable_agent_tpu.ops.dynamic_batching import BatcherCancelled
  from scalable_agent_tpu.runtime import ring_buffer

  def fail(exc):
    if on_failure is None:
      buffer.close()
      raise exc
    on_failure(exc)

  # Trace-span stamping (round 13, telemetry.py): when tracing is on
  # in this process, each completed unroll gets a fresh trace context
  # — actor id (the fleet's thread name), per-loop sequence, the
  # behaviour params version — stamped HOP_DONE here at env-step
  # completion and carried beside the unroll (identity-keyed sidecar;
  # the pytree itself cannot grow a leaf without breaking the wire
  # contract). Downstream hops stamp at ingest/staging/step; a remote
  # pump pops the tag and ships it on the v8 wire.
  actor_name = threading.current_thread().name
  unroll_seq = 0

  try:
    while not stop_event.is_set():
      # The recorder's spans of this unroll carry the (actor, seq) of
      # its trace context, so spans and traces.jsonl hops join.
      span_id = (actor_name, unroll_seq)
      unroll = actor.unroll(span_id=span_id)
      trace = telemetry.begin_unroll_trace(actor_name, unroll_seq)
      if trace is not None:
        telemetry.stamp(trace, telemetry.HOP_DONE)
        telemetry.tag_unroll(unroll, trace)
      unroll_seq += 1
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
            break
          except TimeoutError:
            if not stop_event.is_set():
              continue
            if stop_deadline is None:
              stop_deadline = time.monotonic() + _STOP_PUT_GRACE_SECS
            elif time.monotonic() > stop_deadline:
              return  # stopping and nobody is draining: drop + exit
      if on_unroll is not None and not on_unroll():
        return  # orphaned: a replacement owns this actor's slot
  except (ring_buffer.Closed, BatcherCancelled) as e:
    if not stop_event.is_set():
      fail(e)
  except BaseException as e:
    fail(e)
  finally:
    try:
      actor.close()
    except Exception:
      pass


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

"""Process-hosting contract tests (the reference's py_process_test.py
coverage, re-specified for the TPU build's runtime/py_process.py):
arg passing, the `_tensor_specs` protocol, exception propagation from
constructor and methods, close semantics on clean and error paths,
fleet lifecycle, and dead-pipe → ProcessClosed."""

import os
import subprocess
import sys

import numpy as np
import pytest

from scalable_agent_tpu.envs import base
from scalable_agent_tpu.envs.fake import FakeEnv
from scalable_agent_tpu.envs.jittable import ProcgenEnv
from scalable_agent_tpu.runtime import py_process
from scalable_agent_tpu.runtime.py_process import (
    ProcessClosed, ProxyEnv, PyProcess, RemoteError, SpecMismatchError)


class Calculator:
  """Arg-passing fixture: returns arrays computed from inputs."""

  def __init__(self, bias=0):
    self._bias = bias

  def add(self, x, y):
    return np.asarray(x + y + self._bias, np.int64)

  def pair(self, n):
    return (np.zeros((n,), np.float32), np.ones((n,), np.int32))


class SpeccedZeros:
  """Declares specs; can be told to violate them."""

  def __init__(self, violate=False):
    self._violate = violate

  def zeros(self):
    if self._violate:
      return np.zeros((3,), np.float64)  # wrong dtype and shape
    return np.zeros((2,), np.float32)

  @staticmethod
  def _tensor_specs(method_name, unused_kwargs, unused_ctor_kwargs):
    if method_name == 'zeros':
      return base.ArraySpec((2,), np.dtype(np.float32))
    return None


class FailsInCtor:

  def __init__(self):
    raise ValueError('ctor boom')


class FailsInMethod:

  def __init__(self, marker_path=None):
    self._marker_path = marker_path

  def ok(self):
    return np.int32(7)

  def boom(self):
    raise KeyError('method boom')

  def die(self):
    os._exit(1)  # simulate a crashed env process

  def close(self):
    if self._marker_path:
      with open(self._marker_path, 'w') as f:
        f.write('closed')


class Sleeper:
  """Split-call fixture: a method that takes a while."""

  def __init__(self, tag=0):
    self._tag = tag

  def nap(self, secs):
    import time
    time.sleep(secs)
    return np.int32(self._tag)


class PlatformProbeEnv(ProcgenEnv):
  """A real jittable host env that can also say where its JAX runs."""

  def jax_platforms(self):
    import jax
    return (jax.config.jax_platforms, jax.devices()[0].platform)


def _platform_probe_main():
  """Body of the child-pinning test's PARENT process (this file run as
  a script, under whatever JAX_PLATFORMS the test chose): host the
  probe env in a PyProcess exactly as the fleet does and print what
  the env child reports. The parent itself never touches a backend."""
  py_process.warm_forkserver()
  p = PyProcess(PlatformProbeEnv, dict(
      height=24, width=32, num_actions=4, episode_length=5)).start()
  try:
    frame, _ = p.proxy.initial()
    reward, done, _ = p.proxy.step(1)
    print('CHILD_PLATFORMS', *p.proxy.jax_platforms(), frame.shape,
          flush=True)
  finally:
    p.close()


def test_proxy_arg_passing():
  p = PyProcess(Calculator, dict(bias=10)).start()
  try:
    assert p.proxy.add(1, y=2) == 13
    zeros, ones = p.proxy.pair(4)
    np.testing.assert_array_equal(zeros, np.zeros(4, np.float32))
    np.testing.assert_array_equal(ones, np.ones(4, np.int32))
  finally:
    p.close()


def test_specs_validated_ok_and_mismatch():
  ok = PyProcess(SpeccedZeros).start()
  bad = PyProcess(SpeccedZeros, dict(violate=True)).start()
  try:
    np.testing.assert_array_equal(ok.proxy.zeros(),
                                  np.zeros((2,), np.float32))
    with pytest.raises(SpecMismatchError):
      bad.proxy.zeros()
  finally:
    ok.close()
    bad.close()


def test_constructor_exception_propagates():
  p = PyProcess(FailsInCtor).start()
  try:
    with pytest.raises(RemoteError, match='ctor boom'):
      p.proxy.anything()
  finally:
    p.close()


def test_method_exception_propagates_and_worker_survives():
  p = PyProcess(FailsInMethod).start()
  try:
    with pytest.raises(RemoteError, match='method boom'):
      p.proxy.boom()
    # The worker keeps serving after a method error (reference semantics).
    assert p.proxy.ok() == 7
  finally:
    p.close()


def test_close_reaches_hosted_object(tmp_path):
  marker = str(tmp_path / 'closed.txt')
  p = PyProcess(FailsInMethod, dict(marker_path=marker)).start()
  assert p.proxy.ok() == 7
  p.close()
  assert open(marker).read() == 'closed'
  p.close()  # idempotent


def test_dead_process_raises_process_closed():
  p = PyProcess(FailsInMethod).start()
  try:
    with pytest.raises(ProcessClosed):
      p.proxy.die()
    with pytest.raises(ProcessClosed):
      p.proxy.ok()
  finally:
    p.close()


# --- a call in two halves (PR 26): `_send` to k children, then
# `_receive` from each, so one actor thread has k envs stepping at once.


def test_split_call_steps_children_concurrently():
  import time
  procs = [PyProcess(Sleeper, dict(tag=i)).start() for i in range(3)]
  try:
    for p in procs:
      p.proxy.nap(0.0)  # children constructed before the clock starts
    t0 = time.monotonic()
    for p in procs:
      p._send('nap', (0.4,), {})
    tags = [int(p._receive()) for p in procs]
    took = time.monotonic() - t0
    assert tags == [0, 1, 2]
    assert took < 1.0, took  # three naps in turn would take 1.2 s
    # The whole call is still the two halves in a row.
    assert int(procs[1].proxy.nap(0.0)) == 1
  finally:
    py_process.close_all(procs)


def test_split_call_refuses_a_second_send_and_a_lone_receive():
  p = PyProcess(FailsInMethod).start()
  try:
    with pytest.raises(RuntimeError, match='receive without a send'):
      p._receive()
    p._send('ok', (), {})
    with pytest.raises(RuntimeError, match='send before the receive'):
      p._send('ok', (), {})
    assert p._receive() == 7
    # The refusal left the pipe in step: the next call is answered.
    assert p.proxy.ok() == 7
  finally:
    p.close()


def test_split_call_reports_ctor_failure_on_first_call():
  """The "ctor failure reported on first proxy call" contract, whether
  the child's pipe is found closed at the send or at the receive."""
  p = PyProcess(FailsInCtor).start()
  try:
    p._process.join(30)  # the child has sent its error and gone
    p._send('anything', (), {})
    with pytest.raises(RemoteError, match='ctor boom'):
      p._receive()
  finally:
    p.close()


def test_split_call_closed_pipe_contract():
  p = PyProcess(FailsInMethod).start()
  try:
    # The child dies between the halves: the receive says so, and
    # gives the lock back (the next send fails fast, not parked).
    p._send('die', (), {})
    with pytest.raises(ProcessClosed):
      p._receive()
    with pytest.raises(ProcessClosed):
      p._send('ok', (), {})
      p._receive()
    # A remote exception comes back at the receive, and the worker of
    # a fresh process keeps serving.
    q = PyProcess(FailsInMethod).start()
    try:
      q._send('boom', (), {})
      with pytest.raises(RemoteError, match='method boom'):
        q._receive()
      assert q.proxy.ok() == 7
    finally:
      q.close()
  finally:
    p.close()
  with pytest.raises(ProcessClosed):
    p._send('ok', (), {})


def test_split_call_send_failure_gives_the_lock_back():
  p = PyProcess(Calculator).start()
  try:
    with pytest.raises(TypeError, match='could not serialize'):
      p._send('add', (lambda: 0, 1), {})
    assert p.proxy.add(1, 2) == 3
  finally:
    p.close()


def test_proxy_env_split_step_matches_step():
  kwargs = dict(height=8, width=8, episode_length=3, seed=4)
  whole = ProxyEnv(PyProcess(FakeEnv, kwargs).start())
  halves = ProxyEnv(PyProcess(FakeEnv, kwargs).start())
  try:
    whole.initial()
    halves.initial()
    for i in range(5):
      halves.step_send(i % 2)
      a, b = whole.step(i % 2), halves.step_receive()
      assert a[0] == b[0] and a[1] == b[1]
      np.testing.assert_array_equal(a[2][0], b[2][0])
  finally:
    whole.close()
    halves.close()


# --- `step` through a shared block (PR 33) ---


class WrongStepEnv(FakeEnv):
  """Block fixture: its third step breaks its declared spec (`wrong`:
  'shape', 'dtype' or 'structure'), raises ('raise'), takes a while
  ('sleep') or ends the process ('die'). The other steps are FakeEnv's."""

  def __init__(self, wrong=None, **kw):
    super().__init__(**kw)
    self._wrong, self._steps = wrong, 0

  def step(self, action):
    import time
    reward, done, (frame, instr) = super().step(action)
    self._steps += 1
    if self._steps == 3:
      if self._wrong == 'shape':
        frame = frame[:-1]
      elif self._wrong == 'dtype':
        reward = float(reward)
      elif self._wrong == 'structure':
        return reward, done
      elif self._wrong == 'raise':
        raise KeyError('step boom')
      elif self._wrong == 'sleep':
        time.sleep(60)
      elif self._wrong == 'die':
        os._exit(1)
    return reward, done, (frame, instr)


def _token_env_kwargs(**kw):
  return dict(vocab_size=11, episode_length=6, prompt_length=3, **kw)


def _attached(env_class, kwargs_list, rows=4, **process_kwargs):
  """Hosted envs attached to one shared block, as an ActorGroup does
  it -> (envs, block); the block's name is gone again."""
  envs = [ProxyEnv(PyProcess(env_class, kw, **process_kwargs).start())
          for kw in kwargs_list]
  block = py_process.StepBlock.create(
      envs[0].step_block_specs(), rows, len(envs))
  try:
    for j, env in enumerate(envs):
      env.attach_block(block, j)
  finally:
    block.unlink()
  return envs, block


def _block_step(envs, block, row, actions):
  py_process.StepPass(block, envs).step(row, actions)


def _segments_of(pid):
  return [name for name in os.listdir(py_process._BLOCK_DIR)
          if name.startswith(f'step_block_{pid}_')]


def _block_cases():
  from scalable_agent_tpu.envs.tokens import TokenEnv
  return [
      pytest.param(FakeEnv, dict(height=8, width=8, episode_length=3),
                   id='image'),
      pytest.param(TokenEnv, _token_env_kwargs(), id='tokens')]


@pytest.mark.parametrize('env_class,kwargs', _block_cases())
def test_block_step_is_bitwise_the_pickled_step(env_class, kwargs):
  """Frame for frame, reward for reward, done for done, in their own
  dtypes: k children stepped in one pass over a block (`StepPass`)
  against the same envs stepped by pickled calls, whole and in halves
  (`step_send` to each, then `step_receive` from each, as a group
  without a block steps them). Rows are reused as an unroll reuses
  them, and one pass object serves every step."""
  k, rows = 3, 4
  kwargs_list = [dict(kwargs, seed=i) for i in range(k)]
  piped, halves = [
      [ProxyEnv(PyProcess(env_class, kw, step_block=False).start())
       for kw in kwargs_list] for _ in range(2)]
  envs, block = _attached(env_class, kwargs_list, rows)
  one_pass = py_process.StepPass(block, envs)
  try:
    assert piped[0].step_block_specs() is None  # the argument's doing
    assert not _segments_of(os.getpid())
    for env in piped + halves + envs:
      env.initial()
    for t in range(9):
      row = 1 + t % (rows - 1)
      actions = [(t + j) % 3 for j in range(k)]
      assert one_pass.step(row, np.asarray(actions, np.int32)) is None
      for env, action in zip(halves, actions):
        env.step_send(action)
      halved = [env.step_receive() for env in halves]
      for j, env in enumerate(piped):
        reward, done, observation = env.step(actions[j])
        assert block.reward[row, j] == reward
        assert block.done[row, j] == done
        leaves = list(py_process._leaves(observation))
        assert len(leaves) == len(block.leaves)
        for leaf, column in zip(leaves, block.leaves):
          np.testing.assert_array_equal(column[row, j], leaf)
          assert column.dtype == np.asarray(leaf).dtype
        other = list(py_process._leaves(halved[j]))
        assert len(other) == 2 + len(leaves)
        for x, y in zip(other, [reward, done] + leaves):
          np.testing.assert_array_equal(x, y)
          assert np.asarray(x).dtype == np.asarray(y).dtype
    assert one_pass.waiting is None and one_pass.failed is None
    assert [e._process.block_steps for e in envs] == [9] * k
    assert [e._process.block_steps for e in piped + halves] == [0] * 2 * k
    # `initial` and attaching went down the pipe, like every `step`
    # of the others.
    assert [e._process.pipe_calls for e in envs] == [2] * k
    assert [e._process.pipe_calls for e in piped + halves] == [10] * 2 * k
  finally:
    py_process.close_all([e._process for e in piped + halves + envs])


class SleepyEnv(FakeEnv):
  """FakeEnv whose `step` takes `nap_ms` of its own."""

  def __init__(self, nap_ms=2.0, **kw):
    super().__init__(**kw)
    self._nap = nap_ms / 1e3

  def step(self, action):
    import time
    time.sleep(self._nap)
    return super().step(action)


def test_a_step_carries_the_childs_own_time_by_block_and_by_pipe():
  """PR 37: the hosted env's worker times its `env.step` alone, on its
  own clock: into its column of the block's `busy_ns`, or, for a call
  down the pipe, behind the reply. A duration, so whoever reads it
  needs no clock in common with the child."""
  import time
  kwargs = dict(height=8, width=8, episode_length=30)
  kwargs_list = [dict(kwargs, seed=j, nap_ms=2.0 + 4.0 * j)
                 for j in range(2)]
  piped = ProxyEnv(PyProcess(SleepyEnv, kwargs_list[1],
                             step_block=False).start())
  envs, block = _attached(SleepyEnv, kwargs_list, rows=3)
  try:
    for env in envs + [piped]:
      env.initial()
    assert piped.step_busy_ns() < 2e6  # `initial` took no nap
    for t in range(3):
      t0 = time.perf_counter_ns()
      _block_step(envs, block, 1 + t % 2, [0, 1])
      round_trip = time.perf_counter_ns() - t0
      # Each column its own child's, the slowest under the round trip.
      assert 2e6 <= block.busy_ns[0] < 6e6 <= block.busy_ns[1]
      assert block.busy_ns[1] <= round_trip
      t0 = time.perf_counter_ns()
      piped.step(0)
      assert 6e6 <= piped.step_busy_ns() <= time.perf_counter_ns() - t0
  finally:
    py_process.close_all([e._process for e in envs + [piped]])


def test_other_calls_ride_the_pipe_between_block_steps():
  """A pickled call (`prompt_block`: a block of tokens; `initial`; the
  whole `step`) between two steps through the block: raw bytes and
  pickled messages never meet on the pipe, and `close` still reaches
  the child."""
  from scalable_agent_tpu.envs.tokens import TokenEnv
  kwargs = _token_env_kwargs(prompt_block=8, seed=5)
  (env,), block = _attached(TokenEnv, [kwargs])
  alone = TokenEnv(**kwargs)
  try:
    assert env.initial() == alone.initial()
    begins, prompts, whole = True, 0, 0
    for t in range(14):  # over three episode ends
      if begins:
        tokens, n = env.prompt_block()
        want_tokens, want_n = alone.prompt_block()
        np.testing.assert_array_equal(tokens, want_tokens)
        assert n == want_n
        prompts += 1
      reward, done, (token,) = alone.step(t % 11)
      if t % 5 == 4:  # `step` in one piece stays a pickled call
        assert env.step(t % 11) == (reward, done, (token,))
        whole += 1
      else:
        row = 1 + t % 3
        _block_step([env], block, row, [t % 11])
        assert (block.reward[row, 0], block.done[row, 0],
                block.leaves[0][row, 0]) == (reward, done, token)
      begins = bool(done)
    process = env._process
    assert (prompts, whole) == (4, 2)
    assert process.block_steps == 12
    # attach, initial, the prompt blocks, the whole steps
    assert process.pipe_calls == 2 + prompts + whole
  finally:
    env.close()
  assert not env._process.running
  assert env._process.pipe_calls == 9  # `close` went down the pipe too


@pytest.mark.parametrize('wrong', ['shape', 'dtype', 'structure'])
def test_block_step_checks_the_spec_in_the_child(wrong):
  """A reply that breaks the declared spec never reaches the block:
  the pass raises SpecMismatchError naming the method, as a pickled
  reply does, and the child serves on."""
  kwargs = dict(height=8, width=8, wrong=wrong)
  envs, block = _attached(WrongStepEnv, [kwargs, dict(height=8, width=8)])
  one_pass = py_process.StepPass(block, envs)
  try:
    for t in range(2):
      one_pass.step(1, [0, 0])
    with pytest.raises(SpecMismatchError, match='WrongStepEnv.step'):
      one_pass.step(2, [1, 1])
    assert one_pass.failed == 0
    assert block.seq[1] == block.step_seq  # its mate's step is whole
    assert block.seq[0] != block.step_seq  # nothing was written
    one_pass.step(3, [1, 1])
    assert one_pass.failed is None
    assert block.seq[0] == block.seq[1] == block.step_seq
  finally:
    py_process.close_all([e._process for e in envs])


def test_block_step_failures_keep_the_pipes_contract():
  """In one pass: an exception of the env's comes back as RemoteError
  and the worker serves on; a child that dies between wake-up and
  answer is ProcessClosed, and so is every send to it after; one that
  hangs is killed by `close`, which waits out its timeout for the call
  lock the pass holds and so breaks the parked read. The first failure
  by column is the one raised, after every answer is in."""
  import threading
  import time
  envs, block = _attached(WrongStepEnv, [
      dict(height=8, width=8, wrong=w) for w in ('raise', 'die', 'sleep')])
  raising, dying, hanging = envs
  one_pass = py_process.StepPass(block, envs)
  try:
    for t in range(2):
      one_pass.step(1, [0, 0, 0])
    closer = threading.Timer(
        0.2, lambda: hanging._process.close(timeout=0.5))
    closer.start()
    t0 = time.monotonic()
    with pytest.raises(RemoteError, match='step boom'):
      one_pass.step(2, [1, 1, 1])
    assert time.monotonic() - t0 < 10
    closer.join(timeout=10)
    assert one_pass.failed == 0 and one_pass.waiting is None
    with pytest.raises(ProcessClosed):
      dying.step_send(1)
    with pytest.raises(ProcessClosed):
      one_pass.step(3, [1, 1, 1])  # the dead are no one's column now
    assert one_pass.failed == 1
    # The one that raised serves on, its pipe in step: pickled halves
    # now, and they still refuse a second send before the receive.
    raising.step_send(1)
    with pytest.raises(RuntimeError, match='send before the receive'):
      raising.step_send(1)
    reward, done, (frame, _) = raising.step_receive()
    assert frame.shape == (8, 8, 3)
  finally:
    py_process.close_all([e._process for e in envs], timeout=1.0)


def _step_within(one_pass, row, actions, limit_s=30.0):
  """`one_pass.step` on a thread of its own -> (the exception it
  raised or None, seconds taken); fails the test if it is not done
  within `limit_s`."""
  import threading
  import time
  out = {}

  def run():
    t0 = time.monotonic()
    try:
      one_pass.step(row, actions)
    except BaseException as e:  # handed to the test
      out['error'] = e
    out['seconds'] = time.monotonic() - t0

  thread = threading.Thread(target=run, daemon=True)
  thread.start()
  thread.join(limit_s)
  assert not thread.is_alive(), f'the pass hung past {limit_s} s'
  return out.get('error'), out['seconds']


@pytest.mark.parametrize('wrongs,error', [
    ((None, 'raise', 'shape'), RemoteError),
    ((None, 'shape', 'raise'), SpecMismatchError),
], ids=['raise_then_mismatch', 'mismatch_then_raise'])
def test_a_pass_raises_the_first_failure_and_collects_the_rest(
    wrongs, error):
  """Two members fail one pass, each its own way: the first by column
  is raised and named (`failed`), the other's pickled failure is read
  off its pipe too, and a pickled call to each member afterwards is
  answered as if nothing had happened."""
  envs, block = _attached(WrongStepEnv, [
      dict(height=8, width=8, wrong=w) for w in wrongs])
  one_pass = py_process.StepPass(block, envs)
  try:
    for t in range(2):
      one_pass.step(1, [0, 0, 0])
    raised, _ = _step_within(one_pass, 2, [1, 1, 1])
    assert type(raised) is error and 'WrongStepEnv.step' in str(raised)
    assert one_pass.failed == 1 and one_pass.waiting is None
    assert block.seq[0] == block.step_seq  # the healthy one is whole
    for env in envs:
      frame, _ = env.initial()
      assert frame.shape == (8, 8, 3)
      assert env._process._pending is None
    one_pass.step(3, [1, 1, 1])  # and the next pass is whole
    assert (block.seq == block.step_seq).all()
  finally:
    py_process.close_all([e._process for e in envs])


@pytest.mark.parametrize('when', ['before', 'during'])
def test_a_child_killed_before_or_during_a_pass_is_process_closed(when):
  """A child killed outright (SIGKILL), before the pass wakes it or
  while it steps: the pass raises ProcessClosed for its column within
  the test's own time limit, not a hang, and its mates were answered."""
  import signal
  import threading
  envs, block = _attached(WrongStepEnv, [
      dict(height=8, width=8, wrong='sleep' if j == 1 else None)
      for j in range(3)])
  one_pass = py_process.StepPass(block, envs)
  victim = envs[1]._process._process
  try:
    for t in range(2):
      one_pass.step(1, [0, 0, 0])
    if when == 'before':
      victim.kill()
      victim.join(10)
    else:  # its third step sleeps for a minute: killed in it
      threading.Timer(0.3, victim.kill).start()
    raised, seconds = _step_within(one_pass, 2, [1, 1, 1])
    assert isinstance(raised, ProcessClosed), raised
    assert one_pass.failed == 1 and seconds < 20
    victim.join(10)
    assert victim.exitcode == -signal.SIGKILL
    for env in envs[0::2]:
      assert env.initial()[0].shape == (8, 8, 3)
  finally:
    py_process.close_all([e._process for e in envs], timeout=1.0)


def test_a_row_from_before_is_never_taken_for_the_step():
  """The sequence number: an acknowledgement over a row that an
  earlier step wrote (here: the parent moved on without telling the
  child) is refused."""
  (env,), block = _attached(FakeEnv, [dict(height=8, width=8)])
  one_pass = py_process.StepPass(block, [env])
  begin = block.begin_step

  def forgetful(row):
    begin(row)
    block.step_seq += 1  # `want` still says the step before

  try:
    one_pass.step(1, [0])
    block.begin_step = forgetful
    with pytest.raises(RemoteError, match='a row from before'):
      one_pass.step(1, [0])
    assert one_pass.failed == 0
    assert env.initial()[0].shape == (8, 8, 3)  # the pipe is in step
  finally:
    env.close()


def test_a_type_without_a_fixed_step_spec_stays_on_the_pipe():
  """No `_tensor_specs`, or a `step` spec that is no (reward, done,
  observation) of declared shapes: there is nothing to lay a block
  out from."""
  class Spec(base.ArraySpec):
    pass

  assert PyProcess(Calculator).step_block_specs() is None
  assert PyProcess(SpeccedZeros).step_block_specs() is None
  assert py_process.step_block_specs(None) is None
  assert py_process.step_block_specs(
      (Spec((), np.float32), Spec((), bool))) is None
  assert py_process.step_block_specs(
      (Spec((2,), np.float32), Spec((), bool), (Spec((3,), np.int32),))
  ) is None  # a reward that is no scalar
  assert py_process.step_block_specs(
      (Spec((), np.float32), Spec((), bool),
       (Spec((None, 3), np.int32),))) is None  # a shape left open
  assert py_process.step_block_specs(
      (Spec((), np.float32), Spec((), bool),
       [Spec((4, 3), np.uint8), (Spec((), np.int32),)])) == [
           ((4, 3), '|u1'), ((), '<i4')]
  assert PyProcess(FakeEnv, dict(height=8, width=8)).step_block_specs() == [
      ((8, 8, 3), '|u1'),
      ((FakeEnv(height=8, width=8).initial()[1].shape[0],), '<i4')]


def _killed_parent_main():
  """Body of the killed-parent test's PARENT process (this file run as
  a script): a group of hosted envs on a block, then its own SIGKILL
  mid-unroll, the children parked on their pipes."""
  import signal
  py_process.warm_forkserver()
  envs, block = _attached(FakeEnv, [dict(height=8, width=8)] * 3)
  _block_step(envs, block, 1, [0, 0, 0])
  print('PIDS', os.getpid(), *[e._process._process.pid for e in envs],
        flush=True)
  os.kill(os.getpid(), signal.SIGKILL)


def test_a_killed_parent_leaves_no_segment_and_no_process():
  """The block's name is gone as soon as every child has mapped it, so
  there is nothing to clean up however the parent ends; its children
  see their pipes close and go."""
  import time
  out = subprocess.run(
      [sys.executable, os.path.abspath(__file__), '--killed-parent'],
      capture_output=True, text=True, timeout=120,
      env=dict(os.environ, PYTHONPATH=os.pathsep.join(
          [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
          + sys.path)))
  assert out.returncode == -9, (out.returncode, out.stderr[-2000:])
  parent, *children = map(int, out.stdout.split('PIDS')[1].split())
  assert len(children) == 3
  assert not _segments_of(parent)
  deadline = time.monotonic() + 30
  while time.monotonic() < deadline and any(
      os.path.exists(f'/proc/{pid}') for pid in children):
    time.sleep(0.1)
  assert not [pid for pid in children if os.path.exists(f'/proc/{pid}')]


def test_fleet_lifecycle():
  procs = [PyProcess(Calculator, dict(bias=i)) for i in range(4)]
  with py_process.hosted(procs) as started:
    assert all(p.running for p in started)
    assert [int(p.proxy.add(0, 0)) for p in started] == [0, 1, 2, 3]
  assert not any(p.running for p in procs)


def test_proxy_env_runs_fake_env_out_of_process():
  """A hosted FakeEnv behind ProxyEnv speaks the Environment contract
  (spec-validated), end to end across the process boundary."""
  p = PyProcess(FakeEnv, dict(height=8, width=8, episode_length=3)).start()
  env = ProxyEnv(p)
  try:
    frame, instr = env.initial()
    assert frame.shape == (8, 8, 3) and frame.dtype == np.uint8
    dones = []
    for i in range(6):
      reward, done, obs = env.step(i % 2)
      dones.append(bool(done))
    assert dones == [False, False, True, False, False, True]
  finally:
    env.close()


def test_py_process_hook_lifecycle():
  """Reference-named hook: begin() starts the fleet, end() closes it
  (reference: PyProcessHook ≈L190)."""
  from scalable_agent_tpu.envs.fake import FakeEnv
  from scalable_agent_tpu.runtime.py_process import (
      ProxyEnv, PyProcess, PyProcessHook)
  processes = [PyProcess(FakeEnv,
                         constructor_kwargs=dict(height=8, width=8))
               for _ in range(2)]
  hook = PyProcessHook(processes)
  hook.begin()
  try:
    envs = [ProxyEnv(p) for p in processes]
    for env in envs:
      frame, _ = env.initial()
      assert frame.shape == (8, 8, 3)
  finally:
    hook.end()
  assert all(not p.running for p in processes)


def test_env_child_is_pinned_to_cpu_whatever_the_parent_runs_on():
  """One process per chip: the learner holds it, so a process-hosted
  jittable env (it steps its core through JAX) must come up on the CPU
  backend whether the parent was launched with JAX_PLATFORMS unset
  (every backend would initialise in the child, the chip included) or
  `tpu` (the child's CPU device lookup would raise). The two parents
  run side by side."""
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  parents = {}
  for parent_platforms in (None, 'tpu'):
    env = {k: v for k, v in os.environ.items()
           if k not in ('JAX_PLATFORMS', 'XLA_FLAGS')}
    if parent_platforms is not None:
      env['JAX_PLATFORMS'] = parent_platforms
    env['PYTHONPATH'] = repo + os.pathsep + env.get('PYTHONPATH', '')
    parents[parent_platforms] = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], cwd=repo, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
  for parent_platforms, parent in parents.items():
    stdout, stderr = parent.communicate(timeout=120)
    assert parent.returncode == 0, (parent_platforms, stderr[-2000:])
    assert 'CHILD_PLATFORMS cpu cpu (24, 32, 3)' in stdout, (
        parent_platforms, stdout)


_STOP_PROBE = """
import os
from scalable_agent_tpu.envs.fake import FakeEnv
from scalable_agent_tpu.runtime import py_process

def children():
  mine = []
  for name in os.listdir('/proc'):
    if name.isdigit():
      try:
        with open(f'/proc/{name}/stat') as f:
          ppid = int(f.read().rsplit(')', 1)[1].split()[1])
      except OSError:
        continue
      if ppid == os.getpid():
        mine.append(int(name))
  return mine

py_process.warm_forkserver()
with py_process.hosted([py_process.PyProcess(
    FakeEnv, constructor_kwargs=dict(height=8, width=8))]):
  started = children()
py_process.stop_forkserver()
py_process.stop_forkserver()  # idempotent
print('STARTED', len(started), 'LEFT', children())
"""


def test_stop_forkserver_leaves_no_child_behind():
  """The forkserver and the resource tracker outlive their parent by a
  moment when left alone; stopped, they are gone (and reaped) before
  the call returns."""
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ, JAX_PLATFORMS='cpu',
             PYTHONPATH=repo + os.pathsep + os.environ.get('PYTHONPATH', ''))
  out = subprocess.run([sys.executable, '-c', _STOP_PROBE], cwd=repo,
                       env=env, capture_output=True, text=True, timeout=120)
  assert out.returncode == 0, out.stderr[-2000:]
  assert 'STARTED 2 LEFT []' in out.stdout, out.stdout


if __name__ == '__main__':
  if '--killed-parent' in sys.argv:
    _killed_parent_main()
  else:
    _platform_probe_main()

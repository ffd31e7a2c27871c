"""Process-hosted Python objects (the reference's py_process, TPU-build).

Runs an arbitrary Python class (typically an environment) in a separate
OS process — the GIL escape that lets dozens of envs step concurrently —
and exposes its methods to the host runtime as blocking calls over a
pipe. Re-expresses the reference's `py_process.py` (reference:
py_process.py ≈L50–230) without the TF graph: there is no `tf.py_func`
to wrap because on the TPU build env stepping is host Python already
(runtime/actor.py); what survives is the process-hosting contract:

- `PyProcess(type_, constructor_kwargs)` + `.proxy.<method>(*args)` —
  the call is sent over a pipe, the caller blocks on the reply
  (reference `_TFProxy.__getattr__` ≈L50). Two one-way OS pipes, one
  down and one up, not a socket pair: a byte each way costs the kernel
  about half as much on a pipe.
- `_tensor_specs(method_name, kwargs, constructor_kwargs)` protocol —
  classes declare the dtypes/shapes of method results; the parent
  validates replies against the declaration (the reference needed this
  to build graph ops; here it is a runtime contract check that keeps
  fixed-shape numerics the only thing crossing the boundary).
- Exceptions raised in the constructor or in a method are serialized
  back (with the remote traceback) and re-raised at the call site
  (reference ≈L60–80); the worker keeps serving after a method error.
- A broken/closed pipe raises `ProcessClosed` — the clean-shutdown
  signal, the reference's `IOError → StopIteration` convention (≈L72).
- `start_all` / `close_all` start/stop fleets via a thread pool — the
  reference's `PyProcessHook.begin/end` (≈L190–230) without the session.
- `step` of an env whose declared reply has a fixed spec can go through
  a `StepBlock` in shared memory instead (PR 33): the action and the
  reply live in a block that the caller's group owns and the child has
  mapped (`attach_block`), and the pipe carries one byte each way. A
  `StepPass` steps every env of the block at once, in one pass over
  its columns. Every other call stays a pickled message on the same
  pipe.

Start method: `forkserver` by default. The driver builds env processes
AFTER JAX's inference warmup, i.e. from a parent already running JAX
thread pools — a plain `fork` there copies whatever mutexes happen to
be locked (Python 3.12 warns exactly about this), the classic
once-a-week CI hang. With forkserver, children are forked from the
clean single-threaded server process instead; call `warm_forkserver()`
as early as possible (before JAX spins up) so the one-time fork that
creates the server itself happens from a still-quiet parent.
Constructor kwargs and the hosted class must be picklable (module
level). `fork` remains available as an explicit opt-in for
unpicklable fixtures; `spawn` for classes needing a pristine
interpreter.
"""

import mmap
import multiprocessing
import os
import sys
import tempfile
import threading
import time
import traceback
from multiprocessing.pool import ThreadPool
from multiprocessing.reduction import ForkingPickler

import numpy as np

from scalable_agent_tpu import telemetry

DEFAULT_START_METHOD = 'forkserver'


def warm_forkserver():
  """Start the forkserver process now (idempotent). Best called before
  any JAX import/initialization — see the module docstring.

  The server imports this package once, so every child forks with
  jax/flax already loaded. A child otherwise spends seconds importing
  them before it can unpickle its env class, and fleet.start() waits
  for each actor's first observation in turn: start-up linear in the
  fleet size (about 5 s per actor measured on the chip machine).
  `python experiment.py` got this by accident — the default preload is
  `__main__`, and experiment.py imports the package — while the
  tests and chip_smoke.py did not."""
  from multiprocessing import forkserver
  multiprocessing.set_forkserver_preload(
      ['__main__', 'scalable_agent_tpu.envs.factory'])
  forkserver.ensure_running()


def stop_forkserver():
  """Stop the forkserver and the resource tracker now and wait for both
  (idempotent; the next start brings them back). Left alone they end by
  themselves once this process has gone, but only afterwards — the
  server not before its preload imports finish — so a caller that must
  leave nothing running behind it stops them first. Stop the env
  processes before: the server does not take its children with it.

  The stdlib has no public call for this; `_stop` is what its own test
  clean-up (multiprocessing.util._cleanup_tests) uses."""
  from multiprocessing import forkserver, resource_tracker
  forkserver._forkserver._stop()
  # The tracker ends when the last copy of its pipe closes; the server
  # held one, so it goes second.
  resource_tracker._resource_tracker._stop()


class ProcessClosed(Exception):
  """The hosted process's pipe is closed (clean shutdown or death)."""


class RemoteError(Exception):
  """An exception raised inside the hosted process.

  Carries the remote traceback text; the original exception (when
  picklable) is chained as `__cause__`."""


class SpecMismatchError(Exception):
  """A method reply did not match the class's `_tensor_specs`."""


_CLOSE = '__process_close__'
_ATTACH = '__process_attach_block__'
# One byte goes ahead of everything the parent asks for, so that the
# child knows what to read next: a pickled (method, args, kwargs), or
# nothing more, for a step through its block. A step's answer is one
# byte too: the row is written, or a pickled failure follows. Raw
# bytes and pickled messages never interleave: the call lock keeps a
# call's two halves together.
_CALL, _STEP = b'C', b'S'
_STEPPED, _FAILED = b'K', b'X'
_BLOCK_DIR = '/dev/shm'


def pin_process_to_cpu():
  """Keep THIS process off the accelerator: a chip belongs to one
  process, the learner, and a child that initialises the default
  backend while the parent holds the chip fails or hangs.

  Call it first thing in a child, before anything can use JAX. The
  environment variable covers a `jax` not imported yet; the config
  update covers one imported already (unpickling a hosted class
  imports its module before the worker body runs, and jax reads the
  variable at import only). Whatever JAX_PLATFORMS the parent was
  launched with — unset, `tpu`, anything — the child then finds
  exactly the CPU backend (envs/jittable.py steps its cores on it)."""
  os.environ['JAX_PLATFORMS'] = 'cpu'
  jax = sys.modules.get('jax')
  if jax is not None:
    jax.config.update('jax_platforms', 'cpu')


def _leaves(tree):
  """The leaves of a pytree of tuples and lists, in order (an
  ArraySpec is a tuple and a leaf)."""
  if isinstance(tree, (tuple, list)) and not hasattr(tree, 'dtype'):
    for node in tree:
      yield from _leaves(node)
  else:
    yield tree


def step_block_specs(specs):
  """[(shape, dtype str)] of the observation's leaves, if a `step`
  spec is one a StepBlock can hold: (reward, done, observation) with
  scalar reward and done and every observation leaf of a declared
  shape and dtype. None otherwise."""
  if (not isinstance(specs, (tuple, list)) or hasattr(specs, 'dtype')
      or len(specs) != 3):
    return None
  leaves = list(_leaves(specs))
  if len(leaves) < 3 or not all(
      hasattr(leaf, 'shape') and hasattr(leaf, 'dtype') and
      all(isinstance(d, int) for d in leaf.shape) for leaf in leaves):
    return None
  if any(not hasattr(spec, 'dtype') or tuple(spec.shape)
         for spec in specs[:2]):
    return None
  return [(tuple(leaf.shape), np.dtype(leaf.dtype).str)
          for leaf in _leaves(specs[2])]


class StepBlock:
  """The steps of `columns` envs over `rows` steps, in one buffer:
  `reward` f32 and `done` bool [rows, columns], and `leaves`, the
  observation's leaves as [rows, columns, ...] arrays, so that step
  t of all the envs is the contiguous `leaf[t]` and one env's steps
  are `leaf[:, j]`.

  `create` puts the buffer in shared memory: a hosted env that has
  mapped it (`PyProcess.attach_block`) writes its own column, and a
  step costs the pipe one byte each way. Per column the parent says
  what it wants (`action`, the `row` to write, and `want`, a number
  no earlier step of this block had) and the child says what it did
  (`seq` = `want`, written after the row), so that a row left from an
  earlier step can never be taken for the one asked for. `busy_ns`
  is each column's own time in its env's `step`, the last one's: a
  duration on the clock of whoever stepped the env (the child, for a
  hosted one), so the clocks need not agree. The file is named only
  until every child has mapped it (`unlink`): nothing is left to clean
  up, however the processes end. `private` is the same layout in this
  process's own memory, for envs that cannot map it.
  """

  def __init__(self, leaf_specs, rows, columns, buffer, path=None):
    self.leaf_specs = [(tuple(shape), dtype) for shape, dtype in leaf_specs]
    self.rows, self.columns, self.path = rows, columns, path
    self.step_seq = 0
    fields, _ = self._layout(self.leaf_specs, rows, columns)
    arrays = [
        np.frombuffer(buffer, dtype, int(np.prod(shape, dtype=np.int64)),
                      offset).reshape(shape)
        for shape, dtype, offset in fields]
    (self.action, self.row, self.want, self.seq, self.busy_ns,
     self.reward, self.done) = arrays[:7]
    self.leaves = arrays[7:]

  @staticmethod
  def _layout(leaf_specs, rows, columns):
    """[(shape, dtype, offset)] of the fields, and the bytes in all."""
    shapes = [((columns,), 'i4'), ((columns,), 'i4'), ((columns,), 'i8'),
              ((columns,), 'i8'), ((columns,), 'i8'),
              ((rows, columns), 'f4'), ((rows, columns), '?')]
    shapes += [((rows, columns) + shape, dtype)
               for shape, dtype in leaf_specs]
    fields, offset = [], 0
    for shape, dtype in shapes:
      fields.append((shape, dtype, offset))
      size = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
      offset += -(-size // 64) * 64
    return fields, max(offset, 64)

  @classmethod
  def private(cls, leaf_specs, rows, columns):
    _, size = cls._layout(leaf_specs, rows, columns)
    block = cls(leaf_specs, rows, columns, np.empty(size, np.uint8))
    block.busy_ns[:] = 0  # a column nobody times took none
    return block

  @classmethod
  def create(cls, leaf_specs, rows, columns):
    """A block in shared memory. OSError where there is none to be
    had (no /dev/shm, or no room in it)."""
    _, size = cls._layout(leaf_specs, rows, columns)
    fd, path = tempfile.mkstemp(prefix=f'step_block_{os.getpid()}_',
                                dir=_BLOCK_DIR)
    try:
      os.posix_fallocate(fd, 0, size)  # room now, not a SIGBUS later
      return cls(leaf_specs, rows, columns, mmap.mmap(fd, size), path)
    except BaseException:
      os.unlink(path)
      raise
    finally:
      os.close(fd)

  @classmethod
  def open(cls, path, leaf_specs, rows, columns):
    """The block another process created, by its name."""
    _, size = cls._layout(leaf_specs, rows, columns)
    fd = os.open(path, os.O_RDWR)
    try:
      return cls(leaf_specs, rows, columns, mmap.mmap(fd, size))
    finally:
      os.close(fd)

  def unlink(self):
    """Take the name away (idempotent); the mappings stay."""
    path, self.path = self.path, None
    if path is not None:
      try:
        os.unlink(path)
      except OSError:
        pass

  def begin_step(self, row):
    """The parent, before it wakes the children: every column's next
    step goes to `row`, under a sequence number of its own."""
    self.step_seq += 1
    self.row[:] = row
    self.want[:] = self.step_seq


class _BlockStepper:
  """The child's side of a block: steps the env into its column."""

  def __init__(self, obj, specs, name, path, leaf_specs, rows, columns,
               column):
    self._obj, self._specs, self._name = obj, specs, name
    block = StepBlock.open(path, leaf_specs, rows, columns)
    self._action = block.action[column:column + 1]
    self._row = block.row[column:column + 1]
    self._want = block.want[column:column + 1]
    self._seq = block.seq[column:column + 1]
    self._busy_ns = block.busy_ns[column:column + 1]
    self._reward = block.reward[:, column]
    self._done = block.done[:, column]
    self._columns = [leaf[:, column] for leaf in block.leaves]

  def step(self):
    t0 = time.perf_counter_ns()
    result = self._obj.step(int(self._action[0]))
    self._busy_ns[0] = time.perf_counter_ns() - t0  # the env's own
    # The one check of the reply: here, beside the env, and not on the
    # thread that steps the whole group.
    _validate_specs(result, self._specs, self._name)
    reward, done, observation = result
    row = int(self._row[0])
    self._reward[row] = reward
    self._done[row] = done
    for column, leaf in zip(self._columns, _leaves(observation)):
      column[row] = leaf
    self._seq[0] = self._want[0]  # last: the row is whole


def _worker(down, up, type_, constructor_kwargs):
  """Worker loop: construct, then serve what comes `down` the parent's
  pipe, answering `up` the other: a pickled (method, args, kwargs), or
  a step through the block it was attached to."""
  pin_process_to_cpu()
  try:
    obj = type_(**constructor_kwargs)
  except Exception as e:  # ctor failure → reported on first proxy call
    up.send(('exception', _serialize_error(e)))
    up.close()
    down.close()
    return
  fd_down, fd_up, stepper = down.fileno(), up.fileno(), None
  while True:
    try:
      tag = os.read(fd_down, 1)
      if tag == _STEP:
        try:
          stepper.step()
          os.write(fd_up, _STEPPED)
        except SpecMismatchError as e:
          os.write(fd_up, _FAILED)
          up.send(('mismatch', str(e)))
        except Exception as e:  # keep serving, as after any method
          os.write(fd_up, _FAILED)
          up.send(('exception', _serialize_error(e)))
        continue
      if not tag:
        break  # parent died/closed: fall through to close the object
      request = down.recv()
    except (EOFError, OSError):
      break
    method, args, kwargs = request
    if method == _CLOSE:
      try:
        if hasattr(obj, 'close'):
          obj.close()
        up.send(('ok', None))
      except Exception as e:
        up.send(('exception', _serialize_error(e)))
      break
    try:
      t0 = time.perf_counter_ns()
      if method == _ATTACH:
        validate, *where = args
        specs = (type_._tensor_specs('step', {}, constructor_kwargs)
                 if validate else None)
        stepper = _BlockStepper(obj, specs, f'{type_.__name__}.step',
                                *where)
        result = None
      else:
        result = getattr(obj, method)(*args, **kwargs)
      # With the method's own time, on this process's clock.
      up.send(('ok', result, time.perf_counter_ns() - t0))
    except Exception as e:  # keep serving — reference semantics
      up.send(('exception', _serialize_error(e)))
  for conn in (up, down):
    try:
      conn.close()
    except OSError:
      pass


def _serialize_error(e):
  tb = ''.join(traceback.format_exception(type(e), e, e.__traceback__))
  try:
    import pickle
    pickle.dumps(e)
    payload = e
  except Exception:
    payload = None  # unpicklable exception: text only
  return (payload, tb)


def _validate_specs(result, specs, method):
  """Recursively check a reply against an ArraySpec pytree (None=skip)."""
  if specs is None:
    return
  if hasattr(specs, 'shape') and hasattr(specs, 'dtype'):
    arr = np.asarray(result)
    if tuple(arr.shape) != tuple(specs.shape) or arr.dtype != specs.dtype:
      raise SpecMismatchError(
          f'{method}: got shape={arr.shape} dtype={arr.dtype}, '
          f'spec requires shape={tuple(specs.shape)} dtype={specs.dtype}')
    return
  if isinstance(specs, (tuple, list)):
    if not isinstance(result, (tuple, list)) or len(result) != len(specs):
      raise SpecMismatchError(
          f'{method}: reply structure {type(result).__name__}'
          f'/{len(result) if hasattr(result, "__len__") else "?"} does '
          f'not match spec structure of length {len(specs)}')
    for r, s in zip(result, specs):
      _validate_specs(r, s, method)
    return
  raise SpecMismatchError(f'{method}: unsupported spec node {specs!r}')


class _Proxy:
  """Attribute access builds blocking remote calls (reference _TFProxy)."""

  def __init__(self, process):
    self._process = process

  def __getattr__(self, name):
    if name.startswith('_'):
      raise AttributeError(name)

    def call(*args, **kwargs):
      return self._process._call(name, args, kwargs)

    call.__name__ = name
    return call


class PyProcess:
  """Hosts an instance of `type_` in a child OS process.

  Args:
    type_: class to instantiate in the child. If it defines
      `_tensor_specs(method_name, kwargs, constructor_kwargs)` (static),
      replies are validated against the returned spec pytree.
    constructor_kwargs: kwargs for the child-side constructor (must be
      picklable under the default start method).
    context: multiprocessing start method (None = the module default,
      'forkserver'; 'fork'/'spawn' as explicit opt-ins).
    validate_specs: disable to skip reply validation (hot-path opt-out).
    step_block: False keeps `step` on the pickled pipe even where its
      declared spec would fit a StepBlock (for a test that holds the
      two paths side by side; nothing else sets it).
  """

  def __init__(self, type_, constructor_kwargs=None, context=None,
               validate_specs=True, step_block=True):
    self._type = type_
    self._constructor_kwargs = dict(constructor_kwargs or {})
    self._ctx = multiprocessing.get_context(
        context or DEFAULT_START_METHOD)
    self._validate = validate_specs and hasattr(type_, '_tensor_specs')
    self._step_block = step_block
    # The parent's ends of the pipe down to the child and of the one
    # up from it (`multiprocessing` Connections: None until started
    # and once closed).
    self._down = self._up = None
    self._process = None
    self._lock = threading.Lock()  # pipes are not thread-safe
    # A call between its two halves (_send, _receive): (the thread
    # that holds the lock for it, method, kwargs, a reply already in
    # hand, the env/pipe span).
    self._pending = None
    self._closed = False
    # Env steps that went through a block, and calls that went down
    # the pickled pipe (read by `ActorFleet.stats`).
    self.block_steps = 0
    self.pipe_calls = 0
    # The child's own time in the last pickled call's method, ns (a
    # step through a block leaves its own in the block's `busy_ns`).
    self.busy_ns = 0

  @property
  def proxy(self):
    return _Proxy(self)

  def start(self):
    if self._process is not None:
      raise RuntimeError('already started')
    child_down, self._down = self._ctx.Pipe(duplex=False)
    self._up, child_up = self._ctx.Pipe(duplex=False)
    self._process = self._ctx.Process(
        target=_worker,
        args=(child_down, child_up, self._type, self._constructor_kwargs),
        daemon=True)
    self._process.start()
    # The parent keeps one end of each: the child's death is EOF up,
    # and a write down after it is EPIPE.
    child_down.close()
    child_up.close()
    return self

  def step_block_specs(self):
    """The observation leaves of this env's declared `step` reply, if
    a StepBlock can hold it (`step_block_specs`); None: `step` stays
    on the pipe."""
    if not (self._step_block and hasattr(self._type, '_tensor_specs')):
      return None
    return step_block_specs(self._type._tensor_specs(
        'step', {}, self._constructor_kwargs))

  def attach_block(self, block, column):
    """The child maps `block` (one made by `StepBlock.create`, not yet
    unlinked) now, and from here on a `StepPass` over the block steps
    the env into `column`. Every other call, `step` among them, stays
    a pickled call."""
    self._call(_ATTACH, (self._validate, block.path, block.leaf_specs,
                         block.rows, block.columns, column), {})

  def _call(self, method, args, kwargs):
    self._send(method, args, kwargs)
    return self._receive()

  def _send(self, method, args, kwargs):
    """First half of a call: the request goes down the pipe and the
    child starts on it; `_receive` is the second half. The per-process
    lock is taken here and kept until `_receive` has the reply (or a
    failure here gives it back), so calls still never interleave on
    the pipe and `close()` still finds a call in flight. Between the
    halves the caller may send to OTHER processes: that is how one
    actor thread has k children stepping at once."""
    me = threading.get_ident()
    pending = self._pending
    if pending is not None and pending[0] == me:
      # The lock is not reentrant: this would park the thread for ever.
      raise RuntimeError(
          f'{self._type.__name__}.{method}: send before the receive of '
          f'{pending[1]!r}')
    self._lock.acquire()
    try:
      if self._closed or self._down is None:
        raise ProcessClosed(f'{self._type.__name__} process not running')
      reply = None
      pipe = telemetry.span('env/pipe')  # send -> reply in hand
      try:
        # Pickled before the first byte goes: a request that cannot be
        # must leave the child expecting nothing.
        request = ForkingPickler.dumps((method, args, kwargs))
        self.pipe_calls += 1
        os.write(self._down.fileno(), _CALL)
        self._down.send_bytes(request)
      except (EOFError, OSError, BrokenPipeError) as e:
        reply = self._buffered_reply_or_closed(e)
      except Exception as e:
        # Failed locally (e.g. unpicklable argument) — nothing
        # reached the child; blame the caller, not the remote side.
        raise TypeError(
            f'could not serialize request for '
            f'{self._type.__name__}.{method}: {e!r}') from e
    except BaseException:
      self._lock.release()
      raise
    self._pending = (me, method, kwargs, reply, pipe)

  def _receive(self):
    """Second half of a call: block for the reply to this thread's
    `_send`, give the lock back, and turn the reply into the result
    or the remote exception."""
    pending = self._pending
    if pending is None or pending[0] != threading.get_ident():
      raise RuntimeError(
          f'{self._type.__name__}: receive without a send from this '
          'thread')
    _, method, kwargs, reply, pipe = pending
    try:
      if reply is None:
        reply = self._read_reply(method)
      pipe.end()
    finally:
      self._pending = None
      self._lock.release()
    return self._result(method, kwargs, reply)

  def _read_reply(self, method):
    """The pickled reply to the call in flight (the call lock held)."""
    try:
      return self._up.recv()
    except (EOFError, OSError, BrokenPipeError) as e:
      return self._buffered_reply_or_closed(e)
    except Exception as e:
      # The reply arrived but failed to unpickle (e.g. an exception
      # class whose __reduce__ pickles but can't reconstruct). The
      # message was fully consumed, so the pipe is still in sync —
      # report it as a remote failure instead of leaking a bare
      # unpickling error with no context.
      raise RemoteError(
          f'in hosted {self._type.__name__}.{method}: reply could not '
          f'be deserialized ({e!r})') from e

  def _result(self, method, kwargs, reply):
    """A reply in hand -> the result, or the remote exception."""
    status, payload, *busy_ns = reply
    if busy_ns:
      self.busy_ns = busy_ns[0]
    name = f'{self._type.__name__}.{method}'
    if status == 'exception':
      exc, tb = payload
      err = RemoteError(f'in hosted {name}:\n{tb}')
      if exc is not None:
        raise err from exc
      raise err
    if status == 'mismatch':  # found in the child, against the block
      raise SpecMismatchError(payload)
    if self._validate:
      specs = self._type._tensor_specs(method, kwargs,
                                       self._constructor_kwargs)
      _validate_specs(payload, specs, name)
    return payload

  def _step_failure(self, answer, block, column):
    """Raise what a step through `block` that did not come back whole
    stands for, the call lock held: `answer` is the byte the child
    sent (`_FAILED`: its pickled failure follows, and is read here so
    that the pipe stays in step; b'': its end closed), `_STEPPED` over
    a row from before, or None where the process was closed before
    the byte could go."""
    name = f'{self._type.__name__}.step'
    if answer is None:
      raise ProcessClosed(f'{self._type.__name__} process not running')
    if answer == _STEPPED:
      # Never seen: what the sequence number is there to catch.
      raise RemoteError(
          f'in hosted {name}: column {column} of the block holds step '
          f'{int(block.seq[column])}, not step {block.step_seq}: a row '
          'from before')
    if answer == _FAILED:
      reply = self._read_reply('step')
    else:
      reply = self._buffered_reply_or_closed(
          EOFError('the child closed its end'))
    self._result('step', {}, reply)
    raise RemoteError(f'in hosted {name}: a failed step answered '
                      f'{reply[0]!r}')

  def _buffered_reply_or_closed(self, e):
    # A child whose ctor failed sends ('exception', ...) and closes
    # its end; if it closed before our send/recv, the buffered ctor
    # error would be lost. Drain it so the documented "ctor failure
    # reported on first proxy call" contract holds regardless of
    # timing.
    buffered = self._drain_buffered_reply()
    if buffered is None:
      raise ProcessClosed(
          f'{self._type.__name__} process pipe closed') from e
    return buffered

  def _drain_buffered_reply(self):
    """Return a reply the child pipelined before dying, if any."""
    try:
      if self._up is not None and self._up.poll(0):
        return self._up.recv()
    except (EOFError, OSError, BrokenPipeError):
      pass
    return None

  def close(self, timeout=5.0):
    """Ask the child to close() its object and exit; reap the process.

    Idempotent; safe on a child that already died. If a proxy call is
    blocked on a hung child (it holds the call lock across recv), the
    graceful path is unreachable — terminate the child instead, which
    breaks the blocked recv with EOF."""
    if not self._lock.acquire(timeout=timeout):
      # A call is in flight against an unresponsive child: kill it.
      self._closed = True
      if self._process is not None:
        self._process.terminate()
        self._process.join(timeout)
      return
    try:
      if self._closed:
        return
      self._closed = True
      down, up, process = self._down, self._up, self._process
      self._down = self._up = None
    finally:
      self._lock.release()
    if down is not None:
      try:
        os.write(down.fileno(), _CALL)
        down.send((_CLOSE, (), {}))
        self.pipe_calls += 1
        if up.poll(timeout):
          up.recv()
      except (EOFError, OSError, BrokenPipeError):
        pass
      for conn in (down, up):
        try:
          conn.close()
        except OSError:
          pass
    if process is not None:
      process.join(timeout)
      if process.is_alive():
        process.terminate()
        process.join(timeout)

  @property
  def running(self):
    return (self._process is not None and self._process.is_alive()
            and not self._closed)


def start_all(processes):
  """Start a fleet of PyProcesses (reference PyProcessHook.begin ≈L200).

  Sequential on purpose: `start()` is non-blocking (the child constructs
  asynchronously), and forking from pool threads is what Python 3.12's
  multi-threaded-fork warning is about."""
  processes = list(processes)
  for p in processes:
    p.start()
  return processes


def close_all(processes, timeout=5.0, pool_size=None):
  """Close a fleet concurrently (reference PyProcessHook.end ≈L220)."""
  processes = list(processes)
  if not processes:
    return
  with ThreadPool(pool_size or len(processes)) as pool:
    pool.map(lambda p: p.close(timeout), processes)


class PyProcessHook:
  """Reference-named lifecycle hook (reference: py_process.py ≈L190
  `PyProcessHook(SessionRunHook)`): `begin()` starts the registered
  fleet, `end()` closes it. There is no TF session to hook into here —
  call begin/end around your run loop, or use `hosted(...)` as a
  context manager (same implementation, exception-safe)."""

  def __init__(self, processes):
    self._processes = list(processes)

  def begin(self):
    return start_all(self._processes)

  def end(self, timeout=5.0):
    close_all(self._processes, timeout=timeout)


class hosted(PyProcessHook):
  """Context manager form: `with hosted([PyProcess(...), ...]) as
  procs:` — begin() on enter, end() on exit (error or not)."""

  def __enter__(self):
    return self.begin()

  def __exit__(self, *exc):
    self.end()
    return False


class ProxyEnv:
  """Adapts a hosted env's proxy to the `envs.base.Environment` surface
  so `runtime.actor.Actor` can drive an out-of-process env unchanged."""

  def __init__(self, process: PyProcess):
    self._process = process
    self._proxy = process.proxy
    # What an env offers beyond the Environment surface, and an actor
    # asks for by name (envs/tokens.py), is offered here too.
    if hasattr(process._type, 'prompt_block'):
      self.prompt_block = self._proxy.prompt_block

  def initial(self):
    return self._proxy.initial()

  def step(self, action):
    return self._proxy.step(action)

  def step_send(self, action):
    """`step` in two halves, for an actor thread that steps several
    hosted envs at once: send to each, then `step_receive` from each.
    (Envs attached to a block step together through a `StepPass`.)"""
    self._process._send('step', (action,), {})

  def step_receive(self):
    """(reward, done, observation)."""
    return self._process._receive()

  def step_block_specs(self):
    return self._process.step_block_specs()

  def step_busy_ns(self):
    """The child's own time in the `step` last received."""
    return self._process.busy_ns

  def attach_block(self, block, column):
    self._process.attach_block(block, column)

  def close(self):
    self._process.close()


class StepPass:
  """One step of every env of a shared block, as one pass over the
  block's columns: the actions go in as one vector, one byte down each
  child's pipe in a tight loop wakes them all, the one-byte answers
  come back in a second loop, and one compare of the sequence numbers
  says every row is whole. Only a column that did not come back whole
  (its byte `_FAILED`, its pipe closed, its number not the step's) goes
  through its process's own reply handling, which raises what a step
  by itself would.

  `envs` are `ProxyEnv`s attached to `block`, env j to column j; their
  pipes' descriptors are taken once, here. While `step` blocks,
  `waiting` is the column it waits for (None otherwise); after a step
  that raised, `failed` is the column whose failure it raised (None:
  not one env's)."""

  def __init__(self, block, envs):
    self.block = block
    self._processes = [env._process for env in envs]
    self._locks = [p._lock for p in self._processes]
    self._downs = [p._down.fileno() for p in self._processes]
    self._ups = [p._up.fileno() for p in self._processes]
    self.waiting = None
    self.failed = None

  def step(self, row, actions):
    """Step every env into `row` of the block, env j with `actions[j]`.
    Each process's call lock is held from its byte out to its answer,
    so `close()` from another thread still finds a call in flight.
    Every answer sent for is read, whatever failed, so that no child
    is left mid-call; then the first failure, by column, is raised."""
    block, processes = self.block, self._processes
    n = len(processes)
    self.failed = None
    block.begin_step(row)
    block.action[:] = actions
    # Per column: _STEP while its byte is out, then its answer (b'':
    # the pipe closed; None: the process was closed, nothing sent).
    answers = [_STEP] * n
    for lock in self._locks:
      lock.acquire()
    try:
      pipes = _pipe_spans(n)  # wake -> answer, while armed
      for j, (process, fd) in enumerate(zip(processes, self._downs)):
        if process._closed:  # its descriptors may be another's now
          answers[j] = None
          continue
        process.block_steps += 1
        try:
          os.write(fd, _STEP)
        except OSError:
          answers[j] = b''
      for j, fd in enumerate(self._ups):
        if answers[j] is _STEP:
          self.waiting = j
          try:
            answers[j] = os.read(fd, 1)
          except OSError:
            answers[j] = b''
          if pipes is not None:
            pipes[j].end()
      if (answers.count(_STEPPED) != n or
          not (block.seq == block.step_seq).all()):
        self._raise_first_failure(answers)
    finally:
      self.waiting = None
      for lock in self._locks:
        lock.release()

  def _raise_first_failure(self, answers):
    block, failure = self.block, None
    for j, (process, answer) in enumerate(zip(self._processes, answers)):
      if answer == _STEPPED and block.seq[j] == block.step_seq:
        continue
      self.waiting = j
      try:
        process._step_failure(answer, block, j)
      except BaseException as e:  # the first is raised, below
        if failure is None:
          failure = j, e
    self.failed, exc = failure
    raise exc


def _pipe_spans(n):
  """n `env/pipe` spans begun now, while the recorder is armed; None
  while it is off."""
  first = telemetry.span('env/pipe')
  if first is telemetry.NO_SPAN:
    return None
  return [first] + [telemetry.span('env/pipe') for _ in range(n - 1)]

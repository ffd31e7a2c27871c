"""Process-hosted Python objects (the reference's py_process, TPU-build).

Runs an arbitrary Python class (typically an environment) in a separate
OS process — the GIL escape that lets dozens of envs step concurrently —
and exposes its methods to the host runtime as blocking calls over a
pipe. Re-expresses the reference's `py_process.py` (reference:
py_process.py ≈L50–230) without the TF graph: there is no `tf.py_func`
to wrap because on the TPU build env stepping is host Python already
(runtime/actor.py); what survives is the process-hosting contract:

- `PyProcess(type_, constructor_kwargs)` + `.proxy.<method>(*args)` —
  the call is sent over a `multiprocessing.Pipe`, the caller blocks on
  the reply (reference `_TFProxy.__getattr__` ≈L50).
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

import multiprocessing
import os
import sys
import threading
import traceback
from multiprocessing.pool import ThreadPool

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


def _worker(conn, type_, constructor_kwargs):
  """Worker loop: construct, then serve (method, args, kwargs) requests."""
  pin_process_to_cpu()
  try:
    obj = type_(**constructor_kwargs)
  except Exception as e:  # ctor failure → reported on first proxy call
    conn.send(('exception', _serialize_error(e)))
    conn.close()
    return
  while True:
    try:
      request = conn.recv()
    except (EOFError, OSError):
      break  # parent died/closed: fall through to close the object
    method, args, kwargs = request
    if method == _CLOSE:
      try:
        if hasattr(obj, 'close'):
          obj.close()
        conn.send(('ok', None))
      except Exception as e:
        conn.send(('exception', _serialize_error(e)))
      break
    try:
      result = getattr(obj, method)(*args, **kwargs)
      conn.send(('ok', result))
    except Exception as e:  # keep serving — reference semantics
      conn.send(('exception', _serialize_error(e)))
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
  """

  def __init__(self, type_, constructor_kwargs=None, context=None,
               validate_specs=True):
    self._type = type_
    self._constructor_kwargs = dict(constructor_kwargs or {})
    self._ctx = multiprocessing.get_context(
        context or DEFAULT_START_METHOD)
    self._validate = validate_specs and hasattr(type_, '_tensor_specs')
    self._conn = None
    self._process = None
    self._lock = threading.Lock()  # pipes are not thread-safe
    # A call between its two halves (_send, _receive): (the thread
    # that holds the lock for it, method, kwargs, a reply already in
    # hand, the env/pipe span).
    self._pending = None
    self._closed = False

  @property
  def proxy(self):
    return _Proxy(self)

  def start(self):
    if self._process is not None:
      raise RuntimeError('already started')
    self._conn, child_conn = self._ctx.Pipe(duplex=True)
    self._process = self._ctx.Process(
        target=_worker,
        args=(child_conn, self._type, self._constructor_kwargs),
        daemon=True)
    self._process.start()
    child_conn.close()  # parent keeps one end only
    return self

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
      if self._closed or self._conn is None:
        raise ProcessClosed(f'{self._type.__name__} process not running')
      reply = None
      pipe = telemetry.span('env/pipe')  # send -> reply in hand
      try:
        self._conn.send((method, args, kwargs))
      except (EOFError, OSError, BrokenPipeError) as e:
        reply = self._buffered_reply_or_closed(e)
      except Exception as e:
        # send() failed locally (e.g. unpicklable argument) — nothing
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
        try:
          reply = self._conn.recv()
        except (EOFError, OSError, BrokenPipeError) as e:
          reply = self._buffered_reply_or_closed(e)
        except Exception as e:
          # The reply arrived but failed to unpickle (e.g. an exception
          # class whose __reduce__ pickles but can't reconstruct). The
          # message was fully consumed, so the pipe is still in sync —
          # report it as a remote failure instead of leaking a bare
          # unpickling error with no context.
          raise RemoteError(
              f'in hosted {self._type.__name__}.{method}: reply could '
              f'not be deserialized ({e!r})') from e
      pipe.end()
    finally:
      self._pending = None
      self._lock.release()
    status, payload = reply
    if status == 'exception':
      exc, tb = payload
      err = RemoteError(
          f'in hosted {self._type.__name__}.{method}:\n{tb}')
      if exc is not None:
        raise err from exc
      raise err
    if self._validate:
      specs = self._type._tensor_specs(method, kwargs,
                                       self._constructor_kwargs)
      _validate_specs(payload, specs, f'{self._type.__name__}.{method}')
    return payload

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
      if self._conn is not None and self._conn.poll(0):
        return self._conn.recv()
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
      conn, process = self._conn, self._process
      self._conn = None
    finally:
      self._lock.release()
    if conn is not None:
      try:
        conn.send((_CLOSE, (), {}))
        if conn.poll(timeout):
          conn.recv()
      except (EOFError, OSError, BrokenPipeError):
        pass
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
    hosted envs at once: send to each, then `step_receive` from each."""
    self._process._send('step', (action,), {})

  def step_receive(self):
    return self._process._receive()

  def close(self):
    self._process.close()

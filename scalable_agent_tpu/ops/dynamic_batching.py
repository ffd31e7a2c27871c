"""Dynamic batching of concurrent inference calls (Python API).

Reference parity: `dynamic_batching.py` (reference ≈130 LoC — `batch_fn`,
`batch_fn_with_options(minimum_batch_size, maximum_batch_size,
timeout_ms)` over the C++ Batcher op, loaded via
`tf.load_op_library('batcher.so')` ≈L25). Here the native piece is a
plain C++ shared library (`ops/batcher/batcher.cc`) driven through
ctypes, and the batched function is any Python callable over numpy
arrays — in production a jitted JAX policy on TPU.

Threading model (same as the reference): N caller threads block in
`compute`; ONE computation thread (spawned lazily per decorated fn)
loops get_batch → f(concatenated inputs) → set_outputs. The reference's
documented caveat applies unchanged: with dynamic batching, actions
within one unroll may be computed with different weight versions
(reference: experiment.py ≈L472 comment).
"""

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

from scalable_agent_tpu.analysis.runtime import guarded_by, make_lock

_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
_BATCHER_DIR = os.path.join(_THIS_DIR, 'batcher')
_LIB_PATH = os.path.join(_BATCHER_DIR, 'libbatcher.so')

# Return codes mirroring batcher.cc's enum Rc.
RC_OK, RC_ERROR, RC_CANCELLED, RC_SHAPE, RC_TOO_BIG, RC_CLOSED, \
    RC_BAD_ID, RC_SIZE = range(8)

_lib = None
_lib_lock = threading.Lock()


class BatcherError(RuntimeError):
  """Computation error propagated from the batched function."""


class BatcherCancelled(RuntimeError):
  """The batcher was closed while this call was in flight."""


def _ensure_lib():
  """Load (building if necessary) libbatcher.so."""
  global _lib
  with _lib_lock:
    if _lib is not None:
      return _lib
    # Always invoke make: its batcher.cc dependency makes a fresh build
    # a no-op and a stale .so (edited source) gets rebuilt.
    subprocess.run(['make', '-C', _BATCHER_DIR], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(_LIB_PATH)
    i64 = ctypes.c_longlong
    p = ctypes.c_void_p
    lib.batcher_create.restype = p
    lib.batcher_create.argtypes = [i64, i64, i64, i64]
    lib.batcher_compute_begin.restype = i64
    lib.batcher_compute_begin.argtypes = [
        p, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(i64), i64,
        ctypes.POINTER(i64)]
    lib.batcher_compute_wait.restype = i64
    lib.batcher_compute_wait.argtypes = [p, i64, ctypes.c_char_p, i64]
    lib.batcher_result_count.restype = i64
    lib.batcher_result_count.argtypes = [p, i64]
    lib.batcher_result_size.restype = i64
    lib.batcher_result_size.argtypes = [p, i64, i64]
    lib.batcher_result_copy.restype = i64
    lib.batcher_result_copy.argtypes = [p, i64, i64, ctypes.c_void_p, i64]
    lib.batcher_request_free.restype = None
    lib.batcher_request_free.argtypes = [p, i64]
    lib.batcher_get_batch.restype = i64
    lib.batcher_get_batch.argtypes = [p, ctypes.POINTER(i64),
                                      ctypes.POINTER(i64)]
    lib.batcher_batch_input_copy.restype = i64
    lib.batcher_batch_input_copy.argtypes = [p, i64, i64,
                                             ctypes.c_void_p]
    lib.batcher_set_outputs.restype = i64
    lib.batcher_set_outputs.argtypes = [
        p, i64, i64, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(i64), i64]
    lib.batcher_set_error.restype = i64
    lib.batcher_set_error.argtypes = [p, i64, ctypes.c_char_p]
    lib.batcher_close.restype = None
    lib.batcher_close.argtypes = [p]
    lib.batcher_destroy.restype = None
    lib.batcher_destroy.argtypes = [p]
    _lib = lib
    return lib


def _as_contiguous(arrays) -> List[np.ndarray]:
  out = []
  for a in arrays:
    a = np.asarray(a)
    # Check BEFORE ascontiguousarray, which silently promotes 0-d to 1-d.
    if a.ndim < 1:
      raise ValueError('batched tensors need a leading batch dim; got '
                       f'scalar of dtype {a.dtype}')
    out.append(np.ascontiguousarray(a))
  return out


class Batcher:
  """Low-level handle over the C++ batcher (one input-tensor family).

  Most users want `batch_fn` / `batch_fn_with_options`; this class is
  the substrate (and what tests drive for out-of-order completion)."""

  # Lock discipline (round 18, guarded-by lint): the dtype/shape
  # metadata is published under _meta_lock (the C++ mutex orders the
  # actual batch handoff).
  _in_meta: guarded_by('_meta_lock')
  _out_meta: guarded_by('_meta_lock')

  def __init__(self, num_tensors: int, minimum_batch_size: int = 1,
               maximum_batch_size: int = 1024, timeout_ms: int = 100):
    self._lib = _ensure_lib()
    self._h = self._lib.batcher_create(
        minimum_batch_size, maximum_batch_size, timeout_ms, num_tensors)
    self._num_tensors = num_tensors
    self._meta_lock = make_lock('dynamic_batching.Batcher._meta_lock')
    # dtype/trailing-shape per input tensor, fixed by the first call
    # (published under the lock before compute_begin; the computation
    # thread reads after get_batch — the C++ mutex orders the two).
    self._in_meta: Optional[List] = None
    self._out_meta: Optional[List] = None
    self._closed = False

  # -- caller side --

  @property
  def closed(self) -> bool:
    return self._closed

  def check(self, arrays: Sequence[np.ndarray]) -> int:
    """A request's rows, once its tensors are checked against the
    family the first request fixed (`input_meta`; the first request
    fixes it). `compute` checks every request so; a caller that
    answers a request without the batcher (the inference server's
    inline call) checks it the same way, and the meta its staging is
    laid out by is the same."""
    if len(arrays) != self._num_tensors:
      raise ValueError(
          f'expected {self._num_tensors} tensors, got {len(arrays)}')
    rows = arrays[0].shape[0]
    for a in arrays:
      if a.shape[0] != rows:
        raise ValueError('inconsistent leading (batch) dims: '
                         f'{[x.shape for x in arrays]}')
    with self._meta_lock:
      if self._in_meta is None:
        self._in_meta = [(a.dtype, a.shape[1:]) for a in arrays]
      else:
        for a, (dtype, trail) in zip(arrays, self._in_meta):
          if a.dtype != dtype or a.shape[1:] != trail:
            raise ValueError(
                f'tensor mismatch: got {a.dtype}{a.shape[1:]}, '
                f'expected {dtype}{trail}')
    return rows

  def compute(self, arrays: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Submit rows, block until the computation thread answers."""
    arrays = _as_contiguous(arrays)
    rows = self.check(arrays)

    i64 = ctypes.c_longlong
    n = self._num_tensors
    data = (ctypes.c_void_p * n)(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
    row_bytes = (i64 * n)(
        *[int(np.prod(a.shape[1:], dtype=np.int64)) * a.itemsize
          for a in arrays])
    req_id = i64(0)
    rc = self._lib.batcher_compute_begin(
        self._h, data, row_bytes, rows, ctypes.byref(req_id))
    if rc == RC_CLOSED:
      raise BatcherCancelled('batcher is closed')
    if rc == RC_TOO_BIG:
      raise ValueError(f'rows={rows} exceeds maximum_batch_size')
    if rc == RC_SHAPE:
      raise ValueError('row byte-size mismatch vs. earlier calls')
    assert rc == RC_OK, rc

    err = ctypes.create_string_buffer(4096)
    rc = self._lib.batcher_compute_wait(self._h, req_id, err, 4096)
    try:
      if rc == RC_ERROR:
        raise BatcherError(err.value.decode('utf-8', errors='replace'))
      if rc == RC_CANCELLED:
        raise BatcherCancelled('batcher closed while waiting')
      assert rc == RC_OK, rc
      with self._meta_lock:
        out_meta = list(self._out_meta)
      outs = []
      for i, (dtype, trail) in enumerate(out_meta):
        nbytes = self._lib.batcher_result_size(self._h, req_id, i)
        row_nb = int(np.prod(trail, dtype=np.int64)) * dtype.itemsize
        # out_meta can lag the stored output if the batched function's
        # trailing shape varies across batches; a partial row means the
        # snapshot is stale — fail loudly rather than mis-slice.
        if nbytes and (row_nb == 0 or nbytes % row_nb):
          raise BatcherError(
              f'output {i}: stored {nbytes} bytes is not a whole number '
              f'of rows of shape {tuple(trail)} dtype {dtype} '
              f'({row_nb} bytes/row) — batched fn output shape varied')
        out_rows = nbytes // row_nb if row_nb else 0
        buf = np.empty((out_rows,) + tuple(trail), dtype)
        if nbytes:
          rc = self._lib.batcher_result_copy(
              self._h, req_id, i, buf.ctypes.data_as(ctypes.c_void_p),
              buf.nbytes)
          assert rc == RC_OK, rc
        outs.append(buf)
      return outs
    finally:
      self._lib.batcher_request_free(self._h, req_id)

  # -- computation-thread side --

  def input_meta(self):
    """[(dtype, trailing_shape)] per input tensor, or None before the
    first compute() call fixed it."""
    with self._meta_lock:
      return list(self._in_meta) if self._in_meta is not None else None

  def get_batch_into(self, make_buffers):
    """Zero-copy variant of `get_batch`: the C++ merge-copy lands in
    caller-provided storage instead of freshly allocated arrays (the
    inference server hands its preallocated padded staging buffers, so
    the merged batch materializes already padded — no second
    concatenate/pad pass).

    Args:
      make_buffers: callable `(total_rows) -> [np.ndarray]` returning
        one C-contiguous array per input tensor, dtype/trailing shape
        matching `input_meta()` and leading capacity >= total_rows
        (only the first total_rows rows are written).

    Returns:
      (batch_id, total_rows, buffers) — or None when the batcher is
      closed and drained.
    """
    i64 = ctypes.c_longlong
    batch_id, total_rows = i64(0), i64(0)
    rc = self._lib.batcher_get_batch(
        self._h, ctypes.byref(batch_id), ctypes.byref(total_rows))
    if rc == RC_CLOSED:
      return None
    assert rc == RC_OK, rc
    try:
      buffers = make_buffers(total_rows.value)
      for i, buf in enumerate(buffers):
        rc = self._lib.batcher_batch_input_copy(
            self._h, batch_id, i, buf.ctypes.data_as(ctypes.c_void_p))
        if rc != RC_OK:
          # close() raced us and erased the batch — don't hand the
          # caller uninitialized memory; treat as shutdown.
          return None
      return batch_id.value, total_rows.value, buffers
    except Exception as e:
      # The batch was already dequeued: a make_buffers failure (e.g.
      # allocation under memory pressure) must not strand its parked
      # callers in compute_wait — answer them with the error, then
      # let the caller decide whether its loop survives.
      self.set_error(batch_id.value, f'{type(e).__name__}: {e}')
      raise

  def get_batch(self):
    """Block for the next merged batch → (batch_id, [np arrays]) or
    None when the batcher is closed and drained."""

    def alloc(total_rows):
      with self._meta_lock:
        in_meta = list(self._in_meta)
      return [np.empty((total_rows,) + tuple(trail), dtype)
              for dtype, trail in in_meta]

    item = self.get_batch_into(alloc)
    if item is None:
      return None
    batch_id, _, arrays = item
    return batch_id, arrays

  def set_outputs(self, batch_id: int, arrays: Sequence[np.ndarray]):
    arrays = _as_contiguous([np.asarray(a) for a in arrays])
    rows = arrays[0].shape[0]
    for a in arrays:
      if a.shape[0] != rows:
        raise ValueError('inconsistent output batch dims: '
                         f'{[x.shape for x in arrays]}')
    with self._meta_lock:
      self._out_meta = [(a.dtype, a.shape[1:]) for a in arrays]
    i64 = ctypes.c_longlong
    n = len(arrays)
    data = (ctypes.c_void_p * n)(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
    row_bytes = (i64 * n)(
        *[int(np.prod(a.shape[1:], dtype=np.int64)) * a.itemsize
          for a in arrays])
    rc = self._lib.batcher_set_outputs(
        self._h, batch_id, n, data, row_bytes, rows)
    if rc == RC_SIZE:
      raise ValueError('output rows do not match the batch rows')
    if rc not in (RC_OK, RC_BAD_ID):  # BAD_ID: batch cancelled by close
      raise RuntimeError(f'set_outputs rc={rc}')

  def set_error(self, batch_id: int, message: str):
    self._lib.batcher_set_error(self._h, batch_id,
                                message.encode('utf-8'))

  def close(self):
    if not self._closed:
      self._closed = True
      self._lib.batcher_close(self._h)

  def __del__(self):
    try:
      if getattr(self, '_h', None):
        self.close()
        self._lib.batcher_destroy(self._h)
        self._h = None
    except Exception:
      pass


class _BatchedFunction:
  """A callable wrapping `f` behind a Batcher + computation thread."""

  def __init__(self, f, minimum_batch_size, maximum_batch_size,
               timeout_ms):
    self._f = f
    self._opts = (minimum_batch_size, maximum_batch_size, timeout_ms)
    self._batcher: Optional[Batcher] = None
    self._thread: Optional[threading.Thread] = None
    self._start_lock = make_lock(
        'dynamic_batching._BatchedFunction._start_lock')
    self.__name__ = getattr(f, '__name__', 'batched_fn')

  def _loop(self):
    while True:
      item = self._batcher.get_batch()
      if item is None:
        return
      batch_id, arrays = item
      try:
        outs = self._f(*arrays)
        if isinstance(outs, np.ndarray):
          outs = (outs,)
        self._batcher.set_outputs(
            batch_id, [np.asarray(o) for o in outs])
      except Exception as e:  # propagate to the blocked callers
        self._batcher.set_error(batch_id, f'{type(e).__name__}: {e}')

  def _ensure_started(self, num_tensors):
    with self._start_lock:
      if self._batcher is None:
        mn, mx, to = self._opts
        self._batcher = Batcher(num_tensors, mn, mx, to)
        self._thread = threading.Thread(
            target=self._loop, name=f'batcher-{self.__name__}',
            daemon=True)
        self._thread.start()

  def __call__(self, *arrays):
    self._ensure_started(len(arrays))
    outs = self._batcher.compute([np.asarray(a) for a in arrays])
    return outs[0] if len(outs) == 1 else tuple(outs)

  def close(self):
    with self._start_lock:
      if self._batcher is not None:
        self._batcher.close()
        self._thread.join(timeout=5)


def batch_fn_with_options(minimum_batch_size: int = 1,
                          maximum_batch_size: int = 1024,
                          timeout_ms: int = 100):
  """Decorator: merge concurrent calls to `f` into batched calls
  (reference: dynamic_batching.batch_fn_with_options)."""

  def decorator(f):
    return _BatchedFunction(f, minimum_batch_size, maximum_batch_size,
                            timeout_ms)

  return decorator


def batch_fn(f):
  """Decorator with default options (reference: dynamic_batching.batch_fn)."""
  return _BatchedFunction(f, 1, 1024, 100)


def family_key(arrays: Sequence[np.ndarray]):
  """The obs-spec FAMILY of a request: dtype + trailing shape per
  tensor (the leading batch dim is what merging is free to vary).
  Hashable — the FamilyBatcher's routing key."""
  return tuple((np.asarray(a).dtype.str, np.asarray(a).shape[1:])
               for a in arrays)


class FamilyBatcher:
  """Obs-spec FAMILY bucketing over the C++ batcher (round 22): one
  logical batched function whose concurrent callers may carry
  DIFFERENT tensor specs — e.g. a heterogeneous fleet mixing 16x16
  cue_memory frames with 24x32 gridworld frames.

  The single-queue Batcher fixes one tensor family at the first call
  (a later 16x16 caller would either error or, in a pad-to-max
  design, ship every frame at the fleet-wide max shape). Here each
  family gets its OWN Batcher + computation thread, lazily on first
  sight, so merges never cross families and a frame never pads beyond
  its family's exact shape — the generalization of bucketed padding
  from batch-dim buckets to obs-spec buckets. The cost is one
  computation thread per family and merge opportunities that don't
  cross families (mixed fleets want per-family minimum_batch_size
  floors sized to the family's actor share, not the fleet).

  `make_fn(key)` builds the per-family handler (called once per new
  family; the key is `family_key` of the first request) — typically a
  jitted policy step specialized to that family's shapes.

  `padding_stats()` carries the measured perf claim: useful bytes
  served per family vs the counterfactual naive max-shape cost over
  the SAME request stream (every row padded to the widest family seen)."""

  _families: guarded_by('_lock')
  _rows: guarded_by('_lock')

  def __init__(self, make_fn, minimum_batch_size: int = 1,
               maximum_batch_size: int = 1024, timeout_ms: int = 100):
    self._make_fn = make_fn
    self._opts = (minimum_batch_size, maximum_batch_size, timeout_ms)
    self._lock = make_lock('dynamic_batching.FamilyBatcher._lock')
    self._families = {}  # family key -> _BatchedFunction
    self._rows = {}      # family key -> rows served
    self._closed = False

  def _family(self, key):
    with self._lock:
      if self._closed:
        raise BatcherCancelled('family batcher is closed')
      fn = self._families.get(key)
      if fn is None:
        mn, mx, to = self._opts
        fn = _BatchedFunction(self._make_fn(key), mn, mx, to)
        fn.__name__ = f'family{len(self._families)}'
        self._families[key] = fn
        self._rows[key] = 0
      return fn

  def __call__(self, *arrays):
    arrays = [np.asarray(a) for a in arrays]
    key = family_key(arrays)
    fn = self._family(key)
    out = fn(*arrays)
    with self._lock:
      self._rows[key] += arrays[0].shape[0]
    return out

  @staticmethod
  def _row_bytes(key) -> int:
    total = 0
    for dtype_str, trail in key:
      total += int(np.prod(trail, dtype=np.int64)) * \
          np.dtype(dtype_str).itemsize
    return total

  def padding_stats(self):
    """Measured padded-bytes accounting over everything served so far:
    {families, rows, useful_bytes, max_shape_bytes, waste_ratio, ...}
    (population.padding_report's keys — bucketed == useful because
    family merges pad zero extra bytes; max_shape_bytes is what the
    same stream costs under naive pad-to-fleet-max)."""
    from scalable_agent_tpu import population
    with self._lock:
      counts = {(self._row_bytes(key),): rows
                for key, rows in self._rows.items() if rows}
      families = len(self._families)
      total_rows = float(sum(self._rows.values()))
    report = population.padding_report(counts)
    report['families'] = families
    report['rows'] = total_rows
    return report

  def close(self):
    with self._lock:
      self._closed = True
      families = list(self._families.values())
    for fn in families:
      fn.close()

"""Contract tests for the C++ dynamic batcher.

Re-specifies the reference's dynamic_batching_test.py contract (SURVEY
§2.15: batch merging, max-batch split, timeout flush, error propagation
to the right caller, out-of-order completion, shutdown/cancellation,
shape validation) against the new C++ host batcher, with real Python
threads doing real blocking calls.
"""

import threading
import time

import numpy as np
import pytest

from scalable_agent_tpu.ops import dynamic_batching as db


def _run_threads(fns):
  """Run callables concurrently; re-raise the first exception."""
  results = [None] * len(fns)
  errors = []

  def runner(i, fn):
    try:
      results[i] = fn()
    except Exception as e:  # noqa: BLE001 — re-raised below
      errors.append(e)

  threads = [threading.Thread(target=runner, args=(i, fn))
             for i, fn in enumerate(fns)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=30)
  if errors:
    raise errors[0]
  return results


class TestBasic:

  def test_single_call_passes_through(self):
    @db.batch_fn
    def f(a, b):
      return a + b

    try:
      out = f(np.array([1.0]), np.array([2.0]))
      np.testing.assert_array_equal(out, [3.0])
    finally:
      f.close()

  def test_multiple_sequential_calls(self):
    @db.batch_fn
    def f(a):
      return a * 2

    try:
      for i in range(5):
        np.testing.assert_array_equal(f(np.array([float(i)])),
                                      [2.0 * i])
    finally:
      f.close()

  def test_multiple_outputs_and_2d_rows(self):
    @db.batch_fn
    def f(a):
      return a + 1, (a * 2).astype(np.int32)

    try:
      x = np.arange(6, dtype=np.float32).reshape(2, 3)
      y, z = f(x)
      np.testing.assert_array_equal(y, x + 1)
      assert z.dtype == np.int32
    finally:
      f.close()


class TestMerging:

  def test_concurrent_calls_merge_into_one_batch(self):
    batch_sizes = []

    @db.batch_fn_with_options(minimum_batch_size=2,
                              maximum_batch_size=1024,
                              timeout_ms=5000)
    def f(a):
      batch_sizes.append(a.shape[0])
      return a * 10

    try:
      out1, out2 = _run_threads([
          lambda: f(np.array([1.0])),
          lambda: f(np.array([2.0])),
      ])
      np.testing.assert_array_equal(out1, [10.0])
      np.testing.assert_array_equal(out2, [20.0])
      # min=2 forces the two calls into ONE invocation of f.
      assert batch_sizes == [2], batch_sizes
    finally:
      f.close()

  def test_each_caller_gets_its_own_slice(self):
    @db.batch_fn_with_options(minimum_batch_size=3, timeout_ms=5000)
    def f(a):
      return a * 2

    try:
      outs = _run_threads(
          [lambda v=v: f(np.array([v, v], dtype=np.float64))
           for v in (1.0, 2.0, 3.0)])
      for v, out in zip((1.0, 2.0, 3.0), outs):
        np.testing.assert_array_equal(out, [2 * v, 2 * v])
    finally:
      f.close()

  def test_maximum_batch_size_splits(self):
    batch_sizes = []
    gate = threading.Semaphore(0)

    @db.batch_fn_with_options(minimum_batch_size=2,
                              maximum_batch_size=2, timeout_ms=200)
    def f(a):
      batch_sizes.append(a.shape[0])
      return a

    try:
      _run_threads([lambda v=v: f(np.array([float(v)]))
                    for v in range(4)])
      assert sum(batch_sizes) == 4
      assert all(s <= 2 for s in batch_sizes), batch_sizes
    finally:
      f.close()
      del gate

  def test_timeout_flushes_partial_batch(self):
    @db.batch_fn_with_options(minimum_batch_size=8, timeout_ms=100)
    def f(a):
      return a + 1

    try:
      t0 = time.monotonic()
      out = f(np.array([1.0]))  # never reaches min=8
      dt = time.monotonic() - t0
      np.testing.assert_array_equal(out, [2.0])
      assert dt < 10, dt  # flushed by timeout, not stuck
    finally:
      f.close()


class TestErrors:

  def test_error_propagates_to_caller(self):
    @db.batch_fn
    def f(a):
      raise ValueError('deliberate kaboom')

    try:
      with pytest.raises(db.BatcherError, match='deliberate kaboom'):
        f(np.array([1.0]))
    finally:
      f.close()

  def test_error_hits_only_the_affected_batch(self):
    calls = []

    @db.batch_fn_with_options(minimum_batch_size=1, timeout_ms=10)
    def f(a):
      calls.append(a.shape[0])
      if float(a[0]) < 0:
        raise ValueError('negative!')
      return a

    try:
      with pytest.raises(db.BatcherError, match='negative!'):
        f(np.array([-1.0]))
      np.testing.assert_array_equal(f(np.array([5.0])), [5.0])
    finally:
      f.close()

  def test_shape_validation_wrong_trailing_shape(self):
    @db.batch_fn_with_options(minimum_batch_size=1, timeout_ms=10)
    def f(a):
      return a

    try:
      f(np.zeros((1, 3), np.float32))
      with pytest.raises(ValueError, match='mismatch'):
        f(np.zeros((1, 4), np.float32))
      with pytest.raises(ValueError, match='mismatch'):
        f(np.zeros((1, 3), np.float64))
    finally:
      f.close()

  def test_check_holds_a_request_to_the_family_compute_does(self):
    """`Batcher.check` (PR 39: what a server that answers a request
    without the batcher checks it by): the first request fixes the
    family `input_meta` reports and a later compute is held to it."""
    b = db.Batcher(num_tensors=2, maximum_batch_size=8, timeout_ms=10)
    try:
      assert b.input_meta() is None
      assert b.check([np.zeros((3, 2), np.float32),
                      np.zeros((3,), np.int32)]) == 3
      assert b.input_meta() == [(np.dtype(np.float32), (2,)),
                                (np.dtype(np.int32), ())]
      with pytest.raises(ValueError, match='expected 2 tensors'):
        b.check([np.zeros((3, 2), np.float32)])
      with pytest.raises(ValueError, match='inconsistent leading'):
        b.check([np.zeros((3, 2), np.float32), np.zeros((2,), np.int32)])
      with pytest.raises(ValueError, match='mismatch'):
        b.compute([np.zeros((1, 4), np.float32),
                   np.zeros((1,), np.int32)])
      assert not b.closed
      b.close()
      assert b.closed
    finally:
      b.close()

  def test_scalar_input_rejected(self):
    @db.batch_fn
    def f(a):
      return a

    try:
      with pytest.raises(ValueError, match='leading batch dim'):
        f(np.float32(1.0))
    finally:
      f.close()

  def test_rows_over_maximum_rejected(self):
    @db.batch_fn_with_options(maximum_batch_size=2, timeout_ms=10)
    def f(a):
      return a

    try:
      with pytest.raises(ValueError, match='maximum_batch_size'):
        f(np.zeros((3,), np.float32))
    finally:
      f.close()


class TestShutdown:

  def test_close_cancels_pending_compute(self):
    release = threading.Event()

    @db.batch_fn_with_options(minimum_batch_size=4, timeout_ms=60000)
    def f(a):
      return a

    results = []

    def caller():
      try:
        f(np.array([1.0]))
        results.append('ok')
      except db.BatcherCancelled:
        results.append('cancelled')

    t = threading.Thread(target=caller)
    t.start()
    time.sleep(0.2)  # caller is parked waiting for min=4
    f.close()
    t.join(timeout=10)
    assert not t.is_alive()
    assert results == ['cancelled']
    del release

  def test_compute_after_close_raises(self):
    @db.batch_fn
    def f(a):
      return a

    f(np.array([1.0]))
    f.close()
    with pytest.raises(db.BatcherCancelled):
      f(np.array([1.0]))


class TestOutOfOrder:
  """Drive the low-level API directly: answers may land out of order
  across batches (the reference's out-of-order SetOutputs test)."""

  def test_out_of_order_set_outputs(self):
    b = db.Batcher(num_tensors=1, minimum_batch_size=1,
                   maximum_batch_size=1, timeout_ms=10)
    try:
      outs = {}

      def caller(v):
        def run():
          outs[v] = b.compute([np.array([v], np.float32)])[0]
        return run

      t1 = threading.Thread(target=caller(1.0))
      t1.start()
      time.sleep(0.05)
      t2 = threading.Thread(target=caller(2.0))
      t2.start()

      # max=1 ⇒ two separate batches, FIFO order.
      b1, arr1 = b.get_batch()
      b2, arr2 = b.get_batch()
      np.testing.assert_array_equal(arr1[0], [1.0])
      np.testing.assert_array_equal(arr2[0], [2.0])
      # Answer the SECOND batch first.
      b.set_outputs(b2, [arr2[0] * 100])
      t2.join(timeout=10)
      # The second caller is answered while the FIRST still waits.
      assert outs.get(2.0) is not None and t1.is_alive()
      b.set_outputs(b1, [arr1[0] * 100])
      t1.join(timeout=10)
      np.testing.assert_array_equal(outs[1.0], [100.0])
      np.testing.assert_array_equal(outs[2.0], [200.0])
    finally:
      b.close()

  def test_set_outputs_wrong_rows_raises(self):
    b = db.Batcher(num_tensors=1, minimum_batch_size=1, timeout_ms=10)
    try:
      t = threading.Thread(
          target=lambda: pytest.raises(
              db.BatcherCancelled,
              lambda: b.compute([np.array([1.0], np.float32)])))
      t.start()
      bid, arrs = b.get_batch()
      with pytest.raises(ValueError, match='rows'):
        b.set_outputs(bid, [np.zeros((5,), np.float32)])
    finally:
      b.close()
      t.join(timeout=5)


class TestConcurrencyStress:

  def test_many_threads_many_calls(self):
    """48 threads × 20 calls — the reference's actor-thread regime."""
    @db.batch_fn_with_options(minimum_batch_size=8,
                              maximum_batch_size=64, timeout_ms=5)
    def f(a):
      return a * 2 + 1

    try:
      def worker(tid):
        def run():
          for i in range(20):
            v = float(tid * 100 + i)
            out = f(np.array([v, v + 0.5]))
            np.testing.assert_array_equal(out, [2 * v + 1, 2 * v + 2])
          return True
        return run

      results = _run_threads([worker(t) for t in range(48)])
      assert all(results)
    finally:
      f.close()

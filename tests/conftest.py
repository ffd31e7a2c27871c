"""Test config: force an 8-device virtual CPU mesh before any backend init.

All tests run on CPU (fast, deterministic); multi-chip sharding tests use
the 8 virtual devices. The real-TPU path is exercised by chip_smoke.py,
which does NOT import this file.

JAX_PLATFORMS=cpu is set for every child the tests start; the
jax.config update below pins this process too when pytest was launched
with another value already read into jax's option.
"""

import os

_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
  os.environ['XLA_FLAGS'] = (
      _flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ['JAX_PLATFORMS'] = 'cpu'
# Lock-order detection (round 18, analysis/runtime.py): every test
# runs with the threaded modules' locks instrumented — make_lock
# reads this at import/construction, so it must be set before
# anything imports the package. Detections log + count
# (analysis/lock_cycles); the chaos storms assert zero.
os.environ.setdefault('LOCK_ORDER_CHECK', '1')

# Warm the forkserver (default PyProcess start method) while this
# process is still single-threaded — before jax exists.
from scalable_agent_tpu.runtime.py_process import warm_forkserver  # noqa: E402

warm_forkserver()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')


import pytest  # noqa: E402


def pytest_configure(config):
  config.addinivalue_line(
      'markers', 'slow: long-running; excluded from tier-1 '
      '(-m "not slow")')
  config.addinivalue_line(
      'markers', 'chaos: deterministic fault-injection coverage '
      '(runtime/faults.py) — kept fast so tier-1 (-m "not slow") '
      'exercises at least one injected fault per layer')


# One test of tests/benchmark/test_brumby_cell.py asserts that the cell
# PR 27 added is the LAST of `workloads`, its configuration the last of
# `configs`, its metrics the last of `per_layer` and their `workloads`
# that cell alone: true until the next cell is appended, which the
# contract tells a later PR to do at the end of those lists. A file the
# benchmark already has is not a `model_config` PR's to edit (as
# tests/benchmark/conftest.py says of its own case; root PERF.md
# section 7), so the test is marked an expected failure here, and
# tests/benchmark/test_dots_cell.py holds what stays true of that
# cell's entries (`test_the_cell_before_keeps_its_entries`).
# PR 35 appended the next cell, and test_dots_cell.py's two tests of
# position read "last" too (`workloads[-1]`, `per_layer[-14:-7]`):
# both are marked as well, and tests/benchmark/test_exaone_cell.py
# holds what stays true of all three served cells BY POSITION FROM EACH
# CELL'S OWN ENTRY (`test_new_entries_keep_to_the_contract`,
# `test_the_cells_before_keep_their_entries`), which the next cell
# does not break.
# PR 39 appended a metric after PR 37's thirteen, which
# tests/benchmark/test_cycle_metrics.py reads as `per_layer[-13:]`:
# marked as well; tests/benchmark/test_inline_call_metric.py holds every
# assertion of it from the block's first entry
# (`test_pr37_entries_keep_their_place_from_their_first`).
# The one-pass share was appended after the inline-call share, which
# test_inline_call_metric.py reads as `per_layer[-1]`: marked as well;
# tests/benchmark/test_one_pass_metric.py holds every assertion of it
# by the entry's name
# (`test_the_entry_keeps_to_the_contract[inline_call_share]`).
# The cell of Mamba-2 layers appended five per-layer metrics after the
# one-pass share, which test_one_pass_metric.py reads as `per_layer[-2:]`:
# marked as well; tests/benchmark/test_granite_cell.py holds the same
# from the entry's own place
# (`test_the_one_pass_share_keeps_its_place_after_the_inline_share`).
_OUTDATED = ('test_brumby_cell.py::test_new_entries_keep_to_the_contract',
             'test_dots_cell.py::test_new_entries_keep_to_the_contract',
             'test_dots_cell.py::test_the_cell_before_keeps_its_entries',
             'test_cycle_metrics.py::'
             'test_new_entries_are_appended_and_keep_to_the_contract',
             'test_inline_call_metric.py::'
             'test_the_entry_is_appended_and_keeps_to_the_contract',
             'test_one_pass_metric.py::'
             'test_the_entry_is_appended_after_the_inline_share')


def pytest_collection_modifyitems(items):
  for item in items:
    if item.nodeid.endswith(_OUTDATED):
      item.add_marker(pytest.mark.xfail(
          reason='asserts that an earlier PR\'s cell is the last one; '
                 'see tests/conftest.py', strict=False))


# --- Tier-1 wall sentinel (round 23): the tier-1 lane runs under a
# hard `timeout` in the verify command; a run that creeps past the
# budget gets KILLED with no attribution. Accumulate per-item wall
# here and, when the suite total crosses the soft threshold, print
# the slowest items so the offender is named BEFORE the hard timeout
# starts eating the suite. Threshold sits under the 870 s hard
# budget on purpose — it fires while the run still finishes. ---

_WALL_BUDGET_SOFT_SECS = 800.0
_item_walls = {}


def pytest_runtest_logreport(report):
  if report.duration:
    _item_walls[report.nodeid] = (
        _item_walls.get(report.nodeid, 0.0) + report.duration)


def pytest_terminal_summary(terminalreporter):
  total = sum(_item_walls.values())
  if total <= _WALL_BUDGET_SOFT_SECS:
    return
  terminalreporter.write_sep(
      '=', 'WALL SENTINEL: suite used %.0f s (> %.0f s soft budget)'
      % (total, _WALL_BUDGET_SOFT_SECS))
  terminalreporter.write_line(
      'slowest 10 items (setup+call+teardown) — mark the worst '
      'offenders @pytest.mark.slow or shrink their shapes:')
  worst = sorted(_item_walls.items(), key=lambda kv: -kv[1])[:10]
  for nodeid, wall in worst:
    terminalreporter.write_line('  %8.2f s  %s' % (wall, nodeid))


@pytest.fixture
def batcher_options_spy(monkeypatch):
  """Intercept dynamic_batching.Batcher construction and record each
  instance's merge options (shared by the inference merge-floor tests
  — keeps the spies from drifting if the construction call ever
  changes shape). Since round 7 the InferenceServer drives the
  low-level Batcher directly (pipelined dispatch), so the spy sits on
  the class, covering batch_fn_with_options users too."""
  from scalable_agent_tpu.ops import dynamic_batching
  calls = []
  real = dynamic_batching.Batcher

  class Spy(real):

    def __init__(self, num_tensors, minimum_batch_size=1,
                 maximum_batch_size=1024, timeout_ms=100):
      calls.append({'num_tensors': num_tensors,
                    'minimum_batch_size': minimum_batch_size,
                    'maximum_batch_size': maximum_batch_size,
                    'timeout_ms': timeout_ms})
      super().__init__(num_tensors, minimum_batch_size,
                       maximum_batch_size, timeout_ms)

  monkeypatch.setattr(dynamic_batching, 'Batcher', Spy)
  return calls

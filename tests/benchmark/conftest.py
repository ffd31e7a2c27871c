"""One test of tests/benchmark/test_benchmark_harness.py asserts, on its
last line, that every configuration's `reduced` is empty: true of the
two configurations the first benchmark had (nothing of the paper's
agent is cut), not of a catalog model cut in depth to one chip, whose
`reduced` the contract requires. A file the benchmark already has is
not a `model_config` PR's to edit (PERF.md section 7 asks a
`benchmark` PR for the one-line repair), so the test is marked an
expected failure here, and test_brumby_cell.py holds every one of its
assertions again with that line corrected."""

import pytest

OUTDATED = ('test_benchmark_harness.py::'
            'test_cells_name_their_files_and_at_most_a_quarter_take_four_chips')


def pytest_collection_modifyitems(items):
  for item in items:
    if item.nodeid.endswith(OUTDATED):
      item.add_marker(pytest.mark.xfail(
          reason='asserts reduced == [] of every configuration; see '
                 'tests/benchmark/conftest.py', strict=False))

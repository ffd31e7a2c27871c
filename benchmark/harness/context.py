"""What one run of one cell carries from run.py to its driver: the
cell's files, the Config built from them, the clocks that split the run
into set-up, window and teardown, and the profiler for the traced run.

The profiler records the DEVICE only. With its host tracer on, a fleet
run wrote over two million runtime events in ten seconds, ran at less
than half its speed and took four minutes to stop (PR 22, chip run). So
the benchmark keeps its own host spans in memory, on the host's clock,
and puts them on the trace's clock afterwards through one landmark: a
tiny program run right after the profiler starts, whose end the host
sees (`block_until_ready`) and the trace records.
"""

import contextlib
import glob
import json
import os
import shutil
import tempfile
import time

from benchmark.harness import trace_reduce

CLOCK_SYNC = 'bench_clock_sync'


class RunContext:

  def __init__(self, cell, config_file, traffic_file, config, seed,
               seconds, trace, rehearse, t_start, ledger, logdir,
               keep_trace=None):
    self.cell = cell                  # the `workloads` entry
    self.config_file = config_file    # benchmark/configs/<config>.json
    self.traffic_file = traffic_file  # benchmark/traffic/<traffic>.json
    self.config = config              # the program's Config
    self.seed = seed
    self.seconds = seconds
    self.trace = trace
    self.rehearse = rehearse
    self.ledger = ledger
    self.logdir = logdir
    self.setup_s = None
    self.trace_result = None          # trace_reduce.Trace, once stopped
    self._t_start = t_start           # time.monotonic() at process start
    self._keep_trace = keep_trace
    self._trace_dir = None
    self._spans = None                # [(name, t0_ns, t1_ns)] while tracing
    self._open = {}                   # spans under way: {key: (name, t0_ns)}
    self._sync = None                 # the landmark program, warmed
    self._sync_ns = None              # host clock when it was seen to end

  def param(self, key):
    """A parameter of the traffic file; the rehearsal's tiny value
    where the file gives one."""
    if self.rehearse and key in self.traffic_file.get('rehearse', {}):
      return self.traffic_file['rehearse'][key]
    return self.traffic_file[key]

  def mark(self, what):
    """One line saying how far into the run `what` was reached: the
    split of set-up that PERF.md quotes."""
    print(f't+{time.monotonic() - self._t_start:6.1f} s  {what}',
          flush=True)

  # --- set-up | window | teardown ---

  def open_window(self):
    """Everything before this instant is set-up."""
    if self.trace and self._sync is None:
      self._warm_clock_sync()
    self.setup_s = time.monotonic() - self._t_start
    self.ledger.phase = 'window'
    self.mark('window opens')

  def close_window(self):
    self.ledger.phase = 'after'
    self.mark('window closes')

  # --- the traced run ---

  @contextlib.contextmanager
  def span(self, name):
    """A host span of the benchmark's own, kept if the profiler runs
    when it ends. One that began before the profiler did keeps its own
    start, and one still open when the profiler stops is closed there
    (`trace_stop`): a learner's wait of seconds straddles either end of
    a traced slice more often than not."""
    key = object()
    self._open[key] = (name, time.perf_counter_ns())
    try:
      yield
    finally:
      name, t0 = self._open.pop(key)
      spans = self._spans
      if spans is not None:
        spans.append((name, t0, time.perf_counter_ns()))

  def _take_spans(self, stop_ns):
    """The spans kept while the profiler ran and, closed at `stop_ns`,
    those still under way; none is kept from here on."""
    spans, self._spans = self._spans, None
    spans.extend((name, t0, stop_ns)
                 for name, t0 in list(self._open.values()))
    return spans

  def _warm_clock_sync(self):
    """Compiles the landmark during set-up: nothing compiles inside
    the window."""
    import jax
    import jax.numpy as jnp

    def bench_clock_sync(x):
      return x + 1

    self._sync = (jax.jit(bench_clock_sync), jnp.zeros((), jnp.int32))
    jax.block_until_ready(self._sync[0](self._sync[1]))

  def trace_start(self):
    """Starts the profiler, device only, and sets the landmark."""
    import jax
    if self._sync is None:
      self._warm_clock_sync()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    self._trace_dir = tempfile.mkdtemp(prefix='bench_trace_')
    jax.profiler.start_trace(self._trace_dir, profiler_options=options)
    jax.block_until_ready(self._sync[0](self._sync[1]))
    self._sync_ns = time.perf_counter_ns()
    self._spans = []

  def trace_stop(self):
    """Stops the profiler, reads its file into `self.trace_result`
    with the benchmark's spans on the trace's clock, and deletes it."""
    import jax
    stop_ns = time.perf_counter_ns()
    spans = self._take_spans(stop_ns)
    jax.profiler.stop_trace()
    try:
      (path,) = glob.glob(os.path.join(
          self._trace_dir, 'plugins', 'profile', '*', '*.xplane.pb'))
      if self._keep_trace:
        os.makedirs(self._keep_trace, exist_ok=True)
        shutil.copy(path, self._keep_trace)
      trace = trace_reduce.Trace.from_xplane(path)
      spans.append((trace_reduce.TRACE_SPAN[len(trace_reduce.SPAN_PREFIX):],
                    self._sync_ns, stop_ns))
      trace_reduce.add_host_spans(trace, spans, self._sync_ns,
                                  rf'^jit_{CLOCK_SYNC}\b')
      if self._keep_trace:  # the spans are in no file of the profiler's
        with open(os.path.join(self._keep_trace, 'host_spans.json'),
                  'w') as f:
          json.dump([row for row in trace.to_rows()
                     if row[0] == trace_reduce.HOST_PLANE], f)
      self.trace_result = trace
    finally:
      shutil.rmtree(self._trace_dir, ignore_errors=True)

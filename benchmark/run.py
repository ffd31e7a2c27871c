"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse]

Loads the cell's files by the names in BENCHMARK.json, builds the
program's Config through experiment.py's own flags, warms up the
cell's shapes (set-up), measures for --seconds, checks the outputs and
prints one JSON object as the LAST line of standard output. With
--trace 0 its metrics are the cell's end-to-end metrics, with --trace 1
its per-layer metrics, a breakdown and the device's busy time.

Without a TPU, or with another number of chips than the cell states,
it exits 2 at the device check and prints no result. `--rehearse` runs
the same code at the tiny sizes the cell's files give, on the CPU, to
debug the benchmark itself: every metric is then named
`rehearsal.<name>`, and no number it prints says anything about speed.

No cell, configuration, traffic mix, driver, metric or reader is named
in this file (see benchmark/README.md). Its module level imports
nothing of JAX or of the program: the forkserver that env processes
come from preloads `__main__`, which is this file.
"""

import argparse
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEADLINE_SECS = 1150  # inside the 1200 s a compiling run may take


def say(text):
  print(text, flush=True)


def _deadline():
  """Never outlive the time limit: a hung run must end as a failure,
  not as a hung chip."""
  def expire():
    from benchmark.harness import processes
    say(f'DEADLINE: {DEADLINE_SECS} s passed; killing children, exit 3')
    processes.kill_and_wait(
        list(processes.descendants(os.getpid())), timeout=5.0)
    os._exit(3)
  timer = threading.Timer(DEADLINE_SECS, expire)
  timer.daemon = True
  timer.start()
  return timer


def _arm_compile_cache(rehearse):
  """The persistent compilation cache at JAX_COMPILATION_CACHE_DIR or,
  unset, at the fixed <checkout>/.jax_cache, armed before the program
  is imported (its own rule then leaves an armed cache alone), keeping
  EVERY program: eager set-up is hundreds of programs under JAX's
  default one-second threshold. A rehearsal arms none, as the program
  arms none on a CPU-pinned process (XLA:CPU logs a screen of machine
  features for every entry it loads)."""
  import jax
  if rehearse:
    jax.config.update('jax_enable_compilation_cache', False)
    return None
  if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
    jax.config.update('jax_compilation_cache_dir',
                      os.path.join(ROOT, '.jax_cache'))
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
  return jax.config.jax_compilation_cache_dir


def _device(jax):
  devices = jax.devices()
  return {'platform': devices[0].platform,
          'kind': devices[0].device_kind, 'count': len(devices)}


def _memory_peak(jax):
  """Peak bytes in use on the fullest chip: the arrays the process held
  (`peak_bytes_in_use`) plus what the runtime set aside for running
  programs' temporaries (`peak_bytes_reserved`), which the first does
  not count (PR 22, chip run: 0.25 GB of arrays beside 5.9 GB reserved
  for a step whose activations alone are gigabytes). XLA:CPU reports
  neither."""
  stats = [d.memory_stats() or {} for d in jax.devices()]
  say(f'device memory, allocator stats of chip 0: {stats[0]}')
  return int(max(s.get('peak_bytes_in_use', 0) +
                 s.get('peak_bytes_reserved', 0) for s in stats))


def _read_metrics(loader, manifest, cell, kind, obs, prefix, rehearse):
  """{name: {'value', 'unit'}} for the cell's metrics of `kind`. A
  reader that finds nothing to read returns None. In a rehearsal, which
  has no device trace, the metric is then left out of the line. In a
  run on the chip every source is there, so None means that a name the
  metric looks for (a compiled program, a counter, a summary tag) has
  moved in the program: an error, and no result, rather than a
  yardstick that disappears in the PR that moved it."""
  out = {}
  for entry in loader.cell_metrics(manifest, cell['name'], kind):
    spec = loader.load_metric(entry['name'])
    reader = loader.load_reader(spec['reader'])
    value = reader.read(obs, **spec.get('args', {}))
    if value is None:
      if not rehearse:
        raise loader.BenchmarkError(
            f'metric {entry["name"]} of cell {cell["name"]}: reader '
            f'{spec["reader"]!r} with {spec.get("args", {})} found '
            'nothing to read. If the program renamed what it looks for, '
            'keep the name, or have a benchmark PR change the metric')
      say(f'metric {entry["name"]}: nothing to read, left out')
      continue
    out[prefix + entry['name']] = {'value': float(value),
                                   'unit': entry['unit']}
  return out


def run_cell(args):
  """Returns (exit code, result dict or None)."""
  from benchmark.harness import loader
  manifest = loader.load_manifest()
  cell = loader.find_cell(manifest, args.workload)
  config_file = loader.load_config(manifest, cell['config'])
  traffic_file = loader.load_traffic(cell['traffic'])

  if traffic_file.get('env_processes'):
    # Before this process touches JAX, as experiment.main does: the
    # server imports the package once, every env child forks from it.
    from scalable_agent_tpu.runtime.py_process import warm_forkserver
    warm_forkserver()

  import jax
  say(f't+{time.monotonic() - T_START:6.1f} s  jax imported')
  cache_dir = _arm_compile_cache(args.rehearse)
  from benchmark.harness import context, correct, ledger as ledger_lib
  from benchmark.harness import trace_reduce
  ledger = ledger_lib.CompileLedger()
  ledger.install()

  device = _device(jax)
  say(f'device: platform={device["platform"]} kind={device["kind"]!r} '
      f'count={device["count"]} | cell {cell["name"]} wants '
      f'{cell["chips"]} chip(s) | compile cache {cache_dir}')
  wanted = 'cpu' if args.rehearse else 'tpu'
  if device['platform'] != wanted or device['count'] != cell['chips']:
    say(f'refused: this run needs {cell["chips"]} {wanted} device(s)' +
        (' (JAX_PLATFORMS=cpu and XLA_FLAGS=--xla_force_host_platform_'
         f'device_count={cell["chips"]})' if args.rehearse else
         '; the benchmark never falls back to another platform'))
    return 2, None

  logdir = tempfile.mkdtemp(prefix='bench_logdir_')
  try:
    config = loader.build_config(loader.flag_args(
        config_file, traffic_file,
        {'seed': args.seed, 'logdir': logdir}, rehearse=args.rehearse))
    ctx = context.RunContext(
        cell, config_file, traffic_file, config, args.seed, args.seconds,
        bool(args.trace), args.rehearse, T_START, ledger, logdir,
        keep_trace=args.keep_trace)
    driver = loader.load_driver(traffic_file['driver'])
    ctx.mark('devices up, Config built, driver imported')
    obs = driver.run(ctx)
    if ctx.setup_s is None:
      raise RuntimeError(f'driver {traffic_file["driver"]!r} never '
                         'opened the window')
    checks = obs['checks']
    correct.check_compiles(checks, ledger)
    obs.update(
        config=config, device=device, setup_s=ctx.setup_s,
        trace=ctx.trace_result, summaries=os.path.join(
            logdir, 'summaries.jsonl'),
        compile={phase: ledger.summary(phase)
                 for phase in ('setup', 'window')},
        peaks_path=os.path.join(ROOT, 'benchmark', 'harness',
                                'peaks.json'))
    device['memory_peak_bytes'] = _memory_peak(jax)
    prefix = 'rehearsal.' if args.rehearse else ''
    result = {
        'correct': None, 'attempted': int(obs['attempted']),
        'failed': int(sum(obs['failures'].values())),
        'metrics': _read_metrics(
            loader, manifest, cell,
            'per_layer' if args.trace else 'end_to_end', obs, prefix,
            args.rehearse),
        'device': device}
    if ctx.trace_result is not None and ctx.trace_result.chips():
      busy = trace_reduce.busy(ctx.trace_result)
      device.update(busy_s=busy['busy_s'], window_s=busy['window_s'])
      result['breakdown'] = {
          'device_ops': trace_reduce.top_ops(ctx.trace_result),
          'idle_gaps': trace_reduce.idle_gaps(ctx.trace_result)}
  finally:
    shutil.rmtree(logdir, ignore_errors=True)

  setup = obs['compile']['setup']
  say(f'set-up {ctx.setup_s:.1f} s: {setup["requests"]} compile '
      f'requests, {setup["hits"]} cache hits, {setup["misses"]} '
      f'misses, {setup["uncached"]} uncached, {setup["secs"]:.1f} s in '
      f'them; compiled: {setup["compiled_names"][:12]}')
  for name, count in sorted(obs['failures'].items()):
    say(f'failed[{name}] = {count}')
  for name, ok, detail in checks.rows:
    say(f'check {"ok  " if ok else "FAIL"} {name}: {detail}')
  result['correct'] = checks.ok
  return 0, result


def main(argv):
  parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, required=True)
  parser.add_argument('--trace', type=int, choices=(0, 1), required=True)
  parser.add_argument('--rehearse', action='store_true',
                      help='tiny sizes on the CPU; says nothing of speed')
  parser.add_argument('--keep-trace', default=None,
                      help='copy the raw .xplane.pb into this directory')
  args = parser.parse_args(argv)
  sys.path.insert(0, ROOT)
  from benchmark.harness import processes
  logging.basicConfig(
      level=logging.INFO,
      format='%(asctime)s %(name)s %(levelname)s %(message)s')
  # One line per finished episode would bury everything else.
  logging.getLogger('scalable_agent_tpu').addFilter(
      lambda record: not record.getMessage().startswith('episode '))
  timer = _deadline()
  code, result = 1, None
  try:
    code, result = run_cell(args)
  finally:
    # Stop what was started, on every way out, BEFORE the result.
    left = processes.stop_children()
    timer.cancel()
  if left:
    say(f'processes left running behind the run: {left}; no result')
    return 1
  if result is not None:
    # The result, last: nothing is printed after it.
    print(json.dumps(result), flush=True)
  return code


if __name__ == '__main__':
  sys.exit(main(sys.argv[1:]))

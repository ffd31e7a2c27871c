"""benchmark/run.py from the CPU side: every cell rehearses at tiny
size and ends in the contract's line with no device metric; without
`--rehearse` it refuses to measure on a CPU; it fails in a directory
that holds nothing else of the repo; it leaves no process behind; and a
later PR's cell, configuration, traffic mix, driver, metric and reader
work as NEW files plus entries, with no edit to a file that is there.
None of this describes a TPU topology (see __graft_entry__.py)."""

import concurrent.futures
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, 'BENCHMARK.json')) as _f:
  MANIFEST = json.load(_f)
CELLS = {c['name']: c for c in MANIFEST['workloads']}
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def _session_members(sid):
  """Pids of the live processes in session `sid`."""
  members = []
  for name in os.listdir('/proc'):
    if not name.isdigit():
      continue
    try:
      with open(f'/proc/{name}/stat') as f:
        fields = f.read().rsplit(')', 1)[1].split()
    except OSError:
      continue  # ended while we were reading it
    if int(fields[3]) == sid:
      members.append(int(name))
  return members


def _run(root, *args, devices=1, timeout=900):
  """Runs <root>/benchmark/run.py in a session of its own and, the
  moment it ends, looks for anything it left running there. Output
  goes to files: reading a pipe to its end would wait for every
  process that inherited it, and hide exactly what is looked for."""
  env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO,
             XLA_FLAGS=f'--xla_force_host_platform_device_count={devices}')
  env.pop('JAX_COMPILATION_CACHE_DIR', None)
  with tempfile.TemporaryFile('w+') as out, \
      tempfile.TemporaryFile('w+') as err:
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, 'benchmark', 'run.py'),
         *args], cwd=root, env=env, stdout=out, stderr=err,
        start_new_session=True)
    try:
      proc.wait(timeout=timeout)
    finally:
      left = _session_members(proc.pid)
      for pid in left:
        os.kill(pid, 9)
    out.seek(0)
    err.seek(0)
    done = subprocess.CompletedProcess(
        proc.args, proc.returncode, out.read(), err.read())
    done.left = left
    return done


def _result(done):
  """The last line of standard output, as the driver reads it."""
  assert done.returncode == 0, (done.stdout[-2000:], done.stderr[-3000:])
  return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope='module')
def rehearsals():
  """Every cell once with --trace 0, and the cells that start env
  processes once more with --trace 1, side by side."""
  jobs = [(name, 0) for name in CELLS]
  for name, cell in CELLS.items():
    with open(os.path.join(REPO, 'benchmark', 'traffic',
                           cell['traffic'] + '.json')) as f:
      if json.load(f).get('env_processes'):
        jobs.append((name, 1))
  with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
    futures = {
        job: pool.submit(
            _run, REPO, '--workload', job[0], '--seed', '3', '--seconds',
            '2', '--trace', str(job[1]), '--rehearse',
            devices=CELLS[job[0]]['chips'])
        for job in jobs}
    return {job: future.result() for job, future in futures.items()}


@pytest.mark.parametrize('cell', sorted(CELLS))
def test_rehearsal_ends_in_the_contract_line_with_no_device_metric(
    rehearsals, cell):
  result = _result(rehearsals[cell, 0])
  assert set(result) == RESULT_KEYS
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0
  assert result['device']['platform'] == 'cpu'
  assert result['device']['count'] == CELLS[cell]['chips']
  assert 'busy_s' not in result['device']
  wanted = {'rehearsal.' + m['name'] for m in MANIFEST['end_to_end']
            if cell in m.get('workloads', CELLS)}
  assert set(result['metrics']) == wanted
  for metric in result['metrics'].values():
    assert metric['value'] > 0 and metric['unit']


@pytest.mark.parametrize('cell', sorted(CELLS))
def test_rehearsal_leaves_no_process_behind(rehearsals, cell):
  for (name, trace), done in rehearsals.items():
    if name == cell:
      assert not done.left, f'--trace {trace} left {done.left}'


def test_traced_rehearsal_reports_layer_metrics_the_cpu_can_count(
    rehearsals):
  traced = [done for (_, trace), done in rehearsals.items() if trace]
  assert traced
  for done in traced:
    result = _result(done)
    names = set(result['metrics'])
    assert all(n.startswith('rehearsal.') for n in names)
    # Counts and host clocks are there; nothing read from a device
    # trace is, and no breakdown either.
    assert {'rehearsal.entry.cache_misses',
            'rehearsal.actors.unrolls_per_s',
            'rehearsal.batcher.mean_merge',
            'rehearsal.policy_call_p50_ms'} <= names
    trace_read = {m['name'] for m in MANIFEST['per_layer']
                  if m['source'] == 'device_trace'}
    assert not {n[len('rehearsal.'):] for n in names} & trace_read
    assert 'breakdown' not in result


def test_without_rehearse_a_cpu_run_exits_at_the_device_check():
  done = _run(REPO, '--workload', sorted(CELLS)[0], '--seed', '1',
              '--seconds', '1', '--trace', '0', timeout=300)
  assert done.returncode == 2, done.stderr[-2000:]
  assert 'refused' in done.stdout and '"correct"' not in done.stdout
  assert not done.left


def test_rehearsal_refuses_another_device_count_than_the_cells():
  done = _run(REPO, '--workload', sorted(CELLS)[0], '--seed', '1',
              '--seconds', '1', '--trace', '0', '--rehearse', devices=3,
              timeout=300)
  assert done.returncode == 2 and '"correct"' not in done.stdout


def test_fails_in_a_directory_with_nothing_else_of_the_repo(tmp_path):
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  for path in MANIFEST['paths']:
    shutil.copytree(os.path.join(REPO, path), tmp_path / path)
  env_without_repo = dict(os.environ, JAX_PLATFORMS='cpu')
  env_without_repo.pop('PYTHONPATH', None)
  done = subprocess.run(
      [sys.executable, 'benchmark/run.py', '--workload',
       sorted(CELLS)[0], '--seed', '1', '--seconds', '1', '--trace', '0',
       '--rehearse'], cwd=tmp_path, env=env_without_repo,
      capture_output=True, text=True, timeout=300)
  assert done.returncode != 0
  assert '"correct"' not in done.stdout


# --- A later PR adds files and entries, and edits nothing. ---

_TOY_DRIVER = '''
from benchmark.harness import correct


def run(ctx):
  checks = correct.Checks()
  checks.record('the toy ran', ctx.config.num_actions == 3)
  ctx.open_window()
  ctx.close_window()
  return {'checks': checks, 'attempted': 7, 'failures': {},
          'frames_per_step': ctx.config.frames_per_step,
          'steps': {'count': 7, 'seconds': ctx.param('pretend_seconds')},
          'toy': {'answer': 42}}
'''

_TOY_READER = '''
def read(obs, key):
  return obs.get('toy', {}).get(key)
'''


def _digest(root):
  digests = {}
  for folder, _, files in os.walk(root):
    for file in files:
      if '__pycache__' not in folder:
        path = os.path.join(folder, file)
        with open(path, 'rb') as f:
          digests[os.path.relpath(path, root)] = hashlib.sha256(
              f.read()).hexdigest()
  return digests


def test_a_new_cell_config_traffic_driver_metric_and_reader_are_new_files(
    tmp_path):
  shutil.copytree(os.path.join(REPO, 'benchmark'), tmp_path / 'benchmark')
  before = _digest(tmp_path / 'benchmark')
  bench = tmp_path / 'benchmark'
  (bench / 'configs' / 'toy_model.json').write_text(json.dumps({
      'source': 'a test', 'reduced': [],
      'flags': {'num_actions': 3, 'batch_size': 2, 'unroll_length': 5}}))
  (bench / 'traffic' / 'toy_mix.json').write_text(json.dumps({
      'driver': 'toy_driver', 'flags': {}, 'pretend_seconds': 3.5}))
  (bench / 'drivers' / 'toy_driver.py').write_text(_TOY_DRIVER)
  (bench / 'readers' / 'toy_reader.py').write_text(_TOY_READER)
  (bench / 'metrics' / 'toy.answer.json').write_text(json.dumps({
      'reader': 'toy_reader', 'args': {'key': 'answer'}}))
  manifest = json.loads(json.dumps(MANIFEST))
  manifest['configs'].append({
      'name': 'toy_model', 'source': 'a test', 'reduced': [],
      'file': 'benchmark/configs/toy_model.json', 'why': 'a test'})
  manifest['workloads'].append({
      'name': 'toy_model.toy_mix', 'config': 'toy_model',
      'traffic': 'toy_mix', 'chips': 1, 'why': 'a test'})
  rate = next(m for m in manifest['end_to_end']
              if 'workloads' in m and m['unit'] == 'frames/s')
  rate['workloads'].append('toy_model.toy_mix')
  manifest['per_layer'].append({
      'name': 'toy.answer', 'unit': 'things', 'better': 'higher',
      'source': 'program_counter', 'layer': 'toy', 'moves': rate['name'],
      'workloads': ['toy_model.toy_mix']})
  (tmp_path / 'BENCHMARK.json').write_text(json.dumps(manifest))

  common = ('--workload', 'toy_model.toy_mix', '--seed', '1',
            '--seconds', '1', '--rehearse')
  end_to_end = _result(_run(str(tmp_path), *common, '--trace', '0',
                            timeout=300))
  assert end_to_end['correct'] is True and end_to_end['attempted'] == 7
  assert end_to_end['metrics']['rehearsal.' + rate['name']]['value'] == (
      7 * 2 * 5 * 4 / 3.5)
  assert 'rehearsal.setup_s' in end_to_end['metrics']
  layers = _result(_run(str(tmp_path), *common, '--trace', '1',
                        timeout=300))
  assert layers['metrics']['rehearsal.toy.answer'] == {
      'value': 42.0, 'unit': 'things'}
  after = _digest(tmp_path / 'benchmark')
  assert {k: after[k] for k in before} == before  # nothing edited


# --- The reducer reads the profiler's own file. ---


def test_trace_reads_a_profilers_file_and_a_cpu_has_no_device_plane(
    tmp_path):
  import glob

  import jax
  import jax.numpy as jnp

  from benchmark.harness import trace_reduce
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  options.host_tracer_level = 0
  jax.profiler.start_trace(str(tmp_path), profiler_options=options)
  jnp.ones((8, 8)).sum().block_until_ready()
  jax.profiler.stop_trace()
  (path,) = glob.glob(str(tmp_path / 'plugins/profile/*/*.xplane.pb'))
  trace = trace_reduce.Trace.from_xplane(path)
  assert trace.chips() == []  # nothing to reduce, and no default
  with pytest.raises(ValueError):
    trace_reduce.busy(trace)
  assert not trace_reduce.add_host_spans(
      trace, [('barrier', 0, 10)], 0, '^jit_bench_clock_sync')
  assert 'PLANE' in trace_reduce.describe(path)

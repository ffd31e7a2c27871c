"""chip_smoke.py from the CPU side: it must refuse to run without an
accelerator (before any compile), fail in a directory that holds
nothing else of the repo, and stop non-zero at the first failed phase.
What it proves on the chip, only a chip run can say."""

import os
import shutil
import subprocess
import sys
import tempfile

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _run(cwd, *args, timeout=300, devices=1):
  """Runs the script in a session of its own and, the moment it ends,
  looks for anything it left running there (the forkserver and the
  resource tracker end by themselves only after their parent has)."""
  env = dict(os.environ, JAX_PLATFORMS='cpu',
             XLA_FLAGS=f'--xla_force_host_platform_device_count={devices}')
  # Files, not pipes: reading a pipe to its end would wait for every
  # process that inherited it, and hide exactly what is looked for.
  with tempfile.TemporaryFile('w+') as out, \
      tempfile.TemporaryFile('w+') as err:
    proc = subprocess.Popen(
        [sys.executable, 'chip_smoke.py', *args], cwd=cwd, env=env,
        stdout=out, stderr=err, start_new_session=True)
    try:
      proc.wait(timeout=timeout)
    finally:
      left = _session_members(proc.pid)
      for pid in left:
        os.kill(pid, 9)
    assert not left, f'chip_smoke.py left processes running: {left}'
    out.seek(0)
    err.seek(0)
    return subprocess.CompletedProcess(
        proc.args, proc.returncode, out.read(), err.read())


def test_cpu_pinned_process_exits_at_the_device_check():
  out = _run(REPO)
  assert out.returncode == 2, out.stderr[-2000:]
  assert 'platform=cpu' in out.stdout
  assert 'no accelerator' in out.stdout
  # Before any build or compile, and with no result line.
  assert 'rebuilt' not in out.stdout and '[kernel]' not in out.stdout
  assert '"ok"' not in out.stdout


def test_fails_in_a_directory_with_nothing_else_of_the_repo(tmp_path):
  shutil.copy(os.path.join(REPO, 'chip_smoke.py'), tmp_path)
  out = _run(str(tmp_path))
  assert out.returncode != 0
  assert '"ok"' not in out.stdout


@pytest.mark.parametrize('failing', ['kernel', 'fleet', 'anakin',
                                     'procgen'])
def test_a_failed_phase_ends_the_smoke_nonzero_and_runs_no_later_phase(
    monkeypatch, capsys, failing):
  order = ['kernel', 'fleet', 'anakin', 'procgen']
  ran = []

  def phase(name):
    ran.append(name)
    chip_smoke.check(name != failing, f'{name} phase')

  monkeypatch.setattr(chip_smoke, 'kernel_phase',
                      lambda interpret: phase('kernel'))
  monkeypatch.setattr(chip_smoke, 'fleet_phase',
                      lambda name, *args, **kwargs: phase(name))
  monkeypatch.setattr(chip_smoke, 'anakin_phase',
                      lambda *args: phase('anakin'))
  # The control flow is under test, not the build.
  monkeypatch.setattr(chip_smoke.subprocess, 'run',
                      lambda *args, **kwargs: None)
  # In-process, "every child" is the test session's own forkserver; the
  # tests that run the script as a process check what it leaves behind.
  monkeypatch.setattr(chip_smoke, 'stop_children', lambda: [])
  with pytest.raises(SystemExit) as failure:
    chip_smoke.main(['--cpu-rehearsal'])
  assert failure.value.code not in (0, None)
  assert ran == order[:order.index(failing) + 1]
  out = capsys.readouterr().out
  assert '"ok"' not in out
  assert all(line.startswith('[CPU REHEARSAL, NOT A CHIP RESULT]')
             for line in out.splitlines())


@pytest.mark.slow  # 70-110 s: every phase at toy size (ci.sh full lane)
@pytest.mark.parametrize('devices', [1, 4])
def test_cpu_rehearsal_runs_every_phase_and_prints_no_result(devices):
  """One device, and four virtual ones standing in for the four-chip
  host: the {data: 4} mesh checks and the parity body run too."""
  out = _run(REPO, '--cpu-rehearsal', timeout=900, devices=devices)
  assert out.returncode == 0, out.stderr[-3000:]
  for name in ('fleet', 'anakin', 'procgen'):
    assert f'[{name}] PASS' in out.stdout
  assert 'within 1e-4 of the scan form' in out.stdout
  assert ('the batch is split 4 ways' in out.stdout) == (devices == 4)
  assert ('chip_parity(data=4, model=1)' in out.stdout) == (devices == 4)
  assert '"ok"' not in out.stdout
  assert all(line.startswith('[CPU REHEARSAL, NOT A CHIP RESULT]')
             for line in out.stdout.splitlines())

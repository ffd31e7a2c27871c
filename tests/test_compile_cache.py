"""Persistent compilation cache (round 23): the placement rule
(JAX_COMPILATION_CACHE_DIR set => the code sets nothing; unset => one
fixed path inside the checkout, never logdir-derived), arming order
in `distributed.maybe_initialize` (before the backend early-return so
single-process runs get it too), warm-spin-up cache hits observed
through the JAX monitoring bus, and concurrent members sharing one
cache dir without tripping over each other.
"""

import os
import threading

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import (
    compilation_cache as jax_compilation_cache)

from scalable_agent_tpu import config as config_lib
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.parallel import distributed

CACHE_ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'


@pytest.fixture(autouse=True)
def _cache_placed_by_code(monkeypatch):
  """The rule under test branches on the environment variable; each
  test states the value it needs."""
  monkeypatch.delenv(CACHE_ENV_VAR, raising=False)


def _base_config(logdir, **kw):
  return Config(env_backend='bandit', logdir=logdir, **kw)


def _current_cache_dir():
  # Contextmanager-backed flags are read via attribute access
  # (`jax.config.read` raises for them).
  return jax.config.jax_compilation_cache_dir


class _armed:
  """Arm a cache dir for the duration of a test, restoring the
  process-global jax.config value (and resetting the cache backend)
  on exit so unrelated tests never write into a deleted tmp dir."""

  def __init__(self, dirname):
    self.dirname = dirname

  def __enter__(self):
    self.prev = _current_cache_dir()
    return self

  def __exit__(self, *exc):
    jax.config.update('jax_compilation_cache_dir', self.prev)
    jax_compilation_cache.reset_cache()


# --- Flag resolution. ---


def test_resolved_compile_cache_dir_auto_is_the_fixed_checkout_path(
    tmp_path):
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  assert config_lib.REPO_COMPILE_CACHE_DIR == os.path.join(
      repo, '.jax_cache')
  cfg = _base_config(str(tmp_path))
  assert cfg.compile_cache_dir == 'auto'
  assert cfg.resolved_compile_cache_dir == (
      config_lib.REPO_COMPILE_CACHE_DIR)
  # Never logdir-derived: a directory that moves with the run never
  # hits.
  other = _base_config(str(tmp_path / 'another_run'))
  assert other.resolved_compile_cache_dir == (
      cfg.resolved_compile_cache_dir)
  assert str(tmp_path) not in cfg.resolved_compile_cache_dir


@pytest.mark.parametrize('flag', ['auto', '', 'explicit'])
def test_env_var_set_means_the_code_sets_no_cache_dir(
    tmp_path, monkeypatch, flag):
  # Where JAX_COMPILATION_CACHE_DIR places the cache, no code path
  # sets jax_compilation_cache_dir, whatever --compile_cache_dir says.
  monkeypatch.setenv(CACHE_ENV_VAR, str(tmp_path / 'placed_outside'))
  monkeypatch.setattr(distributed, '_cpu_pinned_platform', lambda: False)
  flag_dir = str(tmp_path / 'flag_cache') if flag == 'explicit' else flag
  cfg = _base_config(str(tmp_path), compile_cache_dir=flag_dir)
  assert cfg.resolved_compile_cache_dir == ''
  sentinel = str(tmp_path / 'what_jax_read_from_the_environment')
  with _armed(None):
    jax.config.update('jax_compilation_cache_dir', sentinel)
    assert distributed.maybe_initialize(cfg) is False
    assert _current_cache_dir() == sentinel
  assert not os.path.exists(str(tmp_path / 'flag_cache'))


def test_resolved_compile_cache_dir_empty_disables(tmp_path):
  cfg = _base_config(str(tmp_path), compile_cache_dir='')
  assert cfg.resolved_compile_cache_dir == ''


def test_resolved_compile_cache_dir_explicit_wins(tmp_path):
  d = os.path.join(str(tmp_path), 'shared_cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)
  assert cfg.resolved_compile_cache_dir == d


# --- Arming. ---


def test_arm_compile_cache_creates_dir_and_updates_jax_config(tmp_path):
  d = os.path.join(str(tmp_path), 'cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)
  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert os.path.isdir(d)
    assert _current_cache_dir() == d


def test_arm_compile_cache_empty_flag_is_a_no_op(tmp_path):
  cfg = _base_config(str(tmp_path), compile_cache_dir='')
  with _armed(None):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert _current_cache_dir() is None
    assert not os.path.exists(os.path.join(str(tmp_path), '.jax_cache'))


def test_arm_compile_cache_survives_an_uncreatable_dir(tmp_path):
  # A read-only checkout costs the warm start, never the run.
  blocker = tmp_path / 'a_file'
  blocker.write_text('')
  cfg = _base_config(str(tmp_path),
                     compile_cache_dir=str(blocker / 'cache'))
  with _armed(None):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert _current_cache_dir() is None


def test_arm_compile_cache_first_writer_wins(tmp_path):
  # Whoever arms first in a process decides; the member configs that
  # follow must NOT re-arm to other dirs (that would shatter the
  # shared cache into N cold ones).
  parent = os.path.join(str(tmp_path), 'parent_cache')
  member = os.path.join(str(tmp_path), 'member_cache')
  with _armed(parent):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(
        _base_config(str(tmp_path), compile_cache_dir=parent))
    distributed.arm_compile_cache(
        _base_config(str(tmp_path), compile_cache_dir=member))
    assert _current_cache_dir() == parent
    assert not os.path.exists(member)


def test_auto_does_not_arm_on_cpu_pinned_process(tmp_path):
  # This test process IS cpu-pinned (tests/conftest.py), so this runs
  # the real gate: XLA:CPU executable reload could SIGSEGV at driver
  # scale on jaxlib 0.4.36 (a full tier-1 run died mid-suite, exit
  # 134/139, the first time a driver test re-hit an entry an earlier
  # test had written), and with one fixed directory every CPU run
  # would share entries — 'auto' must not turn the cache on here.
  cfg = _base_config(str(tmp_path))  # compile_cache_dir='auto'
  with _armed(None):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert _current_cache_dir() is None


def test_auto_arms_the_fixed_path_when_not_cpu_pinned(tmp_path,
                                                      monkeypatch):
  # On an accelerator host 'auto' arms the one fixed directory (here
  # redirected under tmp_path so the test leaves the checkout clean).
  monkeypatch.setattr(distributed, '_cpu_pinned_platform', lambda: False)
  d = os.path.join(str(tmp_path), 'fixed', '.jax_cache')
  monkeypatch.setattr(config_lib, 'REPO_COMPILE_CACHE_DIR', d)
  cfg = _base_config(str(tmp_path / 'logdir'))
  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert _current_cache_dir() == d
    assert os.path.isdir(d)
  assert not os.path.exists(str(tmp_path / 'logdir' / '.jax_cache'))


def test_explicit_dir_arms_even_on_cpu_pinned_process(tmp_path):
  # Explicit opt-in overrides the CPU gate (the caller vouches their
  # programs reload safely — e.g. the small anakin/bandit programs).
  assert distributed._cpu_pinned_platform()  # conftest pins cpu
  d = os.path.join(str(tmp_path), 'cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)
  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    assert _current_cache_dir() == d


def test_maybe_initialize_arms_cache_before_backend_early_return(tmp_path):
  d = os.path.join(str(tmp_path), 'cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)
  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    # No coordinator_address: multi-host init is skipped, but the
    # cache must already be armed by then.
    assert distributed.maybe_initialize(cfg) is False
    assert _current_cache_dir() == d
    assert os.path.isdir(d)


# --- Behavior: warm spin-ups actually hit the persistent cache. ---


def test_second_spinup_of_identical_program_hits_cache(tmp_path):
  d = os.path.join(str(tmp_path), 'cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)
  events = []

  def _listener(event, **kwargs):
    events.append(event)

  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.monitoring.register_event_listener(_listener)
    try:
      @jax.jit
      def f(x):
        return jnp.sin(x) * jnp.cos(x) + 23.0

      f(jnp.ones((8, 8))).block_until_ready()
      assert os.listdir(d), 'cold compile wrote no cache entries'
      # Drop the in-memory executable so the second "spin-up" must
      # go back through the compilation path.
      jax.clear_caches()
      events.clear()
      f(jnp.ones((8, 8))).block_until_ready()
      hits = [e for e in events if 'compilation_cache' in e and 'hit' in e]
      assert hits, f'no persistent-cache hit events in {sorted(set(events))}'
    finally:
      jax.monitoring.unregister_event_listener(_listener)
      jax.config.update('jax_persistent_cache_min_compile_time_secs',
                        prev_min)


def test_the_cache_key_follows_scope_names_and_not_source_lines(tmp_path):
  """A cached executable carries the operation names of the source
  that compiled it, and the device trace's per-scope shares read
  them: a renamed scope must miss the cache, a moved line must not."""
  d = os.path.join(str(tmp_path), 'cache')
  cfg = _base_config(str(tmp_path), compile_cache_dir=d)

  def entries_added_by(scope, blank_lines):
    source = '\n' * blank_lines + (
        'def scoped_step(x):\n'
        f'  with jax.named_scope({scope!r}):\n'
        '    return jnp.tanh(x) @ x + 29.0\n')
    namespace = {'jax': jax, 'jnp': jnp}
    exec(compile(source, 'model.py', 'exec'), namespace)  # noqa: S102
    before = set(os.listdir(d))
    jax.jit(namespace['scoped_step'])(
        jnp.ones((8, 8))).block_until_ready()
    return len(set(os.listdir(d)) - before)

  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(cfg)
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    try:
      assert entries_added_by('torso', 0) >= 1
      assert entries_added_by('torso', 7) == 0
      assert entries_added_by('core', 0) >= 1
    finally:
      jax.config.update('jax_persistent_cache_min_compile_time_secs',
                        prev_min)


def test_concurrent_members_share_one_cache_dir_safely(tmp_path):
  # Two "members" compiling into the same armed dir at once: writes
  # are keyed and atomic on the JAX side; nothing may raise and the
  # dir must hold entries afterwards.
  d = os.path.join(str(tmp_path), 'cache')
  with _armed(d):
    jax.config.update('jax_compilation_cache_dir', None)
    distributed.arm_compile_cache(
        _base_config(str(tmp_path), compile_cache_dir=d))
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    errors = []

    def member(k):
      try:
        @jax.jit
        def g(x):
          return jnp.tanh(x) + float(k) * x

        g(jnp.ones((4, 4))).block_until_ready()
      except Exception as e:  # pragma: no cover - failure path
        errors.append(e)

    try:
      threads = [threading.Thread(target=member, args=(k,))
                 for k in range(2)]
      for t in threads:
        t.start()
      for t in threads:
        t.join()
    finally:
      jax.config.update('jax_persistent_cache_min_compile_time_secs',
                        prev_min)
    assert not errors
    assert os.listdir(d)

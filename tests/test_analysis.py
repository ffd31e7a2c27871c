"""Invariant-analyzer tests (round 18, docs/STATIC_ANALYSIS.md).

Two halves:

1. The contract-lint framework: one SEEDED violation per checker in a
   minimal fixture repo (the no-vacuous-checkers rule — several
   checkers find nothing on the live tree, so each must prove it CAN
   fire), plus the clean-live-repo gate asserting the merged tree
   lints clean.
2. The runtime half: OrderedLock's deterministic two-thread
   opposite-order inversion detection, the Condition integration, the
   incident sink, and the make_lock arming seam.
"""

import os
import subprocess
import sys
import threading

import pytest

from scalable_agent_tpu import analysis
from scalable_agent_tpu.analysis import concurrency  # noqa: F401
from scalable_agent_tpu.analysis import contracts
from scalable_agent_tpu.analysis import runtime as lock_runtime

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- fixture-repo plumbing -------------------------------------------

OBS_DOC = """# Observability
### Durable incident markers
`halt`
## inventory
- `x/y` — a metric.
<!-- lint:summary-scalars:begin -->
- `known_tag`
<!-- lint:summary-scalars:end -->
"""


def mini_repo(tmp_path, files):
  """Write a minimal repo tree; returns its root as str."""
  for rel, content in files.items():
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(content)
  (tmp_path / 'scalable_agent_tpu').mkdir(exist_ok=True)
  return str(tmp_path)


def run_only(root, check):
  return [f for f in analysis.run_checks(root, only=[check])
          if f.check == check]


# --- seeded violations: every checker proven able to fire ------------


@pytest.mark.parametrize('site', [
    "c = telemetry.counter('ghost/metric')",
    # The span recorder's sites are names of the same interface.
    "with telemetry.span('ghost/metric', id=3): pass",
    "p = telemetry.park('ghost/metric')"])
def test_metric_names_fires_both_directions(tmp_path, site):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/foo.py':
          "from scalable_agent_tpu import telemetry\n" + site + "\n",
      'docs/OBSERVABILITY.md': OBS_DOC,
  })
  findings = run_only(root, 'metric-names')
  symbols = {f.symbol for f in findings}
  assert 'ghost/metric' in symbols          # registered, undocumented
  assert 'x/y' in symbols                   # documented, unregistered
  # The line points at the registration site.
  (reg,) = [f for f in findings if f.symbol == 'ghost/metric']
  assert reg.path == 'scalable_agent_tpu/foo.py' and reg.line == 2


def test_slo_objectives_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/slo.py':
          "DEFAULT_OBJECTIVES = (\n"
          "    Objective(name='o1', metric='never/registered'),\n"
          ")\n",
      'docs/OBSERVABILITY.md': OBS_DOC + "| `docd` | `x/y` | v |\n",
  })
  symbols = {f.symbol for f in run_only(root, 'slo-objectives')}
  # unregistered metric + undocumented objective + orphaned doc row
  assert symbols == {'o1', 'docd'}


def test_controller_rules_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/slo.py':
          "DEFAULT_OBJECTIVES = (Objective(name='real',"
          " metric='x/y'),)\n",
      'scalable_agent_tpu/controller.py':
          "KNOWN_ACTUATORS = ('replay_k',)\n"
          "DEFAULT_RULES = (\n"
          "    Rule(objective='bogus', actuator='warp_drive'),\n"
          ")\n",
  })
  symbols = {f.symbol for f in run_only(root, 'controller-rules')}
  assert symbols == {'bogus', 'warp_drive'}


CONFIG_SRC = """import dataclasses
@dataclasses.dataclass
class Config:
  exposed: int = 1
  secret_knob: int = 0
INTERNAL_FIELDS = ('stale_entry',)
"""

EXPERIMENT_SRC = """import flags_shim as flags
flags.DEFINE_integer('exposed', 1, 'doc')
flags.DEFINE_integer('orphan_flag', 2, 'doc')
"""


def test_config_flags_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/config.py': CONFIG_SRC,
      'experiment.py': EXPERIMENT_SRC,
  })
  findings = run_only(root, 'config-flags')
  symbols = {f.symbol for f in findings}
  # flagless field, flag without field, stale INTERNAL_FIELDS entry
  assert symbols == {'secret_knob', 'orphan_flag', 'stale_entry'}
  flagless = next(f for f in findings if f.symbol == 'secret_knob')
  assert 'INTERNAL_FIELDS' in flagless.message


def test_config_flags_internal_allowlist_suppresses(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/config.py':
          CONFIG_SRC.replace("('stale_entry',)", "('secret_knob',)"),
      'experiment.py': EXPERIMENT_SRC,
  })
  symbols = {f.symbol for f in run_only(root, 'config-flags')}
  assert symbols == {'orphan_flag'}


def test_validate_coverage_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/config.py':
          "def validate_foo(config):\n  return []\n",
      'scalable_agent_tpu/driver.py':
          "def train(config):\n  validate_foo(config)\n"
          "def evaluate(config):\n  pass\n",
  })
  findings = run_only(root, 'validate-coverage')
  assert {f.symbol for f in findings} == {'evaluate:validate_foo'}


def test_durable_markers_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/observability.py':
          "class EventLog:\n"
          "  _DURABLE_MARKERS = ('halt', 'ghost_marker')\n",
      'scalable_agent_tpu/driver.py':
          "events.event('health_halt', step=1)\n",
      'docs/OBSERVABILITY.md': OBS_DOC,
  })
  symbols = {f.symbol for f in run_only(root, 'durable-markers')}
  # ghost_marker: emitted nowhere AND missing from the docs list.
  assert 'ghost_marker' in symbols
  msgs = [f.message for f in run_only(root, 'durable-markers')]
  assert any('orphaned fsync rule' in m for m in msgs)


def test_protocol_versions_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/runtime/remote.py':
          "PROTOCOL_VERSION = 6\n_COMPATIBLE_PROTOCOLS = (5, 6, 7)\n",
      'docs/TRANSPORT.md':
          "| version |\n|---|\n| v5 |\n| v6 |\n| v9 |\n",
  })
  findings = run_only(root, 'protocol-versions')
  symbols = {f.symbol for f in findings}
  # v7 undocumented, v9 documented-but-incompatible, and
  # PROTOCOL_VERSION != max(compat).
  assert symbols == {'v7', 'v9', 'v6'}


def test_summary_scalars_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/driver.py':
          "def train(w):\n"
          "  w.scalar('mystery_tag', 1.0, 0)\n"
          "  for key in ('loop_tag_a', 'known_tag'):\n"
          "    w.scalar(key, 2.0, 0)\n",
      # The loops' shared lifecycle writes summary scalars too.
      'scalable_agent_tpu/lifecycle.py':
          "def write_health_scalars(w):\n"
          "  w.scalar('known_tag', 1.0, 0)\n"
          "  w.scalar('lifecycle_tag', 1.0, 0)\n",
      'docs/OBSERVABILITY.md': OBS_DOC,
  })
  symbols = {f.symbol for f in run_only(root, 'summary-scalars')}
  # Literal + loop-resolved tags missing from the doc block; the
  # documented known_tag is written, so it is NOT orphaned.
  assert symbols == {'mystery_tag', 'loop_tag_a', 'lifecycle_tag'}


def test_summary_scalars_fix_docs_round_trip(tmp_path):
  files = {
      'scalable_agent_tpu/driver.py':
          "def train(w):\n  w.scalar('fresh_tag', 1.0, 0)\n",
      'scalable_agent_tpu/lifecycle.py': '',
      'docs/OBSERVABILITY.md': OBS_DOC,
  }
  root = mini_repo(tmp_path, files)
  assert run_only(root, 'summary-scalars')
  changed = contracts.fix_summary_scalar_docs(analysis.CheckContext(root))
  assert changed
  assert run_only(root, 'summary-scalars') == []


def test_checker_inventory_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'docs/STATIC_ANALYSIS.md': "| `imaginary-checker` | what |\n",
  })
  symbols = {f.symbol for f in run_only(root, 'checker-inventory')}
  assert 'imaginary-checker' in symbols      # documented, unregistered
  assert 'guarded-by' in symbols             # registered, undocumented


def test_ci_wiring_fires(tmp_path):
  root = mini_repo(tmp_path, {
      'scripts/ci.sh': "python - <<'LINT_EOF'\nLINT_EOF\n",
  })
  symbols = {f.symbol for f in run_only(root, 'ci-wiring')}
  assert symbols == {'lint-call', 'inline-heredoc'}


def test_sharding_registry_fires(tmp_path):
  root = mini_repo(tmp_path, {
      # Every spelling the checker must see: a from-import alias, the
      # bare name, and the fully-qualified attribute call.
      'scalable_agent_tpu/rogue.py':
          "from jax.sharding import PartitionSpec as P\n"
          "def place():\n"
          "  return P(None, 'model')\n",
      'scalable_agent_tpu/rogue2.py':
          "import jax.sharding\n"
          "spec = jax.sharding.PartitionSpec('data')\n",
      # Round 20: hand-built NamedSharding is the same offense — a
      # placement the registry never resolved (both spellings).
      'scalable_agent_tpu/rogue3.py':
          "from jax.sharding import NamedSharding\n"
          "def pin(mesh, spec):\n"
          "  return NamedSharding(mesh, spec)\n",
      'scalable_agent_tpu/rogue4.py':
          "import jax.sharding\n"
          "def pin(mesh, spec):\n"
          "  return jax.sharding.NamedSharding(mesh, spec)\n",
      # The registry itself is exempt.
      'scalable_agent_tpu/parallel/sharding.py':
          "from jax.sharding import PartitionSpec as P\n"
          "HOME = P('data')\n",
  })
  findings = run_only(root, 'sharding-registry')
  symbols = {f.symbol for f in findings}
  assert symbols == {'scalable_agent_tpu/rogue.py:place',
                     'scalable_agent_tpu/rogue2.py:<module>',
                     'scalable_agent_tpu/rogue3.py:pin',
                     'scalable_agent_tpu/rogue4.py:pin'}
  assert all('registry' in f.message for f in findings)


def test_stale_allowlist_entry_is_a_finding(tmp_path, monkeypatch):
  root = mini_repo(tmp_path, {
      'scripts/ci.sh': "python scripts/lint.py\n",
  })
  monkeypatch.setitem(contracts.ALLOWLISTS, 'ci-wiring',
                      {'never-fires': 'seeded stale entry'})
  findings = analysis.run_checks(root, only=['ci-wiring'])
  assert [f.symbol for f in findings] == ['ci-wiring:never-fires']
  assert findings[0].check == 'allowlist'


def test_unknown_checker_name_raises():
  with pytest.raises(ValueError, match='unknown checker'):
    analysis.run_checks(REPO_ROOT, only=['not-a-checker'])


# --- the guarded-by AST pass -----------------------------------------

GUARDED_SRC = """import threading
from scalable_agent_tpu.analysis.runtime import guarded_by

class Widget:
  _items: guarded_by('_lock')
  _meta: guarded_by('_meta_lock')

  def __init__(self):
    self._lock = threading.Lock()
    self._cv = threading.Condition(self._lock)
    self._meta_lock = threading.Lock()
    self._items = []          # __init__ is exempt
    self._meta = None

  def good(self):
    with self._lock:
      self._items.append(1)

  def good_via_condition(self):
    with self._cv:
      return len(self._items)   # Condition aliases the mutex

  def good_closure(self):
    with self._lock:
      def peek():
        return self._items[-1]  # inherits the lexical held-set
      return peek()

  def _drain_locked(self):
    return self._items.pop()    # caller-held lock: exempt

  def _mixed_locked(self):
    self._items.append(3)       # caller-held lock: exempt
    self._meta = 'x'            # VIOLATION: a DIFFERENT lock family —
                                # the one assumed-held grant is spent
                                # on _lock

  def bad_read(self):
    return len(self._items)     # VIOLATION: no lock

  def bad_wrong_lock(self):
    with self._meta_lock:
      self._items.append(2)     # VIOLATION: wrong lock held
"""


def test_guarded_by_checker_semantics(tmp_path):
  root = mini_repo(tmp_path, {
      'scalable_agent_tpu/widget.py': GUARDED_SRC,
  })
  findings = run_only(root, 'guarded-by')
  symbols = sorted(f.symbol for f in findings)
  assert symbols == ['Widget._mixed_locked._meta',
                     'Widget.bad_read._items',
                     'Widget.bad_wrong_lock._items']
  assert all('_slot' not in s for s in symbols)
  assert all(f.path == 'scalable_agent_tpu/widget.py'
             for f in findings)


# --- the clean-live-repo gate ----------------------------------------


def test_live_repo_lints_clean():
  """The acceptance bar: `python scripts/lint.py` exits 0 on the
  merged tree — every checker runs over the real repo and every real
  violation found during round 18 has been fixed."""
  findings = analysis.run_checks(REPO_ROOT)
  assert findings == [], '\n'.join(f.render() for f in findings)


def test_cli_list_matches_registry():
  out = subprocess.run(
      [sys.executable, os.path.join(REPO_ROOT, 'scripts', 'lint.py'),
       '--list'], capture_output=True, text=True, check=True).stdout
  listed = {line.split(':', 1)[0] for line in out.splitlines() if line}
  assert listed == {n for n, _, _ in analysis.all_checkers()}


# --- OrderedLock: the runtime race detector --------------------------


@pytest.fixture
def clean_graph():
  """Isolate the process-wide graph + raise mode per test."""
  lock_runtime.reset()
  was_raise = lock_runtime._raise_on_cycle
  yield
  lock_runtime.arm(lock_runtime.is_armed(), raise_on_cycle=was_raise)
  lock_runtime.set_incident_sink(None)
  lock_runtime.reset()


def test_make_lock_arming_seam(clean_graph):
  # conftest arms via LOCK_ORDER_CHECK=1, so armed here.
  assert lock_runtime.is_armed()
  assert isinstance(lock_runtime.make_lock('t.armed'),
                    lock_runtime.OrderedLock)
  lock_runtime.arm(False)
  try:
    plain = lock_runtime.make_lock('t.plain')
    assert not isinstance(plain, lock_runtime.OrderedLock)
  finally:
    lock_runtime.arm(True)


def test_two_thread_opposite_order_detects_deterministically(
    clean_graph):
  """The seeded inversion: thread 1 takes A then B; thread 2 takes B
  then A. No actual deadlock occurs (the threads run sequentially),
  yet the graph records the opposite orders and flags the cycle at
  thread 2's acquisition ATTEMPT — detection is deterministic, not
  interleaving-dependent."""
  a = lock_runtime.OrderedLock('t.A')
  b = lock_runtime.OrderedLock('t.B')
  events = []
  lock_runtime.set_incident_sink(
      lambda kind, **f: events.append((kind, f)))

  def t1():
    with a:
      with b:
        pass

  def t2():
    with b:
      with a:
        pass

  th1 = threading.Thread(target=t1)
  th1.start()
  th1.join()
  assert lock_runtime.cycles_detected() == 0
  th2 = threading.Thread(target=t2)
  th2.start()
  th2.join()
  assert lock_runtime.cycles_detected() == 1
  report = lock_runtime.cycle_reports()[0]
  assert report['holding'] == 't.B' and report['acquiring'] == 't.A'
  # The reported cycle walks the pre-existing ordering from the
  # acquired lock back around: A -> B -> A.
  assert report['cycle'][0] == 't.A' and report['cycle'][-1] == 't.A'
  assert 't.B' in report['cycle']
  # The incident sink saw the durable kind.
  assert events and events[0][0] == 'lock_order_inversion'
  assert 't.B' in events[0][1]['cycle']


def test_one_acquisition_closing_two_cycles_reports_both(clean_graph):
  """Review regression: a single acquisition while holding several
  locks can close SEVERAL cycles — each must be reported, because
  the edges are inserted either way and the known-edge fast path
  would suppress an unreported one forever."""
  a = lock_runtime.OrderedLock('t.M1')
  b = lock_runtime.OrderedLock('t.M2')
  c = lock_runtime.OrderedLock('t.M3')

  def run(fn):
    th = threading.Thread(target=fn)
    th.start()
    th.join()

  run(lambda: _nest(c, a))       # edge C->A
  run(lambda: _nest(c, b))       # edge C->B
  assert lock_runtime.cycles_detected() == 0
  # Holding [A, B], acquire C: A->C and B->C EACH close a cycle.
  def closer():
    with a:
      with b:
        with c:
          pass
  run(closer)
  assert lock_runtime.cycles_detected() == 2
  pairs = {(r['holding'], r['acquiring'])
           for r in lock_runtime.cycle_reports()}
  assert pairs == {('t.M1', 't.M3'), ('t.M2', 't.M3')}


def _nest(outer, inner):
  with outer:
    with inner:
      pass


def test_consistent_order_never_flags(clean_graph):
  a = lock_runtime.OrderedLock('t.C')
  b = lock_runtime.OrderedLock('t.D')

  def worker():
    for _ in range(50):
      with a:
        with b:
          pass

  threads = [threading.Thread(target=worker) for _ in range(4)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  assert lock_runtime.cycles_detected() == 0


def test_raise_mode_raises(clean_graph):
  lock_runtime.arm(True, raise_on_cycle=True)
  a = lock_runtime.OrderedLock('t.E')
  b = lock_runtime.OrderedLock('t.F')
  with a:
    with b:
      pass
  caught = []

  def t2():
    try:
      with b:
        with a:
          pass
    except lock_runtime.LockOrderInversion as e:
      caught.append(e)

  th = threading.Thread(target=t2)
  th.start()
  th.join()
  assert len(caught) == 1
  assert 't.E' in str(caught[0]) and 't.F' in str(caught[0])


def test_raise_mode_nonblocking_cycle_releases_lock(clean_graph):
  """Review regression: a SUCCESSFUL non-blocking acquire records its
  edges after the underlying lock is taken — if that detection raises
  (raise mode), the lock must be released on the way out or it leaks
  held-forever (the caller never saw a successful acquire)."""
  lock_runtime.arm(True, raise_on_cycle=True)
  a = lock_runtime.OrderedLock('t.NBR1')
  b = lock_runtime.OrderedLock('t.NBR2')
  run = lambda fn: (lambda t: (t.start(), t.join()))(  # noqa: E731
      threading.Thread(target=fn))
  run(lambda: _nest(b, a))       # record b -> a
  caught = []

  def t2():
    with a:
      try:
        b.acquire(blocking=False)   # succeeds, closes the cycle
      except lock_runtime.LockOrderInversion as e:
        caught.append(e)

  run(t2)
  assert len(caught) == 1
  # b must be free again — the raise path released it.
  assert b.acquire(blocking=False)
  b.release()


def test_reentrant_lock_no_self_edge(clean_graph):
  r = lock_runtime.OrderedLock('t.R', recursive=True)
  with r:
    with r:
      assert r._is_owned()
  assert lock_runtime.cycles_detected() == 0


def test_condition_integration(clean_graph):
  """threading.Condition over an OrderedLock: wait/notify work and
  ownership asserts answer from the per-thread held list."""
  lock = lock_runtime.OrderedLock('t.cond')
  cv = threading.Condition(lock)
  box = []

  def consumer():
    with cv:
      while not box:
        cv.wait(timeout=5.0)
      box.append('seen')

  th = threading.Thread(target=consumer)
  th.start()
  with cv:
    box.append('item')
    cv.notify()
  th.join(timeout=5.0)
  assert not th.is_alive() and box == ['item', 'seen']
  assert lock_runtime.cycles_detected() == 0


def test_nonblocking_acquire_failure_records_no_edge(clean_graph):
  a = lock_runtime.OrderedLock('t.NB1')
  b = lock_runtime.OrderedLock('t.NB2')
  b.acquire()
  hold = threading.Event()
  done = threading.Event()

  def holder():
    with b:
      hold.set()
      done.wait(timeout=5.0)

  # b is held by THIS thread; a failed try-acquire under `a` from a
  # second thread must not invent an a->b edge.
  def prober():
    with a:
      assert not b.acquire(blocking=False)
  th = threading.Thread(target=prober)
  th.start()
  th.join()
  b.release()
  # Now the opposite order for real: b then a — if the failed probe
  # had recorded a->b, this would flag a cycle; it must not.
  with b:
    with a:
      pass
  assert lock_runtime.cycles_detected() == 0


def test_armed_fault_storm_config_flag_exists():
  """The chaos fault storm passes lock_order_check=True; keep the
  knob's existence pinned (config field + experiment flag are also
  covered by the config-flags lint on the live tree)."""
  from scalable_agent_tpu.config import Config
  assert Config().lock_order_check is False
  assert Config(lock_order_check=True).lock_order_check is True

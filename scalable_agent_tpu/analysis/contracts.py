"""Contract-lint checkers: the literal-string contracts that hold the
fleet together, machine-checked both directions.

Ported from the scripts/ci.sh inline heredoc (metric names, SLO
objectives, controller rules — rounds 14/15) and extended to every
contract nothing verified before round 18: config fields <->
experiment.py flags, validate_* coverage in driver.train AND
driver.evaluate, durable incident markers <-> emitted kinds <-> docs,
protocol-version literals <-> the docs/TRANSPORT.md version table,
and the driver's summary-scalar tags <-> the docs/OBSERVABILITY.md
inventory.

Every checker is pure stdlib `ast` + regex over docs — greppable
LITERAL registration/emission is the repo-wide convention that makes
these static checks possible (telemetry.py's docstring states it for
metric names; this module extends the same rule to every contract it
checks). Non-literal names are invisible to the lint and therefore
forbidden on these surfaces.
"""

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from scalable_agent_tpu.analysis import CheckContext, Finding, checker

# Per-check suppressions: {check: {symbol: reason}}. Etiquette: every
# entry carries the reason it exists; the runner flags STALE entries
# (suppressing nothing) as findings, so suppressions die with the
# violations they covered. Prefer fixing over allowlisting — this
# table being empty on a clean tree is the goal state.
ALLOWLISTS: Dict[str, Dict[str, str]] = {}


# --- shared AST helpers ----------------------------------------------


def _str_const(node) -> Optional[str]:
  if isinstance(node, ast.Constant) and isinstance(node.value, str):
    return node.value
  return None


def _str_tuple(node) -> Optional[List[str]]:
  """Literal tuple/list of strings -> list, else None."""
  if isinstance(node, (ast.Tuple, ast.List)):
    out = []
    for elt in node.elts:
      s = _str_const(elt)
      if s is None:
        return None
      out.append(s)
    return out
  return None


def _int_tuple(node) -> Optional[List[int]]:
  if isinstance(node, (ast.Tuple, ast.List)):
    out = []
    for elt in node.elts:
      if not (isinstance(elt, ast.Constant)
              and isinstance(elt.value, int)):
        return None
      out.append(elt.value)
    return out
  return None


def _module_assign(tree: ast.AST, name: str) -> Optional[ast.AST]:
  """The value node of a module-level `name = ...` assignment."""
  for node in tree.body:  # type: ignore[attr-defined]
    if isinstance(node, ast.Assign):
      for tgt in node.targets:
        if isinstance(tgt, ast.Name) and tgt.id == name:
          return node.value
    elif isinstance(node, ast.AnnAssign):
      if (isinstance(node.target, ast.Name) and node.target.id == name
          and node.value is not None):
        return node.value
  return None


def _class_assign(tree: ast.AST, cls: str, name: str
                  ) -> Optional[ast.AST]:
  for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef) and node.name == cls:
      for st in node.body:
        if isinstance(st, ast.Assign):
          for tgt in st.targets:
            if isinstance(tgt, ast.Name) and tgt.id == name:
              return st.value
  return None


_METRIC_NAME = re.compile(r'[a-z0-9_]+(?:/[a-z0-9_]+)+')


def registered_metric_names(ctx: CheckContext,
                            kinds=('counter', 'gauge', 'histogram')
                            ) -> Dict[str, Tuple[str, int]]:
  """Every literal-string telemetry registration in the package:
  {metric_name: (path, line)}. A registration is a call to
  `counter`/`gauge`/`histogram` either bare (telemetry.py itself) or
  as an attribute of `telemetry`/`_telemetry` — `writer.histogram`
  (the summary stream API) is a different surface and excluded, same
  as the ci.sh heredoc this replaces. With `kinds=('span', 'park',
  'activity')`: the span recorder's sites, whose names share the docs'
  spelling."""
  out: Dict[str, Tuple[str, int]] = {}
  for rel in ctx.package_sources():
    for node in ast.walk(ctx.tree(rel)):
      if not isinstance(node, ast.Call) or not node.args:
        continue
      fn = node.func
      if isinstance(fn, ast.Name):
        if fn.id not in kinds:
          continue
      elif isinstance(fn, ast.Attribute):
        if fn.attr not in kinds:
          continue
        if not (isinstance(fn.value, ast.Name)
                and fn.value.id in ('telemetry', '_telemetry')):
          continue
      else:
        continue
      name = _str_const(node.args[0])
      if name and _METRIC_NAME.fullmatch(name):
        out.setdefault(name, (rel, node.lineno))
  return out


def _documented_metric_names(ctx: CheckContext) -> Set[str]:
  doc = ctx.text('docs/OBSERVABILITY.md')
  return set(re.findall(r'`([a-z0-9_]+(?:/[a-z0-9_]+)+)`', doc))


# --- 1. metric names <-> docs inventory ------------------------------


@checker('metric-names',
         'every telemetry counter/gauge/histogram registration and '
         'every span/park site in scalable_agent_tpu/ appears in '
         'docs/OBSERVABILITY.md (the inventory, the span table), and '
         'no documented name is orphaned')
def check_metric_names(ctx: CheckContext) -> List[Finding]:
  registered = registered_metric_names(ctx)
  spans = registered_metric_names(
      ctx, kinds=('span', 'park', 'activity'))
  documented = _documented_metric_names(ctx)
  findings = []
  for names, what, where in ((registered, 'registered metric',
                              'inventory'),
                             (spans, 'span', 'span table')):
    for name in sorted(set(names) - documented):
      path, line = names[name]
      findings.append(Finding(
          'metric-names', path, line, name,
          f'{what} {name!r} is missing from the '
          f'docs/OBSERVABILITY.md {where}'))
  for name in sorted(documented - set(registered) - set(spans)):
    findings.append(Finding(
        'metric-names', 'docs/OBSERVABILITY.md', 1, name,
        f'documented metric {name!r} is no longer registered '
        'anywhere in scalable_agent_tpu/'))
  return findings


# --- 2. SLO objectives <-> registry + docs table ---------------------


def _slo_defaults(ctx: CheckContext) -> List[Tuple[str, str, int]]:
  """[(objective_name, metric, line)] from slo.DEFAULT_OBJECTIVES."""
  tree = ctx.tree('scalable_agent_tpu/slo.py')
  value = _module_assign(tree, 'DEFAULT_OBJECTIVES')
  out = []
  if value is None:
    return out
  for node in ast.walk(value):
    if isinstance(node, ast.Call):
      name = metric = None
      for kw in node.keywords:
        if kw.arg == 'name':
          name = _str_const(kw.value)
        elif kw.arg == 'metric':
          metric = _str_const(kw.value)
      if name and metric:
        out.append((name, metric, node.lineno))
  return out


@checker('slo-objectives',
         "every slo.DEFAULT_OBJECTIVES metric is a registered "
         "telemetry name, and the docs/OBSERVABILITY.md SLO "
         "inventory table matches the default set by name, both "
         "directions")
def check_slo_objectives(ctx: CheckContext) -> List[Finding]:
  registered = set(registered_metric_names(ctx))
  defaults = _slo_defaults(ctx)
  doc = ctx.text('docs/OBSERVABILITY.md')
  doc_names = set(re.findall(
      r'^\|\s*`([a-z0-9_]+)`\s*\|\s*`[a-z0-9_]+(?:/[a-z0-9_]+)+`',
      doc, re.MULTILINE))
  findings = []
  for name, metric, line in defaults:
    if metric not in registered:
      findings.append(Finding(
          'slo-objectives', 'scalable_agent_tpu/slo.py', line, name,
          f'objective {name!r} judges unregistered metric '
          f'{metric!r}: it would evaluate no_data forever'))
  names = {n for n, _, _ in defaults}
  for name in sorted(names - doc_names):
    findings.append(Finding(
        'slo-objectives', 'scalable_agent_tpu/slo.py', 1, name,
        f'default objective {name!r} missing from the '
        'docs/OBSERVABILITY.md SLO inventory table'))
  for name in sorted(doc_names - names):
    findings.append(Finding(
        'slo-objectives', 'docs/OBSERVABILITY.md', 1, name,
        f'documented SLO objective {name!r} is not in '
        'slo.DEFAULT_OBJECTIVES'))
  return findings


# --- 3. controller rules <-> objectives + actuators ------------------


@checker('controller-rules',
         'every controller.DEFAULT_RULES objective is a shipped SLO '
         'default and every actuator a KNOWN_ACTUATORS name')
def check_controller_rules(ctx: CheckContext) -> List[Finding]:
  tree = ctx.tree('scalable_agent_tpu/controller.py')
  slo_names = {n for n, _, _ in _slo_defaults(ctx)}
  known_node = _module_assign(tree, 'KNOWN_ACTUATORS')
  known = set(_str_tuple(known_node) or [])
  rules = _module_assign(tree, 'DEFAULT_RULES')
  findings = []
  if rules is None:
    return [Finding('controller-rules',
                    'scalable_agent_tpu/controller.py', 1,
                    'DEFAULT_RULES',
                    'DEFAULT_RULES not found as a module literal')]
  for node in ast.walk(rules):
    if not isinstance(node, ast.Call):
      continue
    for kw in node.keywords:
      val = _str_const(kw.value)
      if val is None:
        continue
      if kw.arg == 'objective' and val not in slo_names:
        findings.append(Finding(
            'controller-rules', 'scalable_agent_tpu/controller.py',
            node.lineno, val,
            f'rule watches objective {val!r} which is not in '
            'slo.DEFAULT_OBJECTIVES — it can never fire'))
      if kw.arg == 'actuator' and val not in known:
        findings.append(Finding(
            'controller-rules', 'scalable_agent_tpu/controller.py',
            node.lineno, val,
            f'rule drives unknown actuator {val!r} (not in '
            'KNOWN_ACTUATORS)'))
  return findings


# --- 4. config fields <-> experiment.py flags ------------------------


def _config_fields(ctx: CheckContext) -> Dict[str, int]:
  tree = ctx.tree('scalable_agent_tpu/config.py')
  fields: Dict[str, int] = {}
  for node in ast.walk(tree):
    if isinstance(node, ast.ClassDef) and node.name == 'Config':
      for st in node.body:
        if (isinstance(st, ast.AnnAssign)
            and isinstance(st.target, ast.Name)):
          fields[st.target.id] = st.lineno
  return fields


@checker('config-flags',
         'every Config field is exposed as an experiment.py flag or '
         'named in config.INTERNAL_FIELDS; no flag without a field, '
         'no stale INTERNAL_FIELDS entry')
def check_config_flags(ctx: CheckContext) -> List[Finding]:
  fields = _config_fields(ctx)
  cfg_tree = ctx.tree('scalable_agent_tpu/config.py')
  internal_node = _module_assign(cfg_tree, 'INTERNAL_FIELDS')
  findings = []
  if internal_node is None:
    findings.append(Finding(
        'config-flags', 'scalable_agent_tpu/config.py', 1,
        'INTERNAL_FIELDS',
        'config.py must define the INTERNAL_FIELDS literal tuple '
        '(the explicit allowlist for fields deliberately not '
        'exposed as flags)'))
    internal = []
  else:
    internal = _str_tuple(internal_node) or []
  flags: Dict[str, int] = {}
  for node in ast.walk(ctx.tree('experiment.py')):
    if (isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr.startswith('DEFINE_')
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == 'flags' and node.args):
      name = _str_const(node.args[0])
      if name:
        flags[name] = node.lineno
  for name in sorted(set(fields) - set(flags) - set(internal)):
    findings.append(Finding(
        'config-flags', 'scalable_agent_tpu/config.py',
        fields[name], name,
        f'Config.{name} has no experiment.py flag and no '
        'INTERNAL_FIELDS entry — operators cannot set it, and '
        'nothing records that as deliberate'))
  for name in sorted(set(flags) - set(fields)):
    findings.append(Finding(
        'config-flags', 'experiment.py', flags[name], name,
        f'flag --{name} has no Config field: config_from_flags '
        'silently drops it'))
  for name in sorted(internal):
    if name not in fields:
      findings.append(Finding(
          'config-flags', 'scalable_agent_tpu/config.py', 1, name,
          f'INTERNAL_FIELDS entry {name!r} is not a Config field — '
          'stale allowlist entry'))
    elif name in flags:
      findings.append(Finding(
          'config-flags', 'scalable_agent_tpu/config.py', 1, name,
          f'INTERNAL_FIELDS entry {name!r} HAS a flag '
          '(experiment.py:%d) — the allowlist entry is stale'
          % flags[name]))
  return findings


# --- 5. validate_* coverage in driver.train AND driver.evaluate ------


@checker('validate-coverage',
         'every config.validate_* knob group is called from both '
         'driver.train and driver.evaluate')
def check_validate_coverage(ctx: CheckContext) -> List[Finding]:
  cfg_tree = ctx.tree('scalable_agent_tpu/config.py')
  groups: Dict[str, int] = {}
  for node in cfg_tree.body:  # type: ignore[attr-defined]
    if (isinstance(node, ast.FunctionDef)
        and node.name.startswith('validate_')):
      groups[node.name] = node.lineno
  drv = ctx.tree('scalable_agent_tpu/driver.py')
  findings = []
  for entry in ('train', 'evaluate'):
    fn = next((n for n in drv.body  # type: ignore[attr-defined]
               if isinstance(n, ast.FunctionDef) and n.name == entry),
              None)
    if fn is None:
      findings.append(Finding(
          'validate-coverage', 'scalable_agent_tpu/driver.py', 1,
          entry, f'driver.{entry} not found'))
      continue
    called = set()
    for node in ast.walk(fn):
      if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Name):
          called.add(f.id)
        elif isinstance(f, ast.Attribute):
          called.add(f.attr)
    for group in sorted(set(groups) - called):
      findings.append(Finding(
          'validate-coverage', 'scalable_agent_tpu/driver.py',
          fn.lineno, f'{entry}:{group}',
          f'driver.{entry} never calls config.{group} — a bad knob '
          'in that group passes spin-up silently on this path'))
  return findings


# --- 6. durable incident markers <-> emitters <-> docs ---------------


def _emitted_incident_kinds(ctx: CheckContext
                            ) -> Dict[str, Tuple[str, int]]:
  """Literal incident kinds: first args of `<x>.event('kind', ...)`
  calls anywhere in the package or scripts/, plus literal kinds
  handed to an incident `sink(...)` (the analysis runtime's seam)."""
  kinds: Dict[str, Tuple[str, int]] = {}
  sources = ctx.package_sources() + ctx.package_sources('scripts')
  for rel in sources:
    try:
      tree = ctx.tree(rel)
    except SyntaxError:
      continue
    for node in ast.walk(tree):
      if not isinstance(node, ast.Call) or not node.args:
        continue
      f = node.func
      is_event = (isinstance(f, ast.Attribute) and f.attr == 'event')
      is_sink = isinstance(f, ast.Name) and f.id == 'sink'
      if not (is_event or is_sink):
        continue
      kind = _str_const(node.args[0])
      if kind:
        kinds.setdefault(kind, (rel, node.lineno))
  return kinds


def _doc_durable_markers(ctx: CheckContext) -> Set[str]:
  doc = ctx.text('docs/OBSERVABILITY.md')
  m = re.search(
      r'### Durable incident markers\n(.*?)(?:\n#|\Z)', doc, re.S)
  if not m:
    return set()
  return set(re.findall(r'`([a-z0-9_]+)`', m.group(1)))


@checker('durable-markers',
         'every EventLog._DURABLE_MARKERS marker matches an incident '
         'kind some module actually emits, and the '
         'docs/OBSERVABILITY.md durable-marker list matches the code '
         'both directions')
def check_durable_markers(ctx: CheckContext) -> List[Finding]:
  tree = ctx.tree('scalable_agent_tpu/observability.py')
  node = _class_assign(tree, 'EventLog', '_DURABLE_MARKERS')
  markers = _str_tuple(node) if node is not None else None
  findings = []
  if markers is None:
    return [Finding('durable-markers',
                    'scalable_agent_tpu/observability.py', 1,
                    '_DURABLE_MARKERS',
                    'EventLog._DURABLE_MARKERS literal tuple not '
                    'found')]
  kinds = _emitted_incident_kinds(ctx)
  for marker in sorted(markers):
    if not any(marker in kind for kind in kinds):
      findings.append(Finding(
          'durable-markers', 'scalable_agent_tpu/observability.py',
          node.lineno, marker,
          f'durable marker {marker!r} matches no emitted incident '
          'kind anywhere in scalable_agent_tpu/ or scripts/ — '
          'orphaned fsync rule'))
  documented = _doc_durable_markers(ctx)
  if not documented:
    findings.append(Finding(
        'durable-markers', 'docs/OBSERVABILITY.md', 1,
        'durable-markers-section',
        'docs/OBSERVABILITY.md has no "### Durable incident '
        'markers" section listing the fsync markers'))
    return findings
  for marker in sorted(set(markers) - documented):
    findings.append(Finding(
        'durable-markers', 'docs/OBSERVABILITY.md', 1, marker,
        f'durable marker {marker!r} (code) missing from the '
        'docs/OBSERVABILITY.md durable-marker list'))
  for marker in sorted(documented - set(markers)):
    findings.append(Finding(
        'durable-markers', 'docs/OBSERVABILITY.md', 1, marker,
        f'documented durable marker {marker!r} is not in '
        'EventLog._DURABLE_MARKERS'))
  return findings


# --- 7. protocol versions <-> docs/TRANSPORT.md table ----------------


@checker('protocol-versions',
         "remote.py's _COMPATIBLE_PROTOCOLS matches the "
         'docs/TRANSPORT.md version table both directions, and '
         'PROTOCOL_VERSION is the newest compatible version')
def check_protocol_versions(ctx: CheckContext) -> List[Finding]:
  tree = ctx.tree('scalable_agent_tpu/runtime/remote.py')
  compat_node = _module_assign(tree, '_COMPATIBLE_PROTOCOLS')
  compat = _int_tuple(compat_node) if compat_node is not None else None
  current_node = _module_assign(tree, 'PROTOCOL_VERSION')
  findings = []
  if compat is None or not isinstance(current_node, ast.Constant):
    return [Finding('protocol-versions',
                    'scalable_agent_tpu/runtime/remote.py', 1,
                    '_COMPATIBLE_PROTOCOLS',
                    '_COMPATIBLE_PROTOCOLS / PROTOCOL_VERSION '
                    'literals not found')]
  current = current_node.value
  doc = ctx.text('docs/TRANSPORT.md')
  doc_versions = {int(v) for v in
                  re.findall(r'^\|\s*v(\d+)\s*\|', doc, re.M)}
  if not doc_versions:
    return [Finding('protocol-versions', 'docs/TRANSPORT.md', 1,
                    'version-table',
                    'docs/TRANSPORT.md has no protocol version table '
                    '(rows starting `| vN |`)')]
  for v in sorted(set(compat) - doc_versions):
    findings.append(Finding(
        'protocol-versions', 'scalable_agent_tpu/runtime/remote.py',
        compat_node.lineno, f'v{v}',
        f'protocol v{v} is in _COMPATIBLE_PROTOCOLS but missing '
        'from the docs/TRANSPORT.md version table'))
  for v in sorted(doc_versions - set(compat)):
    findings.append(Finding(
        'protocol-versions', 'docs/TRANSPORT.md', 1, f'v{v}',
        f'docs/TRANSPORT.md documents protocol v{v} which is not in '
        '_COMPATIBLE_PROTOCOLS'))
  if current != max(compat):
    findings.append(Finding(
        'protocol-versions', 'scalable_agent_tpu/runtime/remote.py',
        compat_node.lineno, f'v{current}',
        f'PROTOCOL_VERSION ({current}) is not the newest compatible '
        f'version ({max(compat)})'))
  return findings


# --- 8. driver summary scalars <-> docs inventory --------------------

SUMMARY_BLOCK_BEGIN = '<!-- lint:summary-scalars:begin -->'
SUMMARY_BLOCK_END = '<!-- lint:summary-scalars:end -->'


# The files whose `.scalar(...)` calls are the driver's summary block:
# driver.py and the run lifecycle its loops share.
_SUMMARY_SOURCES = ('scalable_agent_tpu/driver.py',
                    'scalable_agent_tpu/lifecycle.py')


def driver_summary_tags(ctx: CheckContext) -> Dict[str, int]:
  """Literal summary-scalar tags the driver writes: first args of
  `.scalar(tag, value, step)` calls in driver.py and lifecycle.py —
  direct literals plus names bound by a `for tag in (<literal tuple>)`
  loop (the replay-stats export shape). Fully dynamic tags (per-level
  episode tags, tracer percentile dicts, stacked step metrics) are
  outside the static contract and documented in prose instead."""
  nodes = [node for rel in _SUMMARY_SOURCES
           for node in ast.walk(ctx.tree(rel))]
  loop_names: Dict[str, List[str]] = {}
  for node in nodes:
    if (isinstance(node, ast.For) and isinstance(node.target, ast.Name)):
      vals = _str_tuple(node.iter)
      if vals:
        loop_names.setdefault(node.target.id, []).extend(vals)
  tags: Dict[str, int] = {}
  for node in nodes:
    if (isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == 'scalar' and node.args):
      arg = node.args[0]
      lit = _str_const(arg)
      if lit is not None:
        tags.setdefault(lit, node.lineno)
      elif isinstance(arg, ast.Name) and arg.id in loop_names:
        for val in loop_names[arg.id]:
          tags.setdefault(val, node.lineno)
  return tags


def documented_summary_tags(ctx: CheckContext) -> Set[str]:
  doc = ctx.text('docs/OBSERVABILITY.md')
  start = doc.find(SUMMARY_BLOCK_BEGIN)
  end = doc.find(SUMMARY_BLOCK_END)
  if start < 0 or end < 0:
    return set()
  # Tags may be namespaced with '/' (e.g. population/best_return).
  return set(re.findall(r'`([a-z0-9_/]+)`', doc[start:end]))


@checker('summary-scalars',
         'every literal summary-scalar tag driver.py or lifecycle.py '
         'writes appears in the generated docs/OBSERVABILITY.md block '
         '(scripts/lint.py --fix-docs regenerates it), and no '
         'documented tag is orphaned')
def check_summary_scalars(ctx: CheckContext) -> List[Finding]:
  tags = driver_summary_tags(ctx)
  documented = documented_summary_tags(ctx)
  findings = []
  if not documented:
    return [Finding(
        'summary-scalars', 'docs/OBSERVABILITY.md', 1,
        'summary-scalar-block',
        'docs/OBSERVABILITY.md has no generated summary-scalar '
        f'inventory block ({SUMMARY_BLOCK_BEGIN} ... '
        f'{SUMMARY_BLOCK_END}) — run scripts/lint.py --fix-docs')]
  for tag in sorted(set(tags) - documented):
    findings.append(Finding(
        'summary-scalars', 'scalable_agent_tpu/driver.py',
        tags[tag], tag,
        f'driver writes summary scalar {tag!r} which is missing '
        'from the docs/OBSERVABILITY.md inventory block (run '
        'scripts/lint.py --fix-docs)'))
  for tag in sorted(documented - set(tags)):
    findings.append(Finding(
        'summary-scalars', 'docs/OBSERVABILITY.md', 1, tag,
        f'documented summary scalar {tag!r} is no longer written by '
        'driver.py (run scripts/lint.py --fix-docs)'))
  return findings


def fix_summary_scalar_docs(ctx: CheckContext) -> bool:
  """Regenerate the summary-scalar block in docs/OBSERVABILITY.md
  from the live driver.py tags. Returns True when the file changed."""
  tags = sorted(driver_summary_tags(ctx))
  body = '\n'.join(
      [SUMMARY_BLOCK_BEGIN] + [f'- `{t}`' for t in tags]
      + [SUMMARY_BLOCK_END])
  path = ctx.root / 'docs/OBSERVABILITY.md'
  doc = path.read_text()
  start = doc.find(SUMMARY_BLOCK_BEGIN)
  end = doc.find(SUMMARY_BLOCK_END)
  if start < 0 or end < 0:
    raise SystemExit(
        'docs/OBSERVABILITY.md has no summary-scalar block markers; '
        'add the section first (see docs/STATIC_ANALYSIS.md)')
  new = doc[:start] + body + doc[end + len(SUMMARY_BLOCK_END):]
  if new != doc:
    path.write_text(new)
    return True
  return False


# --- 9. checker inventory <-> docs/STATIC_ANALYSIS.md ----------------


@checker('checker-inventory',
         'the docs/STATIC_ANALYSIS.md checker table matches '
         'scripts/lint.py --list both directions (the self-applied '
         'contract lint)')
def check_checker_inventory(ctx: CheckContext) -> List[Finding]:
  from scalable_agent_tpu import analysis
  names = {n for n, _, _ in analysis.all_checkers()}
  try:
    doc = ctx.text('docs/STATIC_ANALYSIS.md')
  except FileNotFoundError:
    return [Finding('checker-inventory', 'docs/STATIC_ANALYSIS.md', 1,
                    'docs', 'docs/STATIC_ANALYSIS.md does not exist')]
  doc_names = set(re.findall(r'^\|\s*`([a-z0-9-]+)`\s*\|', doc, re.M))
  findings = []
  for name in sorted(names - doc_names):
    findings.append(Finding(
        'checker-inventory', 'docs/STATIC_ANALYSIS.md', 1, name,
        f'checker {name!r} is missing from the '
        'docs/STATIC_ANALYSIS.md inventory table'))
  for name in sorted(doc_names - names):
    findings.append(Finding(
        'checker-inventory', 'docs/STATIC_ANALYSIS.md', 1, name,
        f'documented checker {name!r} is not registered in the '
        'analysis framework'))
  return findings


# --- 10. ci.sh wiring -------------------------------------------------


@checker('ci-wiring',
         'scripts/ci.sh runs scripts/lint.py and carries no inline '
         'lint heredoc')
def check_ci_wiring(ctx: CheckContext) -> List[Finding]:
  ci = ctx.text('scripts/ci.sh')
  findings = []
  if 'scripts/lint.py' not in ci:
    findings.append(Finding(
        'ci-wiring', 'scripts/ci.sh', 1, 'lint-call',
        'scripts/ci.sh never invokes scripts/lint.py'))
  if 'LINT_EOF' in ci:
    line = ci[:ci.index('LINT_EOF')].count('\n') + 1
    findings.append(Finding(
        'ci-wiring', 'scripts/ci.sh', line, 'inline-heredoc',
        'scripts/ci.sh still contains the inline LINT_EOF lint '
        'heredoc — the checks live in scripts/lint.py now'))
  return findings


# --- 11. sharding registry (round 19) ---------------------------------


@checker('sharding-registry',
         'no inline PartitionSpec(...)/NamedSharding(...) '
         'construction outside parallel/sharding.py — every sharding '
         'decision resolves through the registry')
def check_sharding_registry(ctx: CheckContext) -> List[Finding]:
  """parallel/sharding.py is the ONE source of sharding truth: a
  `PartitionSpec(...)` — or, round 20, a `NamedSharding(...)` binding
  a spec to a mesh — constructed anywhere else in the package (or
  its entry points) is a private sharding decision the registry
  cannot see — exactly the hand-copied-consumer drift this round
  deleted, and exactly what the elastic cross-topology restore would
  silently miss when respecifying for a new mesh. Tests are
  deliberately out of scope (they construct expected specs to assert
  the registry against)."""
  sources = ctx.package_sources()
  try:
    ctx.text('experiment.py')
    sources.append('experiment.py')
  except (FileNotFoundError, OSError):
    pass
  try:
    sources.extend(ctx.package_sources('scripts'))
  except (FileNotFoundError, OSError):
    pass
  findings = []
  for rel in sources:
    if rel.replace('\\', '/') == 'scalable_agent_tpu/parallel/sharding.py':
      continue
    tree = ctx.tree(rel)
    # PartitionSpec names this module can construct with: `from
    # jax.sharding import PartitionSpec [as P]` aliases...
    aliases: Set[str] = set()
    for node in ast.walk(tree):
      if isinstance(node, ast.ImportFrom) and node.module and (
          node.module == 'jax.sharding'
          or node.module.endswith('.sharding')):
        for a in node.names:
          if a.name in ('PartitionSpec', 'NamedSharding'):
            aliases.add(a.asname or a.name)
    func_of: Dict[int, str] = {}
    for node in ast.walk(tree):
      if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        for sub in ast.walk(node):
          if hasattr(sub, 'lineno'):
            func_of.setdefault(sub.lineno, node.name)
    for node in ast.walk(tree):
      if not isinstance(node, ast.Call):
        continue
      inline = (
          # P(...) / PartitionSpec(...) / NamedSharding(...) via a
          # from-import alias
          (isinstance(node.func, ast.Name) and node.func.id in aliases)
          # ...or any attribute spelling:
          # jax.sharding.PartitionSpec(...) / .NamedSharding(...)
          or (isinstance(node.func, ast.Attribute)
              and node.func.attr in ('PartitionSpec',
                                     'NamedSharding')))
      if inline:
        where = func_of.get(node.lineno, '<module>')
        findings.append(Finding(
            'sharding-registry', rel, node.lineno,
            f'{rel}:{where}',
            'inline PartitionSpec/NamedSharding construction outside '
            'parallel/sharding.py — resolve the spec through the '
            'sharding registry (spec helpers or ShardingRegistry '
            'methods) so every consumer sees the same decision'))
  return findings

"""SLO verdict report + perf-regression gate (round 14).

    python scripts/slo_report.py LOGDIR [--bench BENCH_OUT.json]
                                 [--history HISTORY.md]
                                 [--tolerance 0.08] [--json OUT.json]
                                 [--update-fps-baseline BASELINE.json]

The single go/no-go artifact for CI and chip runs:

1. **SLO verdict gate** — reads the run's `SLO_VERDICT.json`
   (written by driver.train's SLO engine, scalable_agent_tpu/slo.py)
   and renders the per-objective table (state, value, target, margin,
   burns, triggered captures). A failing verdict exits nonzero naming
   the violated objectives.

2. **Bench regression gate** (`--bench` with `--history`) — diffs the
   bench headline (`BENCH_OUT.json`'s `value`, the synthetic
   env-frames/s number) against the baseline derived from a history
   file's recorded rounds (the max of the per-round headline column,
   rows like `| r4 | 320,260 fps | ... |`). A drop beyond
   `--tolerance` (default 8%) exits nonzero. The repo ships no
   history file — no row has been recorded on the current chip — so
   without `--history` the gate is skipped. SMOKE-unit bench
   artifacts skip the gate with a note (CPU smoke numbers are
   mechanics checks, not perf records).

3. **Baseline maintenance** (`--update-fps-baseline`) — records the
   run's measured env-frames/s into the per-host baseline file the
   `fps_floor` objective judges future runs against (slo.py
   update_baseline; only do this from a run you would accept as the
   floor).

Exit codes: 0 all gates pass, 1 any gate failed, 2 missing artifacts.
"""

import argparse
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fmt(v, digits=4):
  if v is None:
    return '-'
  try:
    f = float(v)
  except (TypeError, ValueError):
    return str(v)
  if math.isnan(f):
    return '-'
  return f'{f:.{digits}g}'


def load_history_baseline(history_path):
  """The bench-headline baseline from a history file: the max of the
  per-round synthetic headline column (`| rN | 313,838 fps ...`).
  Returns (baseline_fps or None, rows_parsed)."""
  try:
    with open(history_path) as f:
      text = f.read()
  except OSError:
    return None, 0
  rows = re.findall(r'^\|\s*r\d+\s*\|\s*([\d,]+)\s*fps', text,
                    re.MULTILINE)
  values = [float(r.replace(',', '')) for r in rows]
  return (max(values) if values else None), len(values)


def verdict_rows(verdict):
  rows = []
  for name, e in sorted(verdict.get('objectives', {}).items()):
    rows.append({
        'objective': name,
        'severity': e.get('severity'),
        'state': e.get('state'),
        'value': e.get('value'),
        'target': e.get('target'),
        'margin': e.get('margin'),
        'burns': e.get('burns', 0),
        'metric': e.get('metric'),
    })
  return rows


def render_verdict(verdict):
  out = []
  w = out.append
  ok = verdict.get('pass')
  w('== SLO verdict: %s ==' % ('PASS' if ok else 'FAIL'))
  w(f"{'objective':>28} {'sev':>7} {'state':>12} {'value':>12} "
    f"{'target':>12} {'margin':>12} {'burns':>6}")
  for row in verdict_rows(verdict):
    w(f"{row['objective']:>28} {row['severity']:>7} "
      f"{row['state']:>12} {_fmt(row['value']):>12} "
      f"{_fmt(row['target']):>12} {_fmt(row['margin']):>12} "
      f"{row['burns']:>6}")
  captures = verdict.get('captures') or {}
  if captures:
    w('-- triggered captures --')
    for name, cap in sorted(captures.items()):
      w(f'  {name}:')
      for kind in ('flight', 'trace_slice', 'profile'):
        w(f'    {kind}: {cap.get(kind) or "-"}')
  violations = verdict.get('violations') or []
  if violations:
    w('violated objectives: ' + ', '.join(violations))
  return '\n'.join(out)


def bench_gate(bench_path, history_path, tolerance):
  """(gate dict, failed bool). SMOKE artifacts and missing baselines
  report 'skipped' and never fail — the gate only judges numbers that
  are actually comparable."""
  gate = {'bench': bench_path, 'history': history_path,
          'tolerance': tolerance, 'status': 'skipped', 'reason': None}
  try:
    with open(bench_path) as f:
      bench = json.load(f)
  except (OSError, ValueError) as e:
    gate['reason'] = f'unreadable bench artifact: {e}'
    return gate, False
  unit = str(bench.get('unit', ''))
  value = bench.get('value')
  gate['value'] = value
  gate['unit'] = unit
  if 'SMOKE' in unit:
    gate['reason'] = ('SMOKE bench artifact: mechanics check, not a '
                      'perf record — gate skipped')
    return gate, False
  if not history_path:
    gate['reason'] = 'no --history file given: nothing to gate against'
    return gate, False
  baseline, rows = load_history_baseline(history_path)
  gate['baseline'] = baseline
  gate['history_rows'] = rows
  if baseline is None:
    gate['reason'] = 'no parseable headline rows in the history'
    return gate, False
  if value is None:
    gate['reason'] = 'bench artifact carries no headline value'
    return gate, False
  floor = baseline * (1.0 - tolerance)
  gate['floor'] = floor
  gate['ratio'] = float(value) / baseline
  if float(value) < floor:
    gate['status'] = 'fail'
    gate['reason'] = (
        f'headline {value:,.0f} fps is below the regression floor '
        f'{floor:,.0f} ({(1 - tolerance) * 100:.0f}% of the recorded '
        f'best {baseline:,.0f}, {history_path})')
    return gate, True
  gate['status'] = 'pass'
  gate['reason'] = (f'headline {value:,.0f} fps >= floor '
                    f'{floor:,.0f}')
  return gate, False


def main(argv=None):
  parser = argparse.ArgumentParser(
      description='SLO verdict report + bench regression gate')
  parser.add_argument('logdir',
                      help='run directory (has SLO_VERDICT.json)')
  parser.add_argument('--bench', default=None,
                      help='BENCH_OUT.json to gate against the '
                           'history baseline')
  parser.add_argument('--history', default=None,
                      help='baseline source: a markdown table of '
                           'recorded rounds; without it the bench '
                           'gate is skipped')
  parser.add_argument('--tolerance', type=float, default=0.08,
                      help='allowed headline drop vs the history '
                           'baseline (default 0.08)')
  parser.add_argument('--json', default=None,
                      help='also write the combined report here')
  parser.add_argument('--update-fps-baseline', default=None,
                      help='record this run\'s measured env frames/s '
                           'into the per-host baseline file the '
                           'fps_floor objective reads')
  args = parser.parse_args(argv)

  from scalable_agent_tpu import slo as slo_lib

  verdict = slo_lib.read_verdict(args.logdir)
  if verdict is None:
    print(f'no SLO_VERDICT.json under {args.logdir!r} — was the run '
          'started with --slo_engine=false?', file=sys.stderr)
    return 2
  print(render_verdict(verdict))
  failed = not verdict.get('pass', False)

  report = {'logdir': args.logdir, 'slo_pass': verdict.get('pass'),
            'violations': verdict.get('violations') or [],
            'objectives': verdict_rows(verdict)}

  if args.bench:
    gate, bench_failed = bench_gate(args.bench, args.history,
                                    args.tolerance)
    report['bench_gate'] = gate
    print(f"\n== bench regression gate: {gate['status']} ==")
    print(f"   {gate['reason']}")
    failed = failed or bench_failed

  if args.update_fps_baseline:
    fps = _measured_fps(args.logdir)
    if fps is None:
      print('\nno env_frames_per_sec summaries to record as a '
            'baseline', file=sys.stderr)
    else:
      path = slo_lib.update_baseline(args.update_fps_baseline,
                                     {'fps': fps})
      report['fps_baseline'] = {'fps': fps, 'path': path}
      print(f'\nrecorded fps baseline {fps:,.1f} for this host into '
            f'{path}')

  if args.json:
    with open(args.json, 'w') as f:
      json.dump(report, f, indent=2, default=str)
    print(f'\nreport JSON: {args.json}')
  return 1 if failed else 0


def _measured_fps(logdir):
  """The run's steady-state env frames/s: the median of the second
  half of its env_frames_per_sec summary samples (skips warmup)."""
  path = os.path.join(logdir, 'summaries.jsonl')
  values = []
  try:
    with open(path) as f:
      for line in f:
        line = line.strip()
        if not line:
          continue
        try:
          e = json.loads(line)
        except ValueError:
          continue
        if e.get('tag') == 'env_frames_per_sec':
          values.append(float(e['value']))
  except OSError:
    return None
  if not values:
    return None
  tail = sorted(values[len(values) // 2:])
  return tail[len(tail) // 2]


if __name__ == '__main__':
  sys.exit(main())

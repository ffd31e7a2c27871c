"""Convert a run's JSONL summaries into TensorBoard event files.

The reference writes `tf.summary` event files an operator watches in
TensorBoard (experiment.py ≈L570 MonitoredTrainingSession
save_summaries_secs + the manual per-episode Summary protos ≈L590).
This build logs JSONL (observability.py — grep/jq-able, no TF
dependency on the hot path); this offline converter gives reference
operators their TensorBoard view back:

    python scripts/to_tensorboard.py LOGDIR [--out OUT]
    tensorboard --logdir OUT   # default: LOGDIR/tb

Each summary stream becomes a TB run: `summaries.jsonl` -> train,
`summaries_p3.jsonl` -> train_p3 (multi-host: one stream per process),
`eval_summaries.jsonl` -> eval. Scalars convert exactly (tag, value,
step, wall time). Histograms (kind=histogram: integer `counts`,
optional bin `edges`) convert via add_histogram_raw; min/max/sum/
sum_sq are reconstructed from bin centers — fine for the shape-of-
distribution reading these are for.

Import-guarded: requires the `tensorboard` package (ships with torch
in this image); the training path never imports it.
"""

import argparse
import glob
import json
import os
import shutil
import sys


def _run_name(filename):
  base = os.path.basename(filename)
  if base == 'summaries.jsonl':
    return 'train'
  if base == 'eval_summaries.jsonl':
    return 'eval'
  for prefix, run in (('summaries_', 'train_'),
                      ('eval_summaries_', 'eval_')):
    if base.startswith(prefix):
      return run + base[len(prefix):].removesuffix('.jsonl')
  return base.removesuffix('.jsonl')


def _histogram_raw_args(event):
  """JSONL histogram -> add_histogram_raw kwargs. Without edges the
  counts are per-integer bins (e.g. action ids 0..n-1)."""
  counts = event['counts']
  edges = event.get('edges')
  if edges is None:
    edges = [i - 0.5 for i in range(len(counts) + 1)]
  centers = [(edges[i] + edges[i + 1]) / 2 for i in range(len(counts))]
  num = float(sum(counts))
  total = sum(c * x for c, x in zip(counts, centers))
  total_sq = sum(c * x * x for c, x in zip(counts, centers))
  nonzero = [i for i, c in enumerate(counts) if c]
  lo = edges[nonzero[0]] if nonzero else 0.0
  hi = edges[nonzero[-1] + 1] if nonzero else 0.0
  return dict(min=lo, max=hi, num=num, sum=total, sum_squares=total_sq,
              bucket_limits=list(edges[1:]), bucket_counts=list(counts))


def _trace_report_module():
  """scripts/trace_report — the one owner of the hop-order/delta
  algorithm (its `span_hop_deltas`). Script-run resolution: when this
  file runs as `python scripts/to_tensorboard.py`, sys.path[0] is
  scripts/ itself, so `trace_report` imports flat; as a package
  member (`from scripts import to_tensorboard`, the tests) the
  relative-package spelling resolves."""
  try:
    from scripts import trace_report
  except ImportError:
    import trace_report
  return trace_report


def _trace_events(event):
  """One traces.jsonl 'batch' record → [(tag, value, step)] scalars:
  the per-batch policy-lag mean/max (the V-trace staleness curve an
  operator actually watches) and the mean per-hop latency across the
  batch's spans (round 13 — the trace stream's TensorBoard view;
  hop deltas computed by trace_report.span_hop_deltas so the two
  views can never disagree)."""
  if event.get('k') != 'batch':
    return []
  span_hop_deltas = _trace_report_module().span_hop_deltas
  step = int(event.get('step', 0))
  rows = []
  lags = event.get('lag') or []
  if lags:
    rows.append(('trace/policy_lag_mean', sum(lags) / len(lags), step))
    rows.append(('trace/policy_lag_max', float(max(lags)), step))
  deltas = {}
  for span in event.get('spans') or []:
    span_deltas, e2e = span_hop_deltas(span)
    for (n0, n1), ms in span_deltas:
      if ms is None:  # clock-skewed cross-host hop: no fake 0 point
        continue
      deltas.setdefault(f'trace/hop_{n0}_{n1}_ms', []).append(ms)
    if e2e is not None:
      deltas.setdefault('trace/e2e_ms', []).append(e2e)
  for tag, values in deltas.items():
    rows.append((tag, sum(values) / len(values), step))
  return rows


def convert(logdir, out=None):
  """Convert every summary AND trace stream under `logdir`; returns
  {run_name: events_written}. Trace streams (traces.jsonl, round 13)
  become a `trace`/`trace_pN` run of hop-latency and policy-lag
  scalars so TensorBoard operators keep their view of the new
  telemetry plane."""
  out = out or os.path.join(logdir, 'tb')
  streams = sorted(glob.glob(os.path.join(logdir, '*summaries*.jsonl')))
  trace_streams = sorted(glob.glob(os.path.join(logdir,
                                                'traces*.jsonl')))
  if not streams and not trace_streams:
    raise FileNotFoundError(
        f'no *summaries*.jsonl or traces*.jsonl under {logdir!r}')
  # After the input check: this import takes ~15 s (it drags
  # TensorFlow in through tensorboard).
  try:
    from torch.utils.tensorboard import SummaryWriter
  except ImportError as e:
    raise ImportError(
        'scripts/to_tensorboard.py writes events via '
        'torch.utils.tensorboard (`pip install torch tensorboard`); '
        'the training path itself never requires either') from e
  written = {}
  for path in trace_streams:
    base = os.path.basename(path)
    run = ('trace' if base == 'traces.jsonl'
           else 'trace_' + base[len('traces_'):].removesuffix('.jsonl'))
    run_dir = os.path.join(out, run)
    if os.path.isdir(run_dir):
      shutil.rmtree(run_dir)
    writer = SummaryWriter(run_dir)
    n = 0
    with open(path) as f:
      for line in f:
        line = line.strip()
        if not line:
          continue
        try:
          event = json.loads(line)
        except json.JSONDecodeError:
          continue
        for tag, value, step in _trace_events(event):
          writer.add_scalar(tag, value, global_step=step,
                            walltime=event.get('t'))
          n += 1
    writer.close()
    written[run] = n
  for path in streams:
    run = _run_name(path)
    run_dir = os.path.join(out, run)
    # Re-converting must replace, not append: a second event file in
    # the same run dir would make TensorBoard merge both conversions
    # and show every point twice.
    if os.path.isdir(run_dir):
      shutil.rmtree(run_dir)
    writer = SummaryWriter(run_dir)
    n = 0
    skipped = 0
    with open(path) as f:
      for line in f:
        line = line.strip()
        if not line:
          continue
        try:
          event = json.loads(line)
        except json.JSONDecodeError:
          # A crashed trainer can leave a truncated final line; the
          # thousands of valid events before it must still convert.
          skipped += 1
          continue
        step = int(event.get('step', 0))
        wall = event.get('wall_time')
        if event.get('kind') == 'histogram':
          writer.add_histogram_raw(
              event['tag'], global_step=step, walltime=wall,
              **_histogram_raw_args(event))
        else:
          writer.add_scalar(event['tag'], float(event['value']),
                            global_step=step, walltime=wall)
        n += 1
    writer.close()
    if skipped:
      print(f'warning: {run}: skipped {skipped} undecodable line(s) '
            f'in {path}', file=sys.stderr)
    written[run] = n
  return written


def main(argv=None):
  parser = argparse.ArgumentParser(
      description='JSONL summaries -> TensorBoard event files')
  parser.add_argument('logdir', help='run directory (has summaries.jsonl)')
  parser.add_argument('--out', default=None,
                      help='TB output dir (default: LOGDIR/tb)')
  args = parser.parse_args(argv)
  written = convert(args.logdir, args.out)
  for run, n in sorted(written.items()):
    print(f'{run}: {n} events')
  return 0


if __name__ == '__main__':
  sys.exit(main())

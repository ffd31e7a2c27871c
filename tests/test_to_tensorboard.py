"""JSONL -> TensorBoard converter: values survive the round trip.

Written through the real observability.SummaryWriter and read back
with TensorBoard's own EventAccumulator, so the test pins the full
operator-facing path, not the converter's internals.
"""

import numpy as np
import pytest

tb_accumulator = pytest.importorskip(
    'tensorboard.backend.event_processing.event_accumulator')

from scalable_agent_tpu import observability as obs
from scripts import to_tensorboard


@pytest.mark.slow  # tier-1 wall trim (round 20); ci.sh full-suite lane runs it
def test_scalars_and_histograms_round_trip(tmp_path):
  writer = obs.SummaryWriter(str(tmp_path))
  writer.scalar('loss/total', 1.5, step=1)
  writer.scalar('loss/total', 0.5, step=2)
  writer.histogram('actions', np.array([4, 0, 2]), step=2)
  writer.close()
  ev = obs.SummaryWriter(str(tmp_path), filename='eval_summaries.jsonl')
  ev.scalar('atari57/test_median', 42.0, step=2)
  ev.close()

  written = to_tensorboard.convert(str(tmp_path))
  assert written == {'train': 3, 'eval': 1}
  # Idempotent: re-converting replaces the event files (TensorBoard
  # would otherwise merge both passes and plot every point twice).
  to_tensorboard.convert(str(tmp_path))
  import glob as globlib
  assert len(globlib.glob(str(tmp_path / 'tb' / 'train' / '*'))) == 1

  acc = tb_accumulator.EventAccumulator(str(tmp_path / 'tb' / 'train'))
  acc.Reload()
  scalars = acc.Scalars('loss/total')
  assert [(s.step, s.value) for s in scalars] == [(1, 1.5), (2, 0.5)]
  hists = acc.Histograms('actions')
  assert hists[0].step == 2
  assert sum(hists[0].histogram_value.bucket) == 6  # 4 + 0 + 2 actions

  acc_eval = tb_accumulator.EventAccumulator(str(tmp_path / 'tb' / 'eval'))
  acc_eval.Reload()
  assert acc_eval.Scalars('atari57/test_median')[0].value == 42.0


def test_run_names():
  f = to_tensorboard._run_name
  assert f('/x/summaries.jsonl') == 'train'
  assert f('/x/summaries_p3.jsonl') == 'train_p3'
  assert f('/x/eval_summaries.jsonl') == 'eval'
  assert f('/x/eval_summaries_p1.jsonl') == 'eval_p1'


def test_missing_dir_raises(tmp_path):
  with pytest.raises(FileNotFoundError):
    to_tensorboard.convert(str(tmp_path / 'nope'))


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_truncated_final_line_is_skipped(tmp_path):
  """A crashed trainer can leave a partial last line; the valid events
  before it must still convert."""
  writer = obs.SummaryWriter(str(tmp_path))
  writer.scalar('loss/total', 1.0, step=1)
  writer.close()
  with open(writer.path, 'a') as f:
    f.write('{"tag": "loss/total", "va')  # truncated mid-write
  written = to_tensorboard.convert(str(tmp_path))
  assert written == {'train': 1}


@pytest.mark.slow  # tier-1 wall trim (PR 21); ci.sh full-suite lane runs it
def test_trace_stream_converts_to_scalars(tmp_path):
  """traces.jsonl (round 13) -> a `trace` TB run with hop-latency and
  policy-lag scalars, read back through the EventAccumulator."""
  import json
  t0 = 1000.0
  with open(tmp_path / 'traces.jsonl', 'w') as f:
    f.write(json.dumps({'k': 'publish', 'v': 1, 't': t0}) + '\n')
    f.write(json.dumps({
        'k': 'batch', 'step': 2, 't': t0 + 1.0, 'pv': 1,
        'n_fresh': 2, 'lag': [1, 3],
        'spans': [
            {'a': 'a0', 's': 0, 'bv': 0,
             'h': [['done', t0], ['send', t0 + 0.010],
                   ['wire', t0 + 0.030], ['commit', t0 + 0.031],
                   ['staged', t0 + 0.040], ['serve', t0 + 0.050],
                   ['step', t0 + 0.051]]},
            {'a': 'a1', 's': 0, 'bv': 0,
             'h': [['done', t0], ['staged', t0 + 0.020],
                   ['serve', t0 + 0.030], ['step', t0 + 0.031]]},
        ]}) + '\n')
  # A summaries stream alongside: both convert, into separate runs.
  from scalable_agent_tpu import observability as obs
  writer = obs.SummaryWriter(str(tmp_path))
  writer.scalar('loss/total', 1.0, step=2)
  writer.close()

  written = to_tensorboard.convert(str(tmp_path))
  assert written['train'] == 1
  assert written['trace'] > 0
  acc = tb_accumulator.EventAccumulator(
      str(tmp_path / 'tb' / 'trace'))
  acc.Reload()
  tags = set(acc.Tags()['scalars'])
  assert 'trace/policy_lag_mean' in tags
  assert 'trace/policy_lag_max' in tags
  assert 'trace/hop_done_send_ms' in tags
  assert 'trace/e2e_ms' in tags
  lag_mean = acc.Scalars('trace/policy_lag_mean')[0]
  assert lag_mean.step == 2 and abs(lag_mean.value - 2.0) < 1e-6
  hop = acc.Scalars('trace/hop_done_send_ms')[0]
  assert abs(hop.value - 10.0) < 1e-3  # one span has done->send

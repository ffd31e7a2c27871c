"""The sequence-policy cell from the CPU side (PR 27): its entries and
files against the contract, the counts by hand, the reference's two
copies, and its traced rehearsal to the contract's line."""

import json
import os
import types

import pytest

from benchmark.harness import brumby_counts, loader
from test_benchmark_cells import _result, _run

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
CELL = 'brumby14b.decode32'
CONFIG = 'brumby_14b_serve_d4'
CATALOG = {  # the catalog entry's `config`, every key
    'attention_bias': False, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 5120, 'intermediate_size': 17408,
    'max_position_embeddings': 32768, 'max_window_layers': 40,
    'model_type': 'brumby', 'num_attention_heads': 40,
    'num_hidden_layers': 40, 'num_key_value_heads': 8,
    'rms_norm_eps': 1e-06, 'rope_scaling': None, 'rope_theta': 1000000,
    'sliding_window': None, 'tie_word_embeddings': False,
    'use_sliding_window': False, 'vocab_size': 151936}


def _published():
  return types.SimpleNamespace(
      seq_num_layers=4, seq_hidden_size=5120, seq_num_heads=40,
      seq_num_kv_heads=8, seq_head_dim=128, seq_mlp_size=17408,
      num_actions=151936)


def test_cells_name_their_files_and_at_most_a_quarter_take_four_chips():
  """test_benchmark_harness.py's test of this name, with its last line
  corrected (conftest.py): a configuration may be `reduced`, by keys
  its file then states the published value of."""
  cells = MANIFEST['workloads']
  assert 2 <= len(cells) <= 24
  assert len({(c['config'], c['traffic']) for c in cells}) == len(cells)
  four = [c for c in cells if c['chips'] == 4]
  assert all(c['chips'] in (1, 4) for c in cells)
  assert len(four) <= max(1, len(cells) // 4)
  used = set()
  for cell in cells:
    assert len(cell['why']) <= 200
    config = loader.load_config(MANIFEST, cell['config'])
    traffic = loader.load_traffic(cell['traffic'])
    assert os.path.exists(os.path.join(
        REPO, 'benchmark', 'drivers', traffic['driver'] + '.py'))
    assert isinstance(config['flags'], dict) and 'reduced' in config
    used.add(cell['config'])
  assert used == {c['name'] for c in MANIFEST['configs']}
  for config in MANIFEST['configs']:
    assert config['file'].startswith('benchmark/configs/')
    assert len(config['why']) <= 200 and len(config['reduced']) <= 16
    file = loader.load_config(MANIFEST, config['name'])
    assert file['reduced'] == config['reduced']
    assert sorted(file.get('published', {})) == sorted(config['reduced'])


def test_the_configuration_is_the_catalogs_with_the_depth_reduced():
  entry = next(c for c in MANIFEST['configs'] if c['name'] == CONFIG)
  file = loader.load_config(MANIFEST, CONFIG)
  assert entry['reduced'] == ['num_hidden_layers'] == file['reduced']
  assert entry['source'] == file['source'] == (
      'https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/'
      'config.json')
  for key, value in CATALOG.items():
    if key in entry['reduced']:
      assert file[key] == 4 and file['published'][key] == value
    else:
      assert key in file and file[key] == value, key
  # The flags the program is started with say the same as the keys.
  flags = file['flags']
  assert (flags['seq_num_layers'], flags['seq_hidden_size'],
          flags['seq_num_heads'], flags['seq_num_kv_heads'],
          flags['seq_head_dim'], flags['seq_mlp_size'],
          flags['num_actions']) == (
              file['num_hidden_layers'], file['hidden_size'],
              file['num_attention_heads'], file['num_key_value_heads'],
              file['head_dim'], file['intermediate_size'],
              file['vocab_size'])
  assert flags['seq_rope_theta'] == file['rope_theta']
  assert flags['seq_norm_eps'] == file['rms_norm_eps']
  for stated in ('gate', 'q_k_norm_and_rope', 'qk_scale',
                 'normaliser_eps', 'state', 'value_head', 'precision'):
    assert len(file['assumed'][stated]) > 40, stated
  assert 'pipeline' in file['deployment']
  # The file repeats the counts' arithmetic.
  config = loader.build_config(loader.flag_args(
      file, loader.load_traffic('tokens32_decode'),
      {'seed': 1, 'logdir': '/nowhere'}))
  arithmetic = file['arithmetic']
  assert arithmetic['parameters'] == brumby_counts.parameters(config)
  assert arithmetic['parameter_bytes_bfloat16'] == 2 * arithmetic[
      'parameters']
  assert arithmetic['state_bytes_per_session_least'] == (
      brumby_counts.state_bytes_per_slot(config))
  assert arithmetic['least_bytes_a_merged_call_of_32'] == (
      brumby_counts.call_bytes(config, 32))
  assert config.num_actors == 32 and config.inference_state_slots == 32
  assert (config.episode_length, config.token_prompt_length) == (512, 16)


def test_the_arena_the_program_builds_is_the_size_the_file_states():
  import jax
  from scalable_agent_tpu import driver
  file = loader.load_config(MANIFEST, CONFIG)
  config = loader.build_config(loader.flag_args(
      file, loader.load_traffic('tokens32_decode'),
      {'seed': 1, 'logdir': '/nowhere'}))
  agent = driver.build_agent(config, config.num_actions)
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))
  assert nbytes(jax.eval_shape(lambda: agent.initial_state(1))) == (
      file['arithmetic']['state_bytes_per_session_as_tiled'])
  arena = jax.eval_shape(lambda: agent.state_arena(32))
  # 32 sessions, and one row more in every layer's S for padded rows.
  assert nbytes(arena) == (
      32 * file['arithmetic']['state_bytes_per_session_as_tiled'] +
      4 * 8 * 128 * 8320 * 4)
  from scalable_agent_tpu.models import init_params
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == (
      file['arithmetic']['parameters'])


def test_counts_by_hand():
  config = _published()
  # One block: q 5120 x 5120, k and v 5120 x 1024, gate 5120 x 8,
  # output 5120 x 5120, SwiGLU 3 x 5120 x 17408, norms 2 x 5120 + 2 x 128.
  block = (5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 5120 * 5120 +
           3 * 5120 * 17408 + 2 * 5120 + 2 * 128)
  assert block == 330_352_896
  assert brumby_counts.block_parameters(
      brumby_counts.shapes(config)) == block
  # 4 blocks, embedding and untied head, final norm, value head.
  assert brumby_counts.parameters(config) == (
      4 * block + 2 * 151936 * 5120 + 5120 + 5121) == 2_877_246_465
  # 8 heads x 8,256 symmetric terms x (128 values + 1), float32, 4 layers.
  assert 128 * 129 // 2 == 8256
  assert brumby_counts.state_bytes_per_slot(config) == (
      4 * 8 * 8256 * 129 * 4) == 136_323_072
  assert brumby_counts.state_bytes(config, 32) == 2 * 32 * 136_323_072
  # A call: blocks, head, final norm and value head read once in
  # bfloat16, 32 lines of the embedding, the state both ways.
  weights = 2 * (4 * block + 151936 * 5120 + 2 * 5120 + 1 + 32 * 5120)
  assert brumby_counts.call_bytes(config, 32) == (
      weights + 2 * 32 * 136_323_072) == 12_923_672_578
  assert round(brumby_counts.call_bytes(config, 32) / 819e9, 4) == 0.0158
  # FLOPs: 2 a multiply-add of every matrix weight a row, and the
  # state's 3 + 2 x 5 an element.
  matmul = 4 * (block - 2 * 5120 - 2 * 128) + 151936 * 5120 + 5120
  assert brumby_counts.call_flops(config, 32) == 32 * (
      2 * matmul + 4 * 8 * 8256 * 129 * 13)
  # Fewer live rows move less state and the same weights.
  assert (brumby_counts.call_bytes(config, 32) -
          brumby_counts.call_bytes(config, 16)) == 16 * (
              2 * 136_323_072 + 2 * 5120)


def test_the_references_two_copies_are_one_text():
  def body(path):
    with open(os.path.join(REPO, path)) as f:
      text = f.read()
    return text[text.index('For a session\'s tokens'):]
  assert body('benchmark/harness/brumby_ref.py') == body(
      'scalable_agent_tpu/models/retention_reference.py')


def test_new_entries_keep_to_the_contract():
  cell = loader.find_cell(MANIFEST, CELL)
  assert cell == MANIFEST['workloads'][-1] and cell['chips'] == 1
  assert (cell['config'], cell['traffic']) == (CONFIG, 'tokens32_decode')
  assert MANIFEST['configs'][-1]['name'] == CONFIG
  e2e = [m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')]
  assert e2e == ['policy_call_p95_ms', 'setup_s']
  new = [m for m in MANIFEST['per_layer']
         if m.get('workloads') == [CELL]]
  assert [m['name'] for m in new] == [
      'serve.call_hbm_share', 'serve.call_mfu', 'serve.device_idle_share',
      'retention.state_roofline_share', 'serve.state_share',
      'serve.mlp_share', 'serve.lm_head_share']
  assert new == MANIFEST['per_layer'][-len(new):]  # appended, in order
  layers = {m['layer'] for m in MANIFEST['per_layer'][:-len(new)]}
  for m in new:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert (m['unit'], m['moves'], m['source']) == (
        '%', 'policy_call_p95_ms', 'device_trace')
    assert m['layer'] in layers
    spec = loader.load_metric(m['name'])
    assert set(spec) == {'reader', 'args', 'what'}  # listed: no `entry`
  appended = ['inference.call_host_ms_p50', 'inference.device_ms_per_call',
              'policy_call_p50_ms', 'policy_call_p99_ms']
  for m in MANIFEST['per_layer'] + MANIFEST['end_to_end']:
    if m['name'] in appended + ['policy_call_p95_ms']:
      assert m['workloads'] == ['deep_dmlab.fleet32', CELL]
  traffic = loader.load_traffic(cell['traffic'])
  # Not held to the training fleet's layer metrics (serve_loop.py).
  assert 'env_processes' not in traffic
  assert (traffic['warm_calls'], traffic['trace_seconds']) == (64, 15)
  assert (traffic['check_sessions'], traffic['check_steps']) == (4, 48)


def test_readers_find_nothing_where_there_is_nothing():
  for name in ('trace_call_share', 'trace_kernel_roofline',
               'module_scope_share'):
    reader = loader.load_reader(name)
    args = next(loader.load_metric(m['name'])['args']
                for m in MANIFEST['per_layer']
                if loader.load_metric(m['name'])['reader'] == name)
    assert reader.read({}, **args) is None
    counted = {'open': {'server': {'calls': 0, 'requests': 0}},
               'close': {'server': {'calls': 2, 'requests': 64}}}
    assert reader.read({'trace': None, 'counters': counted},
                       **args) is None
  from benchmark.readers import trace_call_share
  assert trace_call_share.live_rows({'counters': counted}) == 32.0


@pytest.fixture(scope='module')
def traced_rehearsal():
  return _run(REPO, '--workload', CELL, '--seed', '2147489999',
              '--seconds', '2', '--trace', '1', '--rehearse')


def test_traced_rehearsal_ends_in_the_contract_line(traced_rehearsal):
  assert not traced_rehearsal.left
  result = _result(traced_rehearsal)
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and 'breakdown' not in result
  names = set(result['metrics'])
  # Counts and host clocks are there; nothing read from a device trace.
  assert names == {'rehearsal.entry.compile_s',
                   'rehearsal.entry.cache_misses',
                   'rehearsal.inference.call_host_ms_p50',
                   'rehearsal.policy_call_p50_ms',
                   'rehearsal.policy_call_p99_ms'}
  out = traced_rehearsal.stdout
  assert 'every merged call carried the whole fleet\'s rows' in out
  assert 'agrees with the attention-form reference' in out


def test_the_parent_cannot_run_the_cell_and_says_so_at_once(tmp_path):
  """A program without the sequence agent ends at its flags, with an
  error, soon, and leaves nothing behind: the driver then measures the
  cell on the change alone."""
  import shutil
  import subprocess
  import sys
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(REPO, 'benchmark'), tmp_path / 'benchmark')
  # The parent's experiment.py: no --agent, no --seq_* flags.
  parent = subprocess.run(
      ['git', 'show', '465eb1a5262a33ae6d5815a2bf020a3f18ba064a:'
       'experiment.py'], cwd=REPO, capture_output=True, text=True)
  if parent.returncode != 0:
    pytest.skip('the parent commit is not in this checkout')
  (tmp_path / 'experiment.py').write_text(parent.stdout)
  done = _run(str(tmp_path), '--workload', CELL, '--seed', '1',
              '--seconds', '1', '--trace', '0', '--rehearse', timeout=300)
  assert done.returncode not in (0, 2) and not done.left
  assert '"correct"' not in done.stdout
  # It ends where the program parses the cell's flags.
  assert 'flag --' in done.stderr

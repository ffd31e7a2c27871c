"""The cell of Mamba-2 layers beside a full attention layer from the CPU
side: its entries and files against the contract, held by position from
the cell's own entry; the configuration against the catalog; the counts
by hand and against the program's parameter tree; the reference's two
copies; the cell's comparison with its control; its readers on an empty
trace and on one by hand; its traced rehearsal to the contract's line;
the parent, which cannot run it and says so at once."""

import ast
import inspect
import os
import types

import numpy as np
import pytest

import jax

from benchmark.drivers import (serve_prefill_decode, serve_ssm_decode,
                               serve_window_decode)
from benchmark.harness import correct, granite_counts, granite_ref, loader
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import (SSMHybridDims, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput
from test_benchmark_cells import _result, _run

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
CELL = 'granite4hsmall.decode32_ctx16k'
CONFIG = 'granite_4_h_small_serve_ep8_d10'
TRAFFIC = 'tokens32_prefill_decode_ssm_ctx16k'
SOURCE = ('https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/'
          'config.json')
PARENT = '5d0b4420692dec985d659ff4947708dc64dc8ebc'
PERIOD = ['mamba'] * 5 + ['attention'] + ['mamba'] * 4
CATALOG = {  # the catalog entry's `config`, every key
    'attention_bias': False, 'attention_multiplier': 0.0078125,
    'embedding_multiplier': 12, 'hidden_act': 'silu', 'hidden_size': 4096,
    'intermediate_size': 768, 'layer_types': PERIOD * 4,
    'logits_scaling': 16, 'mamba_chunk_size': 256, 'mamba_conv_bias': True,
    'mamba_d_conv': 4, 'mamba_d_head': 64, 'mamba_d_state': 128,
    'mamba_expand': 2, 'mamba_n_groups': 1, 'mamba_n_heads': 128,
    'mamba_proj_bias': False, 'max_position_embeddings': 131072,
    'model_type': 'granitemoehybrid', 'normalization_function': 'rmsnorm',
    'num_attention_heads': 32, 'num_experts_per_tok': 10,
    'num_hidden_layers': 40, 'num_key_value_heads': 8,
    'num_local_experts': 72, 'position_embedding_type': 'nope',
    'residual_multiplier': 0.22, 'rms_norm_eps': 1e-05, 'rope_scaling': None,
    'rope_theta': 10000, 'shared_intermediate_size': 1536,
    'tie_word_embeddings': True, 'vocab_size': 100352}
REDUCED = {'num_hidden_layers': (40, 10), 'num_local_experts': (72, 9),
           'vocab_size': (100352, 12544)}
NEW = ['ssm.state_roofline_share', 'ssm.mixer_share', 'ssm_moe.experts_share',
       'ssm_moe.call_hbm_share', 'ssm_moe.call_mfu',
       'ssm_gqa.full_cache_roofline_share', 'ssm_moe.experts_roofline_share']
APPENDED = ['policy_call_p95_ms', 'inference.call_host_ms_p50',
            'inference.device_ms_per_call', 'policy_call_p50_ms',
            'policy_call_p99_ms', 'serve.device_idle_share',
            'serve.lm_head_share']


def _config():
  return loader.build_config(loader.flag_args(
      loader.load_config(MANIFEST, CONFIG), loader.load_traffic(TRAFFIC),
      {'seed': 1, 'logdir': '/nowhere'}))


def _published():
  return types.SimpleNamespace(
      seq_num_layers=10, seq_layer_pattern='MMMMMGMMMM',
      seq_hidden_size=4096, seq_num_heads=32, seq_num_kv_heads=8,
      seq_head_dim=128, seq_ssm_heads=128, seq_ssm_head_dim=64,
      seq_ssm_state=128, seq_conv_width=4, seq_moe_size=768,
      seq_routed_experts=72, seq_experts_held=9, seq_shared_experts=2,
      num_actions=12544, seq_cache_capacity=32768)


def test_the_configuration_is_the_catalogs_with_three_keys_reduced():
  entry = next(c for c in MANIFEST['configs'] if c['name'] == CONFIG)
  file = loader.load_config(MANIFEST, CONFIG)
  assert entry['reduced'] == list(REDUCED) == file['reduced']
  assert entry['source'] == file['source'] == SOURCE
  assert entry['file'] == f'benchmark/configs/{CONFIG}.json'
  for key, value in CATALOG.items():
    if key in REDUCED:
      published, here = REDUCED[key]
      assert value == published == file['published'][key]
      assert file[key] == here
    else:
      assert key in file and file[key] == value, key
  assert sorted(file['published']) == sorted(REDUCED)
  # The flags the program is started with say the same as the keys.
  flags = file['flags']
  said = {
      'seq_num_layers': 'num_hidden_layers',
      'seq_hidden_size': 'hidden_size',
      'seq_num_heads': 'num_attention_heads',
      'seq_num_kv_heads': 'num_key_value_heads',
      'seq_ssm_heads': 'mamba_n_heads', 'seq_ssm_head_dim': 'mamba_d_head',
      'seq_ssm_state': 'mamba_d_state', 'seq_conv_width': 'mamba_d_conv',
      'seq_ssm_block': 'mamba_chunk_size',
      'seq_moe_size': 'intermediate_size',
      'seq_experts_held': 'num_local_experts',
      'seq_experts_per_token': 'num_experts_per_tok',
      'seq_embedding_multiplier': 'embedding_multiplier',
      'seq_residual_multiplier': 'residual_multiplier',
      'seq_attention_multiplier': 'attention_multiplier',
      'seq_logits_scaling': 'logits_scaling',
      'seq_norm_eps': 'rms_norm_eps', 'num_actions': 'vocab_size'}
  for flag, key in said.items():
    assert flags[flag] == file[key], flag
  # Widths the config gives by another name.
  assert flags['seq_ssm_heads'] * flags['seq_ssm_head_dim'] == (
      file['mamba_expand'] * file['hidden_size'])
  assert flags['seq_head_dim'] * flags['seq_num_heads'] == file['hidden_size']
  assert flags['seq_shared_experts'] * flags['seq_moe_size'] == (
      file['shared_intermediate_size'])
  # The router keeps its published width; one group, no scale, softmax.
  assert flags['seq_routed_experts'] == file['published']['num_local_experts']
  assert (flags['seq_router'], flags['seq_routed_scale'],
          flags['seq_expert_groups'], flags['seq_expert_offset']) == (
              'softmax', 1.0, 1, 0)
  # The ten layers are the published list's first ten: a whole period.
  kinds = {'M': 'mamba', 'G': 'attention'}
  assert [kinds[c] for c in flags['seq_layer_pattern']] == (
      file['layer_types'][:10])
  assert (flags['compute_dtype'], flags['param_dtype']) == (
      'bfloat16', 'bfloat16')
  for stated in ('head_dim', 'block', 'mamba', 'ssm_state', 'seed_ssm',
                 'attention', 'router', 'shared_expert', 'init',
                 'value_head', 'precision', 'experts_unread', 'episode'):
    assert len(file['assumed'][stated]) > 40, stated
  assert 'float32' in file['assumed']['ssm_state']
  assert '8 chips share each layer' in file['deployment']
  assert 'pipeline' in file['deployment']
  assert 'granite_ref.py' in file['reference']
  # The file repeats the counts' arithmetic.
  config = _config()
  arithmetic = file['arithmetic']
  s = granite_counts.shapes(config)
  assert arithmetic['parameters'] == granite_counts.parameters(config)
  assert arithmetic['mamba_mixer_parameters'] == (
      granite_counts.mamba_parameters(s))
  assert arithmetic['attention_mixer_parameters'] == (
      granite_counts.attention_parameters(s))
  assert arithmetic['parameters_mamba_layer'] == (
      granite_counts.layer_parameters(s, 'M'))
  assert arithmetic['parameters_attention_layer'] == (
      granite_counts.layer_parameters(s, 'G'))
  assert arithmetic['state_bytes_per_session'] == (
      granite_counts.state_bytes_per_slot(config))
  carried = {'requests': 32, 'ssm_rows_stepped': 288,
             'cache_tokens_read': 32 * 11000, 'experts_hit': 89,
             'routed_rows_held': 320}
  assert arithmetic[
      'least_bytes_a_merged_call_of_32_at_11000_tokens_89_experts_hit'
  ] == granite_counts.call_bytes(config, carried)
  total = (arithmetic['parameter_bytes_bfloat16'] + arithmetic[
      'arena_bytes_32_sessions_and_the_padded_rows_row'])
  assert 9.79e9 < total < 9.81e9 and total > 0.25 * 16.9e9
  # The traffic: 32 sessions, prompts 2,048 + 448 i inside episodes of
  # the full layer's capacity, handed over in chunks of 512.
  assert config.num_actors == 32 and config.inference_state_slots == 32
  assert (config.episode_length, config.seq_cache_capacity) == (32768, 32768)
  assert (config.token_prompt_length, config.token_prompt_stride,
          config.seq_prefill_chunk, config.unroll_length) == (
              2048, 448, 512, 64)
  prompts = [2048 + 448 * i for i in range(32)]
  assert (prompts[-1], sum(prompts) // 32) == (15936, 8992)
  assert sum(-(-(p - 1) // 512) for p in prompts) == 576


def test_the_arena_and_the_parameters_the_program_builds():
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.models import SSMHybridStack
  file = loader.load_config(MANIFEST, CONFIG)
  config = _config()
  agent = driver.build_agent(config, config.num_actions)
  assert isinstance(agent.core(), SSMHybridStack)
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))
  arithmetic = file['arithmetic']
  assert nbytes(jax.eval_shape(lambda: agent.initial_state(1))) == (
      arithmetic['state_bytes_per_session'])
  arena = jax.eval_shape(lambda: agent.state_arena(32))
  # 32 sessions, and one row more for the padded rows of a merged call:
  # nine Mamba layers' conv windows and float32 states beside the full
  # layer's cache, in one row.
  assert nbytes(arena) == 33 * arithmetic['state_bytes_per_session'] == (
      arithmetic['arena_bytes_32_sessions_and_the_padded_rows_row'])
  shapes = [jax.tree_util.tree_map(lambda l: (l.shape, str(l.dtype)), leaf)
            for leaf in arena['layers']]
  mamba = (((33, 3, 8448), 'bfloat16'), ((33, 128, 64, 128), 'float32'))
  assert shapes == [mamba] * 5 + [((33, 2048, 32768), 'bfloat16')] + (
      [mamba] * 4)
  assert (agent.prefill_chunk, agent.cache_capacity, agent.cache_window) == (
      512, 32768, 0)
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == (
      arithmetic['parameters']) == granite_counts.parameters(config) == (
          2_055_035_521)
  assert nbytes(params) == arithmetic['parameter_bytes_bfloat16']


def test_counts_by_hand():
  config = _published()
  s = granite_counts.shapes(config)
  assert (s['mamba'], s['full'], s['channels']) == (9, 1, 8448)
  # Mamba-2: in_proj 4096 x (8192 + 8448 + 128), conv 4 taps and a bias
  # of 8448 channels, dt_bias, A_log and D of 128, the gated norm of
  # 8192, out_proj 8192 x 4096.
  mamba = (4096 * 16768 + 5 * 8448 + 3 * 128 + 8192 + 8192 * 4096)
  assert mamba == 102_286_976 == granite_counts.mamba_parameters(s)
  attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
  assert attention == 41_943_040 == granite_counts.attention_parameters(s)
  expert = 3 * 4096 * 768
  assert expert == 9_437_184 == granite_counts.ffn_parameters(s, 768)
  routed = 4096 * 72 + 2 * expert + 9 * expert
  assert granite_counts.layer_parameters(s, 'M') == (
      mamba + 2 * 4096 + routed) == 206_399_104
  assert granite_counts.layer_parameters(s, 'G') == (
      attention + 2 * 4096 + routed) == 146_055_168
  # The layers, the vocabulary slice and the final norm, 2,055,031,424,
  # and this system's value head.
  assert granite_counts.parameters(config) == (
      9 * 206_399_104 + 146_055_168 + 12544 * 4096 + 4096 + 4097) == (
          2_055_031_424 + 4097)
  # A row's state in one layer: 128 x 64 x 128 float32, 4.19 MB; its
  # conv window 3 x 8448 bfloat16; a token of the cache 4,096 bytes.
  assert granite_counts.state_bytes_per_row_and_layer(config) == 4_194_304
  assert granite_counts.conv_bytes_per_row_and_layer(config) == 50_688
  assert granite_counts.token_bytes(config) == 4096
  assert granite_counts.state_bytes_per_slot(config) == (
      9 * (4_194_304 + 50_688) + 32768 * 4096 + 4)
  # A call of 32 rows, all in 9 Mamba layers, at positions that sum to
  # 300,000 tokens, that hit 85 of the 90 experts held with 310 rows.
  carried = {'requests': 32, 'ssm_rows_stepped': 288,
             'cache_tokens_read': 300_000, 'experts_hit': 85,
             'routed_rows_held': 310}
  assert granite_counts.state_bytes(config, carried) == (
      2 * 288 * 4_194_304) == 2_415_919_104
  assert granite_counts.cache_bytes(config, carried) == 300_000 * 4096
  assert granite_counts.experts_bytes(config, carried) == 85 * 2 * expert
  matrices = (9 * (4096 * 16768 + 8192 * 4096) + attention +
              10 * (4096 * 72 + 2 * expert) + 12544 * 4096 + 4096)
  bf16 = (matrices - 4096 + 10 * 2 * 4096 + 9 * 8192 + 4096 +
          9 * 5 * 8448)
  f32 = 9 * 3 * 128 + 4096 + 1
  assert granite_counts.call_bytes(config, carried) == (
      2 * bf16 + 4 * f32 + 85 * 2 * expert + 2 * 288 * 4_194_304 +
      2 * 288 * 50_688 + 300_000 * 4096)
  assert granite_counts.call_flops(config, carried) == (
      2 * 32 * matrices + 2 * 310 * expert + 300_000 * 32 * 2 * 2 * 128 +
      288 * (5 * 128 * 64 * 128 + 2 * 4 * 8448))
  # As reckoned before the first run, at a mean context of 11 k and 89
  # experts hit: 8.0 GB a call, 9.7 ms at 819 GB/s, the Mamba layers'
  # weights and states more than half of it.
  reckoned = dict(carried, cache_tokens_read=32 * 11000, experts_hit=89)
  total = granite_counts.call_bytes(config, reckoned)
  assert 7.9e9 < total < 8.05e9
  assert 2 * 9 * mamba + granite_counts.state_bytes(config, reckoned) > (
      0.5 * total)


def test_the_references_two_copies_are_one_text_and_import_no_program():
  def read(path):
    with open(os.path.join(REPO, path)) as f:
      return f.read()
  program = read('scalable_agent_tpu/models/ssm_hybrid_reference.py')
  benchmark = read('benchmark/harness/granite_ref.py')
  marker = 'For an episode\'s tokens'
  assert benchmark[benchmark.index(marker):] == program[
      program.index(marker):]
  for text in (program, benchmark):
    imported = {alias.name for node in ast.walk(ast.parse(text))
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for alias in node.names} | {
                    node.module for node in ast.walk(ast.parse(text))
                    if isinstance(node, ast.ImportFrom)}
    assert imported <= {'functools', 'jax', 'jax.numpy'}, imported


def test_the_driver_stands_on_the_check_it_replaces():
  """`serve_ssm_decode.run` puts its check in the place of
  `serve_prefill_decode._check_against_reference` for the length of the
  call, and its verdict is `serve_window_decode._verdict` under its own
  limits: those names, with these arguments, are what it stands on."""
  for module in (serve_prefill_decode, serve_ssm_decode):
    assert list(inspect.signature(
        module._check_against_reference).parameters) == [
            'checks', 'ctx', 'cfg', 'params', 'seams']
  assert list(inspect.signature(
      serve_window_decode._verdict).parameters) == ['compared', 'margin']
  assert serve_ssm_decode.EXCUSED_LIMIT == 0.05
  assert set(serve_ssm_decode.Dims._fields) <= {
      f.name for f in __import__('dataclasses').fields(SSMHybridDims)}


@pytest.fixture(scope='module')
def served_episode():
  """A prompt block of 37 tokens (chunks of 8 in blocks of 4) and 24
  tokens decoded through the inference server's arena at the tiny size
  (three layers `MMG`, the published multipliers), in float32 (the CPU
  has no bfloat16 product), as the cell's driver records them -> (params,
  reference options, episode, recorded, the Mamba layers' states in the
  arena after the last call)."""
  dims = SSMHybridDims(
      moe_size=16, routed_experts=16, experts_held=4, experts_per_token=4,
      expert_groups=1, expert_groups_kept=1, routed_scale=1.0,
      shared_experts=2, router='softmax', layer_pattern='MMG',
      embedding_multiplier=12.0, residual_multiplier=0.22,
      logits_scaling=16.0)
  agent = SequenceAgent(num_actions=97, num_layers=3, hidden_size=32,
                        num_heads=4, norm_eps=1e-5, core_dims=dims)
  obs = {'leaves': (((), np.int32),)}
  params = init_params(agent, jax.random.PRNGKey(2), obs)
  server = InferenceServer(
      agent, params, Config(
          inference_state_cache=True, inference_timeout_ms=20,
          inference_min_batch=1, inference_state_slots=1), seed=11)
  server.warmup(obs, sizes=[1])
  try:
    rng = np.random.RandomState(2)
    block = rng.randint(97, size=37).astype(np.int32)
    handle = server.initial_core_state()
    handle.prefill(block)
    token, rows = rng.randint(97), []
    with jax.default_matmul_precision('highest'):
      for _ in range(24):
        out, _ = server.policy(
            np.zeros(1, np.int32),
            StepOutput(np.zeros(1, np.float32), None, np.zeros(1, bool),
                       (np.array([token], np.int32),)), [handle])
        rows.append((token, 0, int(out.action[0]),
                     float(out.policy_logits[0]), float(out.baseline[0])))
        token = int(out.action[0])
    arena = server._arena_now()
    states = [np.asarray(arena['layers'][i][1][handle.slot]) for i in (0, 1)]
  finally:
    server.close()
  options = dict(dims=dims, num_heads=4, norm_eps=1e-5, block=8)
  return (params, options, (block, 0, 24),
          tuple(np.array(x) for x in zip(*rows)), states)


@pytest.mark.parametrize('control', [None, *serve_ssm_decode.CONTROLS])
def test_a_precision_below_the_configurations_comes_out_not_correct(
    served_episode, control):
  """The cell's own comparison and verdict (`_compare`, `_verdict`, the
  state's row) on an episode the server played. Here the served model is
  float32 and equals the reference to 1e-5, so the verdict is taken at
  the limits a float32 model is held to in tests/test_ssm_serving.py
  (2e-4): against the reference as the configuration states it every
  row holds, at those limits and at the cell's own; against the
  reference with the SSM state in bfloat16 `correct` comes out false,
  by the limits and not by the cap, and the state's difference, in the
  first Mamba layer as in every one, is over a hundred times the served
  one. (On the chip the served model's own
  bfloat16 products are the noise the cell's limits sit above: PERF.md
  section 6.)"""
  from unittest import mock
  params, options, episode, recorded, held = served_episode
  lower = serve_ssm_decode.CONTROLS.get(control, {})
  reference = serve_ssm_decode._Reference(params, dict(options, **lower))
  with jax.default_matmul_precision('highest'):
    compared = serve_prefill_decode._compare(
        reference, episode, recorded, 8, False)
  assert len(compared) == 16
  state = serve_ssm_decode.state_differences(held, reference.states)
  assert len(state) == 2
  margin = serve_prefill_decode.ROUTING_MARGIN['float32']
  verdicts = [serve_ssm_decode._verdict(compared, margin)[0] +
              serve_ssm_decode._state_rows([state])]
  with mock.patch.multiple(serve_ssm_decode, LOG_MU_TOLERANCE=2e-4,
                           BASELINE_TOLERANCE=2e-4, STATE_TOLERANCE=2e-4,
                           STATE_GUARD=2e-4):
    rows, excused, kept = serve_ssm_decode._verdict(compared, margin)
    verdicts.append(rows + serve_ssm_decode._state_rows([state]))
  assert len(rows) == 3 and not excused and len(kept) == 16
  checks = correct.Checks()
  for row in verdicts[-1]:
    checks.record(*row)
  if control is None:
    assert checks.ok and all(ok for _, ok, _ in verdicts[0])
    assert max(max(x[1], x[2]) for x in compared) < 1e-5
    assert max(state) < 1e-5
  else:
    assert not checks.ok, checks.rows
    assert max(x[2] for x in compared) > 10 * 2e-4
    assert min(state) > 1e-3, state


def _brought_by(cell):
  """(index, entry) of the per-layer metrics a cell's PR brought: those
  whose `workloads` BEGIN with the cell."""
  return [(i, m) for i, m in enumerate(MANIFEST['per_layer'])
          if m.get('workloads', [None])[0] == cell]


def test_new_entries_keep_to_the_contract():
  cell = loader.find_cell(MANIFEST, CELL)
  assert cell['chips'] == 1 and len(cell['why']) <= 200
  assert (cell['config'], cell['traffic']) == (CONFIG, TRAFFIC)
  # Appended: after the cell and the configuration before them.
  cells = [c['name'] for c in MANIFEST['workloads']]
  configs = [c['name'] for c in MANIFEST['configs']]
  assert cells.index(CELL) == cells.index('kexaone.decode32_ctx24k') + 1
  assert configs.index(CONFIG) == (
      configs.index('k_exaone_236b_serve_ep8_d5') + 1)
  assert sum(c['chips'] == 4 for c in MANIFEST['workloads']) == 1
  assert len(MANIFEST['configs'][configs.index(CONFIG)]['why']) <= 200
  e2e = [m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')]
  assert e2e == ['policy_call_p95_ms', 'setup_s']
  new = _brought_by(CELL)
  assert [m['name'] for _, m in new] == NEW
  # In order, one after the other, after the cell before's.
  first = new[0][0]
  assert [i for i, _ in new] == list(range(first, first + len(NEW)))
  assert first > _brought_by('kexaone.decode32_ctx24k')[-1][0]
  layers = {m['layer'] for m in MANIFEST['per_layer'][:first]}
  for _, m in new:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert (m['unit'], m['moves'], m['source'], m['workloads']) == (
        '%', 'policy_call_p95_ms', 'device_trace', [CELL])
    assert m['layer'] in layers
    spec = loader.load_metric(m['name'])
    assert set(spec) == {'reader', 'args', 'what'}
    assert spec['args']['module_regex'] == r'^jit_cache_step\b'
    if 'counts' in spec['args']:
      assert spec['args']['counts'] == 'granite_counts'
  assert loader.load_metric('ssm.state_roofline_share')['args'] == {
      'module_regex': r'^jit_cache_step\b',
      'scope_regex': r'(^|[/(])mamba/state_update([/)]|$)',
      'counts': 'granite_counts', 'bytes_quantity': 'state_bytes'}
  for m in MANIFEST['per_layer'] + MANIFEST['end_to_end']:
    if m['name'] in APPENDED:
      # Appended after the cell before, at the end of the list.
      assert m['workloads'][-2:] == ['kexaone.decode32_ctx24k', CELL], (
          m['name'])
    elif m['name'] not in NEW:
      assert CELL not in m.get('workloads', []), m['name']
  reports = {m['name'] for m in
             loader.cell_metrics(MANIFEST, CELL, 'per_layer')}
  # The two entry metrics list no cells: they read every run's compile
  # ledger, this cell's too, and are reported in it.
  assert reports == set(APPENDED) - {'policy_call_p95_ms'} | set(NEW) | {
      'entry.compile_s', 'entry.cache_misses'}
  traffic = loader.load_traffic(TRAFFIC)
  assert 'env_processes' not in traffic
  assert traffic['driver'] == 'serve_ssm_decode'
  assert (traffic['warm_calls'], traffic['trace_seconds']) == (64, 15)
  assert (traffic['check_sessions'], traffic['check_steps']) == (32, 384)


def test_the_one_pass_share_keeps_its_place_after_the_inline_share():
  """What test_one_pass_metric.py :: test_the_entry_is_appended_after_
  the_inline_share holds, read from the entry's own place and not from
  the end of the list (tests/conftest.py): the one-pass share right
  after the inline-call share, and this cell's metrics after both."""
  names = [m['name'] for m in MANIFEST['per_layer']]
  at = names.index('actors.one_pass_step_share')
  assert names[at - 1] == 'inference.inline_call_share'
  assert names[at + 1:] == NEW


def test_readers_find_nothing_where_there_is_nothing():
  counted = {'open': {'server': {'calls': 0, 'requests': 0,
                                 'cache_tokens_read': 0,
                                 'ssm_rows_stepped': 0}},
             'close': {'server': {'calls': 2, 'requests': 64,
                                  'cache_tokens_read': 1000,
                                  'ssm_rows_stepped': 576}}}
  for name in NEW:
    spec = loader.load_metric(name)
    reader = loader.load_reader(spec['reader'])
    assert reader.read({}, **spec['args']) is None, name
    assert reader.read({'trace': None, 'counters': counted},
                       **spec['args']) is None, name


def test_the_state_roofline_share_from_a_trace_by_hand():
  """One chip, two executions of the program of 10 ms each; under
  `mamba/state_update` 3 ms an execution, under the rest of `mamba` 2,
  under `moe/experts` 4, under `gqa/attend_full` 1: 2 x 288 states of
  4.19 MB in 3 ms is 98% of the v5e's 819 GB/s; the mixer's share of the
  program's operations 5 of their 10 ms, the experts' 4; 80 experts of
  18.9 MB in 4 ms (46%) and 160,000 tokens of 4 KB in 1 ms (80%) by the
  same peak."""
  from benchmark.harness import trace_reduce, trace_scopes
  from benchmark.readers import module_scope_share, trace_scope_roofline
  plane, ms = '/device:TPU:0', 1_000_000
  rows = []
  for start in (0, 20 * ms):
    rows += [
        (plane, trace_reduce.MODULES_LINE, 'jit_cache_step(1)', start,
         10 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_0/mamba/mamba/state_update/'
         'ssm_state_update', start + ms, 3 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_1/mamba/mamba/in_proj/dot',
         start + 4 * ms, 2 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_1/moe/moe/experts/cond',
         start + 6 * ms, 4 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_5/gqa/attend_full/gqa_decode_attend',
         start, ms)]
  trace = trace_reduce.Trace.from_rows(rows)
  config = _published()
  obs = {
      'trace': trace, 'config': config,
      'device': {'kind': 'TPU v5 lite'},
      'peaks_path': os.path.join(REPO, 'benchmark', 'harness',
                                 'peaks.json'),
      'counters': {
          'open': {'server': {'calls': 0, 'ssm_rows_stepped': 0,
                              'experts_hit': 0, 'cache_tokens_read': 0}},
          'close': {'server': {'calls': 4, 'ssm_rows_stepped': 4 * 288,
                               'experts_hit': 4 * 80,
                               'cache_tokens_read': 4 * 160_000}}}}
  want = 100 * 2 * 288 * 4_194_304 / 3e-3 / 819e9
  assert trace_scope_roofline.read(
      obs, **loader.load_metric('ssm.state_roofline_share')['args']) == (
          pytest.approx(want))
  assert module_scope_share.read(
      obs, **loader.load_metric('ssm.mixer_share')['args']) == (
          pytest.approx(100 * 5 / 10))
  assert module_scope_share.read(
      obs, **loader.load_metric('ssm_moe.experts_share')['args']) == (
          pytest.approx(100 * 4 / 10))
  assert trace_scope_roofline.read(
      obs, **loader.load_metric('ssm_moe.experts_roofline_share')['args']
  ) == pytest.approx(100 * 80 * 3 * 4096 * 768 * 2 / 4e-3 / 819e9)
  assert trace_scope_roofline.read(
      obs, **loader.load_metric('ssm_gqa.full_cache_roofline_share')['args']
  ) == pytest.approx(100 * 160_000 * 2 * 2 * 8 * 128 / 1e-3 / 819e9)


@pytest.fixture(scope='module')
def traced_rehearsal():
  return _run(REPO, '--workload', CELL, '--seed', '2147489999',
              '--seconds', '2', '--trace', '1', '--rehearse')


def test_traced_rehearsal_ends_in_the_contract_line(traced_rehearsal):
  assert not traced_rehearsal.left
  result = _result(traced_rehearsal)
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and 'breakdown' not in result
  # Counts and host clocks are there; nothing read from a device trace.
  assert set(result['metrics']) == {
      'rehearsal.entry.compile_s', 'rehearsal.entry.cache_misses',
      'rehearsal.inference.call_host_ms_p50', 'rehearsal.policy_call_p50_ms',
      'rehearsal.policy_call_p99_ms'}
  out = traced_rehearsal.stdout
  assert 'prompt tokens handed over; the window opens' in out
  assert 'every merged call carried the whole fleet\'s rows' in out
  assert 'the Mamba layers: ' in out and 'rows stepped in' in out
  assert 'chunks of the chunked form' in out
  assert 'agrees with the reference\'s full forward of the episode' in out
  assert 'no compilation inside the window' in out


def test_the_parent_cannot_run_the_cell_and_says_so_at_once(tmp_path):
  """A program without the core of Mamba-2 layers ends at its flags,
  with an error, soon, and leaves nothing behind: a comparison of the
  two sides can then measure the cell on the change alone."""
  import shutil
  import subprocess
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(REPO, 'benchmark'), tmp_path / 'benchmark')
  parent = subprocess.run(
      ['git', 'show', f'{PARENT}:experiment.py'], cwd=REPO,
      capture_output=True, text=True)
  if parent.returncode != 0:
    pytest.skip('the parent commit is not in this checkout')
  (tmp_path / 'experiment.py').write_text(parent.stdout)
  done = _run(str(tmp_path), '--workload', CELL, '--seed', '1',
              '--seconds', '1', '--trace', '0', '--rehearse', timeout=300)
  assert done.returncode not in (0, 2) and not done.left
  assert '"correct"' not in done.stdout
  assert "Unknown command line flag 'seq_" in done.stderr

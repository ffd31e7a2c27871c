"""The cell of window and full attention layers from the CPU side (PR
35): its entries and files against the contract, the counts by hand,
the reference's two copies, its readers on an empty trace, its traced
rehearsal to the contract's line, and what stays true of the two served
cells before it, each held BY POSITION FROM ITS OWN ENTRY and not from
the end of a list, so that the next cell breaks nothing here."""

import inspect
import os
import types

import numpy as np
import pytest

import jax

from benchmark.drivers import serve_prefill_decode, serve_window_decode
from benchmark.harness import correct, dots_counts, exaone_counts, exaone_ref
from benchmark.harness import loader
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.models import (HybridAttentionDims, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.runtime.inference import InferenceServer
from scalable_agent_tpu.structs import StepOutput
from test_benchmark_cells import _result, _run

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
CELL = 'kexaone.decode32_ctx24k'
CONFIG = 'k_exaone_236b_serve_ep8_d5'
TRAFFIC = 'tokens32_prefill_decode_ctx24k'
SOURCE = ('https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/'
          'config.json')
PARENT = 'a99c2fa2785b93aa227e3a5efaf4d9d59bd6021c'
PERIOD = ['sliding_attention'] * 3 + ['full_attention']
CATALOG = {  # the catalog entry's `config`, every key
    'first_k_dense_replace': 1, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 6144, 'intermediate_size': 18432,
    'layer_types': PERIOD * 12, 'max_position_embeddings': 262144,
    'mlp_layer_types': ['dense'] + ['sparse'] * 47,
    'model_type': 'exaone_moe', 'moe_intermediate_size': 2048,
    'mtp_layer_types': ['full_attention'], 'mtp_sliding_windows': [0],
    'n_group': 1, 'norm_topk_prob': True, 'num_attention_heads': 64,
    'num_experts': 128, 'num_experts_per_tok': 8, 'num_hidden_layers': 48,
    'num_key_value_heads': 8, 'num_nextn_predict_layers': 1,
    'num_shared_experts': 1, 'rms_norm_eps': 1e-05,
    'rope_parameters': {'rope_theta': 1000000, 'rope_type': 'default'},
    'routed_scaling_factor': 2.5, 'scoring_func': 'sigmoid',
    'sliding_window': 128, 'sliding_window_pattern': 'LLLG',
    'sliding_windows': [128, 128, 128, 0] * 12,
    'tie_word_embeddings': False, 'topk_group': 1, 'vocab_size': 153600}
REDUCED = {'num_hidden_layers': (48, 5), 'num_experts': (128, 16),
           'vocab_size': (153600, 19200)}
NEW = ['hybrid_moe.call_hbm_share', 'hybrid_moe.call_mfu',
       'hybrid_moe.attention_share', 'gqa.full_cache_roofline_share',
       'gqa.window_share']


def _config():
  return loader.build_config(loader.flag_args(
      loader.load_config(MANIFEST, CONFIG), loader.load_traffic(TRAFFIC),
      {'seed': 1, 'logdir': '/nowhere'}))


def _published():
  return types.SimpleNamespace(
      seq_num_layers=5, seq_layer_pattern='LLLG', seq_window=128,
      seq_first_dense_layers=1, seq_hidden_size=6144, seq_num_heads=64,
      seq_num_kv_heads=8, seq_head_dim=128, seq_mlp_size=18432,
      seq_moe_size=2048, seq_routed_experts=128, seq_experts_held=16,
      seq_shared_experts=1, num_actions=19200, seq_cache_capacity=32768)


def _brought_by(cell):
  """(index, entry) of the per-layer metrics a cell's PR brought: those
  whose `workloads` BEGIN with the cell."""
  return [(i, m) for i, m in enumerate(MANIFEST['per_layer'])
          if m.get('workloads', [None])[0] == cell]


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
      'seq_first_dense_layers': 'first_k_dense_replace',
      'seq_hidden_size': 'hidden_size',
      'seq_num_heads': 'num_attention_heads',
      'seq_num_kv_heads': 'num_key_value_heads', 'seq_head_dim': 'head_dim',
      'seq_mlp_size': 'intermediate_size',
      'seq_moe_size': 'moe_intermediate_size',
      'seq_layer_pattern': 'sliding_window_pattern',
      'seq_window': 'sliding_window', 'seq_experts_held': 'num_experts',
      'seq_experts_per_token': 'num_experts_per_tok',
      'seq_expert_groups': 'n_group', 'seq_expert_groups_kept': 'topk_group',
      'seq_routed_scale': 'routed_scaling_factor',
      'seq_shared_experts': 'num_shared_experts',
      'seq_norm_eps': 'rms_norm_eps', 'num_actions': 'vocab_size'}
  for flag, key in said.items():
    assert flags[flag] == file[key], flag
  assert flags['seq_rope_theta'] == file['rope_parameters']['rope_theta']
  # The router keeps its published width; the five layers are the
  # published lists' first five: one dense, then a whole period.
  assert flags['seq_routed_experts'] == file['published']['num_experts']
  kinds = {'L': 'sliding_attention', 'G': 'full_attention'}
  assert [kinds[flags['seq_layer_pattern'][i % 4]] for i in range(5)] == (
      file['layer_types'][:5])
  assert file['mlp_layer_types'][:5] == ['dense'] + ['sparse'] * 4
  assert (flags['compute_dtype'], flags['param_dtype']) == (
      'bfloat16', 'bfloat16')
  for stated in ('block_norms', 'qk_norm', 'rotary', 'router', 'score_bias',
                 'window', 'left_out', 'cache', 'experts_unread',
                 'value_head', 'precision', 'init', 'episode'):
    assert len(file['assumed'][stated]) > 40, stated
  assert 'multi-token-prediction' in file['assumed']['left_out']
  assert '8 chips share each layer' in file['deployment']
  assert 'pipeline' in file['deployment']
  assert 'exaone_ref.py' in file['reference']
  # The file repeats the counts' arithmetic.
  config = _config()
  arithmetic = file['arithmetic']
  shapes = exaone_counts.shapes(config)
  assert arithmetic['parameters'] == exaone_counts.parameters(config)
  assert arithmetic['attention_parameters_per_layer'] == (
      exaone_counts.attention_parameters(shapes))
  assert arithmetic['parameters_dense_layer'] == (
      exaone_counts.layer_parameters(shapes, False))
  assert arithmetic['parameters_expert_layer'] == (
      exaone_counts.layer_parameters(shapes, True))
  assert arithmetic['parameter_bytes_bfloat16'] == 2 * arithmetic[
      'parameters']
  assert arithmetic['cache_bytes_per_token_and_layer'] == (
      exaone_counts.token_bytes(config))
  assert arithmetic['state_bytes_per_session'] == (
      exaone_counts.state_bytes_per_slot(config))
  carried = {'requests': 32, 'cache_tokens_read': 32 * 16500,
             'window_tokens_read': 32 * 128, 'experts_hit': 56,
             'routed_rows_held': 128}
  assert arithmetic[
      'least_bytes_a_merged_call_of_32_at_16500_tokens_56_experts_hit'
  ] == exaone_counts.call_bytes(config, carried)
  total = (arithmetic['parameter_bytes_bfloat16'] + arithmetic[
      'arena_bytes_32_sessions_and_the_padded_rows_row'])
  assert 11.9e9 < total < 11.95e9 and total > 0.7 * 16.9e9
  # The traffic: 32 sessions, prompts 4,096 + 640 i inside episodes of
  # a full layer's capacity, handed over in chunks of 512.
  assert config.num_actors == 32 and config.inference_state_slots == 32
  assert (config.episode_length, config.seq_cache_capacity) == (32768, 32768)
  assert (config.token_prompt_length, config.token_prompt_stride,
          config.seq_prefill_chunk, config.unroll_length) == (
              4096, 640, 512, 64)
  prompts = [4096 + 640 * i for i in range(32)]
  assert (prompts[-1], sum(prompts) // 32) == (23936, 14016)
  assert sum(-(-(p - 1) // 512) for p in prompts) == 888


def test_the_arena_the_program_builds_is_the_size_the_file_states():
  import jax
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.models import HybridAttentionStack, init_params
  file = loader.load_config(MANIFEST, CONFIG)
  config = _config()
  agent = driver.build_agent(config, config.num_actions)
  assert isinstance(agent.core(), HybridAttentionStack)
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))
  arithmetic = file['arithmetic']
  assert nbytes(jax.eval_shape(lambda: agent.initial_state(1))) == (
      arithmetic['state_bytes_per_session'])
  arena = jax.eval_shape(lambda: agent.state_arena(32))
  # 32 sessions, and one row more for the padded rows of a merged call.
  assert nbytes(arena) == 33 * arithmetic['state_bytes_per_session'] == (
      arithmetic['arena_bytes_32_sessions_and_the_padded_rows_row'])
  assert [l.shape for l in arena['layers']] == [
      (33, 2048, 128)] * 3 + [(33, 2048, 32768), (33, 2048, 128)]
  assert (agent.prefill_chunk, agent.cache_capacity, agent.cache_window) == (
      512, 32768, 128)
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == (
      arithmetic['parameters']) == 3_712_034_561


def test_counts_by_hand():
  config = _published()
  s = exaone_counts.shapes(config)
  assert (s['full'], s['windowed']) == (1, 4)
  # Attention: q 6144 x 8192, k and v 6144 x 1024, output 8192 x 6144,
  # the norms of a head's query and key.
  attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128
  assert attention == 113_246_464 == exaone_counts.attention_parameters(s)
  expert = 3 * 6144 * 2048
  assert expert == 37_748_736 == exaone_counts.ffn_parameters(s, 2048)
  # An expert layer: attention, two norms, router and its bias, the
  # shared expert, 16 routed experts held.
  routed_layer = attention + 2 * 6144 + 6144 * 128 + 128 + 17 * expert
  assert routed_layer == 755_773_824 == (
      exaone_counts.layer_parameters(s, True))
  dense_layer = attention + 2 * 6144 + 3 * 6144 * 18432
  assert dense_layer == 452_997_376 == (
      exaone_counts.layer_parameters(s, False))
  assert exaone_counts.parameters(config) == (
      dense_layer + 4 * routed_layer + 2 * 19200 * 6144 + 6144 + 6145
  ) == 3_712_034_561
  # A token in a layer: 8 keys and 8 values of 128 in bfloat16. A slot:
  # one cache of 32,768 tokens, four rings of 128, an int32 position.
  assert exaone_counts.token_bytes(config) == 2 * 2 * 8 * 128 == 4096
  assert exaone_counts.state_bytes_per_slot(config) == (
      4096 * (32768 + 4 * 128) + 4) == 136_314_884
  # A call of 32 rows at positions that sum to 500,000 tokens, every
  # ring full, that hit 41 of the 64 experts held with 100 routed rows.
  carried = {'requests': 32, 'cache_tokens_read': 500_000,
             'window_tokens_read': 32 * 128, 'experts_hit': 41,
             'routed_rows_held': 100}
  assert exaone_counts.full_cache_bytes(config, carried) == 500_000 * 4096
  assert exaone_counts.window_cache_bytes(config, carried) == (
      32 * 128 * 4 * 4096)
  # `moe.experts_roofline_share` takes the same from `dots_counts` in
  # this cell too, which reads only the widths both cores share.
  assert exaone_counts.experts_bytes(config, carried) == 41 * 2 * expert == (
      dots_counts.experts_bytes(_config(), carried))
  # Attention per token read, layer and query head: the score and the
  # weighted sum over 128 numbers, 2 a multiply-add.
  reads = 500_000 + 4 * 32 * 128
  assert exaone_counts.attend_flops(config, carried) == (
      reads * 64 * 2 * 2 * 128)
  matrices = attention - 256
  whole_bf16 = (5 * (attention + 2 * 6144) + 3 * 6144 * 18432 +
                4 * (6144 * 128 + expert) + 19200 * 6144 + 6144)
  whole_f32 = 4 * 128 + 6144 + 1
  assert exaone_counts.call_bytes(config, carried) == (
      2 * (whole_bf16 + 32 * 6144) + 4 * whole_f32 + 41 * 2 * expert +
      reads * 4096)
  assert exaone_counts.call_flops(config, carried) == (
      2 * 32 * (5 * matrices + 3 * 6144 * 18432 +
                4 * (6144 * 128 + expert) + 19200 * 6144 + 6144) +
      2 * 100 * expert + reads * 64 * 2 * 2 * 128)
  # As reckoned before the first run, at a mean context of 16.5 k and
  # 56 experts hit: 8.8 GB a call, 10.8 ms at 819 GB/s.
  reckoned = dict(carried, cache_tokens_read=32 * 16500, experts_hit=56)
  assert 8.7e9 < exaone_counts.call_bytes(config, reckoned) < 8.9e9
  # Fewer experts hit move fewer bytes; the rows' FLOPs do not change.
  fewer = dict(carried, experts_hit=40)
  assert (exaone_counts.call_bytes(config, carried) -
          exaone_counts.call_bytes(config, fewer)) == 2 * expert
  assert exaone_counts.call_flops(config, fewer) == (
      exaone_counts.call_flops(config, carried))


def test_the_references_two_copies_are_one_text():
  def body(path):
    with open(os.path.join(REPO, path)) as f:
      text = f.read()
    return text[text.index('For an episode\'s tokens'):]
  assert body('benchmark/harness/exaone_ref.py') == body(
      'scalable_agent_tpu/models/hybrid_attention_reference.py')


def test_the_driver_stands_on_the_check_it_replaces():
  """`serve_window_decode.run` puts its check in the place of
  `serve_prefill_decode._check_against_reference` for the length of the
  call (PERF.md section 7k): that name, with these arguments, is what
  it stands on, and so are the seams it imports."""
  for module in (serve_prefill_decode, serve_window_decode):
    assert list(inspect.signature(
        module._check_against_reference).parameters) == [
            'checks', 'ctx', 'cfg', 'params', 'seams']
  assert '_check_against_reference(checks, ctx, cfg, params, seams)' in (
      inspect.getsource(serve_prefill_decode.run))
  assert list(inspect.signature(
      serve_prefill_decode._compare).parameters) == [
          'reference', 'episode', 'recorded', 'half', 'verbose']
  assert serve_window_decode.EXCUSED_LIMIT == 0.05
  assert set(serve_prefill_decode.ROUTING_MARGIN) == {'bfloat16', 'float32'}


@pytest.fixture(scope='module')
def served_episode():
  """A prompt block of 37 tokens (chunks of 8, rings of 4) and 24 tokens
  decoded through the inference server's arena at the tiny size, in
  float32 (the CPU has no bfloat16 product), as the driver records
  them -> (params, reference options, episode, recorded)."""
  dims = HybridAttentionDims(window=4, cache_capacity=64, prefill_chunk=8)
  agent = SequenceAgent(num_actions=97, num_layers=5, hidden_size=32,
                        num_heads=4, mlp_size=48, rope_theta=1e4,
                        norm_eps=1e-5, core_dims=dims)
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
  finally:
    server.close()
  options = dict(dims=dims, num_heads=4, rope_theta=1e4, norm_eps=1e-5,
                 block=8)
  return (params, options, (block, 0, 24),
          tuple(np.array(x) for x in zip(*rows)))


@pytest.mark.parametrize('control', [None, *serve_window_decode.CONTROLS])
def test_a_precision_below_the_configurations_comes_out_not_correct(
    served_episode, control):
  """The cell's own comparison and verdict (`_compare`, `_verdict`, the
  limits as the cell has them) on an episode the server played: against
  the reference as the configuration states it every row holds; against
  the reference with both caches in float8, or with the router's
  operands in bfloat16, `correct` comes out false. (Here the served
  model is float32 and equals the reference to 1e-3. At the published
  widths on the chip its own bfloat16 products move as many routings as
  a bfloat16 router does, and only the float8 caches fail: PERF.md
  section 6, PR 35.)"""
  params, options, episode, recorded = served_episode
  lower = serve_window_decode.CONTROLS.get(control, {})
  with jax.default_matmul_precision('highest'):
    compared = serve_prefill_decode._compare(
        lambda tokens, actions: exaone_ref.forward(
            params, tokens, actions, **options, **lower),
        episode, recorded, 8, False)
  assert len(compared) == 16
  rows, excused, kept = serve_window_decode._verdict(
      compared, serve_prefill_decode.ROUTING_MARGIN['float32'])
  checks = correct.Checks()
  for row in rows:
    checks.record(*row)
  assert len(checks.rows) == 3 and not excused and len(kept) == 16
  if control is None:
    assert checks.ok, checks.rows
    assert max(max(x[1], x[2]) for x in compared) < 1e-3
  else:
    assert not checks.ok, checks.rows
    # By the limits and not by the cap: nothing is excused here.
    assert max(max(x[1] / 0.04, x[2] / 0.03) for x in compared) > 2


def test_new_entries_keep_to_the_contract():
  cell = loader.find_cell(MANIFEST, CELL)
  assert cell['chips'] == 1 and len(cell['why']) <= 200
  assert (cell['config'], cell['traffic']) == (CONFIG, TRAFFIC)
  # Appended: after the cell and the configuration before them.
  cells = [c['name'] for c in MANIFEST['workloads']]
  configs = [c['name'] for c in MANIFEST['configs']]
  assert cells.index(CELL) == cells.index('dotsvlm1.decode32_ctx8k') + 1
  assert configs.index(CONFIG) == (
      configs.index('dots_vlm1_serve_ep16_d5') + 1)
  assert len(cells) >= 7 and sum(
      c['chips'] == 4 for c in MANIFEST['workloads']) == 1
  assert len(MANIFEST['configs'][configs.index(CONFIG)]['why']) <= 200
  e2e = [m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')]
  assert e2e == ['policy_call_p95_ms', 'setup_s']
  new = _brought_by(CELL)
  assert [m['name'] for _, m in new] == NEW
  # In order, one after the other, after the cell before's.
  first = new[0][0]
  assert [i for i, _ in new] == list(range(first, first + len(NEW)))
  assert first == _brought_by('dotsvlm1.decode32_ctx8k')[-1][0] + 1
  layers = {m['layer'] for m in MANIFEST['per_layer'][:first]}
  for _, m in new:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert (m['unit'], m['moves'], m['source']) == (
        '%', 'policy_call_p95_ms', 'device_trace')
    assert m['layer'] in layers
    spec = loader.load_metric(m['name'])
    assert set(spec) == {'reader', 'args', 'what'}
    assert spec['args']['module_regex'] == r'^jit_cache_step\b'
    if 'counts' in spec['args']:
      assert spec['args']['counts'] == 'exaone_counts'
  # Every roofline share reads its work from the counts module by
  # scope, or of the whole program.
  assert loader.load_metric('gqa.full_cache_roofline_share')['args'][
      'scope_regex'] == r'(^|[/(])gqa/attend_full([/)]|$)'
  appended = {
      'policy_call_p95_ms', 'inference.call_host_ms_p50',
      'inference.device_ms_per_call', 'policy_call_p50_ms',
      'policy_call_p99_ms', 'serve.device_idle_share', 'serve.lm_head_share',
      'latent_moe.experts_share', 'latent_moe.router_share',
      'moe.experts_roofline_share', 'serve.mlp_share'}
  for m in MANIFEST['per_layer'] + MANIFEST['end_to_end']:
    if m['name'] in appended:
      # The dense layer 0 runs under the scope `mlp`, which the latent
      # cell's list leaves out.
      before = ('brumby14b.decode32' if m['name'] == 'serve.mlp_share'
                else 'dotsvlm1.decode32_ctx8k')
      at = m['workloads'].index(CELL)
      assert m['workloads'][at - 1] == before, m['name']
    elif m['name'] not in NEW:
      assert CELL not in m.get('workloads', []), m['name']
  reports = {m['name'] for m in
             loader.cell_metrics(MANIFEST, CELL, 'per_layer')}
  assert reports == appended - {'policy_call_p95_ms'} | set(NEW) | {
      'entry.compile_s', 'entry.cache_misses'}
  traffic = loader.load_traffic(TRAFFIC)
  # Not held to the training fleet's layer metrics (serve_loop.py).
  assert 'env_processes' not in traffic
  assert traffic['driver'] == 'serve_window_decode'
  assert (traffic['warm_calls'], traffic['trace_seconds']) == (64, 15)
  assert (traffic['check_sessions'], traffic['check_steps']) == (32, 384)


def test_the_cells_before_keep_their_entries():
  """What test_dots_cell.py :: test_new_entries_keep_to_the_contract and
  :: test_the_cell_before_keeps_its_entries hold of the entries of PR
  32 and PR 27, with "last" read as "where the cell's own entries lie"
  (tests/conftest.py): nothing of them changed but the cells appended
  to `workloads` lists."""
  cells = [c['name'] for c in MANIFEST['workloads']]
  configs = [c['name'] for c in MANIFEST['configs']]
  served = [
      ('brumby14b.decode32', 'brumby_14b_serve_d4', 'tokens32_decode',
       ['serve.call_hbm_share', 'serve.call_mfu', 'serve.device_idle_share',
        'retention.state_roofline_share', 'serve.state_share',
        'serve.mlp_share', 'serve.lm_head_share'],
       {'serve.device_idle_share', 'serve.mlp_share',
        'serve.lm_head_share'}),
      ('dotsvlm1.decode32_ctx8k', 'dots_vlm1_serve_ep16_d5',
       'tokens32_prefill_decode',
       ['latent_moe.call_hbm_share', 'latent_moe.call_mfu',
        'latent_moe.attention_share', 'latent_moe.experts_share',
        'latent_moe.router_share', 'mla.cache_roofline_share',
        'moe.experts_roofline_share'],
       {'latent_moe.experts_share', 'latent_moe.router_share',
        'moe.experts_roofline_share'})]
  for before, (cell, config, traffic, names, shared) in enumerate(served):
    entry = loader.find_cell(MANIFEST, cell)
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        config, traffic, 1)
    assert cells.index(cell) == 4 + before
    assert configs.index(config) == 2 + before
    assert [m['name'] for m in
            loader.cell_metrics(MANIFEST, cell, 'end_to_end')] == [
                'policy_call_p95_ms', 'setup_s']
    its = _brought_by(cell)
    assert [m['name'] for _, m in its] == names
    assert [i for i, _ in its] == list(range(its[0][0],
                                             its[0][0] + len(names)))
    for _, m in its:
      assert (m['unit'], m['moves'], m['source']) == (
          '%', 'policy_call_p95_ms', 'device_trace')
      later = m['workloads'][1:]
      assert (m['name'] in shared) == bool(later), m['name']
      assert all(cells.index(c) > cells.index(cell) for c in later)
      spec = loader.load_metric(m['name'])
      assert set(spec) == {'reader', 'args', 'what'}
  # The metrics every served cell reports list the cells in the order
  # they came.
  for m in MANIFEST['per_layer'] + MANIFEST['end_to_end']:
    if m['name'] in ('policy_call_p95_ms', 'inference.call_host_ms_p50',
                     'inference.device_ms_per_call', 'policy_call_p50_ms',
                     'policy_call_p99_ms'):
      assert m['workloads'][:3] == [
          'deep_dmlab.fleet32', 'brumby14b.decode32',
          'dotsvlm1.decode32_ctx8k'], m['name']


def test_readers_find_nothing_where_there_is_nothing():
  counted = {'open': {'server': {'calls': 0, 'requests': 0,
                                 'cache_tokens_read': 0,
                                 'window_tokens_read': 0}},
             'close': {'server': {'calls': 2, 'requests': 64,
                                  'cache_tokens_read': 1000,
                                  'window_tokens_read': 256}}}
  for name in NEW:
    spec = loader.load_metric(name)
    reader = loader.load_reader(spec['reader'])
    assert reader.read({}, **spec['args']) is None, name
    assert reader.read({'trace': None, 'counters': counted},
                       **spec['args']) is None, name
  from benchmark.readers import trace_counted_share
  assert trace_counted_share.per_call({'counters': counted}) == {
      'calls': 1.0, 'requests': 32.0, 'cache_tokens_read': 500.0,
      'window_tokens_read': 128.0}


def test_the_full_layers_roofline_share_from_a_trace_by_hand():
  """One chip, two executions of the program of 10 ms each; under
  `gqa/attend_full` 2 ms an execution, under `gqa/attend_window` 0.5:
  819 MB of keys and values in 2 ms is half the v5e's 819 GB/s, and the
  rings' share of the program 5%."""
  from benchmark.harness import trace_reduce, trace_scopes
  from benchmark.readers import module_scope_share, trace_scope_roofline
  plane, ms = '/device:TPU:0', 1_000_000
  rows = []
  for start in (0, 20 * ms):
    rows += [
        (plane, trace_reduce.MODULES_LINE, 'jit_cache_step(1)', start,
         10 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_3/gqa/attend_full/gqa_decode_attend',
         start + ms, 2 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_1/gqa/attend_window/gqa_decode_attend',
         start + 4 * ms, ms // 2),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_0/gqa/proj/dot', start + 5 * ms,
         5 * ms // 2),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_1/moe/experts/cond', start + 8 * ms,
         2 * ms)]
  trace = trace_reduce.Trace.from_rows(rows)
  config = _published()
  tokens = 819e6 / exaone_counts.token_bytes(config)
  obs = {
      'trace': trace, 'config': config,
      'device': {'kind': 'TPU v5 lite'},
      'peaks_path': os.path.join(REPO, 'benchmark', 'harness',
                                 'peaks.json'),
      'counters': {
          'open': {'server': {'calls': 0, 'cache_tokens_read': 0}},
          'close': {'server': {'calls': 4,
                               'cache_tokens_read': 4 * tokens}}}}
  spec = loader.load_metric('gqa.full_cache_roofline_share')
  assert trace_scope_roofline.read(obs, **spec['args']) == (
      pytest.approx(50.0))
  assert module_scope_share.read(
      obs, **loader.load_metric('gqa.window_share')['args']) == (
          pytest.approx(100 * 0.5 / 7.0))
  assert module_scope_share.read(
      obs, **loader.load_metric('hybrid_moe.attention_share')['args']) == (
          pytest.approx(100 * 5.0 / 7.0))


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
  assert 'prompt tokens handed over; the window opens' in out
  assert 'every merged call carried the whole fleet\'s rows' in out
  assert 'tokens read of rings of 4 beside' in out
  assert 'times while decoding' in out
  assert 'agrees with the reference\'s full forward of the episode' in out
  assert 'steps excused for a routing near-tie stay a small share' in out
  assert 'no compilation inside the window' in out


def test_the_parent_cannot_run_the_cell_and_says_so_at_once(tmp_path):
  """A program without the core of window and full layers ends at its
  flags, with an error, soon, and leaves nothing behind: the driver
  then measures the cell on the change alone."""
  import shutil
  import subprocess
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(REPO, 'benchmark'), tmp_path / 'benchmark')
  # The parent's experiment.py: no --seq_layer_pattern, no --seq_window.
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
  # It ends where the program parses the cell's flags.
  assert "Unknown command line flag 'seq_" in done.stderr

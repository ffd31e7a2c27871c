"""The latent-attention cell from the CPU side (PR 32): its entries and
files against the contract, the counts by hand, the reference's two
copies, its readers on an empty trace, and its traced rehearsal to the
contract's line."""

import os
import types

import pytest

from benchmark.harness import dots_counts, loader
from test_benchmark_cells import _result, _run

REPO = loader.ROOT
MANIFEST = loader.load_manifest()
CELL = 'dotsvlm1.decode32_ctx8k'
CONFIG = 'dots_vlm1_serve_ep16_d5'
TRAFFIC = 'tokens32_prefill_decode'
SOURCE = ('https://huggingface.co/rednote-hilab/dots.vlm1.inst/blob/main/'
          'config.json')
PARENT = '9c515802c84355ad32105429fc563fe2f37aa7a7'
CATALOG = {  # the catalog entry's `config`, every key
    'attention_bias': False, 'ep_size': 1, 'first_k_dense_replace': 3,
    'hidden_act': 'silu', 'hidden_size': 7168, 'intermediate_size': 18432,
    'kv_lora_rank': 512, 'max_position_embeddings': 163840,
    'model_type': 'dots_vlm', 'moe_intermediate_size': 2048,
    'moe_layer_freq': 1, 'n_group': 8, 'n_routed_experts': 256,
    'n_shared_experts': 1, 'norm_topk_prob': True,
    'num_attention_heads': 128, 'num_experts_per_tok': 8,
    'num_hidden_layers': 61, 'num_key_value_heads': 128,
    'num_nextn_predict_layers': 1, 'q_lora_rank': 1536,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_scaling': {
        'beta_fast': 32, 'beta_slow': 1, 'factor': 40, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 4096,
        'type': 'yarn'},
    'rope_theta': 10000, 'routed_scaling_factor': 2.5,
    'scoring_func': 'sigmoid', 'seq_aux': True,
    'tie_word_embeddings': False, 'topk_group': 4,
    'topk_method': 'noaux_tc', 'v_head_dim': 128, 'vocab_size': 129280}
REDUCED = {'num_hidden_layers': (61, 5), 'first_k_dense_replace': (3, 1),
           'n_routed_experts': (256, 16), 'vocab_size': (129280, 16160)}


def _config():
  return loader.build_config(loader.flag_args(
      loader.load_config(MANIFEST, CONFIG), loader.load_traffic(TRAFFIC),
      {'seed': 1, 'logdir': '/nowhere'}))


def _published():
  return types.SimpleNamespace(
      seq_num_layers=5, seq_first_dense_layers=1, seq_hidden_size=7168,
      seq_num_heads=128, seq_q_lora_rank=1536, seq_kv_lora_rank=512,
      seq_qk_nope_head_dim=128, seq_qk_rope_head_dim=64, seq_v_head_dim=128,
      seq_mlp_size=18432, seq_moe_size=2048, seq_routed_experts=256,
      seq_experts_held=16, seq_shared_experts=1, num_actions=16160,
      seq_cache_capacity=16384)


def test_the_configuration_is_the_catalogs_with_four_keys_reduced():
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
  flags, scaling = file['flags'], file['rope_scaling']
  said = {
      'seq_num_layers': 'num_hidden_layers',
      'seq_first_dense_layers': 'first_k_dense_replace',
      'seq_hidden_size': 'hidden_size',
      'seq_num_heads': 'num_attention_heads',
      'seq_mlp_size': 'intermediate_size', 'seq_moe_size':
      'moe_intermediate_size', 'seq_kv_lora_rank': 'kv_lora_rank',
      'seq_q_lora_rank': 'q_lora_rank',
      'seq_qk_nope_head_dim': 'qk_nope_head_dim',
      'seq_qk_rope_head_dim': 'qk_rope_head_dim',
      'seq_v_head_dim': 'v_head_dim',
      'seq_experts_held': 'n_routed_experts',
      'seq_experts_per_token': 'num_experts_per_tok',
      'seq_expert_groups': 'n_group', 'seq_expert_groups_kept': 'topk_group',
      'seq_routed_scale': 'routed_scaling_factor',
      'seq_shared_experts': 'n_shared_experts',
      'seq_rope_theta': 'rope_theta', 'seq_norm_eps': 'rms_norm_eps',
      'num_actions': 'vocab_size'}
  for flag, key in said.items():
    assert flags[flag] == file[key], flag
  # The router keeps its published width; the rotary is the published.
  assert flags['seq_routed_experts'] == file['published'][
      'n_routed_experts'] == 256
  assert (flags['seq_rope_factor'], flags['seq_rope_original_max'],
          flags['seq_rope_beta_fast'], flags['seq_rope_beta_slow'],
          flags['seq_rope_mscale'], flags['seq_rope_mscale_all_dim']) == (
              scaling['factor'], scaling['original_max_position_embeddings'],
              scaling['beta_fast'], scaling['beta_slow'], scaling['mscale'],
              scaling['mscale_all_dim'])
  assert (flags['compute_dtype'], flags['param_dtype']) == (
      'bfloat16', 'bfloat16')
  for stated in ('left_out', 'rotary_pairing', 'router', 'score_bias',
                 'cache', 'value_head', 'precision', 'init', 'episode'):
    assert len(file['assumed'][stated]) > 40, stated
  assert '16 chips share each layer' in file['deployment']
  assert 'pipeline' in file['deployment']
  assert 'dots_ref.py' in file['reference']
  # The file repeats the counts' arithmetic.
  config = _config()
  arithmetic = file['arithmetic']
  shapes = dots_counts.shapes(config)
  assert arithmetic['parameters'] == dots_counts.parameters(config)
  assert arithmetic['attention_parameters_per_layer'] == (
      dots_counts.attention_parameters(shapes))
  assert arithmetic['parameters_dense_layer'] == (
      dots_counts.layer_parameters(shapes, False))
  assert arithmetic['parameters_expert_layer'] == (
      dots_counts.layer_parameters(shapes, True))
  assert arithmetic['parameter_bytes_bfloat16'] == 2 * arithmetic[
      'parameters']
  assert arithmetic['cache_bytes_per_token'] == (
      dots_counts.cache_bytes_per_token(config))
  assert arithmetic['state_bytes_per_session'] == (
      dots_counts.state_bytes_per_slot(config))
  carried = {'requests': 32, 'cache_tokens_read': 32 * 7500,
             'experts_hit': 64, 'routed_rows_held': 64}
  assert arithmetic[
      'least_bytes_a_merged_call_of_32_at_7500_tokens_all_64_experts_hit'
  ] == dots_counts.call_bytes(config, carried)
  # The traffic: 32 sessions, prompts 2,048 + 256 i inside episodes of
  # the cache's capacity, handed over in chunks of 512.
  assert config.num_actors == 32 and config.inference_state_slots == 32
  assert (config.episode_length, config.seq_cache_capacity) == (16384, 16384)
  assert (config.token_prompt_length, config.token_prompt_stride,
          config.seq_prefill_chunk) == (2048, 256, 512)
  prompts = [2048 + 256 * i for i in range(32)]
  assert (prompts[-1], sum(prompts) // 32) == (9984, 6016)


def test_the_arena_the_program_builds_is_the_size_the_file_states():
  import jax
  from scalable_agent_tpu import driver
  from scalable_agent_tpu.models import init_params
  file = loader.load_config(MANIFEST, CONFIG)
  config = _config()
  agent = driver.build_agent(config, config.num_actions)
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))
  arithmetic = file['arithmetic']
  assert nbytes(jax.eval_shape(lambda: agent.initial_state(1))) == (
      arithmetic['state_bytes_per_session'])
  arena = jax.eval_shape(lambda: agent.state_arena(32))
  # 32 sessions, and one row more for the padded rows of a merged call.
  assert nbytes(arena) == 33 * arithmetic['state_bytes_per_session'] == (
      arithmetic['arena_bytes_32_sessions_and_the_padded_rows_row'])
  assert arena['layers'][0].shape == (33, 576, 16384)
  assert (agent.prefill_chunk, agent.cache_capacity) == (512, 16384)
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == (
      arithmetic['parameters'])


def test_counts_by_hand():
  config = _published()
  s = dots_counts.shapes(config)
  # Attention: q_a 7168 x 1536, q_b 1536 x 128 x 192, kv_a 7168 x 576,
  # kv_b 512 x 128 x 256, output 16384 x 7168, two latent norms.
  attention = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 +
               512 * 128 * 256 + 128 * 128 * 7168 + 1536 + 512)
  assert attention == 187_107_328 == dots_counts.attention_parameters(s)
  expert = 3 * 7168 * 2048
  assert expert == 44_040_192 == dots_counts.ffn_parameters(s, 2048)
  # An expert layer: attention, two norms, router and its bias, the
  # shared expert, 16 routed experts held.
  routed_layer = attention + 2 * 7168 + 7168 * 256 + 256 + 17 * expert
  assert routed_layer == 937_640_192 == dots_counts.layer_parameters(s, True)
  dense_layer = attention + 2 * 7168 + 3 * 7168 * 18432
  assert dense_layer == 583_483_392 == dots_counts.layer_parameters(s, False)
  assert dots_counts.parameters(config) == (
      dense_layer + 4 * routed_layer + 2 * 16160 * 7168 + 7168 + 7169
  ) == 4_565_728_257
  # The cache: 576 bfloat16 a token a layer, five layers; a slot at
  # its capacity of 16,384 and an int32 position.
  assert dots_counts.cache_bytes_per_token(config) == 5 * 576 * 2 == 5760
  assert dots_counts.state_bytes_per_slot(config) == (
      16384 * 5760 + 4) == 94_371_844
  # A call of 32 rows that read 240,000 cached tokens, hit 41 of the
  # 64 experts held with 60 routed rows.
  carried = {'requests': 32, 'cache_tokens_read': 240_000,
             'experts_hit': 41, 'routed_rows_held': 60}
  assert dots_counts.cache_bytes(config, carried) == 240_000 * 5760
  assert dots_counts.experts_bytes(config, carried) == 41 * 2 * expert
  # Attention per cached token, layer and head: the score over 576
  # numbers and the weighted sum over 512, 2 a multiply-add.
  assert dots_counts.attend_flops(config, carried) == (
      240_000 * 5 * 128 * 2 * (576 + 512))
  whole_bf16 = (5 * (attention + 2 * 7168) + 3 * 7168 * 18432 +
                4 * (7168 * 256 + expert) + 16160 * 7168 + 7168)
  whole_f32 = 4 * 256 + 7168 + 1
  assert dots_counts.call_bytes(config, carried) == (
      2 * (whole_bf16 + 32 * 7168) + 4 * whole_f32 + 41 * 2 * expert +
      240_000 * 5760)
  matrices = attention - 1536 - 512
  assert dots_counts.call_flops(config, carried) == (
      2 * 32 * (5 * matrices + 3 * 7168 * 18432 +
                4 * (7168 * 256 + expert) + 16160 * 7168 + 7168) +
      2 * 60 * expert + 240_000 * 5 * 128 * 2 * (576 + 512))
  # As reckoned before the first run: 8.2 to 10.3 GB a call.
  assert 8.2e9 < dots_counts.call_bytes(config, carried) < 10.3e9
  # Fewer experts hit move fewer bytes; the rows' FLOPs do not change.
  fewer = dict(carried, experts_hit=40)
  assert (dots_counts.call_bytes(config, carried) -
          dots_counts.call_bytes(config, fewer)) == 2 * expert
  assert dots_counts.call_flops(config, fewer) == (
      dots_counts.call_flops(config, carried))


def test_the_references_two_copies_are_one_text():
  def body(path):
    with open(os.path.join(REPO, path)) as f:
      text = f.read()
    return text[text.index('For an episode\'s tokens'):]
  assert body('benchmark/harness/dots_ref.py') == body(
      'scalable_agent_tpu/models/latent_moe_reference.py')


def test_new_entries_keep_to_the_contract():
  cell = loader.find_cell(MANIFEST, CELL)
  assert cell == MANIFEST['workloads'][-1] and cell['chips'] == 1
  assert (cell['config'], cell['traffic']) == (CONFIG, TRAFFIC)
  assert len(cell['why']) <= 200
  assert MANIFEST['configs'][-1]['name'] == CONFIG
  assert len(MANIFEST['configs'][-1]['why']) <= 200
  e2e = [m['name'] for m in
         loader.cell_metrics(MANIFEST, CELL, 'end_to_end')]
  assert e2e == ['policy_call_p95_ms', 'setup_s']
  new = [m for m in MANIFEST['per_layer']
         if m.get('workloads') == [CELL]]
  assert [m['name'] for m in new] == [
      'latent_moe.call_hbm_share', 'latent_moe.call_mfu',
      'latent_moe.attention_share', 'latent_moe.experts_share',
      'latent_moe.router_share', 'mla.cache_roofline_share',
      'moe.experts_roofline_share']
  assert new == MANIFEST['per_layer'][-len(new):]  # appended, in order
  layers = {m['layer'] for m in MANIFEST['per_layer'][:-len(new)]}
  for m in new:
    assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                      'moves', 'workloads'}
    assert (m['unit'], m['moves'], m['source']) == (
        '%', 'policy_call_p95_ms', 'device_trace')
    assert m['layer'] in layers
    spec = loader.load_metric(m['name'])
    assert set(spec) == {'reader', 'args', 'what'}
    assert spec['args']['module_regex'] == r'^jit_cache_step\b'
    if 'counts' in spec['args']:
      assert spec['args']['counts'] == 'dots_counts'
  appended = {
      'policy_call_p95_ms': ['deep_dmlab.fleet32', 'brumby14b.decode32'],
      'inference.call_host_ms_p50': ['deep_dmlab.fleet32',
                                     'brumby14b.decode32'],
      'inference.device_ms_per_call': ['deep_dmlab.fleet32',
                                       'brumby14b.decode32'],
      'policy_call_p50_ms': ['deep_dmlab.fleet32', 'brumby14b.decode32'],
      'policy_call_p99_ms': ['deep_dmlab.fleet32', 'brumby14b.decode32'],
      'serve.device_idle_share': ['brumby14b.decode32'],
      'serve.lm_head_share': ['brumby14b.decode32']}
  for m in MANIFEST['per_layer'] + MANIFEST['end_to_end']:
    if m['name'] in appended:
      assert m['workloads'] == appended[m['name']] + [CELL], m['name']
  reports = {m['name'] for m in
             loader.cell_metrics(MANIFEST, CELL, 'per_layer')}
  assert reports == set(appended) - {'policy_call_p95_ms'} | {
      m['name'] for m in new} | {'entry.compile_s', 'entry.cache_misses'}
  traffic = loader.load_traffic(TRAFFIC)
  # Not held to the training fleet's layer metrics (serve_loop.py).
  assert 'env_processes' not in traffic
  assert traffic['driver'] == 'serve_prefill_decode'
  assert (traffic['warm_calls'], traffic['trace_seconds']) == (64, 15)
  assert (traffic['check_sessions'], traffic['check_steps']) == (32, 384)


def test_the_cell_before_keeps_its_entries():
  """What test_brumby_cell.py :: test_new_entries_keep_to_the_contract
  holds of PR 27's entries, with "last" read as "just before this
  PR's" (tests/conftest.py): nothing of them changed but the cells
  appended to six `workloads` lists."""
  before, config = 'brumby14b.decode32', 'brumby_14b_serve_d4'
  assert MANIFEST['workloads'][-2]['name'] == before
  assert (MANIFEST['workloads'][-2]['config'],
          MANIFEST['workloads'][-2]['traffic']) == (config,
                                                    'tokens32_decode')
  assert MANIFEST['configs'][-2]['name'] == config
  assert [m['name'] for m in
          loader.cell_metrics(MANIFEST, before, 'end_to_end')] == [
              'policy_call_p95_ms', 'setup_s']
  its = MANIFEST['per_layer'][-14:-7]
  assert [m['name'] for m in its] == [
      'serve.call_hbm_share', 'serve.call_mfu', 'serve.device_idle_share',
      'retention.state_roofline_share', 'serve.state_share',
      'serve.mlp_share', 'serve.lm_head_share']
  shared = {'serve.device_idle_share', 'serve.lm_head_share'}
  for m in its:
    assert (m['unit'], m['moves'], m['source']) == (
        '%', 'policy_call_p95_ms', 'device_trace')
    assert m['workloads'] == [before] + (
        [CELL] if m['name'] in shared else [])


def test_readers_find_nothing_where_there_is_nothing():
  counted = {'open': {'server': {'calls': 0, 'requests': 0,
                                 'cache_tokens_read': 0}},
             'close': {'server': {'calls': 2, 'requests': 64,
                                  'cache_tokens_read': 1000}}}
  for name in ('latent_moe.call_hbm_share', 'latent_moe.call_mfu',
               'latent_moe.attention_share', 'latent_moe.experts_share',
               'latent_moe.router_share', 'mla.cache_roofline_share',
               'moe.experts_roofline_share'):
    spec = loader.load_metric(name)
    reader = loader.load_reader(spec['reader'])
    assert reader.read({}, **spec['args']) is None, name
    assert reader.read({'trace': None, 'counters': counted},
                       **spec['args']) is None, name
  from benchmark.readers import trace_counted_share
  assert trace_counted_share.per_call({}) is None
  assert trace_counted_share.per_call({'counters': counted}) == {
      'calls': 1.0, 'requests': 32.0, 'cache_tokens_read': 500.0}
  # The counters around the traced slice win where the driver took them.
  counted.update(
      trace_open={'server': {'calls': 10, 'requests': 320, 'sheds': 0,
                             'admission': 'block', 'state_cache': True}},
      trace_close={'server': {'calls': 14, 'requests': 448, 'sheds': 0,
                              'admission': 'block', 'state_cache': True}})
  assert trace_counted_share.per_call({'counters': counted}) == {
      'calls': 1.0, 'requests': 32.0, 'sheds': 0.0}


def test_a_scopes_roofline_share_from_a_trace_by_hand():
  """One chip, two executions of the program of 10 ms each; under the
  scope 2 ms an execution, of which a `while` of 2 ms spans a body of
  1.5 ms (self time 0.5 + 1.5). 819 MB in 2 ms is half the v5e's
  819 GB/s; 98.5 GFLOP in 2 ms a quarter of its 197 TFLOP/s."""
  from benchmark.harness import trace_reduce, trace_scopes
  from benchmark.readers import trace_scope_roofline
  plane, ms = '/device:TPU:0', 1_000_000
  rows = []
  for start in (0, 20 * ms):
    rows += [
        (plane, trace_reduce.MODULES_LINE, 'jit_cache_step(1)', start,
         10 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_0/mla/attend/while', start + ms,
         2 * ms),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_0/mla/attend/while/body/dot',
         start + ms, 3 * ms // 2),
        (plane, trace_scopes.SCOPES_LINE,
         'jit(cache_step)/core/block_0/mla/proj/dot', start + 4 * ms,
         5 * ms)]
  # The same scope outside the program's executions does not count.
  rows.append((plane, trace_scopes.SCOPES_LINE,
               'jit(prefill_chunk)/core/block_0/mla/attend/dot', 12 * ms,
               4 * ms))
  trace = trace_reduce.Trace.from_rows(rows)
  regex = r'(^|[/(])mla/attend([/)]|$)'
  assert trace_scope_roofline.scope_seconds(
      trace, r'^jit_cache_step\b', regex) == pytest.approx(0.004)
  assert trace_scope_roofline.scope_seconds(
      trace, r'^jit_cache_step\b', r'(^|[/(])moe/experts([/)]|$)') is None
  config = _published()
  tokens = 819e6 / dots_counts.cache_bytes_per_token(config)
  obs = {
      'trace': trace, 'config': config,
      'device': {'kind': 'TPU v5 lite'},
      'peaks_path': os.path.join(REPO, 'benchmark', 'harness',
                                 'peaks.json'),
      'counters': {
          'open': {'server': {'calls': 0, 'cache_tokens_read': 0}},
          'close': {'server': {'calls': 4,
                               'cache_tokens_read': 4 * tokens}}}}
  args = dict(module_regex=r'^jit_cache_step\b', scope_regex=regex,
              counts='dots_counts')
  assert trace_scope_roofline.read(
      obs, bytes_quantity='cache_bytes', **args) == pytest.approx(50.0)
  # The FLOPs of those tokens: 142,222 x 5 x 128 x 2 x 1088 = 198 G in
  # 2 ms, 50.3% of the peak: the larger of the two shares is reported.
  flops = dots_counts.attend_flops(config, {'cache_tokens_read': tokens})
  both = trace_scope_roofline.read(
      obs, bytes_quantity='cache_bytes', flops_quantity='attend_flops',
      **args)
  assert both == pytest.approx(100 * flops / 0.002 / 197e12)
  assert both > 50.0


def test_operations_without_a_scope_take_their_consumers():
  """A prefetch copy inside a conditional takes the conditional's path,
  one before an operation that operation's, within one execution of
  the program; the last of an execution and one outside any keep
  none."""
  from benchmark.harness import trace_enclosure, trace_reduce, trace_scopes
  plane, line, none = ('/device:TPU:0', trace_scopes.SCOPES_LINE,
                       trace_scopes.NO_SCOPE)
  rows = [(plane, trace_reduce.MODULES_LINE, 'jit_cache_step(1)', 0, 100),
          (plane, trace_reduce.MODULES_LINE, 'jit_cache_step(1)', 200, 100),
          (plane, line, none, 5, 5),
          (plane, line, 'mla/proj', 10, 10),
          (plane, line, 'moe/experts/cond', 30, 40),
          (plane, line, none, 32, 10),
          (plane, line, 'moe/experts/cond/dot', 45, 10),
          (plane, line, none, 90, 5),
          (plane, line, none, 150, 5),
          (plane, line, 'mla/proj', 210, 10)]
  trace = trace_reduce.Trace.from_rows(rows)
  assert trace_enclosure.inherit_scopes(trace) == (2, 2)
  ev = trace.planes[plane][line]
  assert list(zip(ev.names, ev.start)) == [
      ('mla/proj', 5), ('mla/proj', 10), ('moe/experts/cond', 30),
      ('moe/experts/cond', 32), ('moe/experts/cond/dot', 45), (none, 90),
      (none, 150), ('mla/proj', 210)]
  # The conditional's self time is what no operation inside it takes:
  # 40 - 10 - 10; the copy's 10 now count under the scope as well.
  from benchmark.readers import trace_scope_roofline
  assert trace_scope_roofline.scope_seconds(
      trace, r'^jit_cache_step', r'(^|[/(])moe/experts([/)]|$)'
  ) == pytest.approx(40e-9)


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
  assert 'agrees with the reference\'s full forward of the episode' in out
  assert 'steps excused for a routing near-tie stay a small share' in out
  assert 'no compilation inside the window' in out


def test_the_parent_cannot_run_the_cell_and_says_so_at_once(tmp_path):
  """A program without the latent core ends at its flags, with an
  error, soon, and leaves nothing behind: the driver then measures the
  cell on the change alone."""
  import shutil
  import subprocess
  shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
  shutil.copytree(os.path.join(REPO, 'benchmark'), tmp_path / 'benchmark')
  # The parent's experiment.py: no --seq_kv_lora_rank, nor its fellows.
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

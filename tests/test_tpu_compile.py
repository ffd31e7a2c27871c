"""The sequence policies' device programs compiled for the TPU v5e
WITHOUT a chip (libtpu's compile-only topology): what the interpreter
cannot show. The retention kernel at the published widths passes
Mosaic (tiling, fast memory); the server's whole `cache_step` at the
benchmark cell's sizes keeps ONE copy of the 4.5 GB state arena (the
kernel's in-place update holds through the donated step) and fits the
chip. The steps are the server's own (`inference.step_functions`, PR
36): each takes its batch as ONE flat parameter. Nothing runs: no
result, no time.

All compile-only tests live in this one file, the topology is described
inside a fixture (never at import), and every compile happens in the
test's own process (the guide's rules: one process holds libtpu)."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import numpy as np

from scalable_agent_tpu.models import (HybridAttentionDims, ImpalaAgent,
                                      LatentMoEDims, SSMHybridDims,
                                      SequenceAgent, init_params)
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.ops import (cache_columns, gqa_pallas, mla_pallas,
                                    retention_pallas, ssm_pallas)
from scalable_agent_tpu.runtime import inference, packing

WIDTHS = dict(num_actions=151936, num_layers=4, hidden_size=5120,
              num_heads=40, num_kv_heads=8, head_dim=128, mlp_size=17408,
              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
SESSIONS = 32


@pytest.fixture(scope='module')
def one_chip():
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  from jax.experimental import topologies
  try:
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
  except Exception as e:  # noqa: BLE001 — whatever libtpu raises
    pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernel(monkeypatch):
  """`update_rows` asks jax.default_backend(), which is the CPU here,
  and would trace the interpreter's form: steer it in the test."""
  monkeypatch.setattr(retention_pallas, '_interpret_on',
                      lambda platform: False)
  monkeypatch.setattr(cache_columns, 'interpret_on', lambda platform: False)
  # The persistent cache cannot read back what a compile-only
  # topology wrote; keep these compiles out of it.
  jax.config.update('jax_enable_compilation_cache', False)
  yield
  jax.config.update('jax_enable_compilation_cache', True)


def _on(sharding, tree):
  return jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
      tree)


TOKEN_ROWS = [(np.int32, ())] * 2 + [(np.float32, ()), (np.bool_, ()),
                                     (np.int32, ())]  # + slot ids


def _lower_step(agent, one_chip, state_cache, rows_meta, params,
                arena=()):
  """(lowered step, its batch layout): the server's merged-call
  program for `agent` (the interleaved bytes taken apart, as on a
  TPU), SESSIONS rows, every argument on the described chip."""
  steps = inference.step_functions(agent, state_cache, planar=True)
  layout = packing.Layout.of_rows(rows_meta, SESSIONS)
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
  packed = jax.ShapeDtypeStruct((layout.words,), jnp.uint32,
                                sharding=one_chip)
  step = jax.jit(steps.step, donate_argnums=(2,) if state_cache else (),
                 static_argnums=(3 + len(arena),))
  return step.lower(_on(one_chip, params), _on(one_chip, key),
                    *_on(one_chip, arena), packed, layout), layout


def _one_flat_batch_parameter(compiled, layout, others):
  """The entry computation takes `others` parameters (weights, key,
  arena leaves) and ONE more: the batch, `u32[words]`, flat (no
  dimension to pad), its bytes on the device within 1% (or one tile:
  `decode32`'s five arrays are 544 B) of what the arrays in it hold."""
  entry = compiled.as_text().split('ENTRY ')[1]
  parameters = [line for line in entry.splitlines()
                if ' parameter(' in line]
  flat = [line for line in parameters
          if f' = u32[{layout.words}]{{0' in line]
  assert len(flat) == 1 and len(parameters) == others + 1, (
      len(flat), len(parameters), others)
  tile = 1024 * 4  # a flat vector is tiled by 1,024 words
  on_device = -(-layout.words * 4 // tile) * tile
  assert on_device - layout.logical_bytes <= max(
      0.01 * layout.logical_bytes, tile)
  return flat[0]


def test_carry_step_takes_one_flat_buffer_at_the_fleets_sizes(
    one_chip, compiled_kernel):
  """`jit_carry_step` of the paper's deep agent for 32 rows of 72 x 96
  frames (`deep_dmlab.fleet32`): seven arrays in one parameter of
  731,520 B; as a parameter of its own the frame alone is laid out
  channels apart with W padded to 128, 884,736 B for 663,552."""
  agent = ImpalaAgent(num_actions=9, torso='deep', use_instruction=True,
                      dtype=jnp.bfloat16)
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0),
      {'frame': (72, 96, 3), 'instr_len': MAX_INSTRUCTION_LEN}))
  meta = [(np.int32, ()), (np.float32, ()), (np.bool_, ()),
          (np.uint8, (72, 96, 3)), (np.int32, (MAX_INSTRUCTION_LEN,)),
          (np.float32, (256,)), (np.float32, (256,))]
  lowered, layout = _lower_step(agent, one_chip, False, meta, params)
  assert layout.logical_bytes == 731424 and layout.words * 4 == 731520
  compiled = lowered.compile()
  _one_flat_batch_parameter(
      compiled, layout, len(jax.tree_util.tree_leaves(params)) + 1)
  text = compiled.as_text()
  assert 'u8[32,72,96,3]' not in text.split('ENTRY ')[1].split('\n')[0]
  # The frame reaches the first convolution channels apart, as XLA
  # lays out a parameter of its own, by way of the matrix unit: no
  # relayout of the C-minor bytes (a `reshape` to bf16[32,72,96,3] and
  # a convolution twelve times slower: PERF.md section 6, PR 36).
  assert 'bf16[32,72,96,3]' not in text
  memory = compiled.memory_analysis()
  assert memory.temp_size_in_bytes < 0.15e9
  # One array out too: key and packed outputs.
  assert memory.output_size_in_bytes < 72_000  # 66,944 B of outputs


def test_retention_kernel_compiles_at_the_published_widths(
    one_chip, compiled_kernel):
  rows, kv, dv, dk, groups, b = SESSIONS + 1, 8, 128, 128, 5, SESSIONS
  f32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
      shape, jnp.float32, sharding=one_chip)
  step = jax.jit(retention_pallas.update_rows.__wrapped__,
                 donate_argnums=(0,))
  compiled = step.lower(
      f32(rows, kv, dv, (dk // 2 + 1) * dk),
      jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip),
      f32(b, kv), f32(b, kv, groups, dk), f32(b, kv, dk),
      f32(b, kv, dv)).compile()
  memory = compiled.memory_analysis()
  state_bytes = rows * kv * dv * (dk // 2 + 1) * dk * 4
  # In place: the state is aliased to the output, nothing is copied.
  assert memory.alias_size_in_bytes >= state_bytes
  assert memory.temp_size_in_bytes < state_bytes // 100


def test_cache_step_holds_one_arena_and_fits_the_chip(
    one_chip, compiled_kernel):
  agent = SequenceAgent(**WIDTHS)
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  arena = jax.eval_shape(lambda: agent.state_arena(SESSIONS))
  lowered, layout = _lower_step(agent, one_chip, True, TOKEN_ROWS, params,
                                arena=(arena,))
  compiled = lowered.compile()
  # `decode32`'s 32 slot ids and tokens: 544 B in one parameter.
  _one_flat_batch_parameter(
      compiled, layout, len(jax.tree_util.tree_leaves((params, arena))) + 1)
  memory = compiled.memory_analysis()
  arena_bytes = sum(l.size * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(arena))
  param_bytes = sum(l.size * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(params))
  assert arena_bytes > 4.3e9 and param_bytes > 5.7e9
  # ONE copy of the arena: all of it aliased, and temporaries far
  # under one layer's state (a gathered copy would be 1.1 GB a layer).
  assert memory.alias_size_in_bytes >= arena_bytes
  assert memory.temp_size_in_bytes < 0.3e9
  total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes +
           memory.output_size_in_bytes - memory.alias_size_in_bytes)
  assert total < 12e9  # of the chip's 16.9e9
  assert compiled.as_text().count(retention_pallas.KERNEL_NAME) >= 4


# The latent-attention policy at the published widths of its benchmark
# cell (PR 32): 5 layers, 16 of 256 experts, an eighth of the vocabulary.
LATENT = dict(
    num_actions=16160, num_layers=5, hidden_size=7168, num_heads=128,
    mlp_size=18432, rope_theta=1e4, dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16, core_dims=LatentMoEDims(
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_dense_layers=1,
        moe_size=2048, routed_experts=256, experts_held=16,
        experts_per_token=8, expert_groups=8, expert_groups_kept=4,
        cache_capacity=16384, prefill_chunk=512))


def _serving_programs(agent, one_chip, chunk):
  """(params bytes, arena bytes, {name: compiled}) of the two programs
  the inference server runs for `agent` (its own:
  `inference.step_functions`): `cache_step` for a merged call of 32
  rows and the prefill chunk, the arena donated to both."""
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  arena = jax.eval_shape(lambda: agent.state_arena(SESSIONS))
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))

  steps = inference.step_functions(agent, True, planar=True)
  spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
      shape, dtype, sharding=one_chip)
  lowered = {
      'cache_step': _lower_step(agent, one_chip, True, TOKEN_ROWS, params,
                                arena=(arena,))[0],
      'prefill_chunk': jax.jit(
          steps.prefill_chunk, donate_argnums=(1,)).lower(
              _on(one_chip, params), _on(one_chip, arena),
              spec((), jnp.int32), spec((chunk,), jnp.int32),
              spec((), jnp.int32), spec((), jnp.bool_))}
  return nbytes(params), nbytes(arena), {
      name: program.compile() for name, program in lowered.items()}


def _copies_of(text, leaf):
  return [line for line in text.splitlines()
          if leaf in line and (' copy(' in line or 'copy-start(' in line)]


def test_latent_programs_never_copy_a_cache_leaf_and_fit_the_chip(
    one_chip, compiled_kernel):
  """Both programs of the latent core, `cache_step` and the prefill
  chunk: the two kernels pass Mosaic at the published widths; the 3.1 GB
  arena is aliased to the output and NO copy of a cache leaf is in
  either program (a TPU lays `[slots, capacity, 576]` out with the
  positions along the lanes, and a program that indexes it the other
  way round copies the leaf there and back, 1.2 GB a layer and call:
  the leaf is `[slots, 576, capacity]` and written by a kernel for that
  reason); temporaries stay small; weights, arena and temporaries fit."""
  params_bytes, arena_bytes, programs = _serving_programs(
      SequenceAgent(**LATENT), one_chip, 512)
  assert arena_bytes > 3.1e9 and params_bytes > 9.1e9
  leaf = 'bf16[33,576,16384]'
  for name, compiled in programs.items():
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= arena_bytes, name
    copies = _copies_of(text, leaf)
    assert not copies, (name, copies[:2])
    # The largest temporary is far under one cache leaf (629 MB).
    assert memory.temp_size_in_bytes < 0.55e9, name
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes +
             memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 13e9, name  # of the chip's 16.9e9
    if name == 'cache_step':
      assert text.count(mla_pallas.KERNEL_NAME) >= 5
      assert text.count(mla_pallas.WRITE_KERNEL_NAME) >= 5
      assert memory.temp_size_in_bytes < 0.1e9


# The policy of window and full attention layers at the published widths
# of its benchmark cell (PR 35): 5 layers `LLLGL`, 16 of 128 experts, an
# eighth of the vocabulary.
HYBRID = dict(
    num_actions=19200, num_layers=5, hidden_size=6144, num_heads=64,
    mlp_size=18432, rope_theta=1e6, norm_eps=1e-5, dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16, core_dims=HybridAttentionDims(
        num_kv_heads=8, head_dim=128, layer_pattern='LLLG', window=128,
        first_dense_layers=1, moe_size=2048, routed_experts=128,
        experts_held=16, experts_per_token=8, cache_capacity=32768,
        prefill_chunk=512))


def test_hybrid_programs_never_copy_the_cache_and_fit_the_chip(
    one_chip, compiled_kernel):
  """Both programs of the core of window and full layers: the decode
  kernel passes Mosaic at the published widths for a cache of 32,768
  columns and for a ring of 128 (8 query heads a group, blocks of 1,024
  columns of all 8 keys and all 8 values); the 4.5 GB arena of two
  kinds of leaf is aliased to the output and NO copy of the full
  layer's 4.4 GB leaf is in either program (a token is a column, written
  by a kernel, prefill a window of 512 columns); temporaries stay small;
  weights, arena and temporaries fit."""
  params_bytes, arena_bytes, programs = _serving_programs(
      SequenceAgent(**HYBRID), one_chip, 512)
  assert 4.49e9 < arena_bytes < 4.5e9 and 7.42e9 < params_bytes < 7.43e9
  for name, compiled in programs.items():
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= arena_bytes, name
    copies = _copies_of(text, 'bf16[33,2048,32768]')
    assert not copies, (name, copies[:2])
    assert memory.temp_size_in_bytes < 0.55e9, name
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes +
             memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 13e9, name  # of the chip's 16.9e9
    if name == 'cache_step':
      # One walk a layer, full or ring, and one column write.
      assert text.count(gqa_pallas.KERNEL_NAME) >= 5
      assert text.count(gqa_pallas.WRITE_KERNEL_NAME) >= 5
      # 122 MB as compiled: layer 0's `q_proj` kernel transposed
      # (`bf16[8192,6144]`, 101 MB: PERF.md section 7) and little else;
      # a thirtieth of the full layer's leaf.
      assert memory.temp_size_in_bytes < 0.15e9


# The policy of Mamba-2 and full attention layers at the published widths
# of its benchmark cell: 10 layers `MMMMMGMMMM`, 9 of 72 experts, an
# eighth of the vocabulary.
GRANITE = dict(
    num_actions=12544, num_layers=10, hidden_size=4096, num_heads=32,
    norm_eps=1e-5, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    core_dims=SSMHybridDims(
        num_kv_heads=8, head_dim=128, layer_pattern='MMMMMGMMMM',
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, conv_width=4,
        ssm_block=256, moe_size=768, routed_experts=72, experts_held=9,
        experts_per_token=10, expert_groups=1, expert_groups_kept=1,
        routed_scale=1.0, shared_experts=2, router='softmax',
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16.0,
        cache_capacity=32768, prefill_chunk=512))


def test_ssm_programs_never_copy_a_state_or_the_cache_and_fit_the_chip(
    one_chip, compiled_kernel):
  """Both programs of the core of Mamba-2 and full layers: the state
  kernel passes Mosaic at the published widths (blocks of 32 heads of
  64 by 128, float32, the decays in scalar memory); the 5.69 GB arena of
  a fixed state beside a growing cache is aliased to the output and NO
  copy of a Mamba layer's 139 MB state leaf or of the full layer's 4.4
  GB cache is in either program (the state updated in place by the
  kernel, a token a column of the cache); temporaries stay small;
  weights, arena and temporaries fit, 9.8 GB of the chip's 16.9."""
  params_bytes, arena_bytes, programs = _serving_programs(
      SequenceAgent(**GRANITE), one_chip, 512)
  assert 5.68e9 < arena_bytes < 5.7e9 and 4.11e9 < params_bytes < 4.12e9
  for name, compiled in programs.items():
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= arena_bytes, name
    for leaf in ('f32[33,128,64,128]', 'bf16[33,2048,32768]'):
      copies = _copies_of(text, leaf)
      assert not copies, (name, leaf, copies[:2])
    assert memory.temp_size_in_bytes < 0.3e9, name
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes +
             memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 10.2e9, name
    if name == 'cache_step':
      # The state kernel in each of nine layers, the walk and the column
      # write in the one full layer.
      assert text.count(ssm_pallas.KERNEL_NAME) >= 9
      assert text.count(gqa_pallas.KERNEL_NAME) >= 1
      assert text.count(gqa_pallas.WRITE_KERNEL_NAME) >= 1
      assert memory.temp_size_in_bytes < 0.1e9

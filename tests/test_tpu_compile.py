"""The sequence policies' device programs compiled for the TPU v5e
WITHOUT a chip (libtpu's compile-only topology): what the interpreter
cannot show. The retention kernel at the published widths passes
Mosaic (tiling, fast memory); the server's whole `cache_step` at the
benchmark cell's sizes keeps ONE copy of the 4.5 GB state arena (the
kernel's in-place update holds through the donated step) and fits the
chip. Nothing runs: no result, no time.

All compile-only tests live in this one file, the topology is described
inside a fixture (never at import), and every compile happens in the
test's own process (the guide's rules: one process holds libtpu)."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from scalable_agent_tpu.models import (HybridAttentionDims, LatentMoEDims,
                                      SequenceAgent, init_params)
from scalable_agent_tpu.ops import (cache_columns, gqa_pallas, mla_pallas,
                                    retention_pallas)
from scalable_agent_tpu.structs import StepOutput

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
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

  def cache_step(params, key, arena, slot_ids, prev_action, reward, done,
                 token):  # runtime/inference.py's, for this agent
    key, sub = jax.random.split(key)
    env_output = StepOutput(reward=reward[None], info=None,
                            done=done[None], observation=(token[None],))
    out, arena = agent.apply(params, prev_action[None], env_output, arena,
                             sample_rng=sub, state_slots=slot_ids)
    return key, arena, out.action[0], out.policy_logits[0], out.baseline[0]

  row = lambda dtype: jax.ShapeDtypeStruct(  # noqa: E731
      (SESSIONS,), dtype, sharding=one_chip)
  compiled = jax.jit(cache_step, donate_argnums=(2,)).lower(
      _on(one_chip, params), _on(one_chip, key), _on(one_chip, arena),
      row(jnp.int32), row(jnp.int32), row(jnp.float32), row(jnp.bool_),
      row(jnp.int32)).compile()
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
  the inference server runs for `agent`: `cache_step` for a merged call
  of 32 rows and the prefill chunk, the arena donated to both."""
  params = jax.eval_shape(lambda: init_params(
      agent, jax.random.PRNGKey(0), {'leaves': (((), 'int32'),)}))
  arena = jax.eval_shape(lambda: agent.state_arena(SESSIONS))
  key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
  nbytes = lambda tree: sum(  # noqa: E731
      l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree))

  def cache_step(params, key, arena, slot_ids, prev_action, reward, done,
                 token):  # runtime/inference.py's, for this agent
    key, sub = jax.random.split(key)
    env_output = StepOutput(reward=reward[None], info=None,
                            done=done[None], observation=(token[None],))
    (out, arena), counters = agent.apply(
        params, prev_action[None], env_output, arena, sample_rng=sub,
        state_slots=slot_ids, mutable=['counters'])
    return (key, arena, out.action[0], out.policy_logits[0],
            out.baseline[0], counters)

  def prefill_chunk(params, arena, slot, tokens, n_valid, reset):
    return agent.apply(params, tokens, arena, slot, n_valid, reset,
                       method=agent.prefill)

  spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
      shape, dtype, sharding=one_chip)
  row = lambda dtype: spec((SESSIONS,), dtype)  # noqa: E731
  lowered = {
      'cache_step': jax.jit(cache_step, donate_argnums=(2,)).lower(
          _on(one_chip, params), _on(one_chip, key), _on(one_chip, arena),
          row(jnp.int32), row(jnp.int32), row(jnp.float32),
          row(jnp.bool_), row(jnp.int32)),
      'prefill_chunk': jax.jit(prefill_chunk, donate_argnums=(1,)).lower(
          _on(one_chip, params), _on(one_chip, arena), spec((), jnp.int32),
          spec((chunk,), jnp.int32), spec((), jnp.int32),
          spec((), jnp.bool_))}
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

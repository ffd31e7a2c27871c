"""The sequence policy's core of window and full attention layers (PR
35): its step and chunk forms against the plain reference's full
forward, the ring before it is full, across its wrap and after a reset,
padded rows, the decode kernel against plain numpy, the routed-expert
layer's share of a deployment. (The inference server's side:
tests/test_hybrid_serving.py.)

Everything runs at a tiny size in float32 on the CPU: hidden 32, 4
query heads in 2 key-value groups of 16, five layers `LLLGL` (the first
dense), a window of 4, a chunk of 8, 16 routed experts of 32 in one
group, 4 a token, a vocabulary of 97.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import (HybridAttentionDims,
                                      HybridAttentionStack, SequenceAgent,
                                      init_params)
from scalable_agent_tpu.models import hybrid_attention
from scalable_agent_tpu.models import hybrid_attention_reference as reference
from scalable_agent_tpu.models import latent_moe, moe
from scalable_agent_tpu.ops import gqa_pallas
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
THETA = 1e4
EPS = 1e-5
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = HybridAttentionDims(window=4, cache_capacity=64, prefill_chunk=8)


def _agent(dims=DIMS, layers=5, **kw):
  return SequenceAgent(num_actions=VOCAB, num_layers=layers, hidden_size=32,
                       num_heads=HEADS, mlp_size=48, rope_theta=THETA,
                       norm_eps=EPS, core_dims=dims, **kw)


def _params(agent, seed=0):
  return init_params(agent, jax.random.PRNGKey(seed), TOKEN_OBS)


def _reference(params, tokens, dims=DIMS, **kw):
  return reference.forward(
      params, tokens, np.zeros_like(tokens), dims=dims, num_heads=HEADS,
      rope_theta=THETA, norm_eps=EPS, block=8, logits=True, **kw)[3]


def _env_output(tokens, dones):
  tokens = jnp.asarray(tokens, jnp.int32)[None]
  return StepOutput(reward=jnp.zeros(tokens.shape, jnp.float32),
                    info=None, done=jnp.asarray(dones, bool)[None],
                    observation=(tokens,))


def _serve(agent, params):
  """(prefill(arena, tokens, slot, reset) in chunks of 8, step(arena,
  tokens [2], slots [2], dones [2]) -> (logits [2, V], arena))."""
  chunk = jax.jit(lambda arena, block, slot, n, reset: agent.apply(
      params, block, arena, slot, n, reset, method=agent.prefill))
  one = jax.jit(lambda arena, token, slots, dones: agent.apply(
      params, jnp.zeros((1, 2), jnp.int32), _env_output(token, dones),
      arena, state_slots=slots))

  def prefill(arena, tokens, slot, reset=True):
    for lo in range(0, len(tokens), 8):
      valid = min(8, len(tokens) - lo)
      block = np.zeros(8, np.int32)
      block[:valid] = tokens[lo:lo + valid]
      arena = chunk(arena, block, jnp.int32(slot), jnp.int32(valid),
                    jnp.bool_(reset and lo == 0))
    return arena

  def step(arena, tokens, slots, dones=(False, False)):
    out, arena = one(arena, np.asarray(tokens, np.int32),
                     np.asarray(slots, np.int32), np.asarray(dones))
    return np.asarray(out.policy_logits[0]), arena

  return prefill, step


def test_the_core_the_widths_name():
  agent = _agent()
  core = agent.core()
  assert isinstance(core, HybridAttentionStack)
  assert (agent.prefill_chunk, agent.cache_capacity, agent.cache_window) == (
      8, 64, 4)
  assert agent.call_counters == moe.COUNTERS == latent_moe.COUNTERS
  assert [DIMS.kind(i) for i in range(5)] == list('LLLGL')
  # Two kinds of leaf a session: a ring of 4 columns, a cache of 64.
  state = jax.eval_shape(lambda: agent.initial_state(1))
  assert [l.shape for l in state['layers']] == [
      (1, 64, 4), (1, 64, 4), (1, 64, 4), (1, 64, 64), (1, 64, 4)]
  arena = jax.eval_shape(lambda: agent.state_arena(3))
  assert arena['layers'][3].shape == (4, 64, 64)
  assert arena['pos'].shape == (4,)
  plain = SequenceAgent(num_actions=VOCAB)
  assert (plain.prefill_chunk, plain.cache_capacity, plain.cache_window,
          plain.call_counters) == (0, 0, 0, ())
  for wrong in (dict(layer_pattern='LXG'), dict(layer_pattern=''),
                dict(window=129), dict(cache_capacity=1500),
                dict(experts_per_token=17), dict(head_dim=7)):
    with pytest.raises(ValueError):
      dataclasses.replace(DIMS, **wrong).check()
  DIMS.check()
  dataclasses.replace(DIMS, window=128, cache_capacity=2048).check()


def test_step_chunk_and_reference_agree_over_an_episode_with_a_reset():
  """An episode of 61 tokens, fifteen rings and seven chunks long:
  every token a step from the carry; a prompt of 29 in chunks (a ragged
  last one) then steps through the arena with a padded row; both the
  reference's logits. Then `done` in mid-stream: what follows is the
  reference's forward of the new episode alone, the ring read before
  it is full again."""
  agent = _agent()
  params = _params(agent)
  rng = np.random.RandomState(7)
  tokens = rng.randint(VOCAB, size=61).astype(np.int32)
  after = rng.randint(VOCAB, size=11).astype(np.int32)
  prefill, step = _serve(agent, params)
  carry_step = jax.jit(lambda state, token, done: agent.apply(
      params, jnp.zeros((1, 1), jnp.int32),
      _env_output([token], [done]), state))
  with HIGHEST:
    ref = np.asarray(_reference(params, tokens))
    ref_after = np.asarray(_reference(params, after))
    state, logits = agent.initial_state(1), []
    for t, token in enumerate(list(tokens) + list(after)):
      out, state = carry_step(state, token, t == len(tokens))
      logits.append(np.asarray(out.policy_logits[0, 0]))
    np.testing.assert_allclose(np.stack(logits[:61]), ref, atol=2e-4)
    np.testing.assert_allclose(np.stack(logits[61:]), ref_after, atol=2e-4)
    assert int(state['pos'][0]) == 11

    arena = prefill(agent.state_arena(3), tokens[:29], 1)
    assert list(np.asarray(arena['pos'])) == [0, 29, 0, 0]
    served = []
    for t in range(29, 61):
      out, arena = step(arena, [tokens[t], 5], [1, 1 << 30])
      served.append(out[0])
    np.testing.assert_allclose(np.stack(served), ref[29:], atol=2e-4)
    # `done` resets the position, never the rows: the ring and the
    # cache still hold the old episode, and nothing of it is read.
    served = []
    for t, token in enumerate(after):
      out, arena = step(arena, [token, 5], [1, 1 << 30], (t == 0, False))
      served.append(out[0])
    np.testing.assert_allclose(np.stack(served), ref_after, atol=2e-4)
  assert list(np.asarray(arena['pos'])) == [0, 11, 0, 0]
  # The other sessions' rows were never touched; the padded rows' row,
  # beyond the last slot, took what the padded rows wrote.
  for leaf in arena['layers']:
    assert not np.any(np.asarray(leaf[0])) and not np.any(
        np.asarray(leaf[2]))
  assert any(np.any(np.asarray(leaf[3])) for leaf in arena['layers'])


@pytest.mark.parametrize('window,chunk', [(4, 8), (8, 8), (16, 8)])
def test_the_chunk_form_at_every_offset_against_the_rings_wrap(window,
                                                               chunk):
  """A prompt of n tokens for every n from 1 to 3 chunks, then a chunk
  that begins at n (a ragged one of 5 tokens): the ring is partly
  full, wraps inside the chunk, is shorter and longer than a chunk.
  The step that follows reads the reference's logits, so the ring the
  chunk left is the episode's last `window` tokens."""
  dims = dataclasses.replace(DIMS, window=window, prefill_chunk=chunk)
  agent = _agent(dims, layers=2)
  params = _params(agent)
  rng = np.random.RandomState(window)
  tokens = rng.randint(VOCAB, size=3 * chunk + 7).astype(np.int32)
  prefill, step = _serve(agent, params)
  one = jax.jit(lambda arena, block, n: agent.apply(
      params, block, arena, jnp.int32(0), n, jnp.bool_(False),
      method=agent.prefill))
  with HIGHEST:
    ref = np.asarray(_reference(params, tokens, dims))
    for n in range(1, 3 * chunk + 1):
      arena = prefill(agent.state_arena(1), tokens[:n], 0)
      block = np.zeros(chunk, np.int32)
      block[:5] = tokens[n:n + 5]
      arena = one(arena, block, jnp.int32(5))
      assert int(arena['pos'][0]) == n + 5
      out, arena = step(arena, [tokens[n + 5], 0], [0, 1 << 30])
      np.testing.assert_allclose(out[0], ref[n + 5], atol=2e-4,
                                 err_msg=f'prompt of {n}')


def test_padded_rows_touch_no_live_row():
  """A call of one live row and one padded row against the same call
  with another token in the padded row: the live row's logits and
  every session's rows are the same to the bit."""
  agent = _agent()
  params = _params(agent)
  prefill, step = _serve(agent, params)
  rng = np.random.RandomState(3)
  with HIGHEST:
    arena = prefill(agent.state_arena(2), rng.randint(VOCAB, size=13), 0)
    arena = prefill(arena, rng.randint(VOCAB, size=6), 1)
    a, arena_a = step(arena, [7, 11], [0, 1 << 30])
    b, arena_b = step(arena, [7, 90], [0, 1 << 30])
  np.testing.assert_array_equal(a[0], b[0])
  for x, y, before in zip(arena_a['layers'], arena_b['layers'],
                          arena['layers']):
    np.testing.assert_array_equal(x[:2], y[:2])
    np.testing.assert_array_equal(x[1], before[1])


def test_kernels_against_plain_numpy():
  """`attend_rows`, interpreted, is the softmax over a row's own columns
  `0..last`, whatever lies beyond them, a group's keys and values
  serving that group's heads, for rows of very different lengths (one
  column, a partial block, all blocks); as a ring's read it is the
  same with `last` capped at the ring. `write_rows` sets one column a
  row and nothing else."""
  rng = np.random.RandomState(0)
  rows, groups, per, dim, capacity = 5, 2, 3, 16, 64
  cache = rng.randn(rows, 2 * groups * dim, capacity).astype(np.float32)
  entry = rng.randn(4, 2 * groups * dim).astype(np.float32)
  slots = np.array([3, 0, 4, 1], np.int32)
  last = np.array([0, 17, 63, 31], np.int32)
  written = np.asarray(gqa_pallas.write_rows(
      jnp.asarray(cache), jnp.asarray(entry), jnp.asarray(slots),
      jnp.asarray(last)))
  want = cache.copy()
  want[slots, :, last] = entry
  np.testing.assert_array_equal(written, want)
  q = rng.randn(4, groups, per, dim).astype(np.float32)
  with HIGHEST:
    got = np.asarray(gqa_pallas.attend_rows(
        jnp.asarray(q), jnp.asarray(written), jnp.asarray(slots),
        jnp.asarray(last), scale=0.25, block=16))
  keys = written[:, :groups * dim].reshape(rows, groups, dim, capacity)
  values = written[:, groups * dim:].reshape(rows, groups, dim, capacity)
  for n in range(4):
    for g in range(groups):
      own = slice(0, last[n] + 1)
      scores = 0.25 * q[n, g].astype(np.float64) @ keys[slots[n], g, :, own]
      weights = np.exp(scores - scores.max(axis=1, keepdims=True))
      weights /= weights.sum(axis=1, keepdims=True)
      np.testing.assert_allclose(
          got[n, g * per:(g + 1) * per],
          weights @ values[slots[n], g, :, own].T, atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
  """The guide's share test: over the 4 shares of a layer of 16 routed
  experts in one group (4 experts each), the routed parts summed and
  the shared expert counted once are the uncut reference's whole
  layer."""
  whole = dataclasses.replace(DIMS, experts_held=16)
  layer = moe.RoutedExperts(whole, 32)
  rng = np.random.RandomState(2)
  x = jnp.asarray(rng.randn(24, 32), jnp.float32)
  live = jnp.ones((24,), bool)
  params = layer.init(jax.random.PRNGKey(3), x, live)['params']
  with HIGHEST:
    weights, margin = reference._router(
        x, params['router']['kernel'], params['e_score_correction_bias'],
        whole)
    ones = jnp.ones((24,))
    shared = reference._ffn_blocks(x, params['shared_expert'], ones,
                                   None, 8)
    uncut = shared + sum(
        reference._ffn_blocks(x, params[f'expert_{e}'], weights[:, e],
                              None, 8) for e in range(16))
    total = jnp.zeros_like(x)
    for share in range(4):
      dims = dataclasses.replace(DIMS, experts_held=4,
                                 expert_offset=4 * share)
      mine = {k: v for k, v in params.items()
              if not k.startswith('expert_')}
      mine.update({f'expert_{e}': params[f'expert_{4 * share + e}']
                   for e in range(4)})
      part = moe.RoutedExperts(dims, 32).apply({'params': mine}, x, live)
      total = total + (part - shared)  # what every chip computes alike
    # Every token's 4 experts were somebody's: nothing is lost.
    assert np.all(np.sum(np.asarray(weights) > 0, axis=1) == 4)
    assert np.all(np.asarray(margin) > 0) and np.all(np.isfinite(margin))
    np.testing.assert_allclose(total + shared, uncut, atol=2e-4)
    # And the program's own uncut layer is the reference's.
    np.testing.assert_allclose(
        layer.apply({'params': params}, x, live), uncut, atol=2e-4)
    # One group: the choice is the plain top-4 of score plus bias.
    scores = jax.nn.sigmoid(x @ params['router']['kernel'])
    chosen, _ = moe.route(scores, params['e_score_correction_bias'], whole)
    top = np.argsort(-np.asarray(
        scores + params['e_score_correction_bias']), axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(chosen), axis=1),
                          np.sort(top, axis=1))


def test_a_lower_cache_precision_shows_in_the_reference():
  """The reference's options: operands and caches rounded as a
  configuration states; a cache in float8 moves the logits by far more
  than one in bfloat16."""
  agent = _agent()
  params = _params(agent)
  tokens = np.random.RandomState(1).randint(VOCAB, size=24).astype(np.int32)
  with HIGHEST:
    exact = np.asarray(_reference(params, tokens))
    bf16 = np.asarray(_reference(params, tokens,
                                 cache_dtype=jnp.bfloat16,
                                 operand_dtype=jnp.bfloat16))
    fp8 = np.asarray(_reference(params, tokens,
                                cache_dtype=jnp.float8_e4m3fn,
                                operand_dtype=jnp.bfloat16))
  assert 0 < np.abs(bf16 - exact).max() < np.abs(fp8 - exact).max()
  assert hybrid_attention.DECODE_BLOCK % 128 == 0

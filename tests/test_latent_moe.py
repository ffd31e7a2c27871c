"""The sequence policy's latent-attention core (PR 32): its two forms
of one attention against the plain reference, the chunk form of the
core protocol for every core, the two kernels against plain numpy, the
routed-expert layer's share of a deployment, routing and YaRN by hand.
(The inference server's side: tests/test_latent_serving.py.)

Everything runs at a tiny size in float32 on the CPU: hidden 32, 4
heads (8 + 4 query/key, 8 value), latents 24 and 16, 16 routed experts
of 32 in 4 groups, 4 a token, a vocabulary of 97.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import (LSTMCore, PowerRetentionStack,
                                      SequenceAgent, init_params)
from scalable_agent_tpu.models import core as core_lib
from scalable_agent_tpu.models import latent_moe, moe
from scalable_agent_tpu.models import latent_moe_reference as reference
from scalable_agent_tpu.models.latent_moe import LatentMoEDims
from scalable_agent_tpu.ops import mla_pallas
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
THETA = 1e4
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = LatentMoEDims(cache_capacity=64, prefill_chunk=8)


def _agent(dims=DIMS, **kw):
  return SequenceAgent(num_actions=VOCAB, num_layers=3, hidden_size=32,
                       num_heads=HEADS, mlp_size=48, rope_theta=THETA,
                       core_dims=dims, **kw)


def _params(agent, seed=0):
  return init_params(agent, jax.random.PRNGKey(seed), TOKEN_OBS)


def _reference(params, tokens, actions, dims=DIMS, **kw):
  return reference.forward(params, tokens, actions, dims=dims,
                           num_heads=HEADS, rope_theta=THETA, block=8, **kw)


def _env_output(tokens, dones):
  tokens = jnp.asarray(tokens, jnp.int32)[None]
  return StepOutput(reward=jnp.zeros(tokens.shape, jnp.float32),
                    info=None, done=jnp.asarray(dones, bool)[None],
                    observation=(tokens,))


def test_logits_of_both_forms_are_the_references():
  """(a) on logits, without the server: the chunk form, then the arena
  step with a padded row, against the reference's logits."""
  agent = _agent()
  params = _params(agent)
  rng = np.random.RandomState(7)
  tokens = rng.randint(VOCAB, size=40).astype(np.int32)
  prompt = 21
  prefill = jax.jit(lambda arena, block, slot, n, reset: agent.apply(
      params, block, arena, slot, n, reset, method=agent.prefill))
  step = jax.jit(lambda arena, token, slots: agent.apply(
      params, jnp.zeros((1, 2), jnp.int32),
      _env_output(token, [False, False]), arena, state_slots=slots))
  with HIGHEST:
    arena = agent.state_arena(3)
    for lo in range(0, prompt, 8):
      valid = min(8, prompt - lo)
      block = np.zeros(8, np.int32)
      block[:valid] = tokens[lo:lo + valid]
      arena = prefill(arena, block, jnp.int32(1), jnp.int32(valid),
                      jnp.bool_(lo == 0))
    assert list(np.asarray(arena['pos'])) == [0, prompt, 0, 0]
    logits = []
    for t in range(prompt, len(tokens)):
      out, arena = step(arena, np.array([tokens[t], 5], np.int32),
                        np.array([1, 1 << 30], np.int32))
      logits.append(np.asarray(out.policy_logits[0, 0]))
    *_, ref_logits = _reference(params, tokens, np.zeros_like(tokens),
                                logits=True)
  np.testing.assert_allclose(np.stack(logits), ref_logits[prompt:],
                             atol=2e-4)
  # The other rows of the arena were never touched.
  for cache in arena['layers']:
    assert not np.any(np.asarray(cache[0])) and not np.any(
        np.asarray(cache[2]))


def _cores():
  return {
      'lstm': (LSTMCore(16), 8),
      'retention': (PowerRetentionStack(2, 32, 4, 2, 8, 48), 32),
      'latent': (latent_moe.LatentMoEStack(
          3, 32, HEADS, 48, THETA, 1e-6, DIMS), 32)}


@pytest.mark.parametrize('name', ['lstm', 'retention', 'latent'])
def test_chunk_form_is_the_single_steps(name):
  """(b): for every core the chunk form advances a session as its
  first `n_valid` single steps do and no further, from the carry and
  from a row of the arena, with and without the reset."""
  core, features = _cores()[name]
  rng = np.random.RandomState(3)
  xs = jnp.asarray(rng.randn(8, features), jnp.float32)
  carry = core.initial_state(1)
  done = jnp.zeros((1,), bool)
  params = core.init(jax.random.PRNGKey(0), carry, xs[:1], done,
                     method=core.step)
  step = jax.jit(lambda carry, x, done: core.apply(
      params, carry, x, done, method=core.step))
  chunk = jax.jit(lambda carry, xs, n, reset, slot=None: core.apply(
      params, carry, xs, n, reset, slot, method=core.chunk))
  with HIGHEST:
    for _ in range(3):  # a past to reset, or to go on from
      carry, _ = step(carry, xs[:1] * 0.5, done)
    for reset in (False, True):
      want, outs = carry, []
      for t in range(5):
        want, out = step(want, xs[t:t + 1],
                         jnp.asarray([reset and t == 0]))
        outs.append(out[0])
      got, got_outs = chunk(carry, xs, jnp.int32(5), jnp.bool_(reset))
      np.testing.assert_allclose(got_outs[:5], jnp.stack(outs), atol=1e-5)
      if name == 'latent':
        # Its carry is compared where it is read: up to the position.
        assert int(got['pos'][0]) == int(want['pos'][0])
        pos = int(got['pos'][0])
        for a, b in zip(got['layers'], want['layers']):
          np.testing.assert_allclose(a[..., :pos], b[..., :pos], atol=1e-5)
      else:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            got, want)
    # From a row of the arena: row 1 is advanced, row 0 is not.
    arena = core.arena(2)
    arena = chunk(arena, xs, jnp.int32(5), jnp.bool_(True),
                  jnp.int32(1))[0]
    again = chunk(core.initial_state(1), xs, jnp.int32(5),
                  jnp.bool_(True))[0]
    rows = jax.tree_util.tree_map(lambda a: a[1:2], arena)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a[:1], b, atol=1e-5),
        rows, again)
    for leaf in jax.tree_util.tree_leaves(arena):
      assert not np.any(np.asarray(leaf[0]))


def test_only_the_latent_core_asks_for_chunks():
  cores = _cores()
  assert cores['lstm'][0].chunk_size == 0
  assert cores['retention'][0].chunk_size == 0
  assert cores['latent'][0].chunk_size == 8
  assert SequenceAgent(num_actions=VOCAB).prefill_chunk == 0
  assert _agent().prefill_chunk == 8 and _agent().cache_capacity == 64


def test_decode_form_is_the_prefill_form_on_the_same_cache():
  """(c): one block, one cache of 37 tokens: the token at position 37
  through the decode form (absorbed, a row of a merged call) and
  through the prefill form (a chunk of one) gives one output."""
  block = latent_moe.LatentMoEBlock(DIMS, 32, HEADS, 48, THETA, 1e-6,
                                    dense=True)
  rng = np.random.RandomState(5)
  cache = jnp.asarray(rng.randn(3, DIMS.cache_width, 64), jnp.float32)
  x = jnp.asarray(rng.randn(1, 32), jnp.float32)
  slots, pos = jnp.array([2]), jnp.array([37])
  live = jnp.array([True])
  params = block.init(jax.random.PRNGKey(1), x, cache, slots, pos, live,
                      None)
  with HIGHEST:
    decode, cache_d = block.apply(params, x, cache, slots, pos, live, None)
    prefill, cache_p = block.apply(
        params, x, cache, slots, pos, live,
        (jnp.int32(2), jnp.int32(37), jnp.int32(1)))
  np.testing.assert_allclose(decode, prefill, atol=1e-4)
  np.testing.assert_array_equal(cache_d, cache_p)
  assert np.any(np.asarray(cache_d[2, :, 37]) != np.asarray(cache[2, :, 37]))
  np.testing.assert_array_equal(cache_d[:2], cache[:2])


def test_kernels_against_plain_numpy():
  """The decode form's two kernels, interpreted: `write_rows` sets one
  column a row and nothing else; `attend_rows` is the softmax over a
  row's own columns `0..pos`, whatever lies beyond them, for rows of
  very different lengths (one block, a partial block, all blocks)."""
  rng = np.random.RandomState(0)
  rows, capacity, width, rank, heads = 5, 64, 20, 16, 4
  cache = rng.randn(rows, width, capacity).astype(np.float32)
  entry = rng.randn(4, width).astype(np.float32)
  slots = np.array([3, 0, 4, 1], np.int32)
  pos = np.array([0, 17, 63, 31], np.int32)
  written = np.asarray(mla_pallas.write_rows(
      jnp.asarray(cache), jnp.asarray(entry), jnp.asarray(slots),
      jnp.asarray(pos)))
  want = cache.copy()
  want[slots, :, pos] = entry
  np.testing.assert_array_equal(written, want)
  q = rng.randn(4, heads, width).astype(np.float32)
  with HIGHEST:
    got = np.asarray(mla_pallas.attend_rows(
        jnp.asarray(q), jnp.asarray(written), jnp.asarray(slots),
        jnp.asarray(pos), rank=rank, block=16))
  for n in range(4):
    own = written[slots[n]][:, :pos[n] + 1].astype(np.float64)
    scores = q[n].astype(np.float64) @ own
    weights = np.exp(scores - scores.max(axis=1, keepdims=True))
    weights /= weights.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(got[n], weights @ own[:rank].T, atol=1e-5)
  # A chunk's window at the capacity's edge: the tokens land on their
  # own columns, none before them moves.
  chunk = jnp.asarray(rng.randn(8, width), jnp.float32)
  edge = np.asarray(core_lib.write_chunk(
      jnp.asarray(cache), chunk, jnp.int32(2), jnp.int32(59), jnp.int32(4),
      jnp.arange(8) < 4))
  want = cache.copy()
  want[2, :, 59:63] = np.asarray(chunk[:4]).T
  np.testing.assert_array_equal(edge, want)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
  """(d), the guide's share test: over the 16 shares of a layer of 16
  routed experts (one expert each), the routed parts summed and the
  shared expert counted once are the uncut reference's whole layer."""
  whole = dataclasses.replace(DIMS, experts_held=16)
  layer = moe.RoutedExperts(whole, 32)
  rng = np.random.RandomState(2)
  x = jnp.asarray(rng.randn(24, 32), jnp.float32)
  live = jnp.ones((24,), bool)
  params = layer.init(jax.random.PRNGKey(3), x, live)['params']
  with HIGHEST:
    weights, _ = reference._router(
        x, params['router']['kernel'], params['e_score_correction_bias'],
        whole)
    ones = jnp.ones((24,))
    shared = reference._ffn_blocks(x, params['shared_expert'], ones,
                                   None, 8)
    uncut = shared + sum(
        reference._ffn_blocks(x, params[f'expert_{e}'], weights[:, e],
                              None, 8) for e in range(16))
    total = jnp.zeros_like(x)
    for share in range(16):
      dims = dataclasses.replace(DIMS, experts_held=1, expert_offset=share)
      mine = {k: v for k, v in params.items()
              if not k.startswith('expert_')}
      mine['expert_0'] = params[f'expert_{share}']
      part = moe.RoutedExperts(dims, 32).apply(
          {'params': mine}, x, live)
      total = total + (part - shared)  # what every chip computes alike
    # Every token's 4 experts were somebody's: nothing is lost.
    assert np.all(np.sum(np.asarray(weights) > 0, axis=1) == 4)
    np.testing.assert_allclose(total + shared, uncut, atol=2e-4)
    # And the program's own uncut layer is the reference's.
    np.testing.assert_allclose(
        layer.apply({'params': params}, x, live), uncut, atol=2e-4)
    # A padded row gets the shared expert and routes nowhere.
    padded = layer.apply({'params': params}, x, ~live)
    np.testing.assert_allclose(padded, shared, atol=2e-4)


def test_routing_by_hand():
  """(e): 8 experts in 4 groups of 2, 2 groups kept, 2 a token."""
  dims = dataclasses.replace(
      DIMS, routed_experts=8, expert_groups=4, expert_groups_kept=2,
      experts_per_token=2, routed_scale=2.5)
  zero = jnp.zeros((8,))
  scores = jnp.asarray([
      # Group 1 holds the largest score of all and next to nothing
      # beside it: a group is judged by its two largest, so groups 0
      # (0.5 + 0.5) and 2 (0.6 + 0.3) beat it (0.8 + 0.01), and the
      # choice is experts 4 and 0 (0.6; then 0.5, the lower of a tie).
      [0.5, 0.5, 0.8, 0.01, 0.6, 0.3, 0.2, 0.2],
      # All alike: ties go to the lower index, groups and experts.
      [0.4] * 8,
  ], jnp.float32)
  chosen, weights = moe.route(scores, zero, dims)
  assert chosen.tolist() == [[4, 0], [0, 1]]
  np.testing.assert_allclose(
      weights, [[2.5 * 0.6 / 1.1, 2.5 * 0.5 / 1.1], [1.25, 1.25]],
      rtol=1e-6)
  # A bias moves the choice and not the weight: +0.2 on expert 5 makes
  # it the second choice of row 0 (0.3 + 0.2 = 0.5 ties expert 0's and
  # loses to the lower index; +0.21 wins), weighed by its own 0.3.
  bias = zero.at[5].set(0.21)
  chosen, weights = moe.route(scores[:1], bias, dims)
  assert chosen.tolist() == [[4, 5]]
  np.testing.assert_allclose(weights, [[2.5 * 0.6 / 0.9, 2.5 * 0.3 / 0.9]],
                             rtol=1e-6)
  chosen, _ = moe.route(scores[:1], zero.at[5].set(0.2), dims)
  assert chosen.tolist() == [[4, 0]]
  # The reference routes the same, and reports how near a choice was.
  kernel = jnp.eye(8)
  logit = lambda s: jnp.log(s) - jnp.log1p(-s)  # noqa: E731
  with HIGHEST:
    ref_weights, margin = reference._router(logit(scores), kernel, zero,
                                            dims)
  np.testing.assert_allclose(
      ref_weights[0], [2.5 * 0.5 / 1.1, 0, 0, 0, 2.5 * 0.6 / 1.1, 0, 0, 0],
      atol=1e-6)
  assert ref_weights[1].tolist()[2:] == [0.0] * 6
  # Both rows chose at a tie: no margin at all.
  assert margin.tolist() == [0.0, 0.0]
  # With the bias the groups rank 1.11, 1.0 | 0.81 (margin 0.19) and
  # the experts 0.6, 0.51 | 0.5 (margin 0.01): the smaller counts.
  with HIGHEST:
    ref_weights, margin = reference._router(logit(scores[:1]), kernel,
                                            bias, dims)
  assert np.flatnonzero(ref_weights[0]).tolist() == [4, 5]
  np.testing.assert_allclose(margin, [0.01], atol=1e-6)
  # A share that holds experts 6 and 7 (group 3, never in the choice)
  # computes nothing for these rows whichever way the ties fall.
  elsewhere = dataclasses.replace(dims, expert_offset=6, experts_held=2)
  with HIGHEST:
    _, margin = reference._router(logit(scores), kernel, zero, elsewhere)
  assert np.all(np.isinf(margin))


def test_yarn_frequencies_by_hand():
  """(f): the published rotary at two positions past the original
  4,096. dim 64, theta 10,000, factor 40: pairs 0..10 turn often
  enough to keep their frequency, pairs 23..31 are stretched 40-fold,
  between them the ramp (j - 10) / 13."""
  dims = dataclasses.replace(DIMS, qk_rope_head_dim=64)
  # The correction dimensions: 64 ln(4096 / (2 pi b)) / (2 ln 10000).
  assert math.floor(64 * math.log(4096 / (2 * math.pi * 32)) /
                    (2 * math.log(1e4))) == 10
  assert math.ceil(64 * math.log(4096 / (2 * math.pi * 1)) /
                   (2 * math.log(1e4))) == 23
  by_hand = {0: 1.0, 10: 1e4 ** (-20 / 64),
             16: 1e4 ** (-32 / 64) * (1 - 6 / 13 + 6 / 13 / 40),
             23: 1e4 ** (-46 / 64) / 40, 31: 1e4 ** (-62 / 64) / 40}
  np.testing.assert_allclose(by_hand[16], 0.0055, rtol=1e-9)
  for freq in (latent_moe.yarn_inv_freq(dims, 1e4),
               reference.inv_freq(dims, 1e4)):
    assert freq.shape == (32,) and freq.dtype == np.float32
    for j, want in by_hand.items():
      np.testing.assert_allclose(freq[j], want, rtol=1e-6)
  # Unit vectors along pair 16 and pair 31, turned at 5,000 and 12,345:
  # (cos, sin) of position x frequency, magnitude mscale(40, 1) /
  # mscale(40, 1) = 1.
  x = np.zeros((2, 64), np.float32)
  x[0, 16] = x[1, 31] = 1.0
  for rotate in (lambda x, pos: latent_moe.rotate(x, pos, dims, 1e4),
                 lambda x, pos: reference._rope(x, pos, dims, 1e4)):
    for pos in (5000, 12345):
      got = np.asarray(rotate(jnp.asarray(x), jnp.array([pos, pos])))
      for row, j in ((0, 16), (1, 31)):
        ang = np.float32(pos) * np.float32(by_hand[j])
        np.testing.assert_allclose(
            [got[row, j], got[row, j + 32]],
            [math.cos(ang), math.sin(ang)], atol=2e-5)
        assert abs(np.sum(got[row] ** 2) - 1.0) < 1e-5
  # The softmax scale: 192^-1/2 m^2, m = 0.1 ln 40 + 1.
  full = dataclasses.replace(DIMS, qk_nope_head_dim=128,
                             qk_rope_head_dim=64)
  m = 0.1 * math.log(40) + 1
  assert abs(m - 1.3689) < 1e-4
  assert abs(latent_moe.softmax_scale(full) - 192 ** -0.5 * m * m) < 1e-12



"""The sequence policy's core of Mamba-2 layers beside full attention
layers (Granite 4.0-H's hybrid): the mixer's decode step against its
chunk form from a nonzero state, across a block's, a chunk's and the
conv window's boundaries; both forms of the whole agent against the
plain reference's full forward; `done` in mid-episode; padded rows; the
state kernel against plain numpy; the softmax router's share of a
deployment; the sigmoid router as it was. (The inference server's side:
tests/test_ssm_serving.py.)

Everything runs at a tiny size in float32 on the CPU: hidden 32, 4
query heads in 2 key-value groups of 16, three layers `MMG`, Mamba-2
layers of 4 heads of 8 by a state of 128 and a conv of 4, a chunk of 8
in blocks of 4, 16 routed experts of 16 in one group, 4 a token by
softmax, a shared expert of 32, a vocabulary of 97.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import (HybridAttentionDims, LatentMoEDims,
                                      SSMHybridDims, SSMHybridStack,
                                      SequenceAgent, init_params)
from scalable_agent_tpu.models import mamba2, moe
from scalable_agent_tpu.models import ssm_hybrid_reference as reference
from scalable_agent_tpu.ops import ssm_pallas
from scalable_agent_tpu.structs import StepOutput

VOCAB = 97
HEADS = 4
EPS = 1e-5
TOKEN_OBS = {'leaves': (((), np.int32),)}
HIGHEST = jax.default_matmul_precision('highest')
DIMS = SSMHybridDims(
    moe_size=16, routed_experts=16, experts_held=16, experts_per_token=4,
    expert_groups=1, expert_groups_kept=1, routed_scale=1.0,
    shared_experts=2, router='softmax', embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=2.0)


def _agent(dims=DIMS, layers=3):
  return SequenceAgent(num_actions=VOCAB, num_layers=layers, hidden_size=32,
                       num_heads=HEADS, norm_eps=EPS, core_dims=dims)


def _params(agent, seed=0):
  return init_params(agent, jax.random.PRNGKey(seed), TOKEN_OBS)


def _reference(params, tokens, dims=DIMS, **kw):
  return reference.forward(
      params, tokens, np.zeros_like(tokens), dims=dims, num_heads=HEADS,
      norm_eps=EPS, block=8, logits=True, **kw)[3]


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
    for lo in range(0, max(len(tokens), 1), 8):
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


@pytest.fixture(scope='module')
def served():
  """(agent, params, prefill, step) of the tiny agent, compiled once for
  an arena of 3 slots and shared by the tests of both forms."""
  agent = _agent()
  params = _params(agent)
  return (agent, params) + _serve(agent, params)


def test_the_core_the_widths_name():
  agent = _agent()
  assert isinstance(agent.core(), SSMHybridStack)
  assert (agent.prefill_chunk, agent.cache_capacity, agent.cache_window) == (
      8, 64, 0)
  assert agent.call_counters == moe.COUNTERS + (mamba2.COUNTER,)
  # A Mamba layer's two leaves (the conv window's last 3 inputs of 4 x 8
  # + 2 x 128 channels, the state), a full layer's cache of 64 columns.
  state = jax.eval_shape(lambda: agent.initial_state(1))
  assert [jax.tree_util.tree_map(lambda l: l.shape, leaf)
          for leaf in state['layers']] == [
              ((1, 3, 288), (1, 4, 8, 128)), ((1, 3, 288), (1, 4, 8, 128)),
              (1, 64, 64)]
  assert state['layers'][0][1].dtype == jnp.float32
  arena = jax.eval_shape(lambda: agent.state_arena(3))
  assert arena['layers'][1][1].shape == (4, 4, 8, 128)
  assert arena['pos'].shape == (4,)
  # The tied head: no matrix of its own.
  params = jax.eval_shape(lambda: _params(agent))['params']
  assert 'policy_logits' not in params and 'embedding' in params
  for wrong in (dict(layer_pattern='MLG'), dict(layer_pattern=''),
                dict(cache_capacity=1500), dict(router='sum'),
                dict(expert_groups=2, expert_groups_kept=1),
                dict(prefill_chunk=6)):
    with pytest.raises(ValueError):
      dataclasses.replace(DIMS, **wrong).check()
  DIMS.check()


class _Mixer(nn.Module):
  """The mixer alone over a carry of its own two leaves, in one row."""
  dims: Any

  @nn.compact
  def __call__(self, h, conv, ssm, pos, prefill):
    live = jnp.ones(h.shape[:1], bool) if prefill is None else (
        jnp.arange(h.shape[0]) < prefill[2])
    return mamba2.Mamba2Mixer(self.dims, 32, EPS, name='mamba')(
        h, conv, ssm, jnp.zeros(h.shape[:1], jnp.int32), pos, live,
        prefill)


def test_decode_step_and_chunk_form_agree_from_a_nonzero_state():
  """From a seeded nonzero conv window and state at position 5: 19
  tokens as three chunks of 8 (blocks of 4 inside each; the last chunk
  ragged, its padding left out) against the same 19 one decode step
  each: every output and both leaves after every chunk agree."""
  mixer = _Mixer(DIMS)
  rng = np.random.RandomState(4)
  h = jnp.asarray(rng.randn(24, 32), jnp.float32)
  conv = jnp.asarray(rng.randn(1, 3, 288), jnp.float32)
  ssm = jnp.asarray(rng.randn(1, 4, 8, 128), jnp.float32)
  params = mixer.init(jax.random.PRNGKey(1), h[:1], conv, ssm,
                      jnp.array([5]), None)
  step = jax.jit(lambda h, conv, ssm, pos: mixer.apply(
      params, h, conv, ssm, pos, None))
  chunk = jax.jit(lambda h, conv, ssm, pos0, n: mixer.apply(
      params, h, conv, ssm, pos0 + jnp.arange(8), (0, pos0, n)))
  with HIGHEST:
    stepped, c1, s1 = [], conv, ssm
    for t in range(19):
      out, c1, s1 = step(h[t:t + 1], c1, s1, jnp.array([5 + t]))
      stepped.append(out[0])
      if t in (7, 15):
        mid = (c1, s1)
    chunked, c2, s2 = [], conv, ssm
    for lo, n in ((0, 8), (8, 8), (16, 3)):
      block = h[lo:lo + 8]
      out, c2, s2 = chunk(block, c2, s2, jnp.int32(5 + lo), jnp.int32(n))
      chunked += list(out[:n])
      if lo == 8:
        np.testing.assert_allclose(c2, mid[0], atol=1e-6)
        np.testing.assert_allclose(s2, mid[1], rtol=1e-5, atol=1e-5)
  np.testing.assert_allclose(np.stack(chunked), np.stack(stepped),
                             rtol=1e-5, atol=1e-5)
  np.testing.assert_allclose(c2, c1, atol=1e-6)
  np.testing.assert_allclose(s2, s1, rtol=1e-5, atol=1e-5)
  # The padding neither decayed nor added: one more step from either
  # pair of leaves is the same step.
  with HIGHEST:
    a = step(h[20:21], c1, s1, jnp.array([24]))
    b = step(h[20:21], c2, s2, jnp.array([24]))
  np.testing.assert_allclose(a[0], b[0], rtol=1e-5, atol=1e-5)
  # At position 0 both forms begin from zero, whatever the leaves hold.
  with HIGHEST:
    fresh = step(h[:1], conv, ssm, jnp.array([0]))
    zero = step(h[:1], jnp.zeros_like(conv), jnp.zeros_like(ssm),
                jnp.array([0]))
    first = chunk(h[:8], conv, ssm, jnp.int32(0), jnp.int32(1))
  np.testing.assert_array_equal(fresh[0], zero[0])
  np.testing.assert_allclose(first[0][0], fresh[0][0], rtol=1e-5, atol=1e-5)


def test_step_chunk_and_reference_agree_over_an_episode_with_a_reset(
    served):
  """An episode of 37 tokens: a prompt of 21 in chunks of 8 (a ragged
  last one) then steps through the arena with a padded row; the
  reference's logits. Then `done` in mid-stream: what follows is the
  reference's forward of the new episode alone."""
  agent, params, prefill, step = served
  rng = np.random.RandomState(7)
  tokens = rng.randint(VOCAB, size=37).astype(np.int32)
  after = rng.randint(VOCAB, size=9).astype(np.int32)
  with HIGHEST:
    ref = np.asarray(_reference(params, tokens))
    ref_after = np.asarray(_reference(params, after))
    arena = prefill(agent.state_arena(3), tokens[:21], 1)
    assert list(np.asarray(arena['pos'])) == [0, 21, 0, 0]
    served = []
    for t in range(21, 37):
      out, arena = step(arena, [tokens[t], 5], [1, 1 << 30])
      served.append(out[0])
    np.testing.assert_allclose(np.stack(served), ref[21:], atol=2e-4)
    served = []
    for t, token in enumerate(after):
      out, arena = step(arena, [token, 5], [1, 1 << 30], (t == 0, False))
      served.append(out[0])
    np.testing.assert_allclose(np.stack(served), ref_after, atol=2e-4)
  assert list(np.asarray(arena['pos'])) == [0, 9, 0, 0]
  # The other sessions' rows were never touched; the padded rows' row,
  # beyond the last slot, took what the padded rows wrote.
  for leaf in jax.tree_util.tree_leaves(arena['layers']):
    assert not np.any(np.asarray(leaf[0])) and not np.any(
        np.asarray(leaf[2]))
    assert np.any(np.asarray(leaf[3]))


def test_done_in_mid_episode_zeroes_the_mamba_leaves(served):
  """A session deep in its episode takes `done` with a token: its Mamba
  leaves after that step are those of an empty session's first step
  with the same token, to the bit; the full layer's cache keeps the old
  episode's columns (only the position restarts)."""
  agent, _, prefill, step = served
  rng = np.random.RandomState(9)
  with HIGHEST:
    old = prefill(agent.state_arena(3), rng.randint(VOCAB, size=19), 0)
    old = step(old, [3, 4], [0, 1 << 30])[1]
    _, done = step(old, [11, 4], [0, 1 << 30], (True, False))
    _, fresh = step(agent.state_arena(3), [11, 4], [0, 1 << 30],
                    (True, False))
  for i in (0, 1):  # the M layers
    for got, want, before in zip(done['layers'][i], fresh['layers'][i],
                                 old['layers'][i]):
      assert np.any(np.asarray(before[0]))
      np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
  cache = np.asarray(done['layers'][2][0])
  np.testing.assert_array_equal(cache[:, 1:20],
                                np.asarray(old['layers'][2][0])[:, 1:20])
  assert int(done['pos'][0]) == 1


def test_padded_rows_touch_no_live_row(served):
  """A call of one live row and one padded row against the same call
  with another token in the padded row: the live row's logits and
  every session's rows are the same to the bit."""
  agent, _, prefill, step = served
  rng = np.random.RandomState(3)
  with HIGHEST:
    arena = prefill(agent.state_arena(3), rng.randint(VOCAB, size=13), 0)
    arena = prefill(arena, rng.randint(VOCAB, size=6), 1)
    a, arena_a = step(arena, [7, 11], [0, 1 << 30])
    b, arena_b = step(arena, [7, 90], [0, 1 << 30])
  np.testing.assert_array_equal(a[0], b[0])
  for x, y, before in zip(jax.tree_util.tree_leaves(arena_a['layers']),
                          jax.tree_util.tree_leaves(arena_b['layers']),
                          jax.tree_util.tree_leaves(arena['layers'])):
    np.testing.assert_array_equal(x[:2], y[:2])
    np.testing.assert_array_equal(x[1], before[1])


def test_state_kernel_against_plain_numpy():
  """`update_rows`, interpreted: the named rows decayed by their heads'
  own factors (0: the row restarts) plus the rank-1 input, `y = S C`,
  in blocks of 32 heads; the rows no call names untouched."""
  rng = np.random.RandomState(0)
  rows, heads, dim, size = 5, 64, 16, 128
  state = rng.randn(rows, heads, dim, size).astype(np.float32)
  slots = np.array([2, 0, 4], np.int32)
  decay = rng.rand(3, heads).astype(np.float32)
  decay[1] = 0
  u = rng.randn(3, heads, dim).astype(np.float32)
  b = rng.randn(3, size).astype(np.float32)
  c = rng.randn(3, size).astype(np.float32)
  got, y = ssm_pallas.update_rows(jnp.asarray(state), jnp.asarray(slots),
                                  *map(jnp.asarray, (decay, u, b, c)))
  want = state.copy()
  want[slots] = (decay[:, :, None, None] * state[slots] +
                 u[..., None] * b[:, None, None, :])
  np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
  np.testing.assert_array_equal(np.asarray(got)[[1, 3]], state[[1, 3]])
  np.testing.assert_allclose(
      np.asarray(y), np.einsum('bhpn,bn->bhp', want[slots], c), atol=1e-4)


def test_the_eight_shares_add_up_to_the_uncut_layer():
  """The guide's share test under the softmax router: over the 8 shares
  of a layer of 16 routed experts (2 each), the routed parts summed and
  the shared expert counted once are the uncut reference's whole
  layer."""
  layer = moe.RoutedExperts(DIMS, 32)
  rng = np.random.RandomState(2)
  x = jnp.asarray(rng.randn(24, 32), jnp.float32)
  live = jnp.ones((24,), bool)
  params = layer.init(jax.random.PRNGKey(3), x, live)['params']
  assert 'e_score_correction_bias' not in params
  with HIGHEST:
    weights, margin = reference._router(x, params['router']['kernel'], DIMS)
    ones = jnp.ones((24,))
    shared = reference._ffn_blocks(x, params['shared_expert'], ones,
                                   None, 8)
    uncut = shared + sum(
        reference._ffn_blocks(x, params[f'expert_{e}'], weights[:, e],
                              None, 8) for e in range(16))
    total = jnp.zeros_like(x)
    for share in range(8):
      dims = dataclasses.replace(DIMS, experts_held=2,
                                 expert_offset=2 * share)
      mine = {k: v for k, v in params.items()
              if not k.startswith('expert_')}
      mine.update({f'expert_{e}': params[f'expert_{2 * share + e}']
                   for e in range(2)})
      part = jax.jit(moe.RoutedExperts(dims, 32).apply)(
          {'params': mine}, x, live)
      total = total + (part - shared)  # what every chip computes alike
    # Every token's 4 experts were somebody's, weighed by a softmax.
    w = np.asarray(weights)
    assert np.all(np.sum(w > 0, axis=1) == 4)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    assert np.all(np.asarray(margin) > 0) and np.all(np.isfinite(margin))
    np.testing.assert_allclose(total + shared, uncut, atol=2e-4)
    np.testing.assert_allclose(
        layer.apply({'params': params}, x, live), uncut, atol=2e-4)
    # The choice is the plain top-4 of the logits.
    logits = x @ params['router']['kernel']
    chosen, gates = moe.route_softmax(logits, DIMS)
    top = np.argsort(-np.asarray(logits), axis=1)[:, :4]
    assert np.array_equal(np.asarray(chosen), top)
    np.testing.assert_allclose(
        gates, jax.nn.softmax(np.take_along_axis(np.asarray(logits), top,
                                                 axis=1)), rtol=1e-6)


class _SigmoidRoutedExperts(nn.Module):
  """The routed layer as it was before the softmax router came, copied
  from models/moe.py: the sigmoid path has to stay this, to the bit."""
  dims: Any
  hidden_size: int
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32
  x_square: float = 1.0

  @nn.compact
  def __call__(self, x, live):
    d = self.dims
    with jax.named_scope('moe'):
      with jax.named_scope('router'):
        w_g = moe._Kernel((self.hidden_size, d.routed_experts),
                          self.param_dtype, 1.0 / self.x_square,
                          name='router')()
        bias = self.param('e_score_correction_bias',
                          nn.initializers.normal(0.02),
                          (d.routed_experts,), jnp.float32)
        scores = jax.nn.sigmoid(jnp.dot(
            x, w_g.astype(jnp.float32), precision=moe.HIGHEST))
        chosen, weights = moe.route(scores, bias, d)
        local = chosen - d.expert_offset
        held = (local >= 0) & (local < d.experts_held) & live[:, None]
        gates = jnp.sum(
            jax.nn.one_hot(local, d.experts_held, dtype=jnp.float32) *
            jnp.where(held, weights, 0.0)[..., None], axis=1)
        hit = jnp.any(gates > 0, axis=0)
        self.sow('counters', 'routed_rows_held',
                 jnp.sum(held).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
        self.sow('counters', 'experts_hit',
                 jnp.sum(hit).astype(jnp.int32),
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((), jnp.int32))
      with jax.named_scope('shared'):
        y = moe.ffn(x, moe._FFNWeights(self.hidden_size,
                                       d.moe_size * d.shared_experts,
                                       self.param_dtype,
                                       name='shared_expert')(), self.dtype)
      with jax.named_scope('experts'):
        for e in range(d.experts_held):
          expert = moe._FFNWeights(self.hidden_size, d.moe_size,
                                   self.param_dtype, name=f'expert_{e}')()
          y = y + jax.lax.cond(
              hit[e],
              lambda w=expert, g=gates[:, e]: moe.ffn(x, w, self.dtype) *
              g[:, None],
              lambda: jnp.zeros_like(x))
    return y


@pytest.mark.parametrize('dims,hidden', [
    # The rehearsal's tiny dims of `dotsvlm1.decode32_ctx8k` (three
    # groups, two kept) and of `kexaone.decode32_ctx24k` (one group).
    (LatentMoEDims(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                   qk_rope_head_dim=4, v_head_dim=8, first_dense_layers=1,
                   moe_size=32, routed_experts=24, experts_held=4,
                   experts_per_token=4, expert_groups=3,
                   expert_groups_kept=2), 64),
    (HybridAttentionDims(num_kv_heads=2, head_dim=16, moe_size=32,
                         routed_experts=16, experts_held=4,
                         experts_per_token=4, expert_groups=1,
                         expert_groups_kept=1), 32)],
    ids=['ctx8k', 'ctx24k'])
def test_the_sigmoid_router_is_the_same_to_the_bit(dims, hidden):
  """Parameters, outputs and counters of the routed layer under the
  sigmoid router against the layer as it was, with the x_square both
  cores pass."""
  x = jnp.asarray(np.random.RandomState(5).randn(32, hidden), jnp.float32)
  live = jnp.arange(32) < 29
  new = moe.RoutedExperts(dims, hidden, x_square=6.0)
  old = _SigmoidRoutedExperts(dims, hidden, x_square=6.0)
  params = new.init(jax.random.PRNGKey(8), x, live)
  was = old.init(jax.random.PRNGKey(8), x, live)
  assert jax.tree_util.tree_all(jax.tree_util.tree_map(
      lambda a, b: np.array_equal(a, b), params, was))
  y, sown = new.apply(params, x, live, mutable=['counters'])
  y_was, sown_was = old.apply(params, x, live, mutable=['counters'])
  np.testing.assert_array_equal(np.asarray(y), np.asarray(y_was))
  assert jax.tree_util.tree_all(jax.tree_util.tree_map(
      lambda a, b: np.array_equal(a, b), sown, sown_was))


def test_a_bfloat16_state_shows_in_the_reference(served):
  """The reference's options: a state held in bfloat16 moves the logits
  by far more than the chunk form's reassociation does."""
  agent, params, prefill, step = served
  tokens = np.random.RandomState(1).randint(VOCAB, size=40).astype(np.int32)
  with HIGHEST:
    exact = np.asarray(_reference(params, tokens))
    lower = np.asarray(_reference(params, tokens, state_dtype=jnp.bfloat16))
    arena = prefill(agent.state_arena(3), tokens[:32], 0)
    served = [step(arena, [tokens[32], 0], [0, 1 << 30])[0][0]]
  own = np.abs(served[0] - exact[32]).max()
  assert own < 1e-4 < np.abs(lower - exact).max()

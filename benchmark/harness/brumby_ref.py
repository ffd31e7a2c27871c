"""The benchmark's own copy of the sequence policy's plain reference
(the program's is scalable_agent_tpu/models/retention_reference.py; a
test holds the two to the same text below this paragraph): kept here so
that a PR which changes the program cannot change what `correct` is
decided against.

The plain reference of the sequence policy's forward pass: float32
jax.numpy, no kernel, no state cache, no batching; power retention in
ATTENTION form over a whole recorded stretch of one session.

For a session's tokens `x_0..x_{T-1}` with `done_t` marking the first
step of an episode, per layer and key-value head `j` (query heads `i`
of its group):

    o_{i,t} = sum_s W_ts v_s / (sum_s W_ts + eps)
    W_ts    = G_ts (q_{i,t} . k_{j,s})^2   for s <= t in t's episode
    G_ts    = prod_{r=s+1..t} g_{j,r}

with q and k after per-head RMSNorm, RoPE at the step's position in
its episode and the scale `head_dim^-1/4` each; then the block's
output projection, the SwiGLU MLP, the final norm, `log softmax` over
the vocabulary (in column blocks, so 151,936 columns fit beside the
weights) and the value head. Everything is computed with
`jax.default_matmul_precision('highest')` by the caller's choice of
`precision`; the parameters are used as given (the served copy's
bfloat16-rounded values, widened to float32 a layer at a time).

`operand_dtype` is the configuration's stated precision of the
projections' operands: where the served model rounds an activation to
bfloat16 before a matrix product, so does the reference, by the same
round-to-nearest; all arithmetic stays float32. None rounds nothing.

`forward_recurrent` is the same model in its recurrent form with the
state held in `state_dtype`: float32 it must agree with `forward`
(tests/test_sequence.py); bfloat16 it is the nearest precision BELOW
the one the configuration states, which the benchmark's tolerances
must reject (PERF.md section 6).

The first step of a stretch must start an episode (`dones[0]`): the
state before it is then zero whatever came earlier.
"""

import functools

import jax
import jax.numpy as jnp

EPS = 1e-2  # as models/retention.py states it, with its reason
HIGHEST = jax.lax.Precision.HIGHEST


def _rounded(x, operand_dtype):
  """x rounded to `operand_dtype`'s precision, kept in float32. By
  `reduce_precision`, which the compiler may not take out: a cast there
  and back is dropped on a TPU as excess precision allowed."""
  if operand_dtype is None:
    return x
  info = jnp.finfo(operand_dtype)
  return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _project(x, kernel, operand_dtype):
  return jnp.dot(_rounded(x, operand_dtype), kernel.astype(jnp.float32),
                 precision=HIGHEST)


def _rms_norm(x, scale, eps):
  var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
  return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
  """x [T, heads, D] at positions pos [T]: the half-rotation form."""
  d = x.shape[-1]
  inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
  cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
  sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
  x1, x2 = x[..., :d // 2], x[..., d // 2:]
  return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _positions(dones):
  """Steps since the episode began, and the episode's index."""
  t = jnp.arange(dones.shape[0])
  start = jax.lax.cummax(jnp.where(dones, t, 0), axis=0)
  return t - start, jnp.cumsum(dones.astype(jnp.int32))


def _qkvg(block, x, pos, dims, operand_dtype):
  heads, kv, hd, theta, eps = dims
  t = x.shape[0]
  h = _rms_norm(x, block['input_norm']['scale'], eps)
  proj = lambda name: _project(  # noqa: E731
      h, block[name]['kernel'], operand_dtype)
  scale = hd ** -0.25
  q = _rope(_rms_norm(proj('q_proj').reshape(t, heads, hd),
                      block['q_norm']['scale'], eps), pos, theta) * scale
  k = _rope(_rms_norm(proj('k_proj').reshape(t, kv, hd),
                      block['k_norm']['scale'], eps), pos, theta) * scale
  v = proj('v_proj').reshape(t, kv, hd)
  gate = jax.nn.sigmoid(proj('g_proj'))              # [T, KV]
  return q.reshape(t, kv, heads // kv, hd), k, v, gate


def _attention_form(q, k, v, gate, episode):
  """q [T, KV, G, D], k, v [T, KV, D], gate [T, KV] -> o [T, KV, G, D]."""
  t = q.shape[0]
  log_g = jnp.cumsum(jnp.log(gate), axis=0)          # [T, KV]
  steps = jnp.arange(t)
  inside = ((steps[:, None] >= steps[None, :]) &
            (episode[:, None] == episode[None, :]))  # [t, s]
  decay = jnp.where(inside[:, :, None],
                    jnp.exp(jnp.where(inside[:, :, None],
                                      log_g[:, None] - log_g[None, :],
                                      0.0)), 0.0)   # [t, s, KV]
  qk = jnp.einsum('tjgd,sjd->tsjg', q, k, precision=HIGHEST)
  w = jnp.square(qk) * decay[..., None]              # [t, s, KV, G]
  num = jnp.einsum('tsjg,sjd->tjgd', w, v, precision=HIGHEST)
  return num / (jnp.sum(w, axis=1)[..., None] + EPS)


def _phi(a):
  """The symmetric degree-2 embedding by the upper triangle:
  D (D + 1) / 2 terms, phi(a) . phi(b) = (a . b)^2."""
  d = a.shape[-1]
  i, j = jnp.triu_indices(d)
  weight = jnp.where(i == j, 1.0, jnp.sqrt(2.0))
  return a[..., i] * a[..., j] * weight


def _recurrent_form(q, k, v, gate, dones, state_dtype):
  kv, groups, hd = q.shape[1:]
  m = hd * (hd + 1) // 2

  def step(carry, x):
    s, z = carry
    q_t, k_t, v_t, g_t, done = x
    g_t = g_t * (1.0 - done.astype(jnp.float32))
    phi_k = _phi(k_t)                                # [KV, M]
    s = (g_t[:, None, None] * s.astype(jnp.float32) +
         phi_k[:, :, None] * v_t[:, None, :]).astype(state_dtype)
    z = (g_t[:, None] * z.astype(jnp.float32) + phi_k).astype(state_dtype)
    phi_q = _phi(q_t)                                # [KV, G, M]
    num = jnp.einsum('jgm,jmd->jgd', phi_q, s.astype(jnp.float32),
                     precision=HIGHEST)
    den = jnp.einsum('jgm,jm->jg', phi_q, z.astype(jnp.float32),
                     precision=HIGHEST)
    return (s, z), num / (den[..., None] + EPS)

  init = (jnp.zeros((kv, m, hd), state_dtype),
          jnp.zeros((kv, m), state_dtype))
  _, o = jax.lax.scan(step, init, (q, k, v, gate, dones))
  return o


@jax.jit
def _head_block(n, columns, lo, actions, lse, picked):
  logits = jnp.dot(n, columns.astype(jnp.float32), precision=HIGHEST)
  width = columns.shape[1]
  here = (actions >= lo) & (actions < lo + width)
  column = jnp.clip(actions - lo, 0, width - 1)
  return (jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1)),
          jnp.where(here, jnp.take_along_axis(
              logits, column[:, None], axis=1)[:, 0], picked))


def _log_probs(n, kernel, actions, operand_dtype, vocab_block):
  """log softmax(n @ kernel)[actions], the columns taken a block at a
  time."""
  n = _rounded(n, operand_dtype)
  lse = jnp.full((n.shape[0],), -jnp.inf, jnp.float32)
  picked = jnp.zeros((n.shape[0],), jnp.float32)
  for lo in range(0, kernel.shape[1], vocab_block):
    lse, picked = _head_block(n, kernel[:, lo:lo + vocab_block], lo,
                              actions, lse, picked)
  return picked - lse


@functools.partial(jax.jit, static_argnames=(
    'dims', 'operand_dtype', 'state_dtype'))
def _block(block, x, pos, episode, dones, dims, operand_dtype,
           state_dtype):
  """One block over the whole stretch, x [T, hidden] -> [T, hidden].
  Jitted by itself: a layer's parameters are widened to float32 while
  it runs and no longer, so the reference fits beside the served model
  (11.5 GB if all were widened at once). `state_dtype` None is the
  attention form."""
  heads, _, hd = dims[:3]
  t = x.shape[0]
  q, k, v, gate = _qkvg(block, x, pos, dims, operand_dtype)
  if state_dtype is None:
    o = _attention_form(q, k, v, gate, episode)
  else:
    o = _recurrent_form(q, k, v, gate, dones, state_dtype)
  x = x + _project(o.reshape(t, heads * hd), block['o_proj']['kernel'],
                   operand_dtype)
  n = _rms_norm(x, block['post_norm']['scale'], dims[4])
  act = jax.nn.silu(_project(
      n, block['gate_proj']['kernel'], operand_dtype)) * _project(
          n, block['up_proj']['kernel'], operand_dtype)
  return x + _project(act, block['down_proj']['kernel'], operand_dtype)


def _forward(params, tokens, dones, actions, state_dtype, num_heads,
             num_kv_heads, head_dim, rope_theta, norm_eps, operand_dtype,
             vocab_block):
  p = params['params']
  dones = jnp.asarray(dones, bool)
  pos, episode = _positions(dones)
  dims = (num_heads, num_kv_heads, head_dim, float(rope_theta),
          float(norm_eps))
  x = jnp.take(p['embedding'], jnp.asarray(tokens), axis=0).astype(
      jnp.float32)
  for i in range(len(p['core'])):
    x = _block(p['core'][f'block_{i}'], x, pos, episode, dones, dims,
               operand_dtype, state_dtype)
  n = _rms_norm(x, p['final_norm']['scale'], norm_eps)
  baseline = (jnp.dot(n, p['baseline']['kernel'].astype(jnp.float32),
                      precision=HIGHEST)[:, 0] +
              p['baseline']['bias'].astype(jnp.float32)[0])
  log_probs = _log_probs(n, p['policy_logits']['kernel'],
                         jnp.asarray(actions), operand_dtype,
                         vocab_block or p['policy_logits']['kernel'].shape[1])
  return log_probs, baseline


def forward(params, tokens, dones, actions, *, num_heads, num_kv_heads,
            head_dim, rope_theta=1e6, norm_eps=1e-6, operand_dtype=None,
            vocab_block=None):
  """One session's stretch in attention form.

  params: the agent's parameter tree; tokens i32 [T] as fed; dones
  bool [T] (`dones[0]` set); actions i32 [T] as taken.
  Returns (log pi(actions) f32 [T], baseline f32 [T]). Call it outside
  `jax.jit`: it runs a layer, and a block of the head's columns, at a
  time.
  """
  return _forward(params, tokens, dones, actions, None, num_heads,
                  num_kv_heads, head_dim, rope_theta, norm_eps,
                  operand_dtype, vocab_block)


def forward_recurrent(params, tokens, dones, actions, *, num_heads,
                      num_kv_heads, head_dim, rope_theta=1e6,
                      norm_eps=1e-6, operand_dtype=None, vocab_block=None,
                      state_dtype=jnp.float32):
  """The same in recurrent form, the state held in `state_dtype`."""
  return _forward(params, tokens, dones, actions, jnp.dtype(state_dtype),
                  num_heads, num_kv_heads, head_dim, rope_theta, norm_eps,
                  operand_dtype, vocab_block)

"""The serving path for a policy whose state grows with its episode:
`serve_loop.py`'s run (driver.play, seeded parameters in the
configuration's dtype, process-hosted token envs -> one actor group ->
C++ batcher -> inference server with the state cache) where every
episode begins with a PROMPT OF THOUSANDS OF TOKENS that the actor
hands to the server as a block and the server takes in chunks
(`InferenceServer.prefill`, program `jit_prefill_chunk`), and every
later token is one row of a merged call (`jit_cache_step`). Nothing
here names a cell: the model and the traffic are the files'.

The recorder, the seams, the caller's clock and the trace's scope line
are `serve_loop.py`'s, imported; what differs is here:

- the window opens `warm_calls` merged calls after the server has
  counted every session's prompt tokens, so all prefill is set-up and
  the window is decode alone (a session that reaches its episode's end
  inside the window begins again through the same path: a reset and
  its prefill are then in the window);
- the server's counters are read once more around the traced slice,
  which follows the window at longer contexts: the per-layer metrics
  that divide counted work by traced time take both from the slice;
- the traced slice's operations that carry no scope path (the
  compiler's prefetch copies of the weights, a quarter of a decode
  step's device time) take the path of what they feed
  (harness/trace_enclosure.py), so that a scope's time holds the
  reading of its own weights;
- the seams also record every block handed over (slot, the call it
  came before, the tokens) and which slot each row of a call is.

`correct`: for the sessions with the shortest and the longest prompt,
what the timed path returned (log mu(action), baseline) against the
plain reference (harness/dots_ref.py: prefill form, no cache, float32,
highest matmul precision, the served parameters widened a matrix at a
time, the head in vocabulary blocks) recomputing the session's whole
last episode from the recorded prompt, tokens and forced actions, after
the arena is released; compared on the first `check_steps / 2` decode
steps after the prompt and the last `check_steps / 2` before the run
ended. Every compared step is held to the two tolerances. A top-k
choice is discontinuous, though: the reference reports, per step, the
smallest margin by which any of its routers' choices that reach this
share of the experts was made, and a step that is out of tolerance AND
under ROUTING_MARGIN (by the operands' precision) is excused as a
near-tie that fell the other way in the served model (counted, printed,
at most EXCUSED_LIMIT of the compared steps); a step out of tolerance
with a wider margin fails the run. Further: every merged call of the window carried the whole
fleet's rows; nothing compiled in the window (run.py); no child opened
the chip, none was left. `failed` = sheds, respawns, quarantines, chain
recoveries.

With DOTS_REFERENCE_READINGS set in the environment the run also prints
every compared step (margin, differences) and the same comparison with
the reference's cache held in float8 (e4m3), the nearest precision
below the configuration's bfloat16: the two readings each tolerance
lies between (PERF.md section 6, PR 32).

Traffic parameters: `warm_calls`, `trace_seconds`, `check_sessions`
(rows of a call the recorder keeps: all of them), `check_steps`.
"""

import collections
import gc
import os
import threading
import time

import jax
import numpy as np

from benchmark.drivers import serve_loop
from benchmark.harness import (correct, dots_ref, processes,
                               trace_enclosure)
from scalable_agent_tpu import driver
from scalable_agent_tpu.envs import factory
from scalable_agent_tpu.models import init_params
from scalable_agent_tpu.runtime import py_process

VOCAB_BLOCK = 4096     # columns of the head the reference takes at once
REFERENCE_BLOCK = 128  # queries, and tokens of a feed-forward, at once

# The limits of `correct`, each between two readings on the chip at the
# published widths (PERF.md section 6, PR 32, has the runs: 8 runs,
# 6,000 compared steps). A step whose routing did not flip differs from
# the reference by the size of bfloat16's step, as in serve_loop.py
# (operands rounded to bfloat16 in program and reference alike, but the
# decode form reassociates the attention the reference takes in prefill
# form): median 0.004, and on the steps with a routing margin to spare
# at most 0.0245 (log mu) and 0.020 (baseline) in any run. The
# reference with its cache held in float8 (e4m3), the nearest precision
# below the configuration's bfloat16, on those same steps: 0.066 and
# 0.043 (median 0.008). The limits lie between, with room on both
# sides: a float8 cache fails both, and the cap below besides.
LOG_MU_TOLERANCE = 0.04
BASELINE_TOLERANCE = 0.03
# A step whose routers chose by less than this (in units of the sigmoid
# scores plus bias) may route otherwise in the served model, whose
# scores differ from the reference's by the rounding of what feeds
# them; by the precision the products' operands are rounded to. On the
# chip 1.3 to 2.2% of the compared steps did (10 to 17 of 768 a run),
# each out of tolerance by far (0.05 to 0.36), at margins up to 2.2e-3:
# 68 of the 76 under 1e-3, none of 4,608 steps above 2.2e-3. With a
# float8 cache 5.7% of the steps are out of tolerance under the margin
# and 9 above it. At most EXCUSED_LIMIT of the compared steps may be
# excused.
ROUTING_MARGIN = {'bfloat16': 1e-2, 'float32': 1e-5}
EXCUSED_LIMIT = 0.05

# The reference's `dims`: the configuration's sizes under the names of
# the program's `LatentMoEDims`, each the flag `seq_<name>`.
Dims = collections.namedtuple('Dims', [
    'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim', 'qk_rope_head_dim',
    'v_head_dim', 'first_dense_layers', 'routed_experts', 'experts_held',
    'expert_offset', 'experts_per_token', 'expert_groups',
    'expert_groups_kept', 'routed_scale', 'rope_factor',
    'rope_original_max', 'rope_beta_fast', 'rope_beta_slow', 'rope_mscale',
    'rope_mscale_all_dim'])


class _Seams(serve_loop._Seams):

  def __init__(self, sessions):
    super().__init__(sessions)
    self.prefills = []  # (slot, index of the call it came before, tokens)
    self.slots = []     # the slot of each row of a policy call


def _fleet_factory(seams, seed):
  def build(config, agent, policy, buffer, levels):
    server = seams.server = policy.__self__
    prefill = server.prefill

    def recorded_prefill(handle, tokens):
      seams.prefills.append((handle.slot, len(seams.recorder.rows),
                             np.array(tokens)))
      return prefill(handle, tokens)

    # A slot handle finds the entry on its server: the instance's
    # attribute is the seam.
    server.prefill = recorded_prefill
    recorded = seams.recorder.wrap(seams.clock.wrap(policy))

    def policy_with_slots(prev_action, env_output, core_state):
      handles = core_state if isinstance(core_state, list) else [core_state]
      seams.slots = [handle.slot for handle in handles]
      return recorded(prev_action, env_output, core_state)

    seams.fleet = driver.make_fleet(
        config, agent, policy_with_slots, buffer, levels,
        seed_base=seed * 1009, is_test=True,
        initial_state_fn=server.initial_core_state)
    return seams.fleet
  return build


def _prompt_tokens(cfg):
  """Tokens the fleet's first prompts hand over: each session's prompt
  but for its last token."""
  n = cfg.num_actors
  return (n * (cfg.token_prompt_length - 1) +
          cfg.token_prompt_stride * n * (n - 1) // 2)


class _Watcher(serve_loop._Watcher):

  def _wait_for(self, ready):
    """Polls the server's counters until `ready(stats)`."""
    last_sample = 0.0
    while not self.gave_up.is_set():
      server = self.seams.server
      if server is not None and self.seams.fleet is not None:
        if ready(server.stats()):
          return True
      if time.monotonic() - last_sample > 2.0:
        self.watch.sample()  # children open a chip, if ever, at start
        last_sample = time.monotonic()
      time.sleep(serve_loop.POLL_SECS)
    return False

  def _run(self):
    ctx = self.ctx
    expected = _prompt_tokens(ctx.config)
    if not self._wait_for(lambda s: s['prefill_tokens'] >= expected):
      return
    calls = self.seams.server.stats()['calls'] + ctx.param('warm_calls')
    ctx.mark(f'{expected} prompt tokens handed over; the window opens '
             f'at merged call {calls}')
    if not self._wait_for(lambda s: s['calls'] >= calls):
      return
    ctx.open_window()
    opened = {'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    self.gave_up.wait(
        max(0.0, opened['perf'] + ctx.seconds - time.perf_counter()))
    closed = {'perf': time.perf_counter(),
              'counters': self.seams.counters()}
    ctx.close_window()
    traced = {}
    if ctx.trace and not self.gave_up.is_set():
      # The traced slice FOLLOWS the window, as in serve_loop.py.
      ctx.trace_start()
      traced['trace_open'] = self.seams.counters()
      self.gave_up.wait(ctx.param('trace_seconds'))
      traced['trace_close'] = self.seams.counters()
      # The play ends HERE, not after the minute or two it takes to
      # read the slice: 50 calls a second meanwhile would carry the
      # longest session to its episode's end and into the next prompt.
      self.stop_event.set()
      serve_loop._stop_trace_with_scopes(ctx)
      # Reading weights is this program's work, and the compiler's
      # prefetch copies that do it carry no scope of their own.
      renamed, left = trace_enclosure.inherit_scopes(ctx.trace_result)
      ctx.mark(f'{renamed} operations without a scope path took their '
               f'consumer\'s, {left} kept none')
    self.watch.sample()
    self.obs = {'opened': opened, 'closed': closed, 'traced': traced}


def _episodes(seams, row):
  """[(block, first call, end call)] of the session in row `row` of
  every call, oldest first."""
  slot = seams.slots[row]
  begun = [(call, tokens) for s, call, tokens in seams.prefills if s == slot]
  ends = [call for call, _ in begun[1:]] + [len(seams.recorder.rows)]
  return [(tokens, call, end) for (call, tokens), end in zip(begun, ends)]


def _compare(reference, episode, recorded, half, verbose):
  """One episode against the reference on its first and last `half`
  decode steps -> [(margin, |log mu difference|, |baseline
  difference|)] a compared step."""
  block, first, end = episode
  tokens, _, actions, log_mu, baseline = (x[first:end] for x in recorded)
  ref_mu, ref_base, margin = jax.device_get(reference(
      np.concatenate([block, tokens]),
      np.concatenate([np.zeros_like(block), actions])))
  steps = sorted(set(range(half)) | set(range(len(tokens) - half,
                                              len(tokens))))
  out = []
  for t in steps:
    at = len(block) + t
    out.append((float(margin[at]), abs(float(ref_mu[at] - log_mu[t])),
                abs(float(ref_base[at] - baseline[t]))))
    if verbose:
      print(f'  step {t:5d} (position {at:5d}): margin {out[-1][0]:.2e} '
            f'|d log mu| {out[-1][1]:.2e} |d baseline| {out[-1][2]:.2e}',
            flush=True)
  return out


def _check_against_reference(checks, ctx, cfg, params, seams):
  half = ctx.param('check_steps') // 2
  dims = Dims(**{name: getattr(cfg, f'seq_{name}')
                 for name in Dims._fields})
  bfloat16 = jax.numpy.bfloat16
  served = dict(
      dims=dims, num_heads=cfg.seq_num_heads, rope_theta=cfg.seq_rope_theta,
      norm_eps=cfg.seq_norm_eps, vocab_block=VOCAB_BLOCK,
      block=REFERENCE_BLOCK,
      operand_dtype=bfloat16 if cfg.compute_dtype == 'bfloat16' else None,
      cache_dtype=bfloat16 if cfg.param_dtype == 'bfloat16' else None)
  readings = bool(os.environ.get('DOTS_REFERENCE_READINGS'))
  # The sessions with the shortest and the longest prompt, each on its
  # last episode that has the steps to compare.
  latest = {}
  for row in range(min(seams.recorder.sessions, len(seams.slots))):
    whole = [e for e in _episodes(seams, row) if e[2] - e[1] >= 2 * half]
    if whole:
      latest[row] = whole[-1]
  if not latest:
    checks.record('an episode with the steps to compare was recorded',
                  False, f'{len(seams.prefills)} blocks handed over, '
                  f'{len(seams.recorder.rows)} calls')
    return
  by_prompt = sorted(latest, key=lambda row: len(latest[row][0]))
  rows = sorted({by_prompt[0], by_prompt[-1]})
  compared = []
  for row in rows:
    episode = latest[row]
    recorded = seams.recorder.session(row)
    print(f'session in row {row}: prompt block of {len(episode[0])} '
          f'tokens, {episode[2] - episode[1]} decode steps recorded',
          flush=True)
    with jax.default_matmul_precision('highest'):
      compared += _compare(
          lambda tokens, actions: dots_ref.forward(
              params, tokens, actions, **served),
          episode, recorded, half, readings)
      if readings:
        lower = _compare(
            lambda tokens, actions: dots_ref.forward(
                params, tokens, actions,
                **dict(served, cache_dtype=jax.numpy.float8_e4m3fn)),
            episode, recorded, half, True)
        print(f'reading, cache in float8 (e4m3): worst |log mu - '
              f'reference| {max(x[1] for x in lower):.3e}, worst '
              f'|baseline - reference| {max(x[2] for x in lower):.3e}',
              flush=True)
  margin = ROUTING_MARGIN[cfg.compute_dtype]
  out = [x for x in compared
         if x[1] > LOG_MU_TOLERANCE or x[2] > BASELINE_TOLERANCE]
  excused = [x for x in out if x[0] < margin]
  kept = [x for x in compared if x not in excused]
  worst_mu = max(x[1] for x in kept)
  worst_base = max(x[2] for x in kept)
  print(f'reference: {len(compared)} steps of {len(rows)} sessions '
        f'compared, {sum(x[0] < margin for x in compared)} with a routing '
        f'margin under {margin:.0e}, {len(excused)} of those out of '
        f'tolerance and excused (their margins: '
        f'{" ".join(f"{x[0]:.1e}" for x in sorted(excused))}; worst |log mu '
        f'- reference| {max((x[1] for x in excused), default=0.0):.3e}, '
        f'|baseline - reference| '
        f'{max((x[2] for x in excused), default=0.0):.3e}); on the others '
        f'worst |log mu - reference| {worst_mu:.3e}, worst |baseline - '
        f'reference| {worst_base:.3e}; the three largest of those with '
        f'their margins: '
        f'{[(f"{x[1]:.3f}", f"{x[2]:.3f}", f"{x[0]:.1e}") for x in sorted(kept, key=lambda x: -max(x[1], x[2]))[:3]]}',
        flush=True)
  checks.record(
      'steps excused for a routing near-tie stay a small share',
      len(excused) <= EXCUSED_LIMIT * len(compared),
      f'{len(excused)} of {len(compared)}, limit {EXCUSED_LIMIT:.0%}')
  checks.record(
      'log mu(a) of the timed path agrees with the reference\'s full '
      'forward of the episode', worst_mu <= LOG_MU_TOLERANCE,
      f'worst {worst_mu:.3e}, tolerance {LOG_MU_TOLERANCE:.0e}, '
      f'{len(kept)} steps')
  checks.record(
      'the baseline of the timed path agrees with the reference',
      worst_base <= BASELINE_TOLERANCE,
      f'worst {worst_base:.3e}, tolerance {BASELINE_TOLERANCE:.0e}')


def run(ctx):
  cfg = ctx.config
  py_process.warm_forkserver()
  checks = correct.Checks()
  watch = processes.ChildWatch()
  seams = _Seams(ctx.param('check_sessions'))
  levels = factory.level_names(cfg)
  spec = factory.make_env_spec(cfg, levels[0], seed=1, is_test=True)
  agent = driver.build_agent(cfg, spec.num_actions)
  params = jax.jit(lambda key: init_params(agent, key, spec.obs_spec))(
      jax.random.PRNGKey(ctx.seed))
  jax.block_until_ready(params)
  ctx.mark('parameters made on the device from the seed: '
           f'{sum(x.size for x in jax.tree_util.tree_leaves(params))}')

  stop_event = threading.Event()
  watcher = _Watcher(ctx, seams, stop_event, watch)
  t_call = time.perf_counter()
  watcher.start()
  try:
    driver.play(cfg, agent, params, spec.obs_spec, levels,
                num_actors=cfg.num_actors,
                fleet_factory=_fleet_factory(seams, ctx.seed),
                stop_event=stop_event,
                stall_timeout_secs=serve_loop.STALL_TIMEOUT_SECS)
  finally:
    watcher.gave_up.set()
    watcher.join(timeout=600)  # it may be reading the traced slice
  if watcher.error is not None:
    raise watcher.error
  if not watcher.obs:
    raise RuntimeError(
        f'the run ended after {time.perf_counter() - t_call:.0f} s '
        'before every prompt was in and the warm calls made: no window')
  opened, closed = watcher.obs['opened'], watcher.obs['closed']
  delta = lambda key: (closed['counters']['server'][key] -  # noqa: E731
                       opened['counters']['server'][key])
  end = closed['counters']['server']
  print(f'window: {delta("calls")} merged calls of {delta("requests")} '
        f'rows in {closed["perf"] - opened["perf"]:.1f} s; they read '
        f'{delta("cache_tokens_read")} cached tokens, hit '
        f'{delta("experts_hit")} experts with {delta("routed_rows_held")} '
        f'routed rows, and {delta("prefill_tokens")} prompt tokens came '
        f'in; state {end["state_bytes_per_slot"]} bytes a slot, arena '
        f'{end["arena_bytes"]}', flush=True)

  waits = seams.clock.waits(opened['perf'], closed['perf'])
  checks.record(
      'every merged call carried the whole fleet\'s rows',
      delta('calls') > 0 and
      delta('requests') == cfg.num_actors * delta('calls'),
      f'{delta("requests")} rows in {delta("calls")} calls of '
      f'{cfg.num_actors} sessions')
  left = processes.env_processes_left()
  checks.record('no env process outlived the run', not left, left)
  checks.record(
      f'watched {len(watch.seen)} child processes, none opened an '
      'accelerator device',
      len(watch.seen) >= cfg.num_actors and not watch.offenders,
      watch.offenders)
  # The reference needs the room the arena took.
  seams.server.release_state()
  gc.collect()
  _check_against_reference(checks, ctx, cfg, params, seams)
  fleet = closed['counters']['fleet']
  return {
      'checks': checks,
      'failures': {
          'actor_respawns': fleet['respawns'],
          'slots_quarantined': fleet['slots_quarantined'],
          'sheds': end['sheds'],
          'chain_recoveries': end['chain_recoveries']},
      'attempted': len(waits),
      'caller_waits': waits,
      'window_seconds': closed['perf'] - opened['perf'],
      'counters': {'open': opened['counters'],
                   'close': closed['counters'],
                   **watcher.obs['traced']},
  }

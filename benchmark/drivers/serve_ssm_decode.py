"""`serve_prefill_decode.py`'s run (prompts of thousands of tokens handed
over as blocks and prefilled in set-up, then a token a call a session,
the window decode alone) for a policy that keeps, in one arena row, a
FIXED state beside a GROWING cache: Mamba-2 layers (a float32 state and
a conv window a session) in a pattern with full attention layers (every
token of the episode). The run loop, the seams, the watcher, the fleet
factory and the comparison of an episode are that file's, imported, as
`serve_window_decode.py` imports them; what differs is here: the
reference that recomputes the episode (harness/granite_ref.py: the SSM
by its defining recurrence over time, attention as a [T, T] causal
product, no cache, no chunking), the widths it is handed, and the
limits, read on the chip for this configuration.

`serve_prefill_decode.run` calls its module's check by name, so this
file's `run` puts its own in that name's place for the length of the
call, as `serve_window_decode.py` does (PERF.md section 7k), and wraps
that file's fleet factory so that the server's `release_state` first
reads the compared sessions' rows of the arena.

`correct`: as that file's. For the sessions with the shortest and the
longest prompt, `log mu(action)` and the baseline the timed path
returned, on the first `check_steps / 2` decode steps after the prompt
and the last `check_steps / 2` before the run ended, against the
reference's full forward of the recorded episode after the arena is
released. Each compared session's prompt went through the chunked form
across many chunks' and inner blocks' boundaries: the check prints how
many, and the seconds it took. A step out of tolerance is excused only
under a routing margin (that file's ROUTING_MARGIN, here in units of
the softmax router's logits) and counts toward EXCUSED_LIMIT, that
file's. And the state itself: each Mamba layer's float32 state S in the
arena's row of each compared session, read after the run's last call
and before the arena is released, against the S the reference's
recurrence holds after the same tokens, as the relative (Frobenius)
difference of the two. In the FIRST Mamba layer, whose input is the
tokens' embedding alone and so the same in program and reference, that
difference is the state's own arithmetic: the number that tells the
state the configuration states from one held a precision lower, which
the tied head's small logits hide from log mu and the baseline. A later
layer's input carries the bfloat16 noise of every layer before it (a
routing near-tie that fell the other way, a rounding that fell
otherwise), and is held to a guard.

With GRANITE_REFERENCE_READINGS set in the environment the run also
prints every compared step and puts the same comparison, under the same
verdict (`serve_window_decode._verdict` with this file's limits, and the
state's row), to the reference one precision below the configuration's
(CONTROLS): the SSM state held in bfloat16 where the configuration says
float32. It has to come out as not correct, by the state's limit. The
check also prints the device's peak memory before and after the
reference, which tells set-up's peak from the reference's own.
"""

import collections
import os
import time
from unittest import mock

import jax
import numpy as np

from benchmark.drivers import serve_prefill_decode as base
from benchmark.drivers import serve_window_decode as window
from benchmark.harness import granite_ref

# The limits of `correct` (PERF.md section 6 has the runs and their
# readings). The tied head's logits are small (the embedding is seeded
# N(0, 0.02^2): the configuration's `assumed.init`), so log mu moves
# little and neither it nor the baseline tells the state's precision
# apart: a step differs from the reference by the size of bfloat16's step
# (operands rounded to bfloat16 in program and reference alike, sums
# taken in another order, the chunked form against the recurrence), at
# most 0.0056 (log mu) and 0.058 (baseline, at a routing margin under
# 1e-3; 0.024 where every router chose by over 1e-2) in the served
# model, and with the SSM state in bfloat16 0.005-0.006 and 0.04-0.08:
# no limit lies between with room. These two hold the served model with
# room above its own readings (a wrong token, position or layer moves
# them by 0.1 and more) and are not what refuses the control.
LOG_MU_TOLERANCE = 0.02
BASELINE_TOLERANCE = 0.06
# What refuses it is the state itself. In the first Mamba layer (its input
# the embedding alone, as the reference computes it) the served model's S
# reads 1.15e-3 to 1.35e-3 on the chip (six sessions of three runs), the
# control's, S rounded to bfloat16 at every token (a decay within 2^-9 of
# 1 rounds back to the state it had), 6.9e-3 to 1.29e-2: the limit lies
# between, over twice the one and under half the other. (At the CPU
# rehearsal's tiny size, float32: 1.9e-7 against 5.6e-3 to 9.3e-3.)
STATE_TOLERANCE = 3e-3
# Every layer: the served model's worst layer reads 8.4e-3 to 1.35e-2 (a
# later layer integrates the bfloat16 noise of all before it), the
# control's 3.1e-2 to 4.6e-2; a guard against a state gone wrong, which
# reads of order one, with room above the served model.
STATE_GUARD = 5e-2
# Steps excused for a routing near-tie: at most that file's share, 5%. A
# router chooses 10 of 72 logits of order one, which lie 0.06 apart at
# the tenth, and a step's margin is the least of ten routers': 72% of
# the steps have one under 1e-2, 13% under 1e-3. Out of these limits and
# under the margin: 0 steps of 768 a run in the served model (three
# runs), 0 to 5 with the state in bfloat16.
EXCUSED_LIMIT = base.EXCUSED_LIMIT

# The reference one precision below the configuration's, the reading
# the first layer's state limit lies under (GRANITE_REFERENCE_READINGS):
# what is rounded lower, as options of `granite_ref.forward`.
CONTROLS = {
    'the SSM state in bfloat16, not float32':
        {'state_dtype': jax.numpy.bfloat16}}

# The reference's `dims`: the configuration's sizes under the names of
# the program's `SSMHybridDims`, each the flag `seq_<name>`.
Dims = collections.namedtuple('Dims', [
    'num_kv_heads', 'head_dim', 'layer_pattern', 'ssm_heads',
    'ssm_head_dim', 'ssm_state', 'routed_experts', 'experts_held',
    'expert_offset', 'experts_per_token', 'routed_scale',
    'embedding_multiplier', 'residual_multiplier', 'attention_multiplier',
    'logits_scaling'])


def _verdict(compared, margin):
  """`serve_window_decode._verdict` under this file's limits."""
  with mock.patch.multiple(window, LOG_MU_TOLERANCE=LOG_MU_TOLERANCE,
                           BASELINE_TOLERANCE=BASELINE_TOLERANCE,
                           EXCUSED_LIMIT=EXCUSED_LIMIT):
    return window._verdict(compared, margin)


def state_differences(served, reference):
  """[S a Mamba layer] of the arena and of the reference -> [||S -
  S_ref|| / ||S_ref|| (Frobenius) a layer]."""
  return [float(np.linalg.norm(np.asarray(a, np.float64) - b) /
                np.linalg.norm(np.asarray(b, np.float64)))
          for a, b in zip(served, reference, strict=True)]


def _state_rows(sessions):
  """[the differences a layer of each compared session the arena held]
  -> the two rows `correct` records of them."""
  first = max((layers[0] for layers in sessions), default=0.0)
  worst = max((max(layers) for layers in sessions), default=0.0)
  return [
      ('the first Mamba layer\'s float32 state in the arena agrees with '
       'the reference\'s recurrence over the same tokens',
       bool(sessions) and first <= STATE_TOLERANCE,
       f'worst ||S - S_ref|| / ||S_ref|| {first:.3e}, tolerance '
       f'{STATE_TOLERANCE:.0e}, {len(sessions)} sessions'),
      ('every Mamba layer\'s state in the arena stays near the '
       'reference\'s', bool(sessions) and worst <= STATE_GUARD,
       f'worst {worst:.3e}, guard {STATE_GUARD:.0e}')]


def _compared(seams, half):
  """(the last episode with the steps to compare of each recorded row,
  the rows of the sessions with the shortest and the longest prompt)."""
  latest = {}
  for row in range(min(seams.recorder.sessions, len(seams.slots))):
    whole = [e for e in base._episodes(seams, row)
             if e[2] - e[1] >= 2 * half]
    if whole:
      latest[row] = whole[-1]
  by_prompt = sorted(latest, key=lambda row: len(latest[row][0]))
  return latest, sorted({by_prompt[0], by_prompt[-1]}) if latest else []


def _read_states(seams, cfg, half):
  """{row: (the row's position, [S f32 [H, P, N] a Mamba layer])} of the
  compared sessions whose compared episode is the one their arena row
  holds (the last they began), read from the arena after the run's last
  call."""
  latest, rows = _compared(seams, half)
  pattern = cfg.seq_layer_pattern
  arena = seams.server._arena_now()
  states = {}
  for row in rows:
    if latest[row][1:] != base._episodes(seams, row)[-1][1:]:
      continue  # a later episode began in the row: its state is there
    slot = seams.slots[row]
    states[row] = (int(arena['pos'][slot]),
                   [np.asarray(leaf[1][slot])
                    for i, leaf in enumerate(arena['layers'])
                    if pattern[i % len(pattern)] == 'M'])
  return states


# That file's factory, as imported: `run` puts the one below in its place.
_plain_fleet_factory = base._fleet_factory


def _fleet_factory(seams, seed, half):
  """`serve_prefill_decode._fleet_factory` whose server reads the
  compared sessions' states into `seams.states` as it releases its
  arena."""
  build = _plain_fleet_factory(seams, seed)

  def build_and_read(config, agent, policy, buffer, levels):
    fleet = build(config, agent, policy, buffer, levels)
    server = seams.server
    release = server.release_state

    def read_then_release():
      seams.states = _read_states(seams, config, half)
      release()

    server.release_state = read_then_release
    return fleet
  return build_and_read


def _out(x):
  return x[1] > LOG_MU_TOLERANCE or x[2] > BASELINE_TOLERANCE


class _Reference:
  """`granite_ref.forward` as `serve_prefill_decode._compare` calls
  it, which keeps the states it returned beside."""

  def __init__(self, params, options):
    self.params, self.options, self.states = params, options, None

  def __call__(self, tokens, actions):
    mu, baseline, margin, self.states = granite_ref.forward(
        self.params, tokens, actions, states=True, **self.options)
    return mu, baseline, margin


def _check_against_reference(checks, ctx, cfg, params, seams):
  started = time.perf_counter()
  print(f'device memory: {window._device_peak()} bytes in use at the peak '
        'of set-up and window, before the reference', flush=True)
  half = ctx.param('check_steps') // 2
  dims = Dims(**{name: getattr(cfg, f'seq_{name}')
                 for name in Dims._fields})
  bfloat16 = jax.numpy.bfloat16
  stored = bfloat16 if cfg.param_dtype == 'bfloat16' else None
  served = dict(
      dims=dims, num_heads=cfg.seq_num_heads, norm_eps=cfg.seq_norm_eps,
      vocab_block=base.VOCAB_BLOCK, block=base.REFERENCE_BLOCK,
      operand_dtype=bfloat16 if cfg.compute_dtype == 'bfloat16' else None,
      conv_dtype=stored, cache_dtype=stored)
  readings = bool(os.environ.get('GRANITE_REFERENCE_READINGS'))
  stats = seams.server.stats()
  print(f'the Mamba layers: {stats["ssm_rows_stepped"]} rows stepped in '
        f'{stats["calls"]} calls, beside {stats["cache_tokens_read"]} '
        f'tokens read of caches of {stats["cache_capacity"]}', flush=True)
  # The sessions with the shortest and the longest prompt, each on its
  # last episode that has the steps to compare.
  latest, rows = _compared(seams, half)
  if not latest:
    checks.record('an episode with the steps to compare was recorded',
                  False, f'{len(seams.prefills)} blocks handed over, '
                  f'{len(seams.recorder.rows)} calls')
    return
  held = getattr(seams, 'states', {})
  margin = base.ROUTING_MARGIN[cfg.compute_dtype]
  compared, states = [], []
  controls = {name: [] for name in CONTROLS} if readings else {}
  control_states = {name: [] for name in controls}
  for row in rows:
    episode = latest[row]
    prompt, steps = len(episode[0]), episode[2] - episode[1]
    print(f'session in row {row}: prompt block of {prompt} tokens in '
          f'{-(-prompt // cfg.seq_prefill_chunk)} chunks of the chunked '
          f'form, {-(-prompt // cfg.seq_ssm_block)} of its inner blocks; '
          f'{steps} decode steps recorded', flush=True)
    recorded = seams.recorder.session(row)

    def against(options, verbose, into):
      reference = _Reference(params, dict(served, **options))
      out = base._compare(reference, episode, recorded, half, verbose)
      if row in held:
        position, arena_states = held[row]
        into.append(state_differences(arena_states, reference.states))
        print(f'  the state in the arena (position {position}, '
              f'{len(episode[0]) + episode[2] - episode[1]} tokens fed) '
              f'against the reference\'s, ||S - S_ref|| / ||S_ref|| a Mamba '
              f'layer: {" ".join(f"{x:.3e}" for x in into[-1])}', flush=True)
      return out

    with jax.default_matmul_precision('highest'):
      compared += against({}, readings, states)
      for name, options in CONTROLS.items() if readings else ():
        print(f'control, {name}:', flush=True)
        controls[name] += against(options, True, control_states[name])
  for name, lower in controls.items():
    # The same verdict on the reference one precision down.
    verdict, excused, _ = _verdict(lower, margin)
    verdict += _state_rows(control_states[name])
    print(f'control, {name}: correct '
          f'{all(ok for _, ok, _ in verdict)}; '
          f'{sum(_out(x) for x in lower)} of {len(lower)} steps out of '
          f'tolerance, {len(excused)} of them under the margin; worst '
          f'|log mu - reference| {max(x[1] for x in lower):.3e}, worst '
          f'|baseline - reference| {max(x[2] for x in lower):.3e}; '
          f'{[(n[:24], ok, detail) for n, ok, detail in verdict]}',
          flush=True)
  verdict, excused, kept = _verdict(compared, margin)
  print(f'reference: {len(compared)} steps of {len(rows)} sessions '
        f'compared in {time.perf_counter() - started:.1f} s, '
        f'{sum(x[0] < margin for x in compared)} with a routing margin '
        f'under {margin:.0e} ({sum(x[0] < margin / 10 for x in compared)} '
        f'under {margin / 10:.0e}), {len(excused)} of those out of '
        f'tolerance and excused (their margins: '
        f'{" ".join(f"{x[0]:.1e}" for x in sorted(excused))}; worst |log mu '
        f'- reference| {max((x[1] for x in excused), default=0.0):.3e}, '
        f'|baseline - reference| '
        f'{max((x[2] for x in excused), default=0.0):.3e}); on the others '
        f'the three largest differences with their margins: '
        f'{[(f"{x[1]:.2e}", f"{x[2]:.2e}", f"{x[0]:.1e}") for x in sorted(kept, key=lambda x: -max(x[1], x[2]))[:3]]}'
        f'; device memory: {window._device_peak()} bytes at the peak, the '
        'reference included', flush=True)
  verdict += _state_rows(states)
  for row in verdict:
    checks.record(*row)


def run(ctx):
  half = ctx.param('check_steps') // 2
  with mock.patch.multiple(
      base, _check_against_reference=_check_against_reference,
      _fleet_factory=lambda seams, seed: _fleet_factory(seams, seed, half)):
    return base.run(ctx)

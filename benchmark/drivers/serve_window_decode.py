"""`serve_prefill_decode.py`'s run (prompts of thousands of tokens handed
over as blocks and prefilled in set-up, then a token a call a session,
the window decode alone) for a policy that keeps TWO kinds of cache a
session: grouped-query attention in a pattern of window layers (a ring
of the episode's last tokens) and full layers (every token). The run
loop, the seams, the watcher, the fleet factory and the comparison of
an episode are that file's, imported; what differs is here: the
reference that recomputes the episode (harness/exaone_ref.py: a [T, T]
mask a layer, causal and in window layers banded, no cache, no ring),
the widths it is handed, and the limits, read on the chip for this
configuration.

`serve_prefill_decode.run` calls its module's check by name, so this
file's `run` puts its own in that name's place for the length of the
call (PERF.md section 7k asks a `benchmark` PR for `run(ctx, check=)`).

`correct`: as that file's. For the sessions with the shortest and the
longest prompt, `log mu(action)` and the baseline the timed path
returned, on the first `check_steps / 2` decode steps after the prompt
and the last `check_steps / 2` before the run ended, against the
reference's full forward of the recorded episode after the arena is
released. Each compared session's prompt crossed a chunk's boundary,
and its episode the ring's wrap, many times: the check prints how
many, and the seconds it took. A step out of tolerance is excused only
under a routing margin (that file's ROUTING_MARGIN) and counts toward
EXCUSED_LIMIT, this file's.

With EXAONE_REFERENCE_READINGS set in the environment the run also
prints every compared step and puts the same comparison, under the same
verdict (`_verdict`), to the reference one precision below the
configuration's (CONTROLS): its two caches in float8 (e4m3) where the
configuration says bfloat16, and the router's operands in bfloat16
where it says float32. The first has to come out as not correct, by the
limits: the reading every limit lies under. The second is read and
recorded: at the published widths it moves as many steps as the served
model's own bfloat16 products do (PERF.md section 6, PR 35;
tests/benchmark/test_exaone_cell.py puts both under the verdict at a
tiny size in float32, where both come out as not correct). The
check also prints the device's peak memory before and after the
reference, which tells set-up's peak from the reference's own.
"""

import collections
import os
import time
from unittest import mock

import jax

from benchmark.drivers import serve_prefill_decode as base
from benchmark.harness import exaone_ref

# The limits of `correct`, each between two readings on the chip at the
# published widths (PERF.md section 6, PR 35: nine runs of the final tree,
# 6,912 compared steps, and both controls on 768 of them). A step whose
# routing did not flip differs from the reference by the size of
# bfloat16's step (operands rounded to bfloat16 in program and reference
# alike; the kernel's running softmax rounds weights the reference
# normalises first): median 0.003, 95 in 100 under 0.009; where the
# routers' margin is over 1e-3 at most 0.0140 (log mu) and 0.0131
# (baseline), over 1e-2 at most 0.0089 and 0.0069. With both caches in
# float8 the median is 0.021, 315 of 768 steps lie beyond these limits
# and of the 35 steps with a margin over 1e-2 ten do, by up to 0.113 and
# 0.071: not correct by every row.
LOG_MU_TOLERANCE = 0.04
BASELINE_TOLERANCE = 0.03
# Steps excused for a routing near-tie: at most that file's share of
# those compared, 5%. A router chooses 8 of 128 sigmoid scores of order
# one, which lie 0.009 apart at the eighth, and a step's margin is the
# least of four routers': 95 to 98 steps in 100 have one under 1e-2, a
# third under 1e-3, whatever the kernel's size. Of 768 steps a run 12 to
# 25 (1.6 to 3.3%) route otherwise in the served model and leave the
# limits, each at a margin under 2.1e-3, differing by up to 0.35. With
# float8 caches 305 do (40%). The router's operands in bfloat16 (the
# second control) move 16: that is inside the served model's own range,
# the verdict cannot tell it apart and no limit can be placed between
# (recorded in PERF.md section 6; at a tiny size in float32, where the
# served model equals the reference, the same control is not correct:
# tests/benchmark/test_exaone_cell.py).
EXCUSED_LIMIT = base.EXCUSED_LIMIT

# The reference one precision below the configuration's, the readings
# each limit lies under (EXAONE_REFERENCE_READINGS): what is rounded
# lower, as options of `exaone_ref.forward`.
CONTROLS = {
    'both caches in float8 (e4m3)':
        {'cache_dtype': jax.numpy.float8_e4m3fn},
    'the router\'s operands in bfloat16, not float32':
        {'router_dtype': jax.numpy.bfloat16}}

# The reference's `dims`: the configuration's sizes under the names of
# the program's `HybridAttentionDims`, each the flag `seq_<name>`.
Dims = collections.namedtuple('Dims', [
    'num_kv_heads', 'head_dim', 'layer_pattern', 'window',
    'first_dense_layers', 'routed_experts', 'experts_held',
    'expert_offset', 'experts_per_token', 'routed_scale'])


def _device_peak():
  stats = jax.local_devices()[0].memory_stats() or {}
  return stats.get('peak_bytes_in_use', 0)


def _verdict(compared, margin):
  """[(margin, |log mu difference|, |baseline difference|)] a compared
  step -> (the three rows `correct` records of them, the steps excused,
  the steps kept)."""
  out = [x for x in compared
         if x[1] > LOG_MU_TOLERANCE or x[2] > BASELINE_TOLERANCE]
  excused = [x for x in out if x[0] < margin]
  kept = [x for x in compared if x not in excused]
  worst_mu = max((x[1] for x in kept), default=0.0)
  worst_base = max((x[2] for x in kept), default=0.0)
  rows = [
      ('steps excused for a routing near-tie stay a small share',
       len(excused) <= EXCUSED_LIMIT * len(compared),
       f'{len(excused)} of {len(compared)}, limit {EXCUSED_LIMIT:.0%}'),
      ('log mu(a) of the timed path agrees with the reference\'s full '
       'forward of the episode', worst_mu <= LOG_MU_TOLERANCE,
       f'worst {worst_mu:.3e}, tolerance {LOG_MU_TOLERANCE:.0e}, '
       f'{len(kept)} steps'),
      ('the baseline of the timed path agrees with the reference',
       worst_base <= BASELINE_TOLERANCE,
       f'worst {worst_base:.3e}, tolerance {BASELINE_TOLERANCE:.0e}')]
  return rows, excused, kept


def _check_against_reference(checks, ctx, cfg, params, seams):
  started = time.perf_counter()
  print(f'device memory: {_device_peak()} bytes in use at the peak of '
        'set-up and window, before the reference', flush=True)
  half = ctx.param('check_steps') // 2
  dims = Dims(**{name: getattr(cfg, f'seq_{name}')
                 for name in Dims._fields})
  bfloat16 = jax.numpy.bfloat16
  served = dict(
      dims=dims, num_heads=cfg.seq_num_heads, rope_theta=cfg.seq_rope_theta,
      norm_eps=cfg.seq_norm_eps, vocab_block=base.VOCAB_BLOCK,
      block=base.REFERENCE_BLOCK,
      operand_dtype=bfloat16 if cfg.compute_dtype == 'bfloat16' else None,
      cache_dtype=bfloat16 if cfg.param_dtype == 'bfloat16' else None)
  readings = bool(os.environ.get('EXAONE_REFERENCE_READINGS'))
  stats = seams.server.stats()
  print(f'the rings: {stats["window_tokens_read"]} tokens read of rings of '
        f'{stats["cache_window"]} beside {stats["cache_tokens_read"]} of '
        f'caches of {stats["cache_capacity"]}', flush=True)
  # The sessions with the shortest and the longest prompt, each on its
  # last episode that has the steps to compare.
  latest = {}
  for row in range(min(seams.recorder.sessions, len(seams.slots))):
    whole = [e for e in base._episodes(seams, row)
             if e[2] - e[1] >= 2 * half]
    if whole:
      latest[row] = whole[-1]
  if not latest:
    checks.record('an episode with the steps to compare was recorded',
                  False, f'{len(seams.prefills)} blocks handed over, '
                  f'{len(seams.recorder.rows)} calls')
    return
  by_prompt = sorted(latest, key=lambda row: len(latest[row][0]))
  rows = sorted({by_prompt[0], by_prompt[-1]})
  margin = base.ROUTING_MARGIN[cfg.compute_dtype]
  compared = []
  controls = {name: [] for name in CONTROLS} if readings else {}
  for row in rows:
    episode = latest[row]
    prompt, steps = len(episode[0]), episode[2] - episode[1]
    print(f'session in row {row}: prompt block of {prompt} tokens in '
          f'{-(-prompt // cfg.seq_prefill_chunk)} chunks, {steps} decode '
          f'steps recorded; the ring of {cfg.seq_window} wrapped '
          f'{prompt // cfg.seq_window} times in the prompt and '
          f'{(prompt + steps) // cfg.seq_window - prompt // cfg.seq_window}'
          ' times while decoding', flush=True)
    recorded = seams.recorder.session(row)
    against = lambda options, verbose: base._compare(  # noqa: E731
        lambda tokens, actions: exaone_ref.forward(
            params, tokens, actions, **dict(served, **options)),
        episode, recorded, half, verbose)
    with jax.default_matmul_precision('highest'):
      compared += against({}, readings)
      for name, options in CONTROLS.items() if readings else ():
        print(f'control, {name}:', flush=True)
        controls[name] += against(options, True)
  for name, lower in controls.items():
    # The same verdict on the reference one precision down.
    verdict, excused, _ = _verdict(lower, margin)
    print(f'control, {name}: correct '
          f'{all(ok for _, ok, _ in verdict)}; '
          f'{sum(x[1] > LOG_MU_TOLERANCE or x[2] > BASELINE_TOLERANCE for x in lower)}'
          f' of {len(lower)} steps out of tolerance, {len(excused)} of '
          f'them under the margin; worst |log mu - reference| '
          f'{max(x[1] for x in lower):.3e}, worst |baseline - reference| '
          f'{max(x[2] for x in lower):.3e}; '
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
        f'{[(f"{x[1]:.3f}", f"{x[2]:.3f}", f"{x[0]:.1e}") for x in sorted(kept, key=lambda x: -max(x[1], x[2]))[:3]]}'
        f'; device memory: {_device_peak()} bytes at the peak, the '
        'reference included', flush=True)
  for row in verdict:
    checks.record(*row)


def run(ctx):
  with mock.patch.object(base, '_check_against_reference',
                         _check_against_reference):
    return base.run(ctx)

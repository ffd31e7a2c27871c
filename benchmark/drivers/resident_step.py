"""The learner step alone: one seeded batch resident on the chip(s),
steps chained on the donated state for the whole window, one barrier at
its end. The host pipeline is bypassed.

One chip or several: the mesh is whatever `driver.choose_mesh` gives
for the devices present, and the state and the step are built the way
`driver.train` builds them for that mesh. So a sharded cell is a
traffic file, not a driver.

Traffic parameters: `done_prob`, `warmup_steps` (each read back; the
first one's loss, which the seed alone decides, is held to
`loss_band`), `calibration_steps` (timed as a chain to size the
window), `trace_steps` (the traced run's chain).
"""

import math
import time

import jax

from benchmark.harness import correct, traffic_gen
from scalable_agent_tpu import driver
from scalable_agent_tpu import learner as learner_lib
from scalable_agent_tpu.models import init_params
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.parallel import train_parallel


def _chain(train_step, state, batch, steps, ctx):
  """`steps` steps dispatched back to back, then ONE barrier: the loss
  of the last step read back as a value, which cannot exist before the
  step that computes it has finished. Returns (state, seconds, loss)."""
  t0 = time.perf_counter()
  with ctx.span('step_dispatch'):
    for _ in range(steps):
      state, metrics = train_step(state, batch)
  with ctx.span('barrier'):
    loss = float(metrics['total_loss'])
  return state, time.perf_counter() - t0, loss


def run(ctx):
  cfg = ctx.config
  checks = correct.Checks()
  mesh = driver.choose_mesh(cfg)
  agent = driver.build_agent(cfg, cfg.num_actions)
  obs_spec = {'frame': (cfg.height, cfg.width, 3),
              'instr_len': MAX_INSTRUCTION_LEN}
  # Weights on the device, in one jitted call, from the seed.
  params = jax.jit(lambda key: init_params(agent, key, obs_spec))(
      jax.random.PRNGKey(ctx.seed))
  count = sum(x.size for x in jax.tree_util.tree_leaves(params))
  expected = ctx.config_file.get('parameters')
  if expected is not None and not ctx.rehearse:
    checks.record('the configuration\'s parameter count',
                  count == expected, f'{count:,} vs {expected:,}')
  initial = jax.device_get(params)
  ctx.mark('parameters made on the device')
  if mesh is None:
    state = learner_lib.make_train_state(params, cfg)
    train_step = learner_lib.make_train_step(agent, cfg)
    shardings = None
  else:
    example = jax.eval_shape(
        traffic_gen.resident_batch_fn(cfg, ctx.param('done_prob')),
        jax.random.PRNGKey(0))
    state = train_parallel.make_sharded_train_state(
        params, cfg, mesh, enable_tp=cfg.model_parallelism > 1)
    train_step, _ = train_parallel.make_sharded_train_step(
        agent, cfg, mesh, example)
    shardings = train_step.batch_shardings
  batch = traffic_gen.resident_batch(cfg, ctx.seed,
                                     ctx.param('done_prob'), shardings)

  jax.block_until_ready(batch)
  ctx.mark('state built, batch resident')
  # Warm-up: the one shape this cell uses. Every loss is read back.
  losses, steps = [], 0
  for _ in range(ctx.param('warmup_steps')):
    state, _, loss = _chain(train_step, state, batch, 1, ctx)
    losses.append(loss)
    steps += 1
  state, calibration_s, loss = _chain(
      train_step, state, batch, ctx.param('calibration_steps'), ctx)
  losses.append(loss)
  steps += ctx.param('calibration_steps')
  step_s = calibration_s / ctx.param('calibration_steps')
  ctx.mark('step warmed and calibrated')

  obs = {'frames_per_step': cfg.frames_per_step}
  ctx.open_window()
  if ctx.trace:
    # The traced run: a short chain under the profiler stands for the
    # window (the chain is the same program at the same cadence).
    ctx.trace_start()
    n = ctx.param('trace_steps')
  else:
    n = max(1, math.ceil(ctx.seconds / step_s))
  state, seconds, loss = _chain(train_step, state, batch, n, ctx)
  if ctx.trace:
    ctx.trace_stop()
  ctx.close_window()
  losses.append(loss)
  steps += n
  obs['steps'] = {'count': n, 'seconds': seconds}
  print(f'window: {n} chained steps in {seconds:.3f} s '
        f'(calibrated {step_s * 1e3:.2f} ms/step); losses '
        f'{[round(x, 4) for x in losses]}', flush=True)

  withheld = correct.check_learner(checks, state, steps, initial, losses)
  if mesh is not None:
    checks.record(
        'the sharded step kept donation on, no gathered-TP workaround',
        train_step.donation_fallback is False and
        train_step.tp_gathered is False)
    checks.record(
        f'the mesh spans the {ctx.cell["chips"]} chips of the cell',
        mesh.devices.size == ctx.cell['chips'], dict(mesh.shape))
  band = ctx.traffic_file.get('loss_band')
  if band is not None and not ctx.rehearse:
    correct.check_band(
        checks, 'loss of the seeded batch under the seeded weights '
        'inside the band measured on the chip', losses[0], band)
  correct.check_vtrace(checks, cfg, ctx.seed)
  obs.update(checks=checks, attempted=n,
             failures={'steps_withheld': withheld})
  return obs

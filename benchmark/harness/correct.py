"""The comparison that decides `correct`.

`correct` is true only if every check a driver records here holds. The
checks every learner shares are functions of this file; a driver adds
its own through `Checks.record`. Each check is also printed, by name,
on a line before the result.

How much this proves: the losses are finite, every step's update was
applied, the parameters moved, nothing compiled inside the window, the
step's V-trace form agrees with a NumPy reference, and (where the data
is fixed by the seed) the loss lands in a band measured on the chip.
It does NOT compare the agent's forward pass, loss or gradients with a
plain float32 reference: the repository has none yet (ROADMAP R1).
"""

import numpy as np

from benchmark.harness import vtrace_ref


class Checks:

  def __init__(self):
    self.rows = []  # (name, ok, detail)

  def record(self, name, ok, detail=''):
    self.rows.append((name, bool(ok), str(detail)))

  @property
  def ok(self):
    return bool(self.rows) and all(ok for _, ok, _ in self.rows)


def check_learner(checks, state, steps, initial_params, losses):
  """What every learner must show after `steps` steps from
  `initial_params` (host copies), whatever fed it. Returns the number
  of steps whose update the in-graph sentinel withheld."""
  import jax
  import optax
  counted = int(jax.device_get(state.update_steps))
  checks.record('update_steps equals the steps run', counted == steps,
                f'{counted} vs {steps}')
  # The in-graph sentinel withholds a non-finite update while the step
  # counter still advances; the optimizer's own count moves only with
  # an APPLIED update.
  applied = int(jax.device_get(
      optax.tree_utils.tree_get(state.opt_state, 'count')))
  checks.record('the optimizer applied every update', applied == steps,
                f'{applied} of {steps}')
  losses = np.asarray(losses, np.float64)
  checks.record('every logged loss is finite',
                losses.size > 0 and bool(np.all(np.isfinite(losses))),
                f'{losses.size} logged')
  final = jax.device_get(state.params)
  leaves = list(zip(jax.tree_util.tree_leaves(final),
                    jax.tree_util.tree_leaves(initial_params)))
  finite = all(np.all(np.isfinite(a)) for a, _ in leaves)
  moved = sum(bool(np.any(np.asarray(a) != np.asarray(b)))
              for a, b in leaves)
  # An instruction encoder that only ever sees an empty or one-word
  # instruction has zero gradient on its recurrent kernels; every
  # other leaf must move, so more than half always do.
  checks.record('parameters finite and moved',
                finite and moved > len(leaves) // 2,
                f'{moved} of {len(leaves)} leaves differ')
  return max(counted - applied, 0)


def check_compiles(checks, ledger):
  window = ledger.summary('window')
  checks.record('no compilation inside the window',
                window['requests'] == 0,
                f'{window["requests"]} request(s): '
                f'{window["compiled_names"][:5]}')


def check_vtrace(checks, config, seed):
  ok, worst = vtrace_ref.check_step_form(config, seed)
  checks.record(
      'the step\'s V-trace form agrees with the NumPy reference', ok,
      f'worst relative error {worst:.2e}, tolerance '
      f'{vtrace_ref.RELATIVE_TOLERANCE:.0e}')


def check_band(checks, name, value, band):
  lo, hi = band
  checks.record(name, lo <= value <= hi, f'{value:.6g} in [{lo}, {hi}]')

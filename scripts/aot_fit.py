"""CI smoke for the compiled v5e-16 HBM fit check (parallel/fit.py).

The real fit gate runs inside `__graft_entry__.dryrun_multichip`
(which prints the flagship B=32/B=16 figures); this script keeps the AOT path green in CI without the flagship compile
cost:

    SMOKE=1 JAX_PLATFORMS=cpu python scripts/aot_fit.py   # <60 s, CPU
    python scripts/aot_fit.py                             # flagship

SMOKE compiles the same full-feature step (deep torso, PopArt + pixel
control + instruction) at tiny shapes over 8 virtual devices and
asserts the memory analysis is sane; the no-SMOKE path is the
flagship `{'data': 16}` check the dryrun runs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
  smoke = os.environ.get('SMOKE') == '1'
  n_devices = 8 if smoke else 16
  flags = os.environ.get('XLA_FLAGS', '')
  if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags +
        f' --xla_force_host_platform_device_count={n_devices}').strip()
  # A virtual-CPU-device AOT check, like __graft_entry__'s dry-run: it
  # pins CPU itself and never takes a chip.
  os.environ['JAX_PLATFORMS'] = 'cpu'
  import jax
  from scalable_agent_tpu.parallel import fit

  devices = jax.devices()
  if len(devices) < n_devices:
    raise RuntimeError(
        f'aot_fit needs {n_devices} devices but found {len(devices)}; '
        'JAX was initialized before the device-count flag could take '
        'effect — set XLA_FLAGS=--xla_force_host_platform_device_'
        f'count={n_devices} in the environment.')
  devices = devices[:n_devices]
  if smoke:
    results = [fit.aot_memory_fit(devices=devices, batch_size=8,
                                  unroll_length=4, height=24, width=32,
                                  num_tasks=3)]
  else:
    results = [fit.aot_memory_fit(devices=devices, batch_size=b)
               for b in (32, 16)]
  for result in results:
    print(fit.format_fit(result), flush=True)
    assert result['live_bytes'] > 0, result
    assert result['mesh'] == {'data': n_devices}, result
    if smoke:
      # Tiny shapes must fit by an enormous margin — a failure here
      # is an analysis-plumbing bug, not a capacity finding.
      assert result['fits'], result
    else:
      assert result['fits'], (
          'flagship full-feature shapes no longer fit the v5e HBM '
          f'budget: {result}')
  print('aot_fit OK')


if __name__ == '__main__':
  main()

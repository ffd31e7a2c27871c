"""CI smoke for the persistent compilation cache (round 23).

Two-process protocol, driven by scripts/ci.sh:

  CI_CACHE_PHASE=fill  — arm the cache through the production seam
      (distributed.maybe_initialize), compile a small program, and
      assert the cache dir holds entries.
  CI_CACHE_PHASE=hit   — a FRESH interpreter arms the same dir,
      compiles the identical program, and proves the executable came
      from the cache via jax's monitoring events (entry-count
      equality proves nothing: a miss rewrites the same key).

The directory follows the program's one placement rule
(Config.resolved_compile_cache_dir): JAX_COMPILATION_CACHE_DIR where
it is set — the code then sets nothing — else the fixed path inside
the checkout. It is passed as an explicit --compile_cache_dir because
'auto' declines to arm on a CPU-pinned process.

This is the cross-process claim the unit tests cannot make: the
second *process* skips XLA compilation entirely — the mechanism that
turns a population spin-up from N cold compiles into 1 cold + N-1
reads, and a restart of the same config into a warm start.
"""

import os
import sys

sys.path.insert(0, os.getcwd())


def main():
  phase = os.environ['CI_CACHE_PHASE']

  import jax
  # Cache tiny programs too — the smoke's matmul compiles in well
  # under the 1 s production write floor.
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  import jax.numpy as jnp

  from scalable_agent_tpu import config as config_lib
  from scalable_agent_tpu.parallel import distributed

  resolved = config_lib.Config().resolved_compile_cache_dir
  distributed.maybe_initialize(
      config_lib.Config(compile_cache_dir=resolved))
  cache_dir = jax.config.jax_compilation_cache_dir
  assert cache_dir == (os.environ.get('JAX_COMPILATION_CACHE_DIR')
                       or config_lib.REPO_COMPILE_CACHE_DIR), cache_dir

  events = []

  def listener(event, **kwargs):
    events.append(event)

  jax.monitoring.register_event_listener(listener)

  @jax.jit
  def program(x):
    return jnp.tanh(x @ x.T).sum()

  out = program(jnp.arange(64.0, dtype=jnp.float32).reshape(8, 8))
  jax.block_until_ready(out)

  entries = os.listdir(cache_dir) if os.path.isdir(cache_dir) else []
  if phase == 'fill':
    assert entries, 'fill phase wrote no cache entries'
    print('compile-cache smoke (fill): %d entr%s under %s'
          % (len(entries), 'y' if len(entries) == 1 else 'ies',
             cache_dir))
    return
  assert phase == 'hit', phase
  hits = [e for e in events
          if 'compilation_cache' in e and 'hit' in e]
  assert hits, ('hit phase compiled from scratch — no cache-hit '
                'monitoring event (saw: %r)' % sorted(set(events)))
  print('compile-cache smoke (hit): fresh process reused the cached '
        'executable (%s)' % hits[0])


if __name__ == '__main__':
  main()

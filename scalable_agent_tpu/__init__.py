"""scalable_agent_tpu — TPU-native IMPALA framework (JAX/XLA/Pallas).

A ground-up re-design of the capabilities of the reference IMPALA
implementation (`RoganInglis/scalable_agent`, a fork of
deepmind/scalable_agent, arXiv:1802.01561) for TPU:

- `vtrace`            — pure-JAX V-trace (scan + associative-scan forms)
- `models`            — agent networks (shallow CNN / deep ResNet torsos,
                        LSTM core with done-reset, instruction encoder)
- `losses`            — IMPALA losses (policy gradient, baseline, entropy)
- `learner`           — jitted train step, optimizer, frame accounting
- `envs`              — environment adapters behind a process-safe spec
                        protocol (fake env for CI, DMLab/ALE import-guarded)
- `runtime`           — host runtime: process-hosted envs, trajectory ring
                        buffer, C++ dynamic batcher, actors, checkpointing
- `parallel`          — mesh construction and sharded (pjit) training
- `dmlab30`           — DMLab-30 task table + human-normalized scoring
"""

import jax

from scalable_agent_tpu import vtrace  # noqa: F401
from scalable_agent_tpu.config import Config  # noqa: F401
from scalable_agent_tpu.structs import (  # noqa: F401
    ActorOutput, AgentOutput, StepOutput, StepOutputInfo)

__version__ = '0.1.0'

# The persistent compilation cache keys a program by its computation
# alone, so a cached executable carries the operation names (named
# scopes, Flax module names) of whichever source compiled it first:
# a profile of it then shows that source's scopes, or none, and the
# per-scope shares read off a device trace (PERF.md, section 3) would
# depend on the cache's history (seen on the chip, PR 24: the commit
# before the scopes ran an executable that had them). So the names go
# into the key, and source lines stay out of the locations, or every
# edit above a traced line would compile every program cold.
jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
jax.config.update('jax_traceback_in_locations_limit', 0)


def __getattr__(name):
  """Lazy top-level API (heavy deps — flax/orbax — load on demand):
  `scalable_agent_tpu.ImpalaAgent`, `.driver`, `.learner`, etc."""
  import importlib
  if name in ('driver', 'learner', 'losses', 'popart', 'unreal',
              'checkpoint', 'observability', 'models', 'envs',
              'runtime', 'parallel'):
    return importlib.import_module(f'scalable_agent_tpu.{name}')
  if name == 'ImpalaAgent':
    from scalable_agent_tpu.models import ImpalaAgent
    return ImpalaAgent
  raise AttributeError(name)

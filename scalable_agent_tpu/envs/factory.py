"""Environment factory: config → host environment instances.

The reference builds envs in `create_environment` (reference:
experiment.py ≈L395–410: PyProcess(PyProcessDmLab, ...) wrapped in
FlowEnvironment, test mode setting allowHoldOutLevels + fixed
mixerSeed). Here the factory is backend-dispatched so the same driver
runs the CI fake envs, DMLab, or Atari — real simulators are
import-guarded (not present in this sandbox; SURVEY §7 "hard parts").

Envs are host-side numpy objects (envs/base.py protocol). With
`config.use_py_process` the driver hosts each one in its own OS process
via runtime/py_process.py — the reference's PyProcess GIL-escape.
"""

from typing import List, Optional, Tuple

from scalable_agent_tpu.config import Config
from scalable_agent_tpu.envs import suites
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN


class EnvSpec(object):
  """What the driver needs to know about a backend before building it."""

  def __init__(self, env_class, constructor_kwargs, num_actions,
               frame_shape=None, observation_leaves=None):
    self.env_class = env_class
    self.constructor_kwargs = dict(constructor_kwargs)
    self.num_actions = num_actions
    # An image env states its frame's shape; any other states the
    # ((shape, dtype), ...) of its observation's leaves.
    self.frame_shape = None if frame_shape is None else tuple(frame_shape)
    self.observation_leaves = observation_leaves

  @property
  def obs_spec(self):
    """What `structs.observation_leaves` reads."""
    if self.observation_leaves is not None:
      return {'leaves': tuple(self.observation_leaves)}
    return {'frame': self.frame_shape, 'instr_len': MAX_INSTRUCTION_LEN}

  def build(self):
    return self.env_class(**self.constructor_kwargs)


def level_names(config: Config) -> List[str]:
  """Training level list; a suite name ('dmlab30', 'atari57') expands
  to its full level list (reference: experiment.py main ≈L630)."""
  if config.level_name in suites.SUITES:
    return list(suites.SUITES[config.level_name].train_levels)
  return [config.level_name]


def test_level_names(config: Config) -> List[str]:
  """Held-out eval variants (reference: dmlab30.LEVEL_MAPPING; see
  envs/suites.py for the per-suite eval-level story)."""
  if config.level_name in suites.SUITES:
    return list(suites.SUITES[config.level_name].test_levels)
  return [config.level_name]


def make_env_spec(config: Config, level_name: str, seed: int,
                  is_test: bool = False,
                  backend: Optional[str] = None) -> EnvSpec:
  """One environment spec for (backend, level, seed).

  `backend` overrides config.env_backend — the heterogeneous-fleet
  seam (round 22): a mixed fleet builds each actor's spec for ITS
  task's backend while every other knob (sizes, seeds, repeats) still
  comes from the one config."""
  backend = backend or config.env_backend
  if backend in ('fake', 'bandit', 'cue_memory'):
    from scalable_agent_tpu.envs import fake
    env_class = {'bandit': fake.ContextualBanditEnv,
                 'cue_memory': fake.CueMemoryEnv,
                 'fake': fake.FakeEnv}[backend]
    num_actions = config.num_actions or (
        5 if backend == 'fake' else 3)
    kwargs = dict(height=config.height, width=config.width,
                  num_actions=num_actions,
                  episode_length=config.episode_length,
                  seed=seed, level_name=level_name,
                  num_action_repeats=config.num_action_repeats)
    frame_shape = (config.height, config.width, 3)
  elif backend in ('gridworld', 'procgen'):
    # Pure-JAX env family (round 16, envs/jittable.py): the host
    # wrapper runs the SAME jittable core the Anakin runtime scans on
    # device at batch=1 — the dual registration the runtime-axis
    # parity gate rides on (one task definition, both runtimes).
    from scalable_agent_tpu.envs import jittable
    env_class = jittable.HOST_ENVS[backend]
    num_actions = (config.num_actions or
                   jittable.DEFAULT_NUM_ACTIONS[backend])
    kwargs = dict(height=config.height, width=config.width,
                  num_actions=num_actions,
                  episode_length=config.episode_length,
                  seed=seed, level_name=level_name,
                  num_action_repeats=config.num_action_repeats)
    if backend == 'procgen':
      # The finite level-id space the curriculum drives (round 22) —
      # host wrapper and Anakin core must agree on its size or the
      # dual-registration parity story breaks.
      kwargs.update(num_levels=config.procgen_num_levels,
                    wall_density=config.procgen_wall_density)
    frame_shape = (config.height, config.width, 3)
  elif backend == 'tokens':
    from scalable_agent_tpu.envs import tokens
    if not config.num_actions:
      raise ValueError('--env_backend=tokens needs --num_actions (the '
                       'vocabulary)')
    # Consecutive seeds (make_fleet's) start a prompt's length apart
    # in their first episode: session i is prompt_length * i steps
    # further than session 0, modulo the episode.
    # With a stride, session i's prompt is stride * (i mod fleet)
    # tokens longer. Where the agent's core computes a chunk at once
    # and its state lives in the server's arena, the prompt goes over
    # as a block (envs/tokens.py), sized for the fleet's longest.
    fleet = max(config.num_actors, 1)
    chunked = (config.seq_core != 'retention' and
               config.inference_state_cache)
    kwargs = dict(vocab_size=config.num_actions,
                  episode_length=config.episode_length,
                  prompt_length=(config.token_prompt_length +
                                 config.token_prompt_stride * (seed % fleet)),
                  seed=seed, level_name=level_name,
                  start_step=config.token_prompt_length * seed,
                  prompt_block=(config.token_prompt_length +
                                config.token_prompt_stride * (fleet - 1)
                                if chunked else 0))
    return EnvSpec(tokens.TokenEnv, kwargs, config.num_actions,
                   observation_leaves=tuple(
                       (spec.shape, spec.dtype)
                       for spec in tokens.observation_specs()))
  elif backend == 'dmlab':
    from scalable_agent_tpu.envs import dmlab
    env_class = dmlab.DmLabEnv
    num_actions = len(dmlab.DEFAULT_ACTION_SET)
    kwargs = dmlab.constructor_kwargs(
        level_name=level_name, seed=seed, is_test=is_test, config=config)
    frame_shape = (config.height, config.width, 3)
  elif backend == 'atari':
    from scalable_agent_tpu.envs import atari
    env_class = atari.AtariEnv
    num_actions = config.num_actions or atari.DEFAULT_NUM_ACTIONS
    # The factory knows both the policy-head size and the backend; the
    # env validates they agree at construction (no silent aliasing).
    # A head smaller than the full 18-action ALE set means the user
    # wants the game's minimal action set — the env still verifies the
    # backend's set has exactly num_actions entries.
    kwargs = dict(game=level_name, seed=seed,
                  height=config.height, width=config.width,
                  num_action_repeats=config.num_action_repeats,
                  is_test=is_test, num_actions=num_actions,
                  sticky_action_prob=config.sticky_action_prob,
                  full_action_set=(
                      num_actions == atari.DEFAULT_NUM_ACTIONS))
    frame_shape = (config.height, config.width, 3)
  else:
    raise ValueError(f'unknown env backend: {backend!r}')
  return EnvSpec(env_class, kwargs, num_actions, frame_shape)


def build_environment(spec: EnvSpec, use_py_process: bool = False
                      ) -> Tuple[object, Optional[object]]:
  """Instantiate (env, process): in-process, or hosted in its own OS
  process behind the py_process proxy (returns the PyProcess so the
  caller controls its lifecycle)."""
  if not use_py_process:
    return spec.build(), None
  from scalable_agent_tpu.runtime import py_process
  process = py_process.PyProcess(spec.env_class,
                                 constructor_kwargs=spec.constructor_kwargs)
  process.start()
  return py_process.ProxyEnv(process), process

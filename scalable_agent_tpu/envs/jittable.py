"""Pure-JAX environment family: gridworld + procgen-style generator.

Round 16 (the `--runtime={fleet,anakin}` axis): the Anakin operating
point (parallel/anakin.py, Podracer arXiv:2104.06272) is only as wide
as the set of environments that can live INSIDE the jitted device step.
bandit/cue_memory proved the architecture; this module opens the
family:

- `GridworldCore`: a G×G navigation task — agent spawns at the origin,
  a goal cell is sampled per episode, four movement actions, sparse
  +1 at the goal. The simplest task whose optimal policy must READ the
  observation spatially (the bandit's is a 1-pixel color lookup).
- `ProcgenCore`: a procgen-style PARAMETERIZED generator — each
  episode draws a level id from a finite level set and derives the
  wall layout deterministically from it in-graph
  (`jax.random.fold_in`), so one config spans `num_levels` distinct
  layouts the way procgen's level sets do. Walls block movement;
  start/goal are fixed corners; generalization pressure comes from the
  layout distribution.

Both cores follow the ENV_CORES protocol (parallel/anakin.py): a
constructor over (height, width, episode_length, num_action_repeats,
num_actions), `init(rng, batch)` / `step(state, action)` over batched
functional state, flow-style episode stats, and a NamedTuple state
whose `rng` field is the one replicated-by-name leaf (every other leaf
is [B]-leading and shards over the data mesh axis — anakin.init_carry's
placement contract).

DUAL REGISTRATION is the point: `GridworldEnv`/`ProcgenEnv` wrap the
SAME cores at batch=1 as host `envs/base.Environment`s (pinned to the
CPU backend so fleet env threads never contend for the learner chip),
registered in envs/factory.py — so one task definition runs under both
runtimes, which is the substrate of the anakin-vs-fleet parity gate
(tests/test_anakin.py). Dynamics parity is by construction, not by a
twin implementation.

HOW THE FRAME IS PAINTED (PR 31): every plane comes from maps that are
static in (height, width, grid) — which cell a pixel lies in never
changes — and none from a per-pixel lookup. Planes 0 and 1 (agent,
goal: one lit cell an env) compare the two [H, W] maps of
`_cell_masks` with the cell's [B] coordinates (`_cell_plane`). Plane
2 of procgen (walls: a whole [B, G, G] layout an env) expands the
layout along the two one-hot maps of `_cell_onehots`
(`ProcgenCore._wall_plane`), elementwise in B, so it shards over the
data axis like everything else here. The only gathers left are [B]
lookups of one element each (`_moves()[action]`, `_blocked`).
"""

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.envs import base
from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
from scalable_agent_tpu.structs import StepOutput, StepOutputInfo


def _zero_instr(batch):
  return jnp.zeros((batch, MAX_INSTRUCTION_LEN), jnp.int32)


def _cell_of_pixel(size, grid):
  """Static [size] map pixel → cell along one axis of the frame: the
  same for every environment and every step (`size` need not divide
  by `grid`)."""
  return (np.arange(size) * grid) // max(size, 1)


def _cell_masks(height, width, grid):
  """Static [H, W] int32 maps pixel → cell row/col: a plane with ONE
  lit cell an env (agent, goal) is these two maps compared with the
  cell's [B] coordinates (`_cell_plane`), without a gather. A plane
  with a whole layout an env (procgen's walls) is painted from
  `_cell_onehots` instead, without one either."""
  rows = _cell_of_pixel(height, grid)
  cols = _cell_of_pixel(width, grid)
  return (jnp.asarray(rows[:, None].repeat(width, 1), jnp.int32),
          jnp.asarray(cols[None, :].repeat(height, 0), jnp.int32))


def _cell_onehots(height, width, grid):
  """Static one-hot maps [H, G] pixel row → cell row and [W, G] pixel
  column → cell column, as 0/1 in bfloat16 (NumPy: constants of
  whatever program uses them)."""
  cells = np.arange(grid)
  return tuple(
      (_cell_of_pixel(size, grid)[:, None] == cells).astype(jnp.bfloat16)
      for size in (height, width))


class GridworldState(NamedTuple):
  """Batched functional gridworld state ([B]-leading except rng)."""
  rng: Any              # PRNG key [] — replicated by name (anakin)
  agent_yx: Any         # i32 [B, 2]
  goal_yx: Any          # i32 [B, 2]
  step_in_episode: Any  # i32 [B]
  episode_return: Any   # f32 [B]
  episode_frames: Any   # i32 [B]


class GridworldCore:
  """Jittable G×G gridworld: reach the per-episode goal cell.

  Actions 0..3 move up/down/left/right (clamped at the borders);
  actions >= 4 are no-ops, so the policy head can be any width >= 4 —
  the hybrid filler runs this core under the MAIN task's action space
  (driver.py), mirroring how the host bandit accepts a wider head.
  Reaching the goal pays +1 and ends the episode; `episode_length`
  caps wandering. Observation: channel 0 = agent cell, channel 1 =
  goal cell at 255 (uint8 [B, H, W, 3]).
  """

  def __init__(self, height=24, width=32, episode_length=12,
               num_action_repeats=1, num_actions=4, grid_size=4):
    if num_actions < 4:
      raise ValueError('GridworldCore needs num_actions >= 4 (four '
                       f'movement actions), got {num_actions}')
    if grid_size < 2:
      raise ValueError(f'grid_size must be >= 2, got {grid_size}')
    self.height, self.width = height, width
    self.episode_length = episode_length
    self.num_action_repeats = num_action_repeats
    self.num_actions = num_actions
    self.grid = grid_size
    self._row_cell, self._col_cell = _cell_masks(height, width,
                                                 grid_size)

  # [dy, dx] per action; rows past 3 are no-ops.
  def _moves(self):
    moves = np.zeros((self.num_actions, 2), np.int32)
    moves[:4] = [(-1, 0), (1, 0), (0, -1), (0, 1)]
    return jnp.asarray(moves)

  def _sample_goal(self, rng, batch):
    """Uniform over cells != (0, 0) — the fixed spawn cell."""
    flat = jax.random.randint(rng, (batch,), 1,
                              self.grid * self.grid)
    return jnp.stack([flat // self.grid, flat % self.grid], axis=-1)

  def _cell_plane(self, yx):
    """[B, H, W] bool: pixels of each env's cell `yx`."""
    return ((self._row_cell[None] == yx[:, 0, None, None]) &
            (self._col_cell[None] == yx[:, 1, None, None]))

  def _observation(self, state):
    agent = self._cell_plane(state.agent_yx)
    goal = self._cell_plane(state.goal_yx)
    frame = jnp.stack(
        [agent.astype(jnp.uint8) * 255, goal.astype(jnp.uint8) * 255,
         jnp.zeros_like(agent, jnp.uint8)], axis=-1)
    return (frame, _zero_instr(state.agent_yx.shape[0]))

  def init(self, rng, batch) -> Tuple[GridworldState, StepOutput]:
    rng, sub = jax.random.split(rng)
    state = GridworldState(
        rng=rng,
        agent_yx=jnp.zeros((batch, 2), jnp.int32),
        goal_yx=self._sample_goal(sub, batch),
        step_in_episode=jnp.zeros((batch,), jnp.int32),
        episode_return=jnp.zeros((batch,), jnp.float32),
        episode_frames=jnp.zeros((batch,), jnp.int32))
    output = StepOutput(
        reward=jnp.zeros((batch,), jnp.float32),
        info=StepOutputInfo(jnp.zeros((batch,), jnp.float32),
                            jnp.zeros((batch,), jnp.int32)),
        done=jnp.ones((batch,), bool),
        observation=self._observation(state))
    return state, output

  def _blocked(self, state, proposed):
    """Movement veto hook (ProcgenCore overrides with its walls)."""
    del state
    return jnp.zeros(proposed.shape[:1], bool)

  def step(self, state: GridworldState, action
           ) -> Tuple[GridworldState, StepOutput]:
    delta = self._moves()[action]
    proposed = jnp.clip(state.agent_yx + delta, 0, self.grid - 1)
    blocked = self._blocked(state, proposed)
    agent = jnp.where(blocked[:, None], state.agent_yx, proposed)

    at_goal = jnp.all(agent == state.goal_yx, axis=-1)
    reward = at_goal.astype(jnp.float32)
    step_count = state.step_in_episode + 1
    done = at_goal | (step_count >= self.episode_length)

    ep_return = state.episode_return + reward
    ep_frames = state.episode_frames + self.num_action_repeats
    info = StepOutputInfo(ep_return, ep_frames)  # emitted: incl. done

    rng, sub = jax.random.split(state.rng)
    fresh_goal, fresh_extra = self._fresh_episode(sub, action.shape[0],
                                                  state)
    new_state = self._replace_episode(
        state, rng=rng,
        agent_yx=jnp.where(done[:, None], jnp.zeros_like(agent), agent),
        goal_yx=jnp.where(done[:, None], fresh_goal, state.goal_yx),
        step_in_episode=jnp.where(done, 0, step_count),
        episode_return=jnp.where(done, jnp.zeros_like(ep_return),
                                 ep_return),
        episode_frames=jnp.where(done, jnp.zeros_like(ep_frames),
                                 ep_frames),
        done=done, fresh_extra=fresh_extra)
    output = StepOutput(reward=reward, info=info, done=done,
                        observation=self._observation(new_state))
    return new_state, output

  def _fresh_episode(self, rng, batch, state):
    """New-episode draws: (goal, extra) — extra is subclass state.
    `state` is the pre-step batched state (ProcgenCore's curriculum
    sampler reads its per-level scores from it)."""
    del state
    return self._sample_goal(rng, batch), None

  def _replace_episode(self, state, rng, agent_yx, goal_yx,
                       step_in_episode, episode_return, episode_frames,
                       done, fresh_extra):
    del done, fresh_extra
    return GridworldState(rng, agent_yx, goal_yx, step_in_episode,
                          episode_return, episode_frames)


class ProcgenState(NamedTuple):
  """GridworldState + the per-env level id the layout derives from,
  plus the per-LEVEL curriculum accumulators (round 22). The two
  [num_levels] leaves are NOT batch-leading: like `rng`, they are
  replicated BY NAME under a mesh (anakin.init_env_carry — shape
  sniffing would mis-shard them whenever num_levels == batch)."""
  rng: Any
  agent_yx: Any
  goal_yx: Any
  step_in_episode: Any
  episode_return: Any
  episode_frames: Any
  level_id: Any      # i32 [B] — index into the finite level set
  level_scores: Any  # f32 [num_levels] — curriculum priority EMAs
  level_visits: Any  # f32 [num_levels] — cumulative transition counts


class ProcgenCore(GridworldCore):
  """Procgen-style parameterized gridworld: per-episode level ids
  index a finite level set; each level's wall layout is derived
  IN-GRAPH from its id (`fold_in(layout_key, level_id)` → bernoulli
  wall mask with start/goal corners cleared), so `num_levels` distinct
  layouts ride one compiled program — the procgen recipe (level-set
  generalization) with zero host involvement. Walls veto movement
  (the agent stays put); the goal is the far corner.
  """

  def __init__(self, height=24, width=32, episode_length=16,
               num_action_repeats=1, num_actions=4, grid_size=5,
               num_levels=8, wall_density=0.25, layout_seed=1234,
               curriculum='uniform', curriculum_temperature=1.0,
               curriculum_eps=0.1):
    super().__init__(height=height, width=width,
                     episode_length=episode_length,
                     num_action_repeats=num_action_repeats,
                     num_actions=num_actions, grid_size=grid_size)
    if num_levels < 1:
      raise ValueError(f'num_levels must be >= 1, got {num_levels}')
    from scalable_agent_tpu import population
    if curriculum not in population.CURRICULUM_MODES:
      raise ValueError(
          f'unknown curriculum {curriculum!r} '
          f'(modes: {", ".join(population.CURRICULUM_MODES)})')
    self.num_levels = num_levels
    self.wall_density = wall_density
    self.layout_seed = layout_seed
    self.curriculum = curriculum
    self.curriculum_temperature = curriculum_temperature
    self.curriculum_eps = curriculum_eps
    self._row_onehot, self._col_onehot = _cell_onehots(
        height, width, grid_size)

  def _walls(self, level_id):
    """[B, G, G] bool wall mask, a pure function of the level id."""
    def one(lid):
      key = jax.random.fold_in(jax.random.PRNGKey(self.layout_seed),
                               lid)
      walls = jax.random.bernoulli(key, self.wall_density,
                                   (self.grid, self.grid))
      # Start and goal corners always open (every level is playable
      # at both ends; connectivity in between is the level's hazard).
      walls = walls.at[0, 0].set(False)
      walls = walls.at[self.grid - 1, self.grid - 1].set(False)
      return walls
    return jax.vmap(one)(level_id)

  def _goal_corner(self, batch):
    corner = jnp.asarray([self.grid - 1, self.grid - 1], jnp.int32)
    return jnp.broadcast_to(corner[None], (batch, 2))

  def _wall_plane(self, walls):
    """[B, G, G] layout → [B, H, W] bool: pixel (h, w) shows cell
    (rows[h], cols[w]). The map from pixel to cell is static, so the
    layout is EXPANDED along the two one-hot maps, elementwise in B,
    and never looked up per pixel: on a TPU v5e an advanced-index
    gather of this plane takes 24 ms at B=512, 64x64, this 0.05
    (PERF.md §6, PR 31). Exact in bfloat16: every factor is 0 or 1 and
    each sum has exactly one non-zero term."""
    rows, cols = self._row_onehot, self._col_onehot
    return jnp.einsum('bij,hi,wj->bhw', walls.astype(rows.dtype),
                      rows, cols) > 0

  def _observation(self, state):
    agent = self._cell_plane(state.agent_yx)
    goal = self._cell_plane(state.goal_yx)
    wall_plane = self._wall_plane(self._walls(state.level_id))
    frame = jnp.stack(
        [agent.astype(jnp.uint8) * 255, goal.astype(jnp.uint8) * 255,
         wall_plane.astype(jnp.uint8) * 255], axis=-1)
    return (frame, _zero_instr(state.agent_yx.shape[0]))

  def init(self, rng, batch) -> Tuple[ProcgenState, StepOutput]:
    rng, sub = jax.random.split(rng)
    state = ProcgenState(
        rng=rng,
        agent_yx=jnp.zeros((batch, 2), jnp.int32),
        goal_yx=self._goal_corner(batch),
        step_in_episode=jnp.zeros((batch,), jnp.int32),
        episode_return=jnp.zeros((batch,), jnp.float32),
        episode_frames=jnp.zeros((batch,), jnp.int32),
        level_id=jax.random.randint(sub, (batch,), 0,
                                    self.num_levels),
        level_scores=jnp.zeros((self.num_levels,), jnp.float32),
        level_visits=jnp.zeros((self.num_levels,), jnp.float32))
    output = StepOutput(
        reward=jnp.zeros((batch,), jnp.float32),
        info=StepOutputInfo(jnp.zeros((batch,), jnp.float32),
                            jnp.zeros((batch,), jnp.int32)),
        done=jnp.ones((batch,), bool),
        observation=self._observation(state))
    return state, output

  def _blocked(self, state, proposed):
    walls = self._walls(state.level_id)
    return walls[jnp.arange(proposed.shape[0]), proposed[:, 0],
                 proposed[:, 1]]

  def _fresh_episode(self, rng, batch, state):
    """New-episode level draw: uniform, or the round-22 prioritized
    curriculum sampler — one in-graph categorical over the per-level
    score EMAs carried in `state` (population.sample_levels), so a
    driven level distribution costs zero host round trips."""
    if self.curriculum == 'uniform':
      fresh = jax.random.randint(rng, (batch,), 0, self.num_levels)
    else:
      from scalable_agent_tpu import population
      fresh = population.sample_levels(
          rng, state.level_scores, batch,
          self.curriculum_temperature,
          self.curriculum_eps).astype(jnp.int32)
    return self._goal_corner(batch), fresh

  def _replace_episode(self, state, rng, agent_yx, goal_yx,
                       step_in_episode, episode_return, episode_frames,
                       done, fresh_extra):
    return ProcgenState(
        rng, agent_yx, goal_yx, step_in_episode, episode_return,
        episode_frames,
        level_id=jnp.where(done, fresh_extra, state.level_id),
        level_scores=state.level_scores,
        level_visits=state.level_visits)


# The jittable registry anakin.ENV_CORES extends — one name, two
# runtimes (the host wrappers below resolve through the same dict).
JITTABLE_CORES = {'gridworld': GridworldCore, 'procgen': ProcgenCore}


@functools.lru_cache(maxsize=None)
def _host_cpu_device():
  """The CPU device host wrappers pin their tiny batch=1 core steps
  to: on a TPU host, fleet env threads must never queue work on the
  learner chip (under JAX_PLATFORMS=cpu this is just the default)."""
  return jax.local_devices(backend='cpu')[0]


class _JittableHostEnv(base.Environment):
  """Host `envs/base.Environment` over a jittable core at batch=1.

  The fleet-runtime half of the dual registration: dynamics come from
  the SAME core the Anakin runtime scans on device (no twin
  implementation to drift), stepped eagerly on the CPU backend and
  squeezed to the host protocol's scalar shapes. Auto-reset and
  flow-style stats are already inside the core's step.
  """

  _CORE_NAME = None  # subclasses pin this (py_process pickles classes)

  def __init__(self, height, width, num_actions, episode_length,
               seed=0, level_name='', num_action_repeats=1,
               num_levels=None, wall_density=None):
    del level_name  # identity rides the factory's level id stamping
    core_cls = JITTABLE_CORES[self._CORE_NAME]
    extra = {} if num_levels is None else {'num_levels': num_levels}
    if wall_density is not None:
      extra['wall_density'] = wall_density
    self._core = core_cls(height=height, width=width,
                          episode_length=episode_length,
                          num_action_repeats=num_action_repeats,
                          num_actions=num_actions, **extra)
    with jax.default_device(_host_cpu_device()):
      self._state, out = self._core.init(jax.random.PRNGKey(seed), 1)
    self._obs = self._host_obs(out)

  def _host_obs(self, out):
    frame, instr = out.observation
    return (np.asarray(frame[0]), np.asarray(instr[0]))

  def initial(self):
    return self._obs

  def step(self, action):
    with jax.default_device(_host_cpu_device()):
      self._state, out = self._core.step(
          self._state, jnp.asarray([int(action)], jnp.int32))
    self._obs = self._host_obs(out)
    return (np.float32(np.asarray(out.reward)[0]),
            np.bool_(np.asarray(out.done)[0]), self._obs)

  @staticmethod
  def _tensor_specs(method_name, unused_kwargs, constructor_kwargs):
    h = constructor_kwargs.get('height', 24)
    w = constructor_kwargs.get('width', 32)
    if method_name == 'initial':
      return base.observation_specs(h, w, MAX_INSTRUCTION_LEN)
    if method_name == 'step':
      return base.step_output_specs(h, w, MAX_INSTRUCTION_LEN)
    return None


class GridworldEnv(_JittableHostEnv):
  _CORE_NAME = 'gridworld'


class ProcgenEnv(_JittableHostEnv):
  _CORE_NAME = 'procgen'


HOST_ENVS = {'gridworld': GridworldEnv, 'procgen': ProcgenEnv}

# The factory's head-size default per backend (config.num_actions=None).
DEFAULT_NUM_ACTIONS = {'gridworld': 4, 'procgen': 4}

"""Population engine (round 22, ROADMAP item 4): the pure functions
behind in-graph auto-curriculum, heterogeneous fleet composition, and
minimal PBT across learner replicas.

Three concerns, one module, zero heavy imports — everything here is
either jit-traceable (the curriculum math rides INSIDE the fused
Anakin step, parallel/anakin.py) or a tiny host-side planner the
driver calls between rounds:

1. CURRICULUM (in-graph): `ProcgenCore`'s finite level-id space
   (envs/jittable.py) becomes a driven distribution. Per-level
   regret/TD-error EMAs accumulate inside the fused step
   (`score_signal` + `update_scores`, segment-sum over the unroll's
   transition-level ids) and the next episode's level id is drawn from
   an epsilon-smoothed softmax over those scores (`level_probs` +
   `sample_levels` — a `jax.random.categorical`, i.e. Gumbel-argmax,
   so the prioritized draw is one fused op with zero host round
   trips). Staleness is handled by DECAY: a level the batch never
   visited has its score multiplied by `decay < 1`, so a stale "hard"
   level drifts back toward the smoothed floor instead of starving
   forever. 'regret' scores positive value loss (the PLR positive
   value-loss proxy, arXiv 2010.03934: levels where returns EXCEED
   the baseline — learnable, not yet learned); 'td' scores |delta|
   (symmetric surprise).

2. FLEET COMPOSITION (host-side): `parse_fleet_tasks` /
   `plan_actor_assignment` turn a `--fleet_tasks='bandit:2,gridworld:2'`
   spec into a per-actor task plan (largest-remainder apportionment —
   the per-task frame budget IS the actor share, since every actor
   contributes frames at the same cadence), and `padding_report`
   quantifies what obs-spec FAMILY bucketing buys: merges that never
   cross families pad zero bytes beyond the family's own frame shape,
   vs naive max-shape padding across the whole fleet.

3. PBT (host-side, process-0-owned per the round-12 per-actuator
   ownership rule): `pbt_decide` ranks members WITHIN comparable
   groups (same suite — cross-suite returns are not commensurable),
   bottom-quantile members exploit a top-quantile donor's weights
   (inheritance travels through the round-2 checkpoint ladder:
   the donor's VERIFIED save is the transfer medium, and the
   inheritor's next restore re-verifies digests), and `pbt_explore`
   perturbs (lr, entropy_cost) multiplicatively — the minimal PBT of
   arXiv 1711.09846. Deterministic under a seeded generator: the
   driver derives one per round, so a re-run replays the decisions.

The driver wires these into `train_anakin` (curriculum telemetry +
CURRICULUM_LEVELS.json), `train_population` (the one-invocation
population run), and `make_fleet` (mixed-suite actor assignment);
docs/PARALLELISM.md carries the operator story. No benchmark cell
runs a curriculum or a population yet.
"""

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The config axis (config.curriculum; experiment.py --curriculum).
CURRICULUM_MODES = ('uniform', 'regret', 'td')

# The two (hyper)parameters minimal PBT explores over — matching the
# IMPALA paper's own PBT axes (learning rate, entropy cost).
PBT_HYPERS = ('learning_rate', 'entropy_cost')


# --------------------------------------------------------------------
# In-graph curriculum (all jit-traceable; no host round trips).
# --------------------------------------------------------------------


def level_probs(scores, temperature: float, eps: float):
  """Sampling distribution over levels: epsilon-smoothed softmax.

  `(1-eps) * softmax(normalize(scores) / temperature) + eps / n` —
  the eps floor guarantees every level keeps nonzero visitation
  probability (the staleness escape hatch: decayed scores PLUS
  guaranteed revisits mean no level's score can silently fossilize).

  normalize() divides by the max score (clipped away from zero), so
  prioritization is SCALE-FREE: TD/regret magnitudes depend on the
  env's reward scale and the training phase (early procgen deltas
  are ~1e-2), and an un-normalized softmax at temperature 1.0 would
  stay indistinguishable from uniform no matter how skewed the
  scores. After normalization the hottest level sits at 1.0 by
  construction and `temperature` has a fixed meaning: max-to-min
  odds of e^(1/temperature) before the eps floor, whatever the
  reward units. All-zero scores normalize to all-zero → uniform."""
  scores = jnp.asarray(scores, jnp.float32)
  n = scores.shape[0]
  norm = scores / jnp.maximum(jnp.max(scores), 1e-8)
  soft = jax.nn.softmax(norm / jnp.maximum(temperature, 1e-6))
  return (1.0 - eps) * soft + eps / n


def sample_levels(rng, scores, batch: int, temperature: float,
                  eps: float):
  """Draw `batch` level ids from `level_probs` — one
  `jax.random.categorical` (Gumbel-argmax over log-probs), so the
  prioritized sampler is a single fused op inside the device step."""
  logits = jnp.log(level_probs(scores, temperature, eps))
  return jax.random.categorical(rng, logits, shape=(batch,))


def score_signal(delta, mode: str):
  """Per-transition priority signal from the TD error `delta`.

  'regret': relu(delta) — the PLR positive-value-loss proxy (returns
  exceeded the baseline: the level is learnable and not yet learned;
  a level the policy has mastered OR cannot score on goes to zero).
  'td': |delta| — symmetric surprise."""
  if mode == 'regret':
    return jax.nn.relu(delta)
  if mode == 'td':
    return jnp.abs(delta)
  raise ValueError(f'unknown curriculum mode {mode!r} '
                   f'(signal modes: regret, td)')


def update_scores(scores, visits, level_ids, signals, alpha: float,
                  decay: float):
  """EMA the per-level scores from one unroll's transition signals.

  `level_ids`/`signals`: [T-1, B] (or any matching shape) transition
  level ids and priority signals. Levels visited this step move
  `(1-alpha)*s + alpha*mean(signal)`; unvisited levels DECAY
  (`decay*s` — staleness handling: an unvisited level's stale score
  loses authority over time). Returns (scores, visits) with visits
  incremented by per-level transition counts. Pure and traceable —
  under a sharded batch the segment sums reduce across devices via
  the partitioner's inserted psum."""
  scores = jnp.asarray(scores, jnp.float32)
  n = scores.shape[0]
  ids = jnp.reshape(level_ids, (-1,))
  sig = jnp.reshape(jnp.asarray(signals, jnp.float32), (-1,))
  sums = jax.ops.segment_sum(sig, ids, num_segments=n)
  counts = jax.ops.segment_sum(jnp.ones_like(sig), ids,
                               num_segments=n)
  visited = counts > 0
  means = sums / jnp.maximum(counts, 1.0)
  new_scores = jnp.where(visited, (1.0 - alpha) * scores + alpha * means,
                         decay * scores)
  return new_scores, visits + counts


def curriculum_metrics(scores, visits, temperature: float,
                       eps: float) -> Dict[str, Any]:
  """Scalar telemetry for the summary stream (traceable; the fused
  step folds these into its metrics dict): sampling-distribution
  entropy (uniform = log n; collapse → 0), score spread, and how many
  levels have ever been visited."""
  p = level_probs(scores, temperature, eps)
  entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-12)))
  return {
      'curriculum_entropy': entropy,
      'curriculum_score_mean': jnp.mean(scores),
      'curriculum_score_max': jnp.max(scores),
      'curriculum_levels_visited': jnp.sum(
          (visits > 0).astype(jnp.float32)),
  }


# --------------------------------------------------------------------
# Heterogeneous fleet composition (host-side planning).
# --------------------------------------------------------------------


def parse_fleet_tasks(spec: str) -> List[Tuple[str, float]]:
  """Parse `--fleet_tasks='bandit:2,gridworld:1'` into
  [(backend, weight)] — weights are RELATIVE actor (and therefore
  frame-budget) shares. A bare name means weight 1."""
  tasks = []
  for part in spec.split(','):
    part = part.strip()
    if not part:
      continue
    if ':' in part:
      name, _, weight = part.partition(':')
      try:
        w = float(weight)
      except ValueError:
        raise ValueError(f'fleet_tasks weight {weight!r} for task '
                         f'{name!r} is not a number')
    else:
      name, w = part, 1.0
    name = name.strip()
    if not name:
      raise ValueError(f'fleet_tasks entry {part!r} has no task name')
    if w <= 0:
      raise ValueError(f'fleet_tasks weight for {name!r} must be > 0, '
                       f'got {w}')
    if any(existing == name for existing, _ in tasks):
      raise ValueError(f'fleet_tasks names {name!r} twice')
    tasks.append((name, w))
  return tasks


def plan_actor_assignment(tasks: Sequence[Tuple[str, float]],
                          num_actors: int) -> List[int]:
  """Apportion `num_actors` across weighted tasks (largest-remainder,
  every task guaranteed >= 1 actor) and return the per-actor task
  index, interleaved round-robin so partial fleets (or a drained
  host's survivors) still sample every task.

  The per-task FRAME BUDGET falls out of this plan: actors produce
  frames at the same cadence, so a task's actor share IS its share of
  the fresh-frame budget (driver.train logs both)."""
  if not tasks:
    raise ValueError('plan_actor_assignment needs at least one task')
  if num_actors < len(tasks):
    raise ValueError(f'{num_actors} actor(s) cannot cover '
                     f'{len(tasks)} task(s) at >= 1 actor each')
  weights = np.asarray([w for _, w in tasks], np.float64)
  quotas = num_actors * weights / weights.sum()
  counts = np.maximum(np.floor(quotas).astype(int), 1)
  # Largest remainder for the leftover seats (ties break by index —
  # deterministic for a given spec).
  while counts.sum() < num_actors:
    frac = quotas - counts  # remainders recompute against bumped counts
    counts[int(np.argmax(frac))] += 1
  while counts.sum() > num_actors:
    # The >=1 floor can overshoot tiny fleets; shave the largest
    # overage but never below 1.
    over = counts - quotas
    over[counts <= 1] = -np.inf
    counts[int(np.argmax(over))] -= 1
  # Round-robin interleave: cycle tasks, emitting each until its count
  # is spent.
  remaining = counts.copy()
  plan: List[int] = []
  while len(plan) < num_actors:
    for i in range(len(tasks)):
      if remaining[i] > 0:
        plan.append(i)
        remaining[i] -= 1
        if len(plan) == num_actors:
          break
  return plan


def frame_bytes(frame_shape: Sequence[int], dtype_bytes: int = 1
                ) -> int:
  """Bytes of one observation frame (uint8 frames by default)."""
  n = dtype_bytes
  for d in frame_shape:
    n *= int(d)
  return n


def padding_report(family_counts: Dict[Tuple[int, ...], int]
                   ) -> Dict[str, float]:
  """What obs-spec FAMILY bucketing buys over naive max-shape padding.

  `family_counts`: {frame_shape: frames_served}. Family-bucketed
  merges never cross obs specs, so each frame costs exactly its own
  family's bytes; a naive single-queue batcher must pad every frame to
  the fleet-wide max shape. Returns padded-bytes-per-useful-frame for
  both policies plus the waste ratio — the bench's mixed-suite row."""
  if not family_counts:
    return {'useful_bytes': 0.0, 'bucketed_bytes': 0.0,
            'max_shape_bytes': 0.0, 'bucketed_bytes_per_frame': 0.0,
            'max_shape_bytes_per_frame': 0.0, 'waste_ratio': 0.0}
  max_frame = max(frame_bytes(s) for s in family_counts)
  frames = sum(family_counts.values())
  useful = float(sum(frame_bytes(s) * c
                     for s, c in family_counts.items()))
  naive = float(max_frame * frames)
  return {
      'useful_bytes': useful,
      'bucketed_bytes': useful,  # family merges pad zero extra bytes
      'max_shape_bytes': naive,
      'bucketed_bytes_per_frame': useful / frames,
      'max_shape_bytes_per_frame': naive / frames,
      'waste_ratio': (naive - useful) / naive if naive else 0.0,
  }


# --------------------------------------------------------------------
# Minimal PBT (host-side; the driver's process-0 decision loop).
# --------------------------------------------------------------------


def pbt_explore(hypers: Dict[str, float], rng: np.random.Generator,
                perturb: float) -> Dict[str, float]:
  """Perturb each hyper multiplicatively by `perturb` or `1/perturb`
  (independent fair coins — arXiv 1711.09846's explore step).
  Iteration order is sorted for determinism under a seeded rng."""
  out = dict(hypers)
  for name in sorted(hypers):
    factor = perturb if rng.random() < 0.5 else 1.0 / perturb
    out[name] = float(hypers[name] * factor)
  return out


def pbt_decide(returns: Sequence[float], groups: Sequence[Any],
               rng: np.random.Generator, quantile: float = 0.25,
               perturb: float = 1.2,
               hypers: Optional[Sequence[Dict[str, float]]] = None
               ) -> List[Optional[Dict[str, Any]]]:
  """One PBT round's exploit/explore decisions.

  `returns[i]` is member i's recent mean episode return; `groups[i]`
  its comparability group (the SUITE — cross-suite returns are not on
  one scale, so ranking stays within-group). In each group with >= 2
  members, the bottom `quantile` members exploit a donor drawn
  uniformly from the top `quantile` (weights via the checkpoint
  ladder, hypers via `pbt_explore`). Returns a per-member decision:
  None (keep training) or {'donor': j, 'hypers': {...}} (only when
  the donor strictly outperforms — equal-return pairs keep)."""
  n = len(returns)
  if hypers is not None and len(hypers) != n:
    raise ValueError(f'{len(hypers)} hyper sets for {n} members')
  decisions: List[Optional[Dict[str, Any]]] = [None] * n
  for g in sorted(set(groups), key=repr):
    idx = [i for i in range(n) if groups[i] == g]
    if len(idx) < 2:
      continue
    ranked = sorted(idx, key=lambda i: (returns[i], i))
    k = max(1, int(round(quantile * len(idx))))
    k = min(k, len(idx) // 2)  # bottom and top never overlap
    bottom, top = ranked[:k], ranked[-k:]
    for i in bottom:
      donor = top[int(rng.integers(len(top)))]
      if returns[donor] <= returns[i]:
        continue
      donor_hypers = dict(hypers[donor]) if hypers is not None else {}
      decisions[i] = {
          'donor': donor,
          'hypers': pbt_explore(donor_hypers, rng, perturb),
      }
  return decisions

#!/usr/bin/env bash
# CI entry: everything the repo can verify without real simulators.
# (The reference ships no CI at all — SURVEY §4 "no CI config"; this is
# the do-better path.) Exits nonzero on any failure.
set -euo pipefail
cd "$(dirname "$0")/.."

echo '== native batcher: build + stress test =='
make -C scalable_agent_tpu/ops/batcher clean all test

echo '== native batcher: ThreadSanitizer =='
make -C scalable_agent_tpu/ops/batcher tsan-test

echo '== unit + integration tests (CPU, 8 virtual devices) =='
python -m pytest tests/ -q

echo '== multi-chip sharding dry-run =='
python __graft_entry__.py

echo '== soak smoke (mechanics only: popart/pc stack runs, tiny shapes;'
echo '   the real flagship soak is scripts/soak.py on the chip) =='
SOAK_SMOKE=1 python scripts/soak.py

echo '== churn-soak smoke (env kill + respawn + resource sampling'
echo '   mechanics; the real >=20 min churn soak runs on the chip) =='
SOAK_SMOKE=1 SOAK_CHURN=1 python scripts/soak.py

echo '== chaos smoke (deterministic fault storm: env hang/crash +'
echo '   socket garbage + NaN burst + interrupted save; asserts zero'
echo '   learner crashes, >=1 rollback, monotone frames — <60 s) =='
CHAOS_SMOKE=1 CHAOS_STORM=fault python scripts/chaos.py

echo '== overload-chaos smoke (fleet at 2x inference slots under shed'
echo '   admission + slow-learner backpressure + REAL mid-storm'
echo '   SIGTERM -> drain -> verified checkpoint + resume manifest ->'
echo '   resume parity; plus the drain/resume + admission selector'
echo '   — <60 s CPU) =='
CHAOS_SMOKE=1 CHAOS_STORM=overload python scripts/chaos.py
JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py -q \
  -k 'drain or admission or shed or waitlist or staleness' \
  -p no:cacheprovider

echo '== partition-chaos smoke (remote feed under conn partition +'
echo '   delay faults, learner hard-killed (-9) mid-storm, restarted'
echo '   learner restores LAST_GOOD, fleet re-attaches within SLO,'
echo '   half-open peer reaped in budget, zero stale-epoch unrolls,'
echo '   zero wedged threads; plus the liveness/reattach selector'
echo '   — <90 s CPU) =='
CHAOS_SMOKE=1 CHAOS_STORM=partition python scripts/chaos.py
JAX_PLATFORMS=cpu python -m pytest tests/test_remote.py \
  tests/test_faults.py -q \
  -k 'reaped or heartbeat or busy or epoch or ping or partition or '\
'crash or unjoined or validate_transport' \
  -p no:cacheprovider

echo '== corruption-chaos smoke (the integrity plane end to end: a'
echo '   bit-flipped unroll refused before the buffer put + re-sent,'
echo '   a corrupt publish refused before install + refetched clean,'
echo '   an injected replica divergence detected + rolled back, and a'
echo '   bit-rotted committed checkpoint skipped via the digest'
echo '   ladder; plus the CRC/digest/SDC test selector — <90 s CPU) =='
CHAOS_SMOKE=1 CHAOS_STORM=corruption python scripts/chaos.py
JAX_PLATFORMS=cpu python -m pytest tests/test_remote.py \
  tests/test_checkpoint.py tests/test_health.py tests/test_faults.py \
  -q -k 'crc or digest or corrupt or bitflip or bitrot or sdc or '\
'fingerprint or discard or integrity' \
  -p no:cacheprovider

echo '== static-analysis lane (round 18: the invariant analyzer —'
echo '   the full contract-lint suite in scripts/lint.py replaces the'
echo '   old inline heredoc: metric names / SLO objectives /'
echo '   controller rules (the ported checks) + config-field flags,'
echo '   validate_* coverage, durable incident markers, protocol'
echo '   versions, summary scalars, the guarded_by lock-discipline'
echo '   AST pass, and the self-applied checker-inventory lint; then'
echo '   the seeded-violation self-tests (every checker proven able'
echo '   to fire) and the OrderedLock inversion-detector unit — the'
echo '   lint itself stays under ~20 s, docs/STATIC_ANALYSIS.md) =='
python scripts/lint.py
JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py -q \
  -p no:cacheprovider

echo '== slo lane (round 14: declarative objectives over the registry,'
echo '   burn-rate evaluation, triggered deep diagnostics, the'
echo '   SLO_VERDICT.json go/no-go artifact + slo_report regression'
echo '   gate; then a tiny driver run asserting the verdict lands with'
echo '   every default objective evaluated and zero captures on a'
echo '   clean run — <90 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q \
  -p no:cacheprovider
JAX_PLATFORMS=cpu python - <<'SLO_EOF'
import json, logging, os, subprocess, sys, tempfile
logging.basicConfig(level=logging.WARNING)
sys.path.insert(0, os.getcwd())
from scalable_agent_tpu import driver, slo
from scalable_agent_tpu.config import Config
logdir = tempfile.mkdtemp(prefix='ci_slo_')
cfg = Config(logdir=logdir, env_backend='bandit', num_actors=2,
             batch_size=2, unroll_length=5, num_action_repeats=1,
             episode_length=4, height=24, width=32, torso='shallow',
             use_py_process=False, use_instruction=False,
             total_environment_frames=10**9, inference_timeout_ms=5,
             checkpoint_secs=0, summary_secs=0, seed=11)
driver.train(cfg, max_steps=6, stall_timeout_secs=60)
verdict = slo.read_verdict(logdir)
assert verdict is not None, 'no SLO_VERDICT.json from the clean run'
assert verdict['pass'], f"clean run verdict FAILED: {verdict['violations']}"
assert not verdict['captures'], 'clean run triggered captures'
expected = {o.name for o in slo.DEFAULT_OBJECTIVES}
got = set(verdict['objectives'])
assert got == expected, f'verdict objectives {got ^ expected} out of sync'
for name, e in verdict['objectives'].items():
    # info objectives are advisory leading indicators (round 15) — a
    # toy env-bound run may burn learner_plane_utilization without
    # failing anything.
    assert (e['state'] in ('ok', 'no_data', 'no_baseline')
            or e['severity'] == 'info'), (name, e)
# The go/no-go gate agrees: slo_report exits 0 on the passing verdict.
rc = subprocess.run([sys.executable, 'scripts/slo_report.py', logdir],
                    stdout=subprocess.DEVNULL).returncode
assert rc == 0, f'slo_report exited {rc} on a passing verdict'
print(f'slo lane OK: {len(got)} objectives evaluated, verdict PASS, '
      'zero captures, slo_report gate green')
SLO_EOF

echo '== controller lane (round 15: the self-healing control plane —'
echo '   policy-table determinism, bounded escalate/revert with'
echo '   hysteresis, fleet elasticity + quarantine rehabilitation,'
echo '   then the load-surge storm: offered load doubles mid-run, the'
echo '   actuated run keeps SLO_VERDICT.json green with the'
echo '   escalation+revert in CONTROLLER_LOG.json while the observe'
echo '   run records the violation it avoided — <90 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_controller.py -q \
  -p no:cacheprovider
JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py \
  tests/test_replay.py tests/test_overload.py tests/test_slo.py \
  tests/test_remote.py -q \
  -k 'target_size or rehabilitat or probation or set_replay_k or '\
'set_admission or control_snapshot' \
  -p no:cacheprovider
CHAOS_SMOKE=1 CHAOS_STORM=controller python scripts/chaos.py

echo '== anakin-runtime lane (round 16: the --runtime={fleet,anakin}'
echo '   axis — jittable env family semantics + mesh sharding, the'
echo '   hybrid filler (yield determinism, fresh-vs-filler frame'
echo '   accounting), then a tiny --runtime=anakin driver run'
echo '   asserting the full lifecycle artifacts land (SLO_VERDICT'
echo '   green, summaries/incidents JSONL, checkpoint restore)'
echo '   — <120 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_anakin.py \
  tests/test_filler.py -q -p no:cacheprovider
JAX_PLATFORMS=cpu python - <<'ANAKIN_EOF'
import json, logging, os, sys, tempfile
logging.basicConfig(level=logging.WARNING)
sys.path.insert(0, os.getcwd())
from scalable_agent_tpu import driver, slo
from scalable_agent_tpu.config import Config
logdir = tempfile.mkdtemp(prefix='ci_anakin_')
cfg = Config(logdir=logdir, runtime='anakin', env_backend='cue_memory',
             batch_size=4, unroll_length=5, num_action_repeats=1,
             height=24, width=32, torso='shallow', use_py_process=False,
             use_instruction=False, summary_secs=0, checkpoint_secs=0,
             total_environment_frames=6 * 4 * 5, seed=5)
run = driver.train(cfg)   # dispatches on --runtime
assert run.frames == 120, run.frames
verdict = slo.read_verdict(logdir)
assert verdict is not None, 'no SLO_VERDICT.json from the anakin run'
assert verdict['pass'], f"anakin verdict FAILED: {verdict['violations']}"
for stream in ('summaries.jsonl', 'incidents.jsonl', 'config.json'):
    assert os.path.exists(os.path.join(logdir, stream)), stream
# Checkpoint restore: a second run on the same logdir resumes at the
# already-met frame target instead of training from step 0.
run2 = driver.train(cfg)
assert run2.frames == 120, run2.frames
print('anakin lane OK: 6 fused steps, verdict PASS, restore green')
ANAKIN_EOF

echo '== multihost lane (round 17: the real multi-process runtime —'
echo '   2 OS processes join jax.distributed over gloo CPU collectives'
echo '   and run the FULL driver over one mesh: per-host fleets'
echo '   feeding process-local shards, the cross-process gradient'
echo '   psum, broadcast-gated collective checkpoints + the SIGKILL'
echo '   drill, the SDC all-gather rollback drill, cross-host trace'
echo '   joins; the validate_distributed/slot-placement unit half'
echo '   runs first.'
echo '   Round 19: the heavy drills (mixed topology, kill drills,'
echo '   cross-process TP) are slow-marked OUT of tier-1 and run HERE'
echo '   — the whole file, no -m filter — <600 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_multihost_unit.py -q \
  -p no:cacheprovider
# Children strip JAX_PLATFORMS/XLA_FLAGS themselves and force their
# own per-process virtual-device topology.
python -m pytest \
  tests/test_multihost.py \
  tests/test_multihost_extra.py \
  -q -p no:cacheprovider

echo '== elastic lane (round 20: elastic pod membership — the'
echo '   resharding edge-case unit tests + v9 membership-ledger units,'
echo '   the 2-proc -> 4-proc checkpoint-reshard parity drill, and the'
echo '   elastic storm smoke: SIGKILL an actor host mid-run, the'
echo '   controller raises POD_TARGET.json, the grow-only supervisor'
echo '   spawns the replacement, it JOINS the live learner, verdict'
echo '   green with zero knob-turning — <300 s CPU) =='
XLA_FLAGS='--xla_force_host_platform_device_count=8' \
  JAX_PLATFORMS=cpu python -m pytest tests/test_sharding.py -q \
  -k 'layout or reshard or topology' -p no:cacheprovider
JAX_PLATFORMS=cpu python -m pytest tests/test_remote.py -q \
  -k 'membership' -p no:cacheprovider
python -m pytest \
  "tests/test_multihost.py::test_reshard_checkpoint_2_to_4_processes" \
  -q -p no:cacheprovider
CHAOS_SMOKE=1 CHAOS_STORM=elastic python scripts/chaos.py

echo '== telemetry smoke (trace spans end to end: registry semantics,'
echo '   tracer pipeline, v8 negotiation + remote stamping,'
echo '   trace_report reconstruction — <60 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py \
  tests/test_observability.py -q -p no:cacheprovider

echo '== inference-plane smoke (state-cache golden parity + slot'
echo '   lifecycle selector — <60 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_runtime.py \
  tests/test_parallel.py -q \
  -k 'state_cache or slot or inflight or version_gate or arena' \
  -p no:cacheprovider

echo '== learner-plane smoke (on-device assembly golden parity +'
echo '   failure paths + sharded Pallas V-trace parity selector'
echo '   — <60 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_learner_plane.py \
  "tests/test_parallel.py::test_pallas_vtrace_sharded_step_matches_single_device" \
  -q -p no:cacheprovider

echo '== sample-reuse smoke (circular replay tier + staged-arena'
echo '   re-serve lifecycle + IMPACT clipped-target parity selector'
echo '   — <60 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_replay.py \
  -q -k 'parity or tier or compos or validation or cadence' \
  -p no:cacheprovider
JAX_PLATFORMS=cpu python -m pytest tests/test_learner_plane.py \
  -q -k 'reserve or reuse' -p no:cacheprovider

echo '== pixel-control fast-path parity (integer rewards + d2s head'
echo '   + bf16-Q levers vs the r5 reference forms — <60 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_unreal.py -q \
  -k 'parity or fast_path or bf16' -p no:cacheprovider

echo '== v5e-16 AOT memory-fit smoke (compiled per-device HBM check'
echo '   mechanics on 8 virtual devices; flagship check runs in the'
echo '   multi-chip dry-run artifact — <60 s CPU) =='
SMOKE=1 JAX_PLATFORMS=cpu python scripts/aot_fit.py

echo '== torso return-comparison smoke (deep vs deep_fast harness'
echo '   mechanics; the real head-to-head is scripts/compare_torsos.py'
echo '   on the chip) =='
SMOKE=1 JAX_PLATFORMS=cpu python scripts/compare_torsos.py

echo '== byte-attribution smoke (cost_analysis mechanics + the'
echo '   round-6 feature itemization rows) =='
SMOKE=1 python scripts/attribute_bytes.py

echo '== conv-lever smoke (variant mechanics + argmax-VJP parity) =='
SMOKE=1 python scripts/conv_levers.py

echo '== pallas fused conv+pool smoke (interpret-mode parity) =='
SMOKE=1 python scripts/pallas_conv_pool.py

echo '== sharding lane (round 19: the declarative registry as the one'
echo '   source of sharding truth — rule/guard/opt-clone semantics,'
echo '   the consumers-agree contract, the checkpoint manifest +'
echo '   cross-mesh resharded restore, and the 2D {data,model} deep-'
echo '   agent parity gate; then the sharding-registry lint'
echo '   (no inline PartitionSpec outside parallel/sharding.py)'
echo '   — <2 min CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_sharding.py -q \
  -p no:cacheprovider
python scripts/lint.py --check sharding-registry

echo '== serving lane (round 21: the multi-tenant serving plane — the'
echo '   version-table/codec/AOT/routing/wire-v10 unit suite + the'
echo '   slow-marked 3-process routed drill, then the routed chaos'
echo '   storm: SIGKILL a'
echo '   serving replica under judged traffic, the router fails over'
echo '   with zero starvation and a green routed-latency verdict'
echo '   — <120 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q \
  -p no:cacheprovider
CHAOS_SMOKE=1 CHAOS_STORM=routed python scripts/chaos.py

echo '== population lane (round 22: the population engine — in-graph'
echo '   curriculum sampler + mixed-fleet bucket-composition + PBT'
echo '   exploit/explore units, the slow learning-curve gate and the'
echo '   one-invocation two-suite population drills (no -m filter:'
echo '   the slow-marked curves run HERE), then a tiny real'
echo '   --runtime=anakin --curriculum=regret driver run asserting'
echo '   verdict PASS + per-level telemetry in summaries +'
echo '   CURRICULUM_LEVELS.json — <600 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_population.py -q \
  -p no:cacheprovider
JAX_PLATFORMS=cpu python - <<'POP_EOF'
import json, logging, os, sys, tempfile
logging.basicConfig(level=logging.WARNING)
sys.path.insert(0, os.getcwd())
from scalable_agent_tpu import driver, slo
from scalable_agent_tpu.config import Config
logdir = tempfile.mkdtemp(prefix='ci_pop_')
cfg = Config(logdir=logdir, runtime='anakin', env_backend='procgen',
             curriculum='regret', procgen_num_levels=4,
             batch_size=4, unroll_length=5, num_action_repeats=1,
             height=24, width=32, torso='shallow', use_py_process=False,
             use_instruction=False, summary_secs=0, checkpoint_secs=0,
             total_environment_frames=6 * 4 * 5, seed=7)
run = driver.train(cfg)
assert run.frames == 120, run.frames
verdict = slo.read_verdict(logdir)
assert verdict is not None and verdict['pass'], verdict
tags = set()
with open(os.path.join(logdir, 'summaries.jsonl')) as f:
    for line in f:
        tags.add(json.loads(line)['tag'])
for tag in ('curriculum_entropy', 'curriculum_levels_visited'):
    assert tag in tags, (tag, sorted(tags))
levels = json.load(open(os.path.join(logdir, 'CURRICULUM_LEVELS.json')))
assert levels['curriculum'] == 'regret'
assert len(levels['visits']) == 4 and sum(levels['visits']) > 0, levels
print('population lane OK: regret curriculum in-graph, verdict PASS, '
      'per-level telemetry landed')
POP_EOF

echo '== fused population + compile cache lane (round 23: vmapped PBT'
echo '   members in ONE Anakin program, on-device weight inheritance,'
echo '   persistent compilation cache — the compile-cache unit tests,'
echo '   a tiny N=2 fused driver run asserting PBT_LOG.json records'
echo '   vectorized=true + verdict PASS + per-member ladders, and a'
echo '   two-process cache smoke: process A compiles into the one'
echo '   placed cache dir (JAX_COMPILATION_CACHE_DIR, else'
echo '   <checkout>/.jax_cache), process B proves a cache HIT via the'
echo '   jax monitoring events — <300 s CPU) =='
JAX_PLATFORMS=cpu python -m pytest tests/test_compile_cache.py -q \
  -p no:cacheprovider
JAX_PLATFORMS=cpu python - <<'FUSED_EOF'
import json, logging, os, sys, tempfile
logging.basicConfig(level=logging.WARNING)
sys.path.insert(0, os.getcwd())
from scalable_agent_tpu import driver, slo
from scalable_agent_tpu.config import Config
logdir = tempfile.mkdtemp(prefix='ci_fused_pop_')
cfg = Config(logdir=logdir, runtime='anakin', env_backend='gridworld',
             pbt_population=2, pbt_vectorized=True,
             pbt_suites='gridworld', pbt_round_frames=80,
             pbt_quantile=0.5, batch_size=4, unroll_length=4,
             num_action_repeats=1, height=24, width=32,
             torso='shallow', use_py_process=False,
             use_instruction=False, summary_secs=0, checkpoint_secs=0,
             total_environment_frames=160, seed=7)
run = driver.train(cfg)
log = json.load(open(os.path.join(logdir, 'PBT_LOG.json')))
assert log['vectorized'] is True, log
assert len(log['rounds']) == 2 and log['winner'] is not None, log
verdict = slo.read_verdict(logdir)
assert verdict is not None and verdict['pass'], verdict
for k in range(2):
    member = os.path.join(logdir, 'member_%02d' % k)
    assert os.listdir(os.path.join(member, 'checkpoints')), member
    assert os.path.exists(os.path.join(member, 'summaries.jsonl'))
print('fused population OK: one program, %d round(s), winner member '
      '%d, verdict PASS' % (len(log['rounds']),
                            log['winner']['member']))
FUSED_EOF
JAX_PLATFORMS=cpu CI_CACHE_PHASE=fill python scripts/_compile_cache_smoke.py
JAX_PLATFORMS=cpu CI_CACHE_PHASE=hit python scripts/_compile_cache_smoke.py

echo 'CI OK'

"""Experiment configuration.

Flag *names* mirror the reference (experiment.py ≈L30–75) so an operator
of the reference finds the same knobs; defaults are the paper's tuned
DMLab values. A dataclass + absl-flags overlay replaces TF1 app flags
(SURVEY §5.6).
"""

import dataclasses
import os
from typing import List, Optional

# Where the persistent compilation cache lives when nothing outside
# places it: ONE fixed path inside the checkout. The directory is how
# a later run finds the entries again, so it never derives from a
# logdir, a temp name, a pid or a time.
REPO_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


@dataclasses.dataclass
class Config:
  # Experiment / run control.
  logdir: str = '/tmp/agent'
  mode: str = 'train'                     # train | test
  test_num_episodes: int = 10

  # Distributed topology (reference: --job_name/--task over gRPC;
  # here: jax.distributed process topology + host actor fleets).
  task: int = -1
  job_name: str = 'learner'
  num_actors: int = 4
  # Multi-process spin-up (round 17): driver.train joins the
  # jax.distributed runtime itself when a coordinator is named —
  # 'host:port' of process 0 (the reference's learner-address role,
  # minus the parameter server). Empty = single-host, or the caller
  # already initialized (the launcher / test harness path); both are
  # no-ops here. num_processes is the total host-process count;
  # process_id is this process's index (-1 = defer to max(task, 0),
  # the reference's --task spelling).
  coordinator_address: str = ''
  num_processes: int = 1
  process_id: int = -1

  # Training.
  total_environment_frames: int = int(1e9)
  batch_size: int = 2
  unroll_length: int = 100
  num_action_repeats: int = 4
  seed: int = 1

  # Loss.
  entropy_cost: float = 0.00025
  baseline_cost: float = 0.5
  discounting: float = 0.99
  reward_clipping: str = 'abs_one'        # abs_one | soft_asymmetric | none

  # Environment.
  dataset_path: str = ''
  level_cache_dir: str = ''               # DMLab compiled-map cache
                                          # override ('' = adapter
                                          # default)
  level_name: str = 'explore_goal_locations_small'
  width: int = 96
  height: int = 72

  # Optimizer (RMSProp, poly-decay to 0 over total frames).
  learning_rate: float = 0.00048
  decay: float = 0.99
  momentum: float = 0.0
  epsilon: float = 0.1

  # TPU-build additions (not in the reference).
  env_backend: str = 'dmlab'              # dmlab | atari | fake |
                                          # bandit | cue_memory |
                                          # gridworld | procgen | tokens
  num_actions: Optional[int] = None       # backend default when None
  sticky_action_prob: float = 0.0         # Atari: per-frame previous-
                                          # action repeat prob (0.25 =
                                          # Machado et al. eval
                                          # protocol; 0 = reference-era
                                          # deterministic)
  episode_length: int = 100               # fake/bandit only (cue_memory
                                          # is fixed two-step episodes)
  use_py_process: bool = True             # host each env in its own process
  publish_params_every: int = 1           # actor weight-snapshot cadence
  model_parallelism: int = 1              # TP width of the mesh
  # How TP matmuls execute (round 17): 'auto' = true sharded compute
  # on TPU/GPU, the 'gathered' workaround on CPU (this jaxlib's
  # partitioner mis-computes DIFFERENTIATED programs over model-
  # sharded leaves — params stay TP-sharded at rest, each step runs
  # gather -> replicated compute -> scatter; parity-gated in
  # tests/test_parallel.py and the tp4 multihost child).
  # 'sharded' | 'gathered' force either path.
  tp_compute: str = 'auto'
  # Which partition-rule set the sharding registry resolves from
  # (round 19, parallel/sharding.py — the ONE source of sharding
  # truth). 'auto' = 'megatron' when model_parallelism > 1 (TP cuts on
  # Dense/LSTM/Conv output features), 'replicated' (pure DP) otherwise
  # — i.e. defaults are unchanged. Naming a set explicitly pins it
  # regardless of the mesh shape.
  sharding_rules: str = 'auto'
  torso: str = 'deep'                     # deep | deep_fast | shallow
  scan_unroll: int = 10                   # LSTM time-scan unroll factor
                                          # (v5e sweep at T=100, B=32:
                                          # 1→40.8ms 5→40.5 10→39.3
                                          # 25→39.1; 10 balances the
                                          # win against compile time)
  # Language/instruction channel. None = auto by task: ON for
  # multi-task dmlab30 and language_*/psychlab_* levels, OFF otherwise
  # — the encoder costs ~6% step time (docs/PERF.md) and single-task
  # levels emit constant/empty instructions. The reference always runs
  # its language net; set True to match it exactly. MIGRATION: the
  # encoder's params are part of the checkpoint structure — resuming a
  # run trained when the default was True (pre-auto) on a non-language
  # level needs an explicit --use_instruction=true.
  use_instruction: Optional[bool] = None
  compute_dtype: str = 'float32'          # float32 | bfloat16
  # The model (PR 27). 'impala': the paper's conv torso + LSTM core
  # over frames. 'sequence': token embedding -> retention blocks ->
  # heads over the vocabulary (models/sequence.py), for the 'tokens'
  # env backend; its widths are the seq_* fields (defaults: the tiny
  # size the CPU tests run), --num_actions is the vocabulary.
  agent: str = 'impala'                   # impala | sequence
  # Dtype the parameters are CREATED in. bfloat16 is for serving a
  # large sequence policy (no float32 master copy ever exists); the
  # learner's RMSProp wants float32.
  param_dtype: str = 'float32'            # float32 | bfloat16
  seq_num_layers: int = 2
  seq_hidden_size: int = 64
  seq_num_heads: int = 4
  seq_num_kv_heads: int = 2
  seq_head_dim: int = 16
  seq_mlp_size: int = 128
  seq_rope_theta: float = 1e6
  seq_norm_eps: float = 1e-6
  # The sequence agent's core (`seq_core` reads which). Neither
  # seq_layer_pattern nor seq_kv_lora_rank: power-retention blocks (the
  # fields above). seq_kv_lora_rank above 0: latent attention (MLA)
  # over a per-session latent cache, dense and routed-expert
  # feed-forward layers (models/latent_moe.py); seq_num_kv_heads and
  # seq_head_dim are then unused. seq_layer_pattern given (one letter a
  # layer, repeated over the depth: L a layer that attends to the last
  # seq_window tokens and keeps a ring of them, G one that attends to
  # the whole episode and keeps it): grouped-query attention of
  # seq_num_kv_heads groups of seq_head_dim over those two kinds of
  # cache, the same feed-forward layers (models/hybrid_attention.py).
  # Defaults: the tiny size the CPU tests run.
  seq_layer_pattern: str = ''
  seq_window: int = 8
  seq_kv_lora_rank: int = 0
  seq_q_lora_rank: int = 24
  seq_qk_nope_head_dim: int = 8
  seq_qk_rope_head_dim: int = 4
  seq_v_head_dim: int = 8
  seq_first_dense_layers: int = 1         # leading layers with the dense MLP
  seq_moe_size: int = 32                  # an expert's width
  seq_routed_experts: int = 16            # the router's outputs
  seq_experts_held: int = 4               # of them computed here ...
  seq_expert_offset: int = 0              # ... from this one on
  seq_experts_per_token: int = 4
  seq_expert_groups: int = 4
  seq_expert_groups_kept: int = 2
  seq_routed_scale: float = 2.5
  seq_shared_experts: int = 1
  seq_rope_factor: float = 40.0           # YaRN; 1: plain rotary
  seq_rope_original_max: int = 4096
  seq_rope_beta_fast: float = 32.0
  seq_rope_beta_slow: float = 1.0
  seq_rope_mscale: float = 1.0
  seq_rope_mscale_all_dim: float = 1.0
  # Tokens of an episode a session's cache holds (>= --episode_length),
  # and the tokens one prefill call takes: where the core computes a
  # chunk at once, an episode's prompt reaches the server as a block.
  seq_cache_capacity: int = 64
  seq_prefill_chunk: int = 8
  # 'tokens' backend: seeded prompt tokens at the start of each
  # episode of --episode_length steps; session i's prompt is
  # token_prompt_stride * (i mod --num_actors) tokens longer.
  token_prompt_length: int = 4
  token_prompt_stride: int = 0
  use_associative_scan: bool = False      # parallel V-trace recursion
  use_pallas_vtrace: bool = False         # fused Pallas V-trace kernel
  use_popart: bool = False                # PopArt value normalization
  popart_beta: float = 3e-4               # PopArt stats EMA step size
  pixel_control_cost: float = 0.0         # >0 enables UNREAL aux task
  pixel_control_discount: float = 0.9
  pixel_control_cell_size: int = 4
  # --- Pixel-control fast path (round 6, docs/PERF.md itemization).
  # Three candidate levers, each parity-gated (tests/test_unreal.py)
  # and never measured on a chip (docs/PERF.md, "Defaults did NOT
  # flip this round").
  # DEFAULTS STAY AT THE r5 REFERENCE FORMS: per the repo's
  # measured accept/reject discipline a default only flips on CHIP
  # numbers, and the round-6 build host had no chip — the CPU-backend
  # compile evidence (scripts/attribute_bytes.py) actually favors the
  # reference forms there (the CPU emitter single-pass-fuses the f32
  # reward reduce and materializes the d2s interleave), which is
  # precisely why these were not flipped blind. BENCH_rN's pc_levers
  # rows carry the on-chip call.
  #
  # Integer-domain pseudo-rewards: uint8 |Δ| + int32 cell sums, f32
  # only at the tiny [T, B, Hc, Wc] output — no full-resolution float
  # frame temporary can exist, where the f32 form leaves that choice
  # to the backend's fusion. Mathematically identical (exact integer
  # sum + one correctly-rounded scale); auto-falls back to the f32
  # form for non-uint8 frames.
  pixel_control_integer_rewards: bool = False
  # Q-head deconv implementation: 'deconv' (the r5 nn.ConvTranspose
  # reference form) | 'd2s' (the stride-2 4x4 deconv re-expressed as
  # one dense 2x2 conv + depth-to-space interleave — parameter-
  # identical, checkpoint-interchangeable, numerics-parity-gated; no
  # zero-stuffed fractionally-strided conv, at the price of an
  # explicit interleave relayout).
  pixel_control_head_impl: str = 'deconv'  # deconv | d2s
  # Cast the pixel-control Q-map to float32 at the head output (the
  # r5 form). False keeps it in the compute dtype until the loss's
  # gather/max — halves the [T+1·B, Hc, Wc, A] head-output bytes at
  # the cost of bf16-rounding the Q-values the loss sees
  # (numerics-AFFECTING: opt-in, measured by pc_levers).
  pixel_control_q_f32: bool = True
  grad_clip_norm: Optional[float] = None
  checkpoint_secs: int = 600              # reference save_checkpoint_secs
  # Learner steps between cross-host checkpoint-cadence broadcasts
  # (multi-host only; the broadcast is a cross-host sync, so it must
  # not run every step).
  checkpoint_check_every_steps: int = 20
  summary_secs: int = 30                  # reference save_summaries_secs
  # jax.profiler trace capture (SURVEY §5.1 — absent upstream):
  # non-empty dir ⇒ capture steps [profile_start, profile_start+steps).
  profile_dir: str = ''
  profile_start_step: int = 20            # past warmup/compile
  profile_num_steps: int = 5
  # Inference batching (reference dynamic_batching ≈2.9). min_batch 0
  # = AUTO: floor the merge at the fleet size so every call carries
  # the whole fleet (r5 sweep: min_batch=4/t60 measured 201.7 e2e fps
  # vs 146.4 at min_batch=1 — docs/PERF.md). Auto is the default
  # since round 6; evaluate() opts out (retiring levels would turn
  # the floor into one batcher-timeout per tail batch). Set an
  # explicit value to pin the floor by hand.
  inference_min_batch: int = 0
  inference_max_batch: int = 1024
  inference_timeout_ms: int = 100
  # --- Actor-plane inference overhaul (round 7; docs/INFERENCE.md).
  # Device-resident recurrent-state cache: each actor owns a slot in
  # an on-device [slots, hidden] arena; the jitted step gathers the
  # carry by slot id and scatters the new one in-graph (Podracer,
  # arXiv:2104.06272), so the per-step wire drops to (action, reward,
  # done, frame, instr, slot_id) and the LSTM carry crosses the host
  # boundary once per UNROLL (the learner's agent_state snapshot)
  # instead of twice per STEP. Numerics-identical to carry-passing
  # (golden parity gate, tests/test_runtime.py — done edges, respawn
  # slot reuse, sharded eval). DEFAULT OFF pending chip rows: per the
  # repo's measured accept/reject discipline a default only flips on
  # chip numbers (the build host's CPU rows of cache×depth are
  # recorded in docs/PERF.md r7).
  inference_state_cache: bool = False
  # Dispatched-but-uncompleted merged inference batches allowed in
  # flight (the actor-plane mirror of staging_depth): 2 lets merged
  # batch k+1 assemble and land on device while batch k computes —
  # per-call latency absorbs the overlap, calls/s gains. 1 restores
  # the pre-round serialized assemble→dispatch→readback loop.
  inference_pipeline_depth: int = 2
  # State-arena capacity in slots (state-cache mode only). 0 = auto:
  # 2× the fleet size with a small floor — respawn headroom, because
  # a wedged actor's slot frees only when its orphaned thread
  # unwinds (runtime/fleet.py respawn contract).
  inference_state_slots: int = 0
  # --- Actor-plane overload & preemption hardening (round 9;
  # docs/ROBUSTNESS.md actor-plane rows). ---
  # Slot admission policy when the state arena is exhausted (the old
  # behavior — raise RuntimeError into the fleet — is gone):
  #   'block' (default): park on a priority waitlist until a slot
  #     frees or the admission deadline passes (then a clean
  #     SlotUnavailable that fleet respawn treats as pause-and-retry);
  #   'shed': same wait, but the deadline rejection is the intended
  #     overload response — counted in stats()['sheds'] and the
  #     driver's inference_sheds summary;
  #   'grow': never park — double the arena in place (one recompile
  #     per growth, counted as arena_grows).
  inference_admission: str = 'block'      # block | shed | grow
  # Deadline for parked slot acquisitions (block and shed policies).
  inference_admission_timeout_secs: float = 10.0
  # Ingest staleness window, in published param versions: a remote
  # unroll generated with params more than this many versions behind
  # the current snapshot is refused at admission (benign 'stale'
  # reply; the client refetches and keeps feeding). 0 = no window.
  max_unroll_staleness: int = 0
  # Consecutive respawns without one completed unroll before a fleet
  # slot gives up and quarantines (surfaced as slots_quarantined);
  # 0 = retry forever (pre-round-9 semantics, minus the hot loop —
  # respawns are always backoff-paced now).
  fleet_quarantine_after: int = 5
  # Preemption drain budget: on SIGTERM (or the preempt_signal fault)
  # the driver stops admissions, flushes in-flight unrolls through
  # the learner, takes a verified checkpoint and writes
  # resume_manifest.json — all within this many seconds.
  preempt_drain_timeout_secs: float = 30.0
  # Ring buffer capacity in batches (reference FIFOQueue capacity=1 +
  # StagingArea double buffer ⇒ bounded policy lag; keep it small).
  queue_capacity_batches: int = 1
  # Staged device batches in flight (BatchPrefetcher depth — the
  # StagingArea role). 2 double-buffers jax.device_put against the
  # (sharded) step so consecutive H2D transfers overlap each other
  # and the compute (BENCH_r05: h2d_ms 1430.5 dominated the fed-loop
  # gap). Each extra slot extends the policy-lag bound by one batch.
  staging_depth: int = 2
  # --- Learner feed staging mode (round 8; docs/PERF.md r8). ---
  # 'batch': host-stack B unrolls (`batch_unrolls`) then one burst
  #   device_put per step — the r5–r7 reference path (BENCH_r05
  #   itemized it at stack_ms 37.5 / h2d_ms 1430.5 per 67.5 MB batch).
  # 'unroll': each completed unroll is device_put the moment it leaves
  #   the TrajectoryBuffer — placed directly on the device owning its
  #   batch slot — and the [T+1, B] batch assembles ON DEVICE via a
  #   jitted donated dynamic_update_slice arena
  #   (runtime/ring_buffer.UnrollBatchStager), so the step-boundary
  #   burst becomes a trickle overlapped with the previous step's
  #   compute and the host stack leaves the hot path. Golden
  #   parity-gated vs the host-stack path (bit-identical batches);
  #   falls back to 'batch' with a warning on topologies the per-slot
  #   placement cannot serve (model-axis batch sharding, indivisible
  #   local batch — parallel/train_parallel.supports_unroll_staging).
  # DEFAULT STAYS 'batch' per the repo's measured accept/reject
  # discipline: both modes × staging_depth were measured head-to-head
  # (exposed H2D ms/step, stack_ms, step gap: docs/PERF.md r8); the
  # flip call waits for chip rows.
  staging_mode: str = 'batch'            # batch | unroll
  # --- Sample reuse (round 10; IMPACT, arXiv 1912.00167 —
  # docs/PERF.md r9). The e2e bench shows the actor/env plane bounding
  # throughput at ~150 fps while the compiled learner step runs ~300k
  # frames/s synthetic: V-trace consumes each frame exactly once, so
  # >99% of learner capacity idles. These knobs multiply learner
  # updates per env frame by re-serving staged batches and replaying
  # retained unrolls. ---
  # Loss surrogate: 'vtrace' is the reference IMPALA path (default);
  # 'impact' is the IMPACT clipped-target surrogate — a target-network
  # param copy held on device anchors both the V-trace corrections
  # (IS ratios pi_target/mu, clipped exactly like the reference's
  # rho-bar) and a PPO-style clip of the pi_theta/pi_target ratio, so
  # replayed/stale data cannot push an unbounded policy-gradient step.
  # Parity-gated: with replay_k=1, replay_ratio=0 and
  # target_update_interval=1 the impact path is bit-identical to the
  # vtrace path (tests/test_replay.py) — the surrogate only diverges
  # when reuse/staleness makes the anchor differ from the live params.
  surrogate: str = 'vtrace'               # vtrace | impact
  # PPO-style clip width of the impact surrogate's current/target
  # ratio (the paper's epsilon).
  impact_epsilon: float = 0.2
  # Learner steps between target-network refreshes (impact only; the
  # version-gated publish cadence applied to the on-device anchor —
  # the refresh is an in-graph select, no host round trip). 1 pins
  # the target to the live params (the parity-gate operating point).
  target_update_interval: int = 1
  # Times each staged device batch is served to the learner before
  # release (IMPACT's sample-reuse K). The staged arena is re-served
  # AS IS — no re-stage, no additional H2D traffic — so K updates ride
  # one transfer; episode stats/frame counters only count the first
  # serve. DEFAULT 1 (no reuse) per the measured accept/reject
  # discipline: step_ms and learner-updates/env-frame were measured
  # across replay_k x replay_ratio (docs/PERF.md r9), and the cue_memory return-vs-wallclock artifact carries
  # the flip call.
  replay_k: int = 1
  # Fraction of each batch's unroll slots drawn from the circular
  # replay tier instead of fresh production ([0, 1); 0 = off). Unlike
  # replay_k, replayed unrolls re-stage (one H2D per replayed unroll)
  # but decouple batch composition from the env plane's rate.
  replay_ratio: float = 0.0
  # Circular replay tier capacity in unrolls (0 = auto: 4x batch).
  # Oldest entries are overwritten IMPACT-style when full (counted as
  # evictions-by-age).
  replay_capacity_unrolls: int = 0
  # Replay staleness window, in PUBLISHED PARAM-VERSION deltas — the
  # SAME unit as --max_unroll_staleness (round 10 unified them; the
  # ingest knob gates admission, this one gates re-serving): a
  # retained unroll whose insert-time param version is more than this
  # many published versions behind the current one is evicted instead
  # of replayed (evictions-by-version). 0 = defer to
  # max_unroll_staleness (both windows then agree); both 0 = no bound.
  replay_max_staleness: int = 0
  # Remote actors (reference --job_name=actor gRPC topology, SURVEY
  # §3.4): learner listens on this port for actor-host connections
  # (0 = disabled); actor hosts point learner_address at it.
  remote_actor_port: int = 0
  # Interface the ingest server binds. The wire is pickle (arbitrary
  # code execution for anyone who can reach the port — same trust
  # model as the reference's unauthenticated TF gRPC runtime), so
  # exposure is OPT-IN: the default is loopback-only, and a real
  # multi-host topology must explicitly bind the cluster-internal
  # interface (or '0.0.0.0' inside a trusted network) — ADVICE r3.
  remote_actor_bind_host: str = '127.0.0.1'
  learner_address: str = ''
  # Min seconds between param snapshots published to remote hosts (a
  # publish is a full device_get; remote staleness ~ this value).
  remote_publish_secs: float = 2.0
  # Publish codec for served param snapshots: 'bf16' (default) casts
  # float32 leaves for the wire (the actor host upcasts back) —
  # exactly halves the dominant term of learner egress
  # (hosts x blob_bytes / remote_publish_secs) at a measured ~5 ms
  # cast cost vs zlib-1's 209 ms for a 0.926 ratio (BENCH_r05;
  # docs/TRANSPORT.md). Acting tolerates the ~3 decimal digits of
  # mantissa (inference already runs bfloat16 compute); training
  # state is never touched. 'f32' opts out and ships exact float32.
  publish_codec: str = 'bf16'
  # LEGACY spelling of the same knob (pre-round-6): '' defers to
  # publish_codec; 'bfloat16' forces the cast regardless of codec.
  remote_params_dtype: str = ''
  # Actor-host elasticity: on disconnect, keep retrying the learner
  # for this many seconds (surviving a learner restart-from-
  # checkpoint) instead of exiting. 0 = exit on disconnect.
  # DEFAULT FLIPPED round 11 (0.0 -> 180.0): the hard-crash restart
  # story (docs/RUNBOOK.md §8) needs the fleet to outlive a learner
  # kill -9 + restore + recompile by default — exiting on the first
  # disconnect turned every learner blip into a dead fleet. The
  # window must cover the learner restart budget (validate_transport
  # warns when it doesn't); envs stay alive and paused on buffer
  # backpressure for the duration.
  actor_reconnect_secs: float = 180.0
  # --- Transport-plane liveness (round 11; docs/TRANSPORT.md v6,
  # docs/ROBUSTNESS.md transport rows). ---
  # Application-level heartbeat interval for the ingest/param lanes:
  # a v6 client pings when its trajectory lane is idle this long (the
  # pong carries the current params version, so an idle fleet still
  # learns about publishes), and the server emits 'busy' keepalives
  # at this cadence while an ack is held back by buffer backpressure
  # (a slow learner stays tellable from a dead one). Negotiated per
  # connection at hello — a v5 peer gets neither. 0 = no heartbeats.
  remote_heartbeat_secs: float = 10.0
  # Idle/half-open connection reaping window: a connection (either
  # lane) that has received NO bytes for this long is reaped —
  # half-open peers (silent partition, killed host behind a live NAT
  # entry) used to pin their reader thread and its buffers forever.
  # With heartbeats on, a live-but-idle peer is never silent longer
  # than remote_heartbeat_secs, so the reap only fires on genuinely
  # dead/blackholed peers. Doubles as the client-side I/O deadline
  # (how long an actor waits on a silent learner before entering its
  # reconnect window) and the server's mid-frame recv/send stall
  # deadline. 0 = never reap, no deadlines (pre-round-11 semantics).
  remote_conn_idle_timeout_secs: float = 60.0
  # Validate/commit workers draining the ingest readers' handoff
  # queue (runtime/remote.py — validation, the backpressure put and
  # the ack run here, off the per-connection reader threads).
  # 0 = auto (min(4, cpu count)).
  ingest_workers: int = 0
  # --- Data-plane integrity (round 12; docs/TRANSPORT.md v7,
  # docs/ROBUSTNESS.md integrity rows). PRs 2/6/8 hardened against
  # components that FAIL; these knobs defend against data that is
  # WRONG — a bit-flipped unroll that still parses, a corrupted
  # publish, disk rot under LAST_GOOD, a chip whose replica copy
  # silently diverged. ---
  # Protocol v7 per-frame CRC32C trailers on both remote lanes,
  # negotiated per connection at hello (v5/v6 peers: off). A corrupt
  # unroll is refused BEFORE the buffer put ('corrupt' reply — the
  # client re-sends once, then quarantines itself); param blobs are
  # trailer-checked by the fetching client. Overhead was measured with
  # CRC on and off (<5% frames/s on the build host, docs/PERF.md r10).
  wire_crc: bool = True
  # Verified checkpoint saves record a per-file content digest
  # (DIGEST_<step>.json + the LAST_GOOD manifest); the restore ladder
  # re-verifies before trusting a step, classifying mismatch as
  # corruption (fallback to the previous retained step) — extends the
  # PR 2 ladder from partial/structural damage to BIT ROT.
  ckpt_digests: bool = True
  # In-graph SDC sentinel: per-data-replica param fingerprints
  # (segmented uint32 sum of bit-cast leaves) cross-checked by the
  # one-step-delayed health readback; replica disagreement =
  # deterministic compute violated -> incident + the PR 2 rollback
  # ladder (counted as sdc_replica_mismatches, separate from
  # non-finite skips). Pure-DP meshes with >= 2 data replicas only;
  # a no-op elsewhere.
  sdc_check: bool = True
  # Multi-host SDC (round 17): all-gather the per-replica fingerprints
  # IN-GRAPH so the host readback touches only a fully-replicated
  # [replicas] array — the device_get of a P('data')-sharded array
  # across processes is illegal (non-addressable shards), which is
  # why the PR 9 gate kept the sentinel single-controller. False
  # restores the old gate (the sentinel silently stays off on
  # multi-process meshes — validate_distributed warns).
  sdc_allgather: bool = True
  # Replay-tier entries keep their insert-time content CRC and are
  # re-verified at every serve (reuse must not multiply host-memory
  # rot into K batches); mismatches evict (replay_evictions_crc).
  replay_crc: bool = True
  # --- Telemetry plane (round 13; docs/OBSERVABILITY.md). ---
  # Per-unroll trace spans: each unroll carries a compact trace
  # context (actor id, sequence, session epoch, behaviour params
  # version, hop timestamps) stamped at env-step completion and
  # completed through ingest → staging → serve → train step; the
  # learner emits traces.jsonl (one line per trained batch with the
  # policy-lag vector) and scripts/trace_report.py reconstructs
  # per-hop latency + the lag distribution. Negotiated on the wire
  # (protocol v8) — older peers simply don't stamp. Default ON: the
  # overhead measured below run-to-run noise with tracing on and off
  # (docs/PERF.md r11 records the accept call); False turns off
  # stamping, the tracer, and the traces.jsonl stream.
  telemetry_trace: bool = True
  # Flight-recorder depth: the most recent N trace records (batches /
  # publishes / installs) plus periodic metrics-registry snapshots
  # kept in memory and dumped with the health halt bundle and every
  # rollback incident — the "last N seconds of pipeline history"
  # an incident postmortem starts from.
  telemetry_flight_len: int = 512
  # --- SLO engine (round 14; slo.py, docs/OBSERVABILITY.md). The
  # sensor-to-verdict half of the control loop: declarative objectives
  # over the metrics registry, evaluated continuously on fast/slow
  # burn windows, with the per-run SLO_VERDICT.json go/no-go artifact
  # and triggered deep diagnostics on page-severity burns. Default ON:
  # the evaluator tick measured sub-millisecond, paid once per cadence interval off the hot loop
  # (docs/PERF.md r12 records the accept call); False removes the
  # thread, the verdict, and the captures entirely. ---
  slo_engine: bool = True
  # Objective set: '' = the shipped defaults (slo.DEFAULT_OBJECTIVES —
  # one per instrumented plane, the table in docs/OBSERVABILITY.md);
  # a path loads a JSON list of objective dicts instead. A spec naming
  # an unregistered metric is a spin-up error, not a silent no-op.
  slo_spec: str = ''
  # Default burn windows for objectives that don't pin their own:
  # multi-window burn-rate alerting — the fast window must be FULLY
  # violating and at least half the slow window too before an
  # objective burns (a blip must not page; a sustained burn must).
  slo_fast_window_secs: float = 30.0
  slo_slow_window_secs: float = 300.0
  # Evaluator cadence (its own thread; the driver's summary block
  # also evaluates, so detection is step-synchronous whenever
  # summaries are frequent). 0 = derive from summary_secs.
  slo_interval_secs: float = 0.0
  # Triggered deep diagnostics: on the FIRST burn of a severity=page
  # objective, dump the flight recorder + a trace_report slice over
  # the violation window into <logdir>/diagnostics/ and capture a
  # bounded jax.profiler trace of the next slo_capture_steps learner
  # steps (one capture per objective per run).
  slo_capture: bool = True
  slo_capture_steps: int = 5
  # Per-host fps baseline file (JSON {hostname: {'fps': value}}): the
  # fps_floor objective judges throughput against THIS host's
  # recorded capability ('' = no baseline — the objective reads
  # no_baseline, never a violation). scripts/slo_report.py
  # --update-fps-baseline records a known-good run into it.
  slo_fps_baseline: str = ''
  # --- Self-healing controller (round 15; controller.py,
  # docs/RUNBOOK.md §12). The verdict-to-actuation half of the
  # control loop: a controller thread maps the SLO engine's burning
  # set + margins to bounded actuator moves through a declarative
  # policy table. 'observe' (default) is the dry run — every move the
  # policy WOULD make is logged (CONTROLLER_LOG.json, applied:false)
  # and nothing is touched; 'act' applies them (replay_k, admission
  # mode, remote publish cadence, fleet size); 'off' removes the
  # thread and the log. The acceptance drill is
  # CHAOS_STORM=controller (scripts/chaos.py). ---
  controller: str = 'observe'             # off | observe | act
  # Policy table: '' = controller.DEFAULT_RULES (the table in
  # docs/OBSERVABILITY.md); a path loads a JSON rule list. A rule
  # over an unknown actuator is a spin-up error.
  controller_policy: str = ''
  # Controller tick cadence (0 = derive from the SLO engine's
  # interval — the judge and the actuator loop then share a clock).
  controller_interval_secs: float = 0.0
  # Hard upper bound the replay_k actuator may escalate to (the
  # bounded-move guarantee; IMPACT's measured-safe reuse range).
  controller_replay_k_max: int = 4
  # Hard upper bound for the publish-cadence actuator, seconds.
  controller_publish_secs_max: float = 30.0
  # Quarantine probation (round 15): how long a quarantined fleet
  # slot (or a self-quarantined remote client) must cool down before
  # a rehabilitation attempt — one probe (re)spawn/unroll, then
  # re-quarantine on repeat failure. The controller's grow-fleet move
  # reclaims slots through this ladder (slots_rehabilitated).
  fleet_probation_secs: float = 30.0
  # Elastic pod membership (round 20): upper bound for the pod_size
  # actuator — the pod-level analogue of fleet_size. The learner does
  # not SPAWN hosts; the actuator publishes the desired host count to
  # <logdir>/POD_TARGET.json (atomic replace) and the cluster
  # supervisor (chaos.py's elastic storm in tests; an operator's
  # orchestration in production) reconciles actual hosts toward it.
  # 0 (default) = actuator not registered; membership accounting
  # (host_joined/host_left incidents, driver/remote_live_hosts) is
  # independent of this knob and always on for v9 peers.
  pod_max_hosts: int = 0
  # --- Runtime axis (round 16; docs/PARALLELISM.md, RUNBOOK §13).
  # 'fleet' is the production Sebulba pipeline (host envs → inference
  # → buffer → learner). 'anakin' fuses act+learn into ONE jitted
  # device step (Podracer arXiv:2104.06272) for jittable env backends
  # (JITTABLE_BACKENDS below) — the r4 bench measured it 4x the fed
  # fleet path on the CI tasks — under the SAME run lifecycle:
  # checkpoint ladder, health watchdog, metrics registry, SLO engine
  # + verdict, summaries/incidents JSONL (driver.train dispatches on
  # this axis; driver.train_anakin is the loop). ---
  runtime: str = 'fleet'                  # fleet | anakin
  # Hybrid filler fleets (fleet runtime only): whenever the
  # prefetcher has NO staged batch ready, the driver runs ONE bounded
  # Anakin self-play step on the learner chips instead of parking on
  # the feed — learner-plane utilization is lifted by construction in
  # env-bound regimes (the BENCH r9 shape: ~150 fps feed vs ~300k fps
  # learner capacity) while a staged batch is never delayed by more
  # than one filler step. Filler updates ride the IMPACT staleness
  # argument (arXiv 1912.00167 — validate_runtime cross-links
  # --surrogate); the frame budget, LR schedule, and fps meter stay
  # on the fleet's fresh-frame clock (filler work is accounted
  # separately: filler_updates/filler_frames summaries + the
  # driver/filler_updates registry counter). DEFAULT OFF per the
  # measured accept/reject discipline: docs/PERF.md r13 records the
  # hybrid row and the call.
  anakin_filler: bool = False
  # Filler env core: '' = auto (env_backend itself when jittable,
  # else 'bandit' — which accepts the main task's action-space width).
  filler_backend: str = ''
  # Filler rollout shape (0 = auto: the fleet's batch_size, and
  # min(unroll_length, 16) — short slices keep the one-filler-step
  # yield bound tight).
  filler_batch_size: int = 0
  filler_unroll_length: int = 0
  # --- Learner failure domain (health.py, round 7). ---
  # Training-health watchdog: the train step skips non-finite updates
  # on device (params carry over unchanged) and the driver escalates
  # bad steps: skip-and-count → rollback to the last-known-good
  # checkpoint after `health_rollback_after` consecutive bad steps →
  # halt with a diagnostic bundle after `health_max_rollbacks`
  # rollbacks. False removes the in-graph guard and the host monitor
  # entirely (exact pre-round-7 step semantics).
  health_watchdog: bool = True
  # Host-side sentinel read cadence. The read is ONE-STEP DELAYED
  # (the stacked scalars of step N are fetched after step N+1 was
  # dispatched, so the device_get reads completed values instead of
  # syncing the dispatch pipeline); the device-side skip protects
  # params regardless of cadence — this only bounds rollback/halt
  # latency.
  health_check_every_steps: int = 1
  health_window: int = 64                 # retained recent checks
  health_min_window: int = 16             # samples before relative
                                          # detectors arm
  health_rollback_after: int = 5          # K consecutive bad steps
  health_max_rollbacks: int = 3           # then halt
  health_loss_explosion_factor: float = 100.0
  health_sigma_divergence_factor: float = 10.0
  # --- Invariant analyzer (round 18; analysis/, docs/STATIC_ANALYSIS
  # .md). Runtime lock-order detection: the threaded modules build
  # their locks through analysis.runtime.make_lock, which returns a
  # plain threading.Lock unless detection is armed — True arms it for
  # this run (driver.train arms BEFORE constructing components and
  # wires detections into incidents.jsonl as durable
  # lock_order_inversion events). Default OFF in production (the
  # graph bookkeeping is cheap but not free); tests and chaos storms
  # run armed (conftest.py sets LOCK_ORDER_CHECK=1; the fault storm
  # passes this flag and asserts zero cycles), so every storm doubles
  # as a race hunt. ---
  lock_order_check: bool = False
  # --- Multi-tenant serving plane (round 21; docs/INFERENCE.md). ---
  # Policy versions resident concurrently in the InferenceServer's
  # version table. 1 (default) reproduces the single-snapshot
  # behaviour exactly; >1 keeps older publishes resident (LRU
  # eviction of unpinned non-live entries) so a re-publish of a
  # resident version flips live WITHOUT a tree copy — the rollback/
  # A/B substrate.
  serving_resident_versions: int = 1
  # Optional byte budget over resident entries, MB (0 = count cap
  # only). Eviction honours pins and never evicts the live entry.
  serving_hbm_budget_mb: float = 0.0
  # Fraction of merged inference calls served by the A/B candidate
  # (the newest non-live resident, or set_ab's explicit version).
  # Granularity is the MERGED call — the C++ batcher folds many
  # actors into one call, so per-request assignment does not exist at
  # this layer.
  serving_ab_fraction: float = 0.0
  # Fraction of merged calls ALSO replayed against the shadow version
  # through a pure step (no key chain, no arena writes) and scored on
  # greedy action agreement vs live — the serving/shadow_divergence
  # gauge. Costs one extra forward per sampled call.
  serving_shadow_fraction: float = 0.0
  # Pre-compile serving steps per (batch bucket, params structure) at
  # publish/warmup time (the jit lower/compile AOT seam) so a version
  # flip or warmed bucket never pays first-call compile on the serve
  # path. DEFAULT OFF pending chip rows per the docs/PERF.md
  # accept/reject discipline (the flip-blackout delta is the number
  # to measure).
  serving_aot: bool = False
  # Comma-separated learner replica addresses ('host:port,...') an
  # actor host routes inference over (runtime/routing.py: health-
  # weighted round-robin, drain on leave, wire v10). '' = no routed
  # serving (params are fetched and inference stays host-local).
  serving_replicas: str = ''
  # --- Population engine (round 22; population.py,
  # docs/PARALLELISM.md §population). ---
  # In-graph auto-curriculum over the procgen level set (anakin
  # runtime AND the hybrid filler — both reach the core through
  # anakin.make_env_core): 'uniform' keeps the reference draw;
  # 'regret' EMAs positive value loss per level (the PLR proxy,
  # arXiv 2010.03934); 'td' EMAs |TD error|. Sampler and score update
  # both live INSIDE the fused device step — zero host round trips
  # per level decision. DEFAULT stays 'uniform' per the measured
  # accept/reject discipline: the curriculum's fps delta is the
  # number to measure, and the regret default flip is parked in ROADMAP housekeeping (b) pending chip rows.
  curriculum: str = 'uniform'             # uniform | regret | td
  curriculum_temperature: float = 1.0     # score-softmax temperature
  curriculum_eps: float = 0.1             # uniform mixing floor — every
                                          # level keeps >0 visitation
                                          # (the staleness escape hatch)
  curriculum_alpha: float = 0.3           # per-level score EMA step
  curriculum_decay: float = 0.995         # unvisited-level score decay
                                          # per fused step (staleness)
  # Procgen level-set size (envs/jittable.ProcgenCore) — the
  # curriculum's support. Both runtimes honor it (the host wrapper
  # receives it through the factory), so the anakin-vs-fleet parity
  # gate holds at any value.
  procgen_num_levels: int = 8
  # Procgen wall density: the Bernoulli rate of the per-level wall
  # mask. 0.25 (the prior hard-coded value) keeps most levels
  # solvable; raising it makes a growing fraction of layouts
  # goal-unreachable — the skewed-difficulty regime where curriculum
  # prioritization structurally beats uniform sampling (unlearnable
  # levels' regret scores decay to zero, so the sampler stops paying
  # for them; uniform keeps wasting 1/n of every batch per dead
  # level).
  procgen_wall_density: float = 0.25
  # Heterogeneous fleet composition (fleet runtime, round 22): '' =
  # single-task (unchanged). 'bandit:2,gridworld:1' runs ONE fleet
  # whose actors split across jittable suites by largest-remainder
  # weight apportionment (population.plan_actor_assignment — the
  # per-task frame budget IS the actor share), with per-task PopArt
  # statistics and per-task return curves riding the existing
  # level-id machinery. All tasks share the model's frame shape
  # (config.height x width); obs-spec FAMILY bucketing in the dynamic
  # batcher keeps mixed shapes merge-local (ops/dynamic_batching.
  # FamilyBatcher).
  fleet_tasks: str = ''
  # Minimal PBT across learner replicas (round 22; population.py,
  # arXiv 1711.09846): 0 = off; >= 2 trains that many independent
  # anakin-runtime members under ONE driver invocation
  # (<logdir>/member_<k>), suites assigned round-robin from
  # pbt_suites. Every pbt_round_frames frames per member, process 0
  # ranks members WITHIN their suite (cross-suite returns are not
  # commensurable) and bottom-quantile members inherit a top-quantile
  # donor's weights through the checkpoint ladder (verified save ->
  # re-verified restore) with (learning_rate, entropy_cost) perturbed
  # by pbt_perturb — each exploit is a durable pbt_exploit incident.
  pbt_population: int = 0
  pbt_round_frames: int = 0               # frames/member/round (0 =
                                          # auto: 1/4 of the budget)
  pbt_suites: str = ''                    # comma-separated jittable
                                          # backends; '' = env_backend
  pbt_quantile: float = 0.25              # exploit bottom/top fraction
  pbt_perturb: float = 1.2                # explore factor (x or /)
  # Fused population (round 23): vmap the N single-device members
  # over a leading member axis so every round trains ONE compiled
  # Anakin program instead of N serial spin-ups — (learning_rate,
  # entropy_cost) become traced per-member scalars, exploit is an
  # on-device stacked-slice copy, PBT decide/explore stays host-side
  # between rounds. Requires a single jittable suite; a model-axis
  # mesh degrades to the serial member loop with a warning.
  pbt_vectorized: bool = False
  # Persistent XLA compilation cache (round 23): armed in
  # distributed.maybe_initialize before the first compile, so repeat
  # spin-ups of identical programs (population rounds, elastic
  # rejoin, serving flips, plain restarts) skip retrace+compile.
  # Where JAX_COMPILATION_CACHE_DIR is set the environment places the
  # cache and this flag is ignored. Otherwise 'auto' =
  # REPO_COMPILE_CACHE_DIR, armed on accelerator hosts only
  # (CPU-pinned processes skip auto-arming, see
  # distributed.arm_compile_cache); '' disables; any other value is
  # the cache dir itself, armed on any backend (shareable across
  # runs/processes — entries are keyed, concurrent writers are safe).
  compile_cache_dir: str = 'auto'

  @property
  def frames_per_step(self):
    return self.batch_size * self.unroll_length * self.num_action_repeats

  @property
  def resolved_wire_dtype(self) -> str:
    """The ingest server's wire_dtype from the codec knobs: the
    legacy `remote_params_dtype` (non-empty) wins, else
    `publish_codec` ('bf16' → 'bfloat16', 'f32' → exact float32).
    Resolved here so the driver and the remote-actor role can never
    disagree on the production default."""
    if self.remote_params_dtype:
      return self.remote_params_dtype
    if self.publish_codec == 'bf16':
      return 'bfloat16'
    if self.publish_codec == 'f32':
      return ''
    if self.publish_codec == 'int8':
      # Round 21: absmax-int8 wire blobs (runtime/codec.py), protocol
      # v10 — v<=9 subscribers are negotiated down to bf16 blobs.
      return 'int8'
    raise ValueError(
        f"publish_codec must be 'bf16', 'f32' or 'int8', got "
        f'{self.publish_codec!r}')

  @property
  def resolved_replay_capacity(self) -> int:
    """Replay-tier capacity with the 0-auto rule applied (4x batch —
    enough history for ratio .75 at replay_k 4 without letting mean
    staleness run away)."""
    if self.replay_capacity_unrolls > 0:
      return self.replay_capacity_unrolls
    return 4 * self.batch_size

  @property
  def resolved_replay_max_staleness(self) -> int:
    """The replay staleness window in published param-version deltas —
    the unit shared with `max_unroll_staleness` (round 10 unified the
    two; they used to be spelled in different units). 0 defers to the
    ingest window so an operator bounding admission staleness bounds
    replay staleness for free; both 0 = unbounded."""
    if self.replay_max_staleness > 0:
      return self.replay_max_staleness
    return self.max_unroll_staleness

  @property
  def resolved_filler_backend(self) -> str:
    """The hybrid filler's env core: the explicit knob, else the run's
    own backend when it is jittable (the filler then self-plays the
    REAL task), else 'bandit' (which accepts any policy-head width —
    the filler must run under the main task's action space)."""
    if self.filler_backend:
      return self.filler_backend
    if self.env_backend in JITTABLE_BACKENDS:
      return self.env_backend
    return 'bandit'

  @property
  def resolved_filler_batch_size(self) -> int:
    return (self.filler_batch_size if self.filler_batch_size > 0
            else self.batch_size)

  @property
  def resolved_filler_unroll_length(self) -> int:
    """Filler rollout length (0-auto: min(T, 16)) — short slices keep
    the one-filler-step yield bound tight at flagship T=100."""
    if self.filler_unroll_length > 0:
      return self.filler_unroll_length
    return min(self.unroll_length, 16)

  @property
  def seq_core(self) -> str:
    """The sequence agent's core, as the seq_* widths name it:
    'retention' | 'latent' | 'hybrid'."""
    if self.seq_layer_pattern:
      return 'hybrid'
    return 'latent' if self.seq_kv_lora_rank > 0 else 'retention'

  @property
  def resolved_use_instruction(self) -> bool:
    """`use_instruction` with the None-auto rule applied (must be
    deterministic in the config alone: train, evaluate, and remote
    actors all resolve independently and the agent param structure —
    hence checkpoints — depends on it)."""
    if self.use_instruction is not None:
      return self.use_instruction
    if self.level_name == 'dmlab30':
      return True
    return self.level_name.startswith(('language_', 'psychlab_'))

  @property
  def resolved_pbt_suites(self) -> List[str]:
    """The population's suite list: the explicit comma list, else the
    run's own backend repeated — members then differ only in hypers
    (classic single-task PBT)."""
    if self.pbt_suites:
      return [s.strip() for s in self.pbt_suites.split(',')
              if s.strip()]
    return [self.env_backend]

  @property
  def resolved_pbt_round_frames(self) -> int:
    """Frames each member trains between PBT decision points (0-auto:
    a quarter of the per-member budget — 4 rounds, enough for one
    exploit to propagate and still show post-exploit learning)."""
    if self.pbt_round_frames > 0:
      return self.pbt_round_frames
    return max(self.total_environment_frames // 4, 1)

  @property
  def resolved_compile_cache_dir(self) -> str:
    """The directory the PROGRAM sets for the persistent compilation
    cache; '' = it sets none. Where JAX_COMPILATION_CACHE_DIR is set
    the cache is placed from outside (jax reads the variable itself)
    and the program sets nothing, whatever the flag says. Resolved
    here so every entry (driver.train, chip_smoke.py, the CI smoke)
    agrees on where the cache lives."""
    if os.environ.get('JAX_COMPILATION_CACHE_DIR'):
      return ''
    if self.compile_cache_dir == 'auto':
      return REPO_COMPILE_CACHE_DIR
    return self.compile_cache_dir


def validate_replay(config: Config) -> List[str]:
  """Validate the sample-reuse knob group (round 10); raises
  ValueError on hard errors, returns human-readable warnings for the
  caller to log (config.py has no logger; driver.train calls this
  before spin-up so a bad knob combination fails before any
  env/checkpoint cost).

  The staleness cross-link (the round-10 unit unification): both
  `max_unroll_staleness` (ingest admission) and `replay_max_staleness`
  (replay eviction) are in PUBLISHED PARAM-VERSION deltas. A replay
  window narrower than the admission window means a remote unroll can
  be admitted as fresh enough to train on once, yet already be too
  stale to ever replay — legal (admission is about training at all,
  replay about training again) but worth a warning since the operator
  probably meant one window."""
  warnings = []
  if config.surrogate not in ('vtrace', 'impact'):
    raise ValueError(f'surrogate must be vtrace|impact, got '
                     f'{config.surrogate!r}')
  if config.replay_k < 1:
    raise ValueError(f'replay_k must be >= 1, got {config.replay_k}')
  if not 0.0 <= config.replay_ratio < 1.0:
    raise ValueError(f'replay_ratio must be in [0, 1) (a batch needs '
                     f'at least one fresh slot), got '
                     f'{config.replay_ratio}')
  if config.target_update_interval < 1:
    raise ValueError(f'target_update_interval must be >= 1, got '
                     f'{config.target_update_interval}')
  if config.impact_epsilon <= 0:
    raise ValueError(f'impact_epsilon must be > 0, got '
                     f'{config.impact_epsilon}')
  if config.replay_capacity_unrolls < 0:
    raise ValueError(f'replay_capacity_unrolls must be >= 0, got '
                     f'{config.replay_capacity_unrolls}')
  if config.replay_max_staleness < 0:
    raise ValueError(f'replay_max_staleness must be >= 0, got '
                     f'{config.replay_max_staleness}')
  reuse_on = config.replay_k > 1 or config.replay_ratio > 0
  if reuse_on and config.surrogate == 'vtrace':
    warnings.append(
        'sample reuse (replay_k=%d, replay_ratio=%.2f) with '
        'surrogate=vtrace: plain V-trace has no clipped-target anchor '
        'against reused/stale data (IMPACT, arXiv 1912.00167) — '
        'consider --surrogate=impact' %
        (config.replay_k, config.replay_ratio))
  if (config.replay_max_staleness > 0 and
      config.max_unroll_staleness > 0 and
      config.replay_max_staleness < config.max_unroll_staleness):
    warnings.append(
        'replay_max_staleness=%d is narrower than '
        'max_unroll_staleness=%d (both in published param-version '
        'deltas): unrolls admitted near the ingest window will be '
        'version-evicted from the replay tier without ever being '
        'replayed' %
        (config.replay_max_staleness, config.max_unroll_staleness))
  if config.replay_ratio > 0 and config.resolved_replay_capacity < \
      config.batch_size:
    warnings.append(
        'replay capacity %d is below batch_size %d: replayed slots '
        'will repeat the same few unrolls within adjacent batches' %
        (config.resolved_replay_capacity, config.batch_size))
  return warnings


# What a learner restart-from-checkpoint actually costs before the
# ingest port answers hellos again: process spawn + jax import +
# checkpoint restore + the 20-40 s inference/train compiles. An actor
# reconnect window shorter than this turns every learner hard-crash
# into a dead fleet — validate_transport cross-links the two.
LEARNER_RESTART_BUDGET_SECS = 90.0


def validate_transport(config: Config) -> List[str]:
  """Validate the transport-liveness knob group (round 11); raises
  ValueError on hard errors, returns human-readable warnings for the
  caller to log (same contract as validate_replay — driver.train and
  run_remote_actor both call it before spin-up).

  The reconnect/restart cross-link: `actor_reconnect_secs` is how long
  an actor host survives a dead learner, and a learner hard-crash
  restart (docs/RUNBOOK.md §8) costs LEARNER_RESTART_BUDGET_SECS
  before the new ingest port answers — a window shorter than the
  budget means the fleet gives up mid-restart and the restarted
  learner comes back to nobody."""
  warnings = []
  if config.remote_heartbeat_secs < 0:
    raise ValueError(f'remote_heartbeat_secs must be >= 0, got '
                     f'{config.remote_heartbeat_secs}')
  if config.remote_conn_idle_timeout_secs < 0:
    raise ValueError(f'remote_conn_idle_timeout_secs must be >= 0, '
                     f'got {config.remote_conn_idle_timeout_secs}')
  if config.actor_reconnect_secs < 0:
    raise ValueError(f'actor_reconnect_secs must be >= 0, got '
                     f'{config.actor_reconnect_secs}')
  if 0 < config.actor_reconnect_secs < LEARNER_RESTART_BUDGET_SECS:
    warnings.append(
        'actor_reconnect_secs=%.1f is shorter than the learner '
        'restart budget (~%.0fs: restore + recompile before the '
        'ingest port answers) — the fleet will give up mid-restart '
        'and a hard-crashed learner comes back to nobody '
        '(docs/RUNBOOK.md §8)' %
        (config.actor_reconnect_secs, LEARNER_RESTART_BUDGET_SECS))
  hb = config.remote_heartbeat_secs
  idle = config.remote_conn_idle_timeout_secs
  if hb > 0 and idle > 0 and hb >= idle:
    warnings.append(
        'remote_heartbeat_secs=%.1f >= remote_conn_idle_timeout_secs'
        '=%.1f: heartbeats cannot keep an idle-but-healthy connection '
        'inside the reaping window — every quiet period becomes a '
        'reap + reconnect cycle' % (hb, idle))
  if idle > 0 and hb == 0:
    warnings.append(
        'remote_conn_idle_timeout_secs=%.1f with heartbeats disabled: '
        'idle-but-healthy peers (slow envs, v5 clients) will be '
        'reaped and must reconnect — set remote_heartbeat_secs > 0 '
        'or size the window above the slowest unroll cadence' % idle)
  if hb > 0 and idle == 0:
    warnings.append(
        'remote_heartbeat_secs=%.1f with idle reaping disabled '
        '(remote_conn_idle_timeout_secs=0): mid-frame stalls still '
        'abort, but a BETWEEN-frames half-open connection is never '
        'reaped and heartbeat misses are not counted — set a nonzero '
        'idle window to get the full liveness story' % hb)
  return warnings


def validate_integrity(config: Config) -> List[str]:
  """Validate the data-plane-integrity knob group (round 12); returns
  human-readable warnings (same contract as validate_replay /
  validate_transport — driver.train and run_remote_actor call it
  before spin-up). All knobs are booleans, so there are no hard range
  errors — only cross-links where a half-enabled integrity plane is
  probably a mistake."""
  warnings = []
  if config.sdc_check and not config.health_watchdog:
    warnings.append(
        'sdc_check=True with health_watchdog=False: replica '
        'fingerprint mismatches would be computed but never escalated '
        '(no monitor, no rollback ladder) — enable the watchdog or '
        'disable the SDC sentinel')
  if not config.wire_crc and config.remote_actor_port:
    warnings.append(
        'wire_crc=False with remote ingest enabled: a bit-flipped '
        'unroll frame that still parses will train the learner on '
        'garbage with no detection (the round-12 integrity plane is '
        'off on the wire); param publishes keep their content digest '
        'either way')
  if (config.replay_crc and not config.wire_crc
      and config.replay_ratio > 0):
    warnings.append(
        'replay_crc=True with wire_crc=False: replayed unrolls are '
        'verified against their INSERT-time CRC, but a remote unroll '
        'corrupted on the wire is inserted already-rotten and will '
        're-serve cleanly — the replay check only covers rot AFTER '
        'retention')
  return warnings


def validate_slo(config: Config) -> List[str]:
  """Validate the SLO knob group (round 14); raises ValueError on
  hard errors, returns warnings (same contract as validate_replay /
  validate_transport / validate_integrity — driver.train calls it
  before spin-up). The spec file itself is loaded (and therefore
  validated) by slo.load_objectives at engine construction; here the
  cross-links."""
  warnings = []
  if config.slo_fast_window_secs <= 0:
    raise ValueError(f'slo_fast_window_secs must be > 0, got '
                     f'{config.slo_fast_window_secs}')
  if config.slo_slow_window_secs <= 0:
    raise ValueError(f'slo_slow_window_secs must be > 0, got '
                     f'{config.slo_slow_window_secs}')
  if config.slo_capture_steps < 1:
    raise ValueError(f'slo_capture_steps must be >= 1, got '
                     f'{config.slo_capture_steps}')
  if config.slo_interval_secs < 0:
    raise ValueError(f'slo_interval_secs must be >= 0, got '
                     f'{config.slo_interval_secs}')
  if not config.slo_engine:
    if config.slo_spec:
      warnings.append(
          'slo_spec=%r with slo_engine=False: the objective set is '
          'loaded by the engine — nothing will judge it' %
          config.slo_spec)
    return warnings
  if config.slo_fast_window_secs >= config.slo_slow_window_secs:
    warnings.append(
        'slo_fast_window_secs=%.1f >= slo_slow_window_secs=%.1f: the '
        'slow window no longer confirms a sustained burn — every '
        'fast-window blip escalates straight to a violation' %
        (config.slo_fast_window_secs, config.slo_slow_window_secs))
  if (config.slo_interval_secs > 0 and
      config.slo_interval_secs * 3 > config.slo_fast_window_secs):
    warnings.append(
        'slo_interval_secs=%.1f leaves fewer than the 3 samples the '
        'fast window (%.1fs) needs before a value objective can '
        'burn — the policy-lag/utilization/fleet objectives would be '
        'structurally unable to fire; lower the interval or widen '
        'slo_fast_window_secs' %
        (config.slo_interval_secs, config.slo_fast_window_secs))
  if not config.telemetry_trace:
    warnings.append(
        'slo_engine=True with telemetry_trace=False: the policy-lag '
        'and end-to-end-span objectives will evaluate as no_data '
        '(their histograms never fill), and page captures lose the '
        'flight/trace-slice artifacts — the verdict only judges the '
        'counter planes')
  if config.slo_capture and not config.health_watchdog:
    warnings.append(
        'slo_capture=True with health_watchdog=False: SLO burns '
        'cannot feed the external-incident ledger (no monitor), so '
        'drain manifests and halt bundles will not name them')
  return warnings


def validate_controller(config: Config) -> List[str]:
  """Validate the self-healing-controller knob group (round 15);
  raises ValueError on hard errors, returns warnings (same contract
  as the other validate_* groups — driver.train calls it before
  spin-up). The policy file itself is loaded (and validated) by
  controller.load_rules at construction; here the cross-links."""
  warnings = []
  if config.controller not in ('off', 'observe', 'act'):
    raise ValueError(f'controller must be off|observe|act, got '
                     f'{config.controller!r}')
  if config.controller_interval_secs < 0:
    raise ValueError(f'controller_interval_secs must be >= 0, got '
                     f'{config.controller_interval_secs}')
  if config.controller_replay_k_max < 1:
    raise ValueError(f'controller_replay_k_max must be >= 1, got '
                     f'{config.controller_replay_k_max}')
  if config.controller_publish_secs_max <= 0:
    raise ValueError(f'controller_publish_secs_max must be > 0, got '
                     f'{config.controller_publish_secs_max}')
  if config.fleet_probation_secs < 0:
    raise ValueError(f'fleet_probation_secs must be >= 0, got '
                     f'{config.fleet_probation_secs}')
  if config.pod_max_hosts < 0:
    raise ValueError(f'pod_max_hosts must be >= 0, got '
                     f'{config.pod_max_hosts}')
  if config.pod_max_hosts > 0 and not config.remote_actor_port:
    warnings.append(
        'pod_max_hosts=%d with remote ingest disabled '
        '(remote_actor_port=0): the pod_size actuator reads the '
        'ingest membership ledger — it will not be registered'
        % config.pod_max_hosts)
  if (config.remote_heartbeat_secs == 0
      and config.remote_conn_idle_timeout_secs > 0
      and config.fleet_probation_secs >
      config.remote_conn_idle_timeout_secs):
    warnings.append(
        'fleet_probation_secs=%.1f exceeds the idle-reaping window '
        '(remote_conn_idle_timeout_secs=%.1f) with heartbeats '
        'disabled: a remote client cooling down in CRC probation '
        'cannot ping, so the learner will reap it as half-open '
        'mid-probation — enable heartbeats or shorten the cool-down'
        % (config.fleet_probation_secs,
           config.remote_conn_idle_timeout_secs))
  if config.controller == 'off':
    if config.controller_policy:
      warnings.append(
          'controller_policy=%r with controller=off: the policy '
          'table is loaded by the controller — nothing will read it'
          % config.controller_policy)
    return warnings
  if not config.slo_engine:
    warnings.append(
        'controller=%s with slo_engine=False: the controller\'s only '
        'input is the SLO engine\'s burning set and margins — it '
        'will be disabled for this run' % config.controller)
  if (config.controller == 'act' and config.surrogate == 'vtrace'
      and config.controller_replay_k_max > 1):
    warnings.append(
        'controller=act may raise replay_k up to %d, but '
        'surrogate=vtrace has no clipped-target anchor against '
        'reused data (IMPACT, arXiv 1912.00167) — consider '
        '--surrogate=impact, or cap --controller_replay_k_max=1'
        % config.controller_replay_k_max)
  return warnings


# Fields deliberately NOT exposed as experiment.py flags — the
# explicit allowlist the `config-flags` contract lint
# (scripts/lint.py, round 18) checks: every Config field must either
# have a flag of the same name or be named here with the reason a
# flag would be wrong. Empty today — every field is operator-facing.
# Allowlist etiquette (docs/STATIC_ANALYSIS.md): entries carry a
# trailing comment saying WHY, and a stale entry (field gone, or flag
# added) is itself a lint finding.
INTERNAL_FIELDS = ()


# Env backends whose dynamics exist as jittable device cores
# (parallel/anakin.ENV_CORES) — the backends --runtime=anakin and the
# hybrid filler can run. Literal here because config.py must not
# import jax-importing modules; tests/test_anakin.py pins this tuple
# against the live ENV_CORES registry.
JITTABLE_BACKENDS = ('bandit', 'cue_memory', 'gridworld', 'procgen')


def validate_runtime(config: Config) -> List[str]:
  """Validate the runtime-axis knob group (round 16); raises
  ValueError on hard errors, returns warnings (same contract as the
  other validate_* groups — driver.train calls it before spin-up for
  BOTH runtimes).

  The filler/SLO cross-link: the hybrid filler lifts
  `learner_plane_utilization` to ~1.0 BY CONSTRUCTION, so that curve
  can no longer signal an env-bound (or dead) env plane —
  `env_plane_utilization` stays the dead-plane signal either way
  (docs/OBSERVABILITY.md; the SLO engine's env-plane objective is the
  page path filler must never mask)."""
  warnings = []
  if config.runtime not in ('fleet', 'anakin'):
    raise ValueError(f'runtime must be fleet|anakin, got '
                     f'{config.runtime!r}')
  if config.filler_batch_size < 0:
    raise ValueError(f'filler_batch_size must be >= 0, got '
                     f'{config.filler_batch_size}')
  if config.filler_unroll_length < 0:
    raise ValueError(f'filler_unroll_length must be >= 0, got '
                     f'{config.filler_unroll_length}')
  if config.agent not in ('impala', 'sequence'):
    raise ValueError(f'agent must be impala|sequence, got '
                     f'{config.agent!r}')
  if config.param_dtype not in ('float32', 'bfloat16'):
    raise ValueError(f'param_dtype must be float32|bfloat16, got '
                     f'{config.param_dtype!r}')
  if (config.agent == 'sequence') != (config.env_backend == 'tokens'):
    raise ValueError(
        'the sequence agent observes a token and the image agent a '
        'frame: --agent=sequence goes with --env_backend=tokens and '
        f'with no other (got agent={config.agent!r}, '
        f'env_backend={config.env_backend!r})')
  if config.agent == 'sequence':
    if config.runtime != 'fleet' or config.fleet_tasks:
      raise ValueError('--agent=sequence runs on the fleet runtime, '
                       'on one task')
    if (config.seq_num_heads % config.seq_num_kv_heads
        or config.seq_head_dim % 2):
      raise ValueError(
          'seq_num_heads must be a multiple of seq_num_kv_heads and '
          'seq_head_dim even')
    if config.use_popart or config.pixel_control_cost > 0:
      raise ValueError('--agent=sequence has neither PopArt value '
                       'columns nor a pixel-control head')
    if config.seq_layer_pattern and config.seq_kv_lora_rank > 0:
      raise ValueError(
          '--seq_layer_pattern names the core of window and full '
          'attention layers, --seq_kv_lora_rank the latent-attention '
          'core: give one of them')
    if config.seq_core != 'retention':
      if config.episode_length > config.seq_cache_capacity:
        raise ValueError(
            f'an episode of {config.episode_length} tokens does not fit '
            f'a cache of --seq_cache_capacity={config.seq_cache_capacity}')
      if not 0 < config.seq_first_dense_layers <= config.seq_num_layers:
        raise ValueError('--seq_first_dense_layers lies in 1..'
                         '--seq_num_layers')
  longest_prompt = (config.token_prompt_length + config.token_prompt_stride
                    * max(config.num_actors - 1, 0))
  if (config.env_backend == 'tokens' and config.token_prompt_stride and
      longest_prompt >= config.episode_length):
    raise ValueError(
        f'the longest prompt ({longest_prompt} tokens: '
        '--token_prompt_length + --token_prompt_stride * (--num_actors '
        f'- 1)) must lie inside the episode of {config.episode_length}')
  if config.runtime == 'anakin':
    if config.env_backend not in JITTABLE_BACKENDS:
      raise ValueError(
          f'--runtime=anakin needs a jittable env backend '
          f'({", ".join(JITTABLE_BACKENDS)}), got '
          f'{config.env_backend!r}; real simulators use the fleet '
          'runtime')
    if config.remote_actor_port:
      warnings.append(
          'runtime=anakin with remote_actor_port=%d: the fused '
          'device loop has no ingest plane — the port will not be '
          'bound' % config.remote_actor_port)
    if config.anakin_filler:
      warnings.append(
          'anakin_filler=True under runtime=anakin is a no-op: the '
          'whole run IS the on-device loop (the filler is the fleet '
          "runtime's idle-slice workload)")
    return warnings
  if not config.anakin_filler:
    if config.filler_backend:
      warnings.append(
          'filler_backend=%r with anakin_filler=False: nothing will '
          'run it' % config.filler_backend)
    return warnings
  if config.resolved_filler_backend not in JITTABLE_BACKENDS:
    raise ValueError(
        f'filler_backend must be jittable '
        f'({", ".join(JITTABLE_BACKENDS)}), got '
        f'{config.filler_backend!r}')
  if config.surrogate == 'vtrace':
    warnings.append(
        'anakin_filler=True with surrogate=vtrace: filler updates are '
        'off-cadence relative to the fleet stream and plain V-trace '
        'has no clipped-target anchor against them (IMPACT, '
        'arXiv 1912.00167) — consider --surrogate=impact')
  if not config.slo_engine:
    warnings.append(
        'anakin_filler=True with slo_engine=False: the filler lifts '
        'learner_plane_utilization to ~1.0 by construction, and with '
        'the engine off nothing watches env_plane_utilization — the '
        'dead-env-plane signal the filler could otherwise mask '
        '(docs/OBSERVABILITY.md)')
  return warnings


def resolve_process_id(config: Config) -> int:
  """The ONE resolution of this process's declared index:
  config.process_id when set, else the reference's --task spelling
  (floored at 0). Shared by validate_distributed and
  distributed.maybe_initialize so the id the validator checks is the
  id the join actually uses."""
  return (config.process_id if config.process_id >= 0
          else max(config.task, 0))


def validate_distributed(config: Config,
                         live_process_count: int = 1) -> List[str]:
  """Validate the multi-process knob group (round 17); raises
  ValueError on hard errors, returns warnings (same contract as the
  other validate_* groups — driver.train calls it before spin-up,
  AFTER distributed.maybe_initialize, passing the live
  jax.process_count() so topologies initialized by a launcher rather
  than these fields are cross-linked too).

  Pure-config checks use the DECLARED topology (num_processes /
  coordinator_address) so they are unit-testable without spawning
  processes; the cross-links below use
  max(declared, live_process_count)."""
  warnings = []
  if config.num_processes < 1:
    raise ValueError(f'num_processes must be >= 1, got '
                     f'{config.num_processes}')
  if config.tp_compute not in ('auto', 'sharded', 'gathered'):
    raise ValueError(f'tp_compute must be auto|sharded|gathered, got '
                     f'{config.tp_compute!r}')
  # Registry rule-set name (round 19): resolved against the same table
  # every consumer queries, so a typo dies here instead of as a
  # mysterious replicated run.
  from scalable_agent_tpu.parallel import sharding as _sharding_lib
  if (config.sharding_rules != 'auto'
      and config.sharding_rules not in _sharding_lib.RULE_SETS):
    raise ValueError(
        f'sharding_rules must be auto|'
        f'{"|".join(sorted(_sharding_lib.RULE_SETS))}, got '
        f'{config.sharding_rules!r}')
  if (config.sharding_rules == 'replicated'
      and config.model_parallelism > 1):
    warnings.append(
        'sharding_rules=replicated with model_parallelism=%d: the '
        'model axis exists but no rule cuts over it — every param '
        'replicates across it (TP memory win forfeited); use '
        'sharding_rules=auto or =megatron to shard'
        % config.model_parallelism)
  if config.coordinator_address:
    host, sep, port = config.coordinator_address.rpartition(':')
    if not sep or not host or not port.isdigit():
      raise ValueError(
          f'coordinator_address must be host:port, got '
          f'{config.coordinator_address!r}')
    if config.num_processes == 1:
      warnings.append(
          'coordinator_address=%r with num_processes=1: a one-process '
          'jax.distributed runtime works but coordinates nothing — '
          'drop the flag or raise the count'
          % config.coordinator_address)
    resolved_id = resolve_process_id(config)
    if resolved_id >= config.num_processes:
      raise ValueError(
          f'process_id {resolved_id} out of range for num_processes='
          f'{config.num_processes}')
  elif config.num_processes > 1:
    raise ValueError(
        f'num_processes={config.num_processes} needs '
        'coordinator_address (host:port of process 0)')
  elif config.process_id >= 0:
    warnings.append(
        'process_id=%d without coordinator_address: nothing will '
        'join a distributed runtime' % config.process_id)
  procs = max(config.num_processes, live_process_count)
  if procs <= 1:
    return warnings
  # --- Multi-process cross-links. ---
  if config.runtime == 'anakin':
    # Hard error, same verdict train_anakin reaches later — but here,
    # before any device/env spin-up: each process would train an
    # unsynchronized replica (the fused loop has no cross-host batch
    # transport).
    raise ValueError(
        'runtime=anakin is single-host; multi-process runs use the '
        'fleet runtime (per-host ingest + gradient psum)')
  if config.sdc_check and not config.sdc_allgather:
    warnings.append(
        'sdc_check=True with sdc_allgather=False on a multi-process '
        'topology: the per-replica fingerprint readback needs the '
        'in-graph all-gather (a cross-process P(\'data\') device_get '
        'is illegal), so the SDC sentinel will be silently OFF — '
        'enable sdc_allgather or drop sdc_check')
  if config.model_parallelism > 1:
    # TP across hosts flips the shard_batch_over_model predicate
    # (parallel/mesh.py): the batch shards over BOTH axes, so
    # batch_size must divide the FULL device count, actors run on a
    # localized param copy (a collective allgather per publish), and
    # unroll staging falls back to batch mode. Legal, but the
    # operator should know the shape changed.
    warnings.append(
        'model_parallelism=%d on a multi-process topology: the model '
        'axis crosses hosts, so the batch shards over BOTH mesh axes '
        '(mesh.shard_batch_over_model) — batch_size must divide the '
        'full device count, param publishes localize via a collective '
        'allgather, and staging_mode=unroll falls back to batch'
        % config.model_parallelism)
  if config.anakin_filler:
    warnings.append(
        'anakin_filler=True on a multi-process topology: the filler '
        'mutates params OUTSIDE the collective train step, so hosts '
        'with different idle patterns would diverge — the driver '
        'disables it (supports_filler) and parks idle slices instead')
  return warnings


def validate_serving(config: Config) -> List[str]:
  """Validate the multi-tenant serving knob group (round 21); raises
  ValueError on hard errors, returns warnings (same contract as the
  other validate_* groups — driver.train and run_remote_actor call it
  before spin-up)."""
  warnings = []
  if config.serving_resident_versions < 1:
    raise ValueError(f'serving_resident_versions must be >= 1, got '
                     f'{config.serving_resident_versions}')
  if config.serving_hbm_budget_mb < 0:
    raise ValueError(f'serving_hbm_budget_mb must be >= 0, got '
                     f'{config.serving_hbm_budget_mb}')
  for name in ('serving_ab_fraction', 'serving_shadow_fraction'):
    value = getattr(config, name)
    if not 0.0 <= value <= 1.0:
      raise ValueError(f'{name} must be in [0, 1], got {value}')
  if (config.serving_resident_versions == 1
      and (config.serving_ab_fraction > 0
           or config.serving_shadow_fraction > 0)):
    warnings.append(
        'serving_ab_fraction/serving_shadow_fraction > 0 with '
        'serving_resident_versions=1: there is never a non-live '
        'resident candidate, so A/B and shadow traffic will not fire '
        '— raise serving_resident_versions')
  if config.serving_replicas and not config.learner_address:
    warnings.append(
        'serving_replicas set without learner_address: routed '
        'inference replicas are an ACTOR-host knob — the learner '
        'role ignores it')
  return warnings


def validate_population(config: Config) -> List[str]:
  """Validate the population knob group (round 22); raises ValueError
  on hard errors, returns warnings (same contract as the other
  validate_* groups — driver.train AND driver.evaluate call it before
  spin-up). Covers the three population axes: curriculum, mixed
  fleets, PBT."""
  warnings = []
  # --- Curriculum. ---
  if config.curriculum not in ('uniform', 'regret', 'td'):
    raise ValueError(f'curriculum must be uniform|regret|td, got '
                     f'{config.curriculum!r}')
  if config.curriculum_temperature <= 0:
    raise ValueError(f'curriculum_temperature must be > 0, got '
                     f'{config.curriculum_temperature}')
  if not 0.0 <= config.curriculum_eps <= 1.0:
    raise ValueError(f'curriculum_eps must be in [0, 1], got '
                     f'{config.curriculum_eps}')
  if not 0.0 < config.curriculum_alpha <= 1.0:
    raise ValueError(f'curriculum_alpha must be in (0, 1], got '
                     f'{config.curriculum_alpha}')
  if not 0.0 < config.curriculum_decay <= 1.0:
    raise ValueError(f'curriculum_decay must be in (0, 1], got '
                     f'{config.curriculum_decay}')
  if config.procgen_num_levels < 1:
    raise ValueError(f'procgen_num_levels must be >= 1, got '
                     f'{config.procgen_num_levels}')
  if not 0.0 <= config.procgen_wall_density < 1.0:
    raise ValueError(f'procgen_wall_density must be in [0, 1), got '
                     f'{config.procgen_wall_density}')
  if config.curriculum != 'uniform':
    curriculum_backends = {config.env_backend}
    if config.anakin_filler:
      curriculum_backends.add(config.resolved_filler_backend)
    if 'procgen' not in curriculum_backends:
      warnings.append(
          'curriculum=%s with env_backend=%r: only the procgen core '
          'has a finite level-id space to prioritize — the sampler '
          'is inert for this run' %
          (config.curriculum, config.env_backend))
    if config.unroll_length < 2:
      warnings.append(
          'curriculum=%s with unroll_length=1: a TD error needs two '
          'consecutive value estimates, so no per-level signal can '
          'accumulate (scores only decay) — use unroll_length >= 2' %
          config.curriculum)
    if config.curriculum_eps == 0:
      warnings.append(
          'curriculum_eps=0: no uniform mixing floor — a level whose '
          'score collapses early may never be revisited, so its stale '
          'score cannot recover (the decay then has nothing to rescue)')
  # --- Heterogeneous fleets. ---
  if config.fleet_tasks:
    from scalable_agent_tpu import population as _population
    tasks = _population.parse_fleet_tasks(config.fleet_tasks)
    if not tasks:
      raise ValueError(f'fleet_tasks={config.fleet_tasks!r} names no '
                       'tasks')
    names = [name for name, _ in tasks]
    for name in names:
      if name not in JITTABLE_BACKENDS:
        raise ValueError(
            f'fleet_tasks names {name!r}: mixed fleets compose the '
            f'jittable suites ({", ".join(JITTABLE_BACKENDS)}) — '
            'real simulators keep their own single-task fleets')
    if 'cue_memory' in names and any(n in ('gridworld', 'procgen')
                                     for n in names):
      raise ValueError(
          'fleet_tasks mixes cue_memory (a fixed 3-action task) with '
          'gridworld/procgen (>= 4 movement actions): one shared '
          'policy head cannot satisfy both — drop one side or widen '
          'with bandit (any head width)')
    if config.runtime == 'anakin':
      warnings.append(
          'fleet_tasks is a fleet-runtime feature (per-actor task '
          'assignment); runtime=anakin runs env_backend=%r only — '
          'the spec is ignored' % config.env_backend)
    elif len(tasks) > config.num_actors:
      raise ValueError(
          f'fleet_tasks names {len(tasks)} tasks but num_actors='
          f'{config.num_actors} cannot cover them at >= 1 actor each')
    if not config.use_popart and len(tasks) > 1:
      warnings.append(
          'fleet_tasks mixes %d suites with use_popart=False: reward '
          'scales will compete in one value head — consider '
          '--use_popart' % len(tasks))
  # --- PBT. ---
  if config.pbt_population < 0:
    raise ValueError(f'pbt_population must be >= 0, got '
                     f'{config.pbt_population}')
  if config.pbt_round_frames < 0:
    raise ValueError(f'pbt_round_frames must be >= 0, got '
                     f'{config.pbt_round_frames}')
  if not 0.0 < config.pbt_quantile <= 0.5:
    raise ValueError(f'pbt_quantile must be in (0, 0.5] (bottom and '
                     f'top slices must not overlap), got '
                     f'{config.pbt_quantile}')
  if config.pbt_perturb <= 1.0:
    raise ValueError(f'pbt_perturb must be > 1 (the explore factor '
                     f'multiplies OR divides), got '
                     f'{config.pbt_perturb}')
  if config.pbt_population == 1:
    warnings.append(
        'pbt_population=1: a population of one has no donor to '
        'exploit — PBT is off (use >= 2, ideally >= 2 per suite)')
  if config.pbt_vectorized and config.pbt_population < 2:
    warnings.append(
        'pbt_vectorized without pbt_population >= 2: there is no '
        'population to vectorize — the flag is inert')
  if config.pbt_population >= 2:
    if config.runtime != 'anakin':
      raise ValueError(
          'pbt_population >= 2 needs --runtime=anakin: population '
          'members are fused-loop replicas (the fleet runtime owns '
          'the host devices exclusively — replicas would contend)')
    suites = config.resolved_pbt_suites
    for suite in suites:
      if suite not in JITTABLE_BACKENDS:
        raise ValueError(
            f'pbt_suites names {suite!r}: population members are '
            f'anakin runs and need jittable backends '
            f'({", ".join(JITTABLE_BACKENDS)})')
    if 'cue_memory' in suites and any(s in ('gridworld', 'procgen')
                                      for s in suites):
      raise ValueError(
          'pbt_suites mixes cue_memory (fixed 3-action) with '
          'gridworld/procgen (>= 4 actions): members share one agent '
          'architecture, so their policy heads must be one width')
    if config.pbt_vectorized:
      if len(set(suites)) > 1:
        raise ValueError(
            'pbt_vectorized: one vmapped program trains ONE suite '
            '(member programs must be structurally identical), but '
            f'pbt_suites names {sorted(set(suites))} — drop '
            '--pbt_vectorized or train a single-suite population')
      if config.model_parallelism > 1:
        warnings.append(
            'pbt_vectorized with model_parallelism=%d: vectorized '
            'members are single-device programs — train_population '
            'degrades to the serial member loop' %
            config.model_parallelism)
    if config.pbt_population < len(suites):
      raise ValueError(
          f'pbt_population={config.pbt_population} cannot cover '
          f'{len(suites)} suites at >= 1 member each')
    if config.pbt_population < 2 * len(suites):
      warnings.append(
          'pbt_population=%d over %d suite(s): some suites get a '
          'single member, and exploit/explore only fires WITHIN a '
          'suite — size the population at >= 2 per suite' %
          (config.pbt_population, len(suites)))
  return warnings


def apply_overrides(config: Config, **overrides) -> Config:
  return dataclasses.replace(config, **overrides)

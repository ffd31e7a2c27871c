"""CLI entry point — flag-compatible with the reference's experiment.py.

Flag names mirror the reference (reference: experiment.py ≈L30–75,
tf.app.flags definitions) so an operator of the reference finds the
same knobs:

  python experiment.py --mode=train --level_name=explore_goal_locations_small \
      --num_actors=48 --batch_size=32 --total_environment_frames=1000000000
  python experiment.py --mode=test --level_name=dmlab30 --test_num_episodes=10

TPU-build additions are grouped at the bottom (env backend selection,
mesh width, dtype). The reference's --job_name/--task multi-process
topology is replaced by jax.distributed (see
scalable_agent_tpu/parallel/distributed.py): every host runs the same
command and the mesh spans them.
"""

import dataclasses
import logging

from absl import app, flags

from scalable_agent_tpu.config import Config

_DEFAULTS = Config()

flags.DEFINE_string('logdir', _DEFAULTS.logdir, 'Experiment directory.')
flags.DEFINE_enum('mode', 'train', ['train', 'test'],
                  'Run mode (the fused on-device loop is '
                  '--mode=train --runtime=anakin).')
flags.DEFINE_integer('test_num_episodes', _DEFAULTS.test_num_episodes,
                     'Episodes per level in test mode.')
flags.DEFINE_integer('task', _DEFAULTS.task,
                     'Process index in multi-host mode (-1: single).')
flags.DEFINE_string('job_name', _DEFAULTS.job_name,
                    "Role: 'learner' (default) or 'actor'. An actor "
                    'job runs an env fleet with CPU inference and '
                    'streams unrolls to --learner_address (the '
                    "reference's --job_name=actor gRPC topology). "
                    'Learner-side multi-CHIP roles are derived from '
                    'jax.distributed, not this flag.')
flags.DEFINE_string('learner_address', _DEFAULTS.learner_address,
                    'host:port of the learner ingest server '
                    '(--job_name=actor).')
flags.DEFINE_integer('remote_actor_port', _DEFAULTS.remote_actor_port,
                     'Learner: listen for remote actor hosts on this '
                     'port (0 = disabled).')
flags.DEFINE_string('remote_actor_bind_host',
                    _DEFAULTS.remote_actor_bind_host,
                    'Learner: interface the ingest server binds '
                    '(default loopback-only). The wire is '
                    'unauthenticated pickle — for real actor hosts, '
                    'explicitly bind a cluster-internal interface; '
                    'never expose the port publicly.')
flags.DEFINE_string('remote_params_dtype',
                    _DEFAULTS.remote_params_dtype,
                    'LEGACY spelling of --publish_codec: \'\' defers '
                    "to the codec, 'bfloat16' forces the bf16 cast.")
flags.DEFINE_float('remote_publish_secs',
                   _DEFAULTS.remote_publish_secs,
                   'Min seconds between param snapshots published to '
                   'remote actor hosts; the main knob on learner '
                   'weight egress (hosts x blob_bytes / this) and '
                   'remote policy staleness (docs/PERF.md).')
flags.DEFINE_float('actor_reconnect_secs',
                   _DEFAULTS.actor_reconnect_secs,
                   'Actor: on disconnect, retry the learner for this '
                   'many seconds (survives a learner restart — size '
                   'it ABOVE the learner restart budget of restore + '
                   'recompile, ~90s; validate_transport warns '
                   'otherwise); 0 = exit on disconnect. Default '
                   'nonzero since round 11 (docs/RUNBOOK.md §8).')
flags.DEFINE_float('remote_heartbeat_secs',
                   _DEFAULTS.remote_heartbeat_secs,
                   'Transport heartbeat cadence (protocol v6, '
                   'negotiated off for v5 peers): idle actors ping '
                   'inside the reaping window, and the learner emits '
                   "'busy' keepalives while backpressure holds an "
                   'ack. 0 = no heartbeats (docs/TRANSPORT.md).')
flags.DEFINE_float('remote_conn_idle_timeout_secs',
                   _DEFAULTS.remote_conn_idle_timeout_secs,
                   'Reap ingest/param-lane connections that received '
                   'no bytes for this long (half-open peers used to '
                   'pin a reader forever); doubles as the mid-frame '
                   'stall + send no-progress deadline and the '
                   "actor's I/O deadline on a silent learner. "
                   '0 = never reap, no deadlines.')
flags.DEFINE_integer('num_actors', _DEFAULTS.num_actors,
                     'Actor (environment) count.')
flags.DEFINE_integer('total_environment_frames',
                     _DEFAULTS.total_environment_frames,
                     'Training length in env frames (after action '
                     'repeat).')
flags.DEFINE_integer('batch_size', _DEFAULTS.batch_size,
                     'Learner batch size (unrolls per SGD step).')
flags.DEFINE_integer('unroll_length', _DEFAULTS.unroll_length,
                     'Trajectory unroll length T (learner sees T+1).')
flags.DEFINE_integer('num_action_repeats', _DEFAULTS.num_action_repeats,
                     'Env frames per agent action.')
flags.DEFINE_integer('seed', _DEFAULTS.seed, 'Random seed.')
flags.DEFINE_float('entropy_cost', _DEFAULTS.entropy_cost,
                   'Entropy cost/multiplier.')
flags.DEFINE_float('baseline_cost', _DEFAULTS.baseline_cost,
                   'Baseline cost/multiplier.')
flags.DEFINE_float('discounting', _DEFAULTS.discounting,
                   'Discounting factor.')
flags.DEFINE_enum('reward_clipping', _DEFAULTS.reward_clipping,
                  ['abs_one', 'soft_asymmetric', 'none'],
                  'Reward clipping.')
flags.DEFINE_string('dataset_path', _DEFAULTS.dataset_path,
                    'Path to dataset needed for psychlab_*, see '
                    'DMLab docs.')
flags.DEFINE_string('level_cache_dir', _DEFAULTS.level_cache_dir,
                    'DMLab compiled-level cache directory override.')
flags.DEFINE_string('level_name', _DEFAULTS.level_name,
                    "Level name, or 'dmlab30' for the full benchmark.")
flags.DEFINE_integer('width', _DEFAULTS.width, 'Frame width.')
flags.DEFINE_integer('height', _DEFAULTS.height, 'Frame height.')
flags.DEFINE_float('learning_rate', _DEFAULTS.learning_rate,
                   'Learning rate.')
flags.DEFINE_float('decay', _DEFAULTS.decay, 'RMSProp decay.')
flags.DEFINE_float('momentum', _DEFAULTS.momentum, 'RMSProp momentum.')
flags.DEFINE_float('epsilon', _DEFAULTS.epsilon, 'RMSProp epsilon.')

# --- TPU-build additions (not in the reference). ---
flags.DEFINE_enum('env_backend', _DEFAULTS.env_backend,
                  ['dmlab', 'atari', 'fake', 'bandit', 'cue_memory',
                   'gridworld', 'procgen', 'tokens'],
                  'Environment backend (fake/bandit/cue_memory are '
                  'simulator-free smoke tasks; gridworld/procgen are '
                  'the pure-JAX family of envs/jittable.py — the same '
                  'task runs under both --runtime values; tokens is '
                  'the seeded token task of envs/tokens.py, for '
                  '--agent=sequence).')
flags.DEFINE_enum('runtime', _DEFAULTS.runtime, ['fleet', 'anakin'],
                  'Training runtime: fleet (host envs -> inference -> '
                  'buffer -> learner, the production pipeline) or '
                  'anakin (act+learn fused into one jitted device '
                  'step for jittable env backends — Podracer '
                  'arXiv:2104.06272 — under the same run lifecycle: '
                  'checkpoints, health ladder, SLO verdict, JSONL '
                  'streams; docs/PARALLELISM.md, RUNBOOK §13).')
flags.DEFINE_bool('anakin_filler', _DEFAULTS.anakin_filler,
                  'Hybrid filler fleets (fleet runtime): run one '
                  'bounded Anakin self-play step on the learner chips '
                  'whenever the prefetcher has no staged batch ready '
                  '(a staged batch is never delayed by more than one '
                  'filler step); fresh-frame clocks unchanged, filler '
                  'work accounted separately. Default OFF pending the '
                  'docs/PERF.md r13 accept/reject call.')
flags.DEFINE_string('filler_backend', _DEFAULTS.filler_backend,
                    "Filler env core ('' = auto: the run's backend "
                    "when jittable, else 'bandit').")
flags.DEFINE_integer('filler_batch_size', _DEFAULTS.filler_batch_size,
                     'Filler rollout batch (0 = auto: batch_size).')
flags.DEFINE_integer('filler_unroll_length',
                     _DEFAULTS.filler_unroll_length,
                     'Filler rollout length (0 = auto: '
                     'min(unroll_length, 16) — short slices keep the '
                     'yield bound tight).')
flags.DEFINE_float('sticky_action_prob', _DEFAULTS.sticky_action_prob,
                   'Atari: per-frame previous-action repeat '
                   'probability (0.25 = Machado et al. evaluation '
                   'protocol).', lower_bound=0.0, upper_bound=1.0)
flags.DEFINE_enum('torso', _DEFAULTS.torso,
                  ['deep', 'deep_fast', 'shallow'],
                  'Agent torso: deep ResNet (reference), deep_fast '
                  '(stride-2 convs replace the max-pools — the HBM-'
                  'bandwidth operating point, docs/PERF.md; '
                  'THROUGHPUT VARIANT, UNVALIDATED RETURNS: a '
                  'different function whose learning evidence is '
                  'bandit-grade only — run '
                  'scripts/compare_torsos.py before trusting it on '
                  "a real task), or the paper's shallow CNN.")
flags.DEFINE_enum('compute_dtype', _DEFAULTS.compute_dtype,
                  ['float32', 'bfloat16'], 'On-device compute dtype.')
flags.DEFINE_enum('agent', _DEFAULTS.agent, ['impala', 'sequence'],
                  "The model: the paper's conv + LSTM agent over "
                  'frames, or a sequence policy (token embedding -> '
                  'power-retention blocks -> heads over the '
                  'vocabulary --num_actions; widths: --seq_*), which '
                  'goes with --env_backend=tokens.')
flags.DEFINE_enum('param_dtype', _DEFAULTS.param_dtype,
                  ['float32', 'bfloat16'],
                  'Dtype the parameters are created in (bfloat16: '
                  'serving a large sequence policy).')
flags.DEFINE_integer('seq_num_layers', _DEFAULTS.seq_num_layers,
                     'Sequence agent: retention blocks.', lower_bound=1)
flags.DEFINE_integer('seq_hidden_size', _DEFAULTS.seq_hidden_size,
                     'Sequence agent: hidden size.', lower_bound=1)
flags.DEFINE_integer('seq_num_heads', _DEFAULTS.seq_num_heads,
                     'Sequence agent: query heads.', lower_bound=1)
flags.DEFINE_integer('seq_num_kv_heads', _DEFAULTS.seq_num_kv_heads,
                     'Sequence agent: key-value heads (one retention '
                     'state each).', lower_bound=1)
flags.DEFINE_integer('seq_head_dim', _DEFAULTS.seq_head_dim,
                     'Sequence agent: head size (even).', lower_bound=2)
flags.DEFINE_integer('seq_mlp_size', _DEFAULTS.seq_mlp_size,
                     'Sequence agent: SwiGLU width.', lower_bound=1)
flags.DEFINE_float('seq_rope_theta', _DEFAULTS.seq_rope_theta,
                   'Sequence agent: RoPE base.')
flags.DEFINE_float('seq_norm_eps', _DEFAULTS.seq_norm_eps,
                   'Sequence agent: RMSNorm epsilon.')
flags.DEFINE_string('seq_layer_pattern', _DEFAULTS.seq_layer_pattern,
                    'Sequence agent: given, the core is grouped-query '
                    'attention (--seq_num_kv_heads groups of '
                    '--seq_head_dim) in this pattern of layers, one '
                    'letter a layer, repeated over the depth: L attends '
                    'to the last --seq_window tokens and keeps a ring of '
                    'them, G to the whole episode and keeps it; with the '
                    'dense and routed-expert feed-forward layers of the '
                    'latent core\'s flags. With an M (a Mamba-2 layer, '
                    'the --seq_ssm_* flags) the core is Granite 4.0-H\'s '
                    'hybrid of M and G layers, routed experts in each.')
flags.DEFINE_integer('seq_window', _DEFAULTS.seq_window,
                     'Window layers: the tokens one attends to, its own '
                     'among them.', lower_bound=1)
flags.DEFINE_integer('seq_kv_lora_rank', _DEFAULTS.seq_kv_lora_rank,
                     'Sequence agent: 0 for the power-retention core (or '
                     'the one --seq_layer_pattern names); '
                     'above 0 the core is latent attention (MLA) with '
                     'this latent width, over a per-session latent cache, '
                     'with dense and routed-expert feed-forward layers '
                     '(the flags below; --seq_num_kv_heads and '
                     '--seq_head_dim are then unused).', lower_bound=0)
flags.DEFINE_integer('seq_q_lora_rank', _DEFAULTS.seq_q_lora_rank,
                     'Latent core: the query latent\'s width.',
                     lower_bound=1)
flags.DEFINE_integer('seq_qk_nope_head_dim', _DEFAULTS.seq_qk_nope_head_dim,
                     'Latent core: a head\'s query/key width without '
                     'position.', lower_bound=1)
flags.DEFINE_integer('seq_qk_rope_head_dim', _DEFAULTS.seq_qk_rope_head_dim,
                     'Latent core: a head\'s rotary width (even); the '
                     'rotary key is one for all heads.', lower_bound=2)
flags.DEFINE_integer('seq_v_head_dim', _DEFAULTS.seq_v_head_dim,
                     'Latent core: a head\'s value width.', lower_bound=1)
flags.DEFINE_integer('seq_first_dense_layers',
                     _DEFAULTS.seq_first_dense_layers,
                     'Latent core: leading layers whose feed-forward is '
                     'the dense MLP of --seq_mlp_size; the others route.',
                     lower_bound=1)
flags.DEFINE_integer('seq_moe_size', _DEFAULTS.seq_moe_size,
                     'Latent core: an expert\'s SwiGLU width.',
                     lower_bound=1)
flags.DEFINE_integer('seq_routed_experts', _DEFAULTS.seq_routed_experts,
                     'Latent core: routed experts, the router\'s outputs.',
                     lower_bound=2)
flags.DEFINE_integer('seq_experts_held', _DEFAULTS.seq_experts_held,
                     'Latent core: how many of the routed experts this '
                     'process holds and computes (its share of an '
                     'expert-parallel deployment).', lower_bound=1)
flags.DEFINE_integer('seq_expert_offset', _DEFAULTS.seq_expert_offset,
                     'Latent core: the first expert held.', lower_bound=0)
flags.DEFINE_integer('seq_experts_per_token',
                     _DEFAULTS.seq_experts_per_token,
                     'Latent core: experts chosen a token.', lower_bound=1)
flags.DEFINE_integer('seq_expert_groups', _DEFAULTS.seq_expert_groups,
                     'Latent core: groups the routed experts lie in.',
                     lower_bound=1)
flags.DEFINE_integer('seq_expert_groups_kept',
                     _DEFAULTS.seq_expert_groups_kept,
                     'Latent core: groups a token may choose from.',
                     lower_bound=1)
flags.DEFINE_float('seq_routed_scale', _DEFAULTS.seq_routed_scale,
                   'Latent core: scale on the normalised expert weights.')
flags.DEFINE_integer('seq_shared_experts', _DEFAULTS.seq_shared_experts,
                     'Latent core: shared experts (one SwiGLU of that '
                     'many times --seq_moe_size).', lower_bound=1)
flags.DEFINE_enum('seq_router', _DEFAULTS.seq_router, ['sigmoid', 'softmax'],
                  'Routed layers: sigmoid scores with a bias that moves the '
                  'choice, or a softmax over the chosen logits.')
flags.DEFINE_integer('seq_ssm_heads', _DEFAULTS.seq_ssm_heads,
                     'Mamba-2 layers (an M in --seq_layer_pattern): heads.',
                     lower_bound=1)
flags.DEFINE_integer('seq_ssm_head_dim', _DEFAULTS.seq_ssm_head_dim,
                     'Mamba-2 layers: a head\'s channels.', lower_bound=1)
flags.DEFINE_integer('seq_ssm_state', _DEFAULTS.seq_ssm_state,
                     'Mamba-2 layers: the state size (d_state).',
                     lower_bound=1)
flags.DEFINE_integer('seq_conv_width', _DEFAULTS.seq_conv_width,
                     'Mamba-2 layers: the causal conv\'s width (d_conv).',
                     lower_bound=2)
flags.DEFINE_integer('seq_ssm_block', _DEFAULTS.seq_ssm_block,
                     'Mamba-2 layers: tokens the chunk form takes at once.',
                     lower_bound=1)
flags.DEFINE_float('seq_embedding_multiplier',
                   _DEFAULTS.seq_embedding_multiplier,
                   'Core of Mamba-2 and attention layers: scale on the '
                   'embedding.')
flags.DEFINE_float('seq_residual_multiplier',
                   _DEFAULTS.seq_residual_multiplier,
                   'Core of Mamba-2 and attention layers: scale on each '
                   'sublayer\'s output.')
flags.DEFINE_float('seq_attention_multiplier',
                   _DEFAULTS.seq_attention_multiplier,
                   'Core of Mamba-2 and attention layers: scale on q . k.')
flags.DEFINE_float('seq_logits_scaling', _DEFAULTS.seq_logits_scaling,
                   'Core of Mamba-2 and attention layers: the logits are '
                   'divided by it.')
flags.DEFINE_float('seq_rope_factor', _DEFAULTS.seq_rope_factor,
                   'Latent core: YaRN context-extension factor (1: plain '
                   'rotary).')
flags.DEFINE_integer('seq_rope_original_max',
                     _DEFAULTS.seq_rope_original_max,
                     'Latent core: YaRN original_max_position_embeddings.',
                     lower_bound=1)
flags.DEFINE_float('seq_rope_beta_fast', _DEFAULTS.seq_rope_beta_fast,
                   'Latent core: YaRN beta_fast.')
flags.DEFINE_float('seq_rope_beta_slow', _DEFAULTS.seq_rope_beta_slow,
                   'Latent core: YaRN beta_slow.')
flags.DEFINE_float('seq_rope_mscale', _DEFAULTS.seq_rope_mscale,
                   'Latent core: YaRN mscale.')
flags.DEFINE_float('seq_rope_mscale_all_dim',
                   _DEFAULTS.seq_rope_mscale_all_dim,
                   'Latent core: YaRN mscale_all_dim.')
flags.DEFINE_integer('seq_cache_capacity', _DEFAULTS.seq_cache_capacity,
                     'Latent core, full layers: tokens of an episode a '
                     'session\'s cache holds (at least --episode_length).',
                     lower_bound=1)
flags.DEFINE_integer('seq_prefill_chunk', _DEFAULTS.seq_prefill_chunk,
                     'Cores with a cache: tokens one prefill call takes; an '
                     'episode\'s prompt reaches the server in such '
                     'blocks.', lower_bound=1)
flags.DEFINE_integer('token_prompt_length',
                     _DEFAULTS.token_prompt_length,
                     'tokens backend: seeded prompt tokens an episode.',
                     lower_bound=1)
flags.DEFINE_integer('token_prompt_stride',
                     _DEFAULTS.token_prompt_stride,
                     'tokens backend: session i\'s prompt is this many '
                     'tokens times (i mod --num_actors) longer.',
                     lower_bound=0)
flags.DEFINE_integer('model_parallelism', _DEFAULTS.model_parallelism,
                     'TP width of the device mesh.')
flags.DEFINE_bool('use_py_process', _DEFAULTS.use_py_process,
                  'Host each env in its own OS process.')
flags.DEFINE_bool('use_instruction', _DEFAULTS.use_instruction,
                  'Enable the language/instruction channel. Default '
                  'auto: on for dmlab30 / language_* / psychlab_* '
                  'levels, off otherwise (the encoder costs ~6% step '
                  'time — docs/PERF.md).')
flags.DEFINE_bool('use_popart', _DEFAULTS.use_popart,
                  'PopArt per-task value normalization.')
flags.DEFINE_float('pixel_control_cost', _DEFAULTS.pixel_control_cost,
                   'UNREAL pixel-control aux loss weight (0 = off).')
flags.DEFINE_integer('episode_length', _DEFAULTS.episode_length,
                     'Episode length of the fake/bandit backends.')
flags.DEFINE_integer('publish_params_every',
                     _DEFAULTS.publish_params_every,
                     'Learner steps between actor weight snapshots.')
flags.DEFINE_integer('inference_min_batch', _DEFAULTS.inference_min_batch,
                     'Dynamic batcher minimum merge size. 0 = auto: '
                     'train-mode merges floor at the fleet size, '
                     'bounded by --inference_timeout_ms (the measured '
                     '+53% e2e merge lever, docs/PERF.md); eval '
                     'ignores the floor (its caller count shrinks as '
                     'levels finish).')
flags.DEFINE_integer('inference_max_batch', _DEFAULTS.inference_max_batch,
                     'Dynamic batcher maximum merge size.')
flags.DEFINE_integer('inference_timeout_ms',
                     _DEFAULTS.inference_timeout_ms,
                     'Dynamic batcher flush timeout.')
flags.DEFINE_bool('inference_state_cache',
                  _DEFAULTS.inference_state_cache,
                  'Keep each actor\'s LSTM carry in a device-resident '
                  'state arena (gather/scatter by slot id in-graph) '
                  'instead of shipping it host<->device every step. '
                  'Numerics-identical (parity-gated; '
                  'docs/INFERENCE.md).')
flags.DEFINE_integer('inference_pipeline_depth',
                     _DEFAULTS.inference_pipeline_depth,
                     'Merged inference batches in flight on device: '
                     '2 overlaps batch assembly/H2D with the previous '
                     'batch\'s compute; 1 = serial dispatch.')
flags.DEFINE_integer('inference_state_slots',
                     _DEFAULTS.inference_state_slots,
                     'State-arena capacity in slots (state-cache '
                     'mode). 0 = auto: 2x the fleet size (respawn '
                     'headroom).')
flags.DEFINE_enum('inference_admission', _DEFAULTS.inference_admission,
                  ['block', 'shed', 'grow'],
                  'Slot admission when the state arena is exhausted: '
                  'block = deadline-bounded priority waitlist '
                  '(default), shed = deadline rejection counted as '
                  'load shedding, grow = double the arena in place. '
                  'Exhaustion never raises into the learner loop '
                  '(docs/ROBUSTNESS.md actor-plane rows).')
flags.DEFINE_float('inference_admission_timeout_secs',
                   _DEFAULTS.inference_admission_timeout_secs,
                   'Deadline for parked slot acquisitions '
                   '(block/shed admission).')
flags.DEFINE_integer('max_unroll_staleness',
                     _DEFAULTS.max_unroll_staleness,
                     'Ingest admission window in published param '
                     'versions: remote unrolls generated more than '
                     'this many versions behind the current snapshot '
                     'are refused (benign; the actor refetches and '
                     'keeps feeding). 0 = no window.')
flags.DEFINE_integer('fleet_quarantine_after',
                     _DEFAULTS.fleet_quarantine_after,
                     'Consecutive respawns without one completed '
                     'unroll before an actor slot quarantines '
                     '(slots_quarantined in summaries); 0 = retry '
                     'forever (backoff-paced).')
flags.DEFINE_float('preempt_drain_timeout_secs',
                   _DEFAULTS.preempt_drain_timeout_secs,
                   'Preemption drain budget: SIGTERM stops '
                   'admissions, flushes in-flight unrolls, takes a '
                   'verified checkpoint and writes '
                   'resume_manifest.json within this many seconds '
                   '(docs/RUNBOOK.md drain/resume).')
flags.DEFINE_integer('num_actions', _DEFAULTS.num_actions,
                     'Policy head size override (None = backend '
                     'default; Atari: 18 full set, fewer = minimal '
                     'set, validated against the backend).')
flags.DEFINE_float('popart_beta', _DEFAULTS.popart_beta,
                   'PopArt statistics EMA step size.')
flags.DEFINE_float('pixel_control_discount',
                   _DEFAULTS.pixel_control_discount,
                   'UNREAL pixel-control n-step discount.')
flags.DEFINE_integer('pixel_control_cell_size',
                     _DEFAULTS.pixel_control_cell_size,
                     'UNREAL pixel-control spatial cell size.')
flags.DEFINE_bool('pixel_control_integer_rewards',
                  _DEFAULTS.pixel_control_integer_rewards,
                  'Integer-domain pixel-control pseudo-rewards '
                  '(uint8 diff + int32 cell sums; no full-resolution '
                  'float frame temporaries — parity-gated byte '
                  'lever, docs/PERF.md r6). Auto-falls back to the '
                  'f32 form for non-uint8 observations.')
flags.DEFINE_enum('pixel_control_head_impl',
                  _DEFAULTS.pixel_control_head_impl,
                  ['deconv', 'd2s'],
                  'Pixel-control Q-head deconv implementation: '
                  'deconv (nn.ConvTranspose reference form, default) '
                  'or d2s (depth-to-space recast — parameter-'
                  'identical, checkpoint-interchangeable, parity-'
                  'gated).')
flags.DEFINE_bool('pixel_control_q_f32', _DEFAULTS.pixel_control_q_f32,
                  'Cast the pixel-control Q-map to float32 at the '
                  'head (default). False keeps it in the compute '
                  'dtype until the loss gather/max — a byte lever '
                  'that bf16-rounds the Q-values the loss sees.')
flags.DEFINE_float('grad_clip_norm', _DEFAULTS.grad_clip_norm,
                   'Global gradient-norm clip (None = off, the '
                   'reference behavior).')
flags.DEFINE_bool('use_associative_scan', _DEFAULTS.use_associative_scan,
                  'V-trace via lax.associative_scan (log-depth in T) '
                  'instead of the sequential scan.')
flags.DEFINE_bool('use_pallas_vtrace', _DEFAULTS.use_pallas_vtrace,
                  'V-trace via the fused Pallas TPU kernel '
                  '(single-device meshes only).')
flags.DEFINE_integer('scan_unroll', _DEFAULTS.scan_unroll,
                     'LSTM time-scan unroll factor (perf knob; see '
                     'config.py for the measured sweep).')
flags.DEFINE_integer('checkpoint_secs', _DEFAULTS.checkpoint_secs,
                     'Seconds between checkpoints (reference '
                     'save_checkpoint_secs=600).')
flags.DEFINE_integer('checkpoint_check_every_steps',
                     _DEFAULTS.checkpoint_check_every_steps,
                     'Learner steps between cross-host checkpoint-'
                     'cadence broadcasts (multi-host).')
flags.DEFINE_integer('summary_secs', _DEFAULTS.summary_secs,
                     'Seconds between summary flushes (reference '
                     'save_summaries_secs=30).')
flags.DEFINE_integer('queue_capacity_batches',
                     _DEFAULTS.queue_capacity_batches,
                     'Trajectory buffer capacity in batches '
                     '(reference FIFOQueue capacity=1; small keeps '
                     'policy lag bounded).')
flags.DEFINE_integer('staging_depth', _DEFAULTS.staging_depth,
                     'Staged device batches in flight (prefetcher '
                     'depth): 2 overlaps consecutive host-to-device '
                     'transfers with the step; each extra slot adds '
                     'one batch of policy lag.')
flags.DEFINE_enum('staging_mode', _DEFAULTS.staging_mode,
                  ['batch', 'unroll'],
                  'Learner feed staging: batch = host-stack + one '
                  'device_put burst per step (default); unroll = '
                  'per-unroll eager H2D + on-device batch assembly '
                  '(the step-boundary burst becomes a trickle '
                  'overlapped with compute — parity-gated; docs/PERF.md '
                  'r8).')
# --- Sample reuse (round 10; IMPACT arXiv 1912.00167 — docs/PERF.md
# r9, RUNBOOK §5 knob guidance). ---
flags.DEFINE_enum('surrogate', _DEFAULTS.surrogate,
                  ['vtrace', 'impact'],
                  'Loss surrogate: vtrace (reference IMPALA path, '
                  'default) or impact (clipped-target surrogate: '
                  'on-device target-network anchor for the V-trace IS '
                  'ratios plus a PPO-style clip of the current/target '
                  'ratio — the staleness-tolerant form sample reuse '
                  'needs; bit-identical to vtrace at replay_k=1, '
                  'replay_ratio=0, target_update_interval=1).')
flags.DEFINE_float('impact_epsilon', _DEFAULTS.impact_epsilon,
                   'Clip width of the impact surrogate\'s '
                   'current/target policy ratio.')
flags.DEFINE_integer('target_update_interval',
                     _DEFAULTS.target_update_interval,
                     'Learner steps between target-network refreshes '
                     '(impact surrogate; in-graph select, no host '
                     'round trip). Interacts with replay staleness: '
                     'the anchor must not refresh slower than the '
                     'replay window ages (RUNBOOK §5).')
flags.DEFINE_integer('replay_k', _DEFAULTS.replay_k,
                     'Times each staged device batch is served to the '
                     'learner before release (no re-stage, no added '
                     'H2D). Default 1 = no reuse, per the measured '
                     'accept/reject discipline (docs/PERF.md r9).')
flags.DEFINE_float('replay_ratio', _DEFAULTS.replay_ratio,
                   'Fraction of each batch\'s unroll slots drawn from '
                   'the circular replay tier ([0, 1); 0 = off). '
                   'Replayed unrolls re-stage (one H2D each), unlike '
                   'replay_k re-serves.')
flags.DEFINE_integer('replay_capacity_unrolls',
                     _DEFAULTS.replay_capacity_unrolls,
                     'Circular replay tier capacity in unrolls '
                     '(0 = auto: 4x batch). Oldest entries overwrite '
                     'IMPACT-style when full.')
flags.DEFINE_integer('replay_max_staleness',
                     _DEFAULTS.replay_max_staleness,
                     'Replay eviction window in PUBLISHED '
                     'PARAM-VERSION deltas — the same unit as '
                     '--max_unroll_staleness (which gates ingest '
                     'admission; this gates re-serving). 0 = defer '
                     'to max_unroll_staleness; both 0 = no bound.')
flags.DEFINE_enum('publish_codec', _DEFAULTS.publish_codec,
                  ['bf16', 'f32', 'int8'],
                  'Wire codec for served param snapshots: bf16 '
                  '(default) halves learner weight egress, actors '
                  'upcast on receipt; f32 ships exact float32; int8 '
                  'absmax-quantizes (runtime/codec.py, wire v10 — '
                  'v<=9 peers still get bf16) and stores resident '
                  'serving versions quantized. Parity-gated on '
                  'greedy action agreement (bench serving stage).')
flags.DEFINE_integer('ingest_workers', _DEFAULTS.ingest_workers,
                     'Validate/commit workers behind the remote-'
                     'ingest reader threads (0 = auto).')
flags.DEFINE_bool('wire_crc', _DEFAULTS.wire_crc,
                  'Protocol v7 per-frame CRC32C trailers on the '
                  'remote lanes (negotiated off for v5/v6 peers): a '
                  'corrupt unroll is refused before the buffer put, '
                  'a corrupt param blob before install '
                  '(docs/TRANSPORT.md v7).')
flags.DEFINE_bool('ckpt_digests', _DEFAULTS.ckpt_digests,
                  'Record per-file content digests on verified '
                  'checkpoint saves and re-verify them in the '
                  'restore ladder — bit rot on a committed step '
                  'falls back instead of restoring garbage.')
flags.DEFINE_bool('sdc_check', _DEFAULTS.sdc_check,
                  'Cross-replica param-fingerprint SDC sentinel '
                  '(pure-DP meshes with >= 2 data replicas): replica '
                  'disagreement escalates through the health ladder '
                  '(docs/ROBUSTNESS.md, docs/RUNBOOK.md §9).')
flags.DEFINE_bool('sdc_allgather', _DEFAULTS.sdc_allgather,
                  'All-gather the per-replica SDC fingerprints '
                  'in-graph so the sentinel runs on multi-process '
                  'meshes too (round 17); false restores the '
                  'single-controller gate.')
flags.DEFINE_string('tp_compute', _DEFAULTS.tp_compute,
                    'How TP matmuls execute: auto (sharded on '
                    'TPU/GPU, the gathered workaround on CPU — this '
                    'jaxlib mis-computes differentiated programs '
                    'over model-sharded leaves), sharded, or '
                    'gathered (docs/PARALLELISM.md).')
flags.DEFINE_string('sharding_rules', _DEFAULTS.sharding_rules,
                    'Partition-rule set the sharding registry '
                    'resolves every placement from (parallel/'
                    'sharding.py): auto (megatron when '
                    'model_parallelism > 1, else replicated), '
                    'replicated, or megatron '
                    '(docs/PARALLELISM.md).')
flags.DEFINE_bool('replay_crc', _DEFAULTS.replay_crc,
                  'Verify replay-tier entries against their '
                  'insert-time CRC at every serve; rot evicts '
                  'instead of re-serving.')
flags.DEFINE_bool('telemetry_trace', _DEFAULTS.telemetry_trace,
                  'Per-unroll trace spans (protocol v8) + the '
                  'traces.jsonl stream and policy-lag attribution '
                  '(scripts/trace_report.py; docs/OBSERVABILITY.md). '
                  'Measured overhead below noise — docs/PERF.md r11.')
flags.DEFINE_integer('telemetry_flight_len',
                     _DEFAULTS.telemetry_flight_len,
                     'Flight-recorder depth: recent trace records + '
                     'registry snapshots dumped with halt bundles '
                     'and rollback incidents.')
# --- SLO engine (round 14; slo.py, docs/OBSERVABILITY.md). ---
flags.DEFINE_bool('slo_engine', _DEFAULTS.slo_engine,
                  'Declarative SLO evaluation over the metrics '
                  'registry: burn-rate windows, slo_violation '
                  'incidents, the per-run SLO_VERDICT.json go/no-go '
                  'artifact, and triggered deep diagnostics '
                  '(docs/OBSERVABILITY.md SLO inventory; overhead '
                  'measured, docs/PERF.md r12).')
flags.DEFINE_string('slo_spec', _DEFAULTS.slo_spec,
                    'JSON objective-set file; empty = the shipped '
                    'default objectives (slo.DEFAULT_OBJECTIVES).')
flags.DEFINE_float('slo_fast_window_secs',
                   _DEFAULTS.slo_fast_window_secs,
                   'Fast burn window for objectives that do not pin '
                   'their own (must be fully violating to burn).')
flags.DEFINE_float('slo_slow_window_secs',
                   _DEFAULTS.slo_slow_window_secs,
                   'Slow burn window (>= half violating confirms a '
                   'sustained burn).')
flags.DEFINE_float('slo_interval_secs', _DEFAULTS.slo_interval_secs,
                   'Evaluator thread cadence (0 = derive from '
                   'summary_secs; the summary block also evaluates).')
flags.DEFINE_bool('slo_capture', _DEFAULTS.slo_capture,
                  'Triggered deep diagnostics on the first burn of a '
                  'page-severity objective: flight dump + trace '
                  'slice + a bounded jax.profiler capture into '
                  '<logdir>/diagnostics/ (one per objective per run).')
flags.DEFINE_integer('slo_capture_steps', _DEFAULTS.slo_capture_steps,
                     'Learner steps a triggered profiler capture '
                     'covers.')
flags.DEFINE_string('slo_fps_baseline', _DEFAULTS.slo_fps_baseline,
                    'Per-host fps baseline file for the fps_floor '
                    'objective (JSON {hostname: {"fps": value}}; '
                    'scripts/slo_report.py --update-fps-baseline '
                    'records one). Empty = objective reads '
                    'no_baseline.')
# --- Self-healing controller (round 15; controller.py,
# docs/RUNBOOK.md §12). ---
flags.DEFINE_enum('controller', _DEFAULTS.controller,
                  ['off', 'observe', 'act'],
                  'Verdict-to-actuation loop over the SLO engine: '
                  'observe (default) dry-runs the policy table into '
                  'CONTROLLER_LOG.json; act applies the bounded '
                  'moves (replay_k, admission mode, publish '
                  'cadence, fleet size); off removes the thread. '
                  'CHAOS_STORM=controller is the acceptance drill.')
flags.DEFINE_string('controller_policy', _DEFAULTS.controller_policy,
                    'JSON rule-list file; empty = the shipped '
                    'controller.DEFAULT_RULES table '
                    '(docs/OBSERVABILITY.md).')
flags.DEFINE_float('controller_interval_secs',
                   _DEFAULTS.controller_interval_secs,
                   'Controller tick cadence (0 = share the SLO '
                   "engine's derived interval).")
flags.DEFINE_integer('controller_replay_k_max',
                     _DEFAULTS.controller_replay_k_max,
                     'Hard upper bound for the replay_k actuator '
                     '(the bounded-move guarantee).')
flags.DEFINE_float('controller_publish_secs_max',
                   _DEFAULTS.controller_publish_secs_max,
                   'Hard upper bound for the publish-cadence '
                   'actuator, seconds.')
flags.DEFINE_float('fleet_probation_secs',
                   _DEFAULTS.fleet_probation_secs,
                   'Quarantine probation cool-down before a '
                   'rehabilitation attempt (fleet slots and the '
                   "remote client's CRC self-quarantine).")
flags.DEFINE_integer('pod_max_hosts', _DEFAULTS.pod_max_hosts,
                     'Upper bound for the pod_size actuator (elastic '
                     'pod membership): the controller publishes the '
                     'desired actor-host count to POD_TARGET.json '
                     'for the cluster supervisor to reconcile. '
                     '0 = actuator off.')
flags.DEFINE_bool('lock_order_check', _DEFAULTS.lock_order_check,
                  'Arm runtime lock-order detection for this run: '
                  'the threaded modules\' locks record the '
                  'process-wide acquisition graph and a cycle (a '
                  'latent ABBA deadlock) lands as a durable '
                  'lock_order_inversion incident + the '
                  'analysis/lock_cycles counter. Default off in '
                  'production; tests/chaos run armed '
                  '(docs/STATIC_ANALYSIS.md).')
flags.DEFINE_integer('serving_resident_versions',
                     _DEFAULTS.serving_resident_versions,
                     'Policy versions resident concurrently in the '
                     'inference version table (1 = the classic '
                     'single snapshot). Re-publishing a resident '
                     'version flips live without a tree copy; LRU '
                     'eviction spares pinned + live entries.')
flags.DEFINE_float('serving_hbm_budget_mb',
                   _DEFAULTS.serving_hbm_budget_mb,
                   'Optional byte budget (MB) over resident serving '
                   'versions; 0 = count cap only.')
flags.DEFINE_float('serving_ab_fraction',
                   _DEFAULTS.serving_ab_fraction,
                   'Fraction of merged inference calls served by the '
                   'A/B candidate version (newest non-live resident '
                   'unless set_ab pins one).')
flags.DEFINE_float('serving_shadow_fraction',
                   _DEFAULTS.serving_shadow_fraction,
                   'Fraction of merged calls also replayed against '
                   'the shadow version (pure step, no RNG/arena '
                   'effects) and scored on greedy agreement into '
                   'the serving/shadow_divergence gauge.')
flags.DEFINE_bool('serving_aot', _DEFAULTS.serving_aot,
                  'Pre-compile serving steps per (batch bucket, '
                  'params structure) at publish/warmup so a version '
                  'flip never pays first-call compile on the serve '
                  'path. Off pending chip rows (docs/PERF.md).')
flags.DEFINE_string('serving_replicas', _DEFAULTS.serving_replicas,
                    'Comma-separated learner replica addresses an '
                    'actor host routes inference over (wire v10 '
                    'health-weighted round-robin; drains on leave). '
                    "'' = host-local inference.")
flags.DEFINE_bool('health_watchdog', _DEFAULTS.health_watchdog,
                  'Learner failure domain (health.py): skip '
                  'non-finite updates on device, roll back to the '
                  'last-known-good checkpoint after K consecutive '
                  'bad steps, halt with a diagnostic bundle after '
                  'the rollback budget (docs/ROBUSTNESS.md).')
flags.DEFINE_integer('health_check_every_steps',
                     _DEFAULTS.health_check_every_steps,
                     'Host-side sentinel read cadence (each check is '
                     'one tiny device_get; the device-side skip '
                     'protects params regardless).')
flags.DEFINE_integer('health_window', _DEFAULTS.health_window,
                     'Recent health checks retained (sliding window '
                     'for the relative detectors + the halt '
                     "bundle's metrics tail).")
flags.DEFINE_integer('health_min_window', _DEFAULTS.health_min_window,
                     'Good samples required before the relative '
                     'detectors (loss explosion, sigma divergence) '
                     'arm.')
flags.DEFINE_integer('health_rollback_after',
                     _DEFAULTS.health_rollback_after,
                     'Consecutive bad steps before an automatic '
                     'checkpoint rollback.')
flags.DEFINE_integer('health_max_rollbacks',
                     _DEFAULTS.health_max_rollbacks,
                     'Rollbacks granted before the watchdog halts '
                     'the run with a diagnostic bundle.')
flags.DEFINE_float('health_loss_explosion_factor',
                   _DEFAULTS.health_loss_explosion_factor,
                   'Finite-loss explosion threshold: |loss| beyond '
                   'this multiple of the window median flags the '
                   'step bad.')
flags.DEFINE_float('health_sigma_divergence_factor',
                   _DEFAULTS.health_sigma_divergence_factor,
                   'PopArt sigma_max beyond this multiple of its '
                   'window median flags the step bad.')
flags.DEFINE_string('profile_dir', _DEFAULTS.profile_dir,
                    'Capture a jax.profiler trace of a few learner '
                    'steps into this directory.')
flags.DEFINE_integer('profile_start_step', _DEFAULTS.profile_start_step,
                     'Learner step at which the trace starts.')
flags.DEFINE_integer('profile_num_steps', _DEFAULTS.profile_num_steps,
                     'Learner steps the trace covers.')
flags.DEFINE_string('coordinator_address', _DEFAULTS.coordinator_address,
                    'jax.distributed coordinator (host:port); empty '
                    'for single-host.')
flags.DEFINE_integer('num_processes', _DEFAULTS.num_processes,
                     'Total process count for jax.distributed.')
flags.DEFINE_integer('process_id', _DEFAULTS.process_id,
                     "This process's index in [0, num_processes); -1 "
                     'defers to max(--task, 0) (the reference\'s '
                     '--task spelling).')
flags.DEFINE_enum('curriculum', _DEFAULTS.curriculum,
                  ['uniform', 'regret', 'td'],
                  'In-graph auto-curriculum over the procgen level '
                  'set (population.py): uniform keeps the reference '
                  'draw; regret prioritizes positive value loss per '
                  'level (the PLR proxy), td prioritizes |TD error|. '
                  'Sampler + score update ride INSIDE the fused '
                  'anakin step — zero host round trips per level '
                  'decision.')
flags.DEFINE_float('curriculum_temperature',
                   _DEFAULTS.curriculum_temperature,
                   'Softmax temperature over per-level scores.')
flags.DEFINE_float('curriculum_eps', _DEFAULTS.curriculum_eps,
                   'Uniform mixing floor of the curriculum sampler '
                   '(every level keeps nonzero visitation — the '
                   'staleness escape hatch).')
flags.DEFINE_float('curriculum_alpha', _DEFAULTS.curriculum_alpha,
                   'Per-level score EMA step size.')
flags.DEFINE_float('curriculum_decay', _DEFAULTS.curriculum_decay,
                   'Per-fused-step score decay for levels the batch '
                   'did not visit (stale scores lose authority).')
flags.DEFINE_integer('procgen_num_levels', _DEFAULTS.procgen_num_levels,
                     'Procgen level-set size (the curriculum\'s '
                     'support); honored by both runtimes.')
flags.DEFINE_float('procgen_wall_density', _DEFAULTS.procgen_wall_density,
                   'Bernoulli wall rate of each procgen layout; '
                   'raising it past ~0.35 makes some levels '
                   'goal-unreachable (the skewed-difficulty regime '
                   'the regret curriculum exploits).')
flags.DEFINE_string('fleet_tasks', _DEFAULTS.fleet_tasks,
                    "Heterogeneous fleet spec, e.g. "
                    "'bandit:2,gridworld:1': one fleet's actors "
                    'split across jittable suites by weight '
                    '(largest-remainder apportionment = the per-task '
                    "frame budget). '' = single-task (unchanged).")
flags.DEFINE_integer('pbt_population', _DEFAULTS.pbt_population,
                     'Minimal PBT (population.py): >= 2 trains that '
                     'many anakin learner replicas under one driver '
                     'invocation with within-suite exploit/explore '
                     'over (learning_rate, entropy_cost); 0 = off.')
flags.DEFINE_integer('pbt_round_frames', _DEFAULTS.pbt_round_frames,
                     'Frames each member trains between PBT decision '
                     'points (0 = auto: a quarter of the per-member '
                     'budget).')
flags.DEFINE_string('pbt_suites', _DEFAULTS.pbt_suites,
                    'Comma-separated jittable backends assigned '
                    "round-robin to population members; '' = the "
                    "run's own env_backend.")
flags.DEFINE_float('pbt_quantile', _DEFAULTS.pbt_quantile,
                   'Bottom/top fraction per suite for exploit '
                   'decisions (in (0, 0.5]).')
flags.DEFINE_float('pbt_perturb', _DEFAULTS.pbt_perturb,
                   'Explore step: each inherited hyper multiplies or '
                   'divides by this factor (fair coin).')
flags.DEFINE_bool('pbt_vectorized', _DEFAULTS.pbt_vectorized,
                  'Fuse the population: vmap the N members over a '
                  'leading member axis so each round trains ONE '
                  'compiled Anakin program (hypers become traced '
                  'per-member scalars; exploit is an on-device '
                  'stacked-slice copy). Single jittable suite only; '
                  'a model-axis mesh falls back to the serial loop.')
flags.DEFINE_string('compile_cache_dir', _DEFAULTS.compile_cache_dir,
                    'Persistent XLA compilation cache, armed before '
                    'the first compile. Ignored where '
                    'JAX_COMPILATION_CACHE_DIR is set (the environment '
                    "places the cache). 'auto' = <checkout>/.jax_cache "
                    'on accelerator hosts (skipped on CPU-pinned '
                    "processes); '' disables; else the cache dir "
                    'itself (shareable across runs and processes, '
                    'armed on any backend).')

FLAGS = flags.FLAGS


def config_from_flags() -> Config:
  cfg = Config()
  overrides = {}
  for field in dataclasses.fields(Config):
    if field.name in FLAGS:
      overrides[field.name] = getattr(FLAGS, field.name)
  return dataclasses.replace(cfg, **overrides)


def main(argv):
  del argv
  logging.basicConfig(
      level=logging.INFO,
      format='%(asctime)s %(name)s %(levelname)s %(message)s')
  # Before the driver/JAX imports below: the one-time fork creating
  # the forkserver (default env-process start method) must happen
  # while this process is still quiet — see runtime/py_process.py.
  from scalable_agent_tpu.runtime.py_process import warm_forkserver
  warm_forkserver()
  # Preemption safety: SIGTERM (k8s eviction, TPU-VM maintenance)
  # must not kill the process mid-step. Round 9 upgrades the response
  # from "unwind through the finally block" to a GRACEFUL DRAIN: the
  # first SIGTERM sets the drain event — driver.train stops
  # admissions, flushes in-flight unrolls through the learner, takes
  # a verified checkpoint and writes resume_manifest.json, then
  # returns cleanly (docs/RUNBOOK.md §7). A second SIGTERM (the
  # platform's kill escalation arriving before the drain finished)
  # falls back to the old raise-through-finally path; a third is
  # ignored so it cannot abort the final save. Only the train loop
  # consumes the drain event — every other mode (actor host, eval)
  # keeps the old first-SIGTERM-raises behavior, or its one
  # graceful shot would be absorbed by an event nobody reads.
  import signal
  import threading
  drain_event = threading.Event()
  drain_supported = threading.Event()

  def _terminate(signum, frame):
    if drain_supported.is_set() and not drain_event.is_set():
      drain_event.set()
      return
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    raise KeyboardInterrupt(f'signal {signum}')

  signal.signal(signal.SIGTERM, _terminate)
  # Multi-process spin-up (round 17): driver.train/evaluate own the
  # join (distributed.maybe_initialize from the config's coordinator
  # fields — idempotent, enables CPU gloo collectives before the
  # backend exists). Actor hosts deliberately DON'T join: they feed
  # the learner over TCP ingest, and joining would put their devices
  # into the training mesh.
  cfg = config_from_flags()
  if cfg.coordinator_address and cfg.job_name == 'actor':
    raise app.UsageError(
        '--job_name=actor does not join jax.distributed (actor hosts '
        'feed over --learner_address TCP ingest); drop '
        '--coordinator_address on actor hosts')
  if cfg.job_name == 'actor':
    # Actor-only host: no TPU, no learner — stream unrolls to the
    # learner's ingest server (reference ≈L625 actor loop).
    if not cfg.learner_address:
      raise app.UsageError('--job_name=actor needs --learner_address')
    if cfg.mode != 'train':
      raise app.UsageError('--job_name=actor only makes sense with '
                           '--mode=train (--mode=test runs its own '
                           'envs)')
    from scalable_agent_tpu.runtime import remote
    remote.run_remote_actor(cfg, cfg.learner_address,
                            task=max(cfg.task, 0))
    return
  from scalable_agent_tpu import driver
  if cfg.mode == 'train':
    # Both runtimes consume the drain event: the fleet loop drains
    # (flush + verified checkpoint + resume manifest); the anakin loop
    # stops cleanly at the next fused-step boundary with its tail
    # checkpoint + SLO verdict (driver.train dispatches on --runtime).
    drain_supported.set()
    run = driver.train(cfg, drain_event=drain_event)
    logging.info('training done at %d frames', run.frames)
  else:
    driver.evaluate(cfg)


if __name__ == '__main__':
  app.run(main)

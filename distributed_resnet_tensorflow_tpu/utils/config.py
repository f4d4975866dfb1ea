"""Typed configuration system.

Replaces the reference's per-entry-point ``tf.app.flags`` blocks (reference
resnet_cifar_main.py:30-88, resnet_imagenet_main.py:31-83,
resnet_cifar_eval.py:27-55 — ~25 flags redefined in every file, see SURVEY.md
§2.16) with a single set of dataclasses defined once, plus dotted-path CLI
overrides (``--train.batch_size=256``) and named presets reproducing the
reference's published configurations.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple


@dataclass
class ModelConfig:
    """Model selection. Mirrors reference HParams (resnet_model.py:36-39) plus
    the size/width axes the reference hard-coded (resnet_model.py:71-74 pins
    resnet_size=50 for both datasets)."""

    name: str = "resnet"              # resnet | logistic | vit | afmoe | sdar_moe | nemotron_h | glm4_moe_lite
    resnet_size: int = 50             # cifar: 6n+2 ∈ {20,32,44,50,56,110,...}; imagenet: 18/34/50/101/152/200
    width_multiplier: int = 1         # Wide-ResNet (e.g. 28-10 → resnet_size=28, width=10)
    num_classes: int = 10
    # bfloat16 compute with fp32 params is the TPU-native choice; the reference
    # was fp32-only (TF1.3 era).
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Cross-replica batchnorm (lax.pmean of batch moments over the data axis)
    # fixes the per-replica-BN accuracy gap the reference suffered
    # (reference README.md:38,54). Both modes supported for comparison.
    cross_replica_bn: bool = True
    bn_momentum: float = 0.997        # reference resnet_model_official.py:37
    bn_epsilon: float = 1e-5          # reference resnet_model_official.py:38
    # >1: estimate BN batch moments from the contiguous center band of H/s
    # rows instead of every position — cuts the stat-pass HBM read to 1/s
    # (ops/batch_norm.py module docstring has the measured story). 1 = exact
    # moments (default everywhere; reference numerics).
    bn_stat_subsample: int = 1
    # normalization contract (ResNet family): "batch" = reference BN
    # semantics (default); "frozen" = BN from running stats even in
    # training (trainable scale/bias, no stat passes — the fine-tune
    # contract); "group" = GroupNorm (batch-independent, stateless — the
    # BN-free training contract; docs/perf_norm_r5.md has the measured MFU
    # of all three). models/resnet.py BatchNormRelu dispatches on this.
    norm: str = "batch"
    gn_groups: int = 32               # GroupNorm group count (norm="group")
    # evaluate the ImageNet 7x7/2 stem via space-to-depth (input [N,224,224,3]
    # -> [N,115,115,12], kernel 7x7x3 -> 4x4x12): mathematically the same
    # conv, but the contraction no longer has the MXU-hostile 3-channel
    # input. Measured +2.7% img/s on RN50 bs128 (docs/perf_imagenet_r4.md);
    # parity pinned by tests/test_models.py::test_stem_space_to_depth_parity.
    stem_space_to_depth: bool = True
    # toy MLP (reference logist_model.py:10-11)
    hidden_units: int = 100
    input_size: int = 32 * 32 * 3
    # ViT family (attention-based; beyond-reference capability)
    vit_patch_size: int = 4
    vit_dim: int = 128
    vit_depth: int = 6
    vit_heads: int = 4
    # GPipe microbatches when mesh.pipeline > 1 (0 → 2 × stages)
    vit_pipeline_microbatches: int = 0
    # >1 → circular (Megatron-interleaved) schedule: v chunks per stage,
    # bubble (P-1)/(v*M+P-1); requires depth % (P*v) == 0 and M >= P
    vit_pipeline_interleave: int = 1
    # Switch MoE: >0 replaces the block MLPs with num_experts experts
    # (models/moe.py), shardable over mesh.expert
    vit_num_experts: int = 0
    vit_expert_capacity_factor: float = 1.25
    vit_moe_top_k: int = 1            # 1 = Switch; 2 = GShard-style top-2
    # auto = gather (O(N+EC)) off the expert mesh axis; hand-scheduled
    # shard_map + lax.all_to_all exchange on it (einsum fallback when the
    # token count doesn't divide over the batch x expert shards)
    vit_moe_dispatch: str = "auto"    # auto | einsum | gather | a2a
    moe_aux_weight: float = 0.01      # Switch load-balancing loss weight
    # auto = ring if mesh.sequence>1; flash on TPU at >=2048 tokens; else dense
    # (afmoe: auto = flash on TPU at any length, dense elsewhere)
    attention_impl: str = "auto"      # auto | dense | blockwise | flash | ring
    # -- causal decoder families (name in models/transformer.FAMILIES:
    # CausalDecoder + models/moe.py DroplessMoe; what a family fixes beyond
    # these sizes (norms, gate, where rotary runs, router, objective) is
    # that table and no field here). Keys are
    # the published config.json's where it has one; the sequence length is
    # data.seq_len.
    hidden_size: int = 256
    num_attention_heads: int = 8      # query heads ...
    num_key_value_heads: int = 2      # ... over this many key/value heads
    head_dim: int = 32
    # one entry a layer: sliding_attention (window + rotary positions) |
    # full_attention (causal; a positional term where the family says so);
    # a one-mixer family's (nemotron_h) also mamba | moe
    layer_types: Tuple[str, ...] = ("sliding_attention", "full_attention")
    sliding_window: int = 64
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_dense_layers: int = 1         # leading layers with a dense SwiGLU
    intermediate_size: int = 512      # ... of this width
    moe_intermediate_size: int = 128  # a routed (and the shared) expert's
    num_experts: int = 16             # the router's width: ALL the experts
    # [lo, hi) of the published experts live on this chip; the layer
    # routes over all of them and computes its own experts' part only
    experts_held: Tuple[int, int] = (0, 16)
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    # the shared expert's width where it is not num_shared_experts x
    # moe_intermediate_size (0)
    moe_shared_expert_intermediate_size: int = 0
    route_scale: float = 1.0
    # rate of the router-bias rule (arXiv:2408.15664): after each update
    # b += coeff * sign(mean(load) - load); no auxiliary loss term
    load_balance_coeff: float = 0.001
    mup_enabled: bool = True          # embedding output x sqrt(hidden_size)
    vocab_held: int = 512             # vocabulary rows held here (untied head)
    # block diffusion (name="sdar_moe", arXiv:2503.09573): ids to a
    # diffusion block; the held row that stands for a masked id (the data
    # draws ids below it); the least noise level of a block
    block_length: int = 4
    mask_token_held: int = 511
    noise_eps: float = 1e-3
    # the Mamba-2 mixer (layer kind "mamba", models/mamba.py): heads x head
    # size channels, B and C in n_groups groups of ssm_state_size, a causal
    # convolution conv_kernel wide, the chunked scan in chunks of chunk_size
    mamba_num_heads: int = 8
    mamba_head_dim: int = 16
    n_groups: int = 2
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    # the mixer's start: Δ log-uniform on [time_step_min, time_step_max],
    # floored at time_step_floor (dt_bias is its softplus inverse)
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # latent attention (a family whose FAMILIES row says attention="latent"):
    # queries through a q_lora_rank latent, keys and values through ONE
    # kv_lora_rank latent beside ONE qk_rope_head_dim rotary key that every
    # head shares; a head's query and key are qk_nope_head_dim +
    # qk_rope_head_dim wide (rotary on the last qk_rope_head_dim), its value
    # v_head_dim; 0 (no width) is refused by LatentAttention, so a latent
    # preset states every one
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # multi-token prediction (DeepSeek-V3, arXiv:2412.19437 section 2.2):
    # blocks after the last layer, the k-th predicting the id k + 1 further
    # on; their mean loss enters the objective times mtp_loss_weight
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3


@dataclass
class DataConfig:
    """Input pipeline. Covers reference cifar_input.py + the tf.data paths
    (SURVEY.md §2.4-2.7)."""

    dataset: str = "cifar10"          # cifar10 | cifar100 | imagenet | synthetic
    data_dir: str = ""
    image_size: int = 32              # 32 cifar, 224 imagenet (reference resnet_imagenet_main.py image_size flag)
    shuffle_buffer: int = 50000       # full-epoch CIFAR shuffle (reference resnet_cifar_main.py:221)
    prefetch_batches: int = 2         # reference prefetches 2*bs samples (resnet_cifar_main.py:232)
    # imagenet decode THREAD pool width; -1 = auto (min(8, host cores,
    # floor 4) — data.resolve_decode_workers, the single resolution point)
    num_parallel_calls: int = -1
    use_native_loader: bool = False   # C++ threaded loader (native/)
    # >0: decode in worker PROCESSES instead of threads (imagenet) — full
    # GIL independence at the price of queue pickling; the measured
    # thread-vs-process scaling story is docs/input_scaling_r4.json.
    # -1 = auto: min(8, host cores) processes on hosts with >2 cores, else
    # 0 (threads — a process pool below that only adds pickling); 0 =
    # explicit threads-only. Explicit settings always win over auto.
    decode_processes: int = -1
    # -- data echoing + decoded-sample cache (data/echo.py) --------------
    # >1: each decoded sample feeds this many training batches overall —
    # samples enter a bounded host cache of decoded uint8 crops and every
    # emitted batch is a fresh seeded reshuffle of the cache, so one JPEG
    # decode feeds echo_factor steps (arXiv:1811.05233's input-bound
    # regime). Train-mode streams only; 1 = off
    echo_factor: int = 1
    # byte bound on the decoded-sample cache; overflowing samples are
    # evicted oldest-first (counted — {"event": "input_echo"} rows) even
    # if they still had echo uses left: the memory bound wins
    echo_cache_mb: float = 256.0
    # >1: re-dispatch each staged device-resident batch group this many
    # times before drawing the next — ONE host→device transfer feeds
    # echo_transfer × steps_per_loop optimizer steps. Each reuse
    # reshuffles the group's batch composition on device (seeded
    # permutation inside the jitted multi-step) and re-draws the device
    # augmentation (step-keyed RNG), so echoed steps stay diverse. The
    # lever past the H2D link ceiling (BENCH_r05: 49 MB/s moves only
    # ~326 uint8 img/s); composes with echo_factor (total echo =
    # echo_factor × echo_transfer decodes saved per step). 1 = off
    echo_transfer: int = 1
    # imagenet on-device augmentation: random-crop jitter padding in
    # pixels (ops/augment.imagenet_train_augment). 0 = flip + VGG
    # standardize only (reference-faithful distribution: the host decode
    # keeps its random resize/crop, the device takes over the flip and
    # the float pass); >0 adds a CIFAR-style pad/crop jitter so echoed
    # appearances of one decoded crop also differ spatially
    augment_pad: int = 0
    # train-time device-side input work (ops/augment.py), auto = on iff TPU.
    # cifar*: crop/flip/standardize inside the jitted step; imagenet: the
    # VGG standardize only (iterator then ships raw uint8 crops) — see
    # data/__init__.py device_augment_enabled, the single source of truth.
    device_augment: str = "auto"      # auto | on | off
    # whole dataset resident in HBM, batches gathered on device, host ships
    # only indices (data/device_dataset.py) — auto = on iff TPU,
    # single-process, CIFAR-scale. Implies device_augment.
    device_dataset: str = "auto"      # auto | on | off
    # -- overlapped staging (docs/input_pipeline.md) --------------------
    # coalesce each batch into one contiguous staging buffer and issue a
    # single device_put per batch (parallel/sharding.CoalescedStager);
    # "off" falls back to per-leaf device_put. auto = on iff running on a
    # real accelerator (per-call transfer overhead is what it amortizes)
    coalesced_transfer: str = "auto"  # auto | on | off
    # device-resident batches the dedicated transfer thread keeps queued
    # ahead of dispatch (data/device_prefetch.device_prefetch). Raised
    # 2 → 3 with the double-buffered transfer issue (round 9): the staging
    # thread now packs batch N+1 while N's transfer is still in flight
    transfer_depth: int = 3
    # reused host staging buffers; must cover the transfers in flight
    # (transfer_depth + the two behind the double-buffered issue point)
    staging_ring: int = 6
    # tolerate this many corrupt/truncated TFRecord records per process
    # before raising (each skip is a counted warning + a
    # {"event": "corrupt_record"} metrics row — data/tfrecord.py); 0 =
    # strict, any corruption raises immediately. Tolerant BY DESIGN: a
    # multi-day run must not die on one rotten byte, and mass corruption
    # (a storage incident) still raises once the budget is spent — set 0
    # to restore the old fail-fast behavior. Truncation is always
    # detected; CRC-detectable corruption (flipped payload bytes) only
    # with verify_crc=True below
    max_corrupt_records: int = 10
    # verify TFRecord CRCs on the python reader path. Costs a pure-python
    # CRC32C pass over every record — reserve for suspect storage; off,
    # only truncated records/headers are detected (and skipped/counted
    # under max_corrupt_records)
    verify_crc: bool = False
    # eval pipeline
    eval_batch_size: int = 100        # reference resnet_cifar_eval.py batch of 100
    # token models (data/tokens.py). dataset="tokens": a batch is
    # {"tokens": int32 [B, seq_len + 1]}, inputs and next-token targets;
    # dataset="blockdiff_tokens": {"tokens": int32 [B, seq_len], "masked":
    # uint8 [B, seq_len], "t": float32 [B, seq_len / model.block_length]}
    seq_len: int = 128


@dataclass
class OptimizerConfig:
    """Optimizer + LR schedule. Reference: SGD / momentum-0.9
    (resnet_model.py:96-99), step-piecewise LR (resnet_cifar_main.py:298-307),
    warmup+piecewise for ImageNet (resnet_imagenet_main.py:236-247).
    Adds LARS for large-batch (bs=32k) scaling."""

    name: str = "momentum"            # sgd | momentum | adam | adamw | lars | lamb
    momentum: float = 0.9
    learning_rate: float = 0.1
    weight_decay: float = 2e-4        # cifar train value (reference resnet_cifar_main.py:99); imagenet: 1e-4
    # True = reference-faithful L2 over ALL trainables incl. BN scale/bias
    # (reference resnet_model.py:85-86); False (default) = kernels only
    decay_all_params: bool = False
    # -- ZeRO-1 sharded weight update (parallel/sharding.py rule table +
    # train/loop.py; arXiv:2004.13336) ---------------------------------
    # shard the optimizer state and the weight update across the `data`
    # mesh axis: gradients reduce-scatter into each replica's optimizer
    # shard, the update runs on 1/N of the state per replica, and the
    # parameter updates all-gather back. auto = on iff the run has >1
    # process (where per-replica optimizer memory is the binding
    # constraint); on = force (raises the
    # unsupported reason outside the envelope); off = the replicated
    # update — the bit-identical exactness oracle the ZeRO-1 path is
    # tested against
    zero1: str = "off"                # auto | on | off
    # leaves smaller than this many ELEMENTS stay replicated under ZeRO-1
    # (a sharded BN-scale moment buys nothing and costs a collective);
    # counted in the zero1 partition report
    zero1_min_size: int = 2048
    # schedule: piecewise | warmup_piecewise | cosine | warmup_poly | constant
    schedule: str = "piecewise"
    boundaries: Tuple[int, ...] = (40000, 60000, 80000)      # reference resnet_cifar_main.py:298-307
    values: Tuple[float, ...] = (0.1, 0.01, 0.001, 0.0001)
    warmup_steps: int = 0             # imagenet recipe: 6240 (reference resnet_imagenet_main.py:236-247)
    warmup_start: float = 0.1
    total_steps: int = 100000
    label_smoothing: float = 0.0
    grad_clip_norm: float = 0.0       # 0 = off
    # LARS
    lars_trust_coefficient: float = 0.001
    lars_eps: float = 0.0


@dataclass
class MeshConfig:
    """Device mesh. Replaces the reference's two comm backends (grpc PS +
    Horovod ring, SURVEY.md §2.8-2.9) with named mesh axes. Values of 0/1
    collapse the axis. -1 on exactly one axis means "all remaining devices"."""

    data: int = -1                    # data parallel (the reference's only axis)
    fsdp: int = 1                     # ZeRO-like param/optimizer sharding
    tensor: int = 1                   # tensor parallelism
    pipeline: int = 1                 # pipeline parallelism
    sequence: int = 1                 # sequence/context parallelism (ring attention)
    expert: int = 1                   # expert parallelism
    # multi-host
    coordinator_address: str = ""     # empty = single process
    num_processes: int = 1
    process_id: int = 0


@dataclass
class TrainConfig:
    batch_size: int = 128             # GLOBAL batch (reference global bs semantics, README.md:41-42)
    train_steps: int = 100000
    eval_every_steps: int = 0         # 0 = no in-loop eval
    log_every_steps: int = 20         # reference LoggingTensorHook cadence (resnet_cifar_main.py:280-285)
    summary_every_steps: int = 100    # reference SummarySaverHook (resnet_cifar_main.py:274-278)
    seed: int = 0
    # gradient accumulation (for large global batches on few chips)
    grad_accum_steps: int = 1
    remat: bool = False               # jax.checkpoint the block stack
    # fuse K optimizer steps into one XLA dispatch (lax.scan over K batches).
    # Amortizes host dispatch — the TPU analog of TPUEstimator's
    # iterations_per_loop. Hooks/logging fire at loop boundaries.
    steps_per_loop: int = 1
    # unroll factor for the steps_per_loop lax.scan. The while-loop form
    # double-buffers the ~430-leaf TrainState carry on TPU (~1.1k tiny
    # async copies/step, measured 2.5 ms/step on ImageNet RN50 bs128 —
    # docs/perf_imagenet_r4.md); full unroll (scan_unroll >= steps_per_loop)
    # removes the loop so the state updates in place. Cost: program size and
    # compile time scale with the factor.
    scan_unroll: int = 1
    # Pallas fused softmax-xent kernel in the train loss (replaces the
    # reference's fused TF op, resnet_model.py:78-80):
    # auto = on iff TPU | on | interpret (CPU tests) | off
    fused_xent: str = "auto"
    # -- mixed-precision training policy (parallel/precision.py;
    # docs/precision.md) ------------------------------------------------
    # "bf16": activations/matmuls compute in bfloat16 with float32 MASTER
    # weights and f32 BN-moment/softmax/loss accumulations — the model is
    # built with a bf16 compute dtype (overriding model.compute_dtype;
    # the policy cast wraps model apply), gradients and the whole
    # optimizer update stay f32, and checkpoints always persist the f32
    # masters so save/restore and serve hot-swap are policy-agnostic.
    # "off" (default): the legacy model.compute_dtype contract, BIT-
    # identical to the pre-policy step — the exactness oracle the cast
    # path is pinned against. fp16 is refused here (needs loss scaling).
    precision: str = "off"            # off | bf16
    # print MFU in the logging hook (XLA cost-analysis FLOPs / peak)
    log_mfu: bool = False


@dataclass
class CheckpointConfig:
    """Reference: chief-only time-based ckpt every 60s via
    MonitoredTrainingSession (resnet_cifar_main.py:327-329), auto-resume."""

    directory: str = ""
    save_every_steps: int = 1000
    save_every_secs: float = 60.0     # time-based like the reference; 0 = off
    max_to_keep: int = 5
    async_save: bool = True
    resume: bool = True               # auto-resume from latest
    # -- per-host SHARDED checkpoints (checkpoint/shards.py) -------------
    # each host stages + fsyncs only the state shards its own devices
    # address (the ZeRO-1 optimizer shard, fsdp param shards) plus a
    # chief-written base of the replicated leaves, all under the existing
    # manifest/commit protocol; the multi-process finalize coordinates
    # over marker FILES on the shared directory — no collectives on the
    # writer thread, so multi-process saves can finally run async.
    # Restore re-assembles leaves from whatever host count wrote them and
    # re-shards into the live state's rule-table layout. auto = on iff
    # the run has >1 process; off = the single-payload orbax layout
    sharded: str = "auto"             # auto | on | off
    # how long a sharded save's finalize may wait on peer-host shard
    # markers (and peers on the chief's commit) before failing the save
    finalize_timeout_secs: float = 300.0


@dataclass
class WatchdogConfig:
    """Distributed health watchdog (resilience/watchdog.py +
    resilience/heartbeat.py): per-process heartbeat daemon + detection of
    dead peers, hung steps, and stragglers, with coordinated teardown
    (graceful stop when peers respond, hard exit 75 when the step loop is
    wedged in a collective). docs/resilience.md has the full story."""

    # auto = on iff the run has >1 process (single-process runs have no
    # peers to watch and no collective to hang in)
    enabled: str = "auto"             # auto | on | off
    # heartbeat publish cadence AND watchdog poll cadence
    interval_secs: float = 1.0
    # a peer whose latest beat is older than this is declared lost
    peer_timeout_secs: float = 20.0
    # hang deadline = max(min_step_timeout_secs,
    #                     step_timeout_scale * rolling per-step-time EWMA)
    step_timeout_scale: float = 10.0
    min_step_timeout_secs: float = 120.0
    # window between requesting a graceful coordinated stop and hard
    # os._exit(75) when the main thread never reaches a stop poll
    grace_secs: float = 10.0
    # straggler accounting window (also the heartbeat/straggler
    # metrics.jsonl export cadence)
    straggler_window_secs: float = 30.0
    # flag a host whose step rate is slower than the median by this factor
    straggler_ratio: float = 1.5
    # beat exchange directory; empty = <log_root>/heartbeats (must be on a
    # filesystem all processes share, like the checkpoint dir). A
    # standalone mode=eval job always gets an "eval"-scoped subdir (of
    # this or of log_root) — its own jax world must not impersonate
    # trainer process 0
    heartbeat_dir: str = ""


@dataclass
class ElasticConfig:
    """Elastic mesh (resilience/elastic.py; docs/resilience.md): on a
    peer-loss verdict the survivors reshard into a smaller mesh
    GENERATION and keep training from the last committed checkpoint
    instead of exiting 75 for a full SLURM requeue; a respawned/replaced
    peer grows the next generation back. 75 remains the FALLBACK when a
    reshard is impossible (chief lost, fewer than min_hosts survivors,
    barrier timeout, max_generations exhausted)."""

    # off by default: the exit-75 requeue contract stays the baseline
    # behavior; "on" requires >1 process and the file watchdog transport
    enabled: str = "off"              # on | off
    # what happens to the global batch when the host count changes:
    #   per_host    — keep each host's per-host batch; the global batch
    #                 scales with the generation's host count (LR is NOT
    #                 rescaled — deliberate, documented)
    #   keep_global — keep the ORIGINAL global batch when it divides the
    #                 new batch-shard count, else fall back to per_host
    #                 with a loud warning
    batch_policy: str = "per_host"    # per_host | keep_global
    # below this many survivors, give up and exit 75 (requeue)
    min_hosts: int = 2
    # membership must be stable this long before the chief commits a
    # generation (absorbs several near-simultaneous failures into ONE
    # reshard instead of a cascade)
    settle_secs: float = 2.0
    # give up on the join barrier (→ exit 75) after this long
    barrier_timeout_secs: float = 60.0
    # bound on one whole transition (barrier + teardown + re-init +
    # restore + rebuild) — ALSO how long the watchdog defers its
    # peer-lost hard-exit while this process can still reshard
    # (resilience/watchdog.py escalation fork)
    reshard_timeout_secs: float = 180.0
    # how long a respawned/replacement peer waits for the live fleet to
    # notice its join and commit the grown generation before giving up
    # with exit 75 (the fleet only polls between steps and may be mid-
    # save — patient by default)
    rejoin_timeout_secs: float = 600.0
    # how long the abandoned distributed-client shutdown thread gets
    # before the survivor proceeds without it
    teardown_timeout_secs: float = 5.0
    # join-file poll cadence inside the barrier; also the throttle for the
    # chief's between-steps pending-join (grow) check
    poll_secs: float = 0.5
    # generation g re-initializes at coordinator port base + g * stride
    # (parallel/distributed.py elastic_coordinator)
    port_stride: int = 7
    # hard cap on transitions in one process lifetime (0 = unlimited);
    # a flapping host cannot thrash the job forever — past the cap the
    # next verdict falls back to exit 75
    max_generations: int = 8
    # barrier/membership state directory; empty = <log_root>/elastic
    # (must be on the shared filesystem, like heartbeats)
    state_dir: str = ""


@dataclass
class ResilienceConfig:
    """Fault-tolerance knobs (resilience/ subsystem; docs/resilience.md).
    The reference had none of this — failure handling was "SLURM restarts
    the job" (SURVEY.md §4.4)."""

    # SIGTERM/SIGINT → finish the step, commit a checkpoint, exit with the
    # resumable code (75) so launchers requeue instead of failing
    handle_signals: bool = True
    # > 0: stop resumable after this many seconds even without a signal —
    # maintenance-window / max-walltime preemption (set it slightly under
    # the SLURM time limit so the final checkpoint beats the SIGKILL)
    deadline_secs: float = 0.0
    # NaN/Inf sentinel: on non-finite loss/grad-norm, roll back to the last
    # good checkpoint, re-seed the data stream, retry with the LR scaled by
    # backoff**strikes; give up loudly after max_strikes rollbacks.
    # 0 strikes = detection only (the guard raises, run dies — old behavior)
    nan_max_strikes: int = 3
    nan_lr_backoff: float = 0.5
    # guard cadence; 0 = follow train.log_every_steps. Keep at or below the
    # checkpoint cadence, else a save can land between blow-up and detection
    nan_check_every_steps: int = 0
    # verify checkpoint manifests (size + sha256 per file) before restoring;
    # damaged checkpoints are skipped in favor of the newest valid one
    verify_on_restore: bool = True
    # bounded-retry policy for checkpoint I/O (resilience/retry.py)
    io_retries: int = 3
    # distributed health watchdog knobs (resilience.watchdog.*)
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    # elastic mesh shrink/grow knobs (resilience.elastic.*)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)


@dataclass
class AnalysisConfig:
    """Static-analysis / debug instrumentation (analysis/ subsystem;
    docs/static_analysis.md). The ``check`` gate itself is config-free —
    these knobs control the RUNTIME aids."""

    # opt-in: raise at the call site the moment a second thread launches a
    # multi-device XLA execution (the cross-thread dispatch deadlock class,
    # docs/input_pipeline.md threading model) instead of wedging the next
    # collective. Costs a lock per dispatch — debug runs, not production.
    dispatch_sanitizer: bool = False


@dataclass
class TelemetryConfig:
    """Flight recorder + goodput accounting (telemetry/;
    docs/observability.md). The reference's only observability was stdout
    logs and TensorBoard scalars (SURVEY.md §2.15); these knobs control the
    span tracer, its anomaly-triggered dumps, and the goodput export."""

    # record spans into the bounded in-memory ring (telemetry/tracer.py).
    # Measured negligible (<2% on the CIFAR headline, the acceptance
    # bar), so on by default; off = every span is a shared no-op.
    enabled: bool = True
    # ring capacity in span events — the flight recorder's memory bound
    # (~100 bytes/event; 65536 ≈ the last few minutes of a busy run)
    ring_events: int = 65536
    # where trace.json dumps land; empty = <log_root>/telemetry
    trace_dir: str = ""
    # goodput metrics-row cadence in steps; 0 = ride
    # train.summary_every_steps
    goodput_every_steps: int = 0
    # when a watchdog anomaly fires, also bracket an on-demand
    # jax.profiler window (utils/profiling.trace_window) of profile_secs
    # into <trace_dir>/profile — device-side visibility at the price of
    # profiler overhead during the incident; once per process
    profile_on_anomaly: bool = False
    profile_secs: float = 5.0
    # metrics.jsonl size-triggered rotation (utils/metrics.MetricsWriter):
    # rotate past this many MB, keep this many rotated segments. A
    # week-long serve/monitor run must not fill the disk. 0 MB = unbounded
    metrics_max_mb: float = 256.0
    metrics_max_segments: int = 4
    # -- device-memory telemetry (telemetry/memory.py) -------------------
    # sample per-device live-array bytes (+ allocator stats where the
    # backend reports them), host RSS, echo-cache and staging-ring
    # occupancy into {"event": "memory"} rows at the summary cadence
    # (train loop) and the serve report cadence. `main.py monitor` rolls
    # the per-host HBM watermark up. Off = no memory rows.
    memory: bool = True
    # -- perf-anomaly sentinel (resilience/watchdog.py) ------------------
    # online step-time outlier detection over a rolling median+MAD
    # window: a slow-but-alive step (no hang, no teardown) triggers a
    # {"event": "perf_anomaly"} row + the flight-recorder dump — today's
    # 2×-slow step should page like a hang does, not wait for the wall
    # clock. Rides the watchdog's detection thread, so it arms with the
    # watchdog (resilience.watchdog.enabled).
    anomaly_detection: bool = True
    # rolling window of per-step-time samples the median/MAD come from
    anomaly_window: int = 32
    # minimum samples before the detector arms (a cold window's MAD is
    # noise)
    anomaly_min_samples: int = 16
    # outlier threshold: median + max(anomaly_mad_k × MAD,
    # (anomaly_min_ratio − 1) × median). The MAD term adapts to the
    # run's jitter; the ratio floor keeps an ultra-steady run (MAD ~ 0)
    # from flagging micro-hiccups.
    anomaly_mad_k: float = 6.0
    anomaly_min_ratio: float = 1.5
    # minimum gap between fired anomalies (a persistently slow host must
    # not dump a trace per detection tick); the episode also re-arms only
    # after a healthy sample
    anomaly_cooldown_secs: float = 60.0


@dataclass
class EvalConfig:
    """Standalone polling evaluator (reference resnet_cifar_eval.py:85-141)."""

    # reference eval_batch_count flag (=50, i.e. 50×100 CIFAR images).
    # For the full ImageNet validation set size it to cover all 50,000
    # images: ceil(50000 / data.eval_batch_size) (=500 at the default 100);
    # the iterator masks the final partial batch, and a larger count just
    # stops at stream exhaustion, so overshooting is safe single-process.
    eval_batch_count: int = 50
    eval_once: bool = False
    poll_interval_secs: float = 60.0  # reference sleeps 60s between polls
    eval_dir: str = ""
    # a polling evaluator skips damaged/vanished checkpoints; this bounds
    # how many it may skip IN A ROW before exiting nonzero — a persistently
    # broken checkpoint stream must page someone, not spin forever
    max_consecutive_failures: int = 5


@dataclass
class ServeConfig:
    """AOT-compiled batched inference server (serve/; docs/serving.md).
    Surfaced as ``main.py serve``; the reference had no serving story at
    all — checkpoints were the end of the line (ROADMAP open item 3)."""

    # request-batch cap; 0 = data.eval_batch_size. Buckets are powers of
    # two (in multiples of Trainer.eval_pad_multiple) up to this cap
    max_batch: int = 0
    # how long the batcher holds the FIRST queued request to coalesce more
    # into a bigger bucket — the p50-latency vs throughput knob (0 =
    # dispatch immediately, smallest bucket)
    max_queue_delay_ms: float = 5.0
    # hot-swap poll cadence (jittered ±50%): how often the background swap
    # thread looks for a newer committed checkpoint
    poll_interval_secs: float = 5.0
    # AOT-compile every bucket at startup so the first request never pays
    # a compile; off = compile lazily on first use (counted + warned)
    warm_buckets: bool = True
    # -- open-loop synthetic load generator (serve/loadgen.py) ------------
    # main.py serve drives it when load_qps > 0, then prints a JSON report
    # and exits; load_qps = 0 serves until SIGINT/SIGTERM
    load_qps: float = 0.0
    load_duration_secs: float = 10.0
    load_seed: int = 0
    # after the load completes, keep serving (idle) until a hot swap has
    # landed or this many extra seconds pass — scripts/serve_smoke.sh's
    # determinism knob; 0 = exit right after the load
    wait_for_swap_secs: float = 0.0
    # reduced-precision serving variants (docs/precision.md): compile-
    # cache buckets become (batch, variant) and every listed variant gets
    # its own weight copy + AOT programs — "bf16" serves from bf16-cast
    # weights through a bf16-compute predict step (about half the weight
    # HBM and MXU-rate matmuls per replica); "int8" is WEIGHT-ONLY
    # quantization (per-output-channel scales, ¼ the kernel HBM,
    # f32 compute over dequantized weights — the parity bound vs the f32
    # variant is pinned in tests/test_precision.py). The FIRST entry is
    # the default a variant-less request is served from; hot swaps
    # rebuild every variant from the new f32 masters. Checkpoints are
    # untouched (serving quantizes/casts at swap time, never at rest).
    variants: Tuple[str, ...] = ("f32",)
    # -- fleet-replica identity (serve/fleet.py spawns replicas with
    # these set; standalone `main.py serve` leaves them off) -------------
    # replica id within a routed fleet: >= 0 moves the metrics stream /
    # READY marker to <log_root>/serve-r<id> and publishes heartbeats
    # into <log_root>/heartbeats-serve under this process_id
    replica_id: int = -1
    # TCP request port (127.0.0.1): > 0 starts the replica listener
    # (serve/wire.py ReplicaListener) so a router can forward requests
    listen_port: int = 0
    # gate hot swaps on the router's per-replica control file
    # (<serve dir>/SWAP_CONTROL.json {"target_step": N}): the swapper
    # only moves to the pinned step — forward for a canary/promote,
    # BACKWARD for a rollback — instead of chasing the newest commit,
    # and HOLDS while no control file exists (an unpinned gated replica
    # must not leak an unvalidated checkpoint past the canary).
    swap_gate: bool = False


@dataclass
class RouteConfig:
    """Serving-fleet front door (serve/router.py + serve/fleet.py;
    ``main.py route``, docs/serving.md fleet section): health-routed
    replicas, watchdog-driven replace, canary rollout with auto-rollback,
    SLO-aware degradation."""

    # -- fleet shape -----------------------------------------------------
    replicas: int = 3
    # first replica's TCP port; replica i listens on base_port + i.
    # 0 = pick free ports at spawn time
    base_port: int = 0
    # forwarding worker threads (each blocks on one attempt at a time,
    # so this bounds the router's concurrent in-flight attempts)
    workers: int = 4
    # -- request path ----------------------------------------------------
    # client-visible deadline: past it the request fails loudly
    request_timeout_ms: float = 10000.0
    # per-attempt transport deadline (connect + send + response)
    attempt_timeout_ms: float = 4000.0
    # hedge: a duplicate attempt goes to ANOTHER replica after this long
    # without a response — requests in flight on a dying replica land on
    # a survivor instead of waiting out attempt_timeout_ms
    hedge_ms: float = 400.0
    # total attempts per request (first + hedges + retries)
    max_attempts: int = 3
    # -- health ----------------------------------------------------------
    health_interval_secs: float = 1.0
    # heartbeat age past which a replica is declared dead (its publisher
    # daemon beats ~1/s even when the dispatch thread is stuck)
    beat_stale_secs: float = 15.0
    # consecutive transport failures: suspect (deprioritized), then dead
    # (drained + replaced by the fleet supervisor)
    suspect_after_failures: int = 2
    dead_after_failures: int = 5
    # route summary-row cadence ({"event": "route"})
    row_interval_secs: float = 5.0
    # -- canary rollout --------------------------------------------------
    # fraction of the fleet a new checkpoint is published to first
    # (ceil(fraction × replicas), never the whole fleet when N > 1)
    canary_fraction: float = 0.34
    # measurement window after every canary replica confirms the step
    canary_window_secs: float = 15.0
    # minimum responses per arm before a promote/rollback verdict
    canary_min_samples: int = 20
    # rollback when canary p99 / control p99 exceeds this
    canary_p99_ratio: float = 2.0
    # rollback when the canary arm's mean top-1 softmax confidence (the
    # accuracy proxy) drops below the control arm's by more than this
    canary_conf_drop: float = 0.2
    # rollback when the canary replicas never confirm the step
    canary_confirm_secs: float = 60.0
    # -- SLO-aware degradation / load shedding ---------------------------
    # p99 above this marks a replica degraded (slo_pressure); 0 = off
    slo_p99_ms: float = 0.0
    # estimated queue delay above this reroutes default-variant traffic
    # to degrade_variant (0 = off); above shed_queue_ms requests are
    # refused with the shed verdict instead of queueing unbounded
    degrade_queue_ms: float = 0.0
    degrade_variant: str = ""
    shed_queue_ms: float = 2000.0
    # -- fleet supervisor (watchdog replace) -----------------------------
    watch_interval_secs: float = 1.0
    # drain + SIGTERM grace before SIGKILL on a replace
    replica_grace_secs: float = 10.0
    # respawn → READY deadline before the replace is abandoned
    warm_timeout_secs: float = 240.0
    # total replaces before the supervisor stops trying (crash-loop cap)
    max_replaces: int = 8
    # -- open-loop load generator (mirrors serve.load_*) -----------------
    load_qps: float = 0.0
    load_duration_secs: float = 10.0
    load_seed: int = 0
    # arrival-schedule shape: steady | diurnal | burst | spike
    # (serve/loadgen.py — all coordinated-omission-free)
    load_shape: str = "steady"


@dataclass
class ExperimentConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    eval: EvalConfig = field(default_factory=EvalConfig)
    analysis: AnalysisConfig = field(default_factory=AnalysisConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    route: RouteConfig = field(default_factory=RouteConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    mode: str = "train"        # train | eval | train_and_eval | serve | route
    log_root: str = "/tmp/drt_tpu"    # reference log_root flag

    # ---- serialization ----
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentConfig":
        cfg = cls()
        _apply_dict(cfg, d)
        return cfg

    def override(self, dotted: str, value: Any) -> None:
        """Apply one dotted-path override, e.g. ("train.batch_size", 256)."""
        obj = self
        parts = dotted.split(".")
        for p in parts:
            if not hasattr(obj, p):
                raise KeyError(f"unknown config key: {dotted}")
            parent, obj = obj, getattr(obj, p)
        setattr(parent, parts[-1], _coerce(value, obj))


def _coerce(value: Any, template: Any) -> Any:
    if isinstance(value, str):
        if isinstance(template, bool):
            return value.lower() in ("1", "true", "yes", "on")
        if isinstance(template, int) and not isinstance(template, bool):
            return int(value)
        if isinstance(template, float):
            return float(value)
        if isinstance(template, tuple):
            if not value.strip():
                return ()
            elems = [v.strip() for v in value.split(",") if v.strip()]
            # element type follows the template's first element; string
            # tuples (serve.variants) pass through unconverted
            if template and isinstance(template[0], float):
                et = float
            elif template and isinstance(template[0], str):
                et = str
            else:
                et = int
            return tuple(et(e) for e in elems)
    if isinstance(template, tuple) and isinstance(value, list):
        return tuple(value)
    return value


def _apply_dict(obj: Any, d: dict) -> None:
    for k, v in d.items():
        if not hasattr(obj, k):
            raise KeyError(f"unknown config key: {k}")
        cur = getattr(obj, k)
        if dataclasses.is_dataclass(cur) and isinstance(v, dict):
            _apply_dict(cur, v)
        else:
            setattr(obj, k, _coerce(v, cur))


# ---------------------------------------------------------------------------
# Presets: named configs reproducing the reference's published runs
# (BASELINE.md table; reference README.md:22-52).
# ---------------------------------------------------------------------------

def _cifar10_resnet50() -> ExperimentConfig:
    """Reference flagship: CIFAR-10 ResNet-50, gbs=128, piecewise LR
    (README.md:28-30 — 93.6% top-1 @ ~80k steps)."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(resnet_size=50, num_classes=10)
    cfg.data = DataConfig(dataset="cifar10", image_size=32)
    cfg.optimizer = OptimizerConfig(
        name="momentum", learning_rate=0.1, weight_decay=2e-4,
        schedule="piecewise", boundaries=(40000, 60000, 80000),
        values=(0.1, 0.01, 0.001, 0.0001), total_steps=100000)
    cfg.train = TrainConfig(batch_size=128, train_steps=100000)
    return cfg


def _cifar10_resnet50_bs512() -> ExperimentConfig:
    """Throughput variant of the flagship: gbs=512 is the measured
    single-chip optimum (+19% img/s over the faithful gbs=128 recipe,
    docs/perf_cifar_r5.md). LR and boundaries follow the linear-scaling
    rule (×4 with 4× fewer steps) so the epoch budget matches the
    reference recipe; the gbs=128 preset remains the accuracy-replay
    default."""
    cfg = _cifar10_resnet50()
    cfg.train.batch_size = 512
    cfg.train.train_steps = 25000
    cfg.optimizer = OptimizerConfig(
        name="momentum", learning_rate=0.4, weight_decay=2e-4,
        schedule="warmup_piecewise", warmup_steps=1000, warmup_start=0.1,
        boundaries=(10000, 15000, 20000),
        values=(0.4, 0.04, 0.004, 0.0004), total_steps=25000)
    return cfg


def _cifar100_wrn2810() -> ExperimentConfig:
    """Wide-ResNet-28-10 on CIFAR-100 (BASELINE.json config 4; exercises the
    width/depth generalization of reference resnet_model_official.py:217-278)."""
    cfg = _cifar10_resnet50()
    cfg.model = ModelConfig(resnet_size=28, width_multiplier=10, num_classes=100)
    cfg.data = DataConfig(dataset="cifar100", image_size=32)
    cfg.optimizer.weight_decay = 5e-4
    return cfg


def _imagenet_resnet50() -> ExperimentConfig:
    """ImageNet ResNet-50 gbs=1024, Intel-Caffe 8-node recipe the reference
    used (resnet_imagenet_main.py:236-247; README.md:42)."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(resnet_size=50, num_classes=1001)
    cfg.data = DataConfig(dataset="imagenet", image_size=224)
    cfg.optimizer = OptimizerConfig(
        name="momentum", learning_rate=0.4, weight_decay=1e-4,
        schedule="warmup_piecewise", warmup_steps=6240, warmup_start=0.1,
        boundaries=(37440, 74880, 99840),
        values=(0.4, 0.04, 0.004, 0.0004), total_steps=112640)
    cfg.train = TrainConfig(batch_size=1024, train_steps=112640,
                            log_every_steps=40)
    cfg.checkpoint.save_every_secs = 600.0  # imagenet default cadence (SURVEY §2.14)
    return cfg


def _imagenet_resnet50_lars32k() -> ExperimentConfig:
    """Large-batch: bs=32k + LARS (BASELINE.json config 5). ZeRO-1 resolves
    on under multi-process (auto): at this scale the per-replica optimizer
    state, not FLOPs, caps what fits (arXiv:2004.13336)."""
    cfg = _imagenet_resnet50()
    cfg.optimizer = OptimizerConfig(
        name="lars", learning_rate=29.0, weight_decay=1e-4,
        schedule="cosine", zero1="auto",
        warmup_steps=800, total_steps=3600, label_smoothing=0.1)
    cfg.train = TrainConfig(batch_size=32768, train_steps=3600,
                            log_every_steps=10,
                            # the arXiv:1811.05233 recipe shape: a
                            # bf16 step (docs/precision.md)
                            precision="bf16")
    return cfg


#: ImageNet train-set size — the epoch↔step conversion the large-batch
#: warmup recipes are specified in (arXiv:1711.04325 / 1811.05233 give
#: warmup in EPOCHS; steps depend on the global batch)
IMAGENET_TRAIN_IMAGES = 1_281_167


def large_batch_steps(batch_size: int, epochs: float) -> int:
    """Steps covering ``epochs`` ImageNet epochs at ``batch_size`` — the
    one conversion both large-batch presets and ad-hoc ``--set`` overrides
    use, so a changed batch size keeps the epoch budget."""
    return max(1, round(epochs * IMAGENET_TRAIN_IMAGES / batch_size))


def _imagenet_resnet50_lars4k() -> ExperimentConfig:
    """Large-batch bs=4096 + LARS, the arXiv:1711.04325 / 1811.05233
    recipe shape: 5-epoch linear warmup (the cure for the bs>512 accuracy
    cliff the reference README documents at 32k), polynomial(2) decay to
    zero over 90 epochs, label smoothing 0.1. ZeRO-1 on: the optimizer
    state shards across the data axis (arXiv:2004.13336), so per-replica
    memory stops scaling with the replica count's optimizer copies."""
    cfg = _imagenet_resnet50()
    bs = 4096
    cfg.optimizer = OptimizerConfig(
        name="lars", learning_rate=13.0, weight_decay=1e-4,
        schedule="warmup_poly", zero1="on",
        warmup_steps=large_batch_steps(bs, 5),
        total_steps=large_batch_steps(bs, 90), label_smoothing=0.1)
    cfg.train = TrainConfig(batch_size=bs,
                            train_steps=large_batch_steps(bs, 90),
                            log_every_steps=20,
                            precision="bf16")  # arXiv:1811.05233 recipe
    return cfg


def _imagenet_resnet50_lamb4k() -> ExperimentConfig:
    """Large-batch bs=4096 + LAMB (trust-ratio-scaled Adam): the same
    5-epoch linear warmup + 90-epoch budget as the LARS recipe, cosine
    decay (LAMB's usual pairing). ZeRO-1 on — LAMB doubles the moment
    state (m AND v per param), which is exactly the memory the sharded
    update exists to split."""
    cfg = _imagenet_resnet50()
    bs = 4096
    cfg.optimizer = OptimizerConfig(
        name="lamb", learning_rate=10.0, weight_decay=1e-4,
        schedule="cosine", zero1="on",
        warmup_steps=large_batch_steps(bs, 5),
        total_steps=large_batch_steps(bs, 90), label_smoothing=0.1)
    cfg.train = TrainConfig(batch_size=bs,
                            train_steps=large_batch_steps(bs, 90),
                            log_every_steps=20,
                            precision="bf16")  # arXiv:1811.05233 recipe
    return cfg


def _vit_long_context() -> ExperimentConfig:
    """Long-context ViT: 256² images at patch 4 → 4096 tokens/image — the
    regime the Pallas flash kernel exists for (attention_impl='auto'
    resolves to 'flash' on TPU past the measured ~2k-token crossover,
    models/transformer.py). Beyond-reference capability; the shipped config
    that exercises the kernel by default."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="vit", num_classes=10, vit_patch_size=4, vit_dim=512,
        vit_depth=8, vit_heads=8)
    cfg.data = DataConfig(dataset="synthetic", image_size=256)
    cfg.optimizer = OptimizerConfig(
        name="adam", learning_rate=1e-3, weight_decay=0.0,
        schedule="cosine", warmup_steps=500, total_steps=20000)
    cfg.train = TrainConfig(batch_size=8, train_steps=20000, remat=True)
    return cfg


def _vit_large_224() -> ExperimentConfig:
    """Classic ViT-L/16 at 224² (196 tokens, dense attention): the
    transformer-family ≥0.55-MFU contract — measured 0.57 MFU at the
    preset's bs=32 per chip, every FLOP XLA-counted
    (docs/perf_vit_classic_r5.md). Per-chip batch is pinned at the
    measured optimum; scale global batch over the `data` mesh axis
    (bs 128 per chip measured ~0.45 — XLA picks a worse program there)."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="vit", num_classes=1000, vit_patch_size=16, vit_dim=1024,
        vit_depth=24, vit_heads=16, attention_impl="dense")
    cfg.data = DataConfig(dataset="synthetic", image_size=224)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.05,
        schedule="cosine", warmup_steps=10000, total_steps=300000)
    cfg.train = TrainConfig(batch_size=32, train_steps=300000,
                            steps_per_loop=8, remat=False)
    return cfg


def _vit_moe() -> ExperimentConfig:
    """Switch-MoE ViT — the expert-parallel member of the preset zoo.
    Sized so every transformer layout elaborates on the virtual 8-device
    gate mesh (dp / dp_fsdp / dp_pp / dp_tp / dp_pp_ep: depth 8 % 2
    stages, heads 4 % tensor 2, experts 4 % expert 2, bs 64 % shards ×
    microbatches), giving the MoE/pipeline collective-schedule families
    a shipped config instead of test-only ad-hoc ones."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="vit", num_classes=10, vit_patch_size=4, vit_dim=128,
        vit_depth=8, vit_heads=4, vit_num_experts=4,
        attention_impl="dense")
    cfg.data = DataConfig(dataset="synthetic", image_size=32)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.02,
        schedule="cosine", warmup_steps=1000, total_steps=50000)
    cfg.train = TrainConfig(batch_size=64, train_steps=50000)
    return cfg


def _trinity_mini_share8() -> ExperimentConfig:
    """Trinity-Mini (arcee-ai, model_type afmoe) at its published widths:
    ONE chip's share of a deployment in which eight chips share each layer
    — experts 0-15 of 128, 25,024 of 200,192 vocabulary rows, attention,
    router and shared expert whole — and five of its 32 layers (one leading
    dense layer and one whole period window, window, window, full; the
    rest would lie on further chips as pipeline stages). 705,474,304
    parameters, 11.3 GB of state at 16 bytes each; sequences of 8,192
    tokens under per-block recomputation and a chunked loss."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="afmoe", compute_dtype="bfloat16", attention_impl="auto",
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128,
        layer_types=("sliding_attention",) * 4 + ("full_attention",),
        sliding_window=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        num_dense_layers=1, intermediate_size=6144,
        moe_intermediate_size=1024, num_experts=128,
        experts_held=(0, 16), num_experts_per_tok=8, num_shared_experts=1,
        route_scale=2.826, load_balance_coeff=0.001, mup_enabled=True,
        vocab_held=25024)
    cfg.data = DataConfig(dataset="tokens", seq_len=8192)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1,
        schedule="cosine", warmup_steps=2000, total_steps=100000)
    cfg.train = TrainConfig(batch_size=2, train_steps=100000,
                            steps_per_loop=1, remat=True)
    return cfg


def _sdar_30b_a3b_share8() -> ExperimentConfig:
    """SDAR-30B-A3B-Chat (JetLM, model_type sdar_moe) at its published
    widths: ONE chip's share of a deployment in which eight chips share
    each layer — experts 0-15 of 128, 18,992 of 151,936 vocabulary rows,
    attention and router whole — and six of its 48 layers (every layer is
    the same: grouped attention with rotary positions, softmax top-8
    experts, no shared expert; the rest would lie on further chips as
    pipeline stages). 645,623,296 parameters, 10.33 GB of state at 16 bytes
    each; the block-diffusion objective in blocks of 4 over sequences of
    4,096 ids, 8,192 positions each through the decoder."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="sdar_moe", compute_dtype="bfloat16", attention_impl="auto",
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, layer_types=("full_attention",) * 6,
        rope_theta=1000000.0, rms_norm_eps=1e-6,
        num_dense_layers=0, intermediate_size=6144,
        moe_intermediate_size=768, num_experts=128, experts_held=(0, 16),
        num_experts_per_tok=8, num_shared_experts=0, mup_enabled=False,
        vocab_held=18992, block_length=4, mask_token_held=18991,
        noise_eps=1e-3)
    cfg.data = DataConfig(dataset="blockdiff_tokens", seq_len=4096)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1,
        schedule="cosine", warmup_steps=2000, total_steps=100000)
    cfg.train = TrainConfig(batch_size=2, train_steps=100000,
                            steps_per_loop=1, remat=True)
    return cfg


def _nemotron3_nano_share16() -> ExperimentConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h) at its
    published widths: ONE chip's share of a deployment in which sixteen
    chips share each layer — experts 0-7 of 128, 16,384 of 131,072
    vocabulary rows, the Mamba-2 mixers, attention, router and shared
    expert whole — and the first nine of its 52 blocks, MEMEM*EME (M
    Mamba-2, E experts, * attention: one whole period; the rest would lie
    on further chips as pipeline stages). 666,963,456 parameters, 10.67 GB
    of state at 16 bytes each; sequences of 8,192 tokens under per-block
    recomputation and a chunked loss."""
    cfg = ExperimentConfig()
    pattern = {"M": "mamba", "E": "moe", "*": "full_attention"}
    cfg.model = ModelConfig(
        name="nemotron_h", compute_dtype="bfloat16", attention_impl="auto",
        hidden_size=2688, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, layer_types=tuple(pattern[k] for k in "MEMEM*EME"),
        rms_norm_eps=1e-5, num_dense_layers=0, intermediate_size=1856,
        moe_intermediate_size=1856, num_experts=128, experts_held=(0, 8),
        num_experts_per_tok=6, num_shared_experts=1,
        moe_shared_expert_intermediate_size=3712, route_scale=2.5,
        load_balance_coeff=0.001, mup_enabled=False, vocab_held=16384,
        mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128)
    cfg.data = DataConfig(dataset="tokens", seq_len=8192)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1,
        schedule="cosine", warmup_steps=2000, total_steps=100000)
    cfg.train = TrainConfig(batch_size=2, train_steps=100000,
                            steps_per_loop=1, remat=True)
    return cfg


def _glm47_flash_share8() -> ExperimentConfig:
    """GLM-4.7-Flash (zai-org, model_type glm4_moe_lite) at its published
    widths: ONE chip's share of a deployment in which eight chips share
    each layer — experts 0-7 of 64, 19,360 of 154,880 vocabulary rows,
    latent attention, router, shared expert, the dense layer and the
    multi-token-prediction block whole — and six of its 47 layers plus the
    prediction block: the leading dense layer and four expert layers (the
    layers after the dense one are all alike; the rest would lie on
    further chips as pipeline stages). 706,518,848 parameters, 11.30 GB of
    state at 16 bytes each; sequences of 8,192 tokens under per-block
    recomputation and a chunked loss, the chunked head called twice."""
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(
        name="glm4_moe_lite", compute_dtype="bfloat16", attention_impl="auto",
        hidden_size=2048, num_attention_heads=20, num_key_value_heads=20,
        layer_types=("full_attention",) * 5, rope_theta=1000000.0,
        rms_norm_eps=1e-5, num_dense_layers=1, intermediate_size=10240,
        moe_intermediate_size=1536, num_experts=64, experts_held=(0, 8),
        num_experts_per_tok=4, num_shared_experts=1, route_scale=1.8,
        load_balance_coeff=0.001, mup_enabled=False, vocab_held=19360,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, num_nextn_predict_layers=1,
        mtp_loss_weight=0.3)
    cfg.data = DataConfig(dataset="tokens", seq_len=8192)
    cfg.optimizer = OptimizerConfig(
        name="adamw", learning_rate=3e-4, weight_decay=0.1,
        schedule="cosine", warmup_steps=2000, total_steps=100000)
    cfg.train = TrainConfig(batch_size=2, train_steps=100000,
                            steps_per_loop=1, remat=True)
    return cfg


def _cifar10_smoke() -> ExperimentConfig:
    """Local smoke test analog of reference scripts/submit_mac_dist.sh
    (1ps+2wk, bs=10, 100 steps on CPU — SURVEY.md §4.1)."""
    cfg = _cifar10_resnet50()
    cfg.model.resnet_size = 20
    cfg.data.dataset = "synthetic"
    cfg.train = TrainConfig(batch_size=10, train_steps=100, log_every_steps=10)
    cfg.optimizer.total_steps = 100
    cfg.checkpoint.save_every_secs = 0.0
    return cfg


PRESETS = {
    "cifar10_resnet50": _cifar10_resnet50,
    "cifar10_resnet50_bs512": _cifar10_resnet50_bs512,
    "cifar100_wrn28_10": _cifar100_wrn2810,
    "imagenet_resnet50": _imagenet_resnet50,
    "imagenet_resnet50_lars32k": _imagenet_resnet50_lars32k,
    "imagenet_resnet50_lars4k": _imagenet_resnet50_lars4k,
    "imagenet_resnet50_lamb4k": _imagenet_resnet50_lamb4k,
    "vit_long_context": _vit_long_context,
    "vit_large_224": _vit_large_224,
    "vit_moe": _vit_moe,
    "trinity_mini_share8": _trinity_mini_share8,
    "sdar_30b_a3b_share8": _sdar_30b_a3b_share8,
    "nemotron3_nano_share16": _nemotron3_nano_share16,
    "glm47_flash_share8": _glm47_flash_share8,
    "smoke": _cifar10_smoke,
}


def resolve_checkpoint_dir(cfg: ExperimentConfig) -> str:
    """Single source of truth for the checkpoint directory — trainer and
    evaluator MUST agree (their only interface is this directory, as in the
    reference, SURVEY.md §3.3)."""
    import os
    return cfg.checkpoint.directory or os.path.join(cfg.log_root, "ckpt")


def stacked_layout_stamp(cfg: ExperimentConfig):
    """Storage-order declaration for depth-stacked encoder params, recorded
    next to checkpoints: the circular pipeline schedule
    (model.vit_pipeline_interleave > 1) stores stage-major layer order, so a
    restore under a different (mesh.pipeline, interleave) must be refused
    (models/pipeline.py circular_layer_order / repack_stacked_params).
    None = no stacked params in this model family."""
    if cfg.model.name != "vit":
        return None
    v = cfg.model.vit_pipeline_interleave
    p = cfg.mesh.pipeline
    if v <= 1 or p <= 1:
        return {"encoder_order": "network"}
    return {"encoder_order": "circular", "pstages": p, "interleave": v,
            "depth": cfg.model.vit_depth}


def get_preset(name: str) -> ExperimentConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name]()


def parse_args(argv: Optional[Sequence[str]] = None) -> ExperimentConfig:
    """CLI: ``--preset cifar10_resnet50 --set train.batch_size=256 ...``"""
    p = argparse.ArgumentParser(description="distributed_resnet_tensorflow_tpu trainer")
    p.add_argument("--preset", default="cifar10_resnet50", choices=sorted(PRESETS))
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="dotted config override, e.g. --set train.batch_size=256")
    p.add_argument("--config_json", default="", help="path to a JSON config to load")
    ns = p.parse_args(argv)
    if ns.config_json:
        with open(ns.config_json) as f:
            cfg = ExperimentConfig.from_dict(json.load(f))
    else:
        cfg = get_preset(ns.preset)
    for ov in ns.set:
        if "=" not in ov:
            raise ValueError(f"--set expects KEY=VALUE, got {ov!r}")
        k, v = ov.split("=", 1)
        cfg.override(k, v)
    return cfg

"""Config / flag system.

Mirrors the reference's single argparse surface (utils.py:102-230 in
/root/reference/CommEfficient) flag-for-flag so experiment commands
port 1:1, but materialises the result in a typed ``Config`` dataclass
that the jitted runtime treats as static. TPU-specific knobs (mesh
shape, dtype policy) are additive.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence

MODES = ("sketch", "true_topk", "local_topk", "fedavg", "uncompressed")
ERROR_TYPES = ("none", "local", "virtual")
DP_MODES = ("worker", "server")
ROBUST_AGGS = ("none", "median", "trimmed", "clip")
SKETCH_DTYPES = ("f32", "bf16", "int8", "fp8")
DOWNLINK_ENCODINGS = ("dense", "delta")

# dataset -> num classes (reference utils.py:37-44)
FED_DATASETS = {
    "CIFAR10": 10,
    "CIFAR100": 100,
    "EMNIST": 62,
    "ImageNet": 1000,
    "PERSONA": -1,
    "Synthetic": 10,
    "TOKENS": -1,  # per-client token streams (data/fed_tokens.py)
}

# natural client counts when --num_clients is omitted
# (reference fed_aggregator.py:66-73)
NATURAL_NUM_CLIENTS = {
    "EMNIST": 3500,
    "CIFAR10": None,  # non-iid CIFAR10 unsupported without --num_clients
    "PERSONA": 17568,
}


def num_classes_of_dataset(dataset_name: str) -> int:
    return FED_DATASETS[dataset_name]


@dataclasses.dataclass
class Config:
    """Typed mirror of the reference's parsed args (utils.py:102-230)."""

    # meta
    do_test: bool = False
    mode: str = "sketch"
    use_tensorboard: bool = False
    do_profile: bool = False  # JAX profiler trace of the first epoch
    # bfloat16 activations/matmuls (params + grads stay float32): full
    # MXU rate on TPU. The TPU analogue of cifar10_fast's fp16
    # training; no reference equivalent (it trains f32)
    do_bf16: bool = False
    # GPT-2 sequence parallelism: shard each client's sequences over
    # this many chips (ring or ulysses attention). 1 = off.
    seq_devices: int = 1
    seq_impl: str = "ring"
    # fault injection: each sampled client independently drops out of
    # the round with this probability (its contribution is excluded
    # and the round renormalises over the survivors). The reference
    # has no dropout simulation (SURVEY §5 failure detection).
    dropout_prob: float = 0.0
    # mixup augmentation for CV training. The reference's imagenet.sh
    # passes --mixup/--mixup_alpha but its parse_args never defines
    # them and its compute_loss_mixup is dead code (SURVEY §2.7);
    # here they work (host-side per-client mixing, lam ~ Beta(a, a)).
    do_mixup: bool = False
    mixup_alpha: float = 1.0
    seed: int = 21

    # model/data
    model: str = "ResNet9"
    do_finetune: bool = False
    do_checkpoint: bool = False
    # full-state resume (beyond the reference's save-only checkpoints)
    do_resume: bool = False
    checkpoint_every: int = 0  # epochs; 0 = end of training only
    checkpoint_path: str = "./checkpoint"
    finetune_path: str = "./finetune"
    finetuned_from: Optional[str] = None
    num_results_train: int = 2
    num_results_val: int = 2
    dataset_name: str = ""
    dataset_dir: str = "./dataset"
    do_batchnorm: bool = False
    nan_threshold: float = 999.0

    # compression
    k: int = 50000
    num_cols: int = 500000
    num_rows: int = 5
    num_blocks: int = 20
    do_topk_down: bool = False

    # optimization
    local_momentum: float = 0.9
    virtual_momentum: float = 0.0
    weight_decay: float = 5e-4
    num_epochs: float = 24.0
    # LR-schedule horizon; defaults to num_epochs. Set it when a run
    # will stop early and be resumed (--resume) so every invocation
    # decays over the same total, keeping resumed training identical
    # to an uninterrupted run.
    schedule_epochs: Optional[float] = None
    num_fedavg_epochs: int = 1
    fedavg_batch_size: int = -1
    fedavg_lr_decay: float = 1.0
    error_type: str = "none"
    lr_scale: Optional[float] = None
    pivot_epoch: float = 5.0

    # parallelization
    port: int = 5315  # kept for CLI parity; unused (no sockets in SPMD runtime)
    num_clients: Optional[int] = None
    num_workers: int = 1  # participating clients per round
    # None = whatever platform JAX reports; the trainers resolve it at
    # start-up and refuse a named platform that is not the one found
    # (parallel/mesh.maybe_initialize_multihost_cli)
    device: Optional[str] = None
    # number of TPU devices for the mesh; <= 0 = all available (the
    # reference's flag counted GPUs and defaulted to 1 — here a single
    # jitted program spans the mesh, so "all" is the natural default)
    num_devices: int = -1
    share_ps_gpu: bool = False  # parity no-op: there is no PS rank
    do_iid: bool = False
    train_dataloader_workers: int = 0
    val_dataloader_workers: int = 0

    # GPT-2 / text
    model_checkpoint: str = "gpt2"
    num_candidates: int = 2
    # candidates evaluated at validation. The reference restricts
    # candidates only when training (fed_persona.py:251-254) — val MC
    # accuracy is over the item's full ~20 candidates. 0 = auto-detect
    # (the maximum candidate count across the val set).
    val_candidates: int = 0
    max_history: int = 2
    local_batch_size: int = 8
    valid_batch_size: int = 8
    microbatch_size: int = -1
    lm_coef: float = 1.0
    mc_coef: float = 1.0
    max_grad_norm: Optional[float] = None
    personality_permutations: int = 1
    eval_before_start: bool = False

    # differential privacy (legacy reference-parity worker/server
    # mechanism — kept bit-for-bit; see --dp below for the
    # accountant-backed sketch mechanism)
    do_dp: bool = False
    dp_mode: str = "worker"
    l2_norm_clip: float = 1.0
    noise_multiplier: float = 0.0
    # DP sketching (privacy/): "sketch" L2-clips each client's dense
    # gradient to --dp_clip and adds calibrated Gaussian noise to the
    # aggregated sketch table BEFORE wire quantization, with an RDP
    # accountant riding the ledger. "off" traces nothing — the round
    # program is HLO-identical to a build without the feature.
    dp: str = "off"
    dp_clip: float = 1.0
    dp_noise_mult: float = 0.0
    # accountant target δ and total ε budget (0 = unlimited). A
    # finite budget arms the privacy_budget_exhausted alarm
    # (--on_divergence semantics) and hard-constrains the autopilot
    # knob ladder (no lattice point that exhausts ε before
    # --num_rounds is ever visited).
    dp_delta: float = 1e-5
    dp_epsilon: float = 0.0

    # --- TPU-native additions (no reference equivalent) ---
    # 2D pod mesh "CxM": C devices data-parallel over ``clients`` ×
    # M devices sharding server state (sketch table columns, momentum,
    # error feedback) over ``model`` — per-device server memory scales
    # as 1/M. "" = the 1-D clients mesh over --num_devices. M > 1 is
    # supported for the server-state modes (sketch, uncompressed);
    # "1x1" compiles to exactly the single-device 1-D program.
    mesh: str = ""
    param_dtype: str = "float32"
    compute_dtype: str = "float32"  # set bfloat16 for MXU throughput
    # lax.approx_max_k (recall approx_recall) for the index-producing
    # top-k selections: unsketch recovery and the true_topk server
    # select (exact top_k at k=50k over millions of coords lowers to
    # a full sort on TPU). Missed coordinates stay in the error
    # accumulators and resurface next round. The DENSE selections
    # (local_topk client masking, topk_down) at large d always use
    # the exact threshold-select path, which is faster than the
    # approximate sort (ops/topk.py) — this flag no longer affects
    # them there.
    approx_topk: bool = False
    approx_recall: float = 0.95  # recall target for --approx_topk
    # multi-host pod launch (jax.distributed): when set, the trainers
    # call initialize_multihost(coordinator_address, num_processes,
    # process_id) before building the mesh — one process per host,
    # same command everywhere (the reference's NCCL init_process_group
    # topology, fed_aggregator.py:161-165). On Cloud TPU pods leave
    # all three unset: auto-detected from the environment.
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # write the final GPT-2 model as pytorch_model.bin + HF config
    # (loadable by transformers.from_pretrained) in addition to the
    # flax msgpack — the reference's save_pretrained contract
    # (fed_aggregator.py:209-212)
    do_hf_export: bool = False
    # Synthetic-dataset heterogeneity dial: classes held by each
    # natural client (1 = the pathological one-class split; >1 =
    # milder non-iid). Ignored by the on-disk datasets, whose splits
    # come from the archives.
    classes_per_client: int = 1
    # Synthetic-dataset size dial: train items per class. 5000 with
    # --num_clients 10000 reproduces the FetchSGD paper's CIFAR10
    # federation shape (10 000 clients x 5 one-class images).
    synthetic_per_class: int = 64
    # Synthetic-dataset class-overlap dial: scales class means against
    # the fixed noise std. 1.0 = trivially separable; 0.025 gives a
    # Bayes ceiling near 0.86, making long-horizon convergence anchors
    # accuracy-discriminating (FedSynthetic.bayes_accuracy reports the
    # exact ceiling for the generated split).
    synthetic_separation: float = 1.0
    # Synthetic val-set size: 128 (default) is fine for smoke runs;
    # discriminating anchors need ~2000 for sub-percent granularity
    synthetic_num_val: int = 128
    # GPT-2: rematerialise transformer blocks in backward (activation
    # memory ~ 1/n_layer, ~1/3 extra FLOPs) — the long-context lever
    do_remat: bool = False
    # GPT-2 attention lowering: "xla" (jax.nn.dot_product_attention)
    # or "flash" (Pallas TPU flash-attention kernel) — see
    # models/gpt2.py GPT2Config.attn_impl
    attn_impl: str = "xla"
    # sketch rotation granularity (ops/sketch.py CountSketch.rot_lanes):
    # -1 = auto (default): 1024 on a TPU backend when the geometry is
    # large-d Pallas-eligible (the round-5 24-epoch anchors measured
    # tail-accuracy parity with full-granularity rotations at both
    # seeds, so the −44% kernel-pair / −8% flagship-round win is on by
    # default — core/rounds.py args2sketch); 0 everywhere else, since
    # quantized rotations pay their heavier collision tail for nothing
    # without the Pallas kernels' addressed rotation. 0 = force full
    # granularity; >0 quantizes rotations to multiples of that many
    # elements.
    # Sketch tables/error state are not comparable across different
    # resolved values (different rotation streams) — a checkpoint
    # resumed under a different backend re-resolves -1, so pin an
    # explicit value when moving sketch-mode checkpoints across
    # platforms.
    sketch_rot_lanes: int = -1
    # wire dtype of the uplinked sketch table (ops/quant.py): "f32"
    # (default; the program compiles bit-identical to a build without
    # the flag), "bf16" (plain cast, summed in bf16 on the wire),
    # "int8"/"fp8" (per-row scales: each shard quantizes against its
    # local row maxabs, then harmonizes onto the pmax'd global row
    # scale with summation headroom so the wire-dtype psum cannot
    # overflow). Emission accumulates in f32; the server dequantizes
    # before momentum/error feedback so optimizer state stays f32.
    # Count-sketch is mean-zero and tolerant of coarse quantization
    # (FedSKETCH; arXiv:1903.04488) — int8 cuts uplink ~4x at a
    # recovery-error cost well inside the probe alarm band on the
    # reference config (README compression-modes table).
    sketch_dtype: str = "f32"
    # downlink encoding of the broadcast update: "dense" ships the
    # changed coordinates as f32 (reference-shaped); "delta" ships
    # (idx:int32, val:wire_dtype) pairs plus a round-delta bitmap
    # naming the indices repeated from the previous round's support,
    # so a client that saw round t-1 pays 1 bit instead of 4 bytes
    # per repeated index. Accounting-level encoding: the compiled
    # round program is unchanged (runtime/fed_model.py).
    downlink_encoding: str = "dense"
    # scan the round's client fan-out in chunks of this many clients
    # (0 = all at once): caps live per-client intermediates at
    # chunk x d — the memory lever for large-W rounds of the local-
    # state modes on one chip (the reference's serial per-worker client
    # loop bounds memory the same way, fed_worker.py:59-133). Ignored
    # on a multi-device mesh (the client axis is already divided).
    client_chunk: int = 0
    # latency-hiding round pipeline (sketch mode): chunk sketch
    # emission over table rows and issue each chunk's wire collective
    # while the next chunk quantizes — XLA's latency-hiding scheduler
    # overlaps collective i with chunk i+1's compute. 1 = today's
    # serial program (bit-identical HLO); N > 1 splits the (r, c)
    # table into min(N, r) row chunks. The folded result is unchanged:
    # the sketch is linear over disjoint row chunks and quantization
    # scales are per-row, so row-chunked quantize + harmonize +
    # collective composes exactly with the whole-table path.
    overlap_depth: int = 1
    # GPT-2: tokens per logits chunk in the chunked tied-head
    # cross-entropy (models/gpt2.py lm_nll_sums_chunked) — the
    # vocab-head temp memory scales with this chunk, not the sequence.
    # 0 = auto: 256 on the sequence-parallel path (the measured memory
    # knee, core/rounds_sp.py), 1024 on the single-device path
    # (throughput-flat across 512-4096 at the 8x geometry).
    tokens_per_chunk: int = 0
    # GPT-2: fused-linear-CE vocab head (ops/flce_pallas.py) — the
    # per-chunk logits round-trips of the chunked path go away
    # entirely. "auto" = Pallas kernels on a TPU default backend at
    # lane-aligned widths, chunked elsewhere; "on"/"off" force.
    # Default off: no cell can show it winning where it is wired
    # (ROADMAP S4).
    fused_ce: str = "off"
    # Per-client state placement (commefficient_tpu/clientstore):
    # "device" keeps the dense (num_clients, *transmit_shape) arrays in
    # HBM (reference-shaped); "host" keeps them in a budgeted host
    # arena with an mmap spill tier and materializes only the round's
    # participants on device — million-client populations on a fixed
    # HBM budget; "auto" resolves at build time: host when the dense
    # population would exceed --clientstore_bytes, device otherwise.
    clientstore: str = "device"
    # arena budget for --clientstore host/auto (bytes); rows beyond it
    # are evicted LRU-first to the mmap spill tier
    clientstore_bytes: int = 1 << 30
    # spill-tier directory ("" = private temp dir, removed on exit)
    clientstore_dir: str = ""
    # telemetry (commefficient_tpu/telemetry): path of the JSONL round
    # ledger ("" = disabled — the no-op fast path costs nothing on the
    # round hot loop). One schema-v1 record per training round: spans,
    # comm bytes (identical to the accounting counters), prefetch
    # hit/miss, compile events, memory watermarks. Render/diff with
    # scripts/telemetry_report.py.
    ledger: str = ""
    # end-of-run console summary of the round ledger (per-span
    # totals/means, byte totals) — works with or without --ledger
    telemetry_console: bool = False
    # algorithm probes (telemetry schema v2): 0 = off (the round step
    # compiles to exactly the pre-probe HLO — no extra outputs). N > 0
    # compiles the cheap O(d) probes (update/residual/momentum norms,
    # NaN/Inf counts, mass coverage) into every round and additionally
    # runs the expensive true sketch-recovery-error probe
    # ‖unsketch(S(g)) − g‖/‖g‖ on rounds where round % N == 0 (it
    # needs the dense aggregate the sketch path otherwise never
    # materialises).
    probe_every: int = 0
    # shorthand for --probe_every 1: every probe, every round
    probe_full: bool = False
    # alarm engine (telemetry/alarms.py) action when a probe rule
    # fires: "log" (warn + ledger flag), "ledger-flag" (ledger flag
    # only), "abort" (flag, then raise DivergenceAbort so the trainer
    # stops at the offending round)
    on_divergence: str = "log"
    # residual-growth rule: Verror-norm growth ratio > this for
    # --alarm_residual_rounds consecutive probed rounds
    alarm_residual_ratio: float = 2.0
    alarm_residual_rounds: int = 3
    # recovery-error rule: ‖unsketch(S(g)) − g‖/‖g‖ above this (1.0 =
    # the recovered update is no better than sending nothing)
    alarm_recovery_error: float = 1.0
    # step-time regression rule (telemetry/alarms.py): fire when a
    # round's wall step time exceeds this ratio x the rolling median
    # of the last --alarm_step_time_window rounds. 0 = off. Works
    # without probes; shares the --on_divergence action.
    alarm_step_time_ratio: float = 0.0
    alarm_step_time_window: int = 16
    # collective-skew rule (telemetry/alarms.py): fire when a traced
    # round's max cross-device collective enter-delta exceeds this
    # ratio x the round's collective seconds (schema-v4 device_time
    # skew stats). 0 = off. Needs --profile to produce trace buckets;
    # shares the --on_divergence action.
    alarm_collective_skew: float = 0.0
    # robust aggregation (core/robust.py): how the round folds the
    # per-client transmits. "none" = the plain datapoint-weighted mean
    # (bit-identical program to a build without the flag); "median" =
    # coordinate-wise median over per-client (or grouped) per-datapoint
    # mean transmits; "trimmed" = coordinate-wise trimmed mean dropping
    # --robust_trim_frac of each tail; "clip" = per-client norm clip to
    # --robust_clip_norm before the plain weighted fold. Robust folds
    # need materialised per-client transmits, so they disable the
    # fused-gradient and sketch-after-local-sum fast paths (sketch mode
    # sketches per client — the median-of-sketches estimator of the
    # sketched-SGD line). The server only ever sees the robust
    # aggregate: rejected client mass is never fed into the virtual
    # momentum/error state.
    robust_agg: str = "none"
    # fraction of clients trimmed from EACH tail per coordinate under
    # --robust_agg trimmed (t = floor(frac * alive))
    robust_trim_frac: float = 0.1
    # per-client transmit-norm clip threshold (per-datapoint-mean
    # scale) under --robust_agg clip; 0 = auto (the median of the
    # round's alive per-client norms)
    robust_clip_norm: float = 0.0
    # --robust_agg median: fold clients into this many groups (mean
    # within a group, median across groups — 1903.04488's
    # median-of-means over sketches); 0 = every client its own group.
    # num_workers must divide evenly.
    robust_median_groups: int = 0
    # byzantine_suspect rule (telemetry/alarms.py): fire when the
    # round's max per-client transmit norm exceeds this ratio x the
    # alive-client mean norm (needs probes for client_norm_* to
    # exist). 0 = off; shares the --on_divergence action.
    alarm_byzantine_ratio: float = 0.0
    # fold_rejection_rate rule: fire when the robust fold's relative
    # deviation from the plain mean exceeds this (the mass the fold
    # rejected; needs --robust_agg != none and probes). 0 = off.
    alarm_fold_rejection: float = 0.0
    # periodic round-cadence autosave (runtime/checkpoint.py): save a
    # full resumable checkpoint every N completed training rounds
    # (0 = off; epoch-cadence --checkpoint_every is independent).
    # Mid-epoch saves capture the sampler's in-progress epoch state,
    # so a crash resumes at the autosaved round, bit-exact.
    checkpoint_every_rounds: int = 0
    # retention for round-cadence autosaves: keep this many numbered
    # history snapshots (ckpt_<tag>_r<round>.npz hardlinks) besides
    # the latest; 0 = latest only
    checkpoint_keep: int = 0
    # buffered asynchronous rounds (asyncfed/): fold the arrival
    # buffer every K arrived clients instead of barriering on the
    # full cohort. 0 = synchronous barrier (the compiled round is
    # bit-identical to async-off builds); K must be in
    # [1, num_workers] — the compiled cohort width stays num_workers
    # and a fold with fewer arrivals pads dead slots (mask 0).
    async_buffer_size: int = 0
    # staleness exponent alpha: an update folded s rounds after it
    # was issued is weighted 1/(1+s)^alpha (transmit AND its
    # datapoint count, so the fold stays a weighted per-datapoint
    # mean and stale mass never corrupts virtual momentum/EF).
    # alpha = 0 keeps weights exactly 1 and the buffered fold
    # reduces bit-exactly to the synchronous round at K = cohort.
    async_staleness_weight: float = 0.0
    # async_staleness rule (telemetry/alarms.py): fire when the
    # round's max folded staleness (rounds) exceeds this. 0 = off;
    # shares the --on_divergence action.
    alarm_async_staleness: float = 0.0
    # job_starvation rule (telemetry/alarms.py), evaluated by the
    # fedservice daemon's own engine: fire when a runnable job has
    # waited more than this many scheduler ticks since it last ran.
    # 0 = off; shares the --on_divergence action.
    alarm_job_starvation: float = 0.0
    # live operations plane (telemetry/live.py): serve the process's
    # in-memory metric registry in Prometheus text exposition format
    # from a localhost-only exporter thread at this port (/metrics +
    # /healthz). 0 = off: nothing is constructed and the build stays
    # bit-identical. Entirely host-side; excluded from the registry
    # run key like the other observability taps.
    live_port: int = 0
    # flight recorder (telemetry/flightrec.py): keep the last N round
    # records in an in-memory ring and dump an atomic postmortem
    # bundle on any alarm fire / graceful shutdown / crash. 0 = off.
    flightrec_rounds: int = 0
    # where postmortem bundles land (stamped into the run registry
    # when --runs_dir is known)
    postmortem_dir: str = "runs/postmortems"
    # per-job SLO targets (telemetry/slo.py) — each 0 leaves that
    # objective un-armed; any nonzero target arms the SLO engine,
    # which merges slo_burn_* probes into the round record and stamps
    # the v6 "slo" key:
    # round-latency objective: a round slower than this p95 target
    # (seconds) is an SLO violation
    slo_round_p95: float = 0.0
    # staleness objective: a round whose max folded staleness exceeds
    # this ceiling (rounds) is a violation
    slo_staleness_max: float = 0.0
    # privacy-burn objective: ε must stay under the linear spend
    # schedule dp_epsilon * (round+1) / slo_eps_rounds over this
    # horizon (rounds); needs --dp sketch with a hard --dp_epsilon
    slo_eps_rounds: int = 0
    # starvation objective (fedservice daemon): a tick whose max
    # job wait exceeds this many ticks is a violation
    slo_starvation: float = 0.0
    # fraction of windowed rounds allowed to violate before the burn
    # rate reads 1.0 (the error budget)
    slo_error_budget: float = 0.05
    # slow / fast rolling windows (rounds) for the multi-window burn
    # rate: burn = min(fast_rate, slow_rate) / error_budget — the
    # fast window gives detection latency, the slow window keeps a
    # transient spike from paging
    slo_window: int = 32
    slo_fast_window: int = 8
    # slo_burn rule (telemetry/alarms.py): fire when slo_burn_max
    # reaches this burn rate. 0 = off; shares the --on_divergence
    # action.
    alarm_slo_burn: float = 0.0
    # adaptive compression autopilot (commefficient_tpu/autopilot):
    # "on" runs the seeded between-rounds controller that walks the
    # discrete knob lattice (sketch_dtype x k x rows x cols x recall)
    # toward the cheapest round program whose recovery error stays
    # inside --autopilot_band, dispatching through a bounded LRU of
    # jitted round variants (re-jit cache). "off" (default): no
    # controller, and the compiled program is bit-identical to a
    # build without the flag (the base variant is built from THIS
    # config object unchanged).
    autopilot: str = "off"
    # target recovery-error band "LO:HI" (required with --autopilot
    # on): the controller cheapens below LO after the cooldown, backs
    # off above HI immediately and never re-enters the offending
    # point. The LO..HI gap is the hysteresis that prevents
    # oscillation.
    autopilot_band: str = ""
    # in-band probed rounds to wait between cheapening moves (back-off
    # ignores it — safety beats cooldown)
    autopilot_cooldown: int = 2
    # bound of the round-variant LRU (jitted programs kept alive);
    # evicted variants recompile on re-visit, stamped in the ledger
    autopilot_cache_size: int = 4
    # pre-compile a decided move's round variant under the current
    # round's host phase (AOT lower+compile), so the switch round
    # never stalls on XLA; only DECIDED points are ever warmed —
    # unvisited lattice points never compile eagerly
    autopilot_warm_ahead: bool = True
    # hold the controller at one lattice point (variant-key spelling,
    # e.g. "int8-k50000-r5-c500000-re9500"): the full autopilot
    # machinery engages (cache, trajectory, manifest record) but no
    # move is ever made — bit-identical to the equivalent static
    # config
    autopilot_pin: str = ""
    # let the ladder extend past the dtype axis into column-halving
    # geometry steps; a geometry move changes the sketch table shape
    # and RESETS server momentum/error feedback (runtime/fed_model.py)
    autopilot_geometry: bool = False

    # populated at runtime (reference sets args.grad_size the same way,
    # fed_aggregator.py:88)
    grad_size: int = 0

    def __post_init__(self):
        self.validate()

    def validate(self) -> "Config":
        """Parse-time cross-flag validation — same checks, same timing
        as the reference's parse_args (utils.py:225-228): only the
        fedavg combination is rejected up front."""
        assert self.mode in MODES, self.mode
        assert self.error_type in ERROR_TYPES, self.error_type
        assert self.dp_mode in DP_MODES, self.dp_mode
        assert self.dp in ("off", "sketch"), \
            "--dp must be off|sketch"
        assert self.dp_clip > 0, "--dp_clip must be > 0"
        assert self.dp_noise_mult >= 0, \
            "--dp_noise_mult must be >= 0"
        assert 0.0 < self.dp_delta < 1.0, \
            "--dp_delta must be in (0, 1)"
        assert self.dp_epsilon >= 0, \
            "--dp_epsilon must be >= 0 (0 = unlimited budget)"
        if self.dp_epsilon > 0:
            assert self.dp != "off", \
                "--dp_epsilon budget needs --dp sketch (nothing " \
                "spends the budget otherwise)"
            assert self.dp_noise_mult > 0, \
                "--dp_epsilon budget needs --dp_noise_mult > 0 " \
                "(a noiseless release exhausts any finite ε " \
                "immediately)"
        assert 0.0 < self.approx_recall <= 1.0, \
            "--approx_recall must be in (0, 1]"
        assert self.tokens_per_chunk >= 0, \
            "--tokens_per_chunk must be >= 0 (0 = auto)"
        assert self.fused_ce in ("auto", "on", "off"), \
            "--fused_ce must be auto|on|off"
        assert self.clientstore in ("device", "host", "auto"), \
            "--clientstore must be device|host|auto"
        assert self.clientstore_bytes >= 0, \
            "--clientstore_bytes must be >= 0"
        assert self.probe_every >= 0, \
            "--probe_every must be >= 0 (0 = probes off)"
        assert self.on_divergence in ("log", "ledger-flag", "abort"), \
            "--on_divergence must be log|ledger-flag|abort"
        assert self.alarm_residual_rounds >= 1, \
            "--alarm_residual_rounds must be >= 1"
        assert self.alarm_step_time_ratio >= 0, \
            "--alarm_step_time_ratio must be >= 0 (0 = rule off)"
        assert self.alarm_step_time_window >= 2, \
            "--alarm_step_time_window must be >= 2"
        assert self.alarm_collective_skew >= 0, \
            "--alarm_collective_skew must be >= 0 (0 = rule off)"
        assert self.robust_agg in ROBUST_AGGS, \
            "--robust_agg must be none|median|trimmed|clip"
        assert 0.0 <= self.robust_trim_frac < 0.5, \
            "--robust_trim_frac must be in [0, 0.5)"
        assert self.robust_clip_norm >= 0, \
            "--robust_clip_norm must be >= 0 (0 = auto)"
        assert self.robust_median_groups >= 0, \
            "--robust_median_groups must be >= 0 (0 = per-client)"
        assert self.alarm_byzantine_ratio >= 0, \
            "--alarm_byzantine_ratio must be >= 0 (0 = rule off)"
        assert self.alarm_fold_rejection >= 0, \
            "--alarm_fold_rejection must be >= 0 (0 = rule off)"
        assert self.checkpoint_every_rounds >= 0, \
            "--checkpoint_every_rounds must be >= 0 (0 = off)"
        assert self.checkpoint_keep >= 0, \
            "--checkpoint_keep must be >= 0"
        assert self.async_buffer_size >= 0, \
            "--async_buffer_size must be >= 0 (0 = synchronous)"
        assert self.async_staleness_weight >= 0, \
            "--async_staleness_weight must be >= 0"
        assert self.alarm_async_staleness >= 0, \
            "--alarm_async_staleness must be >= 0 (0 = rule off)"
        assert self.alarm_job_starvation >= 0, \
            "--alarm_job_starvation must be >= 0 (0 = rule off)"
        assert 0 <= self.live_port <= 65535, \
            "--live_port must be in [0, 65535] (0 = off)"
        assert self.flightrec_rounds >= 0, \
            "--flightrec_rounds must be >= 0 (0 = off)"
        assert self.slo_round_p95 >= 0, \
            "--slo_round_p95 must be >= 0 (0 = objective off)"
        assert self.slo_staleness_max >= 0, \
            "--slo_staleness_max must be >= 0 (0 = objective off)"
        assert self.slo_eps_rounds >= 0, \
            "--slo_eps_rounds must be >= 0 (0 = objective off)"
        if self.slo_eps_rounds > 0:
            assert self.dp != "off" and self.dp_epsilon > 0, \
                "--slo_eps_rounds needs --dp sketch with a hard " \
                "--dp_epsilon budget (nothing spends ε otherwise)"
        assert self.slo_starvation >= 0, \
            "--slo_starvation must be >= 0 (0 = objective off)"
        assert 0.0 < self.slo_error_budget <= 1.0, \
            "--slo_error_budget must be in (0, 1]"
        assert self.slo_window >= 1, \
            "--slo_window must be >= 1"
        assert 1 <= self.slo_fast_window <= self.slo_window, \
            "--slo_fast_window must be in [1, --slo_window]"
        assert self.alarm_slo_burn >= 0, \
            "--alarm_slo_burn must be >= 0 (0 = rule off)"
        assert self.autopilot in ("off", "on"), \
            "--autopilot must be off|on"
        assert self.autopilot_cooldown >= 0, \
            "--autopilot_cooldown must be >= 0"
        assert self.autopilot_cache_size >= 1, \
            "--autopilot_cache_size must be >= 1"
        if self.autopilot == "on":
            assert self.mode == "sketch", \
                "--autopilot on requires --mode sketch (the knob " \
                "lattice is sketch geometry + wire dtype)"
            assert self.autopilot_band, \
                "--autopilot on requires --autopilot_band LO:HI"
            try:
                lo, hi = (float(p)
                          for p in self.autopilot_band.split(":"))
            except ValueError:
                raise AssertionError(
                    "--autopilot_band must be LO:HI, e.g. 0.2:0.6 "
                    f"(got {self.autopilot_band!r})") from None
            assert 0.0 <= lo < hi, \
                "--autopilot_band needs 0 <= LO < HI"
            assert self.probe_period > 0, \
                "--autopilot on needs probes (--probe_every N > 0): " \
                "the controller steers on the recovery-error probe"
        if self.async_buffer_size > 0:
            assert self.async_buffer_size <= self.num_workers, \
                "--async_buffer_size must be <= --num_workers " \
                "(the compiled cohort width is num_workers)"
        assert self.sketch_dtype in SKETCH_DTYPES, \
            "--sketch_dtype must be f32|bf16|int8|fp8"
        assert self.overlap_depth >= 1, \
            "--overlap_depth must be >= 1 (1 = serial round)"
        assert self.downlink_encoding in DOWNLINK_ENCODINGS, \
            "--downlink_encoding must be dense|delta"
        if self.mesh:
            import re
            assert re.fullmatch(r"[0-9]+x[0-9]+", self.mesh.lower()), \
                "--mesh must be CxM (e.g. 4x2)"
            c, m = self.mesh2d
            assert c >= 1 and m >= 1, "--mesh axes must be >= 1"
        if self.mode == "fedavg":
            assert self.local_batch_size == -1, \
                "fedavg requires --local_batch_size -1"
            assert self.local_momentum == 0, \
                "fedavg requires --local_momentum 0"
            assert self.error_type == "none", \
                "fedavg requires --error_type none"
        return self

    def validate_runtime(self) -> "Config":
        """Mode-lattice invariants, checked when the federated runtime
        is built (the reference enforces these in the worker/server hot
        path: fed_worker.py:206-230, fed_aggregator.py:514, 575-578).

        NB the reference's *defaults* (mode=sketch + local_momentum
        0.9) violate these and crash on the first training round;
        failing here at setup is the friendlier equivalent.
        """
        self.validate()
        if self.do_test:
            # the reference's --test short-circuits the worker before
            # any of these asserts run (fed_worker.py:118-123), so its
            # smoke mode works at default flags; normalize the default
            # combo here so ours does too
            if self.mode == "sketch" and self.local_momentum:
                self.virtual_momentum = max(self.virtual_momentum,
                                            self.local_momentum)
                self.local_momentum = 0.0
            if self.mode in ("sketch", "uncompressed") \
                    and self.error_type == "local":
                self.error_type = "virtual"
        if self.sketch_dtype != "f32":
            # the wire dtype quantizes the sketch table; the other
            # modes transmit dense/top-k floats whose accounting and
            # server fold never route through the table quantizer
            assert self.mode == "sketch", \
                "--sketch_dtype != f32 requires --mode sketch " \
                "(only the sketch table has a quantized wire path)"
        if self.overlap_depth > 1:
            # only the sketch table emits in disjoint row chunks;
            # dense transmits have no chunkable collective payload
            assert self.mode == "sketch", \
                "--overlap_depth > 1 requires --mode sketch " \
                "(only the sketch table emits in row chunks)"
        if self.dp != "off":
            assert self.mode == "sketch", \
                "--dp sketch requires --mode sketch (the mechanism " \
                "noises the aggregated sketch table)"
            assert not self.do_dp, \
                "--dp sketch replaces the legacy --do_dp worker/" \
                "server mechanism; enable only one"
            assert self.client_chunk == 0, \
                "--dp sketch noises the round's aggregated table " \
                "once; incompatible with --client_chunk (the " \
                "chunked scan never materialises it pre-wire)"
            # the accountant charges a per-client sqrt(r)·C/W bound;
            # median/trimmed releases don't have it (one client can
            # move a coordinate median by far more than its mean
            # share), and a cohort-derived clip cap (median of alive
            # norms) makes every client's scale depend on everyone's
            # data — also outside the bound
            assert self.robust_agg in ("none", "clip"), \
                "--dp sketch composes only with --robust_agg " \
                "{none,clip}: median/trimmed folds do not have the " \
                "sqrt(r)*clip/W sensitivity the accountant charges"
            assert self.robust_agg != "clip" \
                or self.robust_clip_norm > 0, \
                "--dp sketch with the clip fold needs a fixed " \
                "--robust_clip_norm > 0 (the auto median-of-norms " \
                "cap couples every client's scale to the whole " \
                "cohort, voiding the per-client sensitivity bound)"
        if self.mode == "sketch":
            # sketched SGD with local error/momentum is undefined: we
            # can't know which part of a sketch is "error"
            # (fed_worker.py:221-230)
            assert self.error_type != "local", \
                "sketch mode cannot use local error accumulation"
            assert self.local_momentum == 0, \
                "sketch mode cannot use local momentum " \
                "(momentum factor masking is impossible in sketch space)"
        if self.mode == "true_topk":
            # virtual error is required server-side (fed_aggregator.py:514)
            assert self.error_type == "virtual", \
                "true_topk requires --error_type virtual"
        if self.mode == "local_topk":
            assert self.error_type in ("local", "none"), \
                "local_topk cannot use virtual error (fed_aggregator.py:547)"
        if self.mode == "uncompressed":
            assert self.error_type != "local", \
                "local error accumulation is pointless uncompressed " \
                "(fed_worker.py:223-224)"
        if self.model_axis > 1:
            # the model axis shards *server* state; only the modes
            # whose server state is dense transmit-shaped buffers
            # (sketch tables / uncompressed vectors) have anything to
            # shard — the local-state modes keep their per-client rows
            # on the clients axis
            assert self.mode in ("sketch", "uncompressed"), \
                "--mesh with model axis > 1 supports sketch and " \
                "uncompressed modes only"
            if self.mode == "sketch":
                assert self.num_cols % self.model_axis == 0, \
                    "--mesh model axis must divide --num_cols " \
                    "(the sketch table shards by columns)"
            assert self.client_chunk == 0, \
                "--mesh with model axis > 1 is incompatible with " \
                "--client_chunk (the chunked scan is single-device)"
        if self.robust_agg != "none":
            # robust folds need the round's per-client transmits
            # materialised at once; the chunked scan only ever holds
            # a running sum
            assert self.client_chunk == 0, \
                "--robust_agg needs the full per-client transmit " \
                "stack; incompatible with --client_chunk"
            if self.robust_agg == "median" \
                    and self.robust_median_groups > 1:
                assert self.num_workers % self.robust_median_groups \
                    == 0, "--robust_median_groups must divide " \
                    "--num_workers"
        if self.async_buffer_size > 0:
            # the buffered fold weights the round's per-client
            # transmits by staleness; the chunked scan only ever
            # holds a running sum
            assert self.client_chunk == 0, \
                "--async_buffer_size needs the full per-client " \
                "transmit stack; incompatible with --client_chunk"
        return self

    @property
    def probe_period(self) -> int:
        """Resolved probe cadence: 0 = probes off entirely;
        --probe_full forces every-round probing regardless of
        --probe_every."""
        return 1 if self.probe_full else self.probe_every

    @property
    def resolved_num_clients(self) -> Optional[int]:
        if self.num_clients is not None:
            return self.num_clients
        return NATURAL_NUM_CLIENTS.get(self.dataset_name)

    @property
    def mesh2d(self):
        """Parsed --mesh "CxM" as (clients, model), or None for the
        1-D default."""
        if not self.mesh:
            return None
        c, m = (int(p) for p in self.mesh.lower().split("x"))
        return (c, m)

    @property
    def model_axis(self) -> int:
        """Model-axis size of the requested mesh (1 when unset or
        1-D — the replicated-server-state layout)."""
        shape = self.mesh2d
        return shape[1] if shape else 1

    @property
    def transmit_shape(self):
        """Shape of what one client transmits (and of server V/error
        state): the sketch table in sketch mode, else the flat grad
        (reference fed_worker.py:45-50, fed_aggregator.py:403-407)."""
        if self.mode == "sketch":
            return (self.num_rows, self.num_cols)
        return (self.grad_size,)

    @property
    def upload_floats_per_client(self) -> int:
        """Floats uploaded per participating client per round
        (reference fed_aggregator.py:292-300)."""
        return {
            "uncompressed": self.grad_size,
            "true_topk": self.grad_size,
            "local_topk": self.k,
            "sketch": self.num_rows * self.num_cols,
            "fedavg": self.grad_size,
        }[self.mode]

    @property
    def upload_wire_bytes_per_client(self) -> float:
        """Bytes uploaded per participating client per round, at the
        wire dtype: the quantized sketch table plus (int8/fp8) its
        per-row f32 scales; every other mode ships f32."""
        from commefficient_tpu import accounting
        if self.mode == "sketch":
            return accounting.sketch_wire_bytes(
                self.num_rows, self.num_cols, self.sketch_dtype)
        return accounting.bytes_of(self.upload_floats_per_client, "f32")

    @property
    def downlink_value_bytes(self) -> int:
        """Bytes per broadcast value on the downlink: wire width
        under --downlink_encoding delta (values ship quantized), f32
        under dense."""
        from commefficient_tpu import accounting
        if self.downlink_encoding == "delta":
            return accounting.dtype_bytes(self.sketch_dtype)
        return accounting.dtype_bytes("f32")

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


def build_parser(default_lr: Optional[float] = None,
                 model_names: Optional[Sequence[str]] = None
                 ) -> argparse.ArgumentParser:
    """Argparse surface — same flags as reference utils.py:102-214."""
    parser = argparse.ArgumentParser()

    # meta-args
    parser.add_argument("--test", action="store_true", dest="do_test")
    parser.add_argument("--mode", choices=MODES, default="sketch")
    parser.add_argument("--profile", action="store_true",
                        dest="do_profile")
    parser.add_argument("--bf16", action="store_true", dest="do_bf16")
    parser.add_argument("--seq_devices", type=int, default=1)
    parser.add_argument("--seq_impl", choices=["ring", "ulysses"],
                        default="ring")
    parser.add_argument("--dropout_prob", type=float, default=0.0)
    parser.add_argument("--mixup", action="store_true", dest="do_mixup")
    parser.add_argument("--mixup_alpha", type=float, default=1.0)
    parser.add_argument("--tensorboard", dest="use_tensorboard",
                        action="store_true")
    parser.add_argument("--seed", type=int, default=21)

    # data/model args
    if model_names is None:
        from commefficient_tpu import models
        model_names = models.model_names()
    parser.add_argument("--model", default="ResNet9",
                        choices=model_names or None,
                        help="cv_train: a CV model; gpt2_train: "
                        "GPT2DoubleHeads on PERSONA (any other value), or "
                        "a causal LM on --dataset_name TOKENS: "
                        "JoyAIFlashLM, NemotronHLM, GraniteHybridLM "
                        "(architecture from "
                        "config.json in --model_checkpoint, whose "
                        "model_type must be the model's)")
    parser.add_argument("--finetune", action="store_true", dest="do_finetune")
    parser.add_argument("--checkpoint", action="store_true",
                        dest="do_checkpoint")
    parser.add_argument("--resume", action="store_true",
                        dest="do_resume")
    parser.add_argument("--checkpoint_every", type=int, default=0)
    parser.add_argument("--checkpoint_path", type=str, default="./checkpoint")
    parser.add_argument("--finetune_path", type=str, default="./finetune")
    parser.add_argument("--finetuned_from", type=str,
                        choices=list(FED_DATASETS.keys()))
    parser.add_argument("--num_results_train", type=int, default=2)
    parser.add_argument("--num_results_val", type=int, default=2)
    parser.add_argument("--dataset_name", type=str, default="",
                        choices=list(FED_DATASETS.keys()))
    parser.add_argument("--dataset_dir", type=str, default="./dataset")
    parser.add_argument("--batchnorm", action="store_true",
                        dest="do_batchnorm")
    parser.add_argument("--nan_threshold", type=float, default=999)

    # compression args
    parser.add_argument("--k", type=int, default=50000)
    parser.add_argument("--num_cols", type=int, default=500000)
    parser.add_argument("--num_rows", type=int, default=5)
    parser.add_argument("--num_blocks", type=int, default=20)
    parser.add_argument("--topk_down", action="store_true",
                        dest="do_topk_down")

    # optimization args
    parser.add_argument("--local_momentum", type=float, default=0.9)
    parser.add_argument("--virtual_momentum", type=float, default=0)
    parser.add_argument("--weight_decay", type=float, default=5e-4)
    parser.add_argument("--num_epochs", type=float, default=24)
    parser.add_argument("--schedule_epochs", type=float, default=None)
    parser.add_argument("--num_fedavg_epochs", type=int, default=1)
    parser.add_argument("--fedavg_batch_size", type=int, default=-1)
    parser.add_argument("--fedavg_lr_decay", type=float, default=1)
    parser.add_argument("--error_type", choices=ERROR_TYPES, default="none")
    parser.add_argument("--lr_scale", type=float, default=default_lr)
    parser.add_argument("--pivot_epoch", type=float, default=5)

    # parallelization args
    parser.add_argument("--port", type=int, default=5315)
    parser.add_argument("--num_clients", type=int)
    parser.add_argument("--num_workers", type=int, default=1)
    parser.add_argument("--device", type=str,
                        choices=["cpu", "tpu", "cuda"], default=None,
                        help="platform the run must be on (default: "
                        "the one JAX reports); naming one that JAX "
                        "did not find is an error")
    parser.add_argument("--num_devices", type=int, default=-1)
    parser.add_argument("--share_ps_gpu", action="store_true")
    parser.add_argument("--iid", action="store_true", dest="do_iid")
    parser.add_argument("--train_dataloader_workers", type=int, default=0)
    parser.add_argument("--val_dataloader_workers", type=int, default=0)

    # GPT2 args
    parser.add_argument("--model_checkpoint", type=str, default="gpt2")
    parser.add_argument("--num_candidates", type=int, default=2)
    parser.add_argument("--val_candidates", type=int, default=0)
    parser.add_argument("--max_history", type=int, default=2)
    parser.add_argument("--local_batch_size", type=int, default=8)
    parser.add_argument("--valid_batch_size", type=int, default=8)
    parser.add_argument("--microbatch_size", type=int, default=-1)
    parser.add_argument("--lm_coef", type=float, default=1.0)
    parser.add_argument("--mc_coef", type=float, default=1.0)
    parser.add_argument("--max_grad_norm", type=float)
    parser.add_argument("--personality_permutations", type=int, default=1)
    parser.add_argument("--eval_before_start", action="store_true")

    # differential privacy args
    parser.add_argument("--dp", choices=["off", "sketch"],
                        default="off",
                        help="DP sketching (privacy/): clip each "
                        "client's dense gradient to --dp_clip and "
                        "add calibrated Gaussian noise to the "
                        "aggregated sketch table before wire "
                        "quantization; an RDP accountant rides the "
                        "ledger")
    parser.add_argument("--dp_clip", type=float, default=1.0,
                        help="per-client L2 clip cap for --dp sketch")
    parser.add_argument("--dp_noise_mult", type=float, default=0.0,
                        help="noise multiplier σ for --dp sketch "
                        "(noise std = σ × per-client table "
                        "sensitivity)")
    parser.add_argument("--dp_delta", type=float, default=1e-5,
                        help="accountant δ for the ε(δ) conversion")
    parser.add_argument("--dp_epsilon", type=float, default=0.0,
                        help="total ε budget (0 = unlimited): arms "
                        "the privacy_budget_exhausted alarm and "
                        "hard-constrains the autopilot ladder")
    # legacy reference-parity worker/server DP (was spelled --dp
    # before the sketch mechanism took that flag)
    parser.add_argument("--do_dp", action="store_true", dest="do_dp")
    parser.add_argument("--dp_mode", choices=DP_MODES, default="worker")
    parser.add_argument("--l2_norm_clip", type=float, default=1.0)
    parser.add_argument("--noise_multiplier", type=float, default=0.0)

    # TPU-native additions
    parser.add_argument("--mesh", type=str, default="",
                        help="2D pod mesh 'CxM': C devices "
                        "data-parallel over clients x M devices "
                        "sharding server state over model (sketch/"
                        "uncompressed modes; per-device server memory "
                        "~1/M). Default: 1-D clients mesh")
    parser.add_argument("--param_dtype", type=str, default="float32")
    parser.add_argument("--compute_dtype", type=str, default="float32")
    parser.add_argument("--approx_topk", action="store_true")
    parser.add_argument("--approx_recall", type=float, default=0.95)
    parser.add_argument("--classes_per_client", type=int, default=1)
    parser.add_argument("--synthetic_per_class", type=int, default=64)
    parser.add_argument("--synthetic_separation", type=float,
                        default=1.0)
    parser.add_argument("--synthetic_num_val", type=int, default=128)
    parser.add_argument("--hf_export", action="store_true",
                        dest="do_hf_export")
    parser.add_argument("--coordinator_address", type=str,
                        default=None)
    parser.add_argument("--num_processes", type=int, default=None)
    parser.add_argument("--process_id", type=int, default=None)
    parser.add_argument("--remat", action="store_true",
                        dest="do_remat")
    parser.add_argument("--tokens_per_chunk", type=int, default=0,
                        help="tokens per logits chunk in the chunked "
                        "vocab cross-entropy (0 = auto)")
    parser.add_argument("--fused_ce", type=str, default="off",
                        choices=["auto", "on", "off"],
                        help="fused-linear-CE vocab head (Pallas; "
                        "ops/flce_pallas.py): auto = on at TPU "
                        "default backend, chunked elsewhere")
    parser.add_argument("--attn_impl", type=str, default="xla",
                        choices=["xla", "flash"],
                        help="GPT-2 attention lowering: XLA fusion or "
                        "the Pallas TPU flash-attention kernel")
    parser.add_argument("--sketch_rot_lanes", type=int, default=-1,
                        help="quantize sketch rotations to multiples "
                        "of this lane width (-1 = auto: 1024 on TPU "
                        "at large-d Pallas-eligible geometries, else "
                        "0; 0 = force full granularity); speeds the "
                        "Pallas kernels' rolls")
    parser.add_argument("--sketch_dtype", type=str, default="f32",
                        choices=list(SKETCH_DTYPES),
                        help="wire dtype of the uplinked sketch "
                        "table (sketch mode): f32 (bit-identical "
                        "program to a build without the flag), bf16, "
                        "or int8/fp8 with per-row scales — emission "
                        "stays f32, the table quantizes before the "
                        "all-reduce/reduce-scatter, the server "
                        "dequantizes before momentum/error feedback")
    parser.add_argument("--downlink_encoding", type=str,
                        default="dense",
                        choices=list(DOWNLINK_ENCODINGS),
                        help="downlink byte encoding: dense f32 "
                        "coordinates, or delta — (idx:int32, "
                        "val:wire_dtype) pairs plus a bitmap over "
                        "the previous round's support for repeated "
                        "indices (accounting-level; the compiled "
                        "program is unchanged)")
    parser.add_argument("--overlap_depth", type=int, default=1,
                        help="latency-hiding round pipeline (sketch "
                        "mode): emit the table in min(N, rows) row "
                        "chunks and overlap each chunk's wire "
                        "collective with the next chunk's "
                        "emit+quantize (1 = serial round, "
                        "bit-identical program)")
    parser.add_argument("--client_chunk", type=int, default=0,
                        help="scan the round's client fan-out in "
                        "chunks of this many clients (0 = all at "
                        "once) — memory lever for large rounds of "
                        "the local-state modes on one chip")
    parser.add_argument("--clientstore", type=str, default="device",
                        choices=["device", "host", "auto"],
                        help="per-client state placement: dense HBM "
                        "arrays (device), budgeted host arena + mmap "
                        "spill with per-round participant gather "
                        "(host), or resolve by footprint vs "
                        "--clientstore_bytes (auto)")
    parser.add_argument("--clientstore_bytes", type=int,
                        default=1 << 30,
                        help="host client-store arena budget in bytes "
                        "(rows beyond it spill to mmap)")
    parser.add_argument("--clientstore_dir", type=str, default="",
                        help="client-store spill directory "
                        "(default: private temp dir)")
    parser.add_argument("--ledger", type=str, default="",
                        help="write one JSONL telemetry record per "
                        "training round to this path (spans, comm "
                        "bytes, memory watermarks; see "
                        "scripts/telemetry_report.py)")
    parser.add_argument("--telemetry_console", action="store_true",
                        help="print an end-of-run summary of the "
                        "round telemetry (span totals/means, bytes)")
    parser.add_argument("--probe_every", type=int, default=0,
                        help="algorithm probes (ledger schema v2): "
                        "cheap norm/NaN probes every round, the "
                        "sketch-recovery-error probe every N rounds "
                        "(0 = probes off, no compiled overhead)")
    parser.add_argument("--probe_full", action="store_true",
                        help="shorthand for --probe_every 1")
    parser.add_argument("--on_divergence", type=str, default="log",
                        choices=["log", "ledger-flag", "abort"],
                        help="alarm action when a probe rule fires "
                        "(NaN/Inf, residual growth, recovery error): "
                        "warn, flag the ledger record, or abort the "
                        "run at the offending round")
    parser.add_argument("--alarm_residual_ratio", type=float,
                        default=2.0,
                        help="fire when the error-feedback residual "
                        "norm grows by more than this ratio for "
                        "--alarm_residual_rounds consecutive rounds")
    parser.add_argument("--alarm_residual_rounds", type=int, default=3)
    parser.add_argument("--alarm_recovery_error", type=float,
                        default=1.0,
                        help="fire when relative sketch-recovery "
                        "error exceeds this")
    parser.add_argument("--alarm_step_time_ratio", type=float,
                        default=0.0,
                        help="step_time_regression rule: fire when a "
                        "round's wall step time exceeds this ratio x "
                        "the rolling median (0 = off; action from "
                        "--on_divergence)")
    parser.add_argument("--alarm_step_time_window", type=int,
                        default=16,
                        help="rolling-median window (rounds) for "
                        "--alarm_step_time_ratio")
    parser.add_argument("--alarm_collective_skew", type=float,
                        default=0.0,
                        help="collective_skew rule: fire when a traced "
                        "round's max cross-device collective "
                        "enter-delta exceeds this ratio x its "
                        "collective seconds (0 = off; needs --profile; "
                        "action from --on_divergence)")
    parser.add_argument("--robust_agg", type=str, default="none",
                        choices=list(ROBUST_AGGS),
                        help="robust fold over per-client transmits: "
                        "median (coordinate-wise median of sketch "
                        "groups), trimmed (trimmed mean), clip "
                        "(norm-clipped fold). Rejected mass never "
                        "enters the error-feedback residuals.")
    parser.add_argument("--robust_trim_frac", type=float, default=0.1,
                        help="fraction trimmed from each tail per "
                        "coordinate under --robust_agg trimmed")
    parser.add_argument("--robust_clip_norm", type=float, default=0.0,
                        help="per-client transmit-norm clip threshold "
                        "under --robust_agg clip (0 = auto: median of "
                        "alive per-client norms)")
    parser.add_argument("--robust_median_groups", type=int, default=0,
                        help="number of client groups for "
                        "median-of-sketch-groups (0 = every client "
                        "its own group; must divide --num_workers)")
    parser.add_argument("--alarm_byzantine_ratio", type=float,
                        default=0.0,
                        help="byzantine_suspect rule: fire when "
                        "max/mean per-client transmit norm exceeds "
                        "this ratio (0 = off; needs probes; action "
                        "from --on_divergence)")
    parser.add_argument("--alarm_fold_rejection", type=float,
                        default=0.0,
                        help="fold_rejection_rate rule: fire when the "
                        "robust fold deviates from the plain mean by "
                        "more than this relative rate (0 = off; needs "
                        "probes; action from --on_divergence)")
    parser.add_argument("--checkpoint_every_rounds", type=int,
                        default=0,
                        help="autosave the checkpoint every N rounds "
                        "(0 = off; independent of the epoch-cadence "
                        "--checkpoint_every)")
    parser.add_argument("--checkpoint_keep", type=int, default=0,
                        help="history snapshots retained by the round "
                        "autosaver (0 = latest only)")
    parser.add_argument("--async_buffer_size", type=int, default=0,
                        help="fold the arrival buffer every K arrived "
                        "clients instead of barriering on the cohort "
                        "(0 = synchronous; K <= --num_workers)")
    parser.add_argument("--async_staleness_weight", type=float,
                        default=0.0,
                        help="staleness exponent alpha: an update "
                        "folded s rounds late is weighted "
                        "1/(1+s)^alpha (0 = unweighted; at K = cohort "
                        "it reduces bit-exactly to the sync round)")
    parser.add_argument("--alarm_async_staleness", type=float,
                        default=0.0,
                        help="async_staleness rule: fire when the "
                        "round's max folded staleness exceeds this "
                        "many rounds (0 = off; action from "
                        "--on_divergence)")
    parser.add_argument("--alarm_job_starvation", type=float,
                        default=0.0,
                        help="job_starvation rule (fedservice "
                        "daemon): fire when a runnable job waited "
                        "more than this many scheduler ticks since "
                        "it last ran (0 = off; action from "
                        "--on_divergence)")
    parser.add_argument("--live_port", type=int, default=0,
                        help="serve live metrics (Prometheus text "
                        "exposition) from a localhost-only exporter "
                        "thread at this port: /metrics + /healthz "
                        "(0 = off, nothing constructed)")
    parser.add_argument("--flightrec_rounds", type=int, default=0,
                        help="flight recorder: keep the last N round "
                        "records in memory and dump an atomic "
                        "postmortem bundle on alarm fire / graceful "
                        "shutdown / crash (0 = off)")
    parser.add_argument("--postmortem_dir", type=str,
                        default="runs/postmortems",
                        help="directory postmortem bundles land in")
    parser.add_argument("--slo_round_p95", type=float, default=0.0,
                        help="SLO round-latency objective: a round "
                        "slower than this many seconds is a "
                        "violation (0 = objective off)")
    parser.add_argument("--slo_staleness_max", type=float,
                        default=0.0,
                        help="SLO staleness objective: a round whose "
                        "max folded staleness exceeds this many "
                        "rounds is a violation (0 = off)")
    parser.add_argument("--slo_eps_rounds", type=int, default=0,
                        help="SLO privacy-burn objective: ε must "
                        "stay under the linear spend schedule "
                        "--dp_epsilon * (round+1) / horizon over "
                        "this many rounds (0 = off; needs --dp "
                        "sketch with a hard --dp_epsilon)")
    parser.add_argument("--slo_starvation", type=float, default=0.0,
                        help="SLO starvation objective (fedservice "
                        "daemon): a tick whose max job wait exceeds "
                        "this many ticks is a violation (0 = off)")
    parser.add_argument("--slo_error_budget", type=float,
                        default=0.05,
                        help="fraction of windowed rounds allowed to "
                        "violate an SLO before its burn rate reads "
                        "1.0")
    parser.add_argument("--slo_window", type=int, default=32,
                        help="slow rolling window (rounds) for the "
                        "multi-window burn rate")
    parser.add_argument("--slo_fast_window", type=int, default=8,
                        help="fast rolling window (rounds); burn = "
                        "min(fast, slow rate) / error budget")
    parser.add_argument("--alarm_slo_burn", type=float, default=0.0,
                        help="slo_burn rule: fire when the worst "
                        "per-objective burn rate (slo_burn_max) "
                        "reaches this (0 = off; action from "
                        "--on_divergence)")
    parser.add_argument("--autopilot", type=str, default="off",
                        choices=["off", "on"],
                        help="adaptive compression autopilot "
                        "(commefficient_tpu/autopilot): walk the "
                        "discrete knob lattice (sketch_dtype x k x "
                        "rows x cols x recall) toward the cheapest "
                        "round program whose recovery error stays "
                        "inside --autopilot_band, re-jitting round "
                        "variants through a bounded LRU cache. off "
                        "(default) compiles bit-identical to a build "
                        "without the flag")
    parser.add_argument("--autopilot_band", type=str, default="",
                        help="target recovery-error band LO:HI "
                        "(required with --autopilot on); cheapen "
                        "below LO after the cooldown, back off above "
                        "HI immediately and never re-enter the "
                        "offending point")
    parser.add_argument("--autopilot_cooldown", type=int, default=2,
                        help="in-band probed rounds between "
                        "cheapening moves (back-off ignores it)")
    parser.add_argument("--autopilot_cache_size", type=int, default=4,
                        help="round-variant LRU bound; evicted "
                        "variants recompile on re-visit (ledger-"
                        "stamped)")
    parser.add_argument("--autopilot_warm_ahead", type=int, default=1,
                        help="1 = AOT-compile a decided move's round "
                        "variant under the current round's host "
                        "phase; 0 = lazy compile at the switch "
                        "round's dispatch")
    parser.add_argument("--autopilot_pin", type=str, default="",
                        help="hold the controller at one lattice "
                        "point (variant-key spelling, e.g. "
                        "int8-k50000-r5-c500000-re9500) — full "
                        "autopilot machinery, zero moves, "
                        "bit-identical to the equivalent static "
                        "config")
    parser.add_argument("--autopilot_geometry", action="store_true",
                        help="extend the knob ladder past the dtype "
                        "axis into column-halving geometry steps "
                        "(a geometry move resets server momentum/"
                        "error feedback)")

    return parser


def parse_args(default_lr: Optional[float] = None, argv=None) -> Config:
    parser = build_parser(default_lr)
    ns = parser.parse_args(argv)
    field_names = {f.name for f in dataclasses.fields(Config)}
    kw = {k: v for k, v in vars(ns).items() if k in field_names}
    return Config(**kw)

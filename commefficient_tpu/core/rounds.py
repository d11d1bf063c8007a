"""The federated round as a single SPMD program.

Where the reference runs a round as: queue batches to worker processes
→ each worker loops over its clients serially → NCCL-reduce the summed
transmit → server step on the PS rank (call stack in SURVEY.md §3.1),
here a round is two jitted functions over a ``clients`` mesh:

- ``client_round``: vmap of the per-client local step over the W
  participating clients (sharded across devices), returning the summed
  transmit (one XLA all-reduce), per-client metrics, and updated
  per-client momentum/error rows;
- ``server_round``: the deterministic server update, replicated.

They are split (rather than fused) to mirror the reference's
FedModel.__call__ / FedOptimizer.step protocol — the LR scheduler sits
between them on the host (cv_train.py:198) — but both stay on device;
only scalar metrics ever cross to the host.

Batch layout: a dict of (W, B, ...) arrays with a (W, B) float "mask"
marking real samples — ragged client batches become static shapes via
padding (SURVEY.md §7). ``client_ids`` is (W,) int32.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.core.client import (accumulate_and_compress,
                                           stale_weight_download)
from commefficient_tpu.core.grad import make_eval_metrics, make_forward_grad
from commefficient_tpu.core.server import (ServerState, ServerUpdate,
                                           server_update,
                                           staleness_weights)
from commefficient_tpu.ops.sketch import CountSketch
from commefficient_tpu.parallel.mesh import SHARED_CLIENTS


class ClientStates(NamedTuple):
    """Per-client persistent state, rows sharded over the mesh
    (reference: host shared-memory tensors, fed_aggregator.py:105-129).
    Fields a mode doesn't use are None — never allocated."""
    velocities: Optional[jax.Array]  # (num_clients, *transmit_shape)
    errors: Optional[jax.Array]      # (num_clients, *transmit_shape)
    weights: Optional[jax.Array]     # (num_clients, grad_size), topk_down only

    @staticmethod
    def init(cfg: Config, num_clients: int,
             ps_weights: Optional[jax.Array] = None,
             sharding=None) -> "ClientStates":
        """``sharding`` (a NamedSharding over the client axis) creates
        the big (rows, ...) buffers directly sharded — at
        EMNIST/PERSONA scale a replicated allocation would not fit one
        device. NamedSharding requires the leading dim to divide the
        mesh, so rows are padded up to the next multiple; padded rows
        are never indexed (client ids < num_clients)."""
        rows = num_clients
        if sharding is not None:
            from commefficient_tpu.parallel.mesh import padded_rows
            rows = padded_rows(num_clients, sharding.mesh)
        shape = (rows,) + cfg.transmit_shape
        vel = (jnp.zeros(shape, jnp.float32, device=sharding)
               if cfg.local_momentum > 0 else None)
        err = (jnp.zeros(shape, jnp.float32, device=sharding)
               if cfg.error_type == "local" else None)
        wts = None
        if cfg.do_topk_down:
            assert ps_weights is not None
            wts = (jnp.zeros((rows, cfg.grad_size), jnp.float32,
                             device=sharding) + ps_weights[None, :])
        return ClientStates(vel, err, wts)


class RoundResult(NamedTuple):
    aggregated: jax.Array        # transmit-sum / total datapoints
    metrics: tuple               # per-client batch-mean metrics, each (W,)
    client_states: ClientStates
    # (stats_pytree, alive_scalar) when a stats_fn is configured —
    # the sample-weighted mean of participating clients' batch
    # statistics this round (BatchNorm running-stats parity mode)
    bn_stats: Optional[tuple] = None
    # schema-v2 client-pass probe scalars (--probe_every): aggregate
    # norm + NaN/Inf counts, per-client transmit-norm statistics
    # (paths that materialise per-client transmits), and — on probe
    # cadence rounds in sketch mode — the true recovery error against
    # the dense gradient. None unless the round was built with
    # ``probes=True``; probes-off builds stay HLO-identical.
    probes: Optional[dict] = None


_AUTO_ROT_LANES = 1024


def resolve_rot_lanes(cfg: Config) -> int:
    """Resolve ``--sketch_rot_lanes -1`` (auto, the default).

    Quantized rotations pay a heavier collision tail (rot_lanes/c for
    same-lane-offset pairs instead of 1/c) and buy a rotation by
    address (no roll at all) ONLY inside the Pallas TPU kernels — so
    auto engages 1024 exactly where that trade was measured to win
    with no quality cost:
    a TPU default backend at a Pallas-supported, lane-aligned,
    large-d geometry (−44% on the sketch/estimates kernel pair at
    d=124M, −8% on the flagship GPT-2 federated round; 24-epoch
    anchor tail accuracy at parity with full-granularity rotations at
    both seeds: round 5's chip, another machine). Everywhere else
    auto resolves to 0 (full granularity). Explicit values pass
    through untouched. The default-backend probe lives here, NOT in
    CountSketch.__post_init__: round build runs after any
    jax.distributed initialization / platform selection."""
    lanes = getattr(cfg, "sketch_rot_lanes", 0)
    if lanes >= 0:
        return lanes
    from commefficient_tpu.ops.sketch_pallas import rotation_form, supported
    d, c, r = cfg.grad_size, cfg.num_cols, cfg.num_rows
    # 1,024 is one float32 vreg: auto quantizes exactly where the
    # kernels then address the table by whole vregs (at rot_lanes 0
    # they roll every chunk), and c leaves at least 8 such rotations
    if (d < (1 << 20) or not supported(d, c, r)
            or c // _AUTO_ROT_LANES < 8
            or rotation_form(c, r, _AUTO_ROT_LANES) != "addressed"):
        return 0
    return _AUTO_ROT_LANES if jax.default_backend() == "tpu" else 0


def sketch_is_late(cfg: Config) -> bool:
    """Sketch-mode fast path predicate: sketching after the local
    dense sum (linearity) is legal whenever no per-client op touches
    the table — i.e. absent ``max_grad_norm``'s per-sketch clip.
    Robust folds need per-client sketches (median-of-sketches), so
    ``--robust_agg`` also forces the early-sketch path."""
    return (cfg.mode == "sketch" and cfg.max_grad_norm is None
            and getattr(cfg, "robust_agg", "none") == "none")


def fused_grad_eligible(cfg: Config) -> bool:
    """Fused-gradient fast path predicate: the aggregated quantity is
    exactly the gradient of the sample-weighted mean loss (one
    backward, no (W, d) buffer) when no per-client transform touches
    the gradient. Shared by ``build_client_round`` and
    ``round_plan`` so the telemetry meta record cannot drift from the
    program actually built."""
    return (cfg.mode in ("sketch", "uncompressed", "true_topk")
            and cfg.local_momentum == 0 and cfg.error_type != "local"
            and not cfg.do_topk_down and not cfg.do_dp
            and getattr(cfg, "dp", "off") == "off"
            and cfg.max_grad_norm is None and cfg.microbatch_size <= 0
            and getattr(cfg, "robust_agg", "none") == "none")


def round_plan(cfg: Config) -> dict:
    """Static description of the round program this Config builds —
    which fast paths engage, what one client transmits, what the
    geometry is. Logged once per run as the ledger's meta record
    (telemetry/record.py) so a ledger is interpretable without the
    launching command line."""
    plan = {
        "mode": cfg.mode,
        "error_type": cfg.error_type,
        "grad_size": int(cfg.grad_size),
        "num_workers": int(cfg.num_workers),
        "transmit_shape": list(cfg.transmit_shape),
        "upload_floats_per_client": int(cfg.upload_floats_per_client),
        "fused_grad": fused_grad_eligible(cfg),
        "robust_agg": getattr(cfg, "robust_agg", "none"),
        "client_chunk": int(getattr(cfg, "client_chunk", 0)),
        "overlap_depth": int(getattr(cfg, "overlap_depth", 1)),
        "clientstore": getattr(cfg, "clientstore", "device"),
        "async_buffer_size": int(getattr(cfg, "async_buffer_size", 0)
                                 or 0),
        "async_staleness_weight": float(
            getattr(cfg, "async_staleness_weight", 0.0) or 0.0),
    }
    plan["sketch_dtype"] = getattr(cfg, "sketch_dtype", "f32")
    plan["downlink_encoding"] = getattr(cfg, "downlink_encoding",
                                        "dense")
    if getattr(cfg, "dp", "off") != "off":
        # enough to re-derive the accountant (and the registry's
        # p<eps> key fragment) from the ledger alone
        plan["dp"] = {"mode": str(cfg.dp),
                      "clip": float(cfg.dp_clip),
                      "noise_mult": float(cfg.dp_noise_mult),
                      "delta": float(cfg.dp_delta),
                      "epsilon_budget": float(cfg.dp_epsilon)}
    plan["upload_wire_bytes_per_client"] = float(
        cfg.upload_wire_bytes_per_client)
    if cfg.mode == "sketch":
        plan["sketch"] = {"rows": int(cfg.num_rows),
                          "cols": int(cfg.num_cols),
                          "blocks": int(cfg.num_blocks),
                          "k": int(cfg.k),
                          "late": sketch_is_late(cfg),
                          "rot_lanes": resolve_rot_lanes(cfg)}
    if cfg.mode in ("true_topk", "local_topk"):
        plan["k"] = int(cfg.k)
    if str(getattr(cfg, "autopilot", "off")) == "on":
        # knob-lattice walk parameters: enough to interpret (and
        # replay-check) a ledger whose rounds were dispatched through
        # the bucketed re-jit cache rather than one static program
        from commefficient_tpu.autopilot.lattice import (build_ladder,
                                                         key_of,
                                                         key_str)
        plan["autopilot"] = {
            "band": str(cfg.autopilot_band),
            "cooldown": int(cfg.autopilot_cooldown),
            "cache_size": int(cfg.autopilot_cache_size),
            "warm_ahead": bool(cfg.autopilot_warm_ahead),
            "pin": str(getattr(cfg, "autopilot_pin", "") or ""),
            "base": key_str(key_of(cfg)),
            "ladder": [key_str(k) for k in build_ladder(cfg)],
        }
    return plan


def args2sketch(cfg: Config) -> Optional[CountSketch]:
    """(reference fed_aggregator.py:466-469)"""
    if cfg.mode != "sketch":
        return None
    return CountSketch(d=cfg.grad_size, c=cfg.num_cols, r=cfg.num_rows,
                       num_blocks=cfg.num_blocks, seed=cfg.seed,
                       approx_topk=cfg.approx_topk,
                       approx_recall=cfg.approx_recall,
                       rot_lanes=resolve_rot_lanes(cfg))


def server_select_form(cfg: Config, mesh=None):
    """``(form, candidates)`` of the selection the server round built
    from ``cfg`` on ``mesh`` makes (``CountSketch.select_form``; the
    model-sharded round's distributed select looks at every estimate);
    None outside sketch mode, whose server rounds recover nothing."""
    sketch = args2sketch(cfg)
    if sketch is None:
        return None
    from commefficient_tpu.parallel.mesh import model_axis_size
    if model_axis_size(mesh) > 1:
        return "flat", sketch.d
    return sketch.select_form(cfg.k)


def sketch_rot_form(cfg: Config) -> Optional[str]:
    """The form the sketch kernels of the rounds built from ``cfg``
    apply a rotation in (``CountSketch.rot_form``); None outside
    sketch mode."""
    sketch = args2sketch(cfg)
    return None if sketch is None else sketch.rot_form


def build_client_round(cfg: Config, loss_fn: Optional[Callable],
                       padded_batch_size: int,
                       mesh=None, stats_fn: Callable = None,
                       tree_loss: Callable = None,
                       unravel: Callable = None,
                       dense_rows: bool = False,
                       probes: bool = False,
                       probe_recovery: bool = False,
                       transmit_transform: Callable = None,
                       client_weights: bool = False) -> Callable:
    """Returns jit-able
    ``client_round(ps_weights, client_states, batch, client_ids, rng,
    fedavg_lr) -> RoundResult``.

    ``client_weights=True`` (the asyncfed buffered-arrival driver)
    appends a seventh argument — ``staleness``, (W,) float32 rounds
    each folded update waited in the arrival buffer — and compiles
    the staleness-weighted fold into the round: each client's
    transmit AND its datapoint count scale by
    ``1/(1+staleness)^{--async_staleness_weight}`` before the fold
    (core/server.staleness_weights), so the aggregate stays a
    weighted per-datapoint mean and stale mass never corrupts the
    server's virtual momentum/EF. At alpha == 0 the weighting branch
    is skipped at trace time (weights are identically 1), which is
    what makes the degenerate K == cohort configuration bit-exact
    against the synchronous round; the default ``False`` traces
    nothing and async-off builds stay HLO-identical.

    ``probes=True`` fills ``RoundResult.probes`` with the cheap O(d)
    diagnostics (aggregate norm/NaN/Inf, per-client transmit-norm
    stats where per-client transmits exist). ``probe_recovery=True``
    (sketch mode, the ``--probe_every`` cadence variant) additionally
    computes the TRUE recovery error ‖unsketch(S(g)) − g‖/‖g‖ against
    the dense aggregated gradient — paths where the dense aggregate
    doesn't naturally exist materialise it only in this variant (the
    clipped per-client-sketch path cannot and omits the key). Both are
    trace-time flags: with both False the emitted program is identical
    to a build without them.

    ``dense_rows``: host-clientstore mode (runtime/fed_model.py) — the
    ``client_states`` arrays hold ONLY the round's W participant rows
    (gathered host-side, ordered like ``client_ids``), so state rows
    are indexed by POSITION while the RNG folding below keeps the real
    client ids: every per-client stream is bit-identical to the
    device-resident path.

    Sketch-mode fast path: because sketching is linear and (absent
    ``max_grad_norm``'s per-sketch clip) no per-client op touches the
    table, each device sums its local clients' *dense* gradients and
    sketches **once**, then a single psum of (r, c) tables crosses the
    ICI — identical math to per-client sketching (the FetchSGD
    linearity identity), at 1/clients_per_device the sketch cost and
    with compressed inter-chip traffic. Pass ``mesh`` to enable; falls
    back to sketch-of-local-sum without one.

    ``transmit_transform``: optional traceable
    ``(transmit, batch, client_ids, rng) -> transmit`` applied to the
    materialised per-client transmit stack before the fold — the
    chaos harness's byzantine-attack hook (data/chaos.py; this module
    deliberately never imports chaos). Passing one forces the
    per-client path (the fused program has no per-client transmits);
    the default ``None`` is never traced, keeping the round program
    bit-identical to a build without the parameter.
    """
    cfg.validate_runtime()
    # recovery needs probes on and a sketch to recover from
    probe_recovery = bool(probes and probe_recovery
                          and cfg.mode == "sketch")
    if loss_fn is None:
        # flat loss derived from the tree loss: callers holding a
        # pytree-level loss need not duplicate the unravel closure
        assert tree_loss is not None and unravel is not None, \
            "need loss_fn, or tree_loss + unravel to derive it"

        def loss_fn(p, b):
            return tree_loss(unravel(p), b)

    sketch = args2sketch(cfg)
    sketch_late = sketch_is_late(cfg)
    # Trace-time gate: robust folds replace the mean over materialised
    # per-client transmits; at the default "none" the branch below is
    # never traced and the round program is bit-identical to today's
    # (pinned by test_probes_off_program_identical).
    robust = getattr(cfg, "robust_agg", "none") != "none"
    if transmit_transform is not None:
        assert getattr(cfg, "client_chunk", 0) == 0, \
            "transmit_transform needs the full per-client transmit " \
            "stack; incompatible with --client_chunk"
    # Staleness-weighted fold (asyncfed): a trace-time gate like
    # probes/robust. alpha == 0 means every weight is exactly 1, so
    # the branch is skipped and a K == cohort buffered fold is
    # bit-identical to the synchronous round.
    alpha = float(getattr(cfg, "async_staleness_weight", 0.0))
    weighted = client_weights and alpha != 0.0
    if client_weights:
        assert getattr(cfg, "client_chunk", 0) == 0, \
            "client_weights needs the full per-client transmit " \
            "stack; incompatible with --client_chunk"
    # Fused-gradient fast path: when no per-client transform touches
    # the gradient (no local momentum/error, clip, DP, topk_down or
    # microbatching), the aggregated quantity is exactly the gradient
    # of the sample-weighted mean loss over ALL clients' real samples
    # (+ the analytic weight-decay term). One backward pass then
    # accumulates straight into a single (d,) vector — the (W, d)
    # per-client gradient buffer, its dynamic-update-slices and the
    # cross-client reduction disappear from the program. On a mesh
    # (clients divisible across devices) each device runs the fused
    # backward over its local clients and ONE psum crosses the ICI —
    # of (r, c) sketch tables in sketch mode (compressed traffic, the
    # FetchSGD linearity identity), of the dense gradient otherwise.
    fused_grad = (fused_grad_eligible(cfg)
                  and transmit_transform is None)
    if cfg.mode == "fedavg":
        per_client = _build_fedavg_client_step(cfg, loss_fn,
                                               padded_batch_size)
    elif fused_grad:
        per_client = None
    else:
        step_cfg = cfg.replace(mode="uncompressed", error_type="none",
                               grad_size=cfg.grad_size) \
            if sketch_late else cfg
        per_client = _build_sgd_client_step(step_cfg, loss_fn,
                                            None if sketch_late else sketch,
                                            padded_batch_size)

    # Tree-space backward for the fused sketch path: differentiate
    # w.r.t. the PARAM PYTREE and sketch the leaf gradients directly
    # (CountSketch.sketch_from_leaves). Mathematically identical to
    # the flat-primal path — the flat gradient is exactly the
    # concatenation of the leaf gradients — but autodiff's
    # transpose-of-unravel (a d-sized concatenate) and sketch's pad
    # copy collapse into the kernel-input assembly, removing two
    # 124M-coord copies per round at GPT-2 scale (round-3 xplane
    # "concat/pad ~6 ms", VERDICT weak #5).
    tree_sketch = (cfg.mode == "sketch" and tree_loss is not None
                   and unravel is not None)

    # Quantized wire path (--sketch_dtype, ops/quant.py): a trace-time
    # gate like probes/robust — at the default "f32" none of the
    # branches below are traced and the round program stays
    # bit-identical (pinned by test_quant_f32_program_identical).
    wire = getattr(cfg, "sketch_dtype", "f32")
    quantized = cfg.mode == "sketch" and wire != "f32"

    # DP sketching (--dp sketch, privacy/): the calibrated Gaussian
    # noise lands on the f32 AGGREGATED table — after the fold's
    # datapoint normalisation, before any wire quantization — so the
    # released value is exactly what the accountant charges for and
    # the int8/fp8 qdq that follows is free post-processing. Inner
    # per-client / collective quantization is therefore disabled
    # under DP (tables cross at f32) and the round's one qdq runs on
    # the noisy table below. Trace-time gate: "off" traces nothing
    # and the program is bit-identical to a build without the flag.
    dp_on = getattr(cfg, "dp", "off") == "sketch"
    dp_qdq = quantized and dp_on
    if dp_on:
        quantized = False

    # Latency-hiding round pipeline (--overlap_depth, sketch mode):
    # emit and cross the table in min(depth, r) disjoint row chunks,
    # each chunk's collective issued as soon as its rows are quantized
    # so XLA's latency-hiding scheduler runs chunk i's wire crossing
    # under chunk i+1's compute. Per-row scales make every chunk's
    # quantize + harmonize exactly the row slice of the whole-table
    # algebra, so the folded table is bit-identical at any depth. A
    # trace-time gate like probes/robust: depth 1 traces none of the
    # chunked branches and the program stays bit-identical (pinned by
    # test_probes_off_program_identical).
    depth = int(getattr(cfg, "overlap_depth", 1))
    overlap = cfg.mode == "sketch" and depth > 1

    def _quantize_for_collective(t, axes, n_addends):
        """Local f32 table -> (wire-dtype table, shared scale) ready
        for a wire-dtype psum/psum_scatter (parallel/wire.py owns the
        mesh-facing crossing; ops/quant.py the algebra)."""
        from commefficient_tpu.parallel import wire as wirex
        return wirex.quantize_for_collective(t, wire, axes, n_addends)

    def _qdq_local(t):
        """Single-shard wire crossing: quantize at full range,
        immediately dequantize (n_addends=1 — harmonize is an exact
        identity, so this matches the NumPy mirror bit-for-bit)."""
        from commefficient_tpu.ops import quant
        q, scale = quant.quantize_table(t, wire)
        return quant.dequantize(q, scale)

    def _qdq_local_overlapped(t):
        """Single-shard crossing under --overlap_depth: per-row-chunk
        quantize-dequantize, folded in emission order. Scales are
        per-row, so each chunk's qdq IS the row slice of the
        whole-table qdq — bit-identical result, chunked program (the
        single-device mirror of the chunked collective pipeline)."""
        from commefficient_tpu.core.server import fold_row_chunks
        from commefficient_tpu.parallel.wire import row_chunks
        return fold_row_chunks(
            _qdq_local(jax.lax.slice_in_dim(t, off, off + cnt, axis=0))
            for off, cnt in row_chunks(t.shape[0], depth))

    def _partial_table_emit(g):
        """2D-mesh sketch emission for one model peer: sketch ONLY
        this peer's contiguous ⌈d/M⌉ coordinate slice of the dense
        gradient (slices are disjoint, so the model-axis SUM of the
        partial tables is the sketch of the full gradient — the same
        linearity identity the late-sketch path rests on), then one
        reduce-scatter leaves each peer holding its (r, c/M) column
        shard. Replaces replicate + all-reduce: per-link wire bytes
        drop from 4·r·c to 4·r·c/M and no device ever materialises
        the full table during emission. Tail-shard padding slots are
        zero-valued (a scatter-add of 0 at a clamped index is a
        no-op), so uneven d/M needs no special casing."""
        from commefficient_tpu.parallel.mesh import (MODEL_AXIS,
                                                     model_axis_size)
        M = model_axis_size(mesh)
        d = cfg.grad_size
        n_loc = -(-d // M)
        pad = n_loc * M - d
        gp = jnp.pad(g, (0, pad)) if pad else g
        start = (jax.lax.axis_index(MODEL_AXIS)
                 * n_loc).astype(jnp.int32)
        vals = jax.lax.dynamic_slice(gp, (start,), (n_loc,))
        idx = start + jnp.arange(n_loc, dtype=jnp.int32)
        vals = jnp.where(idx < d, vals, 0.0)
        partial = sketch.sketch_sparse(jnp.minimum(idx, d - 1), vals)
        if overlap:
            # chunked emission: slice the partial table into disjoint
            # row chunks and issue each chunk's model-axis
            # reduce-scatter as soon as its rows are quantized — the
            # unrolled interleaving is what lets the scheduler overlap
            # chunk i's collective with chunk i+1's quantize. Returns
            # the per-chunk results in row order; the client-axis
            # crossing (_client_psum) folds them back. Same headroom
            # algebra per chunk (C*M addends), same ledger bytes: N
            # collectives of cnt·c/M wire elements sum to one of
            # r·c/M.
            from commefficient_tpu.parallel import wire as wirex
            from commefficient_tpu.parallel.mesh import (
                CLIENT_AXIS, client_axis_size)
            C = client_axis_size(mesh)
            chunks = []
            for off, cnt in wirex.row_chunks(sketch.r, depth):
                part = jax.lax.slice_in_dim(partial, off, off + cnt,
                                            axis=0)
                if quantized:
                    q, scale = _quantize_for_collective(
                        part, (CLIENT_AXIS, MODEL_AXIS), C * M)
                    chunks.append(
                        (wirex.wire_reduce_scatter(q, MODEL_AXIS),
                         scale))
                else:
                    chunks.append(jax.lax.psum_scatter(
                        part, MODEL_AXIS, scatter_dimension=1,
                        tiled=True))
            return chunks
        if quantized:
            # quantize the shard-local partial BEFORE the collective:
            # the reduce-scatter moves wire-dtype bytes (r·c·wb per
            # link instead of 4·r·c) and the full-width f32 table
            # still never materialises. Headroom covers every addend
            # the downstream chain sums in wire dtype: M partials in
            # the scatter x C client shards in the following psum.
            from commefficient_tpu.parallel import wire as wirex
            from commefficient_tpu.parallel.mesh import (
                CLIENT_AXIS, client_axis_size)
            C = client_axis_size(mesh)
            q, scale = _quantize_for_collective(
                partial, (CLIENT_AXIS, MODEL_AXIS),
                C * M)
            return wirex.wire_reduce_scatter(q, MODEL_AXIS), scale
        return jax.lax.psum_scatter(partial, MODEL_AXIS,
                                    scatter_dimension=1, tiled=True)

    def _fused_local(ps_weights, batch, total, n_shards,
                     with_dense=False, emit=None, cw=None):
        """Fused backward over the clients in ``batch`` (all of them
        single-device; one device's shard under shard_map), already
        normalised by the GLOBAL datapoint total. The weight-decay
        term is split evenly across shards so the cross-shard sum
        reconstructs (wd/num_workers)·p exactly once — ``n_shards``
        is the number of CLIENT-axis shards (cross-shard sums are
        psums over ``clients``; on a 2D mesh the model peers hold
        coordinate-disjoint slices, never copies, so they must not
        enter the split).

        ``emit`` (2D mesh only) replaces the transmit construction on
        the dense flat gradient — the shard-local partial-sketch +
        reduce-scatter above. The tree-sketch path materialises the
        flat concatenation first in that case: coordinate slicing
        needs the flat layout. ``with_dense`` (probe cadence rounds
        only) appends the dense flat gradient to the return — the
        recovery-error probe's ground truth.

        ``cw`` (asyncfed, weighted builds only): this shard's (W,)
        per-client staleness weights. Each client's loss term scales
        by cw_i·n_i against the already-weighted global ``total``, so
        the fused gradient equals Σ cw_i·t_i / Σ cw_i·n_i — exactly
        the weighted per-client fold."""

        def make_local_loss(fn):
            def local_loss(p):
                def one(b, cwi=None):
                    loss, metrics = fn(p, b)
                    n = jnp.sum(b["mask"])
                    # guard all-padding clients: their (meaningless)
                    # loss must not poison the weighted sum (cf. the
                    # non-fused path's masking in core/grad.py)
                    w = jnp.where(n > 0, loss * n, 0.0)
                    if cwi is not None:
                        w = w * cwi
                    mets = tuple((n > 0) * m
                                 for m in (loss,) + tuple(metrics))
                    return w, mets

                # the weights are this scope's own and the clients'
                # losses are summed below: the axis is named so, and
                # a layer may take every client's rows at once
                # (parallel/mesh.py SHARED_CLIENTS)
                over_clients = jax.vmap(one, axis_name=SHARED_CLIENTS)
                if cw is None:
                    weighted_l, metrics = over_clients(batch)
                else:
                    weighted_l, metrics = over_clients(batch, cw)
                return jnp.sum(weighted_l) / total, metrics

            return local_loss

        # Weight-decay share of this shard. At the default (no
        # dropout) the even 1/n_shards split keeps today's program;
        # under --dropout_prob the share becomes this shard's alive-
        # datapoint fraction so the cross-shard sum matches the
        # per-client path exactly: full (wd/num_workers)·p while any
        # client survives, exact zero on a fully-dropped round (the
        # per-client path's dead transmits are zeros — the fused path
        # must not keep decaying weights on a round nobody joined).
        if cw is not None:
            # weighted build: the wd share is this shard's weighted
            # alive-datapoint fraction, matching the per-client
            # path's Σ cw_i·n_i·(wd/num_workers)·p / total exactly
            n_per = jax.vmap(lambda b: jnp.sum(b["mask"]))(batch)
            wd_frac = jnp.sum(cw * n_per) / total
        elif getattr(cfg, "dropout_prob", 0.0) > 0:
            wd_frac = jnp.sum(batch["mask"]) / total
        else:
            wd_frac = None  # even split — today's exact constants

        def _wd_coef():
            if wd_frac is None:
                return cfg.weight_decay / cfg.num_workers / n_shards
            return (cfg.weight_decay / cfg.num_workers) * wd_frac

        if tree_sketch:
            tree = unravel(ps_weights)
            with jax.named_scope("fwd_bwd"):
                (_, metrics), g_tree = jax.value_and_grad(
                    make_local_loss(tree_loss), has_aux=True)(tree)
            if cfg.weight_decay != 0:
                coef = _wd_coef()
                # decay in f32 regardless of leaf dtype: the flat path
                # computes g + coef*p on the f32 flat vector, and
                # sketch_from_leaves casts leaves to f32 anyway — a
                # sub-f32 param_dtype must not make the tree path
                # accumulate the decay at lower precision than flat
                g_tree = jax.tree_util.tree_map(
                    lambda g, p: (g.astype(jnp.float32)
                                  + coef * p.astype(jnp.float32)),
                    g_tree, tree)
            leaves = jax.tree_util.tree_leaves(g_tree)
            if emit is not None:
                # 2D emission needs the flat coordinate layout (each
                # model peer sketches a contiguous slice) — the flat
                # concatenation comes back, but the per-link payload
                # still drops to (r, c/M)
                flat = jnp.concatenate(
                    [jnp.ravel(l).astype(jnp.float32)
                     for l in leaves])
                with jax.named_scope("compress"):
                    table = emit(flat)
                if with_dense:
                    return table, metrics, flat
                return table, metrics
            with jax.named_scope("compress"):
                table = sketch.sketch_from_leaves(leaves)
            if with_dense:
                return table, metrics, jnp.concatenate(
                    [jnp.ravel(l).astype(jnp.float32)
                     for l in leaves])
            return table, metrics

        with jax.named_scope("fwd_bwd"):
            (_, metrics), g = jax.value_and_grad(
                make_local_loss(loss_fn), has_aux=True)(ps_weights)
        if cfg.weight_decay != 0:
            # Σ_i (wd/num_workers)·p·n_i / total = (wd/num_workers)·p
            g = g + _wd_coef() * ps_weights
        if cfg.mode != "sketch":
            t = g
        else:
            with jax.named_scope("compress"):
                t = emit(g) if emit is not None else sketch.sketch(g)
        if with_dense:
            return t, metrics, g
        return t, metrics

    def _recovery_error(aggregated, dense_g):
        """--probe_full ground truth. Estimates + take-mask kernels:
        replicated work, so on a mesh every device runs it whole."""
        from commefficient_tpu.parallel.mesh import on_every_device
        return on_every_device(
            lambda t, g: sketch.recovery_error(t, g, cfg.k),
            mesh)(aggregated, dense_g)

    def client_round_fused(ps_weights, client_states: ClientStates,
                           batch, client_ids, rng,
                           fedavg_lr=1.0, staleness=None) -> RoundResult:
        del rng, fedavg_lr
        W = client_ids.shape[0]
        if weighted:
            cw = staleness_weights(staleness, alpha)
            n_per = jax.vmap(lambda b: jnp.sum(b["mask"]))(batch)
            total = jnp.maximum(jnp.sum(cw * n_per), 1.0)
        else:
            cw = None
            total = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        from commefficient_tpu.parallel.mesh import (client_axis_size,
                                                     model_axis_size,
                                                     on_every_device)
        ndev = mesh.devices.size if mesh is not None else 1
        C = client_axis_size(mesh)
        # 2D mesh sketch emission: partial-sketch + reduce-scatter
        # over ``model`` — the aggregated table leaves the round
        # column-sharded (parallel/mesh.table_shard_spec). Dense
        # modes keep the replicated emission on any mesh shape (their
        # server state shards under GSPMD instead, build_server_round)
        shard2d = model_axis_size(mesh) > 1 and cfg.mode == "sketch"
        # recovery probe needs the dense aggregate next to the table;
        # in non-sketch fused modes the aggregate IS dense and there
        # is no recovery to measure
        want_dense = probe_recovery and cfg.mode == "sketch"
        dense_g = None
        if ndev > 1 and W % C == 0:
            from commefficient_tpu.parallel.mesh import (
                CLIENT_AXIS, client_spec, replicated_spec, shard_map,
                table_shard_spec)

            def _client_psum(t):
                """The table's client-axis all-reduce — in wire dtype
                on the quantized path (the table crosses the ICI at
                wire width; dequantized right after, so the server
                only ever sees f32). Under --overlap_depth the
                crossing runs per row chunk, interleaved with the
                chunk quantizes, and the chunk-ordered fold
                (core/server.fold_row_chunks) reassembles the
                table."""
                if overlap:
                    from commefficient_tpu.core.server import \
                        fold_row_chunks
                    from commefficient_tpu.parallel import wire as wirex
                    if shard2d:
                        # emit handed back per-chunk reduce-scattered
                        # shards (quantized: with their scales)
                        if quantized:
                            return fold_row_chunks(
                                wirex.wire_allreduce(q, s, CLIENT_AXIS)
                                for q, s in t)
                        return fold_row_chunks(
                            jax.lax.psum(ch, CLIENT_AXIS) for ch in t)
                    return wirex.chunked_quantize_allreduce(
                        t, wire if quantized else "f32",
                        (CLIENT_AXIS,), C, CLIENT_AXIS, depth)
                if not quantized:
                    return jax.lax.psum(t, CLIENT_AXIS)
                from commefficient_tpu.parallel import wire as wirex
                if shard2d:
                    q, scale = t  # emit quantized + reduce-scattered
                else:
                    q, scale = _quantize_for_collective(
                        t, (CLIENT_AXIS,), C)
                return wirex.wire_allreduce(q, scale, CLIENT_AXIS)

            def block(p, local_batch, tot, *rest):
                # mark the replicated params as device-varying before
                # differentiating: otherwise shard_map's transpose
                # rule auto-psums the DENSE per-device gradient to
                # keep the cotangent replicated — a d-sized
                # all-reduce that defeats the compressed-table
                # traffic (and would double-count with ours)
                cw_loc = rest[0] if rest else None
                p = jax.lax.pcast(p, CLIENT_AXIS, to="varying")
                emit = _partial_table_emit if shard2d else None
                if want_dense:
                    # probed cadence round: the dense gradient crosses
                    # the ICI too — the one round where uncompressed
                    # traffic is the price of the ground-truth probe
                    t, metrics, g = _fused_local(p, local_batch, tot,
                                                 C, with_dense=True,
                                                 emit=emit, cw=cw_loc)
                    return (_client_psum(t),
                            jax.lax.psum(g, CLIENT_AXIS), metrics)
                t, metrics = _fused_local(p, local_batch, tot, C,
                                          emit=emit, cw=cw_loc)
                # the round's ONE all-reduce (reference
                # fed_worker.py:139-140 NCCL reduce): sketch tables in
                # sketch mode — inter-chip traffic stays compressed,
                # and on a 2D mesh it runs on the already
                # reduce-scattered (r, c/M) shard
                return _client_psum(t), metrics

            agg_spec = (table_shard_spec() if shard2d
                        else replicated_spec())
            # weighted builds shard the staleness weights along the
            # client axis next to the batch
            wex = (cw,) if cw is not None else ()
            wspec = (client_spec(),) if cw is not None else ()
            if want_dense:
                aggregated, dense_g, metrics = shard_map(
                    block, mesh=mesh,
                    in_specs=(replicated_spec(), client_spec(),
                              replicated_spec()) + wspec,
                    out_specs=(agg_spec, replicated_spec(),
                               client_spec()))(ps_weights, batch,
                                               total, *wex)
            else:
                aggregated, metrics = shard_map(
                    block, mesh=mesh,
                    in_specs=(replicated_spec(), client_spec(),
                              replicated_spec()) + wspec,
                    out_specs=(agg_spec, client_spec()))(ps_weights,
                                                         batch, total,
                                                         *wex)
        else:
            # one device — or a W the client axis does not divide,
            # where shard_batch replicated the batch and every device
            # runs all W clients (on_every_device: the sketch kernel
            # cannot sit in a partitioned multi-device jit)
            def whole(p, b, tot, cw):
                return _fused_local(p, b, tot, 1, with_dense=want_dense,
                                    cw=cw)

            out = on_every_device(whole, mesh)(ps_weights, batch, total,
                                               cw)
            if want_dense:
                aggregated, metrics, dense_g = out
            else:
                aggregated, metrics = out
            if quantized:
                # single-shard wire crossing: quantize-dequantize the
                # aggregated table at full range (exactly the NumPy
                # mirror's np_quantize_table/np_dequantize_table)
                aggregated = (_qdq_local_overlapped(aggregated)
                              if overlap else _qdq_local(aggregated))
        pr = None
        if probes:
            pr = _agg_probes(aggregated)
            if dense_g is not None:
                pr["recovery_error"] = _recovery_error(aggregated,
                                                       dense_g)
        return RoundResult(aggregated, metrics, client_states,
                           _round_bn_stats(stats_fn, ps_weights, batch),
                           probes=pr)

    def client_round(ps_weights, client_states: ClientStates, batch,
                     client_ids, rng, fedavg_lr=1.0,
                     staleness=None) -> RoundResult:
        W = client_ids.shape[0]
        real_ids = client_ids  # pre-sentinel ids for the chaos hook
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(client_ids)

        # dead slots (the loader pads ragged rounds with id 0 and an
        # all-zero mask) must not touch client 0's state — and a real
        # client 0 in the same round would otherwise RACE the pad's
        # no-op row in the state scatter (duplicate indices, order
        # unspecified). Remap them to an out-of-range id: gathers
        # clamp (values unused), scatters drop. In dense_rows mode the
        # state arrays hold only this round's W rows, so state indices
        # are slot POSITIONS (same sentinel treatment); the rngs above
        # were already folded from the REAL ids.
        if dense_rows:
            client_ids = _state_ids(
                jnp.arange(W, dtype=client_ids.dtype), batch)
        else:
            client_ids = _state_ids(client_ids, batch)

        chunk = getattr(cfg, "client_chunk", 0)
        ndev = mesh.devices.size if mesh is not None else 1
        from commefficient_tpu.parallel.mesh import model_axis_size
        shard2d_late = (model_axis_size(mesh) > 1
                        and cfg.mode == "sketch" and sketch_late)
        if 0 < chunk < W and ndev == 1:
            return _client_round_chunked(ps_weights, client_states,
                                         batch, client_ids, rngs,
                                         fedavg_lr, chunk)

        vel_rows = (client_states.velocities[client_ids]
                    if client_states.velocities is not None else None)
        err_rows = (client_states.errors[client_ids]
                    if client_states.errors is not None else None)
        wt_rows = (client_states.weights[client_ids]
                   if client_states.weights is not None else None)

        run_clients = jax.vmap(per_client,
                               in_axes=(None, 0, 0, 0, 0, 0, None))
        if ndev > 1 and cfg.mode == "sketch" and not sketch_late:
            # per-client sketches: the sketch kernel sits inside the
            # vmap, and XLA cannot partition a Mosaic kernel along the
            # client axis the way it partitions the rest of this round
            run_clients = _clients_per_device(run_clients, mesh, W)
        transmit, metrics, new_vel, new_err, new_wts = run_clients(
            ps_weights, _some(vel_rows, W), _some(err_rows, W),
            _some(wt_rows, W), batch, rngs, fedavg_lr)

        if transmit_transform is not None:
            transmit = transmit_transform(transmit, batch, real_ids,
                                          rng)

        if quantized and not sketch_late:
            # per-client uploads (the clipped / robust early-sketch
            # paths materialise per-client tables): each client's
            # table crosses the wire quantized at full range and the
            # server dequantizes before the fold — a dead client's
            # all-zero table survives exactly (scale guard in
            # ops/quant.py)
            transmit = jax.vmap(_qdq_local)(transmit)

        # Σ_clients transmit, ÷ total datapoints — one all-reduce
        # (reference fed_worker.py:131-140 + fed_aggregator.py:328-334)
        # Weighted (asyncfed) builds fold cw_i·transmit_i over
        # Σ cw_i·n_i instead: a weighted per-datapoint mean. The
        # probes below keep reading the UNWEIGHTED per-client
        # transmits — they report what clients sent, not how the
        # fold discounted it.
        # Under --dp sketch the denominator is the STATIC padded
        # datapoint capacity W·B (mask.size), not the alive total:
        # one client's transmit is its clipped gradient × its real
        # datapoint count n_i ≤ B, so its share of a capacity-
        # normalised fold is bounded by n_i/(W·B) ≤ 1/W — the
        # sqrt(r)·C/W sensitivity the accountant charges
        # (privacy/mechanism.py) — on EVERY round. A data-dependent
        # denominator breaks that bound two ways: a mostly-dead round
        # shrinks it below W·n_i (the survivor's share exceeds 1/W
        # against noise calibrated for W), and the weighted async
        # fold's Σ cw·n denominator cancels uniform staleness weights
        # out of the release entirely (no sensitivity shrink to
        # credit). With the fixed denominator the weights genuinely
        # scale the release, so the accountant's w·Δ staleness
        # discount is sound. Trace-time constant: dp-off builds are
        # bit-identical to before.
        if weighted:
            cw = staleness_weights(staleness, alpha)
            n_per = jnp.sum(batch["mask"],
                            axis=tuple(range(1, batch["mask"].ndim)))
            total = jnp.maximum(jnp.sum(cw * n_per), 1.0)
            t_fold = transmit * cw.reshape(
                (W,) + (1,) * (transmit.ndim - 1))
        else:
            cw = None
            total = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
            t_fold = transmit
        if dp_on:
            total = jnp.float32(float(batch["mask"].size))
        fold_pr = None
        if robust:
            from commefficient_tpu.core.robust import robust_fold
            aggregated, fold_pr = robust_fold(cfg, transmit, batch,
                                              probes=probes,
                                              weights=cw)
        elif sketch_late:
            with jax.named_scope("compress"):
                aggregated = _sketch_after_local_sum(
                    sketch, t_fold, mesh,
                    emit=_partial_table_emit if shard2d_late else None,
                    wire="f32" if dp_on else wire,
                    depth=depth if overlap else 1) / total
        else:
            aggregated = jnp.sum(t_fold, axis=0) / total

        if dp_on:
            # the release: one seeded Gaussian draw on the aggregated
            # table (the noise key is a distinguished fold of the
            # round key — disjoint from every per-client stream), then
            # the deferred wire qdq on the NOISY table. Same rng, same
            # round ⇒ bit-identical noise, including across resume.
            from commefficient_tpu.privacy import (add_table_noise,
                                                   round_noise_key,
                                                   table_noise_std)
            aggregated = add_table_noise(aggregated,
                                         round_noise_key(rng),
                                         table_noise_std(cfg))
            if dp_qdq:
                aggregated = (_qdq_local_overlapped(aggregated)
                              if overlap else _qdq_local(aggregated))

        pr = None
        if probes:
            pr = _agg_probes(aggregated)
            pr.update(_client_norm_probes(transmit, batch))
            if fold_pr:
                pr.update(fold_pr)
            if probe_recovery and sketch_late:
                # the dense transmits exist on this path anyway, so
                # the ground-truth aggregate is one extra sum; the
                # clipped per-client-sketch path (max_grad_norm set)
                # has no dense gradient to compare against and omits
                # the key
                dense_g = jnp.sum(t_fold, axis=0) / total
                pr["recovery_error"] = _recovery_error(aggregated,
                                                       dense_g)
        states = ClientStates(
            _scatter(client_states.velocities, client_ids, new_vel),
            _scatter(client_states.errors, client_ids, new_err),
            _scatter(client_states.weights, client_ids, new_wts),
        )
        return RoundResult(aggregated, metrics, states,
                           _round_bn_stats(stats_fn, ps_weights, batch),
                           probes=pr)

    def _client_round_chunked(ps_weights, client_states, batch,
                              client_ids, rngs, fedavg_lr, chunk):
        """--client_chunk: scan over chunks of the round's client
        fan-out, capping live per-client intermediates at chunk x d
        instead of W x d. The reference gets this bound for free by
        running clients SERIALLY per worker process (fed_worker.py:
        59-133); the full vmap is that loop unrolled onto one chip,
        which at W=100, d=6.6M local_topk masking costs ~13 GB of HLO
        temps (measured OOM). Same math: transmits accumulate into the
        running sum chunk by chunk, per-client states scatter back as
        each chunk finishes. Single-device path — on a mesh the client
        axis is already divided across devices."""
        W = client_ids.shape[0]
        n_chunks = -(-W // chunk)
        pad = n_chunks * chunk - W

        def pad0(x):
            return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) \
                if pad else x

        # padded slots carry an OUT-OF-RANGE client id: their state
        # gathers clamp (values discarded — all-zero mask makes the
        # step a no-op) and their state scatters are DROPPED (JAX's
        # default out-of-bounds scatter semantics), so no real
        # client's row is ever touched by a pad slot. Padding with a
        # real id (e.g. 0) would both advance that client's topk_down
        # weights (new_wts has no alive guard) and race its update
        # when it shares the padded chunk.
        sentinel = jnp.iinfo(jnp.int32).max
        ids_p = (jnp.concatenate(
            [client_ids,
             jnp.full((pad,), sentinel, client_ids.dtype)])
            if pad else client_ids).reshape(n_chunks, chunk)
        rngs_p = pad0(rngs).reshape((n_chunks, chunk) +
                                    rngs.shape[1:])
        batch_p = {k: pad0(v).reshape((n_chunks, chunk) + v.shape[1:])
                   for k, v in batch.items()}
        total = jnp.maximum(jnp.sum(batch["mask"]), 1.0)

        def body(carry, inp):
            acc, states = carry
            ids_c, rngs_c, batch_c = inp
            vel_r = (states.velocities[ids_c]
                     if states.velocities is not None else None)
            err_r = (states.errors[ids_c]
                     if states.errors is not None else None)
            wt_r = (states.weights[ids_c]
                    if states.weights is not None else None)
            transmit, metrics, new_vel, new_err, new_wts = jax.vmap(
                per_client, in_axes=(None, 0, 0, 0, 0, 0, None)
            )(ps_weights, _some(vel_r, chunk), _some(err_r, chunk),
              _some(wt_r, chunk), batch_c, rngs_c, fedavg_lr)
            if quantized and not sketch_late:
                # same per-client wire crossing as the unchunked path
                transmit = jax.vmap(_qdq_local)(transmit)
            states = ClientStates(
                _scatter(states.velocities, ids_c, new_vel),
                _scatter(states.errors, ids_c, new_err),
                _scatter(states.weights, ids_c, new_wts),
            )
            ys = metrics
            if probes:
                # per-client transmit norms ride the scan's stacked
                # outputs like the metrics do
                norms = jnp.sqrt(jnp.sum(jax.lax.square(
                    transmit.reshape(chunk, -1)), axis=1))
                ys = (metrics, norms)
            return (acc + jnp.sum(transmit, axis=0), states), ys

        dense_g = None
        if sketch_late and not probe_recovery:
            # chunked + sketch-late: sketch each chunk's dense sum and
            # accumulate tables (linearity) — the (W, d) transmit
            # stack never exists
            def body_sketch(carry, inp):
                table_acc, states = carry
                (chunk_sum, states), ys = body(
                    (jnp.zeros(cfg.grad_size, jnp.float32), states),
                    inp)
                with jax.named_scope("compress"):
                    table_acc = table_acc + sketch.sketch(chunk_sum)
                return (table_acc, states), ys

            (table, states), ys = jax.lax.scan(
                body_sketch,
                (jnp.zeros((sketch.r, sketch.c), jnp.float32),
                 client_states),
                (ids_p, rngs_p, batch_p))
            if quantized:
                table = (_qdq_local_overlapped(table)
                         if overlap else _qdq_local(table))
            aggregated = table / total
        else:
            # dense accumulator: transmit_shape covers both dense (d,)
            # transmits and the (r, c) tables of the clipped (non-late)
            # sketch path; the sketch-late PROBED variant accumulates
            # dense and sketches once at the end (linearity — same
            # table as per-chunk accumulation) so the recovery probe's
            # ground truth exists without a (W, d) stack
            init_shape = ((cfg.grad_size,) if sketch_late
                          else cfg.transmit_shape)
            (acc, states), ys = jax.lax.scan(
                body,
                (jnp.zeros(init_shape, jnp.float32), client_states),
                (ids_p, rngs_p, batch_p))
            if sketch_late:
                with jax.named_scope("compress"):
                    table = sketch.sketch(acc)
                if quantized:
                    table = (_qdq_local_overlapped(table)
                             if overlap else _qdq_local(table))
                aggregated = table / total
                dense_g = acc / total
            else:
                aggregated = acc / total

        if probes:
            metrics, norms = ys
        else:
            metrics = ys
        metrics = tuple(m.reshape(-1)[:W] for m in metrics)
        pr = None
        if probes:
            pr = _agg_probes(aggregated)
            pr.update(_client_norm_stats(norms.reshape(-1)[:W], batch))
            if dense_g is not None:
                pr["recovery_error"] = sketch.recovery_error(
                    aggregated, dense_g, cfg.k)
        return RoundResult(aggregated, metrics, states,
                           _round_bn_stats(stats_fn, ps_weights, batch),
                           probes=pr)

    return client_round_fused if fused_grad else client_round


def _agg_probes(aggregated) -> dict:
    """O(d) reductions over the round's aggregated transmit (dense
    vector or sketch table): its norm plus NaN/Inf element counts —
    the cheapest possible per-round health signal, compiled into the
    round program so no extra device round-trip is ever taken."""
    return {
        "agg_norm": jnp.sqrt(jnp.sum(jax.lax.square(aggregated))),
        "agg_nan": jnp.sum(jnp.isnan(aggregated)).astype(jnp.float32),
        "agg_inf": jnp.sum(jnp.isinf(aggregated)).astype(jnp.float32),
    }


def _client_norm_stats(norms, batch) -> dict:
    """Mean/max/std of per-client transmit norms over ALIVE clients
    (dead dropout/padding slots transmit zero and are excluded from
    mean/std; the max is alive-masked for the same reason). The
    dispersion is the population std — a sudden spread blow-up is the
    straggler/poisoned-client signature."""
    alive = jax.vmap(
        lambda b: jnp.sum(b["mask"]) > 0)(batch).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(alive), 1.0)
    mean = jnp.sum(norms * alive) / n
    var = jnp.sum(alive * jax.lax.square(norms - mean)) / n
    return {"client_norm_mean": mean,
            "client_norm_max": jnp.max(norms * alive),
            "client_norm_std": jnp.sqrt(jnp.maximum(var, 0.0))}


def _client_norm_probes(transmit, batch) -> dict:
    W = transmit.shape[0]
    norms = jnp.sqrt(jnp.sum(jax.lax.square(
        transmit.reshape(W, -1)), axis=1))
    return _client_norm_stats(norms, batch)


def _round_bn_stats(stats_fn, ps_weights, batch):
    """Sample-weighted mean of participating clients' batch statistics
    (the federated replacement for per-worker torch running-stats
    updates): one extra forward per client, only in --batchnorm
    configs. Dropped/padded clients get zero weight; ``alive`` lets
    the server skip the blend on a fully-dropped round."""
    if stats_fn is None:
        return None
    n = jax.vmap(lambda b: jnp.sum(b["mask"]))(batch)   # (W,)
    total = jnp.maximum(jnp.sum(n), 1.0)
    per_client = jax.vmap(stats_fn, in_axes=(None, 0))(ps_weights,
                                                       batch)
    w = n / total
    mean_stats = jax.tree_util.tree_map(
        lambda s: jnp.tensordot(w.astype(s.dtype), s, axes=(0, 0)),
        per_client)
    return mean_stats, jnp.sum(n)


def _sketch_after_local_sum(sketch: CountSketch, transmit, mesh,
                            emit=None, wire="f32", depth=1):
    """(W, d) dense transmits -> (r, c) summed table: per-device local
    dense sum, one sketch per device, psum of tables over the mesh.
    ``emit`` (2D mesh, sketch mode) replaces the full per-device
    sketch with the partial-slice sketch + reduce-scatter over
    ``model`` (build_client_round._partial_table_emit); the returned
    table is then column-sharded (parallel/mesh.table_shard_spec).
    ``wire`` != "f32" quantizes the table before the collective
    (ops/quant.py — the collective payload drops to wire width) and
    dequantizes after; with an ``emit``, the emit closure already did
    the quantize + reduce-scatter and hands back ``(q, scale)``.
    ``depth`` > 1 (--overlap_depth) crosses the table in disjoint
    row chunks — collective i interleaved with chunk i+1's quantize —
    and folds the chunks back in row order (an ``emit`` then hands
    back the per-chunk list)."""
    from commefficient_tpu.parallel.mesh import (CLIENT_AXIS,
                                                 client_axis_size,
                                                 on_every_device,
                                                 replicated_spec,
                                                 shard_map, spec,
                                                 table_shard_spec)
    W = transmit.shape[0]
    if mesh is not None and W % client_axis_size(mesh) == 0 \
            and mesh.devices.size > 1:
        C = client_axis_size(mesh)

        def block(local):  # (W/C, d) on each client-axis shard
            g = jnp.sum(local, axis=0)
            if depth > 1:
                from commefficient_tpu.core.server import \
                    fold_row_chunks
                from commefficient_tpu.parallel import wire as wirex
                if emit is not None:
                    chunks = emit(g)  # per-row-chunk scattered shards
                    if wire != "f32":
                        return fold_row_chunks(
                            wirex.wire_allreduce(q, s, CLIENT_AXIS)
                            for q, s in chunks)
                    return fold_row_chunks(
                        jax.lax.psum(ch, CLIENT_AXIS)
                        for ch in chunks)
                return wirex.chunked_quantize_allreduce(
                    sketch.sketch(g), wire, (CLIENT_AXIS,), C,
                    CLIENT_AXIS, depth)
            if wire != "f32":
                from commefficient_tpu.parallel import wire as wirex
                if emit is None:
                    q, scale = wirex.quantize_for_collective(
                        sketch.sketch(g), wire, (CLIENT_AXIS,), C)
                else:
                    q, scale = emit(g)
                return wirex.wire_allreduce(q, scale, CLIENT_AXIS)
            table = sketch.sketch(g) if emit is None else emit(g)
            return jax.lax.psum(table, CLIENT_AXIS)

        return shard_map(
            block, mesh=mesh,
            in_specs=spec(CLIENT_AXIS, None),
            out_specs=(replicated_spec() if emit is None
                       else table_shard_spec()))(transmit)
    # one device, or a W the client axis does not divide: the dense sum
    # is replicated and every device sketches it whole
    table = on_every_device(sketch.sketch, mesh)(
        jnp.sum(transmit, axis=0))
    if wire != "f32":
        from commefficient_tpu.ops import quant
        if depth > 1:
            # single-device mirror of the chunked crossing: per-chunk
            # qdq (per-row scales -> bit-identical, chunked program)
            from commefficient_tpu.core.server import fold_row_chunks
            from commefficient_tpu.parallel.wire import row_chunks
            return fold_row_chunks(
                quant.dequantize(*quant.quantize_table(
                    jax.lax.slice_in_dim(table, off, off + cnt,
                                         axis=0),
                    wire))
                for off, cnt in row_chunks(table.shape[0], depth))
        return quant.dequantize(*quant.quantize_table(table, wire))
    return table


def _clients_per_device(run_clients, mesh, W):
    """The vmapped per-client step as a ``shard_map`` over the client
    axis — each device steps its own W/C clients — for rounds whose
    per-client step holds a Mosaic kernel. When C does not divide W
    the batch arrived replicated (parallel/mesh.shard_batch) and every
    device steps all W clients."""
    from commefficient_tpu.parallel.mesh import (CLIENT_AXIS,
                                                 client_axis_size,
                                                 client_spec,
                                                 on_every_device,
                                                 replicated_spec,
                                                 shard_map)
    if W % client_axis_size(mesh):
        return on_every_device(run_clients, mesh)
    cs, rs = client_spec(), replicated_spec()

    def block(p, *rest):
        # varying before the per-client backward, as in the fused
        # round's block: shard_map's transpose would otherwise psum
        # each client's dense gradient over the mesh
        return run_clients(
            jax.lax.pcast(p, CLIENT_AXIS, to="varying"), *rest)

    return shard_map(block, mesh=mesh,
                     in_specs=(rs, cs, cs, cs, cs, cs, rs),
                     out_specs=cs)


def _state_ids(client_ids, batch):
    """Ids used for per-client STATE gathers/scatters: dead slots
    (all-zero mask) get an out-of-range sentinel so their scatters
    drop and they can never alias a live client's row. RNG folding
    keeps the original ids (dead slots' streams are unused)."""
    alive = jax.vmap(lambda b: jnp.sum(b["mask"]) > 0)(batch)
    return jnp.where(alive, client_ids,
                     jnp.iinfo(client_ids.dtype).max)


def _some(rows, W):
    """vmap can't map over None: use a zero-size placeholder."""
    return rows if rows is not None else jnp.zeros((W, 0))


def _scatter(arr, ids, rows):
    if arr is None or rows is None or rows.shape[-1] == 0:
        return arr
    return arr.at[ids].set(rows)


def _build_sgd_client_step(cfg, loss_fn, sketch, padded_batch_size):
    """One client's round for all non-fedavg modes
    (reference process_batch + local_step, fed_worker.py:142-232)."""
    forward_grad = make_forward_grad(cfg, loss_fn, sketch,
                                     padded_batch_size)

    def step(ps_weights, velocity, error, client_weights, batch, rng,
             fedavg_lr):
        del fedavg_lr
        batch_size = jnp.sum(batch["mask"])
        if cfg.do_topk_down:
            weights = stale_weight_download(cfg, ps_weights, client_weights)
            # dead slots (dropout / loader padding) did not download:
            # their stale-weight state must not advance (same
            # state-untouched semantics as velocity/error below)
            new_wts = jnp.where(batch_size > 0, weights, client_weights)
        else:
            weights = ps_weights
            new_wts = client_weights

        g_unit, metrics = forward_grad(weights, batch, noise_rng=rng)
        upd = accumulate_and_compress(
            cfg, g_unit,
            velocity if cfg.local_momentum > 0 else None,
            error if cfg.error_type == "local" else None,
            batch_size)
        # a dropped client (--dropout_prob zeroes its whole mask) ran
        # nothing: it transmits 0 and its momentum/error state stays
        # untouched — without this, local-momentum/-error modes would
        # still upload rho*velocity / accumulated error for it
        alive = (batch_size > 0).astype(jnp.float32)
        transmit = upd.transmit * alive

        def keep(new, old):
            if new is None:
                return old
            if old is None:
                return new
            return jnp.where(alive > 0, new, old)

        new_vel = keep(upd.velocity, velocity)
        new_err = keep(upd.error, error)
        return transmit, metrics, new_vel, new_err, new_wts

    return step


def _build_fedavg_client_step(cfg, loss_fn, padded_batch_size):
    """One client's FedAvg round: local SGD over its whole (padded)
    dataset, transmit the weighted weight delta
    (reference fed_worker.py:62-114)."""
    if cfg.fedavg_batch_size == -1:
        sub = padded_batch_size
    else:
        sub = min(cfg.fedavg_batch_size, padded_batch_size)
    n_batches = -(-padded_batch_size // sub)  # ceil
    pad_to = n_batches * sub
    forward_grad = make_forward_grad(cfg, loss_fn, None, sub)

    def step(ps_weights, velocity, error, client_weights, batch, rng,
             fedavg_lr):
        def pad(x):
            w = [(0, pad_to - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, w)

        chunked = {k: pad(v).reshape((n_batches, sub) + v.shape[1:])
                   for k, v in batch.items()}
        client_size = jnp.sum(batch["mask"])

        def local_sgd(carry, inp):
            w, step_i = carry
            microbatch, r = inp
            n = jnp.sum(microbatch["mask"])
            g_unit, metrics = forward_grad(w, microbatch, noise_rng=r)
            # skip all-padding chunks entirely: no weight change, no
            # step increment (the reference never creates such chunks)
            valid = n > 0
            decay = cfg.fedavg_lr_decay ** step_i
            w_new = w - g_unit * fedavg_lr * decay
            w = jnp.where(valid, w_new, w)
            step_i = step_i + valid.astype(jnp.int32)
            w_metrics = tuple(jnp.where(valid, m, 0.0) for m in metrics)
            return (w, step_i), w_metrics

        steps_per_epoch = n_batches
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(cfg.num_fedavg_epochs * steps_per_epoch))

        w = ps_weights
        step_i = jnp.zeros((), jnp.int32)
        all_metrics = []
        for ep in range(cfg.num_fedavg_epochs):
            ep_rngs = rngs[ep * steps_per_epoch:(ep + 1) * steps_per_epoch]
            (w, step_i), ms = jax.lax.scan(
                local_sgd, (w, step_i), (chunked, ep_rngs))
            all_metrics.append(ms)

        # metrics: mean over the local steps actually taken
        # (reference fed_worker.py:103-104)
        n_steps = jnp.maximum(step_i.astype(jnp.float32), 1.0)
        metrics = tuple(
            sum(jnp.sum(ms[i]) for ms in all_metrics) / n_steps
            for i in range(len(all_metrics[0])))

        # transmit = (w_orig - w_final) * |client data|
        # (fed_worker.py:105-109)
        transmit = (ps_weights - w) * client_size
        return transmit, metrics, velocity, error, client_weights

    return step


def build_val_fn(cfg: Config, loss_fn: Callable,
                 stateful: bool = False) -> Callable:
    """Validation shard evaluator: metrics only, batch-mean over the
    shard (reference _call_val + forward_grad(compute_grad=False),
    fed_aggregator.py:339-366). With ``stateful``, ``loss_fn`` takes
    an extra model-state pytree (BatchNorm running stats) that is
    passed per call — an argument, not a closure, so updated stats
    never trigger a re-trace."""
    if stateful:
        def val_shards_state(ps_weights, model_state, batch):
            def one(b):
                loss, metrics = loss_fn(ps_weights, b, model_state)
                return jnp.stack((loss,) + tuple(metrics))

            return jax.vmap(one)(batch)

        return val_shards_state

    eval_metrics = make_eval_metrics(loss_fn)

    def val_shards(ps_weights, batch):
        # batch: (S, B, ...) shards with (S, B) mask
        return jax.vmap(lambda b: jnp.stack(
            eval_metrics(ps_weights, b)))(batch)

    return val_shards


def build_server_round(cfg: Config, probes: bool = False,
                       mesh=None) -> Callable:
    """Returns jit-able ``server_round(ps_weights, server_state,
    aggregated, lr, client_velocities, client_ids, noise_rng) ->
    (new_ps_weights, new_server_state, new_client_velocities,
    weight_update, support)``. ``support`` is ((k,) indices, (k,)
    values) of the update on the index path, ``{"bitmap": packed
    uint8}`` on the exact threshold-select path (see ServerUpdate),
    None for dense modes — it lets the host-side download accounting
    avoid ever transferring the dense update. ``weight_update`` is
    None on the large-d sparse sketch path (prefer_sparse_resketch):
    the update was applied as a k-sized scatter and only ``support``
    (tuple form there) carries its values.

    ``probes=True`` appends a sixth output — the server-side probe
    dict (core/server.py server_update) — so the default arity stays
    five and probes-off callers build a bit-identical program.

    ``mesh`` with a ``model`` axis of size > 1 (parallel/mesh
    make_mesh2d) switches to the model-sharded server programs: the
    shard-mapped distributed-select step for sketch mode
    (core/server.py sketched_update_2d), GSPMD sharding constraints
    for uncompressed — same signature, same return arity. On any
    other mesh of more than one device (1-D, ``Cx1``) the server
    update — the part that holds Mosaic kernels — runs under
    ``parallel/mesh.on_every_device``: every device executes the
    one-device program on its replica of the state. ``None`` and
    one-device meshes build that one-device program itself,
    HLO-identical to a build without the parameter.

    Covers FedOptimizer.step (fed_aggregator.py:431-460) including
    true_topk's masking of participating clients' local velocities at
    the global top-k coordinates (fed_aggregator.py:530-535) — done
    correctly here (the reference has a latent unset-global bug,
    SURVEY.md §2.1).
    """
    cfg.validate_runtime()
    sketch = args2sketch(cfg)
    from commefficient_tpu.parallel.mesh import (model_axis_size,
                                                 on_every_device)
    if model_axis_size(mesh) > 1:
        if cfg.mode == "sketch":
            return _build_server_round_2d_sketch(cfg, sketch, mesh,
                                                 probes)
        assert cfg.mode == "uncompressed", cfg.mode  # config gate
        return _build_server_round_2d_dense(cfg, mesh, probes)

    def update(aggregated, server_state, lr, noise_rng):
        return server_update(cfg, aggregated, server_state, lr, sketch,
                             noise_rng, probes=probes)

    # only the update goes under the replicated shard_map: what follows
    # it (weight step, true_topk's client-velocity rows, which are
    # sharded over ``clients``) is plain XLA and partitions as before
    update = on_every_device(update, mesh)

    def server_round(ps_weights, server_state: ServerState, aggregated,
                     lr, client_velocities=None, client_ids=None,
                     noise_rng=None):
        eff_lr = 1.0 if cfg.mode == "fedavg" else lr
        res: ServerUpdate = update(aggregated, server_state, eff_lr,
                                   noise_rng)
        if res.weight_update is None:
            # large-d k-sparse modes: the support already carries the
            # lr-scaled update values — apply them as a k-sized
            # scatter instead of materialising + subtracting a dense
            # (d,) vector (~6 ms saved per round at GPT-2's d=124M).
            # Sorting (free for the threshold path, a k-sized sort
            # otherwise) lets XLA take the in-place ordered-scatter
            # lowering instead of a d-sized rewrite fusion (measured
            # 4.4 ms in the round-4 xplane). unique_indices holds for
            # the exact/threshold selections but NOT for the big-d
            # approx path, whose degenerate-tie guard clamps
            # out-of-range slots to duplicate (d-1, 0) pairs that rely
            # on scatter-ADD semantics — one shared predicate with
            # ops/sketch.py unsketch, so the big-d gate cannot drift
            from commefficient_tpu.ops.topk import \
                selection_may_duplicate
            unique = not selection_may_duplicate(cfg.grad_size,
                                                 cfg.approx_topk)
            idx, scaled = res.support
            with jax.named_scope("apply"):
                order = jnp.argsort(idx)
                new_ps = ps_weights.at[idx[order]].add(
                    -scaled[order], mode="promise_in_bounds",
                    unique_indices=unique, indices_are_sorted=True)
        else:
            with jax.named_scope("apply"):
                new_ps = ps_weights - res.weight_update
        new_vel = client_velocities
        if (cfg.mode == "true_topk" and cfg.local_momentum > 0
                and client_velocities is not None):
            assert client_ids is not None
            rows = client_velocities[client_ids]
            rows = rows * res.client_velocity_keep.astype(rows.dtype)
            new_vel = client_velocities.at[client_ids].set(rows)
        out = (new_ps, res.state, new_vel, res.weight_update,
               res.support)
        return out + (res.probes,) if probes else out

    return server_round


def _build_server_round_2d_sketch(cfg: Config, sketch: CountSketch,
                                  mesh, probes: bool) -> Callable:
    """Model-sharded FetchSGD server round: shard_map over the full 2D
    mesh with the (r, c) state/aggregate column-sharded over ``model``
    (replicated over ``clients`` — the block is client-invariant).
    The body is core/server.py sketched_update_2d: shard-local
    momentum/error accumulation, one table all-gather, distributed
    threshold-select recovery. The dense weight update, support, and
    probe scalars come back identical on every peer (deterministic
    functions of all-gathered data), so they exit replicated; the new
    state exits on its column shards — per-device server state stays
    1/M across rounds."""
    from commefficient_tpu.core.server import sketched_update_2d
    from commefficient_tpu.parallel.mesh import (MODEL_AXIS,
                                                 model_axis_size,
                                                 replicated_spec,
                                                 shard_map,
                                                 table_shard_spec)
    M = model_axis_size(mesh)
    ts, rs = table_shard_spec(), replicated_spec()

    def body(state, agg, lr):
        res = sketched_update_2d(cfg, sketch, agg, state, lr,
                                 MODEL_AXIS, M, probes=probes)
        out = (res.weight_update, res.state, res.support)
        return out + ((res.probes,) if probes else ())

    out_specs = (rs, ServerState(ts, ts), (rs, rs))
    if probes:
        out_specs = out_specs + (rs,)
    step = shard_map(body, mesh=mesh,
                     in_specs=(ServerState(ts, ts), ts, rs),
                     out_specs=out_specs)

    def server_round(ps_weights, server_state: ServerState, aggregated,
                     lr, client_velocities=None, client_ids=None,
                     noise_rng=None):
        del client_ids, noise_rng  # sketch mode uses neither
        out = step(server_state, aggregated,
                   jnp.asarray(lr, jnp.float32))
        weight_update, new_state, support = out[:3]
        with jax.named_scope("apply"):
            new_ps = ps_weights - weight_update
        ret = (new_ps, new_state, client_velocities, weight_update,
               support)
        return ret + (out[3],) if probes else ret

    return server_round


def _build_server_round_2d_dense(cfg: Config, mesh,
                                 probes: bool) -> Callable:
    """Model-sharded uncompressed server round: the 1-D math verbatim
    (it is elementwise in d) with GSPMD sharding constraints — the
    momentum buffer is pinned model-sharded so per-device server state
    stays 1/M, and the update is pinned replicated where it meets the
    replicated params. No shard_map needed: XLA partitions the
    elementwise chain along the constraint."""
    from commefficient_tpu.parallel.mesh import (replicated,
                                                 server_state_sharding)
    state_sh = server_state_sharding(mesh, cfg.transmit_shape)
    repl = replicated(mesh)

    def server_round(ps_weights, server_state: ServerState, aggregated,
                     lr, client_velocities=None, client_ids=None,
                     noise_rng=None):
        del client_ids
        res: ServerUpdate = server_update(cfg, aggregated, server_state,
                                          lr, None, noise_rng,
                                          probes=probes)
        new_state = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, state_sh),
            res.state)
        upd = jax.lax.with_sharding_constraint(res.weight_update, repl)
        with jax.named_scope("apply"):
            new_ps = ps_weights - upd
        out = (new_ps, new_state, client_velocities, upd, res.support)
        return out + (res.probes,) if probes else out

    return server_round

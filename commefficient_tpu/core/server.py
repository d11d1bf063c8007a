"""Server-side update: virtual momentum, virtual error feedback,
unsketching / top-k recovery.

Pure-functional counterpart of the reference's ``get_server_update``
dispatch and ``_server_helper_*`` family (fed_aggregator.py:471-615).
Because the whole server step is deterministic given the aggregated
gradient, it runs *replicated* on every device of the mesh — the
reference's parameter-server rank dissolves (SURVEY.md §2.9).

``gradient`` is the round's aggregated quantity: a flat (grad_size,)
vector, or an (r, c) sketch table in sketch mode — always the
client-transmit sum divided by the round's total datapoint count
(fed_aggregator.py:334).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.ops.sketch import CountSketch
from commefficient_tpu.ops.topk import topk_with_support


class ServerState(NamedTuple):
    """Virtual momentum & error buffers, dense or sketch-shaped
    (reference FedOptimizer.__init__, fed_aggregator.py:401-411)."""
    Vvelocity: jax.Array
    Verror: jax.Array

    @staticmethod
    def init(cfg: Config, sharding=None) -> "ServerState":
        """``sharding`` (a NamedSharding from
        parallel/mesh.server_state_sharding) places the buffers
        model-sharded on a 2D mesh so per-device server memory scales
        as 1/``model``; None keeps the replicated 1-D layout."""
        shape = cfg.transmit_shape

        def z():
            buf = jnp.zeros(shape, jnp.float32)
            return buf if sharding is None else jax.device_put(
                buf, sharding)

        return ServerState(z(), z())

    @staticmethod
    def restore(Vvelocity, Verror, sharding=None) -> "ServerState":
        """Rebuild from host arrays at checkpoint restore. The
        checkpoint always holds the FULL buffers, so ``sharding``
        (parallel/mesh.server_state_sharding for the CURRENT mesh)
        re-places them under whatever topology the resumed run has —
        a resize is a placement migration, values untouched, which is
        what keeps a resized resume bit-exact vs an unresized one
        (tests/test_elastic.py)."""
        def put(a):
            a = jnp.asarray(a, jnp.float32)
            return a if sharding is None else jax.device_put(
                a, sharding)

        return ServerState(put(Vvelocity), put(Verror))


def fold_row_chunks(chunks) -> jax.Array:
    """Chunk-ordered fold of the overlap pipeline's per-row-chunk
    collectives (``--overlap_depth``): reassemble the dequantized row
    chunks into the (r, c[/M]) table in emission order. The chunks
    cover disjoint row ranges, so the fold is pure concatenation — no
    summation — and is bit-exact regardless of which chunk's
    collective completed first on the wire."""
    return jnp.concatenate(list(chunks), axis=0)


class ServerUpdate(NamedTuple):
    # subtract from ps_weights; None when ``sparse_update`` carries
    # the k-sparse form instead (large-d sketch mode: materialising a
    # dense (d,) update costs ~6 ms at d=124M for 50k real values)
    weight_update: Optional[jax.Array]
    state: ServerState
    # mask of coordinates transmitted to clients this round, used for
    # true_topk's momentum factor masking of *client* velocities
    # (fed_aggregator.py:530-535); None for other modes
    client_velocity_keep: Optional[jax.Array]
    # support of the update for download accounting, in one of two
    # forms: ((k,) indices, (k,) lr-scaled values) on the index path
    # (also consumed for the sparse k-sized weight scatter when
    # weight_update is None), or {"bitmap": packed uint8} of the
    # lr-scaled update's nonzeros on the threshold-select path. None
    # means dense (every coordinate may have changed). Either way the
    # host never needs the dense update shipped off device.
    support: Optional[Union[Tuple[jax.Array, jax.Array],
                            dict]] = None
    # schema-v2 probe scalars (--probe_every): update/residual/momentum
    # norms + selection mass coverage, computed inside the compiled
    # step as O(d) reductions. None unless the caller opted in — the
    # probes-off program must stay HLO-identical to pre-probe builds.
    probes: Optional[dict] = None


def staleness_weights(staleness, alpha: float):
    """FedBuff-style staleness discount ``1/(1+s)^alpha`` for the
    buffered asynchronous fold (asyncfed/). ``alpha`` is a trace-time
    constant — the round builder skips the weighting branch entirely
    at alpha == 0, which is what makes the degenerate-sync
    configuration bit-exact. Applied to a client's transmit AND its
    datapoint count, so the fold stays a weighted per-datapoint mean
    and the server's virtual momentum / error feedback never absorbs
    unnormalised stale mass."""
    return (1.0 + staleness.astype(jnp.float32)) ** jnp.float32(-alpha)


def _use_threshold_select(cfg: Config) -> bool:
    """Exact dense-mode selections (true_topk) at large d go through
    the threshold-select mask instead of the lax.top_k sort — same
    selected set, no sort, no index scatter. Gating is the shared
    predicate in ops/topk.py."""
    from commefficient_tpu.ops.topk import use_threshold_select
    return use_threshold_select(min(cfg.k, cfg.grad_size),
                                cfg.grad_size, cfg.approx_topk)


def _lr_scaled_support(idx, vals, lr):
    """Support of the *weight* update: values scaled by the (scalar or
    per-coordinate) LR, so coordinates with an effective LR of 0 read
    as unchanged — matching a value-compare on ``update * lr``."""
    lr_arr = jnp.asarray(lr, jnp.float32)
    scale = lr_arr[idx] if lr_arr.ndim else lr_arr
    return idx, vals * scale


def _l2(x) -> jax.Array:
    return jnp.sqrt(jnp.sum(jax.lax.square(x)))


def _coverage(selected_mass, dense_mass) -> jax.Array:
    """‖selected‖² / ‖dense‖² — the fraction of the pre-selection
    vector's energy the transmitted top-k/threshold support carries.
    A zero denominator (cold-start buffers) reads as full coverage."""
    return jnp.where(dense_mass > 0,
                     selected_mass / jnp.maximum(dense_mass, 1e-30),
                     1.0)


def server_update(cfg: Config,
                  gradient: jax.Array,
                  state: ServerState,
                  lr,
                  sketch: Optional[CountSketch] = None,
                  noise_rng: Optional[jax.Array] = None,
                  probes: bool = False) -> ServerUpdate:
    """Dispatch on mode (reference get_server_update,
    fed_aggregator.py:471-483). ``lr`` may be a scalar or a
    (grad_size,) per-parameter vector (per-param-group LRs,
    fed_aggregator.py:413-429). For fedavg the caller passes lr=1 —
    the LR was already applied in the clients' local SGD
    (fed_aggregator.py:448-453).

    Under ``--robust_agg`` (core/robust.py) ``gradient`` is already
    the robust aggregate: mass the fold rejected (trimmed tails,
    clipped excess, off-median clients) never reaches this function,
    so it cannot leak into Vvelocity / Verror — the error-feedback
    residuals only ever accumulate what the server actually applied.
    No robust-specific handling belongs here.

    ``probes=True`` (a trace-time flag) additionally fills
    ``ServerUpdate.probes`` with the schema-v2 server diagnostics:
    ``update_norm`` (‖lr-scaled weight update‖), ``residual_norm``
    (‖post-mask Verror‖ — table-space in sketch mode),
    ``momentum_norm`` (‖post-mask Vvelocity‖) and, for the selecting
    modes, ``mass_coverage`` (‖selected‖²/‖dense‖² against the
    pre-selection residual, sketch mode estimating the denominator via
    ``l2estimate``)."""
    helper = {
        "sketch": _sketched,
        "local_topk": _local_topk,
        "true_topk": _true_topk,
        "fedavg": _fedavg,
        "uncompressed": _uncompressed,
    }[cfg.mode]
    return helper(cfg, gradient, state, lr, sketch, noise_rng, probes)


def _state_probes(update_norm, state: ServerState, extra=None) -> dict:
    pr = {"update_norm": update_norm,
          "momentum_norm": _l2(state.Vvelocity),
          "residual_norm": _l2(state.Verror)}
    if extra:
        pr.update(extra)
    return pr


def _fedavg(cfg, avg_update, state, lr, sketch, noise_rng,
            probes=False):
    # (fed_aggregator.py:485-497) — avg_update is the data-weighted
    # mean of client weight *deltas*, LR already applied locally
    assert cfg.error_type == "none" and cfg.local_momentum == 0
    with jax.named_scope("apply"):
        Vvel = avg_update + cfg.virtual_momentum * state.Vvelocity
    new_state = ServerState(Vvel, state.Verror)
    pr = _state_probes(_l2(Vvel), new_state) if probes else None
    return ServerUpdate(Vvel, new_state, None, probes=pr)


def _uncompressed(cfg, gradient, state, lr, sketch, noise_rng,
                  probes=False):
    # (fed_aggregator.py:499-511)
    with jax.named_scope("apply"):
        Vvel = gradient + cfg.virtual_momentum * state.Vvelocity
    if cfg.do_dp and cfg.dp_mode == "server" and cfg.noise_multiplier != 0:
        assert noise_rng is not None, \
            "server-mode DP with noise needs a noise_rng"
        # the reference adds the noise in place on Vvelocity
        # (``grad`` aliases it, fed_aggregator.py:506-510), so the
        # noise persists into the momentum buffer — keep that; the
        # draw routes through privacy/ (lint: noise-confinement)
        from commefficient_tpu.privacy import gaussian_noise
        Vvel = Vvel + gaussian_noise(noise_rng, Vvel.shape,
                                     Vvel.dtype,
                                     std=cfg.noise_multiplier)
    new_state = ServerState(Vvel, state.Verror)
    pr = _state_probes(_l2(Vvel * lr), new_state) if probes else None
    return ServerUpdate(Vvel * lr, new_state, None, probes=pr)


def _true_topk(cfg, gradient, state, lr, sketch, noise_rng,
               probes=False):
    # (fed_aggregator.py:513-544)
    assert cfg.error_type == "virtual"
    with jax.named_scope("apply"):
        Vvel = gradient + cfg.virtual_momentum * state.Vvelocity
        Verr = state.Verror + Vvel

    k = min(cfg.k, cfg.grad_size)
    if _use_threshold_select(cfg):
        # exact selection without the large-d sort (ops/topk.py):
        # the update stays dense end-to-end and accounting takes the
        # bit-packed support of the LR-SCALED update — same value-
        # compare semantics as _lr_scaled_support (lr==0 coordinates
        # read as unchanged)
        from commefficient_tpu.ops.topk import threshold_topk_mask_1d
        with jax.named_scope("select"):
            mask = threshold_topk_mask_1d(jax.lax.square(Verr), k)
            update = jnp.where(mask, Verr, 0.0)
        support = {"bitmap": jnp.packbits((update * lr) != 0)}
    else:
        with jax.named_scope("select"):
            update, idx, vals = topk_with_support(
                Verr, k, approx=cfg.approx_topk,
                recall=cfg.approx_recall)
        support = _lr_scaled_support(idx, vals, lr)
    dense_mass = jnp.sum(jax.lax.square(Verr)) if probes else None
    with jax.named_scope("apply"):
        keep = update == 0
        # error feedback + momentum factor masking at transmitted coords
        Verr = jnp.where(keep, Verr, 0.0)
        Vvel = jnp.where(keep, Vvel, 0.0)
    new_state = ServerState(Vvel, Verr)
    pr = None
    if probes:
        pr = _state_probes(
            _l2(update * lr), new_state,
            {"mass_coverage": _coverage(
                jnp.sum(jax.lax.square(update)), dense_mass)})
    # participating clients' *local* velocities are masked at the same
    # coords by the round engine (the reference does this from the
    # optimizer via globals; here the mask travels in the result —
    # avoiding the reference's latent unset-global bug, SURVEY.md §2.1)
    return ServerUpdate(update * lr, new_state, keep, support,
                        probes=pr)


def _local_topk(cfg, local_topk_grad, state, lr, sketch, noise_rng,
                probes=False):
    # (fed_aggregator.py:546-568): momentum accumulation only; virtual
    # error is impossible (the transmitted quantity is already sparse)
    # and masking virtual momentum would zero all of it every round
    assert cfg.error_type in ("local", "none")
    with jax.named_scope("apply"):
        Vvel = local_topk_grad + cfg.virtual_momentum * state.Vvelocity
    new_state = ServerState(Vvel, state.Verror)
    pr = _state_probes(_l2(Vvel * lr), new_state) if probes else None
    return ServerUpdate(Vvel * lr, new_state, None, probes=pr)


def _sketched(cfg, sketched_grad, state, lr, sketch, noise_rng,
              probes=False):
    """FetchSGD server step (fed_aggregator.py:570-615): momentum and
    error accumulation happen in (r, c) sketch-table space; top-k
    recovery via unsketch; error feedback and momentum factor masking
    are applied in table space at the nonzero buckets of the re-sketch
    of the recovered update."""
    assert sketch is not None
    if cfg.error_type == "local":
        assert cfg.virtual_momentum == 0
    elif cfg.error_type == "virtual":
        assert cfg.local_momentum == 0

    with jax.named_scope("apply"):
        Vvel = sketched_grad + cfg.virtual_momentum * state.Vvelocity
        if cfg.error_type == "local":
            Verr = Vvel
        elif cfg.error_type == "virtual":
            Verr = state.Verror + Vvel
        else:  # "none": Verror stays zero forever -> zero updates,
            # exactly like the reference (fed_aggregator.py:581-587
            # never assigns)
            Verr = state.Verror

    # At large d the k-sparse form wins everywhere: re-sketching the
    # recovered update costs O(r*k) scatter-adds instead of the O(d)
    # dense kernel (~8 ms -> ~1.5 ms at GPT-2 124M), and the dense
    # (d,) update itself is never materialised (with_dense=False).
    # In the dense regime, exact recovery uses the threshold-select
    # mask instead of the top-k sort (22.3 -> ~11 ms full round at
    # ResNet9 scale, rounds 1-5's chip).
    sparse = sketch.prefer_sparse_resketch(cfg.k)
    # pre-mask residual mass for the coverage probe: the true dense
    # residual never exists in sketch mode, so its energy comes from
    # the table's own l2estimate (unbiased median-of-rows)
    dense_mass = (jax.lax.square(CountSketch.l2estimate(Verr))
                  if probes else None)
    if sketch.prefer_threshold_unsketch(cfg.k):  # implies not sparse
        update, _ = sketch.unsketch_dense_mask(Verr, k=cfg.k)
        # bit-packed support of the LR-scaled update: same value-
        # compare semantics as _lr_scaled_support
        support = {"bitmap": jnp.packbits((update * lr) != 0)}
        sel_mass = (jnp.sum(jax.lax.square(update)) if probes
                    else None)
    else:
        update, idx, vals = sketch.unsketch(Verr, k=cfg.k,
                                            with_support=True,
                                            with_dense=not sparse)
        support = _lr_scaled_support(idx, vals, lr)
        sel_mass = jnp.sum(jax.lax.square(vals)) if probes else None

    # re-sketch the recovered update to find which table buckets it
    # occupies (fed_aggregator.py:595-597)
    with jax.named_scope("resketch"):
        if sparse:
            sketched_update = sketch.sketch_sparse(idx, vals)
        else:
            sketched_update = sketch.sketch(update)
        keep = sketched_update == 0

    with jax.named_scope("apply"):
        if cfg.error_type == "virtual":
            Verr = jnp.where(keep, Verr, 0.0)
        # momentum factor masking in table space (both error types;
        # with error "local" this also masks Verror since they alias,
        # fed_aggregator.py:612-613)
        Vvel = jnp.where(keep, Vvel, 0.0)
        if cfg.error_type == "local":
            Verr = Vvel

    new_state = ServerState(Vvel, Verr)
    pr = None
    if probes:
        # update_norm from the lr-scaled support on the sparse path —
        # the dense update is never materialised there
        upd_norm = (_l2(support[1]) if sparse else _l2(update * lr))
        pr = _state_probes(
            upd_norm, new_state,
            {"mass_coverage": _coverage(sel_mass, dense_mass)})
    if sparse:
        # weight_update None: the server round applies the update as a
        # k-sized scatter of the (already lr-scaled) support instead
        # of materialising the dense (d,) vector
        return ServerUpdate(None, new_state, None, support,
                            probes=pr)
    return ServerUpdate(update * lr, new_state, None, support,
                        probes=pr)


def _psum_l2(x, axis_name) -> jax.Array:
    return jnp.sqrt(jax.lax.psum(jnp.sum(jax.lax.square(x)),
                                 axis_name))


def _gather_replicated(x, axis_name: str) -> jax.Array:
    """Tiled all-gather of a 1-D shard whose result ``shard_map``
    types as replicated over ``axis_name``: each peer writes its shard
    at its own offset of a zero buffer and the buffers are psum'd
    (adding zeros is exact). ``jax.lax.all_gather`` returns the same
    values but typed *varying*, and jax 0.9's public API has no
    invariant all-gather — so everything derived from it is refused by
    replicated ``out_specs``. Only for small operands: this moves an
    all-reduce's bytes, not an all-gather's."""
    buf = jnp.zeros((jax.lax.axis_size(axis_name),) + x.shape, x.dtype)
    buf = jax.lax.dynamic_update_index_in_dim(
        buf, x, jax.lax.axis_index(axis_name), 0)
    return jax.lax.psum(buf, axis_name).reshape(-1)


def sketched_update_2d(cfg: Config, sketch: CountSketch,
                       sketched_grad_loc: jax.Array,
                       state: ServerState, lr,
                       axis_name: str, n_model: int,
                       probes: bool = False) -> ServerUpdate:
    """Shard-local FetchSGD server step for the 2D ``clients`` ×
    ``model`` mesh — runs INSIDE shard_map with the sketch table's
    columns sharded over ``axis_name`` (``n_model`` peers, c/M columns
    each). Momentum and error-feedback accumulation stay shard-local,
    so per-device server state and the accumulate FLOPs scale as 1/M.
    Recovery re-materialises the full (r, c) table once per round (one
    tiled all-gather, 4·r·c bytes on the wire) and then runs as a
    distributed select: each peer estimates only its own contiguous
    d/M coordinate slice (``estimates_at``, bit-identical per
    coordinate to the rolled ``estimates``), the global k-th value is
    agreed via psum'd radix histograms, and the k winners are gathered
    (``distributed_threshold_mask_1d``). The selected set — hence the
    dense update, the support, and the re-sketch keep mask — matches
    the 1-D ``_sketched`` selection (lowest-index tie-break, same set
    as ``lax.top_k``)."""
    assert cfg.error_type in ("none", "virtual", "local")
    if cfg.error_type == "local":
        assert cfg.virtual_momentum == 0
    elif cfg.error_type == "virtual":
        assert cfg.local_momentum == 0

    d = cfg.grad_size
    k = min(cfg.k, d)
    with jax.named_scope("apply"):
        Vvel = sketched_grad_loc + cfg.virtual_momentum * state.Vvelocity
        if cfg.error_type == "local":
            Verr = Vvel
        elif cfg.error_type == "virtual":
            Verr = state.Verror + Vvel
        else:  # "none": zero updates forever, like the 1-D path
            Verr = state.Verror

    table = jax.lax.all_gather(Verr, axis_name, axis=1, tiled=True)

    # shard-local estimates over this peer's coordinate slice
    # [p·⌈d/M⌉, (p+1)·⌈d/M⌉); tail-shard padding slots are masked out
    # of the selection population, not zeroed into it
    p = jax.lax.axis_index(axis_name)
    n_loc = -(-d // n_model)
    start = (p * n_loc).astype(jnp.int32)
    gidx = start + jnp.arange(n_loc, dtype=jnp.int32)
    valid = gidx < d
    with jax.named_scope("estimates"):
        est = sketch.estimates_at(table, jnp.minimum(gidx, d - 1))
        est = jnp.where(valid, est, 0.0)

    from commefficient_tpu.ops.topk import distributed_threshold_mask_1d
    with jax.named_scope("select"):
        take = distributed_threshold_mask_1d(jax.lax.square(est), k,
                                             axis_name, valid=valid)
    # candidate extraction: pack this shard's winners into k slots
    # (index d = "empty"), gather all M·k slots, compact to exactly k —
    # the distributed mask selects exactly k coordinates globally
    pos = jnp.nonzero(take, size=k, fill_value=0)[0]
    n_take = jnp.sum(take.astype(jnp.int32))
    slot_ok = jnp.arange(k) < n_take
    cand_idx = jnp.where(slot_ok, start + pos.astype(jnp.int32), d)
    cand_val = jnp.where(slot_ok, est[pos], 0.0)
    # the k winners feed the three outputs that leave the shard_map
    # replicated (update, support, probes), so they are gathered in
    # the form shard_map can type as replicated — M·k elements
    cand_idx = _gather_replicated(cand_idx, axis_name)
    cand_val = _gather_replicated(cand_val, axis_name)
    sel = jnp.nonzero(cand_idx < d, size=k, fill_value=0)[0]
    idx = jnp.minimum(cand_idx[sel], d - 1)  # ascending global order
    vals = cand_val[sel]

    # CountSketch.l2estimate of the full table, from shard-local row
    # sums: the psum is what makes the probe replicated for shard_map
    dense_mass = (jnp.median(jax.lax.psum(
        jnp.sum(jax.lax.square(Verr), axis=1), axis_name))
        if probes else None)
    update = jnp.zeros(d, jnp.float32).at[idx].add(
        vals, mode="promise_in_bounds", unique_indices=True,
        indices_are_sorted=True)
    support = _lr_scaled_support(idx, vals, lr)

    # re-sketch the recovered update, slice this peer's columns, mask
    with jax.named_scope("resketch"):
        st = sketch.sketch_sparse(idx, vals)
        c_loc = Verr.shape[1]
        st_loc = jax.lax.dynamic_slice(st, (0, p * c_loc),
                                       (st.shape[0], c_loc))
        keep = st_loc == 0
    with jax.named_scope("apply"):
        if cfg.error_type == "virtual":
            Verr = jnp.where(keep, Verr, 0.0)
        Vvel = jnp.where(keep, Vvel, 0.0)
        if cfg.error_type == "local":
            Verr = Vvel
    new_state = ServerState(Vvel, Verr)

    pr = None
    if probes:
        pr = {"update_norm": _l2(update * lr),
              "momentum_norm": _psum_l2(Vvel, axis_name),
              "residual_norm": _psum_l2(Verr, axis_name),
              "mass_coverage": _coverage(
                  jnp.sum(jax.lax.square(vals)), dense_mass)}
    return ServerUpdate(update * lr, new_state, None, support,
                        probes=pr)

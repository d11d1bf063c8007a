"""Magnitude top-k sparsification.

TPU-native counterpart of reference utils.py:232-252 (`_topk`): keep
the k largest-magnitude entries of a vector (or of each row of a
matrix), zeroing the rest. Uses `jax.lax.top_k`, which XLA lowers to a
fused partial sort — no NaN workarounds needed (the reference's
zero-initialised output dance at utils.py:239-244 is a CUDA quirk).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Row size at/above which exact selection leaves lax.top_k (a full
# sort at large d on TPU). Current routing: DENSE selections use the
# threshold MASK + where (~3x at d = 6.6M, k = 50k on v5e); 1-D exact
# INDEX selection (unsketch recovery) uses ``threshold_topk_indices``:
# the mask + hierarchical extraction (a naive jnp.nonzero compaction
# would be a d-sized scatter and lose to the sort, the blocked-cumsum
# extraction does not), run over all of d (flat) or, where the k
# candidate blocks are a small part of d, over those alone (two-level,
# PR 33). On one v5e chip at k = 50,000, the selection and the gather
# of its values alone (PR 33's probe, my chip run; PERF.md section 6):
# flat 34.9 / 87.0 / 157.2 ms and two-level 15.9 / 19.6 / 22.5 ms at
# d = 124M / 376M / 701M. Only batched index selections and
# approx_max_k requests remain on the XLA primitives.
_THRESHOLD_SELECT_MIN_D = 1 << 20
_approx_override_logged = False


def use_threshold_select(k: int, d: int, approx: bool) -> bool:
    """The ONE gating predicate for the exact threshold-select path
    (shared by the dense ``topk`` here, the server helpers and
    ``CountSketch.prefer_threshold_unsketch`` — keep them from
    drifting): exact selection, genuine selection (k < d), and a row
    large enough that lax.top_k's sort lowering loses."""
    return not approx and k < d and d >= _THRESHOLD_SELECT_MIN_D


def selection_may_duplicate(d: int, approx: bool) -> bool:
    """The ONE predicate for "can a k-selection's index vector carry
    duplicates": only the big-d approx path (``CountSketch.unsketch``'s
    degenerate-tie guard clamps approx_max_k's out-of-range zero-tie
    picks to duplicate (d-1, 0) pairs). Consumers scattering from such
    a selection must use ADD semantics and must NOT assert
    unique_indices (core/rounds.py server scatter, unsketch's dense
    form) — both derive from here so the big-d gate cannot drift."""
    return approx and d >= _THRESHOLD_SELECT_MIN_D


def _blocked_cumsum(x: jax.Array, block: int = 1024) -> jax.Array:
    """Inclusive cumsum along the last axis via intra-block scans plus
    block-offset scans. XLA's flat cumsum over tens of millions of
    elements lowers to a multi-pass scan (~60 ms at d = 124M on v5e);
    the blocked form runs one short vectorized scan over (B, block)
    plus a tiny scan over B (~6 ms). Exact same values."""
    *lead, d = x.shape
    pad = (-d) % block
    xp = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad)])
    xb = xp.reshape(tuple(lead) + (-1, block))
    intra = jnp.cumsum(xb, axis=-1)
    offs = jnp.cumsum(intra[..., -1], axis=-1)
    offs = jnp.concatenate(
        [jnp.zeros_like(offs[..., :1]), offs[..., :-1]], axis=-1)
    out = (intra + offs[..., None]).reshape(
        tuple(lead) + (d + pad,))
    return out[..., :d]


def _threshold_topk_mask(sq: jax.Array, k: int) -> jax.Array:
    """Exact top-k selection MASK of non-negative ``sq`` along the
    last axis without sorting: binary-search the k-th largest value
    one bit at a time (non-negative f32 order == unsigned-int order on
    the bit pattern; 32 masked count-reductions stream the row instead
    of sorting it), then tie-break equal values by lowest index — the
    same selected set as ``lax.top_k`` (which also prefers lower
    indices on ties). Batched over leading axes; returns a boolean
    mask with exactly k True per row."""
    shape = sq.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    keys = jax.lax.bitcast_convert_type(
        sq.astype(jnp.float32), jnp.uint32).reshape(rows, d)

    # 32 single-bit passes, NOT the nibble search: under vmap (the
    # local_topk per-client masking) the batched nibble histogram
    # lowers worse than this simple loop (29.2 vs 20.3 ms/round
    # measured at ResNet9 scale); the nibble search wins only on the
    # 1-D fast path (threshold_topk_mask_1d)
    def body(i, thresh):
        bit = jnp.uint32(31) - i.astype(jnp.uint32)
        cand = thresh | (jnp.uint32(1) << bit)  # (rows,)
        cnt = jnp.sum((keys >= cand[:, None]).astype(jnp.int32),
                      axis=-1)
        return jnp.where(cnt >= k, cand, thresh)

    t = jax.lax.fori_loop(0, 32, body,
                          jnp.zeros((rows,), jnp.uint32))
    gt = keys > t[:, None]
    eq = keys == t[:, None]
    need = k - jnp.sum(gt.astype(jnp.int32), -1, keepdims=True)
    take = gt | (eq & (_blocked_cumsum(eq.astype(jnp.int32))
                       <= need))
    return take.reshape(shape)


def _nibble_threshold_key(keys: jax.Array, k: int,
                          axis_name: str = None,
                          valid: jax.Array = None) -> jax.Array:
    """k-th largest uint32 key of 1-D ``keys`` by an 8-pass 4-bit
    radix search (vs 32 single-bit passes): each pass histograms the
    current nibble among prefix-matching elements in one streamed
    read — same T as a single-bit binary search (tested), ~40% less
    search traffic at d = 124M. 1-D only: the batched variant was
    measured SLOWER than the single-bit loop under vmap (see
    _threshold_topk_mask).

    ``axis_name``: sum each pass's 16-bucket histogram over that mesh
    axis (``jax.lax.psum``) — the k-th key of the GLOBAL key
    population when ``keys`` is one shard of a vector distributed
    along the axis. Eight tiny (16,) all-reduces; every shard agrees
    on the same threshold. ``valid``: boolean mask excluding padding
    slots from the population (a zero key is a legitimate candidate —
    padding must be masked, not zeroed). Both default to None, which
    keeps the emitted single-device program byte-identical to before
    the parameters existed."""
    assert keys.ndim == 1

    def body(i, carry):
        t, remaining = carry
        shift = jnp.uint32(28) - 4 * i.astype(jnp.uint32)
        # prefix compare as two shifts of <= 28 and 4 bits — a single
        # shift by (shift + 4) would be a shift-by-32 on pass 0,
        # implementation-defined; this form is well-defined and yields
        # the correct all-match on the empty pass-0 prefix
        match = (((keys ^ t) >> shift) >> 4) == 0
        if valid is not None:
            match = match & valid
        nib = (keys >> shift) & 15
        counts = jnp.stack([
            jnp.sum((match & (nib == b)).astype(jnp.int32))
            for b in range(16)])
        if axis_name is not None:
            counts = jax.lax.psum(counts, axis_name)
        suffix = jnp.cumsum(counts[::-1])[::-1]  # count(nib >= b)
        ge = suffix >= remaining
        b = jnp.max(jnp.where(ge, jnp.arange(16), 0)).astype(jnp.uint32)
        above = jnp.where(b < 15, suffix[jnp.minimum(b + 1, 15)], 0)
        return (t | (b << shift), remaining - above)

    t, _ = jax.lax.fori_loop(0, 8, body,
                             (jnp.uint32(0), jnp.int32(k)))
    return t


def _take_from_threshold_1d(keys: jax.Array, t: jax.Array,
                            need) -> jax.Array:
    """take = (> t) ∪ (first ``need`` == t in index order) — the ONE
    XLA construction of the tie-broken mask (the Pallas kernel and
    the batched mask implement the same rule; equivalence-tested)."""
    gt = keys > t
    eq = keys == t
    return gt | (eq & (_blocked_cumsum(eq.astype(jnp.int32))
                       <= need))


def distributed_threshold_mask_1d(sq: jax.Array, k: int,
                                  axis_name: str,
                                  valid: jax.Array = None) -> jax.Array:
    """Exact global top-k selection MASK over non-negative values
    sharded along mesh axis ``axis_name``, where shard p holds the
    coordinates of a contiguous ascending slice (slices ordered by
    ``axis_index``). Runs inside shard_map: the nibble radix search
    agrees the global k-th key via psum'd histograms, then threshold
    ties are taken in GLOBAL lowest-index order — an exclusive
    cross-shard prefix of per-shard tie counts (one (1,) all-gather)
    tells each shard how many of its own ties survive. ``valid``
    masks padding slots out of the population entirely. The union of
    the returned local masks has exactly min(k, #valid) True bits and
    is the same selected set as the single-device threshold select /
    ``lax.top_k`` (lowest-index tie-break)."""
    assert sq.ndim == 1
    keys = jax.lax.bitcast_convert_type(
        sq.astype(jnp.float32), jnp.uint32)
    t = _nibble_threshold_key(keys, k, axis_name=axis_name,
                              valid=valid)
    gt = keys > t
    eq = keys == t
    if valid is not None:
        gt = gt & valid
        eq = eq & valid
    need = k - jax.lax.psum(jnp.sum(gt.astype(jnp.int32)), axis_name)
    eq_counts = jax.lax.all_gather(
        jnp.sum(eq.astype(jnp.int32)), axis_name)  # (n_shards,)
    p = jax.lax.axis_index(axis_name)
    before = jnp.sum(jnp.where(
        jnp.arange(eq_counts.shape[0]) < p, eq_counts, 0))
    local_need = need - before  # <= 0: this shard takes no ties
    return gt | (eq & (_blocked_cumsum(eq.astype(jnp.int32))
                       <= local_need))


def threshold_topk_mask_1d(sq: jax.Array, k: int, *,
                           interpret: bool = False,
                           force_xla: bool = False) -> jax.Array:
    """Fast 1-D exact threshold mask for the server-side selections
    (never vmapped): nibble radix search for the k-th largest key,
    then — on TPU — the fused Pallas take-mask kernel (one streamed
    read + int8 write instead of the XLA path's several (d,)-sized
    intermediates; ops/topk_pallas.py). Falls back to the generic
    XLA mask elsewhere. Same exactly-k, lowest-index-tie-break
    semantics (equivalence-tested; ``interpret``/``force_xla`` are
    test hooks selecting the branch explicitly)."""
    assert sq.ndim == 1
    d = sq.shape[0]
    keys = jax.lax.bitcast_convert_type(
        sq.astype(jnp.float32), jnp.uint32)
    t = _nibble_threshold_key(keys, k)
    from commefficient_tpu.ops import topk_pallas
    need = k - jnp.sum((keys > t).astype(jnp.int32))
    if force_xla or not topk_pallas.supported(d):
        return _take_from_threshold_1d(keys, t, need)
    if interpret:  # test hook: Pallas interpreter on any backend
        return topk_pallas.take_mask_pallas(
            sq.astype(jnp.float32), t.reshape(1), need.reshape(1),
            interpret=True)

    # branch selected at LOWERING time per platform (lax.platform_
    # dependent), not from jax.default_backend() at trace time: a
    # jit(..., backend="cpu") on a TPU-initialized process — or any
    # multi-backend embedder — gets the XLA mask, while tpu
    # lowerings get the fused Pallas take-mask kernel. Both branches
    # compute the identical exactly-k, lowest-index-tie-break mask
    # (equivalence-tested).
    def _pallas(sqf, t, need):
        return topk_pallas.take_mask_pallas(
            sqf, t.reshape(1), need.reshape(1))

    def _xla(sqf, t, need):
        return _take_from_threshold_1d(
            jax.lax.bitcast_convert_type(sqf, jnp.uint32), t, need)

    return jax.lax.platform_dependent(
        sq.astype(jnp.float32), t, need,
        tpu=_pallas, default=_xla)


def _threshold_topk_idx(sq: jax.Array, k: int) -> jax.Array:
    """Indices (ascending) of the threshold-select mask — used by
    tests to check set equivalence with lax.top_k; the hot paths use
    the mask directly (``jnp.nonzero`` compaction is a d-sized
    scatter) or the hierarchical extraction below."""
    take = _threshold_topk_mask(sq, k)

    def row_nonzero(m):
        return jnp.nonzero(m, size=k, fill_value=0)[0]

    if take.ndim == 1:
        return row_nonzero(take)
    flat = take.reshape(-1, take.shape[-1])
    return jax.vmap(row_nonzero)(flat).reshape(
        take.shape[:-1] + (k,))


def _flat_topk_indices(sq: jax.Array, k: int,
                       block: int = 1024) -> jax.Array:
    """``threshold_topk_indices``' flat form: the threshold mask over
    all of non-negative 1-D ``sq`` (an 8-pass nibble search for the
    k-th largest key, then the take-mask kernel) followed by
    hierarchical compaction of its k set bits — blockwise cumsums
    locate each output slot's block (searchsorted over block totals)
    and its column (argmax over the gathered block cumsum row). O(d)
    streaming (about 28 d-sized passes: PERF.md section 6, PR 33) +
    O(k·block) gather work, no sort and no d-sized scatter."""
    d = sq.shape[0]
    take = threshold_topk_mask_1d(sq, k)  # exactly k set bits
    pad = (-d) % block
    bits = jnp.pad(take, (0, pad)).reshape(-1, block)
    intra = jnp.cumsum(bits.astype(jnp.int32), axis=-1)  # (B, block)
    cum = jnp.cumsum(intra[:, -1])  # inclusive block totals (B,)
    slots = jnp.arange(k, dtype=jnp.int32)
    b = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
    offs = cum[b] - intra[b, -1]  # exclusive offset of block b
    j = slots - offs  # rank within block, 0-based
    rows = intra[b]  # (k, block) gather
    col = jnp.argmax(rows > j[:, None], axis=1).astype(jnp.int32)
    return b * block + col


# The two-level exact selection (PR 33). The flat form streams d-sized
# arrays about 28 times to find k of d numbers; where the k candidate
# blocks are a small part of d, the block maxima say which k blocks can
# hold a winner and the flat form runs over k·block candidates instead.
# The block is 128: a row of 128 is the 1-D array's own tile on the
# TPU, so the (d/128, 128) view costs nothing, and d/b + k·b, the flat
# work that is left, is least at b = sqrt(d/k) = 50 … 118 for the
# benchmark's cells; 256 / 512 / 1024 read 28.0 / 27.6 / 34.6 ms where
# 128 reads 22.5 at d = 701M (PR 33's probe, my chip run: PERF.md
# section 6). The ratio: a flat index selection costs
# about 4.5 ms + 0.24 ms a million coordinates, the two-level form
# two small ones + one read of d (4.6 ms at 701M), which crosses near
# d = 6·k·128.
_SELECT_BLOCK = 128
_SELECT_BLOCKED_MIN_RATIO = 8


def select_block(d: int, k: int) -> int:
    """The ONE rule for which form ``threshold_topk_indices`` takes, a
    function of the shapes alone: the candidate block size of the
    two-level form where its k·block candidates are at most a
    ``_SELECT_BLOCKED_MIN_RATIO``-th of d, else 0 (the flat form)."""
    b = _SELECT_BLOCK
    return b if d >= _SELECT_BLOCKED_MIN_RATIO * k * b else 0


def threshold_topk_indices(sq: jax.Array, k: int, block: int = 1024,
                           *, key=None,
                           coarse: int = None) -> jax.Array:
    """Exact top-k INDICES (ascending) of 1-D ``sq`` by its
    non-negative keys, without sorting and without a d-sized scatter.
    Same selected set as lax.top_k, including the lowest-index
    tie-break.

    ``key``: elementwise map from ``sq``'s values to the non-negative
    keys that are compared (``jax.lax.square`` for estimates); None:
    ``sq`` holds the keys. Keys are compared as the uint32 bit
    patterns of float32, so ±inf and NaN are ordered as the flat form
    always ordered them.

    Two forms, chosen from (d, k) by ``select_block``: flat
    (``_flat_topk_indices`` over all of d), or

    two-level, where d >= 8·k·128: (1) each contiguous block of 128
    coordinates gives its largest key, one streamed read of ``sq``;
    (2) the flat form over the d/128 block maxima picks the k blocks
    that can hold a winner (ascending, ties to the lowest block);
    (3) those k rows are gathered from ``sq`` itself (``key`` is
    applied after the gather: no d-sized keyed copy is ever written)
    and the flat form runs over their k·128 candidates, which are in
    ascending global order; (4) positions map back to coordinates.
    Why it is the same selection: with t the k-th largest key and t_lo
    the k-th largest block maximum, t >= t_lo; every key > t sits in a
    block whose maximum is > t_lo or among the lowest-indexed blocks
    whose maximum equals it, and so do the lowest-indexed ties at t
    that the rule takes (ISSUE 33's argument; tests/test_ops.py pins
    it, ties in excluded blocks included).

    ``coarse`` forces a form whatever the shapes (tests, the probe):
    a block size, or 0 for flat; a forced block still falls back to
    flat where there are not more than k blocks."""
    assert sq.ndim == 1, "hierarchical extraction is 1-D"
    d = sq.shape[0]
    keyed = (lambda x: x) if key is None else key
    b = select_block(d, k) if coarse is None else coarse
    if not b or -(-d // b) <= k:
        return _flat_topk_indices(keyed(sq), k, block)
    # rows of b (128: the 1-D array's own tiles on the TPU, so the view
    # costs nothing). A zero tail: key 0 is the smallest there is and
    # the tail holds the highest indices, so it changes no maximum and
    # no pad slot is ever taken (k blocks hold at least k real
    # coordinates)
    x = jnp.pad(sq, (0, (-d) % b)).reshape(-1, b)
    block_max = jnp.max(jax.lax.bitcast_convert_type(
        keyed(x).astype(jnp.float32), jnp.uint32), axis=1)
    cand = _flat_topk_indices(
        jax.lax.bitcast_convert_type(block_max, jnp.float32), k)
    pos = _flat_topk_indices(keyed(x[cand]).reshape(-1), k)
    return cand[pos // b] * b + pos % b


def _select_idx(vec: jax.Array, k: int, approx: bool,
                recall: float) -> jax.Array:
    """Indices of the k largest-magnitude entries along the last axis
    — the ONE place that chooses exact ``top_k`` vs
    ``approx_max_k`` (see ``topk`` for the tradeoff)."""
    if approx and k < vec.shape[-1]:
        _, idx = jax.lax.approx_max_k(jax.lax.square(vec), k,
                                      recall_target=recall)
    else:
        _, idx = jax.lax.top_k(jax.lax.square(vec), k)
    return idx


def topk(vec: jax.Array, k: int, approx: bool = False,
         recall: float = 0.95) -> jax.Array:
    """Return a copy of ``vec`` with everything but the ``k``
    largest-magnitude entries zeroed.

    1-D: global top-k. 2-D: row-wise top-k along the last axis
    (matching torch.topk's dim=-1 default used by the reference).

    ``approx``: use ``lax.approx_max_k`` at the given recall — the
    same --approx_topk tradeoff as unsketch recovery (missed
    coordinates stay in the error accumulator and resurface next
    round).

    At large rows (>= _THRESHOLD_SELECT_MIN_D) the DENSE selection
    always uses the exact threshold path — the mask (32 streaming
    count passes) feeds a ``where``, no sort and no gather/scatter —
    which measures faster than even ``approx_max_k`` + scatter while
    being exact (127 → 20 ms for the full local_topk round at ResNet9
    scale, rounds 1-5's chip). ``approx`` therefore only affects dense
    selections below the threshold size; the index-producing
    selections (unsketch recovery) still honor it everywhere."""
    k = min(k, vec.shape[-1])
    if vec.ndim not in (1, 2):
        raise ValueError(
            f"topk supports 1-D/2-D inputs, got ndim={vec.ndim}")
    if k < vec.shape[-1] \
            and vec.shape[-1] >= _THRESHOLD_SELECT_MIN_D:
        if approx:
            # once per process: --approx_topk runs at this size now
            # select a (different, exact) set than pre-round-3 builds
            # did — surface why comparisons against older runs moved
            global _approx_override_logged
            if not _approx_override_logged:
                _approx_override_logged = True
                import logging
                logging.getLogger(__name__).info(
                    "approx=True ignored for dense selection at d=%d "
                    ">= %d: the exact threshold-select path is faster "
                    "than the approximate sort; "
                    "selected sets differ from pre-threshold-select "
                    "builds", vec.shape[-1], _THRESHOLD_SELECT_MIN_D)
        take = _threshold_topk_mask(jax.lax.square(vec), k)
        return jnp.where(take, vec, jnp.zeros_like(vec))
    idx = _select_idx(vec, k, approx, recall)
    if vec.ndim == 1:
        return jnp.zeros_like(vec).at[idx].set(vec[idx], mode="promise_in_bounds")
    rows = jnp.arange(vec.shape[0])[:, None]
    return jnp.zeros_like(vec).at[rows, idx].set(
        vec[rows, idx], mode="promise_in_bounds")


def topk_values_indices(vec: jax.Array, k: int, approx: bool = False,
                        recall: float = 0.95):
    """(values, indices) of the k largest-magnitude entries of a 1-D
    vector — the sparse representation actually shipped over the wire
    when measuring upload bytes (k floats, fed_aggregator.py:296-297)."""
    idx = _select_idx(vec, min(k, vec.shape[-1]), approx, recall)
    return vec[idx], idx


def topk_with_support(vec: jax.Array, k: int, approx: bool = False,
                      recall: float = 0.95):
    """``(dense, indices, values)`` top-k of a 1-D vector: the zeroed
    dense form plus its sparse support in one place (the canonical
    scatter lives here so sparse-support consumers don't re-derive
    it). ``approx``: lax.approx_max_k selection (see ``topk``)."""
    vals, idx = topk_values_indices(vec, k, approx, recall)
    dense = jnp.zeros_like(vec).at[idx].set(vals,
                                            mode="promise_in_bounds")
    return dense, idx, vals

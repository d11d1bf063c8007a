"""Fused tied-head cross-entropy (fused-linear-CE) Pallas kernels.

The GPT-2 training loss computes ``CE(h @ wte.T, labels)`` where the
(tokens, vocab) logits tensor is ~200 MB f32 per 1k tokens at GPT-2
vocab. The chunked formulation (models/gpt2.py lm_nll_sums_chunked,
the reference loss is gpt2_train.py:88-99) bounds *peak memory* to one
chunk, but each chunk's logits still round-trip HBM up to three times
(forward store+load, checkpointed-backward recompute), and the
backward re-derives the logsumexp it already computed.

These kernels never write logits to HBM at all:

- ``_flce_fwd``: grid (token-blocks, vocab-blocks), vocab inner. Each
  step computes one (BM, BV) logits tile on the MXU and folds it into
  running online-softmax stats (max, sumexp) plus the label-logit
  gather, all VMEM-resident; per-token (lse, tok) vectors are the only
  HBM writes.
- ``_flce_bwd``: grid (vocab-blocks, token-blocks), token inner. One
  logits-tile recompute feeds BOTH gradient products:
  ``dW[j] += d_logitsᵀ @ x`` accumulates f32 in VMEM across the inner
  token loop (written once per vocab block), while ``d_logits @ W[j]``
  lands as a per-vocab-block partial of dX, summed by one cheap XLA
  reduction outside. Total backward matmul work equals the
  checkpointed chunked path (recompute + two products); the logits /
  d_logits HBM round-trips and the duplicate logsumexp pass are gone.

``lm_nll_sums_fused`` is a drop-in for ``lm_nll_sums_chunked`` (same
(Σ nll, Σ valid) per-example contract, same masking semantics) and
falls back to it off-TPU or at unsupported geometries. Gradients are
wired with jax.custom_vjp; vmap (the per-client axis in the federated
round) batches the pallas_call with a leading grid dimension as usual.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.pallas_common import out_struct

# Default tiles: (1024, 2048) keeps the weight-streaming traffic low
# (W is re-read once per token block: M/BM * |W|) while the f32
# logits tile (8 MB) and the backward's f32 dW accumulator (6.3 MB)
# stay comfortably inside VMEM. _STATS_LANES follows the TPU
# flash-attention convention: per-row running stats live in a
# (BM, 128) scratch (one full vreg lane-width) rather than a (BM, 1)
# column, which Mosaic lays out poorly.
_BLOCK_M = 1024
_BLOCK_V = 2048
_STATS_LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024
# The backward's dX comes out as per-vocab-block partials (nv, M, C)
# summed by one XLA reduction — 4x cheaper than the alternatives (an
# i-outer grid's dW partials are (nm, V, C) f32, ~4x larger at every
# M; a second dX kernel pass re-pays the full logits recompute,
# ~9x the partials' HBM traffic at GPT-2 vocab/width). The buffer is
# transient but real: nv * M * C * 2 bytes per call (times the client
# axis under vmap), so calls whose partials would exceed this cap
# fall back to the chunked path instead of risking an HBM OOM the
# chunked path doesn't have. 512 MB admits the T=1024 long-context
# geometry (M=8184 -> 315 MB/client) with an order of magnitude of
# HBM headroom at the benched client counts.
_DXP_LIMIT = 512 * 1024 * 1024


def supported(c: int) -> bool:
    """Pallas path requires a lane-aligned embedding width, and the
    backward's VMEM residents must fit the compiler budget: the f32
    dW accumulator (BV, C) + double-buffered w/x tiles + the f32
    logits/d_logits temporaries ((BM, BV), C-independent). Token and
    vocab counts are padded to tile multiples internally."""
    if c % 128 != 0:
        return False
    acc = _BLOCK_V * c * 4
    tiles = 2 * (_BLOCK_V * c * 2 + _BLOCK_M * c * 2)
    temps = 3 * _BLOCK_M * _BLOCK_V * 4
    return acc + tiles + temps <= _VMEM_LIMIT


def _fwd_kernel(lab_ref, x_ref, w_ref, lse_ref, tok_ref, m_s, s_s, t_s,
                *, nv, v_actual, block_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], -jnp.inf)
        s_s[...] = jnp.zeros_like(s_s[...])
        t_s[...] = jnp.zeros_like(t_s[...])

    x = x_ref[...]                                    # (BM, C)
    w = w_ref[...]                                    # (BV, C)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (BM, BV)
    vid = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(vid < v_actual, logits, -jnp.inf)

    lab = lab_ref[...]                                # (BM, 1)
    m_prev = m_s[...][:, :1]
    bmax = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, bmax)
    # first block: exp(-inf - finite) == 0 folds the empty carry in
    s_new = (s_s[...][:, :1] * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    # at most one vocab block contains the (in-range) label; the
    # where() keeps padded-vocab -inf out of the 0-weighted sum
    t_new = t_s[...][:, :1] + jnp.sum(
        jnp.where(vid == lab, logits, 0.0), axis=1, keepdims=True)

    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    s_s[...] = jnp.broadcast_to(s_new, s_s.shape)
    t_s[...] = jnp.broadcast_to(t_new, t_s.shape)

    @pl.when(j == nv - 1)
    def _write():
        lse_ref[...] = m_new + jnp.log(s_new)
        tok_ref[...] = t_new


def _bwd_kernel(lab_ref, x_ref, w_ref, lse_ref, gl_ref, gt_ref,
                dxp_ref, dw_ref, acc, *, nm, v_actual, block_v,
                compute_dtype):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc[...])

    x = x_ref[...]                                    # (BM, C)
    w = w_ref[...]                                    # (BV, C)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (BM, BV)
    vid = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    lse = lse_ref[...]                                # (BM, 1)
    # padded-vocab columns (w rows are zero-padded, so logits there
    # are 0, not -inf as in the forward) must not leak into p
    p = jnp.where(vid < v_actual, jnp.exp(logits - lse), 0.0)
    d = gl_ref[...] * p + gt_ref[...] * (vid == lab_ref[...]).astype(
        jnp.float32)                                  # (BM, BV) f32
    dc = d.astype(compute_dtype)
    dxp_ref[...] = jax.lax.dot_general(
        dc, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(
            dxp_ref.dtype)[None]                      # (1, BM, C)
    acc[...] += jax.lax.dot_general(
        dc, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (BV, C)

    @pl.when(i == nm - 1)
    def _write():
        dw_ref[...] = acc[...]


def _pad_rows(a, rows):
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _tile_geometry(m, v, block_m, block_v):
    """Shared fwd/bwd tiling: the custom_vjp backward MUST reproduce
    the forward's padding exactly for the residuals to line up, so
    both sides derive it here. Returns (bm, mp, vp, nm, nv)."""
    bm = min(block_m, max(8, -(-m // 8) * 8))
    mp = -(-m // bm) * bm
    vp = -(-v // block_v) * block_v
    return bm, mp, vp, mp // bm, vp // block_v


def _pad_operands(x, w, labels, mp, vp):
    """Zero-pad x/w to tile multiples; padded token rows get label -1
    (never matches a vocab id, and their cotangents are zero)."""
    xp = _pad_rows(x, mp)
    wp = _pad_rows(w, vp)
    lp = jnp.pad(labels.astype(jnp.int32), (0, mp - x.shape[0]),
                 constant_values=-1).reshape(mp, 1)
    return xp, wp, lp


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flce_lse_tok(x, w, labels, block_m=_BLOCK_M, block_v=_BLOCK_V,
                 interpret=False):
    """Per-token (logsumexp, label-logit) of ``x @ w.T`` without
    materialising the (M, V) logits. ``labels`` must be in-range
    (callers substitute 0 for ignored positions and mask outside).
    Differentiable in x and w; nll = lse - tok."""
    lse, tok = _flce_fwd_impl(x, w, labels, block_m, block_v, interpret)
    return lse, tok


def _flce_fwd_impl(x, w, labels, block_m, block_v, interpret):
    m, c = x.shape
    v = w.shape[0]
    bm, mp, vp, nm, nv = _tile_geometry(m, v, block_m, block_v)
    xp, wp, lp = _pad_operands(x, w, labels, mp, vp)

    lse, tok = pl.pallas_call(
        partial(_fwd_kernel, nv=nv, v_actual=v, block_v=block_v),
        grid=(nm, nv),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, c), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((mp, 1), jnp.float32, lp, xp, wp),
            out_struct((mp, 1), jnp.float32, lp, xp, wp),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, _STATS_LANES), jnp.float32),
            pltpu.VMEM((bm, _STATS_LANES), jnp.float32),
            pltpu.VMEM((bm, _STATS_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flce_fwd_pallas",
    )(lp, xp, wp)
    return lse[:m, 0], tok[:m, 0]


def _flce_vjp_fwd(x, w, labels, block_m, block_v, interpret):
    lse, tok = _flce_fwd_impl(x, w, labels, block_m, block_v, interpret)
    return (lse, tok), (x, w, labels, lse)


def _flce_vjp_bwd(block_m, block_v, interpret, res, g):
    x, w, labels, lse = res
    g_lse, g_tok = g
    m, c = x.shape
    v = w.shape[0]
    bm, mp, vp, nm, nv = _tile_geometry(m, v, block_m, block_v)
    xp, wp, lp = _pad_operands(x, w, labels, mp, vp)
    # padded token rows carry zero cotangent, so their (garbage) lse
    # rows and p values contribute nothing to either product
    lsep = jnp.pad(lse, (0, mp - m)).reshape(mp, 1)
    glp = jnp.pad(g_lse.astype(jnp.float32), (0, mp - m)).reshape(mp, 1)
    gtp = jnp.pad(g_tok.astype(jnp.float32), (0, mp - m)).reshape(mp, 1)
    operands = (lp, xp, wp, lsep, glp, gtp)

    dxp, dw = pl.pallas_call(
        partial(_bwd_kernel, nm=nm, v_actual=v, block_v=block_v,
                compute_dtype=x.dtype),
        grid=(nv, nm),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, c), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, c), lambda j, i: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, c), lambda j, i: (j, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_v, c), lambda j, i: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((nv, mp, c), x.dtype, *operands),
            out_struct((vp, c), jnp.float32, *operands),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_v, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flce_bwd_pallas",
    )(*operands)

    # f32 partials reduction: the nv per-vocab-block dX contributions
    # are near-cancelling around softmax mass, so a bf16 tree-sum
    # loses mantissa exactly where the gradient is smallest — bound
    # the rounding to the final cast
    dx = jnp.sum(dxp, axis=0, dtype=jnp.float32)[:m].astype(x.dtype)
    dwo = dw[:v].astype(w.dtype)
    return dx, dwo, np.zeros(labels.shape, jax.dtypes.float0)


flce_lse_tok.defvjp(_flce_vjp_fwd, _flce_vjp_bwd)


def resolve_fused_ce(flag: str, n_embd: int) -> bool:
    """Build-time resolution of --fused_ce (same pattern as
    core.rounds.resolve_rot_lanes): "auto" engages the Pallas path
    only when the process's default backend is TPU and the width is
    lane-aligned — programs built here then jitted onto another
    backend should pass "off"/"on" explicitly."""
    if flag == "on":
        return True
    if flag == "off":
        return False
    return jax.default_backend() == "tpu" and supported(n_embd)


_warned_fallbacks: set = set()


def fused_fallback_reason(e, tm, c, v, dtype, interpret=False,
                          batch_mult=1):
    """Why ``lm_nll_sums_fused`` would take the chunked path for this
    geometry — None when the fused kernels engage.

    ``batch_mult`` is the caller's vmapped multiplicity (the round's
    client axis): the dX-partials buffer exists once PER mapped call
    concurrently, so the OOM guard must scale by it — 8 clients x
    315 MB must not pass a 512 MB per-call check."""
    if not supported(c):
        return (f"embedding width {c} is not lane-aligned / "
                "VMEM-admissible")
    _, mp, _, _, nv = _tile_geometry(e * tm, v, _BLOCK_M, _BLOCK_V)
    dxp_bytes = max(1, int(batch_mult)) * nv * mp * c \
        * jnp.dtype(dtype).itemsize
    if dxp_bytes > _DXP_LIMIT:
        return (f"dX partials would be {dxp_bytes >> 20} MB "
                f"(x{max(1, int(batch_mult))} vmapped calls) — over "
                f"the {_DXP_LIMIT >> 20} MB cap")
    if not interpret and jax.default_backend() != "tpu":
        return (f"default backend is {jax.default_backend()!r}, "
                "not tpu (Mosaic kernels cannot lower)")
    return None


def lm_nll_sums_fused(h, wte, labels, dtype, ignore_index=-100,
                      tokens_per_chunk=1024, interpret=False,
                      batch_mult=1):
    """Drop-in for models.gpt2.lm_nll_sums_chunked backed by the
    fused kernels: per-example (Σ nll, Σ valid) of the tied-head LM
    cross-entropy, logits never materialised even per chunk. Falls
    back to the chunked path (honoring ``tokens_per_chunk``) at
    non-lane-aligned widths, when the backward's dX partials would
    exceed _DXP_LIMIT across ``batch_mult`` concurrent vmapped calls,
    and — unless ``interpret`` — on non-TPU default backends, where
    the Mosaic kernels cannot lower. The fallback warns once per
    reason: it used to be silent, so a timing could 'measure' the
    chunked path against itself."""
    e, tm, c = h.shape
    reason = fused_fallback_reason(e, tm, c, wte.shape[0], dtype,
                                   interpret=interpret,
                                   batch_mult=batch_mult)
    if reason is not None:
        if reason not in _warned_fallbacks:
            _warned_fallbacks.add(reason)
            import warnings
            warnings.warn("lm_nll_sums_fused falling back to the "
                          "chunked path: " + reason)
        from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
        return lm_nll_sums_chunked(h, wte, labels, dtype,
                                   ignore_index=ignore_index,
                                   tokens_per_chunk=tokens_per_chunk)
    with jax.named_scope("lm_head"):
        x = h.astype(dtype).reshape(e * tm, c)
        w = wte.astype(dtype)
        lab = labels.reshape(e * tm)
        valid = lab != ignore_index
        safe = jnp.where(valid, lab, 0)
        lse, tok = flce_lse_tok(x, w, safe, _BLOCK_M, _BLOCK_V,
                                interpret)
        nll = jnp.where(valid, lse - tok, 0.0).reshape(e, tm)
        sv = valid.reshape(e, tm).astype(jnp.float32)
        return jnp.sum(nll, axis=1), jnp.sum(sv, axis=1)

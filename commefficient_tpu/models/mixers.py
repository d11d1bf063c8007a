"""The Mamba-2 state-space mixer and grouped-query attention that the
hybrid language models share (``models/nemotron_h.py``,
``models/granite_hybrid.py``), as ``models/moe.py`` is the expert layer
two models route with; and the gated SiLU feed-forward part of the
dense blocks (``GatedMLP``: ``models/granite_hybrid.py``,
``models/ouro.py``). One code, parametrised by what differs between
the models: attention's score scale, and per layer whether q and k
are rotated by their positions and whether a query sees a window of
its past only.

    M:  [z | xBC | dt] = x W_in;  xBC = silu(conv4(xBC) + b)
        [x | B | C] = xBC;  delta = softplus(dt + dt_bias)
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T   (a head)
        y_t = S_t C_t + D x_t;   A = -exp(A_log)
        out = W_out GroupRMSNorm(y * silu(z))
    *:  softmax_causal(scale q k^T) v, the query heads of a group
        sharing its key/value head; positions only where a layer asks
        for them (``rope_theta``: RoPE on q and k, the half-split
        pairing, the whole head), and where it states a ``window`` a
        query i sees the keys j <= i with i - j < window

A configuration is any object with the fields the mixers read:
``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``,
``ssm_state_size``, ``conv_kernel``, ``chunk_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``layer_norm_epsilon``, ``initializer_range``, ``time_step_min`` /
``_max`` / ``_floor`` and ``dtype``.

What is TPU-shaped here:

- **The recurrence is computed by chunks** (SSD, Dao & Gu
  arXiv:2405.21060, chunks of ``chunk_size`` positions): inside a chunk
  the masked product ``(C B^T * L) X`` with ``L = exp(segsum(delta A))``,
  three matrix products a head on (chunk, chunk) tiles; between chunks
  the state, carried by a ``lax.scan`` over the chunks. It is
  differentiated by plain reverse mode under the clients ``vmap``
  (``core/rounds.py make_local_loss``) and ``--remat``. delta, A, the
  cumulative sums and the exponentials are float32; the products take
  operands in the compute dtype and accumulate in float32.
- **What the scan holds is bounded.** The in-chunk decay ``L`` is
  (sequences, chunks, heads, chunk, chunk) float32, and so are the
  masked product and their cotangents. Where that is more than
  ``SSD_DECAY_BYTES`` the heads are taken a block at a time (heads never
  meet inside the recurrence), each block under ``jax.checkpoint``, so a
  block's decay is all that is alive in either pass
  (``ssd_head_block``). Up to that size the whole is computed at once,
  as it always was. What it buys, end to end on the chip at 64 heads x
  chunks of 256 under a 4-client ``vmap``: 5.81 against 5.74 clients/s
  and 12.58 against 13.05 GB with all heads at once (PERF.md section 6,
  PR 34); at 16 heads x 128 nothing, and nothing is blocked there.
- **Attention does not materialise (heads, T, T) where that is large.**
  Past ``ATTN_SCORE_BYTES`` of float32 scores the queries are taken a
  block at a time against every key (``attn_query_block``): a block's
  rows are whole, so its softmax is the plain one, in float32, and
  nothing of the mathematics differs from the dense form; each block
  under ``jax.checkpoint``. Up to that size the dense form is built.
- **A window layer's cost follows the window, not T.** Where a layer
  states a window shorter than T a block of queries meets only the
  keys its band can reach: a slice of static length (the window
  rounded up to whole blocks, and the block itself) cut with
  ``lax.dynamic_slice`` from keys padded in front, masked to the exact
  band, the plain float32 softmax of the short rows (``attn_plan``).
  A window of T or more is the plain causal layer and is built as one.
- **On the chip every layer the kernel can take is one flash kernel.**
  Where the program runs on a TPU, with no ``query_block`` stated, T
  whole tiles and head sizes the kernel is given
  (``ATTN_KERNEL_HEAD_DIMS``: 64 or 128 for q, k and v; 192 for q and
  k beside 128 for v, ``models/joyai.py``'s latent attention),
  ``gqa_attention`` calls the library's splash kernel
  (``jax.experimental.pallas.ops.tpu.splash_attention``, its
  multi-query form: one call a key/value head with its group of query
  heads, under a ``vmap`` over key/value heads and one over
  sequences), whatever the size of the scores: the dense form's
  float32 (heads, T, T) tensor costs the chip its bandwidth several
  times a layer, forward, under ``--remat`` and backward (PERF.md
  section 6, PR 49: JoyAI's 4 heads at T 1,024 and Nemotron's 4 at
  2,048, 67 MB a client, both dense until then). Online softmax over
  (tile, tile) blocks of scores that never leave VMEM, float32 scores,
  maxima, sums and accumulator, bf16 operands; a ``CausalMask`` or a
  ``LocalMask((T, T), (window - 1, 0), 0)``, whose tables are built at
  trace time, once a shape, and name the tiles the grid visits: the
  causal half, the band. The kernel reads q and k's head size from q
  and v's from v, so the two may differ. The backward pass is the
  kernel's own (dq and dk/dv kernels that recompute the tiles from the
  kept output and log-sum-exp), so the blocked forms'
  ``jax.checkpoint`` and its forward pass are gone. The tile is 1,024
  where that wastes at most 15 % on what the mask hides, else 512
  (``attn_kernel_block``; PERF.md section 6, PR 42: SmallThinker's
  full layer 204.7 -> 45.1 ms, a window layer 108.9 -> 41.7, Granite's
  layer 29.5 -> 11.8, forward + backward alone). The scale is folded
  into q (float32, rounded to bf16 once more; exact where it is a
  power of two; a caller whose q carries its scale already says
  ``scale`` None and q goes in as it is). Off the chip, with a
  ``query_block`` (``SmallThinkerConfig.attn_query_block``, the
  probe's ``blocked_*`` and ``band_*`` rows) and at any other shape
  the ``jax.numpy`` forms above are built, untouched.

Every form is chosen from the platform and the shapes alone
(``attn_plan``): no flag, no environment variable, no model's name.

Scopes (``PERF.md`` section 3): ``ssm_mixer`` (the whole Mamba-2
mixer) > ``ssm_scan`` (decay sums, the in-chunk products, the state
scan; conv, gate-norm and projections outside it); ``gqa_attn``
(scores, softmax, value product; the four projections outside it) and,
where a model names its layers' kinds, ``attn_window`` / ``attn_full``
inside it: on the kernel path they hold the scale's fold, the
transposes to the kernel's (heads, T, D) layout and the kernel's
device operations, ``splash_mqa_fwd_residuals`` (twice a layer under
``--remat``), ``splash_mqa_dq_no_residuals`` and
``splash_mqa_dkv_no_residuals``; ``rope`` (the rotation of q and k,
outside ``gqa_attn``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

#: the most float32 in-chunk decay (sequences x chunks x heads x chunk x
#: chunk x 4 bytes) that ``ssd_chunked`` computes at once
SSD_DECAY_BYTES = 1 << 25

#: the most float32 scores (sequences x query heads x T x T x 4 bytes)
#: that ``GQAttention`` materialises, and the most a block of queries'
ATTN_SCORE_BYTES = 1 << 27
ATTN_BLOCK_BYTES = 1 << 25

#: the flash kernel's tiles: (edge, edge) scores of a block of queries
#: against a block of keys, the same in its three device operations
#: (forward, dq, dk/dv). The larger tile costs less a score (on the chip
#: at T 8,192, 28 / 4 heads of 128: 1.19 against 1.58 ns) and computes
#: more of what the mask hides: it is taken where the tiles visited hold
#: at most ``ATTN_KERNEL_WASTE`` scores for each one needed (the causal
#: half of 8,192: 1.12; its band of 4,096: 1.25, so 512 there). And the
#: head sizes the kernel is given, (q and k's, v's): the library reads
#: the one from q and the other from v
ATTN_KERNEL_BLOCKS = (1024, 512)
ATTN_KERNEL_WASTE = 1.15
ATTN_KERNEL_HEAD_DIMS = ((64, 64), (128, 128), (192, 128))


# --- the Mamba-2 recurrence, by chunks --------------------------------------

def ssd_head_block(S, T, H, G, chunk):
    """Heads of a group that ``ssd_chunked`` takes at once: all of them
    (``H // G``) where the (S, chunks, H, chunk, chunk) float32 decay is
    within ``SSD_DECAY_BYTES``, else as many as keep a block's within
    it."""
    Q = int(chunk)
    per_head = S * -(-T // Q) * Q * Q * 4
    hg = H // G
    if per_head * H <= SSD_DECAY_BYTES:
        return hg
    return max(1, min(hg, SSD_DECAY_BYTES // (per_head * G)))


def _ssd_heads(x, delta, A, B, C, Q, dtype):
    """``ssd_chunked`` of the heads it is given, all at once."""
    S, T, H, P = x.shape
    G, N = B.shape[-2:]
    hg = H // G
    nc = -(-T // Q)
    pad = nc * Q - T

    def chunks(v):                      # (S, T, n, ...) -> (S, nc, n, Q, ...)
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((S, nc, Q) + v.shape[2:]), 2, 3)

    a = chunks(delta * A)                                  # (S, nc, H, Q)
    xd = chunks((x.astype(jnp.float32) * delta[..., None]).astype(dtype))
    xd = xd.reshape(S, nc, G, hg, Q, P)
    Bc, Cc = chunks(B.astype(dtype)), chunks(C.astype(dtype))
    cs = jnp.cumsum(a, axis=-1)                            # float32
    # in a chunk: L[t, u] = exp(sum_{u < v <= t} a_v) for u <= t
    seg = cs[..., :, None] - cs[..., None, :]
    L = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg, -jnp.inf))
    cb = jnp.einsum("scgtn,scgun->scgtu", Cc, Bc,
                    preferred_element_type=jnp.float32)
    m = (cb[:, :, :, None] * L.reshape(S, nc, G, hg, Q, Q)).astype(dtype)
    y = jnp.einsum("scghtu,scghup->scghtp", m, xd,
                   preferred_element_type=jnp.float32)
    # what a chunk adds to the state by its end, and its whole decay
    to_end = jnp.exp(cs[..., -1:] - cs).reshape(S, nc, G, hg, Q, 1)
    added = jnp.einsum("scgun,scghup->scghpn", Bc,
                       (xd * to_end).astype(dtype),
                       preferred_element_type=jnp.float32)
    decay = jnp.exp(cs[..., -1]).reshape(S, nc, G, hg)

    def step(state, inp):
        add, dec = inp
        return state * dec[..., None, None] + add, state

    _, entering = jax.lax.scan(
        step, added[:, 0] * 0.0,
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)           # (S, nc, G, hg, P, N)
    # what the state a chunk entered with gives each of its positions
    y = y + jnp.einsum("scgtn,scghpn->scghtp", Cc, entering.astype(dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cs).reshape(S, nc, G, hg, Q, 1)
    y = jnp.moveaxis(y.reshape(S, nc, H, Q, P), 2, 3)
    return y.reshape(S, nc * Q, H, P)[:, :T]


def ssd_chunked(x, delta, A, B, C, chunk, dtype, head_block=None):
    """``y_t = S_t C_t`` with ``S_t = exp(delta_t A) S_{t-1} + delta_t
    x_t B_t^T`` and ``S_{-1} = 0``, a head at a time, by chunks.

    ``x`` (S, T, H, P); ``delta`` (S, T, H) float32; ``A`` (H,) float32,
    negative; ``B``, ``C`` (S, T, G, N), the H / G heads of a group
    sharing them. Returns ((S, T, H, P) float32, chunks scanned). T is
    padded to whole chunks with delta = 0, which leaves the state as it
    is and adds nothing. Heads are a batch axis and (chunk, chunk),
    (chunk, P), (chunk, N) the tiles, so every product is the MXU's.

    ``head_block`` heads of a group are computed at a time (default:
    ``ssd_head_block`` of the shapes). A group's heads that do not fill
    the last block are padded with heads of delta = 0 and x = 0, which
    give 0 and are cut off again."""
    S, T, H, P = x.shape
    G = B.shape[-2]
    Q, hg = int(chunk), H // G
    hb = int(head_block or ssd_head_block(S, T, H, G, Q))
    n = S * -(-T // Q)
    if hb >= hg:
        return _ssd_heads(x, delta, A, B, C, Q, dtype), n
    nb = -(-hg // hb)

    def blocks(v):      # (a, b, H, *rest) -> (nb, a, b, G * hb, *rest)
        lead, rest = v.shape[:2], v.shape[3:]
        v = jnp.pad(v.reshape(lead + (G, hg) + rest),
                    ((0, 0),) * 3 + ((0, nb * hb - hg),)
                    + ((0, 0),) * len(rest))
        v = jnp.moveaxis(v.reshape(lead + (G, nb, hb) + rest), 3, 0)
        return v.reshape((nb,) + lead + (G * hb,) + rest)

    @jax.checkpoint
    def one(args):
        xb, db, ab = args
        return _ssd_heads(xb, db, ab[0, 0], B, C, Q, dtype)

    # A is a slice of the flat weight vector. Left open, the compiler
    # carries the (blocks, heads) shape back through the slice and
    # reshapes all d weights to (d / heads a block, heads a block), whose
    # tiles pad the short axis to 128: 8 x d floats at blocks of 16
    A = jax.lax.optimization_barrier(A)
    y = jax.lax.map(one, (blocks(x), blocks(delta), blocks(A[None, None])))
    y = jnp.moveaxis(y.reshape(nb, S, T, G, hb, P), 0, 3)
    return y.reshape(S, T, G, nb * hb, P)[:, :, :, :hg].reshape(
        S, T, H, P), n


# --- attention ------------------------------------------------------------

def attn_query_block(S, T, Hq):
    """Queries ``GQAttention`` takes at a time: all ``T`` (the dense
    form) where the (S, Hq, T, T) float32 scores are within
    ``ATTN_SCORE_BYTES``; else a multiple of 128 whose block of scores
    is within ``ATTN_BLOCK_BYTES`` (at least 128)."""
    if S * Hq * T * T * 4 <= ATTN_SCORE_BYTES:
        return T
    return int(max(1, ATTN_BLOCK_BYTES // (S * Hq * T * 4 * 128)) * 128)


class AttnPlan(NamedTuple):
    """How ``gqa_attention`` builds a layer, from the platform and the
    shapes alone: ``block`` queries at a time, each block against
    ``keys`` keys; ``blocked`` unless every query meets every key at
    once (the dense form); ``banded`` where a window cuts the keys a
    block meets. ``pairs`` (query, key) scores a head of a sequence
    computes that way and ``needed`` of them lie in the causal band.
    ``kernel``: None for the ``jax.numpy`` forms; ``"splash"`` where
    the flash kernel is called, on (``block``, ``block``) tiles of the
    scores (``attn_kernel_block``): ``keys`` is then the most keys a
    block of queries visits and ``pairs`` the tiles its grid visits
    times their size; ``"splash_interpret"`` the same kernel
    interpreted off the chip (the tests')."""
    block: int
    keys: int
    blocked: bool
    banded: bool
    pairs: int
    needed: int
    kernel: Optional[str] = None


def _platform():
    """Where the program runs, as ``ops/sketch.py`` resolves its
    backend. The tests patch it: ``"tpu"`` to lower the kernel path
    from the CPU, ``"interpret"`` to run it there by value."""
    return jax.devices()[0].platform


def kernel_tiles(T, block, window=None):
    """(tiles of (block, block) scores the flash kernel's grid visits,
    the most of them in a row of tiles): a row's tiles from the one
    that holds its first query's oldest key to the one on the
    diagonal."""
    rows = [row + 1 - (0 if window is None else max(
        0, (row * block - (window - 1)) // block))
        for row in range(T // block)]
    return sum(rows), max(rows)


def attn_kernel_block(T, needed, window=None):
    """The flash kernel's tile edge for T positions under ``window``
    (None: the whole past), ``needed`` scores in its mask: of
    ``ATTN_KERNEL_BLOCKS`` that T is whole tiles of, the largest that
    wastes no more than ``ATTN_KERNEL_WASTE`` allows, else the
    smallest; None where T is whole tiles of none."""
    fit = [b for b in ATTN_KERNEL_BLOCKS if T % b == 0]
    return next((b for b in fit if kernel_tiles(T, b, window)[0] * b * b
                 <= ATTN_KERNEL_WASTE * needed), min(fit, default=None))


def attn_plan(S, T, Hq, window=None, query_block=None, head_dim=None,
              platform=None):
    """The ``AttnPlan`` of (S, T, Hq) under ``window`` (None, or T or
    more: the whole past). A banded block of ``b`` queries from
    ``first`` meets the keys ``[first - back, first + b)`` with ``back``
    = ``window - 1`` rounded up to whole blocks: ``b + back`` keys,
    whatever ``first`` is.

    The flash kernel takes the layer where all of this holds, and
    nothing else is asked: the program runs on a TPU (``platform``,
    default ``_platform()``); no ``query_block`` is given; T is whole
    tiles (``attn_kernel_block``); ``head_dim`` (one size for q, k and
    v, or the pair (q and k's, v's); None: not said, no kernel) is one
    of ``ATTN_KERNEL_HEAD_DIMS``. How large the ``jax.numpy`` form's
    scores would be is not asked there: it chooses between the dense
    and the blocked form off the chip."""
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys sees nothing")
    w = None if window is None or window >= T else int(window)
    needed = T * (T + 1) // 2 if w is None \
        else w * (w + 1) // 2 + (T - w) * w
    bq = int(query_block or attn_query_block(S, T, Hq))
    blocked = w is not None or bq < T
    kernel = {"tpu": "splash", "interpret": "splash_interpret"}.get(
        _platform() if platform is None else platform)
    dims = tuple(head_dim) if isinstance(head_dim, (tuple, list)) \
        else (head_dim, head_dim)
    tile = attn_kernel_block(T, needed, w) if kernel and not query_block \
        and dims in ATTN_KERNEL_HEAD_DIMS else None
    if tile:
        tiles, widest = kernel_tiles(T, tile, w)
        return AttnPlan(tile, widest * tile, True, w is not None,
                        tiles * tile * tile, needed, kernel)
    if w is None:
        return AttnPlan(bq, T, blocked, False,
                        -(-T // bq) * bq * T if blocked else T * T, needed)
    if not query_block:
        # a block's scores within ATTN_BLOCK_BYTES, as the full form's;
        # no wider than the window: the slack is a block a block
        bq = int(min(max(1, ATTN_BLOCK_BYTES // (S * Hq * (w + 128) * 4
                                                 * 128)), -(-w // 128))
                 * 128)
    keys = bq + -(-(w - 1) // bq) * bq
    return AttnPlan(bq, keys, True, True, -(-T // bq) * bq * keys, needed)


def rope(x, theta):
    """Rotary positions 0 .. T-1 on the last axis of ``x`` (S, T, ...,
    D), float32 in and out: dimension i < D/2 is paired with i + D/2
    (the ``rotate_half`` convention) and turned by t * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _banded_attention(q, k, v, scale, plan, window):
    """``gqa_attention`` of a window shorter than T."""
    S, T, Hkv, g, D = q.shape
    bq, L = plan.block, plan.keys
    back = L - bq
    nq = -(-T // bq)
    tail = nq * bq - T
    kp = jnp.pad(k, ((0, 0), (back, tail), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (back, tail), (0, 0), (0, 0)))

    @jax.checkpoint
    def band(qb, first):
        """The queries at positions first, first + 1, ... against the
        keys at first - back, ...: row ``first`` of the padded keys."""
        kb = jax.lax.dynamic_slice_in_dim(kp, first, L, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, first, L, axis=1)
        att = jnp.einsum("stgqd,sugd->sgqtu", qb, kb,
                         preferred_element_type=jnp.float32) * scale
        i = (first + jnp.arange(bq))[:, None]
        j = (first - back + jnp.arange(L))[None, :]
        seen = (j >= 0) & (j <= i) & (i - j < window)
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("sgqtu,sugd->stgqd", att.astype(qb.dtype), vb)

    qp = jnp.pad(q, ((0, 0), (0, tail)) + ((0, 0),) * 3)
    qp = jnp.moveaxis(qp.reshape(S, nq, bq, Hkv, g, D), 1, 0)
    out = jax.lax.map(lambda a: band(*a),
                      (qp, jnp.arange(nq, dtype=jnp.int32) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(
        S, nq * bq, Hkv, g, v.shape[-1])[:, :T]


@functools.lru_cache(maxsize=32)
def _splash_kernel(T, window, group, block, interpret):
    """The library's splash kernel in its multi-query form: ``group``
    query heads (group, T, D) on one key/value head (T, D), the causal
    mask or the band ``i - j < window``, (block, block) tiles in all
    three device operations. The mask's tables are built here, once a
    shape, and kept as numpy: constants of whichever program is
    traced."""
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)
    mask = masks.CausalMask((T, T)) if window is None \
        else masks.LocalMask((T, T), (window - 1, 0), 0)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mqa_single_device(
            mask=masks.MultiHeadMask([mask] * group), block_sizes=sizes,
            interpret=interpret)
    return jax.tree.map(np.asarray, kernel)


def _kernel_attention(q, k, v, scale, plan, window):
    """``gqa_attention`` through the flash kernel: online softmax over
    the tiles the mask lets through, the scores never outside VMEM;
    its backward pass (two more kernels) keeps the output and the rows'
    log-sum-exp and recomputes the tiles. The kernel takes no scale:
    it is folded into q, in float32, rounded once more to q's dtype;
    ``scale`` None says that q carries it already. v's head size may
    be another than q and k's."""
    S, T, Hkv, g, D = q.shape
    kernel = _splash_kernel(T, int(window) if plan.banded else None, g,
                            plan.block, plan.kernel == "splash_interpret")
    qs = q if scale is None \
        else (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads = jax.vmap(jax.vmap(kernel))      # sequences, key/value heads
    out = heads(jnp.transpose(qs, (0, 2, 3, 1, 4)),
                jnp.transpose(k, (0, 2, 1, 3)),
                jnp.transpose(v, (0, 2, 1, 3)))     # (S, Hkv, g, T, D)
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def gqa_attention(q, k, v, scale, query_block=None, window=None):
    """Causal softmax attention of grouped query heads, exact: ``q``
    (S, T, Hkv, Hq / Hkv, D), ``k`` (S, T, Hkv, D), ``v`` (S, T, Hkv,
    Dv) -> ((S, T, Hkv, Hq / Hkv, Dv) in q's dtype, whether the (heads,
    T, T) scores never exist: a blocked form or the kernel); scores,
    softmax and its statistics float32, the value product in q's dtype.
    ``scale`` multiplies the scores; None where q carries it already.
    ``query_block`` queries at a time (default: ``attn_query_block`` of
    the shapes); with fewer than T the (heads, T, T) scores never
    exist: each block of queries meets every key, masks what lies ahead
    of it and takes the plain softmax of its whole rows, under
    ``jax.checkpoint``. With a ``window`` shorter than T, query i sees
    the keys j <= i with i - j < window, and a block of queries meets
    only the slice of keys its band reaches (``attn_plan``): the same
    softmax of shorter rows. On a TPU, with no ``query_block`` given,
    every form is the flash kernel's where it takes the shapes
    (``attn_plan``)."""
    S, T, Hkv, g, D = q.shape
    plan = attn_plan(S, T, Hkv * g, window, query_block,
                     (D, v.shape[-1]))
    if plan.kernel:
        return _kernel_attention(q, k, v, scale, plan, window), True
    scale = 1.0 if scale is None else scale
    if plan.banded:
        return _banded_attention(q, k, v, scale, plan, int(window)), True
    bq = plan.block

    if bq >= T:                     # the dense form, as it always was
        att = jnp.einsum("stgqd,sugd->sgqtu", q, k,
                         preferred_element_type=jnp.float32) * scale
        causal = jnp.tril(jnp.ones((T, T), bool))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        out = jnp.einsum("sgqtu,sugd->stgqd", att.astype(q.dtype), v)
        return out, False

    @jax.checkpoint
    def rows(qb, first):
        """The queries at positions first, first + 1, ..."""
        att = jnp.einsum("stgqd,sugd->sgqtu", qb, k,
                         preferred_element_type=jnp.float32) * scale
        seen = (first + jnp.arange(bq))[:, None] >= jnp.arange(T)[None, :]
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("sgqtu,sugd->stgqd", att.astype(qb.dtype), v)

    nq = -(-T // bq)
    qp = jnp.pad(q, ((0, 0), (0, nq * bq - T)) + ((0, 0),) * 3)
    qp = jnp.moveaxis(qp.reshape(S, nq, bq, Hkv, g, D), 1, 0)
    out = jax.lax.map(lambda a: rows(*a),
                      (qp, jnp.arange(nq, dtype=jnp.int32) * bq))
    out = jnp.moveaxis(out, 0, 1).reshape(
        S, nq * bq, Hkv, g, v.shape[-1])[:, :T]
    return out, True


# --- layers ---------------------------------------------------------------

class Weights(nn.Module):
    """Declares matrices under the reference's names; no ``Dense``:
    there are no biases, and several are used as stacks."""
    cfg: Any

    def mat(self, name, shape):
        return self.param(name, nn.initializers.normal(
            stddev=self.cfg.initializer_range), shape)


def _dt_bias_init(cfg):
    """Mamba-2's: delta = exp(U(log min, log max)) floored, through the
    inverse of softplus."""
    def init(key, shape):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        d = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg.time_step_floor)
        return d + jnp.log(-jnp.expm1(-d))
    return init


def _uniform(lo, hi, fn=lambda v: v):
    return lambda key, shape: fn(jax.random.uniform(
        key, shape, jnp.float32, lo, hi))


class Mamba2Mixer(Weights):
    """``(y, chunks scanned)``."""

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T, C = x.shape
        H, P, G = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups
        N, K = cfg.ssm_state_size, cfg.conv_kernel
        inner, bc = H * P, G * N
        in_proj = self.mat("in_proj", (C, 2 * inner + 2 * bc + H))
        bound = K ** -0.5           # the conv keeps PyTorch's default
        conv_w = self.param("conv_w", _uniform(-bound, bound),
                            (K, inner + 2 * bc))
        conv_b = self.param("conv_b", _uniform(-bound, bound),
                            (inner + 2 * bc,))
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (H,))
        a_log = self.param("A_log", _uniform(1.0, 16.0, jnp.log), (H,))
        skip = self.param("D", nn.initializers.ones, (H,))
        scale = self.param("gate_norm", nn.initializers.ones, (inner,))
        out_proj = self.mat("out_proj", (inner, C))
        with jax.named_scope("ssm_mixer"):
            zxd = x @ in_proj.astype(dt)
            z = zxd[..., :inner].astype(jnp.float32)
            xbc = zxd[..., inner:2 * inner + 2 * bc].astype(jnp.float32)
            delta = jax.nn.softplus(
                zxd[..., 2 * inner + 2 * bc:].astype(jnp.float32) + dt_bias)
            # causal depthwise conv: position t sees t-K+1 .. t
            xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            xbc = jax.nn.silu(sum(conv_w[k] * xp[:, k:k + T]
                                  for k in range(K)) + conv_b)
            xs = xbc[..., :inner].reshape(S, T, H, P)
            with jax.named_scope("ssm_scan"):
                y, n = ssd_chunked(
                    xs, delta, -jnp.exp(a_log),
                    xbc[..., inner:inner + bc].reshape(S, T, G, N),
                    xbc[..., inner + bc:].reshape(S, T, G, N),
                    cfg.chunk_size, dt)
            y = (y + skip[:, None] * xs).reshape(S, T, inner)
            # gate first, then one norm a group
            g = (y * jax.nn.silu(z)).reshape(S, T, G, inner // G)
            g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                                  + cfg.layer_norm_epsilon)
            out = (g.reshape(S, T, inner) * scale).astype(dt) \
                @ out_proj.astype(dt)
        return out, n


class GQAttention(Weights):
    """``scale`` multiplies the scores: ``head_dim ** -0.5`` where the
    model states none. With ``with_form`` the result is ``(y, whether
    the blocked form was built)``, for a model that counts it. A layer
    with ``rope_theta`` rotates q and k by their positions 0 .. T-1
    (float32, scope ``rope``); one with a ``window`` sees that many
    keys, itself among them; ``kind_scope`` names the layer's kind
    inside ``gqa_attn``; ``query_block`` as ``gqa_attention``'s."""
    scale: Optional[float] = None
    with_form: bool = False
    window: Optional[int] = None
    rope_theta: Optional[float] = None
    kind_scope: Optional[str] = None
    query_block: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T, C = x.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        wq, wk = self.mat("q", (C, Hq * D)), self.mat("k", (C, Hkv * D))
        wv, wo = self.mat("v", (C, Hkv * D)), self.mat("o", (Hq * D, C))
        q = (x @ wq.astype(dt)).reshape(S, T, Hkv, Hq // Hkv, D)
        k = (x @ wk.astype(dt)).reshape(S, T, Hkv, D)
        v = (x @ wv.astype(dt)).reshape(S, T, Hkv, D)
        if self.rope_theta is not None:
            with jax.named_scope("rope"):
                q = rope(q.astype(jnp.float32), self.rope_theta).astype(dt)
                k = rope(k.astype(jnp.float32), self.rope_theta).astype(dt)
        kind = contextlib.nullcontext() if self.kind_scope is None \
            else jax.named_scope(self.kind_scope)
        with jax.named_scope("gqa_attn"), kind:
            out, blocked = gqa_attention(q, k, v, float(
                D ** -0.5 if self.scale is None else self.scale),
                self.query_block, self.window)
        out = out.reshape(S, T, Hq * D) @ wo.astype(dt)
        return (out, blocked) if self.with_form else out


class GatedMLP(Weights):
    """The gated SiLU feed-forward part, ``(silu(x W_g) * x W_u) W_d``
    with ``w_in`` = [W_g | W_u] one (C, 2 ``width``) matrix and
    ``w_out`` = W_d, no bias (``models/granite_hybrid.py``,
    ``models/ouro.py``); scope ``dense_mlp`` holds both products and
    the gate, the norm before it lies outside."""
    width: int = 0

    @nn.compact
    def __call__(self, x):
        dt = self.cfg.dtype
        C, F = x.shape[-1], self.width
        w_in, w_out = self.mat("w_in", (C, 2 * F)), self.mat("w_out", (F, C))
        with jax.named_scope("dense_mlp"):
            ab = x @ w_in.astype(dt)
            return (jax.nn.silu(ab[..., :F]) * ab[..., F:]) \
                @ w_out.astype(dt)

"""Pallas TPU kernels for the count-sketch hot path.

The XLA formulation in :mod:`commefficient_tpu.ops.sketch` materialises
an ``(r, padded_d)`` intermediate for recovery (~140 MB at the flagship
ResNet9 geometry) and re-reads the signed vector once per row when
sketching. These kernels fuse sign application (streamed packed sign
bits by default, in-register murmur mix of the coordinate index for
r > 8), the per-(row, chunk) rotation, and the accumulate/median into
single passes:

- ``sketch_pallas``: one streamed read of the (padded) vector, table
  accumulated in VMEM across the chunk grid — HBM traffic ~= |v| + |table|
  instead of r·|v|.
- ``estimates_pallas``: table stays VMEM-resident across the chunk grid;
  the (r, padded_d) estimate tensor is never materialised — each chunk's
  r rolled/sign-corrected rows are medianed in-register (min/max
  selection network for the flagship r=5 and r=3; odd-even
  transposition sort for other r) and written once.

Hash-identity contract: identical rotation/sign streams to the XLA
path, so Pallas and XLA replicas can mix freely under ``psum``. Tables
match to ULP-level tolerance (chunk summation order differs); recovery
from a given table is bit-exact. Property-tested in
tests/test_pallas_sketch.py.

Rotation trick: a chunk of width c is viewed as a 2-D ``(S, L)`` tile
(L a multiple of 128, so lane-aligned). A 1-D circular shift by
``o = a·L + b`` decomposes into two sublane rolls (a, a+1), a lane roll
(b) of each, and a lane-index select — all supported by Mosaic's
``dynamic_rotate`` at any alignment, unlike a flat 1-D rotate of
unaligned width. Requires ``c % 128 == 0`` (the auto backend falls back
to XLA otherwise, e.g. for the reference's default c=500000).

Reference provenance: this implements the same operator as the
reference's external CUDA ``csvec`` library (fed_aggregator.py:466-469,
fed_worker.py:315-322) — see SURVEY.md §2.9.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.pallas_common import out_struct
from commefficient_tpu.ops.sketch import _mix as _mix_u32  # noqa: E402
# (single source of truth for the murmur mix: the psum-mixing contract
# requires the Pallas and XLA sign streams to stay bit-identical)

# table must stay VMEM-resident for the estimates kernel. The kernels
# raise the Mosaic scoped-VMEM budget (default 16 MB) via
# CompilerParams — v5e cores have headroom well past 64 MB (verified
# on hardware) — so the bound here is table + temporaries with margin.
_TABLE_VMEM_LIMIT = 20 * 1024 * 1024
_VMEM_CEILING = 64 * 1024 * 1024


def _compiler_params(table_bytes: int):
    # table resident + r per-chunk temp rows (~table again) + double-
    # buffered chunk blocks + relayout scratch, with margin
    want = min(_VMEM_CEILING, max(32 * 1024 * 1024, 3 * table_bytes))
    return pltpu.CompilerParams(vmem_limit_bytes=want)


def _pick_lanes(c: int) -> int | None:
    """Widest lane-aligned factorisation of the chunk width."""
    for L in (1024, 512, 256, 128):
        if c % L == 0:
            return L
    return None


def supported(d: int, c: int, r: int) -> bool:
    """Whether the Pallas backend can run this geometry (else XLA).

    The table limit is empirical: the estimates kernel also streams r
    per-chunk value arrays through the median network, but Mosaic's
    scheduler handles the flagship r=5, c=2^19 case (10.5 MB table) on
    v5e. Geometries pushing right up to the limit may still OOM VMEM
    at compile — set backend="xla" explicitly there. The number of
    chunks m no longer bounds it: up to ``_ROT_WHOLE`` entries the
    (r, m) rotation table lies whole in SMEM, as it always has, and
    past that it passes through SMEM ``_ROT_BLOCK`` chunks at a time
    (``_rot_operand``), still inside one kernel call ((5, 1337),
    d = 701M at c = 2^19: PERF.md section 6, PR 32). What bounds d
    now is the kernels' index arithmetic, padded d < 2^31 (the
    estimates kernel's ``valid`` mask compares int32 positions), and
    the chip's memory for the vector itself."""
    L = _pick_lanes(c)
    if L is None or 4 * r * c > _TABLE_VMEM_LIMIT:
        return False
    return -(-d // c) * c < 2 ** 31


#: (r, m) rotation tables of at most this many entries lie whole in
#: SMEM (what every geometry up to PR 31 ran with, bit for bit)
_ROT_WHOLE = 4096
#: past that, the chunks of rotations one SMEM block holds
_ROT_BLOCK = 512


def _rot_operand(rot, r: int, m: int):
    """The (r, m) rotation table as the kernels' first operand:
    ``(operand, its BlockSpec, read)`` with ``read(ref, row, t)`` the
    rotation of ``row`` at grid step ``t``. A small table is one
    unblocked SMEM operand. A larger one is padded to whole blocks of
    ``_ROT_BLOCK`` chunks and block ``t // _ROT_BLOCK`` is the one in
    SMEM at step ``t`` (the pipeline fetches the next as the steps
    reach it), so SMEM holds the same few KB whatever m is."""
    rot = rot.astype(jnp.int32)
    if r * m <= _ROT_WHOLE:
        return (rot, pl.BlockSpec(memory_space=pltpu.SMEM),
                lambda ref, row, t: ref[row, t])
    rot = jnp.pad(rot, ((0, 0), (0, (-m) % _ROT_BLOCK)))
    spec = pl.BlockSpec((r, _ROT_BLOCK), lambda t: (0, t // _ROT_BLOCK),
                        memory_space=pltpu.SMEM)
    return rot, spec, lambda ref, row, t: ref[row, t % _ROT_BLOCK]


def _sign_hash_chunk(t, sign_seed: np.uint32, c: int, S: int, L: int,
                     r: int):
    """One-mix sign scheme (CountSketch._one_mix_signs, r <= 16): a
    single murmur mix of the global index per chunk element; row r's
    sign is bit 16+r. Hoisted out of the kernels' row loops — hashing
    was the dominant kernel cost at 1 mix per (row, coord)."""
    assert r <= 16
    s_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 0)
    l_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 1)
    g = t.astype(jnp.uint32) * jnp.uint32(c) + s_idx * jnp.uint32(L) + l_idx
    return _mix_u32(g ^ sign_seed)


def _flip_from_hash(h, row: int):
    """Sign-bit flip mask for row ``row`` from the one-mix hash: bit
    16+row of ``h`` moved to bit 31. XORing a float32 with this mask
    IS multiplication by the row's ±1 sign (IEEE sign-bit flip is
    exact, bit-identical to ``x * (1 - 2*bit)`` incl. ±0), at 2 VPU
    ops instead of the extract/convert/multiply chain (~7)."""
    assert 0 <= row <= 15
    return (h << (15 - row)) & jnp.uint32(0x80000000)


def _flip_chunk(t, row: int, sign_seed: np.uint32, c: int, S: int, L: int):
    """Per-(row, coord) mix fallback for r > 16 — replicates
    ops.sketch.CountSketch._signs_row on global indices
    ``t*c + s*L + l``, returned as a sign-bit flip mask (bit 16 of the
    row-salted mix moved to bit 31). ``row`` is a Python int; ``t`` is
    traced."""
    s_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 0)
    l_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 1)
    g = t.astype(jnp.uint32) * jnp.uint32(c) + s_idx * jnp.uint32(L) + l_idx
    row_const = (np.uint32((row * 0x9E3779B9) & 0xFFFFFFFF) ^ sign_seed)
    h = _mix_u32(g ^ jnp.uint32(row_const))
    return (h << 15) & jnp.uint32(0x80000000)


def _apply_flip(x, flip):
    """x * sign, as a sign-bit XOR (see _flip_from_hash)."""
    xb = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(xb ^ flip, jnp.float32)


def _roll1d(x, o, S: int, L: int, lane=None):
    """Circular shift of the flattened (S, L) tile by traced ``o``
    (0 <= o < S*L). The lane roll (the expensive cross-lane permute)
    is computed ONCE and the two candidate sublane rolls (a, a+1)
    applied after — legal because rolls on distinct axes commute:
    ``lane_roll(sub_roll(x, a), b) == sub_roll(lane_roll(x, b), a)``.
    ``lane`` is the (S, L) lane iota, hoistable by the caller."""
    a = o // L
    b = o % L
    y = pltpu.roll(x, shift=b, axis=1)
    R1 = pltpu.roll(y, shift=a, axis=0)
    R2 = pltpu.roll(y, shift=a + 1, axis=0)
    if lane is None:
        lane = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
    return jnp.where(lane < b, R2, R1)


def _median3(x, y, z):
    """max(min(x,y), min(max(x,y), z)) — 4 ops vs 6 for the sort."""
    lo = jnp.minimum(x, y)
    hi = jnp.maximum(x, y)
    return jnp.maximum(lo, jnp.minimum(hi, z))


def _median_network(vals):
    """Elementwise median of a list of same-shape arrays. Matches
    jnp.median: middle element for odd r, mean of the two middles for
    even r. min/max compositions are order-exact, so any correct
    network returns the identical value — the flagship r=5 uses the
    classic selection network (10 ops: median3 of the max-of-mins,
    min-of-maxes, and the odd element) instead of a full odd-even
    transposition sort (20 ops); other r fall back to the sort."""
    v = list(vals)
    n = len(v)
    if n == 1:
        return v[0]
    if n == 3:
        return _median3(v[0], v[1], v[2])
    if n == 5:
        f = jnp.maximum(jnp.minimum(v[0], v[1]), jnp.minimum(v[2], v[3]))
        g = jnp.minimum(jnp.maximum(v[0], v[1]), jnp.maximum(v[2], v[3]))
        return _median3(v[4], f, g)
    for rnd in range(n):
        start = rnd % 2
        for i in range(start, n - 1, 2):
            lo = jnp.minimum(v[i], v[i + 1])
            hi = jnp.maximum(v[i], v[i + 1])
            v[i], v[i + 1] = lo, hi
    if n % 2 == 1:
        return v[n // 2]
    return 0.5 * (v[n // 2 - 1] + v[n // 2])


def _flips_for_chunk(t, sgn_block, one_mix: bool, seed, c, S, L, r,
                     row_offset: int = 0):
    """Per-row sign-bit flip masks for chunk ``t``, cheapest source
    first: a streamed packed-sign block (bit ``row`` of a u8 per
    element — 2 shift/and ops per row, no hashing), else the in-kernel
    one-mix hash (r <= 16), else one mix per (row, coord).
    ``row_offset`` shifts every row index by the table-row offset of a
    chunked call (--overlap_depth): the sign stream is keyed by the
    ABSOLUTE table row, so a chunk's rows flip identically to the same
    rows of a whole-table call."""
    if sgn_block is not None:
        b32 = sgn_block.astype(jnp.uint32)
        return [(b32 << (31 - (row_offset + row)))
                & jnp.uint32(0x80000000) for row in range(r)]
    if one_mix:
        h = _sign_hash_chunk(t, seed, c, S, L, r)
        return [_flip_from_hash(h, row_offset + row)
                for row in range(r)]
    return [_flip_chunk(t, row_offset + row, seed, c, S, L)
            for row in range(r)]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def sketch_pallas(vp, rot, c: int, r: int, sign_seed: int,
                  interpret: bool = False, lanes: int | None = None,
                  one_mix: bool = False, rot_step: int = 0, sgn=None):
    """(padded_d,) signed-rotate-accumulate -> (r, c) table.

    ``vp`` is the zero-padded flat vector (padded_d = m*c); ``rot`` is
    the (r, m) int32 host-derived rotation table (static per operator,
    passed as an array so the kernel is geometry-cached). ``rot_step``
    > 0 promises every rotation is a multiple of it; when that step is
    lane-aligned the 5-op arbitrary-shift roll collapses to a single
    sublane roll (CountSketch.rot_lanes). ``sgn`` (optional,
    (padded_d,) uint8): packed sign bits (bit row = hash bit 16+row,
    CountSketch._packed_signs_traced) streamed alongside the vector —
    removes the murmur mix (two emulated u32 multiplies per element,
    the largest r-independent ALU block) from the kernel for ~1 extra
    byte/element of HBM traffic."""
    L = lanes or _pick_lanes(c)
    assert L is not None and c % L == 0
    S = c // L
    m = vp.size // c
    seed = np.uint32(sign_seed)
    sublane = rot_step > 0 and rot_step % L == 0
    packed = sgn is not None
    rot, rot_spec, rot_at = _rot_operand(rot, r, m)

    def kernel(rot_ref, v_ref, *refs):
        (sgn_ref, out_ref) = refs if packed else (None, refs[0])
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        # NOTE: a 1-D (c,) input block with an in-kernel reshape was
        # measured WORSE (sketch 8.3 -> 13.4 ms at d=124M): Mosaic
        # relayouts every chunk inside the kernel, serialized with
        # compute, while the XLA-side 2-D relayout copy costs ~1.5 ms
        # once and overlaps. Keep the 2-D operand.
        chunk = v_ref[:]  # (S, L) chunk t, streamed
        flips = _flips_for_chunk(
            t, sgn_ref[:] if packed else None,
            one_mix, seed, c, S, L, r)
        lane = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
        for row in range(r):
            signed = _apply_flip(chunk, flips[row])
            if sublane:
                rolled = pltpu.roll(signed, rot_at(rot_ref, row, t) // L,
                                    axis=0)
            else:
                rolled = _roll1d(signed, rot_at(rot_ref, row, t), S, L, lane)
            sl = slice(row * S, (row + 1) * S)
            out_ref[sl, :] = out_ref[sl, :] + rolled

    in_specs = [
        rot_spec,
        pl.BlockSpec((S, L), lambda t: (t, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [rot, vp.astype(jnp.float32).reshape(m * S, L)]
    if packed:
        in_specs.append(pl.BlockSpec((S, L), lambda t: (t, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(sgn.reshape(m * S, L))
    out = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r * S, L), lambda t: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((r * S, L), jnp.float32, *operands),
        compiler_params=_compiler_params(4 * r * c),
        interpret=interpret,
        name="sketch_pallas",
    )(*operands)
    return out.reshape(r, c)


@functools.partial(jax.jit,
                   static_argnums=(2, 3, 4, 5, 6, 7, 8, 10))
def sketch_quant_pallas(vp, rot, c: int, r: int, sign_seed: int,
                        interpret: bool = False,
                        lanes: int | None = None, one_mix: bool = False,
                        rot_step: int = 0, sgn=None,
                        row_offset: int = 0):
    """Fused emit + int8 quantize: ``sketch_pallas`` whose f32 table
    lives ONLY in a VMEM scratch accumulator — after the last chunk
    the kernel computes each row's maxabs, quantizes the row at full
    int8 range against it (ops/quant.py ``quantize_local`` semantics,
    bit-identical math), and writes the int8 table + per-row f32
    maxabs. The full-width f32 table never reaches HBM.

    Returns ``(q, rowmax)``: q (r, c) int8, rowmax (r, 1) f32. int8 is
    the only wire dtype with anything to fuse that Mosaic can emit:
    bf16 has no scale (a plain cast of ``sketch_pallas``'s output),
    and fp8's bit-exact contract needs an f16 -> float8_e4m3fn cast
    Mosaic does not have (CountSketch.sketch_quantized keeps fp8
    unfused).

    ``row_offset`` (--overlap_depth chunked emission): ``r`` is then
    the CHUNK row count and ``rot`` the chunk's row slice of the
    rotation table; the sign streams key off the absolute row
    ``row_offset + row``, so each chunk's output is bit-identical to
    the same rows of a whole-table call. The VMEM scratch and the
    compiler's VMEM budget derive from the chunk row count — a
    depth-N pipeline holds one chunk-sized accumulator per in-flight
    chunk instead of N full-table scratches."""
    from commefficient_tpu.ops.quant import QMAX, wire_jnp_dtype
    qmax = QMAX["int8"]
    out_dtype = wire_jnp_dtype("int8")
    L = lanes or _pick_lanes(c)
    assert L is not None and c % L == 0
    S = c // L
    m = vp.size // c
    seed = np.uint32(sign_seed)
    sublane = rot_step > 0 and rot_step % L == 0
    packed = sgn is not None
    assert row_offset >= 0
    if one_mix:
        # the one-mix hash carries 16 sign bits — absolute rows of a
        # chunked call must stay inside them
        assert row_offset + r <= 16, (row_offset, r)
    rot, rot_spec, rot_at = _rot_operand(rot, r, m)

    def kernel(rot_ref, v_ref, *refs):
        if packed:
            sgn_ref, q_ref, rm_ref, acc_ref = refs
        else:
            sgn_ref, (q_ref, rm_ref, acc_ref) = None, refs
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        chunk = v_ref[:]
        flips = _flips_for_chunk(
            t, sgn_ref[:] if packed else None,
            one_mix, seed, c, S, L, r, row_offset)
        lane = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
        for row in range(r):
            signed = _apply_flip(chunk, flips[row])
            if sublane:
                rolled = pltpu.roll(signed, rot_at(rot_ref, row, t) // L,
                                    axis=0)
            else:
                rolled = _roll1d(signed, rot_at(rot_ref, row, t), S, L, lane)
            sl = slice(row * S, (row + 1) * S)
            acc_ref[sl, :] = acc_ref[sl, :] + rolled

        @pl.when(t == m - 1)
        def _():
            for row in range(r):
                sl = slice(row * S, (row + 1) * S)
                block = acc_ref[sl, :]
                rm = jnp.max(jnp.abs(block))
                # identical scale algebra to quantize_local: full
                # range against the local rowmax, zero-row guard 1.0
                s = jnp.where(rm > 0.0, rm / qmax, 1.0)
                q = jnp.clip(jnp.round(block / s), -qmax, qmax)
                q_ref[sl, :] = q.astype(out_dtype)
                rm_ref[row, :] = jnp.full((L,), rm, jnp.float32)

    in_specs = [
        rot_spec,
        pl.BlockSpec((S, L), lambda t: (t, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [rot, vp.astype(jnp.float32).reshape(m * S, L)]
    if packed:
        in_specs.append(pl.BlockSpec((S, L), lambda t: (t, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(sgn.reshape(m * S, L))
    q, rm = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((r * S, L), lambda t: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((r, L), lambda t: (0, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(out_struct((r * S, L), out_dtype, *operands),
                   out_struct((r, L), jnp.float32, *operands)),
        scratch_shapes=[pltpu.VMEM((r * S, L), jnp.float32)],
        compiler_params=_compiler_params(4 * r * c),
        interpret=interpret,
        name="sketch_quant_pallas",
    )(*operands)
    return q.reshape(r, c), rm[:, :1]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def estimates_pallas(table, rot, c: int, r: int, sign_seed: int,
                     interpret: bool = False, lanes: int | None = None,
                     one_mix: bool = False, valid: int | None = None,
                     rot_step: int = 0, sgn=None):
    """(r, c) table -> (padded_d,) median-of-rows estimates, fused
    (the (r, padded_d) intermediate of the XLA path never exists).

    ``valid``: zero estimates at positions >= valid in-kernel — lets
    callers consume the padded vector directly instead of paying the
    ``[:d]`` prefix-slice copy (CountSketch.estimates(padded=True)).
    ``sgn``: optional (padded_d,) packed sign bits, see
    ``sketch_pallas``."""
    L = lanes or _pick_lanes(c)
    assert L is not None and c % L == 0
    S = c // L
    m = rot.shape[1]
    seed = np.uint32(sign_seed)
    sublane = rot_step > 0 and rot_step % L == 0
    packed = sgn is not None
    rot, rot_spec, rot_at = _rot_operand(rot, r, m)

    def kernel(rot_ref, tab_ref, *refs):
        (sgn_ref, out_ref) = refs if packed else (None, refs[0])
        t = pl.program_id(0)
        flips = _flips_for_chunk(
            t, sgn_ref[:] if packed else None,
            one_mix, seed, c, S, L, r)
        lane = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
        vals = []
        for row in range(r):
            trow = tab_ref[row * S:(row + 1) * S, :]
            o = rot_at(rot_ref, row, t)
            back = (jnp.int32(c) - o) % jnp.int32(c)
            if sublane:
                unrolled = pltpu.roll(trow, back // L, axis=0)
            else:
                unrolled = _roll1d(trow, back, S, L, lane)
            vals.append(_apply_flip(unrolled, flips[row]))
        med = _median_network(vals)
        if valid is not None and valid < m * c:
            s_idx = jax.lax.broadcasted_iota(jnp.int32, (S, L), 0)
            l_idx = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
            g = t * c + s_idx * L + l_idx
            med = jnp.where(g < valid, med, 0.0)
        # 1-D output block: the (padded_d,) estimates leave in their
        # consumers' native linear layout (the 2-D (m*S, L) out_shape
        # cost a d-sized relayout on the way to selection)
        out_ref[:] = med.reshape(c)

    in_specs = [
        rot_spec,
        # table resident in VMEM across all chunk steps
        pl.BlockSpec((r * S, L), lambda t: (0, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [rot, table.astype(jnp.float32).reshape(r * S, L)]
    if packed:
        in_specs.append(pl.BlockSpec((S, L), lambda t: (t, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(sgn.reshape(m * S, L))
    out = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((c,), lambda t: (t,),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((m * c,), jnp.float32, *operands),
        compiler_params=_compiler_params(4 * r * c),
        interpret=interpret,
        name="estimates_pallas",
    )(*operands)
    return out

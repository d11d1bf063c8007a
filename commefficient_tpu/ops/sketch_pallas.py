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

Rotation, two forms chosen from the shapes alone (``rotation_form``):

- *addressed*: where every rotation is a multiple of 1,024 elements
  (one float32 vreg, 8 sublanes x 128 lanes; ``CountSketch.rot_lanes``)
  a chunk is viewed as ``(c/128, 128)`` and a rotation by 1024·j moves
  no element inside its vreg, only which vreg row of the table it
  meets: the kernels add into (read from) the VMEM-resident table at
  row offset 8·j and roll nothing. The (·, 128) views of the 1-D
  vector, sign stream and estimates are those arrays' own tiling, so no
  d-sized relayout is paid around the kernels either.
- *rolled*: anything else. A chunk is viewed as a 2-D ``(S, L)`` tile
  (L a multiple of 128, so lane-aligned). A 1-D circular shift by
  ``o = a·L + b`` decomposes into two sublane rolls (a, a+1), a lane
  roll (b) of each, and a lane-index select — all supported by
  Mosaic's ``dynamic_rotate`` at any alignment, unlike a flat 1-D
  rotate of unaligned width. Requires ``c % 128 == 0`` (the auto
  backend falls back to XLA otherwise, e.g. for the reference's
  default c=500000).

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


def _addressed_vmem(table_bytes: int) -> int:
    """What the addressed form asks of VMEM: the table twice over in a
    scratch (the doubled accumulator / the resident table), the sketch
    kernel's output block in its two buffers besides, and the
    double-buffered chunk blocks with margin."""
    return 4 * table_bytes + 12 * 1024 * 1024


def _compiler_params(table_bytes: int, addressed: bool = False):
    # rolled: table resident + r per-chunk temp rows (~table again) +
    # double-buffered chunk blocks + relayout scratch, with margin
    want = (_addressed_vmem(table_bytes) if addressed
            else 3 * table_bytes)
    want = min(_VMEM_CEILING, max(32 * 1024 * 1024, want))
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


def _global_index(t, c: int, S: int, L: int, base=0):
    """uint32 global coordinate of every element of an (S, L) tile that
    starts ``base`` elements into chunk ``t`` (``base`` 0: the whole
    chunk; the addressed row loop passes its block's offset)."""
    s_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 0)
    l_idx = jax.lax.broadcasted_iota(jnp.uint32, (S, L), 1)
    g = t.astype(jnp.uint32) * jnp.uint32(c) + s_idx * jnp.uint32(L) + l_idx
    if isinstance(base, int) and base == 0:
        return g
    return g + jnp.asarray(base).astype(jnp.uint32)


def _sign_hash_chunk(t, sign_seed: np.uint32, c: int, S: int, L: int,
                     r: int, base=0):
    """One-mix sign scheme (CountSketch._one_mix_signs, r <= 16): a
    single murmur mix of the global index per chunk element; row r's
    sign is bit 16+r. Hoisted out of the kernels' row loops — hashing
    was the dominant kernel cost at 1 mix per (row, coord)."""
    assert r <= 16
    return _mix_u32(_global_index(t, c, S, L, base) ^ sign_seed)


def _flip_from_hash(h, row: int):
    """Sign-bit flip mask for row ``row`` from the one-mix hash: bit
    16+row of ``h`` moved to bit 31. XORing a float32 with this mask
    IS multiplication by the row's ±1 sign (IEEE sign-bit flip is
    exact, bit-identical to ``x * (1 - 2*bit)`` incl. ±0), at 2 VPU
    ops instead of the extract/convert/multiply chain (~7)."""
    assert 0 <= row <= 15
    return (h << (15 - row)) & jnp.uint32(0x80000000)


def _flip_chunk(t, row: int, sign_seed: np.uint32, c: int, S: int, L: int,
                base=0):
    """Per-(row, coord) mix fallback for r > 16 — replicates
    ops.sketch.CountSketch._signs_row on global indices
    ``t*c + s*L + l``, returned as a sign-bit flip mask (bit 16 of the
    row-salted mix moved to bit 31). ``row`` is a Python int; ``t`` is
    traced."""
    g = _global_index(t, c, S, L, base)
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
                     row_offset: int = 0, base=0):
    """Per-row sign-bit flip masks for chunk ``t``, cheapest source
    first: a streamed packed-sign block (bit ``row`` of a u8 per
    element — 2 shift/and ops per row, no hashing), else the in-kernel
    one-mix hash (r <= 16), else one mix per (row, coord).
    ``row_offset`` shifts every row index by the table-row offset of a
    chunked call (--overlap_depth): the sign stream is keyed by the
    ABSOLUTE table row, so a chunk's rows flip identically to the same
    rows of a whole-table call. ``base``: the tile starts that many
    elements into the chunk (``_global_index``)."""
    if sgn_block is not None:
        b32 = sgn_block.astype(jnp.uint32)
        return [(b32 << (31 - (row_offset + row)))
                & jnp.uint32(0x80000000) for row in range(r)]
    if one_mix:
        h = _sign_hash_chunk(t, seed, c, S, L, r, base)
        return [_flip_from_hash(h, row_offset + row)
                for row in range(r)]
    return [_flip_chunk(t, row_offset + row, seed, c, S, L, base)
            for row in range(r)]


#: elements of one float32 vreg (8 sublanes x 128 lanes): a rotation
#: by a multiple of it moves whole vregs of the (c/128, 128) view
_VREG = 1024
#: rows of that view one step of the addressed row loop handles: one
#: packed-sign (uint8) tile, four float32 vregs
_ADDR_ROWS = 32
#: blocks a step of that loop (read at 1 / 2 / 4 on the chip: 5.64 /
#: 5.44 / 5.30 ms a sketch call at d = 772M, the estimates unmoved)
_ADDR_UNROLL = 4


def rotation_form(c: int, r: int, rot_step: int) -> str:
    """Which of the kernels' two rotation forms a geometry gets, from
    its shapes alone: ``"addressed"`` where every rotation is a whole
    number of vregs (``rot_step`` a multiple of 1,024, the chunk whole
    (32, 128) tiles, r <= 16, the table small enough to lie in VMEM
    twice over beside its output block) — a chunk is then viewed as
    (c/128, 128) and a rotation by 1024·j is the row offset 8·j into
    the VMEM table, no element moves inside a vreg and nothing is
    rolled; ``"rolled"`` otherwise (``_roll1d`` on an (S, L) tile)."""
    whole = (rot_step > 0 and rot_step % _VREG == 0
             and c % (_ADDR_ROWS * 128) == 0 and r <= 16
             and _addressed_vmem(4 * r * c) <= _VMEM_CEILING)
    return "addressed" if whole else "rolled"


def _tile(c: int, r: int, rot_step: int, lanes: int | None):
    """(addressed, S, L): the chunk's 2-D view. ``lanes`` (tests) pins
    the rolled form at that lane width."""
    if lanes is None and rotation_form(c, r, rot_step) == "addressed":
        return True, c // 128, 128
    L = lanes or _pick_lanes(c)
    assert L is not None and c % L == 0
    return False, c // L, L


def _for_blocks(S: int, body, unroll: int = 1):
    """``body(lo)`` for the first row ``lo`` of every ``_ADDR_ROWS``
    block of an (S, 128) chunk, ``unroll`` blocks a loop step (Mosaic's
    own ``fori_loop`` unrolls fully or not at all)."""
    B, n = _ADDR_ROWS, S // _ADDR_ROWS
    u = unroll if n % unroll == 0 else 1

    def step(i, carry):
        for j in range(u):
            body(pl.multiple_of((i * u + j) * B, B))
        return carry

    jax.lax.fori_loop(0, n // u, step, 0)


def _row_starts(rot_ref, rot_at, t, r: int, S: int):
    """Where chunk ``t`` meets each sketch row of a table held twice
    over, 2·S rows of 128 a sketch row: ``row·2S + 8·(rot // 1024)``.
    The S rows from there on never wrap."""
    return [row * 2 * S + 8 * (rot_at(rot_ref, row, t) // _VREG)
            for row in range(r)]


def _accumulate(acc_ref, rot_ref, rot_at, v_ref, sgn_ref, t, *, addressed,
                one_mix, seed, c, S, L, r, row_offset=0):
    """Add chunk ``t``, signed and rotated, into every row of the
    (r·S, L) accumulator. Addressed: the accumulator is (r·2S, 128),
    each sketch row twice as long, so that a chunk lands at one aligned
    dynamic start with no wrap (``_row_starts``); what lands in a
    row's upper half is what wrapped, and ``_fold`` adds it onto the
    lower after the last chunk. (Addressing each vreg row modulo S
    instead keeps the chunk order of the adds and the parent's table
    to the bit, at four times the dynamic addresses: 9.9 against
    5.3 ms a call at d = 772M, PERF.md section 6, PR 37.)"""
    if not addressed:
        chunk = v_ref[:]  # (S, L) chunk t, streamed
        flips = _flips_for_chunk(
            t, None if sgn_ref is None else sgn_ref[:],
            one_mix, seed, c, S, L, r, row_offset)
        lane = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1)
        for row in range(r):
            rolled = _roll1d(_apply_flip(chunk, flips[row]),
                             rot_at(rot_ref, row, t), S, L, lane)
            sl = slice(row * S, (row + 1) * S)
            acc_ref[sl, :] = acc_ref[sl, :] + rolled
        return
    B = _ADDR_ROWS
    starts = _row_starts(rot_ref, rot_at, t, r, S)

    def block(lo):
        src = pl.ds(lo, B)
        x = v_ref[src, :]
        flips = _flips_for_chunk(
            t, None if sgn_ref is None else sgn_ref[src, :],
            one_mix, seed, c, B, L, r, row_offset, base=lo * L)
        dsts = [pl.ds(pl.multiple_of(starts[row] + lo, 8), B)
                for row in range(r)]
        # every load before the first store: the stores' addresses are
        # dynamic, so a load after one would wait for it
        olds = [acc_ref[dst, :] for dst in dsts]
        for row in range(r):
            acc_ref[dsts[row], :] = olds[row] + _apply_flip(x, flips[row])

    _for_blocks(S, block, _ADDR_UNROLL)


def _fold(acc_ref, dst_ref, dst_rows: int, r: int, S: int):
    """The doubled accumulator's upper halves (what wrapped past a
    table row's end) added onto its lower: table row ``row`` goes to
    ``dst_ref[row·dst_rows : row·dst_rows + S]``."""
    B = _ADDR_ROWS

    def block(lo):
        for row in range(r):
            dst_ref[pl.ds(row * dst_rows + lo, B), :] = (
                acc_ref[pl.ds(row * 2 * S + lo, B), :]
                + acc_ref[pl.ds((row * 2 + 1) * S + lo, B), :])

    _for_blocks(S, block)


def _chunk_operands(vp, sgn, m: int, S: int, L: int):
    """The streamed chunk operands and their BlockSpecs: the vector
    (and the packed signs) as (m·S, L). At L = 128 that view is the
    1-D array's own tiling, a bitcast in the compiled program; wider
    tiles cost a d-sized relayout before the kernel."""
    spec = pl.BlockSpec((S, L), lambda t: (t, 0), memory_space=pltpu.VMEM)
    operands, specs = [vp.astype(jnp.float32).reshape(m * S, L)], [spec]
    if sgn is not None:
        operands.append(sgn.reshape(m * S, L))
        specs.append(spec)
    return operands, specs


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def sketch_pallas(vp, rot, c: int, r: int, sign_seed: int,
                  interpret: bool = False, lanes: int | None = None,
                  one_mix: bool = False, rot_step: int = 0, sgn=None):
    """(padded_d,) signed-rotate-accumulate -> (r, c) table.

    ``vp`` is the zero-padded flat vector (padded_d = m*c); ``rot`` is
    the (r, m) int32 host-derived rotation table (static per operator,
    passed as an array so the kernel is geometry-cached). ``rot_step``
    > 0 promises every rotation is a multiple of it; where that makes
    them whole vregs the kernel addresses the table instead of rolling
    the chunk (``rotation_form``; CountSketch.rot_lanes). ``sgn``
    (optional, (padded_d,) uint8): packed sign bits (bit row = hash
    bit 16+row, CountSketch._packed_signs_traced) streamed alongside
    the vector — removes the murmur mix (two emulated u32 multiplies
    per element, the largest r-independent ALU block) from the kernel
    for ~1 extra byte/element of HBM traffic."""
    m = vp.size // c
    addressed, S, L = _tile(c, r, rot_step, lanes)
    seed = np.uint32(sign_seed)
    packed = sgn is not None
    rot, rot_spec, rot_at = _rot_operand(rot, r, m)

    def kernel(rot_ref, v_ref, *refs):
        sgn_ref = refs[0] if packed else None
        out_ref = refs[1 if packed else 0]
        acc_ref = refs[-1]  # rolled: the output itself
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        _accumulate(acc_ref, rot_ref, rot_at, v_ref, sgn_ref, t,
                    addressed=addressed, one_mix=one_mix, seed=seed,
                    c=c, S=S, L=L, r=r)

        if addressed:
            @pl.when(t == m - 1)
            def _():
                _fold(acc_ref, out_ref, S, r, S)

    operands, chunk_specs = _chunk_operands(vp, sgn, m, S, L)
    operands = [rot] + operands
    out = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=[rot_spec] + chunk_specs,
        out_specs=pl.BlockSpec((r * S, L), lambda t: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((r * S, L), jnp.float32, *operands),
        scratch_shapes=([pltpu.VMEM((r * 2 * S, L), jnp.float32)]
                        if addressed else []),
        compiler_params=_compiler_params(4 * r * c, addressed),
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
    m = vp.size // c
    addressed, S, L = _tile(c, r, rot_step, lanes)
    seed = np.uint32(sign_seed)
    packed = sgn is not None
    T = 2 * S if addressed else S  # accumulator rows a table row
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

        _accumulate(acc_ref, rot_ref, rot_at, v_ref, sgn_ref, t,
                    addressed=addressed, one_mix=one_mix, seed=seed,
                    c=c, S=S, L=L, r=r, row_offset=row_offset)

        @pl.when(t == m - 1)
        def _():
            if addressed:
                _fold(acc_ref, acc_ref, T, r, S)  # in place
            for row in range(r):
                block = acc_ref[row * T:row * T + S, :]
                rm = jnp.max(jnp.abs(block))
                # identical scale algebra to quantize_local: full
                # range against the local rowmax, zero-row guard 1.0
                s = jnp.where(rm > 0.0, rm / qmax, 1.0)
                q = jnp.clip(jnp.round(block / s), -qmax, qmax)
                q_ref[row * S:(row + 1) * S, :] = q.astype(out_dtype)
                rm_ref[row, :] = jnp.full((L,), rm, jnp.float32)

    operands, chunk_specs = _chunk_operands(vp, sgn, m, S, L)
    operands = [rot] + operands
    q, rm = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=[rot_spec] + chunk_specs,
        out_specs=(pl.BlockSpec((r * S, L), lambda t: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((r, L), lambda t: (0, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(out_struct((r * S, L), out_dtype, *operands),
                   out_struct((r, L), jnp.float32, *operands)),
        scratch_shapes=[pltpu.VMEM((r * T, L), jnp.float32)],
        compiler_params=_compiler_params(4 * r * c, addressed),
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
    m = rot.shape[1]
    addressed, S, L = _tile(c, r, rot_step, lanes)
    seed = np.uint32(sign_seed)
    packed = sgn is not None
    masked = valid is not None and valid < m * c
    rot, rot_spec, rot_at = _rot_operand(rot, r, m)

    def tail_mask(med, t):
        g = _global_index(t, c, S, L).astype(jnp.int32)
        return jnp.where(g < valid, med, 0.0)

    def rolled_kernel(rot_ref, tab_ref, *refs):
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
            vals.append(_apply_flip(_roll1d(trow, back, S, L, lane),
                                    flips[row]))
        med = _median_network(vals)
        if masked:
            med = tail_mask(med, t)
        # 1-D output block: the (padded_d,) estimates leave in their
        # consumers' native linear layout (the 2-D (m*S, L) out_shape
        # cost a d-sized relayout on the way to selection)
        out_ref[:] = med.reshape(c)

    def addressed_kernel(rot_ref, tab_ref, *refs):
        sgn_ref = refs[0] if packed else None
        out_ref, res_ref, sems = refs[-3:]
        t = pl.program_id(0)
        B = _ADDR_ROWS

        @pl.when(t == 0)
        def _():
            # the table comes to VMEM once, each row twice over, so
            # that one dynamic start below needs no wrap
            copies = [pltpu.make_async_copy(
                tab_ref.at[pl.ds(row * S, S), :],
                res_ref.at[pl.ds((2 * row + half) * S, S), :],
                sems.at[2 * row + half])
                for row in range(r) for half in range(2)]
            for copy in copies:
                copy.start()
            for copy in copies:
                copy.wait()

        # chunk t of row ``row`` is the table row read from vreg row
        # rot // 1024 on, wrapping: rolled back by address
        starts = _row_starts(rot_ref, rot_at, t, r, S)

        def block(lo):
            src = pl.ds(lo, B)
            flips = _flips_for_chunk(
                t, sgn_ref[src, :] if packed else None,
                one_mix, seed, c, B, L, r, base=lo * L)
            vals = [_apply_flip(
                res_ref[pl.ds(pl.multiple_of(starts[row] + lo, 8), B), :],
                flips[row]) for row in range(r)]
            out_ref[src, :] = _median_network(vals)

        _for_blocks(S, block, _ADDR_UNROLL)

        if masked:
            # the tail lies in the last chunk(s) only
            @pl.when((t + 1) * c > valid)
            def _():
                out_ref[:] = tail_mask(out_ref[:], t)

    operands = [rot, table.astype(jnp.float32).reshape(r * S, L)]
    in_specs = [
        rot_spec,
        # addressed: the kernel fetches the table itself, twice over;
        # rolled: resident in VMEM across all chunk steps
        pl.BlockSpec(memory_space=pl.ANY) if addressed
        else pl.BlockSpec((r * S, L), lambda t: (0, 0),
                          memory_space=pltpu.VMEM),
    ]
    if packed:
        in_specs.append(pl.BlockSpec((S, L), lambda t: (t, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(sgn.reshape(m * S, L))
    if addressed:
        # the (m·S, 128) result is the 1-D estimates' own tiling: the
        # reshape below is a bitcast in the compiled program
        out_spec = pl.BlockSpec((S, L), lambda t: (t, 0),
                                memory_space=pltpu.VMEM)
        out_shape = out_struct((m * S, L), jnp.float32, *operands)
        scratch = [pltpu.VMEM((r * 2 * S, L), jnp.float32),
                   pltpu.SemaphoreType.DMA((2 * r,))]
    else:
        out_spec = pl.BlockSpec((c,), lambda t: (t,),
                                memory_space=pltpu.VMEM)
        out_shape = out_struct((m * c,), jnp.float32, *operands)
        scratch = []
    out = pl.pallas_call(
        addressed_kernel if addressed else rolled_kernel,
        grid=(m,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(4 * r * c, addressed),
        interpret=interpret,
        name="estimates_pallas",
    )(*operands)
    return out.reshape(m * c)

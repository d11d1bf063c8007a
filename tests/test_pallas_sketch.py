"""Pallas sketch kernels vs the XLA rotation-sketch path.

The contract is hash-identity: identical rotation/sign streams, so
Pallas- and XLA-sketched tables may be psum-mixed. Chunk summation
order differs between the two (sequential grid accumulation vs XLA's
tree reduce), so sketch tables match to ULP-level tolerance; recovery
from a given table is a pure permutation + median and matches
bit-for-bit. On CPU the kernels run in interpreter mode."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops.sketch import CountSketch
from commefficient_tpu.ops.sketch_pallas import supported

GEOMS = [
    # (d, c, r) — c lane-aligned (multiple of 128), table VMEM-sized
    (5000, 1024, 3),
    (300, 128, 5),      # d > padded? no: m=3 chunks of 128
    (4096, 4096, 1),    # single chunk, single row
    (70000, 2048, 4),   # even r -> median averages two middles
]


def _pair(d, c, r):
    xla = CountSketch(d=d, c=c, r=r, seed=7, backend="xla")
    pal = CountSketch(d=d, c=c, r=r, seed=7, backend="pallas_interpret")
    return xla, pal


@pytest.mark.parametrize("d,c,r", GEOMS)
def test_sketch_table_matches(d, c, r):
    assert supported(d, c, r)
    xla, pal = _pair(d, c, r)
    v = jnp.asarray(np.random.RandomState(0).randn(d).astype(np.float32))
    tx, tp = np.asarray(xla.sketch(v)), np.asarray(pal.sketch(v))
    # same hash streams; only chunk-sum order differs (ULP-level)
    np.testing.assert_allclose(tx, tp, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("d,c,r", GEOMS)
def test_estimates_bit_exact(d, c, r):
    xla, pal = _pair(d, c, r)
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randn(r, c).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(xla.estimates(table)),
                                  np.asarray(pal.estimates(table)))


def test_unsketch_from_shared_table_bit_exact():
    d, c, r, k = 5000, 1024, 3, 20
    xla, pal = _pair(d, c, r)
    rng = np.random.RandomState(2)
    v = np.zeros(d, np.float32)
    hh = rng.choice(d, k, replace=False)
    v[hh] = rng.randn(k).astype(np.float32) * 100
    v += rng.randn(d).astype(np.float32) * 0.01
    table = xla.sketch(jnp.asarray(v))  # one table, both recoveries
    out_x = xla.unsketch(table, k)
    out_p = pal.unsketch(table, k)
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
    # and the heavy hitters were actually recovered
    recovered = set(np.nonzero(np.asarray(out_p))[0])
    assert len(recovered & set(hh.tolist())) >= int(0.9 * k)


def test_unsupported_geometry_falls_back():
    # the reference default c=500000 is not lane-aligned -> XLA path
    assert not supported(6_500_000, 500_000, 5)
    cs = CountSketch(d=1000, c=500, r=3, backend="auto")
    assert cs._resolve_backend() == "xla"  # c % 128 != 0


def test_pallas_linearity():
    d, c, r = 5000, 1024, 3
    _, pal = _pair(d, c, r)
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(d).astype(np.float32))
    b = jnp.asarray(rng.randn(d).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(pal.sketch(a) + pal.sketch(b)),
        np.asarray(pal.sketch(a + b)), rtol=1e-5, atol=1e-5)


class TestSublaneRotations:
    """rot_lanes > 0: quantized rotations, single-sublane-roll kernel
    fast path. Backend equivalence must hold exactly as for the
    full-granularity operator."""

    def test_rotations_are_quantized(self):
        cs = CountSketch(d=5000, c=1024, r=3, seed=7, rot_lanes=128)
        rot = cs._rotations()
        assert (rot % 128 == 0).all()
        assert rot.max() < 1024

    def test_degenerate_granularity_rejected(self):
        import pytest as _pytest
        cs = CountSketch(d=5000, c=1024, r=3, seed=7, rot_lanes=1024)
        with _pytest.raises(AssertionError):
            cs._rotations()

    def test_sketch_backends_match(self):
        d, c, r = 5000, 1024, 3
        xla = CountSketch(d=d, c=c, r=r, seed=7, backend="xla",
                          rot_lanes=128)
        pal = CountSketch(d=d, c=c, r=r, seed=7,
                          backend="pallas_interpret", rot_lanes=128)
        v = jnp.asarray(np.random.RandomState(3).randn(d)
                        .astype(np.float32))
        np.testing.assert_allclose(np.asarray(xla.sketch(v)),
                                   np.asarray(pal.sketch(v)),
                                   rtol=1e-6, atol=1e-5)

    def test_estimates_backends_bit_exact(self):
        d, c, r = 5000, 1024, 3
        xla = CountSketch(d=d, c=c, r=r, seed=7, backend="xla",
                          rot_lanes=128)
        pal = CountSketch(d=d, c=c, r=r, seed=7,
                          backend="pallas_interpret", rot_lanes=128)
        table = jnp.asarray(np.random.RandomState(4).randn(r, c)
                            .astype(np.float32))
        np.testing.assert_array_equal(np.asarray(xla.estimates(table)),
                                      np.asarray(pal.estimates(table)))

    def test_linearity_and_recovery_still_work(self):
        # c/rot_lanes = 512 — the flagship ratio (c=2^19, lanes 1024);
        # coarse ratios (say 8) measurably hurt recovery and are not
        # what the knob is for
        d, c, r, k = 200000, 65536, 5, 30
        cs = CountSketch(d=d, c=c, r=r, seed=9, backend="xla",
                         rot_lanes=128)
        rng = np.random.RandomState(5)
        v = np.zeros(d, np.float32)
        hh = rng.choice(d, k, replace=False)
        v[hh] = rng.randn(k).astype(np.float32) * 100
        a = jnp.asarray(v)
        b = jnp.asarray(rng.randn(d).astype(np.float32) * 0.01)
        np.testing.assert_allclose(
            np.asarray(cs.sketch(a) + cs.sketch(b)),
            np.asarray(cs.sketch(a + b)), rtol=2e-5, atol=2e-4)
        dense = cs.unsketch(cs.sketch(a), k)
        got = set(np.nonzero(np.asarray(dense))[0].tolist())
        assert len(got & set(hh.tolist())) >= int(0.9 * k)

    def test_sparse_resketch_matches_dense(self):
        # hashes() must agree with the quantized rotation stream
        d, c, r = 5000, 1024, 3
        cs = CountSketch(d=d, c=c, r=r, seed=11, backend="xla",
                         rot_lanes=128)
        rng = np.random.RandomState(6)
        idx = jnp.asarray(np.sort(rng.choice(d, 40, replace=False))
                          .astype(np.int32))
        vals = jnp.asarray(rng.randn(40).astype(np.float32))
        dense = jnp.zeros(d, jnp.float32).at[idx].set(vals)
        np.testing.assert_allclose(np.asarray(cs.sketch_sparse(idx, vals)),
                                   np.asarray(cs.sketch(dense)),
                                   rtol=1e-6, atol=1e-5)


class TestPackedSigns:
    """Packed-sign streaming (CountSketch.packed_signs) must be a pure
    perf lever: identical sign VALUES to in-kernel hashing, so tables
    and recoveries are bit-identical between the two kernel modes."""

    @pytest.mark.parametrize("d,c,r", GEOMS)
    def test_packed_vs_hashed_bit_identical(self, d, c, r):
        packed = CountSketch(d=d, c=c, r=r, seed=7,
                             backend="pallas_interpret")
        hashed = CountSketch(d=d, c=c, r=r, seed=7,
                             backend="pallas_interpret",
                             packed_signs=False)
        assert packed._packed_sign_kernels
        assert not hashed._packed_sign_kernels
        v = jnp.asarray(np.random.RandomState(3).randn(d)
                        .astype(np.float32))
        tp, th = packed.sketch(v), hashed.sketch(v)
        assert jnp.array_equal(tp, th), "sketch tables differ"
        ep = packed.estimates(tp, padded=True)
        eh = hashed.estimates(tp, padded=True)
        assert jnp.array_equal(ep, eh), "estimates differ"

    def test_packed_bits_match_signs_row(self):
        cs = CountSketch(d=4096, c=1024, r=5, seed=11)
        bits = np.asarray(jax.jit(cs._packed_signs_traced)())
        for row in range(cs.r):
            want = np.asarray(cs._signs_row(row))
            got = 1.0 - 2.0 * ((bits >> row) & 1).astype(np.float32)
            np.testing.assert_array_equal(got, want)

    def test_r9_falls_back_to_hashing(self):
        cs = CountSketch(d=2048, c=512, r=9, seed=7,
                         backend="pallas_interpret")
        assert not cs._packed_sign_kernels  # u8 holds 8 row bits
        t = cs.sketch(jnp.ones(2048, jnp.float32))
        assert t.shape == (9, 512)


def test_r17_per_row_mix_path():
    """r > 16 leaves the one-mix scheme: the kernels hash once per
    (row, coord) via _flip_chunk. Pin that branch of the flip-mask
    formulation against the XLA path (it is outside GEOMS and the
    packed-sign eligibility, so nothing else executes it)."""
    d, c, r = 2048, 512, 17
    xla = CountSketch(d=d, c=c, r=r, seed=7, backend="xla")
    pal = CountSketch(d=d, c=c, r=r, seed=7,
                      backend="pallas_interpret")
    assert not pal._one_mix_signs and not pal._packed_sign_kernels
    v = jnp.asarray(np.random.RandomState(5).randn(d)
                    .astype(np.float32))
    tx, tp = xla.sketch(v), pal.sketch(v)
    np.testing.assert_allclose(np.asarray(tx), np.asarray(tp),
                               rtol=1e-6, atol=1e-5)
    assert jnp.array_equal(xla.estimates(tx), pal.estimates(tx))


# (d, c, r) with r * m on both sides of ``_ROT_WHOLE``: 819 chunks
# (4,095 rotations, whole in SMEM) and 1,100 (5,500: three SMEM blocks,
# the last a part of one)
ROT_GEOMS = [(819 * 128 - 7, 128, 5), (1100 * 128 - 7, 128, 5)]


@pytest.mark.parametrize("d,c,r", ROT_GEOMS)
def test_rotation_table_whole_and_blocked_match_the_xla_twin(d, c, r):
    """Past ``_ROT_WHOLE`` the rotation table goes through SMEM a block
    at a time, inside one kernel call: sketch, quantised sketch and
    estimates equal the XLA twin's on both sides of the old limit."""
    from commefficient_tpu.ops import sketch_pallas as sp
    m = -(-d // c)
    assert (r * m > sp._ROT_WHOLE) == (m == 1100) and supported(d, c, r)
    xla, pal = _pair(d, c, r)
    v = jnp.asarray(np.random.RandomState(8).randn(d).astype(np.float32))
    tx = xla.sketch(v)
    np.testing.assert_allclose(np.asarray(tx), np.asarray(pal.sketch(v)),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(xla.estimates(tx)),
                                  np.asarray(pal.estimates(tx)))
    (qx, sx), (qp, s_p) = (cs.sketch_quantized(v, "int8")
                           for cs in (xla, pal))
    np.testing.assert_allclose(np.asarray(sx), np.asarray(s_p), rtol=1e-6)
    # a bucket on a rounding boundary may land one step apart
    assert np.max(np.abs(np.asarray(qx, np.int32)
                         - np.asarray(qp, np.int32))) <= 1


def test_supported_is_bounded_by_index_arithmetic_not_chunks():
    c = 524288
    assert supported(700_903_424, c, 5)        # r * m = 6,685
    assert supported(2 ** 31 - c, c, 5)
    assert not supported(2 ** 31 - c + 1, c, 5)  # padded d = 2^31


# --- the addressed rotation form (rotations that are whole vregs) -------

def _rot_pair(d, c, r, seed=7, rot_lanes=1024):
    xla = CountSketch(d=d, c=c, r=r, seed=seed, backend="xla",
                      rot_lanes=rot_lanes)
    pal = CountSketch(d=d, c=c, r=r, seed=seed,
                      backend="pallas_interpret", rot_lanes=rot_lanes)
    return xla, pal


def _kernel_args(cs):
    """(rot, sign_seed, sgn) as ``CountSketch`` hands them to the
    kernels."""
    _, sign_seed = cs._seeds()
    sgn = (jax.jit(cs._packed_signs_traced)()
           if cs._packed_sign_kernels else None)
    return jnp.asarray(cs._rotations()), int(sign_seed), sgn


@pytest.mark.parametrize("c,r,rot_step,form", [
    (524288, 5, 1024, "addressed"),   # all five cells
    (524288, 6, 1024, "addressed"),   # the most rows that fit at 2^19
    (524288, 8, 1024, "rolled"),      # the table twice over: 67 MB
    (8192, 5, 1024, "addressed"),
    (4096, 5, 1024, "addressed"),     # one (32, 128) tile a vreg row
    (8192, 16, 2048, "addressed"),    # whole vregs, two at a time
    (8192, 5, 0, "rolled"),           # full-granularity rotations
    (8192, 5, 512, "rolled"),         # half a vreg
    (8192, 5, 128, "rolled"),
    (9216, 5, 1024, "rolled"),        # c not whole (32, 128) tiles
    (8192, 17, 1024, "rolled"),       # per-(row, coord) mix
    (1024, 5, 1024, "rolled"),
])
def test_rotation_form_is_a_function_of_the_shapes(c, r, rot_step, form):
    """(c, r, rot_step) alone: the stream's length has no say."""
    from commefficient_tpu.ops.sketch_pallas import rotation_form
    assert rotation_form(c, r, rot_step) == form
    if rot_step == 0 or c % rot_step == 0:
        for m in (1, 13, 238):
            cs = CountSketch(d=m * c - 3, c=c, r=r, rot_lanes=rot_step,
                             backend="pallas_interpret")
            assert cs.rot_form == form


# c / 1024 = 8 (the smallest rotation space the operator admits) and
# 512 (the cells'), d with a padded tail, streams down to one chunk
ADDR_GEOMS = [(20 * 8192 - 77, 8192, 5), (16 * 524288 - 1001, 524288, 2),
              (16 * 8192, 8192, 3), (17 * 8192 - 1, 8192, 9),
              (13 * 8192 - 9, 8192, 5), (8192 - 100, 8192, 5)]


@pytest.mark.parametrize("d,c,r", ADDR_GEOMS)
def test_addressed_matches_the_xla_twin(d, c, r):
    xla, pal = _rot_pair(d, c, r)
    assert pal.rot_form == "addressed"
    assert pal._packed_sign_kernels == (r <= 8)
    v = jnp.asarray(np.random.RandomState(0).randn(d).astype(np.float32))
    tx = xla.sketch(v)
    np.testing.assert_allclose(np.asarray(tx), np.asarray(pal.sketch(v)),
                               rtol=1e-6, atol=1e-5)
    for padded in (False, True):
        np.testing.assert_array_equal(
            np.asarray(xla.estimates(tx, padded=padded)),
            np.asarray(pal.estimates(tx, padded=padded)))


@pytest.mark.parametrize("d,c,r", ADDR_GEOMS[:1] + ADDR_GEOMS[2:5])
@pytest.mark.parametrize("lanes", [1024, 128])
def test_addressed_matches_the_rolled_form(d, c, r, lanes):
    """``lanes=`` pins the rolled form (``_roll1d`` on an (S, lanes)
    tile): the addressed table equals it to summation order, and the
    estimates of one table are the same bits in both forms, tail mask
    included."""
    from commefficient_tpu.ops import sketch_pallas as sp
    _, pal = _rot_pair(d, c, r)
    rot, seed, sgn = _kernel_args(pal)
    vp = jnp.asarray(np.random.RandomState(1).randn(pal._padded_d)
                     .astype(np.float32))
    t_addr, t_roll = (sp.sketch_pallas(vp, rot, c, r, seed, True, L,
                                       pal._one_mix_signs, 1024, sgn)
                      for L in (None, lanes))
    np.testing.assert_allclose(np.asarray(t_addr), np.asarray(t_roll),
                               rtol=1e-6, atol=1e-5)
    for valid in (None, d):
        e_addr, e_roll = (sp.estimates_pallas(
            t_roll, rot, c, r, seed, True, L, pal._one_mix_signs,
            valid, 1024, sgn) for L in (None, lanes))
        np.testing.assert_array_equal(np.asarray(e_addr),
                                      np.asarray(e_roll))


@pytest.mark.parametrize("j", [0, 1, 7])
def test_addressed_rotations_that_wrap(j):
    """One row, 16 chunks of which one is not zero, every rotation
    1024·j given by hand: j = 0 (nothing moves), 1, and S/8 − 1 = 7
    (every vreg row but the first wraps past the table row's end). The
    table is that chunk rolled, to the bit (one add of a non-zero a
    bucket), and every chunk's estimates undo it."""
    from commefficient_tpu.ops import sketch_pallas as sp
    c, m = 8192, 16
    chunk = np.random.RandomState(2).randn(c).astype(np.float32)
    v = np.zeros((m, c), np.float32)
    v[3] = chunk
    rot = jnp.full((1, m), 1024 * j, jnp.int32)
    sgn = jnp.zeros((m * c,), jnp.uint8)  # every sign +1
    table = sp.sketch_pallas(jnp.asarray(v.reshape(-1)), rot, c, 1, 0,
                             True, None, True, 1024, sgn)
    np.testing.assert_array_equal(np.asarray(table[0]),
                                  np.roll(chunk, 1024 * j))
    est = sp.estimates_pallas(table, rot, c, 1, 0, True, None, True,
                              None, 1024, sgn)
    np.testing.assert_array_equal(np.asarray(est).reshape(m, c),
                                  np.tile(chunk, (m, 1)))


@pytest.mark.parametrize("valid", [1, 8192 + 33, 16 * 8192 - 1,
                                   16 * 8192])
def test_addressed_valid_mask(valid):
    """Positions >= valid read zero, whichever chunk they lie in; the
    rest are the unmasked estimates' bits."""
    from commefficient_tpu.ops import sketch_pallas as sp
    d, c, r = 16 * 8192, 8192, 5
    _, pal = _rot_pair(d, c, r)
    rot, seed, sgn = _kernel_args(pal)
    table = jnp.asarray(np.random.RandomState(3).randn(r, c)
                        .astype(np.float32))
    full, cut = (np.asarray(sp.estimates_pallas(
        table, rot, c, r, seed, True, None, True, va, 1024, sgn))
        for va in (None, valid))
    np.testing.assert_array_equal(cut[:valid], full[:valid])
    assert not cut[valid:].any() and full.all()


@pytest.mark.parametrize("rows", [None, (0, 2), (2, 3), (4, 1)])
@pytest.mark.parametrize("m,rot_lanes,form", [
    (20, 1024, "addressed"), (5, 1024, "addressed"), (20, 512, "rolled")])
def test_sketch_quant_rows_take_the_operators_form(rows, m, rot_lanes,
                                                   form):
    """The fused int8 emit shares the row loop: the whole table and
    every ``--overlap_depth`` row chunk (``row_offset`` != 0: signs
    keyed by the absolute row) equal the XLA twin's, and a chunk is the
    same bytes as those rows of the whole call, in either form (a
    chunk takes the whole operator's)."""
    d, c, r = m * 8192 - 77, 8192, 5
    xla, pal = _rot_pair(d, c, r, rot_lanes=rot_lanes)
    assert pal.rot_form == form
    v = jnp.asarray(np.random.RandomState(4).randn(d).astype(np.float32))
    (qx, sx), (qp, s_p) = (cs.sketch_quantized(v, "int8", rows=rows)
                           for cs in (xla, pal))
    np.testing.assert_allclose(np.asarray(sx), np.asarray(s_p), rtol=1e-6)
    assert np.max(np.abs(np.asarray(qx, np.int32)
                         - np.asarray(qp, np.int32))) <= 1
    if rows is not None:
        off, cnt = rows
        q_all, s_all = pal.sketch_quantized(v, "int8")
        np.testing.assert_array_equal(np.asarray(qp),
                                      np.asarray(q_all[off:off + cnt]))
        np.testing.assert_array_equal(np.asarray(s_p),
                                      np.asarray(s_all[off:off + cnt]))


def test_addressed_past_rot_whole():
    """An (r, m) rotation table past ``_ROT_WHOLE`` goes through SMEM a
    block at a time; only what is done with the rotation differs."""
    from commefficient_tpu.ops import sketch_pallas as sp
    c, r = 8192, 8
    d = 520 * c - 5
    assert r * 520 > sp._ROT_WHOLE
    xla, pal = _rot_pair(d, c, r)
    assert pal.rot_form == "addressed"
    v = jnp.asarray(np.random.RandomState(5).randn(d).astype(np.float32))
    tx = xla.sketch(v)
    np.testing.assert_allclose(np.asarray(tx), np.asarray(pal.sketch(v)),
                               rtol=1e-5, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(xla.estimates(tx)),
                                  np.asarray(pal.estimates(tx)))


def test_no_roll_on_the_addressed_path():
    """The traced kernels of a whole-vreg geometry hold no roll; the
    rolled form's do."""
    from commefficient_tpu.ops import sketch_pallas as sp
    d, c, r = 16 * 8192, 8192, 5
    _, pal = _rot_pair(d, c, r)
    rot, seed, sgn = _kernel_args(pal)
    vp = jnp.zeros((d,), jnp.float32)
    table = jnp.zeros((r, c), jnp.float32)
    for lanes, rolls in ((None, False), (1024, True)):
        for fn, args in (
                (sp.sketch_pallas,
                 (vp, rot, c, r, seed, True, lanes, True, 1024, sgn)),
                (sp.sketch_quant_pallas,
                 (vp, rot, c, r, seed, True, lanes, True, 1024, sgn)),
                (sp.estimates_pallas,
                 (table, rot, c, r, seed, True, lanes, True, d - 5, 1024,
                  sgn))):
            text = str(jax.make_jaxpr(
                lambda *a, fn=fn, args=args: fn(*a, *args[1:]))(args[0]))
            # the primitive, not a loop's ``unroll=``
            assert bool(re.search(r"\broll\[", text)) == rolls, (fn, lanes)

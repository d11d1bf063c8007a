"""Pallas sketch kernels vs the XLA rotation-sketch path.

The contract is hash-identity: identical rotation/sign streams, so
Pallas- and XLA-sketched tables may be psum-mixed. Chunk summation
order differs between the two (sequential grid accumulation vs XLA's
tree reduce), so sketch tables match to ULP-level tolerance; recovery
from a given table is a pure permutation + median and matches
bit-for-bit. On CPU the kernels run in interpreter mode."""

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

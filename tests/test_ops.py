"""Unit tests for compression ops: top-k, clipping, count-sketch.

Covers what the reference never tested (SURVEY.md §4): sketch
linearity, unbiased recovery, heavy-hitter top-k accuracy, l2estimate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import CountSketch, clip_by_l2, topk
from commefficient_tpu.ops.sketch import clip_record
from commefficient_tpu.ops.topk import topk_values_indices


class TestTopk:
    def test_1d_keeps_largest_magnitude(self):
        v = jnp.array([1.0, -5.0, 3.0, 0.5, -2.0])
        out = topk(v, 2)
        np.testing.assert_allclose(out, [0.0, -5.0, 3.0, 0.0, 0.0])

    def test_1d_preserves_values_exactly(self):
        rng = np.random.RandomState(0)
        v = jnp.asarray(rng.randn(1000).astype(np.float32))
        out = np.asarray(topk(v, 100))
        nz = np.nonzero(out)[0]
        assert len(nz) == 100
        np.testing.assert_array_equal(out[nz], np.asarray(v)[nz])
        # the kept set is exactly the 100 largest |v|
        thresh = np.sort(np.abs(np.asarray(v)))[-100]
        assert np.all(np.abs(out[nz]) >= thresh)

    def test_2d_rowwise(self):
        v = jnp.array([[1.0, -5.0, 3.0], [0.1, 0.2, -0.3]])
        out = topk(v, 1)
        np.testing.assert_allclose(out, [[0, -5, 0], [0, 0, -0.3]])

    def test_values_indices(self):
        v = jnp.array([1.0, -5.0, 3.0])
        vals, idx = topk_values_indices(v, 2)
        assert set(np.asarray(idx).tolist()) == {1, 2}

    def test_jit_compatible(self):
        f = jax.jit(lambda v: topk(v, 3))
        v = jnp.arange(10.0)
        np.testing.assert_allclose(f(v), topk(v, 3))


class TestThresholdSelect:
    """The exact large-d selection path (_threshold_topk_idx, engaged
    above _THRESHOLD_SELECT_MIN_D): 32 masked count-reductions instead
    of a full sort, same selected SET as lax.top_k including the
    lowest-index tie-break."""

    def test_matches_lax_top_k_set(self):
        from commefficient_tpu.ops.topk import _threshold_topk_idx
        rng = np.random.RandomState(1)
        for d, k in ((4096, 1), (4096, 64), (4096, 4095),
                     (50000, 2000)):
            x = rng.randn(d).astype(np.float32)
            x[rng.randint(0, d, 32)] = 2.5  # magnitude ties
            x[rng.randint(0, d, 32)] = 0.0
            sq = jnp.square(jnp.asarray(x))
            want = set(np.asarray(jax.lax.top_k(sq, k)[1]).tolist())
            got = np.asarray(_threshold_topk_idx(sq, k))
            assert len(set(got.tolist())) == k
            assert set(got.tolist()) == want, (d, k)

    def test_batched_and_vmapped(self):
        from commefficient_tpu.ops.topk import _threshold_topk_idx
        rng = np.random.RandomState(2)
        sq = jnp.square(jnp.asarray(
            rng.randn(3, 8192).astype(np.float32)))
        want = np.asarray(jax.lax.top_k(sq, 100)[1])
        for got in (np.asarray(_threshold_topk_idx(sq, 100)),
                    np.asarray(jax.vmap(
                        lambda s: _threshold_topk_idx(s, 100))(sq))):
            for r in range(3):
                assert set(got[r]) == set(want[r]), r

    def test_all_equal_ties_pick_lowest_indices(self):
        from commefficient_tpu.ops.topk import _threshold_topk_idx
        idx = np.asarray(_threshold_topk_idx(
            jnp.ones(5000, jnp.float32), 7))
        assert idx.tolist() == list(range(7))

    def test_hierarchical_indices_match_lax_top_k(self):
        """threshold_topk_indices (blocked-cumsum compaction, the
        sortless exact selection behind large-d unsketch recovery):
        same selected set as lax.top_k, ascending order, exact k."""
        from commefficient_tpu.ops.topk import threshold_topk_indices
        rng = np.random.RandomState(6)
        for d, k in ((5000, 17), (5000, 1), (100000, 5000),
                     (3000, 2999)):
            x = rng.randn(d).astype(np.float32)
            x[rng.randint(0, d, 60)] = 1.5  # ties
            x[rng.randint(0, d, 60)] = 0.0
            sq = jnp.square(jnp.asarray(x))
            got = np.asarray(threshold_topk_indices(sq, k))
            want = set(np.asarray(jax.lax.top_k(sq, k)[1]).tolist())
            assert len(set(got.tolist())) == k
            assert set(got.tolist()) == want, (d, k)
            assert (np.diff(got) > 0).all()
        # all-equal ties: lowest k indices
        gi = np.asarray(threshold_topk_indices(
            jnp.ones(5000, jnp.float32), 7))
        assert gi.tolist() == list(range(7))

    def test_blocked_cumsum_exact_on_ints(self):
        from commefficient_tpu.ops.topk import _blocked_cumsum
        rng = np.random.RandomState(7)
        x = rng.randint(0, 3, (3, 5000)).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(_blocked_cumsum(jnp.asarray(x))),
            np.cumsum(x, -1))

    def test_unsketch_exact_uses_threshold_path(self, monkeypatch):
        """CountSketch.unsketch's exact selection at large d (here
        forced via the threshold override) recovers the same support
        as lax.top_k of the estimates — compared directly against
        lax.top_k, not against a second unsketch call (jit would
        serve the first trace from cache and make that vacuous)."""
        import importlib

        from commefficient_tpu.ops.sketch import CountSketch
        topk_mod = importlib.import_module(
            "commefficient_tpu.ops.topk")

        cs = CountSketch(d=4096, c=256, r=3)
        rng = np.random.RandomState(8)
        table = jnp.asarray(rng.randn(3, 256).astype(np.float32))

        monkeypatch.setattr(topk_mod, "_THRESHOLD_SELECT_MIN_D", 1)
        dense_t, idx_t, vals_t = cs.unsketch(table, 16,
                                             with_support=True)
        est = cs.estimates(table)
        _, idx_want = jax.lax.top_k(jnp.square(est), 16)
        assert set(np.asarray(idx_t).tolist()) \
            == set(np.asarray(idx_want).tolist())
        np.testing.assert_allclose(
            np.asarray(vals_t),
            np.asarray(est)[np.asarray(idx_t)], rtol=1e-6)
        nz = np.nonzero(np.asarray(dense_t))[0]
        assert set(nz.tolist()) <= set(np.asarray(idx_t).tolist())

    def test_engaged_above_threshold_d(self):
        """topk at d >= _THRESHOLD_SELECT_MIN_D goes through the
        threshold path and still keeps exactly the k largest."""
        from commefficient_tpu.ops.topk import _THRESHOLD_SELECT_MIN_D
        d = _THRESHOLD_SELECT_MIN_D
        rng = np.random.RandomState(3)
        v = rng.randn(d).astype(np.float32)
        out = np.asarray(topk(jnp.asarray(v), 500))
        nz = np.nonzero(out)[0]
        assert len(nz) == 500
        np.testing.assert_array_equal(out[nz], v[nz])
        thresh = np.partition(np.abs(v), -500)[-500]
        assert np.all(np.abs(out[nz]) >= thresh)


def _two_level_cases():
    """name -> (values, k, two_level): inputs on which the two-level
    form of threshold_topk_indices has to pick what lax.top_k picks,
    as a function of the block size."""
    def gaussian(b, rng):
        return rng.randn(200 * b).astype(np.float32), 37, True

    def heavy_ties(b, rng):
        return rng.randint(0, 4, 300 * b).astype(np.float32), 61, True

    def few_nonzeros(b, rng):
        # fewer than k nonzeros and a ragged last block: the zero ties
        # fall to the lowest indices, never into the padded tail
        d, k = 100 * b + b // 2 + 1, 30
        x = np.zeros(d, np.float32)
        x[rng.choice(d, k // 3, replace=False)] = rng.randn(k // 3)
        return x, k, True

    def constant(b, rng):
        return np.full(48 * b, 0.5, np.float32), 11, True

    def ties_in_excluded_blocks(b, rng):
        # one block above the threshold, thirty blocks whose maximum IS
        # the threshold, several ties in each: only the lowest-indexed
        # of them become candidates, the others hold ties that must
        # not be picked
        x = np.zeros(40 * b, np.float32).reshape(40, b)
        for j in range(2, 32):
            x[j, rng.choice(b, rng.randint(1, 4), replace=False)] = 1.0
        x[35, 0], x[35, b - 1] = 2.0, -1.0
        return x.reshape(-1), 9, True

    def ragged_d(b, rng):
        return rng.randn(33 * b + 5).astype(np.float32), 7, True

    def k_not_below_blocks(b, rng):
        # as many blocks as k or fewer: every block would be a
        # candidate, the flat form runs
        return rng.randn(16 * b).astype(np.float32), 16, False

    def infinities(b, rng):
        x = rng.randn(40 * b).astype(np.float32)
        x[[3, 5 * b + 1, 39 * b]] = np.inf, -np.inf, np.inf
        return x, 2, True

    return [gaussian, heavy_ties, few_nonzeros, constant,
            ties_in_excluded_blocks, ragged_d, k_not_below_blocks,
            infinities]


@pytest.mark.parametrize("block", [8, 32, 128])
@pytest.mark.parametrize("case", _two_level_cases(),
                         ids=lambda f: f.__name__)
def test_two_level_indices_match_lax_top_k(case, block, monkeypatch):
    """threshold_topk_indices' two-level form, forced at small d: the
    index ARRAY (the set, ascending) of lax.top_k over the squares,
    ties included, from the values (``key=square``) and from the
    squares themselves."""
    import importlib
    topk_mod = importlib.import_module("commefficient_tpu.ops.topk")
    x, k, two_level = case(block, np.random.RandomState(block))
    masks = []
    real = topk_mod.threshold_topk_mask_1d
    monkeypatch.setattr(
        topk_mod, "threshold_topk_mask_1d",
        lambda sq, k, **kw: (masks.append(sq.shape[0]), real(sq, k, **kw))[1])
    sq = jnp.square(jnp.asarray(x))
    want = np.sort(np.asarray(jax.lax.top_k(sq, k)[1]))
    got = np.asarray(topk_mod.threshold_topk_indices(
        jnp.asarray(x), k, key=jax.lax.square, coarse=block))
    # the flat form makes one mask over d; the two-level form one over
    # the block maxima and one over the k blocks' candidates
    nb = -(-x.size // block)
    assert masks == ([nb, k * block] if two_level else [x.size])
    np.testing.assert_array_equal(got, want)
    assert got.max() < x.size
    np.testing.assert_array_equal(
        np.asarray(topk_mod.threshold_topk_indices(sq, k, coarse=block)),
        want)
    np.testing.assert_array_equal(
        np.asarray(topk_mod.threshold_topk_indices(sq, k, coarse=0)),
        want)


def test_unsketch_support_same_in_both_forms(monkeypatch):
    """CountSketch.unsketch's support with the two-level form engaged
    and with the flat form: identical idx and vals. The jitted method
    would serve the second call from the first's trace, so the method
    under the jit is called."""
    import importlib
    topk_mod = importlib.import_module("commefficient_tpu.ops.topk")
    cs = CountSketch(d=5000, c=256, r=3)
    table = jnp.asarray(
        np.random.RandomState(8).randn(3, 256).astype(np.float32))
    k = 16
    monkeypatch.setattr(topk_mod, "_THRESHOLD_SELECT_MIN_D", 1)
    monkeypatch.setattr(topk_mod, "_SELECT_BLOCK", 8)
    got = {}
    for form, ratio in (("blocked", 4), ("flat", 1 << 20)):
        monkeypatch.setattr(topk_mod, "_SELECT_BLOCKED_MIN_RATIO", ratio)
        assert bool(topk_mod.select_block(cs._padded_d, k)) \
            == (form == "blocked")
        dense, idx, vals = CountSketch.unsketch.__wrapped__(
            cs, table, k, True, False)
        assert dense is None
        got[form] = np.asarray(idx), np.asarray(vals)
    np.testing.assert_array_equal(got["blocked"][0], got["flat"][0])
    np.testing.assert_array_equal(got["blocked"][1], got["flat"][1])
    est = cs.estimates(table)
    np.testing.assert_array_equal(
        got["flat"][0],
        np.sort(np.asarray(jax.lax.top_k(jnp.square(est), k)[1])))


@pytest.mark.parametrize("d,k,form", [
    (700_865_520, 50_000, "blocked"),     # the Nemotron cell
    (376_091_904, 50_000, "blocked"),     # JoyAI
    (124_444_417, 50_000, "blocked"),     # GPT-2
    (60_000_000, 50_000, "blocked"),
    (40_000_000, 50_000, "flat"),         # sparse regime, d < 8·k·128
    (6_584_000, 50_000, "flat"),          # ResNet9: the dense mask
    (100_000, 500, "flat"),               # lax.top_k
])
def test_select_form_is_a_function_of_the_shapes(d, k, form):
    cs = CountSketch(d=d, c=524_288, r=5)
    want = (form, k * 128 if form == "blocked" else d)
    assert cs.select_form(k) == want
    # nothing but (d, k): no backend, seed or rotation setting
    assert CountSketch(d=d, c=524_288, r=5, seed=9, backend="xla",
                       rot_lanes=1024).select_form(k) == want
    assert CountSketch(d=d, c=524_288, r=5,
                       approx_topk=True).select_form(k) == ("flat", d)


def test_resnet9_server_round_never_reaches_the_index_select(
        monkeypatch):
    """ResNet9's geometry is in the dense regime (d < 90·r·k): its
    sketched server update takes the threshold mask and is built
    without ``threshold_topk_indices``, whose two forms it therefore
    cannot tell apart."""
    import importlib

    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (args2sketch,
                                               server_select_form)
    from commefficient_tpu.core.server import ServerState, server_update
    topk_mod = importlib.import_module("commefficient_tpu.ops.topk")

    def never(*a, **kw):
        raise AssertionError("the index select was traced")

    monkeypatch.setattr(topk_mod, "threshold_topk_indices", never)
    cfg = Config(mode="sketch", error_type="virtual", local_momentum=0.0,
                 virtual_momentum=0.9, num_rows=5, num_cols=524_288,
                 num_blocks=1, k=50_000, grad_size=6_584_000,
                 num_workers=1, num_clients=1, dataset_name="CIFAR10")
    sketch = args2sketch(cfg)
    assert sketch.prefer_threshold_unsketch(cfg.k)
    assert server_select_form(cfg) == ("flat", 6_584_000)
    table = jax.ShapeDtypeStruct((5, 524_288), jnp.float32)
    out = jax.eval_shape(
        lambda g, v, e: server_update(cfg, g, ServerState(v, e), 0.1,
                                      sketch, None),
        table, table, table)
    assert out.weight_update.shape == (6_584_000,)
    assert set(out.support) == {"bitmap"}


class TestClip:
    def test_noop_below_clip(self):
        v = jnp.array([0.3, 0.4])  # norm 0.5
        np.testing.assert_allclose(clip_by_l2(v, 1.0), v)

    def test_clips_above(self):
        v = jnp.array([3.0, 4.0])  # norm 5
        out = clip_by_l2(v, 1.0)
        np.testing.assert_allclose(np.linalg.norm(out), 1.0, rtol=1e-6)


class TestCountSketch:
    @pytest.fixture
    def cs(self):
        return CountSketch(d=2048, c=512, r=5, num_blocks=4)

    def test_linearity(self, cs):
        """sketch(a) + sketch(b) == sketch(a + b): required for
        psum-of-sketches to equal the sketch of the summed gradient."""
        rng = np.random.RandomState(1)
        a = jnp.asarray(rng.randn(cs.d).astype(np.float32))
        b = jnp.asarray(rng.randn(cs.d).astype(np.float32))
        np.testing.assert_allclose(
            cs.sketch(a) + cs.sketch(b), cs.sketch(a + b),
            rtol=1e-4, atol=1e-4)

    def test_determinism_across_calls(self, cs):
        v = jnp.asarray(np.random.RandomState(2).randn(cs.d).astype(np.float32))
        np.testing.assert_array_equal(cs.sketch(v), cs.sketch(v))

    def test_scaling(self, cs):
        v = jnp.asarray(np.random.RandomState(3).randn(cs.d).astype(np.float32))
        np.testing.assert_allclose(cs.sketch(2.5 * v), 2.5 * cs.sketch(v),
                                   rtol=1e-4, atol=1e-4)

    def test_heavy_hitter_recovery(self):
        """A sparse signal much larger than the noise floor must be
        recovered at the right coordinates with ~right values."""
        cs = CountSketch(d=10000, c=2000, r=5, num_blocks=5)
        rng = np.random.RandomState(4)
        v = np.zeros(cs.d, np.float32)
        hh_idx = rng.choice(cs.d, 20, replace=False)
        hh_val = rng.randn(20).astype(np.float32) * 100
        v[hh_idx] = hh_val
        v += rng.randn(cs.d).astype(np.float32) * 0.01
        rec = np.asarray(cs.unsketch(cs.sketch(jnp.asarray(v)), k=20))
        assert set(np.nonzero(rec)[0]) == set(hh_idx.tolist())
        np.testing.assert_allclose(rec[hh_idx], hh_val, rtol=0.05, atol=1.0)

    def test_unsketch_exact_when_wide(self):
        """With c >> d and no collisions likely, recovery is exact."""
        cs = CountSketch(d=50, c=4096, r=5, num_blocks=1)
        v = jnp.asarray(np.random.RandomState(5).randn(50).astype(np.float32))
        rec = cs.unsketch(cs.sketch(v), k=50)
        np.testing.assert_allclose(rec, v, rtol=1e-4, atol=1e-4)

    def test_unsketch_k_sparsity(self, cs):
        v = jnp.asarray(np.random.RandomState(6).randn(cs.d).astype(np.float32))
        rec = np.asarray(cs.unsketch(cs.sketch(v), k=64))
        assert np.count_nonzero(rec) <= 64

    def test_estimates_unbiased(self):
        """Mean estimate error across many random seeds ~ 0."""
        rng = np.random.RandomState(7)
        v = np.zeros(500, np.float32)
        v[7] = 10.0
        errs = []
        for seed in range(20):
            cs = CountSketch(d=500, c=50, r=3, num_blocks=1, seed=seed)
            est = np.asarray(cs.estimates(cs.sketch(jnp.asarray(v))))
            errs.append(est[7] - 10.0)
        assert abs(np.mean(errs)) < 1.5

    def test_l2estimate(self):
        cs = CountSketch(d=5000, c=2500, r=5, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(8).randn(cs.d).astype(np.float32))
        true = float(jnp.linalg.norm(v))
        est = float(cs.l2estimate(cs.sketch(v)))
        assert abs(est - true) / true < 0.15

    def test_clip_record_sketch(self):
        cs = CountSketch(d=5000, c=2500, r=5, num_blocks=2)
        v = jnp.asarray(np.random.RandomState(9).randn(cs.d).astype(np.float32))
        table = cs.sketch(v)
        clipped = clip_record(table, 1.0, is_sketch=True)
        assert float(cs.l2estimate(clipped)) <= 1.01

    def test_table_shape_and_jit(self, cs):
        v = jnp.zeros(cs.d)
        f = jax.jit(cs.sketch)
        assert f(v).shape == (cs.r, cs.c)

    def test_hash_quality_uniform(self, cs):
        """Buckets should be near-uniform: chi-square sanity bound."""
        idx = jnp.arange(cs.d, dtype=jnp.int32)
        buckets, signs = cs.hashes(idx)
        counts = np.bincount(np.asarray(buckets[0]), minlength=cs.c)
        expected = cs.d / cs.c
        chi2 = np.sum((counts - expected) ** 2 / expected)
        # dof = c-1; mean c, sd sqrt(2c): allow 5 sd
        assert chi2 < cs.c + 5 * np.sqrt(2 * cs.c)
        assert abs(float(jnp.mean(signs))) < 0.05

    def test_sketch_sparse_matches_dense(self, cs):
        """sketch_sparse(idx, vals) must equal sketch of the dense
        scatter — it replaces the server's O(d) re-sketch of the
        k-sparse recovered update at large d."""
        rng = np.random.RandomState(5)
        idx = rng.choice(cs.d, 64, replace=False).astype(np.int32)
        vals = rng.randn(64).astype(np.float32)
        dense = np.zeros(cs.d, np.float32)
        dense[idx] = vals
        t_dense = np.asarray(cs.sketch(jnp.asarray(dense)))
        t_sparse = np.asarray(cs.sketch_sparse(jnp.asarray(idx),
                                               jnp.asarray(vals)))
        np.testing.assert_allclose(t_dense, t_sparse, rtol=1e-5,
                                   atol=1e-6)

    def test_sketch_sparse_matches_dense_many_rows(self):
        """r > 16 exercises the per-(row, coord) sign fallback in
        hashes()."""
        cs = CountSketch(d=1024, c=128, r=17)
        rng = np.random.RandomState(6)
        idx = rng.choice(cs.d, 32, replace=False).astype(np.int32)
        vals = rng.randn(32).astype(np.float32)
        dense = np.zeros(cs.d, np.float32)
        dense[idx] = vals
        np.testing.assert_allclose(
            np.asarray(cs.sketch(jnp.asarray(dense))),
            np.asarray(cs.sketch_sparse(jnp.asarray(idx),
                                        jnp.asarray(vals))),
            rtol=1e-5, atol=1e-6)

    def test_prefer_sparse_resketch_heuristic(self):
        # GPT-2 flagship geometry: sparse wins
        assert CountSketch(d=124_000_000, c=524288, r=5) \
            .prefer_sparse_resketch(50000)
        # ResNet9 geometry: dense kernel wins
        assert not CountSketch(d=6_600_000, c=524288, r=5) \
            .prefer_sparse_resketch(50000)


class TestKExceedingD:
    def test_topk_k_exceeding_d_is_total(self):
        import jax.numpy as jnp
        from commefficient_tpu.ops.topk import topk

        v = jnp.array([3.0, -1.0, 2.0], jnp.float32)
        np.testing.assert_array_equal(np.asarray(topk(v, k=10)),
                                      np.asarray(v))

    def test_unsketch_k_exceeding_d(self):
        from commefficient_tpu.ops.sketch import CountSketch

        cs = CountSketch(d=50, c=32, r=3, backend="xla")
        v = np.random.RandomState(0).randn(50).astype(np.float32)
        out = cs.unsketch(cs.sketch(v), k=100)  # k > d
        assert out.shape == (50,)


class TestApproxTopk:
    def test_approx_selects_heavy_hitters(self):
        """approx topk keeps ~recall of the true top-k set; selected
        values are preserved exactly and output stays k-sparse."""
        rng = np.random.RandomState(0)
        v = jnp.asarray(rng.randn(100_000).astype(np.float32))
        out = np.asarray(topk(v, 1000, approx=True, recall=0.95))
        nz = np.nonzero(out)[0]
        assert len(nz) <= 1000
        np.testing.assert_array_equal(out[nz], np.asarray(v)[nz])
        true_set = set(np.argsort(np.abs(np.asarray(v)))[-1000:])
        hit = len(true_set & set(nz.tolist())) / 1000
        assert hit >= 0.90  # recall target 0.95 with slack

    def test_approx_2d_rowwise(self):
        rng = np.random.RandomState(1)
        v = jnp.asarray(rng.randn(2, 50_000).astype(np.float32))
        out = np.asarray(topk(v, 500, approx=True))
        assert out.shape == v.shape
        assert all(np.count_nonzero(out[i]) <= 500 for i in range(2))

    def test_approx_with_support_consistent(self):
        from commefficient_tpu.ops.topk import topk_with_support
        rng = np.random.RandomState(2)
        v = jnp.asarray(rng.randn(50_000).astype(np.float32))
        dense, idx, vals = topk_with_support(v, 500, approx=True)
        np.testing.assert_array_equal(
            np.asarray(dense)[np.asarray(idx)], np.asarray(vals))
        np.testing.assert_array_equal(
            np.asarray(vals), np.asarray(v)[np.asarray(idx)])

    def test_exact_default_unchanged(self):
        v = jnp.array([1.0, -5.0, 3.0, 0.5, -2.0])
        np.testing.assert_allclose(topk(v, 2),
                                   [0.0, -5.0, 3.0, 0.0, 0.0])

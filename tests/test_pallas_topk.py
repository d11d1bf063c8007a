"""Fused take-mask Pallas kernel vs the XLA threshold mask — exactly
k selected, identical sets including lowest-index tie-breaks. On CPU
the kernel runs in interpreter mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops.topk import (_nibble_threshold_key,
                                        _threshold_topk_mask,
                                        threshold_topk_mask_1d)
from commefficient_tpu.ops.topk_pallas import _CHUNK


def _mask_via_kernel(sq, k):
    # the shipped path, with its interpret hook (so the same branch
    # selection and need-computation is under test, not a copy)
    return threshold_topk_mask_1d(sq, k, interpret=True)


@pytest.mark.parametrize("d,k", [(_CHUNK, 100), (_CHUNK + 7, 513),
                                 (3 * _CHUNK + 11, 5000)])
def test_kernel_matches_xla_mask(d, k):
    rng = np.random.RandomState(d % 97)
    x = rng.randn(d).astype(np.float32)
    x[rng.randint(0, d, 200)] = 1.5  # magnitude ties
    x[rng.randint(0, d, 200)] = 0.0
    sq = jnp.square(jnp.asarray(x))
    got = np.asarray(_mask_via_kernel(sq, k))
    want = np.asarray(_threshold_topk_mask(sq, k))
    assert got.sum() == k
    np.testing.assert_array_equal(got, want)


def test_kernel_all_equal_ties():
    """All-equal input: exactly the first k indices, across chunk
    boundaries (the SMEM rank carry)."""
    d, k = 2 * _CHUNK, _CHUNK + 17
    got = np.asarray(_mask_via_kernel(jnp.ones(d, jnp.float32), k))
    assert got.sum() == k
    assert got[:k].all() and not got[k:].any()


def test_kernel_zero_threshold_edge():
    """k exceeds the nonzero count: T == 0, the padded zeros beyond d
    must never be selected over real zeros."""
    d = _CHUNK + 100  # forces padding
    k = d - 3
    rng = np.random.RandomState(9)
    x = np.zeros(d, np.float32)
    nz = rng.choice(d, 50, replace=False)
    x[nz] = rng.randn(50)
    sq = jnp.square(jnp.asarray(x))
    got = np.asarray(_mask_via_kernel(sq, k))
    want = np.asarray(_threshold_topk_mask(sq, k))
    assert got.sum() == k
    np.testing.assert_array_equal(got, want)


def test_branch_selected_at_lowering_not_trace():
    """The Pallas-vs-XLA branch is a lax.platform_dependent, decided
    per LOWERING platform — not frozen from jax.default_backend() at
    trace time (round-4 advisor: a jit(..., backend=...) override or
    multi-backend process must not silently trace the wrong branch).
    One trace, lowered for cpu and for tpu: the cpu module must hold
    the XLA mask (no Mosaic custom-call), the tpu module the kernel."""
    d, k = _CHUNK, 100
    sq = jnp.square(jnp.asarray(
        np.random.RandomState(0).randn(d).astype(np.float32)))
    traced = jax.jit(
        lambda v: threshold_topk_mask_1d(v, k)).trace(sq)
    cpu_txt = traced.lower(lowering_platforms=("cpu",)).as_text()
    tpu_txt = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in cpu_txt
    assert "tpu_custom_call" in tpu_txt
    # and the cpu lowering executes correctly end to end
    got = np.asarray(traced.lower(
        lowering_platforms=("cpu",)).compile()(sq))
    want = np.asarray(_threshold_topk_mask(sq, k))
    assert got.sum() == k
    np.testing.assert_array_equal(got, want)


def test_nibble_search_matches_bit_search():
    from commefficient_tpu.ops.topk import _blocked_cumsum  # noqa: F401

    rng = np.random.RandomState(3)
    for d, k in ((4096, 17), (100000, 5000), (5000, 4999)):
        x = rng.randn(d).astype(np.float32)
        x[rng.randint(0, d, 60)] = 2.5
        sq = jnp.square(jnp.asarray(x))
        keys = jax.lax.bitcast_convert_type(sq, jnp.uint32)

        def bit32(keys, k):
            def body(i, t):
                bit = jnp.uint32(31) - i.astype(jnp.uint32)
                cand = t | (jnp.uint32(1) << bit)
                cnt = jnp.sum((keys >= cand).astype(jnp.int32))
                return jnp.where(cnt >= k, cand, t)
            return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))

        assert int(_nibble_threshold_key(keys, k)) == int(bit32(keys, k))

"""Fused-linear-CE kernels (ops/flce_pallas.py) vs the chunked
tied-head cross-entropy (models/gpt2.py lm_nll_sums_chunked).

The chunked path is the numeric reference: same math, logits
materialised one chunk at a time. The fused kernels must reproduce its
per-example (Σ nll, Σ valid) and its gradients w.r.t. hidden states
and the tied embedding, including ignore_index masking, padding to
tile multiples (token, vocab, both), bf16 compute, and vmap batching
over a client axis. On CPU the kernels run in interpreter mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
from commefficient_tpu.ops.flce_pallas import (lm_nll_sums_fused,
                                               resolve_fused_ce,
                                               supported)

# (E, Tm, C, V) — all far below one (1024, 2048) tile, so padding of
# both axes is always exercised; V=2500 crosses a vocab-block border
SHAPES = [
    (3, 17, 128, 301),
    (2, 40, 256, 2500),
    (1, 9, 128, 2048),   # V exactly one block
]


def _case(e, tm, c, v, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(e, tm, c), dtype)
    w = jnp.asarray(rng.randn(v, c) * 0.1, dtype)
    lab = rng.randint(0, v, (e, tm))
    lab[0, : min(5, tm)] = -100            # ignored prefix
    return h, w, jnp.asarray(lab, jnp.int32)


@pytest.mark.parametrize("e,tm,c,v", SHAPES)
def test_forward_matches_chunked(e, tm, c, v):
    h, w, lab = _case(e, tm, c, v)
    sn0, sv0 = lm_nll_sums_chunked(h, w, lab, jnp.float32)
    sn1, sv1 = lm_nll_sums_fused(h, w, lab, jnp.float32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(sn0), np.asarray(sn1),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(sv0), np.asarray(sv1))


@pytest.mark.parametrize("e,tm,c,v", SHAPES[:2])
def test_gradients_match_chunked(e, tm, c, v):
    h, w, lab = _case(e, tm, c, v, seed=1)
    # per-example weights exercise distinct cotangents per token row
    wt = jnp.asarray(np.random.RandomState(2).randn(e), jnp.float32)

    def loss(fn, kw):
        def f(h, w):
            sn, _ = fn(h, w, lab, jnp.float32, **kw)
            return jnp.sum(sn * wt)
        return f

    g0 = jax.grad(loss(lm_nll_sums_chunked, {}), (0, 1))(h, w)
    g1 = jax.grad(loss(lm_nll_sums_fused, {"interpret": True}),
                  (0, 1))(h, w)
    for a, b in zip(g0, g1):
        scale = max(1e-9, float(jnp.max(jnp.abs(a))))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=0, atol=2e-4)


def test_all_ignored_example_is_zero():
    h, w, lab = _case(2, 12, 128, 301, seed=3)
    lab = lab.at[1].set(-100)
    sn, sv = lm_nll_sums_fused(h, w, lab, jnp.float32, interpret=True)
    assert float(sn[1]) == 0.0 and float(sv[1]) == 0.0


def test_vmap_bf16_matches_chunked():
    # v=2500 spans two vocab blocks, so the backward's dX partials
    # reduction (now accumulated in f32, not bf16) is exercised
    rng = np.random.RandomState(4)
    W_, e, tm, c, v = 2, 2, 30, 128, 2500
    h = jnp.asarray(rng.randn(W_, e, tm, c), jnp.float32)
    w = jnp.asarray(rng.randn(v, c) * 0.1, jnp.float32)
    lab = jnp.asarray(rng.randint(0, v, (W_, e, tm)), jnp.int32)

    def make(fn, kw):
        def per_client(h, lab, w):
            sn, sv = fn(h, w, lab, jnp.bfloat16, **kw)
            return jnp.sum(sn / jnp.maximum(sv, 1.0))
        return lambda h, w: jnp.sum(
            jax.vmap(per_client, (0, 0, None))(h, lab, w))

    l0, (gh0, gw0) = jax.value_and_grad(
        make(lm_nll_sums_chunked, {}), (0, 1))(h, w)
    l1, (gh1, gw1) = jax.value_and_grad(
        make(lm_nll_sums_fused, {"interpret": True}), (0, 1))(h, w)
    # bf16 compute: summation-order differences only. Tolerance is
    # 2x tighter than before the f32 dX-partials accumulation.
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-2)
    for g0, g1 in ((gh0, gh1), (gw0, gw1)):
        scale = float(jnp.max(jnp.abs(g0)))
        np.testing.assert_allclose(np.asarray(g0) / scale,
                                   np.asarray(g1) / scale,
                                   rtol=0, atol=1e-2)


def test_dxp_guard_scales_with_vmap_multiplicity(monkeypatch):
    """The dX-partials OOM guard must account for the vmapped client
    axis: N clients materialise N partials buffers concurrently, so a
    geometry that fits per-call can still blow the cap under vmap
    (8 x 315 MB once passed a 512 MB check)."""
    import warnings

    from commefficient_tpu.ops import flce_pallas

    e, tm, c, v = 2, 30, 128, 301
    _, mp, _, _, nv = flce_pallas._tile_geometry(
        e * tm, v, flce_pallas._BLOCK_M, flce_pallas._BLOCK_V)
    one_call = nv * mp * c * jnp.dtype(jnp.float32).itemsize
    # cap between 1x and 8x the per-call buffer
    monkeypatch.setattr(flce_pallas, "_DXP_LIMIT", 4 * one_call)
    assert flce_pallas.fused_fallback_reason(
        e, tm, c, v, jnp.float32, interpret=True, batch_mult=1) is None
    reason = flce_pallas.fused_fallback_reason(
        e, tm, c, v, jnp.float32, interpret=True, batch_mult=8)
    assert reason is not None and "dX partials" in reason

    # the fallback is correct (chunked numbers) and warns, once
    h, w, lab = _case(e, tm, c, v, seed=7)
    monkeypatch.setattr(flce_pallas, "_warned_fallbacks", set())
    sn0, sv0 = lm_nll_sums_chunked(h, w, lab, jnp.float32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sn1, sv1 = flce_pallas.lm_nll_sums_fused(
            h, w, lab, jnp.float32, interpret=True, batch_mult=8)
        flce_pallas.lm_nll_sums_fused(
            h, w, lab, jnp.float32, interpret=True, batch_mult=8)
    hits = [r for r in rec if "falling back" in str(r.message)]
    assert len(hits) == 1, "fallback warning must fire exactly once"
    np.testing.assert_allclose(np.asarray(sn0), np.asarray(sn1),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sv0), np.asarray(sv1))


def test_unaligned_width_falls_back_to_chunked():
    assert not supported(96)
    h, w, lab = _case(2, 11, 96, 301, seed=5)
    sn0, sv0 = lm_nll_sums_chunked(h, w, lab, jnp.float32)
    sn1, sv1 = lm_nll_sums_fused(h, w, lab, jnp.float32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(sn0), np.asarray(sn1),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sv0), np.asarray(sv1))


def test_resolve_fused_ce():
    assert resolve_fused_ce("on", 768)
    assert not resolve_fused_ce("off", 768)
    # auto follows the default backend: engaged on TPU, off elsewhere
    assert resolve_fused_ce("auto", 768) == (
        jax.default_backend() == "tpu")
    assert not resolve_fused_ce("auto", 96)  # unaligned width

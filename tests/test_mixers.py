"""The Mamba-2 mixer and the grouped-query attention that both hybrid
language models build from (models/mixers.py), in both of their forms:
the scan against the stepwise recurrence, the blocked attention against
the dense form, at tiny twins of the two cells' shapes; which form the
shapes choose; and, lowered for the TPU at the cells' real shapes with
nothing run, which form each cell's layers are built in. Tiny sizes,
seeded inputs, float32, CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models import mixers
from commefficient_tpu.models.granite_hybrid import GraniteHybridConfig
from commefficient_tpu.models.mixers import (GQAttention, Mamba2Mixer,
                                             attn_plan, attn_query_block,
                                             gqa_attention, rope,
                                             ssd_chunked, ssd_head_block)
from commefficient_tpu.models.nemotron_h import NemotronHConfig
from test_nemotron_h import _close, _load   # the helpers, not the cases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "granite4-h-micro-pp4-v8"
HIGHEST = jax.default_matmul_precision("highest")

# the stepwise recurrence is the Granite reference's (a stretch of
# positions under one checkpoint); Nemotron's is tests/test_nemotron_h.py's
ref = _load(os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py"),
            "bench_ref_granite4_mixers")


def _json(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# Tiny twins of the two cells' shapes: Nemotron's (the heads of one
# group, all at once; few query heads on one key/value head) and
# Granite's (one group of many heads, a block of them at a time, the
# head count no multiple of the block; many query heads, grouped).

SCAN_TWINS = {"nemotron-like": dict(H=4, G=2, head_block=None),
              "granite-like": dict(H=6, G=1, head_block=4)}


def _scan_case(T, H, G, S=2, P=8, N=8, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (S, T, H, P))
    delta = jax.nn.softplus(jax.random.normal(k[1], (S, T, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (S, T, G, N))
    C = jax.random.normal(k[4], (S, T, G, N))
    return x, delta, A, B, C


def _stepwise(x, delta, A, B, C):
    return jax.vmap(lambda x, d, b, c: ref.recurrence(x, d, A, b, c))(
        x, delta, B, C)


@pytest.mark.parametrize("T", [8, 16, 40, 13],
                         ids=["1chunk", "2chunks", "5chunks", "ragged"])
@pytest.mark.parametrize("twin", sorted(SCAN_TWINS))
def test_the_scan_is_the_stepwise_recurrence_in_both_forms(twin, T):
    """Values and gradients at 1, 2 and 5 chunks of 8 and one length
    that is no whole number of chunks; the blocked form with 6 heads in
    blocks of 4."""
    H, G, hb = (SCAN_TWINS[twin][k] for k in ("H", "G", "head_block"))
    args = _scan_case(T, H, G)

    def chunked(*a):
        return ssd_chunked(*a, 8, jnp.float32, head_block=hb)[0]

    weight = jnp.cos(jnp.arange(T * H * 8, dtype=jnp.float32)
                     ).reshape(1, T, H, 8)
    with HIGHEST:
        got, n = ssd_chunked(*args, 8, jnp.float32, head_block=hb)
        want = _stepwise(*args)
        gp = jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
        gr = jax.grad(lambda *a: jnp.sum(_stepwise(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
    assert n == 2 * -(-T // 8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    _close(gp, gr, rel=2e-5, leaf=2e-4)


@pytest.mark.parametrize("T", [8, 16, 40], ids=["1chunk", "2chunks",
                                                "5chunks"])
@pytest.mark.parametrize("twin", sorted(SCAN_TWINS))
def test_the_scan_under_the_clients_vmap_and_checkpoint(twin, T):
    """As ``core/rounds.py make_local_loss`` and ``--remat`` apply it:
    vmapped over clients, differentiated once, the block recomputed."""
    H, G, hb = (SCAN_TWINS[twin][k] for k in ("H", "G", "head_block"))
    W = 3
    cases = [_scan_case(T, H, G, seed=10 + w) for w in range(W)]
    x, delta, _, B, C = (jnp.stack(v) for v in zip(*cases))
    A = cases[0][2]

    def loss(fn):
        def one(x, d, b, c):
            return jnp.sum(jnp.sin(fn(x, d, A, b, c)))
        return lambda x, d, b, c: jnp.sum(jax.vmap(one)(x, d, b, c))

    chunked = jax.checkpoint(
        lambda *a: ssd_chunked(*a, 8, jnp.float32, head_block=hb)[0])
    with HIGHEST:
        gp = jax.jit(jax.grad(loss(chunked), argnums=(0, 1, 2, 3)))(
            x, delta, B, C)
        gr = jax.jit(jax.grad(loss(_stepwise), argnums=(0, 1, 2, 3)))(
            x, delta, B, C)
    _close(gp, gr, rel=2e-5, leaf=2e-4)


def test_a_head_block_is_taken_from_the_groups_heads():
    """Several groups, blocked: each block holds heads of every group
    with the group's own B and C."""
    args = _scan_case(16, H=12, G=2)
    with HIGHEST:
        got, _ = ssd_chunked(*args, 8, jnp.float32, head_block=4)
        want, _ = ssd_chunked(*args, 8, jnp.float32, head_block=6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(want, _stepwise(*args), rtol=2e-5, atol=2e-5)


ATTN_TWINS = {"nemotron-like": dict(Hkv=1, g=4), "granite-like":
              dict(Hkv=2, g=4)}


@pytest.mark.parametrize("T,bq", [(20, 8), (16, 8), (20, 128)],
                         ids=["ragged", "whole-blocks", "one-padded-block"])
@pytest.mark.parametrize("twin", sorted(ATTN_TWINS))
def test_blocked_attention_is_the_dense_form(twin, T, bq):
    """Values and gradients, with grouped key/value heads, a scale that
    is not 1 / sqrt(D) and T no multiple of the block; under the
    clients ``vmap`` and ``jax.checkpoint``."""
    Hkv, g = ATTN_TWINS[twin]["Hkv"], ATTN_TWINS[twin]["g"]
    W, S, D, scale = 2, 2, 8, 0.37
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k[0], (W, S, T, Hkv, g, D))
    kk = jax.random.normal(k[1], (W, S, T, Hkv, D))
    v = jax.random.normal(k[2], (W, S, T, Hkv, D))

    def loss(block):
        fn = jax.checkpoint(lambda q, k, v: gqa_attention(
            q, k, v, scale, query_block=block)[0])
        return lambda q, k, v: jnp.sum(jnp.sin(jax.vmap(fn)(q, k, v)))

    with HIGHEST:
        got, blocked = jax.vmap(lambda *a: gqa_attention(
            *a, scale, query_block=bq), out_axes=(0, None))(q, kk, v)
        want, not_blocked = jax.vmap(lambda *a: gqa_attention(
            *a, scale, query_block=T), out_axes=(0, None))(q, kk, v)
        gp = jax.jit(jax.grad(loss(bq), argnums=(0, 1, 2)))(q, kk, v)
        gr = jax.jit(jax.grad(loss(T), argnums=(0, 1, 2)))(q, kk, v)
    assert got.shape == want.shape == q.shape
    # the form it says it built is the form it was asked for
    assert blocked is (bq < T) and not_blocked is False
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    # and the dense form is plain softmax attention, written out
    att = jnp.einsum("wstgqd,wsugd->wsgqtu", q, kk) * scale
    att = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), att,
                                   -jnp.inf), axis=-1)
    with HIGHEST:
        plain = jnp.einsum("wsgqtu,wsugd->wstgqd", att, v)
    np.testing.assert_allclose(want, plain, rtol=2e-5, atol=2e-6)


def _dense_masked(q, k, v, scale, window):
    """Every query against every key, the band by a (T, T) mask."""
    T = q.shape[1]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & (i - j < window)
    att = jnp.einsum("stgqd,sugd->sgqtu", q, k) * scale
    att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
    return jnp.einsum("sgqtu,sugd->stgqd", att, v)


BANDS = {"window-of-blocks": (32, 16, 8), "window-no-multiple": (37, 12, 8),
         "T-no-multiple": (21, 8, 8), "window-under-block": (40, 5, 16),
         "window-1": (19, 1, 8), "one-padded-block": (20, 6, 128),
         "block-of-1": (9, 4, 1)}


@pytest.mark.parametrize("case", sorted(BANDS))
def test_the_band_form_is_the_dense_masked_form(case):
    """Values and gradients over (T, window, block): a window that is a
    multiple of the block and one that is none, T that is none, a
    window inside one block, a window of 1 (every query sees itself
    alone: the output is v); under the clients ``vmap`` and
    ``jax.checkpoint``, grouped key/value heads."""
    T, window, bq = BANDS[case]
    W, S, Hkv, g, D, scale = 2, 2, 2, 3, 8, 0.41
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(k[0], (W, S, T, Hkv, g, D))
    kk = jax.random.normal(k[1], (W, S, T, Hkv, D))
    v = jax.random.normal(k[2], (W, S, T, Hkv, D))

    def loss(fn):
        fn = jax.checkpoint(fn)
        return lambda q, k, v: jnp.sum(jnp.sin(jax.vmap(fn)(q, k, v)))

    def band(q, k, v):
        return gqa_attention(q, k, v, scale, query_block=bq,
                             window=window)[0]

    def dense(q, k, v):
        return _dense_masked(q, k, v, scale, window)

    with HIGHEST:
        got, blocked = jax.vmap(lambda *a: gqa_attention(
            *a, scale, query_block=bq, window=window),
            out_axes=(0, None))(q, kk, v)
        want = jax.vmap(dense)(q, kk, v)
        gp = jax.jit(jax.grad(loss(band), argnums=(0, 1, 2)))(q, kk, v)
        gr = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, kk, v)
    assert blocked is True and got.shape == q.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    if window == 1:
        np.testing.assert_allclose(
            got, jnp.broadcast_to(v[:, :, :, :, None], got.shape),
            rtol=1e-6, atol=1e-6)
    plan = attn_plan(S, T, Hkv * g, window, bq)
    assert plan.banded and plan.keys <= window + 2 * bq
    assert plan.needed == sum(min(i + 1, window) for i in range(T))
    assert plan.pairs == -(-T // bq) * bq * plan.keys >= plan.needed


@pytest.mark.parametrize("window", [20, 33], ids=["window=T", "window>T"])
@pytest.mark.parametrize("bq", [8, 20], ids=["blocked", "dense"])
def test_a_window_of_T_or_more_is_plain_causal_attention(window, bq):
    """Bit for bit: the same code path is taken."""
    T = 20
    k = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(k[0], (2, T, 2, 2, 8))
    kk = jax.random.normal(k[1], (2, T, 2, 8))
    v = jax.random.normal(k[2], (2, T, 2, 8))
    got, blocked = gqa_attention(q, kk, v, 0.3, query_block=bq,
                                 window=window)
    want, same = gqa_attention(q, kk, v, 0.3, query_block=bq)
    assert blocked is same is (bq < T)
    np.testing.assert_array_equal(got, want)
    assert attn_plan(2, T, 4, window, bq) == attn_plan(2, T, 4, None, bq)
    with pytest.raises(ValueError, match="window"):
        gqa_attention(q, kk, v, 0.3, window=0)


def test_rope_is_the_complex_rotation():
    """Dimension i < D/2 and i + D/2 as one complex number, multiplied
    by exp(i t theta^(-2i/D)): the textbook rotation; norms are kept
    and position 0 is left as it is."""
    S, T, H, D, theta = 2, 11, 3, 16, 1.5e6
    x = jax.random.normal(jax.random.PRNGKey(13), (S, T, H, D))
    z = np.asarray(x[..., :D // 2]) + 1j * np.asarray(x[..., D // 2:])
    freq = theta ** (-np.arange(0, D, 2) / D)
    turned = z * np.exp(1j * np.arange(T)[None, :, None, None] * freq)
    got = rope(x, theta)
    np.testing.assert_allclose(got[..., :D // 2], turned.real, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got[..., D // 2:], turned.imag, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    # the grouped query layout (S, T, Hkv, g, D) turns the same way
    np.testing.assert_allclose(
        rope(x.reshape(S, T, 1, H, D), theta).reshape(x.shape), got,
        rtol=1e-6, atol=1e-7)


def _shifted_scores(q, k, theta, shift):
    """q_i . k_j with both rotated at positions shifted by ``shift``."""
    pad = ((0, 0), (shift, 0), (0, 0), (0, 0))
    qr = rope(jnp.pad(q, pad), theta)[:, shift:]
    kr = rope(jnp.pad(k, pad), theta)[:, shift:]
    return jnp.einsum("sthd,suhd->shtu", qr, kr)


def test_rope_scores_depend_on_the_distance_alone():
    """A RoPE layer's scores q_i . k_j are those of i - j: the same at
    positions 0 .. T-1 and 5 .. T+4; and they are not the unrotated
    ones."""
    k = jax.random.split(jax.random.PRNGKey(14), 2)
    q = jax.random.normal(k[0], (1, 12, 2, 16))
    kk = jax.random.normal(k[1], (1, 12, 2, 16))
    with HIGHEST:
        here = _shifted_scores(q, kk, 100.0, 0)
        there = _shifted_scores(q, kk, 100.0, 5)
        plain = jnp.einsum("sthd,suhd->shtu", q, kk)
    np.testing.assert_allclose(here, there, rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(here - plain).max()) > 0.1


@pytest.mark.parametrize("theta", [None, 100.0], ids=["nope", "rope"])
def test_a_layers_output_is_reached_through_the_distance_alone(theta):
    """With no positions or with RoPE, a token's output depends on the
    tokens its window shows and on how far back each lies, not on where
    the sequence starts: the last 9 positions of 17, whose window of 4
    hides the first 5 tokens, read what the same tokens give in a
    sequence that starts 5 later. And only a layer with positions
    differs from the plain one."""
    cfg = dataclasses.replace(NemotronHConfig.tiny(), num_attention_heads=4,
                              num_key_value_heads=2)
    layer = GQAttention(cfg, rope_theta=theta, window=4, query_block=4)
    x = jax.random.normal(jax.random.PRNGKey(15), (1, 17, cfg.hidden_size))
    params = layer.init(jax.random.PRNGKey(16), x)["params"]
    with HIGHEST:
        whole = layer.apply({"params": params}, x)
        late = layer.apply({"params": params}, x[:, 5:])
    # position 8 of the whole sees tokens 5 .. 8, which the late start
    # holds at positions 0 .. 3
    np.testing.assert_allclose(whole[:, 8:], late[:, 3:], rtol=2e-4,
                               atol=2e-5)
    # without a window the layer with positions is not the plain one
    full = GQAttention(cfg, rope_theta=theta).apply({"params": params}, x)
    plain = GQAttention(cfg).apply({"params": params}, x)
    assert (float(jnp.abs(full - plain).max()) > 1e-4) is (theta is not None)


def test_the_forms_are_chosen_from_the_shapes():
    """The two cells' shapes, a client at a time as the rounds' vmap
    hands them over: Nemotron's 16 heads x chunks of 128 and 4 query
    heads stay whole and dense; Granite's 64 x 256 and 32 do not."""
    assert ssd_head_block(1, 2048, 16, 1, 128) == 16
    assert ssd_head_block(1, 2048, 64, 1, 256) == 16
    assert ssd_head_block(1, 2048, 128, 8, 128) == 4       # the uncut layer
    assert ssd_head_block(4, 2048, 64, 1, 256) == 4
    assert attn_query_block(1, 2048, 4) == 2048
    assert attn_query_block(1, 2048, 32) == 128
    assert attn_query_block(1, 1024, 64) == 128
    assert attn_query_block(1, 2048, 8) == 2048           # 128 MiB: dense
    assert attn_query_block(1, 4096, 8) == 256
    assert attn_query_block(1, 1 << 15, 64) == 128          # never under 128
    for S, T, H, G, Q in [(1, 2048, 64, 1, 256), (2, 4096, 128, 8, 128)]:
        hb = ssd_head_block(S, T, H, G, Q)
        assert S * T * G * hb * Q * 4 <= mixers.SSD_DECAY_BYTES
    # SmallThinker's cell: one client's 8,192-token sequence, 28 heads:
    # the full layer 128 queries against 8,192 keys, a window layer 128
    # against 4,096 + 128; 1.57 scores computed for each one needed
    full, band = attn_plan(1, 8192, 28), attn_plan(1, 8192, 28, 4096)
    assert (full.block, full.keys, full.blocked, full.banded) == (
        128, 8192, True, False)
    assert (band.block, band.keys, band.blocked, band.banded) == (
        128, 4224, True, True)
    assert band.keys <= 4096 + 2 * band.block
    assert full.pairs == 8192 ** 2 and band.pairs == 8192 * 4224
    ratio = (full.pairs + 3 * band.pairs) / (full.needed + 3 * band.needed)
    assert 1.5 < ratio < 1.6
    # a short window takes wider blocks, never wider than the window
    assert attn_plan(1, 8192, 4, 256).block == 256
    assert attn_plan(1, 2048, 4, 4096) == attn_plan(1, 2048, 4)


def _whiles(fn, *shapes):
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("stablehlo.while")


@pytest.mark.parametrize("config,model_cfg,mixer_loops,attn_loops", [
    ("nemotron3-super-ep64-tp8", NemotronHConfig, 1, 0),
    (CONFIG, GraniteHybridConfig, 2, 1)], ids=["nemotron", "granite"])
def test_lowered_for_the_tpu_each_cell_gets_its_form(
        config, model_cfg, mixer_loops, attn_loops):
    """At the cells' real shapes (one client's 2,048-token sequence,
    bf16), lowered for the TPU with nothing run: Nemotron's mixer holds
    one loop, the scan over chunks, and its attention none (the
    unblocked scan and the dense attention, as before the mixers
    moved); Granite's hold one more each, over head and query blocks."""
    cfg = dataclasses.replace(model_cfg.from_hf(_json("configs", config)),
                              dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 2048, cfg.hidden_size), jnp.bfloat16)
    for module, loops in ((Mamba2Mixer(cfg), mixer_loops),
                          (GQAttention(cfg), attn_loops)):
        params = jax.eval_shape(
            lambda m=module: m.init(jax.random.PRNGKey(0), jnp.zeros(
                (1, 8, cfg.hidden_size), jnp.bfloat16))["params"])
        assert _whiles(lambda p, x, m=module: m.apply({"params": p}, x),
                       params, x) == loops


# --- the flash kernel (``attn_plan``'s kernel path), interpreted on the CPU ---

KERNEL_MASKS = {"full": None, "window": 128, "window-no-tile-multiple": 200}


def _rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel path as the chip takes it, interpreted: tiles of 128
    so that T = 256 holds several, and a score budget the twins pass,
    so that a full layer is a blocked one."""
    monkeypatch.setattr(mixers, "_platform", lambda: "interpret")
    monkeypatch.setattr(mixers, "ATTN_KERNEL_BLOCKS", (128,))
    monkeypatch.setattr(mixers, "ATTN_SCORE_BYTES", 1 << 16)
    monkeypatch.setattr(mixers, "ATTN_BLOCK_BYTES", 1 << 14)


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("g", [1, 4, 7])
@pytest.mark.parametrize("mask", sorted(KERNEL_MASKS))
def test_the_kernel_is_the_dense_masked_form(interpreted, mask, g, D):
    """Outputs and the three gradients of ``gqa_attention`` itself on
    the kernel path against every query meeting every key under a
    (T, T) mask: float32 to 1e-5; bf16 no further from the float32
    truth than the blocked form of the same operands is, by more than
    the second rounding of the scaled q. Under the clients ``vmap``
    and ``jax.checkpoint``; a scale that is no power of two."""
    T, W, S, Hkv, scale = 256, 2, 1, 2, 0.37
    window = KERNEL_MASKS[mask]
    k = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(k[0], (W, S, T, Hkv, g, D))
    kk = jax.random.normal(k[1], (W, S, T, Hkv, D))
    v = jax.random.normal(k[2], (W, S, T, Hkv, D))
    plan = attn_plan(S, T, Hkv * g, window, None, D)
    assert plan.kernel == "splash_interpret" and plan.block == 128
    assert plan.banded is (window is not None)

    def both(fn, *a):
        fn = jax.checkpoint(fn)
        out = jax.vmap(fn)(*a)
        grads = jax.grad(lambda *a: jnp.sum(jnp.sin(
            jax.vmap(fn)(*a).astype(jnp.float32))), argnums=(0, 1, 2))(*a)
        return (out,) + grads

    def program(block):
        return lambda q, k, v: gqa_attention(
            q, k, v, scale, query_block=block, window=window)[0]

    def dense(q, k, v):
        return _dense_masked(q, k, v, scale, T if window is None else window)

    with HIGHEST:
        want = jax.jit(lambda *a: both(dense, *a))(q, kk, v)
        got = jax.jit(lambda *a: both(program(None), *a))(q, kk, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and _rel(a, b) < 1e-5
    half = [a.astype(jnp.bfloat16) for a in (q, kk, v)]
    kernel = jax.jit(lambda *a: both(program(None), *a))(*half)
    blocked = jax.jit(lambda *a: both(program(64), *a))(*half)
    for a, b, truth in zip(kernel, blocked, want):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, truth) < 1.5 * _rel(b, truth) + 1e-3


# The seven cells' attention, a client at a time as the rounds' vmap
# hands it over: (S, T, query heads, head size or (q and k's, v's),
# window). ResNet9 has none; GPT-2 (``models/gpt2.py --attn_impl``)
# runs code of its own and never asks ``attn_plan``; asked at its
# shape it would say what follows. JoyAI's latent attention asks
# through ``models/joyai.py mla_plan``.
CELL_SHAPES = {
    "gpt2": (1, 256, 12, 64, None),
    "joyai": (4, 1024, 4, (192, 128), None),
    "nemotron": (1, 2048, 4, 128, None),
    "granite": (1, 2048, 32, 64, None),
    "ouro": (1, 2048, 16, 128, None),
    "smallthinker-full": (1, 8192, 28, 128, None),
    "smallthinker-window": (1, 8192, 28, 128, 4096)}
# on the chip / off it: (form, queries a block or the tile's edge)
CELL_FORMS = {
    "gpt2": (("dense", 256), ("dense", 256)),       # T no whole tile
    "joyai": (("kernel", 512), ("dense", 1024)),    # 64 MiB a client
    "nemotron": (("kernel", 512), ("dense", 2048)),         # 64 MiB
    "granite": (("kernel", 512), ("blocked", 128)),
    "ouro": (("kernel", 512), ("blocked", 256)),
    "smallthinker-full": (("kernel", 1024), ("blocked", 128)),
    "smallthinker-window": (("kernel-band", 512), ("band", 128))}
# the whole plans on the chip, as they were before the kernel took the
# layers whose scores stay under ``ATTN_SCORE_BYTES`` (PR 49), for the
# cells it had already: (block, keys, blocked, banded, pairs, needed)
KERNEL_PLANS = {
    "granite": (512, 2048, True, False, 2621440, 2098176),
    "ouro": (512, 2048, True, False, 2621440, 2098176),
    "smallthinker-full": (1024, 8192, True, False, 37748736, 33558528),
    "smallthinker-window": (512, 4608, True, True, 28311552, 25167872),
    # and the two it takes since
    "joyai": (512, 1024, True, False, 786432, 524800),
    "nemotron": (512, 2048, True, False, 2621440, 2098176)}


def _form(plan):
    if plan.kernel:
        return "kernel-band" if plan.banded else "kernel"
    return "band" if plan.banded else "blocked" if plan.blocked else "dense"


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_kernel_is_chosen_from_the_platform_and_the_shapes(cell):
    """The rule as a table; and what never gives the kernel: a stated
    ``query_block``, a platform that is no TPU (this one), a T that is
    not whole tiles, a head size the kernel is not given (or none
    said)."""
    S, T, Hq, D, window = CELL_SHAPES[cell]
    chip, host = CELL_FORMS[cell]
    on = attn_plan(S, T, Hq, window, None, D, platform="tpu")
    off = attn_plan(S, T, Hq, window, None, D, platform="cpu")
    assert (_form(on), on.block) == chip and (_form(off), off.block) == host
    assert off == attn_plan(S, T, Hq, window, None, D) \
        == attn_plan(S, T, Hq, window, None, None, platform="tpu") \
        == attn_plan(S, T, Hq, window)
    assert off.kernel is None and on.needed == off.needed
    assert on.banded is off.banded
    # off the chip the scores' size chooses dense or blocked; on it the
    # kernel takes the layer either way
    assert off.blocked is (window is not None
                           or S * Hq * T * T * 4 > mixers.ATTN_SCORE_BYTES)
    for plan in (attn_plan(S, T, Hq, window, 128, D, platform="tpu"),
                 attn_plan(S, T + 128, Hq, window, None, D, platform="tpu"),
                 attn_plan(S, T, Hq, window, None, 96, platform="tpu"),
                 attn_plan(S, T, Hq, window, None, (128, 192),
                           platform="tpu"),
                 attn_plan(S, T, Hq, window, None, D, platform="gpu")):
        assert plan.kernel is None
    if on.kernel:
        assert tuple(on)[:-1] == KERNEL_PLANS[cell] and on.blocked
        assert on.pairs < off.pairs and on.pairs <= 1.5 * on.needed
        assert on.pairs <= 1.25 * on.needed or T < 2048
        assert attn_plan(S, T, Hq, window, None, D,
                         platform="interpret")._replace(kernel="splash") == on
    else:
        assert cell not in KERNEL_PLANS
    if cell == "smallthinker-window":
        full = attn_plan(*CELL_SHAPES["smallthinker-full"][:3], None, None,
                         D, platform="tpu")
        ratio = (full.pairs + 3 * on.pairs) / (full.needed + 3 * on.needed)
        assert 1.12 < ratio < 1.13          # 1.57 off the chip


def test_one_head_size_is_the_pair_of_it():
    """``head_dim`` as one size or as (q and k's, v's): the same plan;
    the kernel's list holds pairs, MLA's 192 / 128 among them."""
    for D in (64, 128):
        assert attn_plan(1, 2048, 8, None, None, D, platform="tpu") \
            == attn_plan(1, 2048, 8, None, None, (D, D), platform="tpu") \
            == attn_plan(1, 2048, 8, None, None, [D, D], platform="tpu")
    assert (192, 128) in mixers.ATTN_KERNEL_HEAD_DIMS
    assert attn_plan(1, 2048, 8, None, None, 192, platform="tpu").kernel \
        is None


TILINGS = [(1024, 256, None), (1024, 256, 512), (1024, 256, 300),
           (1024, 128, 1), (2048, 512, 700), (1536, 512, 1536),
           (2048, 1024, 1025), (512, 512, 100)]


@pytest.mark.parametrize("T,tile,window", TILINGS)
def test_the_kernels_pairs_are_the_tiles_its_grid_visits(T, tile, window,
                                                         monkeypatch):
    """``AttnPlan.pairs`` on the kernel path against a count of the
    tiles that hold any pair the mask lets through, made from the
    (T, T) mask itself; and against the library's own table of the
    tiles its forward grid computes."""
    monkeypatch.setattr(mixers, "ATTN_KERNEL_BLOCKS", (tile,))
    plan = attn_plan(1, T, 64, window, None, 64, platform="tpu")
    assert plan.kernel == "splash" and plan.block == tile
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = (j <= i) & (i - j < (window or T))
    tiles = seen.reshape(T // tile, tile, T // tile, tile).any(axis=(1, 3))
    assert plan.needed == seen.sum()
    assert plan.pairs == tiles.sum() * tile * tile
    assert plan.keys == tiles.sum(axis=1).max() * tile
    assert mixers.kernel_tiles(T, tile, window if plan.banded else None) \
        == (tiles.sum(), tiles.sum(axis=1).max())
    kernel = mixers._splash_kernel(T, window if plan.banded else None, 2,
                                   tile, False)
    table = kernel.fwd_mask_info.block_mask
    assert isinstance(table, np.ndarray)
    assert (table > 0).sum() == tiles.sum()


def _small_thinker(**over):
    from commefficient_tpu.models.smallthinker import SmallThinkerConfig
    return dataclasses.replace(SmallThinkerConfig.tiny(), **dict(dict(
        head_dim=64, sliding_window_size=72,
        sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
        attn_query_block=None), **over))


def test_a_smallthinker_record_counts_the_kernels_layers(interpreted):
    """``attn.kernel_layers``: 4 where the plan's platform is the
    kernel's and no query block is stated, 0 with one stated; and the
    model's loss on the kernel path is the blocked forms' (the same
    weights, one (1, 256) sequence; the platform undone by hand)."""
    from commefficient_tpu.models import smallthinker as st
    ids = jax.random.randint(jax.random.PRNGKey(31), (1, 256), 0, 96)
    module = st.SmallThinkerLM(_small_thinker())
    params = module.init(jax.random.PRNGKey(32), ids[:, :8])["params"]

    def run(module):
        with HIGHEST:
            loss, stats = st.causal_lm_loss(module, params, ids)
        return float(loss[0]), dict(zip(st.STATS, map(float, stats)))

    loss, stats = run(module)
    assert (stats["attn_kernel_layers"], stats["attn_blocked"]) == (4, 1)
    assert (stats["attn_window_layers"], stats["attn_full_layers"]) == (3, 1)
    # tiles of 128: the full layer 3 of 4, a window layer 1 + 2
    assert stats["attn_pairs"] == 4 * (3 + 3 * 3) * 128 * 128
    assert stats["attn_window_keys"] == 256
    stated, counts = run(st.SmallThinkerLM(_small_thinker(
        attn_query_block=64)))
    assert counts["attn_kernel_layers"] == 0 and counts["attn_blocked"] == 1
    assert abs(loss - stated) <= 1e-5 * abs(stated)
    assert dict(st.COUNTERS)["attn.kernel_layers"] is np.max
    assert st.STATS.index("attn_kernel_layers") == [
        n for n, _ in st.COUNTERS].index("attn.kernel_layers")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lowered_for_the_tpu_the_client_round_holds_the_kernels(
        remat, monkeypatch):
    """SmallThinker's cell (2 clients x one 8,192-token sequence, bf16),
    the clients' loss and its gradient lowered for the TPU with nothing
    run: with the platform the chip's, three Mosaic calls a layer kind
    (forward keeping its residuals, dq, dk/dv; under ``--remat`` the
    forward twice); with this host's, none, and the loops of the
    blocked forms instead."""
    import collections
    import re
    from commefficient_tpu.models import smallthinker as st
    cfg = dataclasses.replace(
        st.SmallThinkerConfig.from_hf(_json(
            "configs", "smallthinker-21ba3b-ep8")),
        dtype=jnp.bfloat16, remat=remat)
    module = st.SmallThinkerLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    ids = jax.ShapeDtypeStruct((2, 1, 8192), jnp.int32)

    def loss(p, ids):
        losses, _ = jax.vmap(lambda i: st.causal_lm_loss(module, p, i))(ids)
        return jnp.sum(losses)

    def lowered():
        return jax.jit(jax.grad(loss)).trace(params, ids).lower(
            lowering_platforms=("tpu",)).as_text()

    host = lowered()
    assert "tpu_custom_call" not in host
    monkeypatch.setattr(mixers, "_platform", lambda: "tpu")
    chip = lowered()
    calls = collections.Counter(re.findall(r'kernel_name = "([^"]+)"', chip))
    assert calls == {"splash_mqa_fwd_residuals": 2 * (1 + remat),
                     "splash_mqa_dq_no_residuals": 2,
                     "splash_mqa_dkv_no_residuals": 2}
    assert chip.count("tpu_custom_call") == sum(calls.values())
    # the full layer's and the band's loops over blocks of queries, one
    # a pass of each kind, are gone
    assert chip.count("stablehlo.while") < host.count("stablehlo.while")


# --- JoyAI's latent attention on the kernel path ----------------------------

def _mla_cfg(**over):
    """JoyAI's tiny preset with the published head sizes (128 + 64 for
    q and k, 128 for v), which are what the kernel is given."""
    from commefficient_tpu.models.joyai import JoyAIConfig
    return dataclasses.replace(JoyAIConfig.tiny(), **dict(dict(
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128), **over))


@pytest.mark.parametrize("query_block,window", [(None, None), (16, None),
                                                 (16, 24)],
                         ids=["dense", "blocked", "banded"])
def test_a_q_that_carries_its_scale_says_none(query_block, window):
    """``gqa_attention(scale=None)`` on a q already multiplied by the
    scale (a power of two: exact) is ``gqa_attention(scale)`` on the
    plain q, in every ``jax.numpy`` form, values and form."""
    k = jax.random.split(jax.random.PRNGKey(50), 3)
    q = jax.random.normal(k[0], (2, 48, 2, 2, 16))
    kk, v = (jax.random.normal(k[i], (2, 48, 2, 16)) for i in (1, 2))
    want, form = gqa_attention(q, kk, v, 0.25, query_block, window)
    got, same = gqa_attention(q * 0.25, kk, v, None, query_block, window)
    assert form is same and form is (query_block is not None)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("T,kernel", [(256, True), (200, False)],
                         ids=["whole-tiles", "odd-T-falls-back"])
def test_mla_through_the_kernel_is_mlas_dense_form(interpreted, T, kernel,
                                                   monkeypatch):
    """``MLA`` itself (its projections too) on the kernel path, one
    192-wide score product beside a 128-wide value product at a group
    of one query head, against the dense form of the same weights (the
    platform undone): the output and the gradient to every weight and
    to the input, float32 to 1e-5, under the clients ``vmap`` and
    ``jax.checkpoint``. A T that is not whole tiles builds the dense
    form on the kernel's platform too: the same program, bit for bit."""
    from commefficient_tpu.models import joyai
    cfg = _mla_cfg()
    assert (joyai.mla_plan(cfg, 2, T).kernel == "splash_interpret") is kernel
    module = joyai.MLA(cfg)
    x = jax.random.normal(jax.random.PRNGKey(41), (2, 2, T, cfg.hidden_size))
    params = module.init(jax.random.PRNGKey(42), x[0, :, :8])["params"]

    def both(p, x):
        fn = jax.checkpoint(lambda x: module.apply({"params": p}, x))
        out = jax.vmap(fn)(x)
        grads = jax.grad(lambda p, x: jnp.sum(jnp.sin(jax.vmap(
            jax.checkpoint(lambda x: module.apply({"params": p}, x)))(x))),
            argnums=(0, 1))(p, x)
        return out, grads

    with HIGHEST:
        got = jax.jit(both)(params, x)
        monkeypatch.setattr(mixers, "_platform", lambda: "cpu")
        assert joyai.mla_plan(cfg, 2, T).kernel is None
        want = jax.jit(both)(params, x)
    flat = [jax.tree_util.tree_leaves(t) for t in (got, want)]
    assert got[0].shape == (2, 2, T, cfg.hidden_size)
    for a, b in zip(*flat):
        assert a.shape == b.shape
        if kernel:
            assert _rel(a, b) < 1e-5
        else:
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mla_in_bf16_is_no_further_from_the_truth_than_the_dense_form(
        interpreted, monkeypatch):
    """``mla_attention`` on bf16 operands: through the kernel (the
    scale folded into q in float32, one rounding) it lies no further
    from the float32 dense truth than the bf16 dense form does."""
    from commefficient_tpu.models import joyai
    T, S, H = 256, 2, 2
    k = jax.random.split(jax.random.PRNGKey(43), 3)
    qh = jax.random.normal(k[0], (S, T, H, 192))
    kvh = jax.random.normal(k[1], (S, T, H, 256))
    kr = jax.random.normal(k[2], (S, T, 64))

    def run(dtype, kernel):
        cfg = _mla_cfg(num_attention_heads=H, dtype=dtype)
        return joyai.mla_attention(cfg, qh.astype(dtype), kvh.astype(dtype),
                                   kr.astype(dtype), kernel)

    with HIGHEST:
        truth = run(jnp.float32, None)
    kernel = run(jnp.bfloat16, "splash_interpret")
    dense = run(jnp.bfloat16, None)
    assert kernel.dtype == dense.dtype == jnp.bfloat16
    assert _rel(kernel, truth) < 1.5 * _rel(dense, truth) + 1e-3


def test_a_joyai_record_counts_the_kernels_layers(interpreted, monkeypatch):
    """``attn.kernel_layers``: 6 at the cell's depth (5 trunk layers
    and the MTP module's block) where the plan's platform is the
    kernel's, 0 off it; ``attn.pairs`` the tiles visited; the loss the
    dense form's; and the trainer hands the benchmark's builder these
    counters under the name it reads them by."""
    from commefficient_tpu.models import joyai
    from commefficient_tpu.train import gpt2_train
    cfg = _mla_cfg(num_hidden_layers=5)
    module = joyai.JoyAIFlashLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(44), (1, 256), 0, 96)
    params = module.init(jax.random.PRNGKey(45), ids[:, :8])["params"]

    def run():
        with HIGHEST:
            loss, stats = joyai.causal_lm_loss(module, params, ids)
        return float(loss[0]), dict(zip(joyai.STATS, map(float, stats)))

    loss, stats = run()
    assert stats["attn_kernel_layers"] == 6
    # tiles of 128 at T = 256: 3 of 4; 2 heads, 6 layers, 1 sequence
    assert stats["attn_pairs"] == 2 * 6 * 3 * 128 * 128
    assert stats["attn_pairs_needed"] == 2 * 6 * (256 * 257 // 2)
    monkeypatch.setattr(mixers, "_platform", lambda: "cpu")
    dense, counts = run()
    assert counts["attn_kernel_layers"] == 0
    assert counts["attn_pairs"] == 2 * 6 * 256 * 256
    assert abs(loss - dense) <= 1e-5 * abs(dense)
    assert joyai.STATS[:len(joyai.MOE_STATS)] == joyai.MOE_STATS
    assert [n for n, _ in joyai.COUNTERS[-3:]] == [
        "attn.kernel_layers", "attn.pairs", "attn.pairs_needed"]
    assert [f for _, f in joyai.COUNTERS[-3:]] == [np.max, np.sum, np.sum]
    assert len(joyai.STATS) == len(joyai.COUNTERS)
    # the stop-gap alias its builder reads (ROADMAP yardstick (m))
    assert gpt2_train.MOE_COUNTERS is joyai.COUNTERS


def test_a_nemotron_record_counts_the_kernels_layer(interpreted,
                                                    monkeypatch):
    """``attn.kernel_layers`` on Nemotron's records: its attention
    layers where the plan's platform is the kernel's (head size 128, T
    whole tiles), 0 off it."""
    from commefficient_tpu.models import nemotron_h as nh
    cfg = dataclasses.replace(nh.NemotronHConfig.tiny(), head_dim=128,
                              hybrid_override_pattern="M*E*")
    module = nh.NemotronHLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(46), (1, 256), 0, 96)
    params = module.init(jax.random.PRNGKey(47), ids[:, :8])["params"]

    def run():
        with HIGHEST:
            loss, stats = nh.causal_lm_loss(module, params, ids)
        return float(loss[0]), dict(zip(nh.STATS, map(float, stats)))

    loss, stats = run()
    assert stats["attn_kernel_layers"] == 2
    monkeypatch.setattr(mixers, "_platform", lambda: "cpu")
    dense, counts = run()
    assert counts["attn_kernel_layers"] == 0
    assert counts["ssm_chunks"] == stats["ssm_chunks"]
    assert abs(loss - dense) <= 1e-5 * abs(dense)
    assert dict(nh.COUNTERS)["attn.kernel_layers"] is np.max
    assert len(nh.STATS) == len(nh.COUNTERS)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lowered_for_the_tpu_joyais_client_round_holds_the_kernels(
        remat, monkeypatch):
    """JoyAI's cell (8 clients x 4 sequences x 1,024 tokens, bf16), the
    clients' loss and its gradient lowered for the TPU with nothing
    run. With this host's platform: no Mosaic call, and float32 (8, 4,
    4, 1024, 1024) scores. With the chip's: the three kernels, each one
    function of the program that all six layers call (one mask table,
    one kernel object: ``_splash_kernel`` is cached by shape), the
    forward a second one under ``--remat`` (the recomputation's), and
    no float32 value with two axes of T anywhere."""
    import collections
    import re
    from commefficient_tpu.models import joyai
    cfg = dataclasses.replace(
        joyai.JoyAIConfig.from_hf(_json("configs", "joyai-llm-flash-ep32")),
        dtype=jnp.bfloat16, remat=remat)
    layers = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    assert layers == 6
    module = joyai.JoyAIFlashLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    ids = jax.ShapeDtypeStruct((8, 4, 1024), jnp.int32)

    def loss(p, ids):
        losses, _ = jax.vmap(
            lambda i: joyai.causal_lm_loss(module, p, i))(ids)
        return jnp.sum(losses)

    def lowered():
        return jax.jit(jax.grad(loss)).trace(params, ids).lower(
            lowering_platforms=("tpu",)).as_text()

    scores = re.compile(r"tensor<[0-9x]*1024x1024xf32>")
    host = lowered()
    assert "tpu_custom_call" not in host and scores.search(host)
    monkeypatch.setattr(mixers, "_platform", lambda: "tpu")
    mixers._splash_kernel.cache_clear()
    chip = lowered()
    assert mixers._splash_kernel.cache_info().currsize == 1
    assert not scores.search(chip)
    names = collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', chip))
    # SmallThinker's program holds each twice: its two kinds of layer
    assert names == {"splash_mqa_fwd_residuals": 1 + remat,
                     "splash_mqa_dq_no_residuals": 1,
                     "splash_mqa_dkv_no_residuals": 1}
    assert chip.count("tpu_custom_call") == sum(names.values())


# --- the benchmark's reader of the kernel's share of its roofline ----------

def _traced_ctx(ops, cell, config, monkeypatch):
    """A context as ``benchmark/run.py`` hands its readers, over a
    device trace made by hand: two traced rounds of 1 s on one device,
    ``ops`` (name, start us, duration us) on its ``XLA Ops`` line."""
    from types import SimpleNamespace
    monkeypatch.syspath_prepend(ROOT)
    from benchmark.lib.peaks import peaks_of
    from benchmark.run import load
    key = (7, 1)
    events = [{"ph": "X", "pid": 7, "tid": 1, "name": n, "ts": ts,
               "dur": dur} for n, ts, dur in ops]
    spec = _json("configs", config)
    ctx = {"cell": _json("workloads", cell), "ref": load("reference", config),
           "run": SimpleNamespace(ref_spec=spec), "trace_dir": "by hand",
           "peaks": peaks_of("TPU v5 lite"), "records": [],
           "window": {"first": 0, "first_traced": 0},
           "_trace": {"events": events, "lanes": {key: 0},
                      "windows": [(0, 0.0, 1e6), (1, 1e6, 2e6)],
                      "names": ({7: "/device:TPU:0"}, {key: "XLA Ops"})}}
    return ctx, load("metrics", "kernels.attn_roofline")


@pytest.mark.parametrize("cell,config,least_ms", [
    ("smallthinker_fetchsgd_w2_t8192", "smallthinker-21ba3b-ep8", 47.616),
    ("granite4hm_fetchsgd_w4_t2048", CONFIG, 1.047),
    ("ouro_fetchsgd_w2_t2048", "ouro-2.6b-pp6-l8", 16.752)])
def test_the_attention_roofline_reader(cell, config, least_ms, monkeypatch):
    """On a trace that names no kernel operation the reader finds
    nothing, and says so by None (the parent's program, a cell off the
    kernel path); on one that does, the share is the needed pairs'
    time at the peak over the operations' time a round, whatever else
    the trace holds and outside the traced rounds' windows nothing."""
    others = [("while.570", 10.0, 4e5), ("fusion.12", 5e5, 1e4),
              ("sketch_pallas.3", 6e5, 2e3)]
    ctx, reader = _traced_ctx(others, cell, config, monkeypatch)
    assert reader.read(ctx) is None
    kernel = [("splash_mqa_fwd_residuals", 1e5, 3e4),
              ("splash_mqa_fwd_residuals.1", 2e5, 3e4),
              ("splash_mqa_dkv_no_residuals", 1.2e6, 5e4),
              ("splash_mqa_dq_no_residuals.2", 1.4e6, 9e4),
              ("splash_mqa_dq_no_residuals.2", 2.5e6, 9e4)]   # past them
    ctx, reader = _traced_ctx(others + kernel, cell, config, monkeypatch)
    # 200 ms of kernel operations in two rounds: 100 ms a round
    assert reader.read(ctx) == pytest.approx(least_ms, rel=1e-3)
    z = ctx["ref"]._sizes(ctx["run"].ref_spec)
    T = ctx["cell"]["sequence_length"]
    causal = T * (T + 1) // 2
    # Granite's cut holds one attention layer; SmallThinker's one full
    # and three that see 4,096 keys; Ouro's 8 full ones run 4 times
    pairs = causal if "kinds" in z else 32 * causal if "steps" in z \
        else causal + 3 * (4096 * 4097 // 2 + (T - 4096) * 4096)
    flops = 12 * z["D"] * z["Hq"] * pairs * ctx["cell"]["clients_per_round"]
    assert 100.0 * flops / 197e12 / 0.1 == pytest.approx(least_ms, rel=1e-3)

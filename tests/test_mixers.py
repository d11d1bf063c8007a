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
                                             attn_query_block,
                                             gqa_attention, ssd_chunked,
                                             ssd_head_block)
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

"""``lib/fetchsgd_ref.follow`` held to a dense FetchSGD written out in
numpy, to the memory it may take on the device (d twice), and
``run.py``'s release of the program before the comparison."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT  # noqa: F401  (puts the repo on sys.path)
from benchmark.lib import fetchsgd_ref as fr
from test_rehearsal import _argv, _run


def _toy(widths, seed=0):
    """A reference of its own: a tanh MLP with a squared loss, its
    weights and ``rounds`` batches of W clients x B samples."""
    def client_loss(params, b, spec, q=lambda a: a):
        h = b["x"]
        for name in sorted(params["layers"]):
            h = jnp.tanh(q(h) @ q(params["layers"][name]) + params["bias"])
        per = jnp.mean((h[:, 0] * params["scale"] - b["y"]) ** 2
                       * b["mask_rows"])
        return per

    rng = np.random.RandomState(seed)
    params = {"layers": {f"l{i}": rng.normal(0, 0.5, (widths, widths))
                         .astype(np.float32) for i in range(3)},
              "bias": rng.normal(0, 0.1, (widths,)).astype(np.float32),
              "scale": np.float32(1.5)}
    ref = types.SimpleNamespace(client_loss=client_loss, CLIENTS_PER_BLOCK=2)
    return ref, params


def _batches(widths, rounds, W=6, B=5, seed=1):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(rounds):
        mask = np.ones((W,), np.float32) * B
        mask[-1] = 0.0                      # a client with no samples
        out.append({"x": rng.normal(size=(W, B, widths)).astype(np.float32),
                    "y": rng.normal(size=(W, B)).astype(np.float32),
                    "mask_rows": np.ones((W, B), np.float32),
                    "mask": mask})
    return out


def _dense_fetchsgd(ref, params, batches, lrs, hyper, sk, precision):
    """FetchSGD with the sketch as explicit (bucket, sign) maps over all
    d coordinates and every vector dense, in numpy; the gradients are
    ``jax.grad`` of the same loss, one client at a time."""
    from jax.flatten_util import ravel_pytree
    q = fr.quantizer(precision)
    flat0, unravel = ravel_pytree(params)
    flat = np.asarray(flat0, np.float32)
    i = np.arange(sk.d, dtype=np.uint32)
    t, j = (i // np.uint32(sk.c)).astype(np.int64), i % np.uint32(sk.c)
    _, sign_seed = sk.seeds()
    h = fr._mix(i ^ sign_seed)
    rots = sk.rotations()
    bucket = [(j + rots[t, row]) % sk.c for row in range(sk.r)]
    sign = [1.0 - 2.0 * ((h >> np.uint32(16 + row)) & np.uint32(1))
            .astype(np.float32) for row in range(sk.r)]

    def sketch(vec):
        table = np.zeros((sk.r, sk.c), np.float64)
        for row in range(sk.r):
            np.add.at(table[row], bucket[row], sign[row] * vec)
        return table.astype(np.float32)

    one = jax.jit(jax.value_and_grad(
        lambda w, b: ref.client_loss(unravel(w), b, None, q)))
    u = v = np.zeros((sk.r, sk.c), np.float32)
    out = {"losses": [], "table0": None, "picked": []}
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches):
            g, losses = np.zeros_like(flat, np.float64), []
            for cl in range(len(batch["mask"])):
                b = {k: jnp.asarray(x[cl]) for k, x in batch.items()}
                loss, gw = one(jnp.asarray(flat), b)
                losses.append(float(loss))
                if batch["mask"][cl] > 0:
                    g += batch["mask"][cl] * np.asarray(gw, np.float64)
            g = (g / batch["mask"].sum()).astype(np.float32) + np.float32(
                hyper["weight_decay"] / hyper["num_workers"]) * flat
            table = sketch(g)
            if step == 0:
                out["table0"] = table
            u = np.float32(hyper["rho"]) * u + table
            v = v + u
            est = np.median(np.stack(
                [sign[row] * v[row, bucket[row]] for row in range(sk.r)]),
                axis=0)
            idx = np.argsort(-np.abs(est), kind="stable")[: hyper["k"]]
            picked = np.zeros_like(flat)
            picked[idx] = est[idx]
            keep = sketch(picked) == 0
            u, v = np.where(keep, u, 0), np.where(keep, v, 0)
            flat = flat - np.float32(lrs[step]) * picked
            out["losses"].append(losses)
            out["picked"].append(np.sort(idx))
    out["delta"] = flat - np.asarray(flat0)
    return out


@pytest.mark.parametrize("precision", [None, "fp8"])
def test_follow_is_dense_fetchsgd(precision, monkeypatch):
    """Seven chunks of 128, the last one padded (d = 801), in blocks of
    two, so the last block is half empty; three rounds."""
    monkeypatch.setattr(fr, "BLOCK_COORDS", 256)
    ref, params = _toy(16)
    d = 3 * 16 * 16 + 16 + 1
    sk = fr.SketchSpec(d=d, c=128, r=5, seed=21)
    assert fr._blocks(sk) == (2, 4) and sk.m == 7
    hyper = {"k": 40, "rho": 0.9, "weight_decay": 5e-4, "num_workers": 6}
    batches, lrs = _batches(16, 3), [0.05, 0.04, 0.03]
    got = fr.follow(ref=ref, spec_model=None, params=params,
                    batches=batches, lrs=lrs, hyper=hyper, sk=sk,
                    precision=precision)
    want = _dense_fetchsgd(ref, params, batches, lrs, hyper, sk, precision)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=2e-6)
    np.testing.assert_allclose(got["table0"], want["table0"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.flatnonzero(got["delta"]),
                                  np.flatnonzero(want["delta"]))
    np.testing.assert_allclose(got["delta"], want["delta"],
                               rtol=1e-4, atol=1e-7)
    assert 40 <= np.count_nonzero(got["delta"]) <= 120


@pytest.mark.parametrize("d,c,block", [(801, 128, 256), (5000, 512, 1024),
                                       (300, 128, 1 << 24)])
def test_selection_in_blocks_is_top_k_over_all(d, c, block, monkeypatch):
    """Indices, values and order are ``jax.lax.top_k``'s over the whole
    vector of estimates, ties (a table of small integers) included."""
    monkeypatch.setattr(fr, "BLOCK_COORDS", block)
    sk = fr.SketchSpec(d=d, c=c, r=3, seed=7)
    v = jnp.asarray(np.random.RandomState(3).randint(
        -3, 4, (sk.r, sk.c)).astype(np.float32))
    k = 97
    idx, vals = jax.jit(lambda v: fr._select(sk, v, k))(v)
    est = fr.estimates(sk, v)
    _, want = jax.lax.top_k(jnp.abs(est), k)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_array_equal(vals, est[want])


def test_the_device_holds_d_twice(monkeypatch):
    """Fails if a d-sized device array comes back: the compiled client
    step takes the weights and the accumulator and gives the
    accumulator back in place, with temporaries under a quarter of d;
    and between client blocks, and through the server step, what is
    alive on the device is those two trees and small change."""
    ref, params = _toy(192)
    d = 3 * 192 * 192 + 192 + 1
    batches = _batches(192, 2, W=4, B=2)
    w = jax.tree_util.tree_map(jnp.asarray, params)
    g = jax.tree_util.tree_map(jnp.zeros_like, w)
    cb = next(fr._client_blocks(batches[0], 2))
    with jax.default_matmul_precision("highest"):
        mem = fr.client_step(ref, None, fr.quantizer(None)).lower(
            w, g, cb).compile().memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= 4 * d
    assert held <= 4 * d * 2.5, (held / (4 * d), mem)
    del w, g, cb

    seen = []
    blocks = fr._client_blocks
    add_block = fr._add_block

    def watch():
        seen.append(sum(x.size for x in jax.live_arrays()))

    def watched_blocks(batch, block):
        for cb in blocks(batch, block):
            watch()
            yield cb

    def watched_add(*args):
        watch()
        return add_block(*args)

    monkeypatch.setattr(fr, "_client_blocks", watched_blocks)
    monkeypatch.setattr(fr, "_add_block", watched_add)
    monkeypatch.setattr(fr, "BLOCK_COORDS", 4096)
    sk = fr.SketchSpec(d=d, c=1024, r=3, seed=21)
    before = sum(x.size for x in jax.live_arrays())
    fr.follow(ref=ref, spec_model=None, params=params, batches=batches,
              lrs=[0.1, 0.1], sk=sk, hyper={
                  "k": 50, "rho": 0.9, "weight_decay": 0.0,
                  "num_workers": 4})
    assert len(seen) >= 2 * (2 + fr._blocks(sk)[1])
    assert max(seen) - before <= 2.25 * d, (max(seen) - before) / d


def test_run_releases_the_program_before_the_comparison():
    res, out = _run(_argv("gpt2_fetchsgd_w8", 41), 1)
    assert res["correct"] is True
    lines = out.strip().splitlines()
    released = [json.loads(x.split(":", 1)[1]) for x in lines
                if x.startswith("released:")]
    assert len(released) == 1
    assert released[0]["before"]["arrays"] > 0
    assert released[0]["after"]["arrays"] == 0
    at = [i for i, x in enumerate(lines) if x.startswith("released:")][0]
    assert all(not x.startswith("correct:") for x in lines[:at])
    assert any(x.startswith("correct:") for x in lines[at:])
    assert json.loads(lines[-1]) == res

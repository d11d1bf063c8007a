#!/usr/bin/env python3
"""ROADMAP S4, by hand, on the chip: what does the vocabulary head cost
where few positions carry a label (PR 39's step 0, PERF.md section 6)?

    python3 scripts/head_probe.py [--clients 8] [--examples 16]
        [--tokens 255] [--width 768] [--vocab 50262] [--labelled 55]
        [--rows 1024] [--reps 5] [--iters 10] [--seed 7]

Two timings, forward + backward, bf16 operands as the cells run:

``chunk_body_ms``: one chunk of ``--rows`` rows alone: the table's cast
to bf16, the (rows, vocab) logits, ``logsumexp`` and the label's logit,
the recomputation under ``jax.checkpoint``, both backward products and
the float32 gradient of the table written once. The prediction for a
head that computes only labelled rows is this × ceil(n / rows) + the
partition, gather and write-back.

``head_ms``: ``lm_nll_sums_chunked`` as the fused round calls it: under
the ``vmap`` over ``--clients`` that shares the table and sums the
losses (``SHARED_CLIENTS``, where the tree has the name: the clients'
rows pool), ``--examples`` sequences of ``--tokens`` predicting
positions a client, of which ``--labelled`` a client carry a label (the
rest the ignore index -1), through ``value_and_grad`` of the summed
loss. ``head_unnamed_ms``: the same under a ``vmap`` that says nothing
(a client at a time). Copy the script into a ``git archive`` copy of
another commit to compare (``--rehearse``: tiny sizes on whatever
backend there is).
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--examples", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=255)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50262)
    ap.add_argument("--labelled", type=int, default=55,
                    help="labelled positions a client")
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    if a.rehearse:
        a.clients, a.examples, a.tokens, a.width = 2, 4, 15, 32
        a.vocab, a.labelled, a.rows, a.reps, a.iters = 257, 5, 32, 2, 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    from commefficient_tpu.models.gpt2 import (lm_nll_sums_chunked,
                                               token_nll)

    W, E, Tm, C, V = a.clients, a.examples, a.tokens, a.width, a.vocab
    rng = np.random.RandomState(a.seed)
    h = jnp.asarray(rng.randn(W, E, Tm, C) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(V, C) * 0.02, jnp.float32)
    lab = np.full((W, E * Tm), -1, np.int32)
    for c in range(W):
        at = rng.choice(E * Tm, a.labelled, replace=False)
        lab[c, at] = rng.randint(0, V, a.labelled)
    lab = jnp.asarray(lab.reshape(W, E, Tm))

    def timed(loss, args):
        """ms a forward + backward of ``loss(*args)``, the gradients
        fed back so that no iteration can be dropped."""
        g = jax.grad(loss, argnums=(0, 1))

        @jax.jit
        def step(x, t):
            def body(_, c):
                dx, dt = g(c[0], c[1], *args[2:])
                return (c[0] + 1e-12 * dx.astype(c[0].dtype),
                        c[1] + 1e-12 * dt)
            x, t = jax.lax.fori_loop(0, a.iters, body, (x, t))
            return jnp.sum(x[..., 0].astype(jnp.float32)) + jnp.sum(t[:, 0])

        assert np.isfinite(float(step(*args[:2])))
        ts = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            float(step(*args[:2]))
            ts.append((time.perf_counter() - t0) / a.iters * 1e3)
        return statistics.median(ts), ts

    @jax.checkpoint
    def chunk(hc, t, lc):
        logits = jnp.einsum("rc,vc->rv", hc, t.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        nll, valid = token_nll(logits, lc, -1)
        return jnp.sum(nll * valid)

    hc = h.reshape(-1, C)[:a.rows].astype(jnp.bfloat16)
    lc = jnp.asarray(rng.randint(0, V, hc.shape[0]), jnp.int32)
    body_ms, body_all = timed(chunk, (hc, w, lc))

    try:
        from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    except ImportError:     # a tree from before PR 39
        SHARED_CLIENTS = None

    def head(axis_name):
        def loss(x, t, lb):
            def one(xc, lc):
                sn, sv = lm_nll_sums_chunked(xc, t, lc, jnp.bfloat16,
                                             ignore_index=-1,
                                             tokens_per_chunk=a.rows)
                return jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)
            return jnp.sum(jax.vmap(one, axis_name=axis_name)(x, lb))
        return loss

    head_ms, head_all = timed(head(SHARED_CLIENTS), (h, w, lab))
    unnamed_ms, unnamed_all = timed(head(None), (h, w, lab))
    n = W * a.labelled
    print(json.dumps({
        "geometry": {"clients": W, "examples": E, "tokens": Tm,
                     "width": C, "vocab": V, "rows": a.rows,
                     "positions": W * E * Tm, "labelled": n},
        "chunk_body_ms": round(body_ms, 3),
        "chunk_body_all": [round(t, 3) for t in body_all],
        "head_ms": round(head_ms, 3),
        "head_all": [round(t, 3) for t in head_all],
        "head_unnamed_ms": round(unnamed_ms, 3),
        "head_unnamed_all": [round(t, 3) for t in unnamed_all],
        "chunks_if_compacted": -(-n // a.rows),
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()

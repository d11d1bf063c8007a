"""Isolation A/B of the vocab-head cross-entropy: chunked
(models/gpt2.py lm_nll_sums_chunked) vs fused Pallas
(ops/flce_pallas.py lm_nll_sums_fused), fwd+bwd, at a given
(clients, examples, tokens, width, vocab) geometry.

Times the op pair alone (hidden states precomputed, vmapped over the
client axis like the federated round) so end-to-end round effects
(sketch pipeline, transformer) don't blur the comparison.

Usage: python scripts/flce_bench.py [--clients 4] [--examples 4]
           [--tokens 255] [--width 768] [--vocab 50262]
           [--tokens_per_chunk 1024] [--reps 5] [--iters 20]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--examples", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=255)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50262)
    ap.add_argument("--tokens_per_chunk", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--iters", type=int, default=20,
                    help="op pairs per timed call (amortizes "
                    "dispatch)")
    ap.add_argument("--allow_fallback", action="store_true",
                    help="bench even when the fused path cannot "
                    "engage (the 'fused' column is then the chunked "
                    "fallback — reported, not asserted)")
    ap.add_argument("--ledger", type=str, default="",
                    help="append the result as a telemetry JSONL "
                    "bench record (stdout line unchanged)")
    args = ap.parse_args()

    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    from commefficient_tpu.ops.flce_pallas import (fused_fallback_reason,
                                                   lm_nll_sums_fused)

    W, E, Tm, C, V = (args.clients, args.examples, args.tokens,
                      args.width, args.vocab)

    # the fused timing below is meaningless if lm_nll_sums_fused is
    # silently taking the chunked fallback (it used to: any off-TPU
    # run "measured" the chunked path against itself) — refuse unless
    # told otherwise. batch_mult = W: the bench vmaps the client axis
    # exactly like the federated round.
    reason = fused_fallback_reason(E, Tm, C, V, jnp.bfloat16,
                                   batch_mult=W)
    if reason is not None and not args.allow_fallback:
        print(json.dumps({"error": "fused path would not engage: "
                          + reason,
                          "hint": "pass --allow_fallback to bench "
                          "the fallback anyway"}), file=sys.stderr)
        sys.exit(2)
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(W, E, Tm, C) * 0.02, jnp.float32)
    w = jnp.asarray(rng.randn(V, C) * 0.02, jnp.float32)
    lab = jnp.asarray(rng.randint(0, V, (W, E, Tm)), jnp.int32)

    def bench(fn, kw):
        def per_client(h, lab, w):
            sn, sv = fn(h, w, lab, jnp.bfloat16, **kw)
            return jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)

        def loss(h, w):
            return jnp.sum(jax.vmap(per_client, (0, 0, None))(
                h, lab, w))

        g = jax.grad(loss, argnums=(0, 1))

        @jax.jit
        def step(h, w):
            def body(_, carry):
                dh, dw = g(carry[0], carry[1])
                # feed grads back in so iterations can't be CSE'd
                return (carry[0] + 1e-12 * dh, carry[1] + 1e-12 * dw)
            h2, w2 = jax.lax.fori_loop(0, args.iters, body, (h, w))
            return jnp.sum(h2[..., 0]) + jnp.sum(w2[:, 0])

        s = step(h, w)
        assert np.isfinite(float(s))
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(step(h, w))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2] / args.iters * 1e3

    chunk_ms = bench(lm_nll_sums_chunked,
                     {"tokens_per_chunk": args.tokens_per_chunk})
    fused_ms = bench(lm_nll_sums_fused, {"batch_mult": W})
    out = {
        "geometry": {"clients": W, "examples": E, "tokens": Tm,
                     "width": C, "vocab": V,
                     "tokens_per_chunk": args.tokens_per_chunk},
        "chunked_ms_per_pair": round(chunk_ms, 3),
        "fused_ms_per_pair": round(fused_ms, 3),
        "speedup": round(chunk_ms / fused_ms, 3),
        "fused_path_engaged": reason is None,
        "fallback_reason": reason,
        "backend": jax.default_backend(),
    }
    print(json.dumps(out))
    if args.ledger:
        from commefficient_tpu.telemetry import append_bench_record
        append_bench_record(args.ledger, "flce_bench", out,
                            backend=jax.default_backend())


if __name__ == "__main__":
    main()

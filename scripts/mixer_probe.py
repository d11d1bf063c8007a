#!/usr/bin/env python3
"""ROADMAP S14 and S11, by hand, on the chip: what do the two forms of
the hybrid models' mixers (``models/mixers.py``) cost, forward and
backward under the clients ``vmap``, at a cell's shapes?

    python3 scripts/mixer_probe.py [--clients 4] [--seq 2048] [--reps 5]
        [--attn 32,8,64] [--ssd 64,1,64,128,256] [--rehearse]
        [--window 4096] [--rope_theta 1.5e6] [--kernel_blocks 256,512]
        [--sequences 1] [--mla 4,128,64,128]

Attention (``--attn`` query heads, key/value heads, head size): the
dense form (the float32 (heads, T, T) scores written out), the blocked
form at 128 / 256 / 512 queries a block (``gqa_attention``), and the
library's Pallas flash kernel that ``models/gpt2.py --attn_impl flash``
calls, with the key/value heads repeated to the query heads' count;
and ``attn.kernel``: ``gqa_attention`` with no block given, which on
the chip is the flash kernel the cells run (``attn_plan``; the row
says which form was built), once a ``--kernel_blocks`` tile size (the
probe's own knob: it sets ``mixers.ATTN_KERNEL_BLOCKS`` for the row;
default: the program's own choice, ``attn_kernel_block``).
The scan (``--ssd`` heads, groups, head size, state, chunk):
``ssd_chunked`` with all the heads at once and with 8 / 16 / 32 a
block. Each is jitted as ``jax.grad`` of a sum over a ``vmap`` over
clients of the ``jax.checkpoint``-ed form on bf16 operands, compiled
(its ``memory_analysis().temp_size_in_bytes`` printed: a form whose
temporaries pass the chip is reported and skipped), run once, then
timed over ``--reps`` calls. Every form's gradient is compared with
the first's that ran. PR 34's step 1 (PERF.md section 6).

With ``--mla`` (heads, the q and k head's part without positions, its
rotated part, v's head size: q and k are the two parts wide, v its own
size) one latent-attention layer is timed alone, ``--sequences`` a
client, and nothing else: ``models/joyai.py mla_attention``, what the
``mla_attn`` scope holds, in its dense form (two score products on a
float32 (S, H, T, T) tensor) and through the flash kernel once a
``--kernel_blocks`` tile (``mla.kernel_*``; off the chip the row says
that no kernel was built and times the dense form again). PR 49's
probe: ``--clients 8 --sequences 4 --seq 1024 --mla 4,128,64,128
--kernel_blocks 1024,512,256``.

With ``--window`` one attention layer is timed alone (no scan): the
whole-row forms above, which are what a window layer costs when it is
built as a full layer with a mask, then the band form at 128 / 256 /
512 queries a block, each block against the slice of keys its band
reaches (``gqa_attention(window=...)``; their gradients are compared
among themselves: the mask is another), then ``attn.kernel_band``, the
same layer with no block given. ``--rope_theta`` rotates q and
k inside every timed form, as a layer with positions does. PR 41's
probe: ``--clients 2 --seq 8192 --attn 28,4,128 --window 4096
--rope_theta 1.5e6``.
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
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--attn", default="32,8,64")
    ap.add_argument("--ssd", default="64,1,64,128,256")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--window", type=int, default=None,
                    help="also the band form of a window layer; no scan")
    ap.add_argument("--rope_theta", type=float, default=None,
                    help="rotate q and k by their positions first")
    ap.add_argument("--kernel_blocks", default=None,
                    help="tile sizes of the attn.kernel / mla.kernel rows")
    ap.add_argument("--sequences", type=int, default=1,
                    help="sequences a client (the --mla rows)")
    ap.add_argument("--mla", default=None,
                    help="heads, nope, rope, v: a latent-attention "
                         "layer alone, and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from commefficient_tpu.models import mixers
    from commefficient_tpu.models.mixers import (attn_plan, gqa_attention,
                                                 ssd_chunked)

    W, T = a.clients, a.seq
    Hq, Hkv, D = map(int, a.attn.split(","))
    H, G, P, N, Q = map(int, a.ssd.split(","))
    window = a.window
    if a.rehearse:
        T, Hq, Hkv, D, H, P, N, Q = 256, 4, 2, 8, 8, 8, 8, 16
        window = window and 96
    dt = jnp.bfloat16
    tpu = jax.devices()[0].platform == "tpu"
    limit = 15.5e9 if tpu else float("inf")

    def measure(name, fn, args, first):
        lowered = jax.jit(jax.grad(fn, argnums=tuple(range(len(args)))))
        try:
            exe = lowered.lower(*args).compile()
        except Exception as e:  # noqa: BLE001  (a form the chip refuses)
            print(json.dumps({"form": name, "failed": str(e)[:300]}))
            return first
        temp = exe.memory_analysis().temp_size_in_bytes
        row = {"form": name, "temp_GB": temp / 1e9}
        if temp > limit:
            print(json.dumps(dict(row, skipped="temporaries pass the chip")))
            return first
        try:
            got = jax.block_until_ready(exe(*args))
        except Exception as e:  # noqa: BLE001
            print(json.dumps(dict(row, failed=str(e)[:300])))
            return first
        times = []
        for _ in range(a.reps):
            t = time.perf_counter()
            jax.block_until_ready(exe(*args))
            times.append((time.perf_counter() - t) * 1e3)
        if first is None:
            first = got
        else:
            row["grad_rel_to_first"] = max(
                float(jnp.linalg.norm((x - y).astype(jnp.float32))
                      / jnp.linalg.norm(y.astype(jnp.float32)))
                for x, y in zip(got, first))
        print(json.dumps(dict(row, ms=statistics.median(times),
                              ms_all=[round(t, 2) for t in times])),
              flush=True)
        return first

    def each_tile():
        """Once a ``--kernel_blocks`` tile, set as the program's only
        one; once with the program's own choice where none is given."""
        kept = mixers.ATTN_KERNEL_BLOCKS
        try:
            for b in a.kernel_blocks.split(",") if a.kernel_blocks \
                    else [None]:
                if b is not None:
                    mixers.ATTN_KERNEL_BLOCKS = (int(b),)
                yield
        finally:
            mixers.ATTN_KERNEL_BLOCKS = kept

    # --- latent attention --------------------------------------------------
    if a.mla:
        from commefficient_tpu.models import joyai
        Hm, dn, dr, dv = map(int, a.mla.split(","))
        if a.rehearse:
            Hm, dn, dr, dv = 2, 8, 4, 8
        S = a.sequences
        cfg = joyai.JoyAIConfig(num_attention_heads=Hm, qk_nope_head_dim=dn,
                                qk_rope_head_dim=dr, v_head_dim=dv, dtype=dt)
        k = jax.random.split(jax.random.PRNGKey(2), 3)
        qh = jax.random.normal(k[0], (W, S, T, Hm, dn + dr), dt)
        kvh = jax.random.normal(k[1], (W, S, T, Hm, dn + dv), dt)
        kr = jax.random.normal(k[2], (W, S, T, dr), dt)
        print(json.dumps({"mla": {"clients": W, "sequences": S, "T": T,
                                  "H": Hm, "qk": dn + dr, "v": dv}}))

        def mla_loss(kernel):
            one = jax.checkpoint(lambda qh, kvh, kr: joyai.mla_attention(
                cfg, qh, kvh, kr, kernel))
            return lambda qh, kvh, kr: jnp.sum(jnp.sin(
                jax.vmap(one)(qh, kvh, kr).astype(jnp.float32)))

        first = measure("mla.dense", mla_loss(None), (qh, kvh, kr), None)
        for _ in each_tile():
            plan = joyai.mla_plan(cfg, S, T)
            print(json.dumps({"mla.kernel": {
                "kernel": plan.kernel, "block": plan.block,
                "pairs_over_needed": plan.pairs / plan.needed}}))
            measure(f"mla.kernel_{plan.block}", mla_loss(plan.kernel),
                    (qh, kvh, kr), first)
        return 0

    # --- attention ---------------------------------------------------------
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (W, 1, T, Hkv, Hq // Hkv, D), dt)
    kk = jax.random.normal(k[1], (W, 1, T, Hkv, D), dt)
    v = jax.random.normal(k[2], (W, 1, T, Hkv, D), dt)
    scale = 1.0 / D
    print(json.dumps({"attention": {"clients": W, "T": T, "Hq": Hq,
                                    "Hkv": Hkv, "D": D, "scale": scale}}))

    def turned(x):
        if a.rope_theta is None:
            return x
        from commefficient_tpu.models.mixers import rope
        return rope(x.astype(jnp.float32), a.rope_theta).astype(dt)

    def attn_loss(block, window=None):
        one = jax.checkpoint(lambda q, k, v: gqa_attention(
            turned(q), turned(k), v, scale, query_block=block,
            window=window)[0])
        return lambda q, k, v: jnp.sum(jnp.sin(
            jax.vmap(one)(q, k, v).astype(jnp.float32)))

    def flash_loss(q, k, v):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention)
        b = next(x for x in (512, 256, 128) if T % x == 0)
        blocks = BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=1,
            block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
            block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)

        @jax.checkpoint
        def one(q, k, v):               # a client: (1, T, Hkv, g, D)
            g = q.shape[3]
            qh = q.reshape(1, T, Hkv * g, D).transpose(0, 2, 1, 3)
            kh = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3)
            vh = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)
            out = flash_attention(qh, kh, vh, causal=True, sm_scale=scale,
                                  block_sizes=blocks)
            return out.transpose(0, 2, 1, 3).reshape(q.shape)
        return jnp.sum(jnp.sin(jax.vmap(one)(q, k, v).astype(jnp.float32)))

    def kernel_rows(name, window, first):
        """``gqa_attention`` as the cells call it: no block given."""
        for _ in each_tile():
            plan = attn_plan(1, T, Hq, window, None, D)
            print(json.dumps({name: {
                "kernel": plan.kernel, "block": plan.block,
                "banded": plan.banded,
                "pairs_over_needed": plan.pairs / plan.needed}}))
            measure(f"attn.{name}_{plan.block}", attn_loss(None, window),
                    (q, kk, v), first)

    first = None
    for name, block in [("dense", T), ("blocked_128", 128),
                        ("blocked_256", 256), ("blocked_512", 512)]:
        if block <= T:
            first = measure("attn." + name, attn_loss(block), (q, kk, v),
                            first)
    if tpu and a.rope_theta is None:
        measure("attn.flash_pallas_kv_repeated", flash_loss, (q, kk, v),
                first)
    kernel_rows("kernel", None, first)
    if window is not None:
        first = None
        for block in (128, 256, 512):
            if block <= T:
                plan = attn_plan(1, T, Hq, window, block)
                print(json.dumps({"band": {"window": window, "block": block,
                                           "keys": plan.keys,
                                           "pairs_over_needed":
                                           plan.pairs / plan.needed}}))
                first = measure(f"attn.band_{block}",
                                attn_loss(block, window), (q, kk, v), first)
        kernel_rows("kernel_band", window, first)
        return 0

    # --- the scan -----------------------------------------------------------
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (W, 1, T, H, P), dt)
    delta = jax.nn.softplus(jax.random.normal(k[1], (W, 1, T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (W, 1, T, G, N), dt)
    C = jax.random.normal(k[4], (W, 1, T, G, N), dt)
    print(json.dumps({"scan": {"clients": W, "T": T, "H": H, "G": G, "P": P,
                               "N": N, "chunk": Q}}))

    def scan_loss(hb):
        one = jax.checkpoint(lambda x, d, b, c: ssd_chunked(
            x, d, A, b, c, Q, dt, head_block=hb)[0])
        return lambda x, d, b, c: jnp.sum(jnp.sin(jax.vmap(one)(x, d, b, c)))

    first = None
    for hb in (H // G, 32, 16, 8):
        if hb <= H // G:
            name = "all_heads" if hb == H // G else f"blocks_of_{hb}"
            first = measure("scan." + name, scan_loss(hb),
                            (x, delta, B, C), first)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""ROADMAP S6 and S8, by hand, on the chip: the two sketch kernels
alone and ``CountSketch``'s three entry points, at the cells' d.

    python scripts/sketch_bench.py --kernels 124444417,376091904,700865520,772160448
    python scripts/sketch_bench.py --kernels 327603 --c 8192 --backend pallas_interpret --reps 1

At each d given (the benchmark cells' are in PERF.md section 4; ``--c``
x ``--r`` sketch, ``rot_lanes`` 1024, packed signs) it times the two
Pallas kernels on operands made beforehand, then ``sketch``,
``sketch_from_leaves`` (the d-sized concatenate S6 is about) and
``estimates`` as the round programs call them, and prints each
compiled program's temporaries and a checksum of the table and of the
estimates: the same script run from a ``git archive`` copy of another
commit tells whether they moved (PR 37's step 0, PERF.md section 6).
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


def _force(out):
    """Force completion with a VALUE transfer: materialising bytes on
    the host cannot return before execution finished."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x.ravel()[:1] if hasattr(x, "ravel")
                             else x), out)


def timed(fn, *args, reps=20):
    out = fn(*args)
    _force(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    _force(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def gpt2_like_shapes(d):
    """A leaf-shape list shaped like GPT-2 124M (embeddings + 12 x
    (attn + mlp + ln) + final ln), scaled so totals sum to d."""
    shapes = [(50257, 768), (1024, 768)]
    for _ in range(12):
        shapes += [(768,), (768,), (768, 2304), (2304,), (768, 768),
                   (768,), (768,), (768,), (768, 3072), (3072,),
                   (3072, 768), (768,)]
    shapes += [(768,), (768,)]
    total = sum(int(np.prod(s)) for s in shapes)
    if total > d:
        # small-d smoke: keep the leaf-count/size mix (one embedding-
        # like big leaf + interleaved matrices and vectors), scaled;
        # leaves whose scaled leading dim rounds to zero are DROPPED —
        # flooring them to one full row overshoots d at small scales
        scale = d / total
        shapes = [(int(s[0] * scale),) + tuple(s[1:]) for s in shapes]
        shapes = [s for s in shapes if s[0] > 0]
        total = sum(int(np.prod(s)) for s in shapes)
        assert total <= d, (total, d)
    if total < d:
        shapes.append((d - total,))
    return shapes


def _leaves(v, d):
    """``v`` cut into the leaves of ``gpt2_like_shapes(d)``."""
    leaves, off = [], 0
    for shape in gpt2_like_shapes(d):
        n = int(np.prod(shape))
        leaves.append(jax.lax.dynamic_slice(v, (off,), (n,))
                      .reshape(shape))
        off += n
    assert off == d, (off, d)
    return leaves


def _checksum(x):
    """Two wrapping uint32 sums over the bits of ``x``: equal on two
    commits only if (short of a collision) every element is."""
    bits = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
    pos = jnp.arange(bits.size, dtype=jnp.uint32) | jnp.uint32(1)
    return [int(jnp.sum(bits, dtype=jnp.uint32)),
            int(jnp.sum(bits * pos, dtype=jnp.uint32))]


def run_kernels(args):
    from commefficient_tpu.ops import sketch_pallas as sp
    from commefficient_tpu.ops.sketch import CountSketch

    c, r, lanes = args.c, args.r, 1024
    for d in map(int, args.kernels.split(",")):
        cs = CountSketch(d=d, c=c, r=r, seed=21, backend=args.backend,
                         rot_lanes=lanes)
        interpret = cs._resolve_backend() == "pallas_interpret"
        m, pd = cs._m, cs._padded_d
        _, sign_seed = cs._seeds()
        res = {"d": d, "padded_d": pd, "r_m": r * m,
               "form": sp.rotation_form(c, r, lanes)}
        v = jax.jit(lambda: jax.random.normal(
            jax.random.PRNGKey(3), (d,), jnp.float32))()
        vp = jnp.pad(v, (0, pd - d))
        sgn = jax.jit(cs._packed_signs_traced)()
        rot = jnp.asarray(cs._rotations())
        ms, table = timed(
            lambda a, b: sp.sketch_pallas(a, rot, c, r, int(sign_seed),
                                          interpret, None, True, lanes, b),
            vp, sgn, reps=args.reps)
        res["sketch_kernel_ms"] = round(ms, 3)
        res["table_checksum"] = _checksum(table)
        del vp
        # a table of its own: the estimates' checksum then tells the
        # estimates kernel's bits apart from the sketch kernel's
        tab0 = jax.random.normal(jax.random.PRNGKey(4), (r, c),
                                 jnp.float32)
        ms, est = timed(
            lambda a, b: sp.estimates_pallas(
                a, rot, c, r, int(sign_seed), interpret, None, True, d,
                lanes, b),
            tab0, sgn, reps=args.reps)
        res["estimates_kernel_ms"] = round(ms, 3)
        res["estimates_checksum"] = _checksum(est)
        del est, sgn, tab0

        def through(name, fn, *a):
            exe = jax.jit(fn).lower(*a).compile()
            res[name + "_temp_GB"] = round(
                exe.memory_analysis().temp_size_in_bytes / 1e9, 3)
            ms, o = timed(exe, *a, reps=args.reps)
            res[name + "_ms"] = round(ms, 3)
            return o

        through("sketch", cs.sketch, v)
        through("estimates", lambda t: cs.estimates(t, padded=True),
                table)
        leaves = _leaves(v, d)
        del v
        t2 = through("sketch_from_leaves",
                     lambda ls: cs.sketch_from_leaves(ls), leaves)
        res["leaves_table_equal"] = bool(jnp.array_equal(table, t2))
        del leaves, table, t2
        print(json.dumps(res), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernels", required=True, metavar="D[,D...]")
    ap.add_argument("--c", type=int, default=524288)
    ap.add_argument("--r", type=int, default=5)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--backend", default="auto")
    run_kernels(ap.parse_args())


if __name__ == "__main__":
    main()

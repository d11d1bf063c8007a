"""Sketch-pipeline micro-bench: per-stage times at a given geometry.

Isolates the d-bound pieces of the federated sketch round (client
sketch, recovery estimates, selection, sparse resketch) so kernel work
can be attributed without a full-model xplane (VERDICT round-3 task #3
— the ~25 ms sketch constant at GPT-2 scale). ``--tree`` times
``sketch_from_leaves`` over a GPT-2-shaped leaf list against the flat
``sketch`` + its pad.

``--sketch_dtype {f32,bf16,int8,fp8}`` adds the wire-quantization
stages (quantize_table / dequantize / the fused sketch+quantize op)
and reports the uplink wire bytes next to the f32 reference, so one
invocation shows what a dtype buys in both time and bytes. With
``--ledger`` the result also lands as a bench record and a run
manifest under ``runs/`` (perf-gateable, wire-dtype keyed).

``--kernels D[,D...]`` is PR 37's step 0 (PERF.md section 6): at each
d given (the benchmark cells' are in PERF.md section 4; ``--c`` x
``--r`` sketch, ``rot_lanes`` 1024, packed signs) it times the two Pallas kernels alone on operands made
beforehand, then ``sketch``, ``sketch_from_leaves`` and ``estimates``
as the round programs call them, prints each compiled program's
temporaries and a checksum of the table and of the estimates (the same
script run from a copy of another commit tells whether they moved).

Usage:
  python scripts/sketch_bench.py [--d 124439808] [--c 524288] [--r 5]
      [--k 50000] [--reps 20] [--tree] [--sketch_dtype int8]
  python scripts/sketch_bench.py --kernels 124444417,772160448
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
    """Step 0 of PR 37: see the module docstring."""
    from commefficient_tpu.ops import sketch_pallas as sp
    from commefficient_tpu.ops.sketch import CountSketch

    c, r, lanes = args.c, args.r, 1024
    for d in map(int, args.kernels.split(",")):
        cs = CountSketch(d=d, c=c, r=r, seed=21, backend=args.backend,
                         rot_lanes=lanes)
        interpret = cs._resolve_backend() == "pallas_interpret"
        m, pd = cs._m, cs._padded_d
        _, sign_seed = cs._seeds()
        res = {"d": d, "padded_d": pd, "r_m": r * m}
        if hasattr(sp, "rotation_form"):  # not in a copy before PR 37
            res["form"] = sp.rotation_form(c, r, lanes)
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
    ap.add_argument("--kernels", default="", metavar="D[,D...]",
                    help="time the two sketch kernels alone and the "
                    "three entry points at each of these d")
    ap.add_argument("--d", type=int, default=124_439_808)
    ap.add_argument("--c", type=int, default=524288)
    ap.add_argument("--r", type=int, default=5)
    ap.add_argument("--k", type=int, default=50000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--rot_lanes", type=int, default=0)
    ap.add_argument("--tree", action="store_true")
    ap.add_argument("--chain", type=int, default=0,
                    help="also time N chained sketch->estimates "
                    "iterations inside ONE dispatch (fori_loop): "
                    "kernel time with no per-dispatch latency in it")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform")
    ap.add_argument("--sketch_dtype", default="f32",
                    choices=["f32", "bf16", "int8", "fp8"],
                    help="also time the wire-quantization stages at "
                    "this dtype and report uplink wire bytes")
    ap.add_argument("--ledger", type=str, default="",
                    help="append the result as a telemetry JSONL "
                    "bench record and register a run manifest "
                    "(stdout line unchanged)")
    ap.add_argument("--autopilot", action="store_true",
                    help="also run the federated autopilot acceptance "
                    "leg: an 8-round CPU sketch loop launched at f32 "
                    "where the controller must converge to a >=2x "
                    "cheaper wire dtype with recovery error in band "
                    "every round (run under XLA_FLAGS=--xla_force_"
                    "host_platform_device_count=8 JAX_PLATFORMS=cpu)")
    ap.add_argument("--autopilot_band", default="0.05:0.6",
                    help="LO:HI recovery-error band for the "
                    "--autopilot leg (also keys its baseline pin)")
    ap.add_argument("--autopilot_rounds", type=int, default=8)
    ap.add_argument("--dp", action="store_true",
                    help="also run the DP acceptance leg: a federated "
                    "sketch loop with the full --dp sketch mechanism "
                    "armed (per-client clip + table noise at "
                    "sigma > 0) whose recovery error must hold the "
                    "--dp_band every probed round while the "
                    "accountant's eps grows monotonically")
    ap.add_argument("--dp_noise_mult", type=float, default=0.02,
                    help="noise multiplier for the --dp leg "
                    "(sigma > 0 is the point of the check)")
    ap.add_argument("--dp_band", default="0:0.9",
                    help="LO:HI recovery-error band for the --dp leg")
    ap.add_argument("--dp_rounds", type=int, default=8)
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.kernels:
        run_kernels(args)
        return

    from commefficient_tpu.ops.sketch import CountSketch
    from commefficient_tpu.ops.topk import threshold_topk_indices

    cs = CountSketch(d=args.d, c=args.c, r=args.r, seed=21,
                     backend=args.backend, rot_lanes=args.rot_lanes)
    rng = np.random.RandomState(0)
    v = jnp.asarray(rng.randn(args.d).astype(np.float32))
    res = {"geometry": {"d": args.d, "c": args.c, "r": args.r,
                        "k": args.k,
                        "backend": cs._resolve_backend()}}
    if jax.default_backend() == "tpu":
        # per-op timings below include one dispatch and a forced value
        # transfer each (see _force); only the single-dispatch chained
        # number is a kernel measurement
        res["note"] = ("per-op *_ms include dispatch + D2H — trust "
                       "chain_* for kernel time")

    ms, table = timed(jax.jit(cs.sketch), v, reps=args.reps)
    res["sketch_flat_ms"] = round(ms, 2)

    if args.tree:
        leaves = _leaves(v, args.d)

        fn = jax.jit(lambda ls: cs.sketch_from_leaves(ls))
        ms, table_t = timed(fn, leaves, reps=args.reps)
        res["sketch_from_leaves_ms"] = round(ms, 2)
        res["tables_equal"] = bool(jnp.array_equal(table, table_t))

    ms, est = timed(jax.jit(lambda t: cs.estimates(t, padded=True)),
                    table, reps=args.reps)
    res["estimates_padded_ms"] = round(ms, 2)
    ms, _ = timed(jax.jit(lambda t: cs.estimates(t)), table,
                  reps=args.reps)
    res["estimates_sliced_ms"] = round(ms, 2)

    ms, idx = timed(
        jax.jit(lambda e: threshold_topk_indices(
            e, args.k, key=jax.lax.square)),  # as CountSketch._select
        est, reps=args.reps)
    res["threshold_select_ms"] = round(ms, 2)

    vals = est[idx]
    ms, _ = timed(jax.jit(cs.sketch_sparse), idx, vals,
                  reps=args.reps)
    res["sparse_resketch_ms"] = round(ms, 2)

    ms, _ = timed(jax.jit(lambda t, k=args.k: cs.unsketch(
        t, k, with_support=True, with_dense=False)), table,
        reps=args.reps)
    res["unsketch_sparse_total_ms"] = round(ms, 2)

    from commefficient_tpu import accounting
    wire = args.sketch_dtype
    res["wire"] = {
        "sketch_dtype": wire,
        "upload_wire_bytes": accounting.sketch_wire_bytes(
            args.r, args.c, wire),
        "upload_f32_bytes": accounting.sketch_wire_bytes(
            args.r, args.c, "f32"),
    }
    if wire != "f32":
        from commefficient_tpu.ops import quant
        ms, qs = timed(
            jax.jit(lambda t: quant.quantize_table(t, wire)),
            table, reps=args.reps)
        res["quantize_table_ms"] = round(ms, 2)
        q, scale = qs
        ms, _ = timed(
            jax.jit(lambda qq: quant.dequantize(qq, scale)), q,
            reps=args.reps)
        res["dequantize_ms"] = round(ms, 2)
        ms, _ = timed(
            jax.jit(lambda vv: cs.sketch_quantized(vv, wire)), v,
            reps=args.reps)
        res["sketch_quantized_fused_ms"] = round(ms, 2)

    if args.chain:
        n = args.chain

        @jax.jit
        def chained(v0):
            def body(i, carry):
                v, acc = carry
                t = cs.sketch(v)
                e = cs.estimates(t, padded=True)
                # feed the estimates back so no iteration is dead code
                return e[: args.d] * 0.999, acc + t[0, 0]
            v_out, acc = jax.lax.fori_loop(
                0, n, body, (v0, jnp.float32(0)))
            return acc + jnp.sum(v_out[:8])

        float(chained(v))  # value transfer = real warmup (see _force)
        t0 = time.perf_counter()
        out = chained(v)
        float(out)
        res["chain_sketch_plus_estimates_ms"] = round(
            (time.perf_counter() - t0) / n * 1e3, 2)

    ap_rec = ap_cfg = dp_cfg = None
    if args.autopilot:
        ap_res, ap_rec, ap_cfg = run_autopilot_leg(args)
        res["autopilot"] = ap_res
    if args.dp:
        dp_res, dp_cfg = run_dp_leg(args)
        res["dp"] = dp_res

    print(json.dumps(res))
    if args.ledger:
        from commefficient_tpu.telemetry import (append_bench_record,
                                                 registry)
        append_bench_record(args.ledger, "sketch_bench", res,
                            backend=jax.default_backend())
        if ap_cfg is not None:
            # manifest carries the FED config (autopilot + band) so
            # registry.run_band / run_wire_dtype key the pin from the
            # CONVERGED point, e.g. d8p1qint8b0.05-0.6
            registry.maybe_write_manifest(
                ap_cfg, bench={"sketch_bench": res},
                extra={"autopilot": ap_rec, "wire_dtype": wire})
        elif dp_cfg is not None:
            # DP leg: the manifest config carries dp/dp_epsilon so
            # the perf gate keys this pin under its privacy budget
            # (p<eps> fragment) — never comparable to a dp-off run
            registry.maybe_write_manifest(
                dp_cfg, bench={"sketch_bench": res},
                extra={"wire_dtype": wire})
        else:
            registry.maybe_write_manifest(
                args, bench={"sketch_bench": res},
                extra={"wire_dtype": wire})


def run_autopilot_leg(args):
    """The acceptance loop behind ``--autopilot``: a small federated
    sketch run (heavy-tailed synthetic gradients, probes every round)
    launched at f32 whose controller must walk to a cheaper wire while
    holding the recovery-error band. Returns ``(summary, record,
    cfg)`` — the record replays bit-exact via
    ``commefficient_tpu.autopilot.replay_record`` and rides the run
    manifest, and cfg (ledger attached) is what the manifest is keyed
    by."""
    from commefficient_tpu.autopilot import parse_band, replay_record
    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    W, B, d, num_clients = 4, 2, 512, 16
    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 num_workers=W, local_batch_size=B, seed=5,
                 num_clients=num_clients, k=64, num_rows=5,
                 num_cols=2048, sketch_dtype="f32", probe_every=1,
                 autopilot="on", autopilot_band=args.autopilot_band,
                 autopilot_cooldown=1, ledger=args.ledger)
    model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                     loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    # power-law feature scaling -> heavy-tailed gradients, so top-k
    # recovery sits far below the dense-iid floor and the band has
    # room to hold across the dtype walk (same recipe as the tests)
    scale = (np.arange(1, d + 1) ** -1.5).astype(np.float32)
    rng = np.random.RandomState(5)
    t0 = time.perf_counter()
    for _ in range(args.autopilot_rounds):
        batch = {
            "client_ids": rng.choice(num_clients, W, replace=False)
            .astype(np.int32),
            "x": jnp.asarray(rng.randn(W, B, d).astype(np.float32)
                             * scale),
            "y": jnp.asarray(rng.randn(W, B), jnp.float32),
            "mask": jnp.ones((W, B), jnp.float32)}
        model(batch)
        opt.step()
    wall = time.perf_counter() - t0

    rec = model.autopilot_record()
    lo, hi = parse_band(args.autopilot_band)
    observed = [t for t in rec["trajectory"]
                if t["recovery_error"] is not None]
    counters = model._variants.counters()
    visited = {t["key"] for t in rec["trajectory"]}
    visited.add(rec["initial"])
    summary = {
        "rounds": args.autopilot_rounds,
        "band": args.autopilot_band,
        "initial": rec["initial"],
        "final": rec["final"],
        "initial_wire_bytes": rec["initial_wire_bytes"],
        "final_wire_bytes": rec["final_wire_bytes"],
        "uplink_reduction": round(
            rec["initial_wire_bytes"] / rec["final_wire_bytes"], 2),
        "band_held": bool(observed) and all(
            t["recovery_error"] <= hi for t in observed),
        "panics": sum(t["action"] == "panic"
                      for t in rec["trajectory"]),
        "variant_compiles": counters["misses"],
        "lattice_points_visited": len(visited),
        "compiles_within_visited": counters["misses"] <= len(visited),
        "replay_exact": replay_record(rec)
        == [t["key"] for t in rec["trajectory"]],
        "wall_s": round(wall, 2),
    }
    model.finalize()
    return summary, rec, cfg


def run_dp_leg(args):
    """The acceptance loop behind ``--dp``: the same small federated
    sketch run with the full ``--dp sketch`` mechanism armed —
    per-client L2 clip plus calibrated table noise at sigma > 0.
    Acceptance: every probed round's recovery error holds the
    ``--dp_band`` despite the noise, and the accountant's ε trail in
    the ledger is strictly increasing. Returns ``(summary, cfg)``;
    the summary's (sigma, recovery-error) pair is the BENCHMARKS
    noise-vs-recovery row, and cfg keys the run manifest under its
    privacy budget."""
    import tempfile

    from commefficient_tpu.autopilot import parse_band
    from commefficient_tpu.config import Config
    from commefficient_tpu.privacy import table_noise_std
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    W, B, d, num_clients = 4, 2, 512, 16
    led = args.ledger
    tmpdir = None
    if not led:
        tmpdir = tempfile.mkdtemp(prefix="sketch_bench_dp_")
        led = os.path.join(tmpdir, "dp_ledger.jsonl")
    assert args.dp_noise_mult > 0, "--dp leg needs sigma > 0"
    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 num_workers=W, local_batch_size=B, seed=5,
                 num_clients=num_clients, k=64, num_rows=5,
                 num_cols=2048, probe_every=1, dp="sketch",
                 dp_clip=1.0, dp_noise_mult=args.dp_noise_mult,
                 dp_delta=1e-5, ledger=led)
    model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                     loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    # shared-w_true regression (not iid noise targets): client
    # gradients ALIGN, so the aggregate keeps the per-client scale
    # and the noise-vs-signal ratio is set by the mechanism, not by
    # cross-client cancellation
    scale = (np.arange(1, d + 1) ** -1.5).astype(np.float32)
    rng = np.random.RandomState(5)
    w_true = rng.randn(d).astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(args.dp_rounds):
        x = rng.randn(W, B, d).astype(np.float32) * scale
        batch = {
            "client_ids": rng.choice(num_clients, W, replace=False)
            .astype(np.int32),
            "x": jnp.asarray(x),
            "y": jnp.asarray(x.reshape(-1, d) @ w_true)
            .reshape(W, B),
            "mask": jnp.ones((W, B), jnp.float32)}
        model(batch)
        opt.step()
    wall = time.perf_counter() - t0
    model.finalize()

    # acceptance reads the LEDGER, not the model: the ε trail and
    # the probes must have survived all the way to the v5 records
    eps_traj, errs = [], []
    with open(led) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") != "round":
                continue
            if isinstance(rec.get("dp_epsilon"), (int, float)):
                eps_traj.append(float(rec["dp_epsilon"]))
            rerr = (rec.get("probes") or {}).get("recovery_error")
            if isinstance(rerr, (int, float)):
                errs.append(float(rerr))
    lo, hi = parse_band(args.dp_band)
    summary = {
        "rounds": args.dp_rounds,
        "band": args.dp_band,
        "dp_noise_mult": args.dp_noise_mult,
        "table_noise_std": round(table_noise_std(cfg), 6),
        "eps_spent": eps_traj[-1] if eps_traj else None,
        "eps_monotone": all(b > a for a, b in
                            zip(eps_traj, eps_traj[1:])),
        "charged_rounds": len(eps_traj),
        "recovery_err_mean": (round(sum(errs) / len(errs), 4)
                              if errs else None),
        "recovery_err_max": (round(max(errs), 4) if errs else None),
        "band_held": bool(errs) and all(e <= hi for e in errs),
        "wall_s": round(wall, 2),
    }
    return summary, cfg


if __name__ == "__main__":
    main()

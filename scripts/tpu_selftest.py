"""Hardware self-test: exercises the TPU-only code paths the pytest
suite cannot (it runs on a virtual CPU mesh with Pallas in interpret
mode). Run on a machine with a TPU attached:

    python scripts/tpu_selftest.py [check ...]

(every check, or the named ones.) Prints one PASS/FAIL line per check
and exits nonzero on any failure. Every check asserts values, none a
time. This process holds the chip, so no check may start a child that
needs it. Kernel parity at flagship geometry and the trainer end to
end are ``chip_smoke.py``'s; speeds are ``benchmark/run.py``'s.
"""

import os
import sys
import time

# runnable as `python scripts/tpu_selftest.py` without installing
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

FAILED = []


def check(name, fn):
    try:
        t0 = time.perf_counter()
        detail = fn()
        dt = time.perf_counter() - t0
        print(f"PASS  {name}  ({dt:.1f}s{'; ' + detail if detail else ''})")
    except Exception as e:  # noqa: BLE001 — report and continue
        FAILED.append(name)
        print(f"FAIL  {name}: {type(e).__name__}: {e}")


def bf16_round_trains():
    """Full-size bf16 ResNet9 sketch round executes and is finite."""
    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState
    from commefficient_tpu.models import get_model
    from commefficient_tpu.ops.vec import flatten_params
    from commefficient_tpu.train.cv_train import make_compute_loss

    W, B = 8, 8
    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 weight_decay=5e-4, num_workers=W, local_batch_size=B,
                 k=50000, num_rows=5, num_cols=524288,
                 dataset_name="CIFAR10", seed=21, approx_topk=True)
    module = get_model("ResNet9")(num_classes=10, dtype=jnp.bfloat16)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 32, 32, 3)))["params"]
    flat, unravel = flatten_params(params)
    cfg.grad_size = int(flat.size)
    loss = make_compute_loss(module)
    cr = jax.jit(build_client_round(
        cfg, lambda p, b: loss(unravel(p), b, cfg), B))
    sr = jax.jit(build_server_round(cfg))
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.randn(W, B, 32, 32, 3)
                              .astype(np.float32)),
             "y": jnp.asarray(rng.randint(0, 10, (W, B))
                              .astype(np.int32)),
             "mask": jnp.ones((W, B), jnp.float32)}
    res = cr(flat, ClientStates.init(cfg, 100, flat), batch,
             jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
             1.0)
    ps2, _, _, upd, sup = sr(flat, ServerState.init(cfg),
                             res.aggregated, jnp.float32(0.1))
    assert bool(jnp.isfinite(ps2).all())
    nnz = int((np.asarray(upd) != 0).sum()) if upd is not None \
        else int((np.asarray(sup[1]) != 0).sum())
    assert 0 < nnz <= cfg.k
    return f"update nnz {nnz}"


def probe_smoke():
    """--probe_full program variant on a sketch round: the in-compile
    diagnostics come back clean and the TRUE recovery error against
    the dense gradient is finite and < 1 (heavy-hitter gradient, so
    top-k recovery must capture most of the mass)."""
    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState

    W, B, d = 8, 4, 1 << 18
    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 num_workers=W, local_batch_size=B,
                 k=5000, num_rows=5, num_cols=65536, seed=21)
    cfg.grad_size = d

    def lin_loss(p, b):
        # grad == the client's c vector exactly (masked batch mean of
        # identical rows) — a known ground truth for the probes
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    cr = jax.jit(build_client_round(cfg, lin_loss, B, probes=True,
                                    probe_recovery=True))
    sr = jax.jit(build_server_round(cfg, probes=True))
    rng = np.random.RandomState(0)
    # heavy-tailed coordinates: the top-k floor of the recovery error
    # stays well below 1
    c = rng.randn(W, 1, d).astype(np.float32)
    c[:, :, :2000] *= 50.0
    batch = {"c": jnp.asarray(np.broadcast_to(c, (W, B, d))),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32)
    res = cr(flat, ClientStates.init(cfg, 100, flat), batch,
             jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
             1.0)
    pr = {k: float(v) for k, v in res.probes.items()}
    out = sr(flat, ServerState.init(cfg), res.aggregated,
             jnp.float32(0.1))
    pr.update({k: float(v) for k, v in out[-1].items()})
    assert pr["agg_nan"] == 0 and pr["agg_inf"] == 0, pr
    rec = pr["recovery_error"]
    assert np.isfinite(rec) and 0.0 <= rec < 1.0, pr
    for key in ("update_norm", "residual_norm", "momentum_norm",
                "mass_coverage"):
        assert np.isfinite(pr[key]), pr
    return f"recovery error {rec:.3f}"


def quant_smoke():
    """Quantized uplink path on the REAL backend: the fused Pallas
    emit+quantize kernel must agree bit-for-bit with the unfused
    quantize_local(sketch(.)) path on-device for every wire dtype,
    and a quantized sketch round's TRUE recovery error must stay
    inside the alarm band of the f32 reference round (per-row scales
    bound the quantization penalty; server momentum/EF stays f32) —
    while moving ~4x fewer uplink bytes."""
    from commefficient_tpu import accounting
    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState
    from commefficient_tpu.ops.quant import quantize_local
    from commefficient_tpu.ops.sketch import CountSketch

    d = 1 << 16
    cs = CountSketch(d=d, c=4096, r=3, seed=7)
    v = jnp.asarray(np.random.RandomState(0).randn(d)
                    .astype(np.float32))
    for wire in ("bf16", "int8", "fp8"):
        qf, _ = jax.jit(lambda x, w=wire: cs.sketch_quantized(x, w))(v)
        qu, _ = jax.jit(
            lambda x, w=wire: quantize_local(cs.sketch(x), w))(v)
        assert np.asarray(qf).tobytes() == np.asarray(qu).tobytes(), \
            f"{wire}: fused kernel != unfused quantize"

    W, B = 8, 4

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    rng = np.random.RandomState(0)
    cvec = rng.randn(W, 1, d).astype(np.float32)
    cvec[:, :, :500] *= 50.0  # heavy hitters: recovery floor << 1
    batch = {"c": jnp.asarray(np.broadcast_to(cvec, (W, B, d))),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32)
    errs = {}
    for wire in ("f32", "int8", "fp8"):
        cfg = Config(mode="sketch", error_type="virtual",
                     local_momentum=0.0, virtual_momentum=0.9,
                     num_workers=W, local_batch_size=B, k=500,
                     num_rows=5, num_cols=16384, seed=21,
                     sketch_dtype=wire)
        cfg.grad_size = d
        cr = jax.jit(build_client_round(cfg, lin_loss, B, probes=True,
                                        probe_recovery=True))
        sr = jax.jit(build_server_round(cfg, probes=True))
        res = cr(flat, ClientStates.init(cfg, 100, flat), batch,
                 jnp.arange(W, dtype=jnp.int32),
                 jax.random.PRNGKey(0), 1.0)
        out = sr(flat, ServerState.init(cfg), res.aggregated,
                 jnp.float32(0.1))
        assert bool(jnp.isfinite(out[0]).all()), wire
        pr = {k: float(x) for k, x in res.probes.items()}
        pr.update({k: float(x) for k, x in out[-1].items()})
        assert pr["agg_nan"] == 0 and pr["agg_inf"] == 0, (wire, pr)
        errs[wire] = pr["recovery_error"]
    band = max(2.0 * errs["f32"], errs["f32"] + 0.05)
    assert errs["int8"] <= band, errs
    assert errs["fp8"] <= band, errs
    ratio = (accounting.sketch_wire_bytes(5, 16384, "f32")
             / accounting.sketch_wire_bytes(5, 16384, "int8"))
    return (f"fused==unfused bitwise; recovery err f32 "
            f"{errs['f32']:.3f} int8 {errs['int8']:.3f} fp8 "
            f"{errs['fp8']:.3f}; uplink {ratio:.2f}x smaller at int8")


def overlap_smoke():
    """Latency-hiding round pipeline (--overlap_depth) on the REAL
    backend: a depth-2 chunked int8 round must be BIT-IDENTICAL to
    the depth-1 serial round (per-row scales make every chunk the
    exact row slice of the whole-table algebra — the pipeline
    reorders the schedule, never the math), and a traced pipelined
    round must land an ``overlapped_s`` bucket in its device-time
    attribution for the observatory to read."""
    import shutil
    import tempfile

    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round)
    from commefficient_tpu.parallel.mesh import client_sharding, make_mesh
    from commefficient_tpu.telemetry import trace
    from commefficient_tpu.telemetry.profiler import trace_window

    W, B, d = 8, 4, 1 << 16

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    rng = np.random.RandomState(0)
    batch = {"c": jnp.asarray(rng.randn(W, B, d).astype(np.float32)),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32)
    mesh = make_mesh()
    sharded = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, client_sharding(mesh)), batch)
    aggs, rounds = {}, {}
    for depth in (1, 2):
        cfg = Config(mode="sketch", error_type="virtual",
                     local_momentum=0.0, virtual_momentum=0.9,
                     num_workers=W, local_batch_size=B, k=500,
                     num_rows=4, num_cols=16384, seed=21,
                     sketch_dtype="int8", overlap_depth=depth)
        cfg.grad_size = d
        cr = jax.jit(build_client_round(cfg, lin_loss, B, mesh=mesh))
        res = cr(flat, ClientStates.init(cfg, W, flat), sharded,
                 jnp.arange(W, dtype=jnp.int32),
                 jax.random.PRNGKey(0), 1.0)
        aggs[depth] = np.asarray(res.aggregated)
        rounds[depth] = (cr, res.client_states)
    assert aggs[1].tobytes() == aggs[2].tobytes(), \
        "depth-2 pipelined round != depth-1 serial round"

    # a traced pipelined round must carry the overlapped_s bucket
    logdir = tempfile.mkdtemp(prefix="overlap_smoke_")
    try:
        cr, cs = rounds[2]
        with trace_window(logdir):
            trace.begin_round_marker(0)
            cr(flat, cs, sharded, jnp.arange(W, dtype=jnp.int32),
               jax.random.PRNGKey(1), 1.0
               ).aggregated.block_until_ready()
        buckets = trace.attribute_logdir(logdir)
        assert buckets, "no rounds attributed"
        b0 = buckets[sorted(buckets)[0]]
        ovl = b0.get("overlapped_s")
        assert ovl is not None and ovl >= 0.0, b0
        assert ovl <= b0["collective_s"] + 1e-9, b0
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return (f"depth-2 bitwise == depth-1; overlapped "
            f"{ovl * 1e3:.2f} ms of "
            f"{b0['collective_s'] * 1e3:.2f} ms collective")


def async_smoke():
    """Buffered asynchronous rounds (asyncfed) on the REAL backend:
    the degenerate configuration — buffer size == cohort, staleness
    weight 0, punctual arrivals — must be BIT-IDENTICAL to the
    synchronous barrier round (the async driver adds bookkeeping,
    never math), and a churny arrival schedule must land its
    staleness histogram in the telemetry ledger for the observatory
    to read."""
    import json
    import shutil
    import tempfile

    from commefficient_tpu.config import Config
    from commefficient_tpu.data.chaos import ArrivalSchedule
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    W, B, d = 8, 2, 1 << 10

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    def run(async_k, alpha, sched=None, ledger=""):
        cfg = Config(mode="sketch", error_type="virtual",
                     local_momentum=0.0, virtual_momentum=0.9, k=32,
                     num_rows=3, num_cols=256, num_workers=W,
                     local_batch_size=B, num_clients=64, seed=3,
                     async_buffer_size=async_k,
                     async_staleness_weight=alpha, ledger=ledger)
        model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                         loss, cfg, padded_batch_size=B)
        opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
        if sched is not None:
            model.attach_arrival_process(sched)
        rng = np.random.RandomState(3)
        for _ in range(6):
            batch = {"client_ids": rng.choice(64, W, replace=False)
                     .astype(np.int32),
                     "x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
                     "y": jnp.asarray(rng.randn(W, B), jnp.float32),
                     "mask": jnp.ones((W, B), jnp.float32)}
            model(batch)
            opt.step()
        ps = np.asarray(model.ps_weights)
        model.finalize()
        return ps

    sync = run(0, 0.0)
    deg = run(W, 0.0)  # K == cohort, punctual: the barrier in disguise
    assert np.array_equal(sync, deg), "degenerate async != sync round"

    tmp = tempfile.mkdtemp(prefix="async_smoke_")
    try:
        led = os.path.join(tmp, "ledger.jsonl")
        run(4, 0.5, sched=ArrivalSchedule("churny", seed=3),
            ledger=led)
        hist = None
        with open(led) as f:
            for line in f:
                rec = json.loads(line)
                pr = rec.get("probes") or {}
                if "async_staleness_hist" in pr:
                    hist = pr["async_staleness_hist"]
        assert hist is not None, "no staleness histogram in ledger"
        assert sum(hist) > 0, hist
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return ("degenerate buffered round bitwise == sync; churny "
            f"staleness hist {hist}")


def autopilot_smoke():
    """Adaptive compression autopilot on the REAL backend: from an f32
    launch the probe-driven controller must walk to a cheaper wire
    dtype while the recovery error holds the band on every observed
    round, with the re-jit cache compiling no more round variants than
    lattice points actually visited (warm-ahead never compiles an
    unvisited point) and the recorded trajectory replaying
    bit-exactly."""
    from commefficient_tpu.autopilot import parse_band, replay_record
    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    W, B, d = 4, 2, 512

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 num_workers=W, local_batch_size=B, seed=5,
                 num_clients=16, k=64, num_rows=5, num_cols=2048,
                 sketch_dtype="f32", probe_every=1, autopilot="on",
                 autopilot_band="0.05:0.6", autopilot_cooldown=1)
    model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                     loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    scale = (np.arange(1, d + 1) ** -1.5).astype(np.float32)
    rng = np.random.RandomState(5)
    for _ in range(8):
        model({"client_ids": rng.choice(16, W, replace=False)
               .astype(np.int32),
               "x": jnp.asarray(rng.randn(W, B, d).astype(np.float32)
                                * scale),
               "y": jnp.asarray(rng.randn(W, B), jnp.float32),
               "mask": jnp.ones((W, B), jnp.float32)})
        opt.step()
    rec = model.autopilot_record()
    counters = model._variants.counters()
    model.finalize()

    lo, hi = parse_band(cfg.autopilot_band)
    observed = [t for t in rec["trajectory"]
                if t["recovery_error"] is not None]
    assert observed, "no recovery observations reached the controller"
    assert all(t["recovery_error"] <= hi for t in observed), observed
    assert not any(t["action"] == "panic"
                   for t in rec["trajectory"]), rec["trajectory"]
    assert rec["final_wire_bytes"] * 2 <= rec["initial_wire_bytes"], rec
    visited = {t["key"] for t in rec["trajectory"]}
    visited.add(rec["initial"])
    assert counters["misses"] <= len(visited), (counters, visited)
    assert replay_record(rec) == [t["key"] for t in rec["trajectory"]]
    return (f"{rec['initial'].split('-', 1)[0]} -> {rec['final']}, "
            f"uplink {rec['initial_wire_bytes'] / rec['final_wire_bytes']:.1f}x "
            f"smaller, {counters['misses']} compiles / "
            f"{len(visited)} points visited")


def audit_smoke():
    """Static audit on the REAL backend: zero unwaived lint hits, and
    the sketch fused round compiled for this topology is donation-
    covered and host-transfer-free, with the table psum's wire bytes
    matching the ledger's 4·r·c per-client uplink when the mesh
    actually spans devices. (The fingerprint-vs-baseline diff is a
    CPU-mesh-only check — compiled text differs per platform — so
    it stays in tier-1, not here.)"""
    from commefficient_tpu.analysis.lint import run_lint, unwaived
    from commefficient_tpu.analysis.program import (ProgramSpec,
                                                    audit_client_program)
    from commefficient_tpu.parallel.mesh import make_mesh

    hits = unwaived(run_lint())
    assert not hits, f"unwaived lint violations: {hits[:5]}"
    spec = ProgramSpec("sketch/fused", "sketch", "fused",
                       dict(error_type="virtual",
                            virtual_momentum=0.9))
    entry = audit_client_program(spec, mesh=make_mesh(jax.devices()))
    assert not entry["failures"], entry["failures"]
    counts = entry["collectives"]["counts"]
    # the fused shard_map branch engages when the W=8 fan-out divides
    # the mesh; odd device counts fall back to single-device (no psum)
    if jax.device_count() > 1 and 8 % jax.device_count() == 0:
        assert counts.get("all-reduce"), entry["collectives"]
    return (f"lint clean; sketch/fused collectives {counts or '{}'} "
            f"fp {entry['fingerprint'][:12]}")


def flowlint_smoke():
    """The flowlint whole-program tier on the deployed tree: zero
    unwaived findings from the call-graph checkers (trace-purity,
    prng-keys, wire-dtype-crossing, lock-confinement) and the engine
    staying inside its 10 s wall-time budget — a daemon image ships
    with the same static guarantees CI pinned."""
    import time as _time

    from commefficient_tpu.analysis.flow import build_program
    from commefficient_tpu.analysis.lint import (run_all, unwaived)

    t0 = _time.monotonic()
    program = build_program(None)
    hits = unwaived(run_all(program=program))
    elapsed = _time.monotonic() - t0
    assert not hits, f"unwaived flowlint findings: {hits[:5]}"
    assert elapsed < 10.0, f"engine took {elapsed:.1f}s (budget 10s)"
    return (f"flow tier clean; {len(program.jit_roots)} jit roots, "
            f"{len(program.thread_roots)} thread roots, "
            f"{len(program.traced)} traced fns in {elapsed:.1f}s")


def flash_attention_parity():
    """attn_impl="flash" (Pallas flash-attention kernel) vs the XLA
    attention lowering on the same GPT-2 block — forward and gradient
    agreement at bf16 tolerance. T=256 takes block 256; T=640 takes
    the divisor-selection path (640 = 5·128: block must DIVIDE T, not
    just bound it — the round-4 review crash case)."""
    import dataclasses

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    details = []
    for T in (256, 640):
        base = GPT2Config(vocab_size=512, n_positions=1024, n_embd=256,
                          n_layer=2, n_head=4, dtype=jnp.bfloat16)
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, 512, (2, 2, T)), jnp.int32)
        mc = jnp.full((2, 2), T - 1, jnp.int32)

        outs = {}
        for impl in ("xla", "flash"):
            cfg = dataclasses.replace(base, attn_impl=impl)
            m = GPT2DoubleHeads(cfg)
            p = m.init(jax.random.PRNGKey(0), ids, mc, ids)["params"]

            def loss(pp, m=m, ids=ids, mc=mc):
                lm, mcl = m.apply({"params": pp}, ids, mc, ids)
                return jnp.sum(lm.astype(jnp.float32) ** 2) * 1e-6 + \
                    jnp.sum(mcl.astype(jnp.float32) ** 2) * 1e-3

            l, g = jax.jit(jax.value_and_grad(loss))(p)
            gflat = jnp.concatenate([jnp.ravel(x) for x in
                                     jax.tree_util.tree_leaves(g)])
            outs[impl] = (float(l), np.asarray(gflat, np.float32))
        lx, gx = outs["xla"]
        lf, gf = outs["flash"]
        assert abs(lx - lf) / max(abs(lx), 1e-6) < 2e-2, (T, lx, lf)
        denom = np.maximum(np.abs(gx), 1e-3)
        rel = np.abs(gx - gf) / denom
        assert np.median(rel) < 2e-2, (T, float(np.median(rel)))
        details.append(f"T={T} grad rel {np.median(rel):.1e}")
    return "; ".join(details)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def gqa_kernel_parity():
    """``models/mixers.py gqa_attention`` on the kernel path (what it
    builds on the chip with no block given) against its blocked form at
    128 queries a block, outputs and the three gradients in bf16 under
    a 2-client ``vmap`` and ``jax.checkpoint``: SmallThinker's window
    and full layers (8,192 x 28 / 4 heads of 128, window 4,096),
    Granite's layer (2,048 x 32 / 8 heads of 64) and Nemotron's (2,048
    x 4 / 1 heads of 128: a dense layer off the chip)."""
    from commefficient_tpu.models.mixers import attn_plan, gqa_attention

    details = []
    for T, Hq, Hkv, D, window in ((8192, 28, 4, 128, 4096),
                                  (8192, 28, 4, 128, None),
                                  (2048, 32, 8, 64, None),
                                  (2048, 4, 1, 128, None)):
        plan = attn_plan(1, T, Hq, window, None, D)
        assert plan.kernel == "splash", plan
        k = jax.random.split(jax.random.PRNGKey(T + D), 3)
        q = jax.random.normal(k[0], (2, 1, T, Hkv, Hq // Hkv, D),
                              jnp.bfloat16)
        kk = jax.random.normal(k[1], (2, 1, T, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(k[2], (2, 1, T, Hkv, D), jnp.bfloat16)

        def both(block, window=window, D=D):
            fn = jax.checkpoint(lambda q, k, v: gqa_attention(
                q, k, v, D ** -0.5, query_block=block, window=window)[0])
            out = jax.vmap(fn)

            def loss(q, k, v):
                return jnp.sum(jnp.sin(out(q, k, v).astype(jnp.float32)))
            return jax.jit(lambda *a: (out(*a),) + jax.grad(
                loss, argnums=(0, 1, 2))(*a))

        got, want = both(None)(q, kk, v), both(128)(q, kk, v)
        worst = max(_rel(a, b) for a, b in zip(got, want))
        assert worst < 2e-2, (T, D, window, worst)
        details.append(f"T={T} D={D} window={window} tile {plan.block} "
                       f"worst rel {worst:.1e}")
    return "; ".join(details)


def mla_kernel_parity():
    """``models/joyai.py mla_attention`` through the flash kernel (what
    ``MLA`` builds on the chip: one 192-wide score product beside a
    128-wide value product, a group of one query head) against its
    dense form (two score products on float32 (S, H, T, T) scores),
    outputs and the three gradients in bf16 under a 2-client ``vmap``
    and ``jax.checkpoint`` at the JoyAI cell's shape (4 sequences x
    1,024 x 4 heads)."""
    from commefficient_tpu.models import joyai

    cfg = joyai.JoyAIConfig(num_attention_heads=4, dtype=jnp.bfloat16)
    S, T, H = 4, 1024, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k = jax.random.split(jax.random.PRNGKey(49), 3)
    qh = jax.random.normal(k[0], (2, S, T, H, dn + dr), jnp.bfloat16)
    kvh = jax.random.normal(k[1], (2, S, T, H, dn + dv), jnp.bfloat16)
    kr = jax.random.normal(k[2], (2, S, T, dr), jnp.bfloat16)

    def both(kernel):
        out = jax.vmap(jax.checkpoint(lambda qh, kvh, kr: (
            joyai.mla_attention(cfg, qh, kvh, kr, kernel))))

        def loss(*a):
            return jnp.sum(jnp.sin(out(*a).astype(jnp.float32)))
        return jax.jit(lambda *a: (out(*a),) + jax.grad(
            loss, argnums=(0, 1, 2))(*a))

    plan = joyai.mla_plan(cfg, S, T)
    assert plan.kernel == "splash", plan
    got, want = both(plan.kernel)(qh, kvh, kr), both(None)(qh, kvh, kr)
    worst = max(_rel(a, b) for a, b in zip(got, want))
    assert worst < 2e-2, (plan.block, worst)
    return f"tile {plan.block} worst rel {worst:.1e}"


def moe_pool_parity():
    """``models/moe.py routed_experts`` at the Nemotron cell's expert
    layer (8 clients x 2,048 tokens x 22 picks, 8 held of 512, latent
    1,024, experts 2,688 wide, squared ReLU, bf16 rows): every client's
    held rows in one grouped pass (the ``vmap`` named
    ``SHARED_CLIENTS``) against a client at a time (a ``vmap`` that
    says nothing), the outputs and the gradients of the rows, the gates
    and both weights."""
    from commefficient_tpu.models import moe
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    W, N, k, E, R, C, F = 8, 2048, 22, 8, 512, 1024, 2688
    key = jax.random.split(jax.random.PRNGKey(45), 5)
    x = jax.random.normal(key[0], (W, N, C), jnp.bfloat16)
    router = jax.random.normal(key[1], (C, R)) / C ** 0.5
    w = (0.02 * jax.random.normal(key[2], (E, C, F)),
         0.02 * jax.random.normal(key[3], (E, F, C)))
    cot = jax.random.normal(key[4], (W, N, C))

    def routing(xi):
        top, g = moe.route(xi, router, jnp.zeros((R,)), k, 1.0)
        return moe.dispatch(top, g, 0, E)

    token, gate, load = jax.jit(jax.vmap(routing))(x)

    def both(axis):
        def out(x, gate, w):
            return jax.vmap(lambda xi, ti, gi, li: moe.routed_experts(
                xi, ti, gi, li, w, "relu2", E / R),
                axis_name=axis)(x, token, gate, load)
        return jax.jit(lambda *a: (out(*a),) + jax.grad(
            lambda *a: jnp.sum(out(*a) * cot), argnums=(0, 1, 2))(*a))

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    got, want = (jax.tree_util.tree_leaves(both(axis)(x, gate, w))
                 for axis in (SHARED_CLIENTS, None))
    worst = max(rel(a, b) for a, b in zip(got, want))
    assert worst < 1e-2, worst
    held, rows = int(jnp.sum(load)), moe.pool_rows(W, N * k, E / R)
    assert held <= rows, (held, rows)    # one pass at the cell's routing
    return f"{held} held assignments in one buffer of {rows}; worst rel " \
           f"{worst:.1e}"


def trace_smoke():
    """Device-time attribution round-trip on the REAL backend: a
    trace_window around a few marked rounds of device work must
    produce round windows whose buckets are internally consistent
    (disjoint buckets summing to the window) with nonzero device busy
    time — TPU xplanes name their lanes differently from the CPU
    backend the pytest fixture covers, so the lane detection is what
    this check actually exercises."""
    import shutil
    import tempfile

    from commefficient_tpu.telemetry import trace
    from commefficient_tpu.telemetry.profiler import trace_window

    logdir = tempfile.mkdtemp(prefix="trace_smoke_")
    try:
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2048, 2048).astype(np.float32))
        f = jax.jit(lambda a: a @ a.T + 1.0)
        f(x).block_until_ready()  # compile outside the window
        with trace_window(logdir):
            for r in range(3):
                trace.begin_round_marker(r)
                f(x).block_until_ready()
        buckets = trace.attribute_logdir(logdir)
        assert len(buckets) == 3, sorted(buckets)
        busy = sum(b["busy_s"] for b in buckets.values())
        assert busy > 0, buckets
        for r, b in buckets.items():
            parts = (b["compute_s"] + b["collective_s"]
                     + b["transfer_s"] + b["host_gap_s"])
            assert abs(parts - b["window_s"]) <= 1e-5, (r, b)
        return (f"3 rounds attributed, busy {busy * 1e3:.1f} ms, "
                f"compute {sum(b['compute_s'] for b in buckets.values()) * 1e3:.1f} ms")
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def mesh2d_smoke():
    """2D clients x model mesh on the REAL backend: the pod-scale
    sketch round (partial tables reduce-scattered over ``model``,
    column-sharded server momentum/EF, distributed top-k select) must
    match the 1-D oracle round on this hardware, with per-device
    server shards at 1/M of the table. The mesh shape adapts to the
    attached topology (model axis 2 whenever the device count is
    even)."""
    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState
    from commefficient_tpu.parallel.mesh import (client_sharding,
                                                 make_mesh2d,
                                                 model_axis_size,
                                                 server_state_sharding)

    n = jax.device_count()
    m = 2 if n % 2 == 0 else 1
    c = n // m
    W, B, d = 2 * c, 2, 1 << 12
    cfg = Config(mode="sketch", error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 weight_decay=5e-4, num_workers=W, local_batch_size=B,
                 k=64, num_rows=3, num_cols=512, seed=21,
                 mesh=f"{c}x{m}")
    cfg.grad_size = d
    cfg.validate_runtime()

    def lin_loss(p, b):
        nm = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / nm
        return loss, (loss * 0.0,)

    rng = np.random.RandomState(0)
    batch = {"c": jnp.asarray(rng.randn(W, B, d).astype(np.float32)),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32).at[0].set(0.5)

    def run(mesh):
        two_d = mesh is not None and model_axis_size(mesh) > 1
        cr = jax.jit(build_client_round(cfg, lin_loss, B, mesh=mesh))
        sr = jax.jit(build_server_round(
            cfg, mesh=mesh if two_d else None))
        ss = ServerState.init(
            cfg, sharding=(server_state_sharding(mesh,
                                                 cfg.transmit_shape)
                           if two_d else None))
        ps, cs = flat, ClientStates.init(cfg, W, flat)
        b = batch
        if mesh is not None:
            b = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, client_sharding(mesh)), b)
        for r in range(2):
            res = cr(ps, cs, b, jnp.arange(W, dtype=jnp.int32),
                     jax.random.PRNGKey(r), 1.0)
            cs = res.client_states
            ps, ss, _, _, _ = sr(ps, ss, res.aggregated,
                                 jnp.float32(0.1))
        return np.asarray(ps), np.asarray(ss.Vvelocity), ss

    ps2, vel2, ss2 = run(make_mesh2d(c, m))
    ps1, vel1, _ = run(None)
    scale = max(float(np.abs(ps1).max()), 1e-6)
    err = float(np.abs(ps2 - ps1).max()) / scale
    assert err < 1e-4, err
    np.testing.assert_allclose(vel2, vel1, rtol=0, atol=1e-4)
    if m > 1:
        shapes = {tuple(s.data.shape)
                  for s in ss2.Verror.addressable_shards}
        assert shapes == {(cfg.num_rows, cfg.num_cols // m)}, shapes
    return f"mesh {c}x{m}: params rel err {err:.1e}"


def elastic_smoke():
    """Topology-changing restore on the REAL backend: checkpoint a
    sketch run on a 2x1 clients x model mesh, restore it onto a 1x2
    mesh (same chips, transposed layout), and require the restored
    state bit-identical — asserted by re-saving from the resized model
    and comparing the two archives array for array. The placement
    moved; the values must not."""
    import json
    import tempfile

    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime import FedModel, FedOptimizer
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)

    if jax.device_count() < 2:
        return "skipped (needs >= 2 devices)"

    W, B, D = 4, 2, 256

    def loss(p, batch, _cfg):
        pred = batch["x"] @ p["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        return jnp.sum((pred - batch["y"]) ** 2
                       * batch["mask"]) / n, ()

    def build(mesh):
        cfg = Config(mode="sketch", error_type="virtual",
                     local_momentum=0.0, virtual_momentum=0.9,
                     num_workers=W, local_batch_size=B,
                     num_clients=2 * W, dataset_name="CIFAR10",
                     seed=3, k=16, num_rows=3, num_cols=128,
                     mesh=mesh)
        model = FedModel(None, {"w": jnp.zeros((D,), jnp.float32)},
                         loss, cfg, padded_batch_size=B)
        opt = FedOptimizer([{"lr": 0.2}], cfg, model=model)
        return model, opt

    def mk(r):
        rng = np.random.RandomState(100 + r)
        return {"x": rng.randn(W, B, D).astype(np.float32),
                "y": rng.randn(W, B).astype(np.float32),
                "mask": np.ones((W, B), np.float32),
                "client_ids": np.arange(r, r + W,
                                        dtype=np.int32) % (2 * W)}

    tmp = tempfile.mkdtemp(prefix="elastic_smoke_")
    ck_a = os.path.join(tmp, "a.npz")
    ck_b = os.path.join(tmp, "b.npz")
    model, opt = build("2x1")
    for r in range(3):
        model(mk(r))
        opt.step()
    save_checkpoint(ck_a, model, opt)
    model.finalize()

    model2, opt2 = build("1x2")
    load_checkpoint(ck_a, model2, opt2)
    save_checkpoint(ck_b, model2, opt2)
    model2.finalize()

    za, zb = np.load(ck_a), np.load(ck_b)
    keys = set(za.files) | set(zb.files)
    diffs = []
    for key in sorted(keys - {"meta"}):
        a = za[key] if key in za.files else None
        b = zb[key] if key in zb.files else None
        if a is None or b is None or a.dtype != b.dtype \
                or not np.array_equal(a, b):
            diffs.append(key)
    assert not diffs, f"state drifted across 2x1 -> 1x2: {diffs}"
    meta_b = json.loads(str(zb["meta"]))
    segs = meta_b.get("segments") or []
    assert len(segs) >= 2, segs
    return (f"{len(keys) - 1} arrays bit-equal across 2x1 -> 1x2, "
            f"{len(segs)} lineage segments")


def chaos_smoke():
    """Byzantine sign-flip under --robust_agg median on the REAL
    backend: a flipped minority must leave the robust fold's aggregate
    at the honest gradient while the plain mean is dragged off by the
    flipped mass — the engine guarantee the chaos-harness tests pin on
    the CPU mesh, exercised here on hardware."""
    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round)
    from commefficient_tpu.data.chaos import ChaosConfig, ChaosInjector

    W, B, d = 8, 4, 1 << 14

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    inj = ChaosInjector(ChaosConfig(seed=5, attack="sign_flip",
                                    byzantine_ids=(1, 5)),
                        num_clients=W)
    transform = inj.transmit_transform()
    c = np.random.RandomState(0).randn(1, 1, d).astype(np.float32)
    batch = {"c": jnp.asarray(np.broadcast_to(c, (W, B, d))),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32)
    aggs = {}
    for agg_mode in ("none", "median"):
        cfg = Config(mode="uncompressed", error_type="none",
                     local_momentum=0.0, num_workers=W,
                     local_batch_size=B, seed=5, robust_agg=agg_mode)
        cfg.grad_size = d
        cr = jax.jit(build_client_round(cfg, lin_loss, B,
                                        transmit_transform=transform))
        res = cr(flat, ClientStates.init(cfg, W, flat), batch,
                 jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
                 1.0)
        aggs[agg_mode] = np.asarray(res.aggregated)
    honest = c[0, 0]
    scale = np.linalg.norm(honest)
    err_med = np.linalg.norm(aggs["median"] - honest) / scale
    err_plain = np.linalg.norm(aggs["none"] - honest) / scale
    # 2/8 flipped: plain mean = 0.5*honest (err 0.5); median = honest
    assert err_med < 1e-4, err_med
    assert err_plain > 0.25, err_plain
    return f"median err {err_med:.1e}; plain mean err {err_plain:.2f}"


def dp_smoke():
    """--dp sketch on the REAL backend: a zero-gradient round's
    aggregated table is pure calibrated noise (empirical std ==
    table_noise_std within 5%), one charged round at q=1 matches the
    Mironov closed form restated inline, and --dp off is lowered-text
    IDENTICAL to a build that never saw the dp knobs — privacy costs
    nothing when it is off."""
    import math

    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round)
    from commefficient_tpu.privacy import (build_accountant,
                                           table_noise_std)

    W, B, d = 8, 4, 1 << 14

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    def cfg_of(**kw):
        cfg = Config(mode="sketch", error_type="virtual",
                     local_momentum=0.0, virtual_momentum=0.9,
                     num_workers=W, local_batch_size=B, k=64,
                     num_rows=5, num_cols=16384, seed=21,
                     num_clients=W, dataset_name="CIFAR10", **kw)
        cfg.grad_size = d
        return cfg

    # calibrated noise: zero gradients -> the released table IS the
    # noise draw, so its empirical std must be the mechanism's std
    cfg = cfg_of(dp="sketch", dp_clip=1.0, dp_noise_mult=1.3)
    cr = jax.jit(build_client_round(cfg, lin_loss, B))
    batch = {"c": jnp.zeros((W, B, d), jnp.float32),
             "mask": jnp.ones((W, B), jnp.float32)}
    flat = jnp.zeros((d,), jnp.float32)
    res = cr(flat, ClientStates.init(cfg, W, flat), batch,
             jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
             1.0)
    want = table_noise_std(cfg)
    got = float(np.asarray(res.aggregated).std())
    assert abs(got - want) / want < 0.05, (got, want)

    # one charged round at q = 1 (num_clients == cohort) must equal
    # the Mironov subsampled-Gaussian closed form, restated inline
    # with math-library calls only — independent of the accountant
    acc = build_accountant(cfg)
    acc.step()
    sigma, delta = cfg.dp_noise_mult, cfg.dp_delta
    closed = min(
        a / (2.0 * sigma ** 2) + math.log1p(-1.0 / a)
        - (math.log(delta) + math.log(a)) / (a - 1)
        for a in range(2, 513))
    eps = acc.epsilon()
    assert abs(eps - closed) <= 1e-9 * closed, (eps, closed)

    # --dp off fingerprint identity: inert dp knobs must not perturb
    # the lowered round program by a single character
    texts = []
    for kw in ({}, dict(dp="off", dp_clip=9.9, dp_noise_mult=7.0)):
        c2 = cfg_of(**kw)
        f = jax.jit(build_client_round(c2, lin_loss, B))
        texts.append(f.lower(
            flat, ClientStates.init(c2, W, flat), batch,
            jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
            jnp.float32(1.0)).as_text())
    assert texts[0] == texts[1], "--dp off perturbed the round program"
    return (f"noise std {got:.4g} (calibrated {want:.4g}); "
            f"one-round eps {eps:.4g} == closed form; "
            f"dp-off program identical")


def service_smoke():
    """Multi-tenant daemon (fedservice) on the REAL backend: one job
    driven through the FedService scheduler must be BIT-IDENTICAL to
    driving its FedModel directly (the daemon is control plane, never
    math), and a two-tenant pod must keep its ledgers isolated — one
    ``.job<j>.jsonl`` shard per tenant next to the service's own
    fairness ledger."""
    import json
    import shutil
    import tempfile

    from commefficient_tpu.config import Config
    from commefficient_tpu.fedservice import FedService, JobSpec
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    W, B, d, R = 8, 2, 1 << 10, 4

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    def job_cfg(seed):
        return Config(mode="local_topk", error_type="local",
                      local_momentum=0.9, virtual_momentum=0.0, k=8,
                      num_workers=W, local_batch_size=B,
                      num_clients=64, seed=seed)

    def builder(cfg, mesh):
        model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                         loss, cfg, padded_batch_size=B, mesh=mesh)
        return model, FedOptimizer([{"lr": 0.25}], cfg, model=model)

    def batches(seed):
        rng = np.random.RandomState(seed)
        return [
            {"client_ids": rng.choice(64, W, replace=False)
             .astype(np.int32),
             "x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
             "y": jnp.asarray(rng.randn(W, B), jnp.float32),
             "mask": jnp.ones((W, B), jnp.float32)}
            for _ in range(R)]

    # solo leg
    model, opt = builder(job_cfg(3), None)
    for batch in batches(7):
        model(batch)
        opt.step()
    solo = np.array(model.ps_weights)
    model.finalize()

    tmp = tempfile.mkdtemp(prefix="service_smoke_")
    try:
        led = os.path.join(tmp, "svc.jsonl")
        svc = FedService(Config(num_workers=W, local_batch_size=B,
                                num_clients=64, ledger=led))
        bs_a, bs_b = batches(7), batches(9)
        svc.admit(JobSpec("a", job_cfg(3), builder,
                          lambda r: bs_a[r], rounds=R))
        svc.admit(JobSpec("b", job_cfg(4), builder,
                          lambda r: bs_b[r], rounds=R))
        svc.run()
        daemon = svc.job_state("a")
        svc.close()
        assert np.array_equal(solo, daemon), \
            "single job through daemon != direct driver (bitwise)"
        for j in (0, 1):
            shard = f"{led}.job{j}.jsonl"
            assert os.path.exists(shard), f"missing shard {shard}"
            rounds = sum(1 for line in open(shard)
                         if json.loads(line).get("kind") == "round")
            assert rounds == R, (shard, rounds)
        svc_rounds = sum(1 for line in open(led)
                         if json.loads(line).get("kind") == "round")
        assert svc_rounds >= R, svc_rounds
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return ("1-job daemon bitwise == direct driver; 2 tenants, "
            f"{R} isolated rounds per shard")


def live_smoke():
    """Live operations plane on the REAL backend: start a fedservice
    daemon with the exporter armed, scrape /metrics mid-run and see
    per-job labeled series, trip the ``slo_burn`` rule on a
    deliberately starved tenant (backlog policy), and confirm the
    flight recorder dumped a postmortem bundle the report tool can
    round-trip."""
    import dataclasses
    import json
    import shutil
    import socket
    import tempfile
    import urllib.request

    from commefficient_tpu.config import Config
    from commefficient_tpu.fedservice import FedService, JobSpec
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)
    from commefficient_tpu.telemetry.flightrec import load_postmortem
    from commefficient_tpu.telemetry.live import shutdown_plane

    W, B, d = 8, 2, 1 << 10

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    def builder(cfg, mesh):
        model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                         loss, cfg, padded_batch_size=B, mesh=mesh)
        return model, FedOptimizer([{"lr": 0.25}], cfg, model=model)

    def batches(seed, n):
        rng = np.random.RandomState(seed)
        return [
            {"client_ids": rng.choice(64, W, replace=False)
             .astype(np.int32),
             "x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
             "y": jnp.asarray(rng.randn(W, B), jnp.float32),
             "mask": jnp.ones((W, B), jnp.float32)}
            for _ in range(n)]

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    tmp = tempfile.mkdtemp(prefix="live_smoke_")
    try:
        led = os.path.join(tmp, "svc.jsonl")
        svc_cfg = Config(num_workers=W, local_batch_size=B,
                         num_clients=64, ledger=led, live_port=port,
                         flightrec_rounds=8,
                         postmortem_dir=os.path.join(tmp, "pm"),
                         slo_starvation=1.0, slo_window=4,
                         slo_fast_window=2, alarm_slo_burn=1.0)
        # NB: no live_port here — the daemon propagates its own
        # plane knobs to every tenant at admission
        job_cfg = Config(mode="local_topk", error_type="local",
                         local_momentum=0.9, virtual_momentum=0.0,
                         k=8, num_workers=W, local_batch_size=B,
                         num_clients=64, seed=3)
        svc = FedService(svc_cfg, policy="backlog")
        bs_a, bs_b = batches(7, 6), batches(9, 2)
        svc.admit(JobSpec("a", job_cfg, builder,
                          lambda r: bs_a[r] if r < 6 else None,
                          rounds=6))
        svc.admit(JobSpec("b", dataclasses.replace(job_cfg, seed=4),
                          builder,
                          lambda r: bs_b[r] if r < 2 else None,
                          rounds=2))
        for _ in range(8):
            svc.tick()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) \
            .read().decode()
        series = [l for l in body.splitlines()
                  if l and not l.startswith("#")]
        for want in ('commeff_rounds_total{job="0"',
                     'commeff_rounds_total{job="1"',
                     'commeff_rounds_total{job="service"}',
                     "commeff_round_seconds",
                     "commeff_job_backlog_total",
                     "commeff_alarms_total"):
            assert any(want in l for l in series), (want, series)
        bundle_path = svc.flightrec.last_bundle
        assert bundle_path and os.path.exists(bundle_path), \
            "slo_burn fired but no postmortem bundle dumped"
        svc.close()
        bundle, problems = load_postmortem(bundle_path)
        assert not problems, problems
        assert bundle["rule"] == "slo_burn", bundle["rule"]
        # close()-time alarm backfill: the service ledger's summary
        # record must carry the run's slo_burn fire count
        fired = next(
            (rec.get("alarm_fired") for rec in
             map(json.loads, open(led)) if rec.get("kind") == "summary"
             and rec.get("alarm_fired")), None)
        assert fired and fired.get("slo_burn", 0) >= 1, fired
    finally:
        shutdown_plane()
        shutil.rmtree(tmp, ignore_errors=True)
    return (f"scraped {len(series)} live series; slo_burn tripped, "
            f"postmortem bundle round-trips ({bundle['reason']})")


def main():
    from commefficient_tpu.utils import setup_compile_cache
    setup_compile_cache()
    print(f"devices: {jax.devices()}")
    checks = [("bf16_flagship_round", bf16_round_trains),
              ("probe_smoke", probe_smoke),
              ("quant_smoke", quant_smoke),
              ("overlap_smoke", overlap_smoke),
              ("async_smoke", async_smoke),
              ("service_smoke", service_smoke),
              ("autopilot_smoke", autopilot_smoke),
              ("audit_smoke", audit_smoke),
              ("flowlint_smoke", flowlint_smoke),
              ("trace_smoke", trace_smoke),
              ("mesh2d_smoke", mesh2d_smoke),
              ("elastic_smoke", elastic_smoke),
              ("flash_attention_parity", flash_attention_parity),
              ("gqa_kernel_parity", gqa_kernel_parity),
              ("mla_kernel_parity", mla_kernel_parity),
              ("moe_pool_parity", moe_pool_parity),
              ("chaos_smoke", chaos_smoke),
              ("dp_smoke", dp_smoke),
              ("live_smoke", live_smoke)]
    asked = sys.argv[1:]
    unknown = set(asked) - {name for name, _ in checks}
    if unknown:
        sys.exit(f"no such check: {sorted(unknown)}")
    for name, fn in checks:
        if not asked or name in asked:
            check(name, fn)
    if FAILED:
        print(f"\n{len(FAILED)} check(s) failed: {FAILED}")
        sys.exit(1)
    print("\nall hardware checks passed")


if __name__ == "__main__":
    main()

"""Standing proof that the FetchSGD training path starts on the TPU.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU

One process, which holds the chip for its whole life and never starts
another. Three legs, each checked by the repo's own means:

- kernels: every Pallas kernel ``ops/`` ships, compiled by Mosaic (not
  interpreted) at flagship shapes, against its XLA twin at the
  tolerances tests/test_pallas_sketch.py, test_pallas_topk.py and
  test_flce.py use;
- trainer, 1 chip: ResNet9 at full width in ``sketch`` mode at the
  flagship geometry through ``cv_train`` -> FedModel / FedOptimizer ->
  the jitted rounds, on seeded synthetic CIFAR-shaped data;
- trainer, 4 chips (when the host has them): the same command, whose
  per-epoch losses must agree with the 1-chip leg.

Each trainer leg also asserts *engagement* from the objects the
trainer built: the sketch backend resolved to ``pallas``, the compiled
client and server programs hold ``tpu_custom_call``, and state, batch
and aggregate sit on every device of the mesh. A run that passes on
the XLA twin has checked nothing.

Exits non-zero, printing no result line, without a TPU or if any leg
failed. On success the last stdout line is the result JSON. Times
printed here are set-up facts (compile vs the rest), not speeds.
"""

import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

FLAGSHIP = [
    "--dataset_name", "Synthetic", "--model", "ResNet9",
    "--mode", "sketch", "--error_type", "virtual",
    "--local_momentum", "0", "--virtual_momentum", "0.9",
    "--k", "50000", "--num_rows", "5", "--num_cols", "524288",
    "--num_workers", "8", "--local_batch_size", "8",
    "--num_epochs", "3", "--pivot_epoch", "1", "--lr_scale", "0.1",
    "--bf16", "--seed", "21"]
EPOCHS = int(FLAGSHIP[FLAGSHIP.index("--num_epochs") + 1])
D_RESNET9 = 6_584_000        # asserted against the model the trainer builds
D_GPT2 = 124_439_808         # GPT-2 124M
COLS, ROWS, K = 524288, 5, 50000
# 1-chip vs 4-chip per-epoch mean loss. Both run the same seeded
# batches through sum-of-sketches == sketch-of-sum; what differs is the
# f32 summation order of the bf16 model's gradients (8 clients in one
# backward vs 4 x 2) and of the 4 psum'd tables, which can move a
# near-tied top-k pick and then the trajectory. Measured on the v5e:
# at most 0.17% apart (PR 21); bf16 itself resolves 0.4%.
LOSS_RTOL_1V4 = 0.02

FAILED = []


def leg(name, fn):
    t0 = time.perf_counter()
    try:
        out = fn()
        print(f"PASS  {name}  ({time.perf_counter() - t0:.1f}s)",
              flush=True)
        return out
    except Exception as e:  # noqa: BLE001 — every leg reports, then exit 1
        FAILED.append(name)
        traceback.print_exc()
        print(f"FAIL  {name}: {type(e).__name__}: {e}", flush=True)
        return None


def _timed(jitted, *args):
    """(result, compile seconds, run seconds) — AOT, so the Mosaic
    compile is separated from the first execution."""
    t0 = time.perf_counter()
    exe = jitted.lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _report(what, tc, tr, detail=""):
    print(f"  ok  {what}: compile {tc:.1f}s first run {tr:.2f}s"
          + (f"; {detail}" if detail else ""), flush=True)


# --- kernel leg ------------------------------------------------------------

def sketch_kernels(d, rot_lanes):
    """sketch_pallas / estimates_pallas vs the XLA rotation sketch.
    Same hash streams: tables agree to summation order, recovery from
    a shared table is bit-exact."""
    from commefficient_tpu.ops.sketch import CountSketch
    kw = dict(d=d, c=COLS, r=ROWS, seed=7, rot_lanes=rot_lanes)
    xla = CountSketch(backend="xla", **kw)
    pal = CountSketch(backend="pallas", **kw)
    v = jax.random.normal(jax.random.PRNGKey(0), (d,), jnp.float32)
    tag = f"d={d} rot_lanes={rot_lanes}"

    tp, tc, tr = _timed(jax.jit(pal.sketch), v)
    tx = jax.jit(xla.sketch)(v)
    np.testing.assert_allclose(np.asarray(tp), np.asarray(tx),
                               rtol=1e-6, atol=1e-4)
    _report(f"sketch_pallas {tag}", tc, tr)

    ep, tc, tr = _timed(jax.jit(pal.estimates), tx)
    if d <= 1 << 24:
        ex = np.asarray(jax.jit(xla.estimates)(tx))
        how = "all coordinates"
    else:
        # the XLA twin materialises (r, padded_d) plus a sort of it;
        # at 124M that does not leave room on one chip. Its
        # point-query dual is bit-identical per coordinate
        # (tests/test_mesh2d.py test_estimates_at_bit_identical)
        idx = jnp.concatenate([
            jnp.arange(4096), d - 1 - jnp.arange(4096),
            jax.random.randint(jax.random.PRNGKey(1), (1 << 21,), 0, d)
        ]).astype(jnp.int32)
        ex = np.asarray(jax.jit(xla.estimates_at)(tx, idx))
        ep = ep[idx]
        how = f"{idx.size} sampled coordinates"
    np.testing.assert_array_equal(np.asarray(ep), ex)
    _report(f"estimates_pallas {tag}", tc, tr, f"bit-exact on {how}")


def sketch_quant_kernel(d, rot_lanes):
    """Emit + quantize vs quantize_local of the same Pallas table:
    identical bytes (tests/test_quant.py). int8 is the fused kernel;
    fp8 is the sketch kernel plus XLA's quantize, chosen by code
    (CountSketch.sketch_quantized) — here it only has to run."""
    from commefficient_tpu.ops.quant import quantize_local
    from commefficient_tpu.ops.sketch import CountSketch
    cs = CountSketch(d=d, c=COLS, r=ROWS, seed=7,
                     backend="pallas", rot_lanes=rot_lanes)
    v = jax.random.normal(jax.random.PRNGKey(2), (d,))
    for wire, fused in (("int8", "sketch_quant_pallas"),
                        ("fp8", "sketch_pallas + XLA quantize")):
        (qf, rmf), tc, tr = _timed(
            jax.jit(lambda x, w=wire: cs.sketch_quantized(x, w)), v)
        qu, rmu = jax.jit(
            lambda x, w=wire: quantize_local(cs.sketch(x), w))(v)
        assert qf.dtype.itemsize == 1, qf.dtype
        assert np.asarray(qf).tobytes() == np.asarray(qu).tobytes()
        np.testing.assert_array_equal(np.asarray(rmf), np.asarray(rmu))
        _report(f"{fused} {wire} d={d} rot_lanes={rot_lanes} "
                f"({cs.rot_form})", tc, tr)


def take_mask_kernel(d):
    """take_mask_pallas (through threshold_topk_mask_1d, as the server
    calls it) vs the XLA mask: the identical exactly-k set, with the
    threshold inside a run of ties that spans many grid steps."""
    from commefficient_tpu.ops.topk import threshold_topk_mask_1d
    n_tie, n_big = 4096, K - 1000
    rng = np.random.RandomState(3)
    pos = rng.permutation(np.unique(rng.randint(0, d, 2 * K)))
    assert pos.size >= n_big + n_tie
    sq = jax.random.uniform(jax.random.PRNGKey(3), (d,), jnp.float32,
                            0.0, 4.0)
    sq = sq.at[pos[:n_big]].set(jnp.asarray(
        10.0 + rng.uniform(0.0, 1.0, n_big), jnp.float32))
    # the k-th largest falls in a run of ties: 1000 of these 4096 win
    sq = sq.at[pos[n_big:n_big + n_tie]].set(5.0)
    fn = jax.jit(lambda x: threshold_topk_mask_1d(x, K))
    assert "tpu_custom_call" in fn.lower(sq).as_text()
    got, tc, tr = _timed(fn, sq)
    want = jax.jit(lambda x: threshold_topk_mask_1d(
        x, K, force_xla=True))(sq)
    got = np.asarray(got)
    assert int(got.sum()) == K, int(got.sum())
    np.testing.assert_array_equal(got, np.asarray(want))
    _report(f"take_mask_pallas d={d}", tc, tr)


def flce_kernels():
    """Fused tied-head cross-entropy, forward and backward, at GPT-2
    124M's head (bf16) vs the chunked path
    (tests/test_flce.py test_vmap_bf16_matches_chunked tolerances)."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    from commefficient_tpu.ops.flce_pallas import (fused_fallback_reason,
                                                   lm_nll_sums_fused)
    e, tm, c, v = 4, 256, 768, 50262
    reason = fused_fallback_reason(e, tm, c, v, jnp.bfloat16)
    assert reason is None, reason
    rng = np.random.RandomState(4)
    h = jnp.asarray(rng.randn(e, tm, c), jnp.float32)
    w = jnp.asarray(rng.randn(v, c) * 0.1, jnp.float32)
    lab = rng.randint(0, v, (e, tm))
    lab[0, :5] = -100
    lab = jnp.asarray(lab, jnp.int32)

    def mean_nll(fn):
        def f(h, w):
            sn, sv = fn(h, w, lab, jnp.bfloat16)
            return jnp.sum(sn / jnp.maximum(sv, 1.0))
        return jax.jit(jax.value_and_grad(f, (0, 1)))

    fused = mean_nll(lm_nll_sums_fused)
    assert fused.lower(h, w).as_text().count("tpu_custom_call") >= 2
    (l1, g1), tc, tr = _timed(fused, h, w)
    l0, g0 = mean_nll(lm_nll_sums_chunked)(h, w)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-2)
    for a, b in zip(g0, g1):
        scale = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale,
                                   rtol=0, atol=1e-2)
    _report(f"flce fwd+bwd {e}x{tm}x{c}x{v} bf16", tc, tr)


def kernel_legs():
    """One leg per kernel and geometry, so one refusal by Mosaic does
    not hide what the others do."""
    for d in (D_RESNET9, D_GPT2):
        for rl in (0, 1024):
            leg(f"kernel sketch+estimates d={d} rot_lanes={rl}",
                lambda d=d, rl=rl: sketch_kernels(d, rl))
    # the rolled row loop, and the addressed one (GPT-2's 238 chunks)
    for d, rl in ((D_RESNET9, 0), (D_GPT2, 1024)):
        leg("kernel sketch_quantized int8 (fused) + fp8 (unfused) "
            f"d={d} rot_lanes={rl}",
            lambda d=d, rl=rl: sketch_quant_kernel(d, rl))
    for d in (D_RESNET9, D_GPT2):
        leg(f"kernel take_mask d={d}", lambda d=d: take_mask_kernel(d))
    leg("kernel flce fwd+bwd", flce_kernels)


# --- trainer legs ----------------------------------------------------------

def _device_sets(tree):
    return [len(s.device_set) for s in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda a: a.sharding, tree))]


def trainer_leg(n_dev):
    from commefficient_tpu.core.rounds import args2sketch, round_plan
    from commefficient_tpu.telemetry.core import (compile_delta,
                                                  compile_mark)
    from commefficient_tpu.train import cv_train

    mark, t0 = compile_mark(), time.perf_counter()
    run = cv_train.run(FLAGSHIP + ["--num_devices", str(n_dev)])
    wall = time.perf_counter() - t0
    events, compile_s = compile_delta(mark)
    rows, model, opt = run.results, run.model, run.opt
    args = model.args

    # the run itself
    assert not model.diverged
    assert len(rows) == EPOCHS, f"{len(rows)} epoch rows, not {EPOCHS}"
    losses = [(r["train_loss"], r["test_loss"]) for r in rows]
    assert np.isfinite(losses).all(), losses
    assert losses[-1][0] < losses[0][0], \
        f"train loss did not fall: {losses}"
    assert args.grad_size == D_RESNET9, args.grad_size
    assert args.device == "tpu", args.device

    # engagement: which operator and which programs did that
    backend = args2sketch(args)._resolve_backend()
    assert backend == "pallas", f"sketch backend resolved to {backend!r}"
    rot_lanes = round_plan(args)["sketch"]["rot_lanes"]
    var = model._variants.get(model._variant_key)
    client = var.round_fn.lower(*model._round_abstract).compile()
    n_client = client.as_text().count("tpu_custom_call")
    assert n_client >= 1, "no Mosaic kernel in the compiled client round"
    _, cs_in, batch_in, ids_in = model._round_abstract[:4]
    agg_sh = client.output_shardings.aggregated

    def like(a):  # uncommitted arrays stay unplaced, as in the real call
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if a.committed else None)

    server = opt._server_round.lower(
        like(model.ps_weights),
        jax.tree_util.tree_map(like, opt.server_state),
        jax.ShapeDtypeStruct(tuple(args.transmit_shape), jnp.float32,
                             sharding=agg_sh),
        jax.ShapeDtypeStruct((), jnp.float32), None, ids_in,
        like(opt._noise_rng)).compile()
    n_server = server.as_text().count("tpu_custom_call")
    assert n_server >= 1, "no Mosaic kernel in the compiled server round"

    # placement, from the arrays' own shardings
    placed = {
        "batch": _device_sets(batch_in),
        "client_ids": _device_sets(ids_in),
        "client_states_in": _device_sets(cs_in),
        "client_states_out": _device_sets(model.client_states),
        "aggregate": [len(agg_sh.device_set)],
        "ps_weights": _device_sets(model.ps_weights),
        "server_state": _device_sets(opt.server_state),
    }
    for what, sets in placed.items():
        assert all(n == n_dev for n in sets), \
            f"{what} spans {sets} devices, mesh has {n_dev}"
    assert model.mesh.devices.size == n_dev

    print(f"  ok  {n_dev}-chip trainer: sketch backend {backend}, "
          f"rot_lanes auto -> {rot_lanes}, tpu_custom_call client "
          f"{n_client} server {n_server}, loader "
          f"{type(run.train_loader).__name__}")
    print(f"      placement (devices per array): {placed}"
          + ("  [no per-client state is allocated in this mode]"
             if not placed["client_states_out"] else ""))
    print(f"      losses (train, test) per epoch: "
          f"{[(round(a, 4), round(b, 4)) for a, b in losses]}")
    print(f"      wall {wall:.1f}s; trace + lower + compile "
          f"{compile_s:.1f}s ({events} jax events, which nest: a bound "
          f"on set-up, not a share of wall); epoch train_time "
          f"{[round(r['train_time'], 1) for r in rows]}s", flush=True)
    return losses


def main():
    from commefficient_tpu.utils import setup_compile_cache
    cache = setup_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    import importlib.metadata as md
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    print(f"chip_smoke: platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} compile_cache={cache}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports platform "
              f"{dev.platform!r} — nothing was run", file=sys.stderr)
        return 2

    kernel_legs()
    one = leg("trainer_1chip", lambda: trainer_leg(1))
    if device["count"] >= 4:
        four = leg("trainer_4chip", lambda: trainer_leg(4))
        if one is not None and four is not None:
            def agree():
                np.testing.assert_allclose(four, one,
                                           rtol=LOSS_RTOL_1V4)
                print(f"  ok  4-chip per-epoch losses within "
                      f"{LOSS_RTOL_1V4:.0%} of 1-chip")
            leg("trainer_1chip_vs_4chip", agree)
    else:
        print(f"SKIP  trainer_4chip: {device['count']} device(s)")

    if FAILED:
        print(f"chip_smoke: FAILED legs: {FAILED}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

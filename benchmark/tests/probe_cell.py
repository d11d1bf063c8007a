#!/usr/bin/env python3
"""Readings of a causal-LM cell that the harness does not take, by hand,
on the chip (``builders/lm.py`` cells only; ``--rehearse``: the tiny
presets on any backend):

    python3 benchmark/tests/probe_cell.py lr --workload <cell> --seed <n> \
        --lr_scale 0.1 --rounds 40
        the cell's rounds at another ``--lr_scale``, no comparison: the
        loss by round and whether any was non-finite (the cell's LR is
        the largest that stayed finite, with room: PERF.md section 6)

    python3 benchmark/tests/probe_cell.py flips --workload <cell> --seed <n>
        how often the program's top-k expert selection (the cell's
        precision) differs from the float32 reference's, on the first
        round's batch at the initial weights, per expert layer

    python3 benchmark/tests/probe_cell.py trace --workload <cell> --seed <n> \
        [--rounds 4]
        the cell's rounds under the profiler, read by the benchmark's
        own trace readers: the harness's three traced seconds hold two
        or three of a 0.9 s round, and with a longer one none. Prints
        the device time by scope, the largest operations and the model
        scopes; the readers drop the last traced round
"""

import argparse
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def _cell(name):
    from benchmark.run import load, read_json
    cell = read_json(ROOT, "benchmark", "workloads", name + ".json")
    config = read_json(ROOT, "benchmark", "configs",
                       cell["config"] + ".json")
    cell["num_devices"] = 1
    return (cell, config, load("reference", config["reference"]),
            load("builders", config["builder"]))


def _workdir(a):
    path = os.path.join(ROOT, "benchmark", ".cache",
                        f"probe-{a.workload}-{a.seed}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def lr(a):
    import numpy as np
    run, work = _build(a)
    it, losses = iter(run.loader), []
    for _ in range(a.rounds):
        per_client, w, *_ = run.step(next(it))
        losses.append(float(np.sum(per_client * w) / max(w.sum(), 1.0)))
    print(json.dumps({"lr_scale": a.lr_scale, "seed": a.seed,
                      "non_finite": sum(not math.isfinite(x)
                                        for x in losses),
                      "losses": [round(x, 4) for x in losses]}))
    shutil.rmtree(work, ignore_errors=True)


def _reference_selections(ref, params, ids, spec):
    """The reference's trunk and MTP module walked layer by layer, the
    (tokens, k) selection of each expert layer kept."""
    import jax.numpy as jnp
    q, eps = (lambda x: x), float(spec["rms_norm_eps"])
    tops = {}

    def block(name, p, h):
        h = h + ref._mla(p["attn"], ref._rms(h, p["attn_norm"], eps),
                         spec, q)
        x = ref._rms(h, p["ffn_norm"], eps)
        if "moe" not in p:
            return h + ref._swiglu(x, p["mlp"], q)
        tops[name] = ref.route(p["moe"], x.reshape(-1, x.shape[-1]),
                               spec)[0]
        return h + ref._moe(p["moe"], x, spec, q)

    h = params["embed"][ids]
    for i in range(int(spec["num_hidden_layers"])):
        h = block(f"layer_{i}", params[f"layer_{i}"], h)
    for i in range(int(spec["num_nextn_predict_layers"])):
        m = params[f"mtp_{i}"]
        x = jnp.concatenate(
            [ref._rms(h[:, :-1], m["hnorm"], eps),
             ref._rms(params["embed"][ids[:, 1:]], m["enorm"], eps)],
            axis=-1) @ m["eh_proj"]
        block(f"mtp_{i}", m["block"], x)
    return tops


def flips(a):
    import dataclasses

    import jax
    import numpy as np
    from benchmark.lib import fabricate_tokens
    from commefficient_tpu.data.fed_tokens import FedTokens
    from commefficient_tpu.models.joyai import JoyAIFlashLM
    cell, config, ref, builder = _cell(a.workload)
    work = _workdir(a)
    data = dict(cell["data"])
    if a.rehearse:
        data.update(cell["rehearse"]["data"])
    getattr(fabricate_tokens, data.pop("kind"))(work, a.seed, **data)
    ds = FedTokens(work)
    B = cell["local_batch_size"]
    from commefficient_tpu.config import parse_args
    args = parse_args(default_lr=4e-2, argv=builder._flags(
        cell, config, a.rehearse, work, work))
    module, spec = builder._module(args, config)
    module = JoyAIFlashLM(dataclasses.replace(module.cfg, remat=False))
    params = jax.device_put(builder.host_params(
        ref, spec, a.seed % 2147483629)())
    k = int(spec["num_experts_per_tok"])
    lo, hi = spec["expert_offset"], spec["expert_offset"] \
        + spec["n_routed_experts"]

    @jax.jit
    def program(p, ids):
        _, state = module.apply({"params": p}, ids,
                                mutable=["intermediates"])
        inter = state["intermediates"]
        out = {n: v["moe"]["top"][0] for n, v in inter.items()
               if "moe" in v}
        out.update({n: v["block"]["moe"]["top"][0]
                    for n, v in inter.items() if "block" in v})
        return out

    @jax.jit
    def reference(p, ids):
        with jax.default_matmul_precision("highest"):
            return _reference_selections(ref, p, ids, spec)

    tally = {}
    for c in range(a.clients):
        ids = ds.sequences(np.arange(c * ds.per_client,
                                     c * ds.per_client + B))
        got, want = program(params, ids), reference(params, ids)
        for name in sorted(want):
            g = np.sort(np.asarray(got[name]), axis=-1).reshape(B, -1, k)
            w = np.sort(np.asarray(want[name]), axis=-1).reshape(B, -1, k)
            # the program's MTP block has T positions, the last unused
            g = g[:, :w.shape[1]].reshape(-1, k)
            w = w.reshape(-1, k)
            same = np.array([len(np.intersect1d(x, y))
                             for x, y in zip(g, w)])
            t = tally.setdefault(name, np.zeros(5))
            t += [len(w), (same < k).sum(), (k - same).sum(),
                  ((w >= lo) & (w < hi)).sum(),
                  sum(len(np.setdiff1d(y[(y >= lo) & (y < hi)], x))
                      for x, y in zip(g, w))]
    for name, (n, tok, picks, held, held_lost) in tally.items():
        print(json.dumps({
            "layer": name, "tokens": int(n),
            "tokens_with_another_selection_%": 100.0 * tok / n,
            "picks_that_differ_%": 100.0 * picks / (n * k),
            "picks_on_held_experts": int(held),
            "of_them_not_picked_by_the_program_%":
                100.0 * held_lost / max(held, 1)}))
    tot = sum(tally.values())
    print("selection flips, all expert layers: "
          f"{100.0 * tot[1] / tot[0]:.3f} % of (token, layer) pairs, "
          f"{100.0 * tot[2] / (tot[0] * k):.3f} % of picks, "
          f"{100.0 * tot[4] / max(tot[3], 1):.3f} % of the picks on "
          "experts held here")
    shutil.rmtree(work, ignore_errors=True)


def _build(a):
    """The cell's ``FedRun`` as ``run.py`` builds it, and its work
    directory."""
    from commefficient_tpu.utils import setup_compile_cache
    cell, config, ref, builder = _cell(a.workload)
    if a.lr_scale is not None:
        flags = cell["flags"]
        flags[flags.index("--lr_scale") + 1] = str(a.lr_scale)
    if a.rehearse:
        cell.update({k: v for k, v in cell["rehearse"].items()
                     if k != "data"})
    else:
        setup_compile_cache()
    work = _workdir(a)
    return builder.build(cell, config, ref, a.seed % 2147483629, work,
                         rehearse=a.rehearse), work


def trace(a):
    import jax
    from benchmark.lib import modelscopes, scopes, tracesum
    from benchmark.run import WARMUP_ROUNDS, ListSink
    from commefficient_tpu.telemetry import trace as markers
    run, work = _build(a)
    sink = ListSink()
    run.model.telemetry.add_sink(sink)
    it = iter(run.loader)

    def one_round():
        t = time.perf_counter()
        with run.model.telemetry.span("sampler"):
            batch = next(it)
        losses, *_ = run.step(batch)
        return time.perf_counter() - t, float(losses.mean())

    for _ in range(WARMUP_ROUNDS):
        one_round()
    print("untraced rounds (s, loss):", [one_round() for _ in range(3)])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(work, "trace"),
                             profiler_options=opts)
    markers.set_tracing(True)
    print("traced rounds (s, loss):",
          [one_round() for _ in range(a.rounds)])
    jax.block_until_ready(run.model.ps_weights)
    markers.set_tracing(False)
    jax.profiler.stop_trace()
    run.model.telemetry.close()
    print("memory_stats:", json.dumps(jax.devices()[0].memory_stats()))
    print("counters of the last record:", json.dumps(
        {k: v for k, v in sink.records[-1]["counters"].items()
         if k.startswith("moe.")}))
    ctx = {"trace_dir": os.path.join(work, "trace")}
    scopes.scope_seconds(ctx)
    for names in (("moe_route", "moe_experts", "moe_combine", "ragged-dot"),
                  ("moe_route",), ("moe_experts",), ("moe_combine",),
                  ("ragged-dot",), ("mla_attn",), ("mtp",), ("lm_head",)):
        print("model scope", "+".join(names),
              modelscopes.scopes_ms(ctx, names), "ms a round")
    for needle in ("client_round", "server_round"):
        sec = tracesum.module_seconds(ctx, needle)
        print("module", needle, None if sec is None else
              1e3 * sec / max(tracesum.traced_rounds(ctx), 1), "ms a round")
    shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("lr", "flips", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lr_scale", type=float, default=None,
                    help="in place of the cell's own")
    ap.add_argument("--rounds", type=int, default=None,
                    help="default: 40 (lr), 4 (trace)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets, any backend")
    a = ap.parse_args()
    if a.rounds is None:
        a.rounds = 4 if a.what == "trace" else 40
    sys.exit({"lr": lr, "flips": flips, "trace": trace}[a.what](a))

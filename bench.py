"""Headline benchmark: federated client-updates/sec, ResNet9/CIFAR10
config at a lane-aligned twin of the reference's sketch geometry (see
below — part of the speedup vs the XLA path is that geometry choice).

Runs the full FetchSGD round on a TPU and exits non-zero without one
(a CPU timing is not this metric): ResNet9 (~6.6M params), 8
clients/round x local batch 8, count-sketch 5 rows x 524288 cols (2^19
— the lane-aligned twin of the reference's 500000 default, within 5%
of the same compression ratio; alignment engages the fused Pallas
kernels) + unsketch k=50k + server step.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``vs_baseline`` is the ratio to BASELINE_CLIENTS_PER_SEC, an estimate
of the reference PyTorch implementation's single-A100 throughput on
the same config (the repo publishes no numbers — BASELINE.md; estimate
derived from per-round fwd/bwd + CSVec cost at batch 8).
"""

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates,
                                           build_client_round,
                                           build_server_round,
                                           round_plan)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.models import get_model
from commefficient_tpu.ops.vec import flatten_params
from commefficient_tpu.telemetry import clock
from commefficient_tpu.train.cv_train import make_compute_loss

BASELINE_CLIENTS_PER_SEC = 60.0  # est. reference single-A100 (see doc)

W, B, NUM_CLIENTS, ROUNDS = 8, 8, 100, 100


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", type=str, default="",
                    help="append the result as a telemetry JSONL bench "
                         "record (the stdout line is unchanged)")
    bench_args = ap.parse_args(argv)
    from commefficient_tpu.utils import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX reports platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    cfg = Config(mode="sketch", error_type="virtual", local_momentum=0.0,
                 virtual_momentum=0.9, weight_decay=5e-4,
                 num_workers=W, local_batch_size=B,
                 k=50000, num_rows=5, num_cols=524288, num_blocks=20,
                 dataset_name="CIFAR10", seed=21,
                 # EXACT selection: since round 3 the threshold-select
                 # path (nibble search + fused Pallas take-mask,
                 # ops/topk.py) makes exact recovery FASTER than
                 # approx_max_k at this scale (6.5 vs 9.4 ms/round) —
                 # the headline runs the reference-parity default
                 approx_topk=False)

    module = get_model("ResNet9")(num_classes=10, dtype=jnp.bfloat16)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 32, 32, 3)))["params"]
    flat, unravel = flatten_params(params)
    cfg.grad_size = int(flat.size)

    compute_loss = make_compute_loss(module)

    def loss_tree(p, batch):
        return compute_loss(p, batch, cfg)

    client_round = jax.jit(build_client_round(
        cfg, None, B, tree_loss=loss_tree, unravel=unravel))
    server_round = jax.jit(build_server_round(cfg))

    rng = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rng.randn(W, B, 32, 32, 3).astype(np.float32)),
        "y": jnp.asarray(rng.randint(0, 10, (W, B)).astype(np.int32)),
        "mask": jnp.ones((W, B), jnp.float32),
    }
    ids = jnp.arange(W, dtype=jnp.int32)
    ps = flat
    cs = ClientStates.init(cfg, NUM_CLIENTS, ps)
    ss = ServerState.init(cfg)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def run_rounds(ps, ss):
        """ROUNDS federated rounds chained in one program: device
        throughput with no per-round dispatch in the number. Returns a
        device-computed scalar checksum so forcing completion ships 4
        bytes, not the 26 MB weight vector."""
        def body(r, carry):
            ps, ss = carry
            res = client_round(ps, cs, batch, ids,
                               jax.random.fold_in(key, r), 1.0)
            ps, ss, _, _, _ = server_round(ps, ss, res.aggregated,
                                        jnp.float32(0.1))
            return ps, ss
        ps, ss = jax.lax.fori_loop(0, ROUNDS, body, (ps, ss))
        return ps, ss, jnp.sum(ps)

    # warmup/compile
    w_ps, w_ss, w_sum = run_rounds(ps, ss)
    assert np.isfinite(float(w_sum))

    # median of 3 timed repetitions
    times = []
    for _ in range(3):
        t0 = clock.tick()
        _, _, checksum = run_rounds(ps, ss)
        float(checksum)
        times.append(clock.tick() - t0)
    dt = sorted(times)[1]

    clients_per_sec = W * ROUNDS / dt
    line = {
        "metric": "client_updates_per_sec_resnet9_sketch",
        "value": round(clients_per_sec, 2),
        "unit": "clients/s",
        "vs_baseline": round(clients_per_sec / BASELINE_CLIENTS_PER_SEC,
                             3),
    }
    # the stdout line is the harness contract — it stays exactly as-is;
    # --ledger additionally appends schema-v1 records for
    # scripts/telemetry_report.py
    print(json.dumps(line))
    if bench_args.ledger:
        from commefficient_tpu.telemetry import (JSONLSink,
                                                 make_bench_record,
                                                 make_meta_record)
        sink = JSONLSink(bench_args.ledger)
        sink.write(make_meta_record(
            bench="bench.py", rounds=ROUNDS, workers=W,
            local_batch_size=B, plan=round_plan(cfg)))
        sink.write(make_bench_record(
            line["metric"], line["value"], line["unit"],
            vs_baseline=line["vs_baseline"],
            round_times_s=[round(t, 4) for t in times],
            platform=dev.platform, device_kind=dev.device_kind,
            device_count=jax.device_count()))
        sink.close()
        # run manifest: makes this bench discoverable by
        # scripts/perf_gate.py --runs_dir / telemetry_report --runs_dir
        from commefficient_tpu.telemetry import registry
        registry.maybe_write_manifest(
            bench_args, bench={line["metric"]: line},
            extra={"bench_config": registry.config_dict(cfg),
                   "rounds": ROUNDS, "workers": W})


if __name__ == "__main__":
    main()

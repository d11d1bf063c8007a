"""GPT-2 flagship perf bench: federated FetchSGD round on GPT-2 124M.

Measures ms/round and tokens/s for the reference's LM workload
(gpt2_train.py round loop) at configurable batch geometry, with an
optional xplane profile parsed into a per-op time breakdown (see
BENCHMARKS.md).

Usage:
  python scripts/gpt2_bench.py [--clients 4] [--examples 2]
      [--candidates 2] [--seq 256] [--rounds 10] [--remat]
      [--mode sketch|uncompressed|bare] [--profile DIR] [--reps 3]
  --mode bare = the non-federated control: plain pytree train step at
  the same geometry (no flat vector, no compression, no accounting).
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def build(args):
    import dataclasses

    from commefficient_tpu.config import Config
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.ops.vec import flatten_params
    from commefficient_tpu.train.gpt2_train import \
        make_compute_loss_train

    cfg = Config(mode=args.mode, error_type="virtual",
                 local_momentum=0.0, virtual_momentum=0.9,
                 weight_decay=0.0, num_workers=args.clients,
                 local_batch_size=args.examples, k=50000, num_rows=5,
                 num_cols=524288, num_blocks=20,
                 dataset_name="PERSONA", seed=21,
                 approx_topk=not args.exact,
                 approx_recall=0.95, num_candidates=args.candidates,
                 lm_coef=1.0, mc_coef=1.0,
                 sketch_rot_lanes=args.rot_lanes,
                 tokens_per_chunk=args.tokens_per_chunk,
                 fused_ce=args.fused_ce)

    gcfg = GPT2Config(vocab_size=50262, n_positions=1024,
                      dtype=jnp.bfloat16, remat=args.remat,
                      attn_impl=args.attn_impl)
    module = GPT2DoubleHeads(gcfg)
    dummy = jnp.zeros((1, args.candidates, 8), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), dummy,
                         jnp.zeros((1, args.candidates), jnp.int32),
                         dummy)["params"]
    flat, unravel = flatten_params(params)
    cfg.grad_size = int(flat.size)

    compute_loss = make_compute_loss_train(module, cfg)

    def loss_tree(p, batch):
        return compute_loss(p, batch, cfg)

    client_round = jax.jit(build_client_round(
        cfg, None, args.examples,
        tree_loss=loss_tree, unravel=unravel))
    server_round = jax.jit(build_server_round(cfg))

    rng = np.random.RandomState(0)
    W, B, N, T = args.clients, args.examples, args.candidates, args.seq
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, 50000, (W, B, N, T)), jnp.int32),
        "token_type_ids": jnp.asarray(
            rng.randint(0, 2, (W, B, N, T)), jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, 50000, (W, B, N, T)), jnp.int32),
        "mc_token_ids": jnp.full((W, B, N), T - 1, jnp.int32),
        "mc_labels": jnp.full((W, B), N - 1, jnp.int32),
        "mask": jnp.ones((W, B), jnp.float32),
    }
    ids = jnp.arange(W, dtype=jnp.int32)
    cs = ClientStates.init(cfg, max(cfg.num_workers, 8), flat)
    ss = ServerState.init(cfg)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def run_rounds(ps, ss):
        def body(r, carry):
            ps, ss = carry
            res = client_round(ps, cs, batch, ids,
                               jax.random.fold_in(key, r), 1.0)
            ps, ss, _, _, _ = server_round(ps, ss, res.aggregated,
                                           jnp.float32(0.01))
            return ps, ss

        ps, ss = jax.lax.fori_loop(0, args.rounds, body, (ps, ss))
        return ps, ss, jnp.sum(ps)

    return run_rounds, flat, ss, cfg


def build_bare(args):
    """Control experiment (round-2 review weak #7): the BARE model
    train step — no federation, no flat vector, no compression, no
    byte accounting. Same geometry, same loss math (chunked vocab CE),
    pytree params, momentum-SGD update. federated_overhead =
    federated ms/round − bare ms/step; if that matches the known
    sketch-pipeline constant, the \"42% MFU is the model's limit\"
    claim is a measurement, not an inference."""
    from commefficient_tpu.config import Config
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.train.gpt2_train import \
        make_compute_loss_train

    cfg = Config(mode="uncompressed", error_type="none",
                 local_momentum=0.0, virtual_momentum=0.9,
                 weight_decay=0.0, num_workers=args.clients,
                 local_batch_size=args.examples,
                 dataset_name="PERSONA", seed=21,
                 num_candidates=args.candidates,
                 tokens_per_chunk=args.tokens_per_chunk,
                 fused_ce=args.fused_ce)
    gcfg = GPT2Config(vocab_size=50262, n_positions=1024,
                      dtype=jnp.bfloat16, remat=args.remat,
                      attn_impl=args.attn_impl)
    module = GPT2DoubleHeads(gcfg)
    dummy = jnp.zeros((1, args.candidates, 8), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), dummy,
                         jnp.zeros((1, args.candidates), jnp.int32),
                         dummy)["params"]
    compute_loss = make_compute_loss_train(module, cfg)

    rng = np.random.RandomState(0)
    W, B, N, T = args.clients, args.examples, args.candidates, args.seq
    E = W * B  # one flat batch: the W axis is just batch here
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, 50000, (E, N, T)), jnp.int32),
        "token_type_ids": jnp.asarray(
            rng.randint(0, 2, (E, N, T)), jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, 50000, (E, N, T)), jnp.int32),
        "mc_token_ids": jnp.full((E, N), T - 1, jnp.int32),
        "mc_labels": jnp.full((E,), N - 1, jnp.int32),
        "mask": jnp.ones((E,), jnp.float32),
    }

    def loss_fn(p):
        return compute_loss(p, batch, cfg)[0]

    grad_size = sum(int(np.prod(l.shape)) for l in
                    jax.tree_util.tree_leaves(params))
    cfg.grad_size = grad_size

    @jax.jit
    def run_rounds(params, mom):
        def body(r, carry):
            p, m = carry
            g = jax.grad(loss_fn)(p)
            m = jax.tree_util.tree_map(
                lambda mm, gg: 0.9 * mm + gg, m, g)
            p = jax.tree_util.tree_map(
                lambda pp, mm: pp - 0.01 * mm, p, m)
            return p, m

        p, m = jax.lax.fori_loop(0, args.rounds, body, (params, mom))
        checksum = sum(jnp.sum(l) for l in
                       jax.tree_util.tree_leaves(p)[:1])
        return p, m, checksum

    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    return run_rounds, params, mom, cfg


def parse_xplane(logdir):
    """Aggregate per-op durations from the trace's xplane.pb (the
    tensorboard converter is broken in this image)."""
    import glob
    import os
    os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(
        logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return {}
    xspace = xplane_pb2.XSpace()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        xspace.ParseFromString(f.read())
    totals = {}
    for plane in xspace.planes:
        if "TPU" not in plane.name and "/device" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        for line in plane.lines:
            for ev in line.events:
                name = ev_meta[ev.metadata_id].name
                totals[name] = totals.get(name, 0) \
                    + ev.duration_ps / 1e9  # ms
    return dict(sorted(totals.items(), key=lambda kv: -kv[1])[:40])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--examples", type=int, default=2)
    ap.add_argument("--candidates", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--exact", action="store_true",
                    help="exact top-k selection (the trainer default) "
                         "instead of approx_max_k 0.95")
    ap.add_argument("--mode", default="sketch")
    ap.add_argument("--attn_impl", default="xla",
                    choices=["xla", "flash"])
    ap.add_argument("--rot_lanes", type=int, default=-1,
                    help="-1 = the trainer's auto default (resolves "
                    "per backend/geometry, core/rounds.py "
                    "resolve_rot_lanes); 0 forces full-granularity "
                    "rotations for A/Bs against it")
    ap.add_argument("--fused_ce", default="off",
                    choices=["auto", "on", "off"],
                    help="fused-linear-CE vocab head (ops/"
                    "flce_pallas.py); auto = on at TPU backend")
    ap.add_argument("--tokens_per_chunk", type=int, default=0,
                    help="vocab-CE chunk budget (0 = auto 1024); the "
                    "task-5 sweep knob — larger chunks trade logits "
                    "VMEM/HBM for fewer dWte carry accumulations")
    ap.add_argument("--profile", type=str, default=None)
    ap.add_argument("--ledger", type=str, default="",
                    help="append the result as a telemetry JSONL "
                    "bench record (stdout line unchanged)")
    args = ap.parse_args()

    if args.mode == "bare":
        run_rounds, ps, ss, cfg = build_bare(args)
    else:
        run_rounds, ps, ss, cfg = build(args)

    w_ps, w_ss, w_sum = run_rounds(ps, ss)
    assert np.isfinite(float(w_sum)), "diverged/NaN in warmup"

    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        _, _, checksum = run_rounds(ps, ss)
        float(checksum)
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[len(times) // 2]
    ms_round = dt / args.rounds * 1e3
    tokens = (args.clients * args.examples * args.candidates
              * args.seq)
    out = {
        "ms_per_round": round(ms_round, 2),
        "tokens_per_round": tokens,
        "tokens_per_sec": round(tokens / (ms_round / 1e3)),
        "clients_per_sec": round(args.clients / (ms_round / 1e3), 1),
        # 6 * params * tokens fwd+bwd FLOPs (approx, non-remat)
        "model_tflops_per_sec": round(
            6 * cfg.grad_size * tokens / (ms_round / 1e3) / 1e12, 1),
        "geometry": vars(args),
    }
    print(json.dumps(out))
    if args.ledger:
        from commefficient_tpu.telemetry import append_bench_record
        append_bench_record(args.ledger, "gpt2_bench", out,
                            backend=jax.default_backend())

    if args.profile:
        with jax.profiler.trace(args.profile):
            _, _, checksum = run_rounds(ps, ss)
            float(checksum)
        breakdown = parse_xplane(args.profile)
        per_round = {k: round(v / args.rounds, 3)
                     for k, v in breakdown.items()}
        print(json.dumps({"per_round_op_ms": per_round}, indent=1))


if __name__ == "__main__":
    main()

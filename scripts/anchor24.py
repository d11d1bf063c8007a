"""Discriminating long-horizon convergence anchor: the reference
24-epoch default schedule (PiecewiseLinear 0 -> lr 0.4 @ pivot 5 -> 0
@ 24, wd 5e-4, bf16 — reference utils.py:153-163, cv_train.py:394-406)
at the FetchSGD paper federation geometry (10 000 one-class clients ×
5 images, 100 workers/round), on the class-overlap Synthetic task
(--synthetic_separation 0.025: Bayes ceiling ~0.86,
FedSynthetic.bayes_accuracy) — sub-1.0 ceiling, so the anchor
discriminates accuracy instead of saturating from epoch 1 (round-3
review weak #1). Measured orderings (round 5; the logs are
``runs/anchor24_*``): at the SHARED reference peak (--lr_scale
0.4), true_topk ≈ sketch ≫ fedavg ≈ uncompressed ≫
local_topk-at-one-class (chance). The round-5 per-mode LR sweep
showed the dense-mode gap was an over-hot-LR artifact, not a
compression fact — the round-3/4 "top-k as denoiser" reading of
that gap is RETRACTED: at their own best peak (0.1) uncompressed
tails 0.281 and fedavg 0.290 vs sketch's 0.283, i.e. the paper's
"sketch ≈ uncompressed" quality parity holds once every mode runs
at its own best LR. What IS mode-robust: the top-k family tolerates
the reference 0.4 schedule (selection + error feedback damp the
effective step) while the dense updates diverge there
(uncompressed final test loss 2.10/3.55/3.75 at lr 0.1/0.2/0.4,
monotone in LR) — an
operational robustness advantage of sketch/true_topk, not a
quality gap.

Usage:
  python scripts/anchor24.py [--modes sketch,uncompressed,...]
      [--seed 21] [--epochs 24] [--logdir runs]
Runs modes sequentially (one chip), writes runs/anchor24_<mode>_s<seed>.log,
prints a final ordering summary with the Bayes ceiling.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MODE_FLAGS = {
    "sketch": ["--mode", "sketch", "--error_type", "virtual",
               "--local_momentum", "0", "--virtual_momentum", "0.9",
               "--k", "50000", "--num_rows", "5",
               "--num_cols", "524288"],
    "true_topk": ["--mode", "true_topk", "--error_type", "virtual",
                  "--local_momentum", "0", "--virtual_momentum", "0.9",
                  "--k", "50000"],
    "uncompressed": ["--mode", "uncompressed", "--error_type", "none",
                     "--local_momentum", "0",
                     "--virtual_momentum", "0.9"],
    "local_topk": ["--mode", "local_topk", "--error_type", "local",
                   "--local_momentum", "0.9", "--k", "50000"],
    "fedavg": ["--mode", "fedavg", "--error_type", "none",
               "--local_momentum", "0", "--virtual_momentum", "0.9",
               "--local_batch_size", "-1"],
}


def common_flags(args):
    flags = [
        "--dataset_name", "Synthetic",
        "--num_clients", str(args.num_clients),
        "--synthetic_per_class", "5000",
        "--synthetic_separation", str(args.separation),
        "--synthetic_num_val", "2000",
        "--num_workers", "100",
        "--num_epochs", str(args.epochs),
        "--lr_scale", str(args.lr_scale), "--pivot_epoch", "5",
        "--bf16",
        "--seed", str(args.seed),
    ]
    return flags


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--modes",
                    default="sketch,uncompressed,true_topk,"
                            "local_topk,fedavg")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--epochs", type=float, default=24)
    ap.add_argument("--separation", type=float, default=0.025)
    # the reference default schedule peaks at 0.4 — the right scale
    # for the top-k family here, but the round-4 review showed the
    # DENSE modes (uncompressed/fedavg) diverging late at it on this
    # task; sweep them at their own best LR before stating orderings
    ap.add_argument("--lr_scale", type=float, default=0.4)
    # local_topk's per-client dense error/momentum state is
    # (num_clients, d) f32 — 263 GB at the 10 000-client paper
    # geometry, infeasible for ANY single machine (the reference's
    # host-shm design included, fed_aggregator.py:116-129). Run that
    # mode at the largest fitting federation — 100 clients x 500
    # images with --extra "--client_chunk 10" (the 2 x 2.6 GB state
    # buffers still double-buffer through the scan carry; 250 clients
    # OOMed) — and footnote the geometry change.
    ap.add_argument("--num_clients", type=int, default=10000)
    ap.add_argument("--suffix", default="",
                    help="log-name suffix, e.g. _c100")
    ap.add_argument("--extra", default="",
                    help="extra cv_train flags appended to every "
                    "mode, e.g. '--client_chunk 10'")
    ap.add_argument("--logdir", default="runs")
    args = ap.parse_args()

    from commefficient_tpu.data.synthetic import FedSynthetic
    from commefficient_tpu.train import cv_train

    ceiling = FedSynthetic(
        "", "Synthetic", train=False, do_iid=False,
        num_clients=args.num_clients, per_class=5000, num_val=2000,
        separation=args.separation, seed=args.seed).bayes_accuracy()
    print(f"Bayes ceiling at separation {args.separation}: "
          f"{ceiling:.4f}", flush=True)

    os.makedirs(args.logdir, exist_ok=True)
    summary = {}
    for mode in args.modes.split(","):
        flags = common_flags(args) + MODE_FLAGS[mode]
        if mode != "fedavg":
            flags += ["--local_batch_size", "5"]
        if args.extra:
            import shlex
            flags += shlex.split(args.extra)
        # (fedavg's -1 = local SGD over the client's full 5-image
        # shard is in its MODE_FLAGS)
        log_path = os.path.join(
            args.logdir,
            f"anchor24_{mode}{args.suffix}_s{args.seed}.log")
        print(f"== {mode} -> {log_path}", flush=True)
        # stream to the file as the run goes: a mid-run kill keeps
        # the epochs so far instead of discarding a buffered log
        # line-buffered: the epoch rows land as they print (a
        # block-buffered redirect holds ~60 epochs back)
        with open(log_path, "w", buffering=1) as f:
            f.write(" ".join(flags) + "\n")
            f.flush()
            try:
                with contextlib.redirect_stdout(f):
                    results = cv_train.main(flags)
            except (KeyboardInterrupt, SystemExit):
                raise  # an interrupted sweep must stop, not continue
            except Exception as e:  # record divergence/abort, go on
                results = None
                f.write(f"\nABORTED: {e!r}\n")
        if results:
            tail = [r["test_acc"] for r in results[-5:]]
            summary[mode] = {
                "final_acc": results[-1]["test_acc"],
                # mean of the last 5 epochs: the ordering statistic —
                # robust to single-epoch jitter, unlike a lone final
                # accuracy (the fp-fragility that motivated this
                # anchor in the first place)
                "tail_acc": sum(tail) / len(tail),
                "best_acc": max(r["test_acc"] for r in results),
                "final_loss": results[-1]["train_loss"],
                "epochs": len(results),
            }
        else:
            summary[mode] = {"final_acc": float("nan"),
                             "tail_acc": float("nan"),
                             "best_acc": float("nan"),
                             "final_loss": float("nan"), "epochs": 0}
        print(f"   {mode}: {summary[mode]}", flush=True)

    print(json.dumps({"bayes_ceiling": ceiling, "seed": args.seed,
                      "separation": args.separation,
                      "modes": summary}), flush=True)


if __name__ == "__main__":
    sys.exit(main())

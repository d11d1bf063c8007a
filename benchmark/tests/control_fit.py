#!/usr/bin/env python3
"""The lower-precision control of a causal-LM cell in a process of its
own, by hand, on the chip:

    python3 benchmark/tests/control_fit.py --workload <cell> --seed <n>

``run.py --control`` follows the warm-up's rounds a second time, in the
configuration's ``control_precision``, while the first follow's result,
the program's observed change and the initial weights are all still
the host's: at d = 701M that read 48.0 GB of host memory where the run
without it reads 37.4 (PERF.md section 6, PR 32), and at d = 772M the
run without it already reads 43.8 of the machine's 48.3 GB (PR 34). The
control compares the reference with itself, float32 against the lower
precision, and needs nothing of the program: here the cell's token
streams are written from the seed, the trainer's own loader hands over
the warm-up's batches (with no model live it works on this thread),
the weights are the builder's ``host_params``, and ``follow`` runs
twice with the first result's weight change kept on disk in between.
Prints the four compared numbers against the cell's ``LIMITS`` as
``run.py`` does, and the host's peak. Needs a TPU (``--rehearse``: the
tiny presets on any backend).
"""

import argparse
import json
import os
import resource
import shutil
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import numpy as np
    from benchmark.builders.lm import _flags
    from benchmark.lib import fabricate_tokens
    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.run import (SEED_MODULUS, WARMUP_ROUNDS, load,
                               read_json)
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.core.rounds import args2sketch
    from commefficient_tpu.train import gpt2_train
    from commefficient_tpu.utils import PiecewiseLinear, steps_per_epoch

    if not a.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX sees {jax.devices()[0].platform}")
    cell = read_json(ROOT, "benchmark", "workloads", a.workload + ".json")
    config = read_json(ROOT, "benchmark", "configs",
                       cell["config"] + ".json")
    ref = load("reference", config["reference"])
    builder = load("builders", config["builder"])
    seed = a.seed % SEED_MODULUS
    work = os.path.join(ROOT, "benchmark", ".cache",
                        f"control-{a.workload}-{a.seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        data = dict(cell["data"])
        if a.rehearse:
            cell.update({k: v for k, v in cell["rehearse"].items()
                         if k != "data"})
            data.update(cell["rehearse"]["data"])
        kind = data.pop("kind")
        getattr(fabricate_tokens, kind)(os.path.join(work, "data"), seed,
                                        **data)
        args = parse_args(default_lr=4e-2, argv=_flags(
            cell, config, a.rehearse, os.path.join(work, "data"), work)
            + ["--num_devices", "1"])
        np.random.seed(args.seed)
        if args.do_test:   # gpt2_train.run's smoke-mode sketch
            args.k, args.num_cols = 10, 100
            args.num_rows = args.num_blocks = 1
        _, spec = builder._module(args, config)
        loader, _, train_ds = gpt2_train.get_data_loaders(args, None)
        it = iter(loader)
        batches = [{k: np.array(b[k]) for k in ("input_ids", "mask")}
                   for b in (next(it) for _ in range(WARMUP_ROUNDS))]
        loader.close()
        spe = steps_per_epoch(args.local_batch_size, train_ds,
                              args.num_workers)
        lr = PiecewiseLinear(
            [0, (args.schedule_epochs or args.num_epochs) * spe],
            [args.lr_scale, 0])
        lrs = [float(lr(t)) for t in range(WARMUP_ROUNDS)]
        params = builder.host_params(ref, spec, seed)()
        sizes = [int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(params)]
        args.grad_size = sum(sizes)
        sk = args2sketch(args)
        follow = dict(
            ref=ref, spec_model=spec, params=params, batches=batches,
            lrs=lrs, hyper={"k": int(args.k),
                            "rho": float(args.virtual_momentum),
                            "weight_decay": float(args.weight_decay),
                            "num_workers": int(args.num_workers)},
            sk=fr.SketchSpec(d=int(sk.d), c=int(sk.c), r=int(sk.r),
                             seed=int(sk.seed),
                             rot_lanes=int(sk.rot_lanes)))
        print(f"d = {sum(sizes)}; lrs {lrs}; tokens a round "
              f"{batches[0]['input_ids'].size}", flush=True)
        t = time.perf_counter()
        want = fr.follow(**follow)
        np.save(os.path.join(work, "want.npy"), want.pop("delta"))
        want["delta"] = np.load(os.path.join(work, "want.npy"),
                                mmap_mode="r")
        print(f"float32 follow took {time.perf_counter() - t:.1f} s",
              flush=True)
        t = time.perf_counter()
        ctl = fr.follow(**follow, precision=config["control_precision"])
        print(f"{config['control_precision']} follow took "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        nums = fr.numbers(ctl, want, sizes)
        print("control detail:", json.dumps(fr.detail(ctl, want)))
        rows = fr.verdict(nums, ref.LIMITS)
        for name, value, limit, ok in rows:
            print(f"control[{config['control_precision']}]: {name} = "
                  f"{value:.6g} (limit {limit:g}) "
                  f"{'passes' if ok else 'fails'}")
        print("control_correct:", json.dumps(all(ok for *_, ok in rows)))
        print("host_peak_GB", resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9)
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

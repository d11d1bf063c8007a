"""Assembles a ``train/cv_train.py`` run without training it.

A copy of ``cv_train.run()`` from ``parse_args`` to the LR schedule
(``train/cv_train.py:492-587``), stopping short of ``train()``, with
two differences: the data are written from the seed in the dataset's
own prepared layout first, and the initial weights are made by the
configuration's plain reference (``init_params``) and handed to
``FedModel`` in place of ``module.init``'s, so that program and
reference start from the same weights without either taking them from
the other. What only a change to the program can remove: this copy
(a ``build(argv)`` in ``cv_train``; PERF.md section 7).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


def build(cell, config, ref, seed, workdir, rehearse=False):
    from benchmark.lib import fabricate
    from benchmark.lib.fedrun import (FedRun, check_tree_matches,
                                      seeded_params, trainer_flags)
    from commefficient_tpu.config import num_classes_of_dataset, parse_args
    from commefficient_tpu.models import get_model
    from commefficient_tpu.runtime import FedModel, FedOptimizer, LambdaLR
    from commefficient_tpu.train import cv_train
    from commefficient_tpu.utils import PiecewiseLinear, steps_per_epoch

    data = dict(cell["data"])
    flags = trainer_flags(cell, config, rehearse)
    if rehearse:
        data.update(cell["rehearse"]["data"])
    dataset_dir = os.path.join(workdir, "data")
    kind = data.pop("kind")
    getattr(fabricate, kind)(dataset_dir, seed, **data)
    flags += ["--dataset_dir", dataset_dir, "--seed", str(config["program_seed"]),
              "--num_devices", str(cell["num_devices"])]

    args = parse_args(default_lr=cv_train.DEFAULT_LR, argv=flags)
    np.random.seed(args.seed)
    if args.do_test:   # cv_train.run's smoke-mode sketch
        args.k, args.num_cols = 10, 10
        args.num_rows = args.num_blocks = 1

    train_loader, _, train_ds = cv_train.get_data_loaders(args)
    if args.num_clients is None:
        args.num_clients = int(train_ds.num_clients)

    # build_model without its module.init: the weights are the benchmark's
    model_cls = get_model(args.model)
    kw = dict(num_classes=num_classes_of_dataset(args.dataset_name),
              do_batchnorm=args.do_batchnorm)
    if args.do_bf16:
        kw["dtype"] = jnp.bfloat16
    ref_spec = {k: config[k] for k in ("channels", "initial_channels",
                                       "num_classes")}
    if args.do_test:
        kw.update(model_cls.test_config(kw["num_classes"]))
        ref_spec["channels"] = kw["channels"]
    module = model_cls(**kw)
    make_params = seeded_params(ref, ref_spec, seed)
    params = make_params()
    check_tree_matches(params, jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 32, 32, 3)),
                            train=True)["params"]))

    model = FedModel(module, params,
                     cv_train.make_compute_loss(module, None), args,
                     padded_batch_size=train_loader.B)
    del params
    opt = FedOptimizer([{"lr": 1.0}], args)
    spe = steps_per_epoch(args.local_batch_size, train_ds,
                          args.num_workers)
    horizon = args.schedule_epochs or args.num_epochs
    lambda_step = PiecewiseLinear(
        [0, args.pivot_epoch * spe, horizon * spe],
        [0, args.lr_scale, 0])
    lr_scheduler = LambdaLR(opt, lambda x: lambda_step(x))

    def ref_batch(batch):
        return {k: np.array(batch[k]) for k in ("x", "y", "mask")}

    return FedRun(model=model, opt=opt, lr_scheduler=lr_scheduler,
                  loader=train_loader, args=args, ref_spec=ref_spec,
                  ref_batch=ref_batch, make_params=make_params,
                  zero_lr_hack=True)


def abstract(cell, config, ref):
    """(args, loss_tree, parameter shapes, batch shapes) of the cell at
    its real sizes, with nothing placed on a device: what
    ``tests/compile_v5e.py`` compiles for a described chip."""
    from benchmark.lib.fedrun import trainer_flags
    from commefficient_tpu.config import num_classes_of_dataset, parse_args
    from commefficient_tpu.models import get_model
    from commefficient_tpu.train import cv_train

    args = parse_args(default_lr=cv_train.DEFAULT_LR,
                      argv=trainer_flags(cell, config))
    module = get_model(args.model)(
        num_classes=num_classes_of_dataset(args.dataset_name),
        do_batchnorm=args.do_batchnorm,
        dtype=jnp.bfloat16 if args.do_bf16 else jnp.float32)
    compute_loss = cv_train.make_compute_loss(module, None)
    ref_spec = {k: config[k] for k in ("channels", "initial_channels",
                                       "num_classes")}
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), ref_spec))
    W, B = args.num_workers, args.local_batch_size
    batch = {"x": jax.ShapeDtypeStruct((W, B, 32, 32, 3), jnp.float32),
             "y": jax.ShapeDtypeStruct((W, B), jnp.int32),
             "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
    return (args, lambda p, b: compute_loss(p, b, args), shapes, batch)

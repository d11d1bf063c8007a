"""Assembles a ``train/gpt2_train.py`` run of ``--model OuroLM
--dataset_name TOKENS`` without training it.

``builders/smallthinker.py`` with Ouro's spec keys and config class (one
LM builder for all is a ``benchmark`` PR's: ROADMAP D2): a copy of
``gpt2_train.run()`` from ``parse_args`` to the LR schedule, stopping
short of ``train_gpt2()``; the token streams are written from the seed
first, ``--model_checkpoint`` is a directory that holds the cut's
``config.json`` only (the configuration file's own keys, ``model_type``
``ouro`` among them, which the trainer checks against ``--model``), and
the initial weights are the plain reference's ``init_params``, made on
the **host** a leaf at a time and handed to ``FedModel`` in place of
``module.init``'s: the same weights to both sides. The builder reckons
d from the shapes and holds the configuration's ``num_parameters`` to
it.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.builders.granite_hybrid import host_params
from benchmark.builders.lm import _flags

MODEL = "OuroLM"

_SPEC_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "layer_types",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "rms_norm_eps", "total_ut_steps",
    "entropy_beta", "initializer_range")


def _module(args, config):
    """``gpt2_train.build_causal_lm``'s module, without its init."""
    from commefficient_tpu.models.ouro import OuroConfig, OuroLM
    if args.do_test:
        cfg = OuroConfig.tiny()
        spec = cfg.reference_spec()
    else:
        cfg = OuroConfig.from_hf(config)
        spec = {k: config[k] for k in _SPEC_KEYS}
        if cfg.reference_spec() != spec:
            raise ValueError(f"the program's {cfg} is not the "
                             "configuration's architecture")
        if config["model_type"] != OuroLM.model_type:
            raise ValueError("the trainer would refuse this config.json: "
                             f"model_type {config['model_type']!r}")
    cfg = dataclasses.replace(
        cfg, dtype=jnp.bfloat16 if args.do_bf16 else jnp.float32,
        remat=bool(args.do_remat))
    return OuroLM(cfg), spec


def _counters(args):
    from commefficient_tpu.train import gpt2_train
    if args.model != MODEL or not gpt2_train.is_causal_lm(args):
        raise ValueError(
            f"builders/ouro.py assembles --model {MODEL}")
    return gpt2_train.causal_lm_file(args.model).COUNTERS


def build(cell, config, ref, seed, workdir, rehearse=False):
    # first, before any data is written: a program without the model
    # (the parent of the PR that brought it) fails here, at once
    from commefficient_tpu.models import ouro  # noqa: F401

    from benchmark.lib import fabricate_tokens
    from benchmark.lib.fedrun import FedRun, check_tree_matches
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.runtime import FedModel, FedOptimizer, LambdaLR
    from commefficient_tpu.train import gpt2_train
    from commefficient_tpu.utils import PiecewiseLinear, steps_per_epoch

    data = dict(cell["data"])
    if data["num_clients"] != config["num_clients"] or \
            data["stream_len"] != config["tokens_per_client"] or \
            data["vocab_size"] != config["vocab_size"]:
        raise ValueError("the cell's federation is not the configuration's")
    if rehearse:
        data.update(cell["rehearse"]["data"])
    dataset_dir = os.path.join(workdir, "data")
    model_dir = os.path.join(workdir, "model")
    os.makedirs(model_dir)
    kind = data.pop("kind")
    getattr(fabricate_tokens, kind)(dataset_dir, seed, **data)
    if not rehearse:
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(config, f)
    flags = _flags(cell, config, rehearse, dataset_dir, model_dir) + [
        "--num_devices", str(cell["num_devices"])]

    args = parse_args(default_lr=4e-2, argv=flags)
    np.random.seed(args.seed)
    counters = _counters(args)
    args.num_results_train = 1 + len(counters)
    if args.do_test:   # gpt2_train.run's smoke-mode sketch
        args.k, args.num_cols = 10, 100
        args.num_rows = args.num_blocks = 1

    module, ref_spec = _module(args, config)
    train_loader, _, train_ds = gpt2_train.get_data_loaders(args, None)
    if args.num_clients is None:
        args.num_clients = int(train_ds.num_clients)
    make_params = host_params(ref, ref_spec, seed)
    params = make_params()
    check_tree_matches(params, jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]))
    d = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(params))
    if not rehearse and d != config["num_parameters"]:
        raise ValueError(f"d = {d} by the shapes, the configuration "
                         f"states {config['num_parameters']}")
    print(f"d = {d} parameters by the shapes")

    model = FedModel(module, params,
                     gpt2_train.make_causal_loss(module, args), args,
                     padded_batch_size=train_loader.B)
    model.metric_counters = counters
    del params
    opt = FedOptimizer([{"lr": 1.0}], args)
    spe = steps_per_epoch(args.local_batch_size, train_ds,
                          args.num_workers)
    horizon = args.schedule_epochs or args.num_epochs
    lambda_step = PiecewiseLinear([0, horizon * spe], [args.lr_scale, 0])
    lr_scheduler = LambdaLR(opt, lambda x: lambda_step(x))

    def ref_batch(batch):
        return {k: np.array(batch[k]) for k in ("input_ids", "mask")}

    def batch_note(batch):
        ids = np.asarray(batch["input_ids"])
        real = int(np.asarray(batch["mask"]).sum()) * ids.shape[-1]
        return (f"{real} tokens in {ids.size} positions, none padding; "
                f"{int((ids == 0).sum())} document separators, "
                f"{len(np.unique(ids))} distinct ids")

    return FedRun(model=model, opt=opt, lr_scheduler=lr_scheduler,
                  loader=train_loader, args=args, ref_spec=ref_spec,
                  ref_batch=ref_batch, make_params=make_params,
                  batch_note=batch_note)


def abstract(cell, config, ref):
    """See ``builders/cv.py`` ``abstract``."""
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.train import gpt2_train

    args = parse_args(default_lr=4e-2, argv=_flags(
        cell, config, False, "unused", "unused"))
    args.num_results_train = 1 + len(_counters(args))
    module, ref_spec = _module(args, config)
    compute_loss = gpt2_train.make_causal_loss(module, args)
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), ref_spec))
    W, B = args.num_workers, args.local_batch_size
    T = int(cell["sequence_length"])
    batch = {"input_ids": jax.ShapeDtypeStruct((W, B, T), jnp.int32),
             "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
    return (args, lambda p, b: compute_loss(p, b, args), shapes, batch)

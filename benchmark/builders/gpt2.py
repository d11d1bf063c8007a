"""Assembles a ``train/gpt2_train.py`` run without training it.

A copy of ``gpt2_train.run()`` from ``parse_args`` to the LR schedule
(``train/gpt2_train.py:407-455``), stopping short of ``train_gpt2()``,
with the same two differences as ``builders/cv.py``: corpus and
vocabulary are written from the seed first (``--model_checkpoint`` is a
directory that holds the fabricated vocabulary only), and the initial
weights are the plain reference's ``init_params``, handed to
``FedModel`` in place of ``module.init``'s.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

_BATCH_KEYS = ("input_ids", "token_type_ids", "lm_labels", "mc_token_ids",
               "mc_labels", "mask")
_SPEC_KEYS = ("n_layer", "n_embd", "n_head", "n_positions", "vocab_size",
              "layer_norm_epsilon", "initializer_range", "lm_coef",
              "mc_coef")


def _module(args, config, tokenizer_len):
    """``build_model_and_tokenizer``'s module, without its init."""
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.train.gpt2_train import MAX_SEQ_LEN
    spec = {k: config[k] for k in _SPEC_KEYS}
    if args.do_test:
        tiny = GPT2Config.tiny()
        cfg = dataclasses.replace(
            tiny, vocab_size=max(tokenizer_len, tiny.vocab_size),
            n_positions=max(MAX_SEQ_LEN, tiny.n_positions))
        spec.update(n_layer=cfg.n_layer, n_embd=cfg.n_embd,
                    n_head=cfg.n_head, n_positions=cfg.n_positions,
                    vocab_size=cfg.vocab_size)
    else:
        cfg = GPT2Config(vocab_size=tokenizer_len, n_positions=1024)
        if (cfg.vocab_size, cfg.n_layer, cfg.n_embd, cfg.n_head) != tuple(
                config[k] for k in ("vocab_size", "n_layer", "n_embd",
                                    "n_head")):
            raise ValueError(f"the program's {cfg} is not the "
                             "configuration's architecture")
    if args.do_bf16:
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    return GPT2DoubleHeads(cfg), spec


def build(cell, config, ref, seed, workdir, rehearse=False):
    from benchmark.lib import fabricate
    from benchmark.lib.fedrun import (FedRun, check_tree_matches,
                                      seeded_params, trainer_flags)
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.data.tokenizer import (SPECIAL_TOKENS,
                                                  load_tokenizer)
    from commefficient_tpu.runtime import FedModel, FedOptimizer, LambdaLR
    from commefficient_tpu.train import gpt2_train
    from commefficient_tpu.utils import PiecewiseLinear, steps_per_epoch

    data = dict(cell["data"])
    if data["num_personalities"] != config["num_clients"] or \
            data["utterances_per_dialog"] != config["utterances_per_client"]:
        raise ValueError("the cell's federation is not the configuration's")
    if rehearse:
        data.update(cell["rehearse"]["data"])
    dataset_dir = os.path.join(workdir, "data")
    vocab_dir = os.path.join(workdir, "vocab")
    kind = data.pop("kind")
    getattr(fabricate, kind)(dataset_dir, vocab_dir, seed, **data)
    flags = trainer_flags(cell, config, rehearse) + [
        "--dataset_dir", dataset_dir, "--model_checkpoint", vocab_dir,
        "--seed", str(config["program_seed"]), "--num_devices", str(cell["num_devices"])]

    args = parse_args(default_lr=4e-2, argv=flags)
    np.random.seed(args.seed)
    args.num_results_train = 1
    if args.do_test:   # gpt2_train.run's smoke-mode sketch
        args.k, args.num_cols = 10, 100
        args.num_rows = args.num_blocks = 1

    tokenizer = load_tokenizer(args.model_checkpoint)
    tokenizer.add_special_tokens(SPECIAL_TOKENS)
    module, ref_spec = _module(args, config, len(tokenizer))
    train_loader, _, train_ds = gpt2_train.get_data_loaders(args,
                                                            tokenizer)
    if args.num_clients is None:
        args.num_clients = int(train_ds.num_clients)
    make_params = seeded_params(ref, ref_spec, seed)
    params = make_params()
    dummy = jnp.zeros((1, args.num_candidates, 8), jnp.int32)
    check_tree_matches(params, jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), dummy,
                            jnp.zeros((1, args.num_candidates),
                                      jnp.int32), dummy)["params"]))

    model = FedModel(module, params,
                     gpt2_train.make_compute_loss_train(module, args),
                     args, padded_batch_size=train_loader.B)
    del params
    opt = FedOptimizer([{"lr": 1.0}], args)
    spe = steps_per_epoch(args.local_batch_size, train_ds,
                          args.num_workers)
    horizon = args.schedule_epochs or args.num_epochs
    lambda_step = PiecewiseLinear([0, horizon * spe], [args.lr_scale, 0])
    lr_scheduler = LambdaLR(opt, lambda x: lambda_step(x))

    def ref_batch(batch):
        return {k: np.array(batch[k]) for k in _BATCH_KEYS}

    def batch_note(batch):
        """Sequences are padded on the right to the static length, and
        ``mc_token_ids`` is each one's last real position."""
        real = np.asarray(batch["mc_token_ids"]) + 1
        T = np.asarray(batch["input_ids"]).shape[-1]
        labelled = (np.asarray(batch["lm_labels"]) >= 0).sum()
        return (f"{int(real.sum())} real tokens in {real.size * T} "
                f"positions ({100.0 * real.sum() / (real.size * T):.1f} %, "
                f"longest sequence {int(real.max())}), {int(labelled)} "
                "with a language-model label")

    return FedRun(model=model, opt=opt, lr_scheduler=lr_scheduler,
                  loader=train_loader, args=args, ref_spec=ref_spec,
                  ref_batch=ref_batch, make_params=make_params,
                  batch_note=batch_note)


def abstract(cell, config, ref):
    """See ``builders/cv.py`` ``abstract``."""
    from benchmark.lib.fedrun import trainer_flags
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.train import gpt2_train

    args = parse_args(default_lr=4e-2, argv=trainer_flags(cell, config))
    args.num_results_train = 1
    module, ref_spec = _module(args, config, config["vocab_size"])
    compute_loss = gpt2_train.make_compute_loss_train(module, args)
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), ref_spec))
    W, B, N = args.num_workers, args.local_batch_size, args.num_candidates
    T = gpt2_train.MAX_SEQ_LEN
    i32 = jnp.int32
    batch = {"input_ids": jax.ShapeDtypeStruct((W, B, N, T), i32),
             "token_type_ids": jax.ShapeDtypeStruct((W, B, N, T), i32),
             "lm_labels": jax.ShapeDtypeStruct((W, B, N, T), i32),
             "mc_token_ids": jax.ShapeDtypeStruct((W, B, N), i32),
             "mc_labels": jax.ShapeDtypeStruct((W, B), i32),
             "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
    return (args, lambda p, b: compute_loss(p, b, args), shapes, batch)

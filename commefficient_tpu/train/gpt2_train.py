"""GPT-2 / PersonaChat federated fine-tuning driver — counterpart of
reference gpt2_train.py.

Same structure: double-heads loss (lm_coef*LM + mc_coef*MC) for
training (run with --num_results_train 1), NLL + multiple-choice
accuracy + PPL for validation, linear LR decay
PiecewiseLinear([0, epochs*spe], [lr_scale, 0]), same round loop.

Offline notes: the PersonaChat archive and GPT-2 vocab must be on disk
(zero egress); absent those, --test generates a synthetic archive and
uses the byte-level fallback tokenizer with a tiny GPT-2 config.
"""

from __future__ import annotations

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config, parse_args
from commefficient_tpu.data.fed_persona import (FedPERSONA,
                                                generate_synthetic_personachat)
from commefficient_tpu.data.fed_sampler import FedSampler
from commefficient_tpu.data.loader import (PersonaFedLoader,
                                           PersonaValLoader,
                                           TokenFedLoader,
                                           TokenValLoader)
from commefficient_tpu.data.tokenizer import (SPECIAL_TOKENS,
                                              load_tokenizer)
from commefficient_tpu.models.gpt2 import (GPT2Config, GPT2DoubleHeads,
                                           token_nll)
# a stop-gap (ROADMAP yardstick (m)): JoyAI's tuple as builders/lm.py reads it
from commefficient_tpu.models.joyai import COUNTERS as MOE_COUNTERS  # noqa
from commefficient_tpu.runtime import (FedModel, FedOptimizer, LambdaLR,
                                       TrainRun)
from commefficient_tpu.telemetry import setup_span
from commefficient_tpu.telemetry.alarms import DivergenceAbort
from commefficient_tpu.utils import (PiecewiseLinear, TableLogger,
                                     Timer, steps_per_epoch)

MAX_SEQ_LEN = 256  # static pad length (persona sequences are short)

#: ``--model`` names of the causal LMs this trainer builds (each a
#: module file of ``models/`` that brings the flax module with its
#: ``model_type`` and ``config_class``, ``causal_lm_loss`` and
#: ``COUNTERS``); any other value (the shared parser's default is a CV
#: model) means GPT2DoubleHeads, as before the flag was read here
CAUSAL_LMS = ("JoyAIFlashLM", "NemotronHLM", "GraniteHybridLM",
              "SmallThinkerLM", "OuroLM")


def is_causal_lm(args) -> bool:
    """A causal LM on packed token streams (``--model JoyAIFlashLM
    --dataset_name TOKENS``) instead of double heads on PersonaChat;
    the two flags go together."""
    causal = args.model in CAUSAL_LMS
    if causal != (args.dataset_name == "TOKENS"):
        raise ValueError(
            f"--model {args.model} and --dataset_name "
            f"{args.dataset_name or 'PERSONA'} do not go together: "
            f"{CAUSAL_LMS} train on TOKENS, GPT2DoubleHeads on PERSONA")
    return causal


def causal_lm_file(model: str):
    """The module file of ``models/`` that brings ``--model``."""
    import importlib

    from commefficient_tpu.models import get_model
    return importlib.import_module(get_model(model).__module__)


#: the persona loaders' label of a position that carries none
#: (data/loader.py PersonaFedLoader)
LM_IGNORE = -1


def _head_counters(ignore_index) -> dict:
    """``program_counters`` of a loss (runtime/fed_model.py counts them
    on every round record): ``head.compact`` 1 where the round
    program's vocabulary head is built ordering the rows by label
    (static, as ``select.blocked``; 0 for a causal LM and under
    ``--fused_ce``, whose kernels take every position)."""
    from commefficient_tpu.models.gpt2 import head_compacts
    return {"head.compact": int(head_compacts(ignore_index))}


def _lm_nll_sums(module, params, batch, tokens_per_chunk=0,
                 fused=False, batch_mult=1):
    """Shared forward for the train and val losses: hidden states +
    MC logits from the module, then the tied-head cross-entropy — the
    (tokens, vocab) logits tensor never materialises: chunked
    (models/gpt2.py lm_nll_sums_chunked) by default, or the fused
    Pallas kernels (ops/flce_pallas.py, ``fused=True``) where even the
    per-chunk logits tiles stay in VMEM. Returns per-example
    ((B*N,) Σnll, (B*N,) Σvalid), mc_logits, B, N.
    ``tokens_per_chunk`` 0 = auto (1024 rows a chunk).

    The blocks compute every position; the chunked head computes the
    positions whose ``lm_labels`` is not ``LM_IGNORE`` and skips the
    rest (PersonaChat labels the gold candidate's reply and ``<eos>``
    only: of a client's B x N sequences B carry any label, on a few
    positions each), the clients of a round sharing chunks
    (``lm_nll_sums_chunked``). The fused kernels compute every
    position."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked

    ids = batch["input_ids"]
    B, N, T = ids.shape
    h, wte, mc_logits = module.apply(
        {"params": params}, ids, batch["mc_token_ids"],
        batch["token_type_ids"], return_hidden=True)
    labels = batch["lm_labels"].reshape(B * N, T)
    if fused:
        from commefficient_tpu.ops.flce_pallas import lm_nll_sums_fused
        # batch_mult: this runs under the round's per-client vmap, so
        # the kernel's dX-partials OOM guard must see the vmapped
        # multiplicity — the buffer exists once PER CLIENT concurrently
        sn, sv = lm_nll_sums_fused(h[:, :-1], wte, labels[:, 1:],
                                   module.cfg.dtype,
                                   ignore_index=LM_IGNORE,
                                   tokens_per_chunk=tokens_per_chunk
                                   or 1024, batch_mult=batch_mult)
    else:
        sn, sv = lm_nll_sums_chunked(h[:, :-1], wte, labels[:, 1:],
                                     module.cfg.dtype,
                                     ignore_index=LM_IGNORE,
                                     tokens_per_chunk=tokens_per_chunk
                                     or 1024)
    return sn, sv, mc_logits, B, N


def _resolve_fused(args, module):
    from commefficient_tpu.ops.flce_pallas import resolve_fused_ce
    return resolve_fused_ce(getattr(args, "fused_ce", "off"),
                            module.cfg.n_embd)


def _token_nll(logits, labels, ignore_index=LM_IGNORE):
    """token_nll with the persona loaders' label padding default."""
    return token_nll(logits, labels, ignore_index)


def make_causal_loss(module, args, train=True):
    """A causal LM's loss of a client batch ``{input_ids (B, T), mask
    (B,)}``: the masked mean over sequences of its file's
    ``causal_lm_loss`` (heads chunked as GPT-2's). Training returns the
    client's counts beside it (the file's ``COUNTERS``: the round's
    ``moe.*`` / ``ssm.*`` counters); validation the shape
    ``run_batches`` reads, with no multiple-choice task to score
    (accuracy 0)."""
    causal_lm_loss = causal_lm_file(args.model).causal_lm_loss

    def compute_loss(params, batch, cfg):
        losses, stats = causal_lm_loss(
            module, params, batch["input_ids"],
            getattr(args, "tokens_per_chunk", 0) or 1024)
        m = batch["mask"]
        loss = jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)
        return loss, (tuple(stats) if train else (jnp.zeros(()),))

    # every position of a packed stream carries a label: the files'
    # ``causal_lm_loss`` say so to the head (``ignore_index=None``)
    compute_loss.program_counters = _head_counters(None)
    return compute_loss


def make_compute_loss_train(module, args):
    """(reference gpt2_train.py:88-99) — one result (the combined
    loss); run with --num_results_train 1. Batched formulation of
    gpt2_double_heads_loss applied per example: identical math to a
    per-example vmap (which XLA lowers to a serial scan over examples
    with a materialised f32 logits buffer — measured 10x the cost).
    The LM term is computed by the chunked tied-head cross-entropy
    (models/gpt2.py lm_nll_sums_chunked via _lm_nll_sums) — or the
    fused Pallas kernels (ops/flce_pallas.py) with --fused_ce — so
    the (tokens, vocab) logits tensor never materialises: its f32
    store/reload chain dominated the large-batch training profile."""

    def compute_loss(params, batch, cfg):
        # shift handled in _lm_nll_sums: position t predicts t+1;
        # per example i: token-mean over its valid positions
        sn, sv, mc_logits, B, N = _lm_nll_sums(
            module, params, batch,
            getattr(args, "tokens_per_chunk", 0),
            fused=_resolve_fused(args, module),
            batch_mult=max(1, getattr(args, "num_workers", 1)))
        lm_i = sn.reshape(B, N).sum(1) \
            / jnp.maximum(sv.reshape(B, N).sum(1), 1.0)

        mc_nll, _ = _token_nll(mc_logits[..., None, :],
                               batch["mc_labels"][..., None])
        mc_i = mc_nll[..., 0]

        m = batch["mask"]
        losses = cfg.lm_coef * lm_i + cfg.mc_coef * mc_i
        loss = jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)
        return loss, ()

    compute_loss.program_counters = _head_counters(
        None if _resolve_fused(args, module) else LM_IGNORE)
    return compute_loss


def make_compute_loss_val(module, args):
    """(reference gpt2_train.py:55-86): token-mean NLL + MC accuracy.
    The NLL uses the chunked (or, with --fused_ce, the fused-kernel)
    tied-head cross-entropy: with
    full-candidate validation (N ~ 20) a materialised f32
    (B, N, T, V) logits tensor would be ~8 GB per val shard at the
    natural PersonaChat candidate count."""
    def compute_loss(params, batch, cfg):
        # val shards run under a vmap over shards_per_step =
        # max(1, num_workers) (get_data_loaders) — same multiplicity
        sn, sv, mc_logits, B, N = _lm_nll_sums(
            module, params, batch,
            getattr(args, "tokens_per_chunk", 0),
            fused=_resolve_fused(args, module),
            batch_mult=max(1, getattr(args, "num_workers", 1)))
        m = batch["mask"]
        w = jnp.broadcast_to(m[:, None], (B, N)).reshape(B * N)
        nll = jnp.sum(sn * w) / jnp.maximum(jnp.sum(sv * w), 1.0)

        # padded candidate slots (val items pad up to the loader's
        # static N) must never win the argmax
        cand = batch.get("cand_mask")
        if cand is not None:
            mc_logits = jnp.where(cand > 0, mc_logits, -jnp.inf)
        pred = jnp.argmax(mc_logits, axis=-1)
        acc = jnp.sum((pred == batch["mc_labels"]) * m) \
            / jnp.maximum(jnp.sum(m), 1.0)
        return nll, (acc,)

    return compute_loss


def run_batches(model, opt, lr_scheduler, loader, args, training,
                round_hook=None, epoch=0):
    """(reference gpt2_train.py:169-253). ``round_hook(epoch)`` runs
    after every completed round (round-cadence autosave)."""
    if training:
        model.train(True)
        losses = []

        def process(metrics, i, w):
            # sample-count weighting: see cv_train.run_batches;
            # fully-dropped rounds trained on nothing — excluded
            if w.sum() == 0:
                return True
            loss = float(np.sum(metrics[0] * w) / w.sum())
            losses.append(loss)
            if not math.isfinite(loss) or loss > args.nan_threshold:
                print(f"diverged at round {i} (loss {loss})")
                return False
            return True

        tel = model.telemetry
        it = enumerate(loader)
        try:
            while True:
                # manual pull so the loader wait is a ledger span
                # (lands on the previous round's record — the
                # inter-round gap)
                with tel.span("sampler"):
                    nxt = next(it, None)
                if nxt is None:
                    break
                i, batch = nxt
                lr_scheduler.step()
                metrics = model(batch)
                opt.step()
                w = np.asarray(batch["mask"]).sum(axis=1)
                if not process(metrics, i, w):
                    return None
                if round_hook is not None:
                    round_hook(epoch)
                if args.do_test:
                    break
        except DivergenceAbort as e:
            # alarm engine (--on_divergence abort): the offending
            # round is already ledger-flagged; tel.close() in
            # train_gpt2's finally emits it
            print(f"Stopping at round {e.round_index}: {e}")
            model.diverged = True
            return None
        return float(np.mean(losses)) if losses else float("nan")
    else:
        model.train(False)
        nlls, accs, counts = [], [], []
        for i, batch in enumerate(loader):
            shard_metrics = model(batch)
            nlls.extend(shard_metrics[0].tolist())
            accs.extend(shard_metrics[1].tolist())
            counts.extend(shard_metrics[-1].tolist())
            if args.do_test:
                break
        counts = np.asarray(counts)
        w = counts / max(counts.sum(), 1.0)
        nll = float(np.sum(nlls * w))
        return nll, float(np.sum(accs * w)), float(np.exp(nll))


def train_gpt2(model, opt, lr_scheduler, train_loader, val_loader,
               args, logger=None, start_epoch=0, epoch_hook=None,
               round_hook=None, logdir=None):
    """(reference gpt2_train.py:115-147)"""
    from commefficient_tpu.telemetry.profiler import profile_epoch
    from commefficient_tpu.telemetry.sinks import TensorBoardSink
    from commefficient_tpu.utils import make_logdir
    logger = logger or TableLogger()
    timer = Timer()
    if logdir is None:
        logdir = (make_logdir(args)
                  if (args.use_tensorboard or args.do_profile) else None)
    tel = model.telemetry
    if args.use_tensorboard:
        # the trainer owns the run logdir, so the TB sink attaches
        # here rather than in build_telemetry
        tel.add_sink(TensorBoardSink(logdir))
    results = []
    try:
        for epoch in range(start_epoch, math.ceil(args.num_epochs)):
            with profile_epoch(args, epoch, start_epoch, logdir,
                               telemetry=tel):
                train_loss = run_batches(model, opt, lr_scheduler,
                                         train_loader, args,
                                         training=True,
                                         round_hook=round_hook,
                                         epoch=epoch)
            if train_loss is None:
                print("NaN detected, aborting")
                model.diverged = True
                return results
            train_time = timer()
            nll, acc, ppl = run_batches(model, opt, lr_scheduler,
                                        val_loader, args,
                                        training=False)
            val_time = timer()
            row = {"epoch": epoch + 1,
                   "lr": float(opt.param_groups[0]["lr"]),
                   "train_time": train_time, "train_loss": train_loss,
                   "val_time": val_time, "val_nll": nll, "val_acc": acc,
                   "val_ppl": ppl, "total_time": timer.total_time}
            logger.append(row)
            results.append(row)
            tel.epoch(row, epoch + 1)
            if epoch_hook is not None:
                epoch_hook(epoch + 1)
    finally:
        # sinks flush/close here even on abort; finalize()'s close is
        # a no-op afterwards (idempotent)
        tel.close()
    return results


def build_causal_lm(args: Config):
    """``--model`` of ``CAUSAL_LMS``: the module is the one registered
    under that name, the architecture is ``config.json`` in
    ``--model_checkpoint`` (the published keys, cut to the chip's share
    as the module's ``config_class.from_hf`` reads them; its
    ``model_type`` has to be the module's), or the tiny preset under
    ``--test``. Random weights: no checkpoint format is read yet."""
    import dataclasses
    import json

    from commefficient_tpu.models import get_model
    module_cls = get_model(args.model)
    cfg_json = os.path.join(args.model_checkpoint, "config.json")
    if os.path.exists(cfg_json):
        with open(cfg_json) as f:
            blob = json.load(f)
        if blob.get("model_type") != module_cls.model_type:
            raise ValueError(
                f"--model {args.model} builds model_type "
                f"{module_cls.model_type!r}, but {cfg_json} has model_type "
                f"{blob.get('model_type')!r}")
        cfg = module_cls.config_class.from_hf(blob)
    elif args.do_test:
        cfg = module_cls.config_class.tiny()
    else:
        raise FileNotFoundError(
            f"--model {args.model} needs {cfg_json} (or --test)")
    cfg = dataclasses.replace(
        cfg, dtype=jnp.bfloat16 if args.do_bf16 else jnp.float32,
        remat=bool(args.do_remat))
    module = module_cls(cfg)
    # model-init stream, not noise  # audit: allow(noise-confinement)
    params = module.init(jax.random.PRNGKey(args.seed),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def build_model_and_tokenizer(args: Config):
    import dataclasses

    import json

    if is_causal_lm(args):
        return build_causal_lm(args) + (None,)
    tokenizer = load_tokenizer(args.model_checkpoint)
    tokenizer.add_special_tokens(SPECIAL_TOKENS)
    cfg_json = os.path.join(args.model_checkpoint, "config.json") \
        if os.path.isdir(args.model_checkpoint) else ""
    if os.path.exists(cfg_json):
        # a run dir saved by FedModel.save_pretrained: its config
        # defines the architecture the saved weights fit
        with open(cfg_json) as f:
            blob = json.load(f)
        fields = {f.name for f in dataclasses.fields(GPT2Config)}
        # attn_impl is a runtime lowering knob, not architecture: a
        # config saved from a flash-attention TPU run must not force
        # the Pallas kernel on whatever platform reloads it
        fields.discard("attn_impl")
        cfg = GPT2Config(**{k: v for k, v in blob.items()
                            if k in fields})
    elif args.do_test or tokenizer.__class__.__name__ == "ByteTokenizer":
        cfg = GPT2Config.tiny()
        cfg = dataclasses.replace(
            cfg,
            vocab_size=max(len(tokenizer), cfg.vocab_size),
            n_positions=max(MAX_SEQ_LEN, cfg.n_positions))
    else:
        cfg = GPT2Config(vocab_size=len(tokenizer),
                         n_positions=1024)
    if args.do_bf16:
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    if args.do_remat:
        cfg = dataclasses.replace(cfg, remat=True)
    if getattr(args, "attn_impl", "xla") != "xla":
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    module = GPT2DoubleHeads(cfg)
    dummy = jnp.zeros((1, args.num_candidates, 8), jnp.int32)
    # model-init stream, not noise  # audit: allow(noise-confinement)
    params = module.init(jax.random.PRNGKey(args.seed), dummy,
                         jnp.zeros((1, args.num_candidates),
                                   jnp.int32), dummy)["params"]

    if os.path.isdir(args.model_checkpoint):
        torch_ckpt = os.path.join(args.model_checkpoint,
                                  "pytorch_model.bin")
        flax_ckpt = os.path.join(args.model_checkpoint,
                                 "flax_model.msgpack")
        if os.path.exists(torch_ckpt):
            import torch
            from commefficient_tpu.models.gpt2 import convert_torch_gpt2
            sd = {k: v.numpy() for k, v in
                  torch.load(torch_ckpt, map_location="cpu").items()}
            params = convert_torch_gpt2(sd, cfg)
            print(f"loaded GPT-2 weights from {torch_ckpt}")
        elif os.path.exists(flax_ckpt):
            # a run dir saved by FedModel.save_pretrained; without its
            # config.json the module above was built from tokenizer
            # heuristics and the weights would mis-shape inside jit
            if not os.path.exists(cfg_json):
                raise FileNotFoundError(
                    f"{flax_ckpt} has no config.json beside it; "
                    "cannot reconstruct the saved architecture")
            from flax import serialization
            with open(flax_ckpt, "rb") as f:
                params = serialization.msgpack_restore(f.read())
            print(f"loaded GPT-2 weights from {flax_ckpt}")
    return module, params, tokenizer


def _token_loaders(args: Config):
    """``--dataset_name TOKENS``: per-client token streams
    (data/fed_tokens.py), dealt out by the same sampler."""
    from commefficient_tpu.data.fed_tokens import (
        FedTokens, generate_synthetic_tokens)
    if args.do_test and not os.path.exists(
            os.path.join(args.dataset_dir, "stats.json")):
        generate_synthetic_tokens(args.dataset_dir, seed=args.seed)
    train_ds = FedTokens(args.dataset_dir, train=True,
                         num_clients=args.num_clients)
    val_ds = FedTokens(args.dataset_dir, train=False)
    sampler = FedSampler(train_ds, args.num_workers,
                         args.local_batch_size, seed=args.seed)
    train_loader = TokenFedLoader(
        train_ds, sampler, dropout_prob=args.dropout_prob,
        dropout_seed=args.seed)
    val_loader = TokenValLoader(
        val_ds, args.valid_batch_size,
        shards_per_step=max(1, args.num_workers))
    return train_loader, val_loader, train_ds


@setup_span("data_build")
def get_data_loaders(args: Config, tokenizer):
    """(reference gpt2_train.py:315-355)"""
    if args.dataset_name == "TOKENS":
        return _token_loaders(args)
    if args.do_test and not os.path.exists(
            os.path.join(args.dataset_dir,
                         "personachat_self_original.json")):
        if not os.path.exists(os.path.join(args.dataset_dir,
                                           "stats.json")):
            generate_synthetic_personachat(args.dataset_dir)

    common = dict(do_iid=args.do_iid, num_clients=args.num_clients,
                  seed=args.seed)
    train_ds = FedPERSONA(tokenizer, args.num_candidates,
                          args.max_history,
                          args.personality_permutations,
                          args.dataset_dir, "PERSONA", train=True,
                          **common)
    val_ds = FedPERSONA(tokenizer, -1, args.max_history, 1,
                        args.dataset_dir, "PERSONA", train=False,
                        **common)
    pad_id = tokenizer.convert_tokens_to_ids(["<pad>"])[0]
    sampler = FedSampler(train_ds, args.num_workers,
                         args.local_batch_size, seed=args.seed)
    train_loader = PersonaFedLoader(
        train_ds, sampler, args.num_candidates, MAX_SEQ_LEN, pad_id,
        dropout_prob=args.dropout_prob, dropout_seed=args.seed)
    # full-candidate validation (reference fed_persona.py:251-254
    # restricts candidates only for train items): evaluate MC accuracy
    # over every candidate the val item carries, not num_candidates
    n_val = args.val_candidates
    if n_val <= 0:
        # exact max over the raw val JSON (candidate counts can vary
        # per utterance) — no tokenization needed
        n_val = max((len(u["candidates"]) for d in val_ds.raw_val_set
                     for u in d["utterances"]), default=2)
    val_loader = PersonaValLoader(
        val_ds, args.valid_batch_size, max(n_val, 2),
        MAX_SEQ_LEN, pad_id,
        shards_per_step=max(1, args.num_workers))
    return train_loader, val_loader, train_ds


def main(argv=None):
    """The epoch rows (or, under --finetune, the eval tuple) of
    ``run(argv)`` — what the tests read."""
    return run(argv).results


def cli() -> int:
    """Process entry (console script, ``python -m``)."""
    from commefficient_tpu.train import cli_exit_status
    return cli_exit_status(run)


def run(argv=None) -> TrainRun:
    args = parse_args(default_lr=4e-2, argv=argv)
    from commefficient_tpu.parallel.mesh import \
        maybe_initialize_multihost_cli
    maybe_initialize_multihost_cli(args)
    np.random.seed(args.seed)
    causal = is_causal_lm(args)
    counters = causal_lm_file(args.model).COUNTERS if causal else ()
    args.num_results_train = 1 + len(counters)

    if args.do_test:
        # pre-run CLI override: no round program exists yet for a
        # knob move to diverge from, so the waivers below are safe
        args.k = 10  # audit: allow(knob-mutation)
        args.num_cols = 100  # audit: allow(knob-mutation)
        args.num_rows = 1
        args.num_blocks = 1

    module, params, tokenizer = build_model_and_tokenizer(args)
    train_loader, val_loader, train_ds = get_data_loaders(args,
                                                          tokenizer)
    if args.num_clients is None:
        args.num_clients = int(train_ds.num_clients)

    if causal:
        if args.seq_devices > 1:
            raise ValueError("--seq_devices > 1 shards GPT-2's attention "
                             f"only (core/rounds_sp.py), not {args.model}")
        model = FedModel(module, params,
                         make_causal_loss(module, args), args,
                         compute_loss_val=make_causal_loss(
                             module, args, train=False),
                         padded_batch_size=train_loader.B)
        model.metric_counters = counters
    elif args.seq_devices > 1:
        from commefficient_tpu.runtime.fed_model_sp import (
            SeqParallelFedModel)
        model = SeqParallelFedModel(
            module, params, make_compute_loss_train(module, args),
            args, gpt2_cfg=module.cfg,
            compute_loss_val=make_compute_loss_val(module, args),
            padded_batch_size=train_loader.B)
    else:
        model = FedModel(module, params,
                         make_compute_loss_train(module, args), args,
                         compute_loss_val=make_compute_loss_val(module,
                                                                args),
                         padded_batch_size=train_loader.B)
    # the loader's spans go onto this model's round records, and its
    # thread places each round's batch with this model's placement
    train_loader.telemetry = model.telemetry
    train_loader.placement = model.placement
    if hasattr(model, "attach_participant_feed") \
            and hasattr(train_loader, "peek_next_client_ids"):
        # host client store: one-round lookahead feeds the prefetcher
        model.attach_participant_feed(
            train_loader.peek_next_client_ids)
    opt = FedOptimizer([{"lr": 1.0}], args)

    spe = steps_per_epoch(args.local_batch_size, train_ds,
                          args.num_workers)
    horizon = args.schedule_epochs or args.num_epochs
    lambda_step = PiecewiseLinear([0, horizon * spe],
                                  [args.lr_scale, 0])
    lr_scheduler = LambdaLR(opt, lambda x: lambda_step(x))

    if args.do_finetune:
        # --finetune = eval only (reference gpt2_train.py:308-312)
        out = run_batches(model, opt, lr_scheduler, val_loader, args,
                          training=False)
        print({"val_nll": out[0], "val_acc": out[1], "val_ppl": out[2]})
        return TrainRun(out, model, opt, train_loader)

    from commefficient_tpu.runtime.checkpoint import setup_resume
    start_epoch, epoch_hook, round_hook = setup_resume(
        args, model, opt, lr_scheduler, train_loader, tag="gpt2")

    if args.eval_before_start and start_epoch == 0:
        # (reference gpt2_train.py:207 via --eval_before_start);
        # skipped on resume — the restored model isn't "before start"
        out = run_batches(model, opt, lr_scheduler, val_loader, args,
                          training=False)
        print({"epoch": 0, "val_nll": out[0], "val_acc": out[1],
               "val_ppl": out[2]})

    # one logdir for the whole run: TB events, profiles, and the final
    # model/tokenizer save all land together (reference gpt2_train.py
    # computes log_dir once at startup, :278-283)
    from commefficient_tpu.utils import make_logdir
    logdir = make_logdir(args) if not args.do_test else None
    from commefficient_tpu.utils import GracefulShutdown, sigterm_raises
    interrupted = False
    try:
        with sigterm_raises():
            results = train_gpt2(model, opt, lr_scheduler,
                                 train_loader, val_loader, args,
                                 start_epoch=start_epoch,
                                 epoch_hook=epoch_hook,
                                 round_hook=round_hook, logdir=logdir)
    except GracefulShutdown as e:
        # crash safety: see cv_train.main — no save here; the last
        # round-cadence autosave is the consistent resume point
        print(f"interrupted ({e}); resume from the last autosave")
        interrupted = True
        results = []
        if model.flightrec is not None:
            # see cv_train.main — dump the postmortem before the
            # in-flight state it describes is discarded
            model.flightrec.dump("graceful_shutdown",
                                 context={"signal": str(e)})
        model.interrupted()
    model.finalize()
    from commefficient_tpu.runtime.checkpoint import \
        resume_manifest_extra
    from commefficient_tpu.telemetry import registry
    registry.maybe_write_manifest(
        args, mesh_shape=dict(model.mesh.shape),
        extra={"trainer": "gpt2_train", "epochs": len(results),
               "interrupted": interrupted,
               "diverged": bool(getattr(model, "diverged", False)),
               **resume_manifest_extra(model)})
    if logdir is not None and not getattr(model, "diverged", False) \
            and not interrupted and jax.process_index() == 0:
        # reference gpt2_train.py:146, 278-283: final model + tokenizer
        # saved HF-style into the run's logdir (skipped after a NaN
        # abort — diverged weights are not a final model)
        model.save_pretrained(logdir, hf_format=args.do_hf_export)
        if tokenizer is not None:
            tokenizer.save_pretrained(logdir)
        print(f"saved model + tokenizer to {logdir}"
              + (" (HF torch format)" if args.do_hf_export else ""))
    # the loader's thread, with the epoch it opened ahead, ends with
    # the run
    train_loader.close()
    return TrainRun(results, model, opt, train_loader)


if __name__ == "__main__":
    sys.exit(cli())

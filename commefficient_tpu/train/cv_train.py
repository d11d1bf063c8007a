"""CV experiment driver — counterpart of reference cv_train.py.

Same CLI, same round loop structure (LR scheduler stepped *before*
the round, the LR==0 "HACK STEP" alignment quirk, NaN abort, fractional
epochs, byte-accounting totals, TableLogger rows), driving the SPMD
runtime instead of a process fleet.

Run e.g.:
    python -m commefficient_tpu.train.cv_train --dataset_name Synthetic \
        --mode sketch --error_type virtual --local_momentum 0 \
        --num_clients 10 --num_workers 2 --num_epochs 2
"""

from __future__ import annotations

import math
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import (Config, num_classes_of_dataset,
                                      parse_args)
from commefficient_tpu.data import (FedLoader, FedSampler, ValLoader,
                                    get_dataset_cls)
from commefficient_tpu.data import transforms as T
from commefficient_tpu.models import get_model
from commefficient_tpu.runtime import (FedModel, FedOptimizer, LambdaLR,
                                       TrainRun)
from commefficient_tpu.telemetry import clock, setup_span
from commefficient_tpu.telemetry.alarms import DivergenceAbort
from commefficient_tpu.utils import (PiecewiseLinear, TableLogger,
                                     TSVLogger, Timer, steps_per_epoch)


def masked_mean(values, mask):
    return jnp.sum(values * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_compute_loss(module, init_stats=None):
    """CE loss + accuracy (reference compute_loss_ce,
    cv_train.py:32-50), masked-mean over real samples.

    Mixup support: when the batch carries ``y_b``/``lam`` (added by
    ``apply_mixup`` under ``--mixup``), the loss becomes
    lam*CE(y) + (1-lam)*CE(y_b) — the reference ships this as dead
    code (compute_loss_mixup is never wired and its mixup_data helper
    doesn't exist, SURVEY §2.7); here it works. Accuracy is reported
    against the dominant label."""

    def compute_loss(params, batch, args):
        variables = {"params": params}
        if init_stats is not None:
            # masked batch statistics: padded rows must not enter
            # (the reference's torch batches are dynamically sized,
            # so its BN only ever sees real samples)
            variables["batch_stats"] = init_stats
            logits, _ = module.apply(variables, batch["x"],
                                     mask=batch["mask"],
                                     mutable=["batch_stats"])
        else:
            logits = module.apply(variables, batch["x"])
        return _ce_loss_and_acc(logits, batch)

    return compute_loss


def _ce_loss_and_acc(logits, batch):
    labels = batch["y"]
    logp = jax.nn.log_softmax(logits)

    def nll_of(lab):
        return -jnp.take_along_axis(logp, lab[..., None],
                                    axis=-1)[..., 0]

    if "y_b" in batch:
        lam = batch["lam"]  # per-sample (broadcast of round lam)
        nll = lam * nll_of(labels) \
            + (1.0 - lam) * nll_of(batch["y_b"])
        dominant = jnp.where(lam >= 0.5, labels, batch["y_b"])
    else:
        nll = nll_of(labels)
        dominant = labels
    loss = masked_mean(nll, batch["mask"])
    acc = masked_mean(
        (jnp.argmax(logits, -1) == dominant).astype(jnp.float32),
        batch["mask"])
    return loss, (acc,)


def make_compute_loss_eval(module):
    """Eval loss for stateful-BN models: normalize by the server's
    running statistics (model_state), so metrics are invariant to the
    eval batch composition — the reference's torch BN eval behavior
    (models/resnet9.py:32-59 via nn.BatchNorm2d)."""

    def compute_loss(params, batch, args, model_state):
        logits = module.apply({"params": params,
                               "batch_stats": model_state},
                              batch["x"], train=False)
        return _ce_loss_and_acc(logits, batch)

    return compute_loss


def make_bn_stats_fn(module, init_stats):
    """One client's raw batch statistics: a train-mode forward with a
    mutable batch_stats collection (BatchStatNorm records the masked
    batch mean/var; the server does the running blend). This is a
    second forward per client on top of the gradient pass — accepted
    tradeoff: threading the stats out through the grad/metrics
    machinery would complicate every mode path, and --batchnorm is a
    parity mode, not the perf path (benches are BN-free)."""

    def stats_fn(params, batch):
        _, upd = module.apply({"params": params,
                               "batch_stats": init_stats},
                              batch["x"], mask=batch["mask"],
                              mutable=["batch_stats"])
        return upd["batch_stats"]

    return stats_fn


# Fixup scalar leaf names, matched as the EXACT final path segment —
# a bare substring test ('bias' in path) would silently sweep any
# future parameter whose path merely contains the string into the
# 0.1x group. The name sets cover every scalar the Fixup family
# declares (fixup_resnet9.py: bias1a/1b/2a/2b, bias1/bias2, scale;
# FixupBottleneck adds bias3a/3b; FixupResNet18: add1a/1b/2a/2b, mul)
# plus the Dense head's 'bias', which the reference's substring match
# also places at 0.1x (cv_train.py:366-376).
_FIXUP_BIAS_RE = re.compile(
    r"\['(?:bias(?:[123][ab]?)?|add[12][ab])'\]$")
_FIXUP_SCALE_RE = re.compile(r"\['(?:scale|mul)'\]$")


def fixup_bias_name(name: str) -> bool:
    """Fixup 0.1x 'bias' group membership by parameter-path name
    (reference cv_train.py:366-376 matches torch names by substring;
    here the final path segment must equal a known scalar name)."""
    return _FIXUP_BIAS_RE.search(name) is not None


def fixup_scale_name(name: str) -> bool:
    """Fixup 0.1x 'scale' group: 'mul.scale' in the reference; our
    FixupResNet18 names the multiplicative scalar 'mul'."""
    return _FIXUP_SCALE_RE.search(name) is not None


def apply_mixup(batch, alpha, rng):
    """Host-side mixup (the classic mixup_data recipe): one lambda ~
    Beta(alpha, alpha) per round; inputs are mixed with a permutation
    WITHIN each client's real rows (mixing across clients would leak
    data between federated clients)."""
    lam = float(rng.beta(alpha, alpha)) if alpha > 0 else 1.0
    x = np.asarray(batch["x"]).copy()
    y = np.asarray(batch["y"])
    mask = np.asarray(batch["mask"])
    y_b = y.copy()
    for w in range(x.shape[0]):
        real = np.nonzero(mask[w] > 0)[0]
        if len(real) < 2:
            continue
        perm = real[rng.permutation(len(real))]
        x[w, real] = lam * x[w, real] + (1 - lam) * x[w, perm]
        y_b[w, real] = y[w, perm]
    out = dict(batch)
    out["x"] = x
    out["y_b"] = y_b
    out["lam"] = np.full_like(mask, lam)
    return out


def run_batches(model, opt, lr_scheduler, loader, args, training,
                logger=None, epoch_fraction=1.0, mixup_rng=None,
                round_hook=None, epoch=0):
    """(reference cv_train.py:171-252). ``round_hook(epoch)`` runs
    after every completed round (round-cadence autosave,
    runtime/checkpoint.py RoundAutosaver)."""
    if training:
        model.train(True)
        losses, accs = [], []
        download_total = np.zeros(model.num_clients)
        upload_total = np.zeros(model.num_clients)
        spe = len(loader)
        max_batches = max(1, int(spe * epoch_fraction))
        state = {"t0": clock.wall()}

        def process(metrics, i, w):
            loss, acc, download, upload = (metrics[0], metrics[1],
                                           metrics[-2], metrics[-1])
            download_total[:] += download
            upload_total[:] += upload
            # weight per-client metrics by real sample counts so
            # dropped clients (--dropout_prob) and ragged batches
            # don't dilute the reported numbers; fully-dropped rounds
            # trained on nothing and are excluded from the epoch means
            if w.sum() == 0:
                return True
            losses.append(float(np.sum(loss * w) / w.sum()))
            accs.append(float(np.sum(acc * w) / w.sum()))
            if args.dataset_name == "EMNIST":
                # per-round progress line (reference cv_train.py:
                # 233-237)
                print("LR: {:0.5f}, Loss: {:0.5f}, Acc: {:0.5f}, "
                      "Time: {:0.2f}".format(
                          float(opt.param_groups[0]["lr"]),
                          losses[-1], accs[-1],
                          clock.wall() - state["t0"]))
                state["t0"] = clock.wall()
            if not math.isfinite(losses[-1]) or \
                    losses[-1] > args.nan_threshold:
                print(f"Stopping at batch {i}: diverged "
                      f"(loss {losses[-1]})")
                return False
            return True

        tel = model.telemetry
        it = enumerate(loader)
        try:
            while True:
                # manual pull so the sampler/loader wait is a ledger
                # span (lands on the previous round's record — it's
                # the inter-round host gap)
                with tel.span("sampler"):
                    nxt = next(it, None)
                if nxt is None:
                    break
                i, batch = nxt
                if i >= max_batches:
                    break
                if mixup_rng is not None:
                    batch = apply_mixup(batch, args.mixup_alpha,
                                        mixup_rng)
                lr_scheduler.step()
                if opt.param_groups[0]["lr"] == 0:
                    # "HACK STEP": keep FedAvg's schedule aligned when
                    # the triangular LR hits 0 (reference cv_train.py:
                    # 198-203); every group — schedule zeros hit them
                    # all at once
                    for g in opt.param_groups:
                        g["lr"] = 1e-10
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
            # --on_divergence abort: a probe alarm fired (alarms are
            # already flagged on the round's ledger record, which
            # becomes the run's final record when telemetry closes)
            print(f"Stopping at round {e.round_index}: {e}")
            model.diverged = True
            return None
        if not losses:  # every round fully dropped
            return (float("nan"), float("nan"),
                    download_total, upload_total)
        return (np.mean(losses), np.mean(accs),
                download_total, upload_total)
    else:
        model.train(False)
        losses, accs, counts = [], [], []
        for i, batch in enumerate(loader):
            shard_metrics = model(batch)
            losses.extend(shard_metrics[0].tolist())
            accs.extend(shard_metrics[1].tolist())
            counts.extend(shard_metrics[-1].tolist())
            if args.do_test:
                break
        counts = np.asarray(counts)
        w = counts / max(counts.sum(), 1.0)
        return float(np.sum(losses * w)), float(np.sum(accs * w))


def train(model, opt, lr_scheduler, train_loader, val_loader, args,
          logger=None, timer=None, start_epoch=0, epoch_hook=None,
          round_hook=None):
    """Epoch loop (reference cv_train.py:85-168). ``epoch_hook(ep)``
    runs after each completed epoch and ``round_hook(epoch)`` after
    each completed round (checkpointing)."""
    from commefficient_tpu.telemetry.profiler import profile_epoch
    from commefficient_tpu.telemetry.sinks import TensorBoardSink
    from commefficient_tpu.utils import make_logdir
    timer = timer or Timer()
    logger = logger or TableLogger()
    tsv = TSVLogger()
    logdir = (make_logdir(args)
              if (args.use_tensorboard or args.do_profile) else None)
    tel = model.telemetry
    if args.use_tensorboard:
        # the trainer owns the run logdir, so the TB sink attaches
        # here rather than in build_telemetry
        tel.add_sink(TensorBoardSink(logdir))
    results = []
    num_epochs = args.num_epochs
    # one persistent mixup stream across epochs (fresh draws per round)
    mixup_rng = (np.random.RandomState(args.seed + 77)
                 if args.do_mixup else None)
    try:
        for epoch in range(start_epoch, math.ceil(num_epochs)):
            epoch_fraction = min(1.0, num_epochs - epoch)
            with profile_epoch(args, epoch, start_epoch, logdir,
                               telemetry=tel):
                out = run_batches(model, opt, lr_scheduler,
                                  train_loader, args, training=True,
                                  epoch_fraction=epoch_fraction,
                                  mixup_rng=mixup_rng,
                                  round_hook=round_hook, epoch=epoch)
            if out is None:
                print("NaN detected, aborting training")
                model.diverged = True
                return results
            train_loss, train_acc, download, upload = out
            train_time = timer()
            val_loss, val_acc = run_batches(model, opt, lr_scheduler,
                                            val_loader, args,
                                            training=False)
            val_time = timer()
            row = {
                "epoch": epoch + 1,
                "lr": float(opt.param_groups[0]["lr"]),
                "train_time": train_time,
                "train_loss": float(train_loss),
                "train_acc": float(train_acc),
                "test_time": val_time,
                "test_loss": float(val_loss),
                "test_acc": float(val_acc),
                "down (MiB)": float(download.sum() / (1024 * 1024)),
                "up (MiB)": float(upload.sum() / (1024 * 1024)),
                "total_time": timer.total_time,
            }
            logger.append(row)
            tsv.append(row)
            results.append(row)
            tel.epoch(row, epoch + 1)
            if epoch_hook is not None:
                epoch_hook(epoch + 1)
    finally:
        # sinks flush/close here even on abort; finalize()'s close is
        # a no-op afterwards (idempotent)
        tel.close()
    return results


@setup_span("data_build")
def get_data_loaders(args: Config):
    """(reference cv_train.py:254-287)"""
    name = args.dataset_name
    train_t, val_t = None, None
    if name in ("CIFAR10", "CIFAR100"):
        mean = T.CIFAR10_MEAN if name == "CIFAR10" else T.CIFAR100_MEAN
        std = T.CIFAR10_STD if name == "CIFAR10" else T.CIFAR100_STD
        train_t = T.cifar_train_transform(mean, std)
        val_t = T.cifar_val_transform(mean, std)
    elif name == "EMNIST":
        train_t = T.femnist_train_transform()
        val_t = T.femnist_val_transform()
    elif name == "ImageNet":
        train_t = T.imagenet_train_transform()
        val_t = T.imagenet_val_transform()

    cls = get_dataset_cls(name)
    common = dict(do_iid=args.do_iid, num_clients=args.num_clients,
                  seed=args.seed)
    if name == "Synthetic":
        common["classes_per_client"] = args.classes_per_client
        common["per_class"] = args.synthetic_per_class
        common["separation"] = args.synthetic_separation
        common["num_val"] = args.synthetic_num_val
    train_ds = cls(args.dataset_dir, name, transform=train_t,
                   train=True, **common)
    val_ds = cls(args.dataset_dir, name, transform=val_t, train=False,
                 **common)
    sampler = FedSampler(train_ds, args.num_workers,
                         args.local_batch_size,
                         seed=args.seed)
    # C++ data-plane with threaded prefetch when the transform stack
    # and toolchain allow; Python loader otherwise (same batch dict)
    from commefficient_tpu.data import make_fed_loader
    train_loader = make_fed_loader(train_ds, sampler, seed=args.seed,
                                   prefer_native=not args.do_test,
                                   dropout_prob=args.dropout_prob)
    val_loader = ValLoader(val_ds, args.valid_batch_size,
                           shards_per_step=max(1, args.num_workers))
    return train_loader, val_loader, train_ds


def build_model(args: Config, rng=None):
    num_classes = num_classes_of_dataset(args.dataset_name)
    model_cls = get_model(args.model)
    kw = dict(num_classes=num_classes)
    if args.model == "ResNet9":
        kw["do_batchnorm"] = args.do_batchnorm
    if args.do_bf16:
        if "dtype" in getattr(model_cls, "__dataclass_fields__", {}):
            kw["dtype"] = jnp.bfloat16
        else:
            import warnings
            warnings.warn(f"--bf16 not supported by {args.model}; "
                          "training in float32")
    if args.do_test and hasattr(model_cls, "test_config"):
        kw.update(model_cls.test_config(num_classes))
    module = model_cls(**kw)
    # model-init stream, not noise  # audit: allow(noise-confinement)
    rng = rng if rng is not None else jax.random.PRNGKey(args.seed)
    # EMNIST is 28x28 grayscale, ImageNet 224x224 (reference dataset
    # table at utils.py:37-41 + transforms.py)
    sample_shape = {"EMNIST": (1, 28, 28, 1),
                    "ImageNet": (1, 224, 224, 3)}.get(
        args.dataset_name, (1, 32, 32, 3))
    variables = module.init(rng, jnp.zeros(sample_shape), train=True)
    params = variables["params"]
    init_stats = variables.get("batch_stats")
    return module, params, init_stats


def merge_finetune_params(target, source):
    """Overlay ``source`` (a loaded checkpoint pytree) onto ``target``
    (freshly initialised for the new dataset) wherever leaf shapes
    match; leaves whose shapes differ — the classifier head when the
    class count changed — keep their fresh initialisation. The
    functional form of the reference's head-swap finetuning
    (cv_train.py:342-352, 377-384). Returns (merged, replaced_paths).
    """
    replaced = []

    def rec(t, s, path):
        if isinstance(t, dict):
            out = {}
            for k, v in t.items():
                if isinstance(s, dict) and k in s:
                    out[k] = rec(v, s[k], path + (k,))
                else:
                    replaced.append("/".join(path + (k,)))
                    out[k] = v
            return out
        if getattr(s, "shape", None) == getattr(t, "shape", None):
            return jnp.asarray(s)
        replaced.append("/".join(path))
        return t

    return rec(target, source, ()), replaced


def load_finetune_params(args, params):
    """Load finetune_path/<model>.pkl (trained on --finetuned_from)
    and merge it into the fresh params."""
    import os
    import pickle
    path = os.path.join(args.finetune_path, args.model + ".pkl")
    with open(path, "rb") as f:
        source = pickle.load(f)
    merged, replaced = merge_finetune_params(params, source)
    print(f"finetune: loaded {path}; reinitialised: "
          f"{replaced or 'nothing'}")
    return merged


DEFAULT_LR = 0.4


def main(argv=None):
    """The epoch rows of ``run(argv)`` (what the tests read)."""
    return run(argv).results


def cli() -> int:
    """Process entry (console script, ``python -m``)."""
    from commefficient_tpu.train import cli_exit_status
    return cli_exit_status(run)


def run(argv=None) -> TrainRun:
    args = parse_args(default_lr=DEFAULT_LR, argv=argv)
    from commefficient_tpu.parallel.mesh import \
        maybe_initialize_multihost_cli
    maybe_initialize_multihost_cli(args)
    if args.seq_devices > 1:
        raise ValueError("--seq_devices is a GPT-2 trainer feature "
                         "(sequence parallelism); cv models have no "
                         "sequence axis")
    np.random.seed(args.seed)

    model_cfg = None
    if not args.do_test:
        # overlay per-model recommended hyperparameters onto fields
        # the user left at their defaults (models/configs.py)
        from commefficient_tpu.models.configs import get_model_config
        model_cfg = get_model_config(args.model)
        if model_cfg is not None:
            defaults = parse_args(default_lr=DEFAULT_LR,
                                  argv=[]).__dict__
            applied = model_cfg.set_args(args, defaults)
            if applied:
                print(f"model config {type(model_cfg).__name__}: "
                      f"{applied}")

    if args.do_test:
        # tiny sketch like the reference smoke mode (cv_train.py:329-336)
        # pre-run CLI override: no round program exists yet for a
        # knob move to diverge from, so the waivers below are safe
        args.k = 10  # audit: allow(knob-mutation)
        args.num_cols = 10  # audit: allow(knob-mutation)
        args.num_rows = 1
        args.num_blocks = 1

    train_loader, val_loader, train_ds = get_data_loaders(args)
    if args.num_clients is None:
        args.num_clients = int(train_ds.num_clients)

    module, params, init_stats = build_model(args)
    if args.do_finetune:
        params = load_finetune_params(args, params)
    compute_loss = make_compute_loss(module, init_stats)

    stats_fn = loss_val = None
    if init_stats:  # stateful BN (--batchnorm): running-stats eval
        stats_fn = make_bn_stats_fn(module, init_stats)
        loss_val = make_compute_loss_eval(module)
    model = FedModel(module, params, compute_loss, args,
                     compute_loss_val=loss_val,
                     padded_batch_size=train_loader.B,
                     stats_fn=stats_fn, init_model_state=init_stats)
    # the loader's spans go onto this model's round records, and its
    # thread places each round's batch with this model's placement
    train_loader.telemetry = model.telemetry
    train_loader.placement = model.placement
    if hasattr(train_loader, "peek_next_client_ids"):
        # host client store: the loader's one-round lookahead feeds
        # the prefetch thread (no-op under --clientstore device)
        model.attach_participant_feed(
            train_loader.peek_next_client_ids)

    if args.model.startswith("Fixup") and args.mode != "fedavg":
        # Fixup LR groups (reference cv_train.py:366-376): bias and
        # scale parameters train at 0.1x; built as flat-vector index
        # groups so the per-coordinate LR lines up exactly. The
        # nominal-LR group comes first so logged LR is the schedule's.
        from commefficient_tpu.ops.vec import param_group_indices
        bias_idx, scale_idx, other_idx = param_group_indices(
            params, fixup_bias_name, fixup_scale_name)
        param_groups = [{"lr": 1.0, "index": other_idx},
                        {"lr": 0.1, "index": bias_idx},
                        {"lr": 0.1, "index": scale_idx}]
        print("using fixup learning rates")
    else:
        if args.model.startswith("Fixup") and args.mode == "fedavg":
            # fedavg's client local SGD uses one shared scalar LR
            # (reference g_lr shm, fed_worker.py:57), so per-group
            # Fixup LRs cannot apply — unlike the reference, which
            # also ignores them silently in this combination
            print("WARNING: fedavg uses a scalar LR; Fixup bias/scale "
                  "0.1x groups are not applied")
        param_groups = [{"lr": 1.0}]
    opt = FedOptimizer(param_groups, args)

    spe = steps_per_epoch(args.local_batch_size, train_ds,
                          args.num_workers)
    horizon = args.schedule_epochs or args.num_epochs
    if model_cfg is not None \
            and model_cfg.lr_schedule_shape is not None:
        # per-model epoch-indexed shape x args.lr_scale (the working
        # form of the reference's ModelConfig pattern) — an explicit
        # --lr_scale still takes effect
        shape = model_cfg.lr_schedule_shape
        lr_scheduler = LambdaLR(
            opt, lambda x: args.lr_scale * shape(x / spe))
    else:
        lambda_step = PiecewiseLinear(
            [0, args.pivot_epoch * spe, horizon * spe],
            [0, args.lr_scale, 0])
        lr_scheduler = LambdaLR(opt, lambda x: lambda_step(x))

    from commefficient_tpu.runtime.checkpoint import setup_resume
    start_epoch, epoch_hook, round_hook = setup_resume(
        args, model, opt, lr_scheduler, train_loader, tag=args.model)

    from commefficient_tpu.utils import GracefulShutdown, sigterm_raises
    interrupted = False
    try:
        with sigterm_raises():
            results = train(model, opt, lr_scheduler, train_loader,
                            val_loader, args, start_epoch=start_epoch,
                            epoch_hook=epoch_hook,
                            round_hook=round_hook)
    except GracefulShutdown as e:
        # crash safety: drop in-flight round state, close everything
        # cleanly, and save NOTHING here — the last round-cadence
        # autosave is the consistent resume point, and an end-of-run
        # save now would capture a mid-round server state
        print(f"interrupted ({e}); resume from the last autosave")
        interrupted = True
        results = []
        if model.flightrec is not None:
            # the postmortem preserves the rounds the ledger may not
            # have flushed — dumped before interrupted() discards the
            # in-flight host state it describes
            model.flightrec.dump("graceful_shutdown",
                                 context={"signal": str(e)})
        model.interrupted()
    model.finalize()
    from commefficient_tpu.runtime.checkpoint import \
        resume_manifest_extra
    from commefficient_tpu.telemetry import registry
    registry.maybe_write_manifest(
        args, mesh_shape=dict(model.mesh.shape),
        extra={"trainer": "cv_train", "epochs": len(results),
               "interrupted": interrupted,
               "diverged": bool(getattr(model, "diverged", False)),
               **resume_manifest_extra(model)})

    if args.do_checkpoint and not interrupted \
            and jax.process_index() == 0:
        # params are replicated — one writer on a shared filesystem
        import os
        import pickle
        os.makedirs(args.checkpoint_path, exist_ok=True)
        path = os.path.join(args.checkpoint_path, args.model + ".pkl")
        # audit: allow(host-sync) — end-of-run checkpoint write
        params = jax.device_get(model.params())
        with open(path, "wb") as f:
            pickle.dump(params, f)
        print(f"saved checkpoint to {path}")
        # the reference's exact artifact: torch.save(state_dict) named
        # <model>.pt (cv_train.py:420-423), reference torch key names
        from commefficient_tpu.models.torch_export import (
            save_torch_state_dict, supports_torch_export)
        if supports_torch_export(model.module):
            tpath = os.path.join(args.checkpoint_path,
                                 args.model + ".pt")
            save_torch_state_dict(model.module, params,
                                  getattr(model, "model_state", None),
                                  tpath)
            print(f"saved torch state_dict to {tpath}")
    # the native loader's ring and worker threads end with the run
    train_loader.close()
    return TrainRun(results, model, opt, train_loader)


if __name__ == "__main__":
    sys.exit(cli())

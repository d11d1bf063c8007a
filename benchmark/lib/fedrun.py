"""One assembled federated run, as the harness drives it.

A builder (``benchmark/builders/<family>.py``) assembles the trainer's
own objects exactly as its ``run()`` does and wraps them in a
``FedRun``; the harness then calls ``step`` once per round, which is
the body of the trainer's ``run_batches`` loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np


@dataclasses.dataclass
class FedRun:
    model: Any                 # runtime.FedModel
    opt: Any                   # runtime.FedOptimizer
    lr_scheduler: Any
    loader: Any                # the trainer's own train loader
    args: Any                  # the program's Config
    ref_spec: dict             # what the plain reference needs of the model
    ref_batch: Callable        # loader batch -> {name: (W, B, ...) numpy}
    make_params: Callable      # () -> the initial weights, again from the seed
    zero_lr_hack: bool = False  # cv_train's LR == 0 "HACK STEP"
    batch_note: Any = None     # loader batch -> a line about its filling
    last_aggregate: Any = None  # what the server got, when asked to keep it

    @property
    def clients_per_round(self) -> int:
        return int(self.args.num_workers)

    def lr(self) -> float:
        return float(self.opt.param_groups[0]["lr"])

    def step(self, batch, keep_aggregate=False):
        """One round as ``run_batches`` runs it. Returns (per-client
        losses, per-client real-sample counts, download bytes, upload
        bytes) once the round's metrics are on the host.
        ``keep_aggregate`` holds on to what the round handed the server
        (the sketch table) before ``opt.step()`` consumes it."""
        self.lr_scheduler.step()
        if self.zero_lr_hack and self.opt.param_groups[0]["lr"] == 0:
            for g in self.opt.param_groups:
                g["lr"] = 1e-10
        metrics = self.model(batch)
        if keep_aggregate:
            self.last_aggregate = self.model.pending_aggregated
        self.opt.step()
        w = np.asarray(batch["mask"]).sum(axis=1)
        return (np.asarray(metrics[0]), w, float(metrics[-2].sum()),
                float(metrics[-1].sum()))

    def hyper(self) -> dict:
        a = self.args
        return {"k": int(a.k), "rho": float(a.virtual_momentum),
                "weight_decay": float(a.weight_decay),
                "num_workers": int(a.num_workers)}

    def sketch_spec(self) -> dict:
        """The geometry and hash parameters of the run's count sketch,
        as resolved by the program for this device."""
        from commefficient_tpu.core.rounds import args2sketch
        sk = args2sketch(self.args)
        return {"d": int(sk.d), "c": int(sk.c), "r": int(sk.r),
                "seed": int(sk.seed), "rot_lanes": int(sk.rot_lanes)}

    def engagement(self) -> dict:
        """Which sketch backend the run resolved to, and how many Mosaic
        kernels its two programs hold (lowering only, nothing runs)."""
        import jax
        import jax.numpy as jnp
        from commefficient_tpu.core.rounds import args2sketch
        model, opt = self.model, self.opt
        backend = args2sketch(self.args)._resolve_backend()
        var = model._variants.get(model._variant_key)
        client = var.round_fn.lower(*model._round_abstract).as_text()
        ids_in = model._round_abstract[3]

        def like(a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None)

        server = opt._server_round.lower(
            like(model.ps_weights),
            jax.tree_util.tree_map(like, opt.server_state),
            jax.ShapeDtypeStruct(tuple(self.args.transmit_shape),
                                 jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32), None, ids_in,
            like(opt._noise_rng)).as_text()
        return {"sketch_backend": backend,
                "client_custom_calls": client.count("tpu_custom_call"),
                "server_custom_calls": server.count("tpu_custom_call")}


def trainer_flags(cell, config, rehearse=False):
    """The trainer flags of a cell: the configuration's, the cell's, and
    either the stated precision or, in a rehearsal, the tiny float32
    presets (float32, so that agreement shows the mathematics)."""
    flags = list(config["flags"]) + list(cell["flags"])
    if rehearse:
        return flags + list(cell["rehearse_flags"])
    return flags + list(config["precision_flags"])


def seeded_params(ref, ref_spec, seed):
    """() -> the reference's initial weights from ``seed``, made on the
    device in one jitted call; called again after the window, so that
    no copy of the weights is held through it."""
    import jax

    def make_params():
        return jax.jit(lambda k: ref.init_params(k, ref_spec))(
            jax.random.PRNGKey(seed))

    return make_params


def check_tree_matches(made, module_shapes):
    """The weights the benchmark made have the names and shapes the
    program's module declares; raises with the first difference."""
    import jax
    a = jax.tree_util.tree_flatten_with_path(made)[0]
    b = jax.tree_util.tree_flatten_with_path(module_shapes)[0]
    names_a = [jax.tree_util.keystr(p) for p, _ in a]
    names_b = [jax.tree_util.keystr(p) for p, _ in b]
    if names_a != names_b:
        diff = sorted(set(names_a) ^ set(names_b))[:6]
        raise ValueError(f"reference and module parameter names differ: {diff}")
    for (p, x), (_, y) in zip(a, b):
        if tuple(x.shape) != tuple(y.shape):
            raise ValueError(
                f"{jax.tree_util.keystr(p)}: reference {x.shape} vs "
                f"module {y.shape}")

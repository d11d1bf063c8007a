"""Sequence-parallel federated runtime for GPT-2 (``--seq_devices N``).

Drop-in FedModel variant whose TRAIN path runs the 2-D
clients x seq round (core/rounds_sp.py): each client's forward/backward
is sequence-sharded over ``seq_devices`` chips with ring (or Ulysses)
attention, so context length scales with chips — a capability the
reference lacks entirely (SURVEY.md §2.8). Validation and the
FedOptimizer server step are inherited unchanged.

Mode composition: the SP round produces the round's aggregated DENSE
gradient. ``uncompressed``/``true_topk`` consume it directly; for
``sketch`` it is table-ized once server-side — by sketch linearity
this equals the psum of per-client sketches, so the server math is
identical to the 1-D engine's. Modes needing per-client local state
(local momentum/error, local_topk, fedavg, topk_down) are rejected.

Objective notes (differences vs the 1-D engine, both deliberate):
- clients are weighted equally (per-client mean), vs datapoint-count
  weighting — the standard FedAvg-style choice for ragged clients;
- each client's LM loss is a token-mean over ALL its valid tokens,
  vs the 1-D path's mean of per-example token-means — longer examples
  weigh proportionally to their length. Toggling --seq_devices
  therefore changes training dynamics slightly at equal LR.
Weight decay is applied with the 1-D engine's effective coefficient
(weight_decay / num_workers, see core/grad.py). ``--max_grad_norm``
and ``--dp`` are per-client pre-aggregation operations that cannot be
recovered from the aggregated gradient — they are rejected rather than
silently dropped. Byte accounting is inherited.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import args2sketch
from commefficient_tpu.core.rounds_sp import (build_sp_gpt2_round,
                                              make_sp_mesh,
                                              shift_lm_labels)
from commefficient_tpu.parallel.mesh import on_every_device
from commefficient_tpu.runtime.fed_model import FedModel
from commefficient_tpu.telemetry import clock


class SeqParallelFedModel(FedModel):
    def __init__(self, module, params, compute_loss, args: Config,
                 gpt2_cfg, compute_loss_val=None,
                 padded_batch_size=None):
        if args.mode not in ("uncompressed", "sketch", "true_topk"):
            raise ValueError(
                f"--seq_devices does not support mode={args.mode} "
                "(needs per-client local state)")
        if args.local_momentum > 0 or args.error_type == "local" \
                or args.do_topk_down:
            raise ValueError("--seq_devices requires local_momentum 0, "
                             "error_type none/virtual, no topk_down")
        if args.max_grad_norm is not None or args.do_dp:
            raise ValueError(
                "--seq_devices does not support --max_grad_norm/--dp "
                "(per-client clipping/noise happens before "
                "aggregation and cannot be applied afterwards)")
        n_dev = len(jax.devices())
        if n_dev % args.seq_devices != 0:
            raise ValueError(f"seq_devices={args.seq_devices} must "
                             f"divide device count {n_dev}")
        n_client_axis = n_dev // args.seq_devices

        super().__init__(module, params, compute_loss, args,
                         compute_loss_val=compute_loss_val,
                         padded_batch_size=padded_batch_size)
        # its rounds take their batch on the sequence mesh, placed by
        # _client_pass below: a loader has nothing to place ahead
        from commefficient_tpu.data import staging
        staging.withdraw(self.place_batch)
        self.placement = None

        sp_cfg = dataclasses.replace(gpt2_cfg,
                                     seq_impl=args.seq_impl)
        self._sp_mesh = make_sp_mesh(n_client_axis, args.seq_devices)
        sp_round = build_sp_gpt2_round(
            sp_cfg, self._sp_mesh, self.unravel,
            lm_coef=args.lm_coef, mc_coef=args.mc_coef,
            ignore_index=-1, tokens_per_chunk=args.tokens_per_chunk)
        sketch = args2sketch(args)
        wd = args.weight_decay / max(args.num_workers, 1)
        probes_on = self.probe_period > 0

        def make_round(with_recovery):
            @jax.jit
            def round_and_compress(ps, batch):
                agg, loss = sp_round(ps, batch)
                if wd > 0:  # 1-D engine's effective decay (core/grad.py)
                    agg = agg + wd * ps
                dense = agg
                if sketch is not None:
                    # linearity: sketch(mean of grads) == mean of
                    # sketches. ``dense`` is replicated; every device
                    # sketches it whole (a Mosaic kernel cannot sit in
                    # a partitioned jit)
                    agg = on_every_device(sketch.sketch,
                                          self._sp_mesh)(dense)
                pr = None
                if probes_on:
                    from commefficient_tpu.core.rounds import _agg_probes
                    pr = _agg_probes(agg)
                    if with_recovery and sketch is not None:
                        # the dense aggregate exists pre-sketch on
                        # this path, so ground truth is free here
                        pr["recovery_error"] = on_every_device(
                            lambda t, g: sketch.recovery_error(
                                t, g, args.k),
                            self._sp_mesh)(agg, dense)
                return agg, loss, pr
            return round_and_compress

        self._sp_round = make_round(False)
        self._sp_round_probed = (
            make_round(True)
            if probes_on and sketch is not None else None)

    def _client_pass(self, batch, ridx):
        tel = self.telemetry
        eng = self.alarm_engine
        step_t0 = (clock.tick()
                   if eng is not None and eng.step_time_ratio > 0
                   else None)
        ids_np = np.asarray(batch["client_ids"])
        W = ids_np.shape[0]
        if W % self._sp_mesh.shape["clients"] != 0:
            raise ValueError(
                f"num_workers {W} must be divisible by the client "
                f"axis {self._sp_mesh.shape['clients']}")
        with tel.span("h2d"):
            sp_batch = {
                "input_ids": jnp.asarray(batch["input_ids"]),
                "token_type_ids": jnp.asarray(batch["token_type_ids"]),
                "shifted_labels": shift_lm_labels(
                    jnp.asarray(batch["lm_labels"])),
                "mc_token_ids": jnp.asarray(batch["mc_token_ids"]),
                "mc_labels": jnp.asarray(batch["mc_labels"]),
                "mask": jnp.asarray(batch["mask"]),
            }
        round_fn = self._sp_round
        if (self._sp_round_probed is not None
                and ridx % self.probe_period == 0):
            round_fn = self._sp_round_probed
        with tel.span("round_dispatch"):
            agg, per_client_loss, probes = round_fn(self.ps_weights,
                                                    sp_batch)
        self.pending_aggregated = agg
        self.pending_client_ids = jnp.asarray(ids_np, jnp.int32)
        self.round_index += 1
        tel.close_round()
        self._settle_after_dispatch()

        # per-client losses, like the 1-D engine's metrics arrays —
        # the trainer weights them by real sample counts. _host, not
        # device_get: the (W,) vector is client-axis sharded and not
        # fully addressable on a multi-process mesh
        from commefficient_tpu.runtime.fed_model import _host
        with tel.span("metrics_host"):
            metrics = [np.asarray(_host(per_client_loss), np.float64)]
            probe_vals = (None if probes is None else
                          {k: float(_host(v))
                           for k, v in probes.items()})
        if probe_vals is not None:
            tel.merge_round_probes(ridx, probe_vals)
            self._probe_host[ridx] = probe_vals
        if step_t0 is not None:
            eng.check_step_time(ridx, clock.tick() - step_t0)
        with tel.span("account"):
            down, up = self._account_bytes(ids_np, batch["mask"])
        tel.set_round_bytes(ridx, float(down.sum()), float(up.sum()))
        return metrics + [down, up]

"""Per-client gradient computation: microbatching, clipping, weight
decay, differential privacy, sketching.

Functional counterpart of the reference's ``forward_grad``
(fed_worker.py:251-337). A *loss function* here is

    loss_fn(params_flat, batch) -> (loss, aux_metrics_tuple)

where ``batch`` is a dict of arrays whose leading axis is the sample
axis, including a ``"mask"`` float array marking real (1.0) vs padded
(0.0) samples — padding is how ragged per-client batches become static
shapes under jit (SURVEY.md §7 "hard parts"). ``loss`` must be the
masked *mean* over real samples (like the reference's per-microbatch
mean loss), and metrics likewise.

Reference semantics kept bit-for-bit-in-spirit:
- with microbatching, the gradient is the **sum over microbatches of
  the per-microbatch mean gradient** (a deliberate reference quirk:
  loss.backward() accumulates mean-loss grads, fed_worker.py:268-289 —
  which is why its clip threshold scales by num_iters);
- grad-norm clipping to ``max_grad_norm * num_iters`` for non-sketch
  modes (fed_worker.py:292-294);
- fused weight decay ``g += (wd / num_workers) * weights``
  (utils.py:254-259);
- DP: L2-clip to ``l2_norm_clip``; in worker mode add Gaussian noise
  scaled by ``noise_multiplier * sqrt(num_workers)``
  (fed_worker.py:306-311);
- sketch mode: sketch the gradient, then clip the *sketch* by its
  l2estimate if max_grad_norm is set (fed_worker.py:314-322).
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.ops.sketch import CountSketch, clip_record
from commefficient_tpu.ops.vec import clip_by_l2


def _masked_count(batch) -> jax.Array:
    return jnp.maximum(jnp.sum(batch["mask"]), 1.0)


def make_forward_grad(cfg: Config,
                      loss_fn: Callable,
                      sketch: Optional[CountSketch],
                      padded_batch_size: int):
    """Returns ``forward_grad(params_flat, batch, noise_rng) ->
    (transmit_unit, metrics)`` where ``transmit_unit`` is the
    per-sample-mean (possibly sketched) gradient and ``metrics`` is a
    tuple of batch-mean scalars led by the loss."""

    if cfg.microbatch_size > 0:
        mb = min(cfg.microbatch_size, padded_batch_size)
        num_iters = math.ceil(padded_batch_size / mb)
        pad_to = num_iters * mb
    else:
        mb, num_iters, pad_to = padded_batch_size, 1, padded_batch_size

    grad_loss = jax.grad(
        lambda p, b: loss_fn(p, b)[0], argnums=0)

    def one_microbatch(params_flat, microbatch):
        with jax.named_scope("fwd_bwd"):
            loss, metrics = loss_fn(params_flat, microbatch)
        n = jnp.sum(microbatch["mask"])
        with jax.named_scope("fwd_bwd"):
            g = grad_loss(params_flat, microbatch)
        # an all-padding microbatch contributes nothing (the reference
        # never creates one; padding does)
        valid = n > 0
        g = jnp.where(valid, g, 0.0)
        weighted = tuple(jnp.where(valid, m, 0.0) * n
                         for m in (loss,) + tuple(metrics))
        return g, weighted

    def forward_grad(params_flat, batch, noise_rng=None):
        if num_iters == 1:
            g, weighted = one_microbatch(params_flat, batch)
        else:
            def pad(x):
                pad_width = [(0, pad_to - x.shape[0])] + \
                    [(0, 0)] * (x.ndim - 1)
                return jnp.pad(x, pad_width)

            chunked = {k: pad(v).reshape((num_iters, mb) + v.shape[1:])
                       for k, v in batch.items()}

            def body(carry, microbatch):
                g_acc, w_acc = carry
                g, weighted = one_microbatch(params_flat, microbatch)
                return (g_acc + g,
                        tuple(a + w for a, w in zip(w_acc, weighted))), None

            n_metrics = len(loss_fn(params_flat,
                                    jax.tree_util.tree_map(
                                        lambda v: v[:1], batch))[1]) + 1
            # zero init tied to the batch (x*0 of a batch-derived
            # scalar): under shard_map a plain-zeros carry lacks the
            # body output's varying mesh axes (the gradient depends on
            # the client-sharded batch) and trips the scan carry check
            z = 0.0 * _masked_count(batch)
            init = (jnp.zeros(cfg.grad_size, jnp.float32) + z,
                    tuple(jnp.zeros(()) + z for _ in range(n_metrics)))
            (g, weighted), _ = jax.lax.scan(body, init, chunked)

        batch_size = _masked_count(batch)
        metrics = tuple(w / batch_size for w in weighted)

        # per-worker grad clipping, non-sketch (fed_worker.py:292-294);
        # the reference's num_iters comes from the *real* batch size
        # (fed_worker.py:267), so derive it from the mask, not padding
        if cfg.max_grad_norm is not None and cfg.mode != "sketch":
            real_iters = jnp.ceil(batch_size / mb)
            g = clip_by_l2(g, cfg.max_grad_norm * real_iters)

        # fused weight decay (utils.py:254-259)
        if cfg.weight_decay != 0:
            g = g + (cfg.weight_decay / cfg.num_workers) * params_flat

        # differential privacy (fed_worker.py:306-311); the noise
        # draw routes through privacy/ — the one module allowed raw
        # jax.random noise (analysis/lint.py noise-confinement)
        if cfg.do_dp:
            from commefficient_tpu.privacy import gaussian_noise
            g = clip_by_l2(g, cfg.l2_norm_clip)
            if cfg.dp_mode == "worker":
                assert noise_rng is not None
                noise = gaussian_noise(noise_rng, g.shape, g.dtype,
                                       std=cfg.noise_multiplier)
                g = g + noise * jnp.sqrt(float(cfg.num_workers))

        # DP sketching (--dp sketch, privacy/): L2-clip the client's
        # SUMMED dense gradient — the microbatch-accumulated total,
        # never divided by batch_size, so --dp_clip is calibrated at
        # summed-gradient scale — BEFORE sketching. Sketching is
        # linear, so the aggregated table is the sketch of the
        # clipped sums and the calibrated table noise
        # (core/rounds.py) covers a sqrt(r)·dp_clip/W sensitivity.
        # Trace-time gate: "off" emits today's program bit-for-bit.
        if getattr(cfg, "dp", "off") == "sketch":
            from commefficient_tpu.privacy import dp_clip
            g = dp_clip(g, cfg.dp_clip)

        # compression (fed_worker.py:314-322)
        if cfg.mode == "sketch":
            assert sketch is not None
            with jax.named_scope("compress"):
                table = sketch.sketch(g)
            if cfg.max_grad_norm is not None:
                table = clip_record(table, cfg.max_grad_norm,
                                    is_sketch=True)
            return table, metrics

        return g, metrics

    return forward_grad


def make_eval_metrics(loss_fn: Callable):
    """Validation pass: metrics only, no gradient
    (fed_worker.py:180-183 with compute_grad=False)."""

    def eval_metrics(params_flat, batch) -> Tuple[jax.Array, ...]:
        loss, metrics = loss_fn(params_flat, batch)
        return (loss,) + tuple(metrics)

    return eval_metrics

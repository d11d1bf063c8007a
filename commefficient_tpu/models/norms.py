"""Batch-statistics normalization, with optional federated running
statistics.

The reference's torch BatchNorm keeps running averages per worker
process that never federate and diverge per-worker (SURVEY.md §7
"BatchNorm under client-vmap"). Two TPU-native forms live here:

- default (``track_stats=False``): normalize by the current batch
  statistics in train AND eval, with no mutable state — every model
  stays a pure function of (params, x), exactly what vmap-over-clients
  and the flat-param-vector runtime (ops/vec.py) assume.
- ``track_stats=True`` (ResNet9 ``--batchnorm``): additionally record
  the raw batch mean/var in a flax ``batch_stats`` collection each
  train-mode application. The *server* blends participating clients'
  round-averaged statistics into one canonical running-stats state
  (runtime/fed_model.py), which eval reads via
  ``use_running_average=True`` — so eval metrics are independent of
  the eval batch composition, like the reference's
  ``nn.BatchNorm2d`` eval (models/resnet9.py:32-59), but with a
  single well-defined server state instead of per-worker drift.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


class BatchStatNorm(nn.Module):
    """Per-channel normalization over (N, H, W) with learned scale and
    bias. ``use_running_average`` reads the ``batch_stats`` collection
    instead of computing batch statistics; ``track_stats`` records the
    raw batch statistics (no client-side momentum — the server applies
    the running-average blend, see module docstring)."""
    epsilon: float = 1e-5
    scale_init: float = 1.0
    use_running_average: bool = False
    track_stats: bool = False

    @nn.compact
    def __call__(self, x, mask=None):
        """``mask``: optional (N,) row-validity weights. Padded rows
        (static-shape ragged client batches, SURVEY.md §7) must not
        enter the statistics — the reference's BN only ever sees real
        samples because torch batches are dynamically sized."""
        c = x.shape[-1]
        scale = self.param("scale",
                           nn.initializers.constant(self.scale_init),
                           (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        if self.track_stats:
            ra_mean = self.variable("batch_stats", "mean",
                                    lambda: jnp.zeros((c,), jnp.float32))
            ra_var = self.variable("batch_stats", "var",
                                   lambda: jnp.ones((c,), jnp.float32))
        if self.use_running_average:
            assert self.track_stats, \
                "use_running_average needs track_stats"
            mean, var = ra_mean.value, ra_var.value
        elif mask is not None:
            # statistics reduce in float32 regardless of compute
            # dtype (an 8-bit-mantissa sum over N*H*W elements per
            # channel would corrupt them, and they feed the server's
            # running stats)
            xf = x.astype(jnp.float32)
            w = mask.reshape((-1,) + (1,) * (x.ndim - 1)) \
                .astype(jnp.float32)
            denom = jnp.maximum(
                jnp.sum(w) * float(np.prod(x.shape[1:-1])), 1.0)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.sum(xf * w, axis=axes) / denom
            var = jnp.sum(jnp.square(xf - mean) * w,
                          axis=axes) / denom
            if self.track_stats and not self.is_initializing():
                ra_mean.value = mean
                # recorded (not normalizing) variance gets the Bessel
                # n/(n-1) correction: torch BatchNorm2d normalizes with
                # the biased estimate but feeds the UNBIASED one into
                # running_var, and the server's blend must match that
                ra_var.value = var * (denom / jnp.maximum(
                    denom - 1.0, 1.0))
        else:
            axes = tuple(range(x.ndim - 1))
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            var = jnp.var(xf, axis=axes)
            if self.track_stats and not self.is_initializing():
                n = float(np.prod(x.shape[:-1]))
                ra_mean.value = mean
                ra_var.value = var * (n / max(n - 1.0, 1.0))
        inv = (scale * jax.lax.rsqrt(var + self.epsilon)).astype(x.dtype)
        return x * inv + (bias - mean * inv).astype(x.dtype)


class RMSNorm(nn.Module):
    """Root-mean-square norm over the last axis with a learned scale,
    in float32 whatever the input's dtype (the causal LMs' norm)."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale

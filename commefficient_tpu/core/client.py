"""Client-side update pipeline: momentum, error feedback, compression.

Pure-functional counterpart of the reference worker's ``local_step``
(fed_worker.py:186-232). Operates on whatever the client transmits —
the flat gradient vector, or its (r, c) count-sketch table — given the
per-sample-mean gradient already produced by the model's forward/
backward (see core/grad.py for that part).

Exact reference semantics reproduced:
- the transmitted quantity is the *sum*-of-gradients over the client's
  batch: ``g = g_mean * batch_size`` (fed_worker.py:192);
- local momentum: ``velocity = g + m * velocity`` (fed_worker.py:195-197);
- local error accumulation: ``error += velocity`` (or ``g`` when no
  momentum), transmit the error (fed_worker.py:200-204);
- local_topk: transmit ``topk(to_transmit)``, then error feedback
  (zero error at transmitted coords) and momentum factor masking (zero
  velocity at transmitted coords) (fed_worker.py:206-218).

State that a mode doesn't use is represented as ``None`` (the
reference only allocates the big per-client arrays for modes that need
them, fed_aggregator.py:123-129).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.ops.topk import topk


class ClientUpdate(NamedTuple):
    transmit: jax.Array                    # what this client uploads
    velocity: Optional[jax.Array]          # updated local momentum, or None
    error: Optional[jax.Array]             # updated local error, or None


def accumulate_and_compress(cfg: Config,
                            g_unit: jax.Array,
                            velocity: Optional[jax.Array],
                            error: Optional[jax.Array],
                            batch_size: jax.Array) -> ClientUpdate:
    """One client's momentum/error/compression step.

    ``g_unit`` is the client's per-sample-mean gradient — already
    weight-decayed, clipped, DP-noised and (in sketch mode) sketched,
    i.e. the output of the reference's ``forward_grad``
    (fed_worker.py:251-337). ``batch_size`` is the client's true
    (unpadded) number of samples this round.
    """
    has_velocity = cfg.local_momentum > 0
    has_error = cfg.error_type == "local"
    assert (velocity is not None) == has_velocity
    assert (error is not None) == has_error

    # sum-of-gradients semantics; scaling commutes with sketching
    # (linear), matching the reference's compress-then-scale order
    g = g_unit * batch_size

    if has_velocity:
        velocity = g + cfg.local_momentum * velocity

    if has_error:
        error = error + (velocity if has_velocity else g)
        to_transmit = error
    else:
        to_transmit = velocity if has_velocity else g

    if cfg.mode == "local_topk":
        assert cfg.error_type in ("local", "none")
        with jax.named_scope("compress"):
            to_transmit = topk(to_transmit, k=cfg.k,
                               approx=cfg.approx_topk,
                               recall=cfg.approx_recall)
        kept = to_transmit != 0
        if has_error:
            error = jnp.where(kept, 0.0, error)      # error feedback
        if has_velocity:
            velocity = jnp.where(kept, 0.0, velocity)  # momentum masking

    # invariants the reference asserts in the hot path
    # (fed_worker.py:221-230)
    if has_error:
        assert cfg.mode not in ("sketch", "uncompressed")
    if has_velocity:
        assert cfg.mode != "sketch"

    return ClientUpdate(to_transmit, velocity, error)


def stale_weight_download(cfg: Config,
                          ps_weights: jax.Array,
                          client_weights: jax.Array) -> jax.Array:
    """Simulated download compression for ``--topk_down`` (reference
    ``get_new_worker_weights``, fed_worker.py:234-249): the client
    catches up to the server by applying only the top-k of the weight
    difference to its stale local weights."""
    diff = ps_weights - client_weights
    if cfg.do_topk_down:
        diff = topk(diff, k=cfg.k, approx=cfg.approx_topk,
                    recall=cfg.approx_recall)
    return client_weights + diff

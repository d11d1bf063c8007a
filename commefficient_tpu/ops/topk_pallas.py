"""Pallas TPU kernel for the exact threshold-select mask.

The XLA formulation in :mod:`commefficient_tpu.ops.topk`
(`_threshold_topk_mask`) materialises several (d,)-sized
intermediates after the bit search — keys, gt/eq masks, the int32
tie-rank cumsum and the combined take mask — ~45 ms of HBM traffic at
GPT-2's d = 124M. This kernel fuses all of it into ONE streamed read
of the squared-magnitude vector and one int8 mask write: the grid
walks chunks sequentially (TPU grid order is sequential) carrying the
running equal-to-threshold count in SMEM, so the lowest-index
tie-break is computed exactly as the XLA path does.

Used by the 1-D, non-vmapped server-side selections (unsketch
recovery, true_topk). The generic batched mask in ops/topk.py stays
XLA — a vmapped pallas_call would batch the grid and break the
sequential-carry tie-break.

No reference counterpart: the reference's exact top-k is torch.topk
on GPU (utils.py:232-252); this is the TPU-native answer to its cost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.pallas_common import out_struct

# chunk geometry: 512 x 128 = 64K f32 elements = 256 KB VMEM per
# buffered block — well within budget, big enough to amortise grid
# overhead at d ~ 1e8 (~1900 steps)
_S = 512
_L = 128
_CHUNK = _S * _L


def supported(d: int) -> bool:
    """Worth the kernel only when the XLA intermediates hurt."""
    return d >= _CHUNK


@functools.partial(jax.jit, static_argnums=(3,))
def take_mask_pallas(sq, t_key, need, interpret: bool = False):
    """``sq`` (d,) f32 non-negative keys (squared magnitudes),
    ``t_key`` (1,) uint32 — the k-th largest key's bit pattern from
    the threshold search, ``need`` (1,) int32 — how many
    equal-to-threshold elements to take (k − count(gt)).

    Returns a (d,) bool mask with exactly k True: every key > T plus
    the first ``need`` keys == T in index order."""
    d = sq.shape[0]
    pad = (-d) % _CHUNK
    # padded zeros: key 0 is only ever eq when T == 0, and then the
    # real elements' ranks all precede the pads', so need is exhausted
    # before any pad (count(real keys >= 0) = d >= k)
    sqp = jnp.pad(sq, (0, pad))
    m = (d + pad) // _CHUNK

    def kernel(t_ref, need_ref, x_ref, out_ref, cnt_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            cnt_ref[0] = 0

        keys = jax.lax.bitcast_convert_type(x_ref[:], jnp.uint32)
        T = t_ref[0]
        gt = keys > T
        eq = keys == T
        eqf = eq.astype(jnp.float32)
        # row-major rank of each eq element within the chunk, via
        # triangular matmuls (Mosaic has no cumsum primitive; the MXU
        # does prefix sums for free at tile scale, exact in f32 —
        # counts <= S*L = 64K << 2^24)
        li = jax.lax.broadcasted_iota(jnp.int32, (_L, _L), 0)
        lj = jax.lax.broadcasted_iota(jnp.int32, (_L, _L), 1)
        upper = (li <= lj).astype(jnp.float32)       # (L, L)
        lane_cum = jnp.dot(eqf, upper,
                           preferred_element_type=jnp.float32)
        row_tot = lane_cum[:, _L - 1:_L]             # (S, 1)
        si = jax.lax.broadcasted_iota(jnp.int32, (_S, _S), 0)
        sj = jax.lax.broadcasted_iota(jnp.int32, (_S, _S), 1)
        strict_lower = (sj < si).astype(jnp.float32)  # (S, S)
        row_off = jnp.dot(strict_lower, row_tot,
                          preferred_element_type=jnp.float32)
        rank = (lane_cum.astype(jnp.int32)
                + row_off.astype(jnp.int32) + cnt_ref[0])  # 1-based
        take = gt | (eq & (rank <= need_ref[0]))
        # int8 here is a kernel-local selection bitmap (VMEM out
        # buffer), not a wire format — it never crosses the ICI/host
        # boundary, so quant.py's byte accounting doesn't apply.
        out_ref[:] = take.astype(jnp.int8)  # audit: allow(wire-dtype-crossing)
        cnt_ref[0] = cnt_ref[0] + jnp.sum(eqf).astype(jnp.int32)

    operands = (t_key.astype(jnp.uint32).reshape(1),
                need.astype(jnp.int32).reshape(1),
                sqp.astype(jnp.float32).reshape(m * _S, _L))
    out = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_S, _L), lambda t: (t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_S, _L), lambda t: (t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((m * _S, _L), jnp.int8, *operands),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
        name="take_mask_pallas",
    )(*operands)
    return out.reshape(-1)[:d].astype(bool)

"""Wire-dtype collective crossings over the device mesh
(``--sketch_dtype``).

``ops/quant.py`` owns the quantization *algebra* — scales, summation
headroom, rounding; this module owns where that algebra meets the
*mesh*: which axes the row maxima are pmax'd over, which collective
moves the wire-dtype payload, and the dequantize on the far side.
``core/rounds.py`` routes every quantized wire crossing through here,
so the collective-facing surface the static auditor matches against
(`analysis/program.py`: the wire-dtype psum/psum_scatter plus exactly
one (r, 1) f32 rowmax pmax) has a single owner, like the sharding
specs in ``parallel/mesh.py``.
"""

from __future__ import annotations

import jax

from commefficient_tpu.ops import quant


def quantize_for_collective(table: jax.Array, wire: str, axes,
                            n_addends: int):
    """Local f32 table -> ``(wire-dtype table, shared scale)`` ready
    for a wire-dtype psum/psum_scatter over ``axes``: local-quantize
    at full range, pmax the rowmax over the participating mesh axes
    (the (r, 1) f32 side-channel the ledger counts), harmonize onto
    the shared scale with ``n_addends`` summation headroom. bf16 is
    scale-free (scale None)."""
    q, rowmax = quant.quantize_local(table, wire)
    grm = (quant.global_rowmax_over(rowmax, axes)
           if rowmax is not None else None)
    return quant.harmonize(q, rowmax, grm, wire, n_addends)


def wire_allreduce(q: jax.Array, scale, axis_name) -> jax.Array:
    """The table's aggregation all-reduce at wire width: psum the
    quantized table over ``axis_name`` and dequantize on the far side
    — downstream (server momentum/EF) only ever sees f32."""
    return quant.dequantize(jax.lax.psum(q, axis_name), scale)


def wire_reduce_scatter(q: jax.Array, axis_name,
                        scatter_dimension: int = 1) -> jax.Array:
    """The 2D emission's model-axis crossing: sum partial tables and
    leave each peer its column shard — at wire width when ``q`` is
    quantized (r·c·wb/M per link instead of 4·r·c/M)."""
    return jax.lax.psum_scatter(q, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def row_chunks(r: int, depth: int):
    """``--overlap_depth`` row chunking: ceil-split ``r`` table rows
    into ``min(depth, r)`` contiguous chunks, returned as
    ``[(offset, count), ...]``. Depth is clamped (never an error) so
    one sweep flag works across geometries; clamped depths still name
    distinct programs (an o4 run of a 3-row table is 3 chunks — a
    different program from o2's 2, so the registry's ``o<N>`` keys
    stay honest). Chunks are disjoint row ranges: the collective over
    each composes with per-row quantization scales exactly, so the
    chunked fold is bit-identical to the whole-table crossing."""
    assert r >= 1 and depth >= 1, (r, depth)
    n = min(depth, r)
    size = -(-r // n)
    out = []
    off = 0
    while off < r:
        cnt = min(size, r - off)
        out.append((off, cnt))
        off += cnt
    return out


def chunked_quantize_allreduce(table: jax.Array, wire: str, axes,
                               n_addends: int, axis_name,
                               depth: int) -> jax.Array:
    """Row-chunked quantize + all-reduce: quantize and psum each
    disjoint row chunk separately, interleaved in emission order so
    XLA's latency-hiding scheduler can run chunk i's collective under
    chunk i+1's quantize. Per-row scales make each chunk's algebra
    identical to the row slice of the whole-table crossing (rowmax of
    a chunk == the chunk's rows of the whole-table rowmax), so the
    concatenated result matches ``quantize_for_collective`` +
    ``wire_allreduce`` bit-for-bit — only the collective granularity
    changes. f32 chunks skip quantization (plain per-chunk psum)."""
    import jax.numpy as jnp
    r = table.shape[0]
    parts = []
    for off, cnt in row_chunks(r, depth):
        chunk = jax.lax.slice_in_dim(table, off, off + cnt, axis=0)
        if wire == "f32":
            parts.append(jax.lax.psum(chunk, axis_name))
        else:
            q, scale = quantize_for_collective(chunk, wire, axes,
                                               n_addends)
            parts.append(wire_allreduce(q, scale, axis_name))
    return jnp.concatenate(parts, axis=0)

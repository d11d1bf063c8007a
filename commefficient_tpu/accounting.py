"""Dtype-aware wire-byte accounting.

One home for every byte-width decision the ledger and the
auditor make. Before the quantized wire path every accounting site
hardcoded ``* 4`` (f32); now the uplink table, its per-row scales and
the downlink payload each carry their own dtype, so the arithmetic
lives here and the callers say *what* crossed the wire, not how wide
a float is. ``analysis/lint.py``'s ``byte-literal`` rule keeps inline
byte-width literals out of the accounting code paths.

Wire dtypes are named by the ``--sketch_dtype`` flag surface
(``f32``/``bf16``/``int8``/``fp8``), not by numpy names, because the
name keys perf baselines and audit programs — ``fp8`` pins e4m3fn.
"""

from __future__ import annotations

from typing import Iterable, Union

import numpy as np

# wire name -> (jnp dtype name, bytes per element, carries per-row scales)
# fp8 is e4m3fn: the wider-mantissa variant — sketch tables want
# resolution, the shared row scale absorbs range.
WIRE_DTYPES = {
    "f32": ("float32", 4, False),
    "bf16": ("bfloat16", 2, False),
    "int8": ("int8", 1, True),
    "fp8": ("float8_e4m3fn", 1, True),
}

# the per-row dequantization scales ride the wire as f32
SCALE_WIRE_BYTES = 4

# numpy has no bfloat16/float8; resolve those by name before asking
# np.dtype for the rest
_NAMED_WIDTHS = {
    "bfloat16": 2,
    "bf16": 2,
    "float8_e4m3fn": 1,
    "float8_e5m2": 1,
    "float8_e4m3": 1,
    "fp8": 1,
    "f32": 4,
    "int8": 1,
}


def dtype_bytes(dtype: Union[str, np.dtype, type]) -> int:
    """Bytes per element of ``dtype``.

    Accepts wire names (``f32``/``bf16``/``int8``/``fp8``), jnp dtype
    names (``bfloat16``, ``float8_e4m3fn``), numpy dtypes and scalar
    types.
    """
    name = getattr(dtype, "name", None) or (
        dtype if isinstance(dtype, str) else None)
    if name is not None and name in _NAMED_WIDTHS:
        return _NAMED_WIDTHS[name]
    if name is not None and name in WIRE_DTYPES:
        return WIRE_DTYPES[name][1]
    return int(np.dtype(dtype).itemsize)


def bytes_of(shape: Union[int, Iterable[int]], dtype) -> float:
    """Wire bytes of an array of ``shape`` and ``dtype``.

    The single source of truth for ``elements x width`` accounting
    math; returns float because the ledger's byte counters are f64
    accumulators.
    """
    if isinstance(shape, (int, np.integer)):
        n = int(shape)
    else:
        n = 1
        for s in shape:
            n *= int(s)
    return float(n) * float(dtype_bytes(dtype))


def wire_dtype_name(wire: str) -> str:
    """jnp dtype name for a wire name (validates the wire name)."""
    return WIRE_DTYPES[wire][0]


def wire_has_scales(wire: str) -> bool:
    """True when the wire format carries per-row f32 scales
    (int8/fp8); bf16 and f32 ride scale-free."""
    return WIRE_DTYPES[wire][2]


def sketch_wire_bytes(num_rows: int, num_cols: int, wire: str) -> float:
    """Uplink bytes for one quantized sketch table: the table at wire
    width plus, for the scaled dtypes, one f32 row-scale per row (the
    pmax'd rowmax that rides with the table)."""
    body = bytes_of((num_rows, num_cols), wire_dtype_name(wire))
    if wire_has_scales(wire):
        body += bytes_of((num_rows,), "f32")
    return body


def delta_downlink_bytes(changed: float, repeated: float,
                         prev_support: float, wire: str,
                         have_prev: bool = True) -> float:
    """Downlink bytes for one client under ``--downlink_encoding
    delta``: every changed coordinate ships its value at wire width;
    indices ship as int32 only for coordinates NOT repeated from the
    round the client last saw; repeats are named by a bitmap over the
    previous round's support (1 bit per previous index, byte-padded).

    ``have_prev`` is False when the client missed the previous
    broadcast (its cached support is stale) — then nothing can be
    delta-coded and every changed coordinate ships (idx, val).
    """
    if not have_prev:
        repeated = 0.0
        prev_support = 0.0
    vals = float(changed) * dtype_bytes(wire)
    idxs = (float(changed) - float(repeated)) * dtype_bytes(np.int32)
    bitmap = float(np.ceil(prev_support / 8.0)) if prev_support else 0.0
    return vals + idxs + bitmap

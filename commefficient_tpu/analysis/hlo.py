"""Text-level primitives over StableHLO / compiled-HLO dumps.

Everything here is pure string parsing — no jax import — so the same
helpers serve the CPU-mesh audit, the TPU selftest, and unit tests on
canned program text. Two dialects appear:

* *lowered* text (``jit(f).lower(...).as_text()``): StableHLO. Carries
  the donation attribute ``tf.aliasing_output`` on aliased arguments
  and typed ops like ``stablehlo.dot_general ... : (tensor<2x64xbf16>,
  ...)``.
* *compiled* text (``.compile().as_text()``): post-SPMD optimized HLO.
  The only place GSPMD-induced collectives exist, as op-defining lines
  like ``%all-reduce.7 = f32[64]{0} all-reduce(...)`` (async forms
  split into ``-start``/``-done``; we count starts, not dones), plus
  the ``input_output_alias={ {0}: (1, {}, may-alias) }`` header.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    # fp8 family: quantized sketch tables cross the wire as f8e4m3fn
    "f8e4m3fn": 1, "f8e4m3": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_DTYPES = "|".join(sorted(DTYPE_BYTES, key=len, reverse=True))

# one ``dtype[dims]`` shape inside a compiled-HLO result type; dims may
# be empty (scalar) and carry a layout suffix ``{1,0}`` we ignore
_SHAPE_RE = re.compile(rf"\b({_DTYPES})\[([0-9,]*)\]")

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

# op-defining occurrence: ``= <result type> <kind>(``; `-start` is the
# async issue (counted), `-done` just retires it (skipped)
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<result>\(?[^=()]*?\)?)\s*"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")"
    r"(?P<suffix>-start|-done)?\(")

# host-boundary ops in compiled HLO (op-defining position), plus the
# custom-call escape hatches for host callbacks in either dialect
_TRANSFER_RE = re.compile(
    r"=\s*[^=()]*?\b"
    r"(infeed|outfeed|send|send-done|recv|recv-done)\(")
_CALLBACK_MARKERS = ("xla_python_cpu_callback", "xla_ffi_python",
                     "callback_custom_call", "HostExecute",
                     "annotate_device_placement")

# stablehlo.dot_general / stablehlo.convolution with their typed
# signature ``: (tensor<AxBxbf16>, tensor<...>) -> ...``
_DOT_RE = re.compile(
    r"stablehlo\.(dot_general|convolution)\b[^\n]*?:\s*"
    r"\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)")


def parse_shape(dtype: str, dims: str) -> Tuple[str, Tuple[int, ...], int]:
    """``("f32", "5,16")`` -> (dtype, (5, 16), byte size)."""
    shape = tuple(int(x) for x in dims.split(",") if x) if dims else ()
    n = 1
    for s in shape:
        n *= s
    return dtype, shape, n * DTYPE_BYTES[dtype]


@dataclass
class CollectiveOp:
    kind: str                      # "all-reduce", ... (async-start folded in)
    shapes: List[Tuple[str, Tuple[int, ...], int]]  # result components
    line_no: int
    line: str

    @property
    def bytes(self) -> int:
        return sum(b for _, _, b in self.shapes)


def collective_inventory(compiled_text: str) -> List[CollectiveOp]:
    """All collective ops in a compiled-HLO dump, with per-component
    result shapes (variadic all-reduces XLA's combiner pass merged
    stay visible as multi-shape entries)."""
    out = []
    for no, line in enumerate(compiled_text.splitlines(), 1):
        m = _COLLECTIVE_RE.search(line)
        if not m or m.group("suffix") == "-done":
            continue
        shapes = [parse_shape(d, dims)
                  for d, dims in _SHAPE_RE.findall(m.group("result"))]
        out.append(CollectiveOp(m.group("kind"), shapes, no,
                                line.strip()))
    return out


def collective_summary(ops: List[CollectiveOp]) -> Dict:
    counts: Dict[str, int] = {}
    byte_totals: Dict[str, int] = {}
    for op in ops:
        counts[op.kind] = counts.get(op.kind, 0) + 1
        byte_totals[op.kind] = byte_totals.get(op.kind, 0) + op.bytes
    return {"counts": counts, "bytes": byte_totals,
            "total_bytes": sum(byte_totals.values())}


def matching_collective_bytes(ops: List[CollectiveOp], kind: str,
                              dtype: str,
                              shape: Tuple[int, ...]) -> int:
    """Total bytes over result *components* of exactly this dtype+shape
    for one collective kind. Summing (instead of taking the first hit)
    makes an accidentally duplicated op show up as 2x the expected
    bytes. The 2D audit keys reduce-scatter output shards through here
    the same way the 1-D audit keys the aggregation all-reduce."""
    total = 0
    for op in ops:
        if op.kind != kind:
            continue
        total += sum(b for d, s, b in op.shapes
                     if d == dtype and s == tuple(shape))
    return total


def matching_reduce_bytes(ops: List[CollectiveOp], dtype: str,
                          shape: Tuple[int, ...]) -> int:
    """All-reduce bytes of exactly this dtype+shape — the 1-D uplink
    cross-check's selector."""
    return matching_collective_bytes(ops, "all-reduce", dtype, shape)


def host_transfer_lines(text: str) -> List[str]:
    """Lines holding host-boundary ops (infeed/outfeed/send/recv) or
    host-callback custom-calls, in either dialect."""
    hits = []
    for no, line in enumerate(text.splitlines(), 1):
        if _TRANSFER_RE.search(line) or any(
                mark in line for mark in _CALLBACK_MARKERS):
            hits.append(f"{no}: {line.strip()}")
    return hits


def donation_marks(stablehlo_text: str) -> Dict[str, int]:
    """Donation evidence in the lowered module, one mark per donated
    argument. Two forms exist in jax 0.4.x:

    * ``tf.aliasing_output = N`` — jax paired the donated input with
      output N at trace time (single-device / replicated programs);
    * ``jax.buffer_donor = true`` — under GSPMD the output sharding
      isn't known at lowering, so jax defers the pairing to XLA.

    A dropped ``donate_argnums`` produces NEITHER mark; whether a
    deferred donor actually aliased is settled by the compiled
    module's ``input_output_alias`` header (``compiled_alias_count``).
    """
    return {"aliased": stablehlo_text.count("tf.aliasing_output"),
            "donors": stablehlo_text.count("jax.buffer_donor")}


def compiled_alias_count(compiled_text: str) -> int:
    """Entries in the compiled module's ``input_output_alias={...}``
    header — the backend's final word on which donations stuck. The
    header nests braces (``{ {3}: (1, {}, may-alias) }``), so scan to
    the balanced close and count output-index tuples."""
    m = re.search(r"input_output_alias=(\{)", compiled_text)
    if not m:
        return 0
    start = m.end(1) - 1
    depth = 0
    for i in range(start, len(compiled_text)):
        ch = compiled_text[i]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                region = compiled_text[start:i + 1]
                return len(re.findall(r"\}\s*:", region))
    return 0


def dot_dtype_inventory(stablehlo_text: str) -> Dict[str, int]:
    """dot_general/convolution count by lhs element type in lowered
    text. A bf16 model path must show zero f32 entries — an f32 dot
    there means an operand was silently widened before the contraction
    (2x the FLOP cost and memory traffic of the intended bf16 op)."""
    counts: Dict[str, int] = {}
    for _op, lhs, _rhs in _DOT_RE.findall(stablehlo_text):
        elem = lhs.rsplit("x", 1)[-1] if "x" in lhs else lhs
        counts[elem] = counts.get(elem, 0) + 1
    return counts


_LOC_LINE = re.compile(r"^#loc")
_TRAILING_LOC = re.compile(r"\s+loc\(.*\)\s*$")


def fingerprint(stablehlo_text: str) -> str:
    """SHA-256 of the lowered module with location metadata stripped —
    the trace-cache identity of a (mode, path, probes) program. Two
    lowerings of the same builder must agree bit-for-bit; a drifting
    fingerprint means the program retraces (or changed under you)."""
    lines = []
    for raw in stablehlo_text.splitlines():
        line = raw.strip()
        if not line or _LOC_LINE.match(line):
            continue
        lines.append(_TRAILING_LOC.sub("", line))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()

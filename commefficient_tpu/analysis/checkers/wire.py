"""wire-dtype-crossing: wire-format casts and byte tables have owners.

The quantized wire story (int8/fp8 sketches, bf16 canaries) stays
auditable because exactly two modules are allowed to *cross* dtypes
onto the wire format: ``ops/quant.py`` (encode/decode) and
``parallel/wire.py`` (the collective that moves the encoded bytes).
A stray ``.astype(jnp.int8)`` anywhere else is an unaccounted
quantization — it changes recovery error and wire bytes without the
autopilot, the accountant, or the registry seeing it. Likewise the
byte-width tables (``{"int8": 1, ...}``) live in ``accounting.py``
and ``config.py`` only; a private copy silently forks the pricing.

Flagged outside the owners:

* ``.astype(<wire dtype>)`` / ``lax.convert_element_type(x, <wire>)``
  where the wire dtypes are int8, the fp8 family, and bfloat16
  (uint8 is exempt: hash-byte packing, not a wire format);
* dict literals mapping ≥2 wire-dtype names to numeric widths.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from commefficient_tpu.analysis.flow import FlowChecker, Program

#: modules allowed to cast to wire dtypes
_CAST_OWNERS = {"ops/quant.py", "parallel/wire.py"}
#: modules allowed to hold dtype→bytes tables
_TABLE_OWNERS = _CAST_OWNERS | {"accounting.py", "config.py"}

_WIRE_DTYPES = {"int8", "bfloat16", "float8_e4m3fn", "float8_e5m2",
                "float8_e4m3", "float8_e4m3b11fnuz", "fp8_e4m3",
                "fp8_e5m2"}
_TABLE_KEYS = _WIRE_DTYPES | {"bf16", "fp8", "f32", "float32",
                              "f16", "float16"}


def _dtype_name(expr) -> Optional[str]:
    """The dtype an expression names: ``jnp.int8`` → "int8",
    ``"int8"`` → "int8", bare ``int8`` → "int8"."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    return None


def check(program: Program) -> List[Tuple[str, int, str]]:
    out = []
    for rel in sorted(program.modules):
        mod = program.modules[rel]
        if mod.tree is None:
            continue
        cast_owner = rel in _CAST_OWNERS
        table_owner = rel in _TABLE_OWNERS
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and not cast_owner:
                f = node.func
                if isinstance(f, ast.Attribute) \
                        and f.attr == "astype" and node.args:
                    dt = _dtype_name(node.args[0])
                    if dt in _WIRE_DTYPES:
                        out.append((rel, node.lineno,
                                    f".astype({dt}) outside "
                                    "ops/quant.py and "
                                    "parallel/wire.py — wire-format "
                                    "casts must go through the "
                                    "quantizer so bytes and error "
                                    "are accounted"))
                elif isinstance(f, ast.Attribute) \
                        and f.attr == "convert_element_type" \
                        and len(node.args) >= 2:
                    dt = _dtype_name(node.args[1])
                    if dt in _WIRE_DTYPES:
                        out.append((rel, node.lineno,
                                    f"convert_element_type(..., {dt})"
                                    " outside ops/quant.py and "
                                    "parallel/wire.py — wire-format "
                                    "casts must go through the "
                                    "quantizer"))
            elif isinstance(node, ast.Dict) and not table_owner:
                keys = [k.value for k in node.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)]
                if len(keys) >= 2 and len(keys) == len(node.keys) \
                        and all(k in _TABLE_KEYS for k in keys) \
                        and all(isinstance(v, ast.Constant)
                                and type(v.value) in (int, float)
                                for v in node.values):
                    out.append((rel, node.lineno,
                                "private wire-width byte table — "
                                "use accounting.dtype_bytes so one "
                                "table prices the wire"))
    return out


CHECKER = FlowChecker(
    "wire-dtype-crossing",
    "wire-format cast or byte table outside quant/wire owners",
    check)

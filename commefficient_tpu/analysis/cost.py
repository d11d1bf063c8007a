"""Roofline cost model: expected lower-bound round time.

Closes the loop from the static auditor's program inventories
(hlo.py: FLOPs, collective bytes) to the measured device timelines
(telemetry/trace.py): for a (mode, path, topology) the model computes
the time the round CANNOT beat —

    expected_round_s = max(compute_time, collective_time)

with ``compute_time = FLOPs / (peak_flops x n_devices)`` and
``collective_time = ring all-reduce wire bytes / interconnect BW``.
The ledger then carries ``roofline_utilization = expected / measured
busy`` per profiled round (schema v3): ~1.0 means the round runs at
the roofline, a collapse to 0.1 means 10x is being left on the table
(host gaps, launch overhead, unfused memory-bound tails).

Peak numbers are deliberately coarse catalogue values — the model is
a *lower bound* and a *trend instrument* (did utilization drop vs the
committed perf baseline?), not a simulator. Like hlo.py, nothing here
imports jax; callers pass backend/device strings.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from commefficient_tpu.analysis.hlo import flop_inventory


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float        # bf16/f32 matmul peak per chip, FLOP/s
    hbm_gbps: float          # memory bandwidth, GB/s
    ici_gbps: float          # per-chip interconnect bandwidth, GB/s


# catalogue values (vendor datasheets, rounded); "cpu" is a deliberate
# small stand-in so CPU smoke runs produce finite utilizations, and is
# only ever returned for the cpu backend
CHIP_SPECS = {
    "tpu-v4": ChipSpec("tpu-v4", 275e12, 1228.0, 50.0),
    "tpu-v5e": ChipSpec("tpu-v5e", 197e12, 819.0, 50.0),
    "tpu-v5p": ChipSpec("tpu-v5p", 459e12, 2765.0, 100.0),
    "tpu-v6e": ChipSpec("tpu-v6e", 918e12, 1640.0, 100.0),
    "gpu": ChipSpec("gpu", 312e12, 2039.0, 50.0),
    "cpu": ChipSpec("cpu", 2e11, 50.0, 10.0),
}


def chip_spec(backend: str, device_kind: str = "") -> ChipSpec:
    """Spec lookup from ``jax.default_backend()`` plus the device's
    ``device_kind`` string (e.g. "TPU v5 lite"). A device that is not
    in ``CHIP_SPECS`` raises: a utilization against another chip's
    peak is a wrong number, not an estimate."""
    kind = (device_kind or "").lower()
    if backend == "tpu":
        if "v5 lite" in kind or "v5e" in kind or "v5litepod" in kind:
            return CHIP_SPECS["tpu-v5e"]
        if "v5p" in kind or "v5" in kind:
            return CHIP_SPECS["tpu-v5p"]
        if "v6" in kind:
            return CHIP_SPECS["tpu-v6e"]
        if "v4" in kind:
            return CHIP_SPECS["tpu-v4"]
    elif backend in ("gpu", "cpu"):
        return CHIP_SPECS[backend]
    raise ValueError(f"no chip spec for backend {backend!r}, "
                     f"device_kind {device_kind!r} — add it to "
                     "analysis/cost.py CHIP_SPECS with its source")


def ring_allreduce_wire_bytes(payload_bytes: float,
                              n_devices: int) -> float:
    """Per-chip wire traffic of a ring all-reduce: each chip sends
    (and receives) ``2 (n-1)/n`` of the payload."""
    n = max(int(n_devices), 1)
    if n == 1:
        return 0.0
    return 2.0 * payload_bytes * (n - 1) / n


def expected_round_seconds(total_flops: float,
                           allreduce_payload_bytes: float,
                           spec: ChipSpec,
                           n_devices: int) -> Dict:
    """Roofline lower bound for one round on ``n_devices`` chips.
    ``total_flops`` is the GLOBAL (pre-SPMD) program cost — the
    lowered StableHLO counts every client's pass — so the compute leg
    divides by the device count."""
    n = max(int(n_devices), 1)
    compute_s = float(total_flops) / (spec.peak_flops * n)
    wire = ring_allreduce_wire_bytes(allreduce_payload_bytes, n)
    collective_s = wire / (spec.ici_gbps * 1e9)
    return {"compute_s": compute_s,
            "collective_s": collective_s,
            "expected_round_s": max(compute_s, collective_s),
            "wire_bytes_per_chip": wire}


def build_cost_model(stablehlo_text: str, *, backend: str,
                     device_kind: str = "", n_devices: int = 1,
                     allreduce_payload_bytes: float = 0.0,
                     wire_dtype: str = "f32",
                     label: str = "") -> Dict:
    """One round's roofline expectation from its lowered module text.

    ``allreduce_payload_bytes`` is the round's aggregation payload at
    its WIRE dtype (``Config.upload_wire_bytes_per_client``: sketch
    tables at the --sketch_dtype width + per-row f32 scales, dense
    modes ``4 grad_size``) — passed in rather than re-derived from
    compiled HLO so the profiled run doesn't pay a second full
    compile. ``wire_dtype`` tags the record so a quantized run's
    collective floor is attributable without re-deriving it from the
    byte count. Returns a JSON-able dict the telemetry meta record
    carries."""
    flops = flop_inventory(stablehlo_text)
    spec = chip_spec(backend, device_kind)
    exp = expected_round_seconds(flops["total_flops"],
                                 allreduce_payload_bytes, spec,
                                 n_devices)
    return {
        "label": label,
        "chip": spec.name,
        "backend": backend,
        "n_devices": int(n_devices),
        "total_flops": flops["total_flops"],
        "dot_flops": flops["dot_flops"],
        "conv_flops": flops["conv_flops"],
        "flops_by_dtype": flops["by_dtype"],
        "allreduce_payload_bytes": float(allreduce_payload_bytes),
        "wire_dtype": wire_dtype,
        "wire_bytes_per_chip": exp["wire_bytes_per_chip"],
        "compute_floor_s": exp["compute_s"],
        "collective_floor_s": exp["collective_s"],
        "expected_round_s": exp["expected_round_s"],
    }


def utilization(expected_round_s: Optional[float],
                measured_busy_s: Optional[float]) -> Optional[float]:
    """Roofline utilization fraction (1.0 = running at the bound);
    None when either side is missing/zero."""
    if not expected_round_s or not measured_busy_s:
        return None
    return expected_round_s / measured_busy_s

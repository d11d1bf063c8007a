"""Program audit: lower + compile the jitted round step for every
(mode, path) pair on the mesh and statically check the invariants the
FetchSGD line promises about the compiled program:

* **donation coverage** — every ``donate_argnums`` leaf is actually
  input-output aliased (a dropped donation doubles peak HBM for the
  client-state buffers at scale, silently);
* **collective inventory** — op counts and byte totals per collective
  kind, with the transmit-aggregation all-reduce cross-checked against
  the telemetry ledger's uplink accounting
  (``cfg.upload_wire_bytes_per_client``: the table at the
  ``--sketch_dtype`` wire width + per-row f32 scales where the dtype
  carries them) to exact integer equality for sketch / true_topk /
  uncompressed / fedavg. The quantized programs additionally prove the
  table collective compiled at the wire dtype (s8/f8e4m3fn/bf16) and
  that no f32 table-shaped all-reduce remains. local_topk is the
  documented exception: the mesh reduces the DENSE masked vector over
  the ICI (4·d bytes) while the logical uplink is 4·k — the audit
  asserts the bound instead;
* **no host transfers** — no infeed/outfeed/send/recv/host callbacks
  anywhere in the round program (the only device→host crossing is the
  ``metrics_host`` scalar fetch, which lives OUTSIDE the compiled
  step and is policed by the linter, not here);
* **bf16 dtype discipline** — a bf16 canary model lowers with zero
  f32 dot/conv ops (silent widening = 2x FLOPs + traffic);
* **trace-cache fingerprint** — SHA-256 of the loc-stripped StableHLO
  per (mode, path, probes); double-lowering must agree, and the
  committed ``audit_baseline.json`` pins it so accidental program
  drift / retraces fail visibly.

Geometry is deliberately tiny (d=64, B=2, sketch 2x16): the audit
checks program *shape*, not numerics, and must stay tier-1 fast.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.analysis import hlo
from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates,
                                           build_client_round,
                                           build_server_round)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.parallel.mesh import (client_sharding, make_mesh,
                                             make_mesh2d,
                                             model_axis_size, replicated,
                                             server_state_sharding,
                                             shard_batch)

D = 64            # grad_size
B = 2             # padded batch per client
NUM_CLIENTS = 16  # divisible by the 8-device mesh
MESH_W = 8        # round fan-out on the mesh
CHUNK_W = 4       # fan-out for the single-device chunked path
CHUNK = 2
MESH2D = (4, 2)   # clients x model layout for the 2D audit programs

BASE_CFG = dict(local_momentum=0.0, virtual_momentum=0.0,
                weight_decay=0.0, error_type="none", k=3,
                num_rows=2, num_cols=16, num_blocks=1,
                local_batch_size=B, microbatch_size=-1, seed=21)


@dataclasses.dataclass
class ProgramSpec:
    name: str
    mode: str
    path: str               # "fused" | "per_client" | "chunked" | "fused2d"
    cfg_kw: Dict
    probes: bool = False
    probe_recovery: bool = False

    @property
    def use_mesh(self) -> bool:
        return self.path != "chunked"


def build_specs() -> List[ProgramSpec]:
    """The mode x path matrix. Path forcing mirrors how the runtime
    actually lands on each builder branch (core/rounds.py):

    * fused needs no per-client gradient transform — sketch /
      true_topk / uncompressed with zero local momentum/error;
    * per_client is forced by a per-client op: microbatching for the
      fused-eligible modes, local momentum/error for the rest; fedavg
      is inherently per-client (local SGD);
    * chunked engages only single-device with 0 < client_chunk < W.
    """
    fused = [
        ProgramSpec("sketch/fused", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9)),
        ProgramSpec("true_topk/fused", "true_topk", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9)),
        ProgramSpec("uncompressed/fused", "uncompressed", "fused",
                    dict(virtual_momentum=0.9)),
        # the --probe_every cadence variant: table + dense ground
        # truth both cross the ICI on probed rounds
        ProgramSpec("sketch/fused+probes", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9),
                    probes=True, probe_recovery=True),
        # the pod-scale 2D round: partial tables reduce-scattered over
        # ``model``, the client-axis all-reduce carries only the
        # (r, c/M) column shard
        ProgramSpec("sketch/fused2d", "sketch", "fused2d",
                    dict(error_type="virtual", virtual_momentum=0.9)),
        # quantized wire programs: the table collective must compile
        # at the wire dtype (s8/f8e4m3fn/bf16) with, for the scaled
        # dtypes, exactly one (r, 1) f32 rowmax pmax riding along —
        # the dtype-aware ledger cross-check proves the compiled
        # bytes equal the accounting to the byte
        ProgramSpec("sketch/quant8", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="int8")),
        ProgramSpec("sketch/quantfp8", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="fp8")),
        ProgramSpec("sketch/quantbf16", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="bf16")),
        ProgramSpec("sketch/quant2d", "sketch", "fused2d",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="int8")),
        # latency-hiding chunk pipeline (--overlap_depth): the table
        # crosses the wire in min(depth, r) disjoint row chunks, one
        # wire-dtype collective per chunk — the audit proves the
        # per-chunk collective bytes still sum to the ledger's
        # byte-exact total, one chunk-sized f32 scale pmax rides per
        # chunk, and no f32 table (or chunk) ever crosses the ICI
        ProgramSpec("sketch/overlap2", "sketch", "fused",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="int8", overlap_depth=2)),
        ProgramSpec("sketch/overlap2d", "sketch", "fused2d",
                    dict(error_type="virtual", virtual_momentum=0.9,
                         sketch_dtype="int8", overlap_depth=2)),
    ]
    per_client_kw = {
        "sketch": dict(error_type="virtual", virtual_momentum=0.9,
                       microbatch_size=1),
        "true_topk": dict(error_type="virtual", virtual_momentum=0.9,
                          local_momentum=0.9),
        "local_topk": dict(error_type="local", local_momentum=0.9,
                           virtual_momentum=0.9),
        "uncompressed": dict(virtual_momentum=0.9, local_momentum=0.9),
        "fedavg": dict(local_batch_size=-1),
    }
    per_client = [ProgramSpec(f"{m}/per_client", m, "per_client", kw)
                  for m, kw in per_client_kw.items()]
    chunked = [ProgramSpec(f"{m}/chunked", m, "chunked",
                           dict(kw, client_chunk=CHUNK))
               for m, kw in per_client_kw.items()]
    return fused + per_client + chunked


SERVER_CFG_KW = {
    # aligned with tests/test_accounting.py MODES so the ledger
    # cross-check and the server audit see the same configs
    "uncompressed": dict(virtual_momentum=0.9),
    "sketch": dict(error_type="virtual", virtual_momentum=0.9),
    "true_topk": dict(error_type="virtual", virtual_momentum=0.9),
    "local_topk": dict(error_type="local", local_momentum=0.9,
                       virtual_momentum=0.9),
    "fedavg": dict(local_batch_size=-1),
}


def make_cfg(mode: str, num_workers: int, **kw) -> Config:
    merged = dict(BASE_CFG)
    merged.update(kw)
    cfg = Config(mode=mode, num_workers=num_workers, **merged)
    cfg.grad_size = D
    return cfg


def _toy_loss(params_flat, batch):
    pred = batch["x"] @ params_flat
    sq = (pred - batch["y"]) ** 2
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    loss = jnp.sum(sq * batch["mask"]) / n
    return loss, (loss * 0.0 + 1.0,)


def _client_inputs(cfg: Config, mesh):
    W = cfg.num_workers
    rng = np.random.RandomState(0)
    ps = jnp.zeros((D,), jnp.float32)
    sharding = client_sharding(mesh) if mesh is not None else None
    cs = ClientStates.init(cfg, NUM_CLIENTS, ps, sharding=sharding)
    batch = {"x": jnp.asarray(rng.randn(W, B, D).astype(np.float32)),
             "y": jnp.asarray(rng.randn(W, B).astype(np.float32)),
             "mask": jnp.ones((W, B), jnp.float32)}
    ids = jnp.arange(W, dtype=jnp.int32)
    if mesh is not None:
        batch = shard_batch(mesh, batch)
        ps = jax.device_put(ps, replicated(mesh))
        ids = jax.device_put(ids, replicated(mesh))
    # fixed smoke key for fingerprinting, not a noise source
    return ps, cs, batch, ids, jax.random.PRNGKey(0), jnp.float32(0.1)  # audit: allow(noise-confinement)


def _donated_leaves(tree) -> int:
    return len(jax.tree_util.tree_leaves(tree))


def _audit_texts(jitted, args) -> Dict:
    """Lower twice (retrace determinism), compile once; return the
    parsed common report skeleton."""
    lowered = jitted.lower(*args)
    text = lowered.as_text()
    fp = hlo.fingerprint(text)
    fp2 = hlo.fingerprint(jitted.lower(*args).as_text())
    ctext = lowered.compile().as_text()
    ops = hlo.collective_inventory(ctext)
    transfers = (hlo.host_transfer_lines(text)
                 + hlo.host_transfer_lines(ctext))
    marks = hlo.donation_marks(text)
    return {
        "fingerprint": fp,
        "retrace_stable": fp == fp2,
        "collectives": hlo.collective_summary(ops),
        "_ops": ops,
        "transfers": transfers,
        "marked": marks["aliased"] + marks["donors"],
        "compiled_aliases": hlo.compiled_alias_count(ctext),
    }


def audit_client_program(spec: ProgramSpec, mesh=None,
                         donate: bool = True) -> Dict:
    """Audit one client-round program. ``donate=False`` exists for the
    regression test: dropping donation must fail the coverage check."""
    W = MESH_W if spec.use_mesh else CHUNK_W
    cfg = make_cfg(spec.mode, W, **spec.cfg_kw)
    if spec.use_mesh and mesh is None:
        mesh = (make_mesh2d(*MESH2D) if spec.path == "fused2d"
                else make_mesh(jax.devices()))
    fn = build_client_round(cfg, _toy_loss, B,
                            mesh=mesh if spec.use_mesh else None,
                            probes=spec.probes,
                            probe_recovery=spec.probe_recovery)
    jitted = jax.jit(fn, donate_argnums=(1,) if donate else ())
    args = _client_inputs(cfg, mesh if spec.use_mesh else None)
    entry = _audit_texts(jitted, args)
    ops = entry.pop("_ops")

    expected = _donated_leaves(args[1])
    entry["donation"] = {"expected": expected,
                         "marked": entry.pop("marked"),
                         "compiled_aliases":
                             entry.pop("compiled_aliases")}

    # dtype-aware ledger cross-check: the ledger bills the table at
    # the wire dtype plus (for the scaled dtypes) one f32 row scale
    # per row; the compiled program must carry EXACTLY that — the
    # table collective at wire width and the (r, 1) f32 rowmax pmax.
    # One backend caveat: XLA CPU's collective runtime sums s8
    # natively but PROMOTES bf16 all-reduces to f32 and f8 to f16
    # (all-reduce-promotion pass) — on those wires the audit accepts
    # the promoted dtype, normalises its bytes back to wire width for
    # the ledger equality, and records the promotion so the TPU
    # audit (native bf16 collectives) can pin the real width.
    wire = getattr(cfg, "sketch_dtype", "f32")
    wire_hlo = {"f32": "f32", "bf16": "bf16", "int8": "s8",
                "fp8": "f8e4m3fn"}[wire]
    promoted_ok = {"f32": ("f32",), "int8": ("s8",),
                   "bf16": ("bf16", "f32"),
                   "fp8": ("f8e4m3fn", "f16", "f32")}[wire]

    def _wire_bytes(kind, shapes):
        """(bytes normalised to wire width, matched hlo dtype) of the
        first dtype — native first, then promoted — with a matching
        ``kind`` collective at any of ``shapes``."""
        for dt in promoted_ok:
            raw = sum(hlo.matching_collective_bytes(ops, kind, dt, s)
                      for s in dict.fromkeys(tuple(s) for s in shapes))
            if raw:
                factor = (hlo.DTYPE_BYTES[dt]
                          // hlo.DTYPE_BYTES[wire_hlo])
                return raw // factor, dt
        return 0, wire_hlo

    ledger = int(cfg.upload_wire_bytes_per_client)
    # --overlap_depth chunking: the table crosses in min(depth, r)
    # disjoint row chunks, so the wire collectives (and their f32
    # scale pmaxes) compile at chunk-row shapes instead of the whole
    # table's — the byte totals must still sum to the same ledger
    depth = int(getattr(cfg, "overlap_depth", 1))
    chunks = []
    if depth > 1:
        from commefficient_tpu.parallel.wire import row_chunks
        chunks = row_chunks(cfg.num_rows, depth)
    scale_shapes = [(cfg.num_rows, 1), (cfg.num_rows,)]
    for _off, cnt in chunks:
        scale_shapes += [(cnt, 1), (cnt,)]
    scale = (sum(
        hlo.matching_collective_bytes(ops, "all-reduce", "f32", s)
        for s in dict.fromkeys(scale_shapes))
        if wire in ("int8", "fp8") else 0)
    M = model_axis_size(mesh) if spec.use_mesh else 1
    if M > 1:
        # 2D emission: the client-axis all-reduce and the model-axis
        # reduce-scatter both carry the (r, c/M) column shard — XLA
        # sometimes flattens the shard to 1-D, so both layouts key
        shard = (cfg.num_rows, cfg.num_cols // M)
        shard_shapes = [shard, (shard[0] * shard[1],)]
        for _off, cnt in chunks:
            shard_shapes += [(cnt, cfg.num_cols // M),
                             (cnt * (cfg.num_cols // M),)]
        static, static_dt = _wire_bytes("all-reduce", shard_shapes)
        rs, rs_dt = _wire_bytes("reduce-scatter", shard_shapes)
        entry["uplink"] = {
            "ledger_bytes_per_client": ledger,
            "model_shards": M,
            "wire_dtype": wire,
            "compiled_dtype": static_dt,
            "aggregate_allreduce_bytes": static,
            "reduce_scatter_bytes": rs,
            "scale_allreduce_bytes": scale,
            "relation": "sharded",
        }
    else:
        table_shapes = [cfg.transmit_shape,
                        (int(np.prod(cfg.transmit_shape)),)]
        for _off, cnt in chunks:
            table_shapes += [(cnt, cfg.num_cols),
                             (cnt * cfg.num_cols,)]
        static, static_dt = _wire_bytes("all-reduce", table_shapes)
        rs_dt = static_dt
        entry["uplink"] = {
            "ledger_bytes_per_client": ledger,
            "wire_dtype": wire,
            "compiled_dtype": static_dt,
            "aggregate_allreduce_bytes": static,
            "scale_allreduce_bytes": scale,
            # local_topk sends the dense masked vector over the ICI:
            # the 4·k ledger figure is the logical uplink, bounded by
            # the 4·d wire bytes. Everything else must match exactly.
            "relation": ("bound" if spec.mode == "local_topk"
                         else "exact"),
        }

    failures = []
    don = entry["donation"]
    if don["marked"] < don["expected"]:
        failures.append(
            f"donation: {don['marked']}/{don['expected']} donated "
            "state leaves marked in the lowered module — the "
            "donation was dropped")
    elif don["compiled_aliases"] < don["expected"]:
        failures.append(
            f"donation: XLA aliased {don['compiled_aliases']}/"
            f"{don['expected']} donated state leaves — a donated "
            "buffer is being copied instead of reused")
    if entry["transfers"]:
        failures.append(
            f"host transfers in the round program: "
            f"{entry['transfers'][:3]}")
    if not entry["retrace_stable"]:
        failures.append("fingerprint differs across two lowerings of "
                        "the same builder (nondeterministic trace)")
    if spec.path == "chunked":
        if entry["collectives"]["counts"]:
            failures.append(
                "single-device chunked program emits collectives: "
                f"{entry['collectives']['counts']}")
    elif M > 1:
        if rs * M + scale != ledger:
            failures.append(
                f"2D uplink: reduce-scatter shard bytes {rs} x {M} "
                f"model shards + {scale} scale bytes != ledger "
                f"bytes/client {ledger} ({wire} wire) — the "
                "partial-table emission is not reduce-scattering the "
                "quantized (r, c/M) column shard")
        if static * M + scale != ledger:
            failures.append(
                f"2D uplink: client-axis all-reduce bytes {static} x "
                f"{M} + {scale} scale bytes != ledger bytes/client "
                f"{ledger} ({wire} wire) — the aggregation must carry "
                "only the quantized column shard")
        full = hlo.matching_reduce_bytes(ops, wire_hlo,
                                         cfg.transmit_shape)
        if full:
            failures.append(
                f"2D uplink: {full} bytes all-reduced at the FULL "
                f"table shape {cfg.transmit_shape} — the model-axis "
                "sharding is being undone on the wire")
        if wire != "f32" and hlo.matching_reduce_bytes(
                ops, "f32", cfg.transmit_shape):
            failures.append(
                "2D uplink: an f32 table-shaped all-reduce in the "
                f"{wire}-wire program — the table is crossing the ICI "
                "unquantized")
        if wire != "f32" and hlo.matching_collective_bytes(
                ops, "reduce-scatter", "f32", shard) and rs_dt != "f32":
            failures.append(
                "2D uplink: an f32 shard-shaped reduce-scatter beside "
                f"the {wire} wire path — double traffic")
    elif spec.mode == "local_topk":
        if not (static >= ledger):
            failures.append(
                f"uplink: dense wire bytes {static} < logical ledger "
                f"bytes {ledger}")
    else:
        if static + scale != ledger:
            failures.append(
                f"uplink: aggregation all-reduce bytes {static} + "
                f"{scale} scale bytes != ledger bytes/client {ledger} "
                f"({wire} wire, shape {cfg.transmit_shape})")
        if (wire != "f32" and static_dt != "f32"
                and hlo.matching_reduce_bytes(ops, "f32",
                                              cfg.transmit_shape)):
            failures.append(
                f"uplink: an f32 table-shaped all-reduce beside the "
                f"{wire} ({static_dt}) wire path — the table is "
                "crossing the ICI unquantized")
    if chunks:
        # chunk pipeline shape: one wire crossing per row chunk, and
        # no chunk ever crosses the ICI at f32 (an extra f32 chunk
        # materialisation would silently double the traffic the
        # pipeline exists to hide). Crossings are counted per result
        # component: XLA's combiner may merge the chunk collectives it
        # was handed into one variadic op (the CPU backend does), which
        # is that backend's scheduling and not the program's shape
        kind = "reduce-scatter" if M > 1 else "all-reduce"
        chunk_dt = rs_dt if M > 1 else static_dt
        base_c = cfg.num_cols // M if M > 1 else cfg.num_cols
        chunk_set = set()
        for _off, cnt in chunks:
            chunk_set.update({(cnt, base_c), (cnt * base_c,)})
        n_ops = sum(
            1 for op in ops if op.kind == kind
            for d, s, _b in op.shapes
            if d == chunk_dt and s in chunk_set)
        entry["uplink"]["overlap_depth"] = depth
        entry["uplink"]["chunk_collectives"] = n_ops
        if n_ops != len(chunks):
            failures.append(
                f"overlap: {n_ops} chunk-shaped {kind} crossing(s) "
                f"for {len(chunks)} row chunks — the pipeline is not "
                "issuing one wire collective per chunk")
        if wire != "f32" and chunk_dt != "f32":
            for s in sorted(chunk_set):
                f32b = hlo.matching_reduce_bytes(ops, "f32", s)
                if f32b:
                    failures.append(
                        f"overlap: {f32b} bytes f32-reduced at chunk "
                        f"shape {s} — a chunk is crossing the ICI "
                        "unquantized")
    entry.update(mode=spec.mode, path=spec.path, probes=spec.probes,
                 failures=failures)
    return entry


def audit_server_program(mode: str, donate: bool = True) -> Dict:
    """Audit the server round: ``donate_argnums=(0, 1)`` covers
    ps_weights + both ServerState tables; the server step is
    replicated, so the program must be collective- and transfer-free.

    All three donated leaves (ps_weights, Vvelocity, Verror) alias in
    every mode — non-virtual-error modes thread Verror through
    unchanged and XLA still reuses the buffer — so the check is
    exact."""
    cfg = make_cfg(mode, MESH_W, **SERVER_CFG_KW[mode])
    fn = build_server_round(cfg)
    jitted = jax.jit(fn, donate_argnums=(0, 1) if donate else ())
    args = (jnp.zeros((D,), jnp.float32), ServerState.init(cfg),
            jnp.ones(cfg.transmit_shape, jnp.float32),
            jnp.float32(0.1))
    entry = _audit_texts(jitted, args)
    entry.pop("_ops")
    entry["donation"] = {"expected": 1 + _donated_leaves(args[1]),
                         "marked": entry.pop("marked"),
                         "compiled_aliases":
                             entry.pop("compiled_aliases")}
    failures = []
    don = entry["donation"]
    if min(don["marked"], don["compiled_aliases"]) < don["expected"]:
        failures.append(
            f"donation: {don['marked']} marked / "
            f"{don['compiled_aliases']} compiled-aliased of "
            f"{don['expected']} donated server leaves — ps_weights "
            "and both ServerState tables must reuse their buffers")
    if entry["transfers"]:
        failures.append(f"host transfers: {entry['transfers'][:3]}")
    if entry["collectives"]["counts"]:
        failures.append("replicated server step emits collectives: "
                        f"{entry['collectives']['counts']}")
    if not entry["retrace_stable"]:
        failures.append("nondeterministic server trace")
    entry.update(mode=mode, path="server", probes=False,
                 failures=failures)
    return entry


def audit_server_program_2d(donate: bool = True) -> Dict:
    """Audit the 2D sketch server: momentum/EF column shards update
    locally, the distributed top-k select rebuilds the full table with
    exactly ONE table-sized all-gather (never an all-reduce of a
    table-sized buffer — that would undo the 1/M memory claim on the
    wire), and donation must stick on the sharded state."""
    cfg = make_cfg("sketch", MESH_W, **SERVER_CFG_KW["sketch"])
    mesh = make_mesh2d(*MESH2D)
    fn = build_server_round(cfg, mesh=mesh)
    jitted = jax.jit(fn, donate_argnums=(0, 1) if donate else ())
    state = ServerState.init(
        cfg, sharding=server_state_sharding(mesh, cfg.transmit_shape))
    args = (jnp.zeros((D,), jnp.float32), state,
            jnp.ones(cfg.transmit_shape, jnp.float32),
            jnp.float32(0.1))
    entry = _audit_texts(jitted, args)
    ops = entry.pop("_ops")
    entry["donation"] = {"expected": 1 + _donated_leaves(args[1]),
                         "marked": entry.pop("marked"),
                         "compiled_aliases":
                             entry.pop("compiled_aliases")}
    r, c = cfg.transmit_shape
    table_gathers = sum(
        1 for op in ops if op.kind == "all-gather"
        and any(d == "f32" and s in ((r, c), (r * c,))
                for d, s, _b in op.shapes))
    table_reduce = sum(
        hlo.matching_collective_bytes(ops, "all-reduce", "f32", s)
        for s in ((r, c), (r * c,)))
    entry["table_traffic"] = {"all_gathers": table_gathers,
                              "allreduce_bytes": table_reduce}
    failures = []
    don = entry["donation"]
    if min(don["marked"], don["compiled_aliases"]) < don["expected"]:
        failures.append(
            f"donation: {don['marked']} marked / "
            f"{don['compiled_aliases']} compiled-aliased of "
            f"{don['expected']} donated server leaves — the sharded "
            "momentum/EF tables must reuse their buffers")
    if entry["transfers"]:
        failures.append(f"host transfers: {entry['transfers'][:3]}")
    if table_gathers != 1:
        failures.append(
            f"2D select must rebuild the table with exactly one "
            f"(r, c) all-gather, found {table_gathers}")
    if table_reduce:
        failures.append(
            f"{table_reduce} bytes all-reduced at table size in the "
            "2D server — column shards must stay sharded")
    if not entry["retrace_stable"]:
        failures.append("nondeterministic 2D server trace")
    entry.update(mode="sketch", path="server2d", probes=False,
                 failures=failures)
    return entry


def audit_mesh_1x1_identity() -> Dict:
    """``--mesh 1x1`` must build the SAME program as the 1-D default
    (loc-stripped StableHLO fingerprint): the 2D plumbing may not tax
    the single-device path with even one extra op."""
    cfg = make_cfg("sketch", MESH_W,
                   **dict(error_type="virtual", virtual_momentum=0.9))
    args = _client_inputs(cfg, None)
    texts = {}
    for tag, mesh in (("1d", None), ("1x1", make_mesh2d(1, 1))):
        fn = build_client_round(cfg, _toy_loss, B, mesh=mesh)
        texts[tag] = jax.jit(fn).lower(*args).as_text()
    fp_1d = hlo.fingerprint(texts["1d"])
    fp_11 = hlo.fingerprint(texts["1x1"])
    failures = []
    if fp_1d != fp_11:
        failures.append(
            f"--mesh 1x1 lowers a different program than the 1-D "
            f"default ({fp_1d[:12]} != {fp_11[:12]}) — the 2D branch "
            "leaks into the single-device build")
    return {"mode": "sketch", "path": "mesh1x1", "probes": False,
            "fingerprint": fp_1d, "mesh1x1_fingerprint": fp_11,
            "retrace_stable": True, "failures": failures}


def audit_bf16_canary() -> Dict:
    """bf16 dtype discipline on a conv+dot canary: value_and_grad of a
    small bf16 model must lower with every contraction in bf16 —
    an f32 dot/conv means an operand was silently widened."""

    def model_loss(params, x, y):
        h = jax.lax.conv_general_dilated(
            x, params["conv"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jnp.maximum(h, 0).reshape(x.shape[0], -1)
        logits = h @ params["dense"]
        return jnp.sum((logits.astype(jnp.float32) - y) ** 2)

    bf16 = jnp.bfloat16
    params = {"conv": jax.ShapeDtypeStruct((3, 3, 2, 4), bf16),
              "dense": jax.ShapeDtypeStruct((8 * 8 * 4, 8), bf16)}
    x = jax.ShapeDtypeStruct((2, 8, 8, 2), bf16)
    y = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    jitted = jax.jit(jax.value_and_grad(model_loss))
    text = jitted.lower(params, x, y).as_text()
    dots = hlo.dot_dtype_inventory(text)
    failures = []
    if dots.get("f32", 0):
        failures.append(
            f"{dots['f32']} f32 dot/conv op(s) in the bf16 model "
            f"path (inventory: {dots}) — silent widening")
    if not dots.get("bf16", 0):
        failures.append(f"no bf16 contractions found at all ({dots})"
                        " — parser or model drift")
    return {"mode": "bf16_canary", "path": "lowered-only",
            "probes": False, "dot_dtypes": dots,
            "fingerprint": hlo.fingerprint(text),
            "retrace_stable": True, "failures": failures}


def run_program_audit(server: bool = True) -> Dict:
    """The full matrix. Returns a JSON-able report:
    ``{"programs": {name: entry}, "failures": [str]}`` — ``failures``
    flattens every entry's failed invariant checks."""
    report: Dict = {"jax_version": jax.__version__,
                    "device_count": jax.device_count(),
                    "programs": {}}
    mesh = make_mesh(jax.devices())
    for spec in build_specs():
        report["programs"][spec.name] = audit_client_program(
            spec, mesh=None if spec.path == "fused2d" else mesh)
    if server:
        for mode in SERVER_CFG_KW:
            report["programs"][f"{mode}/server"] = \
                audit_server_program(mode)
        report["programs"]["sketch/server2d"] = \
            audit_server_program_2d()
    report["programs"]["sketch/mesh1x1"] = audit_mesh_1x1_identity()
    report["programs"]["bf16_canary"] = audit_bf16_canary()
    report["failures"] = [
        f"{name}: {msg}"
        for name, entry in report["programs"].items()
        for msg in entry["failures"]]
    return report

"""Device mesh & sharding layout.

The reference's process topology (1 parameter-server + N worker GPU
processes over NCCL, fed_aggregator.py:131-165) maps to a 1-D JAX mesh
with a single ``clients`` axis:

- participating clients' batches and per-client state rows are sharded
  over ``clients`` (what the reference kept in host shared memory,
  fed_aggregator.py:94-129);
- model weights and server state are replicated (every device runs the
  identical deterministic server step — no PS rank);
- the per-round transmit aggregation is a sum over the sharded axis,
  which XLA lowers to one ICI all-reduce — the moral equivalent of the
  reference's single NCCL ``reduce`` per round (fed_worker.py:139-140).

Multi-host pods need no new code: under the standard JAX
multi-controller runtime, ``jax.devices()`` spans hosts, the same mesh
covers ICI+DCN, and XLA routes the collective hierarchically.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported to the rounds
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CLIENT_AXIS = "clients"
MODEL_AXIS = "model"
#: ``axis_name`` of a ``vmap`` (no mesh axis) over a round's clients
#: where they share one set of weights and their losses are summed
#: before they are differentiated (core/rounds.py ``make_local_loss``,
#: core/rounds_sp.py): code under it may take every client's rows at
#: once, and sum a shared weight's gradient over the clients itself,
#: where that is less work than a client at a time (models/gpt2.py
#: ``lm_nll_sums_chunked``). A ``vmap`` of per-client gradients
#: (``client_round``) must not carry the name
SHARED_CLIENTS = "shared_clients"


def axis_bound(name) -> bool:
    """Whether the trace is inside a ``vmap`` that named its axis so."""
    try:
        jax.lax.axis_size(name)
    except NameError:
        return False
    return True


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (CLIENT_AXIS,))


def make_mesh2d(n_clients: int, n_model: int,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """``clients`` × ``model`` mesh for pod-scale rounds: client
    fwd/bwd stays data-parallel over ``clients`` while server state
    (sketch table columns, momentum, error feedback) shards over
    ``model`` so per-device server memory scales as 1/``model``.
    ``--mesh 1x1`` and ``Mx1`` shapes keep the model axis at size 1,
    which every consumer treats as "replicated exactly like the 1-D
    mesh" — the compiled program is identical."""
    devices = list(devices) if devices is not None else jax.devices()
    need = n_clients * n_model
    if need > len(devices):
        raise ValueError(
            f"mesh {n_clients}x{n_model} needs {need} devices, "
            f"have {len(devices)}")
    arr = np.array(devices[:need]).reshape(n_clients, n_model)
    return Mesh(arr, (CLIENT_AXIS, MODEL_AXIS))


def carve_submeshes(demands, devices=None):
    """Disjoint per-job sub-meshes for the fedservice daemon: carve
    the pod's device list into consecutive blocks, one ``CxM`` mesh
    per ``(n_clients, n_model)`` demand, in demand order. The single
    sanctioned spatial-partitioning constructor — fedservice/ never
    builds a Mesh itself, so sharding layout (and the
    inline-partition-spec lint) keeps one owner. Each carved mesh is
    exactly what ``make_mesh2d(C, M, block)`` builds (``Mx1`` demands
    therefore behave like the 1-D mesh — see make_mesh2d), so a job
    admitted to a carved block compiles the same program it would
    compile on a standalone pod of that shape. Raises ValueError when
    the demands oversubscribe the pod — admission control surfaces
    this as a capacity rejection, never a partial carve."""
    devices = list(devices) if devices is not None else jax.devices()
    need = sum(int(c) * int(m) for c, m in demands)
    if need > len(devices):
        raise ValueError(
            f"sub-mesh demands need {need} devices "
            f"({[f'{c}x{m}' for c, m in demands]}), "
            f"have {len(devices)}")
    out, off = [], 0
    for c, m in demands:
        c, m = int(c), int(m)
        out.append(make_mesh2d(c, m, devices[off:off + c * m]))
        off += c * m
    return out


def client_axis_size(mesh: Mesh) -> int:
    """Devices along ``clients`` — the divisor for batch sharding and
    client-state padding (NOT ``mesh.devices.size``, which overcounts
    on a 2D mesh)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(CLIENT_AXIS, mesh.devices.size))


def model_axis_size(mesh: Mesh) -> int:
    """Devices along ``model`` (1 for 1-D meshes / None): the server
    state shard count. All 2D-specific code gates on this being > 1."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(MODEL_AXIS, 1))


# ---------------------------------------------------------------------------
# Sanctioned PartitionSpec constructors. Everything outside parallel/
# must build specs through these (the ``inline-partition-spec`` lint
# rule, analysis/lint.py) so sharding layout has one source of truth.

def client_spec() -> P:
    """Leading axis sharded over ``clients`` (batches, client state)."""
    return P(CLIENT_AXIS)


def replicated_spec() -> P:
    return P()


def spec(*axes) -> P:
    """Generic escape hatch for composed layouts (e.g. the
    ``clients`` × ``seq`` specs in core/rounds_sp.py). Prefer the
    named constructors for anything that is server state."""
    return P(*axes)


def table_shard_spec() -> P:
    """Count-sketch table (r, c): rows replicated, columns sharded
    over ``model`` — every model peer owns a c/M column slice of all
    r rows, so shard-local bucket reads stay contiguous."""
    return P(None, MODEL_AXIS)


def server_state_spec(transmit_shape) -> P:
    """Server momentum / error-feedback buffers, shaped like the
    transmit: (r, c) sketch tables shard columns over ``model``;
    (d,) dense vectors shard the coordinate axis over ``model``."""
    if len(transmit_shape) == 2:
        return table_shard_spec()
    return P(MODEL_AXIS)


def server_state_sharding(mesh: Mesh, transmit_shape) -> NamedSharding:
    """NamedSharding for ServerState leaves: model-sharded when the
    mesh has a model axis of size > 1, replicated otherwise (exactly
    the 1-D layout). NamedSharding pads uneven dims internally, so
    (d,) vectors need no divisibility."""
    if model_axis_size(mesh) <= 1:
        return replicated(mesh)
    return NamedSharding(mesh, server_state_spec(transmit_shape))


def on_every_device(fn, mesh: Optional[Mesh]):
    """``fn`` as the one-device program it is, executed identically by
    every device of ``mesh``: a ``shard_map`` over the whole mesh with
    fully replicated in and out specs. This is how replicated work
    that holds Mosaic kernels (the server step, the unsharded client
    fallbacks) runs on more than one chip — a plain multi-device
    ``jit`` hands the program to the SPMD partitioner, which refuses
    Mosaic custom calls ("cannot be automatically partitioned").
    ``None`` and one-device meshes return ``fn`` itself, so those
    builds keep exactly the program they had."""
    if mesh is None or mesh.devices.size == 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P())


def mesh_shape_dict(mesh: Optional[Mesh]) -> Optional[dict]:
    """``{axis: size}`` view of a mesh for manifests and checkpoint
    topology segments (None for the 1-D no-mesh path). The single
    serialisable mesh description the elastic-resume lineage is keyed
    by — comparing two of these answers "did the topology change?"."""
    if mesh is None:
        return None
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def first_local_device() -> jax.Device:
    """Local device 0 — the canonical probe target for memory stats
    and placement checks. The single sanctioned raw-device escape
    hatch: telemetry code resolves devices through this module (the
    ``raw-devices`` lint rule, analysis/lint.py) so subset meshes and
    multi-host topologies keep one source of truth."""
    return jax.local_devices()[0]


def topology_summary() -> dict:
    """The run's device topology, as recorded by run manifests and
    ledger meta records (the registry's run_key reads the counts):
    ``{device_count, local_device_count, process_index, process_count,
    backend, device_kind}``. A backend that will not initialise
    raises: a made-up topology on a record is worse than no record."""
    devices = jax.devices()
    return {
        "device_count": len(devices),
        "local_device_count": len(jax.local_devices()),
        "process_index": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
    }


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> int:
    """Join the JAX multi-controller runtime for multi-host pods — the
    TPU counterpart of the reference's NCCL process-group init
    (fed_aggregator.py:161-165), except one call replaces the whole
    PS/worker rank topology. After it returns, ``jax.devices()`` spans
    every host, ``make_mesh()`` covers ICI+DCN, and the per-round
    ``psum`` is routed hierarchically by XLA. On Cloud TPU the
    arguments are auto-detected from the environment; pass them
    explicitly elsewhere. Returns this process's index.

    No-op (returns the current process index) when the runtime is
    already initialised or when no cluster is detectable (plain
    single-process dev machine)."""
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    except RuntimeError as e:
        # double-init is fine (the runtime is up); anything else —
        # connection/barrier failures on a real pod — must surface,
        # or each host would silently train alone
        if "only be called once" not in str(e):
            raise
    except ValueError:
        # "coordinator_address should be defined": only tolerable in
        # auto-detect mode on a plain single-process machine
        if (coordinator_address is not None
                or num_processes is not None
                or process_id is not None):
            raise
    return jax.process_index()


def maybe_initialize_multihost_cli(args) -> None:
    """Trainer-CLI wiring, shared by cv_train and gpt2_train: honor
    --device cpu (a no-op once JAX has initialised its backends), join
    the multi-controller runtime when the pod flags
    (--coordinator_address/--num_processes/--process_id) are present,
    then make ``args.device`` true. Left unset it becomes the platform
    JAX reports; set, it must BE that platform — a run configured for
    ``tpu`` does not train on a CPU. Prints the devices once."""
    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    if not (args.coordinator_address is None
            and args.num_processes is None and args.process_id is None):
        # --process_id alone still initializes (and surfaces
        # initialize_multihost's error if the rest can't be detected)
        # rather than silently training alone
        pid = initialize_multihost(args.coordinator_address,
                                   args.num_processes, args.process_id)
        print(f"multihost: process {pid}/{jax.process_count()}")
    dev = jax.devices()[0]
    if args.device is None:
        args.device = dev.platform
    elif dev.platform != {"cuda": "gpu"}.get(args.device, args.device):
        # (--device keeps the reference's names; jax calls cuda "gpu")
        raise RuntimeError(
            f"--device {args.device} was asked for, but JAX reports "
            f"platform {dev.platform!r} ({dev.device_kind})")
    print(f"devices: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={jax.device_count()}")


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Shard leading (client) axis across the mesh."""
    return NamedSharding(mesh, P(CLIENT_AXIS))


def padded_rows(num_clients: int, mesh: Mesh) -> int:
    """Leading-dim size for client-axis-sharded state buffers:
    NamedSharding rejects non-divisible dims, so round up to the mesh
    size (padded rows are never indexed — client ids < num_clients).
    Single source of truth for ClientStates.init and checkpoint
    restore. On a 2D mesh only the ``clients`` axis divides the
    leading dim (rows are replicated over ``model``)."""
    n = client_axis_size(mesh)
    return -(-num_clients // n) * n


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, tree):
    """Place a pytree of (W, ...)-leading arrays with the client axis
    sharded. When W doesn't divide the mesh size (XLA requires
    divisibility) the batch is replicated instead — correct, just not
    load-balanced; pick num_workers divisible by the device count for
    full throughput. The fallback warns once per W so the perf cliff
    is never silent (round-1 review, "mesh-shape perf cliffs")."""
    n = client_axis_size(mesh)

    def put(x):
        if x.shape[0] % n == 0:
            return jax.device_put(x, client_sharding(mesh))
        _warn_unsharded(x.shape[0], n)  # once per (W, n)
        return jax.device_put(x, replicated(mesh))

    return jax.tree_util.tree_map(put, tree)


_WARNED_UNSHARDED = set()


def _warn_unsharded(w: int, n: int):
    if n == 1 or (w, n) in _WARNED_UNSHARDED:
        return
    _WARNED_UNSHARDED.add((w, n))
    import warnings
    warnings.warn(
        f"batch leading dim {w} does not divide the {n}-device mesh: "
        f"replicating instead of sharding the client axis — every "
        f"device computes all {w} clients. Pick --num_workers "
        f"divisible by the device count for full throughput.",
        RuntimeWarning, stacklevel=4)  # shard_batch's caller
    # (stacklevel: warn <- _warn_unsharded <- put <- tree_map frames)

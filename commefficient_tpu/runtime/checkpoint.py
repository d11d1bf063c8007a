"""Full-state checkpoint/resume for the federated runtime.

The reference only saves final weights (``torch.save(state_dict)``,
cv_train.py:420-423) and never optimizer/error state (SURVEY.md §5
"Checkpoint / resume: save-only"). Here a checkpoint captures the
complete round state:

- flat ``ps_weights``
- per-client ``ClientStates`` (velocities / errors / stale weights)
- server ``ServerState`` (virtual momentum + error, dense or
  sketch-shaped)
- round / update counters, byte-accounting state, optimizer step
  count, LR-scheduler position
- optionally the ``FedSampler``'s RNG state, so a resumed run
  continues the exact data order of an uninterrupted one

Format: a single ``np.savez_compressed`` archive with a JSON ``meta``
entry, written atomically (tmp + rename). Resume is bit-exact:
tests/test_checkpoint.py checks interrupted-and-resumed training
reproduces the uninterrupted run's weights exactly.

Checkpoints are TOPOLOGY-PORTABLE (the elastic-pod contract,
tests/test_elastic.py): every state buffer is saved as the full host
array, so restore re-places it under the CURRENT run's mesh and
process count — server momentum/EF columns reshard through
``parallel/mesh.server_state_sharding``, client rows repad through
``padded_rows``, multi-process clientstore side shards merge and
re-split by the new ownership ranges, and the asyncfed arrival
backlog is rebuilt entry for entry. ``meta["topology"]`` /
``meta["segments"]`` record the lineage so manifests (and the perf
gate) can tell a resized run from an unbroken one.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import warnings
import zipfile
from typing import Optional

import jax
import numpy as np

from commefficient_tpu.core.rounds import ClientStates
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.parallel.mesh import mesh_shape_dict

_FMT = 1


class TornCheckpointError(ValueError):
    """A checkpoint archive (main or side shard) is missing,
    truncated or otherwise unreadable. Carries the offending file's
    path in the message so an operator knows exactly which shard to
    recover; ``setup_resume`` catches it and falls back to the newest
    retained autosave that still validates."""


def checkpoint_file(directory: str, tag: str = "state") -> str:
    return os.path.join(directory, f"ckpt_{tag}.npz")


def _shard_file(path: str, process_index: int) -> str:
    """Side file holding a non-zero process's client-store shard."""
    return f"{path}.shard{int(process_index)}.npz"


def _atomic_savez(path: str, **arrays):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _verify_archive(path: str) -> None:
    """Refuse a torn/truncated .npz with an error NAMING the file.
    The atomic tmp+rename write means a torn archive normally cannot
    exist, but a shared filesystem hiccup, a partial copy, or a side
    shard orphaned by a dead process can still leave one — and
    np.load's failure mode on those is an opaque zipfile traceback
    halfway through restore."""
    if not os.path.exists(path):
        raise TornCheckpointError(
            f"checkpoint shard missing: {path}")
    try:
        with zipfile.ZipFile(path) as zf:
            bad = zf.testzip()  # CRC-checks every member
        if bad is not None:
            raise TornCheckpointError(
                f"checkpoint shard {path} is torn: member {bad!r} "
                "fails its CRC")
    except TornCheckpointError:
        raise
    except (zipfile.BadZipFile, OSError, EOFError) as e:
        raise TornCheckpointError(
            f"checkpoint shard {path} is torn/truncated: {e}") from e


def validate_checkpoint(path: str) -> dict:
    """Verify the main archive AND every side shard its meta records,
    returning the meta dict. Restore calls this first so a torn shard
    is reported by name before any state is touched, instead of
    crashing mid-resume with half the model restored."""
    _verify_archive(path)
    try:
        with np.load(path, allow_pickle=False) as z:
            if "meta" not in z.files:
                raise TornCheckpointError(
                    f"checkpoint {path} has no meta entry — torn or "
                    "not a checkpoint archive")
            meta = json.loads(str(z["meta"]))
    except TornCheckpointError:
        raise
    except (ValueError, OSError, EOFError) as e:
        raise TornCheckpointError(
            f"checkpoint {path} is unreadable: {e}") from e
    procs = int((meta.get("clientstore") or {}).get("processes", 1))
    for k in range(1, procs):
        _verify_archive(_shard_file(path, k))
    return meta


def current_topology(mesh=None) -> dict:
    """This run's restore-relevant topology, stamped into checkpoint
    meta segments and registry manifests: the counts whose change
    triggers the migration paths in load_checkpoint."""
    topo = {"device_count": int(jax.device_count()),
            "process_count": int(jax.process_count())}
    ms = mesh_shape_dict(mesh)
    if ms is not None:
        topo["mesh_shape"] = ms
    return topo


def resume_manifest_extra(model) -> dict:
    """Registry stamps for a resumed run: ``resumed_from`` (the
    checkpoint this run restored) and ``topology_segments`` (one
    entry per topology the lineage has run under, the restored chain
    plus the current segment). Empty for unresumed runs, so trainers
    can unconditionally splat it into ``maybe_write_manifest``'s
    extra. A lineage whose segments span more than one topology is
    flagged, never read as one experiment (telemetry/registry.py
    run_topology_changed)."""
    info = getattr(model, "_resume_info", None)
    if not info:
        return {}
    segments = list(getattr(model, "_restored_segments", []))
    segments.append({**current_topology(model.mesh),
                     "round_index": int(model.round_index)})
    return {"resumed_from": dict(info),
            "topology_segments": segments}


def _prune_stale_shards(path: str, processes: int) -> None:
    """Drop side shard files whose index is >= the writing process
    count: they were left by a LARGER previous topology, the meta
    just written no longer records them, and a later resume on yet
    another process count must not merge rows from the dead layout."""
    base = os.path.basename(path)
    pat = re.compile(re.escape(base) + r"\.shard(\d+)\.npz$")
    d = os.path.dirname(path) or "."
    for name in os.listdir(d):
        m = pat.fullmatch(name)
        if m and int(m.group(1)) >= int(processes):
            try:
                os.unlink(os.path.join(d, name))
            except OSError:
                pass


def _merged_store_shard(path: str, z, processes: int) -> dict:
    """Merge every writing process's sparse clientstore shard into
    one global shard: process 0's rows from the main archive plus
    each side file's. Ids are disjoint across shards (contiguous
    ownership ranges), so the merge is a concatenation; init rows are
    identical everywhere and taken first-seen. This is the
    topology-migration path — ``import_shard`` on the restoring side
    then keeps only the rows each NEW process owns."""
    shards = [{k[len("store:"):]: np.asarray(z[k])
               for k in z.files if k.startswith("store:")}]
    for k in range(1, int(processes)):
        sp = _shard_file(path, k)
        _verify_archive(sp)
        with np.load(sp, allow_pickle=False) as sz:
            shards.append({n: np.asarray(sz[n]) for n in sz.files})
    merged: dict = {}
    for sh in shards:
        for n, v in sh.items():
            if n.startswith("init:") and n not in merged:
                merged[n] = v
    merged["ids"] = np.concatenate(
        [np.asarray(sh.get("ids", np.zeros((0,), np.int64)), np.int64)
         for sh in shards])
    fields = sorted({n for sh in shards for n in sh
                     if n != "ids" and not n.startswith("init:")})
    for f in fields:
        parts = []
        for i, sh in enumerate(shards):
            if f not in sh:
                raise TornCheckpointError(
                    f"clientstore shard {i} of {path} lacks field "
                    f"{f!r} — partial shard set")
            parts.append(np.asarray(sh[f]))
        merged[f] = np.concatenate(parts)
    return merged


def save_checkpoint(path: str, model, opt, scheduler=None,
                    sampler=None, epoch: int = 0,
                    extra: Optional[dict] = None,
                    loader=None, mid_epoch: bool = False) -> str:
    """Serialise the full runtime state to ``path`` (.npz).

    ``mid_epoch=True`` (the round-cadence autosaver) additionally
    captures the sampler's LIVE epoch state — permutation, per-client
    cursors, the lookahead's buffered round spec and the post-draw
    RNG — so a resumed run continues the interrupted epoch's
    remaining rounds bit-exactly instead of restarting the epoch.
    Epoch-boundary saves must NOT set it: their exhausted iterator
    state would make the resumed epoch yield zero rounds."""
    # _host, not device_get: on a multi-process mesh the per-client
    # state rows are sharded across processes and not fully addressable
    # — process_allgather (a collective every process must reach)
    # reassembles the global rows; replicated arrays pass through
    from commefficient_tpu.runtime.fed_model import _host

    if getattr(model, "client_store", None) is not None:
        # host client store: land any round still awaiting write-back
        # so the store snapshot below is complete
        model._store_writeback()
    # the last server update's support may still be pending: the
    # accounting state saved below is the state with it applied
    model.settle_update()

    # checkpoint save is a deliberate full sync OFF the round hot
    # path (epoch cadence): materialising state here is the point,
    # and no telemetry round record is open to attribute it to
    arrays = {"ps_weights": _host(model.ps_weights)}  # audit: allow(host-sync)
    cs = model.client_states
    for name, val in (("cs_velocities", cs.velocities),
                      ("cs_errors", cs.errors),
                      ("cs_weights", cs.weights)):
        if val is not None:
            arrays[name] = _host(val)  # audit: allow(host-sync)
    ss = opt.server_state
    arrays["ss_Vvelocity"] = _host(ss.Vvelocity)  # audit: allow(host-sync)
    arrays["ss_Verror"] = _host(ss.Verror)  # audit: allow(host-sync)
    arrays["last_updated"] = model.last_updated
    arrays["client_last_seen"] = model.client_last_seen
    if getattr(model, "model_state", None) is not None:
        # BatchNorm running stats: flatten the pytree with stable,
        # path-derived keys
        from jax.tree_util import keystr, tree_flatten_with_path
        leaves, _ = tree_flatten_with_path(model.model_state)
        for leaf_path, leaf in leaves:
            # audit: allow(host-sync) — same checkpoint-save sync
            arrays["bnstats:" + keystr(leaf_path)] = _host(leaf)

    meta = {
        "format": _FMT,
        "epoch": int(epoch),
        "round_index": int(model.round_index),
        "update_round": int(model._update_round),
        "fedavg_lr": float(model.fedavg_lr),
        "opt_step_count": int(opt._step_count),
        "mode": model.args.mode,
        "grad_size": int(model.args.grad_size),
        "num_clients": int(model.num_clients),
        "transmit_shape": list(model.args.transmit_shape),
        "error_type": model.args.error_type,
        "extra": extra or {},
        # elastic-pod lineage: the topology this archive was written
        # under, plus the chain of earlier segments a resumed run
        # restored through — restore migrates placement whenever the
        # reader's topology differs, and the manifests' segment list
        # flags a ledger that spans topologies
        "topology": current_topology(getattr(model, "mesh", None)),
        "segments": (list(getattr(model, "_restored_segments", []))
                     + [{**current_topology(getattr(model, "mesh",
                                                    None)),
                         "round_index": int(model.round_index)}]),
    }
    if model.args.mode == "sketch":
        # the RESOLVED rotation granularity, not the -1 sentinel: a
        # sketch-space error table decoded under a different rotation
        # stream is silent corruption, and auto (-1) re-resolves per
        # platform — so resume validates the resolved value
        from commefficient_tpu.core.rounds import resolve_rot_lanes
        meta["rot_lanes"] = int(resolve_rot_lanes(model.args))
    store = getattr(model, "client_store", None)
    if store is not None:
        # sparse store snapshot: only the rows clients actually wrote
        # (plus each field's init row, so never-seen clients replay the
        # ORIGINAL run's init on resume). Process 0's shard rides in
        # the main archive; every other process writes its own side
        # file next to it (its rows are not addressable from here).
        meta["clientstore"] = {"fields": list(store.field_names),
                               "processes": int(jax.process_count())}
        shard = store.export_shard()
        if jax.process_index() == 0:
            for k, v in shard.items():
                arrays["store:" + k] = v
        else:
            _atomic_savez(_shard_file(path, jax.process_index()),
                          **shard)
        # asyncfed issue-round stamps: identical on every process
        # (stamp_rounds runs with the full cohort's ids everywhere),
        # so process 0's copy in the main archive covers the pod
        stamp_ids, stamp_rounds = store.export_stamps()
        if stamp_ids.size:
            arrays["store_stamp_ids"] = stamp_ids
            arrays["store_stamp_rounds"] = stamp_rounds
    drv = getattr(model, "_async_driver", None)
    if drv is not None:
        # the buffered-arrival backlog: without it a resumed async
        # run restarts with an empty queue and every in-flight
        # buffered round is silently dropped
        st = drv.export_state()
        meta["asyncfed"] = {
            "fold": st["fold"], "seq": st["seq"],
            "issued_total": st["issued_total"],
            "folded_total": st["folded_total"],
            "pending": int(st["arrive_at"].shape[0]),
            "slot_keys": list(st["slot_keys"]),
        }
        arrays["async_arrive_at"] = st["arrive_at"]
        arrays["async_issue_seq"] = st["issue_seq"]
        arrays["async_issue"] = st["issue"]
        for k, v in st["slots"].items():
            arrays["async:slot:" + k] = v
    acc = getattr(model, "_accountant", None)
    if acc is not None:
        # --dp sketch: the accountant's per-order RDP totals ride as
        # JSON floats (bit-exact round-trip), so a resumed run's ε
        # trajectory continues the unbroken run's exactly — the spent
        # budget survives preemption like every other piece of state
        meta["privacy"] = acc.state_dict()
    if scheduler is not None:
        meta["scheduler_step"] = int(scheduler._step)
    settle = getattr(loader, "settle", None)
    if settle is not None:
        # a loader that makes its batches on a thread of its own
        # (data/loader.py) finishes the round it is making first: the
        # sampler, the dropout stream and the round counter read below
        # are then whole. Rounds it has made and not yet handed over
        # are in flight, like the native ring's: a mid-epoch resume
        # continues, bit-exactly, with the round after them
        settle()
    # an epoch's end is not in flight: where that thread has opened
    # the next epoch before this one was taken to its end, the loader
    # holds the streams as they stood at the end (``held_back()``), and
    # those are what is saved: the resumed run opens that epoch itself,
    # to the same first round
    held_back = getattr(loader, "held_back", None)
    held = {k: v for k, v in (held_back() if held_back else {}).items()
            if v is not None}

    def put_rng(name, state):
        meta[name] = [state[0], None, int(state[2]), int(state[3]),
                      float(state[4])]
        arrays[name + "_keys"] = np.asarray(state[1])

    if sampler is not None and hasattr(sampler.rng, "get_state"):
        put_rng("sampler_rng",
                held.get("sampler_rng") or sampler.rng.get_state())
    # datasets with stateful per-item RNG (e.g. FedPERSONA's
    # personality shuffles) advance it on every access — capture it or
    # a resumed epoch sees different records than the uninterrupted run
    ds = getattr(sampler, "dataset", None)
    ds_rng = getattr(ds, "_rng", None)
    if ds_rng is not None and hasattr(ds_rng, "getstate"):
        version, internal, gauss = (held.get("dataset_rng")
                                    or ds_rng.getstate())
        meta["dataset_rng"] = [int(version), gauss]
        arrays["dataset_rng_state"] = np.asarray(internal, np.int64)
    # the CV transform stacks draw from the GLOBAL numpy RNG — capture
    # it too, or augmentation replays from the re-seeded stream after
    # resume while the uninterrupted run's stream had advanced
    put_rng("np_global_rng",
            held.get("np_global_rng") or np.random.get_state())
    # the native data-plane derives per-round augmentation seeds from
    # its round counter
    if loader is not None and hasattr(loader, "_round_counter"):
        meta["loader_round_counter"] = int(held.get(
            "loader_round_counter", loader._round_counter))
    # --dropout_prob draws from the loader's own RNG stream every
    # round — capture it or a resumed run replays drops from the
    # re-seeded stream while the uninterrupted run's had advanced
    dr = getattr(loader, "_dropout_rng", None)
    if dr is not None and hasattr(dr, "get_state"):
        put_rng("dropout_rng", held.get("dropout_rng") or dr.get_state())
    if mid_epoch and sampler is not None \
            and hasattr(sampler, "export_state"):
        # with the next epoch opened ahead, the epoch the consumer is
        # still in has no round left to deal: the resumed run re-enters
        # it for nothing and opens the next
        st = held.get("sampler_mid") or sampler.export_state()
        if st is not None:
            meta["sampler_mid_epoch"] = True
            arrays["sampler_mid_permuted"] = np.asarray(st["permuted"])
            arrays["sampler_mid_cur"] = np.asarray(st["cur"])
            if st.get("rng_state") is not None:
                put_rng("sampler_mid_rng", st["rng_state"])
            if st.get("spec_workers") is not None:
                arrays["sampler_mid_spec_workers"] = st["spec_workers"]
                arrays["sampler_mid_spec_sizes"] = st["spec_sizes"]
                arrays["sampler_mid_spec_idx"] = st["spec_idx"]

    # every process gathered (the allgathers above are collectives)
    # but exactly one writes — concurrent writers on a shared
    # filesystem would corrupt the archive
    err = None
    if jax.process_index() == 0:
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path) or ".", suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    np.savez_compressed(f, meta=json.dumps(meta),
                                        **arrays)
                os.replace(tmp, path)
                _prune_stale_shards(path, int(jax.process_count()))
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
        except BaseException as e:
            # don't raise yet: the peers are headed into the barrier
            # below, and abandoning it would turn a local I/O error
            # into a pod-wide hang
            err = e
    if jax.process_count() > 1:
        # barrier + failure broadcast: nobody proceeds (or resumes
        # from this path) until the writer finished, and a write
        # failure on process 0 fails every process with the real
        # reason instead of a heartbeat timeout. The broadcast is
        # one-sided: if a NON-zero process dies before reaching it
        # (e.g. in its local gather/serialization above), process 0
        # blocks here until the distributed runtime's collective
        # timeout fires — the general failure mode of any collective,
        # bounded and attributed by that timeout rather than by this
        # layer
        from jax.experimental import multihost_utils
        ok = multihost_utils.broadcast_one_to_all(
            np.int32(0 if err is None else 1))
        if int(ok) and err is None:
            raise RuntimeError(
                f"checkpoint write failed on process 0 ({path})")
    if err is not None:
        raise err
    return path


def load_checkpoint(path: str, model, opt, scheduler=None,
                    sampler=None, loader=None) -> dict:
    """Restore runtime state in place; returns the meta dict (use
    ``meta["epoch"]`` as the resume epoch).

    Topology-portable: the checkpoint may have been written on a
    different mesh shape, device count or process count — state is
    re-placed under THIS run's layout (values untouched, so a resized
    resume stays bit-exact against an unresized one)."""
    validate_checkpoint(path)
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        checks = [("format", _FMT),
                  ("grad_size", int(model.args.grad_size)),
                  ("mode", model.args.mode),
                  ("num_clients", int(model.num_clients))]
        if "transmit_shape" in meta:  # sketch geometry etc.
            checks.append(("transmit_shape",
                           list(model.args.transmit_shape)))
            checks.append(("error_type", model.args.error_type))
        if model.args.mode == "sketch":
            # an absent key is a pre-round-5 checkpoint, written when
            # the default was 0 (full granularity) — it must still
            # refuse a run whose auto default now resolves nonzero,
            # not skip the check
            from commefficient_tpu.core.rounds import resolve_rot_lanes
            got = int(meta.get("rot_lanes", 0))
            want = int(resolve_rot_lanes(model.args))
            if got != want:
                raise ValueError(
                    f"checkpoint rot_lanes={got} does not match "
                    f"this run's {want} ({path})")
        for key, want in checks:
            if meta[key] != want:
                raise ValueError(
                    f"checkpoint {key}={meta[key]!r} does not match "
                    f"this run's {want!r} ({path})")
        # the set of client-state buffers is determined by the config
        # (local momentum / local error / topk_down) — a presence
        # mismatch means the hyperparameters changed, and silently
        # keeping fresh zeros would diverge from the saved trajectory.
        # Derived from the CONFIG (not model.client_states) so it
        # holds for both placements: a host-store run keeps the device
        # arrays None and records its fields in meta instead.
        ck_store = meta.get("clientstore")
        ck_fields = set((ck_store or {}).get("fields", []))
        uses = {
            "velocities": model.args.local_momentum > 0,
            "errors": model.args.error_type == "local",
            "weights": bool(getattr(model.args, "do_topk_down",
                                    False)),
        }
        for field, used in uses.items():
            has = ("cs_" + field in z.files) or (field in ck_fields)
            if has != used:
                raise ValueError(
                    f"checkpoint {'has' if has else 'lacks'} "
                    f"client {field} but this run "
                    f"{'does not use' if not used else 'needs'} them "
                    "— momentum/error/topk_down flags differ")

        import jax.numpy as jnp

        from commefficient_tpu.parallel.mesh import (
            client_sharding, padded_rows, replicated,
            server_state_sharding)

        # per-client state rows were sharded over the clients axis at
        # init (FedModel.__init__) — restore with the same placement.
        # Row padding depends on the mesh size, so a checkpoint taken
        # on a different device count is repadded here (padded rows
        # hold no information: client ids never index them).

        csh = client_sharding(model.mesh)
        nc = int(model.num_clients)
        rows = padded_rows(nc, model.mesh)

        def put_client_rows(arr):
            arr = np.asarray(arr)[:nc]
            if arr.shape[0] < rows:
                pad = np.zeros((rows - arr.shape[0],) + arr.shape[1:],
                               arr.dtype)
                arr = np.concatenate([arr, pad])
            # device_put straight from host numpy: transfers each
            # shard to its device without a replicated stopover
            return jax.device_put(arr, csh)

        model.ps_weights = jax.device_put(
            np.asarray(z["ps_weights"]), replicated(model.mesh))
        store = getattr(model, "client_store", None)
        if store is not None:
            # this run keeps client state in the host store
            if ck_store is not None:
                ck_procs = int(ck_store.get("processes", 1))
                if ck_procs == jax.process_count():
                    # same process count: shard files line up with
                    # ownership, each process imports exactly its own
                    if jax.process_index() == 0:
                        shard = {k[len("store:"):]: np.asarray(z[k])
                                 for k in z.files
                                 if k.startswith("store:")}
                    else:
                        sp = _shard_file(path, jax.process_index())
                        with np.load(sp, allow_pickle=False) as sz:
                            shard = {k: np.asarray(sz[k])
                                     for k in sz.files}
                else:
                    # topology-changing restore: the old shard split
                    # no longer matches this run's ownership ranges —
                    # merge every old process's sparse shard and let
                    # import_shard's write keep only the rows each
                    # NEW process owns (the placement-migration path)
                    shard = _merged_store_shard(path, z, ck_procs)
                store.import_shard(shard)
                if "store_stamp_ids" in z.files:
                    store.import_stamps(z["store_stamp_ids"],
                                        z["store_stamp_rounds"])
            else:
                # dense (device-placement) checkpoint: import every
                # client's row into the store
                nc0 = int(model.num_clients)
                shard = {"ids": np.arange(nc0, dtype=np.int64)}
                for field in store.field_names:
                    shard[field] = np.asarray(z["cs_" + field])[:nc0]
                store.import_shard(shard)
            model.client_states = ClientStates(None, None, None)
        elif ck_fields:
            # host-store checkpoint into a device-placement run:
            # merge all processes' sparse shards (the single-process
            # case merges trivially) and densify over the init rows
            merged = _merged_store_shard(
                path, z, int(ck_store.get("processes", 1)))

            def densify(field):
                if field not in ck_fields:
                    return None
                ids = np.asarray(merged["ids"], np.int64)
                rows_f = np.asarray(merged[field])
                shape = (int(model.num_clients),) + rows_f.shape[1:]
                init = merged.get("init:" + field)
                if init is not None:
                    base = np.broadcast_to(np.asarray(init),
                                           shape).copy()
                else:
                    base = np.zeros(shape, np.float32)
                base[ids] = rows_f
                return put_client_rows(base)

            model.client_states = ClientStates(densify("velocities"),
                                               densify("errors"),
                                               densify("weights"))
        else:
            cs = model.client_states
            model.client_states = ClientStates(
                put_client_rows(z["cs_velocities"])
                if "cs_velocities" in z else cs.velocities,
                put_client_rows(z["cs_errors"])
                if "cs_errors" in z else cs.errors,
                put_client_rows(z["cs_weights"])
                if "cs_weights" in z else cs.weights,
            )
        # server momentum/EF buffers: the archive holds the full host
        # table, so restoring onto a different CxM mesh is a pure
        # placement migration — device_put under the CURRENT mesh's
        # column sharding (values untouched, hence bit-exact vs an
        # unresized run). The <=1 model-axis case restores replicated,
        # exactly the layout FedOptimizer initialised.
        opt.server_state = ServerState.restore(
            np.asarray(z["ss_Vvelocity"]), np.asarray(z["ss_Verror"]),
            sharding=server_state_sharding(
                model.mesh, tuple(model.args.transmit_shape)))
        # an update this model had pending belongs to the state the
        # archive replaces: applied first, then overwritten
        model.settle_update()
        model.last_updated = np.asarray(z["last_updated"])
        model.client_last_seen = np.asarray(z["client_last_seen"])
        if getattr(model, "model_state", None) is not None:
            from jax.tree_util import keystr, tree_flatten_with_path
            leaves, treedef = tree_flatten_with_path(model.model_state)
            if not any(k.startswith("bnstats:") for k in z.files):
                # checkpoint written by a BN-free build (or before
                # running stats existed): keep the fresh init stats
                # rather than refusing the whole restore — weights and
                # optimizer state are still bit-exact, only the running
                # statistics restart their blend
                warnings.warn(
                    "checkpoint has no BN running stats "
                    "(pre-batchnorm format); resuming with freshly "
                    "initialised statistics")
            else:
                restored = []
                for leaf_path, leaf in leaves:
                    key = "bnstats:" + keystr(leaf_path)
                    if key not in z.files:
                        raise ValueError(
                            f"checkpoint lacks BN running stats {key} "
                            "but this run tracks them")
                    restored.append(jnp.asarray(z[key]))
                model.model_state = jax.tree_util.tree_unflatten(
                    treedef, restored)
        model.round_index = meta["round_index"]
        model._update_round = meta["update_round"]
        model._rebuild_round_counts()
        model.fedavg_lr = meta["fedavg_lr"]
        opt._step_count = meta["opt_step_count"]
        if scheduler is not None and "scheduler_step" in meta:
            scheduler._step = meta["scheduler_step"]
        drop = getattr(loader, "close", None)
        if drop is not None:
            # an epoch the loader's thread had opened ahead was drawn
            # from the streams as they were: the restored ones open
            # the next epoch
            drop()
        if sampler is not None and "sampler_rng" in meta:
            s = meta["sampler_rng"]
            sampler.rng.set_state((s[0], np.asarray(z["sampler_rng_keys"]),
                                   s[2], s[3], s[4]))
        ds = getattr(sampler, "dataset", None)
        ds_rng = getattr(ds, "_rng", None)
        if ds_rng is not None and "dataset_rng" in meta:
            version, gauss = meta["dataset_rng"]
            internal = tuple(int(v) for v in z["dataset_rng_state"])
            ds_rng.setstate((version, internal, gauss))
        if "np_global_rng" in meta:
            g = meta["np_global_rng"]
            np.random.set_state((g[0],
                                 np.asarray(z["np_global_rng_keys"]),
                                 g[2], g[3], g[4]))
        if loader is not None and "loader_round_counter" in meta \
                and hasattr(loader, "_round_counter"):
            loader._round_counter = meta["loader_round_counter"]
        dr = getattr(loader, "_dropout_rng", None)
        if dr is not None and "dropout_rng" in meta \
                and hasattr(dr, "set_state"):
            g = meta["dropout_rng"]
            dr.set_state((g[0], np.asarray(z["dropout_rng_keys"]),
                          g[2], g[3], g[4]))
        if sampler is not None and meta.get("sampler_mid_epoch") \
                and hasattr(sampler, "import_state"):
            st = {"permuted": np.asarray(z["sampler_mid_permuted"]),
                  "cur": np.asarray(z["sampler_mid_cur"])}
            if "sampler_mid_rng" in meta:
                r = meta["sampler_mid_rng"]
                st["rng_state"] = (
                    r[0], np.asarray(z["sampler_mid_rng_keys"]),
                    r[2], r[3], r[4])
            if "sampler_mid_spec_workers" in z.files:
                st["spec_workers"] = np.asarray(
                    z["sampler_mid_spec_workers"])
                st["spec_sizes"] = np.asarray(
                    z["sampler_mid_spec_sizes"])
                st["spec_idx"] = np.asarray(z["sampler_mid_spec_idx"])
            sampler.import_state(st)

        # asyncfed backlog: rebuild the arrival heap + counters so
        # queued (in-flight) buffered rounds survive the resume
        drv = getattr(model, "_async_driver", None)
        ck_async = meta.get("asyncfed")
        if drv is not None and ck_async is not None:
            keys = list(ck_async.get("slot_keys", []))
            drv.import_state({
                "fold": ck_async["fold"], "seq": ck_async["seq"],
                "issued_total": ck_async["issued_total"],
                "folded_total": ck_async["folded_total"],
                "slot_keys": keys,
                "arrive_at": np.asarray(z["async_arrive_at"]),
                "issue_seq": np.asarray(z["async_issue_seq"]),
                "issue": np.asarray(z["async_issue"]),
                "slots": {k: np.asarray(z["async:slot:" + k])
                          for k in keys},
            })
        elif drv is not None:
            warnings.warn(
                "checkpoint has no asyncfed state (written by a "
                "synchronous or pre-elastic run); the arrival buffer "
                "resumes empty")
        elif ck_async is not None and int(ck_async.get("pending", 0)):
            raise ValueError(
                f"checkpoint holds {ck_async['pending']} queued async "
                "arrival(s) but this run is synchronous — resume with "
                "--async_buffer_size or the buffered rounds in flight "
                f"are dropped ({path})")

        # DP accountant: restore the spent-budget state bit-exactly.
        # Presence mismatches are hard decisions — a DP resume from a
        # DP-less checkpoint would silently RESET the spent ε to zero
        # (a privacy violation, not an inconvenience), so it refuses;
        # the reverse direction only drops observability and warns.
        ck_priv = meta.get("privacy")
        acc = getattr(model, "_accountant", None)
        if acc is not None and ck_priv is not None:
            from commefficient_tpu.privacy import PrivacyAccountant
            model._accountant = PrivacyAccountant.load_state(ck_priv)
        elif acc is not None:
            raise ValueError(
                "checkpoint has no privacy accountant state but this "
                "run is --dp sketch; resuming would reset the spent "
                f"ε budget to zero ({path})")
        elif ck_priv is not None:
            warnings.warn(
                "checkpoint carries a privacy accountant (written by "
                "a --dp sketch run) but this run has DP off; the "
                "spent-budget state is dropped")

        # lineage, for manifests (resume_manifest_extra) and the next
        # save's meta["segments"] chain
        model._restored_segments = list(
            meta.get("segments")
            or ([meta["topology"]] if meta.get("topology") else []))
        model._resume_info = {
            "checkpoint": os.path.abspath(path),
            "epoch": int(meta.get("epoch", 0)),
            "round_index": int(meta.get("round_index", 0)),
            "topology": meta.get("topology"),
        }
    return meta


def history_file(directory: str, tag: str, round_index: int) -> str:
    """A retained autosave snapshot's path (round-stamped)."""
    return os.path.join(directory,
                        f"ckpt_{tag}_r{int(round_index):08d}.npz")


class RoundAutosaver:
    """``--checkpoint_every_rounds`` round-cadence autosave.

    Called from the trainers' round loop after every completed round.
    Saves a ``mid_epoch`` checkpoint at the configured cadence, then
    retains up to ``--checkpoint_keep`` round-stamped history
    snapshots via hardlinks to the just-written archive (zero copy
    cost; falls back to a copy on link-hostile filesystems) and prunes
    the oldest beyond the budget. A SIGTERM at any point leaves either
    the previous or the new checkpoint intact — never a torn one
    (the save itself is tmp+rename atomic).

    What such a checkpoint holds of the data path near an epoch's end:
    once the sampler has dealt the epoch's last round the loader's
    thread opens the next epoch ahead (data/loader.py), and the save
    then records, through ``loader.held_back()``, the sampler as it
    stood at the end (an epoch with no round left, and the RNG from
    which the next epoch's permutations are drawn), not as it stands.
    The rounds of this epoch that were made and not yet trained on are
    in flight and lost, as ever; the resumed run re-enters the epoch
    for nothing and opens the next one itself, to the rounds the
    uninterrupted run deals."""

    def __init__(self, args, model, opt, scheduler, sampler, loader,
                 tag: str):
        self.every = int(getattr(args, "checkpoint_every_rounds", 0)
                         or 0)
        self.keep = int(getattr(args, "checkpoint_keep", 0) or 0)
        self.args = args
        self.model, self.opt, self.scheduler = model, opt, scheduler
        self.sampler, self.loader, self.tag = sampler, loader, tag
        self.path = checkpoint_file(args.checkpoint_path, tag)
        self._last_saved = -1

    def __call__(self, epoch: int):
        """``epoch``: the 0-based epoch currently in progress (a
        mid-epoch resume re-enters this same epoch)."""
        if self.every <= 0:
            return
        r = int(self.model.round_index)
        if r <= 0 or r % self.every or r == self._last_saved:
            return
        save_checkpoint(self.path, self.model, self.opt,
                        self.scheduler, self.sampler, epoch=int(epoch),
                        loader=self.loader, mid_epoch=True)
        self._last_saved = r
        if self.keep > 0 and jax.process_index() == 0:
            self._retain(r)

    def _retain(self, round_index: int):
        import shutil

        def link(src, dst):
            if os.path.exists(dst) or not os.path.exists(src):
                return
            try:
                os.link(src, dst)
            except OSError:
                shutil.copy2(src, dst)

        hist = history_file(self.args.checkpoint_path, self.tag,
                            round_index)
        link(self.path, hist)
        # multi-process clientstore side shards retain WITH the main
        # archive — a fallback resume onto this snapshot must be able
        # to rebuild the store from the matching shard set
        for k in range(1, jax.process_count()):
            link(_shard_file(self.path, k), _shard_file(hist, k))
        pat = re.compile(
            rf"^ckpt_{re.escape(self.tag)}_r(\d+)\.npz$")
        snaps = sorted(
            (int(m.group(1)), m.group(0))
            for m in (pat.match(n) for n in
                      os.listdir(self.args.checkpoint_path))
            if m)
        for _, name in snaps[:-self.keep]:
            doomed = [name] + [
                n for n in os.listdir(self.args.checkpoint_path)
                if n.startswith(name + ".shard")]
            for victim in doomed:
                try:
                    os.unlink(os.path.join(self.args.checkpoint_path,
                                           victim))
                except OSError:
                    pass


def _resolve_resume_source(directory: str, path: str,
                           tag: str) -> str:
    """The archive ``--resume`` should actually restore: the
    canonical checkpoint when it validates, else the NEWEST retained
    autosave snapshot that does. A torn canonical (shared-fs hiccup,
    partial copy) therefore costs at most ``--checkpoint_every_rounds``
    rounds instead of crashing the resume; with no valid fallback the
    original TornCheckpointError (naming the bad shard) propagates."""
    try:
        validate_checkpoint(path)
        return path
    except TornCheckpointError as torn:
        pat = re.compile(rf"^ckpt_{re.escape(tag)}_r(\d+)\.npz$")
        snaps = sorted(
            ((int(m.group(1)), m.group(0))
             for m in (pat.match(n) for n in os.listdir(directory))
             if m), reverse=True)
        for _, name in snaps:
            hist = os.path.join(directory, name)
            try:
                validate_checkpoint(hist)
            except TornCheckpointError:
                continue
            print(f"WARNING: {torn} — falling back to retained "
                  f"autosave {hist}")
            return hist
        raise


def setup_resume(args, model, opt, scheduler, loader, tag: str):
    """Shared trainer wiring: returns
    ``(start_epoch, epoch_hook, round_hook)``.

    - ``--resume`` requires ``--checkpoint`` and an existing file —
      anything else raises instead of silently training from scratch
      (and then overwriting the directory's checkpoints).
    - a torn canonical checkpoint falls back to the newest retained
      autosave that still validates (``_resolve_resume_source``).
    - ``epoch_hook`` saves every ``--checkpoint_every`` epochs and at
      the end of training.
    - ``round_hook(epoch)`` is the :class:`RoundAutosaver` when
      ``--checkpoint_every_rounds`` is set (None otherwise); the
      trainers call it after every completed round.
    """
    import math

    if not (args.do_checkpoint or args.do_resume):
        return 0, None, None
    if args.do_resume and not args.do_checkpoint:
        raise ValueError("--resume requires --checkpoint")
    path = checkpoint_file(args.checkpoint_path, tag)
    sampler = getattr(loader, "sampler", None)
    start_epoch = 0
    if args.do_resume:
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"--resume: no checkpoint at {path}")
        src = _resolve_resume_source(args.checkpoint_path, path, tag)
        meta = load_checkpoint(src, model, opt, scheduler, sampler,
                               loader)
        start_epoch = meta["epoch"]
        print(f"resumed from {src} at epoch {start_epoch}"
              + (" (mid-epoch)" if meta.get("sampler_mid_epoch")
                 else ""))

    def epoch_hook(ep):
        if (args.checkpoint_every
                and ep % args.checkpoint_every == 0) \
                or ep >= math.ceil(args.num_epochs):
            save_checkpoint(path, model, opt, scheduler, sampler,
                            epoch=ep, loader=loader)

    round_hook = None
    if int(getattr(args, "checkpoint_every_rounds", 0) or 0) > 0:
        round_hook = RoundAutosaver(args, model, opt, scheduler,
                                    sampler, loader, tag)
    return start_epoch, epoch_hook, round_hook

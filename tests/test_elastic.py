"""Topology-changing checkpoint restore (elastic resume).

The elasticity contract (runtime/checkpoint.py): a run checkpointed
on a ``CxM`` mesh restores onto a DIFFERENT ``C'xM'`` mesh with
bit-identical state — sketches are linear objects, so resharding is
pure placement migration — and the continued trajectory matches an
unresized oracle over the same seeded schedule (allclose; XLA
reduction order across placements injects ~1e-6 float noise, the
same bound tests/test_mesh2d.py pins).

Also covered here: asyncfed backlog survival across a resize, the
crafted multi-process clientstore shard merge, the sync-restore-of-
pending-async refusal, and the registry's flag on a ledger that spans
topologies.
"""

import json
import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from commefficient_tpu.config import Config  # noqa: E402
from commefficient_tpu.runtime.checkpoint import (  # noqa: E402
    load_checkpoint, save_checkpoint)
from commefficient_tpu.runtime.fed_model import (  # noqa: E402
    FedModel, FedOptimizer)

W, B, D, NC = 4, 2, 256, 8

SKETCH = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
              virtual_momentum=0.9, k=16, num_rows=3, num_cols=128)
TOPK = dict(mode="local_topk", error_type="local", local_momentum=0.9,
            virtual_momentum=0.0, k=16)
FEDAVG = dict(mode="fedavg", error_type="none", local_momentum=0.0,
              local_batch_size=-1)


def _loss(params, batch, cfg):
    pred = batch["x"] @ params["w"]
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
    return l, (l * 0.0 + 1.0,)


def _mk_cfg(mode_kw, mesh="", async_k=0, **kw):
    base = dict(num_workers=W, local_batch_size=B, seed=5,
                num_clients=NC, mesh=mesh, async_buffer_size=async_k)
    base.update(mode_kw)
    base.update(kw)
    return Config(**base)


def _build(cfg):
    model = FedModel(None, {"w": jnp.zeros((D,), jnp.float32)}, _loss,
                     cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    return model, opt


def _batch(r):
    rng = np.random.RandomState(1000 + r)
    return {"client_ids": rng.choice(NC, W, replace=False)
            .astype(np.int32),
            "x": jnp.asarray(rng.randn(W, B, D), jnp.float32),
            "y": jnp.asarray(rng.randn(W, B), jnp.float32),
            "mask": jnp.ones((W, B), jnp.float32)}


def _run(model, opt, r0, r1):
    for r in range(r0, r1):
        model(_batch(r))
        opt.step()


def _archive_arrays(path):
    with np.load(path, allow_pickle=False) as z:
        return {k: np.asarray(z[k]) for k in z.files if k != "meta"}, \
            json.loads(str(z["meta"]))


def _assert_archives_bit_equal(path_a, path_b):
    arrs_a, _ = _archive_arrays(path_a)
    arrs_b, _ = _archive_arrays(path_b)
    assert set(arrs_a) == set(arrs_b)
    for k in sorted(arrs_a):
        a, b = arrs_a[k], arrs_b[k]
        assert a.dtype == b.dtype, f"{k}: {a.dtype} != {b.dtype}"
        assert np.array_equal(a, b), f"{k} not bit-equal after resize"


# -- restored state is bit-exact across the mesh change -----------------


@pytest.mark.parametrize("mode_kw,mesh_a,mesh_b", [
    (SKETCH, "2x1", "1x2"),
    (TOPK, "2x1", "1x1"),
    (FEDAVG, "2x1", "1x1"),
], ids=["sketch", "local_topk", "fedavg"])
def test_resize_restores_state_bit_exact(tmp_path, mode_kw, mesh_a,
                                         mesh_b):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    ck_a = str(tmp_path / "a.npz")
    ck_b = str(tmp_path / "b.npz")
    model, opt = _build(_mk_cfg(mode_kw, mesh=mesh_a))
    _run(model, opt, 0, 3)
    save_checkpoint(ck_a, model, opt)
    model.finalize()

    model2, opt2 = _build(_mk_cfg(mode_kw, mesh=mesh_b))
    load_checkpoint(ck_a, model2, opt2)
    assert int(model2.round_index) == 3
    save_checkpoint(ck_b, model2, opt2)
    model2.finalize()

    _assert_archives_bit_equal(ck_a, ck_b)
    _, meta_b = _archive_arrays(ck_b)
    # the resized save extends the lineage: old topology + new one
    segs = meta_b.get("segments") or []
    assert len(segs) >= 2
    assert segs[-1]["mesh_shape"] != segs[0]["mesh_shape"] or \
        mesh_a == mesh_b


@pytest.mark.parametrize("mode_kw,mesh_a,mesh_b", [
    (SKETCH, "2x1", "1x2"),
    (TOPK, "2x1", "1x1"),
], ids=["sketch", "local_topk"])
def test_resized_trajectory_matches_unresized_oracle(tmp_path, mode_kw,
                                                     mesh_a, mesh_b):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    ck = str(tmp_path / "ck.npz")
    model, opt = _build(_mk_cfg(mode_kw, mesh=mesh_a))
    _run(model, opt, 0, 3)
    save_checkpoint(ck, model, opt)
    model.finalize()

    # oracle: same topology resume, same seeded schedule
    om, oo = _build(_mk_cfg(mode_kw, mesh=mesh_a))
    load_checkpoint(ck, om, oo)
    _run(om, oo, 3, 6)
    ps_oracle = np.asarray(jax.device_get(om.ps_weights))
    om.finalize()

    rm, ro = _build(_mk_cfg(mode_kw, mesh=mesh_b))
    load_checkpoint(ck, rm, ro)
    _run(rm, ro, 3, 6)
    ps_resized = np.asarray(jax.device_get(rm.ps_weights))
    rm.finalize()

    # cross-placement XLA reduction order injects ~1e-6 noise (same
    # bound as tests/test_mesh2d.py); state itself is bit-exact above
    np.testing.assert_allclose(ps_resized, ps_oracle, rtol=0,
                               atol=1e-4)


# -- asyncfed backlog survives the resize -------------------------------


def _lag(r, n):
    # pure function of (round, cohort size): the schedule replays
    # identically on both sides of the resume with no hidden RNG
    return (np.arange(n) + r) % 3


def test_async_backlog_survives_resize(tmp_path):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    ck_a = str(tmp_path / "a.npz")
    ck_b = str(tmp_path / "b.npz")
    model, opt = _build(_mk_cfg(SKETCH, mesh="2x1", async_k=2))
    model.attach_arrival_process(_lag)
    _run(model, opt, 0, 3)
    save_checkpoint(ck_a, model, opt)
    ps_mid = np.asarray(jax.device_get(model.ps_weights))
    model.finalize()

    _, meta = _archive_arrays(ck_a)
    assert int(meta["asyncfed"]["pending"]) > 0, \
        "drill needs in-flight arrivals at the save point"

    model2, opt2 = _build(_mk_cfg(SKETCH, mesh="1x2", async_k=2))
    model2.attach_arrival_process(_lag)
    load_checkpoint(ck_a, model2, opt2)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(model2.ps_weights)), ps_mid)
    save_checkpoint(ck_b, model2, opt2)
    _assert_archives_bit_equal(ck_a, ck_b)

    # the rebuilt heap folds the same backlog: continue and compare
    # against an unresized oracle resumed from the same checkpoint
    om, oo = _build(_mk_cfg(SKETCH, mesh="2x1", async_k=2))
    om.attach_arrival_process(_lag)
    load_checkpoint(ck_a, om, oo)
    _run(om, oo, 3, 6)
    ps_oracle = np.asarray(jax.device_get(om.ps_weights))
    om.finalize()

    _run(model2, opt2, 3, 6)
    ps_resized = np.asarray(jax.device_get(model2.ps_weights))
    model2.finalize()
    np.testing.assert_allclose(ps_resized, ps_oracle, rtol=0,
                               atol=1e-4)


def test_sync_restore_of_pending_async_refuses(tmp_path):
    ck = str(tmp_path / "ck.npz")
    model, opt = _build(_mk_cfg(SKETCH, async_k=2))
    model.attach_arrival_process(_lag)
    _run(model, opt, 0, 3)
    save_checkpoint(ck, model, opt)
    model.finalize()
    _, meta = _archive_arrays(ck)
    assert int(meta["asyncfed"]["pending"]) > 0

    model2, opt2 = _build(_mk_cfg(SKETCH, async_k=0))
    with pytest.raises(ValueError, match="async_buffer_size"):
        load_checkpoint(ck, model2, opt2)
    model2.finalize()


# -- multi-process clientstore shard migration --------------------------


def test_multiprocess_store_shards_merge_on_restore(tmp_path):
    """A 2-process host-store checkpoint (main archive + side shard)
    restores onto a single process: the ownership split of the OLD
    topology merges, then re-splits under the new one. The 2-process
    layout is crafted by rewriting a real archive — in-process jax
    can't run two processes."""
    ck = str(tmp_path / "ck.npz")
    cfg = _mk_cfg(TOPK, clientstore="host")
    model, opt = _build(cfg)
    _run(model, opt, 0, 3)
    save_checkpoint(ck, model, opt)
    model.finalize()

    with np.load(ck, allow_pickle=False) as z:
        arrays = {k: np.asarray(z[k]) for k in z.files if k != "meta"}
        meta = json.loads(str(z["meta"]))
    ids = arrays["store:ids"]
    assert len(ids) >= 2, "need written rows to split across shards"
    fields = [k[len("store:"):] for k in arrays
              if k.startswith("store:") and k != "store:ids"
              and not k.startswith("store:init:")]
    # split the sparse rows into two contiguous ownership halves
    cut = NC // 2
    lo, hi = ids < cut, ids >= cut
    assert lo.any() and hi.any()
    side = {"ids": ids[hi]}
    for f in fields:
        side[f] = arrays["store:" + f][hi]
        arrays["store:" + f] = arrays["store:" + f][lo]
    for k in list(arrays):
        if k.startswith("store:init:"):
            side[k[len("store:"):]] = arrays[k]
    arrays["store:ids"] = ids[lo]
    meta["clientstore"]["processes"] = 2
    np.savez_compressed(ck, meta=json.dumps(meta), **arrays)
    np.savez_compressed(f"{ck}.shard1.npz", **side)

    model2, opt2 = _build(_mk_cfg(TOPK, clientstore="host"))
    load_checkpoint(ck, model2, opt2)
    # every pre-craft row survives the merge bit-exactly: gather in
    # shard-concatenation order and compare against the split halves
    merged_ids = np.concatenate([ids[lo], ids[hi]])
    got, _ = model2.client_store.gather(merged_ids)
    for f in fields:
        want = np.concatenate([arrays["store:" + f], side[f]])
        np.testing.assert_array_equal(got[f], want)
    # and the next save re-splits under the NEW (single-process)
    # topology: one shard holding the full id set
    ck2 = str(tmp_path / "ck2.npz")
    save_checkpoint(ck2, model2, opt2)
    with np.load(ck2, allow_pickle=False) as z2:
        meta2 = json.loads(str(z2["meta"]))
        ids2 = np.asarray(z2["store:ids"])
    assert int(meta2["clientstore"]["processes"]) == 1
    np.testing.assert_array_equal(np.sort(ids2), np.sort(ids))
    assert not os.path.exists(f"{ck2}.shard1.npz")
    model2.finalize()


# -- a resumed run that crossed a topology boundary ---------------------


def test_run_topology_changed_semantics():
    from commefficient_tpu.telemetry import registry
    assert not registry.run_topology_changed({})
    one = {"topology_segments": [
        {"device_count": 8, "process_count": 1,
         "mesh_shape": {"clients": 8, "model": 1}}]}
    assert not registry.run_topology_changed(one)
    same = {"topology_segments": one["topology_segments"] * 2}
    assert not registry.run_topology_changed(same)
    changed = {"topology_segments": [
        {"device_count": 8, "process_count": 1,
         "mesh_shape": {"clients": 8, "model": 1}},
        {"device_count": 4, "process_count": 1,
         "mesh_shape": {"clients": 4, "model": 1}}]}
    assert registry.run_topology_changed(changed)

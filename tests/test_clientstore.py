"""Host-resident client-state store (commefficient_tpu/clientstore/).

The contract under test: ``--clientstore host`` is a pure *placement*
change — same per-client math, same RNG streams, same aggregation
order — so at populations where both placements fit, every round's
weights, metrics and per-client state rows must be bit-identical to
the dense in-HBM path; checkpoints taken through the store must resume
bit-exactly (and migrate across placements); the arena must evict to
the mmap spill tier under a tiny budget without losing a row; and the
prefetch thread must shut down cleanly with jobs still staged.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.clientstore import (HostClientStore,
                                           StorePrefetcher,
                                           resolve_clientstore,
                                           shard_range, state_fields)
from commefficient_tpu.config import Config

D = 6    # flat parameter dimension of the toy linear model
NC = 24  # simulated population
W = 8    # participants per round (== the 8 virtual devices)
B = 2    # examples per client


def _loss(params, batch, args):
    pred = batch["x"] @ params["w"]
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    loss = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
    return loss, (loss * 0.0 + 1.0,)


def _make_rounds(n_rounds, seed=11, dead_round=2, num_clients=NC):
    """Deterministic (ids, data) per round, with client repeats across
    rounds (state reuse) and one fully-masked slot in ``dead_round``
    (a dropped-out / loader-padding client whose state must stay
    untouched in BOTH placements)."""
    rng = np.random.RandomState(seed)
    rounds = []
    for r in range(n_rounds):
        ids = rng.choice(num_clients, W, replace=False).astype(np.int32)
        x = rng.randn(W, B, D).astype(np.float32)
        y = rng.randn(W, B).astype(np.float32)
        mask = np.ones((W, B), np.float32)
        if r == dead_round:
            mask[-1] = 0.0
        rounds.append((ids, {"x": x, "y": y, "mask": mask}))
    return rounds


def _cfg(clientstore, **kw):
    base = dict(mode="local_topk", error_type="local",
                local_momentum=0.9, virtual_momentum=0.0,
                weight_decay=0.0, k=3, num_workers=W,
                local_batch_size=B, num_clients=NC, seed=5,
                clientstore=clientstore)
    base.update(kw)
    return Config(**base)


def _build(cfg, lr=0.25):
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)
    params = {"w": jnp.zeros((D,), jnp.float32)}
    model = FedModel(None, params, _loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": lr}], cfg, model=model)
    return model, opt


def _drive(model, opt, rounds, feed_ids=None):
    """Run ``rounds`` through model + opt; returns (weights trajectory,
    per-round metric arrays). ``feed_ids``: global round->ids list for
    the prefetch lookahead (indexed by model.round_index, so it works
    across a resume)."""
    if feed_ids is not None and model.client_store is not None:
        def peek():
            nxt = model.round_index + 1
            return feed_ids[nxt] if nxt < len(feed_ids) else None
        model.attach_participant_feed(peek)
    traj, metrics = [], []
    for ids, data in rounds:
        batch = {"client_ids": ids,
                 **{k: jnp.asarray(v) for k, v in data.items()}}
        out = model(batch)
        metrics.append([np.asarray(m) for m in out])
        opt.step()
        traj.append(np.asarray(model.ps_weights, np.float64))
    return traj, metrics


def _device_state_rows(model):
    cs = model.client_states
    out = {}
    for name, val in (("velocities", cs.velocities),
                      ("errors", cs.errors), ("weights", cs.weights)):
        if val is not None:
            out[name] = np.asarray(val)[:model.num_clients]
    return out


def _store_state_rows(model):
    rows, _ = model.client_store.gather(
        np.arange(model.num_clients, dtype=np.int64))
    return {k: np.asarray(v) for k, v in rows.items()}


def _assert_rows_equal(a, b):
    assert set(a) == set(b), (set(a), set(b))
    for name in a:
        np.testing.assert_array_equal(a[name], b[name], err_msg=name)


# ----------------------------------------------------------------------
# bit-equality: host placement vs the dense device placement


@pytest.mark.parametrize("mode_kw", [
    # stateful: per-client momentum + error rows through the store
    dict(),
    # stateless fedavg: empty store, but the full gather/round/
    # write-back loop (and accounting) must still match
    dict(mode="fedavg", error_type="none", local_momentum=0.0,
         local_batch_size=-1),
], ids=["local_topk", "fedavg"])
def test_host_bit_identical_to_device(mode_kw):
    rounds = _make_rounds(4)
    feed = [ids for ids, _ in rounds]

    md, od = _build(_cfg("device", **mode_kw))
    traj_d, met_d = _drive(md, od, rounds)

    mh, oh = _build(_cfg("host", clientstore_bytes=1 << 20, **mode_kw))
    assert mh.clientstore == "host" and mh.client_store is not None
    traj_h, met_h = _drive(mh, oh, rounds, feed_ids=feed)

    for r, (a, b) in enumerate(zip(traj_d, traj_h)):
        np.testing.assert_array_equal(a, b, err_msg=f"round {r}")
    for r, (ma, mb) in enumerate(zip(met_d, met_h)):
        assert len(ma) == len(mb)
        for x, y in zip(ma, mb):
            np.testing.assert_array_equal(x, y, err_msg=f"round {r}")

    # per-client state rows agree for the WHOLE population (incl. the
    # dead slot's untouched row and never-sampled clients)
    _assert_rows_equal(_device_state_rows(md), _store_state_rows(mh))
    if mh._prefetcher is not None:
        # the lookahead actually predicted rounds 1..3
        assert mh._prefetcher.hits >= len(rounds) - 1
    mh.finalize()
    assert mh.client_store is None and mh._prefetcher is None


# ----------------------------------------------------------------------
# checkpoint/resume through the store


def test_checkpoint_resume_bit_exact(tmp_path):
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)
    rounds = _make_rounds(6, seed=13)
    feed = [ids for ids, _ in rounds]
    cfg = _cfg("host", clientstore_bytes=1 << 20)

    # uninterrupted reference
    m0, o0 = _build(cfg)
    traj0, _ = _drive(m0, o0, rounds, feed_ids=feed)
    rows0 = _store_state_rows(m0)
    m0.finalize()

    # interrupted at round 3, "killed", resumed in a fresh process
    m1, o1 = _build(cfg)
    _drive(m1, o1, rounds[:3], feed_ids=feed)
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, m1, o1, epoch=1)
    m1.finalize()

    with np.load(path) as z:
        # sparse store snapshot, not dense cs_* arrays
        assert "store:ids" in z.files
        assert "store:velocities" in z.files
        assert not any(k.startswith("cs_") for k in z.files)

    m2, o2 = _build(cfg)
    load_checkpoint(path, m2, o2)
    assert m2.round_index == 3
    traj2, _ = _drive(m2, o2, rounds[3:], feed_ids=feed)
    np.testing.assert_array_equal(traj0[-1], traj2[-1])
    _assert_rows_equal(rows0, _store_state_rows(m2))
    m2.finalize()


def test_checkpoint_migrates_between_placements(tmp_path):
    """A checkpoint written through the store loads into a device-
    placement run (densified over the init rows) and vice versa, and
    continued training is bit-identical either way."""
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)
    rounds = _make_rounds(4, seed=17)

    # host -> {host, device}
    mh, oh = _build(_cfg("host"))
    _drive(mh, oh, rounds[:2])
    p1 = str(tmp_path / "host.npz")
    save_checkpoint(p1, mh, oh, epoch=1)
    rows_h = _store_state_rows(mh)
    mh.finalize()

    md, od = _build(_cfg("device"))
    load_checkpoint(p1, md, od)
    _assert_rows_equal(rows_h, _device_state_rows(md))
    mh2, oh2 = _build(_cfg("host"))
    load_checkpoint(p1, mh2, oh2)
    td, _ = _drive(md, od, rounds[2:])
    th, _ = _drive(mh2, oh2, rounds[2:])
    np.testing.assert_array_equal(td[-1], th[-1])
    mh2.finalize()

    # device -> host
    md3, od3 = _build(_cfg("device"))
    _drive(md3, od3, rounds[:2])
    p2 = str(tmp_path / "dev.npz")
    save_checkpoint(p2, md3, od3, epoch=1)
    mh3, oh3 = _build(_cfg("host"))
    load_checkpoint(p2, mh3, oh3)
    _assert_rows_equal(_device_state_rows(md3), _store_state_rows(mh3))
    td3, _ = _drive(md3, od3, rounds[2:])
    th3, _ = _drive(mh3, oh3, rounds[2:])
    np.testing.assert_array_equal(td3[-1], th3[-1])
    mh3.finalize()


# ----------------------------------------------------------------------
# the store itself: budget, eviction, spill tier


def test_eviction_to_spill_tier(tmp_path):
    fields = {"v": ((4,), None)}
    row_bytes = 4 * 4
    spill_dir = str(tmp_path / "spill")
    st = HostClientStore(20, fields, budget_bytes=3 * row_bytes,
                         spill_dir=spill_dir)
    assert st.arena_rows == 3
    for cid in range(10):
        st.write([cid], {"v": np.full((1, 4), cid + 1.0, np.float32)})
    assert st.stats["resident_rows"] == 3
    assert st.stats["spill_rows"] == 7
    assert st.stats["evictions"] == 7
    assert st.stats["resident_rows_max"] == 3

    # every row reads back exactly, whichever tier holds it; unwritten
    # clients read the (zero) default
    rows, _ = st.gather(np.arange(20))
    for cid in range(10):
        np.testing.assert_array_equal(rows["v"][cid],
                                      np.full(4, cid + 1.0))
    np.testing.assert_array_equal(rows["v"][10:], 0.0)
    np.testing.assert_array_equal(st.written_ids(), np.arange(10))

    # rewriting a spilled row promotes it back to the arena
    st.write([0], {"v": np.full((1, 4), 99.0, np.float32)})
    rows, _ = st.gather([0])
    np.testing.assert_array_equal(rows["v"][0], np.full(4, 99.0))

    paths = [os.path.join(spill_dir, f) for f in os.listdir(spill_dir)]
    assert paths
    st.close()
    assert all(not os.path.exists(p) for p in paths)
    with pytest.raises(RuntimeError):
        st.gather([0])


def test_zero_budget_spills_everything():
    st = HostClientStore(5, {"v": ((2,), None)}, budget_bytes=0)
    st.write([3], {"v": np.array([[7.0, 8.0]], np.float32)})
    rows, _ = st.gather([3, 4])
    np.testing.assert_array_equal(rows["v"][0], [7.0, 8.0])
    np.testing.assert_array_equal(rows["v"][1], 0.0)
    assert st.stats["resident_rows"] == 0
    assert st.stats["spill_rows"] == 1
    st.close()


def test_init_row_and_ownership():
    init = np.arange(3, dtype=np.float32)
    st = HostClientStore(10, {"w": ((3,), init)}, budget_bytes=1 << 12,
                         owned=(2, 6))
    # unwritten owned clients read the init row; non-owned read zeros
    # (the multi-host allgather-sum counts each row exactly once)
    rows, _ = st.gather([2, 0])
    np.testing.assert_array_equal(rows["w"][0], init)
    np.testing.assert_array_equal(rows["w"][1], 0.0)
    # writes outside the owned shard are dropped
    st.write([0, 3], {"w": np.full((2, 3), 5.0, np.float32)})
    np.testing.assert_array_equal(st.written_ids(), [3])
    rows, _ = st.gather([0, 3])
    np.testing.assert_array_equal(rows["w"][0], 0.0)
    np.testing.assert_array_equal(rows["w"][1], np.full(3, 5.0))
    st.close()


# ----------------------------------------------------------------------
# prefetch thread


def _wait(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_prefetcher_hit_miss_staleness_and_shutdown():
    st = HostClientStore(10, {"v": ((4,), None)}, budget_bytes=1 << 16)
    before = set(threading.enumerate())
    pf = StorePrefetcher(st)

    ids = np.array([1, 2, 3], np.int64)
    st.write(ids, {"v": np.eye(3, 4, dtype=np.float32)})

    # hit
    pf.submit(ids)
    rows = pf.take(ids)
    assert rows is not None and pf.hits == 1
    np.testing.assert_array_equal(rows["v"], np.eye(3, 4))

    # staleness: a row written AFTER the async gather snapshot must be
    # patched in by take()
    pf.submit(ids)
    assert _wait(lambda: pf._done.qsize() > 0)
    st.write([2], {"v": np.full((1, 4), 42.0, np.float32)})
    rows = pf.take(ids)
    np.testing.assert_array_equal(rows["v"][1], np.full(4, 42.0))

    # misprediction: staged ids don't match the round's -> None, and
    # the caller falls back to a synchronous gather
    pf.submit(np.array([7, 8], np.int64))
    assert pf.take(np.array([0, 1], np.int64)) is None
    assert pf.misses == 1

    # shutdown with a job still staged; idempotent; no leaked threads
    pf.submit(ids)
    pf.close()
    pf.close()
    assert not pf._thread.is_alive()
    assert set(threading.enumerate()) - before == set()

    # a worker exception surfaces in take(), not in the worker
    st2 = HostClientStore(4, {"v": ((2,), None)}, budget_bytes=1 << 12)
    pf2 = StorePrefetcher(st2)
    st2.close()
    pf2.submit(np.array([0], np.int64))
    with pytest.raises(RuntimeError):
        pf2.take(np.array([0], np.int64))
    pf2.close()
    st.close()


def test_prefetcher_out_of_order_consumption():
    """Buffered-async overlap (asyncfed) consumes staged gathers out
    of issue order: a ``take`` for the SECOND submit must drain the
    first staged job as a miss — no deadlock, no torn rows — and a
    row written after the async snapshot must still come back patched
    through the version check, never a silently-stale mix."""
    st = HostClientStore(12, {"v": ((4,), None)},
                         budget_bytes=1 << 16)
    pf = StorePrefetcher(st)
    ids1 = np.array([1, 2, 3], np.int64)
    ids2 = np.array([4, 5, 6], np.int64)
    st.write(ids1, {"v": np.ones((3, 4), np.float32)})
    st.write(ids2, {"v": np.full((3, 4), 2.0, np.float32)})
    pf.submit(ids1)
    pf.submit(ids2)
    assert _wait(lambda: pf._done.qsize() == 2)
    # a write landing between the snapshot and the take: version
    # patching must hand back the CURRENT row, not the staged one
    st.write([5], {"v": np.full((1, 4), 42.0, np.float32)})
    rows = pf.take(ids2)
    assert rows is not None
    assert pf.misses == 1 and pf.hits == 1
    np.testing.assert_array_equal(rows["v"][0], np.full(4, 2.0))
    np.testing.assert_array_equal(rows["v"][1], np.full(4, 42.0))
    # the backlog is drained: a further take must return fast with
    # None (synchronous-gather fallback), not wedge on the queue
    t0 = time.time()
    assert pf.take(ids1, timeout=0.5) is None
    assert time.time() - t0 < 5.0
    pf.close()
    st.close()


def test_prefetcher_worker_death_surfaces_out_of_order():
    """The chaos-harness kill hook marks the loop dead exactly like
    an escaped exception: the NEXT take()/submit — even one for a
    job staged before the death — raises the worker-died RuntimeError
    instead of stalling out its timeout."""
    st = HostClientStore(4, {"v": ((2,), None)}, budget_bytes=1 << 12)
    pf = StorePrefetcher(st)
    pf.submit(np.array([0], np.int64))
    assert pf.take(np.array([0], np.int64)) is not None
    pf._fail_for_test(ValueError("chaos"))
    with pytest.raises(RuntimeError, match="prefetch worker died"):
        pf.take(np.array([0], np.int64))
    with pytest.raises(RuntimeError, match="prefetch worker died"):
        pf.submit(np.array([1], np.int64))
    pf.close()
    st.close()


def test_store_issue_round_stamps():
    """asyncfed version stamps: bookkeeping-only per-client issue
    rounds, -1 for never-issued, last issue wins on re-issue."""
    st = HostClientStore(8, {"v": ((2,), None)}, budget_bytes=1 << 12)
    assert st.stamped_round(3) == -1
    st.stamp_rounds(np.array([1, 3], np.int64), 5)
    st.stamp_rounds(np.array([[3]], np.int64), 7)  # any shape of ids
    assert st.stamped_round(1) == 5
    assert st.stamped_round(3) == 7
    assert st.stamped_round(0) == -1
    st.close()


# ----------------------------------------------------------------------
# config plumbing


def test_resolve_clientstore_auto():
    cfg = _cfg("auto", clientstore_bytes=1 << 10).replace(grad_size=100)
    # local_topk + local error + momentum: 2 rows of grad_size f32
    # per client = 800 B; 24 clients = 19200 B > 1 KiB budget -> host
    assert resolve_clientstore(cfg, cfg.num_clients) == "host"
    assert resolve_clientstore(
        cfg.replace(clientstore_bytes=1 << 20), cfg.num_clients) \
        == "device"
    # stateless combo: nothing to store, stays on device at any budget
    fa = _cfg("auto", mode="fedavg", error_type="none",
              local_momentum=0.0, local_batch_size=-1,
              clientstore_bytes=0).replace(grad_size=100)
    assert resolve_clientstore(fa, fa.num_clients) == "device"
    # explicit flags resolve to themselves
    assert resolve_clientstore(_cfg("device"), NC) == "device"
    assert resolve_clientstore(_cfg("host"), NC) == "host"


def test_state_fields_follow_config():
    cfg = _cfg("host").replace(grad_size=7)
    f = state_fields(cfg)
    assert list(f) == ["velocities", "errors"]
    assert f["velocities"][0] == (7,)
    init = np.arange(7, dtype=np.float32)
    f2 = state_fields(cfg.replace(do_topk_down=True), init_weights=init)
    assert list(f2) == ["velocities", "errors", "weights"]
    np.testing.assert_array_equal(f2["weights"][1], init)
    fa = _cfg("host", mode="fedavg", error_type="none",
              local_momentum=0.0,
              local_batch_size=-1).replace(grad_size=7)
    assert state_fields(fa) == {}


def test_shard_range_partitions_population():
    assert shard_range(10, 0, 2) == (0, 5)
    assert shard_range(10, 1, 2) == (5, 10)
    assert shard_range(10, 2, 3) == (8, 10)
    assert shard_range(3, 3, 4) == (3, 3)  # empty trailing shard
    for nc, pc in ((10, 2), (10, 3), (3, 4), (1_000_000, 7)):
        spans = [shard_range(nc, i, pc) for i in range(pc)]
        assert spans[0][0] == 0 and spans[-1][1] == nc
        for (a, b), (c, d) in zip(spans, spans[1:]):
            assert b == c and a <= b and c <= d


# ----------------------------------------------------------------------
# scale: populations far beyond any dense-HBM placement


@pytest.mark.slow
@pytest.mark.parametrize("mode_kw", [
    dict(),
    dict(mode="fedavg", error_type="none", local_momentum=0.0,
         local_batch_size=-1),
], ids=["local_topk", "fedavg"])
def test_million_client_population(mode_kw):
    """1M simulated clients under a ~1000-row store budget: training
    proceeds, resident rows respect the budget, and state survives
    eviction round-trips (the dense device placement would need the
    full (1M, d) arrays resident)."""
    nc = 1_000_000
    budget = 1000 * 2 * D * 4  # ~1000 (velocities+errors) rows
    rounds = _make_rounds(3, seed=23, dead_round=-1, num_clients=nc)
    cfg = _cfg("host", num_clients=nc, clientstore_bytes=budget,
               **mode_kw)
    m, o = _build(cfg)
    traj, _ = _drive(m, o, rounds, feed_ids=[i for i, _ in rounds])
    assert np.all(np.isfinite(traj[-1]))
    st = m.client_store
    participants = {int(c) for ids, _ in rounds for c in ids}
    if st.fields:
        assert st.stats["resident_rows_max"] <= st.arena_rows
        assert set(st.written_ids()) <= participants
    m.finalize()

"""Buffered asynchronous federated rounds (commefficient_tpu/asyncfed).

Four layers of guarantee:

- the seeded ``ArrivalSchedule`` replays bit-identically (golden
  trace) and its ``replay_stats`` summary matches the bench's
  historical inline computation;
- the arrival queue / round driver bookkeeping is exact: arrival
  order, dead-slot padding, staleness accounting, and the
  prefetch-lookahead peek that must be either exactly right or None;
- the DEGENERATE configuration — buffer == cohort, staleness weight
  0, punctual arrivals — is BIT-IDENTICAL to the synchronous round at
  the FedModel level across modes (the async driver adds bookkeeping,
  never math);
- the staleness-weighted fold algebra matches the NumPy mirror to
  1e-6, composed with ``--robust_agg``, a 2-D ``--mesh`` and
  ``--sketch_dtype int8``, under churny and bursty traces.

Plus the observatory surface: the ``async_staleness`` alarm rule, the
``a<K>`` topology fragment and the registry run_key fragment.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from commefficient_tpu.asyncfed import ArrivalQueue, AsyncRoundDriver
from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates, args2sketch,
                                           build_client_round)
from commefficient_tpu.data.chaos import ArrivalSchedule
from reference_mirror import (np_qdq_table, np_robust_fold,
                              np_staleness_weights)


def linear_loss(params_flat, batch):
    pred = batch["x"] @ params_flat
    sq = (pred - batch["y"]) ** 2
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    loss = jnp.sum(sq * batch["mask"]) / n
    return loss, (loss * 0.0 + 1.0,)


def make_cfg(**kw):
    base = dict(mode="uncompressed", local_momentum=0.0,
                virtual_momentum=0.0, weight_decay=0.0,
                error_type="none", num_workers=4, k=3,
                num_rows=3, num_cols=64, num_blocks=1,
                local_batch_size=2, microbatch_size=-1, seed=21)
    base.update(kw)
    return Config(**base)


# -- ArrivalSchedule ----------------------------------------------------


def test_arrival_schedule_golden_trace():
    """The seeded schedules are pinned: any change to the draw order
    silently invalidates every replayed experiment."""
    ch = ArrivalSchedule("churny", seed=7, max_delay=3, churn_frac=0.5)
    got = [ch.delays(6).tolist() for _ in range(4)]
    assert got == [[1, 0, 3, 0, 0, 0], [1, 0, 0, 1, 1, 2],
                   [0, 1, 0, 1, 0, 0], [1, 0, 0, 1, 2, 2]], got
    bu = ArrivalSchedule("bursty", seed=7, max_delay=4,
                         burst_start_prob=0.5, burst_stop_prob=0.3,
                         drop_frac=0.5)
    got = [bu.delays(6).tolist() for _ in range(4)]
    assert got == [[4, 0, 4, 0, 0, 4], [4, 0, 4, 0, 0, 4],
                   [4, 0, 4, 0, 0, 4], [0, 0, 0, 0, 0, 0]], got


@pytest.mark.parametrize("kind", ArrivalSchedule.KINDS)
def test_arrival_schedule_replays(kind):
    a = ArrivalSchedule(kind, seed=3)
    b = ArrivalSchedule(kind, seed=3)
    t1 = [a.delays(8).tolist() for _ in range(6)]
    assert [b.delays(8).tolist() for _ in range(6)] == t1
    a.reset()
    assert [a.delays(8).tolist() for _ in range(6)] == t1
    assert (ArrivalSchedule("uniform", seed=0).delays(5) == 0).all()


def test_replay_stats_matches_inline_summary():
    """replay_stats on a hand-worked trace: two bursts of two rounds,
    16 client-rounds dropped; an empty trace reads all alive."""
    alive = [1.0, 0.5, 0.25, 1.0, 1.0, 0.75, 0.5, 1.0]
    st = ArrivalSchedule.replay_stats(alive, 8)
    assert st == {"burst_count": 2, "burst_rounds": 4,
                  "longest_burst": 2, "alive_frac_min": 0.25,
                  "alive_frac_mean": 0.75,
                  "dropped_client_rounds": 16}
    empty = ArrivalSchedule.replay_stats([], 8)
    assert empty["alive_frac_min"] == 1.0
    assert empty["dropped_client_rounds"] == 0


# -- queue / driver units ----------------------------------------------


def test_arrival_queue_order_and_peek():
    q = ArrivalQueue()
    q.push(2, "late")
    q.push(0, "a")
    q.push(0, "b")
    q.push(1, "mid")
    assert q.peek_arrived(0) == ["a", "b"]  # peek never consumes
    assert len(q) == 4
    assert q.pop_arrived(0, limit=8) == ["a", "b"]
    assert q.pop_arrived(0, limit=8) == []  # "mid" still in flight
    assert q.pop_arrived(2, limit=1) == ["mid"]  # limit respected
    assert q.pop_arrived(2, limit=8) == ["late"]
    assert len(q) == 0


def _host_batch(rng, W, B, d, lo=0, hi=100):
    return {"client_ids": rng.choice(np.arange(lo + 1, hi), W,
                                     replace=False).astype(np.int32),
            "x": rng.randn(W, B, d).astype(np.float32),
            "y": rng.randn(W, B).astype(np.float32),
            "mask": np.ones((W, B), np.float32)}


def test_driver_punctual_identity_and_stats():
    cfg = make_cfg(num_workers=4, async_buffer_size=4)
    drv = AsyncRoundDriver(cfg)
    rng = np.random.RandomState(0)
    b = _host_batch(rng, 4, 2, 3)
    fb, stale = drv.step(b)
    for k in b:
        np.testing.assert_array_equal(fb[k], b[k])
    assert (stale == 0).all() and stale.shape == (4,)
    st = drv.round_stats()
    assert st["async_buffer_occupancy"] == 1.0
    assert st["async_backlog"] == 0.0
    assert st["async_staleness_hist"] == [4]


def test_driver_pads_dead_slots_and_tracks_staleness():
    cfg = make_cfg(num_workers=4, async_buffer_size=4)
    drv = AsyncRoundDriver(cfg)
    # slots 1 and 3 of the first cohort are 2 steps late
    delays = iter([np.array([0, 2, 0, 2])] + [np.zeros(4, np.int64)] * 2)
    drv.attach_arrival_process(lambda r, n: next(delays))
    rng = np.random.RandomState(1)
    b0 = _host_batch(rng, 4, 2, 3)
    fb0, s0 = drv.step(b0)
    # fold 0: only the two punctual slots arrived, rest dead-padded
    np.testing.assert_array_equal(
        fb0["client_ids"][:2], b0["client_ids"][[0, 2]])
    assert (fb0["client_ids"][2:] == 0).all()
    assert (fb0["mask"][2:] == 0).all() and (fb0["mask"][:2] == 1).all()
    assert (s0 == 0).all()
    st = drv.round_stats()
    assert st["async_buffer_occupancy"] == 0.5
    assert st["async_backlog"] == 2.0
    # fold 1: the punctual second cohort fills the buffer first (it
    # arrived at step 1; the stragglers arrive at step 2)
    b1 = _host_batch(rng, 4, 2, 3)
    fb1, s1 = drv.step(b1)
    np.testing.assert_array_equal(fb1["client_ids"], b1["client_ids"])
    assert (s1 == 0).all()
    # fold 2: the stragglers drain with staleness 2
    b2 = _host_batch(rng, 4, 2, 3)
    fb2, s2 = drv.step(b2)
    np.testing.assert_array_equal(
        fb2["client_ids"][:2], b0["client_ids"][[1, 3]])
    assert s2[:2].tolist() == [2.0, 2.0]
    assert drv.round_stats()["async_staleness_max"] == 2.0


def test_driver_peek_next_ids_exact_or_none():
    cfg = make_cfg(num_workers=4, async_buffer_size=2)
    drv = AsyncRoundDriver(cfg)
    rng = np.random.RandomState(2)
    # punctual K=2 < W: after one step the backlog holds 2 arrived
    # entries — the peek must predict the next fold's gather exactly
    b0 = _host_batch(rng, 4, 2, 3)
    drv.step(b0)
    peek = drv.peek_next_ids()
    assert peek is not None
    b1 = _host_batch(rng, 4, 2, 3)
    fb1, _ = drv.step(b1)
    np.testing.assert_array_equal(peek, fb1["client_ids"])
    # drain the backlog below K: the peek must refuse to guess
    drv.step(_host_batch(rng, 4, 2, 3))
    drv.step(_host_batch(rng, 4, 2, 3))
    while len(drv.queue) >= drv.k:
        drv.queue.pop_arrived(drv._fold, 1)
    assert drv.peek_next_ids() is None


def test_driver_stamps_issue_rounds():
    seen = []
    cfg = make_cfg(num_workers=4, async_buffer_size=4)
    drv = AsyncRoundDriver(cfg, stamp=lambda ids, r: seen.append(
        (np.asarray(ids).tolist(), r)))
    rng = np.random.RandomState(3)
    b = _host_batch(rng, 4, 2, 3)
    drv.step(b)
    drv.step(_host_batch(rng, 4, 2, 3))
    assert seen[0] == (b["client_ids"].tolist(), 0)
    assert seen[1][1] == 1


# -- degenerate-sync bit parity at the FedModel level -------------------


def _run_fed(cfg_kw, n_rounds=5, async_k=0, alpha=0.0, sched=None,
             d=64, num_clients=32):
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)
    W, B = 4, 2

    def loss(params, batch, cfg):
        pred = batch["x"] @ params["w"]
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    base = dict(num_workers=W, local_batch_size=B, seed=5,
                num_clients=num_clients, async_buffer_size=async_k,
                async_staleness_weight=alpha)
    base.update(cfg_kw)
    cfg = Config(**base)
    model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)}, loss,
                     cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    if sched is not None:
        model.attach_arrival_process(sched)
    rng = np.random.RandomState(5)
    for _ in range(n_rounds):
        batch = {"client_ids": rng.choice(num_clients, W,
                                          replace=False)
                 .astype(np.int32),
                 "x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
                 "y": jnp.asarray(rng.randn(W, B), jnp.float32),
                 "mask": jnp.ones((W, B), jnp.float32)}
        model(batch)
        opt.step()
    ps = np.asarray(model.ps_weights)
    model.finalize()
    return ps


@pytest.mark.parametrize("mode_kw", [
    dict(mode="sketch", error_type="virtual", local_momentum=0.0,
         virtual_momentum=0.9, k=16, num_rows=3, num_cols=128),
    dict(mode="local_topk", error_type="local", local_momentum=0.9,
         virtual_momentum=0.0, k=16),
    dict(mode="fedavg", error_type="none", local_momentum=0.0,
         local_batch_size=-1),
], ids=["sketch", "local_topk", "fedavg"])
def test_degenerate_buffered_round_is_bit_exact(mode_kw):
    """K == cohort, alpha == 0, punctual arrivals: the buffered round
    must be BIT-IDENTICAL to the synchronous barrier round — the
    subsystem's core invariant (weighting is skipped at trace time,
    the queue pops the issued batch slot for slot)."""
    sync = _run_fed(mode_kw)
    deg = _run_fed(mode_kw, async_k=4, alpha=0.0)
    assert np.array_equal(sync, deg)


def test_churny_buffered_round_diverges_then_stays_finite():
    """Sanity on the non-degenerate path: a churny trace with
    staleness weighting produces a DIFFERENT (but finite) model —
    the async machinery is actually engaged."""
    kw = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
              virtual_momentum=0.9, k=16, num_rows=3, num_cols=128)
    sync = _run_fed(kw)
    churn = _run_fed(kw, async_k=2, alpha=0.5,
                     sched=ArrivalSchedule("churny", seed=9))
    assert np.isfinite(churn).all()
    assert not np.array_equal(sync, churn)


# -- staleness-weighted fold algebra vs the NumPy mirror ----------------


def _pad_round(clients, B, d):
    W = len(clients)
    x = np.zeros((W, B, d), np.float32)
    y = np.zeros((W, B), np.float32)
    mask = np.zeros((W, B), np.float32)
    ids = np.zeros((W,), np.int32)
    for i, (cid, X, Y) in enumerate(clients):
        n = len(Y)
        x[i, :n], y[i, :n], mask[i, :n], ids[i] = X, Y, 1.0, cid
    return ({"x": jnp.asarray(x), "y": jnp.asarray(y),
             "mask": jnp.asarray(mask)},
            jnp.asarray(ids, jnp.int32))


def _staleness_from(kind, W, seed=11):
    sched = ArrivalSchedule(kind, seed=seed, max_delay=4)
    return sched.delays(W).astype(np.float32)


@pytest.mark.parametrize("robust", ["none", "median", "trimmed",
                                    "clip"])
@pytest.mark.parametrize("kind", ["churny", "bursty"])
def test_weighted_fold_matches_mirror(robust, kind):
    """Engine staleness-weighted fold == NumPy mirror to 1e-6: the
    weighted (robust) fold of t_i with weights w_i equals the plain
    (robust) fold of w_i*t_i with w_i*n_i datapoints, including a
    dead pad slot (weight never resurrects it)."""
    d, B, W, alpha = 8, 3, 4, 0.7
    cfg = make_cfg(num_workers=W, grad_size=d, robust_agg=robust,
                   async_buffer_size=W, async_staleness_weight=alpha)
    if kind == "bursty":
        cfg.robust_trim_frac = 0.2
    rng = np.random.default_rng(4)
    w0 = rng.normal(size=d).astype(np.float32)
    clients = [(cid, rng.normal(size=(n, d)).astype(np.float32),
                rng.normal(size=(n,)).astype(np.float32))
               for cid, n in [(1, 3), (2, 2), (3, 3)]]
    padded = clients + [(0, np.zeros((0, d), np.float32),
                         np.zeros((0,), np.float32))]
    batch, ids = _pad_round(padded, B, d)
    stale = _staleness_from(kind, W)
    stale[-1] = 0.0  # pad slots carry staleness 0 by construction

    cr = jax.jit(build_client_round(cfg, linear_loss, B,
                                    client_weights=True))
    ps = jnp.asarray(w0)
    res = cr(ps, ClientStates.init(cfg, W, ps), batch, ids,
             jax.random.PRNGKey(0), jnp.float32(1.0),
             jnp.asarray(stale))

    # mirror: per-client transmit = (masked-mean grad) * n, then the
    # pre-scaled stack through the unweighted mirror fold
    wts = np_staleness_weights(stale, alpha).astype(np.float64)
    transmits, counts = [], []
    for i, (cid, X, Y) in enumerate(padded):
        n = len(Y)
        if n:
            r = X.astype(np.float64) @ w0.astype(np.float64) \
                - Y.astype(np.float64)
            g = X.astype(np.float64).T @ (2.0 * r / n)
        else:
            g = np.zeros(d)
        transmits.append(wts[i] * g * n)
        counts.append(wts[i] * n)
    if robust == "none":
        expect = (np.sum(transmits, axis=0)
                  / max(float(np.sum(counts)), 1.0))
    else:
        expect, _ = np_robust_fold(cfg, transmits, counts)
    np.testing.assert_allclose(np.asarray(res.aggregated), expect,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["churny", "bursty"])
def test_weighted_sketch_int8_fold_matches_mirror(kind):
    """Weighted fold composed with the quantized sketch wire: the
    fused round's aggregate == qdq(sketch(Σ w_i·n_i·g_i / Σ w_i·n_i))
    through the shared CountSketch op + the NumPy quantizer mirror.
    The weighted-mean algebra itself is checked to 1e-6 pre-sketch."""
    d, B, W, alpha = 256, 2, 4, 0.5
    cfg = make_cfg(mode="sketch", error_type="virtual",
                   virtual_momentum=0.9, num_workers=W, grad_size=d,
                   num_rows=3, num_cols=64, sketch_dtype="int8",
                   async_buffer_size=W, async_staleness_weight=alpha)
    rng = np.random.default_rng(6)
    c = rng.normal(size=(W, 1, d)).astype(np.float32)

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    mask = np.ones((W, B), np.float32)
    mask[-1] = 0.0  # a dead pad slot rides along
    batch = {"c": jnp.asarray(np.broadcast_to(c, (W, B, d))),
             "mask": jnp.asarray(mask)}
    stale = _staleness_from(kind, W, seed=13)
    stale[-1] = 0.0
    cr = jax.jit(build_client_round(cfg, lin_loss, B,
                                    client_weights=True))
    flat = jnp.zeros((d,), jnp.float32)
    res = cr(flat, ClientStates.init(cfg, W, flat), batch,
             jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
             jnp.float32(1.0), jnp.asarray(stale))

    wts = np_staleness_weights(stale, alpha).astype(np.float64)
    n_per = mask.sum(axis=1).astype(np.float64)
    total = max(float((wts * n_per).sum()), 1.0)
    dense = np.einsum("w,wd->d", wts * n_per,
                      c[:, 0, :].astype(np.float64)) / total
    table = np.asarray(jax.jit(args2sketch(cfg).sketch)(
        jnp.asarray(dense, jnp.float32)), np.float64)
    expect = np_qdq_table(table.astype(np.float32), "int8")
    np.testing.assert_allclose(np.asarray(res.aggregated), expect,
                               rtol=1e-4, atol=1e-5)


def test_weighted_fold_on_2d_mesh_matches_1d():
    """The weighted fused sketch fold on a 2x2 clients x model mesh
    == the single-device weighted fold (and the f32 variant matches
    the dense mirror to 1e-5): staleness weighting composes with the
    partial-sketch reduce-scatter emission."""
    from commefficient_tpu.parallel.mesh import make_mesh2d

    d, B, W, alpha = 512, 2, 4, 0.5
    cfg = make_cfg(mode="sketch", error_type="virtual",
                   virtual_momentum=0.9, num_workers=W, grad_size=d,
                   num_rows=3, num_cols=64, mesh="2x2",
                   async_buffer_size=W, async_staleness_weight=alpha)
    rng = np.random.default_rng(8)
    c = rng.normal(size=(W, 1, d)).astype(np.float32)

    def lin_loss(p, b):
        n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
        loss = jnp.sum((b["c"] @ p) * b["mask"]) / n
        return loss, (loss * 0.0,)

    batch = {"c": jnp.asarray(np.broadcast_to(c, (W, B, d))),
             "mask": jnp.ones((W, B), jnp.float32)}
    stale = _staleness_from("churny", W, seed=17)
    flat = jnp.zeros((d,), jnp.float32)

    def run(mesh):
        cr = jax.jit(build_client_round(cfg, lin_loss, B, mesh=mesh,
                                        client_weights=True))
        res = cr(flat, ClientStates.init(cfg, W, flat), batch,
                 jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
                 jnp.float32(1.0), jnp.asarray(stale))
        return np.asarray(jax.device_get(res.aggregated))

    agg2d = run(make_mesh2d(2, 2)).reshape(3, -1)
    agg1d = run(None)
    np.testing.assert_allclose(agg2d, agg1d, rtol=1e-5, atol=1e-5)
    # and the table is the sketch of the weighted dense mean
    wts = np_staleness_weights(stale, alpha).astype(np.float64)
    n_per = np.full((W,), float(B))
    total = max(float((wts * n_per).sum()), 1.0)
    dense = np.einsum("w,wd->d", wts * n_per,
                      c[:, 0, :].astype(np.float64)) / total
    table = np.asarray(jax.jit(args2sketch(cfg).sketch)(
        jnp.asarray(dense, jnp.float32)))
    np.testing.assert_allclose(agg1d, table, rtol=1e-5, atol=1e-5)


# -- observatory surface ------------------------------------------------


def test_async_staleness_alarm_rule():
    from commefficient_tpu.telemetry.alarms import build_alarm_engine

    cfg = make_cfg(async_buffer_size=2, async_staleness_weight=0.5,
                   alarm_async_staleness=3.0)
    eng = build_alarm_engine(cfg)
    assert eng is not None
    assert eng.check(0, {"async_staleness_max": 2.0}) == []
    fired = eng.check(1, {"async_staleness_max": 5.0,
                          "async_buffer_occupancy": 0.5,
                          "async_backlog": 7.0})
    assert [f["rule"] for f in fired] == ["async_staleness"]
    assert fired[0]["value"] == 5.0 and fired[0]["backlog"] == 7.0
    # rule off: nothing fires regardless of staleness
    off = build_alarm_engine(make_cfg(alarm_recovery_error=0.9))
    assert off is None or off.check(0, {"async_staleness_max": 99.0}) \
        == []


def test_async_topology_key_forms():
    from commefficient_tpu.telemetry import registry

    assert registry.async_suffix(None) == ""
    assert registry.async_suffix(0) == ""
    assert registry.async_suffix(4) == "a4"
    assert registry.topology_key(8, 1, None, None, 4) == "d8p1a4"
    assert registry.topology_key(None, None, None, None, 4) == "any-a4"
    # the a<K> fragment rides behind a mesh fragment, never instead
    assert registry.topology_key(
        8, 1, {"clients": 4, "model": 2}, None, 2) == "d8p1m4x2a2"


def test_registry_run_key_async_fragment():
    from commefficient_tpu.telemetry import registry

    man = {"config_hash": "abc", "device_count": 8,
           "process_count": 1,
           "config": {"mode": "local_topk", "async_buffer_size": 4}}
    assert registry.run_async_k(man) == 4
    assert registry.run_key(man) == ("abc", 8, 1, "a4")
    man["config"]["async_buffer_size"] = 0
    assert registry.run_async_k(man) is None
    assert registry.run_key(man) == ("abc", 8, 1)


def test_config_validates_async_bounds():
    with pytest.raises(AssertionError):
        make_cfg(async_buffer_size=-1).validate()
    with pytest.raises(AssertionError):
        make_cfg(async_buffer_size=8).validate_runtime()  # > workers
    with pytest.raises(AssertionError):
        make_cfg(async_buffer_size=2, client_chunk=2,
                 num_workers=4).validate_runtime()
    make_cfg(async_buffer_size=2).validate_runtime()

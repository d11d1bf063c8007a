"""Download/upload byte accounting: the round-histogram structure vs
a brute-force ``last_updated > last_seen`` compare (the semantics of
reference fed_aggregator.py:171-196, 240-300 under this framework's
last-updated-round simplification — see runtime/fed_model.py module
docstring)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import accounting
from commefficient_tpu.config import Config
from commefficient_tpu.runtime import FedModel

# downloads ship values as f32 under the dense encoding
VAL_BYTES = accounting.bytes_of(1, "f32")


def make_model(grad_size=50, num_clients=6):
    import flax.linen as nn

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(grad_size // 2, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2)))[
        "params"]
    args = Config(mode="uncompressed", error_type="none",
                  local_momentum=0.0, num_workers=2,
                  local_batch_size=2, num_clients=num_clients,
                  dataset_name="CIFAR10", seed=0)

    def loss(p, batch, cfg):
        return jnp.float32(0.0), ()

    return FedModel(module, params, loss, args)


class BruteForce:
    """Reference implementation: dense last_updated compare."""

    def __init__(self, grad_size, num_clients):
        self.last_updated = np.full(grad_size, -1, np.int64)
        self.last_seen = np.full(num_clients, -1, np.int64)
        self.round = 0

    def note(self, changed_idx):
        self.round += 1
        self.last_updated[changed_idx] = self.round

    def download(self, ids):
        out = np.array([VAL_BYTES * np.sum(self.last_updated
                                           > self.last_seen[c])
                        for c in ids])
        self.last_seen[ids] = self.round
        return out


def test_sparse_support_matches_brute_force():
    rng = np.random.RandomState(0)
    m = make_model()
    d = m.args.grad_size
    bf = BruteForce(d, m.num_clients)
    for _ in range(40):
        k = rng.randint(1, 10)
        idx = rng.choice(d, k, replace=False)
        vals = rng.randn(k)
        vals[rng.rand(k) < 0.3] = 0.0  # zero values don't count
        m.note_update((idx, vals))
        bf.note(idx[vals != 0])
        ids = rng.choice(m.num_clients, 2, replace=False)
        got, _ = m._account_bytes(ids)
        want = bf.download(ids)
        np.testing.assert_array_equal(got[ids], want)


def test_dense_none_marks_everything():
    m = make_model()
    d = m.args.grad_size
    m.note_update(None)
    got, _ = m._account_bytes(np.array([0, 3]))
    np.testing.assert_array_equal(got[[0, 3]], [4.0 * d, 4.0 * d])
    # same clients sync again with no new update: nothing to download
    got2, _ = m._account_bytes(np.array([0, 3]))
    np.testing.assert_array_equal(got2[[0, 3]], [0.0, 0.0])


def test_dense_array_host_compare():
    m = make_model()
    d = m.args.grad_size
    upd = np.zeros(d, np.float32)
    upd[[2, 5, 7]] = 1.0
    m.note_update(upd)
    got, _ = m._account_bytes(np.array([1]))
    assert got[1] == 4.0 * 3


def test_bitmap_support_matches_dense_compare():
    """The packed-bitmap support form (what local_topk ships instead
    of the dense f32 update) must mark exactly the nonzero coords."""
    m = make_model()
    d = m.args.grad_size
    upd = np.zeros(d, np.float32)
    upd[[2, 5, 7, 31]] = 1.0
    m.note_update({"bitmap": jnp.packbits(jnp.asarray(upd) != 0)})
    got, _ = m._account_bytes(np.array([1]))
    assert got[1] == 4.0 * 4

    m2 = make_model()
    m2.note_update(upd)
    got2, _ = m2._account_bytes(np.array([1]))
    assert got2[1] == got[1]


def test_empty_support_changes_nothing():
    m = make_model()
    m.note_update((np.zeros(0, np.int64), np.zeros(0)))
    got, _ = m._account_bytes(np.array([2]))
    assert got[2] == 0.0


def test_rebuild_round_counts_is_lossless():
    rng = np.random.RandomState(1)
    m = make_model()
    d = m.args.grad_size
    for _ in range(10):
        idx = rng.choice(d, 5, replace=False)
        m.note_update((idx, rng.randn(5)))
        m._account_bytes(rng.choice(m.num_clients, 2, replace=False))
    counts_before = m._round_counts[:m._update_round + 2].copy()
    m._rebuild_round_counts()  # what checkpoint restore runs
    np.testing.assert_array_equal(
        counts_before, m._round_counts[:m._update_round + 2])


def test_local_topk_virtual_momentum_sparse_download():
    """local_topk with virtual_momentum > 0 must still account
    downloads by value-comparing the dense update (reference compares
    weight_update != 0, fed_aggregator.py:240-300): the update support
    is only the union of past top-k selections, so a first-round
    download is ~W*k coords, not grad_size."""
    import flax.linen as nn

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(64, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 32)))[
        "params"]
    args = Config(mode="local_topk", error_type="local", k=5,
                  local_momentum=0.9, virtual_momentum=0.9,
                  num_workers=2, local_batch_size=2, num_clients=6,
                  dataset_name="CIFAR10", seed=0)

    def loss(p, batch, cfg):
        pred = module.apply({"params": p}, batch["x"])
        per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        return jnp.sum(per * batch["mask"]) / n, ()

    from commefficient_tpu.runtime import FedOptimizer
    m = FedModel(module, params, loss, args)
    opt = FedOptimizer([{"lr": 0.1}], args)
    d = args.grad_size
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(2, 2, 32).astype(np.float32),
             "y": rng.randn(2, 2).astype(np.float32),
             "mask": np.ones((2, 2), np.float32),
             "client_ids": np.array([0, 1], np.int32)}
    m(batch)
    opt.step()
    got, _ = m._account_bytes(np.array([5]))
    # support after one round is at most num_workers * k coords
    assert 0 < got[5] <= 4.0 * args.num_workers * args.k
    assert got[5] < 4.0 * d


def make_delta_model(wire="int8", grad_size=64, num_clients=6):
    import flax.linen as nn

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(grad_size // 2, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2)))[
        "params"]
    args = Config(mode="sketch", error_type="virtual",
                  local_momentum=0.0, virtual_momentum=0.9,
                  num_rows=2, num_cols=16, num_blocks=1, k=3,
                  num_workers=2, local_batch_size=2,
                  num_clients=num_clients, dataset_name="CIFAR10",
                  seed=0, sketch_dtype=wire,
                  downlink_encoding="delta")

    def loss(p, batch, cfg):
        return jnp.float32(0.0), ()

    return FedModel(module, params, loss, args)


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_delta_downlink_matches_brute_force(wire):
    """--downlink_encoding delta vs a dense-history brute force: per
    client, changed values ship at the wire width, indices (int32)
    only for coords NOT in the previous broadcast's support, repeats
    as one bitmap bit per previous-support coord — and only clients
    that saw the previous broadcast get to delta-code at all."""
    rng = np.random.RandomState(3)
    m = make_delta_model(wire=wire)
    d = m.args.grad_size
    wb = accounting.dtype_bytes(wire)
    idx_b = accounting.dtype_bytes(np.int32)

    last_updated = np.full(d, -1, np.int64)
    last_seen = np.full(m.num_clients, -1, np.int64)
    prev_vec = np.zeros(d, bool)  # previous update's support
    repeated = 0
    bitmap_bits = 0
    rnd = 0
    for _ in range(40):
        if rng.rand() < 0.15:
            sup = None  # dense update
            vec = np.ones(d, bool)
            m.note_update(None)
        else:
            k = rng.randint(1, 10)
            sup = np.sort(rng.choice(d, k, replace=False))
            vec = np.zeros(d, bool)
            vec[sup] = True
            m.note_update((sup, np.ones(len(sup))))
        rnd += 1
        repeated = int((vec & prev_vec).sum())
        bitmap_bits = int(prev_vec.sum())
        prev_vec = vec
        last_updated[vec] = rnd

        ids = rng.choice(m.num_clients, 2, replace=False)
        got, _ = m._account_bytes(ids)
        for c in ids:
            changed = int(np.sum(last_updated > last_seen[c]))
            if last_seen[c] == rnd - 1:  # saw the previous broadcast
                want = (changed * wb
                        + (changed - repeated) * idx_b
                        + int(np.ceil(bitmap_bits / 8)))
            else:
                want = changed * (wb + idx_b)
            assert got[c] == want, (wire, rnd, c, got[c], want)
            last_seen[c] = rnd


def test_delta_downlink_stale_client_pays_full_indices():
    """A client that skipped a broadcast cannot delta-code: every
    changed coord ships (idx, val) with no bitmap."""
    m = make_delta_model(wire="int8")
    d = m.args.grad_size
    idx = np.arange(5)
    m.note_update((idx, np.ones(5)))
    # client 0 syncs at round 1; client 1 stays stale
    m._account_bytes(np.array([0]))
    m.note_update((idx, np.ones(5)))  # identical support: all repeats
    got, _ = m._account_bytes(np.array([0, 1]))
    # fresh client: 5 values + 0 fresh indices + ceil(5/8)=1 bitmap
    assert got[0] == 5 * 1 + 0 * 4 + 1
    # stale client: both rounds' union is still those 5 coords, but
    # nothing delta-codes — 5 x (idx + val)
    assert got[1] == 5 * (4 + 1)


class TestLedgerMatchesBruteForce:
    """Full-stack mode matrix: run a real FedModel + FedOptimizer for
    3 rounds with the JSONL ledger sink attached, and assert each
    round record's uplink/downlink totals equal (a) the accounting
    arrays model(batch) returned and (b) an independent brute-force
    compare of the server weights before/after each step (the
    reference's value-compare semantics). Covers every compression
    mode, not just uncompressed."""

    MODES = {
        "uncompressed": dict(mode="uncompressed", error_type="none",
                             local_momentum=0.0,
                             virtual_momentum=0.9),
        "sketch": dict(mode="sketch", error_type="virtual",
                       local_momentum=0.0, virtual_momentum=0.9,
                       num_rows=2, num_cols=16, num_blocks=1, k=3),
        # quantized wire lattice: the ledger's uplink total must price
        # the table at the wire width plus the f32 row scales, never
        # at a hardcoded 4 bytes/element
        "sketch_bf16": dict(mode="sketch", error_type="virtual",
                            local_momentum=0.0, virtual_momentum=0.9,
                            num_rows=2, num_cols=16, num_blocks=1,
                            k=3, sketch_dtype="bf16"),
        "sketch_int8": dict(mode="sketch", error_type="virtual",
                            local_momentum=0.0, virtual_momentum=0.9,
                            num_rows=2, num_cols=16, num_blocks=1,
                            k=3, sketch_dtype="int8"),
        "sketch_fp8": dict(mode="sketch", error_type="virtual",
                           local_momentum=0.0, virtual_momentum=0.9,
                           num_rows=2, num_cols=16, num_blocks=1,
                           k=3, sketch_dtype="fp8"),
        "true_topk": dict(mode="true_topk", error_type="virtual",
                          local_momentum=0.0, virtual_momentum=0.9,
                          k=3),
        "local_topk": dict(mode="local_topk", error_type="local",
                           local_momentum=0.9, virtual_momentum=0.9,
                           k=3),
        "fedavg": dict(mode="fedavg", error_type="none",
                       local_momentum=0.0, local_batch_size=-1),
    }

    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_round_bytes_match(self, mode, tmp_path):
        import flax.linen as nn

        from commefficient_tpu.runtime import FedOptimizer
        from commefficient_tpu.telemetry.record import validate_record

        class Lin(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4, use_bias=False)(x)

        module = Lin()
        params = module.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 3)))["params"]
        ledger = str(tmp_path / "ledger.jsonl")
        kw = dict(self.MODES[mode])
        kw.setdefault("local_batch_size", 2)
        args = Config(num_workers=2, num_clients=5,
                      dataset_name="CIFAR10", seed=0, ledger=ledger,
                      **kw)

        def loss(p, batch, cfg):
            pred = module.apply({"params": p}, batch["x"])
            per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
            n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
            return jnp.sum(per * batch["mask"]) / n, ()

        model = FedModel(module, params, loss, args,
                         padded_batch_size=2)
        opt = FedOptimizer([{"lr": 0.1}], args)
        bf = BruteForce(args.grad_size, args.num_clients)
        rng = np.random.RandomState(7)
        returned = []  # (down_total, up_total) per round
        for _ in range(3):
            ids = rng.choice(5, 2, replace=False).astype(np.int32)
            batch = {"x": rng.randn(2, 2, 3).astype(np.float32),
                     "y": rng.randn(2, 2).astype(np.float32),
                     "mask": np.ones((2, 2), np.float32),
                     "client_ids": ids}
            w_before = np.asarray(model.ps_weights)
            out = model(batch)
            down, up = out[-2], out[-1]
            # the model accounts the download BEFORE this round's
            # server update lands (end of the client pass) — mirror
            want_down = bf.download(ids)
            np.testing.assert_array_equal(down[ids], want_down)
            # dtype-aware uplink: wire-width table (+ f32 row scales
            # for the scaled dtypes), f32 floats everywhere else
            assert up.sum() == 2 * args.upload_wire_bytes_per_client
            if mode == "sketch_int8":
                assert args.upload_wire_bytes_per_client == \
                    accounting.sketch_wire_bytes(2, 16, "int8")
                assert up.sum() < \
                    VAL_BYTES * 2 * args.upload_floats_per_client
            opt.step()
            w_after = np.asarray(model.ps_weights)
            bf.note(np.nonzero(w_before != w_after)[0])
            returned.append((float(down.sum()), float(up.sum())))
        model.finalize()

        with open(ledger) as f:
            records = [json.loads(line) for line in f]
        for rec in records:
            assert validate_record(rec) == [], rec
        rounds = [r for r in records if r["kind"] == "round"]
        assert [r["round"] for r in rounds] == [0, 1, 2]
        for rec, (down_total, up_total) in zip(rounds, returned):
            assert rec["downlink_bytes"] == down_total
            assert rec["uplink_bytes"] == up_total

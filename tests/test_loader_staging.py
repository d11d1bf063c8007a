"""The round's batch is on the device before the round is asked for:
where a loader knows the consuming model's placement, a thread of its
own makes round r+1 and places it while round r computes
(data/loader.py ``_ReadAhead``, data/staging.py), and
``FedModel._client_pass`` takes that copy instead of issuing one.

What must hold: the same batches in the same order with the same RNG
streams as the loader without read-ahead; one owner of sampler and
ring at a time, whatever ends an epoch; errors raised from ``next()``;
a rebuilt batch placed inline, as every batch used to be. Every wait
here is under ``LIMIT`` seconds: a hang fails its test, not the run.
"""

import gc
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import native
from commefficient_tpu.config import Config
from commefficient_tpu.data import staging
from commefficient_tpu.data.chaos import ChaosConfig, ChaosInjector
from commefficient_tpu.data.fed_sampler import FedSampler
from commefficient_tpu.data.loader import (FedLoader, NativeFedLoader,
                                           PersonaFedLoader,
                                           TokenFedLoader)
from commefficient_tpu.data.synthetic import FedSynthetic
from commefficient_tpu.data.transforms import cifar_train_transform
from commefficient_tpu.parallel.mesh import (client_sharding, make_mesh,
                                             replicated)
from commefficient_tpu.runtime.fed_model import FedModel, FedOptimizer

LIMIT = 120.0       # seconds a whole test may take, waits included
W, B = 2, 4
THREADS = ("loader-stage", "persona-prefetch", "tokens-prefetch")


@pytest.fixture(autouse=True)
def time_limit():
    """A wait that never ends raises in the test that made it."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"a wait in this test passed {LIMIT:g} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, LIMIT)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _loader_threads():
    return [t for t in threading.enumerate() if t.name in THREADS]


def _dataset():
    tf = cifar_train_transform(np.float32(0.1), np.float32(1.1))
    return FedSynthetic("", "Synthetic", transform=tf, num_classes=4,
                        per_class=16, num_val=8, gen_seed=3)


def _make(kind, root):
    """A loader of ``kind`` over data made from fixed seeds: built
    twice, it deals the same rounds. 8 (CV) or more rounds an epoch,
    clients dropped with probability 0.3."""
    drop = dict(dropout_prob=0.3, dropout_seed=5)
    if kind in ("fed", "native"):
        ds = _dataset()
        sampler = FedSampler(ds, num_workers=W, local_batch_size=B, seed=0)
        if kind == "fed":
            return FedLoader(ds, sampler, **drop)
        return NativeFedLoader(ds, sampler, seed=11, depth=3, **drop)
    if kind == "persona":
        from commefficient_tpu.data.fed_persona import (
            FedPERSONA, generate_synthetic_personachat)
        from commefficient_tpu.data.tokenizer import (SPECIAL_TOKENS,
                                                      ByteTokenizer)
        generate_synthetic_personachat(root)
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        ds = FedPERSONA(tok, 2, 2, 1, root, "PERSONA", train=True, seed=3)
        return PersonaFedLoader(
            ds, FedSampler(ds, num_workers=W, local_batch_size=2, seed=3),
            2, 64, 0, **drop)
    from commefficient_tpu.data.fed_tokens import (
        FedTokens, generate_synthetic_tokens)
    generate_synthetic_tokens(root, num_clients=6, stream_len=128,
                              seq_len=32)
    ds = FedTokens(root)
    # depth 1: no thread of the loader's own unless a batch is placed
    return TokenFedLoader(
        ds, FedSampler(ds, num_workers=W, local_batch_size=2, seed=3),
        prefetch_depth=1 if kind == "tokens-depth1" else 2, **drop)


KINDS = ["fed",
         pytest.param("native", marks=pytest.mark.skipif(
             not native.available(), reason="no native toolchain")),
         "persona", "tokens", "tokens-depth1"]


class _Placer:
    """``FedModel.place_batch`` on a mesh of ``n`` devices, without the
    model: the loader is handed a bound method, as by a trainer."""

    place_batch = FedModel.place_batch

    def __init__(self, n):
        self.mesh = make_mesh(jax.devices()[:n])


def _states(loader):
    """Every RNG stream and counter a checkpoint reads off a loader."""
    ds_rng = getattr(loader.dataset, "_rng", None)
    return {"sampler": loader.sampler.rng.get_state(),
            "dropout": loader._dropout_rng.get_state(),
            "numpy": np.random.get_state(),
            "round_counter": getattr(loader, "_round_counter", None),
            "dataset": None if ds_rng is None else ds_rng.getstate()}


def _set_states(loader, st):
    loader.sampler.rng.set_state(st["sampler"])
    loader._dropout_rng.set_state(st["dropout"])
    np.random.set_state(st["numpy"])
    if st["round_counter"] is not None:
        loader._round_counter = st["round_counter"]
    if st["dataset"] is not None:
        loader.dataset._rng.setstate(st["dataset"])


def _assert_same(a, b):
    np.testing.assert_equal(a, b)       # nested, bit for bit


def _assert_batches_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert list(a.keys()) == list(b.keys())
        for k in a:
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
            assert a[k].tobytes() == b[k].tobytes(), k


def _epochs(loader, n):
    """``n`` whole epochs: the batches, and the states after each."""
    np.random.seed(77)      # the CV transforms draw from the global RNG
    out = []
    for _ in range(n):
        out.append((list(loader), _states(loader)))
    return out


@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("kind", KINDS)
def test_read_ahead_deals_the_same_rounds(tmp_path, kind, n_dev):
    """Three epochs with read-ahead against the same loader with none:
    equal batches, order and RNG states; each batch carries a copy
    placed with the model's sharding, equal to its host fields."""
    plain = _make(kind, str(tmp_path / "a"))
    assert plain.placement is None and staging.current() is None
    want = _epochs(plain, 3)
    assert not any(isinstance(b, staging.StagedBatch)
                   for bs, _ in want for b in bs)

    placer = _Placer(n_dev)
    ahead = _make(kind, str(tmp_path / "b"))
    ahead.placement = placer.place_batch
    got = _epochs(ahead, 3)
    assert not _loader_threads()        # each epoch's thread went with it
    for (gb, gs), (wb, ws) in zip(got, want):
        assert len(gb) >= 3
        _assert_batches_equal(gb, wb)
        _assert_same(gs, ws)
        for b in gb:
            assert isinstance(b, staging.StagedBatch)
            assert all(isinstance(v, np.ndarray) for v in b.values())
            dev, ids = staging.staged_copy(b, placer.place_batch)
            assert set(dev) == set(b) - {"client_ids"}
            for k, v in dev.items():
                assert v.sharding.is_equivalent_to(
                    client_sharding(placer.mesh), v.ndim)
                assert len(v.sharding.device_set) == n_dev
                assert np.asarray(v).tobytes() == b[k].tobytes()
            assert ids.sharding.is_equivalent_to(
                replicated(placer.mesh), 1)
            assert np.array_equal(np.asarray(ids), b["client_ids"])
    plain.close()
    ahead.close()


@pytest.mark.parametrize("kind", KINDS)
def test_however_an_epoch_ends_the_thread_goes_and_the_next_is_right(
        tmp_path, kind):
    """An epoch abandoned mid-way (closed, collected), a second
    ``__iter__``, ``close()`` twice: the loader's thread is joined each
    time, and the epoch after is the one a fresh loader deals from the
    same RNG states (so nothing stale was left in ring or hand-over)."""
    placer = _Placer(1)
    loader = _make(kind, str(tmp_path / "a"))
    twin = _make(kind, str(tmp_path / "b"))       # no read-ahead
    loader.placement = placer.place_batch
    np.random.seed(5)
    next(iter(twin))    # FedLoader probes the image shape once, by a draw

    def check():
        start = _states(loader)
        got = list(loader)
        end = _states(loader)
        _set_states(twin, start)
        want = list(twin)
        _assert_batches_equal(got, want)
        _assert_same(_states(twin), end)
        assert not _loader_threads()

    # abandoned: closed
    it = iter(loader)
    next(it), next(it)
    assert len(_loader_threads()) == 1
    it.close()
    check()
    # abandoned: dropped and collected, nothing ever closed it
    it = iter(loader)
    next(it)
    del it
    gc.collect()
    check()
    # a second __iter__ retires the first, whose next() raises
    first = iter(loader)
    next(first)
    second = iter(loader)
    b0 = next(second)
    assert len(_loader_threads()) == 1
    with pytest.raises(RuntimeError, match="retired"):
        next(first)
    rest = list(second)
    assert isinstance(b0, staging.StagedBatch) and len(rest) >= 2
    assert not _loader_threads()
    check()
    # close() mid-epoch, twice
    it = iter(loader)
    next(it)
    loader.close()
    loader.close()
    assert not _loader_threads()
    if kind == "native":
        assert loader._ring is None and loader._epoch is None
    with pytest.raises(RuntimeError, match="retired"):
        next(it)
    check()
    loader.close()
    twin.close()


@pytest.mark.parametrize("kind", ["fed", pytest.param(
    "native", marks=pytest.mark.skipif(
        not native.available(), reason="no native toolchain"))])
def test_an_error_on_the_thread_is_raised_from_next(tmp_path, kind,
                                                    monkeypatch):
    loader = _make(kind, str(tmp_path))
    loader.placement = _Placer(1).place_batch
    if kind == "native":
        # indices past the store: the ring reports them at the pop
        real = loader._spec_to_indices

        def past_the_store(spec):
            ids, idx = real(spec)
            return ids, idx + 10 ** 6
        monkeypatch.setattr(loader, "_spec_to_indices", past_the_store)
        it = iter(loader)
        with pytest.raises(IndexError, match="out-of-range"):
            next(it)
    else:
        real, calls = loader.collate, []

        def second_fails(spec):
            calls.append(1)
            if len(calls) == 2:
                raise ValueError("collate broke")
            return real(spec)
        monkeypatch.setattr(loader, "collate", second_fails)
        it = iter(loader)
        assert isinstance(next(it), staging.StagedBatch)
        with pytest.raises(ValueError, match="collate broke"):
            next(it)
    assert next(it, None) is None       # the generator is finished
    assert not _loader_threads()
    monkeypatch.undo()
    assert len(list(loader)) == 8       # and the loader is not
    loader.close()


def test_settle_and_the_lookahead_under_read_ahead(tmp_path):
    """Between rounds the thread stands still (a checkpoint reads the
    sampler then), and the participant feed answers with the made
    batch's ids: the sampler itself is a round further."""
    loader = _make("fed", str(tmp_path))
    loader.dropout_prob = 0.0
    loader.placement = _Placer(1).place_batch
    it = iter(loader)
    next(it)
    loader.settle()
    before = _states(loader)
    peeked = loader.peek_next_client_ids()
    loader.settle()
    _assert_same(_states(loader), before)
    assert np.array_equal(peeked, next(it)["client_ids"])
    it.close()
    loader.settle()             # no epoch, nothing to wait for


def test_a_copy_is_taken_only_of_the_batch_it_was_made_of():
    a, b = _Placer(1), _Placer(1)
    host = {"client_ids": np.arange(W, dtype=np.int32),
            "x": np.ones((W, B, 3), np.float32),
            "mask": np.ones((W, B), np.float32)}
    batch = staging.stage(host, a.place_batch)
    assert staging.staged_copy(batch, a.place_batch) is not None
    assert staging.staged_copy(batch, b.place_batch) is None
    assert staging.staged_copy(dict(batch), a.place_batch) is None
    assert staging.staged_copy({**batch}, a.place_batch) is None
    assert staging.staged_copy(host, a.place_batch) is None
    edited = staging.stage(host, a.place_batch)
    edited["x"] = edited["x"].copy()                # replaced in place
    assert staging.staged_copy(edited, a.place_batch) is None
    grown = staging.stage(host, a.place_batch)
    grown["lam"] = host["mask"]
    assert staging.staged_copy(grown, a.place_batch) is None


# --- through FedModel ----------------------------------------------------


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


def _loss(params, batch, cfg):
    x = batch["x"].reshape(batch["x"].shape[0], -1)
    logp = jax.nn.log_softmax(x @ params["w"])
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], 1)[:, 0]
    loss = jnp.sum(nll * batch["mask"]) / n
    return loss, (loss * 0.0 + 1.0,)


def _fed_run(wrap=None, rounds=3, hand_over=False, **cfg_kw):
    """The builders' order: the loader first, handed nothing, then the
    model; ``rounds`` rounds of the trainers' loop. Returns (weights,
    round records, the batches' types)."""
    ds = _dataset()
    loader = FedLoader(ds, FedSampler(ds, num_workers=W,
                                      local_batch_size=B, seed=0))
    cfg = Config(mode="sketch", error_type="virtual", local_momentum=0.0,
                 virtual_momentum=0.9, k=16, num_rows=3, num_cols=128,
                 num_workers=W, local_batch_size=B, seed=5,
                 num_clients=ds.num_clients, num_devices=1, **cfg_kw)
    model = FedModel(None, {"w": jnp.zeros((32 * 32 * 3, 4), jnp.float32)},
                     _loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    sink = ListSink()
    model.telemetry.add_sink(sink)
    if hand_over:
        loader.placement = model.placement
    np.random.seed(9)
    feed = iter(loader) if wrap is None else wrap(loader)
    kinds = []
    for _ in range(rounds):
        batch = next(feed)
        kinds.append(type(batch))
        model(batch)
        opt.step()
    feed.close()
    assert not _loader_threads()
    weights = np.asarray(model.ps_weights)
    model.finalize()
    assert staging.current() is None
    counters = [r["counters"] for r in sink.records
                if r.get("kind") == "round"]
    assert len(counters) == rounds
    return weights, counters, kinds


def _h2d(counters):
    return [(c.get("h2d.staged", 0), c.get("h2d.inline", 0))
            for c in counters]


def test_fedmodel_takes_the_staged_copy_and_places_a_rebuilt_batch():
    staged, recs, kinds = _fed_run()
    assert _h2d(recs) == [(1, 0)] * 3
    assert kinds == [staging.StagedBatch] * 3
    assert np.abs(staged).sum() > 0
    # handed over by the trainer: the same
    handed, recs, _ = _fed_run(hand_over=True)
    assert _h2d(recs) == [(1, 0)] * 3
    assert staged.tobytes() == handed.tobytes()

    # anything that rebuilds the batch drops the copy: placed inline,
    # as every batch used to be, and trained to the same weights
    def rebuilt(loader):
        return (dict(b) for b in loader)
    inline, recs, kinds = _fed_run(wrap=rebuilt)
    assert _h2d(recs) == [(0, 1)] * 3 and kinds == [dict] * 3
    assert staged.tobytes() == inline.tobytes()


def test_a_chaos_wrapped_loader_trains_as_it_did():
    """Every client flips its labels: the wrapper's batches are new
    dicts, placed inline; the weights are those of the same wrapper
    over a loader that stages nothing."""
    def chaos(loader):
        inj = ChaosInjector(ChaosConfig(
            attack="label_flip", num_classes=4,
            byzantine_ids=range(loader.dataset.num_clients)),
            loader.dataset.num_clients)
        return inj.wrap_loader(loader)

    def chaos_unstaged(loader):
        loader.placement = None
        staging._LIVE.clear()           # as with no model live
        return chaos(loader)

    flipped, recs, kinds = _fed_run(wrap=chaos)
    assert _h2d(recs) == [(0, 1)] * 3 and kinds == [dict] * 3
    want, recs, _ = _fed_run(wrap=chaos_unstaged)
    assert _h2d(recs) == [(0, 1)] * 3
    assert flipped.tobytes() == want.tobytes()
    clean, _, _ = _fed_run()
    assert flipped.tobytes() != clean.tobytes()


def test_no_placement_with_no_model_live_or_with_several():
    """``staging.current()`` answers while exactly one model is live;
    the async front end, which folds every batch into another,
    publishes none."""
    assert staging.current() is None
    a, b = _Placer(1), _Placer(1)
    staging.publish(a.place_batch)
    staging.publish(a.place_batch)              # once
    assert staging.current() == a.place_batch
    staging.publish(b.place_batch)
    assert staging.current() is None
    ds = _dataset()
    loader = FedLoader(ds, FedSampler(ds, num_workers=W,
                                      local_batch_size=B, seed=0))
    assert type(next(iter(loader))) is dict and not _loader_threads()
    staging.withdraw(b.place_batch)
    assert staging.current() == a.place_batch
    del a
    gc.collect()                                # dropped: stops counting
    assert staging.current() is None
    _, recs, kinds = _fed_run(async_buffer_size=2)
    assert _h2d(recs) == [(0, 1)] * 3 and kinds == [dict] * 3

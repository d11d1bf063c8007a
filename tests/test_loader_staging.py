"""The round's batch is on the device before the round is asked for:
where a loader knows the consuming model's placement, a thread of its
own makes round r+1 and places it while round r computes
(data/loader.py ``_ReadAhead``, data/staging.py), and
``FedModel._client_pass`` takes that copy instead of issuing one.

What must hold: the same batches in the same order with the same RNG
streams as the loader without read-ahead; one owner of sampler and
ring at a time, whatever ends an epoch; errors raised from ``next()``;
a rebuilt batch placed inline, as every batch used to be. The thread is
the loader's for its life: when an epoch's last round is dealt it opens
the next epoch, which the next ``__iter__`` adopts, and a checkpoint
reads the streams as they stood at the end (``held_back()``); a sampler
on the ``np.random`` module is opened on the consumer's thread, one
thread an epoch. Every wait here is under ``LIMIT`` seconds: a hang
fails its test, not the run.
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
    """Every RNG stream and counter a checkpoint reads off a loader,
    as ``save_checkpoint`` reads them: between rounds, and what the
    loader holds back of an epoch's end in place of what stands."""
    loader.settle()
    held = loader.held_back()
    ds_rng = getattr(loader.dataset, "_rng", None)
    live = {"sampler_rng": loader.sampler.rng.get_state(),
            "dropout_rng": loader._dropout_rng.get_state(),
            "np_global_rng": np.random.get_state(),
            "loader_round_counter": getattr(loader, "_round_counter",
                                            None),
            "dataset_rng": None if ds_rng is None else ds_rng.getstate()}
    return {k: v if held.get(k) is None else held[k]
            for k, v in live.items()}


def _set_states(loader, st):
    loader.sampler.rng.set_state(st["sampler_rng"])
    loader._dropout_rng.set_state(st["dropout_rng"])
    np.random.set_state(st["np_global_rng"])
    if st["loader_round_counter"] is not None:
        loader._round_counter = st["loader_round_counter"]
    if st["dataset_rng"] is not None:
        loader.dataset._rng.setstate(st["dataset_rng"])


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
    plain.close()       # the language-model loaders' host read-ahead
    assert not _loader_threads()

    placer = _Placer(n_dev)
    ahead = _make(kind, str(tmp_path / "b"))
    ahead.placement = placer.place_batch
    got = _epochs(ahead, 3)
    # one thread for the three, with the fourth epoch opened on it;
    # the states are what a checkpoint would have recorded at each end
    assert len(_loader_threads()) == 1 and ahead.held_back()
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
    ahead.close()
    assert not _loader_threads() and not ahead.held_back()
    if kind == "native":
        assert ahead._ring is None and ahead._epoch is None


@pytest.mark.parametrize("kind", KINDS)
def test_however_an_epoch_ends_the_thread_goes_and_the_next_is_right(
        tmp_path, kind):
    """An epoch abandoned mid-way (closed, collected), a second
    ``__iter__``, ``close()`` twice: with no successor opened the
    loader's thread is joined each time, and the epoch after is the one
    a fresh loader deals from the same RNG states (so nothing stale was
    left in ring or hand-over). An epoch dealt whole leaves the thread
    alive with the next one opened, which the next ``__iter__``
    adopts."""
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
        twin.close()    # a language-model twin reads ahead on the host
        assert len(_loader_threads()) == 1      # the next is opened

    # abandoned: closed
    it = iter(loader)
    next(it), next(it)
    assert len(_loader_threads()) == 1
    it.close()
    assert not _loader_threads()
    check()
    # abandoned: dropped and collected, nothing ever closed it
    it = iter(loader)       # adopts the epoch check() left opened
    next(it)
    del it
    gc.collect()
    assert not _loader_threads()
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
    assert len(_loader_threads()) == 1
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


# --- across an epoch's end ---------------------------------------------


def _drive(loader, epochs):
    """The trainers' loop over ``epochs`` epochs with a recorder of its
    own: round r's record is open while batch r + 1 is fetched. Returns
    the batches per epoch and the records by round."""
    from commefficient_tpu.telemetry import Telemetry
    sink = ListSink()
    loader.telemetry = tel = Telemetry([sink])
    r, out = 0, []
    tel.begin_round(r)
    for _ in range(epochs):
        out.append([])
        it = iter(loader)
        while True:
            with tel.span("sampler"):
                batch = next(it, None)
            if batch is None:
                break
            out[-1].append(batch)
            tel.set_round_bytes(r, 0.0, 0.0)
            r += 1
            tel.begin_round(r)
    loader.settle()
    tel.set_round_bytes(r, 0.0, 0.0)
    tel.close()
    return out, {x["round"]: x for x in sink.records}


@pytest.mark.parametrize("kind", KINDS)
def test_an_opened_epoch_is_adopted_once(tmp_path, kind):
    """From the second epoch on every ``__iter__`` takes the epoch the
    thread opened: ``data.epoch_preopened`` beside ``data.epoch_start``
    on the same record, one ``data.epoch_open`` an epoch on the loader's
    thread, with no parent, around the ``data.sample`` that opens; the
    consumer's thread opens the first epoch and no other."""
    loader = _make(kind, str(tmp_path))
    loader.placement = _Placer(1).place_batch
    epochs, recs = _drive(loader, 3)
    n = [len(e) for e in epochs]
    firsts = [0, n[0], n[0] + n[1]]
    starts = {r: rec["counters"].get("data.epoch_start", 0)
              for r, rec in recs.items()}
    adopted = {r: rec["counters"].get("data.epoch_preopened", 0)
               for r, rec in recs.items()}
    assert [r for r, c in starts.items() if c] == firsts
    assert all(starts[r] == 1 for r in firsts)
    assert [r for r, c in adopted.items() if c] == firsts[1:]
    assert sum(adopted.values()) / (sum(starts.values()) - 1) == 1.0
    tl = [(rec["round"], i, e) for rec in recs.values()
          for i, e in enumerate(rec["timeline"])]
    opens = [(r, i, e) for r, i, e in tl if e[0] == "data.epoch_open"]
    assert len(opens) == 3              # the fourth is opened, unadopted
    for r, i, e in opens:
        assert e[3] is None and e[4] in THREADS
        inside = [k for rr, _, k in tl
                  if rr == r and k[3] == i and k[4] == e[4]]
        assert [k[0] for k in inside] == ["data.sample"]
    samples = {e[4] for _, _, e in tl if e[0] == "data.sample"}
    assert samples == {"MainThread", loader._thread_name}
    main = [r for r, _, e in tl
            if e[0] == "data.sample" and e[4] == "MainThread"]
    assert main == [0]                  # the first opening, and only it
    loader.close()
    assert not _loader_threads()


@pytest.mark.parametrize("kind", KINDS)
def test_a_sampler_on_the_numpy_module_opens_on_the_consumers_thread(
        tmp_path, kind):
    """``seed=None``: the sampler shares ``np.random`` with whoever else
    draws from it, so nothing of it is drawn early from another thread.
    One thread an epoch, gone with it, nothing held back, and the rounds
    of the loader without read-ahead."""
    def make(root):
        loader = _make(kind, root)
        loader.sampler.rng = np.random          # as seed=None leaves it
        return loader

    plain, loader = make(str(tmp_path / "a")), make(str(tmp_path / "b"))
    plain.placement = None
    loader.placement = _Placer(1).place_batch
    assert not loader._opens_ahead()
    np.random.seed(21)
    want = [list(plain), list(plain)]
    plain.close()
    np.random.seed(21)
    got, recs = _drive(loader, 2)
    assert not _loader_threads() and loader._reader is None
    assert not loader.held_back()
    for g, w in zip(got, want):
        _assert_batches_equal(g, w)
    assert not any("data.epoch_preopened" in rec["counters"]
                   or "data.epoch_open" in rec["spans"]
                   for rec in recs.values())
    opening = [rec["round"] for rec in recs.values()
               for e in rec["timeline"]
               if e[0] == "data.sample" and e[4] == "MainThread"]
    assert opening == [0, len(got[0])]
    loader.close()


@pytest.mark.parametrize("kind", ["fed", pytest.param(
    "native", marks=pytest.mark.skipif(
        not native.available(), reason="no native toolchain"))])
def test_an_error_while_opening_ahead_is_raised_from_the_next_epochs_next(
        tmp_path, kind, monkeypatch):
    """The epoch that is ending is dealt whole, the native ring's rounds
    included; the error waits for the ``next()`` that would have entered
    the epoch that could not be opened."""
    loader = _make(kind, str(tmp_path))
    loader.placement = _Placer(1).place_batch
    real, calls = type(loader.sampler).__iter__, []

    def second_fails(sampler):
        calls.append(1)
        if len(calls) == 2:
            raise ValueError("the sampler broke")
        return real(sampler)
    monkeypatch.setattr(type(loader.sampler), "__iter__", second_fails)
    assert len(list(loader)) == 8
    loader.settle()
    assert len(calls) == 2 and loader.held_back()
    it = iter(loader)
    with pytest.raises(ValueError, match="the sampler broke"):
        next(it)
    assert next(it, None) is None
    assert not _loader_threads() and not loader.held_back()
    assert len(list(loader)) == 8       # and the loader is not broken
    loader.close()


@pytest.mark.parametrize("taken", [6, 8])
@pytest.mark.parametrize("kind", KINDS[:2])
def test_an_abandoned_epoch_whose_successor_is_opened_is_adopted(
        tmp_path, kind, taken):
    """``--test`` breaks out of an epoch after a round, a fractional
    last epoch after some: where the sampler had dealt the epoch's last
    round by then (all 8 taken; 6 of them with the native ring's 3
    rounds of depth), the thread has the next epoch opened, and the
    ``__iter__`` after the abandoned one adopts it: no second opening
    is drawn. What follows is the loader without read-ahead's next
    epoch."""
    plain = _make(kind, str(tmp_path / "a"))
    np.random.seed(4)
    list(plain)
    want = list(plain)
    loader = _make(kind, str(tmp_path / "b"))
    loader.placement = _Placer(1).place_batch
    np.random.seed(4)
    it = iter(loader)
    for _ in range(taken):
        next(it)
    loader.settle()
    opened = bool(loader.held_back())
    assert opened == (taken == 8 or kind == "native")
    it.close()
    assert len(_loader_threads()) == opened
    if opened:
        _assert_batches_equal(list(loader), want)
    loader.close()
    plain.close()
    assert not _loader_threads()


def test_the_participant_feed_sees_past_an_epochs_end(tmp_path):
    """At an epoch's last round the feed answers with the first round of
    the epoch opened ahead, which the next ``__iter__`` hands over."""
    loader = _make("fed", str(tmp_path))
    loader.placement = _Placer(1).place_batch
    it = iter(loader)
    for _ in range(8):
        last = next(it)
    loader.settle()
    peeked = loader.peek_next_client_ids()
    assert peeked is not None and next(it, None) is None
    assert np.array_equal(peeked, loader.peek_next_client_ids())
    first = next(iter(loader))
    assert np.array_equal(peeked, first["client_ids"])
    assert not np.array_equal(last["x"], first["x"])
    loader.close()

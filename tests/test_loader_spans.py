"""The loaders open up the trainer's ``sampler`` span: their spans and
the ``data.epoch_start`` counter land on the round record that is open
while the consumer calls ``next(loader)``, although every loader is
built before the run's ``Telemetry``: handed over as the trainers do
(``loader.telemetry = ...``) or, by a loader left without, found
through ``telemetry.current()`` while exactly one is live."""

import numpy as np
import pytest

from commefficient_tpu import native, telemetry
from commefficient_tpu.data.fed_sampler import FedSampler
from commefficient_tpu.data.loader import (FedLoader, NativeFedLoader,
                                           PersonaFedLoader)
from commefficient_tpu.data.synthetic import FedSynthetic
from commefficient_tpu.data.transforms import Compose, Normalize, ToFloat
from commefficient_tpu.telemetry import NULL_TELEMETRY, Telemetry
from commefficient_tpu.telemetry import core

DEPTH = 4


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


def _cv_loader(cls, **kw):
    tf = Compose([ToFloat(), Normalize(np.float32(0.1), np.float32(1.1))])
    ds = FedSynthetic("", "Synthetic", transform=tf, num_classes=4,
                      per_class=16, num_val=8, gen_seed=3)
    return cls(ds, FedSampler(ds, num_workers=2, local_batch_size=4,
                              seed=0), **kw)


def _persona_loader(root, depth):
    from commefficient_tpu.data.fed_persona import (
        FedPERSONA, generate_synthetic_personachat)
    from commefficient_tpu.data.tokenizer import (SPECIAL_TOKENS,
                                                  ByteTokenizer)
    generate_synthetic_personachat(root)
    tok = ByteTokenizer()
    tok.add_special_tokens(SPECIAL_TOKENS)
    ds = FedPERSONA(tok, 2, 2, 1, root, "PERSONA", train=True, seed=3)
    return PersonaFedLoader(
        ds, FedSampler(ds, num_workers=2, local_batch_size=2, seed=3),
        2, 64, 0, prefetch_depth=depth)


def _token_loader(root, depth):
    from commefficient_tpu.data.fed_tokens import (
        FedTokens, generate_synthetic_tokens)
    from commefficient_tpu.data.loader import TokenFedLoader
    generate_synthetic_tokens(root, num_clients=6, stream_len=128,
                              seq_len=32)
    ds = FedTokens(root)
    return TokenFedLoader(
        ds, FedSampler(ds, num_workers=2, local_batch_size=2, seed=3),
        prefetch_depth=depth)


@pytest.fixture(autouse=True)
def no_live_telemetry(monkeypatch):
    """``current()`` as a fresh process has it: models that earlier
    tests of this worker built and never closed do not count."""
    monkeypatch.setattr(core, "_LIVE", [])


def _drive(loader, epochs=2, bind=True):
    """The trainers' loop: the loader exists first, then the run's
    Telemetry; round r's record is open while batch r + 1 is fetched
    under the ``sampler`` span. Returns the records and, per epoch, the
    round whose record saw that epoch's first ``next()``."""
    sink = ListSink()
    tel = Telemetry([sink])
    if bind:
        loader.telemetry = tel
    else:
        telemetry.set_current(tel)      # what FedModel does
    r, firsts = 0, []
    tel.begin_round(r)
    for _ in range(epochs):
        it = iter(loader)
        firsts.append(r)
        while True:
            with tel.span("sampler"):
                batch = next(it, None)
            if batch is None:
                break
            tel.set_round_bytes(r, 0.0, 0.0)
            r += 1
            tel.begin_round(r)
    tel.set_round_bytes(r, 0.0, 0.0)
    tel.close()
    assert all(telemetry.validate_record(x) == [] for x in sink.records)
    return {x["round"]: x for x in sink.records}, firsts


def _count(rec, name):
    return sum(e[0] == name for e in rec["timeline"])


def _children_of_sampler(rec):
    tl = rec["timeline"]
    tops = [i for i, e in enumerate(tl) if e[0] == "sampler"]
    return tops, [e for e in tl if e[3] in tops]


@pytest.mark.parametrize("bind", [True, False])
def test_fed_loader_spans_and_counters(bind):
    recs, firsts = _drive(_cv_loader(FedLoader), bind=bind)
    assert len(firsts) == 2 and firsts[1] == 8      # 8 rounds an epoch
    for r, rec in recs.items():
        c = rec["counters"]
        assert c.get("data.epoch_start", 0) == (1 if r in firsts else 0)
        tops, kids = _children_of_sampler(rec)
        assert all(e[4] == "MainThread" for e in kids)
        names = {e[0] for e in kids}
        if r == firsts[1]:
            # that record saw the old epoch end (a sampler advance that
            # found nothing) and the new one's first batch
            assert len(tops) == 2
        if r < 15:
            assert {"data.sample", "data.collate"} <= names
            assert _count(rec, "data.collate") == 1
        assert rec["spans"]["sampler"] >= sum(e[2] - e[1] for e in kids)


@pytest.mark.skipif(not native.available(), reason="no native toolchain")
def test_native_loader_spans_and_counters():
    loader = _cv_loader(NativeFedLoader, depth=DEPTH)
    recs, firsts = _drive(loader)
    assert firsts == [0, 8]
    for first in firsts:
        rec = recs[first]
        # a fresh __iter__: depth + 1 rounds indexed and submitted
        # before the first pop
        assert rec["counters"]["data.epoch_start"] == 1
        _, kids = _children_of_sampler(rec)
        by = {}
        for e in kids:
            by.setdefault(e[0], []).append(e)
        assert len(by["data.index"]) == DEPTH + 1 == len(by["data.submit"])
        assert len(by["data.pop_alloc"]) == 1 == len(by["data.pop_wait"])
        # one ring a loader: made in its first record, kept after
        assert len(by.get("data.ring_open", [])) == (first == 0)
        assert all(e[4] == "MainThread" for e in kids)
    assert sum(_count(rec, "data.ring_open") for rec in recs.values()) == 1
    assert not any("data.ring_close" in rec["spans"]
                   for rec in recs.values())
    # every pop says where its round landed; _drive's ``batch`` holds
    # one round while the next is popped, so two buffers take turns
    pops = {r: (rec["counters"].get("data.buffer_fresh", 0),
                rec["counters"].get("data.buffer_reused", 0))
            for r, rec in recs.items()}
    assert pops[0] == (1, 0) == pops[1] and pops[2] == (0, 1) == pops[8]
    assert sum(f for f, _ in pops.values()) == 2
    assert sum(u for _, u in pops.values()) == 14
    # steady state: one in, one out; the epoch's end drains the ring
    assert _count(recs[1], "data.index") == 1 == _count(recs[1],
                                                        "data.pop_wait")
    assert "data.epoch_start" not in recs[1]["counters"]
    assert [_count(recs[r], "data.index") for r in (4, 5, 6, 7)] == [0] * 4
    assert [_count(recs[r], "data.pop_wait") for r in (4, 5, 6)] == [1] * 3
    # the loader's children account for the sampler span
    rec = recs[1]
    _, kids = _children_of_sampler(rec)
    covered = sum(e[2] - e[1] for e in kids)
    assert 0.5 * rec["spans"]["sampler"] <= covered \
        <= rec["spans"]["sampler"]
    # the ring goes at close(), and only there
    sink = ListSink()
    loader.telemetry = tel = Telemetry([sink])
    tel.begin_round(0)
    next(iter(loader))
    loader.close()
    tel.set_round_bytes(0, 0.0, 0.0)
    tel.close()
    rec = sink.records[0]
    assert _count(rec, "data.ring_close") == 1
    assert _count(rec, "data.ring_open") == 0       # still the first ring


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("make, thread", [
    (_persona_loader, "persona-prefetch"),
    (_token_loader, "tokens-prefetch")], ids=["persona", "tokens"])
def test_persona_loader_spans_and_counters(tmp_path, depth, make, thread):
    recs, firsts = _drive(make(str(tmp_path), depth))
    assert len(firsts) == 2 and firsts[1] > 2
    threads = {e[4] for rec in recs.values() for e in rec["timeline"]
               if e[0] == "data.collate"}
    for first in firsts:
        assert recs[first]["counters"]["data.epoch_start"] == 1
    mid = recs[1]
    if depth == 1:
        # collated inside the consumer's next()
        assert threads == {"MainThread"}
        assert _count(mid, "data.collate") == 1
    else:
        # the producer thread collates: its spans are on the timeline
        # under its own name, with no parent on the consumer's stack
        assert threads == {thread}
        collate = [e for rec in recs.values() for e in rec["timeline"]
                   if e[0] == "data.collate"]
        assert all(e[3] is None for e in collate)
        _, kids = _children_of_sampler(mid)
        assert {e[0] for e in kids} == {"data.pop_wait"}


@pytest.mark.skipif(not native.available(), reason="no native toolchain")
def test_the_first_record_counts_the_rings_threads():
    """``host.threads``: the Python threads and the workers of the
    rings open when the run's first record is finished."""
    before = native.ring_threads()
    loader = _cv_loader(NativeFedLoader, depth=DEPTH)
    recs, _ = _drive(loader, epochs=1)
    assert native.ring_threads() == before + loader.n_threads
    c = recs[0]["counters"]
    # the round loop's thread, the recorder's watchdog, the ring's two
    assert c["host.threads"] >= 2 + before + loader.n_threads
    assert c["host.cpus"] >= 1
    assert all("host.threads" not in r["counters"]
               for i, r in recs.items() if i)
    loader.close()
    assert native.ring_threads() == before


@pytest.mark.parametrize("make, thread", [
    (_persona_loader, "persona-prefetch"),
    (_token_loader, "tokens-prefetch")], ids=["persona", "tokens"])
def test_a_producers_spans_carry_their_own_threads_cpu(tmp_path, make,
                                                       thread):
    recs, _ = _drive(make(str(tmp_path), 3), epochs=1)
    seen = 0
    for rec in recs.values():
        assert len(rec["timeline_cpu"]) == len(rec["timeline"])
        for e, cpu in zip(rec["timeline"], rec["timeline_cpu"]):
            if e[4] == thread and e[2] is not None:
                seen += 1
                # its own thread's clock: never more than its wall
                assert 0.0 <= cpu <= e[2] - e[1] + 1e-3
    assert seen >= 2
    collate = sum(rec["cpu"].get("data.collate", 0.0)
                  for rec in recs.values())
    assert 0.0 < collate <= sum(rec["spans"].get("data.collate", 0.0)
                                for rec in recs.values()) + 1e-3


def test_without_a_live_telemetry_the_loaders_record_nothing():
    assert telemetry.current() is NULL_TELEMETRY
    batches = list(_cv_loader(FedLoader))
    assert len(batches) == 8


def test_current_answers_only_while_one_telemetry_is_live():
    """Several tenants in one process (fedservice): a loader that was
    not handed its tenant's Telemetry records nothing, not onto the
    record of whichever model was built last."""
    a, b = Telemetry([ListSink()]), Telemetry([ListSink()])
    telemetry.set_current(a)
    telemetry.set_current(a)            # registered once
    assert telemetry.current() is a
    telemetry.set_current(b)
    assert telemetry.current() is NULL_TELEMETRY
    b.begin_round(0)
    list(_cv_loader(FedLoader))
    assert b._current["spans"] == {} and b._current["timeline"] == []
    b.close()
    assert telemetry.current() is a
    a.close()
    assert telemetry.current() is NULL_TELEMETRY
    # a model dropped without being closed stops counting
    c = Telemetry([ListSink()])
    telemetry.set_current(c)
    telemetry.set_current(Telemetry([ListSink()]))
    assert telemetry.current() is c


def test_two_tenants_loaders_record_each_on_their_own():
    """Two live Telemetries, each loader handed its own: interleaved
    rounds, a prefetching producer thread included, never cross."""
    sinks = [ListSink(), ListSink()]
    tels = [Telemetry([s]) for s in sinks]
    loaders = [_cv_loader(FedLoader), _cv_loader(FedLoader)]
    for tel, loader in zip(tels, loaders):
        telemetry.set_current(tel)
        loader.telemetry = tel
    its = [iter(x) for x in loaders]
    for r in range(4):
        for tel, it in zip(tels, its):
            tel.begin_round(r)
            if r % 2 == tels.index(tel):    # uneven: tenant 0 on even
                with tel.span("sampler"):
                    next(it)
            tel.set_round_bytes(r, 0.0, 0.0)
    for tel in tels:
        tel.close()
    for j, sink in enumerate(sinks):
        for rec in sink.records:
            mine = rec["round"] % 2 == j
            assert ("data.collate" in rec["spans"]) == mine
            assert _count(rec, "data.sample") == (1 if mine else 0)

"""C++ data-plane vs the Python loader path.

No-augmentation assembly must match FedLoader bit-for-bit; augmented
output must be a member of the enumerable crop/flip candidate set;
prefetch-ring pops must equal one-shot assembly in submission order.
Skipped wholesale when no toolchain is present."""

import contextlib
import gc
import os
import threading
import time

import numpy as np
import pytest

from commefficient_tpu import native
from commefficient_tpu.data.fed_sampler import FedSampler
from commefficient_tpu.data.loader import (FedLoader, NativeFedLoader,
                                           make_fed_loader)
from commefficient_tpu.data.synthetic import FedSynthetic
from commefficient_tpu.data.transforms import (Compose, Normalize,
                                               RandomCrop,
                                               RandomHorizontalFlip,
                                               ToFloat)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no native toolchain")

MEAN = np.array([0.1, 0.2, 0.3], np.float32)
STD = np.array([1.1, 0.9, 1.3], np.float32)


def _dataset(transform):
    return FedSynthetic("", "Synthetic", transform=transform,
                        num_classes=4, per_class=16, num_val=8,
                        gen_seed=3)


def _sampler(ds, W=2, B=4, seed=0):
    return FedSampler(ds, num_workers=W, local_batch_size=B, seed=seed)


def test_no_aug_matches_python_loader_bitwise():
    tf = Compose([ToFloat(), Normalize(MEAN, STD)])
    ds_py, ds_nat = _dataset(tf), _dataset(tf)
    py = FedLoader(ds_py, _sampler(ds_py))
    nat = NativeFedLoader(ds_nat, _sampler(ds_nat))
    for b_py, b_nat in zip(py, nat):
        np.testing.assert_array_equal(b_py["client_ids"],
                                      b_nat["client_ids"])
        np.testing.assert_array_equal(b_py["y"], b_nat["y"])
        np.testing.assert_array_equal(b_py["mask"], b_nat["mask"])
        np.testing.assert_array_equal(b_py["x"], b_nat["x"])


def test_augmented_output_is_valid_crop_flip():
    p = 2
    tf = Compose([ToFloat(), RandomCrop(32, p),
                  RandomHorizontalFlip(), Normalize(MEAN, STD)])
    ds = _dataset(tf)
    nat = NativeFedLoader(ds, _sampler(ds), seed=11)
    batch = next(iter(nat))
    images, targets = ds.dense_train_view()

    # each emitted sample must equal one of the (2p+1)^2 * 2
    # crop/flip candidates of SOME stored image with its target
    for w in range(batch["x"].shape[0]):
        for b in range(batch["x"].shape[1]):
            if batch["mask"][w, b] == 0:
                continue
            got = batch["x"][w, b]
            rows = np.nonzero(targets == batch["y"][w, b])[0]
            found = False
            for row in rows:
                img = images[row].astype(np.float32)
                padded = np.pad(img, ((p, p), (p, p), (0, 0)),
                                mode="reflect")
                for i in range(2 * p + 1):
                    for j in range(2 * p + 1):
                        crop = padded[i:i + 32, j:j + 32]
                        for flip in (crop, crop[:, ::-1]):
                            cand = (flip - MEAN) / STD
                            if np.array_equal(cand, got):
                                found = True
                                break
                        if found:
                            break
                    if found:
                        break
                if found:
                    break
            assert found, (w, b)


def test_aug_deterministic_per_seed():
    tf = Compose([ToFloat(), RandomCrop(32, 4),
                  RandomHorizontalFlip(), Normalize(MEAN, STD)])
    ds = _dataset(tf)
    a = next(iter(NativeFedLoader(ds, _sampler(ds, seed=5), seed=9)))
    b = next(iter(NativeFedLoader(ds, _sampler(ds, seed=5), seed=9)))
    c = next(iter(NativeFedLoader(ds, _sampler(ds, seed=5), seed=10)))
    np.testing.assert_array_equal(a["x"], b["x"])
    assert not np.array_equal(a["x"], c["x"])


def test_prefetch_matches_oneshot():
    images = np.random.RandomState(0).randint(
        0, 256, (64, 16, 16, 3)).astype(np.uint8)
    targets = np.arange(64, dtype=np.int32) % 7
    plane = native.NativeDataplane(images, targets, slots=3, B=5,
                                   mean=MEAN, std=STD, crop_pad=2,
                                   do_flip=True)
    rng = np.random.RandomState(1)
    specs = [rng.randint(-1, 64, (3, 5)).astype(np.int64)
             for _ in range(12)]
    expected = [plane.assemble(s, seed=100 + i)
                for i, s in enumerate(specs)]
    with native.Prefetcher(plane, depth=3, n_threads=3) as pf:
        for i, s in enumerate(specs[:6]):
            pf.submit(s, 100 + i)
        for i in range(12):
            x, y, m = pf.pop()
            np.testing.assert_array_equal(x, expected[i][0])
            np.testing.assert_array_equal(y, expected[i][1])
            np.testing.assert_array_equal(m, expected[i][2])
            if i + 6 < 12:
                pf.submit(specs[i + 6], 100 + i + 6)


def test_uint8_scaling_matches_tofloat():
    images = np.random.RandomState(2).randint(
        0, 256, (10, 8, 8, 3)).astype(np.uint8)
    targets = np.zeros(10, np.int32)
    plane = native.NativeDataplane(images, targets, slots=1, B=2,
                                   mean=MEAN, std=STD)
    idx = np.array([[3, 7]], np.int64)
    x, _, _ = plane.assemble(idx, seed=0)
    ref = (images[[3, 7]].astype(np.float32) / 255.0 - MEAN) / STD
    np.testing.assert_allclose(x[0], ref, rtol=0, atol=1e-6)


def test_make_fed_loader_fallback_on_unsupported_transform():
    from commefficient_tpu.data.transforms import RandomRotation
    tf = Compose([ToFloat(), RandomRotation(5), Normalize(MEAN, STD)])
    ds = _dataset(tf)
    with pytest.warns(UserWarning, match="native data-plane"):
        loader = make_fed_loader(ds, _sampler(ds))
    assert isinstance(loader, FedLoader)
    tf2 = Compose([ToFloat(), Normalize(MEAN, STD)])
    ds2 = _dataset(tf2)
    loader2 = make_fed_loader(ds2, _sampler(ds2))
    assert isinstance(loader2, NativeFedLoader)


def test_out_of_range_index_raises():
    images = np.zeros((10, 8, 8, 3), np.uint8)
    targets = np.zeros(10, np.int32)
    plane = native.NativeDataplane(images, targets, slots=1, B=2,
                                   mean=MEAN, std=STD)
    with pytest.raises(IndexError):
        plane.assemble(np.array([[3, 10]], np.int64), seed=0)
    with native.Prefetcher(plane, depth=2, n_threads=1) as pf:
        pf.submit(np.array([[99, 0]], np.int64), 0)
        with pytest.raises(IndexError):
            pf.pop()


def test_prefetch_ring_soak():
    """500 rounds through a 4-thread ring: strict submission-order
    delivery and correct content under sustained concurrency."""
    images = np.random.RandomState(0).randint(
        0, 256, (128, 8, 8, 3)).astype(np.uint8)
    targets = (np.arange(128) % 11).astype(np.int32)
    plane = native.NativeDataplane(images, targets, slots=2, B=3,
                                   mean=MEAN, std=STD, crop_pad=1,
                                   do_flip=True)
    rng = np.random.RandomState(1)
    n = 500
    specs = [rng.randint(-1, 128, (2, 3)).astype(np.int64)
             for _ in range(n)]
    # full-content comparison every round (images are tiny): any
    # out-of-order delivery or corruption fails deterministically
    expected = [plane.assemble(s, seed=i) for i, s in enumerate(specs)]
    with native.Prefetcher(plane, depth=4, n_threads=4) as pf:
        inflight = 0
        submitted = 0
        for i in range(n):
            while submitted < n and inflight < 8:
                pf.submit(specs[submitted], submitted)
                submitted += 1
                inflight += 1
            x, y, m = pf.pop()
            inflight -= 1
            np.testing.assert_array_equal(x, expected[i][0])
            np.testing.assert_array_equal(y, expected[i][1])
            np.testing.assert_array_equal(m, expected[i][2])


def test_library_is_keyed_by_its_source(tmp_path, monkeypatch):
    """A binary that does not match fed_dataplane.cpp is never loaded:
    the file name carries the source's hash (mtimes mean nothing in a
    copied tree, and _build/ is git-ignored but survives on disk)."""
    import hashlib

    from commefficient_tpu import native as native_mod
    with open(native_mod._SRC, "rb") as f:
        src = f.read()
    tag = hashlib.sha256(src).hexdigest()[:12]
    assert os.path.basename(native_mod._compile()) == \
        f"libfed_dataplane-{tag}.so"
    # another source -> another library, whatever sits in _build/
    edited = tmp_path / "fed_dataplane.cpp"
    edited.write_bytes(src + b"\n// edited\n")
    monkeypatch.setattr(native_mod, "_SRC", str(edited))
    monkeypatch.setattr(native_mod, "_BUILD_DIR", str(tmp_path))
    other = native_mod._compile()
    assert other is not None and tag not in other


# ---- one ring for the loader's life, rounds in recycled buffers -------------

AUG = Compose([ToFloat(), RandomCrop(32, 2), RandomHorizontalFlip(),
               Normalize(MEAN, STD)])
PLAIN = Compose([ToFloat(), Normalize(MEAN, STD)])
ROUNDS = 8          # _dataset: 64 images, 2 clients x 4 a round
KEYS = ("client_ids", "x", "y", "mask")


def _within(seconds, fn):
    """``fn()`` on a thread of its own with a time limit: a ring out of
    step would block in ``cet_ring_pop`` for ever."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:      # re-raised below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["value"]


def _copy(batch):
    return {k: np.array(batch[k]) for k in KEYS}


def _same(got, want):
    for k in KEYS:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.fixture
def rings(monkeypatch):
    """The rings made while the test runs."""
    made = []

    class Counted(native.Prefetcher):
        def __init__(self, *a, **kw):
            made.append(self)
            super().__init__(*a, **kw)

    monkeypatch.setattr(native, "Prefetcher", Counted)
    return made


def _ring_per_epoch(tf, seed, takes):
    """What the loader gave when it made a ring at every ``__iter__``:
    a loader rebuilt per epoch over one sampler, the round counter
    carried over; ``takes[e]`` rounds are taken of epoch ``e`` (None:
    all of it) before it is dropped."""
    ds = _dataset(tf)
    sampler = _sampler(ds, seed=5)
    counter, epochs = 0, []
    for take in takes:
        loader = NativeFedLoader(ds, sampler, seed=seed)
        loader._round_counter = counter
        it = iter(loader)
        got = []
        for batch in it:
            got.append(_copy(batch))
            if take is not None and len(got) == take:
                break
        it.close()
        counter = loader._round_counter
        loader.close()
        epochs.append(got)
    return epochs


@pytest.mark.parametrize("tf", [AUG, PLAIN], ids=["aug", "plain"])
def test_three_epochs_through_one_ring_are_bit_identical(tf, rings):
    def body():
        ds = _dataset(tf)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), seed=11)
        got = [[_copy(b) for b in loader] for _ in range(3)]
        assert len(rings) == 1
        loader.close()
        # (1) the one-shot assembly of the same indices and seeds
        ds2 = _dataset(tf)
        twin = NativeFedLoader(ds2, _sampler(ds2, seed=5), seed=11)
        r = 0
        for epoch in got:
            specs = [s for s in twin.sampler if len(s) >= twin.W]
            assert len(specs) == len(epoch) == ROUNDS
            for spec, batch in zip(specs, epoch):
                ids, idx = twin._spec_to_indices(spec)
                x, y, m = twin.plane.assemble(idx, 11 + r)
                _same(batch, {"client_ids": ids, "x": x, "y": y,
                              "mask": m})
                r += 1
        # (2) a ring an epoch
        for mine, theirs in zip(got, _ring_per_epoch(tf, 11, [None] * 3)):
            assert len(mine) == len(theirs)
            for a, b in zip(mine, theirs):
                _same(a, b)
    _within(60, body)


def test_held_batches_are_never_written_again():
    import jax.numpy as jnp

    def body():
        ds = _dataset(AUG)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), seed=3,
                                 depth=2)
        held, copies, on_device = [], [], []
        for _ in range(2):
            for i, batch in enumerate(loader):
                copies.append(_copy(batch))
                if i % 3 == 0:
                    # only a sub-view survives: it holds the owner
                    held.append({k: batch[k][:1] for k in KEYS})
                elif i % 3 == 1:
                    # may alias the numpy memory for the array's life
                    on_device.append((len(copies) - 1,
                                      jnp.asarray(batch["x"])))
                    held.append(None)
                else:
                    held.append(batch)
                del batch
        assert len(copies) == 2 * ROUNDS
        list(loader)        # a third epoch pops over whatever was freed
        for h, c in zip(held, copies):
            if h is not None:
                for k in KEYS:
                    np.testing.assert_array_equal(h[k], c[k][:len(h[k])])
        for i, dev in on_device:
            np.testing.assert_array_equal(np.asarray(dev), copies[i]["x"])
        loader.close()
    _within(60, body)


class _Counts:
    """A recorder that keeps the per-pop buffer counters in order."""

    def __init__(self):
        self.pops = []

    def count(self, name, n=1):
        if name.startswith("data.buffer_"):
            self.pops.append(name)

    def span(self, name):
        return contextlib.nullcontext()


@pytest.mark.parametrize("hold", [False, True], ids=["dropped", "held"])
def test_buffer_counters_say_whether_a_pop_reused_memory(hold):
    def body():
        ds = _dataset(PLAIN)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5))
        loader.telemetry = tel = _Counts()
        kept = []
        for _ in range(2):
            for batch in loader:        # the loop variable holds one
                if hold:
                    kept.append(batch)
        assert len(tel.pops) == 2 * ROUNDS
        if hold:
            assert set(tel.pops) == {"data.buffer_fresh"}
            assert len(loader._ring._pool) == 2 * ROUNDS
        else:
            # the second pop finds the first batch still in the loop
            # variable; from the third on nothing fresh is needed
            assert tel.pops[0] == "data.buffer_fresh"
            assert set(tel.pops[2:]) == {"data.buffer_reused"}
            assert tel.pops.count("data.buffer_fresh") <= 2
            assert len(loader._ring._pool) <= 2
        del kept, batch
        # once let go, all but the reserve is given back
        next(iter(loader))
        assert len(loader._ring._pool) <= 1 + native.Prefetcher._POOL_RESERVE
        loader.close()
    _within(60, body)


@pytest.mark.parametrize("taken", [1, 5], ids=["after_1", "after_depth_plus_1"])
def test_an_abandoned_epoch_leaves_the_ring_in_step(taken, rings):
    def body():
        ds = _dataset(AUG)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), seed=7,
                                 depth=4)
        assert taken in (1, loader.depth + 1)
        got = []
        for take in (taken, None, taken, None):
            it = iter(loader)
            epoch = []
            for batch in it:
                epoch.append(_copy(batch))
                if len(epoch) == take:
                    break
            del it, batch           # collected: the generator is closed
            got.append(epoch)
        assert [len(e) for e in got] == [taken, ROUNDS, taken, ROUNDS]
        assert len(rings) == 1
        loader.close()
        want = _ring_per_epoch(AUG, 7, (taken, None, taken, None))
        for mine, theirs in zip(got, want):
            assert len(mine) == len(theirs)
            for a, b in zip(mine, theirs):
                _same(a, b)
    _within(60, body)


def test_a_new_iter_retires_an_unfinished_one(rings):
    def body():
        ds = _dataset(PLAIN)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5))
        old = iter(loader)
        next(old)
        new = iter(loader)
        first = _copy(next(new))
        with pytest.raises(RuntimeError, match="retired"):
            next(old)
        del old                     # its clean-up must not touch the ring
        rest = [_copy(b) for b in new]
        assert len(rest) == ROUNDS - 1 and len(rings) == 1
        loader.close()
        want = _ring_per_epoch(PLAIN, 0, (1, None))[1]
        for a, b in zip([first] + rest, want):
            _same(a, b)
    _within(60, body)


def _ring_threads(want=None):
    """Live threads named ``cet-ring``; with ``want``, read again for
    up to 2 s until it is that many (a joined thread's /proc entry may
    outlive the join by a moment)."""
    deadline = time.monotonic() + 2.0
    while True:
        n = _count_ring_threads()
        if want is None or n == want or time.monotonic() > deadline:
            return n
        time.sleep(0.01)


def _count_ring_threads():
    names = []
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                names.append(f.read().strip())
        except OSError:     # the thread ended meanwhile
            pass
    return names.count("cet-ring")


@pytest.mark.skipif(not os.path.isdir("/proc/self/task"),
                    reason="needs /proc")
def test_close_joins_the_threads_and_a_closed_loader_reopens(rings):
    def body():
        gc.collect()        # rings that earlier tests left to the collector
        before = _ring_threads()
        ds = _dataset(PLAIN)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), n_threads=3)
        loader.close()      # nothing made yet
        assert len(rings) == 0
        a = [_copy(b) for b in loader]
        assert _ring_threads() == before + 3 and len(rings) == 1
        loader.close()
        loader.close()
        assert _ring_threads(before) == before
        it = iter(loader)
        next(it)
        assert len(rings) == 2 and _ring_threads() == before + 3
        loader.close()      # mid-epoch: the epoch is retired with the ring
        assert _ring_threads(before) == before
        with pytest.raises(RuntimeError, match="retired"):
            next(it)
        assert len(a) == ROUNDS
    _within(60, body)


def test_a_failed_pop_does_not_poison_the_next_epoch():
    def body():
        ds = _dataset(PLAIN)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5))
        good = loader._spec_to_indices

        def bad(round_spec):
            ids, idx = good(round_spec)
            idx[0, 0] = 10 ** 6
            return ids, idx

        loader._spec_to_indices = bad
        with pytest.raises(IndexError):
            list(loader)
        loader._spec_to_indices = good
        assert len(list(loader)) == ROUNDS
        loader.close()
    _within(60, body)


def test_ring_reset_discards_what_was_submitted():
    images = np.random.RandomState(0).randint(
        0, 256, (64, 16, 16, 3)).astype(np.uint8)
    targets = np.arange(64, dtype=np.int32) % 7
    plane = native.NativeDataplane(images, targets, slots=3, B=5,
                                   mean=MEAN, std=STD, crop_pad=2,
                                   do_flip=True)
    rng = np.random.RandomState(1)
    specs = [rng.randint(-1, 64, (3, 5)).astype(np.int64)
             for _ in range(20)]

    def body():
        with native.Prefetcher(plane, depth=3, n_threads=2) as pf:
            pf.reset()                      # an empty ring: nothing to do
            for popped in (0, 1, 2, 5):
                # 2 * depth queued at most: a submit never blocks here
                for i, s in enumerate(specs[:6]):
                    pf.submit(s, i)
                for _ in range(popped):
                    pf.pop()
                pf.reset()
                for i, s in enumerate(specs[10:14]):
                    pf.submit(s, 50 + i)
                for i, s in enumerate(specs[10:14]):
                    want = plane.assemble(s, 50 + i)
                    for a, b in zip(pf.pop(), want):
                        np.testing.assert_array_equal(a, b)
    _within(60, body)


# --- the loader's thread, across an epoch's end --------------------------


def _place(batch):
    """A placement as a loader sees one: any callable of the batch. With
    one, the loader makes its batches on a thread of its own, which
    opens the next epoch while this one is dealt (data/loader.py)."""
    return {k: np.array(v) for k, v in batch.items()}


def _stage_threads():
    return [t for t in threading.enumerate() if t.name == "loader-stage"]


@pytest.mark.parametrize("tf", [AUG, PLAIN], ids=["aug", "plain"])
def test_the_ring_does_not_drain_where_the_next_epoch_is_opened(tf, rings):
    """Read ahead, the next epoch's first ``depth`` + 1 rounds are in
    the ring when this one's last is taken, and what a checkpoint would
    record there is the boundary, not where the thread stands. The three
    epochs are those of a ring an epoch, bit for bit."""
    def body():
        ds = _dataset(tf)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), seed=11)
        loader.placement = _place
        got = []
        for e in range(3):
            it = iter(loader)
            got.append([_copy(next(it)) for _ in range(ROUNDS)])
            loader.settle()
            # the last round is taken, not yet the end: the thread has
            # submitted depth rounds of the next epoch and made one more
            held = loader.held_back()
            assert held["loader_round_counter"] == ROUNDS * (e + 1)
            assert loader._round_counter \
                == ROUNDS * (e + 1) + loader.depth + 1
            assert "dropout_rng" in held and "np_global_rng" not in held
            assert next(it, None) is None
        assert len(rings) == 1 and len(_stage_threads()) == 1
        loader.close()
        for mine, theirs in zip(got, _ring_per_epoch(tf, 11, [None] * 3)):
            assert len(mine) == len(theirs)
            for a, b in zip(mine, theirs):
                _same(a, b)
    _within(60, body)


@pytest.mark.skipif(not os.path.isdir("/proc/self/task"),
                    reason="needs /proc")
def test_close_drops_an_epoch_nobody_adopted(rings):
    """The run's last epoch has a successor opened too: ``close()``
    stops the loader's thread, which empties the ring it owns, and then
    the ring goes; nothing is left running, and the loader reopens."""
    def body():
        gc.collect()
        before = _ring_threads()
        ds = _dataset(AUG)
        loader = NativeFedLoader(ds, _sampler(ds, seed=5), seed=3)
        loader.placement = _place
        assert len(list(loader)) == ROUNDS
        loader.settle()
        ring = loader._ring
        assert loader.held_back() and len(_stage_threads()) == 1
        resets = []
        real = ring.reset
        ring.reset = lambda: (resets.append(1), real())[1]
        loader.close()
        assert resets == [1]            # by the thread that owned it
        assert loader._ring is None and loader._epoch is None
        assert loader._reader is None and not loader.held_back()
        assert not _stage_threads()
        assert _ring_threads(before) == before
        loader.close()
        # (a third epoch by the sampler's count: 7 or 8 whole rounds)
        assert len(list(loader)) >= ROUNDS - 1 and len(rings) == 2
        loader.close()
        assert not _stage_threads()
    _within(60, body)

"""C++ data-plane vs the Python loader path.

No-augmentation assembly must match FedLoader bit-for-bit; augmented
output must be a member of the enumerable crop/flip candidate set;
prefetch-ring pops must equal one-shot assembly in submission order.
Skipped wholesale when no toolchain is present."""

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
    import os

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

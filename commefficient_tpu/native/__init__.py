"""Native (C++) federated data-plane bindings.

Builds ``fed_dataplane.cpp`` on first use with the in-image g++ (no
pybind11 — plain C ABI via ctypes; ctypes releases the GIL around
calls, so ring pops block without stalling Python). Falls back cleanly
when no toolchain is available: callers must check :func:`available`.

Counterpart of the reference's native data plumbing (multiprocessing
queues + torchvision C++ transform kernels, SURVEY.md §2.9).

A :class:`Prefetcher` allocates its round memory once. The ring and its
worker threads live until ``close()``; an epoch that ends or is
abandoned leaves the ring empty (``reset()``), not destroyed: making a
ring first-touches ``depth`` rounds of output, and on the benchmark's
host fresh anonymous pages cost 1.09 ms/MB on whichever thread touches
them. For the same reason ``pop()`` copies the slot into a recycled
buffer, not a fresh ``np.empty``. The reuse rule: a buffer goes out
again only when nothing but the pool refers to it, read off the
reference counts of the arrays that own the memory at pop time. Views
and sub-views hold their owner (numpy collapses ``base`` chains onto
it, so a finalizer on a handed-out view would fire too early), PJRT
holds the array until an asynchronous host-to-device copy completes,
and on the CPU backend ``jnp.asarray`` may alias it for the device
array's whole life: each of them is a reference, and the buffer waits.
With none free the pool allocates (and keeps) a fresh one, so it never
blocks. The ring's own slots are not handed out without a copy: a slot
has to be released by a call, and no call can be made safe against
those aliases without a knob.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "fed_dataplane.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_LOCK = threading.Lock()
_lib_handle = None
_build_failed = False
_RING_THREADS = [0]     # worker threads of the rings now open


def ring_threads() -> int:
    """C++ worker threads the open ``Prefetcher`` rings hold: what the
    round records' ``host.threads`` adds to the Python threads."""
    return _RING_THREADS[0]


def _compile() -> Optional[str]:
    """Path of the library built from ``fed_dataplane.cpp`` as it is
    now, building it if absent. The file name carries a hash of the
    source: ``_build/`` is git-ignored but survives on disk and in
    copies of the tree, where mtimes say nothing, and a binary that
    does not match the source must never be loaded."""
    try:
        with open(_SRC, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:12]
        so = os.path.join(_BUILD_DIR, f"libfed_dataplane-{tag}.so")
        if os.path.exists(so):
            return so
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"  # concurrent first uses
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
             "-pthread", _SRC, "-o", tmp],
            check=True, capture_output=True)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.CalledProcessError):
        return None


def _lib():
    global _lib_handle, _build_failed
    with _LOCK:
        if _lib_handle is not None or _build_failed:
            return _lib_handle
        so = _compile()
        if so is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        ci = ctypes.c_int
        i64 = ctypes.c_int64
        lib.cet_assemble_round.argtypes = [
            u8p, f32p, i32p, i64, ci, ci, ci, ci, ci, ci, ci,
            f32p, f32p, i64p, ctypes.c_uint64, f32p, i32p, f32p]
        lib.cet_assemble_round.restype = ctypes.c_int
        lib.cet_ring_create.argtypes = [
            u8p, f32p, i32p, i64, ci, ci, ci, ci, ci, ci, ci,
            f32p, f32p, ci, ci]
        lib.cet_ring_create.restype = ctypes.c_void_p
        lib.cet_ring_submit.argtypes = [ctypes.c_void_p, i64p,
                                        ctypes.c_uint64]
        lib.cet_ring_submit.restype = None
        lib.cet_ring_pop.argtypes = [ctypes.c_void_p, f32p, i32p, f32p]
        lib.cet_ring_pop.restype = ctypes.c_int64
        lib.cet_ring_oob.argtypes = [ctypes.c_void_p]
        lib.cet_ring_oob.restype = ctypes.c_longlong
        lib.cet_ring_reset.argtypes = [ctypes.c_void_p]
        lib.cet_ring_reset.restype = None
        lib.cet_ring_destroy.argtypes = [ctypes.c_void_p]
        lib.cet_ring_destroy.restype = None
        _lib_handle = lib
        return lib


def available() -> bool:
    return _lib() is not None


def _ptr(arr, ctype):
    if arr is None:
        return ctypes.cast(None, ctypes.POINTER(ctype))
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class NativeDataplane:
    """Round assembly over a dense in-memory image store.

    ``images``: (N, H, W, C) uint8 (raw, scaled by 1/255 natively) or
    float32 in [0, 1]. ``targets``: (N,) int32. Augmentation =
    reflect-pad random crop (``crop_pad``) + horizontal flip
    (``do_flip``) + per-channel normalize — the CIFAR/FEMNIST stacks.
    """

    def __init__(self, images: np.ndarray, targets: np.ndarray,
                 slots: int, B: int, mean, std,
                 crop_pad: int = 0, do_flip: bool = False):
        lib = _lib()
        if lib is None:
            raise RuntimeError("native dataplane unavailable")
        if images.ndim != 4:
            raise RuntimeError(
                f"need (N, H, W, C) images, got {images.shape}")
        self._lib = lib
        # keep alive: the C side borrows these buffers
        self.images = np.ascontiguousarray(images)
        self.targets = np.ascontiguousarray(targets, dtype=np.int32)
        self.slots, self.B = slots, B
        _, self.H, self.W, self.C = self.images.shape
        assert self.C <= 8
        self.mean = np.ascontiguousarray(
            np.broadcast_to(np.asarray(mean, np.float32), (self.C,)))
        self.std = np.ascontiguousarray(
            np.broadcast_to(np.asarray(std, np.float32), (self.C,)))
        self.crop_pad, self.do_flip = crop_pad, int(do_flip)
        if self.images.dtype == np.uint8:
            self._u8, self._f32 = self.images, None
        elif self.images.dtype == np.float32:
            self._u8, self._f32 = None, self.images
        else:
            raise RuntimeError(
                f"unsupported image dtype {self.images.dtype} "
                "(uint8 or float32)")

    def _common_args(self):
        return (_ptr(self._u8, ctypes.c_uint8),
                _ptr(self._f32, ctypes.c_float),
                _ptr(self.targets, ctypes.c_int32),
                ctypes.c_int64(self.images.shape[0]),
                self.H, self.W, self.C, self.slots, self.B,
                self.crop_pad, self.do_flip,
                _ptr(self.mean, ctypes.c_float),
                _ptr(self.std, ctypes.c_float))

    def _alloc_out(self):
        x = np.empty((self.slots, self.B, self.H, self.W, self.C),
                     np.float32)
        y = np.empty((self.slots, self.B), np.int32)
        m = np.empty((self.slots, self.B), np.float32)
        return x, y, m

    def assemble(self, indices: np.ndarray, seed: int):
        """indices: (slots, B) int64 storage rows, -1 = padding."""
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        assert idx.shape == (self.slots, self.B), idx.shape
        x, y, m = self._alloc_out()
        oob = self._lib.cet_assemble_round(
            *self._common_args(), _ptr(idx, ctypes.c_int64),
            ctypes.c_uint64(seed & (2**64 - 1)),
            _ptr(x, ctypes.c_float), _ptr(y, ctypes.c_int32),
            _ptr(m, ctypes.c_float))
        if oob:
            raise IndexError(
                f"{oob} indices out of range for {self.images.shape[0]}"
                " stored rows")
        return x, y, m


class Prefetcher:
    """Bounded ring of pre-assembled rounds, filled by C++ worker
    threads; pops arrive strictly in submission order (deterministic
    regardless of thread scheduling). One thread at a time submits,
    pops and resets: no call here is made safe against another. Under
    ``NativeFedLoader`` that is, for the length of an epoch, the thread
    that iterates the epoch (the loader's own where it places batches
    ahead, else the consumer's), and whoever stops the epoch joins
    that thread before it resets or closes the ring. Popped rounds land
    in recycled buffers (module docstring): hold a batch for as long as
    you like, it is not written again while anything refers to it; a
    batch staged on the device a round ahead is one more such holder,
    so the pool then keeps one buffer more."""

    #: free buffers kept beyond the ones the consumer still holds
    _POOL_RESERVE = 2

    def __init__(self, plane: NativeDataplane, depth: int = 4,
                 n_threads: int = 2, telemetry=None):
        """``telemetry``: the caller's span recorder (the loader sets
        the attribute again at each epoch); None records nothing."""
        if telemetry is None:
            from commefficient_tpu.telemetry import NULL_TELEMETRY
            telemetry = NULL_TELEMETRY
        self.plane = plane
        self.telemetry = telemetry
        self._pool: list = []       # every (x, y, m) this ring handed out
        self._pool_only = None      # _refs() of a buffer only the pool holds
        # allocates and zero-fills ``depth`` rounds of output
        with telemetry.span("data.ring_open"):
            self._handle = plane._lib.cet_ring_create(
                *plane._common_args(), depth, n_threads)
        assert self._handle
        self._n_threads = n_threads
        with _LOCK:
            _RING_THREADS[0] += n_threads

    def submit(self, indices: np.ndarray, seed: int):
        with self.telemetry.span("data.submit"):
            idx = np.ascontiguousarray(indices, dtype=np.int64)
            assert idx.shape == (self.plane.slots, self.plane.B)
            self.plane._lib.cet_ring_submit(
                self._handle, _ptr(idx, ctypes.c_int64),
                ctypes.c_uint64(seed & (2**64 - 1)))

    @staticmethod
    def _refs(bufs) -> int:
        return max(map(sys.getrefcount, bufs))

    def _take_buffers(self):
        """(x, y, m) to pop into: the first pooled triple that only the
        pool refers to, else a fresh one. Free triples beyond the
        reserve are let go, so the pool is what the consumer holds
        plus ``_POOL_RESERVE``."""
        held, free = [], []
        for bufs in self._pool:
            (free if self._refs(bufs) == self._pool_only
             else held).append(bufs)
        self._pool = held + free[:1 + self._POOL_RESERVE]
        if free:
            self.telemetry.count("data.buffer_reused")
            return free[0]
        bufs = self.plane._alloc_out()
        self._pool.append(bufs)
        if self._pool_only is None:
            self._pool_only = self._refs(bufs)
        self.telemetry.count("data.buffer_fresh")
        return bufs

    def pop(self):
        tel = self.telemetry
        with tel.span("data.pop_alloc"):
            x, y, m = self._take_buffers()
        # the wait for the C++ plane, and nothing else
        with tel.span("data.pop_wait"):
            seq = self.plane._lib.cet_ring_pop(
                self._handle, _ptr(x, ctypes.c_float),
                _ptr(y, ctypes.c_int32), _ptr(m, ctypes.c_float))
        assert seq >= 0, "ring stopped"
        oob = self.plane._lib.cet_ring_oob(self._handle)
        if oob:
            raise IndexError(
                f"{oob} out-of-range indices submitted to the ring")
        return x, y, m

    def reset(self):
        """Empty the ring where an epoch stopped: queued specs are
        discarded, slots being filled are waited for and freed, the
        sequence starts again."""
        if self._handle:
            self.plane._lib.cet_ring_reset(self._handle)

    def close(self):
        if self._handle:
            with self.telemetry.span("data.ring_close"):
                self.plane._lib.cet_ring_destroy(self._handle)
            self._handle = None
            self._pool = []
            with _LOCK:
                _RING_THREADS[0] -= self._n_threads

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:
            pass


def native_transform_spec(transform) -> Optional[dict]:
    """Map a data/transforms.py Compose onto the native augmentation
    pipeline, which is exactly ``ToFloat -> [RandomCrop(reflect)] ->
    [RandomHorizontalFlip] -> Normalize`` in that order (the CIFAR /
    FEMNIST-val stacks). Anything else — different order, missing
    ToFloat (the native path always scales uint8 by 1/255), extra
    ops — returns None and the caller falls back to the Python
    loader, so the two paths can never silently diverge."""
    from commefficient_tpu.data import transforms as T

    if not isinstance(transform, T.Compose):
        return None
    ts = list(transform.transforms)
    if not ts or not isinstance(ts.pop(0), T.ToFloat):
        return None
    crop_pad, do_flip, crop_size = 0, False, None
    if ts and isinstance(ts[0], T.RandomCrop):
        t = ts.pop(0)
        if t.fill is not None:
            return None
        crop_pad, crop_size = t.padding, t.size
    if ts and isinstance(ts[0], T.RandomHorizontalFlip):
        ts.pop(0)
        do_flip = True
    if len(ts) != 1 or not isinstance(ts[0], T.Normalize):
        return None
    norm = ts[0]
    return {"crop_pad": crop_pad, "do_flip": do_flip,
            "crop_size": crop_size,  # must equal image H/W (checked
            "mean": norm.mean, "std": norm.std}  # by the loader)

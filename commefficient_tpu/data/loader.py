"""Round-batch construction: sampler output -> fixed-shape padded
engine batches.

The reference ships a flat concatenated tensor batch to the server,
which re-groups rows by client id and queues them to worker processes
(fed_aggregator.py:214-238). Here the loaders themselves emit the
static (W, B, ...) layout the jitted round wants — client axis first,
a (W, B) mask for ragged clients — so the device never sees a dynamic
shape (SURVEY.md §7).

``_RoundLoaderBase`` holds the shared mechanics (B/W resolution,
incomplete-round skipping, epoch length); subclasses provide only
``collate``. Same split for the sharded validation loaders.

The train loaders open up the trainer's ``sampler`` span (the wait in
``next(loader)``): spans ``data.sample`` (advancing the sampler),
``data.index``, ``data.submit``, ``data.pop_alloc``, ``data.pop_wait``
(the native ring: only the C++ plane and its copy into a recycled
buffer are behind it), ``data.ring_open`` / ``data.ring_close`` (once a
loader: the ring is made at the first ``__iter__`` and kept until
``close()``, native/__init__.py), ``data.collate``, and the counters
``data.epoch_start`` (1 on the ``next()`` that entered a fresh
``__iter__``), ``data.epoch_preopened`` (1 beside it where that
``__iter__`` took an epoch the loader's thread had opened ahead) and
``data.buffer_reused`` / ``data.buffer_fresh`` (per pop of the native
ring: whether the round landed in memory the process had touched
before). A loader is built before the run's ``Telemetry``: who builds
both hands it over (``loader.telemetry = model.telemetry``); left
unset, each epoch looks it up once, on the consumer's thread, in
``telemetry.current()``.

**Read-ahead.** Where the consuming model's placement is known
(``loader.placement``, handed over like the recorder, or
``staging.current()``: data/staging.py), the batches are made on a
thread of the loader's own (``_ReadAhead``): while the round loop
waits for round r, that thread advances the sampler, indexes, submits,
pops and drops out round r+1 and then places it on the device (span
``data.stage``), so ``next(loader)`` hands over a batch that is already
resident or in flight. The spans above then run on that thread, with no
parent; the consumer's own wait, for the hand-over, is a
``data.pop_wait`` under ``sampler``. One round ahead (``prefetch_depth``
rounds for the language-model loaders, whose thread existed before):
the thread makes a round only against a credit, whatever epoch the
round is of.

**Across an epoch's end.** The thread is started by the ``next()`` that
enters the first epoch, which opens that epoch itself (the sampler's
``__iter__`` and its first advance: a ``data.sample`` on the consumer's
thread, which could only wait meanwhile), and from then on it is the
loader's for its life. When the sampler has dealt an epoch's last round
the thread puts an ``_EpochEnd`` into its stream of rounds and opens the
next epoch there and then (parentless span ``data.epoch_open`` around
the ``data.sample`` of the opening), so that the permutations of epoch
e+1 run while the device computes what is left of epoch e, the native
ring takes the new epoch's submissions without draining, and the
``__iter__`` that follows *adopts* the opened epoch: its first
``next()`` finds the first ``ahead`` round(s) made and placed. The
rounds run out on the thread where it asks for one more than the
sampler has: for the native loader as it tops up the ring behind the
epoch's last round, ``depth`` rounds of device work before the new
epoch's first batch is asked for, and for the others one round before.
(No earlier, between two rounds once the sampler's look-ahead is
empty: the consumer's wait for the batch made next to the opening is
under half a millisecond a round in the ResNet9 cell as it is, PERF.md
section 6, PR 46.)
It engages where the sampler draws from a stream of its own
(``sampler.rng`` a ``np.random.RandomState``): one that draws from the
``np.random`` module shares that stream with whoever else reads it,
and its epochs are opened on the consumer's thread, one thread an
epoch, as before.

Batches, their order and every RNG stream are those of the loader
without read-ahead: one thread draws, in one order (epoch e's rounds,
then epoch e+1's permutations, then its rounds). What a checkpoint
records of those streams is another matter, since the thread is past
the point the consumer has reached:

- a round the thread has made and not handed over is one more round
  *in flight*, as the native ring's ``depth`` are: drawn from the
  sampler, and lost to a mid-epoch checkpoint, which continues
  bit-exactly from the round after them (``settle()`` keeps such a
  checkpoint from reading the streams while the thread advances them);
- an epoch's end is not in flight. Each ``_EpochEnd`` holds the streams
  as they stood at that end (the sampler's when its last round was
  dealt, the drop-out's, the data set's and the transforms' when the
  epoch's last batch was made), and until the consumer has entered the
  epoch after it ``held_back()`` answers with them: a checkpoint at the
  boundary (runtime/checkpoint.py) stores the state from which the next
  epoch opens, and a run resumed from it deals that epoch's first round
  as the uninterrupted run does.

An epoch abandoned mid-way whose successor is not opened takes the
thread with it (stopped and joined where the generator is closed or
collected), and so does ``close()``, which also drops an opened epoch
nobody adopted: its draws are spent, as an in-flight round's are. An
abandoned epoch whose successor *is* opened is dealt to its end into
nothing, and the next ``__iter__`` adopts the successor. An error raised
while opening ahead is raised from the ``next()`` that would have
entered that epoch. With no placement the CV loaders make their batches
on the consumer's thread, as before.
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Iterator, Optional

import numpy as np

from commefficient_tpu import telemetry
from commefficient_tpu.data import staging

__all__ = ["FedLoader", "ValLoader", "PersonaFedLoader",
           "PersonaValLoader", "TokenFedLoader", "TokenValLoader",
           "NativeFedLoader", "make_fed_loader"]


class _EpochEnd:
    """Where one epoch ends and the next begins in the stream of rounds
    the loader's thread makes. ``streams`` holds what a checkpoint
    reads of the data path as it stood at this end, by the names
    ``held_back()`` gives them: the thread is past it before the
    consumer is."""

    def __init__(self, streams: dict):
        self.streams = streams


class _ReadAhead:
    """The loader's batches, made by a thread of its own at most
    ``ahead`` rounds before the consumer asks for them.

    ``batches`` is the generator of the rounds, epoch after epoch with
    an ``_EpochEnd`` between two, not yet started: it is advanced and
    closed on the thread alone, so what it owns (the sampler's
    iterator, the native ring) keeps one owner at a time. The thread
    makes a round only against a credit, and the consumer gives one
    back with each batch it takes: the thread is never more than
    ``ahead`` rounds in front, and what it has made always has room.
    An epoch's end costs no credit and gives none. The thread's one
    wait is for a credit, and ``stop()`` ends that wait. Each side
    waits in steps of ``_POLL`` seconds and looks, between them, at
    ``stop`` and at whether the other side still lives."""

    _POLL = 0.1
    _JOIN = 60.0    # seconds a round in the making may take to end
    #: every reader whose thread may be alive (weakly: the tests stop
    #: what an earlier test left running, tests/conftest.py)
    live = weakref.WeakSet()

    def __init__(self, batches, ahead: int, name: str):
        self._batches = batches
        self._cv = threading.Condition()
        self._credits = ahead
        self._ready = collections.deque()   # (kind, value), oldest first
        self.stopped = False    # stop() was called, by whoever held it
        self._idle = False      # waiting for a credit, or ended
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self.live.add(self)
        self._thread.start()

    # --- the loader's thread -------------------------------------------

    def _run(self):
        it = self._batches
        try:
            while self._take_credit():
                batch = next(it, None)
                while isinstance(batch, _EpochEnd):
                    self._hand(("end", batch))
                    batch = next(it, None)
                if batch is None:
                    self._hand(("done", None))
                    return
                self._hand(("batch", batch))
        except BaseException as e:  # raised from the consumer's next()
            self._hand(("error", e))
        finally:
            try:
                # here, not where the consumer stops: an unfinished
                # native epoch empties its ring on the owning thread
                it.close()
            finally:
                with self._cv:
                    self._idle = True
                    self._cv.notify_all()

    def _take_credit(self) -> bool:
        with self._cv:
            self._idle = True
            self._cv.notify_all()
            while not self._credits and not self.stopped:
                self._cv.wait(self._POLL)
            if self.stopped:
                return False
            self._credits -= 1
            self._idle = False
            return True

    def _hand(self, item):
        with self._cv:
            self._ready.append(item)
            self._cv.notify_all()

    # --- the consumer's thread -----------------------------------------

    def take(self):
        """The oldest ``(kind, value)`` the thread has made, waited for
        if need be; the thread gets a credit for a batch."""
        with self._cv:
            while True:
                if self.stopped:
                    raise RuntimeError(
                        "this epoch was retired: a later __iter__ (or "
                        "close()) took the loader")
                if self._ready:
                    break
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "the loader's thread ended without a result")
                self._cv.wait(self._POLL)
            item = self._ready.popleft()
            if item[0] == "batch":
                self._credits += 1
                self._idle = False      # it has a round to make
                self._cv.notify_all()
            return item

    def skip_epoch(self) -> bool:
        """Take what is left of the consumer's epoch and drop it.
        False where the thread ended first (its error goes with it)."""
        while True:
            kind, _ = self.take()
            if kind != "batch":
                return kind == "end"

    def peek(self):
        """The batch the next ``take()`` of one returns, if it is made:
        past an epoch's end, the first of the epoch opened ahead."""
        with self._cv:
            kind, val = next((item for item in self._ready
                              if item[0] != "end"), (None, None))
        return val if kind == "batch" else None

    def settle(self):
        """Wait until the thread is between rounds (or has ended). It
        stays there: only ``take()`` hands it a credit."""
        if self._thread is threading.current_thread():
            # a generator finalized by a collection that ran here
            raise RuntimeError("the loader's thread cannot wait for itself")
        with self._cv:
            while not self._idle and self._thread.is_alive():
                self._cv.wait(self._POLL)

    def stop(self):
        """End the thread wherever it is and join it: what it owned is
        free again when this returns. Idempotent."""
        with self._cv:
            self.stopped = True
            self._ready.clear()
            self._cv.notify_all()
        t = self._thread
        if t is not threading.current_thread():
            t.join(self._JOIN)
            if t.is_alive():
                raise RuntimeError(
                    f"the loader's thread {t.name} did not end within "
                    f"{self._JOIN:g} s of being stopped")


class _RoundLoaderBase:
    """Iterate federated train rounds. Rounds with fewer than
    ``num_workers`` distinct clients are skipped, matching the
    reference's run_batches guard (cv_train.py:205-219).

    ``dropout_prob`` injects client failures: each sampled client
    independently drops with that probability — its mask rows are
    zeroed, the engine excludes its transmit and leaves its
    momentum/error state untouched, and the aggregate renormalises
    over survivors (fault injection the reference lacks, SURVEY §5).
    A fully-dropped round still executes with a zero aggregate (the
    server's momentum coasts), keeping round counts, RNG streams and
    the LR schedule identical across the Python and native loaders."""

    def __init__(self, dataset, sampler,
                 max_batch_size: Optional[int] = None,
                 dropout_prob: float = 0.0, dropout_seed: int = 0):
        self.dataset = dataset
        self.sampler = sampler
        if max_batch_size is not None:
            self.B = max_batch_size
        elif sampler.local_batch_size != -1:
            self.B = sampler.local_batch_size
        else:
            self.B = int(np.max(dataset.data_per_client))
        self.W = sampler.num_workers
        self.dropout_prob = dropout_prob
        self._dropout_rng = np.random.RandomState(dropout_seed)

    def _apply_dropout(self, batch: dict) -> dict:
        """Zero dropped clients' mask rows."""
        if self.dropout_prob <= 0.0:
            return batch
        drop = self._dropout_rng.rand(self.W) < self.dropout_prob
        if drop.any():
            batch = dict(batch)
            mask = batch["mask"].copy()
            mask[drop] = 0.0
            batch["mask"] = mask
        return batch

    #: the run's recorder; None: ``telemetry.current()`` at each epoch
    telemetry = None

    def _round_specs(self, tel):
        """The sampler's complete rounds (fewer than ``W`` clients:
        skipped), each advance of the sampler under a ``data.sample``
        span. The epoch is opened here, on the caller's thread: a
        sampler's ``__iter__`` may do an epoch's work (FedSampler
        permutes every client's indices). For a loader's first epoch
        that is the consumer's thread, which can only wait meanwhile
        (and on the chip that work ran a quarter slower on a fresh
        thread: PERF.md section 6, PR 28); for an epoch opened ahead it
        is the loader's, while the device works. Returns the generator
        of the rounds, to be advanced on any one thread."""
        with tel.span("data.sample"):
            it = iter(self.sampler)
            first = next(it, None)

        def rounds(round_spec=first):
            while round_spec is not None:
                if len(round_spec) >= self.W:
                    yield round_spec
                with tel.span("data.sample"):
                    round_spec = next(it, None)
        return rounds()

    def _opens_ahead(self) -> bool:
        """Whether the loader's thread may open the next epoch while
        this one is dealt: the sampler's draws then come earlier than
        its consumer asks for them, from another thread, which only a
        stream of the sampler's own allows."""
        return isinstance(getattr(self.sampler, "rng", None),
                          np.random.RandomState)

    def _spec_streams(self) -> dict:
        """What dealing the rounds advances, as ``held_back()`` names
        it: read where the sampler has dealt an epoch's last round."""
        sampler = self.sampler
        export = getattr(sampler, "export_state", None)
        return {"sampler_rng": sampler.rng.get_state(),
                "sampler_mid": None if export is None else export()}

    def _batch_streams(self) -> dict:
        """What making the batches advances: read where an epoch's last
        batch is made."""
        ds_rng = getattr(self.dataset, "_rng", None)
        has = hasattr(ds_rng, "getstate")
        return {"dropout_rng": self._dropout_rng.get_state(),
                "dataset_rng": ds_rng.getstate() if has else None,
                # the numpy transforms' stream (data/transforms.py)
                "np_global_rng": np.random.get_state()}

    def _spec_stream(self, tel, specs, ends):
        """The loader's thread's rounds: those of the epoch ``specs``
        and, where ``_opens_ahead()``, of every epoch after it, each
        opened as soon as the one before is dealt, with the
        ``_EpochEnd`` between them (noted in ``ends``) handed on first:
        the consumer learns of the end before the opening's work
        begins."""
        while True:
            yield from specs
            if not self._opens_ahead():
                return
            end = _EpochEnd(self._spec_streams())
            ends.append(end)
            yield end
            with tel.span("data.epoch_open"):
                specs = self._round_specs(tel)

    def _batches(self, tel, specs) -> Iterator[dict]:
        """The batches of ``specs``, on whichever thread iterates; an
        ``_EpochEnd`` among them passes through."""
        for round_spec in specs:
            if isinstance(round_spec, _EpochEnd):
                yield round_spec
                continue
            with tel.span("data.collate"):
                batch = self.collate(round_spec)
                counted = self.round_counters(batch)
            batch = self._apply_dropout(batch)
            yield staging.note(batch, counted) if counted else batch

    #: the consuming model's ``place_batch`` (data/staging.py); None:
    #: ``staging.current()`` at each epoch
    placement = None
    _thread_name = "loader-stage"
    _reader = None      # the loader's _ReadAhead, while its thread lives
    _made_for = None    # the (recorder, placement) that reader works with
    _consumer = None    # the __iter__ that is mid-epoch on the reader
    #: the epoch ends the thread has passed and the consumer has not,
    #: oldest first
    _ends = ()

    def _host_ahead(self) -> int:
        """Rounds the loader's thread runs ahead of the consumer where
        nothing is placed; 0: no thread, the consumer makes them."""
        return 0

    def _staged_batches(self, tel, place, specs) -> Iterator[dict]:
        """``_batches`` with each placed on the device as its maker's
        last step (the model's own placement: data/staging.py), and
        each epoch's end given the streams as its last batch left
        them."""
        for batch in self._batches(tel, specs):
            if isinstance(batch, _EpochEnd):
                batch.streams.update(self._batch_streams())
            elif place is not None:
                with tel.span("data.stage"):
                    batch = staging.stage(batch, place)
            yield batch

    def __iter__(self) -> Iterator[dict]:
        self._retire()
        tel = self.telemetry
        if tel is None:
            tel = telemetry.current()
        place = self.placement
        if place is None:
            place = staging.current()
        reader = self._reader
        if reader is not None and (reader.stopped
                                   or self._made_for != (tel, place)):
            self._drop_reader()     # another run's: it opens anew
            reader = None
        tel.count("data.epoch_start")
        if reader is not None:
            # the epoch the thread opened when the last one was dealt
            tel.count("data.epoch_preopened")
            self._ends.popleft()
        else:
            # one round of device read-ahead wherever a batch can be
            # placed: a constant, the same for every loader and device
            ahead = max(self._host_ahead(), 0 if place is None else 1)
            specs = self._round_specs(tel)
            if not ahead:
                yield from self._batches(tel, specs)
                return
            self._ends = ends = collections.deque()
            self._made_for = (tel, place)
            reader = self._reader = _ReadAhead(
                self._staged_batches(
                    tel, place, self._spec_stream(tel, specs, ends)),
                ahead, self._thread_name)
        mine = self._consumer = object()
        kind = None
        try:
            while True:
                with tel.span("data.pop_wait"):
                    kind, val = reader.take()
                if kind != "batch":
                    break
                yield val
                if self._consumer is not mine:
                    raise RuntimeError(
                        "this epoch was retired: a later __iter__ (or "
                        "close()) took the loader")
            if kind == "error":
                raise val
        finally:
            if self._consumer is mine:
                if kind == "end":
                    # dealt whole, and the next is opened: the thread
                    # stays, for the __iter__ that adopts it
                    self._consumer = None
                else:
                    # abandoned (NaN abort, the generator closed or
                    # collected), or the thread ended with the epoch
                    self._retire()

    def _retire(self):
        """End the consumer's unfinished epoch, if there is one; its
        next ``next()`` raises. Where the thread has opened the epoch
        after it, what is left of this one is taken and dropped, and
        the thread stays for the ``__iter__`` that adopts the opened
        epoch; otherwise the thread is stopped and joined, and cannot
        race a later epoch over sampler or ring."""
        if self._consumer is None:
            return
        self._consumer = None
        try:
            self._reader.settle()
            kept = bool(self._ends) and self._reader.skip_epoch()
        except RuntimeError:        # stopped or dead already
            kept = False
        if not kept:
            self._drop_reader()

    def _drop_reader(self):
        """Stop and join the loader's thread; an unfinished epoch and
        an epoch opened ahead go with it."""
        reader, self._reader = self._reader, None
        self._consumer, self._ends = None, ()
        if reader is not None:
            reader.stop()

    def settle(self):
        """Return once the loader's thread is between rounds: the
        sampler, the dropout stream and the loader's counters are then
        whole, and stay so until the next ``next()`` (a checkpoint
        reads them, through ``held_back()``: runtime/checkpoint.py)."""
        reader = self._reader
        if reader is not None:
            reader.settle()

    def held_back(self) -> dict:
        """The streams of the data path that a checkpoint must not read
        where they stand, with what it records in their place: where
        the loader's thread has passed an epoch's end that the consumer
        has not, the state at that end (module docstring), under the
        names ``sampler_rng``, ``sampler_mid`` (the sampler's
        ``export_state()``: an epoch with no round left),
        ``loader_round_counter``, ``dropout_rng``, ``dataset_rng`` and
        ``np_global_rng``. The last three only once the epoch's last
        batch is made; {} where nothing is opened ahead. Call
        ``settle()`` first."""
        return dict(self._ends[0].streams) if self._ends else {}

    def peek_next_client_ids(self):
        """The participant ids of the round the consumer's next
        ``next()`` of a batch receives, one round ahead (the
        client-store prefetch feed, runtime/fed_model.py). None when
        the sampler can't see ahead or the peeked round is incomplete
        (it would be skipped above) — the consumer then falls back to
        a synchronous gather, so a miss costs latency, never
        correctness. With a thread reading ahead the sampler is past
        that round: the answer is the made batch's, or None. At an
        epoch's last round that is the first round of the epoch opened
        ahead, which the next ``__iter__`` hands over first, if it is
        made by then; with no epoch opened ahead it is None."""
        reader = self._reader
        if reader is not None:
            batch = reader.peek()
            return None if batch is None else batch["client_ids"]
        peek = getattr(self.sampler, "peek_next_client_ids", None)
        ids = peek() if peek is not None else None
        if ids is None or len(ids) < self.W:
            return None
        return ids

    def collate(self, round_spec) -> dict:
        raise NotImplementedError

    def round_counters(self, batch) -> dict:
        """What to count of a collated round on the record of the round
        that consumes it (``staging.note``; read by
        ``FedModel._client_pass``). Nothing, by default."""
        return {}

    def close(self):
        """Release what the loader keeps between epochs (its thread,
        with an epoch opened ahead that nobody adopted; the native
        loader's ring and its threads). Idempotent; a closed loader can
        be iterated again."""
        self._retire()
        self._drop_reader()

    def __len__(self):
        from commefficient_tpu.utils import steps_per_epoch
        return steps_per_epoch(self.sampler.local_batch_size,
                               self.dataset, self.W)


class FedLoader(_RoundLoaderBase):
    """CV rounds: ``client_ids`` (W,), ``x`` (W, B, ...) f32, ``y``
    (W, B) i32, ``mask`` (W, B) f32."""

    _img_shape = None

    def _probe_shape(self, idx):
        if self._img_shape is None:
            self._img_shape = np.asarray(self.dataset[int(idx)][1]).shape
        return self._img_shape

    def collate(self, round_spec) -> dict:
        W, B = self.W, self.B
        img_shape = self._probe_shape(round_spec[0][1][0])
        x = np.zeros((W, B) + img_shape, np.float32)
        y = np.zeros((W, B), np.int32)
        mask = np.zeros((W, B), np.float32)
        ids = np.zeros((W,), np.int32)
        for i, (cid, idxs) in enumerate(round_spec):
            ids[i] = cid
            for j, idx in enumerate(idxs[:B]):
                client_id, img, target = self.dataset[int(idx)]
                assert client_id == cid, (client_id, cid)
                x[i, j] = img
                y[i, j] = target
                mask[i, j] = 1.0
        return {"client_ids": ids, "x": x, "y": y, "mask": mask}


class NativeFedLoader(_RoundLoaderBase):
    """CV rounds assembled by the C++ data-plane with threaded
    prefetch (commefficient_tpu/native): gather + reflect-pad random
    crop + flip + normalize run GIL-free while the device steps.

    Same batch dict contract as FedLoader. Augmentation RNG is the
    native splitmix64 stream (deterministic per seed, a different
    stream than the numpy transforms); with augmentation off the
    output matches FedLoader bit-for-bit — tested in
    tests/test_native_dataplane.py.

    One ring for the loader's life: the ``native.Prefetcher`` (its
    ``depth`` rounds of output and its worker threads) is made at the
    first ``__iter__`` and reused by every later one, and popped rounds
    land in its recycled buffers, so the round loop faults in no fresh
    memory. Where the loader's thread opens the next epoch ahead
    (module docstring) the ring does not drain at an epoch's end: the
    new epoch's first rounds are submitted behind the old one's last,
    ``depth`` + 1 rounds before its last pop, and the end of the epoch
    comes out of the ring between them. Elsewhere an epoch drains at its
    end. Either way the sampler's RNG stream, the seed of each round
    (``seed`` + the rounds submitted before it) and every batch are as
    with a ring an epoch, bit for bit.

    One owner at a time: the thread that iterates ``_batches`` submits
    to the ring, pops it and resets it. Where the batches are placed
    ahead that is the loader's own thread, and the consumer's
    otherwise. An epoch abandoned mid-way (the generator closed or
    collected) empties the ring, on the thread that owns it, unless its
    successor is opened already: then its rounds are popped and
    dropped. A new ``__iter__`` retires an earlier unfinished one the
    same way, whose next ``next()`` raises. ``close()`` stops the
    loader's thread, then destroys the ring and joins its workers. The
    pool of recycled buffers holds one round more where a round is
    staged: the batch whose copy is in flight. An error from the
    sampler is raised once the rounds submitted before it are dealt.

    Raises RuntimeError when the toolchain/transform/dataset don't
    support the native path — use :func:`make_fed_loader` for the
    auto-fallback.
    """

    _ring = None    # the loader's native.Prefetcher, once made
    _epoch = None   # the unfinished __iter__ that owns the ring

    def __init__(self, dataset, sampler,
                 max_batch_size: Optional[int] = None,
                 seed: int = 0, depth: int = 4, n_threads: int = 2,
                 dropout_prob: float = 0.0, dropout_seed: int = 0):
        super().__init__(dataset, sampler, max_batch_size,
                         dropout_prob=dropout_prob,
                         dropout_seed=dropout_seed)
        from commefficient_tpu import native

        if not native.available():
            raise RuntimeError("native dataplane unavailable (no g++?)")
        spec = native.native_transform_spec(dataset.transform)
        if spec is None:
            raise RuntimeError("transform not native-representable")
        images, targets = dataset.dense_train_view()
        if images.ndim != 4 or images.shape[1] != images.shape[2]:
            raise RuntimeError(
                "native path needs square (N, H, H, C) storage, got "
                f"{images.shape}")
        if spec["crop_size"] is not None \
                and spec["crop_size"] != images.shape[1]:
            # the native kernel crops back to the image's own size
            raise RuntimeError("crop size != image size")
        self.plane = native.NativeDataplane(
            images, targets, self.W, self.B,
            spec["mean"], spec["std"],
            crop_pad=spec["crop_pad"], do_flip=spec["do_flip"])
        self.seed = seed
        self.depth, self.n_threads = depth, n_threads
        self._round_counter = 0

    def _spec_to_indices(self, round_spec):
        idx = np.full((self.W, self.B), -1, np.int64)
        ids = np.zeros((self.W,), np.int32)
        for i, (cid, idxs) in enumerate(round_spec):
            ids[i] = cid
            rows = [self.dataset.storage_row(int(ix))
                    for ix in idxs[: self.B]]
            idx[i, : len(rows)] = rows
        return ids, idx

    def _open_ring(self, tel):
        """The loader's ring, made on first use; one that an earlier
        unfinished ``__iter__`` still owns is taken from it, emptied."""
        from commefficient_tpu import native

        if self._ring is None:
            self._ring = native.Prefetcher(
                self.plane, self.depth, self.n_threads, telemetry=tel)
        else:
            self._ring.telemetry = tel
            if self._epoch is not None:
                self._ring.reset()
        return self._ring

    def _spec_streams(self) -> dict:
        return {**super()._spec_streams(),
                "loader_round_counter": self._round_counter}

    def _batch_streams(self) -> dict:
        streams = super()._batch_streams()
        del streams["np_global_rng"]    # the plane draws from its seeds
        return streams

    def _batches(self, tel, specs):
        pf = self._open_ring(tel)
        mine = self._epoch = object()
        # ids of the rounds in the ring, oldest first, and the ends of
        # epochs between them
        pending = collections.deque()
        in_ring = 0

        def due(keep):
            """Pop until ``keep`` rounds are left in the ring; an
            epoch's end comes out as soon as the rounds before it."""
            nonlocal in_ring
            while pending and (in_ring > keep
                               or isinstance(pending[0], _EpochEnd)):
                head = pending.popleft()
                if not isinstance(head, _EpochEnd):
                    in_ring -= 1
                    head = self._pop(pf, head)
                yield head
                self._check_owner(mine)

        drained, failed = False, None
        specs = iter(specs)
        try:
            while True:
                try:
                    round_spec = next(specs, None)
                except Exception as e:
                    # the rounds submitted before it are dealt first
                    failed, round_spec = e, None
                if round_spec is None:
                    break
                if isinstance(round_spec, _EpochEnd):
                    pending.append(round_spec)
                else:
                    with tel.span("data.index"):
                        ids, idx = self._spec_to_indices(round_spec)
                    pf.submit(idx, self.seed + self._round_counter)
                    self._round_counter += 1
                    pending.append(ids)
                    in_ring += 1
                yield from due(self.depth)
            yield from due(0)
            drained = True
            if failed is not None:
                raise failed
        finally:
            if self._epoch is mine:
                self._epoch = None
                if not drained:     # abandoned, or a pop raised
                    pf.reset()

    def _check_owner(self, mine):
        """Where an epoch resumes, before it touches sampler or ring."""
        if self._epoch is not mine:
            raise RuntimeError(
                "this epoch was retired: a later __iter__ (or close()) "
                "took the loader's ring")

    def _pop(self, pf, ids):
        x, y, m = pf.pop()
        return self._apply_dropout(
            {"client_ids": ids, "x": x, "y": y, "mask": m})

    def close(self):
        """Destroy the ring and join its threads. Idempotent; a later
        ``__iter__`` makes a new ring."""
        super().close()     # an unfinished epoch's thread first
        self._epoch = None
        ring, self._ring = self._ring, None
        if ring is not None:
            ring.close()

    def __del__(self):
        try:
            self.close()
        except Exception:   # best-effort: interpreter shutdown
            pass


def make_fed_loader(dataset, sampler, max_batch_size=None, seed=0,
                    prefer_native=True, dropout_prob=0.0):
    """NativeFedLoader when the C++ path applies, FedLoader otherwise.
    The fallback is logged (once per call site reason) so a silently
    slow data path is visible; genuine bugs (TypeError etc.) still
    propagate."""
    if prefer_native:
        try:
            return NativeFedLoader(dataset, sampler, max_batch_size,
                                   seed=seed,
                                   dropout_prob=dropout_prob,
                                   dropout_seed=seed)
        except RuntimeError as e:
            import warnings
            warnings.warn(f"native data-plane unavailable ({e}); "
                          "using the Python loader")
    return FedLoader(dataset, sampler, max_batch_size,
                     dropout_prob=dropout_prob, dropout_seed=seed)


class _PrefetchedRoundLoader(_RoundLoaderBase):
    """Language-model rounds, collated in Python.

    ``prefetch_depth`` > 1 runs tokenization/collation on ONE
    background thread, up to that many rounds ahead of the consumer —
    host item prep overlaps the device round (the reference gets this
    from its mp.Queue worker topology, fed_aggregator.py:137-158).
    A single in-order producer keeps every RNG stream (sampler,
    dataset ``_rng`` personality shuffles, dropout) byte-identical to
    the synchronous path, so batches — and checkpointed RNG state at
    epoch end — are deterministic per seed (tested in
    tests/test_gpt2.py TestPersonaPrefetch). It is the thread every
    round loader has (``_ReadAhead``): placing a round on the device
    is its last step for that round, not a second thread behind it,
    and at ``prefetch_depth`` <= 1 it runs one round ahead where there
    is a placement and not at all where there is none."""

    _thread_name = "persona-prefetch"

    def __init__(self, dataset, sampler,
                 max_batch_size: Optional[int] = None,
                 dropout_prob: float = 0.0, dropout_seed: int = 0,
                 prefetch_depth: int = 2):
        super().__init__(dataset, sampler, max_batch_size,
                         dropout_prob=dropout_prob,
                         dropout_seed=dropout_seed)
        self.prefetch_depth = prefetch_depth

    def _host_ahead(self) -> int:
        return self.prefetch_depth if self.prefetch_depth > 1 else 0


class PersonaFedLoader(_PrefetchedRoundLoader):
    """PersonaChat rounds: adds the double-heads arrays
    input_ids/token_type_ids/lm_labels (W, B, N, T), mc_token_ids
    (W, B, N), mc_labels (W, B)."""

    def __init__(self, dataset, sampler, num_candidates: int,
                 max_seq_len: int, pad_id: int = 0,
                 max_batch_size: Optional[int] = None,
                 dropout_prob: float = 0.0, dropout_seed: int = 0,
                 prefetch_depth: int = 2):
        super().__init__(dataset, sampler, max_batch_size,
                         dropout_prob=dropout_prob,
                         dropout_seed=dropout_seed,
                         prefetch_depth=prefetch_depth)
        self.N, self.T, self.pad_id = num_candidates, max_seq_len, pad_id

    def collate(self, round_spec) -> dict:
        from commefficient_tpu.data.fed_persona import persona_collate
        W, B, N, T = self.W, self.B, self.N, self.T
        batch = {
            "input_ids": np.zeros((W, B, N, T), np.int32),
            "token_type_ids": np.zeros((W, B, N, T), np.int32),
            "lm_labels": np.full((W, B, N, T), -1, np.int32),
            "mc_token_ids": np.zeros((W, B, N), np.int32),
            "mc_labels": np.zeros((W, B), np.int32),
            "mask": np.zeros((W, B), np.float32),
        }
        ids = np.zeros((W,), np.int32)
        for i, (cid, idxs) in enumerate(round_spec):
            ids[i] = cid
            records = [self.dataset[int(ix)] for ix in idxs[:self.B]]
            assert all(r[0] == cid for r in records)
            _, arrs = persona_collate(records, N, T, self.pad_id)
            n = len(records)
            for k in ("input_ids", "token_type_ids", "lm_labels",
                      "mc_token_ids", "mc_labels"):
                batch[k][i, :n] = arrs[k]
            batch["mask"][i, :n] = 1.0
        batch["client_ids"] = ids
        return batch

    def round_counters(self, batch) -> dict:
        """``head.positions``: the round's positions; ``head.labelled``:
        those with a language-model label (the gold reply's tokens),
        the rows the vocabulary head computes (models/gpt2.py
        ``lm_nll_sums_chunked``)."""
        labels = batch["lm_labels"]
        return {"head.positions": labels.size,
                "head.labelled": int(np.count_nonzero(labels != -1))}


class TokenFedLoader(_PrefetchedRoundLoader):
    """Causal-LM rounds over per-client token streams
    (data/fed_tokens.py): ``input_ids`` (W, B, T) i32, ``mask`` (W, B).
    Every position of a real row is a token: streams are packed."""

    _thread_name = "tokens-prefetch"

    def collate(self, round_spec) -> dict:
        W, B = self.W, self.B
        ids = np.zeros((W, B, self.dataset.seq_len), np.int32)
        mask = np.zeros((W, B), np.float32)
        cids = np.zeros((W,), np.int32)
        for i, (cid, idxs) in enumerate(round_spec):
            cids[i] = cid
            rows = np.asarray(idxs[:B], np.int64)
            ids[i, :len(rows)] = self.dataset.sequences(rows, cid)
            mask[i, :len(rows)] = 1.0
        return {"client_ids": cids, "input_ids": ids, "mask": mask}


class _ShardedValBase:
    """Validation shards: (S, B, ...) stacked shards of
    ``valid_batch_size`` each — the reference's _call_val splitting
    (fed_aggregator.py:339-350) without the queue plumbing. Final
    partial/empty shards are padded and masked; consumers weight
    per-shard metrics by the mask counts the runtime returns."""

    def __init__(self, dataset, valid_batch_size: int,
                 shards_per_step: int = 8):
        self.dataset = dataset
        self.B = valid_batch_size
        self.S = shards_per_step

    def _shard_indices(self):
        n = len(self.dataset)
        step = self.B * self.S
        for start in range(0, n, step):
            yield np.arange(start, min(start + step, n))

    def __len__(self):
        return int(np.ceil(len(self.dataset) / (self.B * self.S)))


class ValLoader(_ShardedValBase):
    _img_shape = None

    def __iter__(self):
        for idxs in self._shard_indices():
            if self._img_shape is None:
                self._img_shape = np.asarray(
                    self.dataset[int(idxs[0])][1]).shape
            x = np.zeros((self.S, self.B) + self._img_shape, np.float32)
            y = np.zeros((self.S, self.B), np.int32)
            mask = np.zeros((self.S, self.B), np.float32)
            for pos, idx in enumerate(idxs):
                s, j = divmod(pos, self.B)
                _, img, target = self.dataset[int(idx)]
                x[s, j] = img
                y[s, j] = target
                mask[s, j] = 1.0
            yield {"x": x, "y": y, "mask": mask}


class PersonaValLoader(_ShardedValBase):
    def __init__(self, dataset, valid_batch_size: int,
                 num_candidates: int, max_seq_len: int,
                 pad_id: int = 0, shards_per_step: int = 8):
        super().__init__(dataset, valid_batch_size, shards_per_step)
        self.N, self.T, self.pad_id = num_candidates, max_seq_len, pad_id

    def __iter__(self):
        from commefficient_tpu.data.fed_persona import persona_collate
        for idxs in self._shard_indices():
            batch = {
                "input_ids": np.zeros((self.S, self.B, self.N, self.T),
                                      np.int32),
                "token_type_ids": np.zeros(
                    (self.S, self.B, self.N, self.T), np.int32),
                "lm_labels": np.full((self.S, self.B, self.N, self.T),
                                     -1, np.int32),
                "mc_token_ids": np.zeros((self.S, self.B, self.N),
                                         np.int32),
                "mc_labels": np.zeros((self.S, self.B), np.int32),
                "cand_mask": np.zeros((self.S, self.B, self.N),
                                      np.float32),
                "mask": np.zeros((self.S, self.B), np.float32),
            }
            for s in range(self.S):
                rows = idxs[s * self.B:(s + 1) * self.B]
                if len(rows) == 0:
                    break
                records = [self.dataset[int(ix)] for ix in rows]
                _, arrs = persona_collate(records, self.N, self.T,
                                          self.pad_id)
                n = len(records)
                for k in ("input_ids", "token_type_ids", "lm_labels",
                          "mc_token_ids", "mc_labels", "cand_mask"):
                    batch[k][s, :n] = arrs[k]
                batch["mask"][s, :n] = 1.0
            yield batch


class TokenValLoader(_ShardedValBase):
    """Held-out token streams as (S, B, T) shards."""

    def __iter__(self):
        T = int(self.dataset.seq_len)
        for idxs in self._shard_indices():
            ids = np.zeros((self.S * self.B, T), np.int32)
            mask = np.zeros((self.S * self.B,), np.float32)
            ids[:len(idxs)] = self.dataset.sequences(idxs)
            mask[:len(idxs)] = 1.0
            yield {"input_ids": ids.reshape(self.S, self.B, T),
                   "mask": mask.reshape(self.S, self.B)}

"""The seam between a round loader and the model that consumes its
batches: the round's batch is placed on the device by the loader's own
thread, one round before it is asked for (data/loader.py), and
``FedModel._client_pass`` takes that copy and issues none of its own.

A *placement* is a model's ``place_batch(batch) -> device copy``: the
mesh and the client-axis sharding are the model's, so the loader knows
nothing of either. A model publishes its placement while it is live; a
loader is handed one by whoever builds both (``loader.placement =
model.place_batch``) or, handed nothing, finds the one live model's in
:func:`current`, the route ``telemetry.current()`` takes for the
recorder. With no model live, or several, there is no placement and a
loader works as it did without one.

A staged batch is a ``dict`` of the host fields, as ever (its readers
index it, copy it, edit copies of its fields), with the device copy
riding along as an attribute, and beside it what the loader counted in
the batch for the consuming round's record (``note`` / ``counters_of``:
the labelled positions of a language-model round). Whatever rebuilds
the batch between the loader and the model (``dict(batch)``, the async
driver's fold, a chaos wrapper, mixup) makes a plain ``dict`` and
thereby drops both;
:func:`staged_copy` also refuses one whose fields were replaced in
place, or that another model's placement made.
"""

from __future__ import annotations

import weakref
from typing import Callable, NamedTuple, Optional

__all__ = ["NotedBatch", "StagedBatch", "note", "counters_of", "stage",
           "staged_copy", "publish", "withdraw", "current"]


class _Staged(NamedTuple):
    place: Callable     # the placement that made ``device``
    host: tuple         # the (key, value) pairs it was made from
    device: object      # what the placement returned


class NotedBatch(dict):
    """A round's host batch with what its loader counted in it
    (``counters``: name -> number), for the record of the round that
    consumes it: the loader's thread is rounds ahead of that record."""

    __slots__ = ("counters",)


class StagedBatch(NotedBatch):
    """A round's host batch with its device copy (``staged``)."""

    __slots__ = ("staged",)


def note(batch: dict, counters: dict) -> NotedBatch:
    """``batch`` with ``counters`` riding along."""
    out = NotedBatch(batch)
    out.counters = dict(counters)
    return out


def counters_of(batch) -> dict:
    """What the batch's loader counted in it; {} for a plain ``dict``
    (whatever rebuilt the batch dropped the counts with the copy)."""
    return getattr(batch, "counters", None) or {}


def stage(batch: dict, place: Callable) -> StagedBatch:
    """``batch`` with ``place(batch)`` riding along, and its counters
    if it has any. The copy may still be in flight when this returns."""
    out = StagedBatch(batch)
    out.counters = counters_of(batch)
    out.staged = _Staged(place, tuple(batch.items()), place(batch))
    return out


def staged_copy(batch, place: Callable):
    """The device copy ``place`` made of exactly this batch, or None:
    not a staged batch, another placement's, or a batch whose fields
    are no longer the objects that were placed."""
    st = getattr(batch, "staged", None)
    if st is None or st.place != place or len(batch) != len(st.host):
        return None
    if any(batch.get(k) is not v for k, v in st.host):
        return None
    return st.device


# The placement of each model built and not yet finalized. Weak: a
# model dropped without ``finalize()`` stops counting.
_LIVE = []


def _live():
    _LIVE[:] = [r for r in _LIVE if r() is not None]
    return _LIVE


def publish(place: Callable):
    """Called by a model, with a bound method of its own."""
    if all(r() != place for r in _live()):
        _LIVE.append(weakref.WeakMethod(place))


def withdraw(place: Callable):
    _LIVE[:] = [r for r in _live() if r() != place]


def current() -> Optional[Callable]:
    """The placement of the process's one live model; None before one
    is built and while more than one is live (several tenants in one
    process: a loader that cannot say whose batches it makes places
    none)."""
    live = [p for p in (r() for r in _live()) if p is not None]
    return live[0] if len(live) == 1 else None

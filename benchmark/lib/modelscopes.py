"""Device time under the ``jax.named_scope`` names a model brings.

``lib/scopes.py`` reads the round programs' own scopes from one fixed
list; a model's scopes (``models/joyai.py``: ``mla_attn``,
``moe_route``, ``moe_experts``, ``moe_combine``, ``mtp``) are read
here, by the same rule: an operation event belongs to a scope where
its ``tf_op`` argument holds the name as a path component, bare or
wrapped by a transformation, and a set of scopes' time is the **union**
of the intervals of the events that name any of them, per traced round
and device. A loop (each client's ragged products run in one) counts
through the operations of its body. A trace without the argument, or a
program without the scopes (the parent of the PR that named them),
gives None.
"""

from __future__ import annotations

from benchmark.lib import tracelib, tracesum
from benchmark.lib.scopes import _holds, _span


def scopes_ms(ctx, names):
    """ms a traced round, averaged over the devices, during which an
    operation under any of ``names`` ran; None where none did."""
    if not ctx.get("trace_dir"):
        return None
    tr = tracesum.of(ctx)
    wins = tr["windows"]
    if not wins:
        return None
    lo, hi = wins[0][1], wins[-1][2]
    holds = [_holds(n) for n in names]
    per_dev = {}
    for e in tracesum.op_events(ctx):
        ts, end = _span(e)
        if ts < lo or ts >= hi:
            continue
        op = (e.get("args") or {}).get("tf_op") or ""
        if any(h(op) for h in holds):
            per_dev.setdefault(
                tr["lanes"][(e.get("pid"), e.get("tid"))], []).append(
                (ts, end))
    if not per_dev:
        return None
    total = sum(tracelib._measure(tracelib._union(iv))
                for iv in per_dev.values())
    return total / 1e3 / (len(per_dev) * len(wins))

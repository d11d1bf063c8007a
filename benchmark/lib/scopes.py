"""Device time by the program's ``jax.named_scope`` names.

One path, settled on the v5e (PR 25): every ``XLA Ops`` event of the
trace carries its operation's ``op_name`` metadata as the argument
``tf_op`` (``jit(client_round)/fwd_bwd/transpose(jvp(lm_head))/
dot_general:``), and a scope is in it as a path component, bare or
wrapped by the transformations that ran over it. A scope's time is
the **union** of the intervals of the events that name it, and nothing
else. Operations the compiler made itself carry no ``tf_op``: a loop
(``while.N``) counts through its body, whose operations are events of
their own inside its interval; the copies, slices and helper fusions
the compiler put between the program's own (ResNet9's max-pool index
masks, 7 ms a round) belong to no scope, and are printed beside the
scopes as the device's busy time under none. A trace without the
argument, or a program without the scope (the parent of the PR that
named them), gives None.

Scopes of the program (``core/rounds.py``, ``core/server.py``,
``ops/sketch.py``, ``models/gpt2.py``): ``fwd_bwd`` (with ``lm_head``
inside it), ``compress``, ``estimates``, ``select``, ``resketch``,
``apply``.
"""

from __future__ import annotations

import re

from benchmark.lib import tracelib, tracesum

SCOPES = ("fwd_bwd", "lm_head", "compress", "estimates", "select",
          "resketch", "apply")


def _holds(scope):
    return re.compile(r"(?<![\w.])%s(?![\w.])" % re.escape(scope)).search


def _span(e):
    ts = float(e["ts"])
    return ts, ts + float(e.get("dur", 0.0))


def _by_device(ctx, lo, hi):
    """{device: [(ts, end, scopes held, event), ...]}: the window's
    operation events, sorted; an event without a ``tf_op`` holds no
    scope."""
    tr = tracesum.of(ctx)
    holds = {s: _holds(s) for s in SCOPES}
    out = {}
    for e in tracesum.op_events(ctx):
        ts, end = _span(e)
        if ts < lo or ts >= hi:
            continue
        op = (e.get("args") or {}).get("tf_op") or ""
        out.setdefault(tr["lanes"][(e.get("pid"), e.get("tid"))], []).append(
            (ts, end, frozenset(s for s, h in holds.items() if h(op)), e))
    for ops in out.values():
        ops.sort(key=lambda o: o[:2])
    return out


def scope_seconds(ctx):
    """{scope: seconds a traced round, averaged over the devices} for
    the scopes the trace holds; {} where it holds none."""
    tr = tracesum.of(ctx)
    if "scopes" in tr:
        return tr["scopes"]
    wins = tr["windows"]
    out = tr["scopes"] = {}
    if not wins:
        return out
    devs = _by_device(ctx, wins[0][1], wins[-1][2])
    total = {s: 0.0 for s in SCOPES}
    busy = scoped = 0.0
    for ops in devs.values():
        busy += tracelib._measure(tracelib._union([o[:2] for o in ops]))
        scoped += tracelib._measure(tracelib._union(
            [o[:2] for o in ops if o[2]]))
        for s in SCOPES:
            total[s] += tracelib._measure(tracelib._union(
                [o[:2] for o in ops if s in o[2]]))
    per = 1e6 * len(devs) * len(wins)
    out.update({s: v / per for s, v in total.items() if v > 0})
    if out:
        print("device ms a round by scope: "
              + ", ".join(f"{s} {1e3 * v:.3f}" for s, v in out.items())
              + f"; under a scope {1e3 * scoped / per:.3f}, under none "
              f"{1e3 * (busy - scoped) / per:.3f}, of "
              f"{1e3 * busy / per:.3f} busy")
        _describe_largest(devs, len(devs) * len(wins))
    return out


def scope_ms(ctx, names):
    """Summed ms a round of ``names``; None unless the trace holds at
    least one of them."""
    found = scope_seconds(ctx)
    vals = [found[n] for n in names if n in found]
    return 1e3 * sum(vals) if vals else None


def _describe_largest(devs, rounds, top=12):
    """Print what the largest operations are, by name: an operation's
    own ``tf_op``; for one without (the compiler's), the commonest
    ``tf_op`` inside the first of them (what ``while.11`` is) or, with
    nothing inside, the start of its ``long_name``."""
    kinds = {}
    for ops in devs.values():
        for i, (ts, end, _held, e) in enumerate(ops):
            k = kinds.setdefault(e["name"], [0.0, 0, ops, i])
            k[0] += end - ts
            k[1] += 1
    for name, (dur, n, ops, i) in sorted(
            kinds.items(), key=lambda kv: -kv[1][0])[:top]:
        ts, end, held, first = ops[i]
        args = first.get("args") or {}
        what = args.get("tf_op")
        if not what:
            inside = {}
            for a, b, _h, e in ops[i + 1:]:
                if a >= end:
                    break
                op = (e.get("args") or {}).get("tf_op")
                if op and b <= end:
                    key = re.sub(r"/[^/]*$", "", op)
                    inside[key] = inside.get(key, 0.0) + b - a
            what = "no tf_op; " + ("inside the first: " + "; ".join(
                f"{k} ({v / 1e3:.2f} ms)" for k, v in sorted(
                    inside.items(), key=lambda kv: -kv[1])[:2])
                if inside else str(args.get("long_name"))[:150])
        print(f"operation {name}: {dur / 1e3 / rounds:.3f} ms a round in "
              f"{n} events, scopes {sorted(held) or None}; {what[:220]}")

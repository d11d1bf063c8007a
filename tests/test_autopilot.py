"""Adaptive compression autopilot: the knob lattice, the bounded
re-jit cache (isolation: LRU bound, hit/miss counters, eviction), the
deterministic band controller and its bit-exact replay, the run
registry's band keying, and the FedModel integration —
autopilot-off object identity, pinned-knob bit parity with the
equivalent static config, variant-switch bit parity with a fresh
jax.jit, and warm-ahead never compiling an unvisited lattice point."""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.autopilot import (AutopilotController,
                                         RoundVariantCache,
                                         VariantKey, apply_knobs,
                                         build_controller,
                                         build_ladder, key_of,
                                         key_str, parse_band,
                                         parse_key, replay_record,
                                         variant_bytes)
from commefficient_tpu.config import Config
from commefficient_tpu.telemetry import registry


def make_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual",
                local_momentum=0.0, virtual_momentum=0.9,
                num_workers=2, k=16, num_rows=3, num_cols=128,
                num_blocks=1, local_batch_size=2, microbatch_size=-1,
                seed=21)
    base.update(kw)
    return Config(**base)


# --- lattice ------------------------------------------------------------


def test_key_roundtrip_and_apply_knobs_identity():
    cfg = make_cfg()
    key = key_of(cfg)
    assert parse_key(key_str(key)) == key
    # the sanctioned no-op: matching key returns the SAME object, so
    # the autopilot-off build path uses the identical Config instance
    assert apply_knobs(cfg, key) is cfg
    moved = apply_knobs(cfg, key._replace(dtype="int8"))
    assert moved is not cfg
    assert moved.sketch_dtype == "int8"
    assert moved.k == cfg.k and moved.num_cols == cfg.num_cols
    with pytest.raises(ValueError):
        parse_key("int8-k16")


def test_ladder_cost_monotone():
    ladder = build_ladder(make_cfg(sketch_dtype="f32"))
    assert [k.dtype for k in ladder] == ["f32", "bf16", "int8"]
    costs = [variant_bytes(k) for k in ladder]
    assert costs == sorted(costs, reverse=True)
    assert all(a > b for a, b in zip(costs, costs[1:]))
    # fp8 base: no cheaper dtype exists -> one-point ladder
    assert build_ladder(make_cfg(sketch_dtype="fp8")) == \
        [key_of(make_cfg(sketch_dtype="fp8"))]


def test_ladder_geometry_steps():
    cfg = make_cfg(num_cols=256, autopilot_geometry=True)
    ladder = build_ladder(cfg)
    tail = [k for k in ladder if k.dtype == "int8"]
    assert [k.cols for k in tail] == [256, 128, 64]
    costs = [variant_bytes(k) for k in ladder]
    assert all(a > b for a, b in zip(costs, costs[1:]))


def test_parse_band():
    assert parse_band("0.05:0.6") == (0.05, 0.6)
    for bad in ("0.6:0.05", "nope", "0.5"):
        with pytest.raises(ValueError):
            parse_band(bad)


# --- re-jit cache isolation ---------------------------------------------


def test_cache_bound_lru_eviction_counters():
    built, evicted = [], []
    cache = RoundVariantCache(lambda k: built.append(k) or f"v:{k}",
                              max_size=2,
                              on_evict=lambda k, e: evicted.append(k))
    assert cache.get("a") == "v:a" and cache.get("b") == "v:b"
    assert cache.counters() == {"hits": 0, "misses": 2,
                                "evictions": 0, "size": 2}
    assert cache.get("a") == "v:a"          # hit refreshes recency
    assert cache.keys() == ["b", "a"]
    cache.get("c")                          # evicts LRU ("b")
    assert evicted == ["b"] and "b" not in cache
    assert len(cache) == 2
    # re-visit after eviction is a rebuild (the recompile the ledger
    # stamp makes visible), never a stale entry
    cache.get("b")
    assert built == ["a", "b", "c", "b"]
    assert cache.counters() == {"hits": 1, "misses": 4,
                                "evictions": 2, "size": 2}


def test_cache_peek_is_side_effect_free():
    cache = RoundVariantCache(lambda k: f"v:{k}", max_size=2)
    assert cache.peek("a") is None          # no build on absence
    assert cache.misses == 0 and len(cache) == 0
    cache.get("a")
    cache.get("b")
    hits = cache.hits
    assert cache.peek("a") == "v:a"
    assert cache.hits == hits               # no recency touch either
    assert cache.keys() == ["a", "b"]


# --- controller policy --------------------------------------------------


def _ladder3():
    return build_ladder(make_cfg())


def test_controller_cheapen_cooldown_and_hold():
    ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=2)
    assert ctl.observe(0, {"recovery_error": 0.01}) == _ladder3()[1]
    # cooldown: two in-band/low observations must pass before the
    # next cheapen
    assert ctl.observe(1, {"recovery_error": 0.01}) is None
    assert ctl.observe(2, {"recovery_error": 0.01}) is None
    assert ctl.observe(3, {"recovery_error": 0.01}) == _ladder3()[2]
    # in-band at the cheapest point: hold forever
    for r in (4, 5, 6):
        assert ctl.observe(r, {"recovery_error": 0.3}) is None
    assert ctl.key == _ladder3()[2]
    acts = [t["action"] for t in ctl.trajectory]
    assert acts == ["cheapen", "hold", "hold", "cheapen",
                    "hold", "hold", "hold"]


def test_controller_backoff_never_oscillates():
    ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=0)
    ctl.observe(0, {"recovery_error": 0.01})
    ctl.observe(1, {"recovery_error": 0.01})
    assert ctl.key == _ladder3()[2]
    # breach: immediate backoff, and the offending point is fenced
    assert ctl.observe(2, {"recovery_error": 0.9}) == _ladder3()[1]
    # low error again — but the cheap limit is monotone: the breached
    # point is never re-entered, so the knobs cannot oscillate
    for r in range(3, 10):
        assert ctl.observe(r, {"recovery_error": 0.001}) is None
    assert ctl.key == _ladder3()[1]


def test_controller_panic_freezes_ladder():
    ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=0)
    ctl.observe(0, {"recovery_error": 0.01})
    assert ctl.idx == 1
    assert ctl.observe(1, {"recovery_error": 0.3,
                           "agg_nan": 1.0}) == _ladder3()[0]
    assert ctl.trajectory[-1]["action"] == "panic"
    # frozen for good: even a perfect error never cheapens again
    for r in range(2, 8):
        assert ctl.observe(r, {"recovery_error": 1e-4}) is None
    assert ctl.key == _ladder3()[0]


def test_controller_blind_rounds_do_not_pay_cooldown():
    ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=1)
    ctl.observe(0, {"recovery_error": 0.01})    # cheapen, cool=1
    # off-cadence rounds (no recovery observation) must not
    # fast-forward the cooldown
    for r in (1, 2, 3):
        assert ctl.observe(r, {}) is None
        assert ctl.trajectory[-1]["action"] == "blind"
    assert ctl.observe(4, {"recovery_error": 0.01}) is None  # pays
    assert ctl.observe(5, {"recovery_error": 0.01}) == _ladder3()[2]


def test_controller_pinned_holds():
    ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=0,
                              start=2, pinned=True)
    for r, err in enumerate((0.001, 0.9, float("nan"))):
        probes = {"recovery_error": err}
        if err != err:
            probes = {"agg_nan": 1.0}
        assert ctl.observe(r, probes) is None
    assert ctl.key == _ladder3()[2]
    assert all(t["action"] == "pinned" for t in ctl.trajectory)


def test_controller_deterministic_and_replay_exact():
    errs = [0.01, 0.01, 0.01, 0.2, 0.01, 0.9, 0.001, None, 0.3]

    def run():
        ctl = AutopilotController(_ladder3(), (0.05, 0.6), cooldown=1,
                                  seed=7)
        for r, e in enumerate(errs):
            ctl.observe(r, {} if e is None
                        else {"recovery_error": e})
        return ctl

    a, b = run(), run()
    assert a.trajectory == b.trajectory
    rec = a.record()
    assert rec["initial"] == key_str(_ladder3()[0])
    assert rec["final"] == key_str(a.key)
    assert rec["final_wire_bytes"] < rec["initial_wire_bytes"]
    # bit-exact replay from the manifest record alone
    assert replay_record(rec) == [t["key"] for t in rec["trajectory"]]


def test_build_controller_modes():
    assert build_controller(make_cfg()) is None
    cfg = make_cfg(autopilot="on", autopilot_band="0.05:0.6",
                   probe_every=1)
    ctl = build_controller(cfg)
    assert ctl is not None and not ctl.pinned
    assert ctl.key == key_of(cfg)
    # pin at an on-ladder point
    pin = key_str(build_ladder(cfg)[2])
    pinned = build_controller(dataclasses.replace(
        cfg, autopilot_pin=pin))
    assert pinned.pinned and key_str(pinned.key) == pin
    # pin OFF the automatic walk: appended as an extra lattice point
    off = build_controller(dataclasses.replace(
        cfg, autopilot_pin="int8-k8-r3-c128-re9500"))
    assert key_str(off.key) == "int8-k8-r3-c128-re9500"


# --- band keying -------------------------------------------------------


def test_band_suffix_forms():
    assert registry.band_suffix(None) == ""
    assert registry.band_suffix("") == ""
    assert registry.band_suffix("0.2:0.6") == "b0.2-0.6"
    assert registry.band_suffix("0.2-0.6") == "b0.2-0.6"
    assert registry.band_suffix((0.05, 0.6)) == "b0.05-0.6"
    assert registry.topology_key(8, 1, band="0.05:0.6") == "d8p1b0.05-0.6"
    assert registry.topology_key(
        8, 1, wire_dtype="int8", band="0.05:0.6") == "d8p1qint8b0.05-0.6"


def test_registry_band_and_final_dtype_keying():
    man = {"config": {"autopilot": "on",
                      "autopilot_band": "0.05:0.6",
                      "sketch_dtype": "f32", "mode": "sketch"},
           "autopilot": {"final": "int8-k16-r3-c128-re9500"}}
    assert registry.run_band(man) == "0.05:0.6"
    # the converged point (not the launch dtype) keys the wire dtype,
    # so a walk that settled on int8 pins as qint8b<lo-hi>
    assert registry.run_wire_dtype(man) == "int8"
    static = {"config": {"autopilot": "off", "sketch_dtype": "bf16",
                         "mode": "sketch"}}
    assert registry.run_band(static) is None
    assert registry.run_wire_dtype(static) == "bf16"


# --- lint: knob mutation confined to the re-plan API --------------------


def test_knob_mutation_lint_rule():
    import ast

    from commefficient_tpu.analysis.lint import RULES_BY_NAME
    rule = RULES_BY_NAME["knob-mutation"]
    src = ("cfg.k = 3\n"
           "self.args.num_rows = 2\n"
           "x.sketch_dtype = 'int8'\n"
           "out = cfg.replace(k=4, num_cols=64)\n"
           "loop.k = 1\n"             # not a config receiver: legal
           "s = s.replace(':', '-')\n")  # positional replace: legal
    hits = rule.check(pathlib.PurePath("runtime/foo.py"),
                      src.splitlines(), ast.parse(src))
    assert sorted(h[0] for h in hits) == [1, 2, 3, 4]
    # autopilot/ IS the sanctioned re-plan API: exempt
    assert rule.check(pathlib.PurePath("autopilot/lattice.py"),
                      src.splitlines(), ast.parse(src)) == []


# --- round plan ---------------------------------------------------------


def test_round_plan_records_autopilot_block():
    from commefficient_tpu.core.rounds import round_plan
    cfg = dataclasses.replace(
        make_cfg(autopilot="on", autopilot_band="0.05:0.6",
                 probe_every=1), grad_size=64)
    plan = round_plan(cfg)
    ap = plan["autopilot"]
    assert ap["band"] == "0.05:0.6"
    assert ap["base"] == key_str(key_of(cfg))
    assert ap["ladder"][0] == ap["base"]
    assert len(ap["ladder"]) == 3
    assert "autopilot" not in round_plan(
        dataclasses.replace(make_cfg(), grad_size=64))


# --- dp budget constraint -----------------------------------------------


def dp_make_cfg(**kw):
    base = dict(dp="sketch", dp_clip=1.0, dp_noise_mult=1.0,
                dp_delta=1e-5, num_clients=8)
    base.update(kw)
    return make_cfg(**base)


def test_apply_knobs_rescales_noise_on_rows_move():
    """A rows-changing knob move recalibrates dp_noise_mult so the
    ABSOLUTE table noise stays at the launch calibration."""
    import math

    from commefficient_tpu.privacy import table_noise_std

    cfg = dp_make_cfg(num_rows=4)
    moved = apply_knobs(cfg, key_of(cfg)._replace(rows=16))
    assert moved.dp_noise_mult == pytest.approx(math.sqrt(4 / 16))
    assert table_noise_std(moved) == pytest.approx(
        table_noise_std(cfg))
    # dtype-only move: σ untouched (qdq is free post-processing)
    assert apply_knobs(cfg, key_of(cfg)._replace(
        dtype="int8")).dp_noise_mult == cfg.dp_noise_mult
    # dp off: the knob is inert, never rewritten
    off = make_cfg(num_rows=4)
    assert apply_knobs(off, key_of(off)._replace(
        rows=16)).dp_noise_mult == off.dp_noise_mult


def test_budget_feasible_predicate():
    from commefficient_tpu.autopilot.controller import _budget_feasible

    cfg = dp_make_cfg(dp_epsilon=8.0)
    keep = _budget_feasible(cfg)
    assert keep(key_of(cfg))                        # launch point
    assert keep(key_of(cfg)._replace(rows=1))       # σ grows: slower
    assert not keep(key_of(cfg)._replace(rows=12))  # σ shrinks: faster
    # constraint off (no budget / dp off): everything passes
    assert _budget_feasible(make_cfg())(
        key_of(cfg)._replace(rows=12))
    assert _budget_feasible(dp_make_cfg())(
        key_of(cfg)._replace(rows=12))


def test_controller_never_holds_budget_violating_point():
    """The ladder is pre-filtered: every point the controller can
    ever visit fits at least as many rounds under --dp_epsilon as
    the launch plan; an infeasible pin is a launch error, not a
    silent fallback."""
    from commefficient_tpu.autopilot.controller import _budget_feasible

    cfg = dp_make_cfg(autopilot="on", autopilot_band="0.05:0.6",
                      probe_every=1, dp_epsilon=8.0)
    ctl = build_controller(cfg)
    keep = _budget_feasible(cfg)
    assert ctl is not None and all(keep(k) for k in ctl.ladder)

    bad = key_str(key_of(cfg)._replace(rows=12))
    with pytest.raises(ValueError, match="budget"):
        build_controller(dataclasses.replace(cfg, autopilot_pin=bad))

    good = key_str(key_of(cfg)._replace(rows=1))
    pinned = build_controller(dataclasses.replace(cfg,
                                                  autopilot_pin=good))
    assert pinned.pinned and key_str(pinned.key) == good


# --- FedModel integration ----------------------------------------------


def _fed_loss(params, batch, cfg):
    pred = batch["x"] @ params["w"]
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
    return l, (l * 0.0 + 1.0,)


def _heavy_batch(rng, W, B, d, num_clients):
    # power-law feature scaling makes the gradient heavy-tailed, so
    # the sketch's top-k recovery error sits far below the dense-iid
    # floor and the band has room to hold across the dtype walk
    scale = (np.arange(1, d + 1) ** -1.5).astype(np.float32)
    return {"client_ids": rng.choice(num_clients, W, replace=False)
            .astype(np.int32),
            "x": jnp.asarray(rng.randn(W, B, d).astype(np.float32)
                             * scale),
            "y": jnp.asarray(rng.randn(W, B), jnp.float32),
            "mask": jnp.ones((W, B), jnp.float32)}


def _run_fed(cfg_kw, n_rounds=8, d=512, num_clients=16,
             return_model=False):
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)
    W, B = 4, 2
    base = dict(mode="sketch", error_type="virtual",
                local_momentum=0.0, virtual_momentum=0.9,
                num_workers=W, local_batch_size=B, seed=5,
                num_clients=num_clients, k=64, num_rows=5,
                num_cols=2048)
    base.update(cfg_kw)
    cfg = Config(**base)
    model = FedModel(None, {"w": jnp.zeros((d,), jnp.float32)},
                     _fed_loss, cfg, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    rng = np.random.RandomState(5)
    for _ in range(n_rounds):
        model(_heavy_batch(rng, W, B, d, num_clients))
        opt.step()
    ps = np.asarray(model.ps_weights)
    if return_model:
        return ps, model
    model.finalize()
    return ps


def test_autopilot_off_base_variant_is_args_itself():
    """With the autopilot off, the dispatched variant's config must BE
    the model's args object (apply_knobs identity at the base key), so
    the built round program is byte-identical to a build without the
    feature — the object-identity half of the HLO-identity guarantee
    (the audit's program fingerprints pin the other half)."""
    ps, model = _run_fed({}, n_rounds=1, return_model=True)
    var = model._variants.get(model._variant_key)
    assert var.cfg is model.args
    assert model._autopilot is None
    assert model._variants.counters()["size"] == 1
    model.finalize()


def test_autopilot_hlo_invisible_when_off():
    """The autopilot config fields are host-only: flipping them (with
    the controller pinned at the base point) must not change the
    lowered client-round program."""
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round)

    def lower(cfg, d=8, B=3, W=2):
        ps = jax.ShapeDtypeStruct((d,), jnp.float32)
        cs = jax.eval_shape(
            lambda: ClientStates.init(cfg, 4,
                                      jnp.zeros((d,), jnp.float32)))
        batch = {"x": jax.ShapeDtypeStruct((W, B, d), jnp.float32),
                 "y": jax.ShapeDtypeStruct((W, B), jnp.float32),
                 "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
        ids = jax.ShapeDtypeStruct((W,), jnp.int32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        lr = jax.ShapeDtypeStruct((), jnp.float32)

        def loss(flat, batch):
            l = jnp.sum((batch["x"] @ flat - batch["y"]) ** 2
                        * batch["mask"])
            return l, (l * 0.0 + 1.0,)

        return jax.jit(build_client_round(cfg, loss, B)) \
            .lower(ps, cs, batch, ids, rng, lr).as_text()

    off = dataclasses.replace(make_cfg(), grad_size=8)
    on = dataclasses.replace(
        make_cfg(autopilot="on", autopilot_band="0.05:0.6",
                 probe_every=1, autopilot_cooldown=5,
                 autopilot_cache_size=2), grad_size=8)
    assert lower(off) == lower(on)


def test_pinned_knob_bit_identical_to_static():
    """A run pinned at a lattice point must be BIT-identical to the
    equivalent static config — the pin dispatches the same program
    from round 0 and the controller never moves."""
    pin = "int8-k64-r5-c2048-re9500"
    static = _run_fed({"sketch_dtype": "int8"})
    pinned = _run_fed({"autopilot": "on",
                       "autopilot_band": "0.05:0.6",
                       "probe_every": 1, "autopilot_pin": pin})
    assert np.array_equal(static, pinned)


def test_autopilot_walk_band_held_and_compile_isolation():
    """The acceptance walk, compact: from an f32 launch the controller
    converges to int8 (>= 2x cheaper uplink), recovery error stays in
    band on every observed round, and the re-jit cache compiled ONLY
    the visited lattice points."""
    ps, model = _run_fed(
        {"autopilot": "on", "autopilot_band": "0.05:0.6",
         "probe_every": 1, "autopilot_cooldown": 1},
        n_rounds=8, return_model=True)
    ctl = model._autopilot
    rec = model.autopilot_record()
    assert rec["final"].startswith("int8")
    assert rec["final_wire_bytes"] * 2 <= rec["initial_wire_bytes"]
    lo, hi = 0.05, 0.6
    observed = [t for t in rec["trajectory"]
                if t["recovery_error"] is not None]
    assert observed, "no recovery observations reached the controller"
    assert all(t["recovery_error"] <= hi for t in observed)
    assert not any(t["action"] == "panic" for t in observed)
    # replay from the record alone is bit-exact
    assert replay_record(rec) == [t["key"] for t in rec["trajectory"]]
    # compile isolation: every cached variant was visited, and each
    # compiled at most one client flavor (+ server) — never the
    # off-cadence flavor jit keeps lazy, never an unvisited point
    visited = {t["key"] for t in rec["trajectory"]}
    visited.add(rec["initial"])
    cached = model._variants.keys()
    assert {key_str(k) for k in cached} <= visited
    for k in cached:
        var = model._variants.peek(k)
        assert var.compiled <= {"probed", "server"}, \
            (key_str(k), var.compiled)
    assert len(cached) <= len(ctl.ladder)
    model.finalize()


def test_warm_ahead_never_compiles_unvisited_point():
    """_switch_variant AOT-compiles only the point the controller just
    committed to; lattice points never visited must stay absent from
    the cache entirely (jit laziness is not enough — they must never
    even be built)."""
    ps, model = _run_fed(
        {"autopilot": "on", "autopilot_band": "0.0:0.6",
         "probe_every": 1},
        n_rounds=3, return_model=True)
    # band LO=0: nothing is ever below the band, controller holds at
    # the base point forever
    rec = model.autopilot_record()
    assert all(t["action"] in ("hold", "blind")
               for t in rec["trajectory"])
    assert model._variants.counters()["size"] == 1
    assert model._variants.counters()["misses"] == 1
    model.finalize()


def test_variant_switch_bit_identical_to_fresh_jit():
    """After a cache switch, the dispatched variant's program must
    produce bit-identical results to a FRESH jax.jit of the same
    build — the cache is a lookup structure, never a semantic layer."""
    ps, model = _run_fed(
        {"autopilot": "on", "autopilot_band": "0.05:0.6",
         "probe_every": 1, "autopilot_cooldown": 1},
        n_rounds=6, return_model=True)
    var = model._variants.get(model._variant_key)
    assert key_str(var.key).startswith("int8"), \
        "walk did not reach int8; test premise broken"

    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round)
    cfg = var.cfg
    d, W, B = 512, 4, 2
    fresh = jax.jit(build_client_round(
        cfg, None, B, mesh=model.mesh,
        tree_loss=lambda p, b: _fed_loss(p, b, cfg),
        unravel=model.unravel, probes=True, probe_recovery=True))

    rng = np.random.RandomState(11)
    batch = _heavy_batch(rng, W, B, d, 16)
    dev_batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if k != "client_ids"}
    ids = jnp.asarray(batch["client_ids"], jnp.int32)
    key = jax.random.PRNGKey(3)
    ps0 = jnp.asarray(np.asarray(model.ps_weights))

    def run(fn):
        cs = ClientStates.init(cfg, 16, ps0)
        return fn(ps0, cs, dev_batch, ids, key, jnp.float32(0.25))

    a = run(var.round_probed)
    b = run(fresh)
    for xa, xb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
    model.finalize()

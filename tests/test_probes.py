"""Schema-v2 probe layer: in-compile diagnostics vs the NumPy mirror
(all five modes, fused / per-client / chunked paths), the alarm
engine's rules and actions, probes-off program identity (the emitted
HLO must not change when probes are off), and the end-to-end ledger
round-trip including the pipelined deferred-attach path."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates, args2sketch,
                                           build_client_round,
                                           build_server_round)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.telemetry import Telemetry
from commefficient_tpu.telemetry.alarms import (AlarmEngine,
                                                DivergenceAbort,
                                                build_alarm_engine)

from reference_mirror import MirrorFed


def linear_loss(params_flat, batch):
    pred = batch["x"] @ params_flat
    sq = (pred - batch["y"]) ** 2
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    loss = jnp.sum(sq * batch["mask"]) / n
    return loss, (loss * 0.0 + 1.0,)


def make_cfg(**kw):
    base = dict(mode="uncompressed", local_momentum=0.0,
                virtual_momentum=0.0, weight_decay=0.0,
                error_type="none", num_workers=2, k=3,
                num_rows=5, num_cols=16, num_blocks=1,
                local_batch_size=2, microbatch_size=-1, seed=21)
    base.update(kw)
    return Config(**base)


def _round_data(rng, d, n_per_client=(3, 2)):
    return [(cid, rng.normal(size=(n, d)).astype(np.float64),
             rng.normal(size=(n,)).astype(np.float64))
            for cid, n in enumerate(n_per_client)]


def run_engine_probes(cfg, w0, rounds, lr, num_clients=4):
    """test_modes.run_engine, but with the probed program variants;
    returns one merged client+server probe dict per round."""
    d = len(w0)
    cfg = dataclasses.replace(cfg, grad_size=d)
    B = max(len(y) for rnd in rounds for _, _, y in rnd)
    client_round = jax.jit(build_client_round(
        cfg, linear_loss, B, probes=True, probe_recovery=True))
    server_round = jax.jit(build_server_round(cfg, probes=True))

    ps = jnp.asarray(w0, jnp.float32)
    cs = ClientStates.init(cfg, num_clients, ps)
    ss = ServerState.init(cfg)
    rng = jax.random.PRNGKey(cfg.seed)
    out = []
    for rnd_i, clients in enumerate(rounds):
        W = len(clients)
        x = np.zeros((W, B, d), np.float32)
        y = np.zeros((W, B), np.float32)
        mask = np.zeros((W, B), np.float32)
        ids = np.zeros((W,), np.int32)
        for i, (cid, X, Y) in enumerate(clients):
            n = len(Y)
            x[i, :n], y[i, :n], mask[i, :n], ids[i] = X, Y, 1.0, cid
        batch = {"x": jnp.asarray(x), "y": jnp.asarray(y),
                 "mask": jnp.asarray(mask)}
        res = client_round(ps, cs, batch, jnp.asarray(ids),
                           jax.random.fold_in(rng, rnd_i),
                           jnp.float32(lr))
        cs = res.client_states
        ps, ss, new_vel, _, _, sprobes = server_round(
            ps, ss, res.aggregated, jnp.float32(lr),
            cs.velocities, jnp.asarray(ids))
        if new_vel is not None:
            cs = cs._replace(velocities=new_vel)
        probes = {k: float(v) for k, v in res.probes.items()}
        probes.update({k: float(v) for k, v in sprobes.items()})
        out.append(probes)
    return out


def run_mirror_probes(cfg, w0, rounds, lr, num_clients=4, B=None):
    d = len(w0)
    cfg = dataclasses.replace(cfg, grad_size=d)
    m = MirrorFed(cfg, w0, num_clients, sketch=args2sketch(cfg))
    out = []
    for rnd in rounds:
        if cfg.mode == "fedavg":
            m.round_fedavg(rnd, lr)
        else:
            m.round(rnd, lr, B)
        out.append(dict(m.last_probes))
    return out


# --- probe values vs the NumPy mirror ----------------------------------


FUSED_KEYS = {"agg_norm", "agg_nan", "agg_inf"}
CLIENT_KEYS = FUSED_KEYS | {"client_norm_mean", "client_norm_max",
                            "client_norm_std"}
SERVER_KEYS = {"update_norm", "momentum_norm", "residual_norm"}


@pytest.mark.parametrize("cfg_kw,client_keys,extra", [
    # fused fast path (no per-client transmits): agg probes only
    (dict(mode="sketch", error_type="virtual", virtual_momentum=0.9),
     FUSED_KEYS | {"recovery_error"}, {"mass_coverage"}),
    (dict(mode="true_topk", error_type="virtual",
          virtual_momentum=0.9), FUSED_KEYS, {"mass_coverage"}),
    (dict(mode="uncompressed", virtual_momentum=0.9), FUSED_KEYS,
     set()),
    # per-client vmap path: transmit-norm stats appear
    (dict(mode="uncompressed", local_momentum=0.9), CLIENT_KEYS,
     set()),
    (dict(mode="local_topk", error_type="local", k=2), CLIENT_KEYS,
     set()),
    (dict(mode="fedavg", local_batch_size=-1, fedavg_batch_size=2,
          num_fedavg_epochs=1), CLIENT_KEYS, set()),
    # chunked scan path (sketch-late; microbatching defeats the fused
    # fast path so --client_chunk engages): dense accumulator + one
    # end-of-scan sketch, transmit norms ride the scan outputs
    (dict(mode="sketch", error_type="virtual", virtual_momentum=0.9,
          microbatch_size=1, client_chunk=1),
     CLIENT_KEYS | {"recovery_error"}, {"mass_coverage"}),
])
def test_probe_values_match_mirror(cfg_kw, client_keys, extra):
    cfg = make_cfg(**cfg_kw)
    rng = np.random.default_rng(7)
    d = 8
    w0 = rng.normal(size=d)
    rounds = [_round_data(rng, d) for _ in range(3)]
    lr = 0.3
    B = max(len(y) for rnd in rounds for _, _, y in rnd)
    eng = run_engine_probes(cfg, w0, rounds, lr)
    mir = run_mirror_probes(cfg, w0, rounds, lr, B=B)
    for e, m in zip(eng, mir):
        assert set(e) == client_keys | SERVER_KEYS | extra, sorted(e)
        for key in sorted(e):
            np.testing.assert_allclose(
                e[key], m[key], rtol=5e-4, atol=1e-5,
                err_msg=f"probe {key}")


DROPOUT_MODES = [
    # fused fast path under dropout: the WD share must follow the
    # alive-datapoint fraction (core/rounds.py _fused_local)
    (dict(mode="sketch", error_type="virtual", virtual_momentum=0.9),
     FUSED_KEYS | {"recovery_error"}, {"mass_coverage"}),
    (dict(mode="true_topk", error_type="virtual",
          virtual_momentum=0.9), FUSED_KEYS, {"mass_coverage"}),
    (dict(mode="uncompressed", local_momentum=0.9), CLIENT_KEYS,
     set()),
    (dict(mode="local_topk", error_type="local", k=2), CLIENT_KEYS,
     set()),
    (dict(mode="fedavg", local_batch_size=-1, fedavg_batch_size=2,
          num_fedavg_epochs=1), CLIENT_KEYS, set()),
]


@pytest.mark.parametrize("cfg_kw,client_keys,extra", DROPOUT_MODES)
def test_dropout_round_probes_match_mirror(cfg_kw, client_keys, extra):
    """Satellite of the chaos harness: a round with a DEAD slot
    (dropout / loader padding, all-zero mask) must produce the same
    probes as the mirror run over the alive clients only — the dead
    slot contributes nothing to the aggregate (weight decay included)
    and is excluded from the client-norm statistics. All five modes,
    with weight_decay nonzero so the WD share is pinned too."""
    cfg = make_cfg(weight_decay=0.01, dropout_prob=0.5, **cfg_kw)
    rng = np.random.default_rng(11)
    d = 8
    w0 = rng.normal(size=d)
    lr = 0.3
    full = [_round_data(rng, d, (3, 2)) for _ in range(3)]
    # round 1: client 1 is dropped (zero real samples -> all-zero mask)
    dead_cid, dead_X, dead_Y = full[1][1]
    full[1][1] = (dead_cid, dead_X[:0], dead_Y[:0])
    alive_only = [[(c, X, Y) for c, X, Y in rnd if len(Y)]
                  for rnd in full]
    B = max(len(y) for rnd in full for _, _, y in rnd)
    eng = run_engine_probes(cfg, w0, full, lr)
    mir = run_mirror_probes(cfg, w0, alive_only, lr, B=B)
    for e, m in zip(eng, mir):
        assert set(e) == client_keys | SERVER_KEYS | extra, sorted(e)
        for key in sorted(e):
            np.testing.assert_allclose(
                e[key], m[key], rtol=5e-4, atol=1e-5,
                err_msg=f"probe {key}")


@pytest.mark.parametrize("cfg_kw,client_keys,extra", DROPOUT_MODES)
def test_fully_dropped_round_aggregate_is_zero(cfg_kw, client_keys,
                                               extra):
    """Zero-averaging semantics on a FULLY-dropped round: nobody
    trained, so the aggregate must be exactly zero — in particular the
    fused path's analytic weight-decay term must not keep decaying the
    weights when every client's mask is zero."""
    cfg = dataclasses.replace(
        make_cfg(weight_decay=0.01, dropout_prob=0.5, **cfg_kw),
        grad_size=8)
    W, B, d = 2, 3, 8
    client_round = jax.jit(build_client_round(cfg, linear_loss, B))
    rng = np.random.default_rng(13)
    batch = {"x": jnp.asarray(rng.normal(size=(W, B, d)), jnp.float32),
             "y": jnp.asarray(rng.normal(size=(W, B)), jnp.float32),
             "mask": jnp.zeros((W, B), jnp.float32)}
    ps = jnp.asarray(rng.normal(size=d), jnp.float32)
    res = client_round(ps, ClientStates.init(cfg, 4, ps), batch,
                       jnp.arange(W, dtype=jnp.int32),
                       jax.random.PRNGKey(0), jnp.float32(0.3))
    np.testing.assert_array_equal(np.asarray(res.aggregated), 0.0)


def test_recovery_error_is_zero_for_lossless_sketch():
    """A sketch with more bucket capacity than coordinates and
    k >= d recovers exactly -> recovery_error == 0 (up to fp32)."""
    cfg = make_cfg(mode="sketch", error_type="virtual", k=8,
                   num_rows=7, num_cols=64)
    rng = np.random.default_rng(3)
    d = 6
    eng = run_engine_probes(cfg, rng.normal(size=d),
                            [_round_data(rng, d)], 0.3)
    assert eng[0]["recovery_error"] < 1e-5


def test_nan_counts_surface_in_probes():
    cfg = make_cfg(mode="uncompressed")
    rng = np.random.default_rng(5)
    d = 4
    rounds = [_round_data(rng, d)]
    # poison one client's labels: the gradient (hence the aggregate)
    # goes NaN and the probe must count it
    rounds[0][0][2][0] = np.nan
    eng = run_engine_probes(cfg, rng.normal(size=d), rounds, 0.1)
    assert eng[0]["agg_nan"] > 0


# --- probes-off program identity ---------------------------------------


def _lower_text(fn, cfg, d=8, B=3, W=2):
    ps = jax.ShapeDtypeStruct((d,), jnp.float32)
    cs = jax.eval_shape(
        lambda: ClientStates.init(cfg, 4, jnp.zeros((d,), jnp.float32)))
    batch = {"x": jax.ShapeDtypeStruct((W, B, d), jnp.float32),
             "y": jax.ShapeDtypeStruct((W, B), jnp.float32),
             "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
    ids = jax.ShapeDtypeStruct((W,), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    return jax.jit(fn).lower(ps, cs, batch, ids, rng, lr).as_text()


@pytest.mark.parametrize("mode,error_type", [
    ("sketch", "virtual"), ("true_topk", "virtual"),
    ("uncompressed", "none")])
def test_probes_off_program_identical(mode, error_type):
    """probes/probe_recovery are trace-time flags: a build without
    them must emit EXACTLY the program of a default build (the no-op
    overhead guarantee), while the probed build differs."""
    cfg = dataclasses.replace(
        make_cfg(mode=mode, error_type=error_type,
                 virtual_momentum=0.9), grad_size=8)
    default = _lower_text(build_client_round(cfg, linear_loss, 3), cfg)
    explicit_off = _lower_text(
        build_client_round(cfg, linear_loss, 3, probes=False,
                           probe_recovery=False), cfg)
    probed = _lower_text(
        build_client_round(cfg, linear_loss, 3, probes=True,
                           probe_recovery=True), cfg)
    assert default == explicit_off
    assert probed != default

    # observability knobs that live entirely on the host — the skew
    # alarm threshold reads trace-derived buckets, never the program —
    # must be invisible to the lowered HLO
    skew_cfg = dataclasses.replace(cfg, alarm_collective_skew=0.5)
    assert _lower_text(build_client_round(skew_cfg, linear_loss, 3),
                       skew_cfg) == default

    # robust-aggregation / chaos-harness knobs at their inert values
    # must be invisible too: --robust_agg none is a trace-time gate,
    # transmit_transform=None (chaos off) is the identical build path,
    # and the checkpoint/alarm cadences are host-only
    inert_cfg = dataclasses.replace(
        cfg, robust_agg="none", robust_trim_frac=0.2,
        robust_clip_norm=5.0, robust_median_groups=2,
        alarm_byzantine_ratio=4.0, alarm_fold_rejection=0.5,
        checkpoint_every_rounds=3, checkpoint_keep=2,
        # asyncfed knobs without --async_buffer_size: the staleness
        # weight and alarm threshold are host/trace-gated and must
        # not perturb a synchronous build
        async_staleness_weight=0.7, alarm_async_staleness=4.0,
        # --overlap_depth 1 is the serial program by construction:
        # none of the chunked-emission branches trace (the HLO
        # fingerprint identity every audit baseline pins on)
        overlap_depth=1,
        # live-operations plane: exporter port, flight-recorder ring,
        # SLO targets, and the burn-rate alarm are all host-side —
        # they observe the round stream, never enter the program
        live_port=1, flightrec_rounds=4, slo_round_p95=0.5,
        slo_staleness_max=2.0, slo_starvation=1.0,
        slo_window=16, slo_fast_window=4, alarm_slo_burn=2.0)
    assert _lower_text(
        build_client_round(inert_cfg, linear_loss, 3,
                           transmit_transform=None),
        inert_cfg) == default
    # alpha == 0 keeps even a client_weights build's WEIGHTING
    # branch untraced (the staleness arg itself is appended, so the
    # signature — not the fold math — is what differs)
    assert _lower_text(
        build_client_round(cfg, linear_loss, 3, client_weights=False),
        cfg) == default
    # ...while an ACTIVE overlap pipeline (sketch only, and only
    # once a quantized wire gives the chunks something to trace on a
    # single shard) changes the program: per-chunk qdq vs one
    # whole-table qdq
    if mode == "sketch":
        q1_cfg = dataclasses.replace(cfg, sketch_dtype="int8")
        q2_cfg = dataclasses.replace(q1_cfg, overlap_depth=2)
        assert _lower_text(build_client_round(q2_cfg, linear_loss, 3),
                           q2_cfg) != \
            _lower_text(build_client_round(q1_cfg, linear_loss, 3),
                        q1_cfg)
    # an ACTIVE robust fold, by contrast, changes the program
    med_cfg = dataclasses.replace(cfg, robust_agg="median")
    assert _lower_text(build_client_round(med_cfg, linear_loss, 3),
                       med_cfg) != default
    # ...and so does an active staleness-weighted fold
    aw_cfg = dataclasses.replace(cfg, async_buffer_size=2,
                                 async_staleness_weight=0.7)
    aw_round = build_client_round(aw_cfg, linear_loss, 3,
                                  client_weights=True)
    d, B, W = 8, 3, 2
    ps = jax.ShapeDtypeStruct((d,), jnp.float32)
    cs = jax.eval_shape(
        lambda: ClientStates.init(aw_cfg, 4, jnp.zeros((d,),
                                                       jnp.float32)))
    batch = {"x": jax.ShapeDtypeStruct((W, B, d), jnp.float32),
             "y": jax.ShapeDtypeStruct((W, B), jnp.float32),
             "mask": jax.ShapeDtypeStruct((W, B), jnp.float32)}
    assert jax.jit(aw_round).lower(
        ps, cs, batch, jax.ShapeDtypeStruct((W,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((W,), jnp.float32)).as_text() != default

    def _server_text(sr):
        ps = jax.ShapeDtypeStruct((8,), jnp.float32)
        ss = jax.eval_shape(lambda: ServerState.init(cfg))
        agg = ss.Verror if mode == "sketch" else ps
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        ids = jax.ShapeDtypeStruct((2,), jnp.int32)
        return jax.jit(sr).lower(ps, ss, agg, lr, None, ids).as_text()

    s_default = _server_text(build_server_round(cfg))
    s_off = _server_text(build_server_round(cfg, probes=False))
    s_on = _server_text(build_server_round(cfg, probes=True))
    assert s_default == s_off
    assert s_on != s_off


# --- alarm engine ------------------------------------------------------


def _cfg_alarms(**kw):
    base = dict(probe_every=1, on_divergence="log",
                alarm_residual_ratio=2.0, alarm_residual_rounds=2,
                alarm_recovery_error=0.9)
    base.update(kw)
    return make_cfg(**base)


def test_alarm_nan_inf_fires():
    eng = build_alarm_engine(_cfg_alarms())
    fired = eng.check(0, {"agg_nan": 0.0, "agg_inf": 0.0})
    assert fired == []
    fired = eng.check(1, {"agg_nan": 2.0, "agg_inf": 0.0})
    assert [a["rule"] for a in fired] == ["nan_inf"]


def test_alarm_residual_growth_needs_consecutive_rounds():
    eng = build_alarm_engine(_cfg_alarms())
    assert eng.check(0, {"residual_growth": 3.0}) == []  # 1st breach
    fired = eng.check(1, {"residual_growth": 3.0})      # 2nd: fires
    assert [a["rule"] for a in fired] == ["residual_growth"]
    # a healthy round resets the streak
    eng2 = build_alarm_engine(_cfg_alarms())
    eng2.check(0, {"residual_growth": 3.0})
    eng2.check(1, {"residual_growth": 1.0})
    assert eng2.check(2, {"residual_growth": 3.0}) == []


def test_alarm_recovery_error_fires():
    eng = build_alarm_engine(_cfg_alarms())
    assert eng.check(0, {"recovery_error": 0.5}) == []
    fired = eng.check(1, {"recovery_error": 0.95})
    assert [a["rule"] for a in fired] == ["recovery_error"]


def test_alarm_abort_raises_after_flagging():
    eng = build_alarm_engine(_cfg_alarms(on_divergence="abort"))
    with pytest.raises(DivergenceAbort) as exc:
        eng.check(4, {"agg_nan": 1.0})
    assert exc.value.round_index == 4
    assert "nan_inf" in str(exc.value)


def test_alarm_flags_ledger_record(tmp_path):
    from commefficient_tpu.telemetry.sinks import JSONLSink
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry([JSONLSink(path)])
    tel.begin_round(0)
    eng = AlarmEngine(_cfg_alarms(on_divergence="ledger-flag"),
                      telemetry=tel)
    eng.check(0, {"agg_inf": 3.0})
    tel.merge_round_probes(0, {"agg_inf": 3.0})
    tel.set_round_bytes(0, 1.0, 1.0)
    tel.close()
    with open(path) as f:
        rec = json.loads(f.readline())
    assert rec["alarms"] and rec["alarms"][0]["rule"] == "nan_inf"
    assert rec["probes"]["agg_inf"] == 3.0


def test_alarm_engine_off_without_probes():
    assert build_alarm_engine(make_cfg(probe_every=0)) is None


# --- disabled-telemetry fast path covers the new v2 calls --------------


def test_disabled_telemetry_probe_calls_are_noop():
    tel = Telemetry()
    assert not tel.enabled
    tel.merge_round_probes(0, {"agg_norm": 1.0})
    tel.flag_alarm(0, {"rule": "nan_inf"})
    assert not tel._records and tel._current is None


# --- end-to-end ledger round-trip (cv trainer) -------------------------


def _probe_rounds(path):
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    return [r for r in recs if r["kind"] == "round"]


def _cv_args(**kw):
    args = ["--test", "--dataset_name", "Synthetic",
            "--mode", "sketch", "--error_type", "virtual",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "4", "--num_epochs", "2",
            "--lr_scale", "0.1", "--pivot_epoch", "1", "--seed", "5"]
    for key, val in kw.items():
        args += [f"--{key}"] + ([] if val is None else [str(val)])
    return args


def test_probed_run_emits_v2_ledger(tmp_path):
    """Probe fields (introduced in schema v2) on a live ledger —
    records are stamped with the current schema (v3 since the
    device-time fields landed)."""
    from commefficient_tpu.telemetry.record import \
        LEDGER_SCHEMA_VERSION
    from commefficient_tpu.train import cv_train
    path = str(tmp_path / "run.jsonl")
    cv_train.main(_cv_args(probe_every=1, ledger=path))
    rounds = _probe_rounds(path)
    assert rounds
    for r in rounds:
        assert r["schema"] == LEDGER_SCHEMA_VERSION
        assert r["schema"] >= 2
        pr = r["probes"]
        for key in ("agg_norm", "agg_nan", "agg_inf", "update_norm",
                    "momentum_norm", "residual_norm", "mass_coverage",
                    "recovery_error"):
            assert np.isfinite(pr[key]), key
    # residual growth ratio needs two rounds of history
    assert "residual_growth" in rounds[-1]["probes"]


def test_divergence_abort_stops_run_and_flags_ledger(tmp_path):
    """A diverging run (astronomical lr -> NaN aggregate) under
    --on_divergence abort must stop at the offending round, and that
    round's record must carry the nan_inf alarm."""
    from commefficient_tpu.train import cv_train
    path = str(tmp_path / "abort.jsonl")
    results = cv_train.main(
        _cv_args(mode="uncompressed", error_type="none",
                 num_epochs="3", lr_scale="1e18",
                 probe_every=1, on_divergence="abort", ledger=path))
    # epoch 3 aborted mid-flight: its row never lands
    assert len(results) < 3
    rounds = _probe_rounds(path)
    last = rounds[-1]
    assert last["alarms"], "aborting round must be ledger-flagged"
    assert last["alarms"][-1]["rule"] == "nan_inf"
    assert last["alarms"][-1]["action"] == "abort"
    assert last["probes"]["agg_nan"] + last["probes"]["agg_inf"] > 0

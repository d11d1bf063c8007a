"""Performance-observatory tests: device-time trace attribution, the
run registry and its topology keys, the step-time alarm, stale-waiver
detection, and the end-to-end ``--profile`` path on a real CPU mesh.

The golden-trace test runs against ``tests/fixtures/mini.trace.json.gz``
— a hand-authored Chrome trace-event dump with two ``fed_round``
markers, overlapping compute/collective device events, a transfer that
straddles the round boundary, and events that attribution must ignore
(phase annotations, host-lane python frames, out-of-window ops). Its
bucket values are computed by hand and asserted exactly.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from commefficient_tpu.telemetry import registry, trace
from commefficient_tpu.telemetry.alarms import (AlarmEngine,
                                                DivergenceAbort)
from commefficient_tpu.telemetry.core import Telemetry
from commefficient_tpu.telemetry.record import (make_round_record,
                                                validate_record)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "mini.trace.json.gz")


# --- golden trace parser ----------------------------------------------


class TestTraceAttribution:
    def test_fixture_golden_buckets(self):
        """Hand-computed buckets for the checked-in mini trace.

        Round 0 window [1000, 2000) us: device busy = fusion.1 union
        all-reduce.2 (1100-1400) + copy.3 clipped (1900-2000) = 400 us;
        collective 150, transfer 100 (copy minus collective overlap:
        none), compute 150, host gap 600. Round 1 window [2000, 3500):
        copy.3 tail (2000-2100) + fusion.4 (2200-2500) = 400 busy,
        no collective, transfer 100, compute 300, gap 1100."""
        events = trace.load_trace_events(FIXTURE)
        buckets = trace.attribute_rounds(events)
        assert sorted(buckets) == [0, 1]
        # the v3 aggregate buckets must stay BIT-FOR-BIT what they
        # were before per-device attribution existed (the cross-device
        # union is the same interval set the old pooled path measured)
        agg = ("window_s", "busy_s", "compute_s", "collective_s",
               "transfer_s", "host_gap_s")
        assert {k: buckets[0][k] for k in agg} == {
            "window_s": 0.001, "busy_s": 0.0004,
            "compute_s": 0.00015, "collective_s": 0.00015,
            "transfer_s": 0.0001, "host_gap_s": 0.0006}
        assert {k: buckets[1][k] for k in agg} == {
            "window_s": 0.0015, "busy_s": 0.0004,
            "compute_s": 0.0003, "collective_s": 0.0,
            "transfer_s": 0.0001, "host_gap_s": 0.0011}
        # v4: the same rounds also carry per-device lanes (TPU:0 from
        # the /device: pid, cpu:30 from the tf_XLA thread) and skew
        # stats — the all-reduce here runs on ONE lane, so there is no
        # cross-device group to align and no skew
        assert sorted(buckets[0]["per_device"]) == ["TPU:0", "cpu:30"]
        assert buckets[0]["per_device"]["TPU:0"] == {
            "busy_s": 0.0004, "compute_s": 0.00015,
            "collective_s": 0.00015, "transfer_s": 0.0001,
            "wait_s": 0.0, "wire_s": 0.00015}
        assert buckets[1]["per_device"]["cpu:30"] == {
            "busy_s": 0.0003, "compute_s": 0.0003,
            "collective_s": 0.0, "transfer_s": 0.0,
            "wait_s": 0.0, "wire_s": 0.0}
        for r in (0, 1):
            assert buckets[r]["skew"]["n_collectives"] == 0
            assert buckets[r]["skew"]["straggler_device"] is None

    def test_buckets_partition_each_window(self):
        buckets = trace.attribute_rounds(
            trace.load_trace_events(FIXTURE))
        for b in buckets.values():
            parts = (b["compute_s"] + b["collective_s"]
                     + b["transfer_s"] + b["host_gap_s"])
            assert abs(parts - b["window_s"]) < 1e-9
            assert abs((b["busy_s"] + b["host_gap_s"])
                       - b["window_s"]) < 1e-9

    def test_device_lanes_exclude_host_python(self):
        events = trace.load_trace_events(FIXTURE)
        lanes = trace.device_lanes(events)
        # pid 2 is a /device: process, pid 3 hosts a tf_XLA* thread;
        # pid 1 (host python, where the round markers live) is not a
        # device lane
        assert (2, 20) in lanes and (3, 30) in lanes
        assert all(pid != 1 for pid, _tid in lanes)

    def test_round_windows_from_markers(self):
        events = trace.load_trace_events(FIXTURE)
        windows = trace.round_windows(events)
        assert windows == [(0, 1000.0, 2000.0),
                           (1, 2000.0, 3500.0)]

    def test_attribute_logdir_finds_gz(self, tmp_path):
        sub = tmp_path / "plugins" / "profile" / "x"
        sub.mkdir(parents=True)
        with open(FIXTURE, "rb") as f:
            (sub / "host.trace.json.gz").write_bytes(f.read())
        buckets = trace.attribute_logdir(str(tmp_path))
        assert sorted(buckets) == [0, 1]

    def test_no_markers_no_rounds(self):
        events = [{"ph": "M", "pid": 2, "name": "process_name",
                   "args": {"name": "/device:TPU:0"}},
                  {"ph": "X", "pid": 2, "tid": 1, "name": "fusion.1",
                   "ts": 10, "dur": 5, "args": {}}]
        assert trace.attribute_rounds(events) == {}


# --- run registry ----------------------------------------------------


class _Cfg:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class TestRunRegistry:
    def test_manifest_round_trip(self, tmp_path):
        ledger = str(tmp_path / "a.jsonl")
        open(ledger, "w").close()
        args = _Cfg(mode="sketch", k=16, ledger=ledger,
                    do_profile=True)
        path = registry.write_manifest(
            str(tmp_path / "runs"), args=args, ledger=ledger,
            bench={"clients_per_s": {"value": 10.0}},
            mesh_shape={"data": 8}, extra={"trainer": "test"})
        manifests = registry.list_manifests(str(tmp_path / "runs"))
        assert [p for p, _ in manifests] == [path]
        rec = manifests[0][1]
        assert rec["kind"] == "run_manifest"
        assert rec["schema"] == registry.MANIFEST_SCHEMA
        assert rec["config_hash"] == registry.config_hash(args)
        assert rec["ledger"] == os.path.abspath(ledger)
        assert rec["trainer"] == "test"
        assert rec["mesh_shape"] == {"data": 8}
        hits = registry.latest_ledgers(str(tmp_path / "runs"))
        assert hits == [(path, rec, os.path.abspath(ledger))]

    def test_config_hash_ignores_observability_knobs(self):
        a = _Cfg(mode="sketch", k=16, ledger="x.jsonl",
                 do_profile=True, telemetry_console=True)
        b = _Cfg(mode="sketch", k=16, ledger="y.jsonl",
                 do_profile=False, telemetry_console=False)
        c = _Cfg(mode="sketch", k=32, ledger="x.jsonl",
                 do_profile=True, telemetry_console=True)
        assert registry.config_hash(a) == registry.config_hash(b)
        assert registry.config_hash(a) != registry.config_hash(c)

    def test_latest_ledgers_skips_deleted(self, tmp_path):
        runs = str(tmp_path / "runs")
        led1 = str(tmp_path / "old.jsonl")
        led2 = str(tmp_path / "gone.jsonl")
        open(led1, "w").close()
        open(led2, "w").close()
        registry.write_manifest(runs, args=_Cfg(x=1), ledger=led1)
        registry.write_manifest(runs, args=_Cfg(x=2), ledger=led2)
        os.remove(led2)
        hits = registry.latest_ledgers(runs, n=2)
        assert [h[2] for h in hits] == [os.path.abspath(led1)]

    def test_maybe_write_manifest_gates(self, tmp_path):
        # no ledger -> no manifest; --test smoke -> no manifest
        assert registry.maybe_write_manifest(
            _Cfg(ledger=""), runs_dir=str(tmp_path)) is None
        assert registry.maybe_write_manifest(
            _Cfg(ledger="x.jsonl", do_test=True),
            runs_dir=str(tmp_path)) is None
        assert registry.list_manifests(str(tmp_path)) == []


# --- step-time alarm --------------------------------------------------


class _AlarmCfg:
    on_divergence = "ledger-flag"
    alarm_residual_ratio = 10.0
    alarm_residual_rounds = 3
    alarm_recovery_error = 1.0
    alarm_step_time_ratio = 2.0
    alarm_step_time_window = 8


class TestStepTimeAlarm:
    def test_warmup_then_fire_then_keep_firing(self):
        eng = AlarmEngine(_AlarmCfg())
        for r in range(AlarmEngine.STEP_TIME_WARMUP):
            assert eng.check_step_time(r, 0.1) == []
        # healthy round within ratio x median: no alarm
        assert eng.check_step_time(5, 0.15) == []
        fired = eng.check_step_time(6, 0.5)
        assert fired and fired[0]["rule"] == "step_time_regression"
        assert fired[0]["threshold"] == pytest.approx(0.2)
        assert fired[0]["rolling_median"] == pytest.approx(0.1)
        # firing samples are NOT folded into the window, so a
        # sustained regression keeps firing instead of becoming the
        # new normal
        assert eng.check_step_time(7, 0.5)
        assert eng.check_step_time(8, 0.5)

    def test_flags_ledger_record(self):
        tel = Telemetry(sinks=[_ListSink()])
        tel.begin_round(0)
        eng = AlarmEngine(_AlarmCfg(), telemetry=tel)
        for r in range(AlarmEngine.STEP_TIME_WARMUP):
            eng.check_step_time(r, 0.1)
        eng.check_step_time(0, 0.9)
        rec = tel._records[0]
        assert rec["alarms"] and \
            rec["alarms"][0]["rule"] == "step_time_regression"

    def test_abort_action_raises(self):
        class Abort(_AlarmCfg):
            on_divergence = "abort"
        eng = AlarmEngine(Abort())
        for r in range(AlarmEngine.STEP_TIME_WARMUP):
            eng.check_step_time(r, 0.1)
        with pytest.raises(DivergenceAbort):
            eng.check_step_time(5, 0.9)

    def test_disarmed_when_ratio_zero(self):
        class Off(_AlarmCfg):
            alarm_step_time_ratio = 0.0
        eng = AlarmEngine(Off())
        for r in range(20):
            assert eng.check_step_time(r, 100.0) == []

    def test_build_alarm_engine_arms_on_step_time_alone(self):
        from commefficient_tpu.telemetry.alarms import \
            build_alarm_engine

        class NoProbes(_AlarmCfg):
            probe_period = 0
        assert build_alarm_engine(NoProbes()) is not None

        class Nothing(_AlarmCfg):
            probe_period = 0
            alarm_step_time_ratio = 0.0
        assert build_alarm_engine(Nothing()) is None


# --- stale waivers ----------------------------------------------------


class TestStaleWaivers:
    def test_live_orphan_and_unknown(self, tmp_path):
        from commefficient_tpu.analysis import lint
        (tmp_path / "a.py").write_text(
            "# audit: allow(mutable-default-arg)\n"   # live: covers L2
            "def f(a=[]):\n"
            "    return a\n"
            "\n"
            "# audit: allow(mutable-default-arg)\n"   # orphan
            "x = 1\n"
            "\n"
            "# audit: allow(no-such-rule)\n"          # typo'd rule
            "y = 2\n")
        violations = lint.run_lint(root=tmp_path)
        assert [v.waived for v in violations] == [True]
        stale = lint.stale_waivers(root=tmp_path,
                                   violations=violations)
        assert len(stale) == 2
        assert any("a.py:5" in s and "stale waiver" in s
                   for s in stale)
        assert any("a.py:8" in s and "unknown rule" in s
                   for s in stale)

    def test_repo_has_no_stale_waivers(self):
        from commefficient_tpu.analysis import lint
        assert lint.stale_waivers() == []

    def test_stale_waivers_are_hard_failures(self):
        from commefficient_tpu.analysis import baseline as base_mod
        from commefficient_tpu.analysis import lint
        summary = lint.lint_report(
            [], stale=["a.py:5: stale waiver allow(host-sync) — ..."])
        report = base_mod.build_report(
            {"programs": {}, "failures": []}, summary)
        assert any("stale waiver" in f for f in report["failures"])
        # ...and can never be baselined in: the pinned subset keeps
        # only the waived list
        base = base_mod.to_baseline(
            {"programs": {}, "jax_version": "x", "device_count": 8,
             "lint": summary, "failures": []})
        assert "stale_waivers" not in base["lint"]


# --- telemetry emission hold ------------------------------------------


class _ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


class TestEmissionHold:
    def test_hold_buffers_then_merges_device_time(self):
        sink = _ListSink()
        tel = Telemetry(sinks=[sink])
        tel.hold_emission(True)
        for r in range(2):
            tel.begin_round(r)
            tel.set_round_bytes(r, 10.0, 20.0)
        tel.begin_round(2)        # swaps round 1 out
        tel.close_round()         # and this finishes it
        tel.set_round_bytes(2, 10.0, 20.0)
        assert sink.records == []  # everything buffered by the hold
        buckets = {"window_s": 1.0, "busy_s": 0.5, "compute_s": 0.4,
                   "collective_s": 0.1, "transfer_s": 0.0,
                   "host_gap_s": 0.5}
        tel.merge_round_device_time(0, buckets)
        tel.merge_round_device_time(1, buckets)
        tel.hold_emission(False)
        emitted = [r["round"] for r in sink.records
                   if r["kind"] == "round"]
        assert emitted == [0, 1]   # round order preserved
        assert all(r["device_time"] == buckets for r in sink.records
                   if r["kind"] == "round")
        tel.close()
        assert [r["round"] for r in sink.records
                if r["kind"] == "round"] == [0, 1, 2]

    def test_close_overrides_hold(self):
        sink = _ListSink()
        tel = Telemetry(sinks=[sink])
        tel.hold_emission(True)
        tel.begin_round(0)
        tel.close()
        assert [r["round"] for r in sink.records
                if r["kind"] == "round"] == [0]


# --- end-to-end: --profile on the CPU mesh ----------------------------


class TestProfileIntegration:
    def test_profiled_run_attributes_device_time(self, tmp_path):
        """The acceptance criterion: a ``--profile``'d CPU run
        produces a schema-v3 ledger whose per-round device-time
        buckets sum to the round window exactly, and whose windows
        together cover the in-trace wall time to within 10% (+ a
        small absolute epsilon for trace start/stop edges)."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.runtime import FedModel, FedOptimizer
        from commefficient_tpu.telemetry import clock
        from commefficient_tpu.telemetry.profiler import trace_window

        class Lin(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(64, use_bias=False)(x)

        module = Lin()
        params = module.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 32)))["params"]
        ledger = str(tmp_path / "ledger.jsonl")
        args = Config(mode="sketch", error_type="virtual",
                      local_momentum=0.0, virtual_momentum=0.9,
                      num_workers=2, local_batch_size=4,
                      num_clients=4, dataset_name="CIFAR10", seed=0,
                      k=16, num_rows=3, num_cols=256)
        args.ledger = ledger
        args.do_profile = True

        def loss(p, batch, cfg):
            pred = module.apply({"params": p}, batch["x"])
            n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
            return (jnp.sum(pred ** 2 * batch["mask"][..., None])
                    / n, ())

        model = FedModel(module, params, loss, args,
                         padded_batch_size=4)
        opt = FedOptimizer([{"lr": 0.1}], args)
        rng = np.random.RandomState(0)

        def mk(r):
            return {"x": rng.randn(2, 4, 32).astype(np.float32),
                    "y": rng.randn(2, 4).astype(np.float32),
                    "mask": np.ones((2, 4), np.float32),
                    "client_ids": np.array([r % 4, (r + 1) % 4],
                                           np.int32)}

        # round 0 outside the window carries compile/warmup
        model(mk(0))
        opt.step()
        logdir = str(tmp_path / "trace")
        with trace_window(logdir, telemetry=model.telemetry):
            t0 = clock.tick()
            for r in range(1, 5):
                model(mk(r))
                opt.step()
            jax.block_until_ready(model.ps_weights)
            loop_wall = clock.tick() - t0
        model.finalize()

        recs = [json.loads(line) for line in open(ledger)]
        assert all(not validate_record(r) for r in recs)
        rounds = [r for r in recs if r["kind"] == "round"]
        assert len(rounds) == 5
        assert all(r["schema"] == 9 for r in rounds)

        traced = [r for r in rounds if r.get("device_time")]
        assert [r["round"] for r in traced] == [1, 2, 3, 4]
        total_window = 0.0
        for r in traced:
            dt = r["device_time"]
            parts = (dt["compute_s"] + dt["collective_s"]
                     + dt["transfer_s"] + dt["host_gap_s"])
            assert abs(parts - dt["window_s"]) < 1e-5
            assert dt["busy_s"] > 0
            # v4: real traces carry per-device lanes whose wait+wire
            # split partitions each device's collective bucket exactly
            assert dt["per_device"]
            for lane in dt["per_device"].values():
                assert lane["wait_s"] + lane["wire_s"] == \
                    pytest.approx(lane["collective_s"], abs=1e-9)
            assert dt["skew"]["n_collectives"] >= 0
            total_window += dt["window_s"]
        # windows tile the in-trace loop: the last window extends to
        # the trace stop — 10% relative + 50ms absolute covers it
        assert abs(total_window - loop_wall) <= \
            0.1 * loop_wall + 0.05

        trace_meta = [r for r in recs if r["kind"] == "meta"
                      and r.get("trace_rounds")]
        assert len(trace_meta) == 1
        assert trace_meta[0]["trace_rounds"] == 4
        assert trace_meta[0]["trace_busy_s"] > 0


# --- v4: per-device attribution + collective skew ---------------------


SKEW_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                            "skew.trace.json.gz")
OVERLAP_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                               "overlap.trace.json.gz")

AGG_KEYS = ("window_s", "busy_s", "compute_s", "collective_s",
            "transfer_s", "host_gap_s")


class TestSkewAttribution:
    """``skew.trace.json.gz``: two TPU device lanes whose all-reduces
    enter at different times. Round 0: TPU:0 enters all-reduce.7 at
    1300, TPU:1 (the straggler — still computing) at 1450, both exit
    1600 — so TPU:0's collective 300 us splits into 150 us *wait* and
    150 us *wire*, TPU:1's 150 us is all wire. Round 1: a 20 us enter
    delta on all-reduce.8 plus a single-participant reduce-scatter on
    TPU:0 (no peer group: all wire, excluded from skew stats)."""

    def test_fixture_golden_per_device_buckets(self):
        buckets = trace.attribute_rounds(
            trace.load_trace_events(SKEW_FIXTURE))
        assert sorted(buckets) == [0, 1]
        b0 = buckets[0]
        assert {k: b0[k] for k in AGG_KEYS} == {
            "window_s": 0.002, "busy_s": 0.0006,
            "compute_s": 0.0002, "collective_s": 0.0003,
            "transfer_s": 0.0001, "host_gap_s": 0.0014}
        assert b0["per_device"] == {
            "TPU:0": {"busy_s": 0.0006, "compute_s": 0.0002,
                      "collective_s": 0.0003, "transfer_s": 0.0001,
                      "wait_s": 0.00015, "wire_s": 0.00015},
            "TPU:1": {"busy_s": 0.0005, "compute_s": 0.00035,
                      "collective_s": 0.00015, "transfer_s": 0.0,
                      "wait_s": 0.0, "wire_s": 0.00015}}
        assert b0["skew"] == {
            "n_collectives": 1, "max_enter_delta_s": 0.00015,
            "p95_enter_delta_s": 0.00015, "straggler_device": "TPU:1"}
        b1 = buckets[1]
        assert {k: b1[k] for k in AGG_KEYS} == {
            "window_s": 0.001, "busy_s": 0.0003,
            "compute_s": 0.0, "collective_s": 0.0003,
            "transfer_s": 0.0, "host_gap_s": 0.0007}
        assert b1["per_device"] == {
            "TPU:0": {"busy_s": 0.0003, "compute_s": 0.0,
                      "collective_s": 0.0003, "transfer_s": 0.0,
                      "wait_s": 2e-05, "wire_s": 0.00028},
            "TPU:1": {"busy_s": 0.00018, "compute_s": 0.0,
                      "collective_s": 0.00018, "transfer_s": 0.0,
                      "wait_s": 0.0, "wire_s": 0.00018}}
        assert b1["skew"] == {
            "n_collectives": 1, "max_enter_delta_s": 2e-05,
            "p95_enter_delta_s": 2e-05, "straggler_device": "TPU:1"}

    def test_wait_plus_wire_partitions_collective_exactly(self):
        """Per device, wait_s + wire_s must reproduce collective_s
        EXACTLY (wire is computed as the rounded difference, so the
        identity survives 6-dp rounding), and each lane's busy time
        must partition into compute + collective + transfer."""
        for fixture in (FIXTURE, SKEW_FIXTURE, OVERLAP_FIXTURE):
            buckets = trace.attribute_rounds(
                trace.load_trace_events(fixture))
            for b in buckets.values():
                for dev, lane in b["per_device"].items():
                    assert lane["wait_s"] + lane["wire_s"] == \
                        pytest.approx(lane["collective_s"],
                                      abs=1e-12), (fixture, dev)
                    assert lane["compute_s"] + lane["collective_s"] \
                        + lane["transfer_s"] == \
                        pytest.approx(lane["busy_s"], abs=1e-12)

    def test_aggregate_never_exceeds_lane_sums(self):
        """The aggregate buckets are the cross-device interval UNION:
        concurrent work on two lanes collapses, so aggregate busy is
        bounded by the per-lane sum and dominated by every single
        lane."""
        buckets = trace.attribute_rounds(
            trace.load_trace_events(SKEW_FIXTURE))
        for b in buckets.values():
            lane_busy = [l["busy_s"] for l in b["per_device"].values()]
            assert max(lane_busy) <= b["busy_s"] + 1e-12
            assert b["busy_s"] <= sum(lane_busy) + 1e-12

    def test_v4_buckets_validate_and_round_trip(self):
        buckets = trace.attribute_rounds(
            trace.load_trace_events(SKEW_FIXTURE))
        rec = make_round_record(7)
        rec["device_time"] = buckets[0]
        assert validate_record(rec) == []
        back = json.loads(json.dumps(rec))
        assert validate_record(back) == []
        assert back["device_time"] == rec["device_time"]

    def test_overlap_fixture_golden_buckets(self):
        """``overlap.trace.json.gz``: two TPU lanes, round 0 in the
        pipelined shape (all-reduce.5 [1400,1600) runs while TPU:1 is
        still inside fusion.3 until 1450 — 50 us of the pooled
        collective union intersects some lane's compute), round 1 the
        serial shape (all-reduce.7 starts only after every fusion has
        ended — zero intersection). All values hand-computed."""
        buckets = trace.attribute_rounds(
            trace.load_trace_events(OVERLAP_FIXTURE))
        assert sorted(buckets) == [0, 1]
        b0 = buckets[0]
        assert {k: b0[k] for k in AGG_KEYS} == {
            "window_s": 0.001, "busy_s": 0.0007,
            "compute_s": 0.0004, "collective_s": 0.0002,
            "transfer_s": 0.0001, "host_gap_s": 0.0003}
        assert b0["overlapped_s"] == 5e-05
        assert b0["per_device"] == {
            "TPU:0": {"busy_s": 0.0007, "compute_s": 0.0005,
                      "collective_s": 0.0002, "transfer_s": 0.0,
                      "wait_s": 5e-05, "wire_s": 0.00015},
            "TPU:1": {"busy_s": 0.0004, "compute_s": 0.00015,
                      "collective_s": 0.00015, "transfer_s": 0.0001,
                      "wait_s": 0.0, "wire_s": 0.00015}}
        assert b0["skew"] == {
            "n_collectives": 1, "max_enter_delta_s": 5e-05,
            "p95_enter_delta_s": 5e-05, "straggler_device": "TPU:1"}
        b1 = buckets[1]
        assert {k: b1[k] for k in AGG_KEYS} == {
            "window_s": 0.001, "busy_s": 0.0004,
            "compute_s": 0.0002, "collective_s": 0.0002,
            "transfer_s": 0.0, "host_gap_s": 0.0006}
        assert b1["overlapped_s"] == 0.0
        assert b1["per_device"] == {
            "TPU:0": {"busy_s": 0.0004, "compute_s": 0.0002,
                      "collective_s": 0.0002, "transfer_s": 0.0,
                      "wait_s": 0.0, "wire_s": 0.0002},
            "TPU:1": {"busy_s": 0.00035, "compute_s": 0.00015,
                      "collective_s": 0.0002, "transfer_s": 0.0,
                      "wait_s": 0.0, "wire_s": 0.0002}}

    def test_overlapped_is_an_overlay_not_a_fifth_bucket(self):
        """``overlapped_s`` bounds and partition exactness on every
        checked-in fixture: 0 <= overlapped <= collective, and the
        four real buckets still sum to the window to 1e-12 — the
        overlay must never perturb the partition."""
        for fixture in (FIXTURE, SKEW_FIXTURE, OVERLAP_FIXTURE):
            buckets = trace.attribute_rounds(
                trace.load_trace_events(fixture))
            for b in buckets.values():
                assert 0.0 <= b["overlapped_s"] <= \
                    b["collective_s"] + 1e-12, fixture
                parts = (b["compute_s"] + b["collective_s"]
                         + b["transfer_s"] + b["host_gap_s"])
                assert parts == pytest.approx(b["window_s"],
                                              abs=1e-12), fixture

class _SkewAlarmCfg(_AlarmCfg):
    alarm_collective_skew = 0.4


class TestCollectiveSkewAlarm:
    BUCKETS = {"window_s": 1.0, "busy_s": 0.6, "compute_s": 0.5,
               "collective_s": 0.1, "transfer_s": 0.0,
               "host_gap_s": 0.4}

    @staticmethod
    def _with_skew(delta, straggler="TPU:3"):
        b = dict(TestCollectiveSkewAlarm.BUCKETS)
        b["skew"] = {"n_collectives": 2, "max_enter_delta_s": delta,
                     "p95_enter_delta_s": delta,
                     "straggler_device": straggler}
        return b

    def test_fires_above_collective_fraction(self):
        eng = AlarmEngine(_SkewAlarmCfg())
        # threshold = 0.4 x collective_s 0.1 = 0.04 s of skew
        assert eng.check_device_time(0, self._with_skew(0.03)) == []
        fired = eng.check_device_time(1, self._with_skew(0.05))
        assert fired and fired[0]["rule"] == "collective_skew"
        assert fired[0]["straggler_device"] == "TPU:3"
        assert fired[0]["value"] == pytest.approx(0.05)
        assert fired[0]["threshold"] == pytest.approx(0.04)

    def test_no_collective_no_fire(self):
        eng = AlarmEngine(_SkewAlarmCfg())
        b = self._with_skew(0.5)
        b["collective_s"] = 0.0
        assert eng.check_device_time(0, b) == []
        # v3 buckets without skew never fire either
        assert eng.check_device_time(1, dict(self.BUCKETS)) == []

    def test_disarmed_when_zero(self):
        class Off(_AlarmCfg):
            alarm_collective_skew = 0.0
        eng = AlarmEngine(Off())
        assert eng.check_device_time(0, self._with_skew(9.9)) == []

    def test_flags_ledger_record_through_telemetry(self):
        sink = _ListSink()
        tel = Telemetry(sinks=[sink])
        tel.hold_emission(True)
        tel.begin_round(0)
        eng = AlarmEngine(_SkewAlarmCfg(), telemetry=tel)
        tel.on_device_time = eng.check_device_time
        tel.merge_round_device_time(0, self._with_skew(0.09))
        tel.hold_emission(False)
        tel.close()
        rounds = [r for r in sink.records if r["kind"] == "round"]
        assert rounds[0]["alarms"]
        assert rounds[0]["alarms"][0]["rule"] == "collective_skew"

    def test_abort_action_raises_from_merge(self):
        class Abort(_SkewAlarmCfg):
            on_divergence = "abort"
        tel = Telemetry(sinks=[_ListSink()])
        tel.begin_round(0)
        eng = AlarmEngine(Abort(), telemetry=tel)
        tel.on_device_time = eng.check_device_time
        with pytest.raises(DivergenceAbort):
            tel.merge_round_device_time(0, self._with_skew(0.5))

    def test_build_alarm_engine_arms_on_skew_alone(self):
        from commefficient_tpu.telemetry.alarms import \
            build_alarm_engine

        class OnlySkew(_AlarmCfg):
            probe_period = 0
            alarm_step_time_ratio = 0.0
            alarm_collective_skew = 0.5
        assert build_alarm_engine(OnlySkew()) is not None

        class Nothing(OnlySkew):
            alarm_collective_skew = 0.0
        assert build_alarm_engine(Nothing()) is None


# --- cross-host ledger shards -----------------------------------------


def _load_script(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _ShardCfg:
    def __init__(self, ledger, console=False):
        self.ledger = ledger
        self.telemetry_console = console


class TestLedgerShards:
    def test_shard_path_naming(self):
        from commefficient_tpu.telemetry.sinks import shard_ledger_path
        assert shard_ledger_path("/x/a.jsonl", 0) == "/x/a.jsonl"
        assert shard_ledger_path("/x/a.jsonl", 1) == \
            "/x/a.jsonl.p1.jsonl"
        assert shard_ledger_path("/x/a.jsonl", 3) == \
            "/x/a.jsonl.p3.jsonl"

    def test_every_process_writes_its_shard(self, tmp_path, capsys):
        """The old process-0 gate silently dropped every other host's
        telemetry; now process k > 0 writes a process-stamped shard
        and says so once."""
        from commefficient_tpu.telemetry.core import build_telemetry
        ledger = str(tmp_path / "led.jsonl")

        tel0 = build_telemetry(_ShardCfg(ledger), process_index=0,
                               process_count=2)
        tel0.begin_round(0)
        tel0.close()
        tel1 = build_telemetry(_ShardCfg(ledger), process_index=1,
                               process_count=2)
        tel1.begin_round(0)
        tel1.close()

        assert os.path.exists(ledger)
        shard = ledger + ".p1.jsonl"
        assert os.path.exists(shard)
        out = capsys.readouterr().out
        assert "ledger shard" in out and ".p1.jsonl" in out
        canon = [json.loads(l) for l in open(ledger)]
        shrd = [json.loads(l) for l in open(shard)]
        assert all(not validate_record(r) for r in canon + shrd)
        # both sides are process-stamped on a multi-process mesh
        assert {r["process"] for r in canon} == {0}
        assert {r["process"] for r in shrd} == {1}

    def test_single_process_is_unstamped(self, tmp_path):
        from commefficient_tpu.telemetry.core import build_telemetry
        ledger = str(tmp_path / "solo.jsonl")
        tel = build_telemetry(_ShardCfg(ledger), process_index=0,
                              process_count=1)
        tel.begin_round(0)
        tel.close()
        recs = [json.loads(l) for l in open(ledger)]
        assert recs and all("process" not in r for r in recs)

    def _write_shard_fixture(self, tmp_path):
        ledger = str(tmp_path / "fleet.jsonl")
        with open(ledger, "w") as f:
            meta = {"schema": 1, "kind": "meta", "ts": 0.0,
                    "num_devices": 4, "process_count": 2}
            f.write(json.dumps(meta) + "\n")
            for r in range(2):
                rec = make_round_record(r)
                rec["spans"] = {"round_dispatch": 0.05}
                rec["device_time"] = {
                    "window_s": 0.1, "busy_s": 0.08,
                    "compute_s": 0.07, "collective_s": 0.01,
                    "transfer_s": 0.0, "host_gap_s": 0.02}
                f.write(json.dumps(rec) + "\n")
        shard = ledger + ".p1.jsonl"
        with open(shard, "w") as f:
            f.write(json.dumps({"schema": 1, "kind": "meta",
                                "ts": 0.0, "process": 1}) + "\n")
            for r in range(3):  # round 2 exists ONLY on the shard
                rec = make_round_record(r)
                rec["process"] = 1
                rec["spans"] = {"client_feed": 0.01}
                rec["host_rss_peak_bytes"] = 1000.0 + r
                rec["uplink_bytes"] = 64.0
                rec["device_time"] = {
                    "window_s": 0.1, "busy_s": 0.06,
                    "compute_s": 0.05, "collective_s": 0.01,
                    "transfer_s": 0.0, "host_gap_s": 0.04}
                f.write(json.dumps(rec) + "\n")
        return ledger, shard

    def test_merge_joins_shards_on_round_id(self, tmp_path):
        lm = _load_script("ledger_merge")
        ledger, shard = self._write_shard_fixture(tmp_path)
        assert lm.discover_shards(ledger) == [(1, shard)]
        assert lm.main([ledger]) == 0
        merged_path = ledger + ".merged.jsonl"
        assert os.path.exists(merged_path)
        merged = [json.loads(l) for l in open(merged_path)]
        rounds = [r for r in merged if r.get("kind") == "round"]
        assert [r["round"] for r in rounds] == [0, 1, 2]
        for r in rounds[:2]:
            sh = r["shards"]["p1"]
            assert sh["spans"] == {"client_feed": 0.01}
            assert sh["uplink_bytes"] == 64.0
            # per-host host gap: the multi-host straggler scoreboard
            assert r["host_gap_by_process"] == {
                "p0": 0.02, "p1": 0.04}
        # the round only process 1 survived to record is kept, flagged
        assert rounds[2]["shard_only"] is True
        assert rounds[2]["process"] == 1
        # shard meta dropped: only the canonical meta remains
        metas = [r for r in merged if r.get("kind") == "meta"]
        assert len(metas) == 1 and "process" not in metas[0]

    def test_merge_of_shards_with_old_causal_stamps_does_not_fail(
            self, tmp_path, capsys):
        """A v7 ledger written while ``--causal_trace`` existed still
        merges: each record keeps the stamp it came with, unread, and
        the summary says nothing of traces."""
        lm = _load_script("ledger_merge")
        ledger, shard = self._write_shard_fixture(tmp_path)
        for path, job in ((ledger, "solo"), (shard, "solo")):
            recs = [json.loads(l) for l in open(path)]
            for rec in recs:
                if rec.get("kind") == "round":
                    r = rec["round"]
                    rec["schema"] = 7
                    rec["causal"] = {
                        "trace": f"j{job}.r{r}", "job": None,
                        "round": r, "wall": 0.1,
                        "spans": [{"id": f"j{job}.r{r}.s9",
                                   "parent": f"j{job}.r{r}.s0",
                                   "name": "h2d", "bucket": "h2d",
                                   "b": 0.0, "e": 0.05}]}
            with open(path, "w") as f:
                f.writelines(json.dumps(rec) + "\n" for rec in recs)
        assert lm.main([ledger]) == 0
        out = capsys.readouterr()
        assert "2 round(s) joined" in out.out
        assert "causal" not in out.out and "WARNING" not in out.err
        merged = [json.loads(l) for l in open(ledger + ".merged.jsonl")]
        rounds = [r for r in merged if r.get("kind") == "round"]
        assert [r["round"] for r in rounds] == [0, 1, 2]
        for rec in rounds:
            assert validate_record(rec) == []
            # the canonical record's own stamp, not a union of shards'
            assert len(rec["causal"]["spans"]) == 1
        assert "causal" not in rounds[0]["shards"]["p1"]

    def test_merge_without_shards_is_an_error(self, tmp_path):
        lm = _load_script("ledger_merge")
        ledger = str(tmp_path / "solo.jsonl")
        with open(ledger, "w") as f:
            f.write(json.dumps(make_round_record(0)) + "\n")
        assert lm.main([ledger]) == 1

    def test_report_summarizes_merged_shards(self, tmp_path):
        lm = _load_script("ledger_merge")
        tr = _load_script("telemetry_report")
        ledger, _ = self._write_shard_fixture(tmp_path)
        assert lm.main([ledger]) == 0
        records, problems = tr.load_ledger(ledger + ".merged.jsonl")
        assert problems == []
        summ = tr.summarize(records)
        assert summ["shards"]["p1"]["rounds"] == 2
        assert summ["shards"]["p1"]["host_gap_mean_ms"] == \
            pytest.approx(40.0)
        assert summ["shards"]["p1"]["host_rss_peak_bytes"] == 1001.0
        rendered = tr.render_summary(summ, label="merged")
        assert "shard p1" in rendered


# --- registry topology keys -------------------------------------------


class TestRegistryTopologyKeys:
    def test_mesh_shape_extends_topology_key(self):
        """2D-mesh runs key separately per shape; 1-D layouts keep
        the historical mesh-less key."""
        ms = {"clients": 4, "model": 2}
        assert registry.topology_key(8, 1, ms) == "d8p1m4x2"
        assert registry.topology_key(
            8, 1, {"clients": 2, "model": 4}) == "d8p1m2x4"
        assert registry.topology_key(
            8, 1, {"clients": 8, "model": 1}) == "d8p1"
        assert registry.topology_key(8, 1, None) == "d8p1"
        assert registry.topology_key(None, None, ms) == \
            registry.ANY_TOPOLOGY

    def test_run_topology_and_key(self):
        m = {"config_hash": "c", "device_count": 8, "process_count": 2}
        assert registry.run_topology(m) == (8, 2)
        assert registry.run_key(m) == ("c", 8, 2)
        # pre-fleet manifests: unknown topology, never silently
        # comparable with a counted run
        assert registry.run_topology({}) == (None, None)
        assert registry.run_key({"config_hash": "c"}) != \
            registry.run_key(m)
        # 2D-mesh runs get their own comparability key; 1-D runs
        # keep the historical 3-tuple
        m2 = dict(m, mesh_shape={"clients": 4, "model": 2})
        assert registry.run_key(m2) == ("c", 8, 2, "m4x2")
        m1 = dict(m, mesh_shape={"clients": 8, "model": 1})
        assert registry.run_key(m1) == registry.run_key(m)

    def test_manifest_records_live_topology(self, tmp_path):
        ledger = str(tmp_path / "a.jsonl")
        open(ledger, "w").close()
        registry.write_manifest(str(tmp_path / "runs"),
                                args=_Cfg(x=1), ledger=ledger)
        (_, rec), = registry.list_manifests(str(tmp_path / "runs"))
        assert isinstance(rec["device_count"], int)
        assert isinstance(rec["process_count"], int)
        # single-process run: no shard list
        assert "ledger_shards" not in rec

    def _fake_manifest(self, runs, name, ts, chash, ledger, dc, pc,
                       scaling=None):
        out_dir = os.path.join(runs, registry.MANIFEST_DIR)
        os.makedirs(out_dir, exist_ok=True)
        rec = {"schema": 1, "kind": "run_manifest", "ts": ts,
               "config_hash": chash, "ledger": ledger,
               "device_count": dc, "process_count": pc,
               "git_sha": "", "bench": {}}
        if scaling:
            rec["scaling"] = scaling
        path = os.path.join(out_dir, f"run_{name}.json")
        with open(path, "w") as f:
            json.dump(rec, f)
        return path

    def test_latest_ledgers_key_filter(self, tmp_path):
        runs = str(tmp_path / "runs")
        led = str(tmp_path / "led.jsonl")
        open(led, "w").close()
        self._fake_manifest(runs, "a", 1.0, "cfg", led, 1, 1)
        self._fake_manifest(runs, "b", 2.0, "cfg", led, 8, 1)
        self._fake_manifest(runs, "c", 3.0, "cfg", led, 8, 1)
        hits = registry.latest_ledgers(runs, n=5,
                                       key=("cfg", 8, 1))
        assert len(hits) == 2
        assert all(registry.run_topology(m) == (8, 1)
                   for _, m, _ in hits)
        # newest first
        assert hits[0][1]["ts"] == 3.0
        assert registry.latest_ledgers(runs, n=5,
                                       key=("cfg", 2, 1)) == []


# --- scaling curves in the report -------------------------------------


def _write_ledger(path, round_s):
    """A synthetic ledger whose round_dispatch span is ``round_s``."""
    with open(path, "w") as f:
        for r in range(8):
            rec = make_round_record(r)
            rec["spans"] = {"round_dispatch": round_s}
            rec["uplink_bytes"] = rec["downlink_bytes"] = 1024.0
            rec["device_time"] = {"window_s": round_s,
                                  "busy_s": 0.8 * round_s,
                                  "compute_s": 0.7 * round_s,
                                  "collective_s": 0.1 * round_s,
                                  "transfer_s": 0.0,
                                  "host_gap_s": 0.2 * round_s}
            f.write(json.dumps(rec) + "\n")


class TestScalingCurves:
    def _scaling(self, cps, eff, frac=0.1, skew=0.001):
        return {"clients_per_s": cps, "parallel_efficiency": eff,
                "collective_fraction": frac, "max_skew_s": skew}

    def test_groups_by_config_and_orders_by_topology(self):
        tr = _load_script("telemetry_report")
        manifests = [
            ("m4", {"config_hash": "aaaa", "device_count": 4,
                    "process_count": 1,
                    "scaling": self._scaling(300.0, 0.75)}),
            ("m1", {"config_hash": "aaaa", "device_count": 1,
                    "process_count": 1,
                    "scaling": self._scaling(100.0, 1.0)}),
            # a single-point config is not a curve
            ("mx", {"config_hash": "bbbb", "device_count": 1,
                    "process_count": 1,
                    "scaling": self._scaling(50.0, 1.0)}),
            # manifests without a scaling block are ignored
            ("my", {"config_hash": "aaaa", "device_count": 2,
                    "process_count": 1}),
        ]
        curves = tr.scaling_curves(manifests)
        assert len(curves) == 1
        assert curves[0]["config_hash"] == "aaaa"
        assert [(p["device_count"], p["process_count"])
                for p in curves[0]["points"]] == [(1, 1), (4, 1)]
        rendered = tr.render_scaling_curves(curves)
        assert "d1p1" in rendered and "d4p1" in rendered
        assert "eff 0.750" in rendered
        assert "clients/s" in rendered

    def test_newest_manifest_wins_per_topology_point(self):
        tr = _load_script("telemetry_report")
        manifests = [  # list_manifests order: oldest first
            ("old", {"config_hash": "aaaa", "device_count": 2,
                     "process_count": 1,
                     "scaling": self._scaling(10.0, 0.5)}),
            ("new", {"config_hash": "aaaa", "device_count": 2,
                     "process_count": 1,
                     "scaling": self._scaling(20.0, 0.9)}),
            ("one", {"config_hash": "aaaa", "device_count": 1,
                     "process_count": 1,
                     "scaling": self._scaling(11.0, 1.0)}),
        ]
        curves = tr.scaling_curves(manifests)
        (curve,) = curves
        p2 = [p for p in curve["points"]
              if p["device_count"] == 2][0]
        assert p2["clients_per_s"] == 20.0
        assert p2["manifest"] == "new"

    def test_runs_dir_report_renders_curve(self, tmp_path, capsys):
        tr = _load_script("telemetry_report")
        runs = str(tmp_path / "runs")
        out_dir = os.path.join(runs, registry.MANIFEST_DIR)
        os.makedirs(out_dir)
        for i, (dc, cps, eff) in enumerate(
                [(1, 100.0, 1.0), (2, 180.0, 0.9)]):
            ledger = str(tmp_path / f"led{dc}.jsonl")
            _write_ledger(ledger, 0.05)
            rec = {"schema": 1, "kind": "run_manifest",
                   "ts": float(i + 1), "config_hash": "aaaa",
                   "ledger": ledger, "device_count": dc,
                   "process_count": 1, "git_sha": "", "bench": {},
                   "scaling": self._scaling(cps, eff)}
            with open(os.path.join(out_dir,
                                   f"run_{i}.json"), "w") as f:
                json.dump(rec, f)
        assert tr.runs_dir_report(runs, as_json=False) == 0
        out = capsys.readouterr().out
        assert "scaling curve" in out
        assert "d1p1" in out and "d2p1" in out
        # the two runs differ in topology: no cross-topology diff
        assert "no previous run with this config+topology" in out

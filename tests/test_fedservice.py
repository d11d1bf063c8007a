"""Multi-tenant federation service (commefficient_tpu/fedservice).

The daemon's one hard promise: it is CONTROL PLANE ONLY. A job driven
through the scheduler must be bit-identical — per-round ledger records
and final server state — to driving its FedModel directly, with J > 1
tenants interleaved or not. On top of that: admission control rejects
what the pod cannot run (and the ``admission_rejected`` alarm fires),
the deliberately starvable backlog policy trips ``job_starvation``,
per-job ledger shards stay isolated and solo-equivalent, migration is
checkpoint-exact across mesh shapes, and the JSONLSink two-writer
guard refuses a second live writer on one path.
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.fedservice import (AdmissionError, FedService,
                                          JobSpec)
from commefficient_tpu.runtime.fed_model import FedModel, FedOptimizer
from commefficient_tpu.telemetry.sinks import JSONLSink

W, B, DIM = 8, 2, 256

#: wall-clock / host-load fields that legitimately differ between a
#: solo run and a daemon-interleaved one; everything else must match
NONDET_KEYS = ("ts", "spans", "counters", "timeline", "device_time",
               "host_rss_peak_bytes", "hbm_peak_bytes",
               "hbm_reserved_peak_bytes", "cpu", "timeline_cpu", "stall")


def _loss(params, batch, cfg):
    pred = batch["x"] @ params["w"]
    n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    l = jnp.sum((pred - batch["y"]) ** 2 * batch["mask"]) / n
    return l, (l * 0.0 + 1.0,)


def _job_cfg(seed, ledger="", **kw):
    base = dict(mode="local_topk", error_type="local",
                local_momentum=0.9, virtual_momentum=0.0, k=8,
                num_workers=W, local_batch_size=B, num_clients=64,
                seed=seed, ledger=ledger)
    base.update(kw)
    return Config(**base)


def _builder(cfg, mesh):
    model = FedModel(None, {"w": jnp.zeros((DIM,), jnp.float32)},
                     _loss, cfg, padded_batch_size=B, mesh=mesh)
    opt = FedOptimizer([{"lr": 0.25}], cfg, model=model)
    return model, opt


def _batches(seed, n, workers=W):
    rng = np.random.RandomState(seed)
    return [
        {"client_ids": rng.choice(64, workers, replace=False)
         .astype(np.int32),
         "x": jnp.asarray(rng.randn(workers, B, DIM), jnp.float32),
         "y": jnp.asarray(rng.randn(workers, B), jnp.float32),
         "mask": jnp.ones((workers, B), jnp.float32)}
        for _ in range(n)]


def _solo_run(seed, batches, ledger=""):
    model, opt = _builder(_job_cfg(seed, ledger), None)
    for batch in batches:
        model(batch)
        opt.step()
    final = np.array(model.ps_weights)
    model.finalize()
    return final


def _canon(path):
    """Ledger round records minus the wall-clock fields — the part of
    a job ledger that must be bit-identical daemon vs solo."""
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") != "round":
                continue
            kept = {k: v for k, v in rec.items()
                    if k not in NONDET_KEYS}
            out.append(kept)
    return out


def _svc_cfg(ledger="", **kw):
    base = dict(num_workers=W, local_batch_size=B, num_clients=64,
                ledger=ledger)
    base.update(kw)
    return Config(**base)


class TestDeterminism:
    def test_two_job_daemon_bit_identical_to_solo(self, tmp_path):
        """Two interleaved tenants: each job's per-round ledger
        records AND final server state are bit-identical to its own
        solo run."""
        R = 4
        solo_leds = [str(tmp_path / "solo_a.jsonl"),
                     str(tmp_path / "solo_b.jsonl")]
        solo = [
            _solo_run(3, _batches(7, R), solo_leds[0]),
            _solo_run(4, _batches(9, R), solo_leds[1]),
        ]

        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(led))
        bs = [_batches(7, R), _batches(9, R)]
        svc.admit(JobSpec("a", _job_cfg(3), _builder,
                          lambda r: bs[0][r], rounds=R))
        svc.admit(JobSpec("b", _job_cfg(4), _builder,
                          lambda r: bs[1][r], rounds=R))
        svc.run()
        daemon = [svc.job_state("a"), svc.job_state("b")]
        svc.close()

        for j in range(2):
            assert np.array_equal(solo[j], daemon[j]), f"job {j}"
            shard = _canon(f"{led}.job{j}.jsonl")
            ref = _canon(solo_leds[j])
            assert len(shard) == R
            assert shard == ref, f"job {j} ledger diverged"

    def test_tenant_ledgers_validate_and_name_no_tracer(self, tmp_path):
        """Two tenants with a round-cadence autosave each: the daemon's
        ledger and both shards hold schema-8 records that validate, a
        span is a timeline entry on every one of them, and neither the
        daemon nor a tenant carries a second span recorder."""
        from commefficient_tpu.telemetry.record import (
            LEDGER_SCHEMA_VERSION, validate_record)
        R = 3
        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(led))
        bs = [_batches(7, R), _batches(9, R)]
        for j, (name, seed) in enumerate((("a", 3), ("b", 4))):
            cfg = _job_cfg(seed, checkpoint_every_rounds=1,
                           checkpoint_path=str(tmp_path / f"ck{j}"))
            svc.admit(JobSpec(name, cfg, _builder,
                              lambda r, j=j: bs[j][r], rounds=R))
        assert not hasattr(svc, "_causal")
        assert not hasattr(svc.telemetry, "causal")
        for job in svc._jobs:
            assert not hasattr(job, "wait_since")
            assert not hasattr(job.model.telemetry, "causal")
        svc.run()
        svc.close()
        for j in range(2):
            assert os.path.exists(
                os.path.join(str(tmp_path / f"ck{j}"),
                             f"ckpt_job{j}.npz"))
        for path, n_rounds in ((led, None),
                               (f"{led}.job0.jsonl", R),
                               (f"{led}.job1.jsonl", R)):
            recs = [json.loads(line) for line in open(path)]
            rounds = [r for r in recs if r.get("kind") == "round"]
            assert rounds and n_rounds in (None, len(rounds))
            for rec in recs:
                assert rec["schema"] == LEDGER_SCHEMA_VERSION == 9
                assert validate_record(rec) == [], rec
                assert "causal" not in rec
            for rec in rounds:
                assert isinstance(rec["timeline"], list)
                assert set(rec["spans"]) == {e[0]
                                             for e in rec["timeline"]}
        shard = [json.loads(line) for line in open(f"{led}.job0.jsonl")]
        names = {e[0] for r in shard if r.get("kind") == "round"
                 for e in r["timeline"]}
        assert {"client_pass", "server_pass"} <= names
        assert "checkpoint" not in names

    def test_single_job_daemon_parity(self, tmp_path):
        """The J=1 daemon adds zero noise — the reason j1 keeps the
        bare registry key."""
        R = 3
        solo = _solo_run(5, _batches(11, R))
        svc = FedService(_svc_cfg())
        bs = _batches(11, R)
        svc.admit(JobSpec("only", _job_cfg(5), _builder,
                          lambda r: bs[r], rounds=R))
        svc.run()
        daemon = svc.job_state("only")
        svc.close()
        assert np.array_equal(solo, daemon)


class TestAdmission:
    def test_capacity_exceeding_spec_rejected(self, tmp_path):
        """A spatial demand beyond the pod's free devices is refused
        at admission and the always-armed admission_rejected alarm
        lands on the service ledger."""
        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(led))
        bs = _batches(7, 2)
        with pytest.raises(AdmissionError, match="devices"):
            svc.admit(JobSpec("big", _job_cfg(3), _builder,
                              lambda r: bs[r], rounds=2,
                              mesh_demand=(16, 1)))
        svc.close()
        alarms = [a for rec in map(json.loads, open(led))
                  for a in rec.get("alarms") or ()]
        assert any(a["rule"] == "admission_rejected"
                   for a in alarms), alarms

    def test_duplicate_job_id_and_seed_rejected(self):
        svc = FedService(_svc_cfg())
        bs = _batches(7, 2)
        svc.admit(JobSpec("a", _job_cfg(3), _builder,
                          lambda r: bs[r], rounds=2))
        with pytest.raises(AdmissionError, match="already admitted"):
            svc.admit(JobSpec("a", _job_cfg(8), _builder,
                              lambda r: bs[r], rounds=2))
        with pytest.raises(AdmissionError, match="seed"):
            svc.admit(JobSpec("b", _job_cfg(3), _builder,
                              lambda r: bs[r], rounds=2))
        assert svc._rejected == 2
        svc.close()

    def test_spec_validation(self):
        svc = FedService(_svc_cfg())
        with pytest.raises(AdmissionError, match="rounds"):
            svc.admit(JobSpec("z", _job_cfg(3), _builder,
                              lambda r: None, rounds=0))
        svc.close()


class TestFairness:
    def test_starvation_drill_fires_alarm(self, tmp_path):
        """Backlog policy + one huge tenant: the small tenant starves
        past --alarm_job_starvation and the rule fires with its job
        index attached."""
        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(led, alarm_job_starvation=3),
                         policy="backlog")
        big, small = _batches(7, 30), _batches(9, 30)
        svc.admit(JobSpec("big", _job_cfg(3), _builder,
                          lambda r: big[r], rounds=30))
        svc.admit(JobSpec("small", _job_cfg(4), _builder,
                          lambda r: small[r], rounds=3))
        fired = []
        for _ in range(8):
            fired.extend(svc.tick())
        svc.close()
        starve = [a for a in fired if a["rule"] == "job_starvation"]
        assert starve, fired
        assert starve[0]["job"] == 1.0  # the small tenant
        alarms = [a for rec in map(json.loads, open(led))
                  for a in rec.get("alarms") or ()]
        assert any(a["rule"] == "job_starvation" for a in alarms)

    def test_fair_policy_no_starvation(self):
        svc = FedService(_svc_cfg(alarm_job_starvation=2))
        bs = [_batches(7, 5), _batches(9, 5)]
        svc.admit(JobSpec("a", _job_cfg(3), _builder,
                          lambda r: bs[0][r], rounds=5))
        svc.admit(JobSpec("b", _job_cfg(4), _builder,
                          lambda r: bs[1][r], rounds=5))
        fired = []
        while svc.active_jobs():
            fired.extend(svc.tick())
        svc.close()
        assert not [a for a in fired
                    if a["rule"] == "job_starvation"], fired


class TestSLOPlane:
    def test_starved_tenant_burns_slo_and_flags_admission(
            self, tmp_path, capsys):
        """Backlog policy + one huge tenant: the daemon's starvation
        SLO burns past --alarm_slo_burn (the rule fires through the
        normal tick check), round records carry the schema-v6 slo
        stamp, the summary backfills the fire count, and a job
        admitted while the budget burns is flagged — in the meta
        record and its manifest — but not refused."""
        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(led, slo_starvation=1.0,
                                  slo_window=4, slo_fast_window=2,
                                  alarm_slo_burn=1.0),
                         policy="backlog")
        big, small = _batches(7, 20), _batches(9, 20)
        svc.admit(JobSpec("big", _job_cfg(3), _builder,
                          lambda r: big[r], rounds=20))
        svc.admit(JobSpec("small", _job_cfg(4), _builder,
                          lambda r: small[r], rounds=3))
        fired = []
        for _ in range(6):
            fired.extend(svc.tick())
        burn = [a for a in fired if a["rule"] == "slo_burn"]
        assert burn, fired
        assert burn[0]["value"] >= 1.0
        assert burn[0]["slo_burn_starvation"] == burn[0]["value"]
        assert svc.slo_burning_jobs() == ["service"]

        late = _batches(11, 2)
        svc.admit(JobSpec("late", _job_cfg(5), _builder,
                          lambda r: late[r], rounds=2))
        assert "burning their SLO error budget" in \
            capsys.readouterr().out
        svc.close()

        recs = [json.loads(x) for x in open(led)]
        stamped = [r["slo"] for r in recs if r.get("kind") == "round"
                   and r.get("slo")]
        assert stamped and "starvation" in stamped[-1]
        assert stamped[-1]["starvation"]["burn"] >= 1.0
        metas = [r for r in recs if r.get("kind") == "meta"
                 and r.get("slo_burning_at_admission")]
        assert metas and metas[0]["admitted_job"] == "late"
        summ = [r for r in recs if r.get("kind") == "summary"]
        assert summ and summ[0]["alarm_fired"]["slo_burn"] >= 1

    def test_daemon_propagates_plane_knobs_to_tenants(self,
                                                      tmp_path):
        """--live_port / --flightrec_rounds on the daemon cfg arm
        every admitted tenant's sink on the shared registry — one
        scrape endpoint carries job=<j> AND job=service series."""
        import socket

        from commefficient_tpu.telemetry.live import (live_registry,
                                                      shutdown_plane)

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        led = str(tmp_path / "svc.jsonl")
        svc = FedService(_svc_cfg(
            led, live_port=port, flightrec_rounds=4,
            postmortem_dir=str(tmp_path / "pm")))
        try:
            bs = _batches(7, 2)
            svc.admit(JobSpec("a", _job_cfg(3), _builder,
                              lambda r: bs[r], rounds=2))
            job = svc._jobs[0]
            assert job.model.live_sink is not None
            assert job.model.live_sink.labels["job"] == "0"
            assert job.model.flightrec is not None
            assert job.model.flightrec.out_dir == \
                str(tmp_path / "pm")
            svc.run()
            snap = live_registry().snapshot()
            rounds = snap["counters"]["commeff_rounds_total"]
            seen = {snap["labels"][k]["job"]: v
                    for k, v in rounds.items()}
            assert seen["0"] == 2.0
            # the newest tick record drains at close(); at least the
            # earlier ticks have streamed by now
            assert seen["service"] >= 1.0
        finally:
            svc.close()
            shutdown_plane()

    def test_clean_service_has_no_slo_stamp(self):
        """SLO knobs unset: no engine, no stamp, no summary record —
        the bit-identity invariant's observability half."""
        svc = FedService(_svc_cfg())
        assert svc._slo is None
        assert svc.slo_burning_jobs() == []
        svc.close()


class TestSpatialAndMigration:
    def test_spatial_partition_and_release(self):
        """Two 4x1 tenants fill the 8-device pod; their devices come
        back when they drain."""
        svc = FedService(_svc_cfg(num_workers=4))
        bs = [_batches(7, 2, workers=4), _batches(9, 2, workers=4)]

        def mk(i):
            return lambda r: bs[i][r]

        for i, seed in enumerate((3, 4)):
            svc.admit(JobSpec(f"j{i}",
                              _job_cfg(seed, num_workers=4), _builder,
                              mk(i), rounds=2, mesh_demand=(4, 1)))
        assert len(svc._free) == 0
        svc.run()
        assert len(svc._free) == 8
        svc.close()

    def test_migration_is_checkpoint_exact(self, tmp_path):
        """4x1 sub-mesh -> 2x1 mid-run: the migrated job finishes
        with exactly the state a never-migrated run reaches (PR 12
        topology-free restore)."""
        R = 4
        cfg = _job_cfg(3, num_workers=4)
        batches = _batches(7, R, workers=4)
        solo = _solo_run_cfg(cfg, batches)

        svc = FedService(_svc_cfg(num_workers=4),
                         ckpt_dir=str(tmp_path / "ckpt"))
        svc.admit(JobSpec("m", cfg, _builder,
                          lambda r: batches[r], rounds=R,
                          mesh_demand=(4, 1)))
        svc.tick()
        svc.tick()
        before = svc.job_state("m")
        svc.migrate("m", mesh_demand=(2, 1))
        # the restore itself is bit-exact across the mesh change
        assert np.array_equal(before, svc.job_state("m"))
        svc.run()
        migrated = svc.job_state("m")
        svc.close()
        # post-migration rounds: cross-placement XLA reduction order
        # injects ~1e-6 noise (same bound as tests/test_elastic.py)
        np.testing.assert_allclose(migrated, solo, rtol=0, atol=1e-4)


def _solo_run_cfg(cfg, batches):
    model, opt = _builder(dataclasses.replace(cfg), None)
    for batch in batches:
        model(batch)
        opt.step()
    final = np.array(model.ps_weights)
    model.finalize()
    return final


class TestRegistryStamping:
    def test_per_job_manifests_and_job_filter(self, tmp_path):
        """Admission stamps one manifest per tenant (job_id +
        service_run lineage) and latest_ledgers(job=...) narrows to
        that tenant's ledger shard."""
        from commefficient_tpu.telemetry import registry

        led = str(tmp_path / "svc.jsonl")
        runs = str(tmp_path / "runs")
        svc = FedService(_svc_cfg(led), runs_dir=runs)
        bs = [_batches(7, 2), _batches(9, 2)]
        svc.admit(JobSpec("a", _job_cfg(3), _builder,
                          lambda r: bs[0][r], rounds=2))
        svc.admit(JobSpec("b", _job_cfg(4), _builder,
                          lambda r: bs[1][r], rounds=2))
        svc.run()
        svc.close()

        hits = registry.latest_ledgers(runs, n=5, job="a")
        assert len(hits) == 1
        _, manifest, ledger = hits[0]
        assert manifest["job_id"] == "a"
        assert manifest["service_run"] is True
        assert ledger.endswith(".job0.jsonl")
        assert len(registry.latest_ledgers(runs, n=5)) == 2


class TestSinkGuard:
    def test_second_writer_on_same_path_refused(self, tmp_path):
        """Regression: two live JSONLSinks on one path would
        interleave torn records — the second open must raise, and
        close() must release the path for a legitimate reopen."""
        path = str(tmp_path / "led.jsonl")
        sink = JSONLSink(path)
        with pytest.raises(RuntimeError, match="already has a live"):
            JSONLSink(path)
        sink.close()
        again = JSONLSink(path)  # reopen after close is fine
        again.close()

    def test_job_shards_are_distinct_paths(self, tmp_path):
        from commefficient_tpu.telemetry import job_ledger_path
        base = str(tmp_path / "led.jsonl")
        a = JSONLSink(job_ledger_path(base, 0))
        b = JSONLSink(job_ledger_path(base, 1))
        c = JSONLSink(base)
        for s in (a, b, c):
            s.close()


class TestSchedulerLocks:
    @pytest.mark.slow  # compiles a 2-job service run (~7 s); the
    # cheap lock regressions stay in tier-1 via test_live_ops
    def test_probe_threads_race_the_tick_loop(self):
        """flowlint lock-confinement regression: an HTTP scrape
        asking ``active_jobs``/``slo_burning_jobs`` while the
        scheduler ticks (and ``admit`` appends) must never hit
        'list/dict mutated during iteration' — every ``_jobs`` /
        ``_by_id`` / ``_free`` touch now goes through the service
        lock."""
        import threading

        svc = FedService(_svc_cfg(), policy="fair")
        errors = []
        stop = threading.Event()

        def scrape():
            while not stop.is_set():
                try:
                    svc.active_jobs()
                    svc.slo_burning_jobs()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        threads = [threading.Thread(target=scrape) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for j, seed in enumerate((3, 4)):
                svc.admit(JobSpec(f"j{j}", _job_cfg(seed), _builder,
                                  _mk_batch_fn(seed, 1), rounds=1))
            svc.run(max_ticks=4)
        finally:
            stop.set()
            for t in threads:
                t.join()
            svc.close()
        assert errors == []
        assert svc.active_jobs() == 0


def _mk_batch_fn(seed, n):
    batches = _batches(seed, n)
    return lambda r: batches[r] if r < len(batches) else None

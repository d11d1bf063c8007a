"""``run.py`` end to end on the tiny presets (float32, any backend,
counts and ``correct`` only), sound and broken, and the control.

Each case is a process of its own: a cell assembles a ``FedModel``,
which is one to a process, and the four-device case needs its own
``XLA_FLAGS``."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELLS = ["resnet9_fetchsgd_w1250", "gpt2_fetchsgd_w8"]

_BROKEN = '''
import sys
sys.path.insert(0, {root!r})
from benchmark import run as harness

def noop_step(run):
    """a server step that returns its state unchanged"""
    def step():
        run.model.pending_aggregated = None
    run.opt.step = step

def drop_clients(run):
    """half of every round's clients left out of the batch"""
    inner = run.model._call_train
    def call(batch):
        batch = dict(batch)
        mask = batch["mask"].copy()
        mask[: len(mask) // 2] = 0.0
        batch["mask"] = mask
        return inner(batch)
    run.model._call_train = call

sys.exit(harness.main(sys.argv[2:], fault=locals()[sys.argv[1]]))
'''


def _run(argv, devices, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable] + (["-c", script] if script else
                              [os.path.join(ROOT, "benchmark", "run.py")])
    out = subprocess.run(cmd + argv, env=env, cwd=ROOT, text=True,
                         capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def _argv(cell, seed, trace=0):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearse"]


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell, devices):
    res, _ = _run(_argv(cell, 3000000019 + devices), devices)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == devices


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_drives_every_reader(cell):
    """No reader may raise (the run would not end with a result), each
    one the cell lists is driven, and those with something to read on a
    CPU trace read it; the kernel and module readers find nothing there
    and return nothing, which is their one path when a name is absent."""
    res, out = _run(["--workload", cell, "--seed", "23", "--seconds", "4",
                     "--trace", "1", "--rehearse"], 1)
    assert res["correct"] is True and res["metrics"] == {}
    assert "rehearsal: trace summary" in out
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for m in per_layer:
        if cell in m.get("workloads", [cell]):
            assert f"rehearsal: reader {m['name']} " in out
    for name in ("data.sampler_ms", "runtime.host_ms", "runtime.sync_ms",
                 "runtime.round_ms_p50", "models.mfu", "device.idle",
                 "device.idle_untraced", "device.hbm_peak_GB"):
        assert f"rehearsal: reader {name} ran" in out
    for name in ("kernels.sketch_roofline", "round.client_ms"):
        assert f"rehearsal: reader {name} found nothing to read" in out


@pytest.mark.parametrize("fault", ["noop_step", "drop_clients"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    res, out = _run([fault] + _argv(cell, 29 + len(fault)), 1,
                    script=_BROKEN.format(root=ROOT))
    assert res["correct"] is False, out[-1500:]


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, text=True,
        capture_output=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_is_not_correct(cell):
    """The reference computed in fp8 in the program's place fails a
    limit, at a size a test run can hold (the chip readings at the
    cell's own size are in PERF.md section 2)."""
    _, out = _run(_argv(cell, 31) + ["--control"], 1)
    assert "control_correct: false" in out

"""The ``granite4hm_fetchsgd_w4_t2048`` cell rehearsed on the CPU (tiny
presets, float32): every reader the manifest lists for it runs, the
program agrees with the plain reference, a broken step does not, and
the fp8 control fails. ``test_nemotron_cell.py`` is the pattern; this
file is the next entry of ``test_rehearsal.py``'s list."""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import _BROKEN, _argv, _run

CELL = "granite4hm_fetchsgd_w4_t2048"
NEW = ("round.mlp_ms",)
SHARED = ("round.ssm_ms", "round.ssm_scan_ms", "round.attn_ms",
          "round.head_ms", "kernels.sketch_roofline",
          "kernels.estimates_roofline")


@pytest.mark.parametrize("devices", [1, 4])
def test_rehearsal_is_correct(devices):
    res, _ = _run(_argv(CELL, 3000000019 + devices), devices)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["metrics"] == {}
    assert res["device"]["count"] == devices


def test_traced_rehearsal_drives_every_reader():
    res, out = _run(["--workload", CELL, "--seed", "23", "--seconds", "4",
                     "--trace", "1", "--rehearse"], 1)
    assert res["correct"] is True and res["metrics"] == {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    mine = [m["name"] for m in per_layer if CELL in m.get("workloads", [])]
    assert set(NEW) | set(SHARED) == set(mine)
    for m in per_layer:
        if CELL in m.get("workloads", [CELL]):
            assert f"rehearsal: reader {m['name']} " in out
    # the scopes are a device trace's: on the CPU they read nothing
    for name in NEW + SHARED[:3]:
        assert f"rehearsal: reader {name} found nothing to read" in out


# ``test_rehearsal.py``'s planted faults and one more: the server's
# momentum left out of the program while the reference keeps it. Run by
# hand on the chip for the cell-size readings (PERF.md section 2):
# python3 benchmark/tests/test_granite_cell.py <fault> --workload <cell>
# --seed <n> --seconds 5 --trace 0
FAULTS = _BROKEN.replace("sys.exit(", '''
def drop_momentum(run):
    """virtual momentum 0 in the server program, 0.9 in the reference"""
    stated = run.hyper()
    run.args.virtual_momentum = 0.0
    run.hyper = lambda: stated

sys.exit(''', 1)


@pytest.mark.parametrize("fault", ["noop_step", "drop_clients",
                                   "drop_momentum"])
def test_a_broken_timed_path_is_not_correct(fault):
    res, out = _run([fault] + _argv(CELL, 29 + len(fault)), 1,
                    script=FAULTS.format(root=ROOT))
    assert res["correct"] is False, out[-1500:]


def test_the_lower_precision_control_is_not_correct():
    _, out = _run(_argv(CELL, 31) + ["--control"], 1)
    assert "control_correct: false" in out


def test_the_new_reader_returns_nothing_without_its_scope():
    """On the parent of this PR the program names no such scope: the
    reader finds nothing and the line leaves the metric out."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmark.run import load
    for name in NEW:
        assert load("metrics", name).read({"trace_dir": None}) is None


def test_the_parent_has_no_such_cell(tmp_path):
    """A checkout without this PR's entries exits 2 at once."""
    import subprocess
    import sys
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert any(w["name"] == CELL for w in manifest["workloads"])
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL + "_absent", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, text=True, capture_output=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "no cell" in out.stderr


if __name__ == "__main__":
    exec(FAULTS.format(root=ROOT))

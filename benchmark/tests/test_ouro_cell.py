"""The ``ouro_fetchsgd_w2_t2048`` cell rehearsed on the CPU (tiny
presets, float32): every reader the manifest lists for it runs, the
program agrees with the plain reference, a broken step or a broken loop
does not, and the fp8 control fails. ``test_smallthinker_cell.py`` is
the pattern; this file is the next entry of ``test_rehearsal.py``'s
list. Its cases run a cell in a child process and stay by hand, like
the four other cells'."""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import _BROKEN, _argv, _run

CELL = "ouro_fetchsgd_w2_t2048"
NEW = ("round.loop_ms", "round.exit_ms", "models.loop_expected_steps")
SHARED = ("round.head_ms", "round.attn_ms", "round.mlp_ms",
          "kernels.attn_roofline", "kernels.sketch_roofline",
          "kernels.estimates_roofline", "models.attn_pairs_over_needed")


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
    # the scopes and the kernels are a device trace's: on the CPU they
    # read nothing
    for name in NEW[:2] + SHARED[:6]:
        assert f"rehearsal: reader {name} found nothing to read" in out
    # the counters are the round records'
    for name in (NEW[2], SHARED[6]):
        assert f"rehearsal: reader {name} ran" in out
    assert "loop.steps [4.0]" in out
    assert "loop.layer_applications [8.0]" in out


# ``test_rehearsal.py``'s planted faults and this architecture's own
# (``ouro_faults.py``): each breaks the *program* while the reference
# keeps the loop as written down. Run by hand on the chip for the
# cell-size readings (PERF.md section 2):
# python3 benchmark/tests/test_ouro_cell.py <fault> --workload <cell>
# --seed <n> --seconds 5 --trace 0
FAULTS = _BROKEN.replace("sys.exit(", '''
sys.path.insert(0, {root!r} + "/benchmark/tests")
from ouro_faults import (gate_detached, last_step_loss_only,  # noqa: F401
                         norm_after_loop, one_step, pre_norm_only)

sys.exit(''', 1)

REFUSED = ["noop_step", "drop_clients", "one_step", "norm_after_loop",
           "pre_norm_only"]
#: faults that the limits pass *at the tiny preset*: with 32-token
#: sequences, two layers of width 64 and weights of 0.02 every step's
#: next-token loss is log(96) to four digits, so which step a position
#: is charged to, and whether the gate learns, moves the first gradient
#: by less than the cell's limits, which are set for bf16 at the cell's
#: size. Tier-1 holds all five to the gradient by value
#: (``tests/test_ouro.py``); the chip's readings at the cell's size are
#: in PERF.md section 2
PASSED = ["last_step_loss_only", "gate_detached"]


@pytest.mark.parametrize("fault", REFUSED)
def test_a_broken_timed_path_is_not_correct(fault):
    res, out = _run([fault] + _argv(CELL, 29 + len(fault)), 1,
                    script=FAULTS.format(root=ROOT))
    assert res["correct"] is False, out[-1500:]


@pytest.mark.parametrize("fault", PASSED)
def test_a_fault_the_limits_pass_still_moves_the_gradient(fault):
    """In float32 the program is the reference to 1e-6 of the first
    gradient (the sound rehearsal reads 5e-7); with the fault it is
    not, though at this size the cell's limits let it through."""
    import re
    res, out = _run([fault] + _argv(CELL, 29 + len(fault)), 1,
                    script=FAULTS.format(root=ROOT))
    moved = float(re.search(r"correct: grad_rel_l2 = (\S+)", out).group(1))
    assert moved > 2e-4, out[-1500:]


def test_the_lower_precision_control_is_not_correct():
    _, out = _run(_argv(CELL, 31) + ["--control"], 1)
    assert "control_correct: false" in out


def test_the_new_readers_return_nothing_without_their_scope_or_counter():
    """On the parent of this PR the program names no such scope and
    counts no such steps: the readers find nothing and the line leaves
    the metrics out. None of them imports the program."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmark.run import load
    for name in NEW[:2]:
        assert load("metrics", name).read({"trace_dir": None}) is None
    ctx = {"records": [{"kind": "round", "round": r, "counters": {}}
                       for r in range(8)],
           "window": {"first": 3, "first_traced": 7}}
    assert load("metrics", NEW[2]).read(ctx) is None
    ctx["records"][4]["counters"] = {"loop.expected_steps": 2.5}
    ctx.pop("_untraced")
    assert load("metrics", NEW[2]).read(ctx) == 2.5
    for name in NEW:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".py")) as f:
            assert "commefficient_tpu" not in f.read()


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

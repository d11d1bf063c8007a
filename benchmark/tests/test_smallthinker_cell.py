"""The ``smallthinker_fetchsgd_w2_t8192`` cell rehearsed on the CPU (tiny
presets, float32): every reader the manifest lists for it runs, the
program agrees with the plain reference, a broken step or a broken
layer does not, and the fp8 control fails. ``test_granite_cell.py`` is
the pattern; this file is the next entry of ``test_rehearsal.py``'s
list."""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import _BROKEN, _argv, _run

CELL = "smallthinker_fetchsgd_w2_t8192"
NEW = ("round.attn_window_ms", "round.attn_full_ms",
       "models.attn_pairs_over_needed")
SHARED = ("round.attn_ms", "round.moe_ms", "round.route_ms",
          "round.head_ms", "models.moe_load_max_over_mean",
          "kernels.sketch_roofline", "kernels.estimates_roofline")


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
    for name in NEW[:2] + SHARED[:4]:
        assert f"rehearsal: reader {name} found nothing to read" in out
    # the counters are the round records'
    for name in (NEW[2], SHARED[4]):
        assert f"rehearsal: reader {name} ran" in out


# ``test_rehearsal.py``'s planted faults and this architecture's own:
# each breaks the *program* while the reference keeps the published
# layer. Run by hand on the chip for the cell-size readings (PERF.md
# section 2):
# python3 benchmark/tests/test_smallthinker_cell.py <fault> --workload
# <cell> --seed <n> --seconds 5 --trace 0
FAULTS = _BROKEN.replace("sys.exit(", '''
def window_as_full(run):
    """the window layers see their whole past"""
    from commefficient_tpu.models import mixers
    inner = mixers.gqa_attention
    mixers.gqa_attention = lambda q, k, v, scale, query_block=None, \\
        window=None: inner(q, k, v, scale, query_block)

def rope_off(run):
    """no layer rotates q and k"""
    from commefficient_tpu.models import mixers
    mixers.rope = lambda x, theta: x

def router_after_attention(run):
    """the router reads the stream after attention, not the block's
    input"""
    from commefficient_tpu.models import smallthinker

    class Late(smallthinker.Block):
        router_after_attention: bool = True
    smallthinker.Block = Late

def gates_sigmoid(run):
    """the gates are normalised sigmoid scores, not the softmax over the
    chosen logits (the picks stay: sigmoid keeps the logits' order)"""
    import jax.numpy as jnp
    from commefficient_tpu.models import smallthinker
    inner = smallthinker.route
    smallthinker.route = lambda x, router, bias, k, scaling, norm, \\
        scoring: inner(x, router, jnp.zeros((router.shape[1],)), k,
                       scaling, norm)

sys.exit(''', 1)

REFUSED = ["noop_step", "drop_clients", "window_as_full",
           "router_after_attention"]
#: faults that the limits pass *at the tiny preset*: 32-token sequences
#: and 8 layers of width 32 with weights of 0.02 keep every score and
#: router logit so near 0 that rotating q and k, or sigmoid gates for
#: softmax ones, moves the first gradient by less than the cell's
#: limits, which are set for bf16 at the cell's size. At the cell's own
#: size on the chip all five architecture faults are refused (PERF.md
#: section 2: ``rope_off`` reads ``grad_rel_l2`` 0.109, ``gates_sigmoid``
#: 0.048 against 0.02)
PASSED = ["rope_off", "gates_sigmoid"]


@pytest.mark.parametrize("fault", REFUSED)
def test_a_broken_timed_path_is_not_correct(fault):
    res, out = _run([fault] + _argv(CELL, 29 + len(fault)), 1,
                    script=FAULTS.format(root=ROOT))
    assert res["correct"] is False, out[-1500:]


@pytest.mark.parametrize("fault", PASSED)
def test_a_fault_the_limits_pass_still_moves_the_gradient(fault):
    """In float32 the program is the reference to 1e-4 of the first
    gradient (tests/test_smallthinker.py); with the fault it is not,
    though at this size the cell's limits let it through."""
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
    counts no such pairs: the readers find nothing and the line leaves
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
    ctx["records"][4]["counters"] = {"attn.pairs": 3.0,
                                     "attn.pairs_needed": 2.0}
    ctx.pop("_untraced")
    assert load("metrics", NEW[2]).read(ctx) == 1.5
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

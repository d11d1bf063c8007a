"""Process entry points tell the truth: which device a run is on, how
it ended, and where its compile cache lives."""

import ast
import glob
import os
import re

import jax
import pytest

from commefficient_tpu import utils
from commefficient_tpu.train import cv_train

SMOKE = ["--test", "--dataset_name", "Synthetic", "--mode", "sketch",
         "--error_type", "virtual", "--local_momentum", "0",
         "--num_workers", "2", "--num_epochs", "1"]


def test_named_device_that_jax_did_not_find_is_an_error():
    with pytest.raises(RuntimeError, match="--device tpu.*'cpu'"):
        cv_train.main(SMOKE + ["--device", "tpu"])


@pytest.mark.parametrize("extra,status", [
    ([], 0),
    (["--nan_threshold", "1e-6"], 1),     # first round's loss aborts
])
def test_cli_exit_status_reports_divergence(monkeypatch, capsys, extra,
                                            status):
    monkeypatch.setattr(utils, "setup_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", ["cet-cv-train"] + SMOKE + extra)
    assert cv_train.cli() == status
    # and the unset --device resolved to what JAX reports, out loud
    assert "devices: platform=cpu" in capsys.readouterr().out


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert utils.setup_compile_cache() == str(tmp_path)
    assert updates == []        # JAX reads the variable itself

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(utils.__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert utils.setup_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


# --- the documents that say what to run, and the probes left under scripts/ ---

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_QUOTED = re.compile(r"```.*?```|`[^`]+`", re.S)
_DIRS = ("scripts", "commefficient_tpu", "benchmark", "tests")
_IN_TREE = re.compile(
    r"(?<![\w/.<>{}*-])(?:((?:" + "|".join(_DIRS) + r")"
    r"/[\w./*{},-]+)|([\w-]+\.(?:py|json)))(?![\w<{])")
#: what a run, a download or another repository holds, not this tree
NOT_THE_TREES = {"config.json", "stats.json", "vocab.json",
                 "bundle.json", "report.json", "fed_aggregator.py",
                 "personachat_self_original.json"}


def _braces(path):
    m = re.search(r"\{([^{}]*)\}", path)
    if not m:
        return [path]
    return [p for alt in m.group(1).split(",")
            for p in _braces(path[:m.start()] + alt + path[m.end():])]


@pytest.mark.parametrize("doc", ["README.md", "REPRO.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_a_document_names_only_files_the_tree_has(doc):
    """Every back-quoted path under scripts/, commefficient_tpu/,
    benchmark/ and tests/ exists (``*`` and ``{a,b}`` expanded), and so
    does every bare ``*.py`` / ``*.json``, at the root or, as the
    shorthand the documents use, under that name in one of the four."""
    with open(os.path.join(ROOT, doc)) as f:
        quoted = "\n".join(_QUOTED.findall(f.read()))
    below = set(os.listdir(ROOT)).union(*(
        names for d in _DIRS
        for _, _, names in os.walk(os.path.join(ROOT, d))))
    below |= NOT_THE_TREES
    missing = set()
    for path, bare in _IN_TREE.findall(quoted):
        if bare and bare not in below:
            missing.add(bare)
        if path:
            missing.update(p for p in _braces(path.rstrip(".,"))
                           if not glob.glob(os.path.join(ROOT, p)))
    assert not missing, sorted(missing)


@pytest.mark.parametrize("script", sorted(
    os.path.basename(p) for kind in ("probe", "bench")
    for p in glob.glob(os.path.join(ROOT, "scripts", f"*_{kind}.py"))))
def test_a_probe_left_under_scripts_names_the_open_item_it_serves(script):
    """ROADMAP D15's rule: a by-hand probe stays only while an open item
    needs it, and says which in the first line of its docstring."""
    with open(os.path.join(ROOT, "scripts", script)) as f:
        first = ast.get_docstring(ast.parse(f.read())).splitlines()[0]
    with open(os.path.join(ROOT, "ROADMAP.md")) as f:
        roadmap = f.read()
    assert first.startswith("ROADMAP "), first
    items = re.findall(r"\b[SRD]\d+\b", first)
    assert items, first
    for item in items:
        assert f"\n**{item}. " in roadmap, (item, "has no open entry")

"""Process entry points tell the truth: which device a run is on, how
it ended, and where its compile cache lives."""

import os

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

"""Test harness: force an 8-device virtual CPU mesh.

The moral equivalent of the reference's "distributed degrades to
localhost" strategy (SURVEY.md §4): multi-chip sharding is validated on
N virtual CPU devices via --xla_force_host_platform_device_count, no
real pod required. Must run before JAX initialises its backends.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, devs
    return devs


@pytest.fixture(autouse=True)
def no_model_left_live(monkeypatch):
    """``staging.current()`` as a fresh process has it: a model that an
    earlier test of this worker built and never finalized does not
    count, so no loader places its batches with a stranger's mesh (and
    which thread makes a loader's batches does not depend on what ran
    before)."""
    from commefficient_tpu.data import staging
    monkeypatch.setattr(staging, "_LIVE", [])


@pytest.fixture(autouse=True)
def no_loader_thread_left():
    """A loader that reads ahead keeps its thread, with the next epoch
    opened on it, until ``close()`` (data/loader.py): what a test left
    running is stopped here, so that the next test of this worker
    counts its own threads alone."""
    yield
    from commefficient_tpu.data.loader import _ReadAhead
    for reader in list(_ReadAhead.live):
        reader.stop()


@pytest.fixture(scope="session")
def package_parse():
    """One timed cold flowlint run (parse + both lint tiers) on the
    real package, shared by test_audit and test_flowlint — the suite
    pays for exactly one engine run. ``elapsed`` is the cold wall
    time, used by the <10 s engine-budget assertion."""
    import time

    from commefficient_tpu.analysis.flow import build_program
    from commefficient_tpu.analysis.lint import run_all

    t0 = time.monotonic()
    program = build_program(None)
    violations = run_all(program=program)
    elapsed = time.monotonic() - t0
    return {"program": program, "violations": violations,
            "elapsed": elapsed}


# --- fast/slow tiers -----------------------------------------------------
# ``pytest -m fast`` is the <2-minute oracle tier: compression-op math,
# server-mode oracles, sharding invariance, accounting, data-layer
# units. The full (unmarked) suite adds the compile-heavy trainer
# end-to-ends; ``-m "not slow"`` skips only the multi-process smokes.

FAST_MODULES = {
    "test_ops",
    "test_accounting",
    "test_audit",
    "test_mesh2d",
    "test_sharding",
    "test_data_breadth",
    "test_telemetry",
}
FAST_CLASSES = {
    "TestHandDerived",        # reference unit_test.py oracle traces
    "TestSparseServerUpdate",
    "TestPersonaInputs",
    "TestFixupLrGroups",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        cls = item.cls.__name__ if item.cls is not None else ""
        if mod in FAST_MODULES or cls in FAST_CLASSES:
            item.add_marker(pytest.mark.fast)

"""Tier-1's guard over the benchmark's readers (ROADMAP D11): the fast
tests of ``benchmark/tests`` (the manifest, the readers of spans,
counters and scopes on handmade records and recorded traces, the
yardstick's restatements of the program), collected here under their
own names, so that a program PR that renames a span, a counter or a
scope fails here and not in a refusal on the chip. No file under
``benchmark/`` is edited for it.

Those files say ``from conftest import ROOT`` and import each other by
bare name, so while they are loaded ``conftest`` is theirs and their
directory is on ``sys.path``; both are put back before a test runs.
The cases that run a whole cell in a child process
(``test_traced_rehearsal_*``, ``test_rehearsal.py``, ``test_follow.py``
and the four ``test_*_cell.py``) stay by hand, and so do the two that
build a whole model beside its reference (40-60 s of a run that has
60 s of room: ROADMAP D11)."""

import importlib
import importlib.util
import os
import sys

BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")
FILES = ("test_manifest", "test_hostclock_metrics",
         "test_tracing_metrics", "test_yardstick")
BY_HAND = ("test_traced_rehearsal_",
           "test_resnet9_reference_is_the_module_in_f32",
           "test_gpt2_reference_is_the_module_in_f32")


def _their_tests():
    theirs = importlib.util.spec_from_file_location(
        "conftest", os.path.join(BENCH_TESTS, "conftest.py"))
    conftest = importlib.util.module_from_spec(theirs)
    ours, before = sys.modules.get("conftest"), set(sys.modules)
    path = list(sys.path)
    sys.modules["conftest"] = conftest
    sys.path.insert(0, BENCH_TESTS)
    try:
        theirs.loader.exec_module(conftest)
        found = {}
        for name in FILES:
            for key, fn in vars(importlib.import_module(name)).items():
                if (key.startswith("test_") and callable(fn)
                        and fn.__module__ == name
                        and not key.startswith(BY_HAND)):
                    assert key not in found, key
                    found[key] = fn
        return found
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - before:
            if (getattr(sys.modules[name], "__file__", None)
                    or "").startswith(BENCH_TESTS):
                del sys.modules[name]
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours


globals().update(_their_tests())

"""Training entry points: ``cv_train`` and ``gpt2_train``. Each has
``run(argv) -> TrainRun`` (what was built and what it returned),
``main(argv)`` (its epoch rows — what the tests read) and ``cli()``
(the process entry)."""

import sys


def cli_exit_status(run) -> int:
    """Body of both trainers' ``cli``: exit status 0 for a completed
    run, 1 for one that diverged. ``main`` cannot be the console
    script: ``sys.exit`` of its row list exits 1 after a good run and
    0 after a NaN abort that produced no rows."""
    from commefficient_tpu import utils
    utils.setup_compile_cache()
    return int(run(sys.argv[1:]).model.diverged)

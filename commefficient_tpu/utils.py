"""Cross-cutting utilities: LR schedules, loggers, timers.

Functional parity with reference utils.py:14-99 (Logger, PiecewiseLinear,
Exp, TableLogger, TSVLogger, Timer, make_logdir).
"""

from __future__ import annotations

import os
import signal
import threading
from collections import namedtuple
from contextlib import contextmanager
from datetime import datetime

import numpy as np

from commefficient_tpu.telemetry import clock


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a place that can be
    chosen from outside; returns the directory in use.

    For process entry points only (``chip_smoke.py``,
    ``scripts/tpu_selftest.py``, the trainers' ``cli``), before first
    device use — never from ``main(argv)``, which the tests call
    in-process dozens of times and would fill the checkout with CPU
    entries. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: fixed by the package's location because
    the directory is part of the cache key, so a path from a tempdir,
    a pid or a clock would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class GracefulShutdown(Exception):
    """Raised in the main thread when a termination signal arrives
    (``sigterm_raises``). Unwinds the round loop so the trainer can run
    crash-safety cleanup (``FedModel.interrupted`` + ``finalize``)
    instead of dying mid-write; the last round-cadence autosave plus
    the ledger's torn-tail recovery make the run resumable."""

    def __init__(self, signum: int):
        super().__init__(f"received signal {signum}")
        self.signum = signum


@contextmanager
def sigterm_raises(signums=(signal.SIGTERM,)):
    """Install handlers that raise ``GracefulShutdown``; priors are
    restored on exit. Degrades to a no-op outside the main thread
    (where ``signal.signal`` is illegal) so tests can call trainer
    main()s from worker threads."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def _handler(signum, frame):
        raise GracefulShutdown(signum)

    prev = {}
    for s in signums:
        prev[s] = signal.signal(s, _handler)
    try:
        yield
    finally:
        for s, h in prev.items():
            signal.signal(s, h)


class Logger:
    """print-based logger (reference utils.py:14-24)."""

    def debug(self, msg, args=None):
        print(msg.format(args))

    info = warn = error = critical = debug


class PiecewiseLinear(namedtuple("PiecewiseLinear", ("knots", "vals"))):
    """Piecewise-linear schedule; e.g. the triangular CIFAR LR schedule
    PiecewiseLinear([0, pivot_epoch, num_epochs], [0, lr_scale, 0])
    (reference utils.py:26-28, cv_train.py:394-397)."""

    def __call__(self, t):
        return float(np.interp([t], self.knots, self.vals)[0])


class Exp(namedtuple("Exp", ("warmup_epochs", "amplitude", "decay_len"))):
    """Linear warmup then exponential decay (reference utils.py:30-35)."""

    def __call__(self, t):
        if t < self.warmup_epochs:
            return float(np.interp([t], [0, self.warmup_epochs],
                                   [0, self.amplitude])[0])
        return float(self.amplitude
                     * 10 ** (-(t - self.warmup_epochs) / self.decay_len))


def make_logdir(args) -> str:
    """runs/<time>_<workers>/<clients>_<mode>... (reference utils.py:51-64)."""
    rows, cols, k, mode = args.num_rows, args.num_cols, args.k, args.mode
    sketch_str = f"{mode}: {rows} x {cols}" if mode == "sketch" else f"{mode}"
    k_str = f"k: {k}" if mode in ["sketch", "true_topk", "local_topk"] else ""
    clients_str = f"{args.num_workers}/{args.num_clients}"
    current_time = datetime.now().strftime("%b%d_%H-%M-%S")
    return os.path.join(
        "runs", current_time + "_" + clients_str + "_" + sketch_str + "_" + k_str)


class TableLogger:
    """Fixed-width stdout table (reference utils.py:66-74)."""

    def append(self, output):
        if not hasattr(self, "keys"):
            self.keys = output.keys()
            print(*("{:>12s}".format(k) for k in self.keys))
        filtered = [output[k] for k in self.keys]
        print(*("{:12.4f}".format(v)
                if isinstance(v, (float, np.floating)) else "{:12}".format(v)
                for v in filtered))


class TSVLogger:
    """epoch,hours,top1Accuracy TSV accumulator (reference utils.py:76-85)."""

    def __init__(self):
        self.log = ["epoch,hours,top1Accuracy"]

    def append(self, output):
        epoch = output["epoch"]
        hours = output["total_time"] / 3600
        acc = output["test_acc"] * 100
        self.log.append("{},{:.8f},{:.2f}".format(epoch, hours, acc))

    def __str__(self):
        return "\n".join(self.log)


union = lambda *dicts: {k: v for d in dicts for (k, v) in d.items()}  # noqa: E731


class Timer:
    """Wall-clock phase timer (reference utils.py:89-99)."""

    def __init__(self):
        self.times = [clock.wall()]
        self.total_time = 0.0

    def __call__(self, include_in_total=True):
        self.times.append(clock.wall())
        delta_t = self.times[-1] - self.times[-2]
        if include_in_total:
            self.total_time += delta_t
        return delta_t


def steps_per_epoch(local_batch_size: int, dataset, num_workers: int) -> int:
    """Rounds per epoch (reference utils.py:315-321): when the local
    batch is the client's whole dataset, an epoch is num_clients /
    num_workers rounds; otherwise ceil(len(ds) / (lbs * num_workers))."""
    if local_batch_size == -1:
        return int(dataset.num_clients // num_workers)
    batch_size = local_batch_size * num_workers
    return int(np.ceil(len(dataset) / batch_size))

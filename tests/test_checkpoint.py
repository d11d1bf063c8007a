"""Full-state checkpoint/resume: an interrupted-and-resumed run must
reproduce the uninterrupted run bit-for-bit (weights, server momentum/
error, client states, data order) — including runs interrupted
MID-EPOCH by a SIGTERM or an exception, which resume from the
round-cadence autosave (``--checkpoint_every_rounds``)."""

import glob
import json
import os
import signal
import threading

import numpy as np
import pytest

from commefficient_tpu.train import cv_train


def _argv(tmpdir, epochs, extra=()):
    return [
        "--test", "--dataset_name", "Synthetic",
        "--mode", "sketch", "--error_type", "virtual",
        "--local_momentum", "0", "--virtual_momentum", "0.9",
        "--num_clients", "10", "--num_workers", "2",
        "--local_batch_size", "4", "--num_epochs", str(epochs),
        "--lr_scale", "0.1", "--pivot_epoch", "1",
        "--checkpoint", "--checkpoint_path", str(tmpdir),
        "--checkpoint_every", "1", *extra,
    ]


def _load_state(tmpdir):
    import json
    import os
    path = os.path.join(str(tmpdir), "ckpt_ResNet9.npz")
    with np.load(path) as z:
        return ({k: np.array(z[k]) for k in z.files if k != "meta"},
                json.loads(str(z["meta"])))


@pytest.mark.parametrize("mode_extra", [
    (),                                           # sketch + virtual
    ("--mode", "true_topk", "--k", "10"),         # topk + virtual
])
def test_resume_bit_exact(tmp_path, mode_extra):
    cont_dir = tmp_path / "cont"
    resume_dir = tmp_path / "resume"

    # uninterrupted 3-epoch run
    cv_train.main(_argv(cont_dir, 3, mode_extra))
    cont_state, cont_meta = _load_state(cont_dir)

    # 1 epoch, stop, then resume for the remaining 2 (schedule decays
    # over the full 3-epoch horizon in both invocations)
    cv_train.main(_argv(resume_dir, 1,
                        (*mode_extra, "--schedule_epochs", "3")))
    cv_train.main(_argv(resume_dir, 3, (*mode_extra, "--resume")))
    res_state, res_meta = _load_state(resume_dir)

    assert cont_meta["epoch"] == res_meta["epoch"] == 3
    assert cont_meta["round_index"] == res_meta["round_index"]
    assert cont_meta["opt_step_count"] == res_meta["opt_step_count"]
    assert set(cont_state) == set(res_state)
    for k in cont_state:
        np.testing.assert_array_equal(cont_state[k], res_state[k],
                                      err_msg=k)


def test_resume_rejects_mismatched_config(tmp_path):
    cv_train.main(_argv(tmp_path, 1))
    with pytest.raises(ValueError):
        # different mode -> different transmit shape: must refuse
        cv_train.main(_argv(tmp_path, 2,
                            ("--mode", "uncompressed", "--resume",
                             "--error_type", "none",
                             "--virtual_momentum", "0")))


def test_resume_rejects_rot_lanes_mismatch(tmp_path):
    """A sketch checkpoint records its RESOLVED rotation granularity:
    resuming under a different one would decode the saved sketch-space
    error state against the wrong rotation stream — silent corruption,
    so it must refuse (runtime/checkpoint.py rot_lanes check; the
    cross-platform risk is the auto default re-resolving per
    backend)."""
    cv_train.main(_argv(tmp_path, 1))  # auto -> 0 on the CPU backend
    with pytest.raises(ValueError, match="rot_lanes"):
        # 1 is the only granularity the tiny --test sketch (c=10)
        # admits; any resolved value != the checkpoint's 0 must refuse
        cv_train.main(_argv(tmp_path, 2,
                            ("--resume", "--sketch_rot_lanes", "1")))


def test_resume_requires_existing_checkpoint(tmp_path):
    with pytest.raises(FileNotFoundError):
        cv_train.main(_argv(tmp_path / "empty", 1, ("--resume",)))


def test_global_np_rng_and_loader_counter_roundtrip(tmp_path):
    """Augmentation RNG state (global numpy) and the native loader's
    round counter survive save/load."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import Config
    from commefficient_tpu.models import get_model
    from commefficient_tpu.ops.vec import flatten_params
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)

    cfg = Config(mode="uncompressed", error_type="none",
                 local_momentum=0.0, virtual_momentum=0.9,
                 num_workers=2, local_batch_size=2, num_clients=4,
                 dataset_name="CIFAR10", seed=0)
    module = get_model("ResNet9")(
        num_classes=10,
        channels={"prep": 2, "layer1": 2, "layer2": 2, "layer3": 2})
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 32, 32, 3)))["params"]

    def loss(p, batch, args):
        return (jnp.float32(0.0), jnp.float32(0.0))

    model = FedModel(module, params, loss, cfg)
    opt = FedOptimizer([{"lr": 1.0}], cfg)

    class FakeLoader:
        _round_counter = 7
        sampler = None

    path = str(tmp_path / "s.npz")
    np.random.seed(123)
    np.random.rand(5)  # advance the global stream
    save_checkpoint(path, model, opt, loader=FakeLoader(), epoch=1)
    after_save = np.random.rand(3)

    np.random.seed(999)  # scramble
    fresh = FakeLoader()
    fresh._round_counter = 0
    load_checkpoint(path, model, opt, loader=fresh)
    np.testing.assert_array_equal(np.random.rand(3), after_save)
    assert fresh._round_counter == 7


def _midrun_argv(d, epochs, extra=()):
    """1 round per epoch (num_clients == num_workers), so ``--test``'s
    one-round-per-epoch break coincides with the true epoch boundary
    and a mid-run kill/resume replays whole rounds."""
    return [
        "--test", "--dataset_name", "Synthetic", "--iid",
        "--mode", "sketch", "--error_type", "virtual",
        "--local_momentum", "0", "--virtual_momentum", "0.9",
        "--num_clients", "2", "--num_workers", "2",
        "--local_batch_size", "4", "--num_epochs", str(epochs),
        "--lr_scale", "0.1", "--pivot_epoch", "1",
        "--checkpoint", "--checkpoint_path", str(d),
        "--checkpoint_every", "1",
        "--checkpoint_every_rounds", "2", "--checkpoint_keep", "2",
        *extra,
    ]


# killed BETWEEN autosaves (cadence 2, autosave at round 2): the
# resume replays round 3, exercising the ledger's replay dedup
_KILL_ROUND = 3


def _inject_round_failure(monkeypatch, kill_round, action):
    """Wrap RoundAutosaver.__call__: run the real autosave logic,
    then — once per process — kill the run at ``kill_round`` (either
    a real SIGTERM to ourselves, which the trainer's sigterm_raises
    turns into GracefulShutdown, or a raised exception)."""
    from commefficient_tpu.runtime import checkpoint as ckpt
    real = ckpt.RoundAutosaver.__call__
    state = {"fired": False}

    def wrapped(self, epoch):
        real(self, epoch)
        if not state["fired"] \
                and int(self.model.round_index) >= kill_round:
            state["fired"] = True
            if action == "sigterm":
                os.kill(os.getpid(), signal.SIGTERM)
            else:
                raise RuntimeError("chaos: injected round failure")

    monkeypatch.setattr(ckpt.RoundAutosaver, "__call__", wrapped)
    return state


@pytest.fixture(scope="module")
def _uninterrupted_run(tmp_path_factory):
    d = tmp_path_factory.mktemp("cont")
    cv_train.main(_midrun_argv(d, 6))
    return _load_state(d), d


def test_round_autosave_retention(_uninterrupted_run):
    """--checkpoint_keep prunes round-stamped history snapshots to
    the budget (newest kept)."""
    _, d = _uninterrupted_run
    snaps = sorted(os.path.basename(p) for p in
                   glob.glob(os.path.join(str(d), "ckpt_ResNet9_r*.npz")))
    assert len(snaps) == 2, snaps
    rounds = [int(n.split("_r")[1].split(".")[0]) for n in snaps]
    assert rounds == [4, 6]  # cadence-2 autosaves, oldest pruned


@pytest.mark.parametrize("failure", ["sigterm", "exception"])
def test_resume_after_midrun_failure_bit_exact(
        tmp_path, monkeypatch, failure, _uninterrupted_run):
    """Kill a run mid-epoch (SIGTERM or raised exception between
    rounds); the last round-cadence autosave must be a consistent
    resume point and the resumed run bit-exact vs uninterrupted,
    with ledger round ids monotone and deduplicated."""
    (cont_state, cont_meta), _ = _uninterrupted_run
    crash_dir = tmp_path / "crash"
    ledger = str(crash_dir / "led.jsonl")
    extra = ("--ledger", ledger)

    state = _inject_round_failure(monkeypatch, _KILL_ROUND, failure)
    if failure == "sigterm":
        # GracefulShutdown is caught inside main(): clean exit
        cv_train.main(_midrun_argv(crash_dir, 6, extra))
    else:
        with pytest.raises(RuntimeError, match="injected round"):
            cv_train.main(_midrun_argv(crash_dir, 6, extra))
    assert state["fired"]
    monkeypatch.undo()

    # crash saved NOTHING past the last cadence autosave: no final
    # model artifact, checkpoint meta at the autosaved round
    assert not os.path.exists(str(crash_dir / "ResNet9.pkl"))
    crash_meta = _load_state(crash_dir)[1]
    assert crash_meta["round_index"] == _KILL_ROUND - 1

    cv_train.main(_midrun_argv(crash_dir, 6, (*extra, "--resume")))
    res_state, res_meta = _load_state(crash_dir)
    assert res_meta["epoch"] == cont_meta["epoch"] == 6
    assert res_meta["round_index"] == cont_meta["round_index"]
    assert res_meta["opt_step_count"] == cont_meta["opt_step_count"]
    assert set(cont_state) == set(res_state)
    for k in cont_state:
        np.testing.assert_array_equal(cont_state[k], res_state[k],
                                      err_msg=k)
    # the resumed run appended to the SAME ledger; replayed rounds
    # were deduplicated (JSONLSink resume_after), ids stay monotone
    with open(ledger) as f:
        rounds = [rec["round"] for rec in map(json.loads, f)
                  if rec.get("kind") == "round"
                  and rec.get("round") is not None]
    assert rounds == sorted(set(rounds)), rounds
    assert rounds == list(range(rounds[0], rounds[-1] + 1))
    assert rounds[-1] >= cont_meta["round_index"] - 1


def test_gpt2_resume_round_trip(tmp_path):
    """GPT-2 trainer: resumed run continues from the saved epoch and
    reproduces the uninterrupted final state exactly."""
    import json
    import os

    from commefficient_tpu.train import gpt2_train

    def argv(d, epochs, extra=()):
        return [
            "--test", "--dataset_name", "PERSONA",
            "--dataset_dir", str(d / "data"),
            "--mode", "sketch", "--error_type", "virtual",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_workers", "2", "--local_batch_size", "2",
            "--num_epochs", str(epochs), "--lr_scale", "0.01",
            "--checkpoint", "--checkpoint_path", str(d),
            "--checkpoint_every", "1", *extra,
        ]

    def state(d):
        with np.load(os.path.join(str(d), "ckpt_gpt2.npz")) as z:
            return ({k: np.array(z[k]) for k in z.files if k != "meta"},
                    json.loads(str(z["meta"])))

    cont, resume = tmp_path / "c", tmp_path / "r"
    gpt2_train.main(argv(cont, 2))
    # interrupted run: 1 epoch now, but decay over the full horizon
    gpt2_train.main(argv(resume, 1, ("--schedule_epochs", "2")))
    gpt2_train.main(argv(resume, 2, ("--resume",)))
    s1, m1 = state(cont)
    s2, m2 = state(resume)
    assert m1["epoch"] == m2["epoch"] == 2
    for k in s1:
        np.testing.assert_array_equal(s1[k], s2[k], err_msg=k)


# -- torn-shard detection and the retained-autosave fallback ------------


def _truncate(path):
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])


def test_validate_names_missing_side_shard(tmp_path):
    """A multi-process checkpoint whose side shard vanished (dead
    process, partial copy) must refuse by NAME before any state is
    touched."""
    from commefficient_tpu.runtime.checkpoint import (
        TornCheckpointError, validate_checkpoint)

    path = str(tmp_path / "ck.npz")
    meta = {"format": 1, "clientstore": {"fields": ["velocities"],
                                         "processes": 2}}
    np.savez_compressed(path, meta=json.dumps(meta))
    with pytest.raises(TornCheckpointError, match=r"ck\.npz\.shard1"):
        validate_checkpoint(path)
    # a present-but-torn side shard is named the same way
    _truncate_target = path + ".shard1.npz"
    np.savez_compressed(_truncate_target, ids=np.zeros(1, np.int64))
    _truncate(_truncate_target)
    with pytest.raises(TornCheckpointError, match=r"shard1\.npz"):
        validate_checkpoint(path)


def test_torn_canonical_falls_back_to_retained_autosave(
        tmp_path, capsys):
    """A torn canonical checkpoint costs at most the autosave cadence:
    --resume restores the newest retained round snapshot instead of
    crashing, and the run completes."""
    d = tmp_path / "run"
    cv_train.main(_midrun_argv(d, 4))
    _truncate(os.path.join(str(d), "ckpt_ResNet9.npz"))
    cv_train.main(_midrun_argv(d, 6, ("--resume",)))
    out = capsys.readouterr().out
    assert "falling back to retained autosave" in out
    assert "_r00000004.npz" in out
    _, meta = _load_state(d)  # canonical rewritten by the resumed run
    assert meta["epoch"] == 6


def test_torn_canonical_without_fallback_raises(tmp_path):
    """No retained snapshot to fall back to: the original error —
    naming the torn file — propagates instead of silently training
    from scratch."""
    from commefficient_tpu.runtime.checkpoint import TornCheckpointError

    cv_train.main(_argv(tmp_path, 1))
    _truncate(os.path.join(str(tmp_path), "ckpt_ResNet9.npz"))
    with pytest.raises(TornCheckpointError, match=r"ckpt_ResNet9\.npz"):
        cv_train.main(_argv(tmp_path, 2, ("--resume",)))


def test_round_autosave_retention_across_resume_boundary(tmp_path):
    """--checkpoint_keep keeps pruning across a stop/resume: the
    resumed run's autosaves displace the pre-resume snapshots instead
    of accumulating beside them."""
    d = tmp_path / "run"
    cv_train.main(_midrun_argv(d, 4))
    snaps = sorted(glob.glob(os.path.join(str(d), "ckpt_ResNet9_r*.npz")))
    rounds = [int(os.path.basename(n).split("_r")[1].split(".")[0])
              for n in snaps]
    assert rounds == [2, 4]
    cv_train.main(_midrun_argv(d, 8, ("--resume",)))
    snaps = sorted(glob.glob(os.path.join(str(d), "ckpt_ResNet9_r*.npz")))
    rounds = [int(os.path.basename(n).split("_r")[1].split(".")[0])
              for n in snaps]
    assert rounds == [6, 8], rounds
    _, meta = _load_state(d)
    assert meta["round_index"] == 8


# -- the data path's streams where the loader has opened an epoch ahead --


def _cv_data_path(kind, drop):
    from commefficient_tpu.data.fed_sampler import FedSampler
    from commefficient_tpu.data.loader import FedLoader, NativeFedLoader
    from commefficient_tpu.data.synthetic import FedSynthetic
    from commefficient_tpu.data.transforms import cifar_train_transform
    tf = cifar_train_transform(np.float32(0.1), np.float32(1.1))
    ds = FedSynthetic("", "Synthetic", transform=tf, num_classes=4,
                      per_class=16, num_val=8, gen_seed=3)
    sampler = FedSampler(ds, num_workers=2, local_batch_size=4, seed=0)
    kw = dict(dropout_prob=drop, dropout_seed=5)
    if kind == "fed":
        return FedLoader(ds, sampler, **kw)
    return NativeFedLoader(ds, sampler, seed=11, depth=3, **kw)


def _tiny_model():
    import jax.numpy as jnp

    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime.fed_model import (FedModel,
                                                     FedOptimizer)
    cfg = Config(mode="sketch", error_type="virtual", local_momentum=0.0,
                 virtual_momentum=0.9, k=16, num_rows=3, num_cols=128,
                 num_workers=2, local_batch_size=4, seed=5,
                 num_clients=16, num_devices=1)

    def loss(params, batch, cfg):
        return jnp.float32(0.0), (jnp.float32(0.0),)

    model = FedModel(None, {"w": jnp.zeros((12, 4), jnp.float32)}, loss,
                     cfg, padded_batch_size=4)
    return model, FedOptimizer([{"lr": 0.25}], cfg, model=model)


def _copies(batches):
    return [{k: np.array(v) for k, v in b.items()} for b in batches]


def _assert_dealt(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for k in b:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _native_or_skip(kind):
    from commefficient_tpu import native
    if kind == "native" and not native.available():
        pytest.skip("no native toolchain")


@pytest.mark.parametrize("at", ["epoch_end", "last_round", "mid_epoch"])
@pytest.mark.parametrize("kind", ["fed", "native"])
def test_resume_beside_an_epoch_opened_ahead_deals_the_same_rounds(
        tmp_path, kind, at):
    """With a model live the loader's thread opens epoch e+1 when the
    sampler has dealt epoch e's last round (data/loader.py): the live
    streams are then past the boundary, and the checkpoint records what
    the loader held back of it. ``epoch_end``: saved between two epochs,
    the resumed loader deals the next epoch whole, its first round
    included. ``last_round``: saved mid-epoch where every round of the
    epoch is taken, it re-enters that epoch for nothing and deals the
    next whole. ``mid_epoch``: nothing is opened ahead, and it continues
    after the rounds that were in flight, as ever. Against three epochs
    of the loader that makes its batches on the consumer's thread."""
    from commefficient_tpu.data import staging
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)
    _native_or_skip(kind)
    # the native ring's rounds draw their drop-out when popped: those
    # in flight at a mid-epoch save shift that stream, as ever
    drop = 0.3 if kind == "fed" or at == "epoch_end" else 0.0
    plain = _cv_data_path(kind, drop)
    assert staging.current() is None
    np.random.seed(77)
    ref = [_copies(plain) for _ in range(3)]
    plain.close()

    model, opt = _tiny_model()
    assert staging.current() is not None
    loader = _cv_data_path(kind, drop)
    np.random.seed(77)
    _assert_dealt(_copies(loader), ref[0])
    path = str(tmp_path / "ck.npz")
    taken = {"epoch_end": 0, "last_round": len(ref[1]), "mid_epoch": 3}[at]
    it = iter(loader)
    head = _copies(next(it) for _ in range(taken))
    save_checkpoint(path, model, opt, sampler=loader.sampler, epoch=1,
                    loader=loader, mid_epoch=at != "epoch_end")
    assert bool(loader.held_back()) == (at != "mid_epoch")
    # the run that was not interrupted is not disturbed by the save
    _assert_dealt(head + _copies(it), ref[1])
    _assert_dealt(_copies(loader), ref[2])
    loader.close()

    resumed = _cv_data_path(kind, drop)
    np.random.seed(999)
    # (FedLoader reads the image shape off one item, once, by a draw of
    # the transforms': before the streams are restored, here)
    next(iter(resumed))
    meta = load_checkpoint(path, model, opt, sampler=resumed.sampler,
                           loader=resumed)
    assert bool(meta.get("sampler_mid_epoch")) == (at != "epoch_end")
    if at != "epoch_end":
        rest = _copies(resumed)
        in_flight = {"last_round": 0, "mid_epoch": 1 if kind == "fed"
                     else resumed.depth + 1}[at]
        assert len(rest) == len(ref[1]) - taken - in_flight
        _assert_dealt(rest, ref[1][len(ref[1]) - len(rest):])
    _assert_dealt(_copies(resumed), ref[1 if at == "epoch_end" else 2])
    if at == "epoch_end":
        _assert_dealt(_copies(resumed), ref[2])
    resumed.close()
    model.finalize()
    assert not [t for t in threading.enumerate()
                if t.name == "loader-stage"]


def test_checkpoints_written_before_the_read_ahead_crossed_epochs_load(
        tmp_path):
    """The archive's keys are what they were: a loader that holds
    nothing back writes what ``save_checkpoint`` always wrote."""
    from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                      save_checkpoint)
    model, opt = _tiny_model()
    loader = _cv_data_path("fed", 0.3)
    loader.placement = None
    loader.telemetry = model.telemetry
    np.random.seed(3)
    it = iter(loader)
    next(it), next(it)
    assert loader._reader is not None and not loader.held_back()
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, model, opt, sampler=loader.sampler, epoch=0,
                    loader=loader, mid_epoch=True)
    with np.load(path) as z:
        names, meta = set(z.files), json.loads(str(z["meta"]))
    assert {"sampler_rng_keys", "np_global_rng_keys", "dropout_rng_keys",
            "sampler_mid_rng_keys", "sampler_mid_permuted",
            "sampler_mid_cur", "sampler_mid_spec_workers"} <= names
    for key in ("sampler_rng", "np_global_rng", "dropout_rng",
                "sampler_mid_rng"):
        kind, keys, pos, has_gauss, gauss = meta[key]
        assert kind == "MT19937" and keys is None
    assert meta["sampler_mid_epoch"] is True
    rest = _copies(it)
    loader.close()
    resumed = _cv_data_path("fed", 0.3)
    next(iter(resumed))     # the image shape, read once by a draw
    load_checkpoint(path, model, opt, sampler=resumed.sampler,
                    loader=resumed)
    again = _copies(resumed)
    _assert_dealt(again, rest[1:])      # one round was in flight
    resumed.close()
    model.finalize()

"""The round loop's one contract: ``model(batch)`` hands this round's
results to its caller as host arrays, the round's record has its bytes
when the call returns, a checkpoint can be taken after any round, and
the trainers handle round r before they dispatch round r + 1. On the
tiny linear model of test_accounting.py, over the five modes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config, parse_args
from commefficient_tpu.runtime import FedModel, FedOptimizer
from commefficient_tpu.runtime.checkpoint import (RoundAutosaver,
                                                  history_file,
                                                  save_checkpoint)
from commefficient_tpu.telemetry import NULL_TELEMETRY, validate_record

SKETCH = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
              virtual_momentum=0.9, num_rows=2, num_cols=16,
              num_blocks=1, k=3)
MODES = {
    "sketch": SKETCH,
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      local_momentum=0.0, virtual_momentum=0.9, k=3),
    "local_topk": dict(mode="local_topk", error_type="local",
                       local_momentum=0.9, virtual_momentum=0.9, k=3),
    "fedavg": dict(mode="fedavg", error_type="none",
                   local_momentum=0.0, local_batch_size=-1),
    "uncompressed": dict(mode="uncompressed", error_type="none",
                         local_momentum=0.0),
}
# (mode, devices of the clients mesh): every mode on one device, and
# the cells' mode on the 4-device CPU mesh
TOPOLOGIES = [pytest.param(m, 1, id=m) for m in sorted(MODES)] \
    + [pytest.param("sketch", 4, id="sketch-mesh4")]
W, B, CLIENTS = 4, 2, 9


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


def build(mode, num_devices=1, **cfg_kw):
    import flax.linen as nn

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 3)))["params"]
    kw = dict(MODES[mode], **cfg_kw)
    kw.setdefault("local_batch_size", B)
    args = Config(num_workers=W, num_clients=CLIENTS,
                  num_devices=num_devices, dataset_name="CIFAR10",
                  seed=0, **kw)

    def loss(p, batch, cfg):
        pred = module.apply({"params": p}, batch["x"])
        per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum(per * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    model = FedModel(module, params, loss, args, padded_batch_size=B)
    return model, FedOptimizer([{"lr": 0.1}], args), args


def batches(n, seed=7):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(W, B, 3).astype(np.float32),
             "y": rng.randn(W, B).astype(np.float32),
             "mask": np.ones((W, B), np.float32),
             "client_ids": rng.choice(CLIENTS, W, replace=False)
             .astype(np.int32)} for _ in range(n)]


@pytest.mark.parametrize("mode,num_devices", TOPOLOGIES)
def test_call_returns_this_rounds_results(mode, num_devices):
    model, opt, args = build(mode, num_devices)
    assert int(np.prod(model.mesh.devices.shape)) == num_devices
    for name in ("flush", "_inflight", "_oplog"):
        assert not hasattr(model, name), name
    for batch in batches(3):
        out = model(batch)
        assert out is not None
        assert len(out) == args.num_results_train + 2
        assert all(isinstance(m, np.ndarray) for m in out)
        loss, ones, down, up = out
        assert loss.shape == ones.shape == (W,)
        np.testing.assert_array_equal(ones, 1.0)
        # the last two: per-client byte vectors, nonzero only for the
        # clients of this very round
        assert down.shape == up.shape == (CLIENTS,)
        ids = batch["client_ids"]
        rest = np.setdiff1d(np.arange(CLIENTS), ids)
        assert np.all(up[ids] == args.upload_wire_bytes_per_client)
        assert not up[rest].any() and not down[rest].any()
        opt.step()
    model.finalize()


@pytest.mark.parametrize("mode,num_devices", TOPOLOGIES)
def test_record_has_its_bytes_when_the_call_returns(mode, num_devices):
    model, opt, _ = build(mode, num_devices)
    sink = ListSink()
    model.telemetry.add_sink(sink)
    tel = model.telemetry
    want = []
    for r, batch in enumerate(batches(3)):
        out = model(batch)
        # round r - 1's record reached the sink at begin_round(r), no
        # sooner and no later
        assert [rec["round"] for rec in sink.records] == list(range(r))
        rec = tel._current
        assert rec["round"] == r
        want.append((float(out[-2].sum()), float(out[-1].sum())))
        assert (rec["downlink_bytes"], rec["uplink_bytes"]) == want[-1]
        opt.step()
        assert tel._current is rec and len(sink.records) == r
    model.finalize()
    assert [rec["round"] for rec in sink.records] == [0, 1, 2]
    for rec, (down, up) in zip(sink.records, want):
        assert validate_record(rec) == []
        assert (rec["downlink_bytes"], rec["uplink_bytes"]) == (down, up)
        assert "causal" not in rec and rec["timeline"]


@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_checkpoint_can_be_taken_after_any_round(mode, tmp_path):
    model, opt, args = build(mode, checkpoint_every_rounds=2,
                             checkpoint_keep=8,
                             checkpoint_path=str(tmp_path / "auto"))
    os.makedirs(args.checkpoint_path)
    autosave = RoundAutosaver(args, model, opt, None, None, None,
                              tag="t")
    for r, batch in enumerate(batches(6), start=1):
        model(batch)
        opt.step()
        if r <= 2:
            path = save_checkpoint(str(tmp_path / f"r{r}.npz"), model,
                                   opt, mid_epoch=True)
            assert os.path.getsize(path) > 0
        autosave(0)
        # every multiple of the cadence is saved, none is skipped
        saved = [q for q in range(1, r + 1) if os.path.exists(
            history_file(args.checkpoint_path, "t", q))]
        assert saved == list(range(2, r + 1, 2))
    model.finalize()


@pytest.mark.parametrize("mode", ["sketch", "true_topk"])
def test_server_probes_are_finished_in_the_server_pass(mode):
    model, opt, _ = build(mode, probe_every=1)
    model.telemetry.add_sink(ListSink())
    for r, batch in enumerate(batches(3)):
        model(batch)
        rec = model.telemetry._current
        client_side = dict(rec["probes"])
        assert "agg_norm" in client_side
        assert "update_norm" not in client_side
        opt.step()
        # complete on this round's record, still current, and nothing
        # is parked anywhere for later
        assert model.telemetry._current is rec
        probes = rec["probes"]
        for key in ("agg_norm", "update_norm", "momentum_norm",
                    "residual_norm"):
            assert np.isfinite(probes[key]), key
        assert ("residual_growth" in probes) == (r > 0)
        assert model._probe_host == {}
    model.finalize()


@pytest.mark.parametrize("how", ["parser:--pipeline_depth",
                                 "parser:--causal_trace",
                                 "config:pipeline_depth",
                                 "config:causal_trace"])
def test_removed_flags_are_refused(how, capsys):
    kind, name = how.split(":")
    if kind == "parser":
        argv = [name] + (["4"] if name == "--pipeline_depth" else [])
        with pytest.raises(SystemExit) as e:
            parse_args(argv=argv)
        assert e.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err
    else:
        with pytest.raises(TypeError, match=name):
            Config(**{name: 4 if name == "pipeline_depth" else True})
        assert not hasattr(Config(), name)


class _Results(list):
    """What a fake ``model(batch)`` returns: notes when the trainer
    reads it."""

    def __init__(self, items, log, r):
        super().__init__(items)
        self._log, self._r = log, r

    def __getitem__(self, i):
        self._log.append(("handled", self._r))
        return super().__getitem__(i)


class _CountingModel:
    """Stands in for ``FedModel``: counts dispatches, returns results
    like the real call does, and has nothing to drain."""
    telemetry = NULL_TELEMETRY
    num_clients = CLIENTS

    def __init__(self, log):
        self.log, self.rounds = log, 0

    def train(self, training):
        pass

    def __call__(self, batch):
        r, self.rounds = self.rounds, self.rounds + 1
        self.log.append(("model", r))
        per_client = np.zeros(CLIENTS)
        return _Results([np.full(W, float(r)), np.ones(W), per_client,
                         per_client], self.log, r)


class _CountingOpt:
    param_groups = [{"lr": 0.1}]

    def __init__(self, log):
        self.log, self.rounds = log, 0

    def step(self):
        self.log.append(("step", self.rounds))
        self.rounds += 1


class _Schedule:
    def step(self):
        pass


@pytest.mark.parametrize("trainer", ["cv_train", "gpt2_train"])
def test_trainers_handle_round_r_before_dispatching_r_plus_1(trainer):
    import importlib
    run_batches = importlib.import_module(
        f"commefficient_tpu.train.{trainer}").run_batches
    log, hooked = [], []
    model = _CountingModel(log)
    out = run_batches(model, _CountingOpt(log), _Schedule(), batches(5),
                      Config(), True,
                      round_hook=lambda ep: hooked.append(model.rounds))
    # model r, step r, the trainer reads r's results, then r + 1
    order = [e for i, e in enumerate(log) if i == 0 or e != log[i - 1]]
    assert order == [(what, r) for r in range(5)
                     for what in ("model", "step", "handled")]
    assert hooked == [1, 2, 3, 4, 5]
    # rounds' losses 0..4 in order: their mean
    mean_loss = out[0] if trainer == "cv_train" else out
    assert mean_loss == pytest.approx(2.0)

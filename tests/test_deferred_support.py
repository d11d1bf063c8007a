"""The server update's support is settled one dispatch late
(``FedModel.defer_update`` / ``settle_update``): ``opt.step()`` leaves it
in one pending slot and the next ``model(batch)`` applies it right after
dispatching its own program. Whoever reads or writes the accounting
state settles the slot first, so every byte and every piece of state is
what settling at once, inside ``opt.step()``, gives. On the tiny linear
model of test_round_contract.py: the five modes (between them the three
support forms: index/value tuple, device-packed bitmap, ``None``),
sketch on the 4-device mesh, and both downlink encodings."""

import json

import numpy as np
import pytest

from commefficient_tpu.runtime.checkpoint import (load_checkpoint,
                                                  save_checkpoint)
from test_round_contract import (MODES, TOPOLOGIES, ListSink, batches,
                                 build)

ENCODINGS = ["dense", "delta"]
#: the form ``opt.step()`` leaves pending, by mode
FORMS = {"sketch": tuple, "true_topk": tuple, "local_topk": dict,
         "fedavg": dict, "uncompressed": type(None)}
STATE = ("last_updated", "_round_counts", "_repeat_count",
         "_bitmap_bits", "_update_round", "client_last_seen")


def state_of(model):
    return {name: np.array(getattr(model, name)) for name in STATE}


def assert_same_state(a, b, where):
    for name in STATE:
        x, y = a[name], b[name]
        # _round_counts grows in steps of 64: compare what is counted
        n = min(x.size, y.size) if x.ndim else None
        if n is not None and x.size != y.size:
            assert not x[n:].any() and not y[n:].any(), (where, name)
            x, y = x[:n], y[:n]
        np.testing.assert_array_equal(x, y, err_msg=f"{where}: {name}")


def run(model, opt, some_batches, at_once, zero_lr_round=None):
    """Rounds as a trainer runs them; ``at_once`` settles each update
    right after ``opt.step()``, as the loop did before the slot. Returns
    per round (down, up, the state when ``model(batch)`` returned)."""
    out = []
    for r, batch in enumerate(some_batches):
        opt.param_groups[0]["lr"] = 0.0 if r == zero_lr_round else 0.1
        res = model(batch)
        assert model._pending_update is None
        out.append((res[-2], res[-1], state_of(model)))
        opt.step()
        if at_once:
            assert model.settle_update()
            assert not model.settle_update()
        else:
            assert model._pending_update is not None
    return out


@pytest.mark.parametrize("encoding", ENCODINGS)
@pytest.mark.parametrize("mode,num_devices", TOPOLOGIES)
def test_deferred_equals_settled_at_once(mode, num_devices, encoding):
    some = batches(6)
    # fedavg's clients run at the LR the last step set: a zero-LR round
    # there is another algorithm, not another support form
    zero = None if mode == "fedavg" else 3
    runs = []
    for at_once in (False, True):
        model, opt, _ = build(mode, num_devices,
                              downlink_encoding=encoding)
        forms = []
        real = model.defer_update
        model.defer_update = lambda s, f=forms, real=real: (
            f.append(type(s)), real(s))[1]
        rounds = run(model, opt, some, at_once, zero_lr_round=zero)
        # a run that ends on opt.step(): finalize leaves nothing pending
        model.finalize()
        assert model._pending_update is None
        runs.append((rounds, state_of(model), forms))
    (late, late_end, forms), (once, once_end, _) = runs
    want = [FORMS[mode]] * len(some)
    if zero is not None:
        want[zero] = tuple              # the empty support of lr == 0
    assert forms == want
    for r, ((d0, u0, s0), (d1, u1, s1)) in enumerate(zip(late, once)):
        np.testing.assert_array_equal(d0, d1, err_msg=f"down, round {r}")
        np.testing.assert_array_equal(u0, u1, err_msg=f"up, round {r}")
        assert_same_state(s0, s1, f"round {r}")
    assert_same_state(late_end, once_end, "after finalize")
    assert late_end["_update_round"] == len(some)
    # something was billed: the comparison is not of zeros
    assert sum(float(d.sum()) for d, _, _ in late[1:]) > 0


@pytest.mark.parametrize("form", ["blocked", "flat"])
def test_select_counters_on_every_record(form, monkeypatch):
    """A sketch run in the sparse-resketch regime (d > 90·r·k, the
    cells' server path): every round record says which form of the
    exact selection its server program was built with, and over how
    many candidates; the program traced is that form."""
    import importlib

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime import FedModel, FedOptimizer
    from test_round_contract import B, CLIENTS, W
    topk_mod = importlib.import_module("commefficient_tpu.ops.topk")

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(64, use_bias=False)(x)

    d, k, block = 32 * 64, 2, 8
    masks = []
    real = topk_mod.threshold_topk_mask_1d
    monkeypatch.setattr(
        topk_mod, "threshold_topk_mask_1d",
        lambda sq, k, **kw: (masks.append(sq.shape[0]), real(sq, k, **kw))[1])
    if form == "blocked":
        # the cells' regime at a toy size: the threshold select from
        # d = 1 on, blocks of 8
        monkeypatch.setattr(topk_mod, "_THRESHOLD_SELECT_MIN_D", 1)
        monkeypatch.setattr(topk_mod, "_SELECT_BLOCK", block)
    module = Lin()
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 32)))["params"]
    # a seed a form: the sketch is a static argument of the jitted
    # unsketch, and an equal one would be served the other form's trace
    args = Config(num_workers=W, num_clients=CLIENTS, num_devices=1,
                  dataset_name="CIFAR10", local_batch_size=B,
                  seed=11 + (form == "flat"),
                  **dict(MODES["sketch"], num_rows=1, num_cols=64, k=k))

    def loss(p, batch, cfg):
        pred = module.apply({"params": p}, batch["x"])
        per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum(per * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    model = FedModel(module, params, loss, args, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.1}], args)
    assert args.grad_size == d > 90 * args.num_rows * k
    sink = ListSink()
    model.telemetry.add_sink(sink)
    rng = np.random.RandomState(5)
    for _ in range(4):
        model({"x": rng.randn(W, B, 32).astype(np.float32),
               "y": rng.randn(W, B).astype(np.float32),
               "mask": np.ones((W, B), np.float32),
               "client_ids": rng.choice(CLIENTS, W, replace=False)
               .astype(np.int32)})
        opt.step()
    model.finalize()
    want = {"blocked": {"select.blocked": 1,
                        "select.candidates": k * block},
            "flat": {"select.flat": 1, "select.candidates": d}}[form]
    assert len(sink.records) == 4
    for rec in sink.records:
        assert {n: v for n, v in rec["counters"].items()
                if n.startswith("select.")} == want
    # one mask over the block maxima, one over the k blocks; the flat
    # form at this d is lax.top_k
    assert masks == ([d // block, k * block] if form == "blocked" else [])


@pytest.mark.parametrize("mode,geometry,want", [
    # whole-vreg rotations on chunks of whole (32, 128) tiles,
    # whatever the stream's length (16 chunks, 8)
    ("sketch", dict(num_cols=8192, sketch_rot_lanes=1024), "addressed"),
    ("sketch", dict(num_cols=16384, sketch_rot_lanes=1024), "addressed"),
    ("sketch", dict(num_cols=8192, sketch_rot_lanes=0), "rolled"),
    ("sketch", dict(num_cols=8192, sketch_rot_lanes=128), "rolled"),
    # auto on the CPU: full-granularity rotations
    ("sketch", dict(num_cols=8192), "rolled"),
    ("true_topk", {}, None), ("uncompressed", {}, None),
    ("local_topk", {}, None), ("fedavg", {}, None),
])
def test_rotation_form_on_every_sketch_round_record(mode, geometry, want):
    """Every sketch-mode round record says which form its sketch
    kernels apply a rotation in, from the operator's shapes alone
    (d = 131,072 here); outside sketch mode no
    record says either."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import Config
    from commefficient_tpu.runtime import FedModel, FedOptimizer
    from test_round_contract import B, CLIENTS, W

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(512, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 256)))["params"]
    kw = dict(MODES[mode], **geometry)
    kw.setdefault("local_batch_size", B)
    args = Config(num_workers=W, num_clients=CLIENTS, num_devices=1,
                  dataset_name="CIFAR10", seed=0, **kw)

    def loss(p, batch, cfg):
        pred = module.apply({"params": p}, batch["x"])
        per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        l = jnp.sum(per * batch["mask"]) / n
        return l, (l * 0.0 + 1.0,)

    model = FedModel(module, params, loss, args, padded_batch_size=B)
    opt = FedOptimizer([{"lr": 0.1}], args)
    assert args.grad_size == 16 * 8192
    sink = ListSink()
    model.telemetry.add_sink(sink)
    rng = np.random.RandomState(5)
    for _ in range(3):
        model({"x": rng.randn(W, B, 256).astype(np.float32),
               "y": rng.randn(W, B).astype(np.float32),
               "mask": np.ones((W, B), np.float32),
               "client_ids": rng.choice(CLIENTS, W, replace=False)
               .astype(np.int32)})
        opt.step()
    model.finalize()
    assert len(sink.records) == 3
    for rec in sink.records:
        assert {n: v for n, v in rec["counters"].items()
                if n.startswith("sketch.rot_")} == (
                    {} if want is None else {"sketch.rot_" + want: 1})


@pytest.mark.parametrize("mode", sorted(MODES))
def test_checkpoint_with_the_slot_full_continues_the_same(mode, tmp_path):
    some = batches(7)
    model, opt, _ = build(mode)
    whole = run(model, opt, some, at_once=False)
    model.finalize()

    first, opt1, _ = build(mode)
    run(first, opt1, some[:4], at_once=False)
    assert first._pending_update is not None
    path = save_checkpoint(str(tmp_path / "r4.npz"), first, opt1,
                           mid_epoch=True)
    # the save settled it: what the archive holds is the applied state
    assert first._pending_update is None
    assert first._update_round == 4
    second, opt2, _ = build(mode)
    # a model that ran on before being restored: its own pending update
    # does not leak into the restored state
    run(second, opt2, batches(2, seed=11), at_once=False)
    assert second._pending_update is not None
    load_checkpoint(path, second, opt2)
    assert second._pending_update is None
    assert second._update_round == 4
    for tag, (m, o) in (("saver", (first, opt1)),
                        ("restored", (second, opt2))):
        rest = run(m, o, some[4:], at_once=False)
        for r, ((d0, u0, _), (d1, u1, _)) in enumerate(
                zip(whole[4:], rest), start=4):
            np.testing.assert_array_equal(d0, d1,
                                          err_msg=f"{tag} down {r}")
            np.testing.assert_array_equal(u0, u1, err_msg=f"{tag} up {r}")
        m.finalize()


@pytest.mark.parametrize("mode,num_devices", TOPOLOGIES)
def test_counters_and_span_order_on_every_record(mode, num_devices,
                                                 tmp_path):
    model, opt, _ = build(mode, num_devices)
    sink = ListSink()
    model.telemetry.add_sink(sink)
    for r, batch in enumerate(batches(5)):
        model(batch)
        opt.step()
        if r == 2:              # a checkpoint settles update 2 early
            save_checkpoint(str(tmp_path / "c.npz"), model, opt,
                            mid_epoch=True)
    model.finalize()
    assert model._pending_update is None
    got = [(rec["counters"].get("account.deferred", 0),
            rec["counters"].get("account.inline", 0))
           for rec in sink.records]
    assert got == [(0, 0), (1, 0), (1, 0), (0, 1), (1, 0)]
    for rec in sink.records:
        line = rec["timeline"]
        names = [e[0] for e in line]
        i_cp = names.index("client_pass")
        i_disp = names.index("round_dispatch")
        notes = [e for e in line if e[0] == "note_update"]
        # settled between two rounds (by the checkpoint after round 2,
        # by finalize after the last): on the record still current, as
        # a span of its own after that round's server pass
        loose = [e for e in notes if e[3] is None]
        assert len(loose) == (1 if rec["round"] in (2, 4) else 0)
        for e in loose:
            assert e[1] >= line[names.index("server_pass")][2]
        inside = [e for e in notes if e[3] is not None]
        if rec["round"] in (0, 3):
            # none before round 0; round 3's went with the checkpoint
            assert inside == []
            continue
        (note,) = inside
        assert note[3] == i_cp and line[i_disp][3] == i_cp
        # opens after the dispatch closes, closes before the wait for
        # the round's metrics opens
        assert line[i_disp][2] <= note[1] <= note[2]
        assert note[2] <= line[names.index("metrics_host")][1]
        assert note[2] <= line[names.index("account")][1]


def test_at_most_one_update_is_pending_and_they_apply_in_order():
    d = 12
    model, _, _ = build("true_topk")
    ref, _, _ = build("true_topk")
    sup = [(np.array([0, 1, 2]), np.ones(3)),
           (np.array([2, 3]), np.ones(2)),
           None,
           {"bitmap": np.packbits(np.arange(d) % 5 == 0)},
           (np.array([7]), np.ones(1))]
    for s in sup:
        ref.note_update(s)
    model.defer_update(sup[0])
    model.defer_update(sup[1])          # settles the first
    assert model._update_round == 1 and model._pending_update is not None
    model.note_update(sup[2])           # a direct call: the second first
    assert model._update_round == 3 and model._pending_update is None
    model.defer_update(sup[3])
    assert model._update_round == 3
    # the byte accounting itself is a guard
    ids = np.array([0, 1], np.int32)
    down, _ = model._account_bytes(ids)
    assert model._update_round == 4 and model._pending_update is None
    model.defer_update(sup[4])
    assert model.settle_update() and not model.settle_update()
    assert_same_state(state_of(model),
                      dict(state_of(ref),
                           client_last_seen=model.client_last_seen),
                      "in order")
    assert down[ids].tolist() == [4.0 * d, 4.0 * d]


def test_a_pending_device_support_has_its_copy_started(monkeypatch):
    import jax.numpy as jnp
    model, _, _ = build("local_topk")
    started = []
    bitmap = jnp.packbits(jnp.arange(12) % 2 == 0)
    real = type(bitmap).copy_to_host_async
    monkeypatch.setattr(
        type(bitmap), "copy_to_host_async",
        lambda self: (started.append(self.shape), real(self))[1])
    model.defer_update({"bitmap": bitmap})
    model.defer_update((jnp.arange(3), jnp.ones(3)))
    model.defer_update(None)
    model.defer_update((np.zeros(0, np.int64), np.zeros(0)))
    assert started == [(2,), (3,), (3,)]
    assert model.settle_update()
    assert model._update_round == 4


@pytest.fixture(scope="module")
def sp_rounds(tmp_path_factory):
    """The round records of the sequence-parallel GPT-2 trainer, run
    twice from one seed: as it is, and with every update settled at
    once inside ``opt.step()``."""
    from commefficient_tpu.data import staging
    from commefficient_tpu.runtime import FedOptimizer
    from commefficient_tpu.train import gpt2_train

    real_step = FedOptimizer.step

    def step(self):
        real_step(self)
        assert self.model.settle_update()

    out = {}
    for settle in ("deferred", "at_once"):
        tmp = tmp_path_factory.mktemp(f"sp-{settle}")
        ledger = str(tmp / "sp.jsonl")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(staging, "_LIVE", [])    # as conftest.py does
            if settle == "at_once":
                mp.setattr(FedOptimizer, "step", step)
            gpt2_train.main([
                "--test", "--dataset_name", "PERSONA",
                "--dataset_dir", str(tmp / "data"),
                "--mode", "sketch", "--error_type", "virtual",
                "--local_momentum", "0", "--virtual_momentum", "0.9",
                "--num_workers", "2", "--local_batch_size", "2",
                "--num_epochs", "4", "--seq_devices", "4",
                "--seed", "3", "--ledger", ledger])
        with open(ledger) as f:
            out[settle] = [r for r in map(json.loads, f)
                           if r["kind"] == "round"]
    return out


@pytest.mark.parametrize("settle", ["deferred", "at_once"])
def test_seq_parallel_model_counts_where_it_settled(settle, sp_rounds):
    rounds = sp_rounds[settle]
    assert len(rounds) == 4             # an epoch is one round here
    want = (1, 0) if settle == "deferred" else (0, 1)
    assert [(r["counters"].get("account.deferred", 0),
             r["counters"].get("account.inline", 0))
            for r in rounds] == [(0, 0)] + [want] * (len(rounds) - 1)


def test_seq_parallel_model_bills_the_same_bytes(sp_rounds):
    late, once = ([(r["downlink_bytes"], r["uplink_bytes"]) for r in rs]
                  for rs in (sp_rounds["deferred"], sp_rounds["at_once"]))
    assert late == once
    assert sum(d for d, _ in late[1:]) > 0


def test_seq_parallel_model_settles_after_its_dispatch(sp_rounds):
    for rec in sp_rounds["deferred"][1:]:
        line = rec["timeline"]
        names = [e[0] for e in line]
        note = line[names.index("note_update")]
        disp = line[names.index("round_dispatch")]
        assert note[3] == disp[3] == names.index("client_pass")
        assert disp[2] <= note[1]
        assert note[2] <= line[names.index("metrics_host")][1]

"""Granite 4.0-H's stage (models/granite_hybrid.py) against its plain
float32 reference (benchmark/reference/granite4-h-micro-pp4-v8.py): each
kind of block alone and the 10-layer pattern with all four multipliers
off 1, the tied matrix's gradient, the vocabulary shares adding up to
the uncut loss, the counters, the trainer end to end and FetchSGD
rounds through ``FedModel``; with the thresholds lowered, the model in
its mixers' bounded forms (which tests/test_mixers.py holds to the
stepwise recurrence and to dense attention on their own). Tiny sizes,
seeded weights, float32, CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models import mixers
from commefficient_tpu.models.granite_hybrid import (COUNTERS, STATS, Block,
                                                     GraniteHybridConfig,
                                                     GraniteHybridLM,
                                                     causal_lm_loss)
from commefficient_tpu.models.mixers import attn_query_block, ssd_head_block
from test_nemotron_h import _close, _load   # the helpers, not the cases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "granite4-h-micro-pp4-v8"
CELL = "granite4hm_fetchsgd_w4_t2048"
HIGHEST = jax.default_matmul_precision("highest")


ref = _load(os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py"),
            "bench_ref_granite4")


def _tiny(layer_types=None, **kw):
    cfg = GraniteHybridConfig.tiny()
    if layer_types is not None:
        kw["layer_types"] = tuple(layer_types)
    cfg = dataclasses.replace(cfg, **kw)
    return cfg, cfg.reference_spec()


def _json(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# --- program against reference ------------------------------------------------

PERIOD = GraniteHybridConfig.tiny().layer_types


def test_the_tiny_preset_moves_every_multiplier_off_one():
    cfg = GraniteHybridConfig.tiny()
    assert PERIOD == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    for name in ("embedding_multiplier", "attention_multiplier",
                 "residual_multiplier", "logits_scaling"):
        assert getattr(cfg, name) != 1.0, name
    assert cfg.attention_multiplier != cfg.head_dim ** -0.5


@pytest.mark.parametrize("layer_types", [("mamba",), ("attention",), PERIOD],
                         ids=["mamba", "attention", "period"])
def test_loss_and_gradient_match_the_reference(layer_types):
    """One client's loss and gradient, for a model of one block of each
    kind and for the whole period."""
    cfg, spec = _tiny(layer_types)
    module = GraniteHybridLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 20), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "mask": jnp.array([1.0, 1.0, 0.0])}

    def program(p):
        losses, _ = causal_lm_loss(module, p, ids)
        return jnp.sum(losses * batch["mask"]) / jnp.sum(batch["mask"])

    with HIGHEST:
        lp, gp = jax.jit(jax.value_and_grad(program))(params)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: ref.client_loss(p, batch, spec)))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    _close(gp, gr)


@pytest.mark.parametrize("name,factor", [
    ("embedding_multiplier", 2.0), ("attention_multiplier", 3.0),
    ("residual_multiplier", 0.5), ("logits_scaling", 4.0)])
def test_each_multiplier_is_read(name, factor):
    """Moving one multiplier moves the loss, in the program as in the
    reference (weights wide enough that the scores are not all but
    equal)."""
    cfg, spec = _tiny(("mamba", "attention"), initializer_range=0.3)
    other, ospec = _tiny(("mamba", "attention"), initializer_range=0.3,
                         **{name: getattr(cfg, name) * factor})
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "mask": jnp.ones((2,))}
    with HIGHEST:
        base = float(jnp.mean(causal_lm_loss(
            GraniteHybridLM(cfg), params, ids)[0]))
        moved = float(jnp.mean(causal_lm_loss(
            GraniteHybridLM(other), params, ids)[0]))
        want = float(ref.client_loss(params, batch, ospec))
    assert abs(moved - base) > 1e-3 * abs(base)
    assert abs(moved - want) <= 2e-6 * abs(want)


def test_the_tied_matrix_takes_the_sum_of_both_uses_gradients():
    """One leaf for embedding and head: its gradient is the embedding's
    plus the head's of the same model with the two held apart."""
    cfg, spec = _tiny(("mamba", "attention"))
    apart, aspec = _tiny(("mamba", "attention"), tie_word_embeddings=False)
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    assert "lm_head" not in params
    two = dict(params, lm_head=params["embed"])
    assert jax.tree_util.tree_structure(two) == jax.tree_util.tree_structure(
        ref.init_params(jax.random.PRNGKey(1), aspec))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                             cfg.vocab_size)

    def loss(module):
        return lambda p: jnp.mean(causal_lm_loss(module, p, ids)[0])

    with HIGHEST:
        tied = jax.grad(loss(GraniteHybridLM(cfg)))(params)
        split = jax.grad(loss(GraniteHybridLM(apart)))(two)
    assert np.any(np.asarray(split["embed"]))
    assert np.any(np.asarray(split["lm_head"]))
    np.testing.assert_allclose(tied["embed"],
                               split["embed"] + split["lm_head"],
                               rtol=1e-5, atol=1e-7)
    _close({k: v for k, v in tied.items() if k != "embed"},
           {k: v for k, v in split.items()
            if k not in ("embed", "lm_head")})


def test_under_the_clients_vmap_the_gradient_is_the_references():
    cfg, spec = _tiny(remat=True)
    module = GraniteHybridLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(3), spec)
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 2, 16), 0,
                             cfg.vocab_size)
    ones = jnp.ones((2,))

    def program(p):
        losses, stats = jax.vmap(
            lambda i: causal_lm_loss(module, p, i))(ids)
        return jnp.sum(jnp.mean(losses, axis=1)), stats

    def reference(p):
        return jnp.sum(jax.vmap(lambda i: ref.client_loss(
            p, {"input_ids": i, "mask": ones}, spec))(ids))

    with HIGHEST:
        (lp, stats), gp = jax.jit(jax.value_and_grad(
            program, has_aux=True))(params)
        lr, gr = jax.jit(jax.value_and_grad(reference))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    _close(gp, gr)
    stats = dict(zip(STATS, (np.asarray(s) for s in stats)))
    # 2 sequences x 2 chunks of 8 x 9 Mamba-2 layers a client
    assert stats["ssm_chunks"].tolist() == [36.0] * 4
    assert stats["attn_dense"].tolist() == [1.0] * 4
    assert not stats["attn_blocked"].any()


def test_a_blocked_model_is_the_reference_and_says_so(monkeypatch):
    """With the thresholds lowered so that the tiny preset takes the
    bounded forms, the loss and gradient are still the reference's, and
    the counts say which attention was built."""
    monkeypatch.setattr(mixers, "SSD_DECAY_BYTES", 2 * 20 * 8 * 8 * 4 * 3)
    monkeypatch.setattr(mixers, "ATTN_SCORE_BYTES", 1024)
    monkeypatch.setattr(mixers, "ATTN_BLOCK_BYTES", 1024)
    assert ssd_head_block(2, 160, 8, 1, 8) == 3
    assert attn_query_block(2, 160, 4) == 128     # 160 = 128 + a ragged 32
    cfg, spec = _tiny(("mamba", "attention", "mamba"))
    module = GraniteHybridLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 160), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "mask": jnp.ones((2,))}

    def program(p):
        losses, stats = causal_lm_loss(module, p, ids)
        return jnp.mean(losses), stats

    with HIGHEST:
        (lp, stats), gp = jax.value_and_grad(program, has_aux=True)(params)
        lr, gr = jax.value_and_grad(
            lambda p: ref.client_loss(p, batch, spec))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    _close(gp, gr)
    assert dict(zip(STATS, map(float, stats))) == {
        "ssm_chunks": 2 * 20 * 2, "attn_blocked": 1.0, "attn_dense": 0.0,
        "attn_kernel_layers": 0.0}      # off the chip: the blocked form


def test_a_model_with_no_attention_layer_counts_neither_form():
    cfg, _ = _tiny(("mamba",))
    module = GraniteHybridLM(cfg)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    _, stats = causal_lm_loss(module, params, jnp.zeros((1, 8), jnp.int32))
    assert list(map(float, stats)) == [1.0, 0.0, 0.0, 0.0]
    assert [name for name, _ in COUNTERS] == [
        "ssm.chunks", "attn.blocked", "attn.dense", "attn.kernel_layers"]


# --- the vocabulary's shares add up to the uncut loss ---------------------------

def test_the_vocabulary_shares_add_up_to_the_uncut_loss():
    """8 chips hold 12 of 96 rows of the tied matrix each. Every chip's
    partial log-sum-exp over its rows, and the label's logit from the
    chip that holds the label's row, combine to the uncut reference's
    loss; what every chip computes alike (the hidden states) is counted
    once."""
    cfg, spec = _tiny(("mamba", "attention", "mamba"))
    module = GraniteHybridLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(7), spec)
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0,
                             cfg.vocab_size)
    rows = cfg.vocab_size // 8
    with HIGHEST:
        want = ref.sequence_losses(params, ids, spec)
        final, head, _ = module.apply({"params": params}, ids)
        labels = ids[:, 1:]
        lse, picked = [], 0.0
        for chip in range(8):
            share = head[chip * rows:(chip + 1) * rows]
            logits = final[:, :-1] @ share.T       # (S, T-1, rows)
            lse.append(jax.nn.logsumexp(logits, axis=-1))
            local = labels - chip * rows
            here = (local >= 0) & (local < rows)
            picked = picked + jnp.where(here, jnp.take_along_axis(
                logits, jnp.clip(local, 0, rows - 1)[..., None],
                axis=-1)[..., 0], 0.0)
        got = jnp.mean(jax.nn.logsumexp(jnp.stack(lse), axis=0) - picked,
                       axis=-1)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # one share alone is a smaller vocabulary: its loss over the slice
    # is the program's loss of a model that holds those rows only
    small = dataclasses.replace(cfg, vocab_size=rows)
    own = dict(params, embed=params["embed"][:rows])
    inside = ids % rows
    with HIGHEST:
        got = causal_lm_loss(GraniteHybridLM(small), own, inside)[0]
        want = ref.sequence_losses(own, inside, small.reference_spec())
    np.testing.assert_allclose(got, want, rtol=2e-6)


# --- configuration, trainer, FedModel ------------------------------------------

def test_the_configuration_keeps_every_published_width():
    config = _json("configs", CONFIG)
    cfg = GraniteHybridConfig.from_hf(config)
    published = GraniteHybridConfig()
    for f in dataclasses.fields(cfg):
        if f.name not in ("layer_types", "vocab_size"):
            assert getattr(cfg, f.name) == getattr(published, f.name), f.name
    assert (cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_chunk_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim,
            cfg.shared_intermediate_size) == (
        2048, 64, 64, 1, 128, 4, 256, 32, 8, 64, 8192)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling,
            cfg.tie_word_embeddings) == (12, 0.015625, 0.22, 8, True)
    assert cfg.layer_types == published.layer_types[10:20] == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert published.layer_types == tuple(
        config["published"]["layer_types"])
    assert cfg.vocab_size * 8 == published.vocab_size == 100352
    assert sorted(config["reduced"]) == sorted(
        k for k, v in config["published"].items() if config[k] != v)
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), config))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        shapes)) == config["num_parameters"] == 772160448
    parts = config["parameter_count"]
    assert 9 * parts["mamba_layer"] + parts["attention_layer"] \
        + parts["tied_embedding_and_head"] + parts["final_norm"] \
        == config["num_parameters"]


def test_every_number_of_the_catalogs_config_is_kept_or_listed():
    """The catalog's rule: a key of the source's config that differs
    here is named in ``reduced``; every other is kept as published."""
    config = _json("configs", CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


@pytest.mark.parametrize("blob,named", [
    ({"num_local_experts": 62}, "num_local_experts"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"num_hidden_layers": 12}, "num_hidden_layers"),
    ({"layer_types": ["mamba"] * 9 + ["moe"]}, "moe")])
def test_what_is_not_built_is_refused_by_name(blob, named):
    config = dict(_json("configs", CONFIG), **blob)
    with pytest.raises(ValueError, match=named):
        GraniteHybridConfig.from_hf(config)


def _tiny_run(tmp_path, extra=(), model="GraniteHybridLM"):
    from commefficient_tpu.train import gpt2_train
    return gpt2_train.run(
        ["--test", "--model", model, "--dataset_name", "TOKENS",
         "--dataset_dir", str(tmp_path / "tokens"), "--model_checkpoint",
         str(tmp_path), "--mode", "sketch", "--error_type", "virtual",
         "--local_momentum", "0", "--virtual_momentum", "0.9",
         "--num_workers", "4", "--local_batch_size", "2",
         "--num_devices", "1", "--num_epochs", "1", *extra])


def test_the_trainer_trains_it_through_fedmodel(tmp_path):
    out = _tiny_run(tmp_path, ["--remat",
                               "--ledger", str(tmp_path / "ledger.jsonl")])
    row = out.results[0]
    assert np.isfinite(row["train_loss"]) and np.isfinite(row["val_nll"])
    with open(tmp_path / "ledger.jsonl") as f:
        recs = [r for r in map(json.loads, f) if r.get("kind") == "round"]
    assert recs
    for c in (r["counters"] for r in recs):
        # 4 clients x 2 sequences x 32 / 8 chunks x 9 Mamba-2 layers
        assert c["ssm.chunks"] == 4 * 2 * 4 * 9
        assert (c["attn.dense"], c["attn.blocked"]) == (1, 0)
        assert c["attn.kernel_layers"] == 0
        # a packed stream labels every position: the head orders none
        assert c["head.compact"] == 0 and "head.labelled" not in c


@pytest.mark.parametrize("model,model_type", [
    ("GraniteHybridLM", "nemotron_h"), ("NemotronHLM", "granitemoehybrid")])
def test_model_flag_and_config_json_must_agree(tmp_path, model, model_type):
    """A ``config.json`` of another ``model_type`` is refused with both
    sides named: the flag with what it builds, the file with what it
    holds."""
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": model_type}, f)
    with pytest.raises(ValueError) as err:
        _tiny_run(tmp_path, model=model)
    from commefficient_tpu.models import get_model
    for word in (model, model_type, get_model(model).model_type,
                 "config.json"):
        assert word in str(err.value)


def test_a_routed_config_json_is_refused_by_the_trainer(tmp_path):
    with open(tmp_path / "config.json", "w") as f:
        json.dump(dict(_json("configs", CONFIG), num_local_experts=62), f)
    with pytest.raises(ValueError, match="num_local_experts"):
        _tiny_run(tmp_path)


def test_two_fetchsgd_rounds_through_fedmodel_follow_the_reference(
        tmp_path):
    """The comparison that decides the cell's ``correct``, at the tiny
    preset: the benchmark's builder assembles the trainer's own objects
    with the reference's weights, the rounds run as ``run_batches`` runs
    them, and ``fetchsgd_ref.follow`` restates them in plain float32."""
    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.run import load, read_json
    cell = read_json(ROOT, "benchmark", "workloads", CELL + ".json")
    config = read_json(ROOT, "benchmark", "configs",
                       cell["config"] + ".json")
    assert config["builder"] == "granite_hybrid"
    assert cell["clients_per_round"] * cell["local_batch_size"] \
        * cell["sequence_length"] == 8192
    cell.update({k: v for k, v in cell["rehearse"].items() if k != "data"},
                num_devices=1)
    run = load("builders", config["builder"]).build(
        cell, config, ref, 20260929, str(tmp_path), rehearse=True)
    it = iter(run.loader)
    kept = {"batches": [], "losses": [], "lrs": []}
    for i in range(2):
        batch = next(it)
        losses, *_ = run.step(batch, keep_aggregate=True)
        if i == 0:
            table0 = np.asarray(run.last_aggregate)
        kept["batches"].append(run.ref_batch(batch))
        kept["losses"].append(np.asarray(losses, np.float64))
        kept["lrs"].append(run.lr())
    params0 = run.make_params()
    flat0 = np.asarray(ravel_pytree(params0)[0], np.float32)
    observed = {"losses": kept["losses"], "table0": table0,
                "delta": np.asarray(run.model.ps_weights) - flat0}
    want = fr.follow(ref=ref, spec_model=run.ref_spec, params=params0,
                     batches=kept["batches"], lrs=kept["lrs"],
                     hyper=run.hyper(), sk=fr.SketchSpec(**run.sketch_spec()))
    sizes = [int(np.prod(x.shape))
             for x in jax.tree_util.tree_leaves(params0)]
    nums = fr.numbers(observed, want, sizes)
    assert all(ok for *_, ok in fr.verdict(nums, ref.LIMITS)), nums
    assert max(nums.values()) < 1e-4, nums
    assert np.count_nonzero(observed["delta"]) > 0


def test_the_reference_counts_the_rounds_flops():
    """6 a matmul parameter a token (the tied matrix once, as the head)
    plus the recurrence and the causal half of attention."""
    config, cell = _json("configs", CONFIG), _json("workloads", CELL)
    matmul = 9 * (17432576 + 8388608) + 10485760 + 10 * 50331648 \
        + 12544 * 2048
    per_token = 6 * matmul + 9 * 12 * 64 * 64 * 128 \
        + 6 * 2048 * 32 * 64
    assert ref.train_flops_per_round(config, cell) == per_token * 8192
    assert 38e12 < per_token * 8192 < 39e12


def test_block_rejects_an_unknown_layer_type():
    cfg = GraniteHybridConfig.tiny()
    with pytest.raises(ValueError, match="layer type"):
        Block(cfg, "moe").init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8, cfg.hidden_size)))

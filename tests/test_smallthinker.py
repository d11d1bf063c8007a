"""SmallThinker's share (models/smallthinker.py) against its plain
float32 reference (benchmark/reference/smallthinker-21ba3b-ep8.py):
loss and every gradient leaf at two whole periods with T past the
window and both attention forms blocked, the eight expert shares adding
up to the uncut layer with the same picks in all, the router reading
the block's input, the configuration file against the catalog's
``config``, the counters, the trainer end to end and FetchSGD rounds
through ``FedModel``. Tiny sizes, seeded weights, float32, CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models import smallthinker
from commefficient_tpu.models.smallthinker import (COUNTERS, STATS, Block,
                                                   SmallThinkerConfig,
                                                   SmallThinkerLM,
                                                   causal_lm_loss)
from test_nemotron_h import _close, _load   # the helpers, not the cases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "smallthinker-21ba3b-ep8"
CELL = "smallthinker_fetchsgd_w2_t8192"
HIGHEST = jax.default_matmul_precision("highest")
SAME = lambda a: a  # noqa: E731  (the reference's float32 quantizer)

ref = _load(os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py"),
            "bench_ref_smallthinker")

#: the catalog's copy of the published ``config.json``
#: (/opt/skills/guides/model-configs/architectures.jsonl, row
#: SmallThinker-21BA3B-Instruct), restated: no file outside the repo is
#: read by a test
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def _tiny(**kw):
    cfg = dataclasses.replace(SmallThinkerConfig.tiny(), **kw)
    return cfg, cfg.reference_spec()


def _program(module, ids, mask):
    def loss(p):
        losses, _ = causal_lm_loss(module, p, ids)
        return jnp.sum(losses * mask) / jnp.sum(mask)
    return loss


# --- program against reference ------------------------------------------------

LAYOUTS = {"full-nope": ((0,), (0,)), "window-rope": ((1,), (1,)),
           "window-nope": ((1,), (0,)), "full-rope": ((0,), (1,)),
           "two-periods": ((0, 1, 1, 1) * 2, (0, 1, 1, 1) * 2)}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_loss_and_gradient_match_the_reference(layout):
    """One client's loss and every gradient leaf: a model of one layer
    of each kind (and the two kinds the published model does not pair),
    and the two whole periods; T = 37 past the window of 12, which is
    no multiple of the query block of 8, nor is T."""
    windows, ropes = LAYOUTS[layout]
    cfg, spec = _tiny(sliding_window_layout=windows, rope_layout=ropes)
    module = SmallThinkerLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 37), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "mask": jnp.array([1.0, 1.0, 0.0])}
    with HIGHEST:
        lp, gp = jax.jit(jax.value_and_grad(
            _program(module, ids, batch["mask"])))(params)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: ref.client_loss(p, batch, spec)))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    _close(gp, gr)
    for g in (gp, gr):      # the router learns, through the gates alone
        assert np.any(np.asarray(g["layer_0"]["router"]))


def test_under_the_clients_vmap_the_gradient_is_the_references():
    """As the fused round applies it: the clients' ``vmap``, ``--remat``,
    one gradient of the summed losses; and the counts of ``STATS``."""
    cfg, spec = _tiny(remat=True)
    module = SmallThinkerLM(cfg)
    params = ref.init_params(jax.random.PRNGKey(3), spec)
    ids = jax.random.randint(jax.random.PRNGKey(4), (3, 2, 32), 0,
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
    assert len(STATS) == len(COUNTERS) == len(stats)
    assert not stats["dropped"].any()
    # 64 tokens x 6 picks x 4 held of 16 = 96 a layer in expectation
    assert (stats["assignments_here"] > 8 * 48).all()
    assert stats["attn_window_layers"].tolist() == [6.0] * 3
    assert stats["attn_full_layers"].tolist() == [2.0] * 3
    assert stats["attn_blocked"].tolist() == [1.0] * 3
    assert stats["attn_kernel_layers"].tolist() == [0.0] * 3
    # a block of 8 queries meets 8 + 16 keys: window - 1 = 11 in blocks
    assert stats["attn_window_keys"].tolist() == [24.0] * 3
    # 2 sequences x 4 heads x (2 x 32 x 32 + 6 x 32 x 24)
    assert stats["attn_pairs"].tolist() == [8.0 * 6656] * 3
    # ... x (2 x 32 x 33 / 2 + 6 x (12 x 13 / 2 + 20 x 12))
    assert stats["attn_pairs_needed"].tolist() == [8.0 * 2964] * 3
    assert stats["router_pre_attn"].tolist() == [1.0] * 3


# --- the share, and where the router reads ------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 8 chips' expert parts (``expert_offset``
    0, 8, ..., 56), with the attention every chip computes alike counted
    once, give what the reference computes with all 64 experts; the
    router's picks are the same in every share."""
    cfg = dataclasses.replace(
        SmallThinkerConfig.tiny(), sliding_window_layout=(1,),
        rope_layout=(1,), n_router_experts=64, n_held_experts=8)
    whole = dict(cfg.reference_spec(), moe_num_primary_experts=64,
                 expert_offset=0)
    p = ref.init_params(jax.random.PRNGKey(5), whole)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, cfg.hidden_size))
    with HIGHEST:
        want = ref._block(0, p, x, whole, SAME)
        none = dict(p, experts={k: v[:0] for k, v in p["experts"].items()})
        alike = ref._block(0, none, x, dict(whole,
                                            moe_num_primary_experts=0),
                           SAME)
        total, picks = alike, []
        for chip in range(8):
            held = slice(8 * chip, 8 * chip + 8)
            share = dict(p, experts={k: v[held]
                                     for k, v in p["experts"].items()})
            (y, stats, _), state = Block(dataclasses.replace(
                cfg, expert_offset=8 * chip), 0).apply(
                {"params": share}, x, mutable=["intermediates"])
            assert float(stats[2]) == 0.0
            picks.append(np.asarray(state["intermediates"]["top"][0]))
            total = total + (y - alike)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    for top in picks[1:]:
        np.testing.assert_array_equal(top, picks[0])
    assert picks[0].shape == (48, 6) and picks[0].max() >= 8


def test_the_router_reads_the_blocks_input():
    """A block whose router reads the stream after attention picks
    other experts and gives another result; the program is the
    reference, which routes before attention."""
    cfg, spec = _tiny(sliding_window_layout=(1,), rope_layout=(1,))
    p = ref.init_params(jax.random.PRNGKey(7), spec)["layer_0"]
    # attention strong enough to move the picks
    p = dict(p, attn=dict(p["attn"], o=30.0 * p["attn"]["o"],
                          v=30.0 * p["attn"]["v"]))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, cfg.hidden_size))
    with HIGHEST:
        want = ref._block(0, p, x, spec, SAME)
        before, _, built = Block(cfg, 0).apply({"params": p}, x)
        after, _, built_after = Block(cfg, 0, True).apply({"params": p}, x)
    np.testing.assert_allclose(before, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(after - want).max()) > 1e-3
    assert built.tolist() == [1.0, 1.0] and built_after.tolist() == [1.0, 0.0]


# --- configuration ------------------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_from_hf_reads_the_catalogs_config():
    """The published file as the catalog holds it, ``model_type``
    added (it carries ``model_name`` only): the defaults of the class."""
    cfg = SmallThinkerConfig.from_hf(dict(CATALOG,
                                          model_type="smallthinker"))
    assert cfg == SmallThinkerConfig()
    assert (cfg.num_hidden_layers, cfg.n_held_experts, cfg.n_router_experts,
            cfg.window(0), cfg.window(1)) == (52, 64, 64, None, 4096)
    assert cfg.sliding_window_layout[4:8] == (0, 1, 1, 1) == \
        cfg.rope_layout[4:8]


@pytest.mark.parametrize("change,match", [
    ({"num_hidden_layers": 51}, "sliding_window_layout"),
    ({"rope_layout": [0, 1, 1]}, "rope_layout"),
    ({"sliding_window_layout": [0, 2, 1, 1] * 13}, "sliding_window_layout"),
    ({"model_type": "qwen3_moe"}, "model_type"),
    ({"moe_primary_router_apply_softmax": False}, "softmax"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings")],
    ids=["layers", "rope-length", "layout-entry", "model-type",
         "sigmoid-router", "tied"])
def test_from_hf_refuses_what_it_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        SmallThinkerConfig.from_hf({**CATALOG, "model_type": "smallthinker",
                                    **change})


def test_the_configuration_keeps_every_published_width():
    config = _config()
    cfg = SmallThinkerConfig.from_hf(config)
    published = SmallThinkerConfig()
    for name in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                 "head_dim", "sliding_window_size", "rope_theta",
                 "moe_ffn_hidden_size", "n_router_experts",
                 "moe_num_active_primary_experts", "rms_norm_eps"):
        assert getattr(cfg, name) == getattr(published, name), name
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window_size,
            cfg.rope_theta, cfg.moe_ffn_hidden_size, cfg.n_router_experts,
            cfg.moe_num_active_primary_experts) == (
        2560, 28, 4, 128, 4096, 1.5e6, 768, 64, 6)
    assert (cfg.sliding_window_layout, cfg.rope_layout, cfg.n_held_experts,
            cfg.expert_offset, cfg.vocab_size) == (
        (0, 1, 1, 1), (0, 1, 1, 1), 8, 0, 18992)
    assert sorted(config["assumed"])[:7] == [
        "a_router_input", "b_rope", "c_window", "d_model_type",
        "e_expert_layers", "f_secondary_experts", "g_weights_and_data"]
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), config))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        shapes)) == config["num_parameters"] == 370547200
    count = config["parameter_count"]
    assert 4 * count["layer"] + count["embedding_and_head"] \
        + count["final_norm"] == 370547200
    assert sum(count["layer_of_which"].values()) == count["layer"]


def test_every_number_of_the_published_config_is_kept_or_listed():
    """The catalog's rule: a key that differs from the source's is
    named in ``reduced`` with the published value beside it; every
    other key of the catalog's ``config`` is in the file as it is."""
    config = _config()
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert sorted(config["reduced"]) == sorted(config["published"])
    # the cut is the published layers 4-7
    assert CATALOG["sliding_window_layout"][4:8] == \
        config["sliding_window_layout"]
    assert CATALOG["rope_layout"][4:8] == config["rope_layout"]
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["moe_num_primary_experts"] * 8 == config["router_experts"]


def test_the_needed_work_counts_the_band_not_the_square():
    """``train_flops_per_round``: the causal half on the full layer,
    the band on the three window layers."""
    config = _config()
    cell = {"sequence_length": 8192, "clients_per_round": 2,
            "local_batch_size": 1}
    T, w = 8192, 4096
    assert ref.attention_pairs(T) == T * (T + 1) // 2
    assert ref.attention_pairs(T, w) == sum(min(i + 1, w) for i in range(T))
    assert ref.attention_pairs(w, w) == ref.attention_pairs(w)
    pairs = ref.attention_pairs(T) + 3 * ref.attention_pairs(T, w)
    matmul = 4 * (20971520 + 2560 * 64 + 3 * 2560 * 768 * 6 * 8 / 64) \
        + 18992 * 2560
    assert ref.train_flops_per_round(config, cell) == pytest.approx(
        2 * (6 * matmul * T + 12 * 128 * 28 * pairs))


# --- trainer, FedModel --------------------------------------------------------

def _tiny_run(tmp_path, extra=()):
    from commefficient_tpu.train import gpt2_train
    return gpt2_train.run(
        ["--test", "--model", "SmallThinkerLM", "--dataset_name", "TOKENS",
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
        assert c["moe.dropped"] == 0 and c["moe.assignments_here"] > 0
        assert c["moe.load_max"] >= c["moe.load_mean"] > 0
        assert c["moe.router_pre_attn"] == 1
        assert (c["attn.window_layers"], c["attn.full_layers"],
                c["attn.blocked"], c["attn.window_keys"]) == (6, 2, 1, 24)
        # the tiny preset states its query block: never the kernel
        assert c["attn.kernel_layers"] == 0
        # 4 clients x 2 sequences x 4 heads x ...
        assert c["attn.pairs"] == 4 * 8 * 6656
        assert c["attn.pairs_needed"] == 4 * 8 * 2964


def test_a_config_json_of_another_model_type_is_refused(tmp_path):
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": "nemotron_h"}, f)
    with pytest.raises(ValueError) as err:
        _tiny_run(tmp_path)
    assert "SmallThinkerLM" in str(err.value) \
        and "nemotron_h" in str(err.value)


def test_the_cells_config_json_builds_the_published_layer(tmp_path):
    """``build_causal_lm`` on the cell's own ``config.json``, shapes
    only: the trainer reads the file the benchmark writes."""
    from commefficient_tpu.train import gpt2_train
    module_cls = smallthinker.SmallThinkerLM
    config = _config()
    assert config["model_type"] == module_cls.model_type
    assert "SmallThinkerLM" in gpt2_train.CAUSAL_LMS
    module = module_cls(module_cls.config_class.from_hf(config))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = {jax.tree_util.keystr(k): v.shape for k, v in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got["['layer_1']['experts']['gate']"] == (8, 2560, 768)
    assert got["['layer_1']['router']"] == (2560, 64)
    assert got["['layer_0']['attn']['q']"] == (2560, 3584)
    assert got["['layer_0']['attn']['k']"] == (2560, 512)
    assert got["['lm_head']"] == (18992, 2560) == got["['embed']"]
    assert sum(int(np.prod(s)) for s in got.values()) == 370547200


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
    assert config["builder"] == "smallthinker"
    cell.update({k: v for k, v in cell["rehearse"].items() if k != "data"},
                num_devices=1)
    run = load("builders", config["builder"]).build(
        cell, config, ref, 20261001, str(tmp_path), rehearse=True)
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
    run.loader.close()
    run.model.finalize()

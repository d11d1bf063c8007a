"""JoyAI-LLM-Flash's share (models/joyai.py) against its plain float32
reference (benchmark/reference/joyai-llm-flash-ep32.py): one client's
loss and gradient, three FetchSGD rounds through ``FedModel``, the
shares adding up to the uncut layers, the expert layer's counters, the MTP targets, the trainer
end to end. GPT-2's comparison with *its* reference
(benchmark/reference/gpt2-124m-personachat.py) is a case of the first.
Tiny sizes, seeded weights, float32, CPU."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models.joyai import (MOE_STATS, STATS, MLA, ExpertLayer,
                                            JoyAIConfig, JoyAIFlashLM,
                                            causal_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIGHEST = jax.default_matmul_precision("highest")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_ref(config):
    return _load(os.path.join(ROOT, "benchmark", "reference",
                              config + ".py"),
                 "bench_ref_" + config.replace("-", "_"))


ref = _bench_ref("joyai-llm-flash-ep32")


def _rel(a, b):
    a, b = ravel_pytree(a)[0], ravel_pytree(b)[0]
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# --- one client's loss and gradient ----------------------------------------

def _joyai_case():
    cfg = JoyAIConfig.tiny()
    module, spec = JoyAIFlashLM(cfg), cfg.reference_spec()
    params = ref.init_params(jax.random.PRNGKey(1), spec)
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 16), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "mask": jnp.array([1.0, 1.0, 0.0])}

    def program(p):
        losses, _ = causal_lm_loss(module, p, ids)
        return jnp.sum(losses * batch["mask"]) / jnp.sum(batch["mask"])

    return params, program, lambda p: ref.client_loss(p, batch, spec)


def _gpt2_case():
    import dataclasses

    from commefficient_tpu.config import Config
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.train.gpt2_train import make_compute_loss_train
    gref = _bench_ref("gpt2-124m-personachat")
    cfg = dataclasses.replace(GPT2Config.tiny(), n_positions=16)
    spec = {"n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
            "n_head": cfg.n_head, "n_positions": cfg.n_positions,
            "vocab_size": cfg.vocab_size, "lm_coef": 1.0, "mc_coef": 1.0}
    params = gref.init_params(jax.random.PRNGKey(1), spec)
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    B, N, T = 3, 2, 16
    ids = jax.random.randint(k[0], (B, N, T), 0, cfg.vocab_size)
    labels = jnp.where(jax.random.uniform(k[1], (B, N, T)) < 0.5, ids, -1)
    batch = {"input_ids": ids,
             "token_type_ids": jax.random.randint(k[2], (B, N, T), 0, 4),
             "lm_labels": labels.at[:, :, -1].set(ids[:, :, -1]),
             "mc_token_ids": jnp.full((B, N), T - 1, jnp.int32),
             "mc_labels": jax.random.randint(k[3], (B,), 0, N),
             "mask": jnp.array([1.0, 0.0, 1.0])}
    args = Config(num_workers=1)
    loss = make_compute_loss_train(GPT2DoubleHeads(cfg), args)
    return (params, lambda p: loss(p, batch, args)[0],
            lambda p: gref.client_loss(p, batch, spec))


@pytest.mark.parametrize("case", [_joyai_case, _gpt2_case],
                         ids=["joyai", "gpt2"])
def test_system_loss_and_gradient_match_the_reference(case):
    params, program, reference = case()
    with HIGHEST:
        lp, gp = jax.jit(jax.value_and_grad(program))(params)
        lr, gr = jax.jit(jax.value_and_grad(reference))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    assert _rel(gp, gr) <= 2e-5
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gp)[0],
            jax.tree_util.tree_flatten_with_path(gr)[0]):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * max(
            float(jnp.abs(b).max()), 1e-3), jax.tree_util.keystr(path)


def test_the_routers_bias_gets_a_zero_gradient():
    params, program, reference = _joyai_case()
    for fn in (program, reference):
        g = jax.jit(jax.grad(fn))(params)
        for name in ("layer_1", "mtp_0"):
            moe = (g[name]["block"] if name == "mtp_0" else g[name])["moe"]
            assert not np.any(np.asarray(moe["router_bias"]))
            assert np.any(np.asarray(moe["router"]))


def test_under_the_clients_vmap_the_gradient_is_the_references():
    """``core/rounds.py make_local_loss`` vmaps the loss over clients
    and differentiates once: the ragged products run once per client
    and the experts' gradient is summed over them."""
    cfg = JoyAIConfig.tiny()
    module, spec = JoyAIFlashLM(cfg), cfg.reference_spec()
    params = ref.init_params(jax.random.PRNGKey(3), spec)
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 2, 16), 0,
                             cfg.vocab_size)
    ones = jnp.ones((2,))

    def program(p):
        losses, stats = jax.vmap(
            lambda i: causal_lm_loss(module, p, i))(ids)
        return jnp.mean(losses), stats

    def reference(p):
        return jnp.mean(jnp.stack([ref.client_loss(
            p, {"input_ids": ids[c], "mask": ones}, spec)
            for c in range(4)]))

    with HIGHEST:
        (lp, stats), gp = jax.jit(jax.value_and_grad(
            program, has_aux=True))(params)
        lr, gr = jax.jit(jax.value_and_grad(reference))(params)
    assert abs(float(lp) - float(lr)) <= 2e-6 * float(lr)
    assert _rel(gp, gr) <= 2e-5
    assert [s.shape for s in stats] == [(4,)] * len(STATS)
    assert not np.any(np.asarray(stats[MOE_STATS.index("dropped")]))


# --- the shares add up ------------------------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_layer():
    """32 chips' expert shares, the shared expert counted once, give
    what the reference computes with all the experts."""
    cfg = JoyAIConfig(hidden_size=32, moe_intermediate_size=16,
                      n_router_experts=64, n_held_experts=2,
                      num_experts_per_tok=4)
    whole = dict(cfg.reference_spec(), n_routed_experts=64,
                 expert_offset=0, num_hidden_layers=2, q_lora_rank=8,
                 kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
                 v_head_dim=4, intermediate_size=8, vocab_size=8,
                 num_attention_heads=1)
    p = ref.init_params(jax.random.PRNGKey(5), whole)["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 16, 32))
    with HIGHEST:
        want = ref._moe(p, x, whole, lambda a: a)
        shared = ref._swiglu(x, p["shared"], lambda a: a)
        total = shared
        for chip in range(32):
            share = dict(p, experts={k: v[2 * chip:2 * chip + 2]
                                     for k, v in p["experts"].items()})
            layer = ExpertLayer(JoyAIConfig(**{
                **{f: getattr(cfg, f) for f in cfg.__dataclass_fields__},
                "expert_offset": 2 * chip}))
            y, stats = layer.apply({"params": share}, x)
            assert float(stats[2]) == 0.0
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_head_shares_add_up_to_the_uncut_attention():
    """8 chips' head shares give the reference's 8-head MLA."""
    cfg = JoyAIConfig(hidden_size=32, num_attention_heads=1,
                      q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8)
    whole = dict(cfg.reference_spec(), num_attention_heads=8,
                 num_hidden_layers=1, intermediate_size=8, vocab_size=8,
                 moe_intermediate_size=8)
    p = ref.init_params(jax.random.PRNGKey(7), whole)["layer_0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32))
    dq, dkv, dv = 8 + 4, 8 + 8, 8
    with HIGHEST:
        want = ref._mla(p, x, whole, lambda a: a)
        total = 0.0
        for chip in range(8):
            share = dict(p, q_b=p["q_b"][:, chip * dq:(chip + 1) * dq],
                         kv_b=p["kv_b"][:, chip * dkv:(chip + 1) * dkv],
                         o=p["o"][chip * dv:(chip + 1) * dv])
            total = total + MLA(cfg).apply({"params": share}, x)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# --- counters, targets -------------------------------------------------------

def test_every_token_routed_to_one_expert_drops_none():
    """The buffer holds one row a token: the fullest case it is
    promised for is every token of a client on one expert held here."""
    cfg = JoyAIConfig.tiny()
    spec = cfg.reference_spec()
    p = ref.init_params(jax.random.PRNGKey(9), spec)["layer_1"]["moe"]
    first = cfg.expert_offset
    bias = jnp.zeros((cfg.n_router_experts,)).at[first].set(10.0) \
        .at[first + 1:first + cfg.n_held_experts].set(-10.0)
    p = dict(p, router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 24, cfg.hidden_size))
    with HIGHEST:
        y, stats = ExpertLayer(cfg).apply({"params": p}, x)
        want = ref._moe(p, x, spec, lambda a: a)
    assert [float(s) for s in stats] == [48.0, 48.0, 0.0, 0.0, 0.0]
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)


def test_more_than_one_held_expert_a_token_takes_more_passes():
    """Every token on every expert held here: four buffers' worth. The
    layer takes as many passes as that needs and leaves nothing out."""
    cfg = JoyAIConfig.tiny()
    spec = cfg.reference_spec()
    p = ref.init_params(jax.random.PRNGKey(9), spec)["layer_1"]["moe"]
    first = cfg.expert_offset
    bias = jnp.zeros((cfg.n_router_experts,)) \
        .at[first:first + cfg.n_held_experts].set(10.0)
    p = dict(p, router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 8, cfg.hidden_size))

    def program(p, x):
        y, stats = ExpertLayer(cfg).apply({"params": p}, x)
        return jnp.sum(jnp.sin(y)), stats

    with HIGHEST:
        y, stats = ExpertLayer(cfg).apply({"params": p}, x)
        want = ref._moe(p, x, spec, lambda a: a)
        gp = jax.grad(program, argnums=(0, 1), has_aux=True)(p, x)[0]
        gr = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            ref._moe(p, x, spec, lambda a: a))), argnums=(0, 1))(p, x)
    assert [float(s) for s in stats] == [32.0, 8.0, 0.0, 0.0, 0.0]
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert _rel(gp, gr) <= 2e-5


def test_mtp_predicts_the_token_after_next():
    cfg = JoyAIConfig.tiny()
    module, spec = JoyAIFlashLM(cfg), cfg.reference_spec()
    params = ref.init_params(jax.random.PRNGKey(11), spec)
    ids = jax.random.randint(jax.random.PRNGKey(12), (2, 16), 0,
                             cfg.vocab_size)
    with HIGHEST:
        final, mtp, head, _ = module.apply({"params": params}, ids)
        total, _ = causal_lm_loss(module, params, ids)
        main_ref, mtp_ref = ref.sequence_losses(params, ids, spec)

    def mean_nll(h, labels):
        logp = jax.nn.log_softmax(h @ head.T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[..., None], axis=-1)[..., 0], axis=-1)

    by_hand = mean_nll(mtp[:, :-2], ids[:, 2:])
    np.testing.assert_allclose(by_hand, mtp_ref, rtol=1e-5)
    np.testing.assert_allclose(
        total, mean_nll(final[:, :-1], ids[:, 1:])
        + cfg.mtp_loss_weight * by_hand, rtol=1e-5)
    # x_{t+1}, the main head's target, is not what it was trained on
    assert not np.allclose(mean_nll(mtp[:, :-1], ids[:, 1:]), mtp_ref,
                           rtol=1e-3)


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-ep32.json")) as f:
        config = json.load(f)
    cfg = JoyAIConfig.from_hf(config)
    published = JoyAIConfig()
    for name in ("hidden_size", "q_lora_rank", "kv_lora_rank",
                 "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                 "intermediate_size", "moe_intermediate_size",
                 "n_router_experts", "num_experts_per_tok",
                 "routed_scaling_factor", "rope_theta", "rms_norm_eps"):
        assert getattr(cfg, name) == getattr(published, name), name
    assert (cfg.n_held_experts, cfg.num_attention_heads, cfg.vocab_size,
            cfg.num_hidden_layers) == (8, 4, 16160, 5)
    assert sorted(config["reduced"]) == sorted(
        k for k, v in config["published"].items() if config[k] != v)
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), config))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        shapes)) == config["num_parameters"] == 376091904


# --- through FedModel --------------------------------------------------------

def _tiny_run(tmp_path, extra=()):
    from commefficient_tpu.train import gpt2_train
    return gpt2_train.run(
        ["--test", "--model", "JoyAIFlashLM", "--dataset_name", "TOKENS",
         "--dataset_dir", str(tmp_path / "tokens"), "--model_checkpoint",
         str(tmp_path), "--mode", "sketch", "--error_type", "virtual",
         "--local_momentum", "0", "--virtual_momentum", "0.9",
         "--num_workers", "4", "--local_batch_size", "2",
         "--num_devices", "1", "--num_epochs", "1", *extra])


def test_the_trainer_trains_it_through_fedmodel(tmp_path):
    out = _tiny_run(tmp_path, ["--ledger", str(tmp_path / "ledger.jsonl")])
    row = out.results[0]
    assert np.isfinite(row["train_loss"]) and np.isfinite(row["val_nll"])
    with open(tmp_path / "ledger.jsonl") as f:
        recs = [r for r in map(json.loads, f) if r.get("kind") == "round"]
    c = recs[0]["counters"]
    assert c["moe.dropped"] == 0 and c["moe.assignments_here"] > 0
    assert c["moe.load_max"] >= c["moe.load_mean"] > 0
    # the loader was handed the run's recorder (its spans over whole
    # epochs: tests/test_loader_spans.py, the ``tokens`` cases)
    names = {e[0] for r in recs for e in r["timeline"]}
    assert {"data.sample", "data.collate"} <= names


def test_model_and_dataset_flags_go_together(tmp_path):
    from commefficient_tpu.train import gpt2_train
    with pytest.raises(ValueError, match="do not go together"):
        gpt2_train.run(["--test", "--model", "JoyAIFlashLM",
                        "--dataset_dir", str(tmp_path)])


def test_three_fetchsgd_rounds_through_fedmodel_follow_the_reference(
        tmp_path):
    """The comparison that decides a cell's ``correct``, at a tiny size:
    the benchmark's builder assembles the trainer's own objects with the
    reference's weights, three rounds run as ``run_batches`` runs them,
    and ``fetchsgd_ref.follow`` restates them in plain float32."""
    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.run import WARMUP_ROUNDS, load, read_json
    cell = read_json(ROOT, "benchmark", "workloads",
                     "joyai_fetchsgd_w8_t1024.json")
    config = read_json(ROOT, "benchmark", "configs",
                       cell["config"] + ".json")
    cell.update({k: v for k, v in cell["rehearse"].items() if k != "data"},
                num_devices=1)
    bench = load("reference", config["reference"])
    run = load("builders", config["builder"]).build(
        cell, config, bench, 20260928, str(tmp_path), rehearse=True)
    it = iter(run.loader)
    kept = {"batches": [], "losses": [], "lrs": []}
    for i in range(WARMUP_ROUNDS):
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

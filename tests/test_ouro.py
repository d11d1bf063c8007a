"""Ouro's looped model (models/ouro.py) against its plain float32
reference (benchmark/reference/ouro-2.6b-pp6-l8.py): loss and every
gradient leaf at 4 steps and at 1 and 3, a shared weight's gradient as
the sum over the steps' addends, the loop's two forms against each
other, the exit distribution, the gate's gradient, the head's position
weights (models/gpt2.py ``_dense_nll_sums``), the configuration file
against the catalog's ``config``, the planted faults by value, the
benchmark's three new readers on handmade records, and the trainer end
to end. Tiny sizes, seeded weights, float32, CPU.

The share test of the model-configs guide's section 4 has no case here:
no layer is divided (every width, head and vocabulary row is held; the
cut is in depth alone), so there are no shares to add up."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models import gpt2, ouro
from commefficient_tpu.models.ouro import (COUNTERS, STATS, ExitGate,
                                           OuroConfig, OuroLM, Stack,
                                           causal_lm_loss, exit_distribution,
                                           exit_loss)
from test_nemotron_h import _close, _load, _rel   # helpers, not cases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "ouro-2.6b-pp6-l8"
CELL = "ouro_fetchsgd_w2_t2048"
HIGHEST = jax.default_matmul_precision("highest")

ref = _load(os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py"),
            "bench_ref_ouro")
faults = _load(os.path.join(ROOT, "benchmark", "tests", "ouro_faults.py"),
               "bench_ouro_faults")

#: the catalog's copy of the published ``config.json``
#: (/opt/skills/guides/model-configs/architectures.jsonl, row
#: Ouro-2.6B), restated: no file outside the repo is read by a test
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _json(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def _tiny(**kw):
    cfg = dataclasses.replace(OuroConfig.tiny(), **kw)
    return cfg, cfg.reference_spec()


def _setup(seed=1, shape=(3, 32), **kw):
    cfg, spec = _tiny(**kw)
    params = ref.init_params(jax.random.PRNGKey(seed), spec)
    # off its initial 0, so that the gate's bias has a gradient to miss
    params["exit_gate"]["bias"] = jnp.float32([0.3])
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), shape, 0,
                             cfg.vocab_size)
    return cfg, spec, params, ids


def _program(module, ids, mask):
    def loss(p):
        losses, _ = causal_lm_loss(module, p, ids)
        return jnp.sum(losses * mask) / jnp.sum(mask)
    return loss


def _against_reference(cfg, spec, params, ids):
    """(the program's loss and gradient, the reference's)."""
    batch = {"input_ids": ids, "mask": jnp.array([1.0, 1.0, 0.0])}
    with HIGHEST:
        got = jax.jit(jax.value_and_grad(
            _program(OuroLM(cfg), ids, batch["mask"])))(params)
        want = jax.jit(jax.value_and_grad(
            lambda p: ref.client_loss(p, batch, spec)))(params)
    return got, want


# --- program against reference ------------------------------------------------

@pytest.mark.parametrize("steps", [4, 1, 3])
def test_loss_and_gradient_match_the_reference(steps):
    """One client's loss and every gradient leaf, as published (4
    steps) and with the loop shorter: at 1 the exit distribution is the
    one step's certainty and the gate has no gradient."""
    cfg, spec, params, ids = _setup(total_ut_steps=steps)
    (lp, gp), (lr, gr) = _against_reference(cfg, spec, params, ids)
    assert abs(float(lp) - float(lr)) <= 2e-6 * abs(float(lr))
    _close(gp, gr)
    gate = np.abs(np.asarray(gr["exit_gate"]["kernel"])).max()
    assert (gate == 0.0) if steps == 1 else (gate > 1e-6)
    for name in ("norm2", "norm4"):     # the sandwich's outer norms learn
        assert np.any(np.asarray(gp["stack"]["layer_1"][name]["scale"]))


def test_under_the_clients_vmap_the_gradient_is_the_references():
    """As the fused round applies it: the clients' ``vmap``, ``--remat``,
    one gradient of the summed losses; and the counts of ``STATS``."""
    cfg, spec, params, _ = _setup(remat=True)
    module = OuroLM(cfg)
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
    assert [name.replace(".", "_") for name, _ in COUNTERS] == list(STATS)
    assert stats["loop_steps"].tolist() == [4.0] * 3
    assert stats["loop_layer_applications"].tolist() == [8.0] * 3
    assert ((stats["loop_expected_steps"] > 1.0)
            & (stats["loop_expected_steps"] < 4.0)).all()
    assert ((stats["loop_exit_mass_last"] > 0.0)
            & (stats["loop_exit_mass_last"] < 1.0)).all()
    assert stats["attn_kernel_layers"].tolist() == [0.0] * 3
    # 2 sequences x 2 heads x 8 applications x 32 x 32 (dense, off the chip)
    assert stats["attn_pairs"].tolist() == [32.0 * 1024] * 3
    assert stats["attn_pairs_needed"].tolist() == [32.0 * 528] * 3


# --- the loop -----------------------------------------------------------------

def test_a_shared_weights_gradient_is_the_sum_over_the_steps():
    """An unrolled copy with a stack of its own a step, all four holding
    the same values: each stack's gradient is one step's addend, and
    the four add up to the shared stack's gradient; the addends differ,
    so no step is counted twice or left out."""
    cfg, _, params, ids = _setup()
    module = OuroLM(cfg)

    def untied(stacks, rest):
        x, hs = rest["embed"][ids], []
        for stack in stacks:
            x, h = Stack(cfg).apply({"params": stack}, x)
            hs.append(h)
        hs = jnp.stack(hs)
        gates = ExitGate(cfg).apply({"params": rest["exit_gate"]}, hs)
        return jnp.sum(exit_loss(cfg, hs, rest["lm_head"], gates, ids)[0])

    with HIGHEST:
        shared = jax.grad(lambda p: jnp.sum(
            causal_lm_loss(module, p, ids)[0]))(params)
        addends = jax.grad(untied)([params["stack"]] * 4, params)
    total = jax.tree_util.tree_map(lambda *g: sum(g), *addends)
    _close(total, shared["stack"])
    flat = [ravel_pytree(g)[0] for g in addends]
    for a in range(4):
        for b in range(a + 1, 4):
            assert float(jnp.linalg.norm(flat[a] - flat[b])) \
                > 0.05 * float(jnp.linalg.norm(flat[a]))


@pytest.mark.parametrize("remat", [False, True])
def test_the_unrolled_and_the_scanned_loop_agree(remat):
    """Same parameter tree, same loss, same gradient: the module's one
    ``nn.scan`` over the steps with the parameters broadcast, and the
    stack applied four times by hand on that tree."""
    cfg, _, params, ids = _setup(remat=remat)
    module = OuroLM(cfg)

    def unrolled(p):
        x, hs = p["embed"][ids], []
        for _ in range(cfg.total_ut_steps):
            x, h = Stack(cfg).apply({"params": p["stack"]}, x)
            hs.append(h)
        hs = jnp.stack(hs)
        gates = ExitGate(cfg).apply({"params": p["exit_gate"]}, hs)
        return jnp.sum(exit_loss(cfg, hs, p["lm_head"], gates, ids)[0])

    with HIGHEST:
        lu, gu = jax.jit(jax.value_and_grad(unrolled))(params)
        ls, gs = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
            causal_lm_loss(module, p, ids)[0])))(params)
    assert abs(float(lu) - float(ls)) <= 1e-6 * abs(float(lu))
    _close(gs, gu)


# --- the exit distribution and the gate ---------------------------------------

@pytest.mark.parametrize("steps", [1, 2, 4, 6])
def test_the_exit_distribution_sums_to_one_and_the_last_step_takes_the_rest(
        steps):
    g = 3.0 * jax.random.normal(jax.random.PRNGKey(steps), (steps, 5, 7))
    logp, p = exit_distribution(g)
    lam = np.asarray(jax.nn.sigmoid(g), np.float64)
    want = np.stack([(lam[t] if t < steps - 1 else 1.0)
                     * np.prod(1.0 - lam[:t], axis=0) for t in range(steps)])
    np.testing.assert_allclose(np.asarray(p), want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p[-1]),
                               1.0 - np.asarray(p[:-1]).sum(0), atol=2e-7)
    np.testing.assert_allclose(np.exp(np.asarray(logp)), np.asarray(p),
                               rtol=1e-5)
    # its own logit moves nothing: the last step takes what is left
    _, again = exit_distribution(g.at[-1].add(5.0))
    assert np.array_equal(np.asarray(again), np.asarray(p))


def test_with_equal_step_losses_and_no_entropy_term_the_gate_has_no_gradient():
    """sum_t p_t l_t = l where every step predicts alike, whatever p
    is: the gate's gradient is what the entropy term alone gives."""
    cfg, _, params, ids = _setup()
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 32, cfg.hidden_size))
    hs = jnp.stack([h] * 4)

    def loss(gate, beta):
        gates = ExitGate(cfg).apply({"params": gate}, hs)
        return jnp.sum(exit_loss(
            dataclasses.replace(cfg, entropy_beta=beta), hs,
            params["lm_head"], gates, ids)[0])

    with HIGHEST:
        none = jax.grad(loss)(params["exit_gate"], 0.0)
        some = jax.grad(loss)(params["exit_gate"], 0.1)
    assert float(jnp.abs(ravel_pytree(none)[0]).max()) <= 1e-6
    assert float(jnp.abs(ravel_pytree(some)[0]).max()) >= 1e-3


# --- the head's position weights ----------------------------------------------

def _head_inputs(E=3, Tm=21, C=16, V=50):
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    return (jax.random.normal(k[0], (E, Tm, C)),
            0.3 * jax.random.normal(k[1], (V, C)),
            jax.random.randint(k[2], (E, Tm), 0, V),
            jax.random.uniform(k[3], (E, Tm)))


def test_the_weighted_head_is_the_full_logits_weighted_sum():
    """Values and the three gradients (states, table, weights), with
    chunks that do not divide the positions."""
    h, w, labels, pw = _head_inputs()

    def chunked(h, w, pw):
        sn, sv = gpt2.lm_nll_sums_chunked(
            h, w, labels, jnp.float32, ignore_index=None,
            tokens_per_chunk=24, weights=pw)
        return jnp.sum(sn * jnp.arange(1.0, 4.0)), (sn, sv)

    def full(h, w, pw):
        nll, _ = gpt2.token_nll(jnp.einsum("etc,vc->etv", h, w), labels)
        sn = jnp.sum(nll * pw, axis=-1)
        return jnp.sum(sn * jnp.arange(1.0, 4.0)), sn

    with HIGHEST:
        (_, (sn, sv)), got = jax.value_and_grad(
            chunked, argnums=(0, 1, 2), has_aux=True)(h, w, pw)
        (_, want_sn), want = jax.value_and_grad(
            full, argnums=(0, 1, 2), has_aux=True)(h, w, pw)
    np.testing.assert_allclose(np.asarray(sn), np.asarray(want_sn),
                               rtol=2e-6)
    assert np.asarray(sv).tolist() == [21.0] * 3
    for g, wnt in zip(got, want):
        assert _rel(g, wnt) <= 2e-6
    with pytest.raises(ValueError, match="ignore_index=None"):
        gpt2.lm_nll_sums_chunked(h, w, labels, jnp.float32, weights=pw)


def _dense_nll_sums_before(h, wte, labels, dtype, tokens_per_chunk):
    """``models/gpt2.py _dense_nll_sums`` as it stood before it took
    weights, kept here as the value an unweighted caller is held to."""
    E, Tm, C = h.shape
    pad_label = -1
    tc = max(1, min(Tm, tokens_per_chunk // max(E, 1)))
    num_chunks = -(-Tm // tc)
    pad = num_chunks * tc - Tm
    with jax.named_scope("lm_head"):
        hp = jnp.pad(h.astype(dtype), ((0, 0), (0, pad), (0, 0)))
        lp = jnp.pad(labels, ((0, 0), (0, pad)),
                     constant_values=pad_label)
        wte_c = wte.astype(dtype)

    @jax.checkpoint
    def chunk_sums(hc, lc, w):
        logits = jnp.einsum("etc,vc->etv", hc, w,
                            preferred_element_type=jnp.float32)
        nll, valid = gpt2.token_nll(logits, lc, pad_label)
        return jnp.sum(nll * valid, -1), jnp.sum(valid, -1)

    def body(carry, i):
        sn, sv = carry
        hc = jax.lax.dynamic_slice_in_dim(hp, i * tc, tc, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(lp, i * tc, tc, axis=1)
        n, v = chunk_sums(hc, lc, wte_c)
        return (sn + n, sv + v), None

    init = (jnp.sum(hp[:, :, 0] * 0.0, axis=1, dtype=jnp.float32),
            jnp.sum(lp * 0, axis=1).astype(jnp.float32))
    with jax.named_scope("lm_head"):
        (sn, sv), _ = jax.lax.scan(
            body, init, jnp.arange(num_chunks, dtype=jnp.int32))
    return sn, sv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_an_unweighted_head_is_the_program_it_was(dtype):
    """With no weights: the same values and gradients to the bit, and
    the same program (the jaxprs, forward and backward, are equal as
    text); weights of 1 give the same bits too."""
    h, w, labels, _ = _head_inputs()

    def scalar(fn):
        return lambda h, w: jnp.sum(fn(h, w)[0] * jnp.arange(1.0, 4.0))

    now = lambda h, w: gpt2._dense_nll_sums(         # noqa: E731
        h, w, labels, dtype, 24)
    before = lambda h, w: _dense_nll_sums_before(    # noqa: E731
        h, w, labels, dtype, 24)
    ones = lambda h, w: gpt2._dense_nll_sums(        # noqa: E731
        h, w, labels, dtype, 24, jnp.ones(labels.shape))
    for fn in (now, ones):
        for a, b in zip(jax.jit(fn)(h, w), jax.jit(before)(h, w)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.jit(jax.grad(scalar(fn), (0, 1)))(h, w),
                        jax.jit(jax.grad(scalar(before), (0, 1)))(h, w)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(now)(h, w)) \
        == str(jax.make_jaxpr(before)(h, w))
    assert str(jax.make_jaxpr(jax.grad(scalar(now), (0, 1)))(h, w)) \
        == str(jax.make_jaxpr(jax.grad(scalar(before), (0, 1)))(h, w))


# --- the configuration --------------------------------------------------------

def test_from_hf_reads_the_published_config():
    cfg = OuroConfig.from_hf(CATALOG)
    assert cfg == OuroConfig()
    assert cfg.num_hidden_layers == 48 and cfg.total_ut_steps == 4
    assert cfg.layer_types == ("full_attention",) * 48


def test_the_cells_config_is_the_published_one_cut_in_depth_alone():
    """Every catalog key under its own name, unchanged but for
    ``reduced``; the ``published`` block holds what was cut; d from the
    shapes of the module is the file's ``num_parameters``."""
    blob = _json("configs", CONFIG)
    assert blob["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in CATALOG.items():
        if key in blob["reduced"]:
            assert blob["published"][key] == value
        else:
            assert blob[key] == value, key
    assert blob["num_hidden_layers"] == len(blob["layer_types"]) == 8
    assert sorted(k[0] for k in blob["assumed"] if k[1] == "_") \
        == list("abcdefg")
    cfg = OuroConfig.from_hf(blob)
    assert cfg == dataclasses.replace(
        OuroConfig(), layer_types=("full_attention",) * 8)
    shapes = jax.eval_shape(lambda: OuroLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    d = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert d == blob["num_parameters"] == 612438017
    ref_shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), cfg.reference_spec()))
    assert jax.tree_util.tree_map(lambda x: x.shape, ref_shapes) \
        == jax.tree_util.tree_map(lambda x: x.shape, shapes)
    cell = _json("workloads", CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert (cell["clients_per_round"], cell["local_batch_size"],
            cell["sequence_length"]) == (2, 1, 2048)
    assert cell["data"]["vocab_size"] == blob["vocab_size"]
    # 50.3 TFLOP of products and 3.3 of attention a round: every weight
    # four times, 32 layer applications
    flops = ref.train_flops_per_round(cfg.reference_spec(), cell)
    assert flops == 6 * 4 * (8 * 51380224 + 100663296) * 4096 \
        + 12 * 128 * 16 * 32 * (2048 * 2049 // 2) * 2
    assert 53.5e12 < flops < 53.7e12
    z = ref._sizes(cfg.reference_spec())
    assert len(z["windows"]) == 32 and not any(z["windows"])


@pytest.mark.parametrize("key,value,match", [
    ("layer_types", ["full_attention"] * 47 + ["sliding_attention"],
     "full_attention"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("num_hidden_layers", 47, "num_hidden_layers"),
    ("model_type", "llama", "model_type"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act")])
def test_from_hf_refuses_what_is_not_built(key, value, match):
    with pytest.raises(ValueError, match=match):
        OuroConfig.from_hf(dict(CATALOG, **{key: value}))


# --- the planted faults, by value ---------------------------------------------

@pytest.mark.parametrize("fault", [f.__name__ for f in faults.FAULTS])
def test_a_planted_fault_moves_the_first_gradient(fault, monkeypatch):
    """Each fault of ``benchmark/tests/ouro_faults.py`` applied to the
    program alone: in float32 the sound program is the reference to
    2e-5 of the gradient; with the fault it is not, by far."""
    cfg, spec, params, ids = _setup()
    module = OuroLM(cfg)
    for name in ("Stack", "Block", "exit_distribution"):   # put back after
        monkeypatch.setattr(ouro, name, getattr(ouro, name))
    getattr(faults, fault)(types.SimpleNamespace(
        model=types.SimpleNamespace(module=module)))
    batch = {"input_ids": ids, "mask": jnp.array([1.0, 1.0, 0.0])}
    with HIGHEST:
        gp = jax.grad(_program(module, ids, batch["mask"]))(params)
        gr = jax.grad(lambda p: ref.client_loss(p, batch, spec))(params)
    assert _rel(gp, gr) > 0.02, fault


# --- the benchmark's new readers, on handmade records --------------------------

def test_the_new_readers_on_handmade_records():
    """``round.loop_ms`` and ``round.exit_ms`` read nothing without a
    trace; ``models.loop_expected_steps`` reads the counter off the
    untraced records and nothing where no record carries it (the
    parent's program). None of them imports the program."""
    import importlib.util
    import sys
    sys.path.insert(0, ROOT)
    try:
        readers = {}
        for name in ("round.loop_ms", "round.exit_ms",
                     "models.loop_expected_steps"):
            path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
            with open(path) as f:
                assert "commefficient_tpu" not in f.read()
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + name.replace(".", "_"), path)
            readers[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(readers[name])
    finally:
        sys.path.remove(ROOT)
    for name in ("round.loop_ms", "round.exit_ms"):
        assert readers[name].read({"trace_dir": None}) is None
    ctx = {"records": [{"kind": "round", "round": r, "counters": {}}
                       for r in range(8)],
           "window": {"first": 3, "first_traced": 7}}
    read = readers["models.loop_expected_steps"].read
    assert read(ctx) is None
    for r, value in ((3, 1.5), (4, 2.5), (6, 9.0)):   # 6 is traced
        ctx["records"][r]["counters"] = {
            "loop.expected_steps": value, "loop.steps": 4.0,
            "loop.layer_applications": 32.0, "loop.exit_mass_last": 0.1}
    ctx.pop("_untraced")
    assert read(ctx) == 2.0
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(readers) <= mine
    for m in manifest["per_layer"]:
        if m["name"] in readers:
            assert m["workloads"] == [CELL]


# --- the trainer, end to end --------------------------------------------------

def test_the_trainer_trains_it_through_fedmodel(tmp_path):
    """``--model OuroLM --dataset_name TOKENS --mode sketch --remat``
    through ``FedModel`` and the jitted rounds: finite losses, and the
    round records carry the model's counters."""
    from commefficient_tpu.train import gpt2_train
    assert "OuroLM" in gpt2_train.CAUSAL_LMS
    out = gpt2_train.run(
        ["--test", "--model", "OuroLM", "--dataset_name", "TOKENS",
         "--dataset_dir", str(tmp_path / "tokens"), "--model_checkpoint",
         str(tmp_path), "--mode", "sketch", "--error_type", "virtual",
         "--local_momentum", "0", "--virtual_momentum", "0.9",
         "--num_workers", "4", "--local_batch_size", "2",
         "--num_devices", "1", "--num_epochs", "1", "--remat",
         "--ledger", str(tmp_path / "ledger.jsonl")])
    row = out.results[0]
    assert np.isfinite(row["train_loss"]) and np.isfinite(row["val_nll"])
    with open(tmp_path / "ledger.jsonl") as f:
        recs = [r for r in map(json.loads, f) if r.get("kind") == "round"]
    assert recs
    for c in (r["counters"] for r in recs):
        assert c["loop.steps"] == 4 and c["loop.layer_applications"] == 8
        assert 1.0 < c["loop.expected_steps"] < 4.0
        assert 0.0 < c["loop.exit_mass_last"] < 1.0
        assert c["attn.kernel_layers"] == 0         # off the chip
        # 4 clients x 2 sequences x 2 heads x 8 applications x ...
        assert c["attn.pairs"] == 4 * 32 * 1024
        assert c["attn.pairs_needed"] == 4 * 32 * 528


def test_a_config_json_of_another_model_type_is_refused(tmp_path):
    from commefficient_tpu.train import gpt2_train
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": "smallthinker"}, f)
    with pytest.raises(ValueError) as err:
        gpt2_train.run(
            ["--test", "--model", "OuroLM", "--dataset_name", "TOKENS",
             "--dataset_dir", str(tmp_path / "tokens"),
             "--model_checkpoint", str(tmp_path), "--mode", "sketch",
             "--num_workers", "4", "--local_batch_size", "2",
             "--num_devices", "1", "--num_epochs", "1"])
    assert "OuroLM" in str(err.value) and "smallthinker" in str(err.value)

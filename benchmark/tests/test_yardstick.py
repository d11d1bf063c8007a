"""The yardstick held to what it copies or restates: the trace
reduction, the count sketch's hash, the FLOP functions, the peaks, and
each plain reference against the program's flax module in float32."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT
from benchmark import run as harness
from benchmark.lib import fetchsgd_ref as fr
from benchmark.lib import peaks, tracelib
from benchmark.lib.kernelbench import least_seconds


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    ROOT, "tests", "fixtures", "*.trace.json.gz"))), ids=os.path.basename)
def test_trace_reduction_is_the_programs(path):
    from commefficient_tpu.telemetry import trace
    events = trace.load_trace_events(path)
    assert tracelib.load_trace_events(path) == events
    assert tracelib.attribute_rounds(events) == trace.attribute_rounds(events)
    assert tracelib.attribute_rounds(events)


@pytest.mark.parametrize("d,c,r,seed,rot_lanes", [
    (107, 10, 1, 5, 0), (5000, 512, 5, 21, 0), (70000, 8192, 5, 7, 1024),
    (5000, 512, 5, 2147483000, 0)])
def test_restated_sketch_is_the_programs(d, c, r, seed, rot_lanes):
    from commefficient_tpu.ops.sketch import CountSketch
    cs = CountSketch(d=d, c=c, r=r, seed=seed, backend="xla",
                     rot_lanes=rot_lanes)
    sp = fr.SketchSpec(d=d, c=c, r=r, seed=seed, rot_lanes=rot_lanes)
    v = jax.random.normal(jax.random.PRNGKey(0), (d,))
    table = cs.sketch(v)
    np.testing.assert_allclose(fr.sketch(sp, v), table, rtol=1e-6,
                               atol=1e-5)
    np.testing.assert_array_equal(fr.estimates(sp, table),
                                  cs.estimates(table))
    idx = jnp.arange(0, d, 7)
    np.testing.assert_allclose(fr.sketch_sparse(sp, idx, v[idx]),
                               cs.sketch_sparse(idx, v[idx]), atol=1e-6)


def test_flops_and_peaks():
    res = harness.load("reference", "resnet9-cifar10")
    cfg = harness.read_json(ROOT, "benchmark", "configs",
                            "resnet9-cifar10.json")
    one = res.train_flops_per_round(
        cfg, {"clients_per_round": 1, "local_batch_size": 1})
    assert one == pytest.approx(2.27e9, rel=0.01)
    gpt = harness.load("reference", "gpt2-124m-personachat")
    gcfg = harness.read_json(ROOT, "benchmark", "configs",
                             "gpt2-124m-personachat.json")
    cell = {"clients_per_round": 8, "local_batch_size": 8,
            "num_candidates": 2, "sequence_length": 256}
    tokens = 8 * 8 * 2 * 256
    n_matmul = 12 * 12 * 768 * 768 + 50262 * 768
    assert gpt.train_flops_per_round(gcfg, cell) == tokens * (
        6 * n_matmul + 6 * 12 * 256 * 768)
    # within 3% of 6 * N * tokens at N = 124.4M
    assert gpt.train_flops_per_round(gcfg, cell) == pytest.approx(
        6 * 124.44e6 * tokens, rel=0.03)
    p = peaks.peaks_of("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
    t, bound = least_seconds("sketch", 6_584_000, 524288, 5, p)
    assert bound == "hbm" and t == pytest.approx(36.82e6 / 819e9, rel=1e-3)


def test_resnet9_reference_is_the_module_in_f32():
    from commefficient_tpu.models import get_model
    ref = harness.load("reference", "resnet9-cifar10")
    spec = {"channels": {"prep": 4, "layer1": 8, "layer2": 8, "layer3": 16},
            "initial_channels": 3, "num_classes": 10}
    params = ref.init_params(jax.random.PRNGKey(3), spec)
    module = get_model("ResNet9")(num_classes=10, channels=spec["channels"])
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        want = module.apply({"params": params}, x)
        got = ref.logits(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_gpt2_reference_is_the_module_in_f32():
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.train.gpt2_train import make_compute_loss_train
    from commefficient_tpu.config import Config
    ref = harness.load("reference", "gpt2-124m-personachat")
    spec = {"n_layer": 2, "n_embd": 32, "n_head": 2, "n_positions": 64,
            "vocab_size": 300, "lm_coef": 1.0, "mc_coef": 1.0}
    params = ref.init_params(jax.random.PRNGKey(5), spec)
    module = GPT2DoubleHeads(GPT2Config(
        vocab_size=300, n_positions=64, n_embd=32, n_layer=2, n_head=2))
    B, N, T = 3, 2, 16
    k = jax.random.split(jax.random.PRNGKey(6), 4)
    ids = jax.random.randint(k[0], (B, N, T), 0, 300)
    labels = jnp.where(jax.random.uniform(k[1], (B, N, T)) < 0.5, ids, -1)
    batch = {"input_ids": ids, "token_type_ids": ids[..., ::-1],
             "lm_labels": labels,
             "mc_token_ids": jax.random.randint(k[2], (B, N), 0, T),
             "mc_labels": jnp.array([0, 1, 1]),
             "mask": jnp.array([1.0, 1.0, 0.0])}
    args = Config(lm_coef=1.0, mc_coef=1.0)
    loss = make_compute_loss_train(module, args)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p: loss(p, batch, args)[0])(params)
        got, g_got = jax.value_and_grad(
            lambda p: ref.client_loss(p, batch, spec))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_kernel_readers_read_nothing_where_the_mode_has_no_sketch():
    from types import SimpleNamespace
    from benchmark.lib.kernelbench import roofline_share
    ctx = {"run": SimpleNamespace(args=SimpleNamespace(mode="uncompressed"))}
    assert roofline_share(ctx, "sketch") is None
    assert roofline_share(ctx, "estimates") is None

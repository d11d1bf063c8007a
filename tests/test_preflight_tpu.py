"""Pre-flight for the chip: everything that must lower for a TPU does.

Traced on the CPU mesh and lowered with
``lowering_platforms=("tpu",)`` — no execution, no Mosaic compile — so
trace- and lowering-time refusals (shard_map typing of kernel outputs,
Mosaic kernels in a partitioned multi-device jit, casts Mosaic lacks)
are caught here, without a chip. What this cannot see — Mosaic
compilation, VMEM, execution, placement — is ``chip_smoke.py``'s job.

The sketch backend is pinned to ``pallas`` (on the CPU ``auto``
resolves to the XLA twin and none of this would be exercised).
"""

import jax
import jax.numpy as jnp
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates,
                                           build_client_round,
                                           build_server_round)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.ops.sketch import CountSketch
from commefficient_tpu.parallel.mesh import (client_sharding, make_mesh,
                                             replicated)

D_RESNET9, D_GPT2 = 6_584_000, 124_439_808   # flagship grad sizes
D_JOYAI = 376_091_904   # the joyai-llm-flash-ep32 cut (718 chunks of COLS)
D_NEMOTRON = 700_865_520  # nemotron3-super-ep64-tp8: r * m = 6,685
COLS, ROWS, K = 524288, 5, 50000


def sds(shape, dtype=jnp.float32, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def tpu_kernels(fn, *args) -> int:
    """Number of Mosaic kernels in ``fn`` lowered for a TPU."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text().count("tpu_custom_call")


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setattr(CountSketch, "_resolve_backend",
                        lambda self: "pallas")


def _loss(p, b):
    pred = b["x"] @ p[:16]
    n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
    loss = jnp.sum((pred - b["y"]) ** 2 * b["mask"]) / n
    return loss, (loss,)


def _cfg(mode, W=8, **kw):
    return Config(mode=mode, error_type="virtual", local_momentum=0.0,
                  virtual_momentum=0.9, weight_decay=5e-4, num_workers=W,
                  local_batch_size=4, k=K, num_rows=ROWS, num_cols=COLS,
                  grad_size=D_RESNET9, seed=21, **kw)


def _round_kernels(cfg, n_dev, **build_kw):
    """(client, server) Mosaic-kernel counts of the rounds FedModel /
    FedOptimizer would build for ``cfg`` on an ``n_dev`` clients mesh,
    with the placements they use."""
    mesh = make_mesh(jax.devices()[:n_dev])
    W, B, rep = cfg.num_workers, 4, replicated(mesh)
    # shard_batch's rule: client-sharded when the axis divides W
    bsh = client_sharding(mesh) if W % n_dev == 0 else rep
    batch = {"x": sds((W, B, 16), sharding=bsh),
             "y": sds((W, B), sharding=bsh),
             "mask": sds((W, B), sharding=bsh)}
    ps = sds((D_RESNET9,), sharding=rep)
    table = sds(cfg.transmit_shape, sharding=rep)
    client = tpu_kernels(
        build_client_round(cfg, _loss, B, mesh=mesh, **build_kw),
        ps, ClientStates(None, None, None), batch,
        sds((W,), jnp.int32, rep), sds((2,), jnp.uint32), 1.0)
    server = tpu_kernels(
        build_server_round(cfg, mesh=mesh),
        ps, ServerState(table, table), table, sds(()))
    return client, server


@pytest.mark.parametrize("n_dev", [1, 4])
def test_sketch_rounds_lower_for_tpu(pallas, devices, n_dev):
    client, server = _round_kernels(_cfg("sketch"), n_dev)
    assert client == 1          # the sketch emit
    assert server == 3          # estimates, take-mask, re-sketch


@pytest.mark.parametrize("n_dev", [1, 4])
def test_true_topk_rounds_lower_for_tpu(pallas, devices, n_dev):
    client, server = _round_kernels(_cfg("true_topk"), n_dev)
    assert client == 0          # dense transmit: no kernel to hold
    assert server == 1          # take-mask


@pytest.mark.parametrize("kw,build_kw", [
    (dict(W=6), {}),                        # W the client axis can't divide
    (dict(max_grad_norm=1.0), {}),          # per-client sketch in the vmap
    (dict(microbatch_size=2), {}),          # per-client, sketch-late
    (dict(sketch_dtype="int8"), {}),
    ({}, dict(probes=True, probe_recovery=True)),
])
def test_other_sketch_branches_lower_on_four_devices(pallas, devices, kw,
                                                     build_kw):
    """The client-round branches beside the fused shard_map one: each
    holds a kernel and none may sit in a partitioned jit."""
    client, server = _round_kernels(_cfg("sketch", **kw), 4, **build_kw)
    assert client >= 1 and server == 3


@pytest.mark.parametrize("d", [D_RESNET9, D_GPT2, D_JOYAI, D_NEMOTRON])
@pytest.mark.parametrize("rot_lanes", [0, 1024])
def test_sketch_kernels_lower_at_flagship_geometry(d, rot_lanes):
    from commefficient_tpu.ops.sketch_pallas import supported
    # "auto" must pick the kernels at every benchmark geometry: their
    # XLA twin materialises (r, d) float32, 7.5 GB at the largest
    assert supported(d, COLS, ROWS)
    cs = CountSketch(d=d, c=COLS, r=ROWS, seed=7, backend="pallas",
                     rot_lanes=rot_lanes)
    assert tpu_kernels(cs.sketch, sds((d,))) == 1
    assert tpu_kernels(cs.estimates, sds((ROWS, COLS))) == 1


@pytest.mark.parametrize("rot_lanes", [0, 1024])
def test_quantized_emit_lowers_fused_for_int8_unfused_for_fp8(rot_lanes):
    cs = CountSketch(d=D_RESNET9, c=COLS, r=ROWS, seed=7,
                     backend="pallas", rot_lanes=rot_lanes)
    for wire in ("int8", "fp8"):
        # one kernel either way: the fused emit for int8; for fp8 the
        # plain sketch kernel, quantized by XLA ops (Mosaic cannot cast
        # f16 -> float8_e4m3fn, see CountSketch.sketch_quantized)
        assert tpu_kernels(lambda v, w=wire: cs.sketch_quantized(v, w),
                           sds((D_RESNET9,))) == 1


@pytest.mark.parametrize("d", [D_RESNET9, D_GPT2, D_JOYAI, D_NEMOTRON])
def test_take_mask_kernel_lowers(d):
    from commefficient_tpu.ops.topk import threshold_topk_mask_1d
    assert tpu_kernels(lambda sq: threshold_topk_mask_1d(sq, K),
                       sds((d,))) == 1


@pytest.mark.parametrize("d,masks", [
    (D_GPT2, 2), (D_JOYAI, 2), (D_NEMOTRON, 2),     # two-level
    (40_000_000, 1),                                # flat, sparse regime
])
def test_index_select_lowers_in_the_form_its_shapes_pick(d, masks):
    """The exact index selection behind ``unsketch`` at the LM cells'
    sizes: the two-level form holds two small take-mask kernels (over
    the block maxima, over the k blocks' candidates), the flat form one
    over all of d."""
    from commefficient_tpu.ops.topk import threshold_topk_indices
    pad = -(-d // COLS) * COLS
    assert tpu_kernels(
        lambda est: threshold_topk_indices(est, K, key=jax.lax.square),
        sds((pad,))) == masks


def test_flce_kernels_lower_at_gpt2_head(monkeypatch):
    from commefficient_tpu.ops import flce_pallas
    # the fused path asks the default backend; this is a cross-lowering
    monkeypatch.setattr(flce_pallas.jax, "default_backend",
                        lambda: "tpu")
    e, tm, c, v = 4, 256, 768, 50262
    assert flce_pallas.fused_fallback_reason(
        e, tm, c, v, jnp.bfloat16) is None
    lab = sds((e, tm), jnp.int32)

    def mean_nll(h, w, lab):
        sn, sv = flce_pallas.lm_nll_sums_fused(h, w, lab, jnp.bfloat16)
        return jnp.sum(sn / jnp.maximum(sv, 1.0))

    assert tpu_kernels(jax.value_and_grad(mean_nll, (0, 1)),
                       sds((e, tm, c)), sds((v, c)), lab) == 2


def test_mla_kernel_path_lowers_at_the_joyai_cells_shape(monkeypatch):
    """JoyAI's latent attention as the chip builds it (``models/joyai.py
    MLA`` with the platform a TPU: q and k 192 wide, v 128, a group of
    one query head), one layer's forward and backward under the clients
    ``vmap`` and ``jax.checkpoint`` at the cell's shape (8 clients x 4
    sequences x 1,024, bf16): the library's three kernels lower, and
    the float32 (heads, T, T) scores are in no value of the program."""
    import dataclasses
    import json
    import os
    import re
    from commefficient_tpu.models import joyai, mixers
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai-llm-flash-ep32.json")) as f:
        cfg = dataclasses.replace(joyai.JoyAIConfig.from_hf(json.load(f)),
                                  dtype=jnp.bfloat16)
    monkeypatch.setattr(mixers, "_platform", lambda: "tpu")
    plan = joyai.mla_plan(cfg, 4, 1024)
    assert plan.kernel == "splash" and plan.block in mixers.ATTN_KERNEL_BLOCKS
    module = joyai.MLA(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8, cfg.hidden_size), jnp.bfloat16))["params"])
    x = sds((8, 4, 1024, cfg.hidden_size), jnp.bfloat16)

    def loss(p, x):
        layer = jax.checkpoint(lambda x: module.apply({"params": p}, x))
        return jnp.sum(jnp.sin(jax.vmap(layer)(x).astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == [
        "splash_mqa_dkv_no_residuals", "splash_mqa_dq_no_residuals",
        "splash_mqa_fwd_residuals", "splash_mqa_fwd_residuals"]
    assert not re.search(r"tensor<[0-9x]*1024x1024xf32>", text)

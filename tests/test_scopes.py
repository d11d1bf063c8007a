"""Names on the device: every ``jax.named_scope`` of the round programs
is in their debug-info lowering, in all five modes, and every
``pl.pallas_call`` carries its ``name=`` into the TPU lowering (the
trace's kernel events are named after it). Scopes are metadata: that
the lowered text without debug info is the parent's is held by
``tests/test_audit.py`` against ``audit_baseline.json``, whose program
fingerprints this change leaves as they were."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.core.rounds import (ClientStates,
                                           build_client_round,
                                           build_server_round)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.ops.sketch import CountSketch

D, W, B = 64, 4, 2

MODES = {
    # mode: (config, scopes of the client program, of the server's)
    "sketch": (dict(error_type="virtual", virtual_momentum=0.9),
               {"fwd_bwd", "compress"},
               {"estimates", "select", "resketch", "apply"}),
    "true_topk": (dict(error_type="virtual", virtual_momentum=0.9),
                  {"fwd_bwd"}, {"select", "apply"}),
    "local_topk": (dict(error_type="local", local_momentum=0.9),
                   {"fwd_bwd", "compress"}, {"apply"}),
    "fedavg": (dict(error_type="none", virtual_momentum=0.9,
                    local_batch_size=-1), {"fwd_bwd"}, {"apply"}),
    "uncompressed": (dict(error_type="none", virtual_momentum=0.9),
                     {"fwd_bwd"}, {"apply"}),
}
ALL = {"fwd_bwd", "lm_head", "compress", "estimates", "select",
       "resketch", "apply"}


def _loss(p, b):
    pred = b["x"] @ p[:16]
    n = jnp.maximum(jnp.sum(b["mask"]), 1.0)
    loss = jnp.sum((pred - b["y"]) ** 2 * b["mask"]) / n
    return loss, (loss,)


def _cfg(mode, **kw):
    base = dict(mode=mode, local_momentum=0.0, virtual_momentum=0.0,
                weight_decay=5e-4, error_type="none", num_workers=W,
                k=8, num_rows=3, num_cols=16, num_blocks=1,
                local_batch_size=B, grad_size=D, seed=21)
    base.update(MODES[mode][0])
    base.update(kw)
    return Config(**base)


def _programs(cfg):
    """(client, server) lowerings of the rounds ``cfg`` builds."""
    batch = {"x": jnp.zeros((W, B, 16)), "y": jnp.zeros((W, B)),
             "mask": jnp.ones((W, B))}
    ps = jnp.zeros((D,))
    client = jax.jit(build_client_round(cfg, _loss, B)).lower(
        ps, ClientStates.init(cfg, 8, ps), batch,
        jnp.arange(W, dtype=jnp.int32), jax.random.PRNGKey(0),
        jnp.float32(0.1))
    agg = jnp.zeros(cfg.transmit_shape)
    cs = ClientStates.init(cfg, 8, ps)
    server = jax.jit(build_server_round(cfg)).lower(
        ps, ServerState.init(cfg), agg, jnp.float32(0.1),
        cs.velocities, jnp.arange(W, dtype=jnp.int32),
        jax.random.PRNGKey(1))
    return client, server


def scopes_in(lowered) -> set:
    """The program's scope names that are a component of some
    operation's name in the debug-info text (bare, or wrapped by the
    transformations that ran over it: ``transpose(jvp(lm_head))``)."""
    text = lowered.as_text(debug_info=True)
    names = " ".join(re.findall(r'loc\("([^"]*)"', text))
    return {s for s in ALL
            if re.search(r"(?<![\w.])%s(?![\w.])" % s, names)}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_scope_is_in_the_debug_lowering(mode):
    _, want_client, want_server = MODES[mode]
    client, server = _programs(_cfg(mode))
    assert scopes_in(client) == want_client
    assert scopes_in(server) == want_server
    # metadata only: without debug info no scope is in the text
    for low in (client, server):
        plain = low.as_text()
        assert not any(re.search(r"(?<![\w.])%s(?![\w.])" % s, plain)
                       for s in ALL - {"select"})


@pytest.mark.parametrize("kw", [dict(max_grad_norm=1.0),
                                dict(microbatch_size=1),
                                dict(client_chunk=2, microbatch_size=1)])
def test_the_other_sketch_branches_carry_the_client_scopes(kw):
    """Per-client sketches, the sketch after the local sum, and the
    chunked scan: the branches beside the fused one."""
    client, _ = _programs(_cfg("sketch", **kw))
    assert scopes_in(client) == {"fwd_bwd", "compress"}


def test_lm_head_scope_sits_inside_fwd_bwd():
    from commefficient_tpu.models.gpt2 import (GPT2Config,
                                               GPT2DoubleHeads,
                                               lm_nll_sums_chunked)
    cfg = GPT2Config.tiny()
    module = GPT2DoubleHeads(cfg)
    ids = jnp.zeros((2, 2, 8), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), ids,
                         jnp.zeros((2, 2), jnp.int32), ids)["params"]

    def loss(p):
        h, wte, _ = module.apply({"params": p}, ids,
                                 jnp.zeros((2, 2), jnp.int32), ids,
                                 return_hidden=True)
        sn, sv = lm_nll_sums_chunked(h[:, :-1], wte, ids.reshape(4, 8)[:, 1:],
                                     jnp.float32)
        return jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)

    def step(p):
        with jax.named_scope("fwd_bwd"):
            return jax.value_and_grad(loss)(p)

    text = jax.jit(step).lower(params).as_text(debug_info=True)
    # the operations' names (a function's own location, inside the
    # head's loops, starts at the scope it was traced in)
    names = [n for n in re.findall(r'loc\("([^"]*)"', text)
             if n.startswith("jit(step)")]
    head = [n for n in names if re.search(r"(?<![\w.])lm_head(?![\w.])", n)]
    assert head and all("fwd_bwd" in n for n in head)
    # forward and backward of the head are both named
    assert any("transpose(" in n for n in head)
    assert any("transpose(" not in n for n in head)
    # the logits path of the plain forward is named too
    logits = jax.jit(lambda p: module.apply(
        {"params": p}, ids, jnp.zeros((2, 2), jnp.int32), ids)[0]).lower(
        params).as_text(debug_info=True)
    assert re.search(r"(?<![\w.])lm_head(?![\w.])", logits)


@pytest.mark.parametrize("form", ["alone", "pooled", "per_client"])
def test_every_operation_of_the_compacting_head_is_under_lm_head(form):
    """``round.head_ms`` reads the ``lm_head`` scope: the partition,
    the gathers, both chunk loops with their products and the
    write-back, forward and in the custom VJP's backward (which
    inherits no ``transpose(jvp(lm_head))`` and opens the scope
    itself), all carry it, inside ``fwd_bwd``."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    h = jnp.ones((2, 3, 7, 8))
    w = jnp.ones((19, 8))
    lab = jnp.asarray(np.where(np.arange(42).reshape(2, 3, 7) % 5, -1, 3))

    def one(h, w, lab):
        sn, sv = lm_nll_sums_chunked(h, w, lab, jnp.bfloat16,
                                     ignore_index=-1, tokens_per_chunk=8)
        return jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)

    def loss(h, w, lab):
        if form == "alone":
            return one(h[0], w, lab[0])
        return jnp.sum(jax.vmap(
            lambda h, l: one(h, w, l),
            axis_name=SHARED_CLIENTS if form == "pooled" else None)(h, lab))

    def step(h, w, lab):
        with jax.named_scope("fwd_bwd"):
            return jax.value_and_grad(loss, (0, 1))(h, w, lab)

    # the compiled program's own metadata: what a trace's ``tf_op`` is
    hlo = jax.jit(step).lower(h, w, lab).compile().as_text()
    ops = [(m.group(1), n.group(1)) for m, n in (
        (re.search(r"[\])}] (sort|gather|scatter|dot|while)\(", line),
         re.search(r'op_name="([^"]*)"', line))
        for line in hlo.splitlines()) if m and n]
    held = re.compile(r"(?<![\w.])lm_head(?![\w.])").search
    assert {op for op, _ in ops} == {"sort", "gather", "scatter", "dot",
                                     "while"}
    assert all(held(name) and "fwd_bwd" in name for _, name in ops), [
        o for o in ops if not held(o[1])][:3]
    # the logits' product, forward and again in the backward, and the
    # backward's two: three of the four under a transposition
    dots = [name for op, name in ops if op == "dot"]
    assert len(dots) == 4
    assert sum("transpose(" in name for name in dots) == 3


# --- kernel names in the TPU lowering (tests/test_preflight_tpu.py's
# method: traced on the CPU, lowered for a TPU, nothing compiled) -------


def _tpu_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_names(text) -> list:
    """The ``name=`` of each Mosaic kernel, as the operation's name
    carries it (``.../sketch_pallas/pallas_call``, or wrapped:
    ``transpose(jvp(flce_bwd_pallas))/pallas_call``)."""
    return re.findall(r'(\w+)\)*/pallas_call', text)


@pytest.mark.parametrize("d", [6_584_000])
def test_sketch_kernels_are_named_in_the_tpu_lowering(d):
    cs = CountSketch(d=d, c=524288, r=5, seed=7, backend="pallas")
    text = _tpu_text(cs.sketch, _sds((d,)))
    assert text.count("tpu_custom_call") == 1
    assert set(_kernel_names(text)) == {"sketch_pallas"}
    text = _tpu_text(cs.estimates, _sds((5, 524288)))
    assert set(_kernel_names(text)) == {"estimates_pallas"}
    text = _tpu_text(lambda v: cs.sketch_quantized(v, "int8"), _sds((d,)))
    assert set(_kernel_names(text)) == {"sketch_quant_pallas"}


def test_take_mask_kernel_is_named_in_the_tpu_lowering():
    from commefficient_tpu.ops.topk import threshold_topk_mask_1d
    text = _tpu_text(lambda sq: threshold_topk_mask_1d(sq, 50000),
                     _sds((6_584_000,)))
    assert set(_kernel_names(text)) == {"take_mask_pallas"}


def test_flce_kernels_are_named_in_the_tpu_lowering():
    from commefficient_tpu.ops import flce_pallas as fl

    def loss(x, w, labels):
        lse, tok = fl.flce_lse_tok(x, w, labels, fl._BLOCK_M, fl._BLOCK_V,
                                   False)
        return jnp.sum(lse - tok)

    text = _tpu_text(jax.grad(loss, argnums=(0, 1)),
                     _sds((1024, 768), jnp.bfloat16),
                     _sds((50262, 768), jnp.bfloat16),
                     _sds((1024,), jnp.int32))
    assert set(_kernel_names(text)) == {"flce_fwd_pallas",
                                        "flce_bwd_pallas"}

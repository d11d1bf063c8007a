"""Nemotron-H's share (models/nemotron_h.py) against its plain float32
reference (benchmark/reference/nemotron3-super-ep64-tp8.py): each mixer
alone and the 11-layer pattern, the chunked scan against the recurrence
taken one position at a time, the shares adding up to the uncut layers,
the counters, the trainer end to end and FetchSGD rounds through
``FedModel``. The expert layer's shared code (models/moe.py) is held to
both expert forms. Tiny sizes, seeded weights, float32, CPU."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models import moe
from commefficient_tpu.models.nemotron_h import (STATS, Block, GQAttention,
                                                 LatentMoE, Mamba2Mixer,
                                                 NemotronHConfig,
                                                 NemotronHLM,
                                                 causal_lm_loss,
                                                 ssd_chunked)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "nemotron3-super-ep64-tp8"
CELL = "nemotron3s_fetchsgd_w8_t2048"
HIGHEST = jax.default_matmul_precision("highest")
SAME = lambda a: a  # noqa: E731  (the reference's float32 quantizer)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py"),
            "bench_ref_nemotron3")


def _rel(a, b):
    a, b = ravel_pytree(a)[0], ravel_pytree(b)[0]
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _close(got, want, rel=2e-5, leaf=1e-4):
    assert _rel(got, want) <= rel
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_flatten_with_path(want)[0]):
        assert float(jnp.abs(a - b).max()) <= leaf * max(
            float(jnp.abs(b).max()), 1e-3), jax.tree_util.keystr(path)


def _tiny(pattern=None):
    cfg = NemotronHConfig.tiny()
    if pattern is not None:
        cfg = dataclasses.replace(cfg, hybrid_override_pattern=pattern)
    return cfg, cfg.reference_spec()


# --- program against reference ------------------------------------------------

@pytest.mark.parametrize("pattern", ["M", "*", "E", "MEMEMEMEM*E"])
def test_loss_and_gradient_match_the_reference(pattern):
    """One client's loss and gradient, for a model of one mixer of each
    kind and for the whole period."""
    cfg, spec = _tiny(pattern)
    module = NemotronHLM(cfg)
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
    if "E" in pattern:
        name = f"layer_{pattern.index('E')}"
        for g in (gp, gr):
            assert not np.any(np.asarray(g[name]["mixer"]["router_bias"]))
            assert np.any(np.asarray(g[name]["mixer"]["router"]))


def _scan_case(T, S=2, H=4, G=2, P=8, N=8, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (S, T, H, P))
    delta = jax.nn.softplus(jax.random.normal(k[1], (S, T, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (S, T, G, N))
    C = jax.random.normal(k[4], (S, T, G, N))
    return x, delta, A, B, C


def _stepwise(x, delta, A, B, C):
    return jax.vmap(lambda x, d, b, c: ref.recurrence(x, d, A, b, c))(
        x, delta, B, C)


@pytest.mark.parametrize("T", [8, 16, 40, 13],
                         ids=["1chunk", "2chunks", "5chunks", "ragged"])
def test_chunked_scan_is_the_stepwise_recurrence(T):
    """Values and gradients, with T = 1, 2 and 5 chunks of 8 and one
    length that is no whole number of chunks."""
    args = _scan_case(T)

    def chunked(*a):
        return ssd_chunked(*a, 8, jnp.float32)[0]

    weight = jnp.cos(jnp.arange(T * 4 * 8, dtype=jnp.float32)
                     ).reshape(1, T, 4, 8)
    with HIGHEST:
        got, n = ssd_chunked(*args, 8, jnp.float32)
        want = _stepwise(*args)
        gp = jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
        gr = jax.grad(lambda *a: jnp.sum(_stepwise(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
    assert n == 2 * -(-T // 8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    _close(gp, gr, rel=2e-5, leaf=2e-4)


def test_chunked_scan_under_the_clients_vmap_and_checkpoint():
    """As ``core/rounds.py make_local_loss`` and ``--remat`` apply it:
    vmapped over clients, differentiated once, the block recomputed."""
    W, T = 3, 24
    cases = [_scan_case(T, seed=10 + w) for w in range(W)]
    x, delta, _, B, C = (jnp.stack(v) for v in zip(*cases))
    A = cases[0][2]

    def loss(fn):
        def one(x, d, b, c):
            return jnp.sum(jnp.sin(fn(x, d, A, b, c)))
        return lambda x, d, b, c: jnp.sum(jax.vmap(one)(x, d, b, c))

    chunked = jax.checkpoint(
        lambda *a: ssd_chunked(*a, 8, jnp.float32)[0])
    with HIGHEST:
        gp = jax.jit(jax.grad(loss(chunked), argnums=(0, 1, 2, 3)))(
            x, delta, B, C)
        gr = jax.jit(jax.grad(loss(_stepwise), argnums=(0, 1, 2, 3)))(
            x, delta, B, C)
    _close(gp, gr, rel=2e-5, leaf=2e-4)


def test_under_the_clients_vmap_the_gradient_is_the_references():
    cfg, spec = _tiny()
    cfg = dataclasses.replace(cfg, remat=True)
    module = NemotronHLM(cfg)
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
    assert not stats["dropped"].any()
    # 2 sequences x 2 chunks of 8 x 5 Mamba-2 layers a client
    assert stats["ssm_chunks"].tolist() == [20.0] * 4


# --- the shares add up to the uncut layers -------------------------------------

WHOLE = dict(NemotronHConfig.tiny().reference_spec(),
             hybrid_override_pattern="M*E", num_hidden_layers=3,
             mamba_num_heads=8, n_groups=4, num_attention_heads=8,
             num_key_value_heads=2, n_routed_experts=64, expert_offset=0)


def _whole_layer(i):
    p = ref.init_params(jax.random.PRNGKey(5), WHOLE)[f"layer_{i}"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 32))
    return p, x


def test_the_group_shares_add_up_to_the_uncut_mixer():
    """4 chips' shares of a Mamba-2 mixer, each one whole group with
    its 2 heads, give the reference's mixer of 8 heads in 4 groups."""
    p, x = _whole_layer(0)
    cfg = dataclasses.replace(NemotronHConfig.tiny(), mamba_num_heads=2,
                              n_groups=1)
    P, N, H, G = 8, 8, 8, 4
    inner, bc = H * P, G * N

    def cols(chip):
        heads = np.arange(2 * P) + chip * 2 * P
        state = np.arange(N) + chip * N
        return heads, np.concatenate([inner + heads,
                                      2 * inner + state,
                                      2 * inner + bc + state])

    with HIGHEST:
        want = ref._mamba(p, x, WHOLE, SAME)
        total = 0.0
        for chip in range(4):
            heads, xbc = cols(chip)
            h2 = np.arange(2) + 2 * chip
            share = {"in_proj": p["in_proj"][:, np.concatenate(
                         [heads, xbc, 2 * inner + 2 * bc + h2])],
                     "conv_w": p["conv_w"][:, xbc - inner],
                     "conv_b": p["conv_b"][xbc - inner],
                     "dt_bias": p["dt_bias"][h2], "A_log": p["A_log"][h2],
                     "D": p["D"][h2], "gate_norm": p["gate_norm"][heads],
                     "out_proj": p["out_proj"][heads]}
            y, n = Mamba2Mixer(cfg).apply({"params": share}, x)
            assert n == 2 * 2
            total = total + y
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_head_shares_add_up_to_the_uncut_attention():
    """4 chips' shares, 2 query heads each with the key/value head they
    share (two chips hold a copy of each), give the reference's
    attention of 8 query and 2 key/value heads."""
    p, x = _whole_layer(1)
    cfg = dataclasses.replace(NemotronHConfig.tiny(), num_attention_heads=2,
                              num_key_value_heads=1)
    D = 8
    with HIGHEST:
        want = ref._attention(p, x, WHOLE, SAME)
        total = 0.0
        for chip in range(4):
            q = slice(chip * 2 * D, (chip + 1) * 2 * D)
            kv = slice((chip // 2) * D, (chip // 2 + 1) * D)
            share = {"q": p["q"][:, q], "k": p["k"][:, kv],
                     "v": p["v"][:, kv], "o": p["o"][q]}
            total = total + GQAttention(cfg).apply({"params": share}, x)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """64 chips' expert shares, each through the linear ``latent_up``,
    the shared expert counted once, give what the reference computes
    with all the experts."""
    p, x = _whole_layer(2)
    cfg = dataclasses.replace(NemotronHConfig.tiny(), n_held_experts=1)
    with HIGHEST:
        want = ref._moe(p, x, WHOLE, SAME)
        shared = ref._relu2(x @ p["shared"]["w1"]) @ p["shared"]["w2"]
        total = shared
        for chip in range(64):
            share = dict(p, experts={k: v[chip:chip + 1]
                                     for k, v in p["experts"].items()})
            y, stats = LatentMoE(dataclasses.replace(
                cfg, expert_offset=chip)).apply({"params": share}, x)
            assert float(stats[2]) == 0.0
            total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# --- the expert layer's shared code, both forms --------------------------------

def _dense_experts(form, x, top, g, offset, w):
    """Every held expert on every token, weighted by its gate."""
    E = w[0].shape[0]
    held = offset + jnp.arange(E)
    gate = jnp.sum(jnp.where(top[:, :, None] == held[None, None, :],
                             g[:, :, None], 0.0), axis=1)
    if form in ("swiglu", "reglu"):
        act = jax.nn.silu if form == "swiglu" else jax.nn.relu
        h = act(jnp.einsum("nc,ecf->enf", x, w[0])) \
            * jnp.einsum("nc,ecf->enf", x, w[1])
    else:
        h = jnp.square(jax.nn.relu(jnp.einsum("nc,ecf->enf", x, w[0])))
    return jnp.einsum("ne,enc->nc", gate,
                      jnp.einsum("enf,efc->enc", h, w[-1]))


FORMS = [("swiglu", 8, 256, 8), ("relu2", 22, 512, 8),
         ("relu2", 6, 8, 8)]   # the last: every token on most held experts


@pytest.mark.parametrize("form,k,R,E", FORMS,
                         ids=["swiglu-8of256", "relu2-22of512",
                              "relu2-more-passes"])
def test_routed_experts_serves_both_expert_forms(form, k, R, E):
    """(gated SiLU, top-8 of 256) and (squared ReLU, top-22 of 512):
    values, gradients, and no assignment left out, against every held
    expert computed on every token; the third case needs several
    passes."""
    N, C, F, offset = 24, 16, 12, 0 if R == E else 16
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(key[0], (N, C))
    router = jax.random.normal(key[1], (C, R))
    # the held experts a little likelier, so that a few tokens of 24
    # land here whatever the seed
    bias = 0.02 * jax.random.normal(key[2], (R,)) \
        + jnp.zeros((R,)).at[offset:offset + E].set(0.5)
    n_w = 3 if form == "swiglu" else 2
    w = tuple(0.3 * jax.random.normal(
        key[3 + i], (E, C, F) if i < n_w - 1 else (E, F, C))
        for i in range(n_w))

    def program(x, router, w):
        top, g = moe.route(x, router, bias, k, 2.5)
        token, gate, load = moe.dispatch(top, g, offset, E)
        y = moe.routed_experts(x, token, gate, load, w, form)
        return y, moe.layer_stats(load, N)

    def dense(x, router, w):
        top, g = moe.route(x, router, bias, k, 2.5)
        return _dense_experts(form, x, top, g, offset, w)

    with HIGHEST:
        y, stats = program(x, router, w)
        want = dense(x, router, w)
        gp = jax.grad(lambda *a: jnp.sum(jnp.sin(program(*a)[0])),
                      argnums=(0, 1, 2))(x, router, w)
        gr = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                      argnums=(0, 1, 2))(x, router, w)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    assert float(stats[2]) == 0.0 and float(stats[0]) > 0
    if R == E:
        assert float(stats[0]) == N * k > N   # more than one buffer


# --- the expert layer's shared code: softmax routing, the gated-ReLU form,
# --- and whose gradient a vmap gets (models/smallthinker.py's additions) -----

def test_softmax_routing_is_the_softmax_over_all_renormalised():
    """``route(scoring="softmax")``: the k largest logits, their gates
    the softmax over the chosen, which is the softmax over all the
    router's outputs renormalised over the chosen; without
    ``norm_topk_prob`` it is not renormalised; ``scaling`` multiplies;
    it takes no bias; and the sigmoid path is the one it was."""
    N, C, R, k = 40, 16, 64, 6
    key = jax.random.split(jax.random.PRNGKey(21), 3)
    x = jax.random.normal(key[0], (N, C))
    router = jax.random.normal(key[1], (C, R))
    with HIGHEST:
        top, g = moe.route(x, router, None, k, 1.0, scoring="softmax")
        logits = x @ router
    p = jax.nn.softmax(logits, axis=-1)
    want_top = jnp.argsort(-logits, axis=-1)[:, :k]
    np.testing.assert_array_equal(top, want_top)
    chosen = jnp.take_along_axis(p, top, axis=-1)
    np.testing.assert_allclose(
        g, chosen / jnp.sum(chosen, -1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(g, -1), 1.0, rtol=1e-6)
    with HIGHEST:
        _, raw = moe.route(x, router, None, k, 2.0, norm_topk_prob=False,
                           scoring="softmax")
        stop, sg = moe.route(x, router, jnp.zeros((R,)), k, 2.5)
    np.testing.assert_allclose(raw, 2.0 * chosen, rtol=1e-5)
    with pytest.raises(ValueError, match="no bias"):
        moe.route(x, router, jnp.zeros((R,)), k, 1.0, scoring="softmax")
    sig = jnp.take_along_axis(jax.nn.sigmoid(logits), stop, axis=-1)
    np.testing.assert_allclose(
        sg, 2.5 * sig / jnp.sum(sig, -1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, router, None, k, 1.0, scoring="tanh")


def _expert_case(form, W=None, seed=23):
    """(x, router, weights) with 64 router outputs, 8 held from 16."""
    N, C, F, E = 24, 16, 12, 8
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(key[0], ((N, C) if W is None else (W, N, C)))
    # the held experts' columns larger, so that tokens land here
    router = jax.random.normal(key[1], (C, 64)) * jnp.where(
        (jnp.arange(64) >= 16) & (jnp.arange(64) < 24), 3.0, 1.0)
    n_w = 2 if form == "relu2" else 3
    w = tuple(0.3 * jax.random.normal(
        key[2 + i], (E, C, F) if i < n_w - 1 else (E, F, C))
        for i in range(n_w))
    return x, router, w


def _routed_or_plain(form, plain):
    def fn(x, router, w):
        top, g = moe.route(x, router, None, 6, 1.0, scoring="softmax")
        if plain:
            return _dense_experts(form, x, top, g, 16, w)
        token, gate, load = moe.dispatch(top, g, 16, 8)
        return moe.routed_experts(x, token, gate, load, w, form)
    return fn


def test_the_gated_relu_form_has_the_plain_forms_gradient():
    """``"reglu"``: values and the hand-written VJP (ReLU's derivative
    on the gate's side) against autodiff of the plain form, alone."""
    x, router, w = _expert_case("reglu")
    with HIGHEST:
        got = _routed_or_plain("reglu", False)(x, router, w)
        want = _routed_or_plain("reglu", True)(x, router, w)
        gp, gr = (jax.grad(lambda *a: jnp.sum(jnp.sin(
            _routed_or_plain("reglu", plain)(*a))), argnums=(0, 1, 2))(
            x, router, w) for plain in (False, True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    assert float(jnp.abs(want).max()) > 0


@pytest.mark.parametrize("named", [True, False], ids=["pooled", "unnamed"])
@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_grad_of_vmap_sums_the_shared_weights_gradient(form, named):
    """The fused round's transformation: the clients' losses summed,
    then differentiated. Under a ``vmap`` named ``SHARED_CLIENTS`` the
    backward sums the experts' weight gradient over the clients itself;
    under one that says nothing each client's comes out and the
    transformation sums them: the same numbers."""
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    x, router, w = _expert_case(form, W=3)
    axis = SHARED_CLIENTS if named else None

    def loss(plain):
        fn = _routed_or_plain(form, plain)
        return lambda x, w: jnp.sum(jnp.sin(jax.vmap(
            lambda xi: fn(xi, router, w), axis_name=axis)(x)))

    with HIGHEST:
        gp = jax.jit(jax.grad(loss(False), argnums=(0, 1)))(x, w)
        gr = jax.jit(jax.grad(loss(True), argnums=(0, 1)))(x, w)
    _close(gp, gr, rel=2e-5, leaf=2e-4)


@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_vmap_of_grad_gives_each_client_its_own_gradient(form):
    """``core/rounds.py client_round``'s per-client path: shared
    weights, one gradient a client. Every client gets its own (W, E, C,
    F) gradient of the experts' weights, not the sum over the clients
    (which the ``custom_vmap`` rule returned whatever the
    transformation, before the rule read the axis' name)."""
    x, router, w = _expert_case(form, W=3)

    def per_client(plain):
        fn = _routed_or_plain(form, plain)
        return jax.vmap(jax.grad(
            lambda xi, w: jnp.sum(jnp.sin(fn(xi, router, w))),
            argnums=(0, 1)), in_axes=(0, None))

    with HIGHEST:
        gp = jax.jit(per_client(False))(x, w)
        gr = jax.jit(per_client(True))(x, w)
    assert gp[1][0].shape == (3,) + w[0].shape
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    # the clients' gradients differ: none of them is the sum
    assert float(jnp.abs(gr[1][0][0] - gr[1][0][1]).max()) > 1e-3


# --- every client's held rows in one grouped pass (the named vmap's rule) ----

def _steered(router, targets, seed=31):
    """(W, N, C) tokens whose logits on the 8 held experts (columns 16
    to 24 of ``_expert_case``'s router) are ``targets`` (W, 8) plus a
    little noise: a shift solved from the held columns."""
    W, N, C = targets.shape[0], 24, router.shape[0]
    shift = targets @ jnp.linalg.pinv(router[:, 16:24])        # (W, C)
    return 0.05 * jax.random.normal(jax.random.PRNGKey(seed), (W, N, C)) \
        + shift[:, None, :]


def _pool_case(form, case):
    """``(x (3, N, C), router, weights, held_share, remat)`` of one
    case of the pooled rule's tests."""
    x, router, w = _expert_case(form, W=3)
    share, remat = 8 / 64, False
    if case == "several-passes":
        share = 1 / 64     # a buffer of 16 rows (POOL_ALIGN patched to 8)
    elif case == "a-client-with-none":
        x = x.at[1].set(_steered(router, jnp.full((1, 8), -40.0))[0])
    elif case == "all-on-one-expert":
        x = _steered(router, jnp.tile(jnp.float32(
            [[-40.0] * 3 + [40.0] + [-40.0] * 4]), (3, 1)))
        share = 1 / 64
    elif case == "checkpoint":
        share, remat = 1 / 64, True
    return x, router, w, share, remat


def _loads(x, router):
    def one(xi):
        top, g = moe.route(xi, router, None, 6, 1.0, scoring="softmax")
        return moe.dispatch(top, g, 16, 8)[2]
    with HIGHEST:
        return np.asarray(jax.vmap(one)(x))


@pytest.mark.parametrize("case", ["several-passes", "a-client-with-none",
                                  "all-on-one-expert", "checkpoint"])
@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_the_pooled_rule_is_the_per_client_rule(form, case, monkeypatch):
    """Under a ``vmap`` named ``SHARED_CLIENTS`` the forward and the
    backward take every client's held assignments in one pass loop:
    the values and the gradients of ``x``, the router and every weight
    are the per-client rule's (a ``vmap`` that says nothing) and those
    of every held expert computed on every token; with a buffer far
    smaller than the load (several passes), a client that holds
    nothing, every assignment on one expert, and under
    ``jax.checkpoint``."""
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    monkeypatch.setattr(moe, "POOL_ALIGN", 8)
    x, router, w, share, remat = _pool_case(form, case)
    loads = _loads(x, router)
    rows = moe.pool_rows(3, 24 * 6, share)
    if case == "a-client-with-none":
        assert loads[1].sum() == 0 and loads[0].sum() > 0
    elif case == "all-on-one-expert":
        assert (loads == np.eye(8, dtype=int)[3] * 24).all()
    if share < 8 / 64:
        assert loads.sum() > 3 * rows      # more than three buffers' worth

    def loss(how):
        def one(xi, router, w):
            top, g = moe.route(xi, router, None, 6, 1.0, scoring="softmax")
            if how == "plain":
                return _dense_experts(form, xi, top, g, 16, w)
            token, gate, load = moe.dispatch(top, g, 16, 8)
            return moe.routed_experts(xi, token, gate, load, w, form, share)
        if remat:
            one = jax.checkpoint(one)
        axis = SHARED_CLIENTS if how == "pooled" else None

        def both(x, router, w):
            y = jax.vmap(lambda xi: one(xi, router, w), axis_name=axis)(x)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(both, argnums=(0, 1, 2),
                                          has_aux=True))

    with HIGHEST:
        (_, yp), gp = loss("pooled")(x, router, w)
        (_, yc), gc = loss("per-client")(x, router, w)
        (_, yr), gr = loss("plain")(x, router, w)
    np.testing.assert_allclose(yp, yr, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(yp, yc, rtol=2e-5, atol=2e-6)
    _close(gp, gr, rel=2e-5, leaf=2e-4)
    _close(gp, gc, rel=2e-5, leaf=2e-4)
    assert float(jnp.abs(yr).max()) > 0
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(gr))


def test_inside_a_shard_map_the_pool_is_one_devices_clients():
    """``rounds_sp`` and the multi-chip meshes: the named ``vmap`` runs
    inside a ``shard_map`` over ``clients``, so the pool is the two
    clients a device holds; the loops' carries and the counters must
    pass its varying-axes check, and the numbers are the plain form's
    over all four clients."""
    from jax.sharding import Mesh, PartitionSpec as P
    from commefficient_tpu.parallel.mesh import (CLIENT_AXIS, SHARED_CLIENTS,
                                                 shard_map)
    x, router, w = _expert_case("swiglu", W=4)
    mesh = Mesh(np.array(jax.devices()[:2]), (CLIENT_AXIS,))

    def one(xi, w):
        top, g = moe.route(xi, router, None, 6, 1.0, scoring="softmax")
        token, gate, load = moe.dispatch(top, g, 16, 8)
        return (moe.routed_experts(xi, token, gate, load, w, "swiglu",
                                   8 / 64),
                moe.layer_stats(load, 24, 6, 8 / 64))

    def block(x, w):
        # as ``core/rounds.py`` hands a device its weights: varying, so
        # that the gradient is the device's own until the round sums it
        w = jax.lax.pcast(w, CLIENT_AXIS, to="varying")

        def loss(x, w):
            y, stats = jax.vmap(lambda xi: one(xi, w),
                                axis_name=SHARED_CLIENTS)(x)
            return jnp.sum(jnp.sin(y)), stats
        (_, stats), (dx, dw) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, w)
        return dx, jax.lax.psum(dw, CLIENT_AXIS), stats

    with HIGHEST:
        dx, dw, stats = jax.jit(shard_map(
            block, mesh=mesh, in_specs=(P(CLIENT_AXIS), P()),
            out_specs=(P(CLIENT_AXIS), P(), P(CLIENT_AXIS))))(x, w)
        want = jax.grad(lambda x, w: jnp.sum(jnp.sin(jax.vmap(
            lambda xi: _routed_or_plain("swiglu", True)(xi, router, w))(x))),
            argnums=(0, 1))(x, w)
    _close((dx, dw), want, rel=2e-5, leaf=2e-4)
    rows = moe.pool_rows(2, 24 * 6, 8 / 64)
    assert stats.shape == (4, 5) and (stats[:, 2] == 0).all()
    assert (stats[:, 3] == rows).all() and (stats[:, 4] >= 1).all()
    held = np.asarray(stats[:, 0]).reshape(2, 2).sum(1)      # a device
    assert (np.asarray(stats[:, 4]).reshape(2, 2)
            == -(-held // rows)[:, None]).all()


def _count(jaxpr, found):
    """Every equation of ``jaxpr`` and of the programs inside it."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, found)
    return found


@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_the_pooled_rules_program_does_not_grow_with_the_clients(form):
    """``grad(vmap(..., axis_name=SHARED_CLIENTS))``: as many ragged
    products whatever W, and no loop over the W clients; the ``vmap``
    that says nothing keeps its loop (what the walk must find)."""
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS
    fn = _routed_or_plain(form, False)

    def eqns(W, axis):
        x, router, w = _expert_case(form, W=W)
        return _count(jax.make_jaxpr(jax.grad(
            lambda x, w: jnp.sum(jnp.sin(jax.vmap(
                lambda xi: fn(xi, router, w), axis_name=axis)(x))),
            argnums=(0, 1)))(x, w).jaxpr, [])

    def ragged(found):
        return sum(e.primitive.name.startswith("ragged_dot") for e in found)

    def loops_over(found, W):
        return sum(e.primitive.name == "scan" and e.params["length"] == W
                   for e in found)

    few, many = eqns(2, SHARED_CLIENTS), eqns(5, SHARED_CLIENTS)
    n_w = 2 if form == "relu2" else 3
    # forward: a product a weight; backward: those recomputed, their
    # transposes and the weights' outer products
    assert ragged(few) == ragged(many) == 4 * n_w
    assert loops_over(few, 2) == loops_over(many, 5) == 0
    assert loops_over(eqns(5, None), 5) > 0


@pytest.mark.parametrize("model", ["NemotronHLM", "JoyAIFlashLM",
                                   "SmallThinkerLM"])
def test_the_fused_round_pools_the_clients_held_rows(tmp_path, model):
    """Each trainer's tiny model through ``FedModel``: every round
    record says that the expert layers took the clients' rows in one
    buffer (``moe.pool_rows`` > 0), in how many passes, and that none
    was left out."""
    _tiny_run(tmp_path, ["--ledger", str(tmp_path / "ledger.jsonl")],
              model=model)
    with open(tmp_path / "ledger.jsonl") as f:
        recs = [r for r in map(json.loads, f) if r.get("kind") == "round"]
    assert recs
    for c in (r["counters"] for r in recs):
        assert c["moe.dropped"] == 0 and c["moe.assignments_here"] > 0
        assert c["moe.pool_rows"] > 0 and c["moe.pool_passes"] >= 1
        assert c["moe.pool_rows"] % 256 == 0


# --- configuration, trainer, FedModel ------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    config = _config()
    cfg = NemotronHConfig.from_hf(config)
    published = NemotronHConfig()
    for name in ("hidden_size", "mamba_head_dim", "ssm_state_size",
                 "conv_kernel", "chunk_size", "head_dim",
                 "moe_intermediate_size", "moe_latent_size",
                 "moe_shared_expert_intermediate_size", "n_router_experts",
                 "num_experts_per_tok", "routed_scaling_factor",
                 "layer_norm_epsilon"):
        assert getattr(cfg, name) == getattr(published, name), name
    assert (cfg.hidden_size, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.moe_shared_expert_intermediate_size, cfg.n_router_experts,
            cfg.num_experts_per_tok) == (4096, 64, 128, 2688, 1024, 5376,
                                         512, 22)
    assert (cfg.hybrid_override_pattern, cfg.n_held_experts,
            cfg.mamba_num_heads, cfg.n_groups, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.vocab_size) == (
        "MEMEMEMEM*E", 8, 16, 1, 4, 1, 16384)
    # the cut is characters 27-37 of the published pattern
    assert published.hybrid_override_pattern[27:38] == "MEMEMEMEM*E"
    assert len(published.hybrid_override_pattern) == 88
    assert sorted(config["reduced"]) == sorted(
        k for k, v in config["published"].items() if config[k] != v)
    shapes = jax.eval_shape(lambda: ref.init_params(
        jax.random.PRNGKey(0), config))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        shapes)) == config["num_parameters"] == 700865520


def test_every_number_of_the_published_config_is_kept_or_listed():
    """The catalog's rule: a top-level number that differs from the
    source's is named in ``reduced``."""
    config = _config()
    for key, value in config["published"].items():
        assert key in config["reduced"] and config[key] != value
    cfg = NemotronHConfig.from_hf(config)
    assert cfg.reference_spec()["num_hidden_layers"] == 11
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.from_hf(dict(config, num_hidden_layers=12))


def _tiny_run(tmp_path, extra=(), model="NemotronHLM"):
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
    for c in (r["counters"] for r in recs):
        assert c["moe.dropped"] == 0 and c["moe.assignments_here"] > 0
        assert c["moe.load_max"] >= c["moe.load_mean"] > 0
        # 4 clients x 2 sequences x 32 / 8 chunks x 5 Mamba-2 layers
        assert c["ssm.chunks"] == 4 * 2 * 4 * 5


@pytest.mark.parametrize("model,model_type", [
    ("NemotronHLM", "joyai_llm_flash"), ("JoyAIFlashLM", "nemotron_h")])
def test_model_flag_and_config_json_must_agree(tmp_path, model, model_type):
    """``--model`` chooses the module; a ``config.json`` of another
    ``model_type`` is refused with both named."""
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": model_type}, f)
    with pytest.raises(ValueError) as err:
        _tiny_run(tmp_path, model=model)
    assert model in str(err.value) and model_type in str(err.value)


def test_model_and_dataset_flags_go_together(tmp_path):
    from commefficient_tpu.train import gpt2_train
    with pytest.raises(ValueError, match="do not go together"):
        gpt2_train.run(["--test", "--model", "NemotronHLM",
                        "--dataset_dir", str(tmp_path)])


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
    assert config["builder"] == "nemotron_h"
    cell.update({k: v for k, v in cell["rehearse"].items() if k != "data"},
                num_devices=1)
    run = load("builders", config["builder"]).build(
        cell, config, ref, 20260928, str(tmp_path), rehearse=True)
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


def test_block_rejects_an_unknown_pattern_character():
    cfg = NemotronHConfig.tiny()
    with pytest.raises(ValueError, match="pattern character"):
        Block(cfg, "-").init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8, cfg.hidden_size)))

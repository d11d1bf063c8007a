"""One chip's share of NVIDIA-Nemotron-3-Super-120B-A12B in plain
``jax.numpy`` float32: the reference of the ``nemotron3-super-ep64-tp8``
configuration.

Written from the published ``config.json`` (nvidia/NVIDIA-Nemotron-3-
Super-120B-A12B-BF16, ``model_type`` ``nemotron_h``) and the equations
of the layers it names: Mamba-2 (Dao & Gu, arXiv:2405.21060, the
recurrence of section 2 with one scalar decay a head), grouped-query
attention, and a sigmoid-routed expert layer whose experts live in a
latent. Every norm is RMSNorm with eps ``layer_norm_epsilon`` (1e-5),
no linear map has a bias, the conv has one:

    block:  x += mixer(norm(x)), one mixer a block, chosen by the
            character of ``hybrid_override_pattern``; final norm; untied
            head
    M:      [z | xBC | dt] = h W_in       (H P | H P + 2 G N | H)
            xBC_t = silu(sum_{k<4} w_k * xBC_{t-3+k} + b)  (causal, a
            channel at a time); [x | B | C] = xBC
            delta_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
            S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T  (P x N, a
            head; the H / G heads of a group share B and C; S_{-1} = 0)
            y_t = S_t C_t + D x_t
            out = W_out groupnorm(y * silu(z))   (gate first; one RMS
            norm a group of H P / G channels, one learned scale)
    *:      q, k, v = h W_q, h W_k, h W_v  (heads of ``head_dim`` 128)
            a = softmax_causal(q k^T / sqrt(128)) v, the query heads of
            a group with its one key/value head; out = a W_o
    E:      s = sigmoid(h W_r) over all ``router_experts``;
            T = the 22 largest of s + b;  g_e = 5 s_e / sum_{e' in T} s_e'
            u = h W_down                                 (4096 -> 1024)
            y = W_up sum_{e in T and held} g_e W2_e relu(W1_e u)^2
                + W2_s relu(W1_s h)^2    (the shared expert, on h)
            (n_group = topk_group = 1: no group limit; b takes no
            gradient, as published: a balancing rule outside the loss
            moves it, here it is a constant from the seed)
    loss of a sequence = mean CE of the next token over its T-1
            positions; a client's loss is the masked mean over its
            sequences.

**The recurrence is computed as written**, one position after the
other (``jax.lax.scan`` over t with the (H, P, N) state as carry), not
by the chunked algorithm the program uses.

The share (``spec``): ``mamba_num_heads`` heads in ``n_groups`` groups,
``num_attention_heads`` query and ``num_key_value_heads`` key/value
heads, ``n_routed_experts`` experts held, numbered from
``expert_offset`` among the router's ``router_experts`` outputs, and
``vocab_size`` rows of embedding and head, ids and logits over that
slice. What the absent heads, groups, experts and rows would add is
left out, and nothing stands in for them.

Noted departures from the published description:
- **no multi-token-prediction module** (``num_nextn_predict_layers`` 1,
  ``mtp_hybrid_override_pattern`` ``*E``, "shared-weight MTP heads"):
  how it joins the hidden state with the next token's embedding is in
  neither the config nor the catalog's description, and a guessed
  module under a real name is worse than none; the loss is the
  next-token loss a fine-tuning job trains;
- **no positional embedding**: Nemotron-H's attention layers state
  none, and nothing here reads ``rope_theta`` / ``partial_rotary_factor``;
- **packing**: a sequence is a client's documents end to end with a
  separator id; the state is not reset and attention is not masked at
  a document's start (``data/fed_tokens.py`` as it is);
- the router's product is not rounded by ``q`` (the configuration
  states router scores and selection in float32 whatever the matmuls'
  precision); in the recurrence ``q`` rounds what the program hands its
  matrix products (delta x, B, C), the decays stay float32.

No flax, no kernel, nothing of the program. Parameter names are those
the program's module declares; the builder checks names and shapes.
``jax.checkpoint`` around each block changes no arithmetic: one
client's float32 activations (a block's 2,048 saved states among them)
must fit beside the weights-side arrays ``lib/fetchsgd_ref.follow``
holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

# Limits of ``correct``, from 13 sound seeds and the fp8 control on two
# at the cell's own sizes on the chip (PERF.md section 2, PR 32). Only
# ``grad_rel_l2`` separates the precisions and lies between its two
# readings, 3 x over the sound runs' largest and 3.2 x under the
# control's. The other three, which fp8 moves less than the seeds
# differ or hardly at all, sit at three times the sound runs' largest
# or more: the loss at GPT-2's accepted limit (JoyAI's 0.0005 leaves a
# residual stream of 4,096 in bf16 over 11 blocks no room: one sound
# seed in 13 read 0.00053), the parameters' change between its
# first reading and 1 (which 50,000 coordinates of 701M are picked
# differs on 5.6 % of them, so a small leaf's norm differs by chance).
LIMITS = {
    "loss_gap": 0.003,        # sound <= 0.00053 (fp8: 0.0012)
    "grad_norm_gap": 0.0015,  # sound <= 0.00045 (fp8: 0.00081)
    "grad_rel_l2": 0.035,     # sound 0.0108-0.0116; fp8 0.114, 0.117
    "delta_norm_gap": 0.3,    # sound <= 0.098 (fp8: 0.092-0.106)
}


def _sizes(spec):
    g = lambda k: int(spec[k])  # noqa: E731
    z = dict(
        C=g("hidden_size"), pattern=str(spec["hybrid_override_pattern"]),
        H=g("mamba_num_heads"), P=g("mamba_head_dim"), G=g("n_groups"),
        N=g("ssm_state_size"), K=g("conv_kernel"),
        Hq=g("num_attention_heads"), Hkv=g("num_key_value_heads"),
        D=g("head_dim"), E=g("n_routed_experts"), R=g("router_experts"),
        off=g("expert_offset"), k=g("num_experts_per_tok"),
        F=g("moe_intermediate_size"), Z=g("moe_latent_size"),
        Fs=g("moe_shared_expert_intermediate_size"), V=g("vocab_size"))
    if g("num_hidden_layers") != len(z["pattern"]):
        raise ValueError("num_hidden_layers is not the pattern's length")
    return z


def init_params(key, spec):
    """normal(0, ``initializer_range``) matrices, norm scales 1, the
    router's bias normal(0, ``router_bias_std``); the Mamba-2 mixers'
    own: delta = exp(U(log ``time_step_min``, log ``time_step_max``))
    floored at ``time_step_floor`` and ``dt_bias`` its inverse softplus,
    ``A_log`` = log U(1, 16), ``D`` = 1, the conv U(+-1 / sqrt(kernel))
    (PyTorch's default, which the published code leaves). float32, one
    traced call."""
    z = _sizes(spec)
    std = float(spec.get("initializer_range", 0.02))
    bstd = float(spec.get("router_bias_std", 0.02))
    keys = iter(jax.random.split(key, 12 * len(z["pattern"]) + 4))

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def norm(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def mamba():
        C, H, K = z["C"], z["H"], z["K"]
        inner, bc = H * z["P"], z["G"] * z["N"]
        d = jnp.maximum(jnp.exp(uniform(
            (H,), math.log(float(spec.get("time_step_min", 0.001))),
            math.log(float(spec.get("time_step_max", 0.1))))),
            float(spec.get("time_step_floor", 1e-4)))
        return {"in_proj": normal((C, 2 * inner + 2 * bc + H)),
                "conv_w": uniform((K, inner + 2 * bc), -K ** -0.5, K ** -0.5),
                "conv_b": uniform((inner + 2 * bc,), -K ** -0.5, K ** -0.5),
                "dt_bias": d + jnp.log(-jnp.expm1(-d)),
                "A_log": jnp.log(uniform((H,), 1.0, 16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "gate_norm": jnp.ones((inner,), jnp.float32),
                "out_proj": normal((inner, C))}

    def attention():
        C, D = z["C"], z["D"]
        return {"q": normal((C, z["Hq"] * D)), "k": normal((C, z["Hkv"] * D)),
                "v": normal((C, z["Hkv"] * D)), "o": normal((z["Hq"] * D, C))}

    def moe():
        C, E, Z, F, Fs = z["C"], z["E"], z["Z"], z["F"], z["Fs"]
        return {"router": normal((C, z["R"])),
                "router_bias": normal((z["R"],), bstd),
                "latent_down": normal((C, Z)), "latent_up": normal((Z, C)),
                "experts": {"w1": normal((E, Z, F)), "w2": normal((E, F, Z))},
                "shared": {"w1": normal((C, Fs)), "w2": normal((Fs, C))}}

    make = {"M": mamba, "*": attention, "E": moe}
    p = {"embed": normal((z["V"], z["C"])),
         "lm_head": normal((z["V"], z["C"])), "norm": norm(z["C"])}
    for i, kind in enumerate(z["pattern"]):
        p[f"layer_{i}"] = {"norm": norm(z["C"]), "mixer": make[kind]()}
    return p


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _mm(x, w, q):
    return q(x) @ q(w)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, delta, A, B, C, q=lambda a: a):
    """``y_t = S_t C_t``, ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t
    B_t^T``, one position after the other. ``x`` (T, H, P), ``delta``
    (T, H), ``A`` (H,), ``B`` / ``C`` (T, G, N) -> (T, H, P)."""
    T, H, P = x.shape
    G, N = B.shape[-2:]
    hg = H // G

    def step(S, inp):
        xd, dec, b, c = inp                 # (H, P), (H,), (G, N), (G, N)
        bh, ch = jnp.repeat(b, hg, axis=0), jnp.repeat(c, hg, axis=0)
        S = dec[:, None, None] * S + xd[:, :, None] * bh[:, None, :]
        return S, jnp.sum(S * ch[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((H, P, N), jnp.float32),
        (q(x * delta[..., None]), jnp.exp(delta * A), q(B), q(C)))
    return y


def _mamba(p, h, spec, q):
    z = _sizes(spec)
    S, T, _ = h.shape
    H, P, G, N, K = z["H"], z["P"], z["G"], z["N"], z["K"]
    inner, bc = H * P, G * N
    zxd = _mm(h, p["in_proj"], q)
    gate, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * bc],
                     zxd[..., 2 * inner + 2 * bc:])
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_w"][k] * xp[:, k:k + T]
                          for k in range(K)) + p["conv_b"])
    x = xbc[..., :inner].reshape(S, T, H, P)
    B = xbc[..., inner:inner + bc].reshape(S, T, G, N)
    C = xbc[..., inner + bc:].reshape(S, T, G, N)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = jax.vmap(lambda x, d, b, c: recurrence(x, d, A, b, c, q))(
        x, delta, B, C)
    y = (y + p["D"][:, None] * x).reshape(S, T, inner)
    g = (y * jax.nn.silu(gate)).reshape(S, T, G, inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + float(spec["layer_norm_epsilon"]))
    return _mm(g.reshape(S, T, inner) * p["gate_norm"], p["out_proj"], q)


def _attention(p, h, spec, q):
    z = _sizes(spec)
    S, T, _ = h.shape
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    qh = _mm(h, p["q"], q).reshape(S, T, Hkv, Hq // Hkv, D)
    kh = _mm(h, p["k"], q).reshape(S, T, Hkv, D)
    vh = _mm(h, p["v"], q).reshape(S, T, Hkv, D)
    att = jnp.einsum("stgqd,sugd->sgqtu", q(qh), q(kh)) \
        / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    out = jnp.einsum("sgqtu,sugd->stgqd", q(att), q(vh))
    return _mm(out.reshape(S, T, Hq * D), p["o"], q)


def route(p, x, spec):
    """(N, C) tokens -> ((N, k) expert ids of all ``router_experts``,
    (N, k) gates): sigmoid scores, top-k of score + bias, the chosen
    scores normalised to ``routed_scaling_factor``. float32."""
    k = int(spec["num_experts_per_tok"])
    s = jax.nn.sigmoid(x @ p["router"])
    _, top = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), k)
    sel = jnp.take_along_axis(s, top, axis=-1)
    if spec.get("norm_topk_prob", True):
        sel = sel / (jnp.sum(sel, -1, keepdims=True) + 1e-20)
    return top, float(spec["routed_scaling_factor"]) * sel


def _moe(p, h, spec, q):
    z = _sizes(spec)
    shape = h.shape
    x = h.reshape(-1, shape[-1])
    top, g = route(p, x, spec)
    held = z["off"] + jnp.arange(z["E"])
    # (N, E): the gate of each held expert, 0 where it was not chosen
    gate = jnp.sum(jnp.where(top[:, :, None] == held[None, None, :],
                             g[:, :, None], 0.0), axis=1)
    u = _mm(x, p["latent_down"], q)
    e = p["experts"]
    a = _relu2(jnp.einsum("nz,ezf->enf", q(u), q(e["w1"])))
    y = jnp.einsum("enf,efz->enz", q(a), q(e["w2"]))
    routed = _mm(jnp.einsum("ne,enz->nz", gate, y), p["latent_up"], q)
    s = p["shared"]
    return (routed + _mm(_relu2(_mm(x, s["w1"], q)), s["w2"], q)) \
        .reshape(shape)


_MIXERS = {"M": _mamba, "*": _attention, "E": _moe}


def _block(kind, p, x, spec, q):
    h = _rms(x, p["norm"]["scale"], float(spec["layer_norm_epsilon"]))
    return x + _MIXERS[kind](p["mixer"], h, spec, q)


def sequence_losses(params, ids, spec, q=lambda a: a):
    """(S, T) token ids -> (S,) mean next-token NLL."""
    z = _sizes(spec)
    h = params["embed"][ids]
    for i, kind in enumerate(z["pattern"]):
        h = jax.checkpoint(
            lambda p, x, kind=kind: _block(kind, p, x, spec, q))(
            params[f"layer_{i}"], h)
    h = _rms(h, params["norm"]["scale"], float(spec["layer_norm_epsilon"]))
    logits = q(h[:, :-1]) @ q(params["lm_head"]).T
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=-1)


def client_loss(params, b, spec, q=lambda a: a):
    """One client's masked-mean loss. ``b``: input_ids (B, T), mask
    (B,)."""
    losses = sequence_losses(params, b["input_ids"], spec, q)
    return jnp.sum(losses * b["mask"]) / jnp.maximum(
        jnp.sum(b["mask"]), 1.0)


def train_flops_per_round(spec, cell):
    """FLOPs one round's forward and backward passes need: 6 per matmul
    parameter a token touches (2 forward, 4 backward): a Mamba-2 mixer's
    two projections; attention's four; in an expert layer the router,
    the two latent projections, the shared expert and the *expected*
    share of the held experts (k * held / router_experts of them a
    token, what a uniform router sends here); the head once. Plus the
    recurrence's 6 * 2 * heads * P * N a token and ``M`` layer (the
    state's update and its read-out, as the recurrence states them: the
    chunked algorithm's extra in-chunk work is not needed work) and
    attention's 6 * T * heads * 2 * head_dim / 2 a token and ``*`` layer
    (QK^T and PV, the causal half only). The embedding gather, the
    conv and the norms are no matmul. No recomputation counted."""
    z = _sizes(spec)
    C, T = z["C"], int(cell["sequence_length"])
    inner, bc = z["H"] * z["P"], z["G"] * z["N"]
    mamba = C * (2 * inner + 2 * bc + z["H"]) + inner * C
    attention = 2 * C * z["Hq"] * z["D"] + 2 * C * z["Hkv"] * z["D"]
    moe = (C * z["R"] + 2 * C * z["Z"] + 2 * C * z["Fs"]
           + 2 * z["Z"] * z["F"] * z["k"] * z["E"] / z["R"])
    n = {kind: z["pattern"].count(kind) for kind in "M*E"}
    matmul = (n["M"] * mamba + n["*"] * attention + n["E"] * moe
              + z["V"] * C)
    per_token = (6 * matmul + n["M"] * 12 * z["H"] * z["P"] * z["N"]
                 + n["*"] * 6 * T * z["Hq"] * 2 * z["D"] // 2)
    tokens = cell["clients_per_round"] * cell["local_batch_size"] * T
    return per_token * tokens

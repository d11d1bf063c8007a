"""One pipeline stage of ibm-granite/granite-4.0-h-micro in plain
``jax.numpy`` float32: the reference of the ``granite4-h-micro-pp4-v8``
configuration.

Written from the published ``config.json`` (``model_type``
``granitemoehybrid``, ``num_local_experts`` 0: the dense member of the
family) and the equations of the layers it names: Mamba-2 (Dao & Gu,
arXiv:2405.21060, the recurrence of section 2 with one scalar decay a
head) and grouped-query attention, each followed in its block by a
gated SiLU feed-forward part, under four multipliers. Every norm is
RMSNorm with eps ``rms_norm_eps`` (1e-5) and a learned scale; no linear
map has a bias (``attention_bias``, ``mamba_proj_bias`` false), the conv
has one (``mamba_conv_bias``):

    h_0 = embedding_multiplier E[ids]                       (12)
    block i, r = residual_multiplier (0.22):
            h += r mixer_i(norm_1(h)),  mixer by ``layer_types[i]``
            [a | b] = norm_2(h) W_in          (2048 -> 2 x 8192)
            h += r (silu(a) * b) W_out        (8192 -> 2048)
    mamba:  [z | xBC | dt] = u W_in       (H P | H P + 2 G N | H)
            xBC_t = silu(sum_{k<4} w_k * xBC_{t-3+k} + b)  (causal, a
            channel at a time); [x | B | C] = xBC
            delta_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
            S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T  (P x N, a
            head; the H / G heads of a group share B and C; S_{-1} = 0)
            y_t = S_t C_t + D x_t
            out = W_out groupnorm(y * silu(z))   (gate first; one RMS
            norm a group of H P / G channels: with ``mamba_n_groups`` 1,
            over all 4,096, one learned scale)
    attention:  q, k, v = u W_q, u W_k, u W_v  (32 / 8 / 8 heads of 64)
            a = softmax_causal(attention_multiplier q k^T) v  (1/64, not
            1/sqrt(64)), the query heads of a group with its one
            key/value head; out = a W_o; no positional embedding
            (``position_embedding_type`` ``nope``)
    logits = E norm(h) / logits_scaling  (8; ``tie_word_embeddings``:
            the embedding matrix is the head)
    loss of a sequence = mean CE of the next token over its T-1
            positions; a client's loss is the masked mean over its
            sequences.

**The recurrence is computed as written**, one position after the
other (``jax.lax.scan`` over t with the (H, P, N) state as carry), not
by the chunked algorithm the program uses; **attention is plain and
whole**, the (heads, T, T) scores and all, not by blocks of queries.

The share (``spec``): ``layer_types`` is one pipeline stage's layers
(each whole: every head of both kinds, every width) and ``vocab_size``
the rows held of the tied matrix, ids and logits over that slice. What
the absent rows would add is left out, and nothing stands in for them.

Noted departures from the published description:
- **packing**: a sequence is a client's documents end to end with a
  separator id; the state is not reset and attention is not masked at
  a document's start (``data/fed_tokens.py`` as it is);
- **initialisation** (the catalog's copy of the config carries none of
  these keys): ``initializer_range`` 0.02; Mamba-2's published
  initialisation for ``dt_bias`` / ``A_log`` / ``D`` with
  ``time_step_min`` / ``_max`` / ``_floor`` 0.001 / 0.1 / 1e-4; PyTorch's
  default for the conv;
- ``rope_theta``, ``mamba_expand``, ``intermediate_size`` and
  ``num_experts_per_tok`` are in the configuration and read by nothing
  (no positions; the inner width is heads x head size; the feed-forward
  width is ``shared_intermediate_size``; there are no experts);
- in the recurrence ``q`` rounds what the program hands its matrix
  products (delta x, B, C), the decays stay float32.

No flax, no kernel, nothing of the program. Parameter names are those
the program's module declares; the builder checks names and shapes.
``jax.checkpoint`` around each block, and around each stretch of
positions inside the recurrence, changes no arithmetic: one client's
float32 activations (a mixer's saved (64, 64, 128) states among them:
4.3 GB for 2,048 positions, 0.2 GB kept a stretch at a time) must fit
beside the weights-side arrays ``lib/fetchsgd_ref.follow`` holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

#: positions of the recurrence whose states are kept together in the
#: backward pass (memory only)
STRETCH = 64

# Limits of ``correct``, from the sound seeds and the fp8 control at the
# cell's own sizes on the chip (PERF.md section 2, PR 34).
# ``grad_rel_l2`` separates the precisions and lies between its two
# readings, 3.0 x over the sound runs' largest and 3.4 x under the
# control's (the sound reading is twice the other LM cells': twenty
# residual additions in bf16 where they have eleven or fewer).
# ``loss_gap`` separates them too, by less, and lies between its two
# readings as well: 2.1 x over the sound runs' largest, 2.3 x under the
# control's smaller seed (1.6 x under its smallest step). Both are small
# beside the other cells' (logits / 8 on weights of 0.02: the
# prediction is all but uniform and the loss 9.42 on every client), so
# the limit is this cell's own and not an accepted cell's: JoyAI's
# 0.0005 would pass the control seven times over. The sound reading is
# the worst of 12 client losses a run, each the mean of 2,047 tokens'
# bf16 rounding: it scatters little (3.1e-6 to 1.43e-5 over 16 seeds).
# ``grad_norm_gap``, which fp8 moves by 0.9 to 1.5 x, sits at three times the
# sound runs' largest and has no upper reading. ``delta_norm_gap``
# separates only a state left unchanged (which reads 1) from one that
# stepped: it sits between its first reading (0.187) and 1, the more
# room above, and a program with the server's momentum dropped reads
# 0.40 and passes (PERF.md section 2). The program and the reference
# pick 87 % of the same 150,000 coordinates of 772M, and the worst leaf
# is every time one of 64 to 17,408 elements (a ``dt_bias``, a norm's
# scale, a ``conv_b``) in which one side stepped a single coordinate
# and the other none, 0.002 to 0.005 against a floor of a tenth of the
# largest leaf's change (0.019): chance in the selection, not rounding,
# and the control reads the same (a floor for tiny leaves in
# ``fetchsgd_ref.numbers`` is a ``benchmark`` issue's, PERF.md section
# 7).
LIMITS = {
    "loss_gap": 0.00003,  # sound <= 0.0000143; fp8 0.000068, 0.000089
    "grad_norm_gap": 0.0025,  # sound <= 0.00079 (fp8: 0.00122, 0.00071)
    "grad_rel_l2": 0.07,      # sound 0.0224-0.0232; fp8 0.236, 0.231
    "delta_norm_gap": 0.6,    # sound 0.147-0.250 (fp8: 0.256, 0.285)
}


def _sizes(spec):
    g = lambda k: int(spec[k])  # noqa: E731
    z = dict(
        C=g("hidden_size"), kinds=tuple(spec["layer_types"]),
        H=g("mamba_n_heads"), P=g("mamba_d_head"), G=g("mamba_n_groups"),
        N=g("mamba_d_state"), K=g("mamba_d_conv"),
        Hq=g("num_attention_heads"), Hkv=g("num_key_value_heads"),
        F=g("shared_intermediate_size"), V=g("vocab_size"),
        tied=bool(spec.get("tie_word_embeddings", True)),
        eps=float(spec["rms_norm_eps"]))
    z["D"] = z["C"] // z["Hq"]
    if g("num_hidden_layers") != len(z["kinds"]):
        raise ValueError("num_hidden_layers is not layer_types' length")
    if int(spec.get("num_local_experts", 0)) > 0:
        raise ValueError("num_local_experts > 0: not this reference")
    return z


def init_params(key, spec):
    """normal(0, ``initializer_range``) matrices, norm scales 1; the
    Mamba-2 mixers' own: delta = exp(U(log ``time_step_min``, log
    ``time_step_max``)) floored at ``time_step_floor`` and ``dt_bias`` its
    inverse softplus, ``A_log`` = log U(1, 16), ``D`` = 1, the conv
    U(+-1 / sqrt(kernel)) (PyTorch's default). float32, one traced
    call."""
    z = _sizes(spec)
    std = float(spec.get("initializer_range", 0.02))
    keys = iter(jax.random.split(key, 10 * len(z["kinds"]) + 4))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

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

    make = {"mamba": mamba, "attention": attention}
    p = {"embed": normal((z["V"], z["C"])), "norm": norm(z["C"])}
    if not z["tied"]:
        p["lm_head"] = normal((z["V"], z["C"]))
    for i, kind in enumerate(z["kinds"]):
        p[f"layer_{i}"] = {
            "norm1": norm(z["C"]), "mixer": make[kind](),
            "norm2": norm(z["C"]),
            "mlp": {"w_in": normal((z["C"], 2 * z["F"])),
                    "w_out": normal((z["F"], z["C"]))}}
    return p


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _mm(x, w, q):
    return q(x) @ q(w)


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

    # the positions in order, a stretch of them under one checkpoint
    n = max(d for d in range(1, STRETCH + 1) if T % d == 0)
    inputs = jax.tree_util.tree_map(
        lambda v: v.reshape((T // n, n) + v.shape[1:]),
        (q(x * delta[..., None]), jnp.exp(delta * A), q(B), q(C)))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, inp: jax.lax.scan(step, S, inp)),
        jnp.zeros((H, P, N), jnp.float32), inputs)
    return y.reshape(T, H, P)


def _mamba(p, u, z, q):
    S, T, _ = u.shape
    H, P, G, N, K = z["H"], z["P"], z["G"], z["N"], z["K"]
    inner, bc = H * P, G * N
    zxd = _mm(u, p["in_proj"], q)
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
                          + z["eps"])
    return _mm(g.reshape(S, T, inner) * p["gate_norm"], p["out_proj"], q)


def _attention(p, u, z, q, scale):
    S, T, _ = u.shape
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    qh = _mm(u, p["q"], q).reshape(S, T, Hkv, Hq // Hkv, D)
    kh = _mm(u, p["k"], q).reshape(S, T, Hkv, D)
    vh = _mm(u, p["v"], q).reshape(S, T, Hkv, D)
    att = jnp.einsum("stgqd,sugd->sgqtu", q(qh), q(kh)) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    out = jnp.einsum("sgqtu,sugd->stgqd", q(att), q(vh))
    return _mm(out.reshape(S, T, Hq * D), p["o"], q)


def _block(kind, p, h, spec, q):
    z = _sizes(spec)
    r = float(spec["residual_multiplier"])
    u = _rms(h, p["norm1"]["scale"], z["eps"])
    if kind == "mamba":
        y = _mamba(p["mixer"], u, z, q)
    elif kind == "attention":
        y = _attention(p["mixer"], u, z, q,
                       float(spec["attention_multiplier"]))
    else:
        raise ValueError(f"no mixer for layer type {kind!r}")
    h = h + r * y
    ab = _mm(_rms(h, p["norm2"]["scale"], z["eps"]), p["mlp"]["w_in"], q)
    a, b = ab[..., :z["F"]], ab[..., z["F"]:]
    return h + r * _mm(jax.nn.silu(a) * b, p["mlp"]["w_out"], q)


def sequence_losses(params, ids, spec, q=lambda a: a):
    """(S, T) token ids -> (S,) mean next-token NLL."""
    z = _sizes(spec)
    h = float(spec["embedding_multiplier"]) * params["embed"][ids]
    for i, kind in enumerate(z["kinds"]):
        h = jax.checkpoint(
            lambda p, x, kind=kind: _block(kind, p, x, spec, q))(
            params[f"layer_{i}"], h)
    h = _rms(h, params["norm"]["scale"], z["eps"])
    head = params["embed"] if z["tied"] else params["lm_head"]
    logits = (q(h[:, :-1]) @ q(head).T) / float(spec["logits_scaling"])
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
    two projections; attention's four; every block's gated part (three
    products of hidden x ``shared_intermediate_size``); the tied matrix
    once, as the head (the embedding is a gather). Plus the recurrence's
    6 * 2 * heads * P * N a token and ``mamba`` layer (the state's update
    and its read-out, as the recurrence states them: the chunked
    algorithm's extra in-chunk work is not needed work) and attention's
    6 * T * heads * 2 * head size / 2 a token and ``attention`` layer
    (QK^T and PV, the causal half only). The conv and the norms are no
    matmul. No recomputation counted."""
    z = _sizes(spec)
    C, T = z["C"], int(cell["sequence_length"])
    inner, bc = z["H"] * z["P"], z["G"] * z["N"]
    mamba = C * (2 * inner + 2 * bc + z["H"]) + inner * C
    attention = 2 * C * z["Hq"] * z["D"] + 2 * C * z["Hkv"] * z["D"]
    n = {kind: z["kinds"].count(kind) for kind in ("mamba", "attention")}
    matmul = (n["mamba"] * mamba + n["attention"] * attention
              + len(z["kinds"]) * 3 * C * z["F"] + z["V"] * C)
    per_token = (6 * matmul + n["mamba"] * 12 * z["H"] * z["P"] * z["N"]
                 + n["attention"] * 6 * T * z["Hq"] * 2 * z["D"] // 2)
    tokens = cell["clients_per_round"] * cell["local_batch_size"] * T
    return per_token * tokens

"""One chip's share of JoyAI-LLM-Flash in plain ``jax.numpy`` float32:
the reference of the ``joyai-llm-flash-ep32`` configuration.

Written from the published ``config.json`` (jdopensource/JoyAI-LLM-Flash,
``model_type`` ``joyai_llm_flash``) and the equations of the block it
names, DeepSeek-V3's (arXiv:2412.19437, sections 2.1.1, 2.1.2, 2.2).
All norms are RMSNorm (eps 1e-6), the activation is silu, no biases:

    block:  x += MLA(norm(x));  x += FFN(norm(x))
            FFN = dense SwiGLU (7168) in layer 0, the expert layer after
    MLA:    c_q = norm(x W_dq)                                  (1536)
            [q_i^n | q_i^r] = c_q W_uq,i                        (128 | 64)
            [c_kv | k^r] = x W_dkv;  c_kv = norm(c_kv)          (512 | 64)
            [k_i^n | v_i] = c_kv W_ukv,i                        (128 | 128)
            RoPE (theta 32e6, interleaved pairs) on q_i^r and on the
            one k^r all heads share
            a_i = softmax_causal((q_i^n.k_i^n + q_i^r.k^r) / sqrt(192)) v_i
            y = sum_i a_i W_o,i     over the heads held here
    expert: s = sigmoid(x W_r)  over all 256;  T = top8(s + b)
            g_e = 2.5 s_e / sum_{e' in T} s_e'   for e in T
            y = SwiGLU_shared(x) + sum_{e in T and held} g_e SwiGLU_e(x)
            (n_group = topk_group = 1: no group limit; b takes no
            gradient, as published: a balancing rule outside the loss
            moves it, here it is a constant from the seed)
    MTP:    h' = [norm(h_t) ; norm(Emb(x_{t+1}))] W_eh    (4096 -> 2048)
            one expert-layer block, a norm, the shared embedding and
            head; position t predicts x_{t+2}. h_t is the last block's
            output before the model's final norm.
    loss of a sequence = mean CE of the main head (T-1 positions)
            + lambda * mean CE of the MTP head (T-2 positions);
            a client's loss is the masked mean over its sequences.

The share (``spec``): ``n_routed_experts`` experts held, numbered from
``expert_offset`` among the router's ``router_experts`` outputs;
``num_attention_heads`` heads held; ``vocab_size`` rows of embedding
and head held, ids and logits over that slice. What the absent experts,
heads and rows would add is left out, and nothing stands in for them.

Noted departures: the router's product is not rounded by ``q`` (the
configuration states router scores and selection in float32 whatever
the matmuls' precision); the MTP block sees positions 0..T-2 (RoPE is
relative, so the offset by one changes nothing).

No flax, no kernel, nothing of the program. Parameter names are those
the program's module declares; the builder checks names and shapes.
``jax.checkpoint`` around each block changes no arithmetic: one
client's float32 activations must fit beside the weights-side arrays
``lib/fetchsgd_ref.follow`` holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

# Limits of ``correct``, from 20 sound seeds and the fp8 control on 3 at
# the cell's own sizes on the chip (PERF.md section 2, PR 27). Only
# ``grad_rel_l2`` separates the precisions and lies between its two
# readings; the other three, which fp8 hardly moves or moves less than
# the seeds differ, sit at three times the sound runs' largest.
LIMITS = {
    "loss_gap": 0.0005,       # sound <= 0.00016 (fp8: >= 0.00065)
    "grad_norm_gap": 0.003,   # sound <= 0.0011 (fp8: 0.0002-0.0017)
    "grad_rel_l2": 0.03,      # sound <= 0.0110, with top-8 selections
                              # that differ on 1.3 % of picks; fp8 >= 0.095
    "delta_norm_gap": 0.24,   # sound <= 0.079 (fp8: 0.076-0.094)
}


def _sizes(spec):
    g = lambda k: int(spec[k])  # noqa: E731
    return dict(
        C=g("hidden_size"), L=g("num_hidden_layers"),
        dense=g("first_k_dense_replace"), H=g("num_attention_heads"),
        rq=g("q_lora_rank"), rkv=g("kv_lora_rank"),
        dn=g("qk_nope_head_dim"), dr=g("qk_rope_head_dim"),
        dv=g("v_head_dim"), F=g("intermediate_size"),
        Fe=g("moe_intermediate_size"), E=g("n_routed_experts"),
        R=g("router_experts"), off=g("expert_offset"),
        k=g("num_experts_per_tok"), V=g("vocab_size"),
        mtp=g("num_nextn_predict_layers"))


def init_params(key, spec):
    """normal(0, ``initializer_range``) matrices, norm scales 1, the
    router's bias normal(0, ``router_bias_std``); float32, one traced
    call."""
    z = _sizes(spec)
    std = float(spec.get("initializer_range", 0.02))
    bstd = float(spec.get("router_bias_std", 0.02))
    keys = iter(jax.random.split(key, 16 * (z["L"] + z["mtp"]) + 8))

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    def norm(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(c, f):
        return {"gate": normal((c, f)), "up": normal((c, f)),
                "down": normal((f, c))}

    def block(moe):
        C, H = z["C"], z["H"]
        b = {"attn_norm": norm(C), "ffn_norm": norm(C),
             "attn": {"q_a": normal((C, z["rq"])), "q_norm": norm(z["rq"]),
                      "q_b": normal((z["rq"], H * (z["dn"] + z["dr"]))),
                      "kv_a": normal((C, z["rkv"] + z["dr"])),
                      "kv_norm": norm(z["rkv"]),
                      "kv_b": normal((z["rkv"], H * (z["dn"] + z["dv"]))),
                      "o": normal((H * z["dv"], C))}}
        if not moe:
            b["mlp"] = swiglu(C, z["F"])
            return b
        E, Fe = z["E"], z["Fe"]
        b["moe"] = {"router": normal((C, z["R"])),
                    "router_bias": normal((z["R"],), bstd),
                    "experts": {"gate": normal((E, C, Fe)),
                                "up": normal((E, C, Fe)),
                                "down": normal((E, Fe, C))},
                    "shared": swiglu(C, Fe)}
        return b

    p = {"embed": normal((z["V"], z["C"])),
         "lm_head": normal((z["V"], z["C"])), "norm": norm(z["C"])}
    for i in range(z["L"]):
        p[f"layer_{i}"] = block(moe=i >= z["dense"])
    for i in range(z["mtp"]):
        p[f"mtp_{i}"] = {"enorm": norm(z["C"]), "hnorm": norm(z["C"]),
                         "eh_proj": normal((2 * z["C"], z["C"])),
                         "block": block(moe=True), "norm": norm(z["C"])}
    return p


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


def _mm(x, w, q):
    return q(x) @ q(w)


def _swiglu(x, p, q):
    return _mm(jax.nn.silu(_mm(x, p["gate"], q)) * _mm(x, p["up"], q),
               p["down"], q)


def _rope(x, theta):
    """Rotate the interleaved pairs (x[2i], x[2i+1]) of the last axis
    by position * theta^(-2i/D); x: (S, T, ..., D)."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (D // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _mla(p, x, spec, q):
    z = _sizes(spec)
    eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
    S, T, _ = x.shape
    H, dn, dr, dv, r = z["H"], z["dn"], z["dr"], z["dv"], z["rkv"]
    cq = _rms(_mm(x, p["q_a"], q), p["q_norm"], eps)
    qh = _mm(cq, p["q_b"], q).reshape(S, T, H, dn + dr)
    kv = _mm(x, p["kv_a"], q)
    ckv = _rms(kv[..., :r], p["kv_norm"], eps)
    kvh = _mm(ckv, p["kv_b"], q).reshape(S, T, H, dn + dv)
    qn, qr = qh[..., :dn], _rope(qh[..., dn:], theta)
    kn, v = kvh[..., :dn], kvh[..., dn:]
    kr = _rope(kv[..., r:], theta)                       # (S, T, dr)
    att = (jnp.einsum("sthd,suhd->shtu", q(qn), q(kn))
           + jnp.einsum("sthd,sud->shtu", q(qr), q(kr))) \
        / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    out = jnp.einsum("shtu,suhd->sthd", q(att), q(v))
    return _mm(out.reshape(S, T, H * dv), p["o"], q)


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


def _moe(p, x, spec, q):
    z = _sizes(spec)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    top, g = route(p, x, spec)
    held = z["off"] + jnp.arange(z["E"])
    # (N, E): the gate of each held expert, 0 where it was not chosen
    gate = jnp.sum(jnp.where(top[:, :, None] == held[None, None, :],
                             g[:, :, None], 0.0), axis=1)
    e = p["experts"]
    h = jax.nn.silu(jnp.einsum("nc,ecf->enf", q(x), q(e["gate"]))) \
        * jnp.einsum("nc,ecf->enf", q(x), q(e["up"]))
    y = jnp.einsum("enf,efc->enc", q(h), q(e["down"]))
    y = jnp.einsum("ne,enc->nc", gate, y) + _swiglu(x, p["shared"], q)
    return y.reshape(shape)


def _block(p, x, spec, q):
    eps = float(spec["rms_norm_eps"])
    x = x + _mla(p["attn"], _rms(x, p["attn_norm"], eps), spec, q)
    h = _rms(x, p["ffn_norm"], eps)
    if "moe" in p:
        return x + _moe(p["moe"], h, spec, q)
    return x + _swiglu(h, p["mlp"], q)


def _nll(h, head, labels, q):
    """(S, T', C) hidden, (S, T') labels -> (S,) mean NLL."""
    logits = q(h) @ q(head).T
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=-1)


def sequence_losses(params, ids, spec, q=lambda a: a):
    """(S, T) token ids -> ((S,) main-head mean NLL, (S,) MTP-head mean
    NLL; zeros without an MTP module)."""
    z = _sizes(spec)
    eps = float(spec["rms_norm_eps"])
    block = jax.checkpoint(lambda p, x: _block(p, x, spec, q))
    h = params["embed"][ids]
    for i in range(z["L"]):
        h = block(params[f"layer_{i}"], h)
    main = _nll(_rms(h, params["norm"], eps)[:, :-1], params["lm_head"],
                ids[:, 1:], q)
    mtp = jnp.zeros_like(main)
    for i in range(z["mtp"]):
        m = params[f"mtp_{i}"]
        # position t: the trunk's h_t beside the embedding of x_{t+1}
        nxt = params["embed"][ids[:, 1:]]
        hm = _mm(jnp.concatenate([_rms(h[:, :-1], m["hnorm"], eps),
                                  _rms(nxt, m["enorm"], eps)], axis=-1),
                 m["eh_proj"], q)
        hm = block(m["block"], hm)
        mtp = mtp + _nll(_rms(hm, m["norm"], eps)[:, :-1],
                         params["lm_head"], ids[:, 2:], q)
    return main, mtp


def client_loss(params, b, spec, q=lambda a: a):
    """One client's masked-mean loss. ``b``: input_ids (B, T), mask
    (B,)."""
    main, mtp = sequence_losses(params, b["input_ids"], spec, q)
    losses = main + float(spec.get("mtp_loss_weight", 0.3)) * mtp
    return jnp.sum(losses * b["mask"]) / jnp.maximum(
        jnp.sum(b["mask"]), 1.0)


def train_flops_per_round(spec, cell):
    """FLOPs one round's forward and backward passes need: 6 per matmul
    parameter a token touches (2 forward, 4 backward): MLA, the dense
    MLP, in an expert layer the router, the shared expert and the
    *expected* share of the held experts (k * held / router_experts of
    them a token, what a uniform router sends here), ``eh_proj``, and
    the head once per head application (main and MTP); plus attention's
    6 * T * heads * (qk + v) / 2 per token and attention layer (QK^T and
    PV, forward and backward, the causal half only). The embedding
    gather is no matmul. No recomputation counted."""
    z = _sizes(spec)
    C, H = z["C"], z["H"]
    T = int(cell["sequence_length"])
    mla = (C * z["rq"] + z["rq"] * H * (z["dn"] + z["dr"])
           + C * (z["rkv"] + z["dr"]) + z["rkv"] * H * (z["dn"] + z["dv"])
           + H * z["dv"] * C)
    attn = 6 * T * H * (z["dn"] + z["dr"] + z["dv"]) // 2
    expert = 3 * C * z["Fe"]
    moe = C * z["R"] + expert * (
        int(spec.get("n_shared_experts", 1))
        + z["k"] * z["E"] / z["R"])
    dense_layers = z["dense"]
    moe_layers = z["L"] - z["dense"] + z["mtp"]
    matmul = ((z["L"] + z["mtp"]) * mla + dense_layers * 3 * C * z["F"]
              + moe_layers * moe + z["mtp"] * 2 * C * C
              + (1 + z["mtp"]) * z["V"] * C)
    per_token = 6 * matmul + (z["L"] + z["mtp"]) * attn
    tokens = (cell["clients_per_round"] * cell["local_batch_size"] * T)
    return per_token * tokens

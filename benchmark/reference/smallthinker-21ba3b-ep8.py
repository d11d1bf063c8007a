"""One chip's share of SmallThinker-21BA3B-Instruct in plain
``jax.numpy`` float32: the reference of the ``smallthinker-21ba3b-ep8``
configuration.

Written from the published ``config.json`` (PowerInfer/SmallThinker-
21BA3B-Instruct, ``model_type`` ``smallthinker``) and the catalog's
description of the family (window attention of 4,096 with RoPE beside
global attention with no positions, 3 : 1; 64 experts, 6 a token, no
shared expert, sparse ReGLU, the router placed before attention).
Every norm is RMSNorm with eps ``rms_norm_eps`` (1e-6), no linear map
has a bias. C = ``hidden_size``, x the block's input (T, C):

    r   = x W_r                          (C -> ``router_experts``)
    top = the ``moe_num_active_primary_experts`` largest of r
    g   = softmax(r[top])    (``moe_primary_router_apply_softmax`` and
          ``norm_topk_prob``: the softmax over all the router's outputs,
          renormalised over the chosen, is the same numbers)
    n   = RMSNorm_1(x);  q = n W_q,  k = n W_k,  v = n W_v
    rope_layout[l] = 1:  q_t, k_t <- R(t) q_t, R(t) k_t, with R(t) the
          rotation of the pairs (i, i + D/2), i < D/2, by
          t * theta^(-2i/D): ``rotate_half``, over the whole head
    s_ij = q_i . k_j / sqrt(D)  for j <= i, and where
          sliding_window_layout[l] = 1 also i - j < sliding_window_size;
          a_i = sum_j softmax_j(s_ij) v_j,  the query heads of a group
          with its one key/value head
    h   = x + a W_o
    m   = RMSNorm_2(h)
    y   = h + sum_{e in top and held} g_e W_d^e (relu(W_g^e m) * (W_u^e m))
    logits = RMSNorm_f(y_L) W_head;  loss of a sequence = mean CE of the
          next token over its T-1 positions; a client's loss is the
          masked mean over its sequences.

**Attention is computed against a mask built from (i, j) alone**: a
block of query rows at a time (``ROWS``, memory only) meets *every* key
of the sequence, window layer or not, and what the mask leaves out is
-inf before the softmax. Nothing of the program's slicing of keys is
restated here.

The share (``spec``): ``moe_num_primary_experts`` experts held, numbered
from ``expert_offset`` among the router's ``router_experts`` outputs,
and ``vocab_size`` rows of embedding and head, ids and logits over that
slice; attention, norms and the router are whole. What the absent
experts and rows would add is left out, and nothing stands in for them.

Noted departures from the published description, and what the catalog
leaves open (the configuration's ``assumed`` (a)-(g)):
- (a) **the router reads the block's raw input**, not its norm:
  ``described_as`` says "router placed before attention" and no more;
  the public implementations of the model compute the raw input;
- (b) RoPE pairs dimension i with i + D/2 (``rotate_half``) and covers
  all 128 dimensions of a head; ``rope_scaling`` is null;
- (c) the window counts the query itself: i - j < 4,096;
- (e) every layer is an expert layer (the catalog's copy carries no
  ``moe_layer_layout``; 52 x (21.14M + 64 x 5.90M) + 778M = 21.5B is
  the published size only so);
- (f) **no secondary experts**: ``described_as`` names "primary +
  secondary experts", ``config`` has no key for them; ``config`` is
  trusted;
- (g) **packing**: a sequence is a client's documents end to end with a
  separator id; attention is not masked at a document's start
  (``data/fed_tokens.py`` as it is);
- the router's product is not rounded by ``q`` (the configuration
  states router logits and selection in float32 whatever the matmuls'
  precision), nor are the rotations.

No flax, no kernel, nothing of the program. Parameter names are those
the program's module declares; the builder checks names and shapes.
``jax.checkpoint`` around each block and around each block of query
rows changes no arithmetic: one client's float32 activations (a block
of 256 rows' scores against 8,192 keys over 28 heads: 0.23 GB) must
fit beside the weights-side arrays ``lib/fetchsgd_ref.follow`` holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

#: query rows whose scores against every key exist together (memory
#: only)
ROWS = 256

# Limits of ``correct``, from 8 sound seeds and the fp8 control on two
# of them at the cell's own sizes on the chip (PERF.md section 2, PR 41,
# call 2). Only ``grad_rel_l2`` separates the precisions and lies
# between its two readings, 2.7 x over the sound runs' largest and 2.8 x
# under the control's smaller. The other three, which fp8 moves less
# than the seeds differ (``grad_norm_gap``: 0.00047 on one control seed,
# under two sound seeds' 0.00036) or unevenly (``loss_gap``: 0.0008 and
# 0.0055), have no upper reading to lie under: ``grad_norm_gap`` sits at
# 3.3 x the sound runs' largest; the loss takes the limit of the
# harness's accepted cells (GPT-2's and Nemotron's 0.003: 11 x over the
# sound runs' largest, 27 x over the first reading; JoyAI's 0.0005 would
# leave 1.9 x); the parameters' change lies between its first reading
# (0.0068) and 1, which a state left unchanged reads, the more room
# above the reading (13 x over the sound runs' largest). Twelve further
# seeds ran under these limits afterwards (call 4, from the final tree's
# ``git archive`` copy), every one ``correct``: ``loss_gap`` <= 0.00035,
# ``grad_norm_gap`` <= 0.00042, ``grad_rel_l2`` 0.0055-0.0075 (2.7 x
# under), ``delta_norm_gap`` <= 0.0228.
LIMITS = {
    "loss_gap": 0.003,        # sound 0.00009-0.00026 (fp8: 0.0008, 0.0055)
    "grad_norm_gap": 0.0012,  # sound 0.00002-0.00036 (fp8: 0.00047, 0.0023)
    "grad_rel_l2": 0.02,      # sound 0.0057-0.0075; fp8 0.0572, 0.0556
    "delta_norm_gap": 0.3,    # sound 0.0055-0.0228 (fp8: 0.028, 0.041)
}


def _sizes(spec):
    g = lambda k: int(spec[k])  # noqa: E731
    z = dict(
        C=g("hidden_size"), L=g("num_hidden_layers"),
        Hq=g("num_attention_heads"), Hkv=g("num_key_value_heads"),
        D=g("head_dim"), window=g("sliding_window_size"),
        windows=[int(v) for v in spec["sliding_window_layout"]],
        ropes=[int(v) for v in spec["rope_layout"]],
        theta=float(spec["rope_theta"]),
        E=g("moe_num_primary_experts"), R=g("router_experts"),
        off=g("expert_offset"), k=g("moe_num_active_primary_experts"),
        F=g("moe_ffn_hidden_size"), V=g("vocab_size"),
        eps=float(spec["rms_norm_eps"]))
    if len(z["windows"]) != z["L"] or len(z["ropes"]) != z["L"]:
        raise ValueError("a layout's length is not num_hidden_layers")
    if not spec.get("moe_primary_router_apply_softmax", True):
        raise ValueError("only the softmax router is written down here")
    return z


def init_params(key, spec):
    """normal(0, ``initializer_range``) matrices, norm scales 1.
    float32, a leaf at a time."""
    z = _sizes(spec)
    std = float(spec.get("initializer_range", 0.02))
    keys = iter(jax.random.split(key, 8 * z["L"] + 2))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((z["C"],), jnp.float32)}

    C, D, E, F = z["C"], z["D"], z["E"], z["F"]
    p = {"embed": normal((z["V"], C)), "lm_head": normal((z["V"], C)),
         "norm": norm()}
    for i in range(z["L"]):
        p[f"layer_{i}"] = {
            "router": normal((C, z["R"])),
            "attn_norm": norm(), "ffn_norm": norm(),
            "attn": {"q": normal((C, z["Hq"] * D)),
                     "k": normal((C, z["Hkv"] * D)),
                     "v": normal((C, z["Hkv"] * D)),
                     "o": normal((z["Hq"] * D, C))},
            "experts": {"gate": normal((E, C, F)), "up": normal((E, C, F)),
                        "down": normal((E, F, C))}}
    return p


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _mm(x, w, q):
    return q(x) @ q(w)


def rotate(x, theta):
    """RoPE on (S, T, ..., D) at positions 0 .. T-1, the textbook
    ``x cos + rotate_half(x) sin`` with the D/2 frequencies repeated
    over both halves."""
    T, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (D,))
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def seen(i, j, window=None):
    """Whether query position ``i`` sees key position ``j``."""
    ok = j <= i
    return ok if window is None else ok & (i - j < window)


def attend(qh, kh, vh, window, q):
    """``qh`` (S, T, Hkv, g, D), ``kh`` / ``vh`` (S, T, Hkv, D) ->
    (S, T, Hkv, g, D): every row's softmax over the keys it sees,
    ``ROWS`` query rows at a time against all T keys."""
    S, T, Hkv, g, D = qh.shape
    rows = min(ROWS, T)
    n = -(-T // rows)
    qp = jnp.pad(qh, ((0, 0), (0, n * rows - T)) + ((0, 0),) * 3)
    qp = jnp.moveaxis(qp.reshape(S, n, rows, Hkv, g, D), 1, 0)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(qb, first):
        s = jnp.einsum("stgqd,sugd->sgqtu", q(qb), q(kh)) \
            / jnp.sqrt(jnp.float32(D))
        i = (first + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen(i, j, window), s, -jnp.inf), -1)
        return jnp.einsum("sgqtu,sugd->stgqd", q(p), q(vh))

    out = jax.lax.map(lambda a: block(*a),
                      (qp, jnp.arange(n, dtype=jnp.int32) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(S, n * rows, Hkv, g, D)[:, :T]


def _attention(p, n, z, layer, q):
    S, T, _ = n.shape
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    qh = _mm(n, p["q"], q).reshape(S, T, Hkv, Hq // Hkv, D)
    kh = _mm(n, p["k"], q).reshape(S, T, Hkv, D)
    vh = _mm(n, p["v"], q).reshape(S, T, Hkv, D)
    if z["ropes"][layer]:
        qh, kh = rotate(qh, z["theta"]), rotate(kh, z["theta"])
    a = attend(qh, kh, vh, z["window"] if z["windows"][layer] else None, q)
    return _mm(a.reshape(S, T, Hq * D), p["o"], q)


def route(router, x, spec):
    """(N, C) tokens -> ((N, k) expert ids of all ``router_experts``,
    (N, k) gates): the k largest logits, the softmax over them.
    float32."""
    r = x @ router
    val, top = jax.lax.top_k(r, int(spec["moe_num_active_primary_experts"]))
    if spec.get("norm_topk_prob", True):
        return top, jax.nn.softmax(val, axis=-1)
    return top, jnp.take_along_axis(jax.nn.softmax(r, -1), top, axis=-1)


def experts(p, m, top, g, z, q):
    """What the held experts add to each of the (N, C) tokens ``m``."""
    held = z["off"] + jnp.arange(z["E"])
    # (N, E): the gate of each held expert, 0 where it was not chosen
    gate = jnp.sum(jnp.where(top[:, :, None] == held[None, None, :],
                             g[:, :, None], 0.0), axis=1)
    a = jnp.maximum(jnp.einsum("nc,ecf->enf", q(m), q(p["gate"])), 0.0) \
        * jnp.einsum("nc,ecf->enf", q(m), q(p["up"]))
    y = jnp.einsum("enf,efc->enc", q(a), q(p["down"]))
    return jnp.einsum("ne,enc->nc", gate, y)


def _block(layer, p, x, spec, q):
    z = _sizes(spec)
    C = x.shape[-1]
    top, g = route(p["router"], x.reshape(-1, C), spec)   # before attention
    h = x + _attention(p["attn"], _rms(x, p["attn_norm"]["scale"], z["eps"]),
                       z, layer, q)
    m = _rms(h, p["ffn_norm"]["scale"], z["eps"]).reshape(-1, C)
    return h + experts(p["experts"], m, top, g, z, q).reshape(h.shape)


def sequence_losses(params, ids, spec, q=lambda a: a):
    """(S, T) token ids -> (S,) mean next-token NLL."""
    z = _sizes(spec)
    h = params["embed"][ids]
    for i in range(z["L"]):
        h = jax.checkpoint(
            lambda p, x, i=i: _block(i, p, x, spec, q))(
            params[f"layer_{i}"], h)
    h = _rms(h, params["norm"]["scale"], z["eps"])
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


def attention_pairs(T, window=None):
    """(query, key) pairs of one head over a T-token sequence that the
    mask lets through: the causal half, or the band."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def train_flops_per_round(spec, cell):
    """FLOPs one round's forward and backward passes need: 6 per matmul
    parameter a token touches (2 forward, 4 backward): attention's four
    projections, the router, the *expected* share of the held experts
    (k * held / router_experts of them a token, what a uniform router
    sends here), the head once. Plus attention's scores and value
    products over the pairs the mask lets through, 12 * head_dim a
    pair and query head (QK^T and PV, forward and backward): the causal
    half on a full layer, the band on a window layer; what a program
    computes outside them is not needed work. The embedding gather, the
    rotations and the norms are no matmul. No recomputation counted."""
    z = _sizes(spec)
    C, T = z["C"], int(cell["sequence_length"])
    attention = 2 * C * z["Hq"] * z["D"] + 2 * C * z["Hkv"] * z["D"]
    moe = C * z["R"] + 3 * C * z["F"] * z["k"] * z["E"] / z["R"]
    matmul = z["L"] * (attention + moe) + z["V"] * C
    pairs = sum(attention_pairs(T, z["window"] if w else None)
                for w in z["windows"])
    per_sequence = 6 * matmul * T + 12 * z["D"] * z["Hq"] * pairs
    return per_sequence * cell["clients_per_round"] \
        * cell["local_batch_size"]

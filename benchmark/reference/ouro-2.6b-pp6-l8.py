"""A pipeline stage's share of Ouro-2.6B, a looped language model, in
plain ``jax.numpy`` float32: the reference of the ``ouro-2.6b-pp6-l8``
configuration.

Written from the published ``config.json`` (ByteDance/Ouro-2.6B,
``model_type`` ``ouro``: hidden 2,048, 48 ``full_attention`` layers,
16 = 16 heads of 128, a gated SiLU part of 5,632, RMSNorm eps 1e-6,
RoPE theta 1e6, vocabulary 49,152 untied, ``total_ut_steps`` 4) and,
for what the config has no key for, from the family's paper ("Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) and
public modelling file: the configuration's ``assumed`` (a)-(g). C =
``hidden_size``, x (T, C), every norm RMSNorm with a weight, no linear
map but the gate has a bias:

    (a) block:  a = x + N2(Attn(N1(x)));   y = a + N4(MLP(N3(a)))
    (b) Attn:   q, k, v = n W_q, n W_k, n W_v;  q_t, k_t <- R(t) q_t,
                R(t) k_t, R(t) the rotation of the pairs (i, i + D/2),
                i < D/2, by t * theta^(-2i/D): ``rotate_half``, over
                the whole head, positions 0 .. T-1 of the packed
                sequence;  s_ij = q_i . k_j / sqrt(D) for j <= i;
                out = (softmax_j(s) v) W_o
        MLP:    W_d (silu(W_g n) * (W_u n));  ``w_in`` = [W_g | W_u]
    (c) loop:   x_0 = E[ids];  for t = 1 .. steps:  x <- block_{L-1}(
                ... block_0(x)) on the *same* weights;  h_t = N_f(x);
                x <- h_t      (the final norm sits inside the loop)
    (d) gate:   lambda_t = sigmoid(h_t . w_e + b_e), one map for all t
    (e) exit:   p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j)
                for 1 < t < steps;  p_steps = prod_{j<steps}(1 -
                lambda_j)  (what is left: sum_t p_t = 1), from
                log sigmoid(+-g)
    (f) loss:   l_{t,i} = NLL of id_{i+1} under softmax(W_head h_{t,i});
                L_i = sum_t p_{t,i} l_{t,i} - beta H(p_{.,i}),  H = -sum_t
                p log p;  a sequence's loss = mean of L_i over its T - 1
                predicting positions; a client's loss the masked mean
                over its sequences. Gradients flow through l, through p
                into the gate, and through the gate into the stack.

Left out, as in the program: the paper's second stage (the gate trained
alone against each step's improvement) and inference-time early exit
(``early_exit_threshold`` is copied and read by nothing).

**The counts go by layer application.** ``_sizes`` lists one attention
entry for each of the L x steps applications (none windowed), so
``attention_pairs`` summed over them, and ``train_flops_per_round``,
count what a pass computes: every weight of the stack and the head
``steps`` times.

Noted departures: the gate's product, the rotations and the exit
distribution are not rounded by ``q`` (the configuration states them in
float32 whatever the matmuls' precision); attention is computed a block
of ``ROWS`` query rows at a time against every key, full logits a
chunk of ``HEAD_ROWS`` positions, and ``jax.checkpoint`` stands around
each block application, row block and head chunk: memory only, no
arithmetic changed.

No flax, no kernel, nothing of the program. Parameter names are those
the program's module declares; the builder checks names and shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

#: query rows whose scores against every key exist together, and
#: positions whose logits over the vocabulary do (memory only)
ROWS = 512
HEAD_ROWS = 512

# Limits of ``correct``, from chip runs at the cell's own sizes (PERF.md
# section 2, PR 48; every run is in CHANGES.md). The cell as committed
# (the loop one scan, --lr_scale 0.4) was read on 5 sound seeds and the
# fp8 control on 1 of them; the same sizes in forms the tree no longer
# holds or at another LR on 13 more (unrolled at 0.4: 7, with the
# control on 2; unrolled at 0.1: 2; the scan at 0.1: 4). Each line
# below gives the committed cell's readings first.
# Only ``grad_rel_l2`` separates the precisions and lies between its two
# readings, 2.4 x over the sound runs' largest and 2.4 x under the
# control's smallest (the sound runs read two to four times the other
# LM cells': 32 layer applications and 64 residual additions in bf16).
# The other three, which fp8 moves less than the seeds differ
# (``grad_norm_gap``: 0.0029 and 0.0023 on two control seeds, under a
# sound seed's 0.0056), have no upper reading to lie under:
# ``grad_norm_gap`` sits at 1.8 x the largest of the 18 sound readings
# (whose root mean square is 0.0021: the limit is 4.7 of those); the
# loss takes the limit of the harness's accepted cells (0.003: 7 x over
# the committed cell's first reading, 2.7 x over the largest of the
# 18); the parameters' change lies between its first reading (0.046)
# and 1, which a state left unchanged reads, the more room above the
# reading. The five planted faults of ``benchmark/tests/ouro_faults.py``
# read ``grad_rel_l2`` 0.39-2.7 at the cell's size (unrolled, 0.1), and
# ``gate_detached``, the faintest, 0.29 on the committed cell.
LIMITS = {
    # scan 0.4: 0.00013-0.00067 (fp8 0.0015); others 0.00007-0.0011
    # (fp8 0.00066, 0.0010)
    "loss_gap": 0.003,
    # scan 0.4: 0.0014-0.0056 (fp8 0.0029); others 0.00005-0.0032
    # (fp8 0.0071, 0.0023)
    "grad_norm_gap": 0.01,
    # scan 0.4: 0.0167-0.0279, fp8 0.1859; others 0.0188-0.0288, fp8
    # 0.1669, 0.1823
    "grad_rel_l2": 0.07,
    # scan 0.4: 0.032-0.046 (fp8 0.051); others 0.029-0.053 (fp8 0.051,
    # 0.066)
    "delta_norm_gap": 0.3,
}


def _sizes(spec):
    g = lambda k: int(spec[k])  # noqa: E731
    kinds = list(spec["layer_types"])
    z = dict(
        C=g("hidden_size"), L=g("num_hidden_layers"),
        Hq=g("num_attention_heads"), Hkv=g("num_key_value_heads"),
        D=g("head_dim"), F=g("intermediate_size"), V=g("vocab_size"),
        steps=g("total_ut_steps"), theta=float(spec["rope_theta"]),
        eps=float(spec["rms_norm_eps"]),
        beta=float(spec.get("entropy_beta", 0.1)))
    if len(kinds) != z["L"] or set(kinds) - {"full_attention"}:
        raise ValueError(f"layer_types {kinds} is not num_hidden_layers "
                         f"{z['L']} entries of 'full_attention'")
    if z["steps"] < 1:
        raise ValueError("total_ut_steps < 1")
    # one entry a layer *application*, in the order a pass runs them:
    # what ``lib/attnbench.needed_pairs`` sums ``attention_pairs`` over
    z.update(window=None, windows=[0] * (z["L"] * z["steps"]))
    return z


def init_params(key, spec):
    """normal(0, ``initializer_range``) matrices, norm scales 1, the
    gate's bias 0. float32, a leaf at a time."""
    z = _sizes(spec)
    std = float(spec.get("initializer_range", 0.02))
    keys = iter(jax.random.split(key, 6 * z["L"] + 3))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((z["C"],), jnp.float32)}

    C, D, F = z["C"], z["D"], z["F"]
    stack = {"norm": norm()}
    for i in range(z["L"]):
        stack[f"layer_{i}"] = {
            "norm1": norm(), "norm2": norm(), "norm3": norm(),
            "norm4": norm(),
            "attn": {"q": normal((C, z["Hq"] * D)),
                     "k": normal((C, z["Hkv"] * D)),
                     "v": normal((C, z["Hkv"] * D)),
                     "o": normal((z["Hq"] * D, C))},
            "mlp": {"w_in": normal((C, 2 * F)), "w_out": normal((F, C))}}
    return {"embed": normal((z["V"], C)), "lm_head": normal((z["V"], C)),
            "exit_gate": {"kernel": normal((C, 1)),
                          "bias": jnp.zeros((1,), jnp.float32)},
            "stack": stack}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _mm(x, w, q):
    return q(x) @ q(w)


def rotate(x, theta):
    """RoPE on (S, T, H, D) at positions 0 .. T-1, the textbook
    ``x cos + rotate_half(x) sin`` with the D/2 frequencies repeated
    over both halves."""
    T, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def attend(qh, kh, vh, q):
    """(S, T, H, D) each -> (S, T, H, D): every row's softmax over the
    keys at or before it, ``ROWS`` query rows at a time against all T
    keys."""
    S, T, H, D = qh.shape
    rows = min(ROWS, T)
    n = -(-T // rows)
    qp = jnp.pad(qh, ((0, 0), (0, n * rows - T), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape(S, n, rows, H, D), 1, 0)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(qb, first):
        s = jnp.einsum("sthd,suhd->shtu", q(qb), q(kh)) \
            / jnp.sqrt(jnp.float32(D))
        i = (first + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), -1)
        return jnp.einsum("shtu,suhd->sthd", q(p), q(vh))

    out = jax.lax.map(lambda a: block(*a),
                      (qp, jnp.arange(n, dtype=jnp.int32) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(S, n * rows, H, D)[:, :T]


def _attention(p, n, z, q):
    S, T, _ = n.shape
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    qh = _mm(n, p["q"], q).reshape(S, T, Hq, D)
    kh = _mm(n, p["k"], q).reshape(S, T, Hkv, D)
    vh = _mm(n, p["v"], q).reshape(S, T, Hkv, D)
    qh, kh = rotate(qh, z["theta"]), rotate(kh, z["theta"])
    # a key/value head serves Hq / Hkv query heads (1 as published)
    kh, vh = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (kh, vh))
    return _mm(attend(qh, kh, vh, q).reshape(S, T, Hq * D), p["o"], q)


def _mlp(p, n, z, q):
    ab = _mm(n, p["w_in"], q)
    F = z["F"]
    return _mm(jax.nn.silu(ab[..., :F]) * ab[..., F:], p["w_out"], q)


def _block(p, x, z, q):
    """(a): a norm before and after each of attention and the gated
    part."""
    eps = z["eps"]
    a = x + _rms(_attention(p["attn"], _rms(x, p["norm1"]["scale"], eps),
                            z, q), p["norm2"]["scale"], eps)
    return a + _rms(_mlp(p["mlp"], _rms(a, p["norm3"]["scale"], eps), z, q),
                    p["norm4"]["scale"], eps)


def hidden_states(params, ids, spec, q=lambda a: a):
    """(c): (S, T) token ids -> [h_1, ..., h_steps], each (S, T, C)."""
    z = _sizes(spec)
    stack = params["stack"]
    x, hs = params["embed"][ids], []
    for _ in range(z["steps"]):
        for i in range(z["L"]):
            x = jax.checkpoint(lambda p, v: _block(p, v, z, q))(
                stack[f"layer_{i}"], x)
        x = _rms(x, stack["norm"]["scale"], z["eps"])
        hs.append(x)
    return hs


def exit_probabilities(params, hs):
    """(d), (e): [h_t] -> (log p, p), each (steps, S, T)."""
    gate = params["exit_gate"]
    g = jnp.stack([h @ gate["kernel"][:, 0] + gate["bias"][0] for h in hs])
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)      # sum_{j<=t}
    logp = [jax.nn.log_sigmoid(g[t]) + (stay[t - 1] if t else 0.0)
            for t in range(len(hs) - 1)]
    logp.append(stay[-2] if len(hs) > 1 else jnp.zeros_like(g[0]))
    logp = jnp.stack(logp)
    return logp, jnp.exp(logp)


def token_nll(h, head, labels, q):
    """(S, N, C) states and (S, N) next ids -> (S, N) NLL, full logits
    of ``HEAD_ROWS`` positions at a time."""
    S, N, C = h.shape
    rows = min(HEAD_ROWS, N)
    n = -(-N // rows)
    hp = jnp.pad(h, ((0, 0), (0, n * rows - N), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, n * rows - N)))

    @jax.checkpoint
    def chunk(hc, lc):
        logits = q(hc) @ q(head).T
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, lc[..., None], axis=-1)[..., 0]

    out = jax.lax.map(lambda a: chunk(*a), (
        jnp.moveaxis(hp.reshape(S, n, rows, C), 1, 0),
        jnp.moveaxis(lp.reshape(S, n, rows), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(S, n * rows)[:, :N]


def sequence_losses(params, ids, spec, q=lambda a: a):
    """(f): (S, T) token ids -> (S,) losses."""
    z = _sizes(spec)
    hs = hidden_states(params, ids, spec, q)
    logp, p = exit_probabilities(params, hs)
    logp, p = logp[:, :, :-1], p[:, :, :-1]
    nll = jnp.stack([token_nll(h[:, :-1], params["lm_head"], ids[:, 1:], q)
                     for h in hs])
    entropy = -jnp.sum(p * logp, axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - z["beta"] * entropy, axis=-1)


def client_loss(params, b, spec, q=lambda a: a):
    """One client's masked-mean loss. ``b``: input_ids (B, T), mask
    (B,)."""
    losses = sequence_losses(params, b["input_ids"], spec, q)
    return jnp.sum(losses * b["mask"]) / jnp.maximum(
        jnp.sum(b["mask"]), 1.0)


def attention_pairs(T, window=None):
    """(query, key) pairs of one head over a T-token sequence that the
    causal mask lets through, one layer application; no layer has a
    window."""
    if window is not None:
        raise ValueError("no layer of this model sees a window")
    return T * (T + 1) // 2


def train_flops_per_round(spec, cell):
    """FLOPs one round's forward and backward passes need: 6 per matmul
    parameter a token touches (2 forward, 4 backward), **once for each
    of the ``steps`` applications**: attention's four projections and
    the gated part's three of every layer, and the head, which reads
    every step's states. Plus attention's scores and value products
    over the causal half, 12 * head_dim a pair and query head (QK^T and
    PV, forward and backward), for each of the L x steps layer
    applications. The embedding gather, the rotations, the norms and
    the gate's 2,048 weights are not counted. No recomputation."""
    z = _sizes(spec)
    C, T = z["C"], int(cell["sequence_length"])
    layer = 2 * C * z["Hq"] * z["D"] + 2 * C * z["Hkv"] * z["D"] \
        + 3 * C * z["F"]
    matmul = z["steps"] * (z["L"] * layer + z["V"] * C)
    pairs = sum(attention_pairs(T) for _ in z["windows"])
    per_sequence = 6 * matmul * T + 12 * z["D"] * z["Hq"] * pairs
    return per_sequence * cell["clients_per_round"] \
        * cell["local_batch_size"]

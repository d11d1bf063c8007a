"""GPT-2 with double heads in plain ``jax.numpy`` float32: the reference
of the ``gpt2-124m-personachat`` configuration.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"; openai ``gpt2``
``config.json``: 12 layers, 768 wide, 12 heads, 1024 positions,
layer-norm epsilon 1e-5, ``gelu_new``) and the double-heads fine-tuning
of the source (CommEfficient ``gpt2_train.py:88-99``, after Wolf et al.'s
transfer-learning-conv-ai):

    h   = wte[ids] + wpe[pos] + wte[token_types]
    per block: h += proj(attn(ln_1(h)));  h += mlp(ln_2(h))
        attn: causal softmax(q k^T / sqrt(64)) v over 12 heads
        mlp:  768 -> 3072, gelu (tanh form), 3072 -> 768
    h   = ln_f(h)
    LM:  logits = h wte^T (tied); position t predicts token t+1;
         per example, the mean NLL over its labelled positions
         (labels -1 are ignored) across its candidates
    MC:  a linear 768 -> 1 on h at ``mc_token_ids`` of each candidate;
         cross-entropy over the candidates
    loss of an example = lm_coef * LM + mc_coef * MC; a client's loss is
    the masked mean over its examples.

No flax, no kernel, nothing of the program. Parameter names are those
flax gives the program's module, so the builder can hand the same
weights to both; the builder checks names and shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: clients differentiated together by ``lib/fetchsgd_ref.follow``
CLIENTS_PER_BLOCK = 1

# Limits of ``correct``: see the note in ``resnet9-cifar10.py``; the
# readings these were set from are in PERF.md section 2.
LIMITS = {
    "loss_gap": 0.003,        # a part of the batch left out
    "grad_norm_gap": 0.003,   # a gradient scaled or partly dropped
    "grad_rel_l2": 0.022,     # computing below bf16
    "delta_norm_gap": 0.2,    # a step that returns its state unchanged
}


def init_params(key, spec):
    """GPT-2's initialisation (normal, std 0.02; layer-norm scale 1,
    every bias 0) from ``key``, float32, in one traced call."""
    C, L = int(spec["n_embd"]), int(spec["n_layer"])
    V, P = int(spec["vocab_size"]), int(spec["n_positions"])
    std = float(spec.get("initializer_range", 0.02))
    keys = iter(jax.random.split(key, 4 * L + 3))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def dense(cin, cout):
        return {"kernel": normal((cin, cout)),
                "bias": jnp.zeros((cout,), jnp.float32)}

    def ln():
        return {"scale": jnp.ones((C,), jnp.float32),
                "bias": jnp.zeros((C,), jnp.float32)}

    tr = {"wte": normal((V, C)), "wpe": normal((P, C)), "ln_f": ln()}
    for i in range(L):
        tr[f"h_{i}"] = {
            "ln_1": ln(), "ln_2": ln(),
            "attn": {"c_attn": dense(C, 3 * C), "c_proj": dense(C, C)},
            "mlp": {"c_fc": dense(C, 4 * C), "c_proj": dense(4 * C, C)}}
    return {"transformer": tr, "mc_head": dense(C, 1)}


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p, q):
    return q(x) @ q(p["kernel"]) + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params, ids, types, spec, q):
    """(S, T) token and token-type ids -> (S, T, C) final hidden."""
    tr = params["transformer"]
    S, T = ids.shape
    H = int(spec["n_head"])
    eps = float(spec.get("layer_norm_epsilon", 1e-5))
    h = tr["wte"][ids] + tr["wpe"][:T][None] + tr["wte"][types]
    C = h.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(int(spec["n_layer"])):
        b = tr[f"h_{i}"]
        qkv = _dense(_ln(h, b["ln_1"], eps), b["attn"]["c_attn"], q)
        qh, kh, vh = (a.reshape(S, T, H, C // H).transpose(0, 2, 1, 3)
                      for a in jnp.split(qkv, 3, axis=-1))
        att = (q(qh) @ q(kh).transpose(0, 1, 3, 2)) / jnp.sqrt(
            jnp.float32(C // H))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        out = (q(att) @ q(vh)).transpose(0, 2, 1, 3).reshape(S, T, C)
        h = h + _dense(out, b["attn"]["c_proj"], q)
        m = _gelu(_dense(_ln(h, b["ln_2"], eps), b["mlp"]["c_fc"], q))
        h = h + _dense(m, b["mlp"]["c_proj"], q)
    return _ln(h, tr["ln_f"], eps)


def client_loss(params, b, spec, q=lambda a: a):
    """One client's masked-mean double-heads loss. ``b``: input_ids,
    token_type_ids, lm_labels (B, N, T); mc_token_ids (B, N);
    mc_labels (B,); mask (B,)."""
    B, N, T = b["input_ids"].shape
    h = hidden(params, b["input_ids"].reshape(B * N, T),
               b["token_type_ids"].reshape(B * N, T), spec, q)
    wte = params["transformer"]["wte"]
    logits = q(h[:, :-1]) @ q(wte).T                       # (BN, T-1, V)
    labels = b["lm_labels"].reshape(B * N, T)[:, 1:]
    valid = (labels != -1).astype(jnp.float32)
    safe = jnp.where(labels != -1, labels, 0)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, safe[..., None], axis=-1)[..., 0]
    lm = jnp.sum((nll * valid).reshape(B, -1), axis=1) / jnp.maximum(
        jnp.sum(valid.reshape(B, -1), axis=1), 1.0)

    idx = jnp.clip(b["mc_token_ids"].reshape(B * N), 0, T - 1)
    cls = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
    mc_logits = (cls @ params["mc_head"]["kernel"]
                 + params["mc_head"]["bias"])[:, 0].reshape(B, N)
    mc = jax.nn.logsumexp(mc_logits, axis=-1) - jnp.take_along_axis(
        mc_logits, b["mc_labels"][:, None], axis=-1)[:, 0]

    losses = float(spec.get("lm_coef", 1.0)) * lm \
        + float(spec.get("mc_coef", 1.0)) * mc
    return jnp.sum(losses * b["mask"]) / jnp.maximum(
        jnp.sum(b["mask"]), 1.0)


def train_flops_per_round(spec, cell):
    """FLOPs one round's forward and backward passes need: 6 per
    matmul parameter per token (2 forward, 4 backward; the tied
    embedding counts once, as the head it multiplies through, the
    position table not at all) plus attention's 6 * L * T * C per token
    (QK^T and PV, forward and backward, the causal half only), no
    recomputation counted, times the tokens of a round (every position
    of the padded length, as the shapes have it)."""
    C, L = int(spec["n_embd"]), int(spec["n_layer"])
    V = int(spec["vocab_size"])
    T = int(cell["sequence_length"])
    matmul_params = L * 12 * C * C + V * C
    per_token = 6 * matmul_params + 6 * L * T * C
    tokens = (cell["clients_per_round"] * cell["local_batch_size"]
              * cell["num_candidates"] * T)
    return per_token * tokens

"""A pipeline stage's share of an Ouro looped language model
(``model_type`` ``ouro``: ByteDance/Ouro-2.6B's ``config.json``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741), in
flax: a stack of dense blocks applied ``total_ut_steps`` times **on the
same parameters**, the final norm inside the loop, one exit gate shared
by the steps, and the expected next-token loss over the steps' exit
distribution.

    block (four RMSNorms, "sandwich"):
        a = x + N2(Attn(N1(x)));   y = a + N4(MLP(N3(a)))
        Attn: q, k, v = n W_q, n W_k, n W_v (heads of ``head_dim``, no
              bias), RoPE on q and k (``rotate_half`` over the whole
              head, ``rope_theta``), causal softmax(q k^T / sqrt(D)) v,
              W_o:                  models/mixers.py ``GQAttention``
        MLP:  W_d (silu(W_g n) * W_u n):  models/mixers.py ``GatedMLP``
    loop:   x_0 = E[ids];  for t = 1 .. steps:
              x <- block_{L-1}( ... block_0(x));  h_t = N_f(x);  x <- h_t
    gate:   lambda_t = sigmoid(h_t . w_e + b_e)        a position
    exit:   p_t = lambda_t prod_{j<t} (1 - lambda_j),  t < steps;
            p_steps = prod_{j<steps} (1 - lambda_j)    (what is left)
    loss:   l_t = the next token's NLL under softmax(W_head h_t);
            L = sum_t p_t l_t - ``entropy_beta`` H(p),  a position;
            a sequence's loss is the mean of L over its T - 1
            predicting positions

Each weight of the stack and the head is read ``steps`` times a pass
and its gradient is the sum of as many addends; under ``remat`` the
backward keeps ``steps`` x L block inputs. The paper's second stage
(the gate trained apart from the stack) and early exit in evaluation
(the config's ``early_exit_threshold``, which nothing here reads) are
not built: every step always runs.

The equations are restated in plain ``jax.numpy`` in
``benchmark/reference/ouro-2.6b-pp6-l8.py``, the float32 reference this
module is tested against (``tests/test_ouro.py``). Norms, the
rotations, softmax statistics, the gate, the exit distribution and its
entropy are float32 whatever ``dtype`` is.

- **The loop's form.** One ``nn.scan`` over the steps with the
  parameters broadcast: a program of L blocks in a loop whose backward
  adds a step's gradient to the one accumulator as it goes. The plain
  reference is the unrolled form, and the tests hold this one to it.
  (Unrolled, ``steps`` x L blocks in the program, the cell's round on
  the chip was 2.5 % longer, 600.0 against 585.0 ms, with four times
  the Mosaic calls to compile and 0.16 GB less at the peak: PERF.md
  section 6, PR 48.)
- **The share.** ``layer_types`` is the stage's layers; every layer,
  the vocabulary and every head are whole. The loop runs over the held
  layers; nothing stands in for the absent stages.

Scopes (``PERF.md`` section 3): ``ut_loop`` (the ``steps`` passes over
the stack, final norms included) > the blocks' ``rope``, ``gqa_attn``,
``dense_mlp``; ``exit_gate`` (gates, exit distribution, entropy); the
head's ``lm_head`` is ``lm_nll_sums_chunked``'s, which takes the exit
distribution as its position weights.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models import register_model
from commefficient_tpu.models.mixers import (GatedMLP, GQAttention, Weights,
                                             attn_plan)
from commefficient_tpu.models.norms import RMSNorm

#: a client's counts, which ``causal_lm_loss`` returns beside the loss:
#: the loop's steps and its layer applications (steps x layers); the
#: mean over predicting positions of sum_t t p_t and of p_steps; and,
#: counted once per layer *application*, the layers the flash kernel
#: built and the (query, key) scores computed and needed
#: (``models/mixers.py attn_plan``)
STATS = ("loop_steps", "loop_layer_applications", "loop_expected_steps",
         "loop_exit_mass_last", "attn_kernel_layers", "attn_pairs",
         "attn_pairs_needed")

#: how ``FedModel`` folds them into the round record's counters
COUNTERS = (("loop.steps", np.max), ("loop.layer_applications", np.max),
            ("loop.expected_steps", np.mean),
            ("loop.exit_mass_last", np.mean),
            ("attn.kernel_layers", np.max), ("attn.pairs", np.sum),
            ("attn.pairs_needed", np.sum))

PUBLISHED_LAYER_TYPES = ("full_attention",) * 48


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    #: the entropy term's weight (the config carries none: assumed)
    entropy_beta: float = 0.1
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    remat: bool = False

    @staticmethod
    def tiny() -> "OuroConfig":
        """Test-scale: two layers, two heads, four steps, nothing
        wide."""
        return OuroConfig(
            vocab_size=96, hidden_size=64, intermediate_size=48,
            layer_types=("full_attention",) * 2, num_attention_heads=2,
            num_key_value_heads=2, head_dim=32)

    @staticmethod
    def from_hf(blob: dict) -> "OuroConfig":
        """From a ``config.json`` of the cut: the published keys."""
        if blob.get("model_type", "ouro") != "ouro":
            raise ValueError(f"model_type {blob['model_type']!r} is not "
                             "'ouro'")
        if blob.get("use_sliding_window", False):
            raise ValueError("use_sliding_window true: every layer sees "
                             "its whole past here, as published")
        if blob.get("rope_scaling") is not None:
            raise ValueError(f"rope_scaling {blob['rope_scaling']!r}: only "
                             "plain RoPE (null) is built")
        if blob.get("tie_word_embeddings", False):
            raise ValueError("tie_word_embeddings true: embedding and "
                             "head are two matrices here, as published")
        if blob.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {blob['hidden_act']!r}: the "
                             "gated part is SiLU's")
        fields = {f.name for f in dataclasses.fields(OuroConfig)}
        kw = {k: v for k, v in blob.items() if k in fields}
        for key in ("dtype", "remat"):
            kw.pop(key, None)
        kw["layer_types"] = tuple(blob["layer_types"])
        cfg = OuroConfig(**kw)
        unknown = set(cfg.layer_types) - {"full_attention"}
        if unknown:
            raise ValueError(f"no layer for layer_types {sorted(unknown)}: "
                             "only 'full_attention' is built")
        layers = blob.get("num_hidden_layers", len(cfg.layer_types))
        if layers != len(cfg.layer_types):
            raise ValueError(f"num_hidden_layers {layers} is not the "
                             f"length of layer_types {len(cfg.layer_types)}")
        if cfg.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {cfg.total_ut_steps}")
        return cfg

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    def reference_spec(self) -> dict:
        """The same sizes under the keys the plain reference reads."""
        spec = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("dtype", "remat")}
        spec.update(layer_types=list(self.layer_types),
                    num_hidden_layers=self.num_hidden_layers)
        return spec


# --- layers ---------------------------------------------------------------

class Block(Weights):
    """The sandwich block: a norm before and after each of attention
    and the gated part."""

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype

        def norm(name, v):
            return RMSNorm(cfg.rms_norm_eps, name=name)(v).astype(dt)

        a = GQAttention(cfg, rope_theta=cfg.rope_theta,
                        name="attn")(norm("norm1", x))
        x = x + norm("norm2", a)
        m = GatedMLP(cfg, cfg.intermediate_size,
                     name="mlp")(norm("norm3", x))
        return x + norm("norm4", m)


class Stack(Weights):
    """One pass: the held blocks, then the final norm. ``(what the next
    step takes, h_t float32)``; the second argument is ``nn.scan``'s."""

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        block_cls = nn.remat(Block) if cfg.remat else Block
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, name=f"layer_{i}")(x)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        return h.astype(cfg.dtype), h


class ExitGate(Weights):
    """(steps, S, T, C) float32 final states -> (steps, S, T) gate
    logits ``h . w_e + b_e``: one linear map with a bias, shared by the
    steps, float32."""

    @nn.compact
    def __call__(self, hs):
        w = self.mat("kernel", (hs.shape[-1], 1))
        b = self.param("bias", nn.initializers.zeros, (1,))
        with jax.named_scope("exit_gate"):
            return jnp.einsum("nstc,c->nst", hs, w[:, 0],
                              precision=jax.lax.Precision.HIGHEST) + b[0]


def exit_distribution(gates):
    """(steps, ...) gate logits -> (log p, p), float32, p summing to 1
    over the steps: ``p_t = sigmoid(g_t) prod_{j<t} sigmoid(-g_j)`` for
    t < steps and the last step what is left (its own logit is read by
    nothing), from ``log_sigmoid`` of plus and minus the logits."""
    g = gates.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(g[:1]), stay], axis=0)
    logp = jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]) + before[:-1], before[-1:]], axis=0)
    return logp, jnp.exp(logp)


@register_model("OuroLM")
class OuroLM(nn.Module):
    """(S, T) token ids -> (the steps' final hidden states (steps, S,
    T, C) float32, head weight (V, C), gate logits (steps, S, T)
    float32, the attention layers' counts). The head is applied by the
    loss in token chunks (``models/gpt2.py lm_nll_sums_chunked``), to
    every step's states, so no (tokens, vocab) logits tensor exists."""
    cfg: OuroConfig = OuroConfig()

    #: ``config.json``'s ``model_type`` and its reader, for the trainer
    model_type = "ouro"
    config_class = OuroConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T = input_ids.shape
        steps = cfg.total_ut_steps
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", init,
                          (cfg.vocab_size, cfg.hidden_size))
        x = embed[input_ids].astype(dt)
        with jax.named_scope("ut_loop"):
            _, hs = nn.scan(
                Stack, variable_broadcast="params",
                split_rngs={"params": False}, length=steps)(
                cfg, name="stack")(x, None)
        gates = ExitGate(cfg, name="exit_gate")(hs)
        # as ``gqa_attention`` builds each application, from the shapes
        plan = attn_plan(S, T, cfg.num_attention_heads,
                         head_dim=cfg.head_dim)
        applied = steps * cfg.num_hidden_layers
        heads = S * cfg.num_attention_heads * applied
        attn = (applied * (plan.kernel is not None), heads * plan.pairs,
                heads * plan.needed)
        return hs, head, gates, tuple(jnp.float32(v) for v in attn)


def exit_loss(cfg, hs, head, gates, input_ids, tokens_per_chunk=1024):
    """The steps' states (steps, S, T, C), the head and the gate logits
    (steps, S, T) -> ((S,) losses: the expected next-token NLL over the
    exit distribution minus ``entropy_beta`` times its entropy, averaged
    over the T - 1 predicting positions; the mean of sum_t t p_t; the
    mean of p_steps)."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    steps, S, T, C = hs.shape
    with jax.named_scope("exit_gate"):
        logp, p = exit_distribution(gates[:, :, :-1])
        entropy = -jnp.sum(p * logp, axis=(0, 2))                  # (S,)
        t = jnp.arange(1, steps + 1, dtype=jnp.float32)[:, None, None]
        expected, last = jnp.mean(jnp.sum(t * p, axis=0)), jnp.mean(p[-1])
    # the steps' streams are further examples of the one head: each
    # position's nll is weighted by its step's exit probability
    sn, _ = lm_nll_sums_chunked(
        hs[:, :, :-1].reshape(steps * S, T - 1, C), head,
        jnp.tile(input_ids[:, 1:], (steps, 1)), cfg.dtype,
        ignore_index=None, tokens_per_chunk=tokens_per_chunk,
        weights=p.reshape(steps * S, T - 1))
    losses = (jnp.sum(sn.reshape(steps, S), axis=0)
              - cfg.entropy_beta * entropy) / (T - 1)
    return losses, expected, last


def causal_lm_loss(module, params, input_ids, tokens_per_chunk=1024):
    """Per-sequence loss (``exit_loss``) and the ``STATS`` scalars."""
    cfg = module.cfg
    hs, head, gates, attn = module.apply({"params": params}, input_ids)
    losses, expected, last = exit_loss(cfg, hs, head, gates, input_ids,
                                       tokens_per_chunk)
    steps = hs.shape[0]
    loop = (jnp.float32(steps),
            jnp.float32(steps * cfg.num_hidden_layers), expected, last)
    return losses, loop + attn

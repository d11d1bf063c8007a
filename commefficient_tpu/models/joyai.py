"""One chip's share of a JoyAI-LLM-Flash layer stack, in flax: MLA
(latent attention), a leading dense SwiGLU layer, sigmoid-routed expert
layers with a shared expert, a depth-1 multi-token-prediction module,
untied embedding and head over a vocabulary slice.

The block is DeepSeek-V3's (arXiv:2412.19437) as JoyAI-LLM-Flash's
``config.json`` sizes it; the equations are restated in
``benchmark/reference/joyai-llm-flash-ep32.py``, the plain float32
reference this module is tested against. What is TPU-shaped here:

- **The expert layer is the expert-parallel layer on one chip**
  (``models/moe.py``, shared with ``models/nemotron_h.py``): it is told
  which experts it holds (``n_held_experts`` from ``expert_offset``),
  routes over all ``n_router_experts`` in float32 and computes its own
  experts' part with ``routed_experts``, three ragged products a pass.
  With the experts of one chip of 32 a token lands here 0.25 times in
  expectation, so one pass at a quarter full is the rule.
- bf16 compute on float32 parameters as ``models/gpt2.py``: norms,
  RoPE, softmax, router scores and selection in float32.
- **On the chip the latent attention is one flash kernel a layer**
  (``models/mixers.py gqa_attention``, the code the other LMs'
  attention runs): q = [q_nope | RoPE(q_rope)] and k = [k_nope |
  RoPE(k_rope), the same for every head] are 192 wide, v 128, a group
  of one query head a key/value head, so the float32 (heads, T, T)
  scores never leave VMEM. The kernel takes no scale: it is folded
  into q where q is still float32 (the ``q_b`` product's accumulator),
  so q is rounded to bf16 once. Off the chip, and where T is not
  whole tiles, the two score products on a materialised float32 score
  tensor stay as they were (``mla_plan``: the platform and the shapes
  decide, no flag). RoPE is ``rope`` in both.

Scopes (``PERF.md`` section 3): ``mla_attn`` (RoPE, then either the
two score products, softmax and the value product, or the
concatenations to the kernel's operands, its transposes and its three
device operations; the latent projections outside it), ``moe_route`` (scores,
top-k, dispatch order and gather), ``moe_experts`` (the ragged
products), ``moe_combine`` (gates, scatter-add, shared expert add),
``mtp``; the heads' ``lm_head`` is ``lm_nll_sums_chunked``'s. Inside
``routed_experts`` the gathers are ``moe_route`` and the scatter-adds
``moe_combine``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models import register_model
from commefficient_tpu.models.mixers import attn_plan, gqa_attention
from commefficient_tpu.models.norms import RMSNorm
from commefficient_tpu.models.moe import (MOE_COUNTERS, MOE_STATS,
                                          client_stats, dispatch, fold_stats,
                                          layer_stats, no_stats, route,
                                          routed_experts)

#: a client's counts, which ``causal_lm_loss`` returns beside the loss:
#: ``models/moe.py``'s six; how many latent-attention layers (the MTP
#: module's among them) the flash kernel built (``mla_plan``: on a TPU,
#: T whole tiles); the (query, key) scores the client's attention
#: computes over heads, sequences and layers, and how many of them the
#: causal mask needs
STATS = MOE_STATS + ("attn_kernel_layers", "attn_pairs", "attn_pairs_needed")

#: how ``FedModel`` folds them into the round record's counters
COUNTERS = MOE_COUNTERS + (
    ("attn.kernel_layers", np.max), ("attn.pairs", np.sum),
    ("attn.pairs_needed", np.sum))


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280          # rows held of embedding and head
    hidden_size: int = 2048
    num_hidden_layers: int = 40       # dense + expert layers, no MTP
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32     # heads held
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_router_experts: int = 256       # the router's published width
    n_held_experts: int = 256         # experts whose weights are here
    expert_offset: int = 0            # id of the first of them
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32e6
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    remat: bool = False

    @staticmethod
    def tiny() -> "JoyAIConfig":
        """Test-scale: every mechanism present, nothing wide."""
        return JoyAIConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=48, moe_intermediate_size=16,
            n_router_experts=64, n_held_experts=4, expert_offset=8,
            num_experts_per_tok=4)

    @staticmethod
    def from_hf(blob: dict) -> "JoyAIConfig":
        """From a ``config.json`` of the cut: the published keys, with
        ``n_routed_experts`` the experts held, ``router_experts`` the
        router's width (default: the same) and ``expert_offset``."""
        fields = {f.name for f in dataclasses.fields(JoyAIConfig)}
        kw = {k: v for k, v in blob.items() if k in fields}
        held = int(blob.get("n_routed_experts", 256))
        kw.update(n_held_experts=held,
                  n_router_experts=int(blob.get("router_experts", held)))
        kw.pop("dtype", None)
        return JoyAIConfig(**kw)

    def reference_spec(self) -> dict:
        """The same sizes under the keys the reference reads."""
        spec = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("dtype", "remat", "n_router_experts",
                                  "n_held_experts")}
        spec.update(n_routed_experts=self.n_held_experts,
                    router_experts=self.n_router_experts)
        return spec


# --- layers ---------------------------------------------------------------

def _init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


def rope(x, theta):
    """Rotate the interleaved pairs of the last axis; x: (S, T, ..., D),
    float32."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (D // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


class _Weights(nn.Module):
    """Declares matrices under the reference's names; no ``Dense``:
    there are no biases, and several are used as stacks."""
    cfg: JoyAIConfig

    def mat(self, name, shape, std=None):
        init = _init(self.cfg) if std is None \
            else nn.initializers.normal(stddev=std)
        return self.param(name, init, shape)


def mla_plan(cfg, S, T):
    """How a latent-attention layer of (S, T) is built, as
    ``gqa_attention`` would build it from the platform and the shapes
    (``models/mixers.py attn_plan``): with the plan's ``kernel`` the
    flash kernel on 192-wide q and k and 128-wide v, without it the
    dense form."""
    return attn_plan(S, T, cfg.num_attention_heads, head_dim=(
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim))


def mla_attention(cfg, qh, kvh, k_rope, kernel):
    """What ``mla_attn`` holds: ``qh`` (S, T, H, dn + dr) = [q_nope |
    q_rope], ``kvh`` (S, T, H, dn + dv) = [k_nope | v], ``k_rope``
    (S, T, dr), the same for every head -> (S, T, H, dv) in
    ``cfg.dtype``. RoPE in float32 on both rotated parts, then, with
    ``kernel`` (``mla_plan``'s), one 192-wide score product through
    ``gqa_attention`` at a group of one query head a key/value head
    (the kernel takes no scale: q gets it here in float32, which is
    what ``MLA`` hands over there, the ``q_b`` product's accumulator,
    so q is rounded to ``cfg.dtype`` once, and ``gqa_attention`` is
    told ``scale`` None: q carries it); without it the two score
    products, their float32 (S, H, T, T) sum scaled and masked, the
    plain softmax and the value product."""
    dt, dn = cfg.dtype, cfg.qk_nope_head_dim
    S, T, H, _ = qh.shape
    scale = float(qh.shape[-1] ** -0.5)
    if kernel:
        qh = qh.astype(jnp.float32) * scale
    qr = rope(qh[..., dn:].astype(jnp.float32), cfg.rope_theta)
    kr = rope(k_rope.astype(jnp.float32), cfg.rope_theta)
    if kernel:
        q = jnp.concatenate([qh[..., :dn], qr], axis=-1).astype(dt)
        k = jnp.concatenate([kvh[..., :dn], jnp.broadcast_to(
            kr.astype(dt)[:, :, None], (S, T, H, kr.shape[-1]))], axis=-1)
        return gqa_attention(q[:, :, :, None], k, kvh[..., dn:], None)[0][
            :, :, :, 0]
    att = (jnp.einsum("sthd,suhd->shtu", qh[..., :dn], kvh[..., :dn],
                      preferred_element_type=jnp.float32)
           + jnp.einsum("sthd,sud->shtu", qr.astype(dt), kr.astype(dt),
                        preferred_element_type=jnp.float32)) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    return jnp.einsum("shtu,suhd->sthd", att.astype(dt), kvh[..., dn:])


class MLA(_Weights):
    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T, C = x.shape
        H, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
        dv, r = cfg.v_head_dim, cfg.kv_lora_rank
        q_a = self.mat("q_a", (C, cfg.q_lora_rank))
        cq = RMSNorm(cfg.rms_norm_eps, name="q_norm")(x @ q_a.astype(dt))
        q_b = self.mat("q_b", (cfg.q_lora_rank, H * (dn + dr)))
        kv_a = self.mat("kv_a", (C, r + dr))
        kv = x @ kv_a.astype(dt)
        ckv = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(kv[..., :r])
        kv_b = self.mat("kv_b", (r, H * (dn + dv)))
        o = self.mat("o", (H * dv, C))
        kernel = mla_plan(cfg, S, T).kernel
        # beside the kernel q stays the product's float32 accumulator:
        # ``mla_attention`` folds the scale in before the one rounding
        qh = jnp.matmul(cq.astype(dt), q_b.astype(dt),
                        preferred_element_type=jnp.float32 if kernel
                        else None).reshape(S, T, H, dn + dr)
        kvh = (ckv.astype(dt) @ kv_b.astype(dt)).reshape(S, T, H, dn + dv)
        with jax.named_scope("mla_attn"):
            out = mla_attention(cfg, qh, kvh, kv[..., r:], kernel)
        return out.reshape(S, T, H * dv) @ o.astype(dt)


class SwiGLU(_Weights):
    width: int = 0

    @nn.compact
    def __call__(self, x):
        dt, C = self.cfg.dtype, x.shape[-1]
        gate = self.mat("gate", (C, self.width))
        up = self.mat("up", (C, self.width))
        down = self.mat("down", (self.width, C))
        return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) \
            @ down.astype(dt)


class _Experts(_Weights):
    @nn.compact
    def __call__(self):
        cfg = self.cfg
        E, C, F = (cfg.n_held_experts, cfg.hidden_size,
                   cfg.moe_intermediate_size)
        return (self.mat("gate", (E, C, F)), self.mat("up", (E, C, F)),
                self.mat("down", (E, F, C)))


class ExpertLayer(_Weights):
    """The expert layer of one chip: ``(y, stats)`` with ``stats`` =
    ``models/moe.py layer_stats``' five float32 counts."""

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        N, C = x.shape
        E, k = cfg.n_held_experts, cfg.num_experts_per_tok
        router = self.mat("router", (C, cfg.n_router_experts))
        bias = self.mat("router_bias", (cfg.n_router_experts,))
        gate_w, up_w, down_w = _Experts(cfg, name="experts")()
        with jax.named_scope("moe_route"):
            top, g = route(x, router, bias, k, cfg.routed_scaling_factor,
                           cfg.norm_topk_prob)                # (N, k)
            self.sow("intermediates", "top", top)
            token, gate, load = dispatch(top, g, cfg.expert_offset, E)
        share = E / cfg.n_router_experts
        routed = routed_experts(x.astype(dt), token, gate, load,
                                (gate_w, up_w, down_w), "swiglu", share)
        shared = SwiGLU(cfg, cfg.moe_intermediate_size
                        * cfg.n_shared_experts, name="shared")(x)
        with jax.named_scope("moe_combine"):
            y = (routed + shared.astype(jnp.float32)).astype(dt)
        return y.reshape(shape), layer_stats(load, N, k, share)


class Block(nn.Module):
    cfg: JoyAIConfig
    moe: bool = True

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        x = x + MLA(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x).astype(dt))
        h = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x).astype(dt)
        if not self.moe:
            return x + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h), \
                no_stats()
        y, stats = ExpertLayer(cfg, name="moe")(h)
        return x + y, stats


class MTPModule(_Weights):
    """Depth-1 multi-token prediction: position t joins the trunk's
    h_t with the embedding of x_{t+1}; its hidden predicts x_{t+2}."""

    @nn.compact
    def __call__(self, h, emb_next, block_cls):
        cfg, dt = self.cfg, self.cfg.dtype
        C = cfg.hidden_size
        eh = self.mat("eh_proj", (2 * C, C))
        x = jnp.concatenate(
            [RMSNorm(cfg.rms_norm_eps, name="hnorm")(h),
             RMSNorm(cfg.rms_norm_eps, name="enorm")(emb_next)],
            axis=-1).astype(dt) @ eh.astype(dt)
        x, stats = block_cls(cfg, moe=True, name="block")(x)
        return RMSNorm(cfg.rms_norm_eps, name="norm")(x), stats


@register_model("JoyAIFlashLM")
class JoyAIFlashLM(nn.Module):
    """(S, T) token ids -> (final hidden (S, T, C) float32, MTP hidden
    or None, head weight (V, C), the expert layers' ``layer_stats`` folded
    over layers).
    The heads are applied by the loss in token chunks
    (``models/gpt2.py lm_nll_sums_chunked``), so no (tokens, vocab)
    logits tensor exists."""
    cfg: JoyAIConfig = JoyAIConfig()

    #: ``config.json``'s ``model_type`` and its reader, for the trainer
    model_type = "joyai_llm_flash"
    config_class = JoyAIConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg, dt = self.cfg, self.cfg.dtype
        embed = self.param("embed", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", _init(cfg),
                          (cfg.vocab_size, cfg.hidden_size))
        block_cls = nn.remat(Block) if cfg.remat else Block
        h = embed[input_ids].astype(dt)
        stats = no_stats()
        for i in range(cfg.num_hidden_layers):
            h, s = block_cls(cfg, moe=i >= cfg.first_k_dense_replace,
                             name=f"layer_{i}")(h)
            stats = fold_stats(stats, s)
        final = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        mtp = None
        for i in range(cfg.num_nextn_predict_layers):
            # the sequence's last position has no next token: it wraps
            # to the first one, and the loss leaves it out (causal
            # attention: no other position sees it)
            with jax.named_scope("mtp"):
                nxt = embed[jnp.roll(input_ids, -1, axis=1)].astype(dt)
                mtp, s = MTPModule(cfg, name=f"mtp_{i}")(h, nxt, block_cls)
            stats = fold_stats(stats, s)
        return final, mtp, head, stats


def causal_lm_loss(module, params, input_ids, tokens_per_chunk=1024):
    """Per-sequence loss (main head's mean NLL + ``mtp_loss_weight`` x
    the MTP head's) and the ``STATS`` scalars."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    cfg = module.cfg
    final, mtp, head, stats = module.apply({"params": params}, input_ids)
    sn, sv = lm_nll_sums_chunked(final[:, :-1], head, input_ids[:, 1:],
                                 cfg.dtype, ignore_index=None,
                                 tokens_per_chunk=tokens_per_chunk)
    loss = sn / jnp.maximum(sv, 1.0)
    if mtp is not None:
        with jax.named_scope("mtp"):
            mn, mv = lm_nll_sums_chunked(
                mtp[:, :-2], head, input_ids[:, 2:], cfg.dtype,
                ignore_index=None, tokens_per_chunk=tokens_per_chunk)
        loss = loss + cfg.mtp_loss_weight * mn / jnp.maximum(mv, 1.0)
    expert_layers = (cfg.num_hidden_layers - cfg.first_k_dense_replace
                     + cfg.num_nextn_predict_layers)
    # as ``MLA`` builds each layer, from the shapes alone
    S, T = input_ids.shape
    plan = mla_plan(cfg, S, T)
    layers = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    heads = S * cfg.num_attention_heads * layers
    attn = (layers * (plan.kernel is not None), heads * plan.pairs,
            heads * plan.needed)
    return loss, client_stats(
        stats, expert_layers * cfg.n_held_experts) + tuple(
            jnp.float32(v) for v in attn)

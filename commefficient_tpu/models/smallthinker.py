"""One chip's share of a SmallThinker layer stack (``model_type``
``smallthinker``: PowerInfer/SmallThinker-21BA3B-Instruct's
``config.json``), in flax: every block grouped-query attention *and* a
routed expert part, the attention laid out by two per-layer lists
(``sliding_window_layout``: a window of ``sliding_window_size`` keys or
the whole past; ``rope_layout``: rotary positions or none), and **the
router read from the block's input, before attention**, its picks and
gates used after attention by the expert part; untied embedding and
head over a vocabulary slice.

    r   = x W_r                       float32; x is the block's input
    top = the 6 largest of r;  g = softmax(r[top])
    n   = RMSNorm_1(x);  q, k, v = n W_q, n W_k, n W_v  (heads of 128)
    rope_layout[l] = 1:  q, k <- RoPE(q, k; theta, the whole head)
    s_ij = q_i . k_j / sqrt(128),  j <= i;  sliding_window_layout[l] = 1:
           also i - j < sliding_window_size
    h   = x + (softmax_j(s) v) W_o    (7 query heads share a k/v head)
    m   = RMSNorm_2(h)
    y   = h + sum_{e in top, e held here} g_e W_d^e (relu(W_g^e m) * W_u^e m)
    logits = RMSNorm_f(y_L) W_head

RMSNorm eps ``rms_norm_eps``, no bias anywhere, no shared expert. The
equations are restated, with attention against a mask built from (i, j)
alone, in ``benchmark/reference/smallthinker-21ba3b-ep8.py``, the plain
float32 reference this module is tested against
(``tests/test_smallthinker.py``). The attention is ``models/mixers.py``'s
(``GQAttention`` with a window and rotary positions a layer: a window
layer's cost follows the window), the expert layer ``models/moe.py``'s
(``route(scoring="softmax")``, the ``"reglu"`` form), the code the
Nemotron / Granite and the JoyAI / Nemotron cells run.

- **The share.** ``n_held_experts`` experts from ``expert_offset`` and
  ``vocab_size`` rows are what this chip holds of a layer; attention,
  norms and the router are whole (the GShard layout: expert parallelism
  inside a data-parallel group), every width, the router's outputs and
  its picks a token the published ones. No exchange, and nothing stands
  in for the absent chips.

Scopes (``PERF.md`` section 3): ``moe_route`` (the pre-attention
routing and the dispatch order; the gathers inside ``routed_experts``),
``rope``, ``gqa_attn`` > ``attn_window`` / ``attn_full`` by the layer's
kind, ``moe_experts``, ``moe_combine``; the head's ``lm_head`` is
``lm_nll_sums_chunked``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models import register_model
from commefficient_tpu.models.mixers import GQAttention, Weights, attn_plan
from commefficient_tpu.models.moe import (MOE_COUNTERS, MOE_STATS,
                                          client_stats, dispatch, fold_stats,
                                          layer_stats, no_stats, route,
                                          routed_experts)
from commefficient_tpu.models.norms import RMSNorm

#: a client's counts, which ``causal_lm_loss`` returns beside the loss:
#: ``models/moe.py``'s four; how many layers see a window and how many
#: the whole past (a window of T or more is the whole past); whether
#: any layer was built a block of queries at a time; the keys a query
#: block of a window layer meets; the (query, key) scores the client's
#: attention computes over heads, sequences and layers, and how many of
#: them the causal bands need; 1: the router read the block's input;
#: how many layers the flash kernel built (``models/mixers.py
#: attn_plan``: on a TPU, with no ``attn_query_block`` given)
STATS = MOE_STATS + ("attn_window_layers", "attn_full_layers",
                     "attn_blocked", "attn_window_keys", "attn_pairs",
                     "attn_pairs_needed", "router_pre_attn",
                     "attn_kernel_layers")

#: how ``FedModel`` folds them into the round record's counters
COUNTERS = MOE_COUNTERS + (
    ("attn.window_layers", np.max), ("attn.full_layers", np.max),
    ("attn.blocked", np.max), ("attn.window_keys", np.max),
    ("attn.pairs", np.sum), ("attn.pairs_needed", np.sum),
    ("moe.router_pre_attn", np.max), ("attn.kernel_layers", np.max))

#: the 52 published layers: 0, 4, 8, ... see the whole past and carry no
#: positions, the three after each see 4,096 keys and carry RoPE
PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936          # rows held of embedding and head
    hidden_size: int = 2560
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    rope_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    n_router_experts: int = 64        # the router's published width
    n_held_experts: int = 64          # experts whose weights are here
    expert_offset: int = 0            # id of the first of them
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    remat: bool = False
    #: queries attention takes at a time (None: from the shapes,
    #: ``models/mixers.py``); no part of the architecture
    attn_query_block: Optional[int] = None

    @staticmethod
    def tiny() -> "SmallThinkerConfig":
        """Test-scale: two whole periods, a window shorter than the
        rehearsal's 32-token sequences that is no multiple of the query
        block, both attention forms blocked, nothing wide."""
        return SmallThinkerConfig(
            vocab_size=96, hidden_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8,
            sliding_window_layout=(0, 1, 1, 1) * 2,
            rope_layout=(0, 1, 1, 1) * 2, sliding_window_size=12,
            n_router_experts=16, n_held_experts=4, expert_offset=4,
            moe_num_active_primary_experts=6, moe_ffn_hidden_size=24,
            attn_query_block=8)

    @staticmethod
    def from_hf(blob: dict) -> "SmallThinkerConfig":
        """From a ``config.json`` of the cut: the published keys, with
        ``moe_num_primary_experts`` the experts held, ``router_experts``
        the router's width (default: the same) and ``expert_offset``."""
        if blob.get("model_type", "smallthinker") != "smallthinker":
            raise ValueError(f"model_type {blob['model_type']!r} is not "
                             "'smallthinker'")
        if not blob.get("moe_primary_router_apply_softmax", True):
            raise ValueError(
                "moe_primary_router_apply_softmax false: only the "
                "published router (softmax over the chosen logits) is "
                "built")
        if blob.get("tie_word_embeddings", False):
            raise ValueError("tie_word_embeddings true: embedding and "
                             "head are two matrices here, as published")
        fields = {f.name for f in dataclasses.fields(SmallThinkerConfig)}
        kw = {k: v for k, v in blob.items() if k in fields}
        held = int(blob.get("moe_num_primary_experts", 64))
        kw.update(n_held_experts=held,
                  n_router_experts=int(blob.get("router_experts", held)))
        for key in ("sliding_window_layout", "rope_layout"):
            kw[key] = tuple(int(v) for v in blob[key])
        for key in ("dtype", "remat", "attn_query_block"):
            kw.pop(key, None)
        cfg = SmallThinkerConfig(**kw)
        layers = int(blob.get("num_hidden_layers", cfg.num_hidden_layers))
        for key in ("sliding_window_layout", "rope_layout"):
            layout = getattr(cfg, key)
            if len(layout) != layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{key} {list(layout)} is not one 0 or 1 for each of "
                    f"num_hidden_layers {layers}")
        return cfg

    @property
    def num_hidden_layers(self) -> int:
        return len(self.sliding_window_layout)

    def reference_spec(self) -> dict:
        """The same sizes under the keys the plain reference reads."""
        spec = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("dtype", "remat", "attn_query_block",
                                  "n_router_experts", "n_held_experts")}
        spec.update(moe_num_primary_experts=self.n_held_experts,
                    router_experts=self.n_router_experts,
                    num_hidden_layers=self.num_hidden_layers,
                    sliding_window_layout=list(self.sliding_window_layout),
                    rope_layout=list(self.rope_layout))
        return spec

    def window(self, layer: int) -> Optional[int]:
        """The keys a query of ``layer`` sees; None: its whole past."""
        return self.sliding_window_size \
            if self.sliding_window_layout[layer] else None


# --- layers ---------------------------------------------------------------

class _Experts(Weights):
    @nn.compact
    def __call__(self):
        cfg = self.cfg
        E, C, F = (cfg.n_held_experts, cfg.hidden_size,
                   cfg.moe_ffn_hidden_size)
        return (self.mat("gate", (E, C, F)), self.mat("up", (E, C, F)),
                self.mat("down", (E, F, C)))


class Block(Weights):
    """``(h after attention and the expert part, the expert part's
    ``layer_stats``, (whether attention
    was built a block of queries at a time, whether the router read the
    block's input))``. ``router_after_attention`` is the other
    placement, which no published layer has: the tests' and the cell's
    planted fault."""
    layer: int = 0
    router_after_attention: bool = False

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T, C = x.shape
        E, k = cfg.n_held_experts, cfg.moe_num_active_primary_experts
        router = self.mat("router", (C, cfg.n_router_experts))
        weights = _Experts(cfg, name="experts")()
        window = cfg.window(self.layer)

        def routing(tokens):
            with jax.named_scope("moe_route"):
                top, g = route(tokens.reshape(-1, C), router, None, k, 1.0,
                               cfg.norm_topk_prob, scoring="softmax")
                self.sow("intermediates", "top", top)
                return dispatch(top, g, cfg.expert_offset, E)

        if not self.router_after_attention:
            token, gate, load = routing(x)
        n = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x).astype(dt)
        a, blocked = GQAttention(
            cfg, with_form=True, window=window,
            rope_theta=cfg.rope_theta if cfg.rope_layout[self.layer]
            else None,
            kind_scope="attn_full" if window is None else "attn_window",
            query_block=cfg.attn_query_block, name="attn")(n)
        h = x + a
        if self.router_after_attention:
            token, gate, load = routing(h)
        m = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(h).astype(dt)
        share = E / cfg.n_router_experts
        y = routed_experts(m.reshape(-1, C), token, gate, load, weights,
                           "reglu", share)
        with jax.named_scope("moe_combine"):
            out = h + y.astype(dt).reshape(S, T, C)
        return out, layer_stats(load, S * T, k, share), jnp.float32(
            [blocked, not self.router_after_attention])


@register_model("SmallThinkerLM")
class SmallThinkerLM(nn.Module):
    """(S, T) token ids -> (final hidden (S, T, C) float32, head weight
    (V, C), the expert parts' ``layer_stats`` folded over layers, the
    attention layers' counts as ``STATS`` names them). The head is applied by the loss in token
    chunks (``models/gpt2.py lm_nll_sums_chunked``), so no (tokens,
    vocab) logits tensor exists."""
    cfg: SmallThinkerConfig = SmallThinkerConfig()

    #: ``config.json``'s ``model_type`` and its reader, for the trainer
    model_type = "smallthinker"
    config_class = SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg, dt = self.cfg, self.cfg.dtype
        S, T = input_ids.shape
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", init,
                          (cfg.vocab_size, cfg.hidden_size))
        block_cls = nn.remat(Block) if cfg.remat else Block
        h = embed[input_ids].astype(dt)
        stats, blocked, pre = no_stats(), 0.0, 1.0
        for i in range(cfg.num_hidden_layers):
            h, s, b = block_cls(cfg, i, name=f"layer_{i}")(h)
            stats = fold_stats(stats, s)
            blocked, pre = jnp.maximum(blocked, b[0]), jnp.minimum(pre, b[1])
        # as ``gqa_attention`` builds each layer, from the shapes alone
        plans = [attn_plan(S, T, cfg.num_attention_heads, cfg.window(i),
                           cfg.attn_query_block, cfg.head_dim)
                 for i in range(cfg.num_hidden_layers)]
        banded = [p for p in plans if p.banded]
        heads = S * cfg.num_attention_heads
        attn = (len(banded), len(plans) - len(banded), blocked,
                max((p.keys for p in banded), default=0),
                heads * sum(p.pairs for p in plans),
                heads * sum(p.needed for p in plans), pre,
                sum(p.kernel is not None for p in plans))
        return (RMSNorm(cfg.rms_norm_eps, name="norm")(h), head, stats,
                tuple(jnp.float32(v) for v in attn))


def causal_lm_loss(module, params, input_ids, tokens_per_chunk=1024):
    """Per-sequence mean next-token NLL and the ``STATS`` scalars."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    cfg = module.cfg
    final, head, stats, attn = module.apply({"params": params}, input_ids)
    sn, sv = lm_nll_sums_chunked(final[:, :-1], head, input_ids[:, 1:],
                                 cfg.dtype, ignore_index=None,
                                 tokens_per_chunk=tokens_per_chunk)
    return sn / jnp.maximum(sv, 1.0), client_stats(
        stats, cfg.num_hidden_layers * cfg.n_held_experts) + attn

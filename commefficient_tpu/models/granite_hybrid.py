"""A stage of a Granite 4.0-H layer stack (``model_type``
``granitemoehybrid``: ibm-granite/granite-4.0-h-micro's ``config.json``),
in flax: blocks of a mixer *and* a gated feed-forward part, the mixer by
``layer_types`` (``mamba`` a Mamba-2 state-space mixer, ``attention``
grouped-query attention with no positional embedding), four muP-style
multipliers, and one matrix that is both embedding and head.

    h_0   = embedding_multiplier E[ids]
    block:  h += residual_multiplier mixer(RMSNorm_1(h))
            [a | b] = RMSNorm_2(h) W_in
            h += residual_multiplier (silu(a) * b) W_out
    mamba:      models/mixers.py ``Mamba2Mixer`` (one group here: the
                gated norm spans all the heads)
    attention:  models/mixers.py ``GQAttention`` with the scores scaled
                by attention_multiplier (1/64 at heads of 64, not
                1/sqrt(64))
    logits = E RMSNorm(h) / logits_scaling   (tie_word_embeddings)

RMSNorm eps ``rms_norm_eps``, no bias in any linear map, one on the
conv. ``num_local_experts`` is 0 in the dense models of the family;
the routed variants are refused by name (``from_hf``). The equations
are restated, with the recurrence taken one position at a time and
attention whole, in ``benchmark/reference/granite4-h-micro-pp4-v8.py``,
the plain float32 reference this module is tested against
(``tests/test_granite_hybrid.py``).

The mixers are the code ``models/nemotron_h.py`` runs, under its
scopes (``ssm_mixer`` > ``ssm_scan``, ``gqa_attn``) and, from the
shapes alone, in their bounded forms where a layer's heads are all held
(``models/mixers.py``). The share: ``layer_types`` is the stage's layers
and ``vocab_size`` the rows held of the tied matrix; every layer is
whole. No exchange, and nothing stands in for the absent chips.

Scopes (``PERF.md`` section 3): the mixers'; ``dense_mlp`` (the gated
part's two products and the gate; its norm outside it); the head's
``lm_head`` is ``lm_nll_sums_chunked``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models import register_model
from commefficient_tpu.models.mixers import (GatedMLP, GQAttention,
                                             Mamba2Mixer, attn_plan)
from commefficient_tpu.models.norms import RMSNorm

#: a client's counts, which ``causal_lm_loss`` returns beside the loss:
#: the chunks its Mamba-2 mixers scanned (sequences x chunks a sequence
#: x ``mamba`` layers), and which form its attention layers were built
#: in (1 / 0; both 0 with no attention layer), and how many of them
#: the flash kernel built (``models/mixers.py attn_plan``)
STATS = ("ssm_chunks", "attn_blocked", "attn_dense", "attn_kernel_layers")

#: how ``FedModel`` folds them into the round record's counters
COUNTERS = (("ssm.chunks", np.sum), ("attn.blocked", np.max),
            ("attn.dense", np.max), ("attn.kernel_layers", np.max))

#: the 40 published layers: attention at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352          # rows held of the tied matrix
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    dtype: Any = jnp.float32
    remat: bool = False

    # what ``models/mixers.py`` reads, under its names
    mamba_num_heads = property(lambda self: self.mamba_n_heads)
    mamba_head_dim = property(lambda self: self.mamba_d_head)
    n_groups = property(lambda self: self.mamba_n_groups)
    ssm_state_size = property(lambda self: self.mamba_d_state)
    conv_kernel = property(lambda self: self.mamba_d_conv)
    chunk_size = property(lambda self: self.mamba_chunk_size)
    layer_norm_epsilon = property(lambda self: self.rms_norm_eps)
    head_dim = property(
        lambda self: self.hidden_size // self.num_attention_heads)

    @staticmethod
    def tiny() -> "GraniteHybridConfig":
        """Test-scale: one whole period, every multiplier off 1 and the
        score scale off 1 / sqrt(head size), nothing wide."""
        return GraniteHybridConfig(
            vocab_size=96, hidden_size=32,
            layer_types=PUBLISHED_LAYER_TYPES[10:20], mamba_n_heads=8,
            mamba_d_head=8, mamba_d_state=8, mamba_chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, embedding_multiplier=3.0,
            attention_multiplier=0.25, residual_multiplier=0.5,
            logits_scaling=2.0)

    @staticmethod
    def from_hf(blob: dict) -> "GraniteHybridConfig":
        """From a ``config.json`` of the cut: the published keys."""
        if int(blob.get("num_local_experts", 0)) > 0:
            raise ValueError(
                f"num_local_experts {blob['num_local_experts']}: the routed "
                "variants of granitemoehybrid are not built here (the "
                "feed-forward part is shared_intermediate_size alone)")
        if blob.get("position_embedding_type", "nope") != "nope":
            raise ValueError(
                "position_embedding_type "
                f"{blob['position_embedding_type']!r}: only 'nope' (no "
                "positional embedding) is built")
        fields = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
        kw = {k: v for k, v in blob.items() if k in fields}
        kw.pop("dtype", None)
        kw["layer_types"] = tuple(blob["layer_types"])
        cfg = GraniteHybridConfig(**kw)
        unknown = set(cfg.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"no mixer for layer_types {sorted(unknown)}")
        layers = blob.get("num_hidden_layers", len(cfg.layer_types))
        if layers != len(cfg.layer_types):
            raise ValueError(f"num_hidden_layers {layers} is not the "
                             f"length of layer_types {len(cfg.layer_types)}")
        return cfg

    def reference_spec(self) -> dict:
        """The same sizes under the keys the plain reference reads."""
        spec = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("dtype", "remat")}
        spec.update(layer_types=list(self.layer_types),
                    num_hidden_layers=len(self.layer_types))
        return spec

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)


class Block(nn.Module):
    """``(h after both sub-layers, (chunks scanned, attention built
    blocked, attention built dense))``, the counts as the mixer built
    it."""
    cfg: GraniteHybridConfig
    kind: str = "mamba"

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        r = cfg.residual_multiplier
        h = RMSNorm(cfg.rms_norm_eps, name="norm1")(x).astype(dt)
        if self.kind == "mamba":
            y, chunks = Mamba2Mixer(cfg, name="mixer")(h)
            built = (chunks, 0, 0)
        elif self.kind == "attention":
            y, blocked = GQAttention(cfg, scale=cfg.attention_multiplier,
                                     with_form=True, name="mixer")(h)
            built = (0, int(blocked), int(not blocked))
        else:
            raise ValueError(f"no mixer for layer type {self.kind!r}")
        x = x + (r * y).astype(dt)
        h = RMSNorm(cfg.rms_norm_eps, name="norm2")(x).astype(dt)
        y = GatedMLP(cfg, cfg.shared_intermediate_size, name="mlp")(h)
        return x + (r * y).astype(dt), built


@register_model("GraniteHybridLM")
class GraniteHybridLM(nn.Module):
    """(S, T) token ids -> (final hidden (S, T, C) float32 divided by
    ``logits_scaling``, head weight (V, C): the embedding itself where
    tied, ``STATS``). The head is applied by the loss in token chunks
    (``models/gpt2.py lm_nll_sums_chunked``), so no (tokens, vocab)
    logits tensor exists; the tied matrix is one parameter leaf, whose
    gradient is the sum of both uses."""
    cfg: GraniteHybridConfig = GraniteHybridConfig()

    #: ``config.json``'s ``model_type`` and its reader, for the trainer
    model_type = "granitemoehybrid"
    config_class = GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg, dt = self.cfg, self.cfg.dtype
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = embed if cfg.tie_word_embeddings else self.param(
            "lm_head", init, (cfg.vocab_size, cfg.hidden_size))
        block_cls = nn.remat(Block) if cfg.remat else Block
        h = (cfg.embedding_multiplier * embed[input_ids]).astype(dt)
        built = (0, 0, 0)
        for i, kind in enumerate(cfg.layer_types):
            h, b = block_cls(cfg, kind, name=f"layer_{i}")(h)
            built = tuple(x + y for x, y in zip(built, b))
        chunks, blocked, dense = (jnp.float32(x) for x in built)
        # as ``gqa_attention`` builds the attention layers, from the shapes
        plan = attn_plan(*input_ids.shape, cfg.num_attention_heads,
                         head_dim=cfg.head_dim)
        kernel = cfg.layer_types.count("attention") * (
            plan.kernel is not None)
        final = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        return (final / cfg.logits_scaling, head,
                (chunks, jnp.minimum(blocked, 1), jnp.minimum(dense, 1),
                 jnp.float32(kernel)))


def causal_lm_loss(module, params, input_ids, tokens_per_chunk=1024):
    """Per-sequence mean next-token NLL and the ``STATS`` scalars."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    final, head, stats = module.apply({"params": params}, input_ids)
    sn, sv = lm_nll_sums_chunked(final[:, :-1], head, input_ids[:, 1:],
                                 module.cfg.dtype, ignore_index=None,
                                 tokens_per_chunk=tokens_per_chunk)
    return sn / jnp.maximum(sv, 1.0), stats

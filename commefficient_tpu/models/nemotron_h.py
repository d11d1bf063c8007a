"""One chip's share of a Nemotron-H layer stack (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Super's ``config.json``), in flax:
blocks that are one mixer each, laid out by a pattern string, ``M`` a
Mamba-2 state-space mixer, ``*`` grouped-query attention with no
positional embedding, ``E`` a LatentMoE feed-forward part; untied
embedding and head over a vocabulary slice.

    block:   x += mixer(RMSNorm(x)),  eps 1e-5; final RMSNorm
    M:       [z | xBC | dt] = x W_in;  xBC = silu(conv4(xBC) + b)
             [x | B | C] = xBC;  delta = softplus(dt + dt_bias)
             S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T   (a head)
             y_t = S_t C_t + D x_t;   A = -exp(A_log)
             out = W_out GroupRMSNorm(y * silu(z))
    *:       softmax_causal(q k^T / sqrt(128)) v, the query heads of a
             group sharing its key/value head
    E:       s = sigmoid(x W_r) over all the router's experts; the 22
             largest of s + b; g = 5 s / sum of the chosen s
             u = x W_down;  y = W_up sum_e g_e W2_e relu(W1_e u)^2
                              + W2_s relu(W1_s x)^2

The equations are restated, with the recurrence taken one position at
a time, in ``benchmark/reference/nemotron3-super-ep64-tp8.py``, the
plain float32 reference this module is tested against
(``tests/test_nemotron_h.py``). The Mamba-2 mixer (the recurrence by
chunks) and the attention are ``models/mixers.py``'s, the code
``models/granite_hybrid.py`` builds from too; what is TPU-shaped in
them is told there. Here:

- **The share.** ``mamba_num_heads`` heads in ``n_groups`` groups,
  ``num_attention_heads`` query and ``num_key_value_heads`` key/value
  heads, ``n_held_experts`` experts from ``expert_offset`` and
  ``vocab_size`` rows are what this chip holds of a layer; every width,
  the router's outputs and its picks a token are the published ones. A
  group's norm, B and C need the group's heads together, so a Mamba
  share is whole groups. The expert layer is ``models/moe.py``'s, the
  code ``models/joyai.py`` routes with. No exchange, and nothing stands
  in for the absent chips.

Scopes (``PERF.md`` section 3): the mixers' ``ssm_mixer`` >
``ssm_scan`` and ``gqa_attn``; ``moe_route``, ``moe_experts`` (the
latent projections inside it), ``moe_combine``; the head's ``lm_head``
is ``lm_nll_sums_chunked``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.models import register_model
from commefficient_tpu.models.mixers import (GQAttention,  # noqa: F401
                                             Mamba2Mixer, attn_plan,
                                             ssd_chunked)
from commefficient_tpu.models.mixers import Weights as _Weights
from commefficient_tpu.models.moe import (MOE_COUNTERS, MOE_STATS,
                                          client_stats, dispatch, fold_stats,
                                          layer_stats, no_stats, route,
                                          routed_experts)
from commefficient_tpu.models.norms import RMSNorm

#: a client's counts, which ``causal_lm_loss`` returns beside the loss:
#: ``models/moe.py``'s six, the chunks its Mamba-2 mixers scanned
#: (sequences x chunks a sequence x ``M`` layers), and how many of its
#: attention layers the flash kernel built (``models/mixers.py
#: attn_plan``)
STATS = MOE_STATS + ("ssm_chunks", "attn_kernel_layers")

#: how ``FedModel`` folds them into the round record's counters
COUNTERS = MOE_COUNTERS + (("ssm.chunks", np.sum),
                           ("attn.kernel_layers", np.max))

#: the 88 published layers
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*E"
                     "MEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072          # rows held of embedding and head
    hidden_size: int = 4096
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    mamba_num_heads: int = 128        # heads held
    mamba_head_dim: int = 64
    n_groups: int = 8                 # groups held (whole ones)
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32     # query heads held
    num_key_value_heads: int = 2      # key/value heads held
    head_dim: int = 128
    n_router_experts: int = 512       # the router's published width
    n_held_experts: int = 512         # experts whose weights are here
    expert_offset: int = 0            # id of the first of them
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    dtype: Any = jnp.float32
    remat: bool = False

    @staticmethod
    def tiny() -> "NemotronHConfig":
        """Test-scale: one whole period, every mechanism, nothing wide."""
        return NemotronHConfig(
            vocab_size=96, hidden_size=32,
            hybrid_override_pattern="MEMEMEMEM*E", mamba_num_heads=2,
            mamba_head_dim=8, n_groups=1, ssm_state_size=8, chunk_size=8,
            num_attention_heads=2, num_key_value_heads=1, head_dim=8,
            n_router_experts=64, n_held_experts=4, expert_offset=8,
            num_experts_per_tok=6, moe_intermediate_size=24,
            moe_latent_size=16, moe_shared_expert_intermediate_size=40)

    @staticmethod
    def from_hf(blob: dict) -> "NemotronHConfig":
        """From a ``config.json`` of the cut: the published keys, with
        ``n_routed_experts`` the experts held, ``router_experts`` the
        router's width (default: the same) and ``expert_offset``."""
        fields = {f.name for f in dataclasses.fields(NemotronHConfig)}
        kw = {k: v for k, v in blob.items() if k in fields}
        held = int(blob.get("n_routed_experts", 512))
        kw.update(n_held_experts=held,
                  n_router_experts=int(blob.get("router_experts", held)))
        kw.pop("dtype", None)
        cfg = NemotronHConfig(**kw)
        layers = blob.get("num_hidden_layers",
                          len(cfg.hybrid_override_pattern))
        if layers != len(cfg.hybrid_override_pattern):
            raise ValueError(
                f"num_hidden_layers {layers} is not the length of "
                f"hybrid_override_pattern {cfg.hybrid_override_pattern!r}")
        return cfg

    def reference_spec(self) -> dict:
        """The same sizes under the keys the plain reference reads."""
        spec = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("dtype", "remat", "n_router_experts",
                                  "n_held_experts")}
        spec.update(n_routed_experts=self.n_held_experts,
                    router_experts=self.n_router_experts,
                    num_hidden_layers=len(self.hybrid_override_pattern))
        return spec

    def count(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)


# --- layers ---------------------------------------------------------------
# (``Mamba2Mixer`` and ``GQAttention`` are imported; the scores keep the
# default scale, 1 / sqrt(``head_dim``))

def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class _Pair(_Weights):
    """``w1`` and ``w2`` of a squared-ReLU feed-forward part, or of a
    stack of them."""
    shapes: Any = ()

    @nn.compact
    def __call__(self):
        return self.mat("w1", self.shapes[0]), self.mat("w2", self.shapes[1])


class LatentMoE(_Weights):
    """The expert layer of one chip: ``(y, stats)`` with ``stats`` =
    ``models/moe.py layer_stats``' five float32 counts."""

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        N, C = x.shape
        E, Z = cfg.n_held_experts, cfg.moe_latent_size
        F, Fs = (cfg.moe_intermediate_size,
                 cfg.moe_shared_expert_intermediate_size)
        router = self.mat("router", (C, cfg.n_router_experts))
        bias = self.mat("router_bias", (cfg.n_router_experts,))
        down, up = self.mat("latent_down", (C, Z)), self.mat("latent_up",
                                                             (Z, C))
        w1, w2 = _Pair(cfg, ((E, Z, F), (E, F, Z)), name="experts")()
        s1, s2 = _Pair(cfg, ((C, Fs), (Fs, C)), name="shared")()
        with jax.named_scope("moe_route"):
            top, g = route(x, router, bias, cfg.num_experts_per_tok,
                           cfg.routed_scaling_factor, cfg.norm_topk_prob)
            self.sow("intermediates", "top", top)
            token, gate, load = dispatch(top, g, cfg.expert_offset, E)
        with jax.named_scope("moe_experts"):
            u = x @ down.astype(dt)
        share = E / cfg.n_router_experts
        routed = routed_experts(u, token, gate, load, (w1, w2), "relu2",
                                share)
        with jax.named_scope("moe_experts"):
            routed = routed.astype(dt) @ up.astype(dt)
        shared = _relu2(x @ s1.astype(dt)) @ s2.astype(dt)
        with jax.named_scope("moe_combine"):
            y = routed + shared
        return y.reshape(shape), layer_stats(
            load, N, cfg.num_experts_per_tok, share)


class Block(nn.Module):
    """``(x + mixer(norm(x)), the expert layer's ``layer_stats``,
    chunks scanned)``."""
    cfg: NemotronHConfig
    kind: str = "M"

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.dtype
        h = RMSNorm(cfg.layer_norm_epsilon, name="norm")(x).astype(dt)
        moe, chunks = no_stats(), 0
        if self.kind == "M":
            y, chunks = Mamba2Mixer(cfg, name="mixer")(h)
        elif self.kind == "*":
            y = GQAttention(cfg, name="mixer")(h)
        elif self.kind == "E":
            y, moe = LatentMoE(cfg, name="mixer")(h)
        else:
            raise ValueError(f"no mixer for pattern character {self.kind!r}")
        return x + y, moe, chunks


@register_model("NemotronHLM")
class NemotronHLM(nn.Module):
    """(S, T) token ids -> (final hidden (S, T, C) float32, head weight
    (V, C), the expert layers' ``layer_stats`` folded over layers, (chunks
    scanned, attention layers the flash kernel built)). The head is applied
    by the loss in token chunks (``models/gpt2.py
    lm_nll_sums_chunked``), so no (tokens, vocab) logits tensor
    exists."""
    cfg: NemotronHConfig = NemotronHConfig()

    #: ``config.json``'s ``model_type`` and its reader, for the trainer
    model_type = "nemotron_h"
    config_class = NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg, dt = self.cfg, self.cfg.dtype
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", init,
                          (cfg.vocab_size, cfg.hidden_size))
        block_cls = nn.remat(Block) if cfg.remat else Block
        h = embed[input_ids].astype(dt)
        stats, chunks = no_stats(), 0
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            h, s, n = block_cls(cfg, kind, name=f"layer_{i}")(h)
            stats, chunks = fold_stats(stats, s), chunks + n
        # as ``gqa_attention`` builds the attention layers, from the shapes
        plan = attn_plan(*input_ids.shape, cfg.num_attention_heads,
                         head_dim=cfg.head_dim)
        kernel = cfg.count("*") * (plan.kernel is not None)
        return (RMSNorm(cfg.layer_norm_epsilon, name="norm")(h), head,
                stats, (jnp.float32(chunks), jnp.float32(kernel)))


def causal_lm_loss(module, params, input_ids, tokens_per_chunk=1024):
    """Per-sequence mean next-token NLL and the ``STATS`` scalars."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    cfg = module.cfg
    final, head, stats, counts = module.apply({"params": params}, input_ids)
    sn, sv = lm_nll_sums_chunked(final[:, :-1], head, input_ids[:, 1:],
                                 cfg.dtype, ignore_index=None,
                                 tokens_per_chunk=tokens_per_chunk)
    return sn / jnp.maximum(sv, 1.0), client_stats(
        stats, cfg.count("E") * cfg.n_held_experts) + counts

"""GPT-2 with double heads (LM + multiple-choice), in flax.

The reference imports ``GPT2DoubleHeadsModel`` from pytorch_transformers
(gpt2_train.py:4-6, 262-273); here the transformer is in-tree and
TPU-shaped:

- causal attention via a single fused qkv projection feeding
  ``jax.nn.dot_product_attention`` (lowered to a fused TPU kernel);
- weight-tied LM head (logits = h @ wte.T), like GPT-2;
- MC head: take the hidden state at ``mc_token_ids`` per candidate,
  project to a scalar (the pytorch_transformers SequenceSummary with
  cls_index behavior);
- all shapes static; works under vmap over federated clients.

Double-heads batch layout (matching the reference collate,
fed_persona.py:360-392): input_ids / token_type_ids / lm_labels are
(B, num_candidates, T), mc_token_ids (B, num_candidates),
mc_labels (B,).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap, sequential_vmap

from commefficient_tpu.models import register_model
from commefficient_tpu.parallel.mesh import SHARED_CLIENTS, axis_bound


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # computation dtype (params stay float32); bfloat16 runs the MXU
    # at full rate. LayerNorm statistics and logits stay float32.
    dtype: Any = jnp.float32
    # Sequence/context parallelism (a capability the reference lacks,
    # SURVEY.md §2.8): set to a mesh axis name and call the model
    # inside shard_map with input_ids sharded on T over that axis.
    # Attention runs as ring attention ("ring") or all-to-all Ulysses
    # ("ulysses", needs n_head % axis_size == 0); position embeddings
    # and the MC-head gather become global-position aware. Hidden
    # states / LM logits stay sequence-sharded inside the model — use
    # an out_spec partitioned on T to reassemble, or keep them sharded
    # for a distributed loss.
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    # single-chip attention lowering: "xla" = jax.nn.dot_product_
    # attention (XLA fusion), "flash" = the Pallas TPU flash-attention
    # kernel (jax.experimental.pallas.ops.tpu.flash_attention) — the
    # model-side kernel experiment; no cell rules on it yet
    # (ROADMAP S11 (c), D7)
    attn_impl: str = "xla"
    # rematerialise each transformer block's activations in the
    # backward pass (jax.checkpoint): peak activation memory drops
    # from O(n_layer * B * T * n_embd) to O(B * T * n_embd) + one
    # block's internals, at ~1/3 extra FLOPs — the standard lever for
    # long-context training on HBM-bound chips
    remat: bool = False

    @staticmethod
    def tiny() -> "GPT2Config":
        """Test-scale config (the moral equivalent of --test's model
        shrink, cv_train.py:329-336)."""
        return GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                          n_layer=2, n_head=2)


def _dense_init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(4 * self.cfg.n_embd, dtype=self.cfg.dtype,
                     kernel_init=_dense_init(self.cfg), name="c_fc")(x)
        h = jax.nn.gelu(h, approximate=True)
        return nn.Dense(self.cfg.n_embd, dtype=self.cfg.dtype,
                        kernel_init=_dense_init(self.cfg),
                        name="c_proj")(h)


class CausalSelfAttention(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, attn_mask=None):
        B, T, C = x.shape
        H = self.cfg.n_head
        qkv = nn.Dense(3 * C, dtype=self.cfg.dtype,
                       kernel_init=_dense_init(self.cfg),
                       name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, C // H)
        k = k.reshape(B, T, H, C // H)
        v = v.reshape(B, T, H, C // H)
        if self.cfg.seq_axis is not None:
            from commefficient_tpu.parallel.ring_attention import (
                ring_attention, ulysses_attention)
            attn = (ring_attention if self.cfg.seq_impl == "ring"
                    else ulysses_attention)
            out = attn(q, k, v, self.cfg.seq_axis, causal=True)
        elif self.cfg.attn_impl == "flash" and T % 128 == 0:
            # T % 128 != 0 (shape-probe inits, odd batch tails) falls
            # through to the XLA path: the flash BACKWARD kernel tiles
            # by block // 128 and traces to a broadcasting error at
            # unaligned T (reproduced at T=8/64/200 on jax 0.9.0) —
            # and at short T the XLA lowering wins anyway
            # (rounds 1-5's chip)
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                BlockSizes, flash_attention)
            # kernel layout is (B, H, T, hd); scale explicitly — the
            # kernel's default sm_scale is 1.0, XLA's is hd^-0.5.
            # Block size must DIVIDE the sequence, not just bound it
            # (T=768 with block 512 raises in the kernel); the T % 128
            # guard above guarantees a divisor exists in this list
            b = next(x for x in (512, 256, 128) if T % x == 0)
            blocks = BlockSizes(
                block_q=b, block_k_major=b, block_k=b, block_b=1,
                block_q_major_dkv=b, block_k_major_dkv=b,
                block_k_dkv=b, block_q_dkv=b,
                block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
            out = flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                sm_scale=float((C // H) ** -0.5),
                block_sizes=blocks)
            out = out.transpose(0, 2, 1, 3)
        else:
            out = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape(B, T, C)
        return nn.Dense(C, dtype=self.cfg.dtype,
                        kernel_init=_dense_init(self.cfg),
                        name="c_proj")(out)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        eps = self.cfg.layer_norm_epsilon
        x = x + CausalSelfAttention(self.cfg, name="attn")(
            nn.LayerNorm(epsilon=eps, name="ln_1")(x)
            .astype(self.cfg.dtype))
        x = x + MLP(self.cfg, name="mlp")(
            nn.LayerNorm(epsilon=eps, name="ln_2")(x)
            .astype(self.cfg.dtype))
        return x


class GPT2Transformer(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None):
        cfg = self.cfg
        B, T = input_ids.shape
        wte = self.param("wte", _dense_init(cfg),
                         (cfg.vocab_size, cfg.n_embd))
        wpe = self.param("wpe", _dense_init(cfg),
                         (cfg.n_positions, cfg.n_embd))
        pos = jnp.arange(T)
        if cfg.seq_axis is not None:
            # T here is the local shard; offset to global positions
            pos = pos + jax.lax.axis_index(cfg.seq_axis) * T
        h = wte[input_ids] + wpe[pos][None]
        if token_type_ids is not None:
            # token types index the same embedding table, GPT-2 style
            h = h + wte[token_type_ids]
        block_cls = nn.remat(Block) if cfg.remat else Block
        for i in range(cfg.n_layer):
            h = block_cls(cfg, name=f"h_{i}")(h)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, name="ln_f")(h)
        return h, wte


@register_model("GPT2DoubleHeads")
class GPT2DoubleHeads(nn.Module):
    """LM logits + per-candidate MC logits.

    ``return_hidden=True`` skips the LM head matmul and returns the
    final hidden states + tied embedding instead of lm_logits — the
    training loss then computes the LM cross-entropy in token chunks
    (``lm_nll_sums_chunked``) so the (tokens, vocab) logits tensor is
    never materialised (f32 it is ~6.6 GB at 65k tokens; its
    store/reload chain dominated the large-batch profile)."""
    cfg: GPT2Config = GPT2Config()

    @nn.compact
    def __call__(self, input_ids, mc_token_ids, token_type_ids=None,
                 return_hidden=False):
        # flatten candidates into the batch axis
        B, N, T = input_ids.shape
        flat_ids = input_ids.reshape(B * N, T)
        flat_tt = (token_type_ids.reshape(B * N, T)
                   if token_type_ids is not None else None)
        h, wte = GPT2Transformer(self.cfg, name="transformer")(
            flat_ids, flat_tt)
        flat_h = h
        if not return_hidden:
            # tied weights; logits accumulate in float32
            with jax.named_scope("lm_head"):
                lm_logits = jnp.einsum(
                    "btc,vc->btv", h.astype(self.cfg.dtype),
                    wte.astype(self.cfg.dtype),
                    preferred_element_type=jnp.float32)
            lm_logits = lm_logits.reshape(B, N, T, -1)

        h = h.reshape(B, N, T, -1)
        if self.cfg.seq_axis is not None:
            # mc_token_ids are GLOBAL positions; the owning shard
            # contributes its hidden state, psum broadcasts it
            ax = self.cfg.seq_axis
            n_shards = jax.lax.axis_size(ax)
            gpos = jax.lax.axis_index(ax) * T + jnp.arange(T)
            idx = jnp.clip(mc_token_ids, 0, n_shards * T - 1)
            sel = (gpos[None, None, :] == idx[..., None]).astype(h.dtype)
            cls_h = jax.lax.psum(
                jnp.einsum("bnt,bntc->bnc", sel, h), ax)
        else:
            idx = jnp.clip(mc_token_ids, 0, T - 1)
            cls_h = jnp.take_along_axis(
                h, idx[..., None, None], axis=2)[:, :, 0]  # (B, N, C)
        mc_logits = nn.Dense(1, kernel_init=_dense_init(self.cfg),
                             name="mc_head")(cls_h)[..., 0]  # (B, N)
        if return_hidden:
            return flat_h, wte, mc_logits
        return lm_logits, mc_logits


def token_nll(logits, labels, ignore_index=-100):
    """(..., T, V) logits + (..., T) labels -> ((..., T) f32 NLL,
    (..., T) f32 validity). Logsumexp formulation: the (..., T, V)
    log-softmax tensor is never materialised (at GPT-2 vocab size that
    buffer is ~800 MB f32 per training round, and a per-example vmap
    of it lowers to a serial scan — measured 10x the loss cost)."""
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tok = jnp.take_along_axis(logits, safe[..., None],
                              axis=-1)[..., 0].astype(jnp.float32)
    return lse - tok, valid.astype(jnp.float32)


def _zeros(shape, dtype, like):
    """Zeros to carry through a loop: derived from ``like`` and not
    bare ``jnp.zeros``, so that inside a ``shard_map`` they vary over
    the mesh axes the loop's results vary over (the loop carry-type
    check; cf. models/moe.py ``_zeros``)."""
    return jnp.zeros(shape, dtype) + (jnp.ravel(like)[0] * 0).astype(dtype)


def _dense_nll_sums(h, wte, labels, dtype, tokens_per_chunk, weights=None):
    """Every position carries a label (raw token ids): straight slices
    of ``tokens_per_chunk`` rows through a ``lax.scan``, each chunk's
    logits recomputed in the backward (``jax.checkpoint``). No
    partition, gather or write-back. ``weights`` (E, Tm) float32, where
    given, multiply each position's nll before the sum (Σ valid stays
    the count) and are differentiated through: a position's cotangent
    is its nll, from the chunk's recomputed logits. With none the
    program is the one it was before the argument existed."""
    E, Tm, C = h.shape
    pad_label = -1  # no token id; masks the positions padded below
    tc = max(1, min(Tm, tokens_per_chunk // max(E, 1)))
    num_chunks = -(-Tm // tc)
    pad = num_chunks * tc - Tm
    # cast ONCE before chunking (the transformer's final hidden may be
    # f32 out of the last LayerNorm) and slice inside the scan rather
    # than pre-transposing to a (chunks, E, tc, C) copy — the copy
    # measured ~15 ms at 65k tokens
    with jax.named_scope("lm_head"):
        hp = jnp.pad(h.astype(dtype), ((0, 0), (0, pad), (0, 0)))
        lp = jnp.pad(labels, ((0, 0), (0, pad)),
                     constant_values=pad_label)
        wte_c = wte.astype(dtype)  # cast once, outside the scan
        wp = None if weights is None else jnp.pad(
            weights.astype(jnp.float32), ((0, 0), (0, pad)))

    @jax.checkpoint
    def chunk_sums(hc, lc, w, *pc):
        logits = jnp.einsum("etc,vc->etv", hc, w,
                            preferred_element_type=jnp.float32)
        nll, valid = token_nll(logits, lc, pad_label)
        for c in pc:        # the chunk's weights, where there are any
            nll = nll * c
        return jnp.sum(nll * valid, -1), jnp.sum(valid, -1)

    def body(carry, i):
        sn, sv = carry
        hc, lc, *pc = (jax.lax.dynamic_slice_in_dim(a, i * tc, tc, axis=1)
                       for a in (hp, lp) + (() if wp is None else (wp,)))
        n, v = chunk_sums(hc, lc, wte_c, *pc)
        return (sn + n, sv + v), None

    # the zero init is derived from the inputs (x*0 sums) rather than
    # jnp.zeros so that under shard_map it carries the same varying
    # mesh axes as the body's output — a plain-zeros carry trips the
    # scan carry-type check when this runs on a sequence shard
    init = (jnp.sum(hp[:, :, 0] * 0.0, axis=1, dtype=jnp.float32),
            jnp.sum(lp * 0, axis=1).astype(jnp.float32))
    with jax.named_scope("lm_head"):
        (sn, sv), _ = jax.lax.scan(
            body, init, jnp.arange(num_chunks, dtype=jnp.int32))
    return sn, sv


def _over_clients(one, pooled, table_grad=False):
    """``one(h, wte, labels[, g])`` as it runs under a ``vmap``. Not
    ``pooled``: an element at a time, whatever is batched. ``pooled``
    (the ``vmap`` over ``SHARED_CLIENTS``): with the table shared the
    batch axis is folded into the example axis and ``one`` runs once
    over every client's rows; with ``table_grad`` its result is the
    backward's ``(dh, dw)``, and ``dw`` is then the sum over the
    clients, unbatched, which is what summed losses ask for a shared
    weight. A table a client has no rows to share: an element at a
    time again."""
    seq = sequential_vmap(one)
    if not pooled:
        return seq
    fn = custom_vmap(one)

    @fn.def_vmap
    def rule(axis_size, in_batched, h, wte, *per_example):
        if in_batched[1]:
            out = jax.vmap(seq, in_axes=[0 if b else None
                                         for b in in_batched])(
                h, wte, *per_example)
            return out, jax.tree_util.tree_map(lambda _: True, out)

        def fold(a, batched):
            if not batched:
                a = jnp.broadcast_to(a, (axis_size,) + a.shape)
            return a.reshape((-1,) + a.shape[2:])

        def unfold(a):
            return a.reshape((axis_size, -1) + a.shape[1:])

        out = fn(fold(h, in_batched[0]), wte, *(
            fold(a, b) for a, b in zip(per_example, in_batched[2:])))
        if table_grad:
            return (unfold(out[0]), out[1]), (True, False)
        return unfold(out), True

    return fn


@functools.lru_cache(maxsize=None)
def _compact_nll(dtype, ignore_index, rows_max, pooled):
    """``nll_sums(h (E, Tm, C) in dtype, wte (V, C), labels (E, Tm)) ->
    (E,) f32 Σ nll`` over the labelled positions alone: the rows are
    ordered labelled-first and only ``ceil(labelled / rows)`` chunks of
    them meet the table, forward and backward. A loop of dynamic length
    has no reverse mode, so the function carries its own VJP, which
    recomputes each chunk's logits (one chunk's logits live at a time,
    as under ``jax.checkpoint``) and sums the table's gradient in
    float32 over the chunks. What a ``vmap`` makes of it:
    ``_over_clients``."""

    def plan(labels):
        """(flat labels, the rows' order padded to whole chunks, the
        labelled count, rows a chunk)."""
        R = labels.size
        rows = max(1, min(rows_max, R))
        lab = labels.reshape(R)
        valid = lab != ignore_index
        # a stable partition: the labelled rows first, in place order
        order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
        return (lab, jnp.pad(order, (0, -R % rows)),
                jnp.sum(valid, dtype=jnp.int32), rows)

    def chunk(i, hf, w, lab, order, n, rows):
        """Chunk ``i``'s rows of ``hf``, which of them are labelled
        rows (the last chunk's tail is not), their labels, logits and
        logsumexp."""
        idx = jax.lax.dynamic_slice_in_dim(order, i * rows, rows)
        live = i * rows + jnp.arange(rows, dtype=jnp.int32) < n
        hc = hf[idx]
        lc = jnp.where(live, lab[idx], 0)
        logits = jnp.einsum("rc,vc->rv", hc, w,
                            preferred_element_type=jnp.float32)
        return idx, live, hc, lc, logits, jax.nn.logsumexp(logits, -1)

    def fwd_counted(h, wte, labels):
        """(Σ nll by example, the chunk bodies that ran)."""
        E, Tm, C = h.shape
        with jax.named_scope("lm_head"):
            hf, w = h.reshape(E * Tm, C), wte.astype(dtype)
            lab, order, n, rows = plan(labels)

            def body(i, carry):
                sn, ran = carry
                idx, live, _, lc, logits, lse = chunk(
                    i, hf, w, lab, order, n, rows)
                tok = jnp.take_along_axis(logits, lc[:, None], 1)[:, 0]
                nll = jnp.where(live, lse - tok, 0.0)
                # folded by the row's example; compare and sum, exact
                # in float32 and no scatter
                mine = (idx // Tm)[:, None] == jnp.arange(E)[None, :]
                return (sn + jnp.sum(jnp.where(mine, nll[:, None], 0.0),
                                     0), ran + 1)

            return jax.lax.fori_loop(
                0, (n + rows - 1) // rows, body,
                (_zeros((E,), jnp.float32, hf), n * 0))

    def fwd(h, wte, labels):
        return fwd_counted(h, wte, labels)[0]

    def bwd(h, wte, labels, g):
        E, Tm, C = h.shape
        with jax.named_scope("lm_head"):
            hf, w = h.reshape(E * Tm, C), wte.astype(dtype)
            lab, order, n, rows = plan(labels)

            def body(i, carry):
                dh, dw = carry
                idx, live, hc, lc, logits, lse = chunk(
                    i, hf, w, lab, order, n, rows)
                # d nll / d logits = softmax - onehot(label), times the
                # example's cotangent; 0 on the rows past n
                p = jnp.exp(logits - lse[:, None])
                hit = jnp.arange(w.shape[0])[None, :] == lc[:, None]
                scale = jnp.where(live, g[idx // Tm], 0.0)
                dl = (jnp.where(hit, p - 1.0, p)
                      * scale[:, None]).astype(dtype)
                dhc = jnp.einsum("rv,vc->rc", dl, w,
                                 preferred_element_type=jnp.float32)
                dw = dw + jnp.einsum("rv,rc->vc", dl, hc,
                                     preferred_element_type=jnp.float32)
                # each labelled row is written once; the rows past n
                # go nowhere, the unlabelled stay the zeros they were
                dh = dh.at[jnp.where(live, idx, E * Tm)].set(
                    dhc.astype(dtype), mode="drop")
                return dh, dw

            dh, dw = jax.lax.fori_loop(
                0, (n + rows - 1) // rows, body,
                (_zeros(hf.shape, dtype, hf),
                 _zeros(w.shape, jnp.float32, hf)))
            return dh.reshape(h.shape), dw.astype(wte.dtype)

    fwd = _over_clients(fwd, pooled)
    bwd = _over_clients(bwd, pooled, table_grad=True)

    @jax.custom_vjp
    def nll_sums(h, wte, labels):
        return fwd(h, wte, labels)

    def vjp_fwd(h, wte, labels):
        return fwd(h, wte, labels), (h, wte, labels)

    def vjp_bwd(res, g):
        dh, dw = bwd(*res, g)
        return dh, dw, None

    nll_sums.defvjp(vjp_fwd, vjp_bwd)
    #: for tests: how many chunk bodies a forward ran
    nll_sums.chunks_run = lambda *a: fwd_counted(*a)[1]
    return nll_sums


def head_compacts(ignore_index) -> bool:
    """Whether ``lm_nll_sums_chunked`` called with this ``ignore_index``
    orders the rows by label and computes the labelled ones alone
    (round counter ``head.compact``)."""
    return ignore_index is not None


def lm_nll_sums_chunked(h, wte, labels, dtype, ignore_index=-100,
                        tokens_per_chunk=1024, weights=None):
    """Per-example (Σ nll, Σ valid) of the tied-head LM cross-entropy
    without materialising the (E, T, V) logits tensor.

    ``h`` (E, Tm, C) are the final hidden states at the *predicting*
    positions (callers pass ``h[:, :-1]``), ``labels`` (E, Tm) the
    shifted targets, ``tokens_per_chunk`` the rows of one chunk's
    (rows, V) logits, the most that is live at a time: the backward
    recomputes a chunk's logits instead of storing them. Same math as
    ``token_nll`` of the full logits (fp summation order aside).

    **What is computed and what is skipped.** A position whose label is
    ``ignore_index`` adds 0 to Σ nll, 0 to Σ valid, a zero row to
    ``h``'s gradient and nothing to the table's, so it never meets the
    table: the rows are ordered labelled-first (one stable sort of the
    E · Tm flags) and the chunk loops, forward and backward, run
    ``ceil(labelled / rows)`` times, a count found at run time
    (``_compact_nll``; PersonaChat labels the gold reply only, 1.3 % of
    the positions). ``ignore_index=None`` says that every position
    carries a label (the causal LMs' raw token ids): nothing is
    ordered, gathered or written back, the chunks are straight slices
    and their count is static (``_dense_nll_sums``). That path alone
    takes ``weights`` (E, Tm): Σ nll becomes Σ weight · nll, a
    position at a time, differentiable in the weights too (a looped
    LM's expected loss over its exit distribution: ``models/ouro.py``).

    Inside a ``vmap`` over clients that share the table and whose
    losses are summed before they are differentiated, which its
    builder says by naming the axis ``SHARED_CLIENTS``
    (core/rounds.py ``make_local_loss``, core/rounds_sp.py), the
    clients' rows fill common chunks: 8 clients of 55 labelled rows
    are one chunk of 1,024, and the table's gradient comes out summed
    over them, which is what that transformation asks for a shared
    weight. Any other ``vmap`` runs one compaction an element: a
    table a client, and per-client gradients of a shared table
    (``vmap`` of ``grad``: core/rounds.py ``client_round``), where a
    sum over the clients would be every client's wrong answer."""
    if not head_compacts(ignore_index):
        return _dense_nll_sums(h, wte, labels, dtype, tokens_per_chunk,
                               weights)
    if weights is not None:
        raise ValueError("position weights go with ignore_index=None: the "
                         "compacted head sums a sequence's nll unweighted")
    with jax.named_scope("lm_head"):
        sv = jnp.sum(labels != ignore_index, axis=1, dtype=jnp.float32)
        hd = h.astype(dtype)
        # inside a shard_map the table's gradient is summed over the
        # mesh axes the rows vary over and the table does not
        vary = tuple(jax.typeof(hd).vma - jax.typeof(wte).vma)
        if vary:
            wte = jax.lax.pcast(wte, vary, to="varying")
        sn = _compact_nll(jnp.dtype(dtype), int(ignore_index),
                          int(tokens_per_chunk),
                          axis_bound(SHARED_CLIENTS))(hd, wte, labels)
    return sn, sv


def gpt2_double_heads_loss(lm_logits, mc_logits, lm_labels, mc_labels,
                           lm_coef=1.0, mc_coef=1.0,
                           ignore_index=-100):
    """Training loss (reference gpt2_train.py:88-99): lm_coef*CE(LM,
    shifted) + mc_coef*CE(MC). Returns (loss, lm_loss, mc_loss), each
    a scalar mean over valid positions / examples."""
    # shift: predict token t+1 from position t
    nll, valid = token_nll(lm_logits[..., :-1, :], lm_labels[..., 1:],
                           ignore_index)
    lm_loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)

    mc_nll, _ = token_nll(mc_logits[..., None, :],
                          mc_labels[..., None], ignore_index)
    mc_loss = jnp.mean(mc_nll[..., 0])
    return lm_coef * lm_loss + mc_coef * mc_loss, lm_loss, mc_loss


def convert_gpt2_to_hf(params, cfg: GPT2Config):
    """Inverse of ``convert_torch_gpt2``: emit an HF-`transformers`
    GPT2DoubleHeadsModel state dict (numpy values) + HF config dict
    from this module's params pytree — so a model fine-tuned here can
    be handed back to the torch/HF ecosystem, matching the reference's
    ``save_pretrained`` contract (fed_aggregator.py:209-212,
    gpt2_train.py:146).

    Layout notes: HF GPT2 Conv1D stores (in, out) — identical to flax
    Dense kernels, no transpose; LayerNorm ``weight`` = flax ``scale``;
    the MC head maps to ``multiple_choice_head.summary`` (a torch
    Linear, (out, in) — transposed); ``lm_head.weight`` is the tied
    ``wte`` (HF re-ties on load, included for completeness)."""
    import numpy as np

    def a(x):
        return np.asarray(x)

    t = params["transformer"]
    sd = {
        "transformer.wte.weight": a(t["wte"]),
        "transformer.wpe.weight": a(t["wpe"]),
        "transformer.ln_f.weight": a(t["ln_f"]["scale"]),
        "transformer.ln_f.bias": a(t["ln_f"]["bias"]),
        "lm_head.weight": a(t["wte"]),
    }
    for i in range(cfg.n_layer):
        b = t[f"h_{i}"]
        pre = f"transformer.h.{i}."
        sd[pre + "ln_1.weight"] = a(b["ln_1"]["scale"])
        sd[pre + "ln_1.bias"] = a(b["ln_1"]["bias"])
        sd[pre + "attn.c_attn.weight"] = a(b["attn"]["c_attn"]["kernel"])
        sd[pre + "attn.c_attn.bias"] = a(b["attn"]["c_attn"]["bias"])
        sd[pre + "attn.c_proj.weight"] = a(b["attn"]["c_proj"]["kernel"])
        sd[pre + "attn.c_proj.bias"] = a(b["attn"]["c_proj"]["bias"])
        sd[pre + "ln_2.weight"] = a(b["ln_2"]["scale"])
        sd[pre + "ln_2.bias"] = a(b["ln_2"]["bias"])
        sd[pre + "mlp.c_fc.weight"] = a(b["mlp"]["c_fc"]["kernel"])
        sd[pre + "mlp.c_fc.bias"] = a(b["mlp"]["c_fc"]["bias"])
        sd[pre + "mlp.c_proj.weight"] = a(b["mlp"]["c_proj"]["kernel"])
        sd[pre + "mlp.c_proj.bias"] = a(b["mlp"]["c_proj"]["bias"])
    if "mc_head" in params:
        sd["multiple_choice_head.summary.weight"] = \
            a(params["mc_head"]["kernel"]).T
        sd["multiple_choice_head.summary.bias"] = \
            a(params["mc_head"]["bias"])

    # HF GPT2Config field names coincide with GPT2Config's for every
    # architectural field; the extras below make the dir loadable by
    # transformers.from_pretrained. num_labels=1 gives the DoubleHeads
    # summary head its (1, n_embd) projection.
    hf_config = {
        "model_type": "gpt2",
        "architectures": ["GPT2DoubleHeadsModel"],
        "vocab_size": cfg.vocab_size,
        "n_positions": cfg.n_positions,
        "n_ctx": cfg.n_positions,
        "n_embd": cfg.n_embd,
        "n_layer": cfg.n_layer,
        "n_head": cfg.n_head,
        "layer_norm_epsilon": cfg.layer_norm_epsilon,
        "initializer_range": cfg.initializer_range,
        "activation_function": "gelu_new",
        "summary_type": "cls_index",
        "summary_use_proj": True,
        "summary_proj_to_labels": True,
        "summary_first_dropout": 0.0,
        "num_labels": 1,
    }
    return sd, hf_config


def convert_torch_gpt2(state_dict, cfg: GPT2Config):
    """Convert a (pytorch_)transformers GPT2 state dict into this
    module's params pytree, including the Conv1D (transposed linear)
    layout and resized embeddings for added special tokens
    (gpt2_train.py:101-112). Accepts a dict of numpy arrays."""
    import numpy as np

    def a(name):
        # hub checkpoints for the bare "gpt2" model store keys without
        # the "transformer." base-model prefix; re-saved
        # GPT2LMHeadModel/DoubleHeads dicts include it — accept both
        if name in state_dict:
            return np.asarray(state_dict[name])
        return np.asarray(state_dict[name.removeprefix("transformer.")])

    p = {"transformer": {}}
    t = p["transformer"]
    wte = a("transformer.wte.weight")
    if wte.shape[0] < cfg.vocab_size:
        # new special-token rows: mean-init like HF resize
        extra = np.tile(wte.mean(0, keepdims=True),
                        (cfg.vocab_size - wte.shape[0], 1))
        wte = np.concatenate([wte, extra], 0)
    t["wte"] = wte
    t["wpe"] = a("transformer.wpe.weight")
    for i in range(cfg.n_layer):
        pre = f"transformer.h.{i}."
        # HF GPT2 Conv1D stores (in, out) — same as flax Dense kernels
        t[f"h_{i}"] = {
            "ln_1": {"scale": a(pre + "ln_1.weight"),
                     "bias": a(pre + "ln_1.bias")},
            "attn": {
                "c_attn": {"kernel": a(pre + "attn.c_attn.weight"),
                           "bias": a(pre + "attn.c_attn.bias")},
                "c_proj": {"kernel": a(pre + "attn.c_proj.weight"),
                           "bias": a(pre + "attn.c_proj.bias")},
            },
            "ln_2": {"scale": a(pre + "ln_2.weight"),
                     "bias": a(pre + "ln_2.bias")},
            "mlp": {
                "c_fc": {"kernel": a(pre + "mlp.c_fc.weight"),
                         "bias": a(pre + "mlp.c_fc.bias")},
                "c_proj": {"kernel": a(pre + "mlp.c_proj.weight"),
                           "bias": a(pre + "mlp.c_proj.bias")},
            },
        }
    t["ln_f"] = {"scale": a("transformer.ln_f.weight"),
                 "bias": a("transformer.ln_f.bias")}
    rng = np.random.RandomState(0)
    p["mc_head"] = {
        "kernel": rng.normal(0, cfg.initializer_range,
                             (cfg.n_embd, 1)).astype(np.float32),
        "bias": np.zeros((1,), np.float32),
    }
    return p

"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context support at all (SURVEY.md §2.8:
sequences are padded per-batch and processed whole, fed_persona.py:
360-392) — this module is a capability the TPU build adds as
first-class: sequences sharded over a ``seq`` mesh axis so context
length scales with the number of chips.

Two standard formulations, both built on XLA collectives over ICI:

- ``ring_attention``: blockwise causal attention with an online
  (flash-style) softmax; KV blocks rotate around the ring via
  ``jax.lax.ppermute`` while each device keeps its Q shard. Peak
  memory per device is O(T_local · d) and the KV transfer overlaps
  the block matmuls. Exact — not an approximation.
- ``ulysses_attention``: ``jax.lax.all_to_all`` reshards from
  sequence-sharded to head-sharded, runs ordinary fused attention on
  full sequences per head group, and reshards back. Cheaper at modest
  T (two all-to-alls instead of n-1 permutes) but requires
  n_head % axis_size == 0.

Both are called inside ``shard_map`` with q/k/v sharded on the
sequence (T) axis: shapes (B, T_local, H, D). Causal masking uses
global positions derived from ``jax.lax.axis_index``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


_NEG_INF = -1e30  # finite mask value: keeps the online softmax NaN-free
                  # for fully-masked (future) KV blocks


def _block_attn(q, k, v, bias_mask, o, m, l, scale):
    """One KV block of online-softmax attention.

    q (B, Tq, H, D); k/v (B, Tk, H, D); bias_mask (Tq, Tk) additive.
    Carries: o (B, Tq, H, D) un-normalised output, m/l (B, Tq, H)
    running max / normaliser.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = s + bias_mask[None, None, :, :]
    m_blk = jnp.max(s, axis=-1)                    # (B, H, Tq)
    m_new = jnp.maximum(m, m_blk.transpose(0, 2, 1))
    # correction of previous accumulators
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new.transpose(0, 2, 1)[..., None])  # (B,H,Tq,Tk)
    l_new = l * corr + jnp.sum(p, axis=-1).transpose(0, 2, 1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o * corr[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name: str, causal: bool = True):
    """Exact blockwise attention over a sequence-sharded ring.

    Must run inside shard_map; q/k/v are the local shards
    (B, T_local, H, D). Returns the local output shard.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    q_pos = idx * T + jnp.arange(T)  # global positions of our queries

    def mask_for(kv_owner):
        """(Tq, Tk) additive causal mask for the block originally
        owned by device ``kv_owner``."""
        if not causal:
            return jnp.zeros((T, T), jnp.float32)
        k_pos = kv_owner * T + jnp.arange(T)
        allowed = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(allowed, 0.0, _NEG_INF)

    # derive the accumulators from q so they carry q's full
    # varying-axes set (the loop carry must type-match after mixing
    # with the rotated KV blocks — and under a multi-axis mesh, e.g.
    # clients x seq, the inputs vary over more axes than just ours)
    zero = (q * 0.0).astype(jnp.float32)
    o = zero
    m = jnp.sum(zero, axis=-1) + _NEG_INF  # (B, T, H)
    l = jnp.sum(zero, axis=-1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        o, m, l, kk, vv = carry
        owner = (idx - s) % n  # which device's KV block we hold now
        o, m, l = _block_attn(q, kk, vv, mask_for(owner), o, m, l,
                              scale)
        # rotate KV to the next device (skipped result unused on the
        # last step but keeps the loop body uniform)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return o, m, l, kk, vv

    o, m, l, _, _ = jax.lax.fori_loop(
        0, n, step, (o, m, l, k.astype(jnp.float32),
                     v.astype(jnp.float32)))
    # fully-masked rows (none under causal with self block) guard
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style):
    reshard seq->heads, dense attention on the full sequence, reshard
    back. Requires H % axis_size == 0. Exact."""
    n = jax.lax.axis_size(axis_name)
    B, T, H, D = q.shape
    assert H % n == 0, f"n_head {H} must divide axis size {n}"

    def seq_to_heads(x):
        # (B, T_local, H, D) -> (B, T_global, H/n, D)
        x = x.reshape(B, T, n, H // n, D)
        x = jax.lax.all_to_all(x, axis_name, split_axis=2,
                               concat_axis=1, tiled=False)
        # all_to_all inserts the gathered axis at concat position
        return x.reshape(B, n * T, H // n, D)

    def heads_to_seq(x):
        x = x.reshape(B, n, T, H // n, D)
        x = jax.lax.all_to_all(x, axis_name, split_axis=0 + 1,
                               concat_axis=2 + 1, tiled=False)
        return x.reshape(B, T, H, D)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = jax.nn.dot_product_attention(qh, kh, vh, is_causal=causal)
    return heads_to_seq(out)


def dense_reference(q, k, v, causal: bool = True):
    """Single-device oracle for tests."""
    return jax.nn.dot_product_attention(q, k, v, is_causal=causal)

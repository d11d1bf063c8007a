"""Federated round with sequence parallelism inside each client — the
2-D mesh composition ("clients" x "seq").

The 1-D engine (core/rounds.py) shards *clients* over the mesh; each
client's forward fits one device. For long-sequence federated LM
training (GPT-2/PersonaChat at context lengths the reference could
never reach — it has no sequence parallelism at all, SURVEY.md §2.8),
this module composes both axes:

- the client batch is sharded over ``clients`` AND its token arrays
  over ``seq``;
- inside one ``shard_map`` block, each device holds its client slice's
  sequence shard; the GPT-2 forward runs ring (or Ulysses) attention
  over ``seq`` (models/gpt2.py seq_axis) with global-position
  embeddings;
- the loss is a masked token-CE over local positions (labels are
  pre-shifted host-side so the shard boundary needs no halo exchange)
  plus the MC-head CE, normalised by ``psum`` counts over ``seq``;
- parameter gradients are ``psum``-ed over ``seq`` (params are
  replicated on that axis), then the per-client transmits sum over
  ``clients`` — exactly the 1-D engine's aggregation semantics, so the
  aggregated gradient equals the dense single-device oracle
  (tested in tests/test_rounds_sp.py) and any linear compressor
  (count-sketch) composes on top unchanged.

Client state: the SP round is *stateless* per client (uncompressed /
sketch modes only — no local momentum, no local error feedback), so
the host-resident client store (clientstore/) never applies here;
``--clientstore host`` composes with the 1-D engine's stateful modes
(local_topk, fedavg). If stateful modes are ever added to this path,
the dense_rows participant-row contract in core/rounds.py
build_client_round is the template.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from commefficient_tpu.models.gpt2 import (GPT2Config, GPT2DoubleHeads,
                                           lm_nll_sums_chunked,
                                           token_nll)
from commefficient_tpu.parallel.mesh import (CLIENT_AXIS, SHARED_CLIENTS,
                                             client_spec, replicated_spec,
                                             shard_map, spec)

SEQ_AXIS = "seq"


def make_sp_mesh(n_clients_axis: int, n_seq_axis: int,
                 devices=None) -> Mesh:
    import numpy as np
    devices = list(devices) if devices is not None else jax.devices()
    n = n_clients_axis * n_seq_axis
    assert len(devices) >= n, (len(devices), n)
    return Mesh(np.array(devices[:n]).reshape(n_clients_axis,
                                              n_seq_axis),
                (CLIENT_AXIS, SEQ_AXIS))


def shift_lm_labels(lm_labels, ignore_index: int = -1):
    """Host-side global shift: position t is labelled with token t+1
    (the loss shift of gpt2_double_heads_loss), so sequence shards
    never need their right neighbour's first token. Default
    ignore_index -1 matches the persona loaders' label padding
    (data/loader.py PersonaFedLoader)."""
    shifted = jnp.roll(lm_labels, -1, axis=-1)
    return shifted.at[..., -1].set(ignore_index)


def build_sp_gpt2_round(cfg: GPT2Config, mesh: Mesh,
                        unravel: Callable, lm_coef: float = 1.0,
                        mc_coef: float = 1.0,
                        ignore_index: int = -1,
                        tokens_per_chunk: int = 0):
    """Returns jit-able ``round(flat_params, batch) -> (agg_grad,
    per_client_losses)`` — losses are per participating client (W,),
    zero for clients with no real examples, so the trainer reports
    per-client metrics exactly like the 1-D engine.

    ``batch`` (host layout, W = participating clients):
      input_ids / token_type_ids (W, B, N, T) int32,
      shifted_labels (W, B, N, T) int32 (see shift_lm_labels),
      mc_token_ids (W, B, N) int32 — GLOBAL positions,
      mc_labels (W, B) int32, mask (W, B) float32 per-EXAMPLE mask
      (ragged client batches: padded rows are excluded from both loss
      terms; a client with no real rows contributes nothing).
    """
    sp_cfg = dataclasses.replace(cfg, seq_axis=SEQ_AXIS)
    model = GPT2DoubleHeads(sp_cfg)
    ignore = ignore_index
    # 0 = auto: 256 tokens/chunk — the measured knee of the SP
    # temp-memory table (round 5, compiled temporaries on the CPU:
    # 0.89 GB vs 1.20 GB at the old 1024 default and 1.91 GB for the
    # dense-equivalent full-shard chunk at T_local=1024; within noise
    # of 128) and throughput-flat. --tokens_per_chunk overrides.
    tokens_per_chunk = tokens_per_chunk or 256

    def client_loss(flat, ids, tt, labels, mc_ids, mc_labels,
                    ex_mask):
        """Local-shard loss contributions for ONE client:
        (lm_nll_sum_local, lm_valid_count_local, mc_nll_mean) —
        the seq-psum happens outside so grad sees pure locals.
        ``ex_mask`` (B,) zeroes padded examples out of both terms.

        The LM term uses the chunked tied-head cross-entropy
        (models/gpt2.py lm_nll_sums_chunked) on the LOCAL sequence
        shard: the (B·N, T_local, V) logits tensor is never
        materialised, so peak vocab-head memory is one token chunk —
        SP keeps the long-context headroom it exists to provide
        instead of re-capping it at real vocab sizes. Labels arrive
        globally pre-shifted (shift_lm_labels), so local sums need no
        halo and seq-psum to the exact global numerator/denominator."""
        params = unravel(flat)
        B, N, Tl = ids.shape
        h, wte, mc_logits = model.apply(
            {"params": params}, ids, mc_ids, tt, return_hidden=True)
        sn, sv = lm_nll_sums_chunked(
            h, wte, labels.reshape(B * N, Tl), sp_cfg.dtype,
            ignore_index=ignore, tokens_per_chunk=tokens_per_chunk)
        e_mask = jnp.broadcast_to(ex_mask[:, None],
                                  (B, N)).reshape(B * N)
        lm_sum = jnp.sum(sn * e_mask)
        lm_cnt = jnp.sum(sv * e_mask)
        mc_nll, _ = token_nll(mc_logits[..., None, :],
                              mc_labels[..., None], ignore)
        mc = (jnp.sum(mc_nll[..., 0] * ex_mask)
              / jnp.maximum(jnp.sum(ex_mask), 1.0))
        return lm_sum, lm_cnt, mc

    def block(flat, ids, tt, labels, mc_ids, mc_labels, mask):
        # local shapes: (Wl, B, N, Tl) tokens, (Wl, B, N) mc, (Wl, B).
        # Gradients of the replicated ``flat`` are automatically
        # psum-med over BOTH mesh axes by shard_map's autodiff, so the
        # per-device objective must be the exact local share of the
        # global weighted objective: the lm term contributes its LOCAL
        # numerator over the GLOBAL count (seq shards sum to the full
        # mean) and the mc term — identical on every seq shard after
        # the gather-psum — is divided by the seq axis size.
        assert mask.ndim == 2, f"mask must be (W, B), got {mask.shape}"
        ex_mask = mask  # (Wl, B) per-example
        w = (jnp.sum(ex_mask, axis=1) > 0).astype(jnp.float32)  # (Wl,)
        seq_n = jax.lax.axis_size(SEQ_AXIS)

        def local_objective(f):
            def per_client(ids_c, tt_c, labels_c, mc_c, mcl_c, ex_c):
                lm_sum, lm_cnt, mc = client_loss(
                    f, ids_c, tt_c, labels_c, mc_c, mcl_c, ex_c)
                global_cnt = jnp.maximum(
                    jax.lax.psum(lm_cnt, SEQ_AXIS), 1.0)
                share = (lm_coef * lm_sum / global_cnt
                         + mc_coef * mc / seq_n)
                report = (lm_coef
                          * jax.lax.psum(lm_sum, SEQ_AXIS) / global_cnt
                          + mc_coef * mc)
                return share, report

            # ``f`` is shared and the shares are summed: the clients
            # of this block pool their labelled rows in the head
            shares, reports = jax.vmap(
                per_client, axis_name=SHARED_CLIENTS)(
                ids, tt, labels, mc_ids, mc_labels, ex_mask)
            return jnp.sum(shares * w), reports

        (_, losses), g = jax.value_and_grad(
            local_objective, has_aux=True)(flat)
        # g is already Sum_c w_c * grad_c, replicated everywhere:
        # differentiating the replicated ``flat`` inside the block
        # makes shard_map's transpose insert the cross-device psum
        n_clients = jnp.maximum(
            jax.lax.psum(jnp.sum(w), CLIENT_AXIS), 1.0)
        # per-client reported losses, zeroed for non-participating
        # rows; identical on every seq shard (the lm report is
        # seq-psummed inside per_client), so a CLIENT_AXIS out-spec
        # reassembles the global (W,) vector
        return g / n_clients, losses * w

    tok = spec(CLIENT_AXIS, None, None, SEQ_AXIS)
    per_client = client_spec()
    fn = shard_map(
        block, mesh=mesh,
        in_specs=(replicated_spec(), tok, tok, tok, per_client,
                  per_client, per_client),
        out_specs=(replicated_spec(), per_client))

    def round_fn(flat_params, batch):
        return fn(flat_params, batch["input_ids"],
                  batch["token_type_ids"], batch["shifted_labels"],
                  batch["mc_token_ids"], batch["mc_labels"],
                  batch["mask"])

    return round_fn

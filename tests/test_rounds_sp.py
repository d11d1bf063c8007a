"""2-D mesh (clients x seq) federated GPT-2 round vs the dense
single-device oracle: aggregated gradient and loss must match."""

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.core.rounds_sp import (build_sp_gpt2_round,
                                              make_sp_mesh,
                                              shift_lm_labels)
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.ops.vec import flatten_params

IGNORE = -1


def _batch(rng, W, B, N, T, vocab):
    ids = rng.randint(0, vocab, (W, B, N, T)).astype(np.int32)
    tt = rng.randint(0, vocab, (W, B, N, T)).astype(np.int32)
    labels = ids.copy()
    labels[..., : T // 4] = IGNORE  # some ignored context positions
    mc_ids = rng.randint(0, T, (W, B, N)).astype(np.int32)
    mc_labels = rng.randint(0, N, (W, B)).astype(np.int32)
    return {
        "input_ids": jnp.asarray(ids),
        "token_type_ids": jnp.asarray(tt),
        "shifted_labels": shift_lm_labels(jnp.asarray(labels)),
        "mc_token_ids": jnp.asarray(mc_ids),
        "mc_labels": jnp.asarray(mc_labels),
        "mask": jnp.ones((W, B), jnp.float32),
    }


def _dense_oracle(cfg, params, flat, unravel, batch, lm_coef, mc_coef):
    model = GPT2DoubleHeads(cfg)

    def client_loss(f, ids, tt, labels, mc_ids, mc_labels):
        lm_logits, mc_logits = model.apply({"params": unravel(f)},
                                           ids, mc_ids, tt)
        valid = labels != IGNORE
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(lm_logits)
        nll = -jnp.take_along_axis(logp, safe[..., None],
                                   axis=-1)[..., 0]
        lm = jnp.sum(nll * valid) / jnp.maximum(
            jnp.sum(valid).astype(jnp.float32), 1.0)
        mc_logp = jax.nn.log_softmax(mc_logits, axis=-1)
        mc = jnp.mean(-jnp.take_along_axis(
            mc_logp, mc_labels[..., None], axis=-1)[..., 0])
        return lm_coef * lm + mc_coef * mc

    losses, grads = [], []
    W = batch["input_ids"].shape[0]
    for w in range(W):
        loss, g = jax.value_and_grad(client_loss)(
            flat, batch["input_ids"][w], batch["token_type_ids"][w],
            batch["shifted_labels"][w], batch["mc_token_ids"][w],
            batch["mc_labels"][w])
        losses.append(loss)
        grads.append(g)
    agg = sum(grads) / W
    return agg, sum(losses) / W


def test_sp_round_matches_dense_oracle():
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 1, 2, 32
    mesh = make_sp_mesh(2, 4)

    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(0)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)

    round_fn = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel))
    agg_sp, per_client_sp = round_fn(flat, batch)
    assert per_client_sp.shape == (W,)
    loss_sp = np.asarray(per_client_sp).sum() / W

    agg_ref, loss_ref = _dense_oracle(cfg, params, flat, unravel,
                                      batch, 1.0, 1.0)
    np.testing.assert_allclose(float(loss_sp), float(loss_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(agg_sp), np.asarray(agg_ref),
                               rtol=5e-4, atol=2e-5)


def test_sp_per_client_losses_match_oracle():
    """Each client's reported loss equals its own dense-oracle loss —
    not a replicated round mean (round-2 review weak #6)."""
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 1, 2, 32
    mesh = make_sp_mesh(2, 4)
    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(7)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)

    round_fn = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel))
    _, per_client_sp = round_fn(flat, batch)
    for w in range(W):
        _, loss_w = _dense_oracle(
            cfg, params, flat, unravel,
            {k: v[w:w + 1] for k, v in batch.items()}, 1.0, 1.0)
        np.testing.assert_allclose(float(per_client_sp[w]),
                                   float(loss_w), rtol=1e-5,
                                   atol=1e-5)


def test_sp_no_full_vocab_logits_buffer():
    """The compiled SP round must not contain the (B·N, T_local, V)
    LM logits tensor: the chunked vocab CE caps the vocab-head buffer
    at one token chunk (round-2 review weak #6)."""
    import re

    cfg = GPT2Config(vocab_size=512, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 1, 2, 64
    mesh = make_sp_mesh(2, 4)
    T_local = T // 4
    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(5)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)

    # chunk = 4 tokens per example: any f32 buffer of V columns must
    # have token dim <= 4, never the full local shard of 16
    round_fn = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel,
                                           tokens_per_chunk=4 * B * N))
    text = round_fn.lower(flat, batch).compile().as_text()
    full = re.findall(rf"f32\[[0-9,]*{T_local},{cfg.vocab_size}\]",
                      text)
    assert not full, f"full-shard vocab logits present: {full[:3]}"


def test_sp_tokens_per_chunk_threading(monkeypatch):
    """--tokens_per_chunk reaches the chunked vocab CE: 0 resolves to
    the auto default (256, the measured memory knee: core/rounds_sp.py),
    an explicit value passes through unchanged (round-3 review
    weak #3: the knee was hard-coded out of reach)."""
    from commefficient_tpu.core import rounds_sp
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked

    seen = []

    def capture(h, wte, labels, dtype, ignore_index=-100,
                tokens_per_chunk=1024):
        seen.append(tokens_per_chunk)
        return lm_nll_sums_chunked(h, wte, labels, dtype,
                                   ignore_index=ignore_index,
                                   tokens_per_chunk=tokens_per_chunk)

    monkeypatch.setattr(rounds_sp, "lm_nll_sums_chunked", capture)

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 1, 2, 32
    mesh = make_sp_mesh(2, 4)
    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(0)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)

    ref, _ = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel))(
        flat, batch)
    assert seen and all(c == 256 for c in seen)  # 0 -> auto 256

    seen.clear()
    out, _ = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel,
                                         tokens_per_chunk=8))(
        flat, batch)
    assert seen and all(c == 8 for c in seen)
    # chunking is an evaluation order, not a different objective
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=2e-5)


def test_sp_round_ragged_examples():
    """Padded example rows are excluded from loss and gradient."""
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 2, 2, 32
    mesh = make_sp_mesh(2, 4)
    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(2)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)
    # client 1's second example is padding
    batch["mask"] = jnp.asarray([[1.0, 1.0], [1.0, 0.0]], jnp.float32)

    round_fn = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel))
    agg_sp, per_client_sp = round_fn(flat, batch)
    loss_sp = np.asarray(per_client_sp).sum() / W

    # oracle: slice client 1 down to its single real example
    trimmed = {
        "input_ids": [batch["input_ids"][0], batch["input_ids"][1, :1]],
        "token_type_ids": [batch["token_type_ids"][0],
                           batch["token_type_ids"][1, :1]],
        "shifted_labels": [batch["shifted_labels"][0],
                           batch["shifted_labels"][1, :1]],
        "mc_token_ids": [batch["mc_token_ids"][0],
                         batch["mc_token_ids"][1, :1]],
        "mc_labels": [batch["mc_labels"][0], batch["mc_labels"][1, :1]],
    }
    losses, grads = [], []
    for w in range(W):
        one = {k: jnp.asarray(v[w])[None] for k, v in trimmed.items()}
        one["mask"] = jnp.ones((1, one["input_ids"].shape[1]),
                               jnp.float32)
        a, l = _dense_oracle(cfg, params, flat, unravel, one, 1.0, 1.0)
        grads.append(a)
        losses.append(l)
    agg_ref = sum(grads) / W
    np.testing.assert_allclose(float(loss_sp),
                               float(sum(losses) / W),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(agg_sp), np.asarray(agg_ref),
                               rtol=5e-4, atol=2e-5)


def test_sp_round_client_mask():
    """A masked-out client contributes nothing."""
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    W, B, N, T = 2, 1, 2, 32
    mesh = make_sp_mesh(2, 4)
    dense = GPT2DoubleHeads(cfg)
    rng = np.random.RandomState(1)
    ids0 = jnp.zeros((B, N, T), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), ids0,
                        jnp.zeros((B, N), jnp.int32), ids0)["params"]
    flat, unravel = flatten_params(params)
    batch = _batch(rng, W, B, N, T, cfg.vocab_size)
    batch["mask"] = jnp.asarray([[1.0], [0.0]], jnp.float32)

    round_fn = jax.jit(build_sp_gpt2_round(cfg, mesh, unravel))
    agg_sp, per_client_sp = round_fn(flat, batch)
    assert float(per_client_sp[1]) == 0.0  # masked client reports 0

    agg_ref, _ = _dense_oracle(
        cfg, params, flat, unravel,
        {k: v[:1] for k, v in batch.items()}, 1.0, 1.0)
    np.testing.assert_allclose(np.asarray(agg_sp), np.asarray(agg_ref),
                               rtol=5e-4, atol=2e-5)


def test_gpt2_train_cli_seq_devices(tmp_path):
    """Full trainer path with --seq_devices: sequence-parallel client
    rounds feeding the sketch-mode server step."""
    from commefficient_tpu.train import gpt2_train

    results = gpt2_train.main([
        "--test", "--dataset_name", "PERSONA",
        "--dataset_dir", str(tmp_path / "data"),
        "--mode", "sketch", "--error_type", "virtual",
        "--local_momentum", "0", "--virtual_momentum", "0.9",
        "--num_workers", "2", "--local_batch_size", "2",
        "--num_epochs", "1", "--seq_devices", "4",
    ])
    assert len(results) == 1
    assert np.isfinite(results[0]["train_loss"])
    assert np.isfinite(results[0]["val_ppl"])


def test_seq_devices_rejects_local_state_modes(tmp_path):
    from commefficient_tpu.train import gpt2_train
    import pytest as _pytest

    with _pytest.raises(ValueError):
        gpt2_train.main([
            "--test", "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path / "data"),
            "--mode", "local_topk", "--error_type", "local",
            "--num_workers", "2", "--local_batch_size", "2",
            "--num_epochs", "1", "--seq_devices", "4",
        ])

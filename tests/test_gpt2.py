"""GPT-2 double-heads tests: shapes, loss masking, torch parity,
persona input building, end-to-end smoke."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models.gpt2 import (GPT2Config, GPT2DoubleHeads,
                                           convert_torch_gpt2,
                                           gpt2_double_heads_loss)


class TestModel:
    def test_shapes(self):
        cfg = GPT2Config.tiny()
        m = GPT2DoubleHeads(cfg)
        B, N, T = 2, 2, 16
        ids = jnp.zeros((B, N, T), jnp.int32)
        mc = jnp.full((B, N), T - 1, jnp.int32)
        params = m.init(jax.random.PRNGKey(0), ids, mc, ids)["params"]
        lm, mcl = m.apply({"params": params}, ids, mc, ids)
        assert lm.shape == (B, N, T, cfg.vocab_size)
        assert mcl.shape == (B, N)

    def test_loss_ignores_masked_labels(self):
        lm = jnp.zeros((1, 1, 4, 8))
        mc = jnp.zeros((1, 1))
        labels_all_ignored = jnp.full((1, 1, 4), -1, jnp.int32)
        loss, lm_loss, _ = gpt2_double_heads_loss(
            lm, mc, labels_all_ignored, jnp.zeros((1,), jnp.int32),
            ignore_index=-1)
        assert float(lm_loss) == 0.0

    def test_causality(self):
        """Changing a future token must not affect past LM logits."""
        cfg = GPT2Config.tiny()
        m = GPT2DoubleHeads(cfg)
        B, N, T = 1, 1, 8
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, N, T)),
                          jnp.int32)
        mc = jnp.full((B, N), T - 1, jnp.int32)
        params = m.init(jax.random.PRNGKey(0), ids, mc, ids)["params"]
        lm1, _ = m.apply({"params": params}, ids, mc, ids)
        ids2 = ids.at[0, 0, -1].set((ids[0, 0, -1] + 1)
                                    % cfg.vocab_size)
        lm2, _ = m.apply({"params": params}, ids2, mc, ids2)
        np.testing.assert_allclose(lm1[0, 0, :-1], lm2[0, 0, :-1],
                                   atol=1e-5)


class TestTorchParity:
    def test_transformer_matches_hf_gpt2(self):
        """Random-init HF torch GPT-2 -> convert -> identical LM
        logits. Proves the checkpoint conversion path and the
        transformer math (layout, LN eps, gelu, causal mask)."""
        torch = pytest.importorskip("torch")
        from transformers import GPT2Config as HFConfig
        from transformers import GPT2LMHeadModel

        hf_cfg = HFConfig(vocab_size=128, n_positions=32, n_embd=16,
                          n_layer=2, n_head=2)
        torch.manual_seed(0)
        hf = GPT2LMHeadModel(hf_cfg).eval()

        cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=16,
                         n_layer=2, n_head=2)
        sd = {k: v.numpy() for k, v in hf.state_dict().items()}
        params = convert_torch_gpt2(sd, cfg)

        m = GPT2DoubleHeads(cfg)
        rng = np.random.RandomState(1)
        ids_np = rng.randint(0, 128, (2, 1, 16))
        with torch.no_grad():
            want = hf(torch.tensor(ids_np.reshape(2, 16))
                      ).logits.numpy()
        ids = jnp.asarray(ids_np, jnp.int32)
        mc = jnp.full((2, 1), 15, jnp.int32)
        lm, _ = m.apply({"params": {"params": params}["params"]},
                        ids, mc, None)
        got = np.asarray(lm[:, 0])
        np.testing.assert_allclose(got, want.reshape(2, 16, 128),
                                   rtol=2e-3, atol=2e-3)


class TestPersonaInputs:
    def test_build_input_from_segments(self):
        from commefficient_tpu.data.fed_persona import \
            build_input_from_segments
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        bos, eos, s1, s2 = tok.convert_tokens_to_ids(
            SPECIAL_TOKENS[:-1])
        persona = [[10, 11]]
        history = [[20], [21]]
        reply = [30, 31]
        inst = build_input_from_segments(persona, history, reply, tok,
                                         lm_labels=True)
        # layout: [bos p p] [s1 20] [s2 21]... wait — speaker parity:
        # last segment (reply) gets speaker2, alternating backwards
        ids = inst["input_ids"]
        assert ids[0] == bos
        assert ids[-1] == eos
        assert inst["mc_token_ids"] == len(ids) - 1
        # lm labels: -1 everywhere except the reply tokens + eos
        # (reference fed_persona.py:354-357: [-1]*prefix + [-1] +
        # sequence[-1][1:], where sequence[-1] = [spk, *reply, eos])
        labels = inst["lm_labels"]
        n_prefix = len(ids) - (len(reply) + 1)
        assert all(l == -1 for l in labels[:n_prefix])
        assert labels[-(len(reply) + 1):] == [30, 31, eos]

    def test_build_input_golden_streams(self):
        """Hardcoded golden token streams for the serialization
        protocol (generated from the reference algorithm,
        fed_persona.py:330-358): exact ids/types/labels/mc positions,
        covering empty persona, empty history, odd/even history
        lengths (the type-vs-speaker parity quirk) and with_eos."""
        from commefficient_tpu.data.fed_persona import \
            build_input_from_segments
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        golden = [
            (dict(persona=[[10, 11]], history=[[20], [21]],
                  reply=[30, 31], lm_labels=True, with_eos=True),
             [256, 10, 11, 259, 20, 258, 21, 259, 30, 31, 257],
             [258, 258, 258, 259, 259, 258, 258, 259, 259, 259, 259],
             [-1, -1, -1, -1, -1, -1, -1, -1, 30, 31, 257], 10),
            (dict(persona=[[10, 11], [12]],
                  history=[[20], [21], [22]], reply=[30],
                  lm_labels=False, with_eos=True),
             [256, 10, 11, 12, 258, 20, 259, 21, 258, 22, 259, 30,
              257],
             [258, 258, 258, 258, 259, 259, 258, 258, 259, 259, 258,
              258, 258],
             [-1] * 13, 12),
            (dict(persona=[[5]], history=[], reply=[7, 8, 9],
                  lm_labels=True, with_eos=False),
             [256, 5, 259, 7, 8, 9],
             [258, 258, 259, 259, 259, 259],
             [-1, -1, -1, 7, 8, 9], 5),
            (dict(persona=[], history=[[1], [2], [3], [4]],
                  reply=[6], lm_labels=True, with_eos=True),
             [256, 259, 1, 258, 2, 259, 3, 258, 4, 259, 6, 257],
             [258, 259, 259, 258, 258, 259, 259, 258, 258, 259, 259,
              259],
             [-1, -1, -1, -1, -1, -1, -1, -1, -1, -1, 6, 257], 11),
        ]
        for kw, ids, tt, lm, mc in golden:
            inst = build_input_from_segments(
                kw["persona"], kw["history"], kw["reply"], tok,
                lm_labels=kw["lm_labels"], with_eos=kw["with_eos"])
            assert inst["input_ids"] == ids
            assert inst["token_type_ids"] == tt
            assert inst["lm_labels"] == lm
            assert inst["mc_token_ids"] == mc

    def test_synthetic_archive_and_dataset(self, tmp_path):
        from commefficient_tpu.data.fed_persona import (
            FedPERSONA, generate_synthetic_personachat)
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        generate_synthetic_personachat(str(tmp_path))
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        ds = FedPERSONA(tok, 2, 2, 1, str(tmp_path), "PERSONA",
                        train=True)
        assert ds.num_clients == 8
        cid, *rest = ds[0]
        assert cid == 0
        assert len(rest) == 5
        val = FedPERSONA(tok, -1, 2, 1, str(tmp_path), "PERSONA",
                         train=False)
        assert val[0][0] == -1


class TestPersonaPrefetch:
    """PersonaFedLoader's background collation must be byte-identical
    to the synchronous path — every RNG stream in submission order
    (round-2 review weak #7: the prefetch BENCHMARKS promised now
    exists)."""

    def _stack(self, root, depth, epochs=2):
        from commefficient_tpu.data.fed_persona import FedPERSONA
        from commefficient_tpu.data.fed_sampler import FedSampler
        from commefficient_tpu.data.loader import PersonaFedLoader
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        ds = FedPERSONA(tok, 2, 2, 1, root, "PERSONA", train=True,
                        seed=3)
        sampler = FedSampler(ds, num_workers=2, local_batch_size=2,
                             seed=3)
        loader = PersonaFedLoader(ds, sampler, 2, 64, 0,
                                  dropout_prob=0.3, dropout_seed=5,
                                  prefetch_depth=depth)
        out = []
        for _ in range(epochs):  # dataset _rng persists across epochs
            out.extend(list(loader))
        return out

    def test_identical_to_synchronous(self, tmp_path):
        from commefficient_tpu.data.fed_persona import (
            generate_synthetic_personachat)
        generate_synthetic_personachat(str(tmp_path))
        sync = self._stack(str(tmp_path), depth=1)
        pre = self._stack(str(tmp_path), depth=3)
        assert len(sync) == len(pre) and len(sync) > 2
        for a, b in zip(sync, pre):
            assert a.keys() == b.keys()
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)

    def test_abandoned_iteration_is_safe(self, tmp_path):
        """Breaking out mid-epoch (NaN abort) must retire the producer
        without deadlock, and a later fresh iteration still yields."""
        from commefficient_tpu.data.fed_persona import (
            FedPERSONA, generate_synthetic_personachat)
        from commefficient_tpu.data.fed_sampler import FedSampler
        from commefficient_tpu.data.loader import PersonaFedLoader
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        generate_synthetic_personachat(str(tmp_path))
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        ds = FedPERSONA(tok, 2, 2, 1, str(tmp_path), "PERSONA",
                        train=True)
        loader = PersonaFedLoader(
            ds, FedSampler(ds, num_workers=2, local_batch_size=2,
                           seed=0), 2, 64, 0, prefetch_depth=2)
        it = iter(loader)
        next(it)
        it.close()  # abandon
        again = list(loader)
        assert len(again) >= 1


class TestGpt2TrainSmoke:
    def test_end_to_end(self, tmp_path):
        from commefficient_tpu.train import gpt2_train
        results = gpt2_train.main([
            "--test", "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path),
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--num_workers", "2",
            "--local_batch_size", "2", "--num_epochs", "1",
            "--lr_scale", "0.01",
        ])
        assert len(results) == 1
        assert np.isfinite(results[0]["train_loss"])
        assert np.isfinite(results[0]["val_ppl"])


class TestPretrainedLoadPath:
    """The reference's core GPT-2 story is fine-tuning a *pretrained*
    HF checkpoint (gpt2_train.py:262-285, incl. special-token
    embedding resize). Fabricate a random-weight HF-layout dir
    (pytorch_model.bin + vocab.json/merges.txt) and prove the whole
    disk path: tokenizer load, weight conversion, embedding resize,
    logits parity, and a federated round."""

    def _fabricate(self, d):
        torch = pytest.importorskip("torch")
        import json as _json

        from transformers import GPT2Config as HFConfig
        from transformers import GPT2LMHeadModel

        from commefficient_tpu.data.tokenizer import _bytes_to_unicode
        # byte-level vocab (the real GPT-2 vocab's first 256 entries)
        vocab = {ch: i for i, ch in
                 enumerate(_bytes_to_unicode().values())}
        with open(os.path.join(d, "vocab.json"), "w") as f:
            _json.dump(vocab, f)
        with open(os.path.join(d, "merges.txt"), "w") as f:
            f.write("#version: 0.2\n")
        hf_cfg = HFConfig(vocab_size=256, n_positions=256, n_embd=32,
                          n_layer=2, n_head=2)
        torch.manual_seed(7)
        hf = GPT2LMHeadModel(hf_cfg).eval()
        torch.save(hf.state_dict(),
                   os.path.join(d, "pytorch_model.bin"))
        return hf

    def test_disk_path_resize_and_logits(self, tmp_path):
        torch = pytest.importorskip("torch")
        from commefficient_tpu.config import Config
        from commefficient_tpu.data.tokenizer import GPT2BPETokenizer
        from commefficient_tpu.train.gpt2_train import \
            build_model_and_tokenizer

        hf = self._fabricate(str(tmp_path))
        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, num_workers=1,
                      local_batch_size=2, num_clients=2,
                      dataset_name="PERSONA", seed=0, do_test=True,
                      model_checkpoint=str(tmp_path))
        module, params, tok = build_model_and_tokenizer(args)

        assert isinstance(tok, GPT2BPETokenizer)
        assert len(tok) == 256 + 5  # 5 special tokens added
        wte = np.asarray(params["transformer"]["wte"])
        assert wte.shape == (261, 32)
        base = hf.state_dict()["transformer.wte.weight"].numpy()
        np.testing.assert_array_equal(wte[:256], base)
        # resized rows are the mean of the base embedding (HF resize)
        np.testing.assert_allclose(
            wte[256:], np.tile(base.mean(0, keepdims=True), (5, 1)),
            rtol=1e-6)

        # logits parity on base-vocab ids through the loaded params
        rng = np.random.RandomState(3)
        ids_np = rng.randint(0, 256, (2, 1, 16))
        with torch.no_grad():
            want = hf(torch.tensor(ids_np.reshape(2, 16))
                      ).logits.numpy()
        lm, _ = module.apply({"params": params},
                             jnp.asarray(ids_np, jnp.int32),
                             jnp.full((2, 1), 15, jnp.int32), None)
        np.testing.assert_allclose(np.asarray(lm[:, 0])[..., :256],
                                   want.reshape(2, 16, 256),
                                   rtol=2e-3, atol=2e-3)

    def test_federated_round_from_pretrained(self, tmp_path):
        """One --test federated round starting from the fabricated HF
        checkpoint (reference gpt2_train.py round loop on a pretrained
        model)."""
        pytest.importorskip("torch")
        from commefficient_tpu.data.fed_persona import \
            generate_synthetic_personachat
        from commefficient_tpu.train import gpt2_train

        ckpt = tmp_path / "ckpt"
        data = tmp_path / "data"
        ckpt.mkdir()
        data.mkdir()
        self._fabricate(str(ckpt))
        generate_synthetic_personachat(str(data))
        results = gpt2_train.main([
            "--test", "--dataset_name", "PERSONA",
            "--dataset_dir", str(data),
            "--model_checkpoint", str(ckpt),
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--num_workers", "2",
            "--local_batch_size", "2", "--num_epochs", "1",
            "--lr_scale", "0.01",
        ])
        assert np.isfinite(results[0]["train_loss"])
        assert np.isfinite(results[0]["val_ppl"])


class TestFullCandidateValidation:
    """Reference restricts candidates only when *training*
    (fed_persona.py:251-254): val MC accuracy is measured over the
    item's full candidate list, not num_candidates."""

    def _val_ds(self, tmp_path, n_cands):
        from commefficient_tpu.data.fed_persona import (
            FedPERSONA, generate_synthetic_personachat)
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        generate_synthetic_personachat(str(tmp_path),
                                       num_candidates=n_cands)
        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        # num_candidates=2 restriction must NOT apply to val items
        return FedPERSONA(tok, 2, 2, 1, str(tmp_path), "PERSONA",
                          train=False)

    def test_val_items_keep_all_candidates(self, tmp_path):
        ds = self._val_ds(tmp_path, n_cands=5)
        cid, input_ids, mc_tok, lm_lab, mc_lab, tt = ds[0]
        assert cid == -1
        assert len(input_ids) == 5          # all candidates kept
        assert mc_lab == 4                  # gold is last

    def test_val_loader_pads_and_masks(self, tmp_path):
        from commefficient_tpu.data.loader import PersonaValLoader
        ds = self._val_ds(tmp_path, n_cands=5)
        loader = PersonaValLoader(ds, 2, 8, 64, pad_id=0,
                                  shards_per_step=1)
        batch = next(iter(loader))
        assert batch["input_ids"].shape[2] == 8
        # real rows: 5 valid candidate slots, 3 padded; gold index 4
        rows = np.nonzero(batch["mask"])
        np.testing.assert_array_equal(
            batch["cand_mask"][rows][:, :5], 1.0)
        np.testing.assert_array_equal(
            batch["cand_mask"][rows][:, 5:], 0.0)
        np.testing.assert_array_equal(batch["mc_labels"][rows], 4)

    def test_mc_argmax_never_picks_padded_slot(self):
        """compute_loss_val masks mc_logits with cand_mask: a padded
        slot carrying the max raw logit must not be predicted."""
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.train.gpt2_train import \
            make_compute_loss_val

        from commefficient_tpu.models.gpt2 import GPT2Config

        class StubModule:
            cfg = GPT2Config.tiny()

            def apply(self, variables, input_ids, mc_token_ids,
                      token_type_ids, return_hidden=False):
                assert return_hidden
                B, N, T = input_ids.shape
                h = jnp.zeros((B * N, T, 8), jnp.float32)
                wte = jnp.zeros((16, 8), jnp.float32)
                mc = jnp.zeros((B, N), jnp.float32)
                mc = mc.at[..., -1].set(10.0)  # padded slot: max
                mc = mc.at[..., 1].set(5.0)    # gold slot: runner-up
                return h, wte, mc

        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, num_workers=1,
                      local_batch_size=2, num_clients=2,
                      dataset_name="PERSONA", seed=0)
        loss_fn = make_compute_loss_val(StubModule(), args)
        B, N, T = 2, 4, 8
        batch = {
            "input_ids": np.zeros((B, N, T), np.int32),
            "token_type_ids": np.zeros((B, N, T), np.int32),
            "lm_labels": np.full((B, N, T), -1, np.int32),
            "mc_token_ids": np.zeros((B, N), np.int32),
            "mc_labels": np.full((B,), 1, np.int32),
            "cand_mask": np.zeros((B, N), np.float32),
            "mask": np.ones((B,), np.float32),
        }
        batch["cand_mask"][..., :2] = 1.0  # only slots 0,1 are real
        _, (acc,) = loss_fn(None, batch, None)
        assert float(acc) == 1.0  # masked argmax lands on gold (1)
        # without the mask the padded slot 3 would win and acc = 0
        del batch["cand_mask"]
        _, (acc_unmasked,) = loss_fn(None, batch, None)
        assert float(acc_unmasked) == 0.0

    def test_end_to_end_full_candidates(self, tmp_path):
        """A random-init --test run over a 5-candidate archive: val MC
        accuracy is measured over all 5 (chance ~1/5, and certainly
        below the 2-candidate chance of 1/2 it used to report)."""
        from commefficient_tpu.data.fed_persona import \
            generate_synthetic_personachat
        from commefficient_tpu.train import gpt2_train
        generate_synthetic_personachat(str(tmp_path), num_candidates=5)
        results = gpt2_train.main([
            "--test", "--dataset_name", "PERSONA",
            "--dataset_dir", str(tmp_path),
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--num_workers", "2",
            "--local_batch_size", "2", "--num_epochs", "1",
            "--lr_scale", "0.0",
        ])
        assert 0.0 <= results[0]["val_acc"] <= 0.45


class TestRemat:
    def test_remat_identical_outputs_and_grads(self):
        """--remat must not change the math — same forward logits and
        same gradients, only the backward's memory/FLOP schedule."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from commefficient_tpu.models.gpt2 import (GPT2Config,
                                                   GPT2DoubleHeads)

        cfg = GPT2Config.tiny()
        rng = np.random.RandomState(0)
        B, N, T = 2, 2, 10
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, N, T)),
                          jnp.int32)
        mc = jnp.asarray(rng.randint(0, T, (B, N)), jnp.int32)
        base = GPT2DoubleHeads(cfg)
        remat = GPT2DoubleHeads(dataclasses.replace(cfg, remat=True))
        params = base.init(jax.random.PRNGKey(0), ids, mc)["params"]

        lm0, mc0 = base.apply({"params": params}, ids, mc)
        lm1, mc1 = remat.apply({"params": params}, ids, mc)
        np.testing.assert_array_equal(np.asarray(lm0), np.asarray(lm1))
        np.testing.assert_array_equal(np.asarray(mc0), np.asarray(mc1))

        def loss(module, p):
            lm, _ = module.apply({"params": p}, ids, mc)
            return jnp.sum(lm ** 2)

        g0 = jax.grad(lambda p: loss(base, p))(params)
        g1 = jax.grad(lambda p: loss(remat, p))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


class TestBatchedTrainLoss:
    def test_matches_per_example_double_heads_loss(self):
        """The batched train loss must equal the mask-weighted mean of
        gpt2_double_heads_loss applied example by example (the
        formulation it replaced for speed)."""
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.models.gpt2 import (
            GPT2Config, GPT2DoubleHeads, gpt2_double_heads_loss)
        from commefficient_tpu.train.gpt2_train import (
            make_compute_loss_train)

        cfg = Config(mode="uncompressed", error_type="none",
                     local_momentum=0.0, num_workers=2,
                     local_batch_size=2, num_clients=4,
                     dataset_name="PERSONA", seed=0,
                     lm_coef=2.0, mc_coef=0.5)
        gcfg = GPT2Config.tiny()
        module = GPT2DoubleHeads(gcfg)
        rng = np.random.RandomState(0)
        B, N, T = 3, 2, 12
        batch = {
            "input_ids": jnp.asarray(
                rng.randint(0, gcfg.vocab_size, (B, N, T)), jnp.int32),
            "token_type_ids": jnp.asarray(
                rng.randint(0, gcfg.vocab_size, (B, N, T)), jnp.int32),
            "lm_labels": jnp.asarray(np.where(
                rng.rand(B, N, T) < 0.3, -1,
                rng.randint(0, gcfg.vocab_size, (B, N, T))), jnp.int32),
            "mc_token_ids": jnp.asarray(rng.randint(0, T, (B, N)),
                                        jnp.int32),
            "mc_labels": jnp.asarray(rng.randint(0, N, (B,)),
                                     jnp.int32),
            "mask": jnp.asarray([1.0, 1.0, 0.0]),
        }
        params = module.init(jax.random.PRNGKey(0),
                             batch["input_ids"],
                             batch["mc_token_ids"],
                             batch["input_ids"])["params"]
        got, _ = make_compute_loss_train(module, cfg)(params, batch,
                                                      cfg)

        lm_logits, mc_logits = module.apply(
            {"params": params}, batch["input_ids"],
            batch["mc_token_ids"], batch["token_type_ids"])
        per = []
        for i in range(B):
            loss_i, _, _ = gpt2_double_heads_loss(
                lm_logits[i:i + 1], mc_logits[i:i + 1],
                batch["lm_labels"][i:i + 1],
                batch["mc_labels"][i:i + 1],
                lm_coef=cfg.lm_coef, mc_coef=cfg.mc_coef,
                ignore_index=-1)
            per.append(float(loss_i))
        m = np.asarray(batch["mask"])
        want = float(np.sum(np.asarray(per) * m) / m.sum())
        np.testing.assert_allclose(float(got), want, rtol=2e-5)

    def test_chunked_lm_loss_gradients_match_full_logits(self):
        """Gradients through the chunked (scan + checkpoint) LM loss
        must match gradients of the same loss computed from full
        logits — the chunking is a memory schedule, not new math."""
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.models.gpt2 import (
            GPT2Config, GPT2DoubleHeads, lm_nll_sums_chunked,
            token_nll)

        gcfg = GPT2Config.tiny()
        module = GPT2DoubleHeads(gcfg)
        rng = np.random.RandomState(1)
        B, N, T = 2, 2, 14  # T-1=13, tc=2: pad=1 exercises padding
        ids = jnp.asarray(rng.randint(0, gcfg.vocab_size, (B, N, T)),
                          jnp.int32)
        mc = jnp.asarray(rng.randint(0, T, (B, N)), jnp.int32)
        labels = jnp.asarray(np.where(
            rng.rand(B * N, T) < 0.3, -1,
            rng.randint(0, gcfg.vocab_size, (B * N, T))), jnp.int32)
        params = module.init(jax.random.PRNGKey(0), ids, mc,
                             ids)["params"]

        def loss_chunked(p):
            h, wte, _ = module.apply({"params": p}, ids, mc, ids,
                                     return_hidden=True)
            sn, sv = lm_nll_sums_chunked(h[:, :-1], wte,
                                         labels[:, 1:], gcfg.dtype,
                                         ignore_index=-1,
                                         tokens_per_chunk=8)
            return jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)

        def loss_full(p):
            h, wte, _ = module.apply({"params": p}, ids, mc, ids,
                                     return_hidden=True)
            logits = jnp.einsum("btc,vc->btv",
                                h[:, :-1].astype(gcfg.dtype),
                                wte.astype(gcfg.dtype),
                                preferred_element_type=jnp.float32)
            nll, valid = token_nll(logits, labels[:, 1:], -1)
            return jnp.sum(nll * valid) \
                / jnp.maximum(jnp.sum(valid), 1.0)

        lc, gc = jax.value_and_grad(loss_chunked)(params)
        lf, gf = jax.value_and_grad(loss_full)(params)
        np.testing.assert_allclose(float(lc), float(lf), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(gc),
                        jax.tree_util.tree_leaves(gf)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


class TestFabricatedAssets:
    """The full-size learning-run stand-ins (zero-egress environment):
    the fabricated 50257-entry BPE vocab and the learnable
    persona-correlated corpus. Their invariants are load-bearing for
    the convergence evidence — the NLL floor math assumes every
    synthetic word is ONE token, and MC learnability assumes the gold
    candidate is last and shares the persona's signature."""

    @staticmethod
    def _dialog_signature(dialog):
        # reconstruct the signature: persona, history and gold replies
        # all draw from the SAME signature_size-word set
        words = {w for s in dialog["personality"] for w in s.split()}
        for u in dialog["utterances"]:
            words |= set(u["candidates"][-1].split())
            for h in u["history"]:
                words |= set(h.split())
        return frozenset(words)

    def test_fabricated_vocab_single_token_words(self, tmp_path):
        import random

        from commefficient_tpu.data.tokenizer import (GPT2BPETokenizer,
                                                      SPECIAL_TOKENS,
                                                      fabricate_bpe_vocab)
        words = fabricate_bpe_vocab(str(tmp_path), vocab_size=50257,
                                    num_words=500, seed=3)
        tok = GPT2BPETokenizer(str(tmp_path))
        assert len(tok) == 50257
        assert tok.add_special_tokens(SPECIAL_TOKENS) == 5
        assert len(tok) == 50262  # the reference fine-tune vocab size
        rng = random.Random(0)
        sample = rng.sample(words, 40)
        ids = set()
        for w in sample:
            bare, spaced = tok.encode(w), tok.encode(" " + w)
            assert len(bare) == 1 and len(spaced) == 1, w
            ids.update(bare + spaced)
        assert len(ids) == 80  # distinct tokens, bare != spaced
        # ids spread across the table, not a dense prefix
        assert max(ids) - min(ids) > 25000
        # decode round-trips through the byte table
        s = " ".join(sample[:5])
        assert tok.decode(tok.encode(s)) == s

    def test_learnable_corpus_structure(self, tmp_path):
        import json

        from commefficient_tpu.data.fed_persona import (
            RAW_NAME, generate_learnable_personachat)
        words = [a + b for a in ("ba", "ke", "lu", "mi", "po", "su")
                 for b in ("da", "fe", "go", "ni", "ra", "tu")]
        generate_learnable_personachat(
            str(tmp_path), words, num_personalities=6,
            dialogs_per_personality=2, utterances_per_dialog=3,
            num_candidates=4, signature_size=5, num_val_dialogs=4,
            seed=0)
        with open(tmp_path / RAW_NAME) as f:
            data = json.load(f)
        assert len(data["train"]) == 12 and len(data["valid"]) == 4

        sig_of = self._dialog_signature
        train_sigs, val_sigs = [], []
        for split, sigs in (("train", train_sigs),
                            ("valid", val_sigs)):
            for d in data[split]:
                sig = sig_of(d)
                # everything the persona says fits one signature set
                assert len(sig) <= 5, sorted(sig)
                sigs.append(sig)
                for u in d["utterances"]:
                    cands = u["candidates"]
                    assert len(cands) == 4
                    # gold last, drawn from the persona signature
                    assert set(cands[-1].split()) <= sig
        # val personalities are UNSEEN in training (the rule, not the
        # strings, is what validation measures)
        assert not set(train_sigs) & set(val_sigs)

    def test_seen_persona_val_tier(self, tmp_path):
        """val_from_train_sigs=True: train split byte-identical to the
        default corpus (same seed), val dialogs reuse TRAIN
        signatures — the easier seen-persona evaluation tier."""
        import json

        from commefficient_tpu.data.fed_persona import (
            RAW_NAME, generate_learnable_personachat)
        words = [a + b for a in ("ba", "ke", "lu", "mi")
                 for b in ("da", "fe", "go", "ni")]
        kw = dict(num_personalities=4, dialogs_per_personality=2,
                  utterances_per_dialog=2, num_candidates=3,
                  signature_size=4, num_val_dialogs=4, seed=5)
        generate_learnable_personachat(str(tmp_path / "a"), words,
                                       **kw)
        generate_learnable_personachat(str(tmp_path / "b"), words,
                                       val_from_train_sigs=True, **kw)
        a = json.load(open(tmp_path / "a" / RAW_NAME))
        b = json.load(open(tmp_path / "b" / RAW_NAME))
        assert a["train"] == b["train"]

        train_sigs = [self._dialog_signature(d) for d in b["train"]]
        for d in b["valid"]:
            v = self._dialog_signature(d)
            assert any(v <= t for t in train_sigs), sorted(v)


def test_trainer_losses_thread_tokens_per_chunk(monkeypatch):
    """--tokens_per_chunk reaches the chunked vocab CE from BOTH
    trainer loss closures (0 = the 1024 auto default)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import Config
    from commefficient_tpu.models import gpt2 as gpt2_mod
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.train.gpt2_train import (
        make_compute_loss_train, make_compute_loss_val)

    seen = []
    orig = gpt2_mod.lm_nll_sums_chunked

    def capture(h, wte, labels, dtype, ignore_index=-100,
                tokens_per_chunk=1024):
        seen.append(tokens_per_chunk)
        return orig(h, wte, labels, dtype, ignore_index=ignore_index,
                    tokens_per_chunk=tokens_per_chunk)

    monkeypatch.setattr(gpt2_mod, "lm_nll_sums_chunked", capture)

    gcfg = GPT2Config.tiny()
    module = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    B, N, T = 2, 2, 12
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, gcfg.vocab_size, (B, N, T)), jnp.int32),
        "token_type_ids": jnp.zeros((B, N, T), jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, gcfg.vocab_size, (B, N, T)), jnp.int32),
        "mc_token_ids": jnp.full((B, N), T - 1, jnp.int32),
        "mc_labels": jnp.full((B,), N - 1, jnp.int32),
        "mask": jnp.ones((B,), jnp.float32),
        "cand_mask": jnp.ones((B, N), jnp.float32),
    }
    params = module.init(jax.random.PRNGKey(0), batch["input_ids"],
                         batch["mc_token_ids"],
                         batch["token_type_ids"])["params"]

    base = Config(mode="uncompressed", error_type="none",
                  local_momentum=0.0, num_workers=1,
                  local_batch_size=2, dataset_name="PERSONA")
    ref, _ = make_compute_loss_train(module, base)(params, batch, base)
    assert seen and all(c == 1024 for c in seen)  # 0 -> auto 1024

    import dataclasses
    args = dataclasses.replace(base, tokens_per_chunk=6)
    seen.clear()
    got, _ = make_compute_loss_train(module, args)(params, batch, args)
    assert seen and all(c == 6 for c in seen)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)

    seen.clear()
    make_compute_loss_val(module, args)(params, batch, args)
    assert seen and all(c == 6 for c in seen)


class TestSavePretrained:
    def test_model_and_tokenizer_roundtrip(self, tmp_path):
        """reference fed_aggregator.py:205-212 / gpt2_train.py:278-283:
        final weights + config + tokenizer written HF-style; weights
        and special-token ids survive a reload."""
        import jax
        import jax.numpy as jnp
        from flax import serialization

        from commefficient_tpu.config import Config
        from commefficient_tpu.data.tokenizer import (ByteTokenizer,
                                                      SPECIAL_TOKENS)
        from commefficient_tpu.models.gpt2 import (GPT2Config,
                                                   GPT2DoubleHeads)
        from commefficient_tpu.runtime import FedModel

        cfg = GPT2Config.tiny()
        module = GPT2DoubleHeads(cfg)
        dummy = jnp.zeros((1, 2, 8), jnp.int32)
        params = module.init(jax.random.PRNGKey(0), dummy,
                             jnp.zeros((1, 2), jnp.int32),
                             dummy)["params"]
        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, num_workers=2,
                      local_batch_size=2, num_clients=4,
                      dataset_name="PERSONA", seed=0)

        def loss(p, batch, cfg_):
            return jnp.float32(0.0), ()

        model = FedModel(module, params, loss, args)
        out = tmp_path / "saved"
        model.save_pretrained(str(out))
        assert (out / "config.json").exists()
        with open(out / "flax_model.msgpack", "rb") as f:
            restored = serialization.msgpack_restore(f.read())
        flat0 = jax.tree_util.tree_leaves(model.params())
        flat1 = jax.tree_util.tree_leaves(restored)
        assert len(flat0) == len(flat1)
        for a, b in zip(flat0, flat1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        tok = ByteTokenizer()
        tok.add_special_tokens(SPECIAL_TOKENS)
        tok.save_pretrained(str(out))
        assert (out / "special_tokens.json").exists()

    def test_hf_export_roundtrip_transformers_logits(self, tmp_path):
        """hf_format export (round-2 review missing #2): train a
        federated round, export pytorch_model.bin + HF config, load
        with the real `transformers` GPT2DoubleHeadsModel, and match
        both LM and MC logits — the artifact goes back to the torch/HF
        ecosystem like the reference's save_pretrained
        (fed_aggregator.py:209-212)."""
        torch = pytest.importorskip("torch")
        from transformers import GPT2DoubleHeadsModel

        import jax
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.models.gpt2 import (GPT2Config,
                                                   GPT2DoubleHeads)
        from commefficient_tpu.runtime import FedModel, FedOptimizer
        from commefficient_tpu.train.gpt2_train import (
            make_compute_loss_train)

        cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=16,
                         n_layer=2, n_head=2)
        module = GPT2DoubleHeads(cfg)
        B, N, T = 2, 2, 16
        dummy = jnp.zeros((1, N, 8), jnp.int32)
        params = module.init(jax.random.PRNGKey(0), dummy,
                             jnp.zeros((1, N), jnp.int32),
                             dummy)["params"]
        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, virtual_momentum=0.9,
                      num_workers=2, local_batch_size=B,
                      num_clients=4, dataset_name="PERSONA", seed=0,
                      num_results_train=1)
        model = FedModel(module, params,
                         make_compute_loss_train(module, args), args)
        opt = FedOptimizer([{"lr": 0.01}], args)

        rng = np.random.RandomState(0)
        ids_np = rng.randint(0, 128, (2, B, N, T)).astype(np.int32)
        batch = {
            "input_ids": ids_np,
            "token_type_ids": rng.randint(
                0, 128, (2, B, N, T)).astype(np.int32),
            "lm_labels": ids_np.copy(),
            "mc_token_ids": np.full((2, B, N), T - 1, np.int32),
            "mc_labels": rng.randint(0, N, (2, B)).astype(np.int32),
            "mask": np.ones((2, B), np.float32),
            "client_ids": np.array([0, 1], np.int32),
        }
        model(batch)
        opt.step()  # weights move: the export is of a TRAINED model

        out = tmp_path / "hf"
        model.save_pretrained(str(out), hf_format=True)
        assert (out / "pytorch_model.bin").exists()

        hf = GPT2DoubleHeadsModel.from_pretrained(str(out)).eval()
        ids2 = rng.randint(0, 128, (B, N, T)).astype(np.int32)
        tt2 = rng.randint(0, 128, (B, N, T)).astype(np.int32)
        mc2 = np.full((B, N), T - 1, np.int32)
        with torch.no_grad():
            hf_out = hf(torch.tensor(ids2.astype(np.int64)),
                        token_type_ids=torch.tensor(
                            tt2.astype(np.int64)),
                        mc_token_ids=torch.tensor(
                            mc2.astype(np.int64)))
        lm, mc = module.apply({"params": model.params()},
                              jnp.asarray(ids2),
                              jnp.asarray(mc2), jnp.asarray(tt2))
        np.testing.assert_allclose(np.asarray(lm),
                                   hf_out.logits.numpy(),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(mc),
                                   hf_out.mc_logits.numpy(),
                                   rtol=2e-3, atol=2e-3)

        # and the framework's own reload path reads the same dir
        from commefficient_tpu.models.gpt2 import convert_torch_gpt2
        sd = {k: v.numpy()
              for k, v in torch.load(str(out / "pytorch_model.bin"),
                                     map_location="cpu").items()}
        p2 = convert_torch_gpt2(sd, cfg)
        for a, b in zip(
                jax.tree_util.tree_leaves(
                    model.params()["transformer"]),
                jax.tree_util.tree_leaves(p2["transformer"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_bpe_tokenizer_roundtrip(self, tmp_path):
        """Saved vocab/merges/special files reload into an equivalent
        tokenizer (self-contained run dirs)."""
        import json

        from commefficient_tpu.data.tokenizer import (GPT2BPETokenizer,
                                                      SPECIAL_TOKENS)

        vocab = {"l": 0, "o": 1, "w": 2, "lo": 3, "low": 4, "Ġ": 5}
        (tmp_path / "vocab.json").write_text(json.dumps(vocab))
        (tmp_path / "merges.txt").write_text(
            "#version: 0.2\nl o\nlo w")
        tok = GPT2BPETokenizer(str(tmp_path))
        tok.add_special_tokens(SPECIAL_TOKENS)
        out = tmp_path / "saved"
        tok.save_pretrained(str(out))
        tok2 = GPT2BPETokenizer(str(out))
        assert tok2.encoder == tok.encoder
        assert tok2.bpe_ranks == tok.bpe_ranks
        assert tok2.special == tok.special
        assert tok2.encode("low") == tok.encode("low")
        assert len(tok2) == len(tok)


# --- the compacting vocabulary head (models/gpt2.py) -----------------------
# lm_nll_sums_chunked with an ignore index computes the labelled rows
# alone; everything below holds it to token_nll of the full logits.

_HW, _HE, _HT, _HC, _HV, _HROWS = 3, 4, 15, 16, 67, 16


def _head_labels(case):
    """(W, E, Tm) labels of a case; -1 where a position carries none.
    A client has E * Tm = 60 rows, a chunk 16."""
    rng = np.random.RandomState(3)
    full = rng.randint(0, _HV, (_HW, _HE * _HT)).astype(np.int32)
    lab = np.full_like(full, -1)
    per_client = {"sparse": 2, "all": _HE * _HT, "none": 0,
                  "ragged": 7, "three_chunks": 40,
                  "padding_example": 18}[case]
    for c in range(_HW):
        at = rng.choice(_HE * _HT, per_client, replace=False)
        lab[c, at] = full[c, at]
    lab = lab.reshape(_HW, _HE, _HT)
    if case == "padding_example":
        lab[:, 2] = -1
    return jnp.asarray(lab)


def _head_inputs():
    rng = np.random.RandomState(5)
    h = jnp.asarray(rng.randn(_HW, _HE, _HT, _HC), jnp.float32)
    w = jnp.asarray(rng.randn(_HV, _HC) * 0.3, jnp.float32)
    coef = jnp.asarray(rng.rand(_HW, _HE) + 0.5, jnp.float32)
    return h, w, coef


def _head_pair(dtype):
    """(the head under test, token_nll of the full logits), both
    ``(h (E, Tm, C), w, labels) -> (Σ nll, Σ valid)`` by example."""
    from commefficient_tpu.models.gpt2 import (lm_nll_sums_chunked,
                                               token_nll)

    def head(h, w, lab):
        return lm_nll_sums_chunked(h, w, lab, dtype, ignore_index=-1,
                                   tokens_per_chunk=_HROWS)

    def full(h, w, lab):
        logits = jnp.einsum("etc,vc->etv", h.astype(dtype),
                            w.astype(dtype),
                            preferred_element_type=jnp.float32)
        nll, valid = token_nll(logits, lab, -1)
        return jnp.sum(nll * valid, -1), jnp.sum(valid, -1)

    return head, full


def _head_form(form, fn, lab, coef):
    """``(h, w) -> (loss, (Σ nll, Σ valid))`` and the gradients'
    arguments, for one way the head is called."""
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS

    def one(h, w, lab, coef):
        sn, sv = fn(h, w, lab)
        return jnp.sum(sn * coef), (sn, sv)

    if form == "unbatched":
        return lambda h, w: one(h[0], w, lab[0], coef[0])
    if form == "pooled":      # summed losses, the table shared
        def loss(h, w):
            ls, aux = jax.vmap(lambda h, l, c: one(h, w, l, c),
                               axis_name=SHARED_CLIENTS)(h, lab, coef)
            return jnp.sum(ls), aux
        return loss
    if form == "table_batched":   # a table a client: the fallback
        def loss(h, w):
            wb = w[None] * jnp.arange(1.0, 1.0 + _HW)[:, None, None]
            ls, aux = jax.vmap(one, axis_name=SHARED_CLIENTS)(
                h, wb, lab, coef)
            return jnp.sum(ls), aux
        return loss
    assert form == "local_loss"   # core/rounds.py make_local_loss

    def local_loss(h, w):
        def one_client(h, l):
            sn, sv = fn(h, w, l)
            loss = jnp.sum(sn) / jnp.maximum(jnp.sum(sv), 1.0)
            n = jnp.sum(sv)
            return jnp.where(n > 0, loss * n, 0.0), (sn, sv)
        weighted, aux = jax.vmap(one_client,
                                 axis_name=SHARED_CLIENTS)(h, lab)
        return jnp.sum(weighted) / 7.0, aux
    return local_loss


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


_HEAD_CASES = ("sparse", "all", "none", "ragged", "three_chunks",
               "padding_example")
_HEAD_FORMS = ("unbatched", "pooled", "table_batched", "local_loss")


@pytest.mark.parametrize("case,form,dtype", [
    (c, f, "float32") for c in _HEAD_CASES for f in _HEAD_FORMS] + [
    (c, "pooled", "bfloat16") for c in _HEAD_CASES])
def test_compacting_head_matches_full_logits(case, form, dtype):
    """Per-example Σ nll and Σ valid, and the gradients w.r.t. the
    hidden states and the table, against ``token_nll`` of the full
    logits: few labels, all, none (zero chunks: zeros, no NaN), a count
    that is no multiple of the chunk, one that needs three chunks, an
    example with no label inside a batch; called alone, under the
    clients' ``vmap`` with the table shared (its gradient the sum over
    the clients) or a table a client, and through ``value_and_grad``
    of the summed vmapped loss as ``make_local_loss`` builds it."""
    dt = jnp.dtype(dtype)
    tol = 1e-5 if dtype == "float32" else 2e-2
    h, w, coef = _head_inputs()
    lab = _head_labels(case)
    head, full = _head_pair(dt)
    (l1, (sn1, sv1)), g1 = jax.jit(jax.value_and_grad(
        _head_form(form, head, lab, coef), (0, 1), has_aux=True))(h, w)
    (l0, (sn0, sv0)), g0 = jax.value_and_grad(
        _head_form(form, full, lab, coef), (0, 1), has_aux=True)(h, w)
    np.testing.assert_array_equal(np.asarray(sv1), np.asarray(sv0))
    _close(sn1, sn0, tol)
    _close(l1, l0, tol)
    for a, b in zip(g1, g0):
        assert np.all(np.isfinite(np.asarray(a)))
        _close(a, b, tol)
    if case == "none":
        assert float(jnp.sum(sv1)) == 0.0 and float(l1) == 0.0
        assert not np.any(np.asarray(g1[0])) and not np.any(
            np.asarray(g1[1]))


def test_compacting_head_gives_each_client_its_own_table_gradient():
    """``vmap`` of ``grad`` with a shared table (core/rounds.py
    ``client_round``'s per-client path) asks for every client's own
    gradient of the table: no pooling there, whatever the axis is
    called in the fused round."""
    h, w, coef = _head_inputs()
    lab = _head_labels("ragged")
    head, full = _head_pair(jnp.dtype("float32"))

    def per_client(fn):
        def one(h, l, c):
            return jax.grad(lambda w: jnp.sum(fn(h, w, l)[0] * c))(w)
        return jax.vmap(one)(h, lab, coef)

    got, want = jax.jit(lambda: per_client(head))(), per_client(full)
    assert got.shape == (_HW, _HV, _HC)
    _close(got, want, 1e-5)


@pytest.mark.parametrize("n", [0, 1, _HROWS, _HROWS + 1])
def test_head_work_follows_the_labelled_count(n):
    """The chunk bodies a forward runs: ceil(labelled / rows a chunk),
    found at run time, not the positions' count fixed at trace time."""
    from commefficient_tpu.models.gpt2 import _compact_nll
    h, w, _ = _head_inputs()
    lab = np.full((_HE * _HT,), -1, np.int32)
    lab[np.random.RandomState(n).choice(lab.size, n, replace=False)] = 1
    run = jax.jit(_compact_nll(jnp.dtype("float32"), -1, _HROWS,
                               False).chunks_run)
    got = int(run(h[0], w, jnp.asarray(lab.reshape(_HE, _HT))))
    assert got == -(-n // _HROWS)


def _ops_by_name(lowered):
    """[(operation, its location: the name stack and the Python frames
    it was traced under)] of a lowering, regions included."""
    def walk(op):
        yield op.name, str(op.location)
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    yield from walk(child.operation)
    return list(walk(lowered.compiler_ir("stablehlo").operation))


def test_a_causal_callers_head_orders_gathers_and_writes_back_nothing():
    """``ignore_index=None``: every position carries a label. No sort,
    and no gather or scatter but the label's logit (``token_nll``'s
    ``take_along_axis``) and its transpose; with an ignore index the
    same call holds all three."""
    from commefficient_tpu.models.gpt2 import lm_nll_sums_chunked
    h, w, _ = _head_inputs()
    lab = jnp.abs(_head_labels("all"))[0]

    def lowered(ignore):
        def loss(h, w):
            sn, sv = lm_nll_sums_chunked(h, w, lab, jnp.float32,
                                         ignore_index=ignore,
                                         tokens_per_chunk=_HROWS)
            return jnp.sum(sn / jnp.maximum(sv, 1.0))
        return _ops_by_name(jax.jit(jax.value_and_grad(loss, (0, 1)))
                            .lower(h[0], w))

    def moved_rows(ops):
        return [(op, name) for op, name in ops
                if op in ("stablehlo.gather", "stablehlo.scatter",
                          "stablehlo.sort")
                and "token_nll" not in name]

    causal, masked = lowered(None), lowered(-1)
    assert any(op == "stablehlo.gather" for op, _ in causal)
    assert moved_rows(causal) == []
    assert {op for op, _ in moved_rows(masked)} == {
        "stablehlo.gather", "stablehlo.scatter", "stablehlo.sort"}
    sn_c, sv_c = lm_nll_sums_chunked(h[0], w, lab, jnp.float32,
                                     ignore_index=None,
                                     tokens_per_chunk=_HROWS)
    sn_m, sv_m = lm_nll_sums_chunked(h[0], w, lab, jnp.float32,
                                     ignore_index=-1,
                                     tokens_per_chunk=_HROWS)
    np.testing.assert_array_equal(np.asarray(sv_c), np.asarray(sv_m))
    _close(sn_c, sn_m, 1e-5)


def test_persona_batches_carry_their_labelled_count(tmp_path):
    """``data.collate`` counts the round's positions and those with a
    language-model label on the loader's thread; the counts ride along
    with the batch (through a dropout's rebuilt mask too) for the
    record of the round that consumes it."""
    from commefficient_tpu.data import staging
    from commefficient_tpu.data.fed_persona import (
        generate_synthetic_personachat)
    generate_synthetic_personachat(str(tmp_path))
    batches = TestPersonaPrefetch()._stack(str(tmp_path), depth=3,
                                           epochs=1)
    assert len(batches) > 2
    for b in batches:
        labels = np.asarray(b["lm_labels"])
        assert staging.counters_of(b) == {
            "head.positions": labels.size,
            "head.labelled": int((labels != -1).sum())}
        assert 0 < staging.counters_of(b)["head.labelled"] < labels.size
    assert staging.counters_of(dict(batches[0])) == {}


@pytest.mark.parametrize("mode", ["sketch", "local_topk"])
def test_round_records_carry_the_head_counters(tmp_path, mode):
    """Every round record of a PersonaChat run: ``head.positions``,
    ``head.labelled`` (its own batch's) and ``head.compact`` 1, in the
    fused round (the clients pool their rows) and the per-client one."""
    from commefficient_tpu.train import gpt2_train
    flags = {"sketch": ["--error_type", "virtual", "--virtual_momentum",
                        "0.9"],
             "local_topk": ["--error_type", "local", "--k", "50"]}[mode]
    results = gpt2_train.main([
        "--test", "--dataset_name", "PERSONA", "--dataset_dir",
        str(tmp_path), "--mode", mode, "--local_momentum", "0",
        "--num_workers", "2", "--local_batch_size", "2", "--num_epochs",
        "3", "--lr_scale", "0.01", "--num_devices", "1",
        "--ledger", str(tmp_path / "ledger.jsonl"), *flags])
    assert np.isfinite(results[0]["train_loss"])
    with open(tmp_path / "ledger.jsonl") as f:
        recs = [r["counters"] for r in map(json.loads, f)
                if r.get("kind") == "round"]
    assert len(recs) >= 2
    for c in recs:
        assert c["head.compact"] == 1
        assert c["head.positions"] == 2 * 2 * 2 * gpt2_train.MAX_SEQ_LEN
        assert 0 < c["head.labelled"] < c["head.positions"] // 8

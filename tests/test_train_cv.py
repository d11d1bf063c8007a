"""End-to-end CV trainer tests on synthetic data (fast, tiny model)."""

import numpy as np

from commefficient_tpu.train import cv_train


class TestCvTrainSmoke:
    def test_smoke_sketch_mode(self):
        """--test smoke: tiny model, tiny sketch, 1 round per epoch
        (the reference's de-facto integration test, SURVEY.md §4)."""
        results = cv_train.main([
            "--test", "--dataset_name", "Synthetic",
            "--mode", "sketch", "--error_type", "virtual",
            "--local_momentum", "0",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "4", "--num_epochs", "2",
            "--lr_scale", "0.1", "--pivot_epoch", "1",
        ])
        assert len(results) == 2
        assert np.isfinite(results[-1]["train_loss"])
        assert np.isfinite(results[-1]["test_acc"])
        assert results[-1]["up (MiB)"] > 0

    def test_smoke_fedavg(self):
        results = cv_train.main([
            "--test", "--dataset_name", "Synthetic",
            "--mode", "fedavg", "--local_momentum", "0",
            "--local_batch_size", "-1", "--fedavg_batch_size", "4",
            "--num_clients", "10", "--num_workers", "2",
            "--num_epochs", "1", "--lr_scale", "0.1",
            "--pivot_epoch", "0.5",
        ])
        assert len(results) == 1
        assert np.isfinite(results[-1]["train_loss"])

    def test_learns_uncompressed(self):
        """A real (non---test) run on an easy synthetic task must beat
        chance accuracy within a few epochs."""
        results = cv_train.main([
            "--dataset_name", "Synthetic",
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "8", "--num_epochs", "3",
            "--lr_scale", "1.0", "--pivot_epoch", "1",
            "--model", "ResNet9", "--test",
        ])
        # --test shrinks the model; blobs are separable, so even the
        # 1-channel net should move off chance by the last epoch
        assert results[-1]["train_loss"] < results[0]["train_loss"] + 0.5


class TestFixupLrGroups:
    def test_param_group_indices_partition(self):
        """bias/scale/other index groups partition the flat vector
        exactly (every coordinate in exactly one group)."""
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.models import get_model
        from commefficient_tpu.ops.vec import (flatten_params,
                                               param_group_indices)

        cls = get_model("FixupResNet9")
        m = cls(**cls.test_config())
        p = m.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3)))["params"]
        flat, _ = flatten_params(p)
        bias, scale, other = param_group_indices(
            p, cv_train.fixup_bias_name, cv_train.fixup_scale_name)
        all_idx = np.concatenate([bias, scale, other])
        assert len(all_idx) == flat.size
        assert len(np.unique(all_idx)) == flat.size
        assert len(bias) > 0 and len(scale) > 0 and len(other) > 0

    def test_resnet18_scalars_in_01x_groups(self):
        """FixupResNet18 names its fixup scalars add1a/add1b/add2a/
        add2b/mul — every one of them (and nothing kernel-shaped) must
        land in a 0.1x group, matching the reference's substring match
        on 'add1a.bias'/'mul.scale' torch names (fixup_resnet18.py)."""
        import jax
        import jax.numpy as jnp
        from jax.tree_util import keystr, tree_flatten_with_path

        from commefficient_tpu.models import get_model
        from commefficient_tpu.ops.vec import (flatten_params,
                                               param_group_indices)

        cls = get_model("FixupResNet18")
        m = cls()
        p = m.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3)))["params"]
        flat, _ = flatten_params(p)
        bias, scale, other = param_group_indices(
            p, cv_train.fixup_bias_name, cv_train.fixup_scale_name)
        # partition
        all_idx = np.concatenate([bias, scale, other])
        assert len(all_idx) == flat.size
        assert len(np.unique(all_idx)) == flat.size
        # every scalar leaf (the fixup params are all scalars) is in a
        # 0.1x group; every kernel is in the 1.0x group
        leaves, _ = tree_flatten_with_path(p)
        offset = 0
        tenth = set(bias.tolist()) | set(scale.tolist())
        n_scalars = 0
        for path, leaf in leaves:
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            span = set(range(offset, offset + n))
            if leaf.size == 1 and "kernel" not in keystr(path):
                n_scalars += 1
                assert span <= tenth, f"scalar {keystr(path)} not 0.1x"
            elif "kernel" in keystr(path):
                assert span.isdisjoint(tenth), \
                    f"kernel {keystr(path)} wrongly 0.1x"
            offset += n
        assert n_scalars > 0

    def test_resnet50_bottleneck_scalars_in_01x_groups(self):
        """FixupBottleneck declares bias3a/bias3b — every scalar leaf
        of a (tiny) FixupResNet50 must land in a 0.1x group and every
        kernel in the 1.0x group (the regex anchoring must not drop
        the third-conv biases)."""
        import jax
        import jax.numpy as jnp
        from jax.tree_util import keystr, tree_flatten_with_path

        from commefficient_tpu.models import get_model
        from commefficient_tpu.ops.vec import (flatten_params,
                                               param_group_indices)

        m = get_model("FixupResNet50")(num_classes=5,
                                       stage_sizes=(1, 1, 1, 1))
        p = m.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 64, 64, 3)))["params"]
        flat, _ = flatten_params(p)
        bias, scale, other = param_group_indices(
            p, cv_train.fixup_bias_name, cv_train.fixup_scale_name)
        leaves, _ = tree_flatten_with_path(p)
        tenth = set(bias.tolist()) | set(scale.tolist())
        offset = 0
        saw_bias3 = False
        for path, leaf in leaves:
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            span = set(range(offset, offset + n))
            name = keystr(path)
            if leaf.size == 1 and "kernel" not in name:
                assert span <= tenth, f"scalar {name} not 0.1x"
                saw_bias3 = saw_bias3 or "bias3" in name
            elif "kernel" in name:
                assert span.isdisjoint(tenth), f"kernel {name} 0.1x"
            offset += n
        assert saw_bias3, "fixture lost its bias3 scalars"

    def test_name_match_anchored_to_leaf_segment(self):
        """The 0.1x groups match the EXACT final path segment, not a
        bare substring — a hypothetical parameter whose path merely
        contains 'bias'/'add'/'scale' must stay in the 1.0x group
        (round-2 advisor finding)."""
        for name in ("['FixupBlock_0']['add1a']", "['bias1a']",
                     "['Dense_0']['bias']", "['bias2']",
                     "['FixupBottleneck_0']['bias3a']",
                     "['FixupBottleneck_0']['bias3b']"):
            assert cv_train.fixup_bias_name(name), name
        for name in ("['mul']", "['Block_0']['scale']",):
            assert cv_train.fixup_scale_name(name), name
        for name in ("['additive_embed']", "['addnorm']['kernel']",
                     "['bias_corrector']", "['add1a']['kernel']"):
            assert not cv_train.fixup_bias_name(name), name
        for name in ("['rescale_factor']", "['scale_mlp']['kernel']",
                     "['multiplier']", "['mul']['kernel']"):
            assert not cv_train.fixup_scale_name(name), name

    def test_lr_vector_alignment(self):
        """FedOptimizer.get_lr with index groups: each coordinate gets
        its own group's LR (reference cv_train.py:366-376 semantics,
        but aligned with the flat vector)."""
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.models import get_model
        from commefficient_tpu.ops.vec import param_group_indices
        from commefficient_tpu.runtime import FedModel, FedOptimizer

        cls = get_model("FixupResNet9")
        m = cls(**cls.test_config())
        p = m.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3)))["params"]
        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, num_workers=2,
                      local_batch_size=2, num_clients=4,
                      dataset_name="CIFAR10", seed=0)

        def loss(params, batch, cfg):
            return jnp.float32(0.0), ()

        model = FedModel(m, p, loss, args)
        bias, scale, other = param_group_indices(
            p, cv_train.fixup_bias_name, cv_train.fixup_scale_name)
        opt = FedOptimizer([{"lr": 0.1, "index": bias},
                            {"lr": 0.1, "index": scale},
                            {"lr": 1.0, "index": other}], args)
        lr = np.asarray(opt.get_lr())
        assert lr.shape == (args.grad_size,)
        assert np.all(lr[bias] == np.float32(0.1))
        assert np.all(lr[scale] == np.float32(0.1))
        assert np.all(lr[other] == np.float32(1.0))

    def test_fixup_end_to_end(self):
        """Training with the Fixup LR groups runs and stays finite
        (the vector-LR server step compiles in every mode)."""
        results = cv_train.main([
            "--test", "--dataset_name", "Synthetic",
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "4", "--num_epochs", "1",
            "--lr_scale", "0.1", "--pivot_epoch", "0.5",
            "--model", "FixupResNet9",
        ])
        assert np.isfinite(results[-1]["train_loss"])


class TestBatchNormRunningStats:
    """--batchnorm parity mode: the server blends participating
    clients' batch statistics into one running-stats state and eval
    normalizes with it — so eval metrics are invariant to the eval
    batch composition (reference models/resnet9.py BN eval via
    nn.BatchNorm2d running stats)."""

    def _setup(self):
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.config import Config
        from commefficient_tpu.models import get_model
        from commefficient_tpu.runtime import FedModel, FedOptimizer
        from commefficient_tpu.train.cv_train import (
            make_bn_stats_fn, make_compute_loss,
            make_compute_loss_eval)

        cls = get_model("ResNet9")
        module = cls(do_batchnorm=True, **cls.test_config())
        variables = module.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 32, 32, 3)), train=True)
        params, init_stats = variables["params"], \
            variables["batch_stats"]
        assert init_stats  # BN collection exists
        args = Config(mode="uncompressed", error_type="none",
                      local_momentum=0.0, virtual_momentum=0.9,
                      num_workers=2, local_batch_size=4,
                      num_clients=6, dataset_name="CIFAR10", seed=0)
        model = FedModel(
            module, params, make_compute_loss(module, init_stats),
            args, compute_loss_val=make_compute_loss_eval(module),
            stats_fn=make_bn_stats_fn(module, init_stats),
            init_model_state=init_stats)
        opt = FedOptimizer([{"lr": 0.05}], args)
        return model, opt, init_stats

    def _train_round(self, model, opt, seed=0):
        rng = np.random.RandomState(seed)
        batch = {
            "x": rng.randn(2, 4, 32, 32, 3).astype(np.float32),
            "y": rng.randint(0, 10, (2, 4)),
            "mask": np.ones((2, 4), np.float32),
            "client_ids": np.array([0, 1], np.int32),
        }
        model(batch)
        opt.step()
        return batch

    def test_stats_update_and_blend(self):
        import jax

        model, opt, init_stats = self._setup()
        before = jax.tree_util.tree_leaves(init_stats)
        self._train_round(model, opt)
        after = jax.tree_util.tree_leaves(model.model_state)
        # running stats moved off init by the 0.1 blend
        changed = [not np.allclose(np.asarray(a), np.asarray(b))
                   for a, b in zip(before, after)]
        assert any(changed)
        # vars stay positive (0.9*1 + 0.1*batch_var)
        for path_leaf in jax.tree_util.tree_leaves(model.model_state):
            assert np.all(np.isfinite(np.asarray(path_leaf)))

    def test_eval_invariant_to_batch_composition(self):
        model, opt, _ = self._setup()
        self._train_round(model, opt)
        model.train(False)

        rng = np.random.RandomState(1)
        S, B = 2, 4
        x = rng.randn(S * B, 32, 32, 3).astype(np.float32)
        y = rng.randint(0, 10, S * B)

        def run_val(order, s, b):
            xo, yo = x[order], y[order]
            batch = {
                "x": xo.reshape(s, b, 32, 32, 3),
                "y": yo.reshape(s, b),
                "mask": np.ones((s, b), np.float32),
            }
            loss_s, acc_s, counts = model(batch)
            # weighted mean over shards = sample mean (mask all-real)
            w = counts / counts.sum()
            return (np.sum(loss_s * w), np.sum(acc_s * w))

        base = run_val(np.arange(S * B), S, B)
        perm = rng.permutation(S * B)
        shuffled = run_val(perm, S, B)
        resized = run_val(np.arange(S * B), 4, 2)  # different shards
        np.testing.assert_allclose(base, shuffled, rtol=1e-5)
        np.testing.assert_allclose(base, resized, rtol=1e-5)

    def test_masked_stats_ignore_padded_rows(self):
        """Recorded batch statistics over a padded batch equal the
        statistics of the unpadded batch: padded zero rows must not
        dilute the mean or skew the variance."""
        import jax
        import jax.numpy as jnp

        from commefficient_tpu.models.norms import BatchStatNorm

        norm = BatchStatNorm(track_stats=True)
        rng = np.random.RandomState(0)
        real = rng.randn(3, 4, 4, 2).astype(np.float32) + 1.5
        padded = np.concatenate(
            [real, np.zeros((5, 4, 4, 2), np.float32)])
        mask = np.array([1, 1, 1, 0, 0, 0, 0, 0], np.float32)

        v = norm.init(jax.random.PRNGKey(0), jnp.asarray(real))
        _, upd_real = norm.apply(v, jnp.asarray(real),
                                 jnp.ones(3, jnp.float32),
                                 mutable=["batch_stats"])
        _, upd_pad = norm.apply(v, jnp.asarray(padded),
                                jnp.asarray(mask),
                                mutable=["batch_stats"])
        for k in ("mean", "var"):
            np.testing.assert_allclose(
                np.asarray(upd_pad["batch_stats"][k]),
                np.asarray(upd_real["batch_stats"][k]), rtol=1e-5)

    def test_recorded_var_unbiased_torch_parity(self):
        """The RECORDED batch variance carries the Bessel n/(n-1)
        correction: torch nn.BatchNorm2d normalizes with the biased
        estimate but updates running_var with the unbiased one, and the
        server blend claims parity with torch BN eval (round-2 advisor
        finding). momentum=1.0 makes torch's running_var equal the
        batch's unbiased var directly."""
        import jax
        import jax.numpy as jnp
        import torch

        from commefficient_tpu.models.norms import BatchStatNorm

        rng = np.random.RandomState(3)
        x = rng.randn(4, 6, 6, 5).astype(np.float32) * 2.0 + 0.7

        norm = BatchStatNorm(track_stats=True)
        v = norm.init(jax.random.PRNGKey(0), jnp.asarray(x))
        _, upd = norm.apply(v, jnp.asarray(x),
                            mutable=["batch_stats"])

        tbn = torch.nn.BatchNorm2d(5, momentum=1.0)
        tbn.train()
        with torch.no_grad():
            tbn(torch.from_numpy(x.transpose(0, 3, 1, 2)))
        np.testing.assert_allclose(
            np.asarray(upd["batch_stats"]["var"]),
            tbn.running_var.numpy(), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(upd["batch_stats"]["mean"]),
            tbn.running_mean.numpy(), rtol=1e-4, atol=1e-5)
        # masked path agrees with the unmasked one on an all-real batch
        _, upd_m = norm.apply(v, jnp.asarray(x),
                              jnp.ones(4, jnp.float32),
                              mutable=["batch_stats"])
        np.testing.assert_allclose(
            np.asarray(upd_m["batch_stats"]["var"]),
            tbn.running_var.numpy(), rtol=1e-4)

    def test_resume_from_pre_batchnorm_checkpoint(self, tmp_path):
        """A checkpoint written without BN running stats (pre-
        batchnorm format) still restores weights/optimizer state; the
        stats fall back to fresh init with a warning instead of a
        hard failure (round-2 advisor finding)."""
        import json
        import warnings

        import jax

        from commefficient_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        model, opt, init_stats = self._setup()
        self._train_round(model, opt)
        path = str(tmp_path / "c.npz")
        save_checkpoint(path, model, opt)
        # strip the bnstats entries, simulating the older format
        with np.load(path, allow_pickle=False) as z:
            kept = {k: z[k] for k in z.files
                    if not k.startswith("bnstats:")}
        stripped = str(tmp_path / "old.npz")
        np.savez(stripped, **kept)

        model2, opt2, _ = self._setup()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            load_checkpoint(stripped, model2, opt2)
        assert any("running stats" in str(x.message) for x in w)
        np.testing.assert_array_equal(
            np.asarray(model2.ps_weights),
            np.asarray(model.ps_weights))
        for a, b in zip(jax.tree_util.tree_leaves(model2.model_state),
                        jax.tree_util.tree_leaves(init_stats)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))

    def test_checkpoint_roundtrip_carries_stats(self, tmp_path):
        import jax

        from commefficient_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        model, opt, _ = self._setup()
        self._train_round(model, opt)
        want = [np.asarray(leaf) for leaf in
                jax.tree_util.tree_leaves(model.model_state)]
        path = str(tmp_path / "c.npz")
        save_checkpoint(path, model, opt)

        model2, opt2, _ = self._setup()
        load_checkpoint(path, model2, opt2)
        got = [np.asarray(leaf) for leaf in
               jax.tree_util.tree_leaves(model2.model_state)]
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)


class TestFinetune:
    def test_merge_replaces_only_mismatched_head(self):
        import jax
        import jax.numpy as jnp
        from commefficient_tpu.models import get_model
        from commefficient_tpu.train.cv_train import merge_finetune_params

        mk = lambda n: get_model("ResNet9")(
            num_classes=n,
            channels={"prep": 2, "layer1": 2, "layer2": 2, "layer3": 2})
        p10 = mk(10).init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 32, 32, 3)))["params"]
        p4 = mk(4).init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 32, 32, 3)))["params"]
        merged, replaced = merge_finetune_params(p4, p10)
        assert replaced == ["Dense_0/kernel"]
        # body copied from source, head kept fresh
        import numpy as np
        np.testing.assert_array_equal(
            np.asarray(merged["ConvBN_0"]["Conv_0"]["kernel"]),
            np.asarray(p10["ConvBN_0"]["Conv_0"]["kernel"]))
        np.testing.assert_array_equal(
            np.asarray(merged["Dense_0"]["kernel"]),
            np.asarray(p4["Dense_0"]["kernel"]))

    def test_finetune_end_to_end(self, tmp_path):
        """Train + checkpoint, then a --finetune run loads the body."""
        from commefficient_tpu.train import cv_train

        base = [
            "--test", "--dataset_name", "Synthetic",
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--virtual_momentum", "0",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "4", "--num_epochs", "1",
            "--lr_scale", "0.1", "--pivot_epoch", "1",
        ]
        cv_train.main(base + ["--checkpoint",
                              "--checkpoint_path", str(tmp_path)])
        out = cv_train.main(base + ["--finetune",
                                    "--finetune_path", str(tmp_path)])
        assert len(out) == 1


class TestMixup:
    def test_apply_mixup_mixes_within_client_only(self):
        import numpy as np
        from commefficient_tpu.train.cv_train import apply_mixup

        rng = np.random.RandomState(0)
        W, B = 2, 4
        x = np.arange(W * B, dtype=np.float32).reshape(W, B, 1, 1, 1)
        y = np.arange(W * B, dtype=np.int32).reshape(W, B)
        mask = np.ones((W, B), np.float32)
        mask[1, 2:] = 0.0  # client 1 has 2 real rows
        out = apply_mixup({"x": x, "y": y, "mask": mask}, 1.0, rng)
        lam = out["lam"][0, 0]
        assert 0.0 <= lam <= 1.0
        # mixed values stay within each client's own row range
        for w in range(W):
            real = np.nonzero(mask[w] > 0)[0]
            lo, hi = x[w, real].min(), x[w, real].max()
            assert (out["x"][w, real] >= lo - 1e-6).all()
            assert (out["x"][w, real] <= hi + 1e-6).all()
            # y_b is a permutation of the client's own labels
            assert set(out["y_b"][w, real]) <= set(y[w, real])
        # padded rows untouched
        np.testing.assert_array_equal(out["x"][1, 2:], x[1, 2:])

    def test_mixup_end_to_end_smoke(self):
        from commefficient_tpu.train import cv_train

        results = cv_train.main([
            "--test", "--dataset_name", "Synthetic",
            "--mode", "uncompressed", "--error_type", "none",
            "--local_momentum", "0", "--num_clients", "10",
            "--num_workers", "2", "--local_batch_size", "4",
            "--num_epochs", "1", "--lr_scale", "0.1",
            "--pivot_epoch", "1", "--mixup", "--mixup_alpha", "0.5",
        ])
        assert np.isfinite(results[-1]["train_loss"])


class TestModelConfigs:
    def test_fixup50_overlay_respects_explicit_flags(self):
        from commefficient_tpu.config import parse_args
        from commefficient_tpu.models.configs import get_model_config

        defaults = parse_args(0.4, []).__dict__
        mc = get_model_config("FixupResNet50")
        # user left lr_scale at default, set weight_decay explicitly
        args = parse_args(0.4, ["--model", "FixupResNet50",
                                "--weight_decay", "0.123"])
        applied = mc.set_args(args, defaults)
        assert args.lr_scale == 0.1 and "lr_scale" in applied
        assert args.weight_decay == 0.123  # explicit flag wins
        assert "weight_decay" not in applied
        # shape: peak 1.0, 10x decays at 30/60/90; effective LR is
        # args.lr_scale * shape(epoch)
        assert abs(mc.lr_schedule_shape(0) - 1.0) < 1e-9
        assert abs(mc.lr_schedule_shape(45) - 0.1) < 1e-9
        assert abs(mc.lr_schedule_shape(95) - 0.001) < 1e-9

    def test_unknown_model_has_no_config(self):
        from commefficient_tpu.models.configs import get_model_config
        assert get_model_config("ResNet9") is None


class TestDeterminism:
    def test_same_seed_identical_training(self):
        """Two identical runs (same seed) must produce bit-identical
        epoch metrics end to end (engine, data order, init)."""
        base = [
            "--test", "--dataset_name", "Synthetic",
            "--mode", "sketch", "--error_type", "virtual",
            "--local_momentum", "0", "--virtual_momentum", "0.9",
            "--num_clients", "10", "--num_workers", "2",
            "--local_batch_size", "4", "--num_epochs", "2",
            "--lr_scale", "0.1", "--pivot_epoch", "1", "--seed", "33",
        ]
        a = cv_train.main(base)
        b = cv_train.main(base)
        assert len(a) == len(b) == 2
        for ra, rb in zip(a, b):
            assert ra["train_loss"] == rb["train_loss"]
            assert ra["test_acc"] == rb["test_acc"]

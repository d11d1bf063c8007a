"""Differentially-private sketching (``--dp sketch``) and the ε/δ
accountant (privacy/): the in-round mechanism against the NumPy
mirror, the RDP composition against an independently-restated
reference (exact integer binomials, to 1e-6 over 100+ rounds), and
the runtime lifecycle — per-dispatch charging, schema-v5 ledger
stamping, budget abort at the predicted round, checkpoint
continuity — against closed-form predictions."""

import dataclasses
import json
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.core.robust import _TINY, clip_factors, robust_fold
from commefficient_tpu.core.rounds import (ClientStates, args2sketch,
                                           build_client_round)
from commefficient_tpu.privacy import (PrivacyAccountant,
                                       add_table_noise, build_accountant,
                                       dp_clip, np_dp_clip, np_dp_noise,
                                       round_noise_key, sample_rate_of,
                                       steps_to_budget, table_noise_std)
from commefficient_tpu.privacy.accountant import DEFAULT_ORDERS
from commefficient_tpu.privacy.mechanism import table_sensitivity

from reference_mirror import MirrorFed, np_clip_factors
from test_modes import linear_loss, make_cfg, run_engine


# ------------------------------------------------------------------ #
# independent accountant mirror: exact integer binomials (math.comb) #
# instead of the accountant's lgamma route, log1p(-1/α) instead of   #
# log((α-1)/α) — same math, different code, so a transcription bug   #
# in either cannot self-verify.                                      #
# ------------------------------------------------------------------ #

def mirror_rdp(q, sigma, alpha):
    if sigma <= 0:
        return math.inf
    if q <= 0:
        return 0.0
    if q >= 1:
        return alpha / (2.0 * sigma * sigma)
    logs = [math.log(math.comb(alpha, k))
            + (alpha - k) * math.log(1.0 - q)
            + (k * math.log(q) if k else 0.0)
            + k * (k - 1) / (2.0 * sigma * sigma)
            for k in range(alpha + 1)]
    m = max(logs)
    return (m + math.log(sum(math.exp(t - m) for t in logs))) \
        / (alpha - 1)


def mirror_epsilon(q, sigma, delta, weights):
    """ε after charging one round per entry of ``weights`` (the fold
    weight scale w: effective noise multiplier σ/w)."""
    best = math.inf
    for a in DEFAULT_ORDERS:
        tot = sum(mirror_rdp(q, sigma / w, a) for w in weights)
        if not math.isfinite(tot):
            continue
        eps = (tot + math.log1p(-1.0 / a)
               - (math.log(delta) + math.log(a)) / (a - 1))
        best = min(best, max(eps, 0.0))
    return best


def dp_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual", k=4,
                num_rows=5, num_cols=64, dp="sketch",
                dp_clip=0.5, dp_noise_mult=0.3)
    base.update(kw)
    return make_cfg(**base)


def rounds_data(seed=0, n_rounds=3, d=8, num_clients=4, W=2, B=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n_rounds):
        ids = rng.choice(num_clients, W, replace=False)
        out.append([(int(cid), rng.randn(B, d).astype(np.float32),
                     rng.randn(B).astype(np.float32)) for cid in ids])
    return out


def run_mirror_dp(cfg, w0, rounds, lr, num_clients=4):
    """MirrorFed with the engine's per-round keys threaded in, so the
    mirror's noise draw is the SAME bits as the engine's."""
    cfg = dataclasses.replace(cfg, grad_size=len(w0))
    m = MirrorFed(cfg, w0, num_clients, sketch=args2sketch(cfg))
    rng = jax.random.PRNGKey(cfg.seed)
    return [m.round(r, lr, rng=jax.random.fold_in(rng, i))
            for i, r in enumerate(rounds)]


W0 = [0.0, 0.5, -0.3, 0.1, 0.0, 0.2, -0.1, 0.05]


class TestClipAlgebra:
    """One clip helper for the robust fold AND the DP clip — pinned
    bit-identical to the pre-refactor inline formula."""

    def test_clip_factors_pins_prerefactor_formula(self):
        norms = jnp.asarray([0.0, 1e-13, 0.3, 1.0, 7.5], jnp.float32)
        for tau in (0.1, 1.0, 4.0):
            want = jnp.minimum(1.0, jnp.float32(tau)
                               / jnp.maximum(norms, 1e-12))
            np.testing.assert_array_equal(
                np.asarray(clip_factors(norms, jnp.float32(tau))),
                np.asarray(want))

    def test_robust_clip_fold_bit_identical(self):
        """The full robust clip fold vs the pre-refactor algebra
        restated inline (same jnp ops in the same order) — the
        clip_factors extraction must be invisible at the bit level."""
        cfg = make_cfg(robust_agg="clip", robust_clip_norm=0.5)
        rng = np.random.RandomState(3)
        W, B, d = 4, 2, 6
        transmit = jnp.asarray(rng.randn(W, d).astype(np.float32))
        batch = {"mask": jnp.ones((W, B), jnp.float32)}
        got, _ = jax.jit(lambda t, b: robust_fold(cfg, t, b))(
            transmit, batch)

        def inline(t, b):
            flatT = t.reshape(W, -1).astype(jnp.float32)
            n = jnp.sum(b["mask"], axis=1).astype(jnp.float32)
            total = jnp.maximum(jnp.sum(n), 1.0)
            g = flatT / jnp.maximum(n, 1.0)[:, None]
            norms = jnp.sqrt(jnp.sum(g * g, axis=1))
            tau = jnp.float32(0.5)
            scale = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-12))
            return jnp.sum(scale[:, None] * flatT, axis=0) / total

        want = jax.jit(inline)(transmit, batch)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want))

    def test_np_mirror_matches_jax(self):
        norms = np.array([0.0, 0.2, 1.0, 9.0], np.float32)
        np.testing.assert_allclose(
            np_clip_factors(norms, 0.7),
            np.asarray(clip_factors(jnp.asarray(norms),
                                    jnp.float32(0.7))),
            rtol=1e-7)

    def test_dp_clip_exact_inside_cap_and_matches_mirror(self):
        g = np.linspace(-1, 1, 16).astype(np.float32)
        inside = np.asarray(dp_clip(jnp.asarray(g), 100.0))
        np.testing.assert_array_equal(inside, g)  # no-op inside cap
        clipped = np.asarray(dp_clip(jnp.asarray(g), 0.5))
        assert abs(np.linalg.norm(clipped) - 0.5) < 1e-6
        np.testing.assert_allclose(clipped, np_dp_clip(g, 0.5),
                                   rtol=1e-6, atol=1e-7)


class TestMechanism:
    def test_noise_replay_bit_exact(self):
        key = round_noise_key(jax.random.PRNGKey(7))
        a = np.asarray(add_table_noise(jnp.zeros((3, 8)), key, 0.25))
        b = np.asarray(add_table_noise(jnp.zeros((3, 8)), key, 0.25))
        np.testing.assert_array_equal(a, b)
        other = round_noise_key(jax.random.PRNGKey(8))
        assert not np.array_equal(
            a, np.asarray(add_table_noise(jnp.zeros((3, 8)),
                                          other, 0.25)))

    def test_noise_key_disjoint_from_client_streams(self):
        rng = jax.random.PRNGKey(11)
        nk = np.asarray(round_noise_key(rng))
        for cid in range(64):
            assert not np.array_equal(
                nk, np.asarray(jax.random.fold_in(rng, cid)))

    def test_table_noise_std_closed_form(self):
        cfg = dp_cfg(dp_clip=0.25, dp_noise_mult=0.8, num_rows=5,
                     num_workers=2)
        assert table_sensitivity(5, 0.25, 2) \
            == math.sqrt(5) * 0.25 / 2
        assert table_noise_std(cfg) == 0.8 * math.sqrt(5) * 0.25 / 2

    def test_np_dp_noise_matches_jitted_draw(self):
        # same key -> same threefry bits; the uniform->normal tail can
        # fuse differently inside the round jit, so ulp-level only
        key = round_noise_key(jax.random.PRNGKey(3))
        jitted = jax.jit(lambda t: add_table_noise(t, key, 0.7))
        got = np.asarray(jitted(jnp.zeros((5, 64), jnp.float32)))
        np.testing.assert_allclose(got, np_dp_noise(key, (5, 64), 0.7),
                                   rtol=1e-6, atol=1e-7)


class TestAccountant:
    def test_subsampled_matches_mirror_120_rounds(self):
        q, sigma, delta = 0.037, 1.1, 1e-5
        acc = PrivacyAccountant(sigma, q, delta)
        for _ in range(120):
            acc.step()
        want = mirror_epsilon(q, sigma, delta, [1.0] * 120)
        assert abs(acc.epsilon() - want) <= 1e-6 * max(1.0, want)

    def test_full_participation_matches_closed_form(self):
        # q=1: per-round RDP is exactly α/(2σ²)
        sigma, delta, n = 2.0, 1e-6, 150
        acc = PrivacyAccountant(sigma, 1.0, delta)
        for _ in range(n):
            acc.step()
        want = mirror_epsilon(1.0, sigma, delta, [1.0] * n)
        assert abs(acc.epsilon() - want) <= 1e-6 * max(1.0, want)

    def test_staleness_weighted_matches_mirror(self):
        q, sigma, delta = 0.25, 0.9, 1e-5
        weights = [1.0, 0.5, 0.25] * 34  # 102 rounds
        acc = PrivacyAccountant(sigma, q, delta)
        for w in weights:
            acc.step(weight_scale=w)
        want = mirror_epsilon(q, sigma, delta, weights)
        assert abs(acc.epsilon() - want) <= 1e-6 * max(1.0, want)

    def test_weight_scale_is_sigma_rescale(self):
        a = PrivacyAccountant(1.0, 0.3, 1e-5)
        b = PrivacyAccountant(2.0, 0.3, 1e-5)
        for _ in range(20):
            a.step(weight_scale=0.5)
            b.step()
        assert a.epsilon() == b.epsilon()

    def test_sigma_override_matches_rebuilt(self):
        a = PrivacyAccountant(1.0, 0.3, 1e-5)
        b = PrivacyAccountant(1.7, 0.3, 1e-5)
        for _ in range(10):
            a.step(sigma=1.7)
            b.step()
        assert a.epsilon() == b.epsilon()

    def test_quantized_wire_is_free_postprocessing(self):
        # the accountant charges the noisy f32 release; the int8 qdq
        # after it must not change the account
        f32 = build_accountant(dp_cfg(dp_noise_mult=1.0))
        int8 = build_accountant(dp_cfg(dp_noise_mult=1.0,
                                       sketch_dtype="int8"))
        for _ in range(5):
            f32.step()
            int8.step()
        assert f32.epsilon() == int8.epsilon()
        assert build_accountant(make_cfg()) is None  # --dp off

    def test_state_roundtrip_bit_exact_through_json(self):
        acc = PrivacyAccountant(1.3, 0.41, 3e-6)
        for w in (1.0, 0.7, 0.7, 1.0, 0.33):
            acc.step(weight_scale=w)
        back = PrivacyAccountant.load_state(
            json.loads(json.dumps(acc.state_dict())))
        assert back.state_dict() == acc.state_dict()
        assert back.epsilon() == acc.epsilon()
        for _ in range(5):  # continuity: both keep composing equally
            acc.step()
            back.step()
        assert back.epsilon() == acc.epsilon()

    def test_epsilon_zero_before_first_step_and_monotone(self):
        acc = PrivacyAccountant(1.0, 0.5, 1e-5)
        assert acc.epsilon() == 0.0
        prev = 0.0
        for _ in range(30):
            acc.step()
            assert acc.epsilon() >= prev
            prev = acc.epsilon()

    def test_sigma_zero_spends_infinite_epsilon(self):
        acc = PrivacyAccountant(0.0, 0.5, 1e-5)
        acc.step()
        assert math.isinf(acc.epsilon())

    def test_steps_to_budget_brackets_the_curve(self):
        sigma, q, delta, budget = 1.0, 0.5, 1e-5, 10.0
        n = steps_to_budget(sigma, q, delta, budget)
        acc = PrivacyAccountant(sigma, q, delta)
        assert acc.epsilon_after(n) <= budget < acc.epsilon_after(n + 1)
        assert acc.rounds_left(budget) == n


class TestDPRound:
    """The compiled DP round against MirrorFed with the same keys."""

    def test_noised_round_matches_mirror(self):
        cfg = dp_cfg()
        rounds = rounds_data(seed=20)
        got = run_engine(cfg, W0, rounds, lr=0.01)
        want = run_mirror_dp(cfg, W0, rounds, lr=0.01)
        for r, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4,
                                       err_msg=f"round {r}")

    def test_noise_before_int8_qdq_matches_mirror(self):
        """int8 wire under DP: ONE qdq on the NOISY aggregated table.
        A wrong order (noise after qdq, or per-client qdq left on)
        diverges from the mirror immediately."""
        cfg = dp_cfg(sketch_dtype="int8", dp_noise_mult=0.5)
        rounds = rounds_data(seed=21)
        got = run_engine(cfg, W0, rounds, lr=0.01)
        want = run_mirror_dp(cfg, W0, rounds, lr=0.01)
        for r, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_allclose(g, w, rtol=5e-3, atol=5e-4,
                                       err_msg=f"round {r}")

    def test_tight_clip_matches_mirror(self):
        cfg = dp_cfg(dp_clip=0.05, dp_noise_mult=0.0)
        rounds = rounds_data(seed=22)
        got = run_engine(cfg, W0, rounds, lr=0.01)
        want = run_mirror_dp(cfg, W0, rounds, lr=0.01)
        for r, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4,
                                       err_msg=f"round {r}")

    def test_seeded_replay_bit_exact(self):
        cfg = dp_cfg(dp_noise_mult=1.0)
        rounds = rounds_data(seed=23)
        a = run_engine(cfg, W0, rounds, lr=0.01)
        b = run_engine(cfg, W0, rounds, lr=0.01)
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra, rb)

    def test_dp_off_program_identical(self):
        """--dp off must trace NOTHING: the lowered round is
        byte-identical whatever the (inert) dp_* knobs say, and a
        --dp sketch build differs."""
        d, B = 8, 3
        base = dataclasses.replace(
            make_cfg(mode="sketch", error_type="virtual", k=4,
                     num_rows=5, num_cols=64), grad_size=d)
        inert = dataclasses.replace(base, dp_clip=7.0,
                                    dp_noise_mult=3.0, dp_delta=1e-7)
        dp = dataclasses.replace(base, dp="sketch")

        def text(cfg):
            fn = build_client_round(cfg, linear_loss, B)
            args = (jnp.zeros(d),
                    ClientStates.init(cfg, 4, jnp.zeros(d)),
                    {"x": jnp.zeros((2, B, d)),
                     "y": jnp.zeros((2, B)),
                     "mask": jnp.ones((2, B))},
                    jnp.zeros(2, jnp.int32), jax.random.PRNGKey(0),
                    jnp.float32(0.01))
            return jax.jit(fn).lower(*args).as_text()

        assert text(base) == text(inert)
        assert text(base) != text(dp)


class TestCapacityDenominator:
    """--dp sketch normalises every fold by the STATIC padded
    capacity W·B: the transmit is the clipped gradient × the real
    datapoint count n_i, so only a data-independent denominator
    keeps one client's share of the released mean within the charged
    sqrt(r)·C/W sensitivity — on padded / mostly-dead rounds AND
    under staleness weights (which would cancel out of a
    weighted-total denominator)."""

    def test_mostly_dead_round_uses_capacity_denominator(self):
        d, B, W = 8, 3, 2
        base = dataclasses.replace(
            make_cfg(mode="sketch", error_type="virtual", k=4,
                     num_rows=5, num_cols=64), grad_size=d)
        # huge clip (exact no-op) + zero noise isolates the fold
        # algebra: the DP round differs from dp-off ONLY by the
        # capacity denominator
        dp = dataclasses.replace(base, dp="sketch", dp_clip=1e6,
                                 dp_noise_mult=0.0)
        rng = np.random.RandomState(5)
        batch = {"x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
                 "y": jnp.asarray(rng.randn(W, B), jnp.float32),
                 "mask": jnp.asarray([[1, 0, 0], [0, 0, 0]],
                                     jnp.float32)}

        def agg(cfg):
            fn = jax.jit(build_client_round(cfg, linear_loss, B))
            res = fn(jnp.zeros(d),
                     ClientStates.init(cfg, W, jnp.zeros(d)), batch,
                     jnp.arange(W, dtype=jnp.int32),
                     jax.random.PRNGKey(0), jnp.float32(0.01))
            return np.asarray(res.aggregated)

        off, got = agg(base), agg(dp)
        assert np.linalg.norm(off) > 0
        # one alive datapoint: dp-off divides by 1, DP divides by
        # the static W·B capacity
        np.testing.assert_allclose(got, off / (W * B), rtol=1e-6,
                                   atol=1e-8)

    def test_full_round_capacity_denominator_is_inert(self):
        """With every slot full the alive total IS W·B, so the DP
        round at huge clip / zero noise equals the dp-off round
        exactly."""
        d, B, W = 8, 3, 2
        base = dataclasses.replace(
            make_cfg(mode="sketch", error_type="virtual", k=4,
                     num_rows=5, num_cols=64), grad_size=d)
        dp = dataclasses.replace(base, dp="sketch", dp_clip=1e6,
                                 dp_noise_mult=0.0)
        rng = np.random.RandomState(6)
        batch = {"x": jnp.asarray(rng.randn(W, B, d), jnp.float32),
                 "y": jnp.asarray(rng.randn(W, B), jnp.float32),
                 "mask": jnp.ones((W, B), jnp.float32)}

        def agg(cfg):
            fn = jax.jit(build_client_round(cfg, linear_loss, B))
            res = fn(jnp.zeros(d),
                     ClientStates.init(cfg, W, jnp.zeros(d)), batch,
                     jnp.arange(W, dtype=jnp.int32),
                     jax.random.PRNGKey(0), jnp.float32(0.01))
            return np.asarray(res.aggregated)

        np.testing.assert_allclose(agg(dp), agg(base), rtol=1e-6,
                                   atol=1e-8)

    def test_robust_clip_fold_capacity_and_mirror_matches(self):
        from reference_mirror import np_robust_fold

        W, B, d = 4, 2, 6
        base = make_cfg(robust_agg="clip", robust_clip_norm=0.5)
        dp = dp_cfg(robust_agg="clip", robust_clip_norm=0.5)
        rng = np.random.RandomState(7)
        transmit = jnp.asarray(rng.randn(W, d).astype(np.float32))
        mask = np.zeros((W, B), np.float32)
        mask[0, 0] = 1.0  # one alive datapoint in a W=4 cohort
        batch = {"mask": jnp.asarray(mask)}
        got_base, _ = robust_fold(base, transmit, batch)
        got_dp, _ = robust_fold(dp, transmit, batch)
        np.testing.assert_allclose(np.asarray(got_dp),
                                   np.asarray(got_base) / (W * B),
                                   rtol=1e-6, atol=1e-8)
        want, _ = np_robust_fold(dp, [np.asarray(t) for t in
                                      transmit],
                                 mask.sum(axis=1), capacity=B)
        np.testing.assert_allclose(np.asarray(got_dp), want,
                                   rtol=1e-6, atol=1e-7)

    def test_dp_robust_composition_guards(self):
        """The accountant's bound only covers folds where a client's
        influence is its own clipped share: median/trimmed releases
        and cohort-derived clip caps are refused at config time."""
        with pytest.raises(AssertionError):
            dp_cfg(robust_agg="median").validate_runtime()
        with pytest.raises(AssertionError):
            dp_cfg(robust_agg="trimmed",
                   robust_trim_frac=0.2).validate_runtime()
        with pytest.raises(AssertionError):
            # auto median-of-norms cap
            dp_cfg(robust_agg="clip").validate_runtime()
        ok = dp_cfg(robust_agg="clip", robust_clip_norm=1.0)
        assert ok.validate_runtime().robust_agg == "clip"


def _lin_model(args):
    import flax.linen as nn

    from commefficient_tpu.runtime import FedModel, FedOptimizer

    class Lin(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4, use_bias=False)(x)

    module = Lin()
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 3)))["params"]

    def loss(p, batch, cfg):
        pred = module.apply({"params": p}, batch["x"])
        per = jnp.sum((pred - batch["y"][..., None]) ** 2, -1)
        n = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
        return jnp.sum(per * batch["mask"]) / n, ()

    model = FedModel(module, params, loss, args, padded_batch_size=4)
    opt = FedOptimizer([{"lr": 0.05}], args)
    return model, opt


def _dp_args(**kw):
    base = dict(mode="sketch", error_type="virtual",
                local_momentum=0.0, virtual_momentum=0.9, k=2,
                num_rows=3, num_cols=32, num_blocks=1, num_workers=2,
                local_batch_size=4, num_clients=4,
                dataset_name="CIFAR10", seed=0, dp="sketch",
                dp_clip=1.0, dp_noise_mult=1.0, dp_delta=1e-5)
    base.update(kw)
    return Config(**base)


def _round_batch(rng):
    return {"x": rng.randn(2, 4, 3).astype(np.float32),
            "y": rng.randn(2, 4).astype(np.float32),
            "mask": np.ones((2, 4), np.float32),
            "client_ids": np.array([0, 1], np.int32)}


class TestRuntimeCharge:
    """The accountant's runtime lifecycle through FedModel."""

    def test_charged_once_per_dispatched_round(self):
        args = _dp_args()
        model, opt = _lin_model(args)
        rng = np.random.RandomState(0)
        for _ in range(3):
            model(_round_batch(rng))
            opt.step()
        assert model._accountant.steps == 3
        ref = PrivacyAccountant(1.0, sample_rate_of(args), 1e-5)
        for _ in range(3):
            ref.step()
        assert model._accountant.epsilon() == ref.epsilon()

    def test_budget_abort_at_predicted_round(self):
        from commefficient_tpu.telemetry.alarms import DivergenceAbort

        q = sample_rate_of(_dp_args())
        probe = PrivacyAccountant(1.0, q, 1e-5)
        eps = []
        for _ in range(3):
            probe.step()
            eps.append(probe.epsilon())
        budget = (eps[1] + eps[2]) / 2.0  # 2 rounds fit, 3 don't
        assert steps_to_budget(1.0, q, 1e-5, budget) == 2

        args = _dp_args(dp_epsilon=budget, on_divergence="abort")
        model, opt = _lin_model(args)
        rng = np.random.RandomState(0)
        for _ in range(2):
            model(_round_batch(rng))
            opt.step()
        with pytest.raises(DivergenceAbort):
            model(_round_batch(rng))
            opt.step()

    def test_ledger_round_records_carry_v5_keys(self):
        from commefficient_tpu.telemetry.record import (
            LEDGER_SCHEMA_VERSION, make_round_record, validate_record)

        rec = make_round_record(0)
        assert rec["schema"] == 9 == LEDGER_SCHEMA_VERSION
        assert rec["dp_epsilon"] is None \
            and rec["dp_delta"] is None and rec["dp_sigma"] is None
        assert validate_record(rec) == []
        del rec["dp_epsilon"]
        assert any("dp_epsilon" in p for p in validate_record(rec))

    def test_set_round_privacy_stamps_open_record(self):
        from commefficient_tpu.telemetry.core import Telemetry

        out = []

        class _Sink:
            def write(self, rec):
                out.append(rec)

            def flush(self):
                pass

            def close(self):
                pass

        tel = Telemetry(sinks=[_Sink()])
        tel.begin_round(0)
        tel.set_round_privacy(0, 1.25, 1e-5, 0.8)
        tel.set_round_bytes(0, 10, 20)
        tel.close()
        rounds = [r for r in out if r.get("kind") == "round"]
        assert rounds and rounds[0]["dp_epsilon"] == 1.25
        assert rounds[0]["dp_delta"] == 1e-5
        assert rounds[0]["dp_sigma"] == 0.8

    def test_async_round_charges_largest_alive_weight(self):
        """A staleness-weighted round charges weight_scale =
        (1 + s_min)^{-alpha} over the ALIVE slots only: DP folds
        normalise by the static W·B capacity (core/rounds.py), so a
        client's released contribution is cw_i·t_i/(W·B) — genuinely
        scaled by its fold weight — and the round's worst case is the
        largest alive weight. Dead slots (including one with the
        globally smallest staleness) must not set the charge, and the
        ledger σ is the effective σ/w."""
        from commefficient_tpu.runtime.fed_model import FedModel

        sigmas = []

        class _Tel:
            def set_round_privacy(self, ridx, eps, delta, sigma):
                sigmas.append(sigma)

        fake = SimpleNamespace(
            _accountant=PrivacyAccountant(1.0, 1.0, 1e-5),
            telemetry=_Tel(), alarm_engine=None)
        cfg = SimpleNamespace(dp_noise_mult=1.0,
                              async_staleness_weight=0.5,
                              dp_epsilon=0.0)
        staleness = np.array([3.0, 1.0, 7.0])
        mask = np.array([[1, 1], [0, 0], [1, 0]], np.float32)
        FedModel._charge_privacy(fake, 0, cfg, staleness, mask)
        w = (1.0 + 3.0) ** -0.5  # slot 1 (s=1) is dead: alive min is 3
        ref = PrivacyAccountant(1.0, 1.0, 1e-5)
        ref.step(weight_scale=w)
        assert fake._accountant.epsilon() == ref.epsilon()
        assert sigmas == [1.0 / w]

    def test_sync_and_dead_rounds_charge_full_sensitivity(self):
        """No discount without the async driver (staleness is None)
        and none on a fully-dead fold (pure-noise release; charging 1
        is conservative)."""
        from commefficient_tpu.runtime.fed_model import FedModel

        class _Tel:
            def set_round_privacy(self, *a):
                pass

        fake = SimpleNamespace(
            _accountant=PrivacyAccountant(1.0, 1.0, 1e-5),
            telemetry=_Tel(), alarm_engine=None)
        cfg = SimpleNamespace(dp_noise_mult=1.0,
                              async_staleness_weight=0.5,
                              dp_epsilon=0.0)
        FedModel._charge_privacy(fake, 0, cfg)
        FedModel._charge_privacy(fake, 1, cfg, np.array([2.0, 5.0]),
                                 np.zeros((2, 3), np.float32))
        ref = PrivacyAccountant(1.0, 1.0, 1e-5)
        ref.step()
        ref.step()
        assert fake._accountant.epsilon() == ref.epsilon()

    def test_no_subsampling_amplification_credit(self):
        """FedSampler draws cohorts without replacement until clients
        exhaust their epoch data — not Poisson — so sample_rate_of
        claims q = 1 even for a small cohort of a big federation."""
        assert sample_rate_of(_dp_args(num_clients=1000)) == 1.0
        assert sample_rate_of(_dp_args()) == 1.0
        # the accountant built for such a config prices the plain
        # (unamplified) Gaussian round
        acc = build_accountant(_dp_args(num_clients=1000))
        acc.step()
        ref = PrivacyAccountant(1.0, 1.0, 1e-5)
        ref.step()
        assert acc.epsilon() == ref.epsilon()


class TestCheckpointContinuity:
    def test_accountant_survives_save_load_bit_exact(self, tmp_path):
        from commefficient_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        args = _dp_args()
        model, opt = _lin_model(args)
        rng = np.random.RandomState(1)
        batches = [_round_batch(rng) for _ in range(4)]
        for b in batches[:2]:
            model(b)
            opt.step()
        path = str(tmp_path / "ck.npz")
        save_checkpoint(path, model, opt)
        spent = model._accountant.state_dict()

        model2, opt2 = _lin_model(args)
        load_checkpoint(path, model2, opt2)
        assert model2._accountant.state_dict() == spent

        # continuity: original and resumed runs keep composing equally
        for b in batches[2:]:
            model(b)
            opt.step()
            model2(b)
            opt2.step()
        assert model2._accountant.epsilon() == model._accountant.epsilon()
        assert model2._accountant.steps == 4

    def test_dp_run_refuses_dpless_checkpoint(self, tmp_path):
        from commefficient_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        off = _dp_args(dp="off", dp_noise_mult=0.0)
        model_off, opt_off = _lin_model(off)
        rng = np.random.RandomState(2)
        model_off(_round_batch(rng))
        opt_off.step()
        path = str(tmp_path / "off.npz")
        save_checkpoint(path, model_off, opt_off)

        model_dp, opt_dp = _lin_model(_dp_args())
        with pytest.raises(ValueError, match="privacy accountant"):
            load_checkpoint(path, model_dp, opt_dp)

    def test_dpless_run_warns_on_dp_checkpoint(self, tmp_path):
        from commefficient_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        model_dp, opt_dp = _lin_model(_dp_args())
        rng = np.random.RandomState(3)
        model_dp(_round_batch(rng))
        opt_dp.step()
        path = str(tmp_path / "dp.npz")
        save_checkpoint(path, model_dp, opt_dp)

        off = _dp_args(dp="off", dp_noise_mult=0.0)
        model_off, opt_off = _lin_model(off)
        with pytest.warns(UserWarning, match="privacy accountant"):
            load_checkpoint(path, model_off, opt_off)


# ------------------------------------------------------------------ #
# run-registry privacy keying: the p<eps> topology fragment          #
# ------------------------------------------------------------------ #

class TestPrivacyKeying:
    def test_privacy_suffix_forms(self):
        from commefficient_tpu.telemetry import registry

        assert registry.privacy_suffix(None) == ""
        # 0.0 is DP with an unlimited budget, NOT an absence
        assert registry.privacy_suffix(0.0) == "p0"
        assert registry.privacy_suffix(3.5) == "p3.5"
        assert registry.privacy_suffix(8) == "p8"
        assert registry.topology_key(8, 1, dp_epsilon=3.5) == "d8p1p3.5"
        assert registry.topology_key(
            8, 1, wire_dtype="int8", band="0.05:0.6",
            dp_epsilon=2.0) == "d8p1qint8b0.05-0.6p2"
        assert registry.topology_key(dp_epsilon=1.5) == "any-p1.5"

    def test_registry_run_key_privacy_fragment(self):
        from commefficient_tpu.telemetry import registry

        man = {"config_hash": "abc", "device_count": 8,
               "process_count": 1,
               "config": {"mode": "sketch", "dp": "sketch",
                          "dp_epsilon": 3.5}}
        assert registry.run_dp_epsilon(man) == 3.5
        assert registry.run_key(man) == ("abc", 8, 1, "p3.5")
        # unlimited budget still keys off the noiseless pin
        man["config"]["dp_epsilon"] = 0.0
        assert registry.run_dp_epsilon(man) == 0.0
        assert registry.run_key(man) == ("abc", 8, 1, "p0")
        man["config"]["dp"] = "off"
        assert registry.run_dp_epsilon(man) is None
        assert registry.run_key(man) == ("abc", 8, 1)

    def test_round_plan_records_dp_block(self):
        from commefficient_tpu.core.rounds import round_plan

        cfg = dataclasses.replace(
            make_cfg(mode="sketch", error_type="virtual", k=8,
                     num_rows=3, num_cols=128, dp="sketch",
                     dp_clip=2.0, dp_noise_mult=0.5, dp_delta=1e-6,
                     dp_epsilon=4.0),
            grad_size=64)
        blk = round_plan(cfg)["dp"]
        assert blk == {"mode": "sketch", "clip": 2.0,
                       "noise_mult": 0.5, "delta": 1e-6,
                       "epsilon_budget": 4.0}
        assert "dp" not in round_plan(
            dataclasses.replace(make_cfg(mode="sketch",
                                         error_type="virtual", k=8,
                                         num_rows=3, num_cols=128),
                                grad_size=64))

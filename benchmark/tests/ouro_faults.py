"""The planted faults of the Ouro cell: each breaks the *program*'s
looped model (``commefficient_tpu/models/ouro.py``) while the reference
keeps the architecture as written down. ``fault(run)`` is called after
the run is assembled and before its first round is traced;
``benchmark/tests/test_ouro_cell.py`` runs them through ``run.py``
(rehearsed on the CPU, by hand at the cell's size on the chip) and
``tests/test_ouro.py`` holds each to moving the first gradient, by
value."""

import dataclasses


def one_step(run):
    """the stack runs once, not ``total_ut_steps`` times"""
    module = run.model.module
    object.__setattr__(module, "cfg", dataclasses.replace(
        module.cfg, total_ut_steps=1))


def norm_after_loop(run):
    """the next step takes the stack's raw output: the final norm is
    applied for the head and the gate only, outside the loop"""
    import flax.linen as nn
    from commefficient_tpu.models import ouro

    class NormOutside(ouro.Stack):
        @nn.compact
        def __call__(self, x, _=None):
            cfg = self.cfg
            block = nn.remat(ouro.Block) if cfg.remat else ouro.Block
            for i in range(cfg.num_hidden_layers):
                x = block(cfg, name=f"layer_{i}")(x)
            return x, ouro.RMSNorm(cfg.rms_norm_eps, name="norm")(x)
    ouro.Stack = NormOutside


def last_step_loss_only(run):
    """the loss is the last step's alone: no exit distribution weights
    the steps' losses, and there is no entropy term"""
    import jax.numpy as jnp
    from commefficient_tpu.models import ouro

    def last_only(gates):
        p = jnp.zeros(gates.shape, jnp.float32).at[-1].set(1.0)
        return jnp.zeros_like(p), p
    ouro.exit_distribution = last_only


def gate_detached(run):
    """no gradient flows through the exit distribution: the gate is not
    trained and sends nothing back into the stack"""
    import jax
    from commefficient_tpu.models import ouro
    inner = ouro.exit_distribution
    ouro.exit_distribution = lambda gates: inner(
        jax.lax.stop_gradient(gates))


def pre_norm_only(run):
    """a block has two norms, not four: nothing normalises what
    attention and the gated part return"""
    import flax.linen as nn
    from commefficient_tpu.models import ouro

    class PreNorm(ouro.Block):
        @nn.compact
        def __call__(self, x):
            cfg = self.cfg

            def norm(name, v):
                return ouro.RMSNorm(cfg.rms_norm_eps, name=name)(v).astype(
                    cfg.dtype)

            x = x + ouro.GQAttention(cfg, rope_theta=cfg.rope_theta,
                                     name="attn")(norm("norm1", x))
            return x + ouro.GatedMLP(cfg, cfg.intermediate_size,
                                     name="mlp")(norm("norm3", x))
    ouro.Block = PreNorm


FAULTS = (one_step, norm_after_loop, last_step_loss_only, gate_detached,
          pre_norm_only)

"""Device time per round under the ``mla_attn`` scope: RoPE, the two
score products (latent and rotary), softmax and the value product of
the heads held here, forward and backward; the latent projections
around them are outside it. From the trace."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("mla_attn",))

"""Plumbing shared by the Pallas TPU kernels (sketch, take-mask,
fused-linear-CE)."""

from __future__ import annotations

import jax


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry for a ``pl.pallas_call``, typed as varying
    over every mesh axis any of ``operands`` varies over.

    Inside ``shard_map`` with ``check_vma`` on — which the fused round
    keeps on, its psum suppression rests on the typing
    (core/rounds.py) — jax refuses an output struct whose ``vma`` is
    unset. A kernel's output varies wherever an input does. Outside
    ``shard_map`` the set is empty and this is the plain struct."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

"""FetchSGD in plain ``jax.numpy`` float32: the yardstick ``correct`` is
decided against.

Follows Rothchild et al. (ICML 2020, arXiv:2007.07682), Algorithm 1, as
the reference implementation runs it (CommEfficient ``fed_aggregator.py``
sketch mode, virtual momentum + virtual error):

    g_t   = sum_i n_i * grad(loss_i)(w_t) / sum_i n_i + (wd / W) * w_t
    S_t   = sketch(g_t)                        # what the server receives
    u_t   = rho * u_{t-1} + S_t                # momentum, table space
    v_t   = v_{t-1} + u_t                      # error feedback, table space
    D_t   = top-k by magnitude of unsketch(v_t)
    u_t, v_t zeroed at the buckets sketch(D_t) occupies
    w_t+1 = w_t - lr_t * D_t

No flax, no kernel, no module of the program: the model's loss comes
from ``benchmark/reference/<config>.py`` and everything runs under
``jax.default_matmul_precision("highest")``.

Noted departure: the count sketch's hash (which bucket and sign each
coordinate gets) *defines* the table, so it cannot be chosen freely: it
is restated here from its description in ``ops/sketch.py``'s docstring
(the rotation sketch: coordinate i = t*c + j goes to bucket
(j + o[row, t]) mod c, rotations and signs from murmur3's fmix32 of the
seed). It is restated, not imported; ``tests/test_yardstick.py`` holds
it to ``CountSketch(backend="xla")``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def _mix(x):
    """murmur3 fmix32 on uint32 (numpy or jax arrays alike)."""
    u = x.dtype.type
    x = x ^ (x >> u(16))
    x = x * u(_M1)
    x = x ^ (x >> u(13))
    x = x * u(_M2)
    return x ^ (x >> u(16))


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    d: int
    c: int
    r: int
    seed: int
    rot_lanes: int = 0

    @property
    def m(self) -> int:
        return -(-self.d // self.c)

    def seeds(self):
        base = self.seed & 0xFFFFFFFF
        rot = (base * 0x9E3779B9 + 1) & 0xFFFFFFFF
        sign = (base * 0x6C62272E + 2) & 0xFFFFFFFF
        return np.uint32(rot), np.uint32(sign)

    def rotations(self) -> np.ndarray:
        """(m, r) int32 rotations in [0, c)."""
        assert self.r <= 16, "one-mix signs cover r <= 16 only"
        rot_seed, _ = self.seeds()
        rows = np.arange(self.r, dtype=np.uint32)[None, :]
        chunks = np.arange(self.m, dtype=np.uint32)[:, None]
        with np.errstate(over="ignore"):
            h = _mix(rows * np.uint32(0x7FEB352D)
                     ^ chunks * np.uint32(0x846CA68B) ^ rot_seed)
        if self.rot_lanes > 0:
            s = np.uint32(self.c // self.rot_lanes)
            return ((h % s) * np.uint32(self.rot_lanes)).astype(np.int32)
        return (h % np.uint32(self.c)).astype(np.int32)


def _chunk_signs(spec: SketchSpec, t):
    """(r, c) float32 signs of chunk ``t`` (traced uint32 scalar)."""
    _, sign_seed = spec.seeds()
    idx = t * jnp.uint32(spec.c) + jnp.arange(spec.c, dtype=jnp.uint32)
    h = _mix(idx ^ jnp.uint32(sign_seed))
    rows = jnp.arange(spec.r, dtype=jnp.uint32)[:, None]
    bit = (h[None, :] >> (jnp.uint32(16) + rows)) & jnp.uint32(1)
    return 1.0 - 2.0 * bit.astype(jnp.float32)


def _add_chunks(spec: SketchSpec, table, chunks, rots, ts):
    """``table`` plus the chunks' share of the sketch: sign and rotate
    each (c,) row of ``chunks``, add them in order. ``rots`` (n, r) and
    ``ts`` (n,) are the chunks' rotations and numbers."""

    def body(acc, inp):
        x, rot_t, t = inp
        sx = _chunk_signs(spec, t) * x[None, :]
        rows = [jnp.roll(sx[row], rot_t[row]) for row in range(spec.r)]
        return acc + jnp.stack(rows), None

    return jax.lax.scan(body, table, (chunks, rots, ts))[0]


def _estimate_chunks(spec: SketchSpec, table, rots, ts):
    """(n, c) median-of-rows estimates of the chunks numbered ``ts``
    (n,), whose rotations are ``rots`` (n, r)."""

    def body(_, inp):
        rot_t, t = inp
        signs = _chunk_signs(spec, t)
        rows = [signs[row] * jnp.roll(table[row], -rot_t[row])
                for row in range(spec.r)]
        return None, jnp.median(jnp.stack(rows), axis=0)

    return jax.lax.scan(body, None, (rots, ts))[1]


def sketch(spec: SketchSpec, vec):
    """(d,) -> (r, c) table: sign, rotate each chunk, add the chunks."""
    vp = jnp.pad(vec.astype(jnp.float32),
                 (0, spec.m * spec.c - spec.d)).reshape(spec.m, spec.c)
    return _add_chunks(
        spec, jnp.zeros((spec.r, spec.c), jnp.float32), vp,
        jnp.asarray(spec.rotations()),
        jnp.arange(spec.m, dtype=jnp.uint32))


def estimates(spec: SketchSpec, table):
    """(r, c) table -> (d,) median-of-rows estimates."""
    est = _estimate_chunks(spec, table, jnp.asarray(spec.rotations()),
                           jnp.arange(spec.m, dtype=jnp.uint32))
    return est.reshape(-1)[: spec.d]


def sketch_sparse(spec: SketchSpec, idx, vals):
    """Table of the k-sparse vector (idx, vals), by scatter-add."""
    _, sign_seed = spec.seeds()
    rots = jnp.asarray(spec.rotations())            # (m, r)
    i = idx.astype(jnp.uint32)
    t = (i // jnp.uint32(spec.c)).astype(jnp.int32)
    j = (i % jnp.uint32(spec.c)).astype(jnp.int32)
    h = _mix(i ^ jnp.uint32(sign_seed))
    table = jnp.zeros((spec.r, spec.c), jnp.float32)
    for row in range(spec.r):
        bucket = (j + rots[t, row]) % spec.c
        bit = (h >> jnp.uint32(16 + row)) & jnp.uint32(1)
        sign = 1.0 - 2.0 * bit.astype(jnp.float32)
        table = table.at[row, bucket].add(sign * vals)
    return table


# --- precisions -----------------------------------------------------------

def quantizer(name):
    """Operand rounding that stands for computing a matmul or a
    convolution in ``name``: identity for float32; a round trip through
    bfloat16; or a round trip through float8_e4m3fn with one scale per
    tensor (amax / 448), the usual fp8 recipe. Straight-through, so the
    backward pass sees the rounded operands and unrounded cotangents:
    the mildest form of the lower precision, hence the hardest control
    to tell from a sound run."""
    if name in (None, "f32", "float32"):
        return lambda x: x
    if name in ("bf16", "bfloat16"):
        def rnd(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
    elif name == "fp8":
        def rnd(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / s).astype(jnp.float8_e4m3fn) \
                .astype(jnp.float32) * s
    else:
        raise ValueError(f"no quantizer for precision {name!r}")
    return lambda x: x + jax.lax.stop_gradient(rnd(x) - x)


# --- the three steps ------------------------------------------------------

#: coordinates in a block of chunks: what the server step holds on the
#: device at once beside the tables (64 MB), whatever d is
BLOCK_COORDS = 1 << 24


def _client_blocks(batch, block):
    W = int(np.shape(batch["mask"])[0])
    block = max(b for b in range(1, min(block, W) + 1) if W % b == 0)
    for s in range(0, W, block):
        yield {k: jnp.asarray(np.asarray(v)[s:s + block])
               for k, v in batch.items()}


def ravel_host(tree):
    """The tree's leaves as one float32 numpy vector, in
    ``ravel_pytree``'s order; nothing is put on a device."""
    return np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in jax.tree_util.tree_leaves(tree)])


def _read_back(tree, out, spans):
    """The device tree's leaves into the host vector ``out``, leaf i at
    ``spans[i]``."""
    leaves = jax.tree_util.tree_leaves(tree)
    for x in leaves:
        x.copy_to_host_async()
    for x, (a, b) in zip(leaves, spans):
        out[a:b] = np.asarray(x).reshape(-1)


def client_step(ref, spec_model, q):
    """The jitted ``(w, g, client block) -> (g + the block's gradient of
    sum_i n_i * loss_i, the block's losses)``. ``w`` and ``g`` are trees
    and ``g`` is donated, so each leaf's gradient is added where the
    accumulator lies: the step holds d twice on the device, beside one
    block's activations and the leaf gradients in flight."""

    def block_sum(w, cb):
        def one(b):
            loss = ref.client_loss(w, b, spec_model, q)
            n = jnp.sum(b["mask"])
            return jnp.where(n > 0, loss * n, 0.0), loss
        weighted, losses = jax.vmap(one)(cb)
        return jnp.sum(weighted), losses

    def step(w, g, cb):
        (_, losses), gb = jax.value_and_grad(block_sum, has_aux=True)(w, cb)
        return jax.tree_util.tree_map(jnp.add, g, gb), losses

    return jax.jit(step, donate_argnums=1)


def _blocks(sk: SketchSpec):
    """(chunks in a block, blocks): the server step's unit of work."""
    nb = min(sk.m, max(1, BLOCK_COORDS // sk.c))
    return nb, -(-sk.m // nb)


def _block_rotations(sk: SketchSpec) -> np.ndarray:
    """(blocks, chunks in a block, r) rotations; the chunks past the
    last are all zeros, as is what they are fed."""
    nb, nblocks = _blocks(sk)
    rots = np.zeros((nblocks * nb, sk.r), np.int32)
    rots[: sk.m] = sk.rotations()
    return rots.reshape(nblocks, nb, sk.r)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _add_block(sk, table, chunks, rots, t0):
    ts = t0 + jnp.arange(chunks.shape[0], dtype=jnp.uint32)
    return _add_chunks(sk, table, chunks, rots, ts)


def _select(sk: SketchSpec, v, k):
    """The k largest ``|estimates(v)|`` as (indices, estimates), in
    ``jax.lax.top_k``'s order over all d (larger first, the lower index
    first among equals), with one block of estimates on the device at a
    time: each block's own top k, merged into the k kept so far. The
    kept ones lie at lower indices than the block's and both lists are
    in that order, so the merge, which prefers the earlier position
    among equals, keeps it. Exact."""
    nb, nblocks = _blocks(sk)
    span = nb * sk.c
    if nblocks * span >= 2 ** 31:
        raise ValueError(f"d = {sk.d}: the selection's indices are int32")

    def body(kept, inp):
        mag, idx, val = kept
        rot_b, b = inp
        est = _estimate_chunks(
            sk, v, rot_b, b * jnp.uint32(nb)
            + jnp.arange(nb, dtype=jnp.uint32)).reshape(-1)
        base = b.astype(jnp.int32) * span
        inside = base + jnp.arange(span, dtype=jnp.int32) < sk.d
        bmag, at = jax.lax.top_k(
            jnp.where(inside, jnp.abs(est), -1.0), min(k, span))
        mag, pick = jax.lax.top_k(jnp.concatenate([mag, bmag]), k)
        return (mag, jnp.concatenate([idx, base + at])[pick],
                jnp.concatenate([val, est[at]])[pick]), None

    start = (jnp.full((k,), -1.0), jnp.zeros((k,), jnp.int32),
             jnp.zeros((k,), jnp.float32))
    (_, idx, val), _ = jax.lax.scan(
        body, start, (jnp.asarray(_block_rotations(sk)),
                      jnp.arange(nblocks, dtype=jnp.uint32)))
    return idx, val


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _server(sk, k, rho, u, v, table):
    """Momentum and error feedback in table space, the selection, and
    the selected coordinates' buckets zeroed in both tables."""
    u = rho * u + table
    v = v + u
    idx, vals = _select(sk, v, k)
    keep = sketch_sparse(sk, idx, vals) == 0
    return jnp.where(keep, u, 0.0), jnp.where(keep, v, 0.0), idx, vals


def follow(ref, spec_model, params, batches, lrs, hyper,
           sk: SketchSpec, precision=None):
    """Run ``len(batches)`` FetchSGD rounds from ``params``.

    ``ref.client_loss(params, client_batch, spec_model, q) -> scalar`` is
    one client's masked-mean loss. ``hyper``: k, rho (virtual momentum),
    weight_decay. Returns per-round per-client losses, the first
    round's table, and the flat weight change after the last round.

    The flat vectors (the weights at the start and now, the round's
    gradient) are the host's, in numpy. The device holds d twice: the
    weights as the tree ``client_loss`` takes, and the tree the
    clients' gradients are added into, which is read back once a round.
    The server step works in table space on blocks of chunks: the
    sketch is fed from the host, the estimates are made and selected
    from block by block, and the k selected coordinates step the host's
    weights, whose changed leaves are put on the device again.
    """
    q = quantizer(precision)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = [np.shape(x) for x in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    ends = np.cumsum(sizes)
    spans = list(zip(ends - sizes, ends))
    flat0 = ravel_host(leaves)
    del leaves, params
    flat = flat0.copy()
    if flat.size != sk.d:
        raise ValueError(f"{flat.size} weights for a sketch of d = {sk.d}")
    k = min(int(hyper["k"]), sk.d)
    rho = float(hyper["rho"])
    decay = np.float32(hyper["weight_decay"] / hyper["num_workers"])
    block = int(getattr(ref, "CLIENTS_PER_BLOCK", 1))
    nb, nblocks = _blocks(sk)
    span = nb * sk.c
    rots = _block_rotations(sk)

    def put(i):
        (a, b), shape = spans[i], shapes[i]
        return jax.device_put(flat[a:b].reshape(shape))

    step = client_step(ref, spec_model, q)
    zeros = jax.jit(lambda: treedef.unflatten(
        [jnp.zeros(s, jnp.float32) for s in shapes]))
    w = [put(i) for i in range(len(shapes))]
    grad = np.empty_like(flat)
    out = {"losses": [], "table0": None}
    u = v = jnp.zeros((sk.r, sk.c), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches):
            total = np.float32(max(float(np.asarray(batch["mask"]).sum()),
                                   1.0))
            g, losses = zeros(), []
            for cb in _client_blocks(batch, block):
                g, ls = step(treedef.unflatten(w), g, cb)
                losses.append(ls)
            _read_back(g, grad, spans)
            del g
            table = jnp.zeros((sk.r, sk.c), jnp.float32)
            for i in range(nblocks):
                a, b = i * span, min((i + 1) * span, sk.d)
                chunks = np.zeros((span,), np.float32)
                chunks[: b - a] = grad[a:b] / total + decay * flat[a:b]
                table = _add_block(sk, table, chunks.reshape(nb, sk.c),
                                   rots[i], np.uint32(i * nb))
            if t == 0:
                out["table0"] = np.asarray(table)
            u, v, idx, vals = _server(sk, k, rho, u, v, table)
            idx, vals = np.asarray(idx), np.asarray(vals)
            flat[idx] += -np.float32(lrs[t]) * vals
            if t + 1 < len(batches):
                for i in np.unique(np.searchsorted(ends, idx, side="right")):
                    w[i] = put(i)
            out["losses"].append(np.concatenate(
                [np.asarray(ls) for ls in losses]))
    flat -= flat0
    out["delta"] = flat
    return out


# --- the comparison -------------------------------------------------------

def _leaf_norms(flat, sizes):
    out, o = [], 0
    for n in sizes:
        out.append(float(np.linalg.norm(flat[o:o + n].astype(np.float64))))
        o += n
    return np.asarray(out)


def _worst_gap(got, want, share_of_largest=0.0):
    """Worst leaf: |norm_got - norm_want| over max(norm_want of that
    leaf, norm_want of the median leaf). Some leaves are all but zero.
    ``share_of_largest`` raises the floor to that share of the largest
    leaf's norm: a top-k update of 50,000 coordinates leaves most of a
    124M-parameter model's 148 leaves a handful of coordinates each, the
    median leaf among them, and which handful is chance."""
    floor = max(float(np.median(want)),
                share_of_largest * float(np.max(want)))
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.maximum(want, floor), 1e-30)))


def numbers(observed, reference, leaf_sizes):
    """The numbers ``correct`` compares, program (or control) against
    the float32 reference. ``observed`` / ``reference``: dicts with
    ``losses`` (rounds x clients), ``table0`` (r, c), ``delta`` (d,)."""
    lo = np.asarray(observed["losses"], np.float64)
    lr_ = np.asarray(reference["losses"], np.float64)
    t_o = np.asarray(observed["table0"], np.float64)
    t_r = np.asarray(reference["table0"], np.float64)
    rows_o = np.linalg.norm(t_o, axis=1)
    rows_r = np.linalg.norm(t_r, axis=1)
    return {
        # each step's loss, worst client of the worst step
        "loss_gap": float(np.max(np.abs(lo - lr_)
                                 / np.maximum(np.abs(lr_), 1e-30))),
        # the first gradient as the optimizer gets it (the table), by
        # the worst leaf (a table's leaves are its rows)
        "grad_norm_gap": _worst_gap(rows_o, rows_r),
        # the same gradient, distance instead of norm: what separates
        # precisions (see PERF.md section 2)
        "grad_rel_l2": float(np.linalg.norm(t_o - t_r)
                             / max(np.linalg.norm(t_r), 1e-30)),
        # the parameters' change after the steps, by the worst leaf
        "delta_norm_gap": _worst_gap(
            _leaf_norms(np.asarray(observed["delta"]), leaf_sizes),
            _leaf_norms(np.asarray(reference["delta"]), leaf_sizes),
            share_of_largest=0.1),
    }


def detail(observed, reference):
    """Readings printed beside the compared numbers, compared with
    nothing: each step's worst loss gap, the share of the reference's
    changed coordinates that the other side changed too, and the gap of
    the whole change's norm."""
    lo = np.asarray(observed["losses"], np.float64)
    lr_ = np.asarray(reference["losses"], np.float64)
    d_o = np.asarray(observed["delta"])
    d_r = np.asarray(reference["delta"])
    both = np.count_nonzero((d_o != 0) & (d_r != 0))
    n_r = float(np.linalg.norm(d_r.astype(np.float64)))
    return {
        "loss_gap_by_step": [float(x) for x in np.max(
            np.abs(lo - lr_) / np.maximum(np.abs(lr_), 1e-30), axis=1)],
        "changed": [int(np.count_nonzero(d_o)), int(np.count_nonzero(d_r))],
        "changed_in_both_share": both / max(np.count_nonzero(d_r), 1),
        "delta_total_norm_gap": abs(float(np.linalg.norm(
            d_o.astype(np.float64))) - n_r) / max(n_r, 1e-30),
    }


def verdict(nums, limits):
    """[(name, value, limit, ok)] for every limit; all must hold."""
    rows = []
    for name, limit in sorted(limits.items()):
        value = nums[name]
        ok = math.isfinite(value) and value <= limit
        rows.append((name, value, limit, ok))
    return rows

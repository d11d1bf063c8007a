"""Count-sketch (CSVec) — the FetchSGD compression operator, TPU-native.

In-tree replacement for the reference's external CUDA `csvec` library
(used at fed_aggregator.py:5,466-469,586-597 and fed_worker.py:315-322;
API surface documented in SURVEY.md §2.9). Semantics:

- An ``(r, c)`` table of buckets. Coordinate ``i`` is hashed by each of
  the r rows to a column ``h_r(i)`` and a sign ``s_r(i) ∈ {±1}``;
  sketching adds ``s_r(i)·v[i]`` into ``table[r, h_r(i)]``.
- Recovery estimates ``v[i] ≈ median_r(s_r(i)·table[r, h_r(i)])``;
  ``unsketch(k)`` returns a dense vector keeping only the k
  largest-magnitude estimates (heavy hitters).
- ``l2estimate() = sqrt(median_r ‖table[r]‖²)``.

**TPU-first hash design — the rotation (circulant) sketch.** A CUDA
count-sketch scatter-adds to random buckets; random scatter/gather is
the worst workload for a TPU's vector units (measured: >200 ms for the
ResNet9-sized sketch via XLA scatter). Instead, the padded coordinate
space is split into ``m = ceil(d/c)`` contiguous chunks of width c,
and row r assigns coordinate ``i`` (chunk ``t = i // c``, offset
``j = i % c``) the bucket

    h_r(i) = (j + o[r, t]) mod c

with a pseudorandom per-(row, chunk) rotation ``o[r, t]`` and
per-coordinate murmur signs. Then:

- sketching row r = sign-multiply + per-chunk ``roll`` + chunk-sum —
  aligned VPU ops, zero scatter;
- recovery row r = per-chunk inverse ``roll`` of the table row —
  zero gather.

Collision analysis (why CS guarantees survive): two coords in the same
chunk keep their offset distance under rotation, so they **never**
collide (better than the classic 1/c); coords in chunks t ≠ t' collide
iff ``o[r,t] - o[r,t'] ≡ j' - j (mod c)`` — probability 1/c,
independent across rows. Per-pair collision probability ≤ 1/c
throughout, which is the only property the count-sketch variance bound
uses; signs are iid per coordinate, so estimates stay unbiased.

Rotations and signs are counter-based (murmur3 mixer of the seed), so
the operator is stateless and bit-deterministic on every replica —
``psum(table)`` over the mesh equals the sketch of the summed vector
exactly (linearity + fixed hashes).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)

# chunk counts up to this get fully unrolled static-shift rolls (fast
# path); above it, a scan with dynamic shifts keeps the emitted XLA
# program constant-size (tiny-c configs like --num_cols 1000 at
# grad_size 1e6 would otherwise unroll thousands of ops)
_UNROLL_LIMIT = 128


def _mix(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer — cheap, well-dispersed, VPU-friendly."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def _np_mix(x: np.ndarray) -> np.ndarray:
    """numpy twin of _mix (identical uint32 wraparound semantics)."""
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(13))
    x = x * _M2
    x = x ^ (x >> np.uint32(16))
    return x


@dataclasses.dataclass(frozen=True)
class CountSketch:
    """Static description of a sketch operator (d, c, r, seed).

    Mirrors ``CSVec(d, c, r, numBlocks)`` (reference
    fed_aggregator.py:466-469) minus the device argument — placement is
    the mesh's job. ``num_blocks`` is accepted for CLI parity (it was
    the reference CUDA library's memory knob) but unused: the rotation
    formulation has no memory blow-up to manage. Instances are
    hashable and static under jit.
    """

    d: int
    c: int
    r: int
    num_blocks: int = 20
    seed: int = 42
    # TPU-native approximate top-k for recovery (lax.approx_max_k,
    # ~3x faster at recall 0.95). Algorithmically safe for FetchSGD —
    # error feedback re-surfaces missed heavy hitters next round — but
    # off by default for exact reference parity.
    approx_topk: bool = False
    # recall target for approx_topk: lower = smaller internal sort =
    # faster (measured ~2x at 0.85, which still selects ~94% of the
    # true top-k on gaussian data); missed coordinates stay in the
    # error accumulator and resurface next round
    approx_recall: float = 0.95
    # "auto" | "xla" | "pallas" | "pallas_interpret": auto picks the
    # fused Pallas kernels (ops/sketch_pallas.py) on TPU when the
    # geometry supports them (c lane-aligned, table VMEM-resident) and
    # the roll-based XLA path otherwise. Identical hash streams; sketch
    # tables agree to ULP-level summation-order tolerance, recovery
    # from a given table is bit-exact.
    backend: str = "auto"
    # > 0: quantize rotations to multiples of this many elements.
    # At a multiple of 1,024 (one float32 vreg: 8 sublanes x 128
    # lanes) on a chunk of whole (32, 128) tiles every rotation moves
    # whole vregs, and the Pallas kernels apply it as a row offset into
    # the VMEM-resident table (ops/sketch_pallas.py ``rotation_form``:
    # "addressed") where they otherwise roll each chunk through the
    # 5-op arbitrary-shift decomposition (the kernels were VPU-bound
    # on rolls at large d). Collision
    # tradeoff: coords in chunks t != t' with equal lane offset
    # (j ≡ j' mod rot_lanes, a 1/rot_lanes fraction of pairs) collide
    # with probability rot_lanes/c instead of 1/c; all other cross-
    # chunk pairs never collide. The AVERAGE per-pair collision rate
    # stays 1/c, so expected recovery error is unchanged while the
    # tail is heavier (round 5 measured recovery quality before the
    # auto default, core/rounds.py, was set). 0 = off (full-
    # granularity rotations, the reference-quality default).
    rot_lanes: int = 0
    # stream precomputed packed sign bits ((padded_d,) uint8, bit row
    # = hash bit 16+row) into the Pallas kernels instead of hashing
    # in-kernel. The murmur mix is two u32 multiplies per element —
    # emulated multi-op on the VPU and the largest r-independent ALU
    # block in both kernels; the table costs ~1 byte/element of HBM
    # traffic (~0.15 ms at d=124M vs ~2-3 ms of hashing per kernel
    # call) and is computed ON-DEVICE inside the round program (a
    # closed-over 125 MB host constant measured 11.7 s lowering +
    # 27.6 s compile + a 250 MB HLO — never do that), where XLA CSE
    # shares one materialisation across the clients vmap and the
    # sketch/estimates pair. Eligible when one-mix signs apply and
    # r <= 8 (u8 holds 8 row bits); ineligible geometries hash
    # in-kernel as before. Sign VALUES are identical either way.
    packed_signs: bool = True

    def __post_init__(self):
        assert self.d > 0 and self.c > 0 and self.r > 0
        self._check_rot_lanes_engage()

    # --- hashing ---------------------------------------------------------

    @property
    def _m(self) -> int:
        """number of coordinate chunks"""
        return -(-self.d // self.c)  # ceil

    @property
    def _padded_d(self) -> int:
        return self._m * self.c

    def _seeds(self):
        base = np.uint64(self.seed & 0xFFFFFFFF)
        mask = np.uint64(0xFFFFFFFF)
        rot = np.uint32((base * np.uint64(0x9E3779B9) + np.uint64(1)) & mask)
        sign = np.uint32((base * np.uint64(0x6C62272E) + np.uint64(2)) & mask)
        return rot, sign

    def _rotations(self) -> np.ndarray:
        """(r, m) rotations in [0, c) — computed host-side in numpy so
        the rolls below get *static* shifts (XLA lowers them to plain
        slice+concat instead of dynamic-slice chains). With
        ``rot_lanes`` set, rotations are drawn uniformly from the
        c/rot_lanes multiples of rot_lanes (see the field comment)."""
        rot_seed, _ = self._seeds()
        rows = np.arange(self.r, dtype=np.uint32)[:, None]
        chunks = np.arange(self._m, dtype=np.uint32)[None, :]
        with np.errstate(over="ignore"):
            h = _np_mix(rows * np.uint32(0x7FEB352D)
                        ^ chunks * np.uint32(0x846CA68B)
                        ^ rot_seed)
        if self.rot_lanes > 0:
            assert self.c % self.rot_lanes == 0, (self.c, self.rot_lanes)
            # the rotation space must stay large: c/rot_lanes distinct
            # rotations bound the same-lane-offset collision rate at
            # rot_lanes/c per row. At c == rot_lanes every rotation is
            # zero and stride-c pairs collide in EVERY row — degenerate
            assert self.c // self.rot_lanes >= 8, \
                f"rot_lanes {self.rot_lanes} too coarse for c={self.c}"
            s = np.uint32(self.c // self.rot_lanes)
            return ((h % s) * np.uint32(self.rot_lanes)).astype(np.int64)
        return (h % np.uint32(self.c)).astype(np.int64)

    @property
    def _one_mix_signs(self) -> bool:
        """r <= 16: all rows' signs come from distinct high bits of a
        SINGLE murmur mix per coordinate (bits are independent after
        fmix32) — 1/r the hashing cost, the dominant cost of the fused
        kernels. Larger r falls back to one mix per (row, coord)."""
        return self.r <= 16

    def _sign_hash(self, idx: jax.Array) -> jax.Array:
        """uint32 per-coordinate sign hash (one-mix scheme)."""
        _, sign_seed = self._seeds()
        return _mix(idx ^ sign_seed)

    def _signs_row(self, row: int | jax.Array) -> jax.Array:
        """(padded_d,) float32 signs for one row."""
        _, sign_seed = self._seeds()
        idx = jnp.arange(self._padded_d, dtype=jnp.uint32)
        if self._one_mix_signs:
            h = self._sign_hash(idx)
            bit = (h >> (jnp.uint32(16) + jnp.uint32(row))) & 1
        else:
            h = _mix(idx ^ (jnp.uint32(row) * jnp.uint32(0x9E3779B9))
                     ^ sign_seed)
            bit = (h >> 16) & 1
        return 1.0 - 2.0 * bit.astype(jnp.float32)

    @property
    def _packed_sign_kernels(self) -> bool:
        """Whether the Pallas kernels stream precomputed sign bits
        (see the ``packed_signs`` field comment)."""
        return self.packed_signs and self._one_mix_signs and self.r <= 8

    def _packed_signs_traced(self) -> jax.Array:
        """(padded_d,) uint8 packed sign bits — bit ``row`` is the
        one-mix hash bit 16+row, i.e. exactly the bit
        ``_signs_row(row)`` reads. Built from jnp ops INSIDE the
        caller's trace (never a host-side constant; see the field
        comment for why), so XLA CSEs the subgraph wherever it
        appears more than once in a program."""
        idx = jnp.arange(self._padded_d, dtype=jnp.uint32)
        h = self._sign_hash(idx)
        mask = jnp.uint32((1 << self.r) - 1)
        return ((h >> 16) & mask).astype(jnp.uint8)

    def hashes(self, idx: jax.Array):
        """(buckets, signs) for int32 coordinate indices: buckets
        uint32 (r, n) in [0, c); signs float32 (r, n) in {±1}."""
        rot = jnp.asarray(self._rotations(), jnp.uint32)
        _, sign_seed = self._seeds()
        i = idx.astype(jnp.uint32)[None, :]
        t = (i // jnp.uint32(self.c)).astype(jnp.int32)
        j = i % jnp.uint32(self.c)
        rows = jnp.arange(self.r, dtype=jnp.uint32)[:, None]
        buckets = (j + jnp.take_along_axis(
            jnp.broadcast_to(rot, (self.r, self._m)), t, axis=1)) \
            % jnp.uint32(self.c)
        if self._one_mix_signs:
            h = self._sign_hash(i)
            bit = (h >> (jnp.uint32(16) + rows)) & 1
        else:
            h = _mix(i ^ (rows * jnp.uint32(0x9E3779B9)) ^ sign_seed)
            bit = (h >> 16) & 1
        signs = 1.0 - 2.0 * bit.astype(jnp.float32)
        return buckets, signs

    # --- sketching (accumulateVec) --------------------------------------

    def _check_rot_lanes_engage(self):
        """rot_lanes only pays off where the kernels address the table
        instead of rolling the chunk: every rotation a whole number of
        vregs on a chunk of whole tiles (``rot_form``). Otherwise the
        user eats the heavier collision tail for zero speedup — warn
        once."""
        if self.rot_lanes <= 0:
            return
        import logging
        log = logging.getLogger(__name__)
        # construction stays JAX-runtime-free: probing the backend here
        # would call jax.devices() inside __post_init__, locking in a
        # backend before a multi-host embedder's
        # jax.distributed.initialize() / platform override runs. The
        # resolved-backend warning fires lazily from _resolve_backend
        # at first use instead; only the explicit backend="xla" case is
        # knowable (and warned) now.
        if self.backend == "xla":
            self._warn_rot_lanes_no_pallas("xla")
            return
        from commefficient_tpu.ops.sketch_pallas import rotation_form
        if rotation_form(self.c, self.r, self.rot_lanes) != "addressed":
            log.warning(
                "rot_lanes=%d at c=%d, r=%d: rotations are quantized "
                "(heavier collision tail) but they are not whole vregs "
                "of whole (32, 128) tiles, so the kernels still roll "
                "every chunk — use rot_lanes=1024 with c a multiple "
                "of 4096", self.rot_lanes, self.c, self.r)

    def _warn_rot_lanes_no_pallas(self, resolved: str):
        """Quantized rotations pay their collision tail only to buy
        the Pallas kernels' addressed rotation; any non-pallas lowering
        (unsupported geometry, non-TPU platform, explicit
        backend="xla") gains nothing from them — warn once per
        instance."""
        if getattr(self, "_rot_lanes_warned", False):
            return
        object.__setattr__(self, "_rot_lanes_warned", True)
        import logging
        logging.getLogger(__name__).warning(
            "sketch_rot_lanes=%d with backend %r: the addressed "
            "rotation only exists in the Pallas TPU kernels — rotations "
            "are quantized (heavier collision tail) for zero speedup "
            "here; use rot_lanes=0", self.rot_lanes, resolved)

    def _resolve_backend(self) -> str:
        resolved = self.backend
        if resolved == "auto":
            from commefficient_tpu.ops.sketch_pallas import supported
            if not supported(self.d, self.c, self.r):
                resolved = "xla"
            else:
                # Mosaic kernels only lower on TPU; everywhere else
                # (the CPU test mesh) auto means the XLA twin.
                # chip_smoke.py asserts which one a chip run got.
                platform = jax.devices()[0].platform
                resolved = "pallas" if platform == "tpu" else "xla"
        if resolved != "pallas" and self.rot_lanes > 0:
            self._warn_rot_lanes_no_pallas(resolved)
        return resolved

    def sketch(self, v: jax.Array) -> jax.Array:
        """Dense (d,) vector -> (r, c) sketch table, scatter-free."""
        assert v.shape == (self.d,), v.shape
        vp = jnp.pad(v.astype(jnp.float32), (0, self._padded_d - self.d))
        return self._sketch_padded(vp)

    def sketch_from_leaves(self, leaves) -> jax.Array:
        """Gradient-pytree leaves -> (r, c) table, bit-identical to
        ``sketch`` of their ``ravel_pytree`` concatenation.

        The flat-primal fused round pays two d-sized copies between
        the model backward and the kernel: autodiff's
        transpose-of-unravel concatenates the leaf cotangents into the
        (d,) flat gradient, then ``sketch`` pads it to padded_d. With
        tree-space gradients this assembles the kernel input in ONE
        concatenate (leaves + zero tail) — XLA lowers it to parallel
        writes into the padded buffer, and the flat (d,) gradient never
        exists (the concat/pad item in the round-3 xplane breakdown,
        VERDICT round 3 weak #5)."""
        parts = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
        total = sum(p.size for p in parts)
        assert total == self.d, (total, self.d)
        pad = self._padded_d - self.d
        if pad:
            parts.append(jnp.zeros((pad,), jnp.float32))
        return self._sketch_padded(jnp.concatenate(parts))

    def _sketch_padded(self, vp: jax.Array) -> jax.Array:
        """(padded_d,) pre-padded vector -> (r, c) table."""
        assert vp.shape == (self._padded_d,), vp.shape
        m, c = self._m, self.c
        backend = self._resolve_backend()
        if backend in ("pallas", "pallas_interpret"):
            from commefficient_tpu.ops.sketch_pallas import sketch_pallas
            _, sign_seed = self._seeds()
            sgn = (self._packed_signs_traced()
                   if self._packed_sign_kernels else None)
            return sketch_pallas(vp, jnp.asarray(self._rotations()),
                                 c, self.r, int(sign_seed),
                                 backend == "pallas_interpret",
                                 one_mix=self._one_mix_signs,
                                 rot_step=self.rot_lanes, sgn=sgn)
        rot = self._rotations()  # host constants -> static rolls

        if m <= _UNROLL_LIMIT:
            rows = []
            for row in range(self.r):
                signed = (vp * self._signs_row(row)).reshape(m, c)
                rolled = jnp.stack([
                    jnp.roll(signed[t], int(rot[row, t]))
                    for t in range(m)])
                rows.append(jnp.sum(rolled, axis=0))
            return jnp.stack(rows)

        # many-chunk regime (small c): scan over chunks with dynamic
        # rolls to keep the emitted program constant-size
        rot_dev = jnp.asarray(rot, jnp.int32)

        def one_row(row, rots):
            signed = (vp * self._signs_row(row)).reshape(m, c)

            def body(acc, inp):
                chunk, o = inp
                return acc + jnp.roll(chunk, o), None

            # zero init derived from the input (x*0), not jnp.zeros:
            # under shard_map (a per-client sketch inside a spanning
            # mesh) a plain-zeros carry lacks the body output's
            # varying mesh axes and trips the scan carry-type check
            out, _ = jax.lax.scan(body, signed[0] * 0.0,
                                  (signed, rots))
            return out

        return jax.vmap(one_row)(jnp.arange(self.r, dtype=jnp.uint32),
                                 rot_dev)

    def sketch_quantized(self, v: jax.Array, wire: str, rows=None):
        """Dense (d,) vector -> (wire-dtype (r, c) table, (r, 1) f32
        rowmax): the emit + local-quantize wire path. For int8 on the
        Pallas backend the f32 table only ever exists in the kernel's
        VMEM scratch (ops/sketch_pallas.sketch_quant_pallas);
        everything else sketches then quantizes (same algebra,
        ops/quant.py quantize_local), so the two paths agree exactly
        on a given table. Callers harmonize the result onto the shared
        global scale before the wire collective (core/rounds.py).

        ``rows`` — optional ``(offset, count)`` row chunk
        (--overlap_depth chunked emission): emit + quantize ONLY those
        table rows. The Pallas kernel then runs with a chunk-sized
        VMEM scratch, the chunk's rotation-row slice and sign streams
        keyed by the absolute row, so the chunk is bit-identical to
        the same rows of a whole-table call (per-row scales make the
        quantization algebra row-separable)."""
        from commefficient_tpu.ops.quant import quantize_local
        off, cnt = rows if rows is not None else (0, self.r)
        assert 0 <= off and off + cnt <= self.r, (off, cnt, self.r)
        if wire == "bf16":
            # scale-free cast — nothing to fuse
            q, rm = quantize_local(self.sketch(v), wire)
            if rows is not None:
                q = jax.lax.slice_in_dim(q, off, off + cnt, axis=0)
            return q, rm
        backend = self._resolve_backend()
        # fp8 is never fused: ops/quant.py's bit-exact contract rounds
        # f32 -> f16 -> float8_e4m3fn, and Mosaic has no f16 -> f8
        # cast (the v5e has no fp8 unit; the fused fp8 kernel never
        # lowered for TPU). Its table still comes from the Pallas
        # sketch kernel below; only the quantize runs as XLA ops.
        if backend in ("pallas", "pallas_interpret") and wire == "int8":
            from commefficient_tpu.ops.sketch_pallas import (
                _pick_lanes, sketch_quant_pallas)
            assert v.shape == (self.d,), v.shape
            vp = jnp.pad(v.astype(jnp.float32),
                         (0, self._padded_d - self.d))
            _, sign_seed = self._seeds()
            sgn = (self._packed_signs_traced()
                   if self._packed_sign_kernels else None)
            rot = self._rotations()
            if rows is not None:
                rot = rot[off:off + cnt]
            # a row chunk takes the whole operator's rotation form (a
            # smaller table could be addressed where the whole is not)
            # so that it stays the same bits as the whole call's rows
            lanes = (None if self.rot_form == "addressed"
                     else _pick_lanes(self.c))
            return sketch_quant_pallas(
                vp, jnp.asarray(rot), self.c, cnt,
                int(sign_seed),
                backend == "pallas_interpret", lanes,
                one_mix=self._one_mix_signs,
                rot_step=self.rot_lanes, sgn=sgn,
                row_offset=off)
        table = self.sketch(v)
        if rows is not None:
            table = jax.lax.slice_in_dim(table, off, off + cnt,
                                         axis=0)
        return quantize_local(table, wire)

    # --- recovery --------------------------------------------------------

    def estimates(self, table: jax.Array,
                  padded: bool = False) -> jax.Array:
        """Median-of-rows estimates for all d coordinates — gather-free
        (per-chunk inverse rolls of the table rows). Materialises
        (r, padded_d): fine up to tens of millions of coords.

        ``padded=True`` returns the full (padded_d,) vector with the
        tail coordinates (>= d) zeroed instead of slicing to (d,):
        ``est[:d]`` is a d-sized prefix copy (~2 ms at GPT-2's d=124M)
        that the index-selection consumers never need — zeros lose
        every magnitude comparison, so selection over the padded
        vector picks the identical set (indices stay < d as long as
        the vector has >= k nonzero estimates, which any real gradient
        table does)."""
        assert table.shape == (self.r, self.c), table.shape
        m, c = self._m, self.c
        backend = self._resolve_backend()
        if backend in ("pallas", "pallas_interpret"):
            from commefficient_tpu.ops.sketch_pallas import estimates_pallas
            _, sign_seed = self._seeds()
            sgn = (self._packed_signs_traced()
                   if self._packed_sign_kernels else None)
            est = estimates_pallas(table, jnp.asarray(self._rotations()),
                                   c, self.r, int(sign_seed),
                                   backend == "pallas_interpret",
                                   one_mix=self._one_mix_signs,
                                   valid=self.d if padded else None,
                                   rot_step=self.rot_lanes, sgn=sgn)
            return est if padded else est[: self.d]
        rot = self._rotations()

        if m <= _UNROLL_LIMIT:
            ests = []
            for row in range(self.r):
                unrolled = jnp.stack([
                    jnp.roll(table[row], -int(rot[row, t]))
                    for t in range(m)])  # (m, c): chunk t's table view
                ests.append(unrolled.reshape(-1) * self._signs_row(row))
            return self._finish_estimates(
                jnp.median(jnp.stack(ests), axis=0), padded)

        rot_dev = jnp.asarray(rot, jnp.int32)

        def one_row(row, trow, rots):
            unrolled = jax.lax.map(lambda o: jnp.roll(trow, -o), rots)
            return unrolled.reshape(-1) * self._signs_row(row)

        ests = jax.vmap(one_row)(jnp.arange(self.r, dtype=jnp.uint32),
                                 table, rot_dev)
        return self._finish_estimates(jnp.median(ests, axis=0), padded)

    def _finish_estimates(self, est_full: jax.Array,
                          padded: bool) -> jax.Array:
        if not padded:
            return est_full[: self.d]
        if self._padded_d == self.d:
            return est_full
        # zero the tail in place of the slice; the iota compare fuses
        # into the median's elementwise epilogue
        pos = jnp.arange(self._padded_d, dtype=jnp.int32)
        return jnp.where(pos < self.d, est_full, 0.0)

    def estimates_at(self, table: jax.Array,
                     idx: jax.Array) -> jax.Array:
        """Median-of-rows estimates for an arbitrary int32 index
        vector — the gather-based dual of ``estimates()``. Element i
        of row ``row`` in the rolled path reads
        ``table[row, (i % c + rot[row, i // c]) % c]`` times the sign
        bit, which is exactly the (bucket, sign) pair ``hashes()``
        produces, so this is bit-identical per coordinate to
        ``estimates(table)[idx]`` (same float32 products, same
        median) while doing O(r·n) work instead of O(r·d). Used by
        the 2D server round, where each model peer estimates only its
        own coordinate slice of the gathered table. Indices must be
        in [0, padded_d); padded-tail indices return garbage, so
        callers mask them out themselves."""
        assert table.shape == (self.r, self.c), table.shape
        buckets, signs = self.hashes(idx)
        vals = jnp.take_along_axis(
            table, buckets.astype(jnp.int32), axis=1) * signs
        return jnp.median(vals, axis=0)

    @partial(jax.jit, static_argnums=(0, 2, 3, 4))
    def unsketch(self, table: jax.Array, k: int,
                 with_support: bool = False,
                 with_dense: bool = True):
        """(r, c) table -> dense (d,) vector keeping only the k
        largest-magnitude estimated coordinates (reference
        ``CSVec.unSketch(k)``; server use at fed_aggregator.py:592).

        ``with_support=True`` additionally returns the (k,) selected
        indices and their values — the sparse form of the update, used
        so downstream consumers (download-byte accounting) never need
        the dense vector on the host."""
        k = min(k, self.d)
        # the big-d selections never need the (d,) prefix slice of the
        # estimates — selection over the tail-zeroed padded vector
        # picks the identical set (see ``estimates``); the small-d
        # lax.top_k path keeps the slice (d == padded_d there is
        # common, and the sort dominates anyway)
        from commefficient_tpu.ops.topk import _THRESHOLD_SELECT_MIN_D
        big_d = self.d >= _THRESHOLD_SELECT_MIN_D
        with jax.named_scope("estimates"):
            est = self.estimates(table, padded=big_d)
        with jax.named_scope("select"):
            return self._select(est, k, big_d, with_support, with_dense)

    def _select(self, est, k: int, big_d: bool, with_support: bool,
                with_dense: bool):
        """``unsketch`` after the estimates: the k largest-magnitude
        ones as (dense, idx, vals)."""
        from commefficient_tpu.ops.topk import (threshold_topk_indices,
                                                use_threshold_select)
        if self.approx_topk:
            _, idx = jax.lax.approx_max_k(
                jax.lax.square(est), k,
                recall_target=self.approx_recall)
            if big_d:
                # degenerate guard (sub-k support): approx_max_k breaks
                # zero-ties in unspecified order and could pick a tail
                # slot; clamp it in range for the promise_in_bounds
                # scatters, and force the value to 0 below — est[d-1]
                # is generally nonzero, and a duplicated
                # (d-1, est[d-1]) pair would double-count under
                # sketch_sparse's scatter-ADD on the sparse-resketch
                # path. The threshold path needs no guard — its
                # lowest-index tie-break can't reach the tail while
                # k <= d
                oob = idx >= self.d
                idx = jnp.minimum(idx, self.d - 1)
        else:
            if use_threshold_select(k, self.d, False):
                # exact selection without the full sort, and where the
                # k candidate blocks are a small part of d without
                # looking at all of d either (``select_form``); the
                # squares are taken of the gathered candidates, not
                # written out over d
                idx = threshold_topk_indices(est, k,
                                             key=jax.lax.square)
            else:
                _, idx = jax.lax.top_k(jax.lax.square(est), k)
            oob = None
        vals = est[idx]
        if self.approx_topk and big_d:
            vals = jnp.where(oob, 0.0, vals)
        if not with_dense:
            # support-only form: at large d the dense (d,) scatter is
            # the single most expensive piece of the server step —
            # callers on the sparse path never need it
            assert with_support
            return None, idx, vals
        # scatter-ADD, not set: the big-d approx guard above can leave
        # duplicate (d-1) slots whose vals are forced 0 — under .set a
        # legitimate (d-1, est[d-1]) pick could lose to a forced-0
        # duplicate (order-nondeterministic); under .add over a zero
        # init the zeros are inert and unique-index inputs are
        # unchanged. selection_may_duplicate (ops/topk.py) is the one
        # shared predicate for when duplicates are possible.
        from commefficient_tpu.ops.topk import selection_may_duplicate
        dense = jnp.zeros(self.d, jnp.float32).at[idx].add(
            vals, mode="promise_in_bounds",
            unique_indices=not selection_may_duplicate(
                self.d, self.approx_topk))
        if with_support:
            return dense, idx, vals
        return dense

    def unsketch_dense_mask(self, table: jax.Array, k: int):
        """Exact dense unsketch without the top-k sort: the
        threshold-select mask (ops/topk.py: an 8-pass nibble search,
        then the take-mask kernel) keeps the k largest-magnitude estimates via a ``where`` — no
        sort, no index gather/scatter. Returns ``(dense, mask)``;
        use where the consumer never needs the (k,) index form (the
        dense-regime server step; download accounting takes the
        bit-packed mask). Selection set is identical to ``unsketch``'s
        exact path (lowest-index tie-break, tested)."""
        from commefficient_tpu.ops.topk import threshold_topk_mask_1d
        k = min(k, self.d)
        with jax.named_scope("estimates"):
            est = self.estimates(table)
        with jax.named_scope("select"):
            mask = threshold_topk_mask_1d(jax.lax.square(est), k)
            return jnp.where(mask, est, 0.0), mask

    def prefer_threshold_unsketch(self, k: int) -> bool:
        """Dense-regime exact recovery via the threshold mask: wins
        once d is large enough that lax.top_k lowers to an expensive
        full sort (~13 ms extra per round at ResNet9's d=6.6M,
        rounds 1-5; the mask's `select` scope reads 1.18 ms there,
        PERF.md section 5). Approximate recovery (approx_topk) stays
        on the index path — approx_max_k is cheaper than the nibble
        search's count passes; and the sparse-resketch regime needs
        indices anyway."""
        from commefficient_tpu.ops.topk import use_threshold_select
        return (use_threshold_select(k, self.d, self.approx_topk)
                and not self.prefer_sparse_resketch(k))

    def select_form(self, k: int):
        """``(form, candidates)`` of the selection a server round's
        recovery of ``k`` coordinates makes over this sketch's
        estimates, from the shapes alone: ``("blocked", k·block)``
        where ``unsketch`` reaches ``threshold_topk_indices`` and the
        two-level form engages there (ops/topk.py ``select_block``, on
        the padded length the selection sees), else ``("flat", d)``:
        the threshold mask, ``lax.top_k`` or ``approx_max_k`` over
        every estimate. The round records' ``select.*`` counters."""
        from commefficient_tpu.ops.topk import (select_block,
                                                use_threshold_select)
        k = min(k, self.d)
        if (not self.approx_topk
                and use_threshold_select(k, self.d, False)
                and not self.prefer_threshold_unsketch(k)):
            block = select_block(self._padded_d, k)
            if block:
                return "blocked", k * block
        return "flat", self.d

    @property
    def rot_form(self) -> str:
        """``"addressed"`` or ``"rolled"``: the form the Pallas kernels
        of this geometry apply a rotation in, from the shapes alone
        (ops/sketch_pallas.py ``rotation_form``). The round records'
        ``sketch.rot_*`` counters."""
        from commefficient_tpu.ops.sketch_pallas import rotation_form
        return rotation_form(self.c, self.r, self.rot_lanes)

    def sketch_sparse(self, idx: jax.Array,
                      vals: jax.Array) -> jax.Array:
        """(n,) int32 indices + (n,) values -> (r, c) table, identical
        (to summation order) to ``sketch`` of the dense scatter of
        ``vals`` at ``idx``. Costs O(r*n) scatter-adds instead of O(d)
        kernel work — the winning form for re-sketching a k-sparse
        recovered update once d >> r*k (see ``prefer_sparse_resketch``;
        at GPT-2's d=124M the dense kernel costs ~8 ms while 5x50k
        scatter-adds cost ~1.5 ms)."""
        buckets, signs = self.hashes(idx.astype(jnp.int32))
        rows = jnp.broadcast_to(
            jnp.arange(self.r, dtype=jnp.int32)[:, None], buckets.shape)
        contrib = signs * vals[None, :].astype(jnp.float32)
        return jnp.zeros((self.r, self.c), jnp.float32) \
            .at[rows, buckets.astype(jnp.int32)] \
            .add(contrib, mode="promise_in_bounds")

    def prefer_sparse_resketch(self, k: int) -> bool:
        """Cost model from measured v5e numbers: the dense kernel runs
        ~14-15M coords/ms; TPU scatter-add ~6 us per 1k elements. The
        sparse path wins when d/14e6 > r*k*6e-6, i.e. d > ~90*r*k
        (GPT-2 124M with r=5, k=50k: yes; ResNet9 6.6M: no)."""
        return self.d > 90 * self.r * k

    # --- norms -----------------------------------------------------------

    @staticmethod
    def l2estimate(table: jax.Array) -> jax.Array:
        """sqrt(median over rows of per-row sum of squares) — the sketch
        estimate of ‖v‖₂ (reference utils.py:309 via CSVec.l2estimate)."""
        return jnp.sqrt(jnp.median(jnp.sum(jax.lax.square(table), axis=1)))

    def recovery_error(self, table: jax.Array, dense: jax.Array,
                       k: int) -> jax.Array:
        """Relative top-k recovery error ‖unsketch(S(v)) − v‖ / ‖v‖
        of this operator against the TRUE dense vector — the ground-
        truth fidelity probe (--probe_full). 0 would be lossless; the
        top-k floor is sqrt(1 − ‖v_topk‖²/‖v‖²) for an exact sketch,
        so values near 1 mean the recovered heavy hitters carry almost
        none of the vector's mass. A zero vector reports 0."""
        assert dense.shape == (self.d,), dense.shape
        est = self.unsketch(table, k)
        num = jnp.linalg.norm(est - dense.astype(jnp.float32))
        den = jnp.linalg.norm(dense.astype(jnp.float32))
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)


def clip_record(record: jax.Array, clip: float, *, is_sketch: bool) -> jax.Array:
    """Reference ``clip_grad`` (utils.py:305-313): L2-clip a dense
    vector, or a sketch table by its l2estimate. Only ever shrinks."""
    if not is_sketch:
        from commefficient_tpu.ops.vec import clip_by_l2
        return clip_by_l2(record, clip)
    norm = CountSketch.l2estimate(record)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return record * scale

"""The expert-parallel layer on one chip, for every model that has one:
routing over all the router's experts (sigmoid scores, or the softmax
of the chosen logits), the dispatch order of the assignments that land
on the experts held here, their ragged products, and the combine under
the gates.

Shared by ``models/joyai.py`` (gated SiLU experts on the hidden state,
8 sigmoid picks of 256), ``models/nemotron_h.py`` (squared-ReLU experts
in a latent, 22 sigmoid picks of 512) and ``models/smallthinker.py``
(gated ReLU experts, 6 softmax picks of 64, the router read before
attention). What is TPU-shaped:

- The layer is told which experts it holds (``E`` of them from
  ``expert_offset``), routes over all the router's outputs in float32,
  and computes its own experts' part: the (token, expert) assignments
  that land here are sorted by expert and taken a buffer of ``tokens``
  rows at a time (static: a round program has no dynamic shape): a
  gather, the ragged products (``jax.lax.ragged_dot``: XLA's tiled TPU
  kernel, whose cost follows the rows, not rows x experts) and a
  scatter-add under the gates. A routing that sends the average token
  to more than one held expert takes a further pass (a loop of dynamic
  length), so no assignment is ever left out (``moe.dropped`` counts
  what the passes did not reach: 0). No exchange, and nothing stands in
  for the absent chips.
- **Under the clients ``vmap``** (``core/rounds.py make_local_loss``)
  ``ragged_dot`` has no batching rule for an unbatched weight, and a
  batched one would copy the experts per client: ``routed_experts``
  carries its own VJP and runs once per client
  (``jax.custom_batching``), which is also what lets its loop have a
  length of its own per client; the weights stay shared. Where the
  ``vmap`` says that its clients' losses are summed before they are
  differentiated (``parallel/mesh.py SHARED_CLIENTS``: the fused
  round) their gradient is summed over the clients inside the
  backward's own loop (a stack of per-client expert gradients, W x 88
  MB a weight at Nemotron-3-Super's widths, would outlive its layer:
  PERF.md section 6, PR 32); under any other ``vmap`` (per-client
  gradients of shared weights, ``core/rounds.py client_round``) each
  client gets its own.

Scopes (``PERF.md`` section 3): ``moe_route`` (scores, top-k, dispatch
order and the gathers), ``moe_experts`` (the ragged products),
``moe_combine`` (gates, scatter-adds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap, sequential_vmap

from commefficient_tpu.parallel.mesh import SHARED_CLIENTS, axis_bound

#: a client's routing counts, which a causal LM's loss returns beside
#: the loss and ``train/gpt2_train.py`` turns into the round's ``moe.*``
#: counters: (token, expert) assignments to experts held here over all
#: expert layers; the fullest (layer, expert)'s; the mean over (layer,
#: expert); assignments no pass of ``routed_experts`` reached (0)
MOE_STATS = ("assignments_here", "load_max", "load_mean", "dropped")

#: how ``FedModel`` folds the clients' ``MOE_STATS`` (in that order)
#: into the round record's ``moe.*`` counters
MOE_COUNTERS = (("moe.assignments_here", np.sum), ("moe.load_max", np.max),
                ("moe.load_mean", np.mean), ("moe.dropped", np.sum))


# --- the held experts' products -------------------------------------------

def _ragged(x, w, sizes):
    """(M, K) rows sorted by group, (G, K, N) float32, (G,) -> (M, N)
    float32, computed in ``x``'s dtype. Rows past the groups are zero on
    the CPU and whatever the buffer held on the TPU: mask them."""
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                              preferred_element_type=jnp.float32)


def _ragged_outer(x, dy, sizes):
    """(M, K), (M, N), (G,) -> (G, K, N) float32: each group's x^T dy."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        x, dy, sizes, dims, preferred_element_type=jnp.float32)


def _pass(p, x, token, gate, load):
    """Pass ``p`` of the sorted assignments: rows [pN, (p+1)N). Returns
    the rows' tokens, gates, validity, each expert's share of the rows
    and the gathered inputs."""
    N = x.shape[0]
    with jax.named_scope("moe_route"):
        lo = p * N
        rows = jax.lax.dynamic_slice_in_dim(token, lo, N)
        g = jax.lax.dynamic_slice_in_dim(gate, lo, N)
        ends = jnp.cumsum(load)
        sizes = (jnp.clip(ends, lo, lo + N)
                 - jnp.clip(ends - load, lo, lo + N)).astype(jnp.int32)
        valid = ((lo + jnp.arange(N)) < ends[-1])[:, None]
        xg = x[rows]
    return rows, g, valid, sizes, xg


# An expert form: ``ffn(xg, valid, sizes, *w) -> (saved, h, o)`` (what
# the backward needs of the pre-activations, the last product's input,
# the experts' outputs) and ``back(xg, saved, h, do, valid, sizes, *w)
# -> (dxg, dw...)``. The TPU kernel leaves the rows past the groups
# unwritten: every ragged product is masked before anything reads it.

def _swiglu_ffn(xg, valid, sizes, wg, wu, wd):
    a = jnp.where(valid, _ragged(xg, wg, sizes), 0.0)
    b = jnp.where(valid, _ragged(xg, wu, sizes), 0.0)
    h = (jax.nn.silu(a) * b).astype(xg.dtype)
    return (a, b), h, jnp.where(valid, _ragged(h, wd, sizes), 0.0)


def _swiglu_back(xg, saved, h, do, valid, sizes, wg, wu, wd):
    a, b = saved
    dt = xg.dtype
    dh = jnp.where(
        valid, _ragged(do, jnp.swapaxes(wd, 1, 2), sizes), 0.0)
    sa = jax.nn.sigmoid(a)
    da = (dh * b * sa * (1.0 + a * (1.0 - sa))).astype(dt)
    db = (dh * a * sa).astype(dt)
    dxg = jnp.where(
        valid, _ragged(da, jnp.swapaxes(wg, 1, 2), sizes)
        + _ragged(db, jnp.swapaxes(wu, 1, 2), sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(xg, db, sizes),
                 _ragged_outer(h, do, sizes))


def _relu2_ffn(xg, valid, sizes, w1, w2):
    a = jnp.where(valid, _ragged(xg, w1, sizes), 0.0)
    h = jnp.square(jax.nn.relu(a)).astype(xg.dtype)
    return (a,), h, jnp.where(valid, _ragged(h, w2, sizes), 0.0)


def _relu2_back(xg, saved, h, do, valid, sizes, w1, w2):
    (a,) = saved
    dh = jnp.where(
        valid, _ragged(do, jnp.swapaxes(w2, 1, 2), sizes), 0.0)
    da = (dh * 2.0 * jax.nn.relu(a)).astype(xg.dtype)
    dxg = jnp.where(
        valid, _ragged(da, jnp.swapaxes(w1, 1, 2), sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(h, do, sizes))


def _reglu_ffn(xg, valid, sizes, wg, wu, wd):
    a = jnp.where(valid, _ragged(xg, wg, sizes), 0.0)
    b = jnp.where(valid, _ragged(xg, wu, sizes), 0.0)
    h = (jax.nn.relu(a) * b).astype(xg.dtype)
    return (a, b), h, jnp.where(valid, _ragged(h, wd, sizes), 0.0)


def _reglu_back(xg, saved, h, do, valid, sizes, wg, wu, wd):
    a, b = saved
    dt = xg.dtype
    dh = jnp.where(
        valid, _ragged(do, jnp.swapaxes(wd, 1, 2), sizes), 0.0)
    da = jnp.where(a > 0.0, dh * b, 0.0).astype(dt)
    db = (dh * jax.nn.relu(a)).astype(dt)
    dxg = jnp.where(
        valid, _ragged(da, jnp.swapaxes(wg, 1, 2), sizes)
        + _ragged(db, jnp.swapaxes(wu, 1, 2), sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(xg, db, sizes),
                 _ragged_outer(h, do, sizes))


#: expert form -> (forward, backward); "swiglu": (gate, up, down) with
#: down(silu(gate x) * up x); "relu2": (w1, w2) with w2 relu(w1 x)^2;
#: "reglu": (gate, up, down) with down(relu(gate x) * up x)
FORMS = {"swiglu": (_swiglu_ffn, _swiglu_back),
         "relu2": (_relu2_ffn, _relu2_back),
         "reglu": (_reglu_ffn, _reglu_back)}


def _zeros(shape, load):
    """float32 zeros to carry through a pass loop: derived from the
    client's ``load`` and not ``jnp.zeros``, so that inside a
    ``shard_map`` over clients they vary over the mesh axis as the
    loop's results do (the scan carry-type check; cf. models/gpt2.py
    ``lm_nll_sums_chunked``)."""
    return jnp.zeros(shape, jnp.float32) \
        + (load[0] * 0).astype(jnp.float32)


def passes(load, N):
    """Buffers of ``N`` rows the held assignments fill."""
    return (jnp.sum(load) + N - 1) // N


@functools.lru_cache(maxsize=None)
def _routed(form, pooled):
    """``routed_experts`` of one expert form: the forward and backward
    pass loops, each run once per client, under one custom VJP.
    ``pooled``: a ``vmap`` over it sums the clients' losses before it
    differentiates them, so the shared weights' gradient is summed over
    the clients as they are taken; else each client's is its own."""
    ffn, back = FORMS[form]

    @sequential_vmap
    def fwd(x, token, gate, load, *w):
        N, C = x.shape

        def body(p, y):
            rows, g, valid, sizes, xg = _pass(p, x, token, gate, load)
            with jax.named_scope("moe_experts"):
                o = ffn(xg, valid, sizes, *w)[2]
            with jax.named_scope("moe_combine"):
                return y.at[rows].add(o * g[:, None])

        return jax.lax.fori_loop(0, passes(load, N), body,
                                 _zeros((N, C), load))

    def bwd_one(x, token, gate, load, w, dy, dw):
        """One client's backward pass loop: ``(dx, dgate, dw + this
        client's weight gradients)``."""
        N, C = x.shape
        dt = x.dtype

        def body(p, carry):
            dx, dgate, *dw = carry
            rows, g, valid, sizes, xg = _pass(p, x, token, gate, load)
            with jax.named_scope("moe_experts"):
                saved, h, o = ffn(xg, valid, sizes, *w)
            with jax.named_scope("moe_combine"):
                dyg = jnp.where(valid, dy[rows], 0.0)
                dg = jnp.sum(dyg * o, axis=-1)
                do = (dyg * g[:, None]).astype(dt)
            with jax.named_scope("moe_experts"):
                dxg, dws = back(xg, saved, h, do, valid, sizes, *w)
                dw = [acc + one for acc, one in zip(dw, dws)]
            with jax.named_scope("moe_route"):
                dx = dx.at[rows].add(dxg)
                dgate = jax.lax.dynamic_update_slice_in_dim(
                    dgate, dg, p * N, axis=0)
            return (dx, dgate, *dw)

        dx, dgate, *dw = jax.lax.fori_loop(
            0, passes(load, N), body,
            (_zeros(x.shape, load), _zeros(gate.shape, load), *dw))
        return (dx.astype(dt), dgate, *dw)

    def bwd_alone(x, token, gate, load, *rest):
        *w, dy = rest
        return bwd_one(x, token, gate, load, w, dy,
                       [_zeros(a.shape, load) for a in w])

    # not pooled: a client at a time, every result batched, the weight
    # gradients among them (W x the experts, which only a path that
    # wants per-client gradients pays)
    bwd = custom_vmap(bwd_alone) if pooled else sequential_vmap(bwd_alone)

    def bwd_over_clients(axis_size, in_batched, x, token, gate, load,
                         *rest):
        """The clients one after the other, as ``sequential_vmap`` would
        take them, but with the weight gradients summed as they go: the
        weights are the batch's own (unbatched), so what the
        transformation asks of their cotangent is its sum over the
        batch, and a stack of per-client (E, C, F) gradients (W times
        the experts, a layer) is never made."""
        *w, dy = rest
        if any(in_batched[4:-1]):
            raise NotImplementedError(
                "routed_experts under vmap shares the experts' weights")
        per_client = [a if batched else jnp.broadcast_to(
            a, (axis_size,) + a.shape) for a, batched in zip(
            (x, token, gate, load, dy),
            tuple(in_batched[:4]) + (in_batched[-1],))]

        def step(dw, c):
            dx, dgate, *dw = bwd_one(*c[:4], w, c[4], dw)
            return dw, (dx, dgate)

        dw, (dx, dgate) = jax.lax.scan(
            step, [_zeros(a.shape, per_client[3][0]) for a in w],
            tuple(per_client))
        return (dx, dgate, *dw), (True, True) + (False,) * len(w)

    if pooled:
        bwd.def_vmap(bwd_over_clients)

    @jax.custom_vjp
    def routed(x, token, gate, load, *w):
        return fwd(x, token, gate, load, *w)

    def vjp_fwd(*args):
        return fwd(*args), args

    def vjp_bwd(res, dy):
        dx, dgate, *dw = bwd(*res, dy)
        return (dx, None, dgate, None, *dw)

    routed.defvjp(vjp_fwd, vjp_bwd)
    return routed


def routed_experts(x, token, gate, load, weights, form="swiglu"):
    """What the experts held here add to each token, float32 (N, C).

    ``x`` (N, C) in the compute dtype; ``token`` / ``gate`` (A_max,):
    the token and the gate of every (token, expert) assignment, those
    to held experts first and sorted by expert; ``load`` (E,): how many
    each held expert has; ``weights``: the held experts' float32 stacks
    in the order their ``form`` of ``FORMS`` takes them ("swiglu":
    (E, C, F), (E, C, F), (E, F, C); "reglu": the same three; "relu2":
    (E, C, F), (E, F, C)).
    The assignments are taken N rows a pass, as many passes as the load
    needs (one, unless the average token picks more than one expert
    held here), each pass the form's ragged products: every assignment
    is computed whatever the routing, at a cost that follows the load.
    Carries its own VJP (no reverse mode runs through a loop of dynamic
    length) and recomputes the pass's activations there. Under ``vmap``
    it runs once per batch element with the weights shared (they may
    not be batched); inside a ``vmap`` named ``SHARED_CLIENTS`` (the
    losses are summed before they are differentiated) their gradient is
    summed over the batch in float32 as the elements are taken, inside
    any other each element has its own."""
    return _routed(form, axis_bound(SHARED_CLIENTS))(
        x, token, gate, load, *weights)


# --- routing and dispatch ---------------------------------------------------

def route(x, router, bias, k, scaling, norm_topk_prob=True,
          scoring="sigmoid"):
    """(N, C) tokens -> ((N, k) expert ids among all the router's
    outputs, (N, k) gates), float32. ``scoring`` "sigmoid": sigmoid
    scores, the k largest of score + bias (the bias takes no gradient),
    the chosen scores normalised to ``scaling``. "softmax": the k
    largest logits (no bias), the gates ``scaling`` x the softmax over
    the chosen logits, which is the softmax over all renormalised over
    the chosen (without ``norm_topk_prob``: not renormalised)."""
    r = jnp.dot(x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        if bias is not None:
            raise ValueError("softmax routing takes no bias")
        chosen, top = jax.lax.top_k(r, k)
        if norm_topk_prob:
            g = jax.nn.softmax(chosen, -1)
        else:
            g = jnp.take_along_axis(jax.nn.softmax(r, -1), top, axis=-1)
        return top, scaling * g
    if scoring != "sigmoid":
        raise ValueError(f"no router scoring {scoring!r}")
    s = jax.nn.sigmoid(r)
    _, top = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    sel = jnp.take_along_axis(s, top, axis=-1)
    if norm_topk_prob:
        sel = sel / (jnp.sum(sel, -1, keepdims=True) + 1e-20)
    return top, scaling * sel


def dispatch(top, g, expert_offset, E):
    """Every assignment of ``top`` / ``g`` (N, k), those to the ``E``
    experts held here (ids from ``expert_offset``) first and sorted by
    expert: ((N*k,) tokens, (N*k,) gates, (E,) int32 load)."""
    k = top.shape[-1]
    local = (top - expert_offset).reshape(-1)             # (N*k,)
    key = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum(jax.nn.one_hot(key, E + 1, dtype=jnp.int32),
                   axis=0)[:E]                            # (E,)
    return order // k, g.reshape(-1)[order], load


def layer_stats(load, N):
    """float32 (assignments here, the fullest expert's, dropped) of one
    expert layer of one client."""
    total = jnp.sum(load)
    done = jnp.minimum(total, passes(load, N) * N)
    return jnp.stack([total, jnp.max(load),
                      total - done]).astype(jnp.float32)


def fold_stats(total, layer):
    """Sum assignments and drops over layers, keep the fullest expert."""
    return jnp.stack([total[0] + layer[0], jnp.maximum(total[1], layer[1]),
                      total[2] + layer[2]])

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
  that land here are sorted by expert and taken a buffer of rows at a
  time (static: a round program has no dynamic shape): a gather, the
  ragged products (``jax.lax.ragged_dot``: XLA's tiled TPU kernel,
  whose cost follows the rows, not rows x experts) and a scatter-add
  under the gates. A routing that fills more than the buffer takes a
  further pass (a loop of dynamic length), so no assignment is ever
  left out (``moe.dropped`` counts what the passes did not reach: 0).
  No exchange, and nothing stands in for the absent chips.
- **Under the clients ``vmap``** (``core/rounds.py make_local_loss``)
  ``ragged_dot`` has no batching rule for an unbatched weight, and a
  batched one would copy the experts per client: ``routed_experts``
  carries its own VJP, and its forward and backward each carry their
  own batching rule (``jax.custom_batching``); the weights stay
  shared. **Once per client**, always: ``route`` and ``dispatch`` (the
  scores, the top-k, the sort that puts a client's held assignments
  first and in expert order). **Once per round and layer**, where the
  ``vmap`` says that its clients' losses are summed before they are
  differentiated (``parallel/mesh.py SHARED_CLIENTS``: the fused
  round): the pass loop. To the experts W clients of N tokens are W x N
  tokens: the clients' sorted lists are read as one, "expert by expert,
  within an expert client by client" (``_pool_order``: three cumulative
  sums of the (W, E) loads and a look-up in a table of E x W entries, no
  new sort), a buffer of ``pool_rows`` rows a pass, sized from the load
  the shapes predict and not from N; each weight is cast to the rows'
  dtype (and, backward, transposed) once a call, and the weights'
  gradient comes out of the grouped products already summed over the
  clients, in float32 (``moe.pool_rows`` / ``moe.pool_passes`` on the
  round record say so; PERF.md section 6, PR 45). Under any other
  ``vmap`` (per-client gradients of shared weights, ``core/rounds.py
  client_round``) and alone, the same loop runs once per client, N rows
  a pass, and each client gets its own weight gradient.

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
#: expert); assignments no pass of ``routed_experts`` reached (0); the
#: rows of the buffer that took every client's assignments at once (0
#: where each client had its own loop); the most passes an expert layer
#: took to get through them (1 unless the routing is skewed)
MOE_STATS = ("assignments_here", "load_max", "load_mean", "dropped",
             "pool_rows", "pool_passes")

#: how ``FedModel`` folds the clients' ``MOE_STATS`` (in that order)
#: into the round record's ``moe.*`` counters
MOE_COUNTERS = (("moe.assignments_here", np.sum), ("moe.load_max", np.max),
                ("moe.load_mean", np.mean), ("moe.dropped", np.sum),
                ("moe.pool_rows", np.max), ("moe.pool_passes", np.max))


# --- the held experts' products -------------------------------------------

def _ragged(x, w, sizes):
    """(M, K) rows sorted by group, (G, K, N) in ``x``'s dtype, (G,) ->
    (M, N) float32. Rows past the groups are zero on the CPU and
    whatever the buffer held on the TPU: mask them."""
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


def _ragged_outer(x, dy, sizes):
    """(M, K), (M, N), (G,) -> (G, K, N) float32: each group's x^T dy."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        x, dy, sizes, dims, preferred_element_type=jnp.float32)


# An expert form: ``ffn(xg, valid, sizes, *w) -> (saved, h, o)`` (what
# the backward needs of the pre-activations, the last product's input,
# the experts' outputs) and ``back(xg, saved, h, do, valid, sizes, *wt)
# -> (dxg, dw...)``. ``w`` are the stacks in the rows' dtype and ``wt``
# their transposes (E, out, in): the caller casts and transposes once,
# outside its pass loop. The TPU kernel leaves the rows past the groups
# unwritten: every ragged product is masked before anything reads it.

def _swiglu_ffn(xg, valid, sizes, wg, wu, wd):
    a = jnp.where(valid, _ragged(xg, wg, sizes), 0.0)
    b = jnp.where(valid, _ragged(xg, wu, sizes), 0.0)
    h = (jax.nn.silu(a) * b).astype(xg.dtype)
    return (a, b), h, jnp.where(valid, _ragged(h, wd, sizes), 0.0)


def _swiglu_back(xg, saved, h, do, valid, sizes, wgt, wut, wdt):
    a, b = saved
    dt = xg.dtype
    dh = jnp.where(valid, _ragged(do, wdt, sizes), 0.0)
    sa = jax.nn.sigmoid(a)
    da = (dh * b * sa * (1.0 + a * (1.0 - sa))).astype(dt)
    db = (dh * a * sa).astype(dt)
    dxg = jnp.where(
        valid, _ragged(da, wgt, sizes) + _ragged(db, wut, sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(xg, db, sizes),
                 _ragged_outer(h, do, sizes))


def _relu2_ffn(xg, valid, sizes, w1, w2):
    a = jnp.where(valid, _ragged(xg, w1, sizes), 0.0)
    h = jnp.square(jax.nn.relu(a)).astype(xg.dtype)
    return (a,), h, jnp.where(valid, _ragged(h, w2, sizes), 0.0)


def _relu2_back(xg, saved, h, do, valid, sizes, w1t, w2t):
    (a,) = saved
    dh = jnp.where(valid, _ragged(do, w2t, sizes), 0.0)
    da = (dh * 2.0 * jax.nn.relu(a)).astype(xg.dtype)
    dxg = jnp.where(valid, _ragged(da, w1t, sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(h, do, sizes))


def _reglu_ffn(xg, valid, sizes, wg, wu, wd):
    a = jnp.where(valid, _ragged(xg, wg, sizes), 0.0)
    b = jnp.where(valid, _ragged(xg, wu, sizes), 0.0)
    h = (jax.nn.relu(a) * b).astype(xg.dtype)
    return (a, b), h, jnp.where(valid, _ragged(h, wd, sizes), 0.0)


def _reglu_back(xg, saved, h, do, valid, sizes, wgt, wut, wdt):
    a, b = saved
    dt = xg.dtype
    dh = jnp.where(valid, _ragged(do, wdt, sizes), 0.0)
    da = jnp.where(a > 0.0, dh * b, 0.0).astype(dt)
    db = (dh * jax.nn.relu(a)).astype(dt)
    dxg = jnp.where(
        valid, _ragged(da, wgt, sizes) + _ragged(db, wut, sizes), 0.0)
    return dxg, (_ragged_outer(xg, da, sizes), _ragged_outer(xg, db, sizes),
                 _ragged_outer(h, do, sizes))


#: expert form -> (forward, backward); "swiglu": (gate, up, down) with
#: down(silu(gate x) * up x); "relu2": (w1, w2) with w2 relu(w1 x)^2;
#: "reglu": (gate, up, down) with down(relu(gate x) * up x)
FORMS = {"swiglu": (_swiglu_ffn, _swiglu_back),
         "relu2": (_relu2_ffn, _relu2_back),
         "reglu": (_reglu_ffn, _reglu_back)}


# --- the pass loops: one client's rows, or every client's in one pool -----

#: the pool's buffer over the load its shapes predict (W x A x the held
#: share of the router's experts), and the rows it is rounded up to.
#: ``scripts/moe_probe.py`` on one v5e chip (PR 45; PERF.md section 6),
#: one layer forward + backward, ms at a buffer of 1 / 1.25 / 1.5 / 2 x
#: the predicted load and at W x N, against the per-client loops' 21.9 /
#: 19.5 / 21.1: JoyAI 7.7 / 8.2 / 8.8 / 10.4 / 14.7 (8,183 held of 8,192
#: predicted), Nemotron 8.7 (two passes: 5,841 held of 5,632 predicted)
#: / 5.7 / 6.7 / 7.1 / 7.2, SmallThinker 16.2 / 17.0 / 18.2 / 19.7 /
#: 17.4. The products' time follows the rows filled, the gathers' and
#: scatter-adds' the buffer: a quarter of slack costs 0.5-0.8 ms a
#: layer, and an even router's draw already passes 1 x
POOL_SLACK = 1.25
POOL_ALIGN = 256


def pool_rows(W, assignments, held_share):
    """Rows M of the buffer that takes the pooled held assignments of
    ``W`` clients with ``assignments`` (token, expert) picks each, of
    which ``held_share`` land here if the router spreads them evenly:
    static, from the shapes alone. Never more than every pick."""
    want = int(np.ceil(POOL_SLACK * W * assignments * held_share))
    return min(W * assignments, -(-want // POOL_ALIGN) * POOL_ALIGN)


def passes(total, rows):
    """Buffers of ``rows`` rows that ``total`` assignments fill."""
    return (total + rows - 1) // rows


def _zeros(shape, load):
    """float32 zeros to carry through a pass loop: derived from the
    clients' ``load`` and not ``jnp.zeros``, so that inside a
    ``shard_map`` over clients they vary over the mesh axis as the
    loop's results do (the scan carry-type check; cf. models/gpt2.py
    ``lm_nll_sums_chunked``)."""
    return jnp.zeros(shape, jnp.float32) \
        + (load.reshape(-1)[0] * 0).astype(jnp.float32)


def _pool_order(load, A, N):
    """The pooled order of W clients' held assignments, "expert by
    expert, within an expert client by client", from their (W, E)
    loads alone: each client's list is already sorted by expert, so a
    pooled position's source follows from the (expert, client) group
    it falls in. Returns the groups' pooled ends (E*W,), what to add to
    a pooled position in a group to get its place in the flattened
    (W*A,) lists, the group's offset into the flattened (W*N,) tokens,
    and the experts' pooled starts and ends (E,)."""
    W, E = load.shape
    groups = load.T.reshape(-1)                   # (E*W,): expert-major
    ends = jnp.cumsum(groups)
    own = (jnp.cumsum(load, axis=1) - load).T.reshape(-1)
    client = jnp.tile(jnp.arange(W, dtype=load.dtype), E)
    held = jnp.sum(load, axis=0)
    expert_ends = jnp.cumsum(held)
    return (ends, client * A + own - (ends - groups), client * N,
            expert_ends - held, expert_ends)


def _pass(p, M, order, x, token, gate):
    """Pass ``p`` of the pooled order: positions [pM, (p+1)M). Returns
    the rows' (flattened) tokens, their places in the flattened lists
    (past the lists' end where the row is none), gates, validity, each
    expert's share of the rows and the gathered inputs."""
    ends, to_list, to_token, starts, expert_ends = order
    with jax.named_scope("moe_route"):
        lo = p * M
        j = lo + jnp.arange(M, dtype=ends.dtype)
        valid = j < ends[-1]
        group = jnp.minimum(jnp.searchsorted(
            ends, j, side="right", method="compare_all"),
                            ends.shape[0] - 1)
        place = to_list[group] + j
        src = jnp.where(valid, place, 0)
        rows = token[src] + to_token[group]
        sizes = (jnp.clip(expert_ends, lo, lo + M)
                 - jnp.clip(starts, lo, lo + M)).astype(jnp.int32)
    return (rows, jnp.where(valid, place, token.shape[0]), gate[src],
            valid[:, None], sizes, x[rows])


def _pool_fwd(ffn, M, x, token, gate, load, w):
    """(W, N, C), (W, A), (W, A), (W, E) -> float32 (W, N, C): one pass
    loop over the W clients' pooled held assignments, ``M`` rows a
    pass, the weights cast once."""
    W, N, C = x.shape
    order = _pool_order(load, token.shape[1], N)
    x, token, gate = x.reshape(W * N, C), token.reshape(-1), gate.reshape(-1)
    with jax.named_scope("moe_experts"):
        w = [a.astype(x.dtype) for a in w]

    def body(p, y):
        rows, _, g, valid, sizes, xg = _pass(p, M, order, x, token, gate)
        with jax.named_scope("moe_experts"):
            o = ffn(xg, valid, sizes, *w)[2]
        with jax.named_scope("moe_combine"):
            return y.at[rows].add(o * g[:, None])

    y = jax.lax.fori_loop(0, passes(order[0][-1], M), body,
                          _zeros((W * N, C), load))
    return y.reshape(W, N, C)


def _pool_bwd(ffn, back, M, x, token, gate, load, dy, w):
    """The backward of ``_pool_fwd``: ``(dx (W, N, C), dgate (W, A),
    float32 dw... summed over the W clients)``. Recomputes each pass;
    the weights are cast, and transposed, once."""
    W, N, C = x.shape
    A = token.shape[1]
    dt = x.dtype
    order = _pool_order(load, A, N)
    x, token, gate = x.reshape(W * N, C), token.reshape(-1), gate.reshape(-1)
    dy = dy.reshape(W * N, C)
    shapes = [a.shape for a in w]
    with jax.named_scope("moe_experts"):
        w = [a.astype(dt) for a in w]
        wt = [jnp.swapaxes(a, 1, 2) for a in w]

    def body(p, carry):
        dx, dgate, *dw = carry
        rows, src, g, valid, sizes, xg = _pass(p, M, order, x, token, gate)
        with jax.named_scope("moe_experts"):
            saved, h, o = ffn(xg, valid, sizes, *w)
        with jax.named_scope("moe_combine"):
            dyg = jnp.where(valid, dy[rows], 0.0)
            dg = jnp.sum(dyg * o, axis=-1)
            do = (dyg * g[:, None]).astype(dt)
        with jax.named_scope("moe_experts"):
            dxg, dws = back(xg, saved, h, do, valid, sizes, *wt)
            dw = [acc + one for acc, one in zip(dw, dws)]
        with jax.named_scope("moe_route"):
            dx = dx.at[rows].add(dxg)
            dgate = dgate.at[src].set(dg, mode="drop", unique_indices=True)
        return (dx, dgate, *dw)

    dx, dgate, *dw = jax.lax.fori_loop(
        0, passes(order[0][-1], M), body,
        (_zeros((W * N, C), load), _zeros((W * A,), load),
         *[_zeros(s, load) for s in shapes]))
    return (dx.astype(dt).reshape(W, N, C), dgate.reshape(W, A), *dw)


def _over_clients(run, n, n_out, pooled, held_share):
    """``run(M, *stacked, w) -> results`` as a function of ``n``
    per-client arguments and then the shared weights, with its batching
    rule. ``stacked``: the per-client arguments with a leading clients'
    axis, which the first ``n_out`` results have too. Alone it is a
    pool of one, N rows a pass, and so it is once per element under a
    ``vmap`` that says nothing (every result batched, the weight
    gradients among them: W x the experts, which only a path that wants
    per-client gradients pays); ``pooled``: once for all the ``vmap``'s
    elements, ``pool_rows`` a pass, the other results unbatched (the
    weights are the batch's own, so what the transformation asks of
    their cotangent is its sum over the batch: what the pooled products
    give, and a stack of per-client (E, C, F) gradients is never
    made)."""
    def alone(*args):
        out = run(args[0].shape[0], *[a[None] for a in args[:n]], args[n:])
        return tuple(o[0] for o in out[:n_out]) + tuple(out[n_out:])

    if not pooled:
        return sequential_vmap(alone)
    fn = custom_vmap(alone)

    @fn.def_vmap
    def over_clients(axis_size, in_batched, *args):
        if any(in_batched[n:]):
            raise NotImplementedError(
                "routed_experts under vmap shares the experts' weights")
        stacked = [a if batched else jnp.broadcast_to(
            a, (axis_size,) + a.shape)
            for a, batched in zip(args[:n], in_batched)]
        M = pool_rows(axis_size, stacked[1].shape[1], held_share)
        out = tuple(run(M, *stacked, args[n:]))
        return out, (True,) * n_out + (False,) * (len(out) - n_out)

    return fn


@functools.lru_cache(maxsize=None)
def _routed(form, pooled, held_share):
    """``routed_experts`` of one expert form: the forward and backward
    pass loops under one custom VJP, each with ``_over_clients``'
    batching rule. ``pooled``: a ``vmap`` over it sums the clients'
    losses before it differentiates them."""
    ffn, back = FORMS[form]
    fwd = _over_clients(
        lambda M, *a: (_pool_fwd(ffn, M, *a),), 4, 1, pooled, held_share)
    bwd = _over_clients(
        functools.partial(_pool_bwd, ffn, back), 5, 2, pooled, held_share)

    @jax.custom_vjp
    def routed(*args):
        return fwd(*args)[0]

    def vjp_fwd(*args):
        return fwd(*args)[0], args

    def vjp_bwd(res, dy):
        dx, dgate, *dw = bwd(*res[:4], dy, *res[4:])
        return (dx, None, dgate, None, *dw)

    routed.defvjp(vjp_fwd, vjp_bwd)
    return routed


def routed_experts(x, token, gate, load, weights, form="swiglu",
                   held_share=1.0):
    """What the experts held here add to each token, float32 (N, C).

    ``x`` (N, C) in the compute dtype; ``token`` / ``gate`` (A,): the
    token and the gate of every (token, expert) assignment, those to
    held experts first and sorted by expert; ``load`` (E,): how many
    each held expert has; ``weights``: the held experts' float32 stacks
    in the order their ``form`` of ``FORMS`` takes them ("swiglu":
    (E, C, F), (E, C, F), (E, F, C); "reglu": the same three; "relu2":
    (E, C, F), (E, F, C)); ``held_share``: the held experts over all
    the router's (static: it sizes the pool's buffer, below).

    The held assignments are taken a buffer of rows a pass, as many
    passes as the load needs, each pass a gather, the form's ragged
    products, the masked tail and a scatter-add under the gates: every
    assignment is computed whatever the routing, at a cost that follows
    the load. Carries its own VJP (no reverse mode runs through a loop
    of dynamic length) and recomputes the pass's activations there; the
    weights are cast (and, backward, transposed) once a call.

    Once per client: alone, and under a ``vmap`` that says nothing of
    its losses (N rows a pass; the weights shared, they may not be
    batched; each element has its own weight gradient). Once per round
    and layer: inside a ``vmap`` named ``SHARED_CLIENTS`` (the losses
    are summed before they are differentiated) the W clients' lists are
    read as one, expert by expert and within an expert client by
    client, ``pool_rows(W, A, held_share)`` rows a pass (one pass
    unless the routing is skewed), and the weights' gradient comes out
    of the products summed over the clients, in float32."""
    return _routed(form, axis_bound(SHARED_CLIENTS), float(held_share))(
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


@custom_vmap
def _pooled_total(load):
    """Σ load over the clients of the ``vmap`` this is traced under (as
    the pooled rule of ``routed_experts`` sees them), on each client.
    Not ``psum``: inside a ``shard_map`` its varying-axes check refuses
    a ``psum`` over a ``vmap``'s axis (jax 0.9.0)."""
    return jnp.sum(load)


@_pooled_total.def_vmap
def _pooled_total_over_clients(axis_size, in_batched, load):
    return jnp.broadcast_to(jnp.sum(load), (axis_size,)), True


def layer_stats(load, N, k=1, held_share=1.0):
    """float32 ``LAYER_STATS`` of one expert layer of one client with
    ``N`` tokens of ``k`` picks, as ``routed_experts`` takes it where
    this is traced: the client's assignments here, its fullest
    expert's, what the passes left out, and the pool's buffer and
    passes (0 where each client has a loop of its own, N rows a
    pass)."""
    total = jnp.sum(load)
    if axis_bound(SHARED_CLIENTS):
        W = jax.lax.axis_size(SHARED_CLIENTS)
        rows = pool_rows(W, N * k, held_share)
        pooled = _pooled_total(load)
        n = passes(pooled, rows)
        # the pool's count, a W-th on each client
        left = (pooled - jnp.minimum(pooled, n * rows)) / W
        pool = (rows, n)
    else:
        left = total - jnp.minimum(total, passes(total, N) * N)
        pool = (0, 0)
    return jnp.stack([jnp.float32(v) for v in (
        total, jnp.max(load), left, *pool)])


#: how ``fold_stats`` folds each of ``layer_stats``' entries over layers
LAYER_STATS = (jnp.add, jnp.maximum, jnp.add, jnp.maximum, jnp.maximum)


def no_stats():
    """What a layer with no experts adds to ``fold_stats``."""
    return jnp.zeros((len(LAYER_STATS),), jnp.float32)


def fold_stats(total, layer):
    """Sum assignments and drops over layers, keep the fullest expert,
    the pool's buffer and the most passes a layer took."""
    return jnp.stack([fold(total[i], layer[i])
                      for i, fold in enumerate(LAYER_STATS)])


def client_stats(stats, experts):
    """The folded ``layer_stats`` of a client as ``MOE_STATS`` names
    them; ``experts``: (layer, expert) pairs the mean load is over."""
    return (stats[0], stats[1], stats[0] / max(experts, 1), *stats[2:])

"""Routed experts as a grouped product.

A router picks ``k`` of ``E`` experts a token. The layer that runs here
holds a contiguous range of them (all, on one chip; its share under expert
parallelism) and computes what its own experts add: the (token, expert)
pairs it holds are sorted by expert, the group sizes come from the router,
and each of the three products of an expert runs once over its group
(``jax.lax.ragged_dot``, which the TPU's compiler turns into its own grouped
matmul kernel; in a profile those operations are named ``ragged-dot-*`` and
carry no scope). No product is taken over experts a token did not choose.

**The pair buffer** the gather, the products and the rows they write are as
long as the pairs held here, not as long as every pair of every slot: pairs
whose expert lives elsewhere, and a padding slot's, sort behind the last
group, and the buffer is cut before most of them. A shape is static, so the
buffer takes the shortest of a few static lengths that holds this
dispatch's count of held pairs (``sum(group_sizes)``, known on the device
before any product: :func:`buffer_lengths`, chosen by ``jax.lax.switch``
with no round trip to the host). The last length is every pair, ``N * k``:
no pair is dropped whatever the imbalance. With half the experts held and
72 % of the slots real a third of the pairs are held (PR 30). Where the
held range is a small share of the router's outputs (16 of 768) every pair
is tens of times the pairs held: nothing is then made as long as every
pair (at 6,144 features a gathered operand of that length is 1.2 GB). The
buffer's rows are added to their tokens instead of every pair fetching its
row, and the last length walks the held pairs a short buffer at a time
(:func:`_held_pairs_in_turns`).

**Identity experts** (zero-computation experts): the router's last outputs
may be experts that return their input (:func:`route`'s ``bias`` and
``scale`` are that family's too). A pair that chose one costs no product
and takes no row of the buffer: it sorts behind the held groups with the
pairs of experts held elsewhere, and :func:`identity_part` adds its weight
times the token for every token.

(The Pallas grouped matmul that ships with JAX takes the held range as an
offset, which would spare the remapping, but it wants every expert matrix
copied into its own layout: 6.4 GB here, more than the chip has left:
compiled for v5e, PR 28.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: rows a short buffer's length is rounded up to: whole tiles of the
#: matrix unit's 128 rows, and few distinct lengths among the buckets
ROW_TILE = 256
#: a short buffer's length over the pairs that even routing of a dispatch
#: with every slot real would hold here. The first serves a dispatch the
#: packer left a fifth or more empty; the second every full dispatch, with
#: an eighth of room for a router that leans towards the held range
ROOM = (0.8, 1.125)
#: an expert's activation by its published name (``hidden_act``): the
#: gated unit is ``act(W_gate x) * (W_up x)``, SwiGLU or ReGLU
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(x, router, k: int, renormalise: bool, bias=None,
          scale: float = 1.0, scoring: str = "softmax"):
    """The router's scores over all of its outputs in float32 (``scoring``:
    ``"softmax"`` over the outputs, or ``"sigmoid"`` of each output alone),
    the ``k`` largest a token. x (N, H); router (H, E); ``bias`` (E,)
    float32: a correction added to the scores **for the choice only** (the
    weights are the scores themselves); ``scale``: what the weights are
    multiplied by, after they are renormalised to one where ``renormalise``
    says so. Returns (weights (N, k) float32, experts (N, k) int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring {scoring!r}: \"softmax\" and "
                         f"\"sigmoid\" are what runs")
    if bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def identity_part(x, weights, experts, first: int, valid):
    """What the identity experts add, and how many pairs chose one: the
    router's outputs from ``first`` on return their input, so a token gets
    ``(the sum of its weights there) * x``, with no product and no row of
    the pair buffer. x (N, H); weights, experts (N, k) from :func:`route`;
    valid (N,) False at padding. Returns (y (N, H) float32; float32 (2,):
    [pairs of real tokens that chose an identity expert, all chosen pairs
    of real tokens])."""
    chose = (experts >= first) & valid[:, None]
    weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=1, keepdims=True)
    pairs = jnp.stack([jnp.sum(chose, dtype=jnp.float32),
                       jnp.sum(valid, dtype=jnp.float32) * experts.shape[1]])
    return weight * x.astype(jnp.float32), pairs


def group_by_expert(experts, held: tuple[int, int], valid=None):
    """Sort the (token, expert) pairs of ``experts`` (N, k) by expert.

    ``held`` is the range [lo, hi) of experts that live here; pairs of
    other experts, and of tokens ``valid`` (N,) marks as padding, go
    behind the last group: the held pairs are the first
    ``sum(group_sizes)`` sorted places. Returns (order (N*k,): the pair at
    each sorted place; group_sizes (hi - lo,) int32)."""
    lo, hi = held
    n_held = hi - lo
    local = experts - lo
    here = (local >= 0) & (local < n_held)
    if valid is not None:
        here = here & valid[:, None]
    group = jnp.where(here, local, n_held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    # a comparison a (pair, expert) and a sum: a scatter of N*k ones into
    # the groups costs the TPU 0.34 ms a layer, this nothing (PR 30)
    sizes = jnp.sum(group[:, None] == jnp.arange(n_held, dtype=group.dtype),
                    axis=0, dtype=jnp.int32)
    return order, sizes


def buffer_lengths(n_pairs: int, held_share: float) -> tuple[int, ...]:
    """The static lengths a dispatch's pair buffer may take, ascending: at
    most two short ones, from the pairs a dispatch of ``n_pairs`` (N * k)
    holds here under even routing (``held_share``: the held range over the
    router's outputs), then ``n_pairs`` itself. A short length that would
    not be shorter is left out: a layer that holds every expert keeps one,
    for dispatches that are a fifth padding; a handful of pairs keep
    none."""
    lengths: list[int] = []
    for room in ROOM:
        rows = math.ceil(room * held_share * n_pairs / ROW_TILE) * ROW_TILE
        if rows < n_pairs and rows not in lengths:
            lengths.append(rows)
    return (*lengths, n_pairs)


def buffer_branch(sizes, lengths: tuple[int, ...]):
    """Which of ``lengths`` (ascending) this dispatch's buffer takes: the
    shortest that holds every held pair. int32 scalar, on the device."""
    short = jnp.asarray(lengths[:-1], jnp.int32)
    return jnp.sum(jnp.sum(sizes) > short, dtype=jnp.int32)


def buffer_use(sizes, lengths: tuple[int, ...]):
    """What one execution adds to the buffer's counters, float32 (3,):
    [1, 1 if it took the full length, the rows of the length it took]
    (float32: a sum over days of dispatches rounds, and never wraps)."""
    branch = buffer_branch(sizes, lengths)
    rows = jnp.asarray(lengths, jnp.float32)[branch]
    return jnp.stack([jnp.float32(1.0),
                      (branch == len(lengths) - 1).astype(jnp.float32),
                      rows])


def _products(xs, scale, sizes, w_gate, w_up, w_down, act: str):
    """An expert's three products over its group of the buffer's rows
    ``xs`` (rows, H), grouped by ``sizes``: (rows, H) in ``xs``'s dtype.
    ``scale`` (rows, 1): the router's weight of each row's pair."""
    gate = jax.lax.ragged_dot(xs, w_gate, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, sizes,
                            preferred_element_type=jnp.float32)
    # the router's weight goes in before the last product, where a row is
    # F wide and not H
    hidden = (ACTIVATIONS[act](gate) * up * scale).astype(xs.dtype)
    return jax.lax.ragged_dot(hidden, w_down, sizes,
                              preferred_element_type=xs.dtype)


def _held_pairs(rows: int, x, weights, order, back, sizes, w_gate, w_up,
                w_down, act: str = "silu"):
    """The held experts' part over the first ``rows`` sorted places, which
    hold every held pair: (N, H) float32. ``back`` (N*k,): the sorted
    place of each pair."""
    n, k = weights.shape
    place = order[:rows]
    out = _products(x[place // k], weights.reshape(-1)[place][:, None],
                    sizes, w_gate, w_up, w_down, act)
    # back to the pairs' own order. A pair that is not held sorted behind
    # the last group, perhaps behind the buffer's end, and rows there hold
    # nothing defined: they are selected away, not scaled away. (Adding
    # the buffer's rows to their tokens with a scatter instead costs the
    # TPU as much at the short length, more at the others: PR 30.)
    pairs = out[jnp.minimum(back, rows - 1)].reshape(n, k, -1)
    kept = (back < jnp.sum(sizes)).reshape(n, k, 1)
    return jnp.sum(jnp.where(kept, pairs, 0).astype(jnp.float32), axis=1)


#: where every pair is this many times the shortest buffer or more, the
#: held pairs are sparse among them: :func:`_held_pairs_in_turns` serves
#: every length (16 of 768 outputs held: 55 times; half of 512: 2.5)
SPARSE_FROM = 4


def _held_pairs_in_turns(rows: int, x, weights, order, back, sizes, w_gate,
                         w_up, w_down, act: str = "silu"):
    """The held experts' part where the held pairs are few among all
    (:data:`SPARSE_FROM`), ``rows`` sorted places at a time: as many turns
    as hold them all (one under a short length that holds them; under the
    last length two, where a router leans towards the held range by more
    than the short buffers' room), each a buffer of ``rows`` whose groups
    are the part of every expert's group that lies in it, its rows added to
    their tokens. No array is as long as every pair: :func:`_held_pairs`'
    way back, every pair fetching its row, is 98,304 rows for 2,048 held
    (1.2 GB at 6,144 features, in every branch), and adding 2,048 rows is
    the cheaper the fewer they are."""
    n, k = weights.shape
    total = jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    places = jnp.pad(order, (0, rows))      # a turn's slice never runs off
    scales = weights.reshape(-1)

    def turn(i, y):
        at = i * rows
        place = jax.lax.dynamic_slice_in_dim(places, at, rows)
        inside = jnp.clip(jnp.minimum(ends, at + rows)
                          - jnp.maximum(starts, at), 0).astype(sizes.dtype)
        token = place // k
        out = _products(x[token], scales[place][:, None], inside, w_gate,
                        w_up, w_down, act)
        # behind the last held pair a row holds nothing defined
        held = (at + jnp.arange(rows) < total)[:, None]
        return y.at[token].add(jnp.where(held, out, 0).astype(jnp.float32))

    return jax.lax.fori_loop(0, -(-total // rows), turn,
                             jnp.zeros((n, x.shape[1]), jnp.float32))


def grouped_experts(x, weights, experts, w_gate, w_up, w_down,
                    held: tuple[int, int], valid=None,
                    lengths: tuple[int, ...] | None = None,
                    act: str = "silu"):
    """What the held experts add for every token:
    ``sum_e p_e W_down[e] (act(W_gate[e] x) * (W_up[e] x))`` over the
    token's chosen experts that live here (``act``: a name of
    :data:`ACTIVATIONS`).

    x (N, H); weights, experts (N, k) from :func:`route`; w_gate, w_up
    (hi - lo, H, F); w_down (hi - lo, F, H); ``lengths``: the pair buffer's
    static lengths, ascending, the last N * k (:func:`buffer_lengths`;
    None: that one alone). Returns (y (N, H) float32, group_sizes
    (hi - lo,) int32: tokens each held expert took). Where the held pairs
    are few among all, :func:`_held_pairs_in_turns` computes every
    length."""
    n, k = experts.shape
    lengths = lengths or (n * k,)
    if lengths[-1] != n * k:
        raise ValueError(f"the last buffer length must hold every pair: "
                         f"{lengths} for {n * k}")
    order, sizes = group_by_expert(experts, held, valid)
    if n * k >= SPARSE_FROM * lengths[0]:
        # a short length in one turn; every pair in turns of the longest
        # short one. No pair looks its row up, so no way back is sorted
        back = order
        branches = [functools.partial(_held_pairs_in_turns, rows, act=act)
                    for rows in (*lengths[:-1], lengths[-2])]
    else:
        back = jnp.argsort(order)
        branches = [functools.partial(_held_pairs, rows, act=act)
                    for rows in lengths]
    y = jax.lax.switch(
        buffer_branch(sizes, lengths), branches,
        x, weights, order, back, sizes, w_gate, w_up, w_down)
    return y, sizes

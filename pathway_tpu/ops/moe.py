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
72 % of the slots real a third of the pairs are held (PR 30).

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


def route(x, router, k: int, renormalise: bool):
    """Softmax over all experts in float32, the ``k`` largest a token.
    x (N, H); router (H, E). Returns (weights (N, k) float32, experts
    (N, k) int32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


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


def _held_pairs(rows: int, x, weights, order, back, sizes, w_gate, w_up,
                w_down, act: str = "silu"):
    """The held experts' part over the first ``rows`` sorted places, which
    hold every held pair: (N, H) float32. ``back`` (N*k,): the sorted
    place of each pair."""
    n, k = weights.shape
    place = order[:rows]
    xs = x[place // k]                                      # (rows, H)
    gate = jax.lax.ragged_dot(xs, w_gate, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, sizes,
                            preferred_element_type=jnp.float32)
    # the router's weight goes in before the last product, where a row is
    # F wide and not H
    scale = weights.reshape(-1)[place][:, None]
    hidden = (ACTIVATIONS[act](gate) * up * scale).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, sizes,
                             preferred_element_type=x.dtype)
    # back to the pairs' own order. A pair that is not held sorted behind
    # the last group, perhaps behind the buffer's end, and rows there hold
    # nothing defined: they are selected away, not scaled away. (Adding
    # the buffer's rows to their tokens with a scatter instead costs the
    # TPU as much at the short length, more at the others: PR 30.)
    pairs = out[jnp.minimum(back, rows - 1)].reshape(n, k, -1)
    kept = (back < jnp.sum(sizes)).reshape(n, k, 1)
    return jnp.sum(jnp.where(kept, pairs, 0).astype(jnp.float32), axis=1)


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
    (hi - lo,) int32: tokens each held expert took)."""
    n, k = experts.shape
    lengths = lengths or (n * k,)
    if lengths[-1] != n * k:
        raise ValueError(f"the last buffer length must hold every pair: "
                         f"{lengths} for {n * k}")
    order, sizes = group_by_expert(experts, held, valid)
    y = jax.lax.switch(
        buffer_branch(sizes, lengths),
        [functools.partial(_held_pairs, rows, act=act) for rows in lengths],
        x, weights, order, jnp.argsort(order), sizes, w_gate, w_up, w_down)
    return y, sizes

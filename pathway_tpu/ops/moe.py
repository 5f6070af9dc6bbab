"""Routed experts as a grouped product.

A router picks ``k`` of ``E`` experts a token. The layer that runs here
holds a contiguous range of them (all, on one chip; its share under expert
parallelism) and computes what its own experts add: the (token, expert)
pairs it holds are sorted by expert, the group sizes come from the router,
and each of the three products of an expert runs once over its group
(``jax.lax.ragged_dot``, which the TPU's compiler turns into its own grouped
matmul kernel; in a profile those operations are named ``ragged-dot-*`` and
carry no scope). No pair is dropped whatever the imbalance, since the
sorted buffer has room for every pair, and no product is taken over experts
a token did not choose. Pairs whose expert lives elsewhere sort behind the
last group, where no product reads them. (The Pallas grouped matmul that
ships with JAX takes the held range as an offset, which would spare the
remapping, but it wants every expert matrix copied into its own layout:
6.4 GB here, more than the chip has left: compiled for v5e, PR 28.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
    behind the last group. Returns (order (N*k,): the pair at each sorted
    place; here (N*k,) bool by sorted place; group_sizes (hi - lo,)
    int32)."""
    lo, hi = held
    n_held = hi - lo
    local = experts - lo
    here = (local >= 0) & (local < n_held)
    if valid is not None:
        here = here & valid[:, None]
    group = jnp.where(here, local, n_held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)[:n_held]
    return order, here.reshape(-1)[order], sizes


def grouped_experts(x, weights, experts, w_gate, w_up, w_down,
                    held: tuple[int, int], valid=None):
    """What the held experts add for every token:
    ``sum_e p_e W_down[e] (silu(W_gate[e] x) * (W_up[e] x))`` over the
    token's chosen experts that live here.

    x (N, H); weights, experts (N, k) from :func:`route`; w_gate, w_up
    (hi - lo, H, F); w_down (hi - lo, F, H). Returns (y (N, H) float32,
    group_sizes (hi - lo,) int32: tokens each held expert took)."""
    n, k = experts.shape
    order, here, sizes = group_by_expert(experts, held, valid)
    xs = x[order // k]                                      # (N*k, H)
    gate = jax.lax.ragged_dot(xs, w_gate, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, sizes,
                            preferred_element_type=jnp.float32)
    # the router's weight goes in before the last product, where a row is
    # F wide and not H
    scale = weights.reshape(-1)[order][:, None]
    hidden = (jax.nn.silu(gate) * up * scale).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, sizes,
                             preferred_element_type=x.dtype)
    # back to the pairs' own order; rows behind the last group hold nothing
    # defined, so they are selected away, not scaled away
    back = jnp.argsort(order)
    pairs = out[back].reshape(n, k, -1)
    kept = here[back].reshape(n, k, 1)
    y = jnp.sum(jnp.where(kept, pairs, 0).astype(jnp.float32), axis=1)
    return y, sizes

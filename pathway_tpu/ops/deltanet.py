"""Gated delta rule for linear-attention layers: the causal depthwise
convolution in front of it and the chunked scan.

A layer keeps one state ``S`` (key dim x value dim) a value head and reads
and writes it once a token::

    S <- exp(g_t) S
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

Token by token that is ``T`` dependent steps of rank-one updates. The
chunked form (Yang et al. 2024, "Parallelizing Linear Transformers with the
Delta Rule over Sequence Length"; the gate as in Gated DeltaNet) cuts the
sequence into chunks of 64: inside a chunk the updates' mutual dependence
is one unit-lower-triangular system per head, solved at once, and what is
left is matrix products; across chunks only ``S`` is carried, ``T / 64``
steps. A chunk is a multiple of 16 (the solve's block).

Rows here are ragged-packed: several documents lie back to back in one row
and a document's first token must meet a zero state and a zero convolution
history. ``starts`` marks those tokens. The reset costs no extra pass: a
token's reach inside its chunk is masked to its own document, and the
carried state reaches only the tokens before the chunk's first start.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64


def causal_conv(x, w, pos):
    """Depthwise causal convolution over time with no bias:
    ``y_t = sum_i w[i] x_{t-(K-1)+i}``, a tap reaching before its
    document's first token reads zero.

    x (B, T, C); w (K, C); pos (B, T): a token's position in its document.
    """
    taps, t = w.shape[0], x.shape[1]
    w = w.astype(x.dtype)
    out = x * w[taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        inside = (pos >= back)[..., None]
        out = out + jnp.where(inside, shifted, 0) * w[taps - 1 - back]
    return out


BLOCK = 16


def _solve_unit_lower(a, rhs):
    """``(I + A)^-1 rhs`` for strictly lower triangular ``a`` (..., C, C),
    by blocks of 16 and products alone (the chip's triangular-solve routine
    took 15 % of the ingest step at these shapes: my chip run, PR 28). A
    diagonal block is nilpotent, so its inverse is the finite product
    ``(I - A)(I + A^2)(I + A^4)(I + A^8)``; the blocks below the diagonal
    are then substituted forward, four steps for a chunk of 64."""
    c = a.shape[-1]
    n = c // BLOCK
    lead = a.shape[:-2]
    blocks = a.reshape(lead + (n, BLOCK, n, BLOCK))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(BLOCK, dtype=a.dtype)
    power, inv = -diag, eye - diag
    for _ in range(BLOCK.bit_length() - 2):
        power = power @ power
        inv = inv @ (eye + power)
    rhs = rhs.reshape(lead + (n, BLOCK, rhs.shape[-1]))
    out = []
    for i in range(n):
        b = rhs[..., i, :, :]
        for j in range(i):
            b = b - blocks[..., i, :, j, :] @ out[j]
        out.append(inv[..., i, :, :] @ b)
    return jnp.concatenate(out, axis=-2)


def gated_delta_rule(q, k, v, g, beta, starts, chunk: int = CHUNK):
    # float32 operands would go through the chip's matrix unit as single
    # bfloat16 passes: the state and the chunk's system are kept to float32
    with jax.default_matmul_precision("highest"):
        return _gated_delta_rule(q, k, v, g, beta, starts, chunk)


def _gated_delta_rule(q, k, v, g, beta, starts, chunk: int):
    """The recurrence above for every head of every row, chunked.

    q, k (B, T, H, dk): normalised and scaled by the caller, one key head
    a value head; v (B, T, H, dv); g, beta (B, T, H) float32, ``g <= 0``
    the log of the decay; starts (B, T) bool, True at a document's first
    token (the state it meets is zero). Returns o (B, T, H, dv) float32.
    The state and everything that touches it are float32.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
        starts = jnp.pad(starts, ((0, 0), (0, pad)), constant_values=True)
    n = (t + pad) // chunk
    f32 = jnp.float32

    def chunks(a):          # (B, T, H, ...) -> (B, H, N, C, ...)
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v = (chunks(a.astype(f32)) for a in (q, k, v))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    # a document's number within its row: tokens see each other, and the
    # carried state, only inside one document
    seg = jnp.cumsum(starts.astype(jnp.int32), axis=1).reshape(b, 1, n, chunk)
    before = jnp.pad(seg[..., -1], ((0, 0), (0, 0), (1, 0)),
                     constant_values=-1)[..., :n, None]   # (B, 1, N, 1)
    carry = (seg == before).astype(f32)       # the carried state reaches it
    tail = (seg == seg[..., -1:]).astype(f32)  # it reaches the next chunk
    gc = jnp.cumsum(g, axis=-1)                # (B, H, N, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    reach = lower & (seg[..., :, None] == seg[..., None, :])
    decay = jnp.where(reach, jnp.exp(jnp.minimum(
        gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)   # (B,H,N,C,C)
    kb = k * beta[..., None]
    # (I + A) u = beta v - (beta k exp(gc)) S0, A strictly lower: row i
    # holds what the earlier tokens of the chunk wrote, as k_i reads it
    a = jnp.einsum("bhnid,bhnjd->bhnij", kb, k) * decay
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * (jnp.exp(gc) * carry)[..., None]], -1)
    solved = _solve_unit_lower(jnp.tril(a, -1), rhs)
    value, k_carried = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k) * decay
    q_carried = q * (jnp.exp(gc) * carry)[..., None]
    k_tail = k * (jnp.exp(gc[..., -1:] - gc) * tail)[..., None]
    s_keep = jnp.exp(gc[..., -1]) * carry[..., -1]          # (B, H, N)

    def step(s, xs):
        value_n, k_carried_n, qk_n, q_carried_n, k_tail_n, keep_n = xs
        u = value_n - k_carried_n @ s                       # (B, H, C, dv)
        o = q_carried_n @ s + qk_n @ u
        s = s * keep_n[..., None, None] + jnp.einsum(
            "bhcd,bhce->bhde", k_tail_n, u)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (
        value, k_carried, qk, q_carried, k_tail, s_keep))
    _s, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    o = jnp.moveaxis(o, 0, 2)                               # (B,H,N,C,dv)
    return jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)[:, :t]

"""Gated delta rule for linear-attention layers: the causal depthwise
convolution in front of it and the chunked scan, in two lowerings.

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
steps.

Rows here are ragged-packed: several documents lie back to back in one row
and a document's first token must meet a zero state and a zero convolution
history. ``starts`` marks those tokens. The reset costs no extra pass: a
token's reach inside its chunk is masked to its own document, and the
carried state reaches only the tokens before the chunk's first start.

:func:`gated_delta_rule` is the entry. :func:`_gated_delta_rule` is the
definition in plain JAX (every chunk's system at once, a ``lax.scan`` over
the chunks, the solve by blocks of 16): what every backend but the TPU and
every shape the kernel does not tile runs, and what the kernel is held to.
:func:`_scan_kernel` is the same algorithm as one Pallas kernel a layer for
the TPU: grid (row, group of 8 value heads, chunk), the chunks of a row in
order; a step reads the chunk's q and k of the key heads, v, the cumulated
gate and beta, keeps everything of chunk x chunk shape and the state in
vector memory, and writes the chunk of ``o``. Its float32 products run at
the reference's precision (``highest``); two value heads of a key head
share one pass through the matrix unit wherever the operands are chunk x
chunk (:func:`_blocks`), and the in-chunk system is inverted by block
forward substitution from pairs of tokens up (:func:`_unit_lower_inverse`).
A chunk past a row's last real token is skipped and written as zeros.
:func:`scan_lowerings` counts which lowering each compiled program took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops import lowering_count

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


@functools.partial(jax.jit, static_argnames=("chunk",))
def gated_delta_rule(q, k, v, g, beta, starts, real=None,
                     chunk: int = CHUNK):
    """The recurrence of the module docstring for every head of every
    row, chunked.

    q, k (B, T, nk, dk): normalised and scaled by the caller, one key head
    serving ``nv // nk`` value heads in turn; v (B, T, nv, dv); g, beta
    (B, T, nv) float32, ``g <= 0`` the log of the decay; starts (B, T)
    bool, True at a document's first token (the state it meets is zero);
    real (B, T) bool or None: the slots that hold a token somebody reads.
    Returns o (B, T, nv, dv) float32, defined at the real slots (past a
    row's last real token the kernel writes zeros, the reference what the
    padding computes).

    One algorithm, two lowerings, chosen by what can be observed: lowered
    for a TPU at the shapes of :func:`_kernel_tiles` it is
    :func:`_scan_kernel`; on every other platform and at every other
    shape :func:`_gated_delta_rule`, the definition the kernel is held to.
    :func:`scan_lowerings` counts which one each compiled program took.
    Jitted, so that the layers of one program share one trace and one
    lowering of it (the kernel's costs 0.2 s, and a warm-up walks eight
    programs of three layers).
    """
    # float32 operands would go through the chip's matrix unit as single
    # bfloat16 passes: the state and the chunk's system are kept to float32
    with jax.default_matmul_precision("highest"):
        def reference(q, k, v, g, beta, starts):
            r = v.shape[2] // q.shape[2]
            q, k = (jnp.repeat(a, r, axis=2) for a in (q, k))
            return _took(_gated_delta_rule(q, k, v, g, beta, starts, chunk),
                         "reference")

        if not _kernel_tiles(q.shape, v.shape, chunk):
            return reference(q, k, v, g, beta, starts)

        def kernel(q, k, v, g, beta, starts):
            return _took(_scan_kernel(q, k, v, g, beta, starts, real),
                         "kernel")

        return jax.lax.platform_dependent(q, k, v, g, beta, starts,
                                          tpu=kernel, default=reference)


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


# ---------------------------------------------------------------------------
# which lowering a compiled program took
# ---------------------------------------------------------------------------

def scan_lowerings() -> dict:
    """Scans lowered in this process by the lowering they took: ``kernel``
    (the fused TPU kernel) or ``reference`` (:func:`_gated_delta_rule`),
    one count a scan of a compiled program (an eager call counts as one:
    ``ops/lowering_count.py``). ``/metrics`` shows it as
    ``pathway_tpu_deltanet_scan_programs``."""
    return lowering_count.counts("deltanet_scan", ("kernel", "reference"))


def _took(o, lowering: str):
    return lowering_count.took(o, "deltanet_scan", lowering)


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------

#: value heads a grid step holds (with their key heads): their chunks go
#: through the four matrix units abreast, which hides each one's chain of
#: dependent products (8 and 16 read the same on the chip, 4 reads 10 % more)
GROUP = 8


def _kernel_tiles(q_shape: tuple, v_shape: tuple, chunk: int) -> bool:
    """The shapes the kernel tiles: a head's keys and values fill whole
    lanes of the chip's vector registers, the value heads come in pairs
    to a key head (two go side by side through the matrix unit), and the
    chunk is the one its in-chunk solve is written for."""
    (nk, dk), (nv, dv) = q_shape[2:], v_shape[2:]
    return dk % 128 == 0 and dv % 128 == 0 and nv % (2 * nk) == 0 \
        and chunk == CHUNK


def _dot(a, b, dims=((2,), (1,))):
    """Float32 products, one a head (the leading axis), at the precision
    the reference runs its own."""
    return jax.lax.dot_general(a, b, (dims, ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _where(mask, a, b=0.0):
    """``jnp.where`` as the primitives it is made of, for a mask of the
    result's shape. ``jnp.where``'s jitted wrapper (sixty of them in the
    kernel's body) and a boolean's broadcast are traced anew every time a
    program that holds the kernel is lowered: half a minute of a warm-up
    that walks eight programs (my chip runs, PR 32)."""
    return jax.lax.select(mask, *(jnp.broadcast_to(
        jnp.asarray(x, jnp.float32), mask.shape) for x in (a, b)))


def _blocks(m, left):
    """Two heads' (C, C) matrices side by side, ``[M1 | M2]`` (pairs, C,
    2C), as the block diagonal ``[[M1, 0], [0, M2]]``: to the right of
    ``[X1 | X2]`` it gives ``[X1 M1 | X2 M2]``, one pass of C rows through
    the matrix unit for both heads."""
    return jnp.concatenate([_where(left, m), _where(left, 0.0, m)], axis=1)


def _unit_lower_inverse(a, ii, jj, left):
    """``(I + A)^-1`` for strictly lower triangular ``a``, two heads side
    by side (pairs, C, 2C), by whole matrix products. Two tokens invert
    by a sign; then two neighbours ``T1``, ``T2`` already inverted join
    into one block of twice the size, whose inverse has ``-T2 A21 T1``
    below its diagonal (block forward substitution, five times for a chunk
    of 64): the rows of the lower neighbours alone go through the matrix
    unit."""
    c = a.shape[1]
    # two tokens lie in one block of ``size`` (a power of two) where their
    # numbers differ below that bit alone
    apart = ii ^ jj
    inv = (ii == jj).astype(a.dtype) - _where(apart < 2, a)
    size = 2
    while size < c:
        lower = range(size, c, 2 * size)     # where a lower neighbour starts
        join = (apart >= size) & (apart < 2 * size)
        below = _dot(_dot(jnp.concatenate(
            [inv[:, at:at + size] for at in lower], axis=1),
            _blocks(_where(join, a), left)), _blocks(inv, left))
        inv = jnp.concatenate([
            inv[:, at:at + size] if at // size % 2 == 0
            else inv[:, at:at + size] - below[:, (at - size) // 2:
                                              (at + size) // 2]
            for at in range(0, c, size)], axis=1)
        size *= 2
    return inv


def _scan_body(live_ref, q_ref, k_ref, v_ref, cols_ref, rows_ref, keep_ref,
               o_ref, s_ref, *, group: int, ratio: int, dk: int, dv: int):
    """One chunk of one row for ``group`` value heads. Blocks: q, k
    (C, group / ratio * dk); v, o (C, group * dv); cols (C, 3 + 2 group):
    the chunk's document numbers, whether the carried state reaches a
    token, whether the token reaches the next chunk, then a head's
    cumulated gate and its beta; rows (1 + group / 2, 2C): the document
    numbers and the cumulated gates again, along the lanes, two heads side
    by side. ``s_ref`` (group, dk, dv) is the state, which lives in vector
    memory for the length of the row. ``live_ref`` (B,) holds a row's
    chunks up to its last real token, ``keep_ref`` (1, group) in scalar
    memory what the chunk keeps of the state it met, a head.

    The (C, C) matrices of a chunk (scores, decay, the system and its
    inverse) are held for two value heads of one key head side by side,
    (C, 2C): a whole vector register wide, and one pass through the matrix
    unit for both (:func:`_blocks`)."""
    row, n = pl.program_id(0), pl.program_id(2)
    c = q_ref.shape[0]

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(n >= live_ref[row])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n < live_ref[row])
    def _():
        cols, rows = cols_ref[...], rows_ref[...]
        # masks are made with the pairs' axis: broadcasting a boolean is a
        # traced function of its own in the kernel's lowering
        shape = (group // 2, c, 2 * c)
        ii = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        left = lane < c
        jj = lane & (c - 1)
        # tokens see each other only inside one document
        reach = (ii >= jj) & (jnp.broadcast_to(cols[:, 0:1], shape)
                              == jnp.broadcast_to(rows[0:1, :], shape))
        carry, tail = cols[:, 1:2], cols[:, 2:3]
        column = lambda at: cols[:, at:at + 1]
        per_head = lambda at: jnp.stack(
            [column(at + h) for h in range(group)])
        per_pair = lambda at: _where(left, *(jnp.stack(
            [column(at + h) for h in range(side, group, 2)])
            for side in (0, 1)))
        key = lambda ref: jnp.stack(
            [ref[:, h // ratio * dk:(h // ratio + 1) * dk]
             for h in range(0, group, 2)])
        two = lambda a: jnp.stack([jnp.concatenate(
            [a[h], a[h + 1]], axis=0)
            for h in range(0, group, 2)])                  # heads -> pairs
        one = lambda a, h: a[h // 2, h % 2 * c:(h % 2 + 1) * c]  # and back
        q, k = key(q_ref), key(k_ref)
        # the key head's products, for the two value heads it serves
        scores = _dot(jnp.concatenate([k, q], axis=1),
                      jnp.concatenate([k, k], axis=1), ((2,), (2,)))
        decay = _where(reach, jnp.exp(jnp.minimum(
            per_pair(3) - jnp.stack(
                [rows[1 + p:2 + p] for p in range(group // 2)]),
            0.0)))                                          # (pairs, C, 2C)
        # (I + A) u = beta v - (beta k exp(gc)) S0
        inverse = _unit_lower_inverse(_where(
            ii > jj, scores[:, :c] * per_pair(3 + group) * decay),
            ii, jj, left)
        q, k = (jnp.repeat(a, 2, axis=0) for a in (q, k))
        gc, beta = per_head(3), per_head(3 + group)
        last = gc[:, c - 1:c, :]
        carried = jnp.exp(gc) * carry
        v = jnp.stack([v_ref[:, h * dv:(h + 1) * dv] for h in range(group)])
        s = s_ref[...]
        read = _dot(jnp.concatenate(
            [k * (beta * carried), q * carried], axis=1), s)
        u = _dot(_blocks(inverse, left), two(v * beta - read[:, :c]))
        o = two(read[:, c:]) + _dot(_blocks(scores[:, c:] * decay, left), u)
        fresh = _dot(k * (jnp.exp(last - gc) * tail), jnp.stack(
            [one(u, h) for h in range(group)]), ((1,), (1,)))
        for h in range(group):
            o_ref[:, h * dv:(h + 1) * dv] = one(o, h)
            s_ref[h] = s[h] * keep_ref[0, h] + fresh[h]


def _scan_kernel(q, k, v, g, beta, starts, real=None, *,
                 interpret: bool = False):
    """:func:`_gated_delta_rule` as one kernel: a grid step reads a
    chunk's q and k of the key heads, v, the gate and beta once, computes
    the chunk's system, its solve, ``o`` and the new state in vector
    memory, and writes the chunk of ``o``. Grid (row, group of value
    heads, chunk), the chunks of a row in order; a chunk past a row's last
    real token is written as zeros."""
    b, t, nk, dk = q.shape
    nv, dv = v.shape[2:]
    ratio = nv // nk
    group = next(gr for gr in range(min(GROUP, nv), 0, -1)
                 if nv % gr == 0 and gr % ratio == 0)
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
        starts = jnp.pad(starts, ((0, 0), (0, pad)), constant_values=True)
        if real is not None:
            real = jnp.pad(real, ((0, 0), (0, pad)))
    n = (t + pad) // CHUNK
    f32 = jnp.float32
    seg = jnp.cumsum(starts.astype(jnp.int32), axis=1).reshape(b, n, CHUNK)
    before = jnp.pad(seg[..., -1], ((0, 0), (1, 0)),
                     constant_values=-1)[:, :n, None]
    per_row = jnp.stack([seg, seg == before, seg == seg[..., -1:]],
                        axis=-1).astype(f32)                 # (B, N, C, 3)

    def by_group(a):        # (B, T, nv) -> (B, N, groups, C, group)
        a = a.astype(f32).reshape(b, n, CHUNK, nv // group, group)
        return jnp.moveaxis(a, 3, 2)

    gc = jnp.cumsum(g.astype(f32).reshape(b, n, CHUNK, nv), axis=2)
    # what a chunk keeps of the state it met, a head
    keep = jnp.exp(gc[:, :, -1]) * per_row[:, :, -1, 1:2]    # (B, N, nv)
    gc, beta = by_group(gc), by_group(beta)
    cols = jnp.concatenate([jnp.broadcast_to(
        per_row[:, :, None], gc.shape[:4] + (3,)), gc, beta], axis=-1)
    # along the lanes, two heads side by side: (B, N, groups, 1 + group/2, 2C)
    rows = jnp.concatenate([jnp.broadcast_to(jnp.tile(
        seg.astype(f32), 2)[:, :, None, None], gc.shape[:3] + (1, 2 * CHUNK)),
        jnp.swapaxes(gc, 3, 4).reshape(gc.shape[:3] + (-1, 2 * CHUNK))],
        axis=3)
    if real is None:
        live = jnp.full((b,), n, jnp.int32)
    else:
        holds = real.reshape(b, n, CHUNK).any(axis=-1)
        live = jnp.max(jnp.where(holds, jnp.arange(1, n + 1), 0),
                       axis=1).astype(jnp.int32)
    keys, values = group // ratio * dk, group * dv
    tokens = lambda r, hg, c, live: (r, c, hg)         # of (B, T, features)
    scalars = lambda r, hg, c, live: (r, c, hg, 0, 0)  # of (B, N, groups, ..)
    o = pl.pallas_call(
        functools.partial(_scan_body, group=group, ratio=ratio, dk=dk,
                          dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nv // group, n),
            in_specs=[
                pl.BlockSpec((None, CHUNK, keys), tokens),
                pl.BlockSpec((None, CHUNK, keys), tokens),
                pl.BlockSpec((None, CHUNK, values), tokens),
                pl.BlockSpec((None, None, None, CHUNK, 3 + 2 * group),
                             scalars),
                pl.BlockSpec((None, None, None, 1 + group // 2, 2 * CHUNK),
                             scalars),
                pl.BlockSpec((None, None, None, 1, group), scalars,
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((None, CHUNK, values), tokens),
            scratch_shapes=[pltpu.VMEM((group, dk, dv), f32)]),
        out_shape=jax.ShapeDtypeStruct((b, n * CHUNK, nv * dv), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(live, q.astype(f32).reshape(b, n * CHUNK, nk * dk),
      k.astype(f32).reshape(b, n * CHUNK, nk * dk),
      v.astype(f32).reshape(b, n * CHUNK, nv * dv), cols, rows,
      keep.reshape(b, n, nv // group, 1, group))
    return o.reshape(b, n * CHUNK, nv, dv)[:, :t]

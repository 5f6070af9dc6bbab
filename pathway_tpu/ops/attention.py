"""Attention kernels: the encoder's whole-sequence fused kernel
(:func:`flash_attention`, below) and the decoder's blocked causal attention
over packed rows (:func:`segment_attention`, at the end of the file).

**The encoder's.**

The XLA fallback (models/encoder.py _dense_attention) materializes the
(B, H, S, S) float32 score tensor in HBM — at encoder bench shapes
(B=1024, H=12, S=128) that is ~800 MB written+read per layer, and HBM
bandwidth, not MXU, bounds the forward pass. This kernel keeps each
(S, S) score tile in VMEM for one (batch, head) grid cell: qk^T → masked
softmax → @v with no HBM round-trip, f32 accumulation on the MXU
(preferred_element_type) and bf16 operands.

Scope: bidirectional (encoder) attention with a key-validity mask, whole
sequence resident per grid cell — right for S ≤ ~1k (VMEM budget). Longer
sequences use the separate sequence-parallel path
(pathway_tpu/parallel/ring_attention.py, its own online-softmax blockwise
attention over the mesh). The encoder uses the XLA path by default; how
this kernel compares with it on the chip is not measured on the current
machine (CHANGES.md PR 21 records whether Mosaic compiles it at all).
``interpret`` is explicit and defaults to compiled: nothing here guesses
the backend, so a chip whose platform name is unexpected cannot land on
the interpreter unnoticed. CPU tests pass ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.ops import lowering_count

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _attn_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref):
    # blocks: q/k/v (TB, S, H, D), mask (TB, 1, S) — all heads + a strip of
    # batches per grid cell so the MXU sees one big batched contraction and
    # the (S, S) scores never leave VMEM
    q = q_ref[:]
    k = k_ref[:]
    v = v_ref[:]
    mask = mask_ref[:]                           # (TB, 1, S)
    TB, S, H, D = q.shape
    scale = D ** -0.5

    def fold(x):  # (TB, S, H, D) → (TB*H, S, D) batched for dot_general
        return x.transpose(0, 2, 1, 3).reshape(TB * H, S, D)

    qh, kh, vh = fold(q), fold(k), fold(v)
    scores = jax.lax.dot_general(
        qh, kh, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale      # (TB*H, S, S) f32
    key_valid = jnp.repeat(mask[:, 0, :] != 0, H, axis=0)  # (TB*H, S)
    scores = jnp.where(key_valid[:, None, :], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    probs = (p / denom).astype(v.dtype)
    out = jax.lax.dot_general(
        probs, vh, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (TB*H, S, D)
    out_ref[:] = out.reshape(TB, H, S, D).transpose(0, 2, 1, 3).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_attention(q, k, v, mask, *, interpret: bool = False):
    """Fused attention: q,k,v (B, S, H, D); mask (B, S) key validity.
    Returns (B, S, H, D) in q's dtype. Drop-in for the encoder's
    ``attn_fn`` hook (models/encoder.py encode)."""
    from jax.experimental import pallas as pl

    B, S, H, D = q.shape
    # strip of batches per cell: amortize per-cell overhead, bound VMEM
    block_b = 1
    for cand in (8, 4, 2):
        # scores + exp + probs copies live simultaneously: keep the f32
        # (TB*H, S, S) tensor under ~2 MB so the ~16 MB scoped VMEM holds
        # qkv blocks and intermediates too
        if B % cand == 0 and cand * H * S * S * 4 <= 2 * 1024 * 1024:
            block_b = cand
            break
    mask_i = mask.astype(jnp.int32).reshape(B, 1, S)

    out = pl.pallas_call(
        _attn_kernel,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, S, H, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((block_b, S, H, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((block_b, S, H, D), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((block_b, 1, S), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, S, H, D), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        interpret=interpret,
    )(q, k, v, mask_i)
    return out


# ---------------------------------------------------------------------------
# blocked causal attention over packed rows (the decoder's)
# ---------------------------------------------------------------------------
#
# A row holds several documents back to back (``seg``: a slot's document,
# -1 padding; ``pos``: its position in its document; a document's tokens
# lie in consecutive slots in order of position, as the packer lays them).
# A query sees the keys of its own document at or before its own slot and,
# under a window, at most ``window - 1`` positions back. Rows are cut into
# query blocks and key blocks; the softmax is accumulated online over the
# key blocks a query block can see, so nothing of shape T x T is ever
# written, and key blocks that lie wholly in the future, in other documents
# or behind the window are neither fetched nor multiplied: the first key
# block a query block sees and how many follow are computed on the device
# from ``seg``, ``pos`` and ``window`` before the loop (:func:`_block_ranges`).
#
# One algorithm, two lowerings, chosen by what the function observes (platform,
# head size, row length): on a TPU with heads of whole lanes it is one Pallas
# kernel (:func:`_segment_kernel`), everywhere else the same loop in plain
# ``jax.numpy`` (:func:`_blockwise`), both over the same blocks, which follow
# the row's length and the query heads a key head serves (:func:`block_sizes`:
# read from the operands' shape). Keys and values may differ in width and the
# scale may be given: latent attention in prefill (:func:`latent_attention`) is
# this core over keys of 128 + 64 features and values of 128.
#
# **A learned choice of keys.** Where a model ranks the keys a query sees
# (:func:`select_keys`: a small scorer over every visible pair, and of each
# query's visible keys the ``topk`` best, exactly), the core takes the
# choice as one more operand: a mask of the row, (T, T) int8, which outlives
# the layer that made it (the layers after it attend over the same choice),
# and a flag a tile that says whether anything in it was chosen. Both
# lowerings read the mask's tile beside their ranges and skip a tile with
# nothing chosen.

#: the running max a row starts from, and a masked score: both large and
#: finite, so that nothing makes a NaN, and the masked one the lower, so
#: that a row that has seen no key yet gives a masked score the weight
#: ``exp(_MASKED - _UNSEEN) = 0`` with no second mask after the exponential
_UNSEEN = -1e30
_MASKED = -2e30


def block_sizes(t: int, rep: int) -> tuple[int, int, int]:
    """(query block, key block, padded row length) for rows of ``t``
    slots whose key heads serve ``rep`` query heads each: key blocks of
    1,024, fewer keys where a row is shorter, one block where it is shorter
    than 256. A query block's ``rep`` heads are stacked as rows of one
    product, so a key block read once serves them all: blocks of 256
    queries where heads are grouped (1,792 rows for 7 heads a key head),
    and as many queries as keys where every head has keys of its own
    (``rep`` 1: latent attention). On the chip a row of 16,384 slots that
    is one document takes a full layer 27.8 ms with key blocks of 512,
    18.4 ms with 1,024 (51.7 with 256; query blocks of 128 read 32.5): a
    step's fixed work, the running max, sum and accumulator read and
    written, is spread over more keys (my chip run, PR 33). Ungrouped, a
    call of 16 heads over a choice on a row of 16,384 slots (three
    documents, values of 256) takes 13.0 ms with query blocks of 256, 10.2
    with 512, 9.0 with 1,024, and one of 64 heads on a row of 8,192 (two
    documents, values of 128) 13.7, 11.5, 11.3 ms, to the same bits: a
    grid step that does nothing costs a third of a microsecond and the
    grid has a quarter of them; ``ingest_docs_per_s`` read 3.11, 3.45, 3.58
    in the first cell and 3.41, 3.52, 3.54 in the second (my chip run,
    PR 42)."""
    if t <= 256:
        return t, t, t
    bk = next(size for size in (1024, 512, 256) if t >= size)
    return (256 if rep >= 2 else bk), bk, -(-t // bk) * bk


def _block_ranges(xp, seg, pos, window, bq: int, bk: int):
    """For every query block of every row: (the first key block it can
    see, how many it sees from there on; 0 for a block of padding), each
    (B, T / bq) int32. ``xp`` is ``jax.numpy`` or ``numpy``: the host
    counts with the same lines (:func:`attention_work`)."""
    b, t = seg.shape
    slot = xp.arange(t, dtype=xp.int32)[None, :]
    real = seg >= 0
    back = pos if window is None else xp.minimum(pos, window - 1)
    first_key = xp.where(real, slot - back, t).reshape(b, t // bq, bq)
    last_key = xp.where(real, slot, -1).reshape(b, t // bq, bq)
    lo, hi = first_key.min(axis=-1) // bk, last_key.max(axis=-1) // bk
    count = xp.where(hi >= 0, hi - lo + 1, 0)
    return (xp.where(count > 0, lo, 0).astype(xp.int32),
            count.astype(xp.int32))


def _max_steps(t: int, window, bq: int, bk: int) -> int:
    """The most key blocks one query block can see."""
    if window is None:
        return t // bk
    return min(t // bk, -(-(window - 1 + bq) // bk) + 1)


def attention_work(seg: np.ndarray, pos: np.ndarray, windows: tuple,
                   index_topk: int | None = None, indexers: int = 0, *,
                   rep: int) -> dict:
    """What the attention layers of one dispatch have to do, counted on
    the host from the packed rows (``seg``, ``pos`` (B, T) as the packer
    made them; ``windows``: each attention layer's window, None for full
    attention; ``rep``: the query heads a key head serves, which decides
    the cores' query block): ``attn_pairs_full`` the visible (query, key)
    pairs of a full layer (a document of n tokens has n (n + 1) / 2),
    ``attn_pairs_window`` of a window layer (where the model has one),
    ``attn_tiles_run`` the key blocks the kernel's ranges admit and
    ``attn_tiles_all`` all key blocks up to the diagonal, both summed over
    query blocks and attention layers, ``attn_query_block`` the queries of
    a block they are counted at (the kernel's own: :func:`block_sizes`).
    Where the model chooses its keys (``index_topk``; ``indexers``: the
    layers that hold a scorer):
    ``attn_pairs_indexed`` the pairs the scorers rank (every visible pair, a
    scorer) and ``attn_pairs_selected`` the pairs the cores attend over (a
    query's visible keys or ``index_topk``, the fewer, an attention
    layer)."""
    t = seg.shape[1]
    bq, bk, padded = block_sizes(t, rep)
    if padded != t:
        seg = np.pad(seg, ((0, 0), (0, padded - t)), constant_values=-1)
        pos = np.pad(pos, ((0, 0), (0, padded - t)))
    real = seg >= 0
    reach = (pos.astype(np.int64) + 1)[real]
    out = {"attn_pairs_full": int(reach.sum()), "attn_query_block": bq}
    if index_topk is not None:
        out["attn_pairs_indexed"] = indexers * out["attn_pairs_full"]
        out["attn_pairs_selected"] = len(windows) * int(
            np.minimum(reach, index_topk).sum())
    to_diagonal = seg.shape[0] * int(
        ((np.arange(1, padded // bq + 1) * bq - 1) // bk + 1).sum())
    out["attn_tiles_run"], out["attn_tiles_all"] = 0, 0
    for window in sorted(set(windows), key=lambda w: w or 0):
        layers = windows.count(window)
        if window is not None:
            out["attn_pairs_window"] = int(np.minimum(reach, window).sum())
        _lo, count = _block_ranges(np, seg, pos, window, bq, bk)
        out["attn_tiles_run"] += layers * int(count.sum())
        out["attn_tiles_all"] += layers * to_diagonal
    return out


def attention_lowerings() -> dict:
    """Attention cores lowered in this process by the lowering they took:
    ``kernel`` (the Pallas TPU kernel) or ``blockwise`` (plain JAX), one
    count a call of a compiled program (``ops/lowering_count.py``), and,
    once a program holds a core over a learned choice of keys,
    ``sparse_kernel`` and ``sparse_blockwise`` for those; once one holds a
    core of ungrouped heads, ``query_block_512`` or ``query_block_1024``:
    the cores, of those above, that took a query block larger than 256, by
    the block (:func:`block_sizes`). ``/metrics`` shows it as
    ``pathway_tpu_attention_programs``."""
    took = lowering_count.counts("attention", ("kernel", "blockwise"))
    sparse = lowering_count.counts("attention", ("sparse_kernel",
                                                 "sparse_blockwise"))
    if any(sparse.values()):
        took.update(sparse)
    wide = lowering_count.counts("attention", ("query_block_512",
                                               "query_block_1024"))
    took.update({name: n for name, n in wide.items() if n})
    return took


#: features a vector register of the chip holds side by side
LANES = 128


def _kernel_tiles(v_shape: tuple, padded: int) -> bool:
    """The shapes the kernel tiles: a head's values fill whole lanes of the
    chip's vector registers and a row whole blocks of 128 slots. (Keys of
    another width than the values are padded with zeros to whole lanes on
    the way in, which is exact.)"""
    return v_shape[3] % LANES == 0 and padded % 128 == 0


@functools.partial(jax.jit, static_argnames=("window", "scale"))
def segment_attention(q, k, v, seg, pos, *, window: int | None = None,
                      scale: float | None = None, choice=None):
    """Causal softmax attention inside each document of packed rows, with
    grouped heads and an optional window.

    q (B, T, nh, d); k (B, T, nkv, d); v (B, T, nkv, dv), ``nh // nkv``
    query heads a key head, in the dtype the products run in (sums are
    float32); seg (B, T)
    int32 a slot's document, -1 padding; pos (B, T) int32 its position in
    its document (a document's tokens lie in consecutive slots, in
    order). ``visible(t, s) = seg[t] == seg[s] >= 0 and s <= t and
    (window is None or pos[t] - pos[s] < window)``; scores are scaled by
    ``scale`` (None: ``d ** -0.5``). Returns (B, T, nh, dv) float32, defined
    at the real slots (padding reads zeros). ``choice``
    (:func:`select_keys`'s): the keys each query attends over, of those it
    sees; the softmax runs over them alone.

    One algorithm, two lowerings: lowered for a TPU at the shapes of
    :func:`_kernel_tiles` it is :func:`_segment_kernel`, on every other
    platform and at every other shape :func:`_blockwise`.
    :func:`attention_lowerings` counts which one each compiled program
    took. Jitted, so that the layers of one program that share a window
    share one trace and one lowering."""
    b, t, nh, d = q.shape
    bq, bk, padded = block_sizes(t, nh // k.shape[2])
    if padded != t:
        grow = ((0, 0), (0, padded - t))
        q, k, v = (jnp.pad(a, grow + ((0, 0), (0, 0))) for a in (q, k, v))
        seg = jnp.pad(seg, grow, constant_values=-1)
        pos = jnp.pad(pos, grow)
    seg, pos = seg.astype(jnp.int32), pos.astype(jnp.int32)
    lo, count = _block_ranges(jnp, seg, pos, window, bq, bk)
    # the scale is that of the keys as given: the kernel may widen them
    sizes = dict(window=window, bq=bq, bk=bk,
                 scale=d ** -0.5 if scale is None else scale)

    # a core over a choice is counted under a name of its own
    name = "" if choice is None else "sparse_"

    def blockwise(q, k, v, seg, pos, lo, count, *choice):
        return lowering_count.took(
            _blockwise(q, k, v, seg, pos, lo, count, *choice, **sizes),
            "attention", name + "blockwise")

    if choice is None:
        choice = ()
    else:
        # the choice counts a tile at its own loop's query block
        # (:func:`select_keys`); a larger one of this core holds several
        mask, tiles = choice
        choice = ((mask, tiles.reshape(b, padded // bq, -1,
                                       padded // bk).sum(axis=2)),)
    if not _kernel_tiles(v.shape, padded):
        out = blockwise(q, k, v, seg, pos, lo, count, *choice)
    else:
        def kernel(q, k, v, seg, pos, lo, count, *choice):
            if d % LANES:
                grow = ((0, 0),) * 3 + ((0, -d % LANES),)
                q, k = jnp.pad(q, grow), jnp.pad(k, grow)
            return lowering_count.took(
                _segment_kernel(q, k, v, seg, pos, lo, count, *choice,
                                **sizes),
                "attention", name + "kernel")

        out = jax.lax.platform_dependent(q, k, v, seg, pos, lo, count,
                                         *choice, tpu=kernel,
                                         default=blockwise)
    if bq > 256:
        out = lowering_count.took(out, "attention", f"query_block_{bq}")
    return out[:, :t]


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, seg, pos, *,
                     scale: float, choice=None):
    """The core of multi-head latent attention in prefill (the unabsorbed
    form: keys and values expanded a head), causal inside each document of
    packed rows: ``score[t, s, head] = (q_nope[t, head] . k_nope[s, head] +
    q_rope[t, head] . k_rope[s]) * scale``.

    q_nope, k_nope (B, T, nh, dn): the parts without positions; q_rope
    (B, T, nh, dr) and k_rope (B, T, dr), rotated by the caller: **one
    rotary key for all heads**; v (B, T, nh, dv), of another width than the
    keys; ``scale`` given (the model's, over dn + dr). Returns (B, T, nh,
    dv) float32. One head's keys are its own part beside the shared one,
    dn + dr wide, and the core is :func:`segment_attention`'s with no
    grouping: its kernel on the chip where the values fill whole lanes (the
    keys' 192 features padded with zeros to 256), the blockwise loop
    elsewhere. ``choice``: :func:`segment_attention`'s."""
    b, t, nh, _ = q_nope.shape
    shared = jnp.broadcast_to(k_rope[:, :, None, :],
                              (b, t, nh, k_rope.shape[-1]))
    return segment_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([k_nope, shared], axis=-1), v, seg, pos, scale=scale,
        choice=choice)


def _order_bits(x):
    """float32 -> uint32 that orders as the floats do (``-0.0`` as
    ``0.0``): the float's bits with the sign turned for a positive number
    and every bit for a negative one."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _largest(keys, see, topk: int):
    """Of each row's entries of ``keys`` (Q, T) uint32 that ``see`` admits,
    the ``topk`` largest, a tie at the edge going to the earlier entry:
    (Q, T) bool. Exact: the ``topk``-th largest key of a row is found a bit
    at a time from the top (32 counts over the row, no sort), everything
    above it is taken, and of its equals the first that are still
    wanted."""
    keys = jnp.where(see, keys, 0)

    def bit(i, found):
        with_bit = found | (jnp.uint32(1 << 31) >> i)
        enough = jnp.sum(keys >= with_bit[:, None], axis=1) >= topk
        return jnp.where(enough, with_bit, found)

    edge = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.uint32))
    above = see & (keys > edge[:, None])
    equal = see & (keys == edge[:, None])
    wanted = topk - jnp.sum(above, axis=1)
    # equals at the edge beyond those wanted: rare (two float32 scores the
    # same to the last bit), and only then are they counted along the row
    first = lambda: equal & (jnp.cumsum(equal, axis=1) <= wanted[:, None])
    return above | jax.lax.cond(
        jnp.any(jnp.sum(equal, axis=1) > wanted), first, lambda: equal)


@functools.partial(jax.jit, static_argnames=("topk",))
def select_keys(q_idx, k_idx, w_idx, seg, pos, *, topk: int):
    """The learned choice of keys (DeepSeek sparse attention's lightning
    indexer): every visible (query, key) pair is scored

        ``I[t, s] = sum_j w_idx[t, j] * relu(q_idx[t, j] . k_idx[s])``

    over the index heads ``j``, and a query attends over all the keys it
    sees (its own document, at or before its own slot) where they are at
    most ``topk``, else over the ``topk`` of largest ``I[t, .]``, exactly
    (a tie at the edge: the earlier key; never an approximate top-k).

    q_idx (B, T, nI, dI) and k_idx (B, T, dI), **one key a token for all
    index heads**, in the dtype the products run in (ReLU, the weighted
    sum and the choice are float32); w_idx (B, T, nI) float32; seg, pos
    (B, T) as :func:`segment_attention`'s. Returns (``choice``, ``chosen``):
    ``choice`` is what :func:`segment_attention` takes, a pair (the mask
    (B, T', T') int8, one where query ``t`` attends over key ``s``, T' the
    row padded to whole blocks; (B, T' / bq, T' / bk) int32, the chosen
    pairs of each tile of this loop's blocks, which a core sums to its
    own), a value that the layers after this one attend over as well;
    ``chosen`` the float32 count of chosen pairs of the dispatch.

    A block of 256 queries at a time, whatever the cores' query block: its
    scores against the key blocks it can see (:func:`_block_ranges`), then
    the edge of each of its queries (:func:`_largest`). Nothing is as
    large as all heads' scores of a row: the largest arrays are one block's
    scores of one key block, (bq, nI, bk) float32, and the mask; a block's
    scores over the row, (bq, T') float32, stay in vector memory through
    :func:`_largest`'s 32 passes (PR 40)."""
    b, t, ni, di = q_idx.shape
    _, bk, padded = block_sizes(t, 1)
    bq = min(256, padded)
    if padded != t:
        grow = ((0, 0), (0, padded - t))
        q_idx = jnp.pad(q_idx, grow + ((0, 0), (0, 0)))
        k_idx, w_idx = (jnp.pad(a, grow + ((0, 0),)) for a in (k_idx, w_idx))
        seg = jnp.pad(seg, grow, constant_values=-1)
        pos = jnp.pad(pos, grow)
    seg, pos = seg.astype(jnp.int32), pos.astype(jnp.int32)
    lo, count = _block_ranges(jnp, seg, pos, None, bq, bk)
    nq, nk = padded // bq, padded // bk
    slot = jnp.arange(padded, dtype=jnp.int32)
    f32 = jnp.float32
    blocks = lambda a: a.reshape((b * nq, bq) + a.shape[2:])

    def query_block(xs):
        at, qb, wb, seg_q, pos_q, first, steps = xs
        row = at // nq
        keys, seg_k, pos_k = (jax.lax.dynamic_index_in_dim(
            a, row, keepdims=False) for a in (k_idx, seg, pos))

        def key_block(j, scores):
            start = (first + j) * bk
            kb = jax.lax.dynamic_slice_in_dim(keys, start, bk)
            s = jnp.einsum("qjd,kd->qjk", qb, kb, preferred_element_type=f32)
            return jax.lax.dynamic_update_slice(
                scores, jnp.sum(jax.nn.relu(s) * wb[:, :, None], axis=1),
                (0, start))

        scores = jax.lax.fori_loop(0, steps, key_block,
                                   jnp.zeros((bq, padded), f32))
        see = _visible(seg_q[:, None], pos_q[:, None],
                       ((at % nq) * bq + jnp.arange(bq))[:, None],
                       seg_k[None, :], pos_k[None, :], slot[None, :], None)
        # a block none of whose queries sees more than ``topk`` keys (a
        # document's first ones; short documents) has nothing to rank
        chosen = jax.lax.cond(
            jnp.max(jnp.sum(see, axis=1)) > topk,
            lambda: _largest(_order_bits(scores), see, topk), lambda: see)
        return chosen.astype(jnp.int8), jnp.sum(
            chosen.reshape(bq, nk, bk), axis=(0, 2), dtype=jnp.int32)

    mask, tiles = jax.lax.map(query_block, (
        jnp.arange(b * nq), blocks(q_idx), blocks(w_idx.astype(f32)),
        blocks(seg), blocks(pos), lo.reshape(-1), count.reshape(-1)))
    choice = (mask.reshape(b, padded, padded), tiles.reshape(b, nq, nk))
    return choice, jnp.sum(tiles.astype(f32))


def _visible(seg_q, pos_q, slot_q, seg_k, pos_k, slot_k, window):
    """The mask of one block from its queries' (.., bq, 1) and its keys'
    (.., 1, bk) document, position and slot."""
    see = (seg_q == seg_k) & (seg_k >= 0) & (slot_k <= slot_q)
    if window is not None:
        see = see & (pos_q - pos_k < window)
    return see


def _blockwise(q, k, v, seg, pos, lo, count, choice=None, *, window,
               bq: int, bk: int, scale: float | None = None):
    """:func:`segment_attention` in plain JAX: a ``lax.map`` over the
    query blocks, inside it a loop over the key blocks that block can see
    (of any row: the rows of a dispatch walk the union of their ranges),
    the online softmax of ``parallel/ring_attention.py``. The largest
    array is one block's scores, (B, nh, bq, bk). ``choice``: the mask's
    tile is read beside the block's own mask, and a step in which no row's
    tile holds a chosen key computes nothing."""
    b, t, nh, d = q.shape
    nkv, dv = k.shape[2], v.shape[3]
    nq, nk = t // bq, t // bk
    q = q.reshape(b, nq, bq, nkv, nh // nkv, d)
    scale = d ** -0.5 if scale is None else scale
    first = jnp.min(jnp.where(count > 0, lo, nk), axis=0)         # (nq,)
    steps = jnp.maximum(
        jnp.max(jnp.where(count > 0, lo + count, 0), axis=0) - first, 0)
    f32 = jnp.float32

    def query_block(i):
        qb = q[:, i]                                      # (B, bq, g, r, d)
        cut = lambda a, at, n: jax.lax.dynamic_slice_in_dim(a, at, n, axis=1)
        seg_q, pos_q = (cut(a, i * bq, bq)[:, :, None] for a in (seg, pos))
        slot_q = (i * bq + jnp.arange(bq))[None, :, None]

        def key_block(j, carry):
            m, l, acc = carry
            at = (first[i] + j) * bk
            kb, vb = cut(k, at, bk), cut(v, at, bk)       # (B, bk, g, d)
            seg_k, pos_k = (cut(a, at, bk)[:, None, :] for a in (seg, pos))
            see = _visible(seg_q, pos_q, slot_q, seg_k, pos_k,
                           (at + jnp.arange(bk))[None, None, :],
                           window)                           # (B, bq, bk)
            if choice is not None:
                see = see & (jax.lax.dynamic_slice(
                    choice[0], (0, i * bq, at), (b, bq, bk)) != 0)
            see = see[:, None, None]                      # (B,1,1,bq,bk)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                           preferred_element_type=f32) * scale
            s = jnp.where(see, s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(vb.dtype), vb,
                preferred_element_type=f32)
            return m_new, alpha * l + jnp.sum(p, axis=-1), acc

        if choice is not None:
            chosen = key_block
            key_block = lambda j, carry: jax.lax.cond(
                jnp.any(choice[1][:, i, first[i] + j] > 0),
                lambda: chosen(j, carry), lambda: carry)

        stats = (b, nkv, nh // nkv, bq)
        _m, l, acc = jax.lax.fori_loop(
            0, steps[i], key_block,
            (jnp.full(stats, _UNSEEN, f32), jnp.zeros(stats, f32),
             jnp.zeros(stats + (dv,), f32)))
        return acc * jnp.where(l > 0, 1.0 / l, 0.0)[..., None]

    out = jax.lax.map(query_block, jnp.arange(nq))      # (nq,B,g,r,bq,dv)
    return jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(b, t, nh, dv)


def _segment_body(lo_ref, count_ref, qdoc_ref, kdoc_ref, *refs, rep: int,
                  d: int, dv: int, bq: int, bk: int, nk: int, window,
                  scale: float, sparse: bool = False):
    """One key block of one query block of one key head's ``rep`` query
    heads. Blocks: q (bq, rep * d), o (bq, rep * dv); k (bk, d), v (bk,
    dv); qcols (bq, 128): the
    queries' document in lane 0 and position in lane 1; krows (8, bk): the
    keys' in sublanes 0 and 1. Scalar memory: the query block's first key
    block and its count, and for every query block and key block the
    document all its slots belong to (or a negative number where they do
    not share one). Vector memory, for the length of a query block: the
    heads' queries stacked as rows (rep * bq, d), the running max and sum
    (rep * bq, 1) and the accumulator (rep * bq, dv), float32. ``sparse``:
    one more scalar array, whether anything of a tile was chosen, and one
    more block, the tile (bq, bk) of the choice's mask, which is the
    block's mask whole (a chosen key is a visible one)."""
    from jax.experimental import pallas as pl

    if sparse:
        tiles_ref, q_ref, k_ref, v_ref, qcols_ref, krows_ref, choice_ref, \
            o_ref, qs_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, qcols_ref, krows_ref, o_ref, qs_ref, m_ref, \
            l_ref, acc_ref = refs
    row, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    at = row * pl.num_programs(2) + qi
    count = count_ref[at]
    # past the block's last step the index stays where it was: as the
    # blocks' index maps have it (``key_block`` of :func:`_segment_kernel`)
    kb = lo_ref[at] + jnp.minimum(j, jnp.maximum(count, 1) - 1)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        for r in range(rep):
            qs_ref[r * bq:(r + 1) * bq, :] = q_ref[:, r * d:(r + 1) * d]
        m_ref[...] = jnp.full(m_ref.shape, _UNSEEN, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def accumulate(see):
        s = jax.lax.dot_general(
            qs_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale                # (R, bk)
        if see is not None:
            # one mask for the block, added to every head's rows
            hide = jax.lax.select(see, jnp.zeros((bq, bk), f32),
                                  jnp.full((bq, bk), _MASKED, f32))
            s = (s.reshape(rep, bq, bk) + hide[None]).reshape(rep * bq, bk)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[...] = m_new

    live = j < count

    def on_an_edge():
        shape = (bq, bk)
        spread = lambda a: jnp.broadcast_to(a, shape)
        see = _visible(
            spread(qcols_ref[:, 0:1]), spread(qcols_ref[:, 1:2]),
            qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            spread(krows_ref[0:1, :]), spread(krows_ref[1:2, :]),
            kb * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1), window)
        accumulate(see)

    if sparse:
        pl.when(live & (tiles_ref[at * nk + kb] > 0))(
            lambda: accumulate(choice_ref[...].astype(jnp.int32) != 0))
    else:
        # every query of the block sees every key of the block: both lie
        # in one document, the keys wholly before the queries and inside
        # the window of the last of them. Only the other blocks build a
        # mask
        inside = (qdoc_ref[at] == kdoc_ref[row * nk + kb]) \
            & ((kb + 1) * bk - 1 <= qi * bq)
        if window is not None:
            inside = inside & ((qi + 1) * bq - 1 - kb * bk < window)
        pl.when(live & inside)(lambda: accumulate(None))
        pl.when(live & jnp.logical_not(inside))(on_an_edge)

    @pl.when(j == jnp.maximum(count, 1) - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] * jnp.where(l > 0, 1.0 / l, 0.0)
        for r in range(rep):
            o_ref[:, r * dv:(r + 1) * dv] = out[r * bq:(r + 1) * bq]


def _segment_kernel(q, k, v, seg, pos, lo, count, choice=None, *, window,
                    bq: int, bk: int, scale: float | None = None,
                    interpret: bool = False):
    """:func:`segment_attention` as one Pallas kernel. Grid (row, key
    head, query block, key-block step), the steps innermost: step ``j`` of
    a query block reads key block ``lo + j`` while ``j < count`` and stays
    on the last one after that, so that blocks outside a query block's
    range are neither fetched nor multiplied (``lo``, ``count``: scalar
    prefetch, :func:`_block_ranges`). The ``nh // nkv`` query heads of a
    key head are stacked as rows of one product, so a key block is read
    once for all of them; operands in the dtype given (bfloat16 on the
    chip), the running max, sum and accumulator float32 in vector memory;
    the mask is built only in blocks on an edge (the diagonal, the
    window's edge, a document's edge, padding). With a ``choice`` the
    block's mask is the tile of the choice's, fetched beside the keys, and
    a tile with nothing chosen is not multiplied."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, nh, d = q.shape
    nkv, dv = k.shape[2], v.shape[3]
    rep = nh // nkv
    nq, nk = t // bq, t // bk
    steps = _max_steps(t, window, bq, bk)

    def one_document(a, size, other):
        """(B, T / size): the document all slots of a block share, or
        ``other`` (negative) where they share none or it is padding."""
        blocks = a.reshape(b, t // size, size)
        first = blocks[..., 0]
        same = jnp.all(blocks == first[..., None], axis=-1) & (first >= 0)
        return jnp.where(same, first, other).astype(jnp.int32).reshape(-1)

    qcols = jnp.pad(jnp.stack([seg, pos], axis=-1),
                    ((0, 0), (0, 0), (0, 126)))              # (B, T, 128)
    krows = jnp.pad(jnp.stack([seg, pos], axis=1),
                    ((0, 0), (0, 6), (0, 0)))                # (B, 8, T)

    def key_block(r, g, i, j, lo_ref, count_ref, *_):
        at = r * nq + i
        return lo_ref[at] + jnp.minimum(
            j, jnp.maximum(count_ref[at], 1) - 1)

    queries = lambda r, g, i, j, *_: (r, i, g)
    keys = lambda r, g, i, j, *s: (r, key_block(r, g, i, j, *s), g)
    rows = rep * bq
    # with a choice: its tiles' counts behind the scalars, its mask's tile
    # behind the blocks
    sparse = choice is not None
    counts, mask_spec, mask = (), (), ()
    if sparse:
        mask, counts = (choice[0],), (choice[1].reshape(-1),)
        mask_spec = (pl.BlockSpec((None, bq, bk), lambda r, g, i, j, *s: (
            r, i, key_block(r, g, i, j, *s))),)
    out = pl.pallas_call(
        functools.partial(_segment_body, rep=rep, d=d, dv=dv, bq=bq, bk=bk,
                          nk=nk, window=window,
                          scale=d ** -0.5 if scale is None else scale,
                          **({"sparse": True} if sparse else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(counts),
            grid=(b, nkv, nq, steps),
            in_specs=[
                pl.BlockSpec((None, bq, rep * d), queries),
                pl.BlockSpec((None, bk, d), keys),
                pl.BlockSpec((None, bk, dv), keys),
                pl.BlockSpec((None, bq, 128),
                             lambda r, g, i, j, *_: (r, i, 0)),
                pl.BlockSpec((None, 8, bk), lambda r, g, i, j, *s: (
                    r, 0, key_block(r, g, i, j, *s))),
                *mask_spec,
            ],
            out_specs=pl.BlockSpec((None, bq, rep * dv), queries),
            scratch_shapes=[pltpu.VMEM((rows, d), q.dtype),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, t, nh * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(lo.reshape(-1), count.reshape(-1), one_document(seg, bq, -2),
      one_document(seg, bk, -3), *counts, q.reshape(b, t, nh * d),
      k.reshape(b, t, nkv * d), v.reshape(b, t, nkv * dv), qcols, krows,
      *mask)
    return out.reshape(b, t, nh, dv)

"""Fused (flash-style) attention Pallas kernel for the encoder.

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

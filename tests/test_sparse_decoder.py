"""The decoder of the GLM-5.2 pattern (models/decoder.py
``DecoderConfig.tiny_indexed``: latent attention over a learned choice of
keys, the choice an indexer's and shared by the layers behind it, a dense or
an expert feed-forward a layer, a sigmoid router, an ungated shared expert)
against its plain reference (benchmark/reference/glm_moe_dsa.py), the
indexer's choice (ops/attention.py ``select_keys``) against
``jax.lax.top_k`` query by query, the sparse core in both lowerings against
a dense masked softmax, and the router with sigmoid scores (ops/moe.py), at
a small size on the CPU."""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import glm_moe_dsa as reference  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402
from pathway_tpu.ops import attention, moe  # noqa: E402

TOPK = 24
CONFIG = decoder.DecoderConfig.tiny_indexed(compute_dtype=jnp.float32,
                                            max_len=128)
#: the same model as the benchmark's configuration file states one: the
#: published lists whole, the layers 2-5 of them held
REF_CONFIG = dict(
    vocab_size=CONFIG.vocab_size, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=32,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    published={"n_routed_experts": 8}, experts_held=[0, 8],
    n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=2.5,
    index_topk=TOPK, index_n_heads=2, index_head_dim=16,
    mlp_layer_types=["dense"] * 3 + ["sparse"] * 5,
    indexer_types=["full", "full", "full", "shared", "shared", "full",
                   "shared", "shared"],
    layers_held=[2, 6], rope_parameters={"rope_theta": 1e7},
    rms_norm_eps=1e-5)


@pytest.fixture(scope="module")
def weights():
    made = reference.weights(REF_CONFIG, 7)
    return dict(made, layers=list(made["layers"]))


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _one_minus_cos(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return 1.0 - np.sum(a * b, axis=1) / (
        np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1))


def _rows(t, docs):
    """(seg, pos) (rows, t) of rows that hold documents of the lengths
    ``docs[row]`` back to back, the rest padding."""
    seg = np.full((len(docs), t), -1, np.int32)
    pos = np.zeros((len(docs), t), np.int32)
    for row, lengths in enumerate(docs):
        at = 0
        for doc, n in enumerate(lengths):
            seg[row, at:at + n], pos[row, at:at + n] = doc, np.arange(n)
            at += n
    return seg, pos


def _visible(seg, pos):
    t = seg.shape[1]
    slot = np.arange(t)
    return (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] >= 0) \
        & (slot[None, None, :] <= slot[None, :, None])


def _index_operands(seg, heads=2, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    b, t = seg.shape
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    return normal(b, t, heads, dim), normal(b, t, dim), normal(b, t, heads)


def _top_k_sets(q, k, w, seg, pos, topk):
    """The choice by its definition, (B, T, T) bool: every visible pair's
    score, and ``jax.lax.top_k`` over each query's."""
    scores = (np.maximum(np.einsum("btjd,bsd->btjs", q, k), 0)
              * w[..., None]).sum(axis=2)
    see = _visible(seg, pos)
    want = np.zeros_like(see)
    for row, t in zip(*np.nonzero(seg >= 0)):
        if see[row, t].sum() <= topk:
            want[row, t] = see[row, t]
            continue
        _, best = jax.lax.top_k(jnp.where(see[row, t], scores[row, t],
                                          -jnp.inf), topk)
        want[row, t, np.asarray(best)] = True
    return want


# -- the indexer's choice -------------------------------------------------------

#: documents shorter than, as long as and longer than the 24 keys a query
#: keeps, their edges inside a block of 256 slots; a row of one document
DOCS = [(10, TOPK, 150, 40, 300), (640,)]


def test_the_choice_is_top_k_s_set_query_by_query():
    seg, pos = _rows(640, DOCS)
    q, k, w = _index_operands(seg)
    (mask, tiles), chosen = attention.select_keys(q, k, w, seg, pos,
                                                  topk=TOPK)
    got = np.asarray(mask)[:, :640, :640] != 0
    want = _top_k_sets(q, k, w, seg, pos, TOPK)
    assert (got == want).all()
    # beyond the row and at padding nothing is chosen; the count and the
    # tiles' counts are the mask's
    assert int(np.asarray(mask).sum()) == int(want.sum()) == int(chosen)
    # the tiles are counted at the choice's own 256 queries, whatever the
    # cores' query block
    (_, bk, padded), bq = attention.block_sizes(640, 1), 256
    assert tiles.shape == (2, padded // bq, padded // bk)
    assert (np.asarray(tiles) == np.asarray(mask).astype(np.int64).reshape(
        2, padded // bq, bq, padded // bk, bk).sum(axis=(2, 4))).all()
    # what the packer counts on the host from the documents' places
    reach = (pos + 1)[seg >= 0]
    work = attention.attention_work(seg, pos, (None,) * 4, TOPK, 2, rep=1)
    assert work["attn_pairs_full"] == reach.sum()
    assert work["attn_pairs_indexed"] == 2 * reach.sum()
    assert work["attn_pairs_selected"] == 4 * np.minimum(reach, TOPK).sum() \
        == 4 * int(chosen)
    assert "attn_pairs_indexed" not in attention.attention_work(
        seg, pos, (None,), rep=1)


def test_a_tie_at_the_edge_goes_to_the_earlier_key():
    """Scores that are equal to the last bit (one index head, keys that
    repeat): of the equals at a query's edge the earlier are kept, as
    ``top_k`` keeps them, and exactly ``topk`` in all."""
    seg, pos = _rows(64, [(64,)])
    q, k, w = _index_operands(seg, heads=1, dim=4, seed=3)
    k[0, 8:] = k[0, 8 + np.arange(56) % 4]          # four distinct keys
    w[:] = 1.0
    (mask, _tiles), _ = attention.select_keys(q, k, w, seg, pos, topk=10)
    got = np.asarray(mask)[:, :64, :64] != 0
    assert (got == _top_k_sets(q, k, w, seg, pos, 10)).all()
    assert (got.sum(axis=2)[0] == np.minimum(np.arange(64) + 1, 10)).all()


def test_with_the_whole_row_kept_the_core_is_latent_attention_s():
    """``index_topk`` at the row's length: every visible key is chosen and
    the sparse core gives what the core without a choice gives."""
    t = 256
    seg, pos = _rows(t, [(256,), (100, 60, 40)])
    rng = np.random.default_rng(2)
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    ops = (normal(2, t, 4, 16), normal(2, t, 4, 8), normal(2, t, 4, 16),
           normal(2, t, 8), normal(2, t, 4, 32), seg, pos)
    choice, chosen = attention.select_keys(*_index_operands(seg), seg, pos,
                                           topk=t)
    assert int(chosen) == _visible(seg, pos).sum()
    plain = np.asarray(attention.latent_attention(*ops, scale=24 ** -0.5))
    sparse = np.asarray(attention.latent_attention(*ops, scale=24 ** -0.5,
                                                   choice=choice))
    assert np.abs(sparse - plain)[seg >= 0].max() < 1e-6
    assert not sparse[seg < 0].any()
    # a narrower choice is another result
    narrow, _ = attention.select_keys(*_index_operands(seg), seg, pos,
                                      topk=TOPK)
    other = np.asarray(attention.latent_attention(*ops, scale=24 ** -0.5,
                                                  choice=narrow))
    assert np.abs(other - plain)[seg >= 0].max() > 1e-3


def _masked_softmax(q, k, v, mask, scale):
    s = np.einsum("bthd,bshd->bhts", q, k) * scale
    s = np.where(mask[:, None], s, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p = np.nan_to_num(p / p.sum(axis=-1, keepdims=True))
    return np.einsum("bhts,bshd->bthd", p, v)


def test_the_kernel_at_values_of_256_equals_the_blockwise_loop():
    """Heads with keys and values of 256 features (192 + 64, and the
    published 256) over a choice of 48 keys a query, two rows of two blocks
    of 256 slots (the grouped cores' blocks, which are the choice's own
    grain), through the interpreter: the sparse kernel against the sparse
    blockwise loop and the dense softmax over the chosen keys."""
    t, docs = 512, [(100, 300, 100), (512,)]
    seg, pos = _rows(t, docs)
    rng = np.random.default_rng(1)
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    q, k, v = normal(2, t, 4, 256), normal(2, t, 4, 256), normal(2, t, 4, 256)
    choice, _ = attention.select_keys(*_index_operands(seg), seg, pos,
                                      topk=48)
    mask = np.asarray(choice[0])[:, :t, :t] != 0
    want = _masked_softmax(q, k, v, mask, 256 ** -0.5)
    bq, bk, padded = attention.block_sizes(t, 2)
    assert (bq, bk, padded) == (256, 512, 512)
    assert attention._kernel_tiles(v.shape, padded)
    lo, count = attention._block_ranges(jnp, jnp.asarray(seg),
                                        jnp.asarray(pos), None, bq, bk)
    sizes = dict(window=None, bq=bq, bk=bk, scale=256 ** -0.5)
    loop = np.asarray(jax.jit(functools.partial(
        attention._blockwise, **sizes))(q, k, v, seg, pos, lo, count, choice))
    kernel = np.asarray(jax.jit(functools.partial(
        attention._segment_kernel, interpret=True, **sizes))(
            q, k, v, seg, pos, lo, count, choice))
    real = seg >= 0
    assert kernel.shape == loop.shape == v.shape
    assert np.abs(loop - want)[real].max() < 2e-5
    assert np.abs(kernel - loop)[real].max() < 2e-5
    assert not kernel[~real].any()
    # a tile in which nothing was chosen is skipped: its queries, which
    # see no other, read zeros
    emptied = (choice[0].at[1, 256:, :].set(0),
               choice[1].at[1, 1, 0].set(0))
    mask[1, 256:, :] = False
    want = _masked_softmax(q, k, v, mask, 256 ** -0.5)
    again = np.asarray(jax.jit(functools.partial(
        attention._segment_kernel, interpret=True, **sizes))(
            q, k, v, seg, pos, lo, count, emptied))
    assert np.abs(again - want)[real].max() < 2e-5
    assert not again[1, 256:].any() and again[1, :256].any()


# -- the query block of ungrouped heads --------------------------------------------

#: name: (slots a row, the documents' lengths row by row). Every document of
#: a first row starts and ends off every edge of a block of 256, 512 or
#: 1,024 slots; a second row is one document, whose blocks below the
#: diagonal build no mask
UNGROUPED_ROWS = {
    "one_key_block": (512, [(100, 300, 90), (512,)]),
    "two_key_blocks": (2048, [(300, 500, 700, 400), (2048,)]),
    "a_row_of_no_whole_block": (1200, [(300, 500, 390), (1200,)]),
}


@pytest.mark.parametrize("chosen", [False, True],
                         ids=["every_visible_key", "a_choice_of_48"])
@pytest.mark.parametrize("rows", UNGROUPED_ROWS)
def test_ungrouped_heads_run_query_blocks_as_long_as_key_blocks(rows,
                                                                chosen):
    """Two heads with keys of their own (``rep`` 1): ``block_sizes`` gives
    as many queries a block as keys (512 on a row of 512, 1,024 on a longer
    one), and at them the kernel through the interpreter, the blockwise
    loop and ``segment_attention`` as the CPU runs it equal a plain masked
    softmax, over every visible key and over a choice; the choice's tile
    counts, made at 256 queries, summed to the core's tiles are the mask's
    sums there, and a core's tile whose count is 0 is not multiplied; the
    host counts the tiles the device's ranges admit."""
    t, docs = UNGROUPED_ROWS[rows]
    seg, pos = _rows(t, docs)
    rng = np.random.default_rng(5)
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    q, k, v = normal(2, t, 2, 128), normal(2, t, 2, 128), normal(2, t, 2, 128)
    bq, bk, padded = attention.block_sizes(t, q.shape[2] // k.shape[2])
    assert (bq, bk, padded) == ((512, 512, 512) if t == 512
                                else (1024, 1024, 2048))
    nq, nk = padded // bq, padded // bk
    mask, choice = _visible(seg, pos), None
    if chosen:
        choice, _ = attention.select_keys(*_index_operands(seg), seg, pos,
                                          topk=48)
        whole = np.asarray(choice[0]).astype(np.int64)
        mask = whole[:, :t, :t] != 0
        assert choice[1].shape == (2, padded // 256, nk)
        summed = np.asarray(choice[1]).reshape(2, nq, -1, nk).sum(axis=2)
        assert (summed == whole.reshape(2, nq, bq, nk, bk).sum(
            axis=(2, 4))).all()
    want = _masked_softmax(q, k, v, mask, 128 ** -0.5)
    real = seg >= 0
    # the core as the CPU runs it: the blockwise loop at the larger block
    before = attention.attention_lowerings().get(f"query_block_{bq}", 0)
    got = np.asarray(attention.segment_attention(q, k, v, seg, pos,
                                                 choice=choice))
    assert attention.attention_lowerings()[f"query_block_{bq}"] >= max(
        before, 1)
    assert np.abs(got - want)[real].max() < 2e-5 and not got[~real].any()
    # both lowerings themselves, on the row padded to whole blocks
    grow = ((0, 0), (0, padded - t))
    wide = [jnp.pad(a, grow + ((0, 0), (0, 0))) for a in (q, k, v)] \
        + [jnp.pad(seg, grow, constant_values=-1), jnp.pad(pos, grow)]
    lo, count = attention._block_ranges(jnp, wide[3], wide[4], None, bq, bk)
    at_core = () if choice is None else ((choice[0], jnp.asarray(summed)),)
    sizes = dict(window=None, bq=bq, bk=bk)
    lowerings = (attention._blockwise, functools.partial(
        attention._segment_kernel, interpret=True))
    for lowering in lowerings:
        out = np.asarray(jax.jit(functools.partial(lowering, **sizes))(
            *wide, lo, count, *at_core))[:, :t]
        assert np.abs(out - want)[real].max() < 2e-5, lowering
        assert not out[~real].any()
    # the host's count is the device's, at the grain the core runs
    work = attention.attention_work(seg, pos, (None,) * 3, rep=1)
    assert work["attn_query_block"] == bq
    assert work["attn_tiles_run"] == 3 * int(np.asarray(count).sum())
    assert work["attn_tiles_all"] == 3 * 2 * sum(
        ((i + 1) * bq - 1) // bk + 1 for i in range(nq))
    if not chosen:
        return
    # a row's first query block sees key block 0 alone. With that tile's
    # count at 0 neither lowering multiplies it, whatever its mask holds
    # (the loop walks a step while any row's tile holds a key: both rows'
    # are emptied): its queries read zeros, every other one what it read
    emptied = (choice[0], jnp.asarray(summed).at[:, 0, 0].set(0))
    for lowering in lowerings:
        out = np.asarray(jax.jit(functools.partial(lowering, **sizes))(
            *wide, lo, count, emptied))[:, :t]
        assert not out[:, :bq].any()
        assert np.abs(out - want)[:, bq:][real[:, bq:]].max(initial=0) < 2e-5
    # ``segment_attention`` sums the choice's own tiles: with one of the two
    # or four that make the core's at 0 the tile is still multiplied, with
    # all of them not
    half = (choice[0], choice[1].at[:, 0, 0].set(0))
    both = (choice[0], choice[1].at[:, :bq // 256, 0].set(0))
    still = np.asarray(attention.segment_attention(q, k, v, seg, pos,
                                                   choice=half))
    assert np.abs(still - want)[real].max() < 2e-5
    gone = np.asarray(attention.segment_attention(q, k, v, seg, pos,
                                                  choice=both))
    assert not gone[:, :bq].any() and gone[:, bq:].any() == (t > bq)
    assert np.abs(gone - want)[:, bq:][real[:, bq:]].max(initial=0) < 2e-5


# -- the router -------------------------------------------------------------------

def test_the_sigmoid_router_s_weights_sum_to_the_scaling_factor():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((50, 64), dtype=np.float32)
    router = rng.standard_normal((64, 16), dtype=np.float32) * 0.3
    bias = rng.standard_normal(16).astype(np.float32) * 0.2
    weights, experts = moe.route(x, router, 4, True, bias, 2.5, "sigmoid")
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    want = np.argsort(-(scores + bias), axis=1)[:, :4]
    assert (np.sort(np.asarray(experts), axis=1) == np.sort(want, axis=1)).all()
    assert np.allclose(np.asarray(weights).sum(axis=1), 2.5, atol=1e-5)
    # the weights are the scores themselves, renormalised: the bias chooses
    # and weighs nothing
    picked = np.take_along_axis(scores, np.asarray(experts), axis=1)
    assert np.allclose(np.asarray(weights),
                       picked / picked.sum(axis=1, keepdims=True) * 2.5,
                       atol=1e-5)
    # softmax stays what it was, and another name is refused
    soft, _ = moe.route(x, router, 4, False)
    assert np.all(np.asarray(soft).sum(axis=1) < 1.0)
    with pytest.raises(ValueError, match="sigmoid"):
        moe.route(x, router, 4, True, None, 1.0, "tanh")


def test_the_shares_add_up():
    """Over all 16 held ranges of 32 experts the routed parts, with the
    shared expert and what every chip computes alike counted once, equal
    the expert layer that holds every expert."""
    whole = decoder.DecoderConfig.tiny_indexed(
        compute_dtype=jnp.float32, num_experts=32, num_experts_per_tok=4)
    p = decoder.init_params(jax.random.PRNGKey(3), whole)["layers"][1]["moe"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 40, 64), dtype=np.float32))
    valid = jnp.ones((2, 40), bool)
    want, counters = decoder.moe_layer(x, p, valid, whole)
    assert int(counters["tokens_per_expert"].sum()) == 2 * 40 * 4
    # the shared expert's part is every chip's: a chip with no held pair
    # gives it alone
    shared = jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"]) \
        @ p["shared_down"]
    total = np.zeros_like(np.asarray(want))
    for lo in range(0, 32, 2):
        held = decoder.DecoderConfig.tiny_indexed(
            compute_dtype=jnp.float32, num_experts=32, num_experts_per_tok=4,
            experts_held=(lo, lo + 2))
        part = dict(p, **{name: p[name][lo:lo + 2]
                          for name in ("gate", "up", "down")})
        got, _ = decoder.moe_layer(x, part, valid, held)
        total += np.asarray(got - shared)
    assert np.abs(total + np.asarray(shared) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(shared)).max() > 1e-3


# -- the configuration ------------------------------------------------------------

def test_the_configuration_names_its_layers():
    kinds = [CONFIG.layer_kind(i) for i in range(4)]
    assert [k.mixer for k in kinds] == ["indexed"] * 4
    assert [k.indexer for k in kinds] == ["full", "shared", "shared", "full"]
    assert [k.mlp for k in kinds] == ["dense", "sparse", "sparse", "sparse"]
    assert CONFIG.attention_windows == (None,) * 4
    assert CONFIG.attention_index == (TOPK, 2)
    assert CONFIG.shared_width == 32
    assert decoder.DecoderConfig.tiny_latent().attention_index is None
    tree = decoder.init_params(jax.random.PRNGKey(0), CONFIG)["layers"]
    assert ["indexer" in layer["mixer"] for layer in tree] \
        == [True, False, False, True]
    assert ["ffn" in layer for layer in tree] == [True, False, False, False]
    assert "shared_router" not in tree[1]["moe"] and "bias" in tree[1]["moe"]
    # latent attention with neither identity experts nor an indexer is
    # refused as it was; a shared layer needs a full one before it
    with pytest.raises(ValueError, match="identity"):
        decoder.DecoderConfig.tiny_latent(
            zero_expert_type="copy").layer_kind(0)
    with pytest.raises(ValueError, match="shared"):
        decoder.DecoderConfig.tiny_indexed(
            indexer_types=("shared", "full", "shared", "shared")
        ).layer_kind(0)


# -- the decoder against its reference --------------------------------------------

def _packed(docs, rng, width=128):
    """``encode_ragged``'s operands for rows of documents of the lengths
    ``docs[row]``, and the documents' ids padded one a row."""
    seg, pos = _rows(width, docs)
    ids = np.where(seg >= 0, rng.integers(1, CONFIG.vocab_size, seg.shape), 0)
    rows, offs, texts, lens = [], [], [], []
    for row, lengths in enumerate(docs):
        at = 0
        for n in lengths:
            rows.append(row)
            offs.append(at + n - 1)
            texts.append(np.pad(ids[row, at:at + n], (0, width - n)))
            lens.append(n)
            at += n
    return (ids.astype(np.int32), seg, pos, np.asarray(rows, np.int32),
            np.asarray(offs, np.int32)), np.stack(texts), np.asarray(lens)


#: two rows of 128 slots: documents shorter than, as long as and longer
#: than the 24 keys kept, and one that fills a row
PACKED = [(10, TOPK, 60, 34), (128,)]


def test_the_forward_equals_the_reference(weights):
    args, texts, lens = _packed(PACKED, np.random.default_rng(0))
    want = reference.embed(weights, texts, lens, REF_CONFIG)
    got, aux = jax.jit(CONFIG.encode_ragged)(weights, *args)
    assert _one_minus_cos(got, want).max() < 1e-5
    # the device's own counts: what the attention layers attended over, of
    # what they could see; the packer's count from the places is the same
    reach = (args[2] + 1)[args[1] >= 0]
    assert float(aux["visible_pairs"]) == 4 * reach.sum()
    assert float(aux["selected_pairs"]) == 4 * np.minimum(reach, TOPK).sum()
    work = attention.attention_work(args[1], args[2],
                                    CONFIG.attention_windows,
                                    *CONFIG.attention_index,
                                    rep=CONFIG.attention_rep)
    assert work["attn_pairs_selected"] == float(aux["selected_pairs"])
    assert int(aux["buffer"][0]) == 3          # three expert layers ran
    # a padded batch runs the same forward
    mask = np.arange(128)[None, :] < lens[:, None]
    padded, _ = jax.jit(CONFIG.encode)(weights, texts, mask)
    assert _one_minus_cos(padded, want).max() < 1e-5
    # both controls are other models: at this size the int8 one by a
    # little, the one that attends over every visible key by a lot, in the
    # documents that are longer than the choice alone
    dense = reference.control(weights, texts, lens, REF_CONFIG, "dense")
    gap = _one_minus_cos(dense, want)
    assert gap[lens <= TOPK].max() < 1e-6 and gap[lens > TOPK].min() > 1e-3
    int8 = reference.control(weights, texts, lens, REF_CONFIG, "int8")
    assert _one_minus_cos(int8, want).min() > 1e-5


def test_heads_in_groups_give_what_all_heads_at_once_give(weights,
                                                          monkeypatch):
    args, _texts, _lens = _packed(PACKED, np.random.default_rng(1))
    at_once, _ = jax.jit(CONFIG.encode_ragged)(weights, *args)
    monkeypatch.setattr(decoder, "HEAD_GROUP", 2)
    in_groups, _ = jax.jit(CONFIG.encode_ragged)(weights, *args)
    assert _one_minus_cos(in_groups, at_once).max() < 1e-6


def test_a_shared_layer_attends_over_its_full_layer_s_choice(weights):
    """Layer 1 has no indexer: whatever its input, the keys it attends over
    are layer 0's, handed on as a value; and its output over them is the
    reference's layer over the same choice."""
    args, _texts, _lens = _packed([(100,)], np.random.default_rng(2))
    _ids, seg, pos = (jnp.asarray(a) for a in args[:3])
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 128, 64), dtype=np.float32))
    layers = weights["layers"]
    _y, choice, chosen = decoder.indexed_attention_layer(
        x, layers[0]["mixer"], pos, seg, CONFIG)
    assert int(chosen) == np.minimum(np.arange(100) + 1, TOPK).sum()
    outs = []
    for other in (x, -x, jnp.roll(x, 1, axis=1)):
        y, same, made = decoder.indexed_attention_layer(
            other, layers[1]["mixer"], pos, seg, CONFIG, choice)
        assert same is choice and made is None
        outs.append(np.asarray(y))
    assert np.abs(outs[0] - outs[1]).max() > 1e-3
    # the reference's attention of the shared layer over that choice
    sizes = reference._sizes(REF_CONFIG)
    mask = jnp.asarray(np.asarray(choice[0])[0, :128, :128] != 0)
    want, _ = reference._mla(x[0], layers[1]["mixer"], sizes, jnp.matmul,
                             100, mask)
    assert np.abs(outs[0][0, :100] - np.asarray(want)[:100]).max() < 1e-5
    # the whole forward hands layer 0's choice to layers 1 and 2 and layer
    # 3's own to layer 3: the reference's layers, one after the other
    state = weights["embed"][np.asarray(args[0])[0, :100]]
    chosen_ref = None
    kinds = list(zip(sizes["mlp"], sizes["indexers"]))
    for layer, kind in zip(layers, kinds):
        state, chosen_ref = reference._layer(jnp.asarray(state), layer,
                                             sizes, jnp.matmul, kind,
                                             chosen_ref)
    got, _ = decoder._forward(weights, jnp.asarray(args[0]), pos, seg, CONFIG)
    want = reference._rms_norm(state, weights["final_norm"], 1e-5)
    assert np.abs(np.asarray(got)[0, :100] - np.asarray(want)).max() < 1e-4


# -- what the program says of itself ----------------------------------------------

def test_the_scopes_and_the_lowering_for_the_chip():
    """Lowered for the TPU at the published head (keys 192 + 64, values
    256) a layer with an indexer carries the sparse kernel under
    ``decoder.attention.sparse``, its projections under
    ``decoder.attention.latent`` and the indexer under
    ``decoder.attention.index``; a shared layer no indexer; lowered for the
    CPU the sparse blockwise loop."""
    wide = decoder.DecoderConfig.tiny_indexed(
        num_attention_heads=2, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, index_head_dim=128, num_hidden_layers=2,
        mlp_layer_types=("dense", "sparse"),
        indexer_types=("full", "shared"))
    tree = jax.eval_shape(lambda key: decoder.init_params(key, wide),
                          jax.random.PRNGKey(0))["layers"]
    x = jax.ShapeDtypeStruct((1, 512, wide.hidden_size), jnp.float32)
    pos = jax.ShapeDtypeStruct((1, 512), jnp.int32)

    def lowered(platform):
        def both(x, layers, pos):
            y, choice, _ = decoder.indexed_attention_layer(
                x, layers[0]["mixer"], pos, pos, wide)
            z, _, _ = decoder.indexed_attention_layer(
                y, layers[1]["mixer"], pos, pos, wide, choice)
            return z

        before = attention.attention_lowerings()
        text = jax.jit(both).trace(x, tree, pos).lower(
            lowering_platforms=(platform,)).as_text(debug_info=True)
        after = attention.attention_lowerings()
        return text, {name: after[name] - before.get(name, 0)
                      for name in after
                      if after[name] != before.get(name, 0)}

    text, took = lowered("tpu")
    # the two layers' cores share one trace and one lowering, at the
    # query block of ungrouped heads
    assert took == {"sparse_kernel": 1, "query_block_512": 1}
    assert text.count("tpu_custom_call") >= 1
    for scope in ("decoder.attention.latent", "decoder.attention.index",
                  "decoder.attention.sparse"):
        assert scope in text, scope
    assert "decoder.attention.full" not in text
    _text, took = lowered("cpu")
    assert took == {"sparse_blockwise": 1, "query_block_512": 1}


def test_the_embedder_counts_the_pairs_and_metrics_show_them(weights):
    """Through ``JaxEncoderEmbedder``: the packer's count of a dispatch
    (the ``embedder.dispatch`` span's fields) and the device's own, summed
    by ``expert_load()`` and shown by ``/metrics``."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)

    from pathway_tpu.models.tokenizer import HashTokenizer
    from pathway_tpu.xpacks.llm.embedders import (JaxEncoderEmbedder,
                                                  expert_load_stats)

    emb = JaxEncoderEmbedder(
        config=CONFIG, params=weights, max_len=128, ragged=True,
        ragged_max_seqs=2,
        tokenizer=HashTokenizer(vocab_size=CONFIG.vocab_size, max_len=128))
    texts = [" ".join(f"w{i}" for i in range(n)) for n in (5, 30, 90, 126)]
    chunks = emb.pack_ragged(texts)
    selected = visible = 0
    for args, _n_docs, _n_pad in chunks:
        emb._embeddings(emb.encode_ragged_chunk(args))
        work = emb.dispatch_work(args)
        reach = (args[2] + 1)[args[1] >= 0]
        assert work["attn_pairs_indexed"] == 2 * reach.sum()
        assert work["attn_pairs_selected"] \
            == 4 * np.minimum(reach, TOPK).sum()
        selected += work["attn_pairs_selected"]
        visible += 4 * work["attn_pairs_full"]
    load = emb.expert_load()
    assert load["selected_pairs"] == selected
    assert load["visible_pairs"] == visible
    assert 0 < selected < visible
    assert expert_load_stats()["selected_pairs"] >= selected
    samples = {family: value for family, _labels, value in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    assert samples["pathway_tpu_attention_pairs_selected"] >= selected
    assert samples["pathway_tpu_attention_pairs_visible"] >= visible
    assert samples["pathway_tpu_attention_pairs_selected"] \
        < samples["pathway_tpu_attention_pairs_visible"]

"""The blocked attention over packed rows (ops/attention.py
``segment_attention``: the plain blockwise lowering, and the Pallas kernel
through the interpreter) against a dense masked softmax, and the decoder of
the SmallThinker pattern (models/decoder.py ``DecoderConfig.tiny_windowed``:
one period of a full layer without positions and three window layers, ReGLU
experts, the router on the mixer's input) against its plain reference
(benchmark/reference/smallthinker.py), at a small size on the CPU."""

from __future__ import annotations

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import smallthinker as reference  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402
from pathway_tpu.ops import attention  # noqa: E402

WINDOW = 24
CONFIG = decoder.DecoderConfig.tiny_windowed(compute_dtype=jnp.float32,
                                             max_len=128)
#: the same model as the benchmark's configuration file states one
REF_CONFIG = dict(
    vocab_size=CONFIG.vocab_size, hidden_size=64, num_hidden_layers=4,
    rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, rope_theta=1.5e6, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], sliding_window_size=WINDOW,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2,
    moe_ffn_hidden_size=32, norm_topk_prob=True)


@pytest.fixture(scope="module")
def weights():
    return reference.weights(REF_CONFIG, 7)


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
    ``docs[row]`` back to back, as the packer lays them."""
    seg = np.full((len(docs), t), -1, np.int32)
    pos = np.zeros((len(docs), t), np.int32)
    for r, lengths in enumerate(docs):
        at = 0
        for j, n in enumerate(lengths):
            seg[r, at:at + n], pos[r, at:at + n] = j, np.arange(n)
            at += n
    return seg, pos


def _dense(q, k, v, seg, pos, window):
    """The definition: the whole masked score tensor, in float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    t, rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (np.repeat(a, rep, axis=2) for a in (k, v))
    at = np.arange(t)
    see = (seg[:, :, None] == seg[:, None, :]) & (seg >= 0)[:, None, :] \
        & (at[None, :, None] >= at[None, None, :])
    if window is not None:
        see &= pos[:, :, None] - pos[:, None, :] < window
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = np.where(see[:, None], scores, -np.inf)
    top = np.where(see.any(-1)[:, None], scores.max(-1), 0.0)
    p = np.where(see[:, None], np.exp(scores - top[..., None]), 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-300)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


#: name: (slots a row, the documents' lengths row by row). The lowerings
#: are walked with query blocks of 128 and key blocks of 256 (a row of 512
#: slots is four on two): the sizes of ``block_sizes`` at a quarter, which
#: the interpreter gets through sixteen times sooner.
ROWS = {
    "one_document": (512, [(512,)]),
    "a_packed_row_of_several": (512, [(175, 5, 75, 257), (1, 511)]),
    # longer than the window of 150 by more than a key block
    "a_document_longer_than_the_window": (512, [(475, 37)]),
    # the window's edge of the queries 256-383 lies inside key block 0 and
    # that of 448-511 inside key block 1
    "a_window_s_edge_inside_a_block": (512, [(10, 502)]),
    # the last query block and a third of a key block hold padding
    "padding_at_the_end": (512, [(250, 100)]),
    # a row that is no whole number of blocks is padded to one
    "a_row_of_no_whole_block": (300, [(175, 100)]),
}
BQ, BK = 128, 256


def _operands(t, docs, heads, d=128, seed=0):
    rng = np.random.default_rng(seed)
    b = len(docs)
    q = rng.standard_normal((b, t, heads, d)).astype(np.float32)
    k, v = (rng.standard_normal((b, t, 1, d)).astype(np.float32)
            for _ in range(2))
    return (q, k, v) + _rows(t, docs)


@pytest.mark.parametrize("heads", [7, 8])
@pytest.mark.parametrize("window", [None, 150])
@pytest.mark.parametrize("rows", ROWS)
def test_both_lowerings_equal_the_dense_masked_softmax(rows, window, heads):
    """The blockwise lowering and the kernel through the interpreter,
    grouped heads 7:1 and 8:1, against the whole masked score tensor, at
    the real slots; padding reads zeros; a block no query can see is not
    walked. ``segment_attention`` itself, as the CPU runs it, agrees."""
    t, docs = ROWS[rows]
    q, k, v, seg, pos = _operands(t, docs, heads)
    want = _dense(q, k, v, seg, pos, window)
    real = seg >= 0
    before = attention.attention_lowerings()
    got = np.asarray(attention.segment_attention(q, k, v, seg, pos,
                                                 window=window))
    after = attention.attention_lowerings()
    assert got.shape == q.shape and got.dtype == np.float32
    assert np.abs(got - want)[real].max() < 2e-5
    assert not got[~real].any()
    assert after["kernel"] == before["kernel"] \
        and after["blockwise"] >= before["blockwise"]
    grow = ((0, 0), (0, 512 - t))
    wide = [jnp.pad(a, grow + ((0, 0), (0, 0))) for a in (q, k, v)] \
        + [jnp.pad(seg, grow, constant_values=-1), jnp.pad(pos, grow)]
    lo, count = attention._block_ranges(jnp, wide[3], wide[4], window, BQ,
                                        BK)
    # jitted: op by op the interpreter compiles each primitive alone
    for lowering in (attention._blockwise, functools.partial(
            attention._segment_kernel, interpret=True)):
        out = np.asarray(jax.jit(lambda *a: lowering(
            *a, window=window, bq=BQ, bk=BK))(*wide, lo, count))[:, :t]
        assert np.abs(out - want)[real].max() < 2e-5, lowering
        assert not out[~real].any()
    # the ranges: never past the diagonal, nothing for a block of padding,
    # and one key block where the window and the documents allow it
    diagonal = (np.arange(1, 512 // BQ + 1) * BQ - 1) // BK + 1
    assert (np.asarray(lo + count) <= diagonal).all()
    assert (np.asarray(count)[~np.pad(real, grow).reshape(
        len(docs), -1, BQ).any(-1)] == 0).all()
    if rows == "a_window_s_edge_inside_a_block" and window:
        # the queries from 448 on see keys from 299 on: key block 1 alone
        assert np.asarray(lo).tolist() == [[0, 0, 0, 0]]
        assert np.asarray(count).tolist() == [[1, 1, 2, 2]]
    if rows == "one_document" and window:
        # the queries 384-511 see keys from 235 on: the window spares the
        # fourth query block nothing, a longer row's blocks it does
        assert np.asarray(count).tolist() == [[1, 1, 2, 2]]
    if rows == "padding_at_the_end":
        # the second document starts at slot 250, inside key block 0
        assert np.asarray(count).tolist() == [[1, 1, 2, 0]]


def test_a_window_spares_whole_key_blocks_of_a_long_row():
    """A row of 2,048 slots, one document, at the sizes of
    ``block_sizes`` for grouped heads: eight query blocks of 256 on two key
    blocks of 1,024. Under a window of 600 the last query block sees key
    block 1 alone."""
    q, k, v, seg, pos = _operands(2048, [(2048,)], 2, d=32)
    assert attention.block_sizes(2048, 2) == (256, 1024, 2048)
    assert attention.block_sizes(16384, 2) == (256, 1024, 16384)
    assert attention.block_sizes(512, 2) == (256, 512, 512)
    assert attention.block_sizes(1200, 2) == (256, 1024, 2048)
    assert attention.block_sizes(128, 2) == (128, 128, 128)
    lo, count = attention._block_ranges(np, seg, pos, 600, 256, 1024)
    assert lo.tolist() == [[0, 0, 0, 0, 0, 0, 0, 1]]
    assert count.tolist() == [[1, 1, 1, 1, 2, 2, 2, 1]]
    got = np.asarray(attention.segment_attention(q, k, v, seg, pos,
                                                 window=600))
    assert np.abs(got - _dense(q, k, v, seg, pos, 600)).max() < 2e-5


@pytest.mark.parametrize("t, grouped, ungrouped", [
    (16384, (256, 1024, 16384), (1024, 1024, 16384)),
    (8192, (256, 1024, 8192), (1024, 1024, 8192)),
    (2048, (256, 1024, 2048), (1024, 1024, 2048)),
    (1200, (256, 1024, 2048), (1024, 1024, 2048)),
    (1024, (256, 1024, 1024), (1024, 1024, 1024)),
    # key blocks of 512: the query block is never the larger
    (600, (256, 512, 1024), (512, 512, 1024)),
    (512, (256, 512, 512), (512, 512, 512)),
    (300, (256, 256, 512), (256, 256, 512)),
    (256, (256, 256, 256), (256, 256, 256)),
    (128, (128, 128, 128), (128, 128, 128)),
])
def test_the_query_block_follows_the_heads_a_key_head_stacks(t, grouped,
                                                             ungrouped):
    """``block_sizes``: with two or more query heads a key head every row
    reads what it read before the stacking was an argument (SmallThinker's
    7, Qwen's 8); a head with keys of its own takes as many queries a
    block as keys, 1,024 on a long row; the key block and the padded length
    follow the row alone."""
    for rep in (2, 7, 8, 64):
        assert attention.block_sizes(t, rep) == grouped
    assert attention.block_sizes(t, 1) == ungrouped
    bq, bk, padded = ungrouped
    assert ungrouped[1:] == grouped[1:]
    assert padded % bq == 0 and bq == bk and padded % bk == 0


def test_no_array_of_the_blockwise_lowering_has_two_axes_of_the_row():
    """The lowered text of the core at rows of 2,048 slots holds no tensor
    with two axes of 2,048 (nor of any multiple): the largest is one
    block's scores, 256 x 1,024 a head. The dense form it replaces does."""
    t = 2048
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((1, t, 4, 32), (1, t, 2, 32), (1, t, 2, 32))] \
        + [jax.ShapeDtypeStruct((1, t), jnp.int32)] * 2
    square = re.compile(rf"tensor<(\d+x)*{t}x(\d+x)*{t}x")
    for window in (None, 700):
        text = jax.jit(lambda *a: attention.segment_attention(
            *a, window=window)).lower(*shapes).as_text()
        assert "x256x1024xf32>" in text and not square.search(text)

    def dense(q, k, v, seg, pos):
        scores = jnp.einsum("bqgd,bkgd->bgqk", q[:, :, ::2], k)
        return jnp.einsum("bgqk,bkgd->bqgd", jax.nn.softmax(scores), v)

    assert square.search(jax.jit(dense).lower(*shapes).as_text())


def test_the_kernel_is_taken_for_the_chip_at_its_shapes_alone():
    """Lowered for the TPU with heads of 128 features an attention layer
    carries one kernel call under its scope; lowered for the CPU, or with
    heads of 32 features, none. The counter says which lowering a program
    took, as ``/metrics`` shows it."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)

    def lowered(config, platform, kind, layer=0):
        p = jax.eval_shape(lambda key: decoder.init_params(key, config),
                           jax.random.PRNGKey(0))["layers"][layer]["mixer"]
        x = jax.ShapeDtypeStruct((2, 512, config.hidden_size), jnp.float32)
        pos = jax.ShapeDtypeStruct((2, 512), jnp.int32)
        before = attention.attention_lowerings()
        text = jax.jit(lambda x, p, pos: decoder.attention_layer(
            x, p, pos, pos, config, kind)).trace(x, p, pos).lower(
                lowering_platforms=(platform,)).as_text(debug_info=True)
        after = attention.attention_lowerings()
        # the counts this lowering added; what else the process has
        # lowered before (another file's cores) is none of it
        return text, {name: after[name] - before.get(name, 0)
                      for name in after
                      if after[name] != before.get(name, 0)}

    window = decoder.LayerKind("attention", 200, True)
    full = decoder.LayerKind("attention", None, False)
    wide = decoder.DecoderConfig.tiny_windowed(head_dim=128,
                                               num_attention_heads=7,
                                               num_key_value_heads=1)
    for kind, scope in ((window, "decoder.attention.window"),
                        (full, "decoder.attention.full")):
        text, took = lowered(wide, "tpu", kind)
        assert took == {"kernel": 1}
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        assert len(calls) == 1 and "_segment_body" in calls[0]
        assert scope in text
    text, took = lowered(wide, "cpu", window)
    assert took == {"blockwise": 1}
    assert "tpu_custom_call" not in text
    text, took = lowered(decoder.DecoderConfig.tiny_windowed(), "tpu", window)
    assert took == {"blockwise": 1}
    assert "tpu_custom_call" not in text
    # the other family's attention layer takes the same core
    text, took = lowered(decoder.DecoderConfig.tiny(head_dim=128), "tpu",
                         decoder.LayerKind("attention"), layer=3)
    assert took == {"kernel": 1}
    assert "decoder.attention.full" in text
    samples = {(f, labels.get("lowering")): v for f, labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    counted = attention.attention_lowerings()
    assert counted["kernel"] >= 3 and counted["blockwise"] >= 2
    for name in ("kernel", "blockwise"):
        assert samples["pathway_tpu_attention_programs", name] \
            == counted[name]


def test_a_layer_s_kind_follows_from_the_published_keys():
    qwen = decoder.DecoderConfig.tiny()
    assert [qwen.layer_kind(i).mixer for i in range(4)] \
        == ["deltanet"] * 3 + ["attention"]
    assert qwen.attention_windows == (None,)
    assert qwen.layer_kind(3) == decoder.LayerKind("attention", None, True)
    kinds = [CONFIG.layer_kind(i) for i in range(4)]
    assert kinds == [decoder.LayerKind("attention", None, False)] \
        + [decoder.LayerKind("attention", WINDOW, True)] * 3
    assert CONFIG.attention_windows == (None, WINDOW, WINDOW, WINDOW)
    assert all(CONFIG.is_attention(i) for i in range(4))


def test_reference_weights_are_the_program_s_tree(weights):
    again = reference.weights(REF_CONFIG, 7)
    other = reference.weights(REF_CONFIG, 8)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b)
               for a, b in zip(leaves(weights), leaves(again)))
    assert not np.array_equal(weights["embed"], other["embed"])
    tree = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    made = decoder.init_params(jax.random.PRNGKey(0), CONFIG)
    assert tree(weights) == tree(made)
    # no gate's half of q_proj, no q/k norm, no shared expert; plain norms
    assert set(made["layers"][0]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                               "o_proj"}
    assert set(made["layers"][0]["moe"]) == {"router", "gate", "up", "down"}
    assert float(made["final_norm"].min()) == 1.0
    assert abs(float(weights["embed"].std()) - 0.02) < 1e-3


def test_padded_batch_agrees_with_the_reference(weights):
    """Documents shorter than the window of 24, at it, one over it and
    five times it."""
    rng = np.random.default_rng(0)
    lens = np.array([100, 65, 24, 25, 1, 128])
    ids = rng.integers(0, CONFIG.vocab_size, (len(lens), 128)).astype(
        np.int32)
    mask = np.arange(128)[None] < lens[:, None]
    got, load = jax.jit(CONFIG.encode)(weights, ids, mask)
    want = reference.embed(weights, ids, lens, REF_CONFIG)
    # seeded weights of deviation 0.02 leave the scores nearly level, so
    # what attention is told apart by is small: the agreement is asked to
    # 1e-6 (it reads 1e-7), where rotary on the first layer reads 8e-6
    assert _one_minus_cos(got, want).max() < 1e-6
    assert int(load["tokens_per_expert"].sum()) == int(lens.sum()) * 2 * 4
    # what the reference is told apart by: the window, the positions, the
    # router's input, the experts' activation
    for changed in (dict(sliding_window_size=WINDOW + 1),
                    dict(rope_layout=(1, 1, 1, 1)),
                    dict(router_input="moe_input"),
                    dict(hidden_act="silu")):
        other, _ = jax.jit(decoder.DecoderConfig.tiny_windowed(
            compute_dtype=jnp.float32, max_len=128, **changed).encode)(
                weights, ids, mask)
        assert _one_minus_cos(other, want)[0] > 3e-6, changed


def _embedder(weights, **kw):
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    return JaxEncoderEmbedder(config=CONFIG, params=jax.device_put(weights),
                              max_len=128, **kw)


def _texts(lengths):
    rng = np.random.default_rng(1)
    return [" ".join(f"w{rng.integers(0, 300)}" for _ in range(n))
            for n in lengths]


def test_a_packed_row_of_documents_equals_the_documents_alone(weights):
    """Attention's reach, the window and the rotary positions all restart
    at a document's first token."""
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    texts = _texts((30, 50, 20, 100, 3, 60))
    together = np.asarray(emb.encode_batch_device(texts))
    alone = np.concatenate([np.asarray(emb.encode_batch_device([t]))
                            for t in texts])
    assert np.abs(together - alone).max() < 1e-5
    ids, mask = emb.tokenizer.batch(texts, max_len=128)
    want = reference.embed(weights, ids, mask.sum(axis=1), REF_CONFIG)
    assert _one_minus_cos(together, want).max() < 1e-5
    padded = _embedder(weights, ragged=False)
    assert _one_minus_cos(padded.encode_batch_device(texts),
                          want).max() < 1e-5


def test_the_step_s_lowered_text_carries_both_attention_scopes(weights):
    emb = _embedder(weights, ragged=True, ragged_max_seqs=1)
    (args, _n_docs, _n_pad), = emb.pack_ragged(_texts((30, 50)))
    text = jax.jit(emb.ragged_device_producer).lower(
        weights, *args).as_text(debug_info=True)
    for scope in ("decoder.attention/decoder.attention.full",
                  "decoder.attention/decoder.attention.window",
                  "decoder.moe.route", "decoder.moe.experts", "decoder.pool"):
        assert scope in text, scope
    assert "decoder.moe.shared" not in text and "decoder.deltanet" not in text


def _by_hand(lengths, window, t=128):
    """Visible pairs of a full and of a window layer, and with rows of one
    block (128 slots) the key blocks: one a row that holds a document."""
    full = sum(n * (n + 1) // 2 for n in lengths)
    cut = sum(n * (n + 1) // 2 if n <= window
              else window * (window + 1) // 2 + (n - window) * window
              for n in lengths)
    return full, cut


def test_a_fused_dispatch_s_span_counts_what_attention_has_to_do(
        weights, monkeypatch):
    """``embedder.dispatch`` carries ``attn_pairs_full``,
    ``attn_pairs_window``, ``attn_tiles_run`` and ``attn_tiles_all``,
    counted from the documents' places; ``/metrics`` sums the tiles."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex)
    from pathway_tpu.xpacks.llm import embedders

    monkeypatch.setattr(embedders, "_ATTENTION_EMBEDDERS", set())
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    index = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(64, reserved_space=256, metric="cos"))
    texts = _texts((30, 50, 20, 100, 3, 60))
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    rec = FlightRecorder.from_env()
    rec.mark_leg(7)
    index.add_batch([Pointer(i) for i in range(6)], texts)
    rec.clear_leg()
    rec.enabled = False
    spans = [sp[5] for sp in rec.spans() if sp[0] == "embedder.dispatch"]
    _ids, mask = emb.tokenizer.batch(texts, max_len=128)
    lens = mask.sum(axis=1).tolist()
    assert [sp["rows"] for sp in spans] == [2, 1]
    # first-fit in order: rows (32, 52, 22), (102, 5) and (62,)
    assert lens == [32, 52, 22, 102, 5, 62]
    for span, docs in zip(spans, ((32, 52, 22, 102, 5), (62,))):
        full, cut = _by_hand(docs, WINDOW)
        assert span["attn_pairs_full"] == full
        assert span["attn_pairs_window"] == cut
        # rows of 128 slots are one block: four layers, one key block a row
        assert span["attn_tiles_run"] == span["attn_tiles_all"] \
            == 4 * span["rows"]
    assert emb.attention_tiles() == (12, 12)
    samples = {f: v for f, _labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    assert samples["pathway_tpu_attention_tiles_run"] == 12
    assert samples["pathway_tpu_attention_tiles_all"] == 12


@pytest.mark.parametrize("docs, windows, run, of", [
    # a row of 2,048 slots: 8 query blocks on 2 key blocks; to the diagonal
    # 4 x 1 + 4 x 2 = 12 key blocks a layer
    ([(2048,)], (None,), 12, 12),
    # a window of 300 keys: the queries from 1,536 on see key block 1 alone
    ([(2048,)], (300,), 4 + 2 + 2 + 1 + 1, 12),
    # the third document of 512 starts on key block 1; the last two query
    # blocks hold padding and run nothing
    ([(512, 512, 512)], (None,), 6, 12),
    ([(512, 512, 512)], (None, 300, 300, 300), 24, 48),
    # two rows
    ([(2048,), (100,)], (None,), 13, 24),
])
def test_tiles_counted_on_the_host_are_the_kernel_s_ranges(docs, windows,
                                                           run, of):
    seg, pos = _rows(2048, docs)
    work = attention.attention_work(seg, pos, windows, rep=7)
    assert (work["attn_tiles_run"], work["attn_tiles_all"]) == (run, of)
    assert work["attn_query_block"] == 256
    bq, bk, _ = attention.block_sizes(2048, 7)
    on_device = sum(int(attention._block_ranges(
        jnp, jnp.asarray(seg), jnp.asarray(pos), w, bq, bk)[1].sum())
        for w in windows)
    assert on_device == run
    assert ("attn_pairs_window" in work) == any(windows)

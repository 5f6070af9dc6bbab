"""The decoder of the LongCat-Flash pattern (models/decoder.py
``DecoderConfig.tiny_latent``: layers of two latent attention sublayers, two
dense feed-forwards and a shortcut-connected expert layer whose router's
last outputs are identity experts) against its plain reference
(benchmark/reference/longcat_flash.py), the latent attention core
(ops/attention.py ``latent_attention``: keys of another width than values,
one rotary key for all heads, the scale given) against a dense softmax, and
the router with a correction bias and identity experts (ops/moe.py), at a
small size on the CPU."""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import longcat_flash as reference  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402
from pathway_tpu.ops import attention, moe  # noqa: E402

CONFIG = decoder.DecoderConfig.tiny_latent(compute_dtype=jnp.float32,
                                           max_len=128)
#: the same model as the benchmark's configuration file states one
REF_CONFIG = dict(
    vocab_size=CONFIG.vocab_size, hidden_size=64, num_layers=2,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, n_routed_experts=8, zero_expert_num=4,
    zero_expert_type="identity", moe_topk=3, routed_scaling_factor=6,
    rope_theta=1e7, rms_norm_eps=1e-5)


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
    ``docs[row]`` back to back, as the packer lays them."""
    seg = np.full((len(docs), t), -1, np.int32)
    pos = np.zeros((len(docs), t), np.int32)
    for r, lengths in enumerate(docs):
        at = 0
        for j, n in enumerate(lengths):
            seg[r, at:at + n], pos[r, at:at + n] = j, np.arange(n)
            at += n
    return seg, pos


def _latent_operands(t, docs, heads, dn, dr, dv, seed=0):
    rng = np.random.default_rng(seed)
    b = len(docs)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    return (draw(b, t, heads, dn), draw(b, t, heads, dr),
            draw(b, t, heads, dn), draw(b, t, dr), draw(b, t, heads, dv)) \
        + _rows(t, docs)


def _dense_latent(q_nope, q_rope, k_nope, k_rope, v, seg, pos, scale):
    """The definition: the whole masked score tensor, in float64."""
    q_nope, q_rope, k_nope, k_rope, v = (
        np.asarray(a, np.float64) for a in (q_nope, q_rope, k_nope, k_rope,
                                            v))
    at = np.arange(seg.shape[1])
    see = (seg[:, :, None] == seg[:, None, :]) & (seg >= 0)[:, None, :] \
        & (at[None, :, None] >= at[None, None, :])
    scores = (np.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + np.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) * scale
    scores = np.where(see[:, None], scores, -np.inf)
    top = np.where(see.any(-1)[:, None], scores.max(-1), 0.0)
    p = np.where(see[:, None], np.exp(scores - top[..., None]), 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-300)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


LATENT_ROWS = {
    "one_document": (512, [(512,)]),
    "a_packed_row_of_several": (512, [(175, 5, 75, 257), (1, 511)]),
    "padding_at_the_end": (512, [(250, 100)]),
    "a_row_of_no_whole_block": (300, [(175, 100)]),
}


@pytest.mark.parametrize("rows", LATENT_ROWS)
def test_the_latent_core_s_blockwise_lowering_equals_a_dense_softmax(rows):
    """Keys 3 x 16 + 16 wide (a head's own part and the rotary key all
    heads share), values 16, the scale given: ``latent_attention`` as the
    CPU runs it (the blockwise loop) against the whole masked score tensor
    at the real slots; padding reads zeros."""
    t, docs = LATENT_ROWS[rows]
    ops = _latent_operands(t, docs, heads=3, dn=48, dr=16, dv=16)
    scale = 0.31
    want = _dense_latent(*ops, scale)
    real = ops[5] >= 0
    before = attention.attention_lowerings()
    got = np.asarray(attention.latent_attention(*ops, scale=scale))
    after = attention.attention_lowerings()
    assert got.shape == ops[4].shape and got.dtype == np.float32
    assert np.abs(got - want)[real].max() < 2e-5
    assert not got[~real].any()
    assert after["kernel"] == before["kernel"] \
        and after["blockwise"] >= before["blockwise"]
    # the scale is the one given, not the keys' width's
    other = np.asarray(attention.latent_attention(*ops, scale=64 ** -0.5))
    assert np.abs(other - want)[real].max() > 1e-3


def test_the_kernel_at_one_published_shape_block_equals_the_blockwise_loop():
    """64 heads with keys of 128 + 64 features and values of 128, two rows
    of one block of 256 slots, through the interpreter: the kernel (its
    keys padded with zeros to 256 lanes, as on the chip) against the
    blockwise loop and the dense softmax."""
    t, docs = 256, [(256,), (100, 60, 40)]
    q_nope, q_rope, k_nope, k_rope, v, seg, pos = _latent_operands(
        t, docs, heads=64, dn=128, dr=64, dv=128)
    scale = 192 ** -0.5
    want = _dense_latent(q_nope, q_rope, k_nope, k_rope, v, seg, pos, scale)
    q = np.concatenate([q_nope, q_rope], axis=-1)
    k = np.concatenate([k_nope, np.broadcast_to(
        k_rope[:, :, None], q_rope.shape)], axis=-1)
    grow = ((0, 0),) * 3 + ((0, 64),)
    bq, bk, padded = attention.block_sizes(t, 1)
    assert (bq, bk, padded) == (256, 256, 256)
    assert attention._kernel_tiles(v.shape, padded)
    lo, count = attention._block_ranges(jnp, jnp.asarray(seg),
                                        jnp.asarray(pos), None, bq, bk)
    sizes = dict(window=None, bq=bq, bk=bk, scale=scale)
    loop = np.asarray(jax.jit(functools.partial(
        attention._blockwise, **sizes))(q, k, v, seg, pos, lo, count))
    kernel = np.asarray(jax.jit(functools.partial(
        attention._segment_kernel, interpret=True, **sizes))(
            np.pad(q, grow), np.pad(k, grow), v, seg, pos, lo, count))
    real = seg >= 0
    assert kernel.shape == loop.shape == v.shape
    assert np.abs(loop - want)[real].max() < 2e-5
    assert np.abs(kernel - loop)[real].max() < 2e-5
    assert not kernel[~real].any()


#: rows of two query blocks of 1,024, the documents' edges off every
#: block's (256, 512, 1,024): name: (slots a row, lengths row by row)
LONG_LATENT_ROWS = {
    "two_key_blocks": (2048, [(300, 500, 700, 400), (1, 2047)]),
    "a_row_of_no_whole_block": (1100, [(300, 500, 290), (1100,)]),
}


@pytest.mark.parametrize("rows", LONG_LATENT_ROWS)
def test_the_latent_core_at_query_blocks_of_1024_equals_a_dense_softmax(
        rows):
    """Every head of latent attention has keys of its own, so the core takes
    as many queries a block as keys, 1,024: rows of two key blocks against
    the whole masked score tensor, and the counter names the block."""
    t, docs = LONG_LATENT_ROWS[rows]
    ops = _latent_operands(t, docs, heads=2, dn=48, dr=16, dv=16)
    assert attention.block_sizes(t, 1) == (1024, 1024, 2048)
    want = _dense_latent(*ops, 0.31)
    real = ops[5] >= 0
    got = np.asarray(attention.latent_attention(*ops, scale=0.31))
    assert np.abs(got - want)[real].max() < 2e-5
    assert not got[~real].any()
    assert attention.attention_lowerings()["query_block_1024"] >= 1


@pytest.mark.parametrize("docs, run, of", [
    # a row of 2,048 slots of ungrouped heads: 2 query blocks of 1,024 on 2
    # key blocks; to the diagonal 1 + 2 = 3 key blocks a core
    ([(2048,)], 3, 3),
    # the third document of 512 lies in the second block and sees key block
    # 1 alone
    ([(512, 512, 512)], 1 + 1, 3),
    # the second document, slots 700-1,399, starts inside key block 0: its
    # queries from 1,024 on see both key blocks
    ([(700, 700)], 1 + 2, 3),
    ([(2048,), (100,)], 3 + 1, 6),
])
def test_the_host_counts_ungrouped_cores_tiles_at_blocks_of_1024(weights,
                                                                 docs, run,
                                                                 of):
    """``attention_work`` told that no heads are stacked counts the key
    blocks ``_block_ranges`` admits on the device's side at query blocks of
    1,024, and the embedder tells it so from the configuration's head
    counts (``dispatch_work``, which the ``embedder.dispatch`` span
    carries)."""
    seg, pos = _rows(2048, docs)
    cores = CONFIG.attention_windows
    assert cores == (None,) * 4 and CONFIG.attention_rep == 1
    assert decoder.DecoderConfig.tiny_windowed().attention_rep == 2
    work = attention.attention_work(seg, pos, cores, rep=1)
    assert (work["attn_tiles_run"], work["attn_tiles_all"]) \
        == (4 * run, 4 * of)
    assert work["attn_query_block"] == 1024
    bq, bk, _ = attention.block_sizes(2048, 1)
    _lo, count = attention._block_ranges(jnp, jnp.asarray(seg),
                                         jnp.asarray(pos), None, bq, bk)
    assert int(count.sum()) == run
    # stacked heads count the same rows at 256 queries: other numbers
    grouped = attention.attention_work(seg, pos, cores, rep=2)
    assert grouped["attn_query_block"] == 256
    assert grouped["attn_tiles_all"] == 4 * work["attn_tiles_all"]
    assert _embedder(weights, ragged=True).dispatch_work(
        (None, seg, pos)) == work


def test_the_kernel_is_taken_for_the_chip_at_the_published_head_alone():
    """Lowered for the TPU with values of 128 features a latent attention
    sublayer carries one kernel call under ``decoder.attention.full``, its
    projections under ``decoder.attention.latent``; lowered for the CPU,
    or with the tiny head, none. ``/metrics`` shows the count."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)

    def lowered(config, platform):
        p = jax.eval_shape(lambda key: decoder.init_params(key, config),
                           jax.random.PRNGKey(0))["layers"][0]["mixer"][0]
        x = jax.ShapeDtypeStruct((1, 512, config.hidden_size), jnp.float32)
        pos = jax.ShapeDtypeStruct((1, 512), jnp.int32)
        before = attention.attention_lowerings()
        text = jax.jit(lambda x, p, pos: decoder.latent_attention_layer(
            x, p, pos, pos, config)).trace(x, p, pos).lower(
                lowering_platforms=(platform,)).as_text(debug_info=True)
        after = attention.attention_lowerings()
        # the counts this lowering added, no other
        return text, {name: after[name] - before.get(name, 0)
                      for name in after
                      if after[name] != before.get(name, 0)}

    wide = decoder.DecoderConfig.tiny_latent(
        num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128)
    # a row of 512 slots of ungrouped heads: one block of 512 queries and
    # as many keys
    text, took = lowered(wide, "tpu")
    assert took == {"kernel": 1, "query_block_512": 1}
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "_segment_body" in calls[0]
    assert "decoder.attention.full" in text
    assert "decoder.attention.latent" in text
    # the keys reach the kernel 256 wide, the values 128
    assert "512x512xbf16" in calls[0] and "512x256xbf16" in calls[0]
    text, took = lowered(wide, "cpu")
    assert took == {"blockwise": 1, "query_block_512": 1}
    assert "tpu_custom_call" not in text
    text, took = lowered(decoder.DecoderConfig.tiny_latent(), "tpu")
    assert took == {"blockwise": 1, "query_block_512": 1}
    assert "tpu_custom_call" not in text
    samples = {(f, labels.get("lowering")): v for f, labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    counted = attention.attention_lowerings()
    for name in ("kernel", "blockwise", "query_block_512"):
        assert samples["pathway_tpu_attention_programs", name] \
            == counted[name]


def test_a_layer_s_form_follows_from_the_published_keys():
    assert [CONFIG.layer_kind(i) for i in range(2)] \
        == [decoder.LayerKind("latent", None, True)] * 2
    # two attention cores a layer, all full
    assert CONFIG.attention_windows == (None,) * 4
    assert CONFIG.router_outputs == 12 and CONFIG.held == (0, 8)
    assert not CONFIG.is_attention(0)
    with pytest.raises(ValueError, match="MLA"):
        decoder.DecoderConfig.tiny_latent(
            attention_method="GQA").layer_kind(0)
    # the other two families' configurations are what they were
    assert decoder.DecoderConfig.tiny().attention_windows == (None,)
    assert decoder.DecoderConfig.tiny_windowed().attention_windows \
        == (None, 24, 24, 24)
    assert decoder.DecoderConfig.tiny().router_outputs == 8


def test_reference_weights_are_the_program_s_tree(weights):
    lazy = reference.weights(REF_CONFIG, 7)
    other = reference.weights(REF_CONFIG, 8)
    leaves = jax.tree_util.tree_leaves
    # a layer is made anew at every asking, from the seed alone
    assert len(lazy["layers"]) == 2
    for i, layer in enumerate(lazy["layers"]):
        assert all(np.array_equal(a, b) for a, b in zip(
            leaves(layer), leaves(weights["layers"][i])))
    assert lazy["layers"][0] is not lazy["layers"][0]
    assert not np.array_equal(weights["embed"], other["embed"])
    assert not np.array_equal(weights["layers"][0]["moe"]["gate"],
                              weights["layers"][1]["moe"]["gate"])
    tree = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    made = decoder.init_params(jax.random.PRNGKey(0), CONFIG)
    assert tree(weights) == tree(made)
    layer = made["layers"][0]
    assert set(layer) == {"norm_in", "norm_post", "mixer", "ffn", "moe"}
    assert set(layer["mixer"][0]) == {"q_a", "q_norm", "q_b", "kv_a",
                                      "kv_norm", "kv_b", "o"}
    assert set(layer["moe"]) == {"router", "bias", "gate", "up", "down"}
    assert layer["moe"]["router"].shape == (64, 12)
    assert float(made["final_norm"].min()) == 1.0
    assert abs(float(weights["embed"].std()) - 0.02) < 1e-3
    assert abs(float(weights["layers"][0]["moe"]["bias"].std()) - 0.01) \
        < 5e-3
    # an expert's matrix is its own, whichever range of them is held
    share = reference.weights(dict(
        REF_CONFIG, n_routed_experts=4, experts_held=[4, 8],
        published={"n_routed_experts": 8}), 7)["layers"][1]["moe"]
    whole = weights["layers"][1]["moe"]
    assert np.array_equal(share["gate"], whole["gate"][4:])
    assert np.array_equal(share["down"], whole["down"][4:])
    assert np.array_equal(share["router"], whole["router"])


def _batch(lens, seed=0, width=128):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CONFIG.vocab_size, (len(lens), width)).astype(
        np.int32)
    return ids, np.arange(width)[None] < np.asarray(lens)[:, None]


def test_the_reference_s_waves_equal_its_layer_a_document_alone(
        weights, monkeypatch):
    """The check computes a wave of documents a call to the device, each in
    a window that reaches over the next documents, and leaves out the
    blocks behind a document's last token: with blocks small enough that
    documents end inside a window, between blocks and across two waves,
    every document reads as the layer's definition over it alone."""
    for name, value in (("TOKEN_BLOCK", 32), ("QUERY_BLOCK", 32),
                        ("HEAD_GROUP", 2), ("WAVE_WIDTHS", 3)):
        monkeypatch.setattr(reference, name, value)
    lens = np.array([128, 70, 33, 1, 128, 97])
    ids, _ = _batch(lens, seed=5)
    waves = reference._waves(lens, 128, 3 * 128)
    assert [len(w["docs"]) for w in waves] == [5, 1]   # shortest first
    assert waves[0]["starts"] == [0, 8, 48, 120, 224]  # at eights
    assert all(w["starts"][-1] + 128 <= 3 * 128 for w in waves)
    got = reference.embed(weights, ids, lens, REF_CONFIG)
    sizes = reference._sizes(REF_CONFIG)

    @jax.jit
    def alone(x):
        for layer in weights["layers"]:
            x = reference._layer(x, layer, sizes, jnp.matmul)
        return reference._rms_norm(x[-1], weights["final_norm"],
                                   sizes["eps"])

    for row, n, unit in zip(ids, lens, got):
        want = np.asarray(alone(weights["embed"][row[:n]]))
        assert np.abs(unit - want / np.linalg.norm(want)).max() < 1e-6


def test_padded_batch_agrees_with_the_reference(weights):
    lens = np.array([100, 65, 24, 25, 1, 128])
    ids, mask = _batch(lens)
    got, aux = jax.jit(CONFIG.encode)(weights, ids, mask)
    want = reference.embed(weights, ids, lens, REF_CONFIG)
    assert _one_minus_cos(got, want).max() < 1e-6          # reads 1e-7
    # every real token chose three of twelve outputs in each of two layers
    pairs = int(lens.sum()) * 3 * 2
    assert float(aux["pairs"]) == pairs
    held = int(aux["tokens_per_expert"].sum())
    assert held + float(aux["zero_pairs"]) == pairs
    # what the reference is told apart by
    for changed in (dict(routed_scaling_factor=1.0),
                    dict(mla_scale_kv_lora=False),
                    dict(zero_expert_num=0, num_experts=12),
                    dict(hidden_act="relu"),
                    dict(norm_topk_prob=True)):
        cfg = decoder.DecoderConfig.tiny_latent(
            compute_dtype=jnp.float32, max_len=128, **changed)
        tree = weights
        if "zero_expert_num" in changed:
            # twelve experts with weights: the last four a copy of others
            tree = dict(weights, layers=[dict(layer, moe={
                name: np.concatenate([a, a[:4]]) if a.ndim == 3 else a
                for name, a in layer["moe"].items()})
                for layer in weights["layers"]])
        other, _ = jax.jit(cfg.encode)(tree, ids, mask)
        # seeded weights of deviation 0.02 leave pre-activations small, so
        # the activation's form reads 4e-6; the rest 1e-4 and more
        assert _one_minus_cos(other, want)[0] > 2e-6, changed


def test_bfloat16_agrees_with_the_reference_inside_its_tolerance(weights):
    """What the chip serves in: products in bfloat16, sums float32. At the
    tiny widths the rounding reads 1e-4 in the mean; the tolerance is a
    tenth of what two different documents read."""
    lens = np.array([100, 65, 24, 25, 7, 128])
    ids, mask = _batch(lens, seed=3)
    served = decoder.DecoderConfig.tiny_latent(max_len=128)
    assert served.compute_dtype == jnp.bfloat16
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, weights)
    got, _ = jax.jit(served.encode)(bf16, ids, mask)
    want = reference.embed(weights, ids, lens, REF_CONFIG)
    gap = _one_minus_cos(got, want)
    apart = _one_minus_cos(want[:-1], want[1:])
    assert gap.max() < 5e-3 and gap.mean() < 2e-3
    assert apart.min() > 10 * gap.max()


def _embedder(weights, **kw):
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    return JaxEncoderEmbedder(config=CONFIG, params=jax.device_put(weights),
                              max_len=128, **kw)


def _texts(lengths):
    rng = np.random.default_rng(1)
    return [" ".join(f"w{rng.integers(0, 300)}" for _ in range(n))
            for n in lengths]


def test_a_packed_row_of_documents_equals_the_documents_alone(weights):
    """Attention's reach and the rotary positions restart at a document's
    first token; ``encode_ragged`` and ``encode`` agree with the
    reference."""
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


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(weights):
    """Expert parallelism over two and over four chips: every chip routes
    over all twelve outputs and adds what its own experts give and the
    identity experts' part; the shares' sums, with the identity part and
    everything outside the experts counted once, are the uncut reference's
    layer."""
    p = jax.tree_util.tree_map(jnp.asarray, weights["layers"][0])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 24, 64)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    seg = jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32)[:, None], (2, 24))
    valid = seg >= 0

    def config(lo, hi):
        return decoder.DecoderConfig.tiny_latent(
            compute_dtype=jnp.float32, experts_held=(lo, hi))

    def held(lo, hi):
        return dict(p["moe"], **{name: p["moe"][name][lo:hi]
                                 for name in ("gate", "up", "down")})

    def share(lo, hi):
        y, counters = decoder.shortcut_layer(
            x, dict(p, moe=held(lo, hi)), pos, seg, valid, config(lo, hi))
        return np.asarray(y), counters

    sizes = reference._sizes(REF_CONFIG)
    want = np.stack([np.asarray(reference._layer(row, p, sizes, jnp.matmul))
                     for row in x])
    whole, counters = share(0, 8)
    assert np.abs(whole - want).max() < 1e-5
    # the expert layer's input, and the identity experts' part of its output
    a = decoder._rms_norm(
        x + decoder.latent_attention_layer(
            decoder._rms_norm(x, p["norm_in"][0], 1e-5, False),
            p["mixer"][0], pos, seg, CONFIG),
        p["norm_post"][0], 1e-5, False)
    chosen = moe.route(a.reshape(-1, 64), p["moe"]["router"], 3, False,
                       p["moe"]["bias"], 6.0)
    same, pairs = moe.identity_part(a.reshape(-1, 64), *chosen, 8,
                                    valid.reshape(-1))
    same = np.asarray(same).reshape(x.shape)
    assert np.abs(same).max() > 1e-2
    for cuts in ((0, 4, 8), (0, 2, 4, 6, 8)):
        outside, experts = [], 0.0
        for lo, hi in zip(cuts, cuts[1:]):
            y, c = share(lo, hi)
            layer = np.asarray(decoder.moe_layer(a, held(lo, hi), valid,
                                                 config(lo, hi))[0])
            outside.append(y - layer)    # what every chip computes alike
            experts = experts + (layer - same)
            assert float(c["zero_pairs"]) == float(pairs[0])
            assert float(c["pairs"]) == float(pairs[1]) == 2 * 24 * 3
            assert np.array_equal(
                c["tokens_per_expert"],
                counters["tokens_per_expert"][lo:hi])
        assert all(np.abs(o - outside[0]).max() < 1e-6 for o in outside)
        total = outside[0] + experts + same
        assert np.abs(total - want).max() < 1e-5, cuts
    # a share's reference holds the same share
    half = reference._sizes(dict(REF_CONFIG, n_routed_experts=4,
                                 experts_held=[4, 8],
                                 published={"n_routed_experts": 8}))
    part = dict(p, moe=held(4, 8))
    want_half = np.stack([np.asarray(
        reference._layer(row, part, half, jnp.matmul)) for row in x])
    assert np.abs(share(4, 8)[0] - want_half).max() < 1e-5


def test_the_correction_bias_changes_the_choice_and_not_the_weight():
    """Two tokens, four outputs, two chosen: without a bias the two
    likeliest; a bias lifts the third over the second, and its weight is
    its probability, times the scale, not the biased score."""
    x = jnp.eye(2, 4, dtype=jnp.float32)
    router = jnp.asarray([[2.0, 1.0, 0.9, -1.0], [0.0, 3.0, 0.0, 2.9],
                          [0.0] * 4, [0.0] * 4], jnp.float32)
    probs = np.asarray(jax.nn.softmax(router[:2], axis=-1))
    plain_w, plain_e = moe.route(x, router, 2, False)
    assert np.asarray(plain_e).tolist() == [[0, 1], [1, 3]]
    bias = jnp.asarray([0.0, 0.0, 0.05, 0.0])
    weights, experts = moe.route(x, router, 2, False, bias, 6.0)
    assert np.asarray(experts).tolist() == [[0, 2], [1, 3]]
    assert np.allclose(weights, 6.0 * np.asarray(
        [[probs[0, 0], probs[0, 2]], [probs[1, 1], probs[1, 3]]]))
    # where the bias changes no choice the weights are the plain ones
    assert np.allclose(np.asarray(weights)[1] / 6.0, np.asarray(plain_w)[1])
    renormed, _ = moe.route(x, router, 2, True, bias)
    assert np.allclose(np.asarray(renormed).sum(axis=1), 1.0)


def test_identity_pairs_take_no_buffer_row_and_are_counted():
    """A dispatch of 2,048 tokens, three of twelve outputs a token, two of
    the eight experts with weights held: the buffer's lengths follow the
    held share of all outputs (2 / 12), identity pairs and the pairs of
    experts held elsewhere sort behind the held groups, and ``zero_pairs /
    pairs`` reads the share routed to the identity experts."""
    config = decoder.DecoderConfig.tiny_latent(compute_dtype=jnp.float32,
                                               experts_held=(0, 2),
                                               max_len=2048)
    p = decoder.init_params(jax.random.PRNGKey(3), config)["layers"][0]["moe"]
    # a router that tells tokens apart, and no bias: the choices spread
    # evenly over the twelve outputs
    p = dict(p, router=50.0 * p["router"], bias=jnp.zeros((12,)))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 2048, 64)).astype(np.float32))
    valid = jnp.arange(2048)[None] < 1800
    lengths = moe.buffer_lengths(2048 * 3, 2 / 12)
    assert lengths == (1024, 1280, 6144)
    y, counters = jax.jit(lambda x, p, v: decoder.moe_layer(
        x, p, v, config))(x, p, valid)
    held = int(counters["tokens_per_expert"].sum())
    executions, full, rows = np.asarray(counters["buffer"]).tolist()
    assert (executions, full) == (1.0, 0.0) and rows in (1024.0, 1280.0)
    assert held <= rows < 1800 * 3
    zero, pairs = float(counters["zero_pairs"]), float(counters["pairs"])
    assert pairs == 1800 * 3
    assert 0.25 < zero / pairs < 0.42                     # 4 of 12 outputs
    # against every pair computed one by one
    flat = x.reshape(-1, 64)
    weights, experts = moe.route(flat, p["router"], 3, False, p["bias"], 6.0)
    want = np.zeros((2048, 64), np.float32)
    for e in range(2):
        out = (jax.nn.silu(flat @ p["gate"][e]) * (flat @ p["up"][e])) \
            @ p["down"][e]
        want += np.asarray(jnp.sum(jnp.where(experts == e, weights, 0.0),
                                   axis=1, keepdims=True) * out)
    want += np.asarray(jnp.sum(jnp.where(experts >= 8, weights, 0.0),
                               axis=1, keepdims=True) * flat)
    real = np.asarray(valid).reshape(-1)
    assert np.abs(np.asarray(y).reshape(-1, 64) - want)[real].max() < 1e-4
    # a router that leans towards the held range: every pair, in turns of
    # the longest short buffer, and still none dropped
    lean = dict(p, bias=p["bias"].at[:2].add(1.0))
    y2, c2 = jax.jit(lambda x, p, v: decoder.moe_layer(
        x, p, v, config))(x, lean, valid)
    assert np.asarray(c2["buffer"]).tolist() == [1.0, 1.0, 6144.0]
    assert int(c2["tokens_per_expert"].sum()) == 2 * 1800 > 1280
    w2, e2 = moe.route(flat, lean["router"], 3, False, lean["bias"], 6.0)
    want2 = np.zeros((2048, 64), np.float32)
    for e in range(2):
        out = (jax.nn.silu(flat @ p["gate"][e]) * (flat @ p["up"][e])) \
            @ p["down"][e]
        want2 += np.asarray(jnp.sum(jnp.where(e2 == e, w2, 0.0), axis=1,
                                    keepdims=True) * out)
    want2 += np.asarray(jnp.sum(jnp.where(e2 >= 8, w2, 0.0), axis=1,
                                keepdims=True) * flat)
    assert np.abs(np.asarray(y2).reshape(-1, 64) - want2)[real].max() < 1e-4


def test_the_other_decoders_aux_keeps_its_keys():
    for config in (decoder.DecoderConfig.tiny(max_len=64),
                   decoder.DecoderConfig.tiny_windowed(max_len=64)):
        params = decoder.init_params(jax.random.PRNGKey(0), config)
        ids, mask = _batch([10, 64], width=64)
        _, aux = jax.jit(config.encode)(params, ids, mask)
        assert set(aux) == {"tokens_per_expert", "buffer"}
    params = decoder.init_params(jax.random.PRNGKey(0), CONFIG)
    ids, mask = _batch([10, 64], width=64)
    _, aux = jax.jit(CONFIG.encode)(params, ids, mask)
    assert set(aux) == {"tokens_per_expert", "buffer", "zero_pairs", "pairs"}


def test_the_step_s_lowered_text_carries_the_scopes(weights):
    emb = _embedder(weights, ragged=True, ragged_max_seqs=1)
    (args, _n_docs, _n_pad), = emb.pack_ragged(_texts((30, 50)))
    text = jax.jit(emb.ragged_device_producer).lower(
        weights, *args).as_text(debug_info=True)
    for scope in ("decoder.attention/decoder.attention.latent",
                  "decoder.attention/decoder.attention.full", "decoder.ffn",
                  "decoder.moe.route", "decoder.moe.experts", "decoder.pool"):
        assert scope in text, scope
    for scope in ("decoder.moe.shared", "decoder.deltanet",
                  "decoder.attention.window"):
        assert scope not in text, scope
    # a layer's weights wait for the layer's input
    assert "optimization_barrier" in text


def test_expert_load_and_metrics_show_the_identity_pairs(weights,
                                                         monkeypatch):
    """``embedder.dispatch`` carries the pairs and the tiles of four
    attention cores; ``expert_load()`` returns ``zero_pairs`` and ``pairs``
    and ``/metrics`` shows both."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex)
    from pathway_tpu.xpacks.llm import embedders

    monkeypatch.setattr(embedders, "_ATTENTION_EMBEDDERS", set())
    monkeypatch.setattr(embedders, "_AUX_EMBEDDERS", set())
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
    for span, docs in zip(spans, (lens[:5], lens[5:])):
        assert span["attn_pairs_full"] == sum(n * (n + 1) // 2 for n in docs)
        assert "attn_pairs_window" not in span
        # rows of 128 slots are one block: four cores, one key block a row
        assert span["attn_tiles_run"] == span["attn_tiles_all"] \
            == 4 * span["rows"]
        assert span["attn_query_block"] == 128
    load = emb.expert_load()
    assert load["pairs"] == sum(lens) * 3 * 2
    assert 0 < load["zero_pairs"] < load["pairs"]
    assert load["zero_pairs"] + int(load["tokens_per_expert"].sum()) \
        == load["pairs"]
    assert load["expert_layers"] == 2 * 2 and load["dispatches"] == 2
    samples = {f: v for f, _labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    assert samples["pathway_tpu_moe_zero_expert_pairs"] == load["zero_pairs"]
    assert samples["pathway_tpu_moe_pairs"] == load["pairs"]
    assert samples["pathway_tpu_attention_tiles_run"] == 12

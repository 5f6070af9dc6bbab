"""The hybrid decoder as an embedder (models/decoder.py, ops/deltanet.py,
ops/moe.py) against its plain reference (benchmark/reference/qwen3_next.py:
token-by-token recurrence, every held expert through a mask, one document
at a time), at a small size on the CPU: hidden 64, one period of four
layers, 8 experts top-2, 2 key/value heads."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import qwen3_next as reference  # noqa: E402
from pathway_tpu.models import decoder  # noqa: E402
from pathway_tpu.ops import deltanet, moe  # noqa: E402

CONFIG = decoder.DecoderConfig.tiny(compute_dtype=jnp.float32)
#: the same model as the benchmark's configuration files state one
REF_CONFIG = dict(
    vocab_size=CONFIG.vocab_size, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, rms_norm_eps=1e-6, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
    rope_theta=1e7, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, num_experts=8, num_experts_routed=8,
    experts_held=[0, 8], num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True)


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


def test_reference_weights_depend_on_the_seed_alone(weights):
    again = reference.weights(REF_CONFIG, 7)
    other = reference.weights(REF_CONFIG, 8)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b)
               for a, b in zip(leaves(weights), leaves(again)))
    assert not np.array_equal(weights["embed"], other["embed"])
    tree = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert tree(weights) == tree(decoder.init_params(
        jax.random.PRNGKey(0), CONFIG))
    assert abs(float(weights["embed"].std()) - 0.02) < 1e-3


def test_padded_batch_agrees_with_the_reference(weights):
    rng = np.random.default_rng(0)
    lens = np.array([200, 65, 64, 63, 1, 130])
    ids = rng.integers(0, CONFIG.vocab_size, (len(lens), 256)).astype(
        np.int32)
    mask = np.arange(256)[None] < lens[:, None]
    got, load = jax.jit(CONFIG.encode)(weights, ids, mask)
    want = reference.embed(weights, ids, lens, REF_CONFIG)
    assert _one_minus_cos(got, want).max() < 1e-5
    # every real token chose two experts in each of four layers; padding
    # chose none
    assert int(load["tokens_per_expert"].sum()) == int(lens.sum()) * 2 * 4
    # four expert layers, each with two thirds of the slots padding: the
    # pair buffer took its short length (2,560 of 6 x 256 x 2 rows), never
    # the full one
    assert moe.buffer_lengths(6 * 256 * 2, 1.0) == (2560, 3072)
    assert load["buffer"].tolist() == [4.0, 0.0, 4 * 2560.0]


def _embedder(weights, **kw):
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    config = decoder.DecoderConfig.tiny(compute_dtype=jnp.float32,
                                        max_len=128)
    return JaxEncoderEmbedder(config=config, params=jax.device_put(weights),
                              max_len=128, **kw)


def _texts(lengths):
    rng = np.random.default_rng(1)
    return [" ".join(f"w{rng.integers(0, 300)}" for _ in range(n))
            for n in lengths]


def test_a_packed_row_of_three_documents_equals_the_three_alone(weights):
    """The state-reset test: recurrent state, convolution, rotary positions
    and attention's reach all restart at a document's first token."""
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    texts = _texts((30, 50, 20, 100, 3, 60))
    packs = emb.pack_ragged(texts)
    (ids, doc_map, _pos, doc_seq, doc_off), n_docs, _n_pad = packs[0]
    assert (doc_seq[:3] == 0).all() and n_docs > 3      # three in row 0
    # last-token pooling: the packer hands the last token's offset
    assert doc_map[0, doc_off[0]] == 0 and doc_map[0, doc_off[0] + 1] == 1
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


def _buffer_expected(emb, texts):
    """(expert-layer executions, those at the full length, buffer rows)
    of embedding ``texts`` with every expert held: a real token's two
    pairs are both held, and each of four layers takes the shortest length
    that holds a dispatch's."""
    layers = full = rows = 0
    for (ids, doc_map, *_), _n_docs, _n_pad in emb.pack_ragged(texts):
        lengths = moe.buffer_lengths(ids.size * 2, 1.0)
        took = min(c for c in lengths if c >= 2 * int((doc_map >= 0).sum()))
        layers, rows = layers + 4, rows + 4 * took
        full += 4 * (took == lengths[-1])
    return layers, full, rows


@pytest.mark.parametrize("lengths, full_layers", [
    ((30, 50, 20), 0),          # most of one row: the short buffer
    ((30, 50, 20, 60, 70), 0),  # two dispatches, a third empty: the same
    ((120, 126, 127), 8),       # two dispatches of full rows: every pair
])
def test_expert_load_is_summed_on_the_device_and_fetched_on_request(
        weights, monkeypatch, lengths, full_layers):
    from pathway_tpu.xpacks.llm import embedders

    # rows of 128 slots x 2: lengths (224, 256) for one, (416, 512) for two
    monkeypatch.setattr(moe, "ROW_TILE", 32)
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    assert emb.expert_load() is None
    texts = _texts(lengths)
    emb.encode_batch_device(texts)
    # summed where they were made: nothing has come to the host yet
    assert all(isinstance(a, jax.Array) for a in emb._aux_sum.values())
    load = emb.expert_load()
    ids, mask = emb.tokenizer.batch(texts, max_len=128)
    assert load["dispatches"] == len(emb.pack_ragged(texts))
    assert isinstance(load["tokens_per_expert"], np.ndarray)
    assert load["tokens_per_expert"].shape == (8,)
    assert int(load["tokens_per_expert"].sum()) == int(mask.sum()) * 2 * 4
    assert (load["expert_layers"], load["full_buffer_layers"],
            load["buffer_rows"]) == _buffer_expected(emb, texts)
    assert load["full_buffer_layers"] == full_layers
    stats = embedders.expert_load_stats()
    assert stats["max"] >= stats["mean"] > 0 and stats["dispatches"] >= 1
    assert stats["full_buffer_layers"] >= full_layers
    assert 224 <= stats["buffer_rows_mean"] <= 512


def test_the_shares_of_an_expert_layer_sum_to_the_whole(weights):
    """Experts 0-3 on one chip and 4-7 on another, each routing over all
    eight: their routed parts plus the shared expert, counted once, are the
    uncut layer, which is the reference's."""
    p = jax.tree_util.tree_map(jnp.asarray, weights["layers"][0]["moe"])
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 24, 64)).astype(np.float32))
    valid = jnp.ones((2, 24), bool)

    def layer(lo, hi):
        part = dict(p, gate=p["gate"][lo:hi], up=p["up"][lo:hi],
                    down=p["down"][lo:hi])
        config = decoder.DecoderConfig.tiny(compute_dtype=jnp.float32,
                                            experts_held=(lo, hi))
        y, counters = decoder.moe_layer(x, part, valid, config)
        assert set(counters) == {"tokens_per_expert", "buffer"}
        return y, counters["tokens_per_expert"], counters["buffer"]

    whole, load, used = layer(0, 8)
    (low, load_low, _), (high, load_high, _) = layer(0, 4), layer(4, 8)
    # 48 tokens x 2 pairs: too few rows for a short buffer
    assert used.tolist() == [1.0, 1.0, 96.0]
    flat = x.reshape(-1, 64)
    shared = ((jax.nn.silu(flat @ p["shared_gate"]) * (flat @ p["shared_up"]))
              @ p["shared_down"]) * jax.nn.sigmoid(flat @ p["shared_router"])
    assert np.allclose(low + high - shared.reshape(x.shape), whole,
                       atol=1e-6)
    assert np.array_equal(np.concatenate([load_low, load_high]), load)
    assert int(load.sum()) == 2 * 24 * 2
    sizes = reference._sizes(REF_CONFIG)
    want = np.stack([np.asarray(reference._moe(row, p, sizes, jnp.matmul))
                     for row in x])
    assert np.allclose(whole, want, atol=1e-6)
    # a share's reference holds the same share
    half = reference._sizes(dict(REF_CONFIG, experts_held=[4, 8]))
    part = dict(p, gate=p["gate"][4:], up=p["up"][4:], down=p["down"][4:])
    want_high = np.stack([np.asarray(reference._moe(row, part, half,
                                                    jnp.matmul))
                          for row in x])
    assert np.allclose(high, want_high, atol=1e-6)


def test_grouped_product_drops_no_token_when_one_expert_takes_all():
    """A router that sends every token to expert 5: the grouped product
    against a loop over the tokens."""
    rng = np.random.default_rng(3)
    n, h, f, e = 40, 16, 8, 8
    x = jnp.asarray(rng.standard_normal((n, h)).astype(np.float32))
    w_gate, w_up = (jnp.asarray(rng.standard_normal((e, h, f)).astype(
        np.float32)) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((e, f, h)).astype(np.float32))
    router = np.zeros((h, e), np.float32)
    router[:, 5] = 1.0
    weights, experts = moe.route(jnp.abs(x), jnp.asarray(router), 2, True)
    assert (np.asarray(experts[:, 0]) == 5).all()
    got, sizes = moe.grouped_experts(x, weights, experts, w_gate, w_up,
                                     w_down, (0, e))
    assert int(sizes[5]) == n and int(sizes.sum()) == 2 * n
    want = np.zeros((n, h), np.float32)
    for t in range(n):
        for w, ex in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            hidden = jax.nn.silu(x[t] @ w_gate[ex]) * (x[t] @ w_up[ex])
            want[t] += w * np.asarray(hidden @ w_down[ex])
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
    # held elsewhere: nothing is added here, and nothing is counted
    got, sizes = moe.grouped_experts(x, weights, experts, w_gate[:2],
                                     w_up[:2], w_down[:2], (6, 8))
    mask = np.asarray(experts) >= 6
    assert int(sizes.sum()) == int(mask.sum())
    assert np.allclose(np.asarray(got)[~mask.any(axis=1)], 0.0)


def _pairs(n, held_pairs, lo=0):
    """(n, 2) experts of 8: the first ``held_pairs`` tokens choose one of
    experts lo..lo+3 and one of the other four, the rest two of the other
    four."""
    i = np.arange(n)
    other = (lo + 4 + i % 4) % 8
    first = np.where(i < held_pairs, lo + i % 4, (lo + 4 + (i + 1) % 4) % 8)
    return np.stack([first, other], axis=1).astype(np.int32)


#: 64 tokens x 2: with half the experts held and a tile of 8 rows the pair
#: buffer is 56, 72 or 128 rows long; with all held 104 or 128
BUFFER_CASES = {
    # name: (experts (64, 2), valid (64,) or None, held, branch)
    "mostly_padding": (_pairs(64, 64), np.arange(64) < 16, (0, 4), 0),
    "full_rows_even_routing": (_pairs(64, 64), None, (0, 4), 1),
    "count_at_the_short_length": (_pairs(64, 56), None, (0, 4), 0),
    "count_one_over_the_short_length": (_pairs(64, 57), None, (0, 4), 1),
    "count_at_the_middle_length": (_pairs(64, 64), np.arange(64) != 9,
                                   (4, 8), 1),
    "count_one_over_the_middle_length": (
        np.where(np.arange(64)[:, None] < 9, [[1, 2]], _pairs(64, 64)),
        None, (0, 4), 2),
    "every_pair_on_one_held_expert": (np.full((64, 2), 1, np.int32), None,
                                      (0, 4), 2),
    "all_held_a_quarter_padding": (_pairs(64, 64), np.arange(64) < 48,
                                   (0, 8), 0),
    "all_held_full_rows": (_pairs(64, 64), None, (0, 8), 1),
}


@pytest.mark.parametrize("case", BUFFER_CASES)
def test_pair_buffer_takes_the_shortest_length_that_holds_the_held_pairs(
        case, monkeypatch):
    """``grouped_experts`` against the dense sum over a token's chosen
    held experts, at every length of the buffer; the counter says which
    length ran."""
    experts, valid, (lo, hi), branch = BUFFER_CASES[case]
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    rng = np.random.default_rng(5)
    n, h, f = 64, 16, 8
    x = rng.standard_normal((n, h)).astype(np.float32)
    w_gate, w_up = (rng.standard_normal((hi - lo, h, f)).astype(np.float32)
                    for _ in range(2))
    w_down = rng.standard_normal((hi - lo, f, h)).astype(np.float32)
    weights = rng.uniform(0.1, 1.0, (n, 2)).astype(np.float32)
    lengths = moe.buffer_lengths(n * 2, (hi - lo) / 8)
    assert lengths == ((56, 72, 128) if hi - lo == 4 else (104, 128))
    got, sizes = jax.jit(moe.grouped_experts, static_argnums=(6, 8))(
        x, weights, experts, w_gate, w_up, w_down, (lo, hi),
        None if valid is None else jnp.asarray(valid), lengths)
    want = np.zeros((n, h), np.float64)
    count = np.zeros(hi - lo, np.int64)
    for t in range(n):
        if valid is not None and not valid[t]:
            continue
        for w, e in zip(weights[t], experts[t] - lo):
            if 0 <= e < hi - lo:
                hidden = np.asarray(jax.nn.silu(x[t] @ w_gate[e])) \
                    * (x[t] @ w_up[e])
                want[t] += w * (hidden @ w_down[e])
                count[e] += 1
    assert np.array_equal(sizes, count)
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
    took = int(moe.buffer_branch(sizes, lengths))
    assert took == branch and lengths[took] >= count.sum()
    assert took == 0 or lengths[took - 1] < count.sum()
    assert moe.buffer_use(sizes, lengths).tolist() == [
        1.0, float(took == len(lengths) - 1), float(lengths[took])]
    # no shorter length would have done: cut there the buffer loses a pair,
    # and the token its part of the answer
    if took:
        order, _ = moe.group_by_expert(
            jnp.asarray(experts), (lo, hi),
            None if valid is None else jnp.asarray(valid))
        short = moe._held_pairs(lengths[took - 1], x, weights, order,
                                jnp.argsort(order), sizes, w_gate, w_up,
                                w_down)
        assert not np.allclose(short, want, rtol=1e-5, atol=1e-5)


def test_the_experts_products_run_over_the_static_lengths():
    """In ``moe_layer``'s program the grouped products' rows are the
    buffer's static lengths, three products a length, each length a branch
    of one switch under the experts' scope; the full length is one of
    them in every program."""
    config = decoder.DecoderConfig.tiny(compute_dtype=jnp.float32,
                                        experts_held=(0, 4), max_len=512)
    p = decoder.init_params(jax.random.PRNGKey(0), config)["layers"][0]["moe"]
    x, valid = jnp.zeros((2, 512, 64)), jnp.ones((2, 512), bool)
    layer = lambda x, p, valid: decoder.moe_layer(x, p, valid, config)
    lengths = moe.buffer_lengths(2 * 512 * 2, 0.5)
    assert lengths == (1024, 1280, 2048)

    def products(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                found.append(eqn.invars[0].aval.shape[0])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                products(sub, found)
        return found

    rows = products(jax.make_jaxpr(layer)(x, p, valid).jaxpr, [])
    assert sorted(rows) == sorted(lengths * 3)
    text = jax.jit(layer).lower(x, p, valid).as_text(debug_info=True)
    for branch in range(3):
        assert f"decoder.moe.experts/cond/branch_{branch}_fun" in text
    # a handful of pairs keep the one length, and no switch
    few = jax.make_jaxpr(layer)(x[:, :32], p, valid[:, :32])
    assert products(few.jaxpr, []) == [128] * 3
    assert "cond" not in {e.primitive.name for e in few.jaxpr.eqns}


def _recurrence(q, k, v, g, beta, starts):
    """Token by token, one row: the definition."""
    t, h, dk = q.shape
    state = np.zeros((h, dk, v.shape[-1]), np.float64)
    out = np.zeros(v.shape, np.float64)
    for i in range(t):
        if starts[i]:
            state[:] = 0.0
        state *= np.exp(g[i])[:, None, None]
        read = np.einsum("hk,hkv->hv", k[i], state)
        state += np.einsum("hk,hv->hkv", k[i],
                           (v[i] - read) * beta[i][:, None])
        out[i] = np.einsum("hk,hkv->hv", q[i], state)
    return out


def _scan_operands(rng, b, t, nk, nv, dk, dv, starts):
    q, k = (rng.standard_normal((b, t, nk, dk)) for _ in range(2))
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = rng.standard_normal((b, t, nv, dv))
    g = -rng.uniform(0.0, 2.0, (b, t, nv))
    beta = rng.uniform(0.0, 1.0, (b, t, nv))
    return q, k, v, g, beta, starts


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_chunked_scan_equals_the_recurrence(length):
    """Heads of 8 features: no shape of the kernel's, so the reference
    lowering, which the counter says."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 3, 8, 8
    starts = rng.random((b, length)) < 0.03
    starts[:, 0] = True
    q, k, v, g, beta, _ = _scan_operands(rng, b, length, h, h, dk, dv,
                                         starts)
    before = deltanet.scan_lowerings()
    got = np.asarray(deltanet.gated_delta_rule(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)),
        jnp.asarray(starts)))
    after = deltanet.scan_lowerings()
    assert (after["reference"] - before["reference"],
            after["kernel"] - before["kernel"]) == (1, 0)
    assert got.shape == (b, length, h, dv)
    for row in range(b):
        want = _recurrence(q[row], k[row], v[row], g[row], beta[row],
                           starts[row])
        assert np.allclose(got[row], want, atol=2e-4), \
            np.abs(got[row] - want).max()


def test_causal_convolution_restarts_at_a_document():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((1, 12, 5)).astype(np.float32)
    w = rng.standard_normal((4, 5)).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 5, 6]], np.int32)
    got = np.asarray(deltanet.causal_conv(jnp.asarray(x), jnp.asarray(w),
                                          jnp.asarray(pos)))[0]
    for lo, hi in ((0, 5), (5, 12)):
        doc = np.pad(x[0, lo:hi], ((3, 0), (0, 0)))
        want = sum(doc[i:i + hi - lo] * w[i] for i in range(4))
        assert np.allclose(got[lo:hi], want, atol=1e-6)


@pytest.mark.parametrize("lengths", [(30, 50), (126, 127)])
def test_expert_load_is_exposed_on_metrics(weights, monkeypatch, lengths):
    """/metrics names the busiest and the mean expert, the dispatches
    counted, and how long the experts' pair buffer was (most of a row: the
    short length; two full rows: every pair), once an embedder that routes
    tokens has run."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)
    from pathway_tpu.xpacks.llm import embedders

    monkeypatch.setattr(moe, "ROW_TILE", 32)
    # the other tests' embedders are no part of this reading
    monkeypatch.setattr(embedders, "_AUX_EMBEDDERS", set())
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    texts = _texts(lengths)
    emb.encode_batch_device(texts)
    samples = {f: v for f, _labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    assert samples["pathway_tpu_moe_tokens_per_expert_max"] \
        >= samples["pathway_tpu_moe_tokens_per_expert_mean"] > 0
    assert samples["pathway_tpu_moe_dispatches"] == 1
    layers, full, rows = _buffer_expected(emb, texts)
    assert full == (4 if lengths == (126, 127) else 0)
    assert samples["pathway_tpu_moe_full_buffer_layers"] == full
    assert samples["pathway_tpu_moe_buffer_rows_mean"] == rows / layers


def test_a_fused_dispatch_writes_an_embedder_dispatch_span(weights,
                                                           monkeypatch):
    """``embedder.dispatch`` (tokens, docs, rows) around each fused
    dispatch, in the live recorder's store, under the leg in flight; the
    expert load comes back from the fused step too. No recorder, no span."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex)

    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    index = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(64, reserved_space=256, metric="cos"))
    texts = _texts((30, 50, 20, 100, 3, 60))
    index.add_batch([Pointer(i) for i in range(3)], texts[:3])
    assert index.fused_batches == 1 and emb.expert_load()["dispatches"] == 1
    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    rec = FlightRecorder.from_env()
    rec.mark_leg(41)
    index.add_batch([Pointer(10 + i) for i in range(6)], texts)
    rec.clear_leg()
    spans = [sp for sp in rec.spans() if sp[0] == "embedder.dispatch"]
    _ids, mask = emb.tokenizer.batch(texts, max_len=128)
    assert [sp[3] for sp in spans] == [("tick", 41)] * 2
    assert sum(sp[5]["docs"] for sp in spans) == 6
    assert sum(sp[5]["tokens"] for sp in spans) == int(mask.sum())
    assert [sp[5]["rows"] for sp in spans] == [2, 1]
    assert len(index) == 9 and index.fused_fallbacks == 0
    rec.enabled = False
    # the rows are the embedder's: a document finds itself first
    ((key, _score),), = index.search([(Pointer(99), texts[3], 1, None)])
    assert key == Pointer(13)


def _documents(t, *firsts):
    starts = np.zeros(t, bool)
    starts[list(firsts)] = True
    return starts


#: name: (tokens a row, a document's first tokens row by row, real slots a
#: row or None for all, key heads). Two value heads a key head, 128
#: features a head.
KERNEL_CASES = {
    "length_1": (1, [(0,)], None, 1),
    "length_63": (63, [(0, 20)], None, 1),
    "length_64": (64, [(0, 63)], None, 1),
    "length_65": (65, [(0, 64)], None, 1),
    "length_200": (200, [(0, 70, 71)], None, 1),
    # two rows, two key heads: a value head finds its key head by index
    "length_512": (512, [(0, 100, 300), (0, 256, 448)], None, 2),
    # a document from slot 40 to slot 239 lies in chunks 0 to 3 and starts
    # inside chunk 0; the next one starts on the edge of chunk 4
    "starts_mid_chunk_and_spans_three_chunks": (
        320, [(0, 40, 240)], None, 1),
    # row 0's last three chunks and row 1's last chunk hold padding alone:
    # every padded slot its own document, as the packer marks it
    "last_chunks_hold_no_real_token": (
        320, [(0, 90) + tuple(range(130, 320)),
              (0,) + tuple(range(250, 320))], [130, 250], 1),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_scan_kernel_equals_the_reference_lowering_and_the_recurrence(case):
    """The fused kernel through the interpreter: q and k come with the key
    heads and are read by index, never repeated; against
    ``_gated_delta_rule`` to 1e-5 and against the token-by-token definition
    to 2e-4, at the real slots; past a row's last real token it writes
    zeros."""
    t, firsts, real_upto, nk = KERNEL_CASES[case]
    rng = np.random.default_rng(t)
    b, nv, d = len(firsts), 2 * nk, 128
    starts = np.stack([_documents(t, *row) for row in firsts])
    q, k, v, g, beta, _ = _scan_operands(rng, b, t, nk, nv, d, d, starts)
    real = None if real_upto is None else \
        np.arange(t)[None] < np.asarray(real_upto)[:, None]
    operands = [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)] \
        + [jnp.asarray(starts)]
    # jitted: op by op each lowering would compile some sixty small programs
    got = np.asarray(jax.jit(
        lambda *a: deltanet._scan_kernel(*a, interpret=True))(
            *operands, None if real is None else jnp.asarray(real)))
    assert got.shape == (b, t, nv, d)
    repeated = [jnp.repeat(a, nv // nk, axis=2) for a in operands[:2]]
    reference = np.asarray(jax.jit(
        deltanet._gated_delta_rule, static_argnums=6)(
            *repeated, *operands[2:], deltanet.CHUNK))
    read = np.ones((b, t), bool) if real is None else real
    assert np.abs(got - reference)[read].max() < 1e-5
    for row in range(b):
        want = _recurrence(*(np.repeat(a[row], nv // nk, axis=1)
                             for a in (q, k)), v[row], g[row], beta[row],
                           starts[row])
        assert np.allclose(got[row][read[row]], want[read[row]], atol=2e-4)
    if real is not None:
        # whole chunks past the last real token: written, as zeros
        dead = np.arange(t)[None] >= -(-np.asarray(real_upto)[:, None]
                                       // deltanet.CHUNK) * deltanet.CHUNK
        assert dead.any() and (got[dead] == 0.0).all()
        assert np.abs(reference[dead]).max() > 0.0


def test_the_kernel_is_taken_for_the_chip_at_its_shapes_alone():
    """Lowered for the TPU at the published head sizes (16 key heads
    serving 32 value heads of 128 features), ``deltanet_layer`` carries the
    kernel's call under ``decoder.deltanet.scan`` and no (.., 64, 64)
    float32 result outside it; lowered for the CPU, or with heads of 8
    features, it carries none. The counter says which lowering a program
    took, as ``/metrics`` shows it."""
    import re

    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)

    def lowered(config, platform):
        p = jax.eval_shape(lambda key: decoder.init_params(key, config),
                           jax.random.PRNGKey(0))["layers"][0]["mixer"]
        x = jax.ShapeDtypeStruct((2, 128, config.hidden_size), jnp.float32)
        pos = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        before = deltanet.scan_lowerings()
        text = jax.jit(lambda x, p, pos: decoder.deltanet_layer(
            x, p, pos, config, pos >= 0)).trace(x, p, pos).lower(
                lowering_platforms=(platform,)).as_text(debug_info=True)
        after = deltanet.scan_lowerings()
        return text, {name: after[name] - before[name] for name in after}

    wide = decoder.DecoderConfig.tiny(
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=128, linear_value_head_dim=128)
    text, took = lowered(wide, "tpu")
    assert took == {"kernel": 1, "reference": 0}
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    # the call's location is the scope's; the kernel is named for the trace
    assert "decoder.deltanet.scan" in text and "_scan_body" in calls[0]
    chunk_squares = re.compile(r"tensor<(\d+x)*64x64xf32>")
    assert not chunk_squares.search(text)
    text, took = lowered(wide, "cpu")
    assert took == {"kernel": 0, "reference": 1}
    assert "tpu_custom_call" not in text and chunk_squares.search(text)
    text, took = lowered(decoder.DecoderConfig.tiny(), "tpu")
    assert took == {"kernel": 0, "reference": 1}
    assert "tpu_custom_call" not in text and chunk_squares.search(text)
    samples = {(f, labels.get("lowering")): v for f, labels, v in
               _parse_samples(_metrics_lines(_FakeRuntime()))}
    counted = deltanet.scan_lowerings()
    assert counted["kernel"] >= 1 and counted["reference"] >= 2
    for name in ("kernel", "reference"):
        assert samples["pathway_tpu_deltanet_scan_programs", name] \
            == counted[name]


def test_the_host_waits_for_the_forward_two_before(weights, monkeypatch):
    """The runtime queues dozens of dispatches without a wait: behind a
    slow model the first legs after a release then retire in a tenth of
    their device time and ``DeviceBackpressure`` reads a pace ten times the
    device's (my chip runs, PR 33). A forward's ``aux`` is ready when the
    forward is done, so the embedder waits, at each dispatch, for the one
    ``DISPATCHES_AHEAD`` before it: one runs, one is queued, the host packs
    the next."""
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex)
    from pathway_tpu.xpacks.llm import embedders

    assert embedders.DISPATCHES_AHEAD == 2
    emb = _embedder(weights, ragged=True, ragged_max_seqs=2)
    noted, waited = [], []
    note, ready = emb.note_producer_aux, jax.block_until_ready

    def noting(aux):
        noted.append(aux)
        note(aux)

    def waiting(x):
        # what it is asked to wait for, and how many had been noted by then
        waited.append((x, len(noted)))
        return ready(x)

    monkeypatch.setattr(emb, "note_producer_aux", noting)
    monkeypatch.setattr(jax, "block_until_ready", waiting)
    index = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(64, reserved_space=256, metric="cos"))
    # rows of 128 slots, two a dispatch: five dispatches in one leg
    texts = _texts((100, 100, 100, 100, 100, 100, 100, 100, 100))
    assert len(emb.pack_ragged(texts)) == 5
    index.add_batch([Pointer(i) for i in range(len(texts))], texts)
    monkeypatch.undo()
    assert len(noted) == 5
    # the third dispatch waited for the first, the fourth for the second...
    assert [(x is noted[n - 3], n) for x, n in waited] \
        == [(True, 3), (True, 4), (True, 5)]
    assert len(emb._aux_ahead) == 2
    # and the sum is what it was: every dispatch counted once
    assert emb.expert_load()["dispatches"] == 5

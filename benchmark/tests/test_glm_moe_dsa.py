"""The fifth architecture's own tests (``"model": "glm_moe_dsa"``; CPU): the
benchmark resolves with six cells, its configuration is the catalog's row
cut as it says, its cost functions are held to hand-reckoned numbers at two
shapes, both controls stay refused at a tiny size by the function
``run_cell`` calls, the five new readers read a recorded profile's scopes
and a recorder's spans, and the cell is rehearsed at a toy size through
``run_cell`` to ``correct: true``, traced and untraced. Nothing here is a
speed."""

from __future__ import annotations

import copy
import dataclasses
import os
import time
import types

import numpy as np
import pytest

from benchmark.lib import check, runner, spec, trace

from conftest import FAKE_PEAKS, ROOT

CELL = "glm-5.2-embed.ingest-long-docs"
LONGCAT = "longcat-flash-embed.ingest-sections"
NEW = ("sparse_attention_roofline", "indexer_roofline",
       "ingest.attention_sparse_share", "ingest.attention_index_share",
       "attention.selected_share")


@pytest.fixture(scope="module")
def cell():
    return spec.load(ROOT).cell(CELL)


def _cut(cell, config: dict, serving: dict, words: dict, docs: int,
         index: dict | None = None, warm: dict | None = None):
    config = {**copy.deepcopy(cell.config), **config}
    config["serving"].update(serving)
    config["index"].update(index or {})
    mix = copy.deepcopy(cell.traffic)
    mix["backlog"]["words"].update(words)
    mix["backlog"]["docs"] = docs
    mix["warm"].update(warm or {})
    mix["trace_s"] = 2
    return dataclasses.replace(cell, config=config, traffic=mix)


def test_the_repo_s_own_benchmark_resolves_with_six_cells():
    """``test_longcat_flash.py``'s restatement over the cells the benchmark
    has now (that one pins five and fails since this cell is there; no file
    the benchmark had is a ``model_config`` PR's to edit)."""
    loaded = spec.load(ROOT)
    models = {"bge-small-10m.ingest-backlog": "bert",
              "bge-small-10m.query-steady": "bert",
              "qwen3-next-a3b-embed.ingest-chunks": "qwen3_next",
              "smallthinker-21b-embed.ingest-long-mixed": "smallthinker",
              LONGCAT: "longcat_flash", CELL: "glm_moe_dsa"}
    assert set(loaded.cells) == set(models)
    for name, cell in loaded.cells.items():
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.layers
        assert cell.chips == cell.config["chips"] == 1
        model = models[name]
        assert cell.config["model"] == model
        assert cell.model.__file__.endswith(f"benchmark/models/{model}.py")
        assert cell.reference.__file__.endswith(
            f"benchmark/reference/{model}.py")
    # the new cell reports what the other latent-attention cell does but
    # what reads scopes and counters this model does not have, and its five
    other = {m.name for m in loaded.cells[LONGCAT].layers}
    here = {m.name for m in loaded.cells[CELL].layers}
    assert other - here == {"attention_roofline",
                            "ingest.attention_full_share",
                            "moe.zero_expert_share"}
    assert here - other == set(NEW)
    assert {m.name for m in loaded.cells[CELL].end_to_end} \
        == {"setup_s", "ingest_docs_per_s", "peak_hbm_gib"}
    for entry in loaded.benchmark["per_layer"][-5:]:
        assert entry["workloads"] == [CELL] and entry["layer"] == "encoder"
        assert entry["moves"] == "ingest_docs_per_s"
    assert tuple(e["name"] for e in loaded.benchmark["per_layer"][-5:]) == NEW
    assert loaded.benchmark["workloads"][-1]["name"] == CELL
    assert loaded.benchmark["configs"][-1]["name"] == "glm-5.2-embed"


def test_the_configuration_is_the_published_row_cut_as_it_says(cell):
    """Every key of the catalog's row under its name, but the keys
    ``reduced`` names; those beside their published values."""
    import json

    c = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
        published = row["config"]
        assert row["source_url"] == c["source"]
    else:   # the numbers of the row, as the issue states them
        published = {
            "hidden_size": 6144, "num_attention_heads": 64,
            "num_hidden_layers": 78, "n_routed_experts": 256,
            "vocab_size": 154880, "q_lora_rank": 2048, "kv_lora_rank": 512,
            "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
            "v_head_dim": 256, "intermediate_size": 12288,
            "moe_intermediate_size": 2048, "index_topk": 2048,
            "index_n_heads": 32, "index_head_dim": 128,
            "num_experts_per_tok": 8, "n_shared_experts": 1,
            "routed_scaling_factor": 2.5, "scoring_func": "sigmoid"}
    entry = next(e for e in spec.load(ROOT).benchmark["configs"]
                 if e["name"] == c["name"])
    differs = {k for k, v in published.items() if c[k] != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs | {"index"} == set(entry["reduced"]) == set(c["reduced"])
    assert {k: published[k] for k in differs} == c["published"]
    assert entry["source"] == c["source"] and c["model"] == "glm_moe_dsa"
    assert entry["source"].endswith("zai-org/GLM-5.2/blob/main/config.json")
    # the floors: the dense layer and one whole period of four, 16 >= 8
    # experts held, an eighth of the vocabulary; 16 chips share a layer
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) \
        == (5, 16, 154880 // 8)
    assert c["experts_held"] == [0, 16] and c["layers_held"] == [2, 7]
    mlp, indexers = cell.model.held_layers(c)
    assert mlp == ["dense"] + ["sparse"] * 4
    assert indexers == ["full", "shared", "shared", "shared", "full"]
    assert len(c["mlp_layer_types"]) == len(c["indexer_types"]) == 78
    assert c["chips_sharing_a_layer"] == 16
    assert c["n_routed_experts"] * c["chips_sharing_a_layer"] == 256
    assert c["serving"]["rows_per_dispatch"] * c["serving"]["max_len"] \
        == c["serving"]["tokens_per_dispatch"] == 16384
    assert c["guarantees"] == spec.load(ROOT).cell(LONGCAT).config[
        "guarantees"]
    for key in ("indexer", "indexer_hadamard", "indexer_precision",
                "indexer_choice", "indexer_shared", "prefill_form",
                "head_dim", "router", "e_score_correction_bias",
                "shared_expert", "first_k_dense_replace", "rope",
                "tokenizer", "layers_held"):
        assert key in c["assumed"], key
    # what the deployment holds on this chip (the issue's arithmetic)
    mla = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 \
        + 64 * 256 * 6144
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32
    ffn, router, expert = 3 * 6144 * 12288, 6144 * 256, 3 * 6144 * 2048
    assert round(mla / 1e6, 2) == 165.02 and round(indexer / 1e6, 2) == 9.37
    assert cell.model._latent_proj_params(c) == mla
    assert cell.model._indexer_proj_params(c) == indexer
    dense = mla + indexer + ffn
    sparse = mla + router + 17 * expert
    assert round(dense / 1e6, 1) == 400.9 and round(sparse / 1e6, 1) == 808.3
    params = dense + 3 * sparse + (sparse + indexer) + 19360 * 6144
    assert round(params / 1e6) == 3762
    assert c["index"] == dict(c["index"], rows=62500, reserved_rows=65536)
    assert round(c["index"]["reserved_rows"] * 6144 * 2 / 1e9, 2) == 0.81
    # the cell's parameters, letter for letter
    mix = cell.traffic
    assert mix["backlog"] == {
        "docs": 3072, "words": {"dist": "uniform", "min": 2048, "max": 8192},
        "files_per_dir": 4096}
    assert "queries" not in mix and "documents" not in mix
    assert mix["warm"] == {"ticks": 20, "quiet_ticks": 10}
    assert mix["after"] == {"k": 3, "self_retrievals": 16,
                            "embedding_sample": 8}
    assert mix["trace_s"] == 4 and mix["vocab_words"] == 4096


def test_costs_by_hand_at_two_shapes(cell):
    model, c = cell.model, cell.config
    # 8 x 16 / 256 = half a held expert a token
    assert model.held_a_token(c) == 0.5
    for shape in ((1, 16384), (2, 4096)):
        tokens = shape[0] * shape[1]
        # four expert layers: 37.75M multiply-adds a held pair; 16 experts'
        # weights read once a layer, a held pair's row in and out in bf16
        flops, nbytes = model.experts_cost(c, shape)
        assert flops == 4 * 2 * tokens * 0.5 * 3 * 6144 * 2048
        assert nbytes == 4 * (2 * 16 * 3 * 6144 * 2048
                              + tokens * 0.5 * 2 * 2 * 6144)
        part, part_bytes = model.experts_cost(c, shape, 0.8)
        assert part == pytest.approx(0.8 * flops)
        assert part_bytes == pytest.approx(
            nbytes - 4 * 0.2 * tokens * 0.5 * 2 * 2 * 6144)
    assert round(model.experts_cost(c, (1, 16384))[0] / 1e12, 3) == 2.474
    # the sparse cores: three documents of 5,000 tokens; a query attends
    # over min(visible, 2,048) keys in each of five layers; a chosen pair
    # costs each of 64 heads 2 x (256 + 256) flops; q, the expanded k and
    # v, and o once a layer in bf16
    n, docs = 5000, 3
    selected = docs * (2048 * 2049 // 2 + (n - 2048) * 2048)
    visible = docs * n * (n + 1) // 2
    flops, nbytes = model.sparse_attention_cost(c, docs * n, 5 * selected)
    assert flops == 64 * 2 * (256 + 256) * 5 * selected
    assert 64 * 2 * (256 + 256) == 65536              # 65.5 kFLOP a pair
    assert nbytes == 5 * docs * n * 2 * 64 * (256 + 256 + 256 + 256)
    assert round(flops / 1e12, 2) == 8.01
    # the indexers: every visible pair, 32 heads of 128 features, in the
    # two layers that have one; 2 x 4,096 + 2 x 128 + 4 x 32 bytes a token
    # read an indexer and a bit a pair written
    flops, nbytes = model.indexer_cost(c, docs * n, 2 * visible)
    assert flops == 32 * 2 * 128 * 2 * visible
    assert nbytes == 2 * docs * n * (8192 + 256 + 128) + 2 * visible / 8
    assert round(flops / 1e12, 2) == 0.61
    # a shape of a quarter of the tokens: a quarter of the tokens' bytes
    assert model.sparse_attention_cost(c, docs * n / 4, 0)[1] \
        == 5 * docs * n / 4 * 2 * 64 * 1024
    # the whole forward, attention counted at three documents of 5,122
    shape, tokens = (1, 16384), 16384
    flops, nbytes = model.dispatch_cost(c, shape, True)
    mla, indexer = 165019648, 9371648
    dense = 5 * mla + 2 * indexer + 3 * 6144 * 12288 \
        + 4 * (6144 * 256 + 3 * 6144 * 2048)
    m = 5122
    stated_visible = 3 * m * (m + 1) // 2
    stated_selected = 3 * (2048 * 2049 // 2 + (m - 2048) * 2048)
    assert flops == pytest.approx(
        2 * tokens * dense + 65536 * 5 * stated_selected
        + 8192 * 2 * stated_visible + 4 * 2 * tokens * 0.5 * 3 * 6144 * 2048)
    # 2.46 GFLOP a token outside the cores, the indexers' scores and the
    # held experts (the issue's 2.61 with the experts' 0.15)
    assert round(2 * dense / 1e9, 2) == 2.46
    assert round(flops / 1e12, 1) == 51.6 and round(nbytes / 1e9, 1) == 27.4
    # a real row holds more pairs than the stated length's: the share reads
    # low
    assert sum(k * (k + 1) // 2 for k in (8194, 5000, 3190)) > stated_visible
    small, _ = model.dispatch_cost(c, (2, 4096), True)
    assert small < flops / 2


def _toy(cell, **config):
    """The cell at a size the CPU rehearses: every mechanism, toy widths."""
    return _cut(
        cell,
        dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=32, intermediate_size=96, moe_intermediate_size=32,
             index_topk=48, index_n_heads=4, index_head_dim=16,
             n_routed_experts=4, experts_held=[0, 4],
             published=dict(cell.config["published"], n_routed_experts=16),
             vocab_size=8192, **config),
        dict(max_len=256, rows_per_dispatch=1),
        dict(min=20, max=190),
        docs=12000, index=dict(rows=20000, reserved_rows=65536),
        warm=dict(ticks=150, quiet_ticks=10))


def test_both_controls_are_refused_at_the_tiny_size(cell):
    """Toy widths, float32 products over the bfloat16 weights ``build``
    serves, documents of up to 254 tokens over a choice of 48 keys: the
    program agrees with the reference to the weights' rounding; the
    reference with the choice left out and the reference in int8 lie
    several times further off, by the function ``run_cell`` calls."""
    from benchmark.lib.vector_store import System

    toy = _toy(cell)
    toy.config["serving"]["compute_dtype"] = "float32"
    rng = np.random.default_rng(5)
    docs = [" ".join(f"word{i}" for i in rng.integers(0, 4096, n))
            for n in (30, 46, 120, 200, 254, 90)]
    system = System(toy, 5, "/tmp", log=lambda _m: None)
    system.make_embedder()
    fails, cos = check.embeddings_agree(system, docs)
    assert not fails and cos["min_cos"] > 1 - 1e-3
    ref = toy.reference
    assert ref.CONTROL_KINDS == ("int8", "dense")
    program = 1.0 - cos["mean_cos"]
    for kind, least in (("dense", 10 * program), ("int8", 3 * program)):
        system.served_embeddings = lambda texts, kind=kind: \
            check.reference_embeddings(
                system, texts,
                embed=lambda *args: ref.control(*args, kind=kind))
        _fails, found = check.embeddings_agree(system, docs)
        assert 1.0 - found["mean_cos"] > least, (kind, found)
    # at the published widths the limits stand between the program's
    # readings and both controls' on the chip (PERF.md section 2)
    assert 0.8 < ref.MIN_COS < ref.MIN_MEAN_COS < 0.99


def _fake_run(cell, scopes: dict, spans: list, load: dict | None):
    """A run that holds one chip's scopes of the fused ingest program, the
    recorder's spans and an embedder's counters."""
    device = trace.DeviceTrace(
        name="/device:TPU:0", busy_s=1.0, ops={}, gaps=[],
        modules={"jit_step": [0.5, 0.5]},
        scopes={f"jit_step/{path}": s for path, s in scopes.items()})
    reduced = trace.Reduced(0.0, 2.0, [device], [], 0.0)
    recorder = types.SimpleNamespace(spans=lambda t0=None, t1=None: spans)
    embedder = types.SimpleNamespace(expert_load=lambda: load)
    system = types.SimpleNamespace(
        runtime=types.SimpleNamespace(recorder=recorder), embedder=embedder)
    return types.SimpleNamespace(
        cell=cell, trace=reduced, traced=(0.0, 2.0), w0=0.0, w1=2.0,
        extras={"system": system, "peaks": FAKE_PEAKS})


def test_the_new_readers(cell):
    """The five readers over scopes as the compiler names them on the chip
    (a scope inside the heads' loop stands behind ``while/body``, as the
    recorded profile ``data/tpu_scopes`` has it) and over the spans' new
    fields; nothing to read where the program has neither."""
    readers = {m.name: m.read for m in cell.layers}
    r = trace.reduce_xplane(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "tpu_scopes.xplane.pb"))
    # the recorded toy's scope inside its loop, found wherever it stands
    assert any("while/body" in key and key.endswith("toy.mlp")
               for key in r.devices[0].scopes)
    inside = "decoder.attention/while/body/closed_call/"
    scopes = {
        "decoder.attention/decoder.attention.index": 0.04,
        inside + "decoder.attention.sparse/pallas_call": 0.2,
        inside + "decoder.attention.latent": 0.06,
        "decoder.ffn": 0.1, "": 0.6}
    tokens, visible, selected = 13000, 40_000_000, 25_000_000
    span = ("embedder.dispatch", 0.5, 0.6, None, "t", dict(
        docs=3, rows=1, tokens=tokens, attn_pairs_full=visible,
        attn_pairs_indexed=2 * visible, attn_pairs_selected=5 * selected))
    load = {"tokens_per_expert": np.ones(16), "selected_pairs": 6.0e9,
            "visible_pairs": 1.0e10}
    run = _fake_run(cell, scopes, [span, span], load)
    assert readers["ingest.attention_sparse_share"](run) \
        == pytest.approx(100 * 0.2 / 1.0)
    assert readers["ingest.attention_index_share"](run) \
        == pytest.approx(100 * 0.04 / 1.0)
    flops, nbytes = cell.model.sparse_attention_cost(
        cell.config, 2 * tokens, 2 * 5 * selected)
    assert readers["sparse_attention_roofline"](run) == pytest.approx(
        100 * max(flops / 1e12, nbytes / 1e11) / 0.2)
    flops, nbytes = cell.model.indexer_cost(cell.config, 2 * tokens,
                                            2 * 2 * visible)
    assert readers["indexer_roofline"](run) == pytest.approx(
        100 * max(flops / 1e12, nbytes / 1e11) / 0.04)
    assert readers["attention.selected_share"](run) == pytest.approx(60.0)
    # a program from before this one: no such scope, field or counter
    old = _fake_run(cell, {"decoder.attention/decoder.attention.full": 0.3},
                    [span[:5] + (dict(docs=3, rows=1, tokens=tokens,
                                      attn_pairs_full=visible),)],
                    {"tokens_per_expert": np.ones(16)})
    for name in NEW:
        assert readers[name](old) is None, name
    untraced = _fake_run(cell, {}, [], None)
    untraced.trace = untraced.traced = None
    for name in NEW:
        assert readers[name](untraced) is None, name


@pytest.mark.parametrize("trace_on", [False, True])
def test_the_cell_rehearsed_through_run_cell(cell, tmp_path, trace_on):
    toy = _toy(cell)
    line = runner.run_cell(toy, seed=3, seconds=24, trace=trace_on,
                           expected_platform="cpu",
                           t_start=time.perf_counter(),
                           out_dir=str(tmp_path), peaks=FAKE_PEAKS)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0
    assert list(line)[-1] == "compared"
    got = line["metrics"]
    if not trace_on:
        assert set(got) == {"setup_s", "ingest_docs_per_s", "peak_hbm_gib"}
        assert got["ingest_docs_per_s"]["value"] > 0
        return
    # the program's counters and spans are read on any backend; a scope is
    # the chip's alone (the CPU's profile keeps none), and its readers
    # leave their metrics out
    assert 1.0 <= got["moe.expert_load_max_over_mean"]["value"] < 2.0
    assert 0 < got["ingest.dispatch_tokens_mean"]["value"] <= 256
    assert 0 < got["attention.tiles_run_share"]["value"] <= 100.0
    # documents of 22 to 192 tokens over a choice of 48 keys
    assert 30.0 < got["attention.selected_share"]["value"] < 90.0
    assert got["ingest.fused_fallbacks"]["value"] == 0
    for name in ("moe_roofline", "sparse_attention_roofline",
                 "indexer_roofline", "ingest.moe_share",
                 "ingest.attention_share", "ingest.attention_sparse_share",
                 "ingest.attention_index_share",
                 "ingest.attention_latent_share", "ingest.dense_ffn_share"):
        assert name not in got

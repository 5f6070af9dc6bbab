"""The fourth architecture's own tests (``"model": "longcat_flash"``; CPU):
the benchmark resolves with five cells, its configuration is the catalog's
row cut as it says, its cost functions are held to hand-reckoned numbers at
the published shape, its control stays refused by the function ``run_cell``
calls and a text gone wrong by the other limit, and its cell is rehearsed at
a toy size through ``run_cell`` to ``correct: true``, traced and untraced.
Nothing here is a speed."""

from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np
import pytest

from benchmark.lib import check, runner, spec

from conftest import FAKE_PEAKS, ROOT, load_tool

CELL = "longcat-flash-embed.ingest-sections"
SMALLTHINKER = "smallthinker-21b-embed.ingest-long-mixed"


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


def test_the_repo_s_own_benchmark_resolves_with_five_cells():
    """``test_smallthinker.py``'s restatement over the cells the benchmark
    has now (that one pins four and fails since this cell is there; no
    file the benchmark had is a ``model_config`` PR's to edit)."""
    loaded = spec.load(ROOT)
    models = {"bge-small-10m.ingest-backlog": "bert",
              "bge-small-10m.query-steady": "bert",
              "qwen3-next-a3b-embed.ingest-chunks": "qwen3_next",
              SMALLTHINKER: "smallthinker", CELL: "longcat_flash"}
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
    # the new cell reports every metric the other long-row decoder's does,
    # and its own three
    other = {m.name for m in loaded.cells[SMALLTHINKER].layers}
    here = {m.name for m in loaded.cells[CELL].layers}
    assert other - here == set()
    assert here - other == {"ingest.attention_latent_share",
                            "ingest.dense_ffn_share", "moe.zero_expert_share"}
    assert {m.name for m in loaded.cells[CELL].end_to_end} \
        == {"setup_s", "ingest_docs_per_s", "peak_hbm_gib"}
    # the three new metrics are this cell's alone
    for entry in loaded.benchmark["per_layer"][-3:]:
        assert entry["workloads"] == [CELL] and entry["layer"] == "encoder"
        assert entry["moves"] == "ingest_docs_per_s"


def test_the_configuration_is_the_published_row_cut_as_it_says(cell):
    """Every number of the catalog's row under its key, but the keys
    ``reduced`` names; those beside their published values."""
    c = cell.config
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    entry = next(e for e in spec.load(ROOT).benchmark["configs"]
                 if e["name"] == c["name"])
    differs = {k for k, v in published.items() if c[k] != v}
    assert differs == {"num_layers", "n_routed_experts", "vocab_size"}
    assert differs | {"index"} == set(entry["reduced"]) == set(c["reduced"])
    assert {k: published[k] for k in differs} == c["published"]
    assert entry["source"] == c["source"] and c["model"] == "longcat_flash"
    assert entry["source"].endswith(
        "meituan-longcat/LongCat-Flash-Omni/blob/main/config.json")
    # the floors: four layers, 16 >= 8 experts held, an eighth of the
    # vocabulary; 224 chips, 32 sharing a layer
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) \
        == (4, 16, 131072 // 8)
    assert c["experts_held"] == [0, 16]
    assert c["chips_sharing_a_layer"] == 32 and c["pipeline_stages"] == 7
    assert "224" in c["deployment"]
    assert c["n_routed_experts"] * c["chips_sharing_a_layer"] == 512
    assert c["serving"]["rows_per_dispatch"] * c["serving"]["max_len"] \
        == c["serving"]["tokens_per_dispatch"] == 8192
    smallthinker = spec.load(ROOT).cell(SMALLTHINKER).config
    assert c["guarantees"] == smallthinker["guarantees"]
    for key in ("text_path_only", "router_bias", "e_score_correction_bias",
                "norm_topk_prob", "rope", "tokenizer", "hidden_act",
                "num_key_value_heads"):
        assert key in c["assumed"], key
    # what the deployment holds on this chip (the issue's arithmetic)
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 6144
    ffn, router, expert = 3 * 6144 * 12288, 6144 * 768, 3 * 6144 * 2048
    assert round(mla / 1e6, 2) == 90.57 and round(ffn / 1e6, 2) == 226.49
    outside = 2 * mla + 2 * ffn + router
    assert round(outside / 1e6, 1) == 638.8
    assert round(expert / 1e6, 2) == 37.75
    params = 4 * (outside + 16 * expert) + 16384 * 6144
    assert round(params / 1e6) == 5072
    # half of what the weights leave room for: the reference runs beside
    assert c["index"] == dict(c["index"], rows=125000, reserved_rows=131072)
    assert round(c["index"]["reserved_rows"] * 6144 * 2 / 1e9, 2) == 1.61
    # the cell's parameters, letter for letter
    mix = cell.traffic
    assert mix["backlog"] == {
        "docs": 3072, "words": {"dist": "uniform", "min": 512, "max": 6144},
        "files_per_dir": 4096}
    assert "queries" not in mix and "documents" not in mix
    assert mix["warm"] == {"ticks": 20, "quiet_ticks": 10}
    assert mix["after"] == {"k": 3, "self_retrievals": 32,
                            "embedding_sample": 16}
    assert mix["trace_s"] == 4 and mix["vocab_words"] == 4096


def test_costs_at_the_published_shape(cell):
    """Hand-reckoned: a dispatch of one row of 8,192 slots."""
    model, c, shape = cell.model, cell.config, (1, 8192)
    tokens = 8192
    # 12 x 16 / 768 = a quarter of a held expert a token, 37.75M
    # multiply-adds each, four layers; 16 experts' weights read once a
    # layer, a held pair's row in and out in bf16
    assert model.held_a_token(c) == 0.25
    flops, nbytes = model.experts_cost(c, shape)
    assert flops == 4 * 2 * tokens * 0.25 * 3 * 6144 * 2048
    assert nbytes == 4 * (2 * 16 * 3 * 6144 * 2048
                          + tokens * 0.25 * 2 * 2 * 6144)
    assert round(flops / 1e12, 3) == 0.618 and round(nbytes / 1e9, 2) == 5.03
    part, part_bytes = model.experts_cost(c, shape, 0.8)
    assert part == pytest.approx(0.8 * flops)
    assert part_bytes == pytest.approx(
        nbytes - 4 * 0.2 * tokens * 0.25 * 2 * 2 * 6144)
    # attention: two sections of 4,000 tokens. A sublayer sees n (n + 1) / 2
    # pairs a section; a pair costs each of 64 heads 2 x (192 + 128) flops,
    # over 8 sublayers; q, the expanded k and v, and o once in bf16
    n = 4000
    pairs = 2 * n * (n + 1) // 2
    flops, nbytes = model.attention_cost(c, 2 * n, pairs, 0)
    assert flops == 8 * 64 * 2 * (192 + 128) * pairs
    assert 8 * 64 * 2 * (192 + 128) == 327680          # 0.33 MFLOP a pair
    assert nbytes == 8 * 2 * n * 2 * 64 * (192 + 192 + 128 + 128)
    assert round(flops / 1e12, 2) == 5.24
    # the reader hands it the window's pairs too; the model has no window
    assert model.attention_cost(c, 2 * n, pairs, 123) == (flops, nbytes)
    # the whole forward, attention counted at two sections of 3,330
    flops, nbytes = model.dispatch_cost(c, shape, True)
    dense = 4 * (2 * (90570752 + 3 * 6144 * 12288) + 6144 * 768)
    stated = 2 * 3330 * 3331 // 2
    assert flops == pytest.approx(
        2 * tokens * dense + 327680 * stated
        + 4 * 2 * tokens * 0.25 * 3 * 6144 * 2048)
    # 5.1 GFLOP a token outside the cores (the issue's 5.2 with the held
    # experts' 0.1)
    assert round(2 * dense / 1e9, 2) == 5.11
    assert round(flops / 1e12, 1) == 46.1 and round(nbytes / 1e9, 1) == 22.1
    # a real row holds more pairs than the stated length's: the share reads
    # low
    assert sum(m * (m + 1) // 2 for m in (6146, 1500)) > stated


def _small(cell, **config):
    """The published widths at a size the CPU holds: one layer, two experts
    held, a sliver of the vocabulary, documents of at most 128 tokens."""
    return _cut(cell, dict(num_layers=1, vocab_size=4608, n_routed_experts=2,
                           experts_held=[0, 2], **config),
                dict(max_len=128, rows_per_dispatch=1),
                dict(min=3, max=126), docs=64)


def test_the_int8_control_is_refused_at_the_published_widths(cell):
    """Every width as published (hidden 6,144, 64 heads of 128 + 64 and
    128 features through bottlenecks of 1,536 and 512, feed-forwards of
    12,288, experts of 2,048, twelve of 768 a token); what a CPU cannot
    hold is cut: one layer, two experts held, a sliver of the vocabulary, 6
    documents of at most 128 tokens. The program's bfloat16 path passes
    both limits; the reference in int8, one scale a tensor, is refused by
    the mean."""
    tool = load_tool("control")
    small = _small(cell)
    got = tool.readings(small, 5, tool.documents(small, 5), 6, 0)
    program, control = got["program"], got["control"]
    assert not program["refused"], program
    assert program["one_minus_mean_cos"] \
        < (1.0 - cell.reference.MIN_MEAN_COS) / 2
    assert program["one_minus_min_cos"] < 1.0 - cell.reference.MIN_COS
    assert control["refused"], control
    assert control["one_minus_mean_cos"] > 1.0 - cell.reference.MIN_MEAN_COS
    assert control["one_minus_mean_cos"] >= 3 * program["one_minus_mean_cos"]


def _toy(cell):
    """The cell at a size the CPU rehearses: every mechanism, toy widths."""
    return _cut(
        cell,
        dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, ffn_hidden_size=96, expert_ffn_hidden_size=32,
             num_layers=2, n_routed_experts=4, experts_held=[0, 4],
             published=dict(cell.config["published"], n_routed_experts=8),
             zero_expert_num=4, moe_topk=3, vocab_size=8192),
        dict(max_len=256, rows_per_dispatch=1),
        dict(min=20, max=190),
        docs=12000, index=dict(rows=20000, reserved_rows=65536),
        warm=dict(ticks=150, quiet_ticks=10))


def test_a_text_gone_wrong_is_refused_by_the_least_cosine(cell):
    """One served embedding of six swapped for its neighbour's, at the
    published widths (one layer): different documents' embeddings lie
    nearly at right angles there (1 - cos 0.95 and more; at toy widths
    they are 0.01 apart and no limit could tell them), so the least cosine
    refuses the run (the function ``run_cell`` calls)."""
    from benchmark.lib.vector_store import System

    small = _small(cell)
    tool = load_tool("control")
    docs = tool.documents(small, 11)[:6]
    system = System(small, 11, "/tmp", log=lambda _m: None)
    system.make_embedder()
    sound = system.served_embeddings

    def one_wrong(texts):
        out = np.array(sound(texts))
        out[3] = out[4]
        return out

    fails, cos = check.embeddings_agree(system, docs)
    assert not fails and cos["min_cos"] > small.reference.MIN_COS
    system.served_embeddings = one_wrong
    fails, cos = check.embeddings_agree(system, docs)
    assert cos["min_cos"] < 0.5 < small.reference.MIN_COS
    assert any("min cos" in f for f in fails), fails


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearsed_through_run_cell(cell, tmp_path, trace):
    toy = _toy(cell)
    line = runner.run_cell(toy, seed=3, seconds=24, trace=trace,
                           expected_platform="cpu",
                           t_start=time.perf_counter(),
                           out_dir=str(tmp_path), peaks=FAKE_PEAKS)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0
    assert list(line)[-1] == "compared"
    got = line["metrics"]
    if not trace:
        assert set(got) == {"setup_s", "ingest_docs_per_s", "peak_hbm_gib"}
        assert got["ingest_docs_per_s"]["value"] > 0
        return
    # the program's counters and spans are read on any backend; a scope is
    # the chip's alone (the CPU's profile keeps none), and its readers
    # leave their metrics out
    assert 1.0 <= got["moe.expert_load_max_over_mean"]["value"] < 2.0
    assert 0 < got["ingest.dispatch_tokens_mean"]["value"] <= 256
    assert 0 < got["attention.tiles_run_share"]["value"] <= 100.0
    # 4 of the router's 12 outputs are identity experts
    assert 20.0 < got["moe.zero_expert_share"]["value"] < 50.0
    assert got["ingest.fused_fallbacks"]["value"] == 0
    for name in ("moe_roofline", "attention_roofline", "ingest.moe_share",
                 "ingest.attention_share", "ingest.attention_full_share",
                 "ingest.attention_latent_share", "ingest.dense_ffn_share"):
        assert name not in got

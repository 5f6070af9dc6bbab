"""The third architecture's own tests (``"model": "smallthinker"``; CPU):
its configuration is the catalog's row cut as it says, its cost functions
are held to hand-reckoned numbers at the published shape, its control stays
refused by the function ``run_cell`` calls, and its cell is rehearsed at a
toy size through ``run_cell`` to ``correct: true``, traced and untraced.
Nothing here is a speed."""

from __future__ import annotations

import copy
import dataclasses
import time

import pytest

from benchmark.lib import runner, spec

from conftest import FAKE_PEAKS, ROOT, load_tool

CELL = "smallthinker-21b-embed.ingest-long-mixed"


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


def test_the_repo_s_own_benchmark_resolves_with_every_cell():
    """``test_spec.py::test_the_repo_s_own_benchmark_resolves`` and its
    restatement in ``test_qwen3_next.py`` over the cells the benchmark has
    now: the first pins them to ``bge-small-10m``'s two (marked in
    ``benchmark/conftest.py``), the second to three, and fails since this
    cell is there; neither file is a ``model_config`` PR's to edit."""
    loaded = spec.load(ROOT)
    models = {"bge-small-10m.ingest-backlog": "bert",
              "bge-small-10m.query-steady": "bert",
              "qwen3-next-a3b-embed.ingest-chunks": "qwen3_next",
              CELL: "smallthinker"}
    assert set(loaded.cells) == set(models)
    for name, cell in loaded.cells.items():
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.layers
        assert cell.chips == cell.config["chips"] == 1
        # the configuration's "model" found both of the architecture's files
        model = models[name]
        assert cell.config["model"] == model
        assert cell.model.__file__.endswith(f"benchmark/models/{model}.py")
        assert cell.reference.__file__.endswith(
            f"benchmark/reference/{model}.py")
    with pytest.raises(spec.SpecError, match="no workload 'nope'"):
        loaded.cell("nope")
    # the new cell reports every metric the other decoder's does but the
    # delta-rule scan's two, and its own three
    other = {m.name for m in
             loaded.cells["qwen3-next-a3b-embed.ingest-chunks"].layers}
    here = {m.name for m in loaded.cells[CELL].layers}
    assert other - here == {"deltanet_roofline", "ingest.deltanet_share"}
    assert here - other == {"attention_roofline",
                            "ingest.attention_full_share",
                            "attention.tiles_run_share"}
    assert {m.name for m in loaded.cells[CELL].end_to_end} \
        == {"setup_s", "ingest_docs_per_s", "peak_hbm_gib"}


def test_the_configuration_is_the_published_row_cut_as_it_says(cell):
    """Every number of the catalog's row under its key, but the key
    ``reduced`` names; that one beside its published value."""
    c = cell.config
    period = [0, 1, 1, 1]
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": period * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": period * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    entry = next(e for e in spec.load(ROOT).benchmark["configs"]
                 if e["name"] == c["name"])
    differs = {k for k, v in published.items() if c[k] != v}
    assert differs == {"num_hidden_layers"}
    assert differs | {"index"} == set(entry["reduced"]) == set(c["reduced"])
    assert {k: published[k] for k in differs} == c["published"]
    assert entry["source"] == c["source"] and c["model"] == "smallthinker"
    # one whole period of the pattern, every layer whole on this chip
    assert c["num_hidden_layers"] == 4 == len(period)
    assert c["chips_sharing_a_layer"] == 1 and c["pipeline_stages"] == 13
    assert c["serving"]["rows_per_dispatch"] * c["serving"]["max_len"] \
        == c["serving"]["tokens_per_dispatch"] == 16384 \
        == c["max_position_embeddings"]
    qwen = spec.load(ROOT).cell("qwen3-next-a3b-embed.ingest-chunks").config
    assert c["guarantees"] == qwen["guarantees"]
    # what the deployment holds on this chip: 1,983.5M parameters, 3.97 GB
    # in bfloat16, beside a 5.37 GB slab
    expert = 3 * 2560 * 768
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
    assert round((attention + 64 * expert) / 1e6, 2) == 398.62
    params = 4 * (attention + 64 * expert + 2 * 2560) + 151936 * 2560 + 2560
    assert round(params / 1e6, 1) == 1983.5
    assert c["index"]["reserved_rows"] * 2560 * 2 == 5368709120
    # the cell's parameters, letter for letter
    mix = cell.traffic
    assert mix["backlog"] == {
        "docs": mix["backlog"]["docs"],
        "words": {"dist": "geometric", "mean": 4000, "shift": 50, "min": 50,
                  "max": 16382}, "files_per_dir": 4096}
    assert mix["backlog"]["docs"] in (4096, 8192)
    assert "queries" not in mix and "documents" not in mix
    assert mix["warm"] == {"ticks": 20, "quiet_ticks": 10}
    assert mix["after"] == {"k": 3, "self_retrievals": 32,
                            "embedding_sample": 64}
    assert mix["trace_s"] == 4 and mix["vocab_words"] == 4096


def test_costs_at_the_published_shape(cell):
    """Hand-reckoned: a dispatch of one row of 16,384 slots."""
    model, c, shape = cell.model, cell.config, (1, 16384)
    tokens = 16384
    # six experts a token, 5.898M multiply-adds each, four layers; 64
    # experts' weights read once a layer, a pair's row in and out in bf16
    flops, nbytes = model.experts_cost(c, shape)
    assert flops == 4 * 2 * tokens * 6 * 3 * 2560 * 768
    assert nbytes == 4 * (2 * 64 * 3 * 2560 * 768 + tokens * 6 * 2 * 2 * 2560)
    assert round(flops / 1e12, 2) == 4.64 and round(nbytes / 1e9, 2) == 7.05
    part, part_bytes = model.experts_cost(c, shape, 0.8)
    assert part == pytest.approx(0.8 * flops)
    assert part_bytes == pytest.approx(
        nbytes - 4 * 0.2 * tokens * 6 * 2 * 2 * 2560)
    # attention: one document of 16,000 tokens. A full layer sees
    # n (n + 1) / 2 pairs, a window layer 4096 x 4097 / 2 + (n - 4096) x 4096;
    # a pair costs each of 28 heads 4 x 128 flops; q, k, v, o once
    n = 16000
    full, cut = n * (n + 1) // 2, 4096 * 4097 // 2 + (n - 4096) * 4096
    flops, nbytes = model.attention_cost(c, n, full, cut)
    assert flops == 4 * 128 * 28 * (full + 3 * cut)
    assert round(4 * 128 * 28 * full / 1e12, 2) == 1.84       # the issue's
    assert round(4 * 128 * 28 * cut / 1e12, 2) == 0.82
    assert nbytes == 4 * n * 2 * 128 * (2 * 28 + 2 * 4)
    # the whole forward, attention counted at four documents of 4,096
    flops, nbytes = model.dispatch_cost(c, shape, True)
    pairs = 4 * 4096 * 4097 // 2
    dense = 4 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64)
    assert flops == pytest.approx(
        2 * tokens * dense + 4 * 4 * 128 * 28 * pairs
        + 4 * 2 * tokens * 6 * 3 * 2560 * 768)
    assert round(flops / 1e12, 2) == 9.33 and round(nbytes / 1e9, 1) == 11.1
    # a real row holds more pairs than the stated length's: the mix's
    # longer documents; the share reads low
    real = sum(m * (m + 1) // 2 for m in (2961, 154, 9120, 3607))
    assert real > pairs


def _small(cell):
    """The published widths at a size the CPU holds."""
    return _cut(cell, dict(moe_num_primary_experts=16, vocab_size=8192,
                           sliding_window_size=48),
                dict(max_len=128, rows_per_dispatch=1),
                dict(mean=40, shift=3, min=3, max=126), docs=64)


def test_the_int8_control_is_refused_at_the_published_widths(cell):
    """Every width as published (hidden 2,560, 28 heads on 4 of 128
    features, experts of width 768, six a token); what a CPU cannot hold is
    cut: 16 experts, a nineteenth of the vocabulary, a window of 48, 8
    documents of at most 128 tokens. The program's bfloat16 path passes both
    limits; the reference in int8, one scale a tensor, is refused."""
    tool = load_tool("control")
    small = _small(cell)
    got = tool.readings(small, 5, tool.documents(small, 5), 8, 0)
    program, control = got["program"], got["control"]
    assert not program["refused"], program
    assert program["one_minus_mean_cos"] \
        < (1.0 - cell.reference.MIN_MEAN_COS) / 2
    assert program["one_minus_min_cos"] < 1.0 - cell.reference.MIN_COS
    assert control["refused"], control
    assert control["one_minus_mean_cos"] >= 3 * program["one_minus_mean_cos"]


def _toy(cell):
    """The cell at a size the CPU rehearses: every mechanism, toy widths."""
    return _cut(
        cell,
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, moe_num_primary_experts=8,
             moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
             vocab_size=8192, sliding_window_size=48),
        dict(max_len=256, rows_per_dispatch=1),
        dict(mean=60, shift=3, min=3, max=254),
        docs=12000, index=dict(rows=20000, reserved_rows=65536),
        warm=dict(ticks=150, quiet_ticks=10))


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearsed_through_run_cell(cell, tmp_path, trace):
    toy = _toy(cell)
    # /v1/statistics runs two legs (a second of rows) ahead of the index,
    # more or less at one edge or the other: the window is long enough that
    # the difference is well inside the check's 5 % of what it ingested
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
    assert got["ingest.fused_fallbacks"]["value"] == 0
    for name in ("moe_roofline", "attention_roofline", "ingest.moe_share",
                 "ingest.attention_share", "ingest.attention_full_share"):
        assert name not in got

"""The second architecture's own tests (``"model": "qwen3_next"``; CPU):
its control stays refused by the function ``run_cell`` calls, its cell is
rehearsed at a toy size through ``run_cell`` to ``correct: true``, traced
and untraced, and its cost functions are held to hand-reckoned numbers at
the published shape. Nothing here is a speed."""

from __future__ import annotations

import copy
import dataclasses
import time

import pytest

from benchmark.lib import runner, spec

from conftest import FAKE_PEAKS, ROOT, load_tool

CELL = "qwen3-next-a3b-embed.ingest-chunks"


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
    """``test_spec.py::test_the_repo_s_own_benchmark_resolves`` over the
    cells the benchmark has now: that one pins them to ``bge-small-10m``'s
    two and is marked in ``benchmark/conftest.py``."""
    loaded = spec.load(ROOT)
    models = {"bge-small-10m.ingest-backlog": "bert",
              "bge-small-10m.query-steady": "bert", CELL: "qwen3_next"}
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


def test_the_configuration_is_the_published_row_cut_as_it_says(cell):
    """Every number of the catalog's row under its key, but the keys
    ``reduced`` names; each of those beside its published value."""
    c = cell.config
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    entry = next(e for e in spec.load(ROOT).benchmark["configs"]
                 if e["name"] == c["name"])
    differs = {k for k, v in published.items() if c[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs | {"index"} == set(entry["reduced"]) == set(c["reduced"])
    assert {k: published[k] for k in differs} == c["published"]
    lo, hi = c["experts_held"]
    assert hi - lo == c["num_experts"] == 256
    assert c["num_experts_routed"] == published["num_experts"]
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0
    assert c["serving"]["rows_per_dispatch"] * c["serving"]["max_len"] \
        == c["serving"]["tokens_per_dispatch"] == 4096
    # what the deployment holds on this chip: 3,522M parameters, 7.04 GB
    # in bfloat16, beside a 4.29 GB slab
    expert = 3 * 2048 * 512
    delta = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    dense = 2048 * 512 + expert + 2048
    params = 3 * delta + attention + 4 * (256 * expert + dense) \
        + 75968 * 2048
    assert round(params / 1e6) == 3522
    assert c["index"]["reserved_rows"] * 2048 * 2 == 4294967296


def test_costs_at_the_published_shape(cell):
    """Hand-reckoned: a dispatch of 8 x 512 slots."""
    model, c, shape = cell.model, cell.config, (8, 512)
    tokens = 4096
    # five held experts a token in expectation, 3.146M multiply-adds each,
    # four layers; 256 experts' weights read once a layer, and a pair's
    # row in and out in bfloat16
    flops, nbytes = model.experts_cost(c, shape)
    assert flops == 4 * 2 * tokens * 5 * 3 * 2048 * 512
    assert nbytes == 4 * (2 * 256 * 3 * 2048 * 512
                          + tokens * 5 * 2 * 2 * 2048)
    assert round(nbytes / 1e9, 2) == 7.11
    # padding is no useful work: at slots 61 % full the products fall with
    # the tokens, the weights' bytes stay
    part, part_bytes = model.experts_cost(c, shape, 0.61)
    assert part == pytest.approx(0.61 * flops)
    assert part_bytes == pytest.approx(
        nbytes - 4 * 0.39 * tokens * 5 * 2 * 2 * 2048)
    assert model.scan_cost(c, shape, 0.5)[0] \
        == model.scan_cost(c, shape)[0] / 2
    # a value head's token: two 64 x 128 score products, the solve over
    # 256 columns at half, three 128 x 128 state products, 64 x 128 more
    flops, nbytes = model.scan_cost(c, shape)
    macs = 32 * (2 * 64 * 128 + 64 * 256 / 2 + 3 * 128 * 128 + 64 * 128)
    assert flops == 3 * tokens * 2 * macs
    assert nbytes == 3 * tokens * (2 * (2 * 2048 + 2 * 4096)
                                   + 32 * 2 * 4 * 128 * 128 / 64)
    # the whole forward: 1.79 TFLOP (9.1 ms at 197 TFLOP/s) and 9.2 GB
    # (11.2 ms at 819 GB/s): memory and compute bound it alike
    flops, nbytes = model.dispatch_cost(c, shape, True)
    assert round(flops / 1e12, 2) == 1.79
    assert round(nbytes / 1e9, 1) == 9.2
    per_token = flops / tokens / 2
    mixers = 3 * (2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048) \
        + 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    dense = 4 * (2048 * 512 + 3 * 2048 * 512 + 2048)
    scores = 2 * 16 * 256 * 513 / 2
    assert per_token == pytest.approx(
        mixers + dense + scores + 3 * macs + 4 * 5 * 3 * 2048 * 512)


def _small(cell):
    """The published widths at a size the CPU holds."""
    return _cut(cell, dict(num_experts=32, num_experts_routed=64,
                           experts_held=[0, 32], vocab_size=8192),
                dict(max_len=64, rows_per_dispatch=2),
                dict(min=3, max=60), docs=64)


def test_the_int8_control_is_refused_at_the_published_widths(cell):
    """Every width as published; what a CPU cannot hold is cut as the chip's
    share is cut, further: 32 of 64 routed experts held (top-10, so five
    held a token as in the deployment), a ninth of the held vocabulary, 8
    short documents. The program's bfloat16 path passes both limits; the
    reference in int8, one scale a tensor, is refused by the mean cosine,
    the number that holds it on the chip (PERF.md section 2). With fewer
    experts to choose among fewer tokens swap one, so both read lower here
    than on the chip (program 0.0046 against 0.0075-0.0200, control 0.086
    against 0.147-0.203)."""
    tool = load_tool("control")
    small = _small(cell)
    got = tool.readings(small, 5, tool.documents(small, 5), 8, 0)
    program, control = got["program"], got["control"]
    limit = 1.0 - cell.reference.MIN_MEAN_COS
    assert not program["refused"]
    assert program["one_minus_mean_cos"] < limit / 2
    assert program["one_minus_min_cos"] < 1.0 - cell.reference.MIN_COS
    assert control["refused"]
    assert control["one_minus_mean_cos"] > limit
    assert control["one_minus_mean_cos"] >= 3 * program["one_minus_mean_cos"]


def test_a_text_gone_wrong_is_refused_by_the_worst_text(cell):
    """One served embedding replaced by its neighbour's, at the published
    widths: the worst text reads as two different documents do, far under
    ``MIN_COS``."""
    import tempfile

    from benchmark.lib import check
    from benchmark.lib.vector_store import System

    small = _small(cell)
    with tempfile.TemporaryDirectory() as workdir:
        system = System(small, 5, workdir, log=lambda _m: None)
        system.make_embedder()
        texts = load_tool("control").documents(small, 5)[:8]
        served = system.served_embeddings

        def one_wrong(batch):
            out = served(batch).copy()
            out[3] = out[4]
            return out

        fails, found = check.embeddings_agree(system, texts)
        assert not fails, fails
        system.served_embeddings = one_wrong
        fails, found = check.embeddings_agree(system, texts)
    assert any("min cos" in f for f in fails)
    assert found["min_cos"] < small.reference.MIN_COS / 2


def _toy(cell):
    """The cell at a size the CPU rehearses: every mechanism, toy widths."""
    return _cut(
        cell,
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16, num_experts=4,
             num_experts_routed=8, experts_held=[0, 4],
             num_experts_per_tok=2, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, vocab_size=8192),
        dict(max_len=64, rows_per_dispatch=8), dict(min=3, max=40),
        docs=24000, index=dict(rows=20000, reserved_rows=65536),
        # an edge falls at every dispatch, and a toy leg holds 25 of them
        # where the cell's holds 6: the mix's 30 edges are one leg here.
        # The three ticks drained before the first submit waits are larger
        # than the bounded ones, and have to retire before the window opens
        warm=dict(ticks=150, quiet_ticks=10))


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearsed_through_run_cell(cell, tmp_path, trace):
    toy = _toy(cell)
    # /v1/statistics runs a bounded tick (0.4 s of rows) ahead of the index
    # at one edge or the other: the window is long enough that one tick is
    # well inside the check's 5 % of what it ingested
    line = runner.run_cell(toy, seed=3, seconds=16, trace=trace,
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
    assert 0 < got["ingest.dispatch_tokens_mean"]["value"] <= 8 * 64
    assert got["ingest.leg_ms_p95"]["value"] > 0
    assert got["ingest.fused_fallbacks"]["value"] == 0
    for name in ("moe_roofline", "deltanet_roofline", "ingest.moe_share",
                 "ingest.deltanet_share", "ingest.attention_share"):
        assert name not in got
    # the device is the slower side here, and ticks stay bounded from the
    # first submit that waits: a leg of some commit intervals, not the
    # whole backlog
    assert got["ingest.rows_per_tick"]["value"] < 1000

"""What ``correct`` has to refuse, refused: the control (the reference in the
nearest precision below the configuration's, in the program's place) at the
published shape, and a run whose timed path is broken underneath, once for
each fault these cells can have. CPU arithmetic: the chip's readings, which
the limits were set from, are in PERF.md section 2."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from benchmark.lib import runner, spec, vector_store

from conftest import FAKE_PEAKS, ROOT, load_tool, tiny_cell, toy_cell


def test_the_int8_control_is_refused_at_the_published_shape():
    """BGE-small as published, weights from the seed, 16 of the mix's
    documents spread over the length range: the program's bfloat16 path
    passes both limits, and the reference computed in int8 is refused by
    the mean cosine, the number that holds it on the chip (PERF.md section
    2), three times the program's reading or more away."""
    tool = load_tool("control")  # reads the same numbers on the chip
    cell = spec.load(ROOT).cell("bge-small-10m.query-steady")
    got = tool.readings(cell, 5, tool.documents(cell, 5), 16, 0)
    limit = 1.0 - cell.reference.MIN_MEAN_COS
    program, control = got["program"], got["control"]
    assert not program["refused"]
    assert program["one_minus_mean_cos"] < limit / 2
    assert program["one_minus_min_cos"] < 1.0 - cell.reference.MIN_COS
    assert control["refused"]
    assert control["one_minus_mean_cos"] > limit
    assert control["one_minus_mean_cos"] >= 3 * program["one_minus_mean_cos"]
    # the other lower precisions are read beside it, refused or not
    assert set(got) == {"seed", "program", "control"} | {
        f"control.{kind}" for kind in cell.reference.CONTROL_KINDS[1:]}


def test_the_second_architecture_s_control_is_refused_too(tmp_path):
    """The toy's reference over its table rounded to int8, in the toy's
    place: every reference brings a control that its limits refuse."""
    tool = load_tool("control")
    cell = toy_cell(tmp_path / "checkout")
    got = tool.readings(cell, 5, tool.documents(cell, 5), 64, 0)
    assert not got["program"]["refused"]
    assert got["control"]["refused"]
    assert got["control"]["one_minus_mean_cos"] \
        >= 3 * got["program"]["one_minus_mean_cos"]


def _swapped_hits(system) -> None:
    """An answer altered where it is produced: the index's first two hits
    of every query change places."""
    search = system.index.search

    def altered(queries):
        return [tuple(hits[1::-1]) + tuple(hits[2:])
                for hits in search(queries)]

    system.index.search = altered


def _rolled_embeddings(system) -> None:
    """The encoder's output altered where it is produced: every served
    embedding (queries, and the check's sample) turned by one feature."""
    import jax.numpy as jnp

    encode = system.embedder.encode_batch_device
    system.embedder.encode_batch_device = \
        lambda texts: jnp.roll(encode(texts), 1, axis=-1)


def _half_the_batch(system) -> None:
    """Half of every ingest batch left out."""
    add_batch = system.index.add_batch

    def halved(keys, texts, filter_data=None):
        return add_batch(keys[::2], texts[::2],
                         None if filter_data is None else filter_data[::2])

    system.index.add_batch = halved


@pytest.mark.parametrize("cell_name, fault, number", [
    ("bge-small-10m.query-steady", _swapped_hits, "first_hits_wrong"),
    ("bge-small-10m.query-steady", _rolled_embeddings, "min_cos"),
    ("bge-small-10m.ingest-backlog", _half_the_batch, "rows_off_file_count"),
])
def test_a_broken_timed_path_comes_out_as_not_correct(
        tmp_path, monkeypatch, cell_name, fault, number):
    """The rest of a run, driven past the look for a chip, with the fault
    planted in the program's objects once the server is up."""
    start = vector_store.System.start

    def broken(system):
        start(system)
        fault(system)

    monkeypatch.setattr(vector_store.System, "start", broken)
    cell = tiny_cell(cell_name)
    line = runner.run_cell(
        cell, seed=3, seconds=3, trace=False, expected_platform="cpu",
        t_start=time.perf_counter(), out_dir=str(tmp_path),
        peaks=FAKE_PEAKS, log=lambda _m: None)
    assert line["correct"] is False
    pair = line["compared"][number]
    if number == "min_cos":
        assert pair["value"] < pair["limit"]
    else:
        assert pair["value"] > pair["limit"]
    with open(tmp_path / f"{cell.name}.seed3.trace0.json") as f:
        assert json.load(f)["failures"]
    assert np.isfinite(pair["value"])

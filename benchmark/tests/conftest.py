"""The benchmark's own tests: ``python -m pytest benchmark/tests`` from the
root of the repo, on the CPU (nothing here states a speed). They are not
part of ``tests/``: a later PR may not change the yardstick, so its tests
live with it."""

from __future__ import annotations

import copy
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: stands in for the peaks table's row in a rehearsal off the chip
FAKE_PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "test"}


@pytest.fixture(autouse=True)
def _fresh_graph():
    """The program's parse graph is process-wide: one server per test."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


@pytest.fixture(autouse=True, scope="session")
def _no_cache_io():
    """Keep the CPU rehearsals out of the checkout's compile cache, which
    the chip's programs own."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


def tiny_cell(name: str):
    """The cell ``name`` cut to a size the CPU rehearses in seconds: a toy
    encoder, 20,000 filler rows, short documents, few of them."""
    from benchmark.lib import spec

    cell = spec.load(ROOT).cell(name)
    config, mix = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    config.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                  intermediate_size=128, vocab_size=8192,
                  max_position_embeddings=128)
    config["serving"]["max_len"] = 48
    config["index"].update(rows=20000, reserved_rows=65536)
    for part in ("corpus", "backlog", "documents"):
        if part in mix:
            mix[part]["words"].update(mean=12, max=40)
    if "corpus" in mix:
        mix["corpus"]["docs"] = 60
    if "backlog" in mix:
        mix["backlog"].update(docs=40000, files_per_dir=4096)
        mix["warm"].update(ticks=4, quiet_ticks=2)
    if "settle_s" in mix:
        mix["settle_s"] = 2
    mix["trace_s"] = 2
    return dataclasses.replace(cell, config=config, traffic=mix)

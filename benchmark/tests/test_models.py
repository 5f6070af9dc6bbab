"""What moved out of the harness into the architecture's files still says
what it said: the BERT reference through the signature every reference has,
the costs the rooflines divide by, and the embedder protocol the harness
relies on, failing by name."""

from __future__ import annotations

import types

import numpy as np
import pytest

from benchmark.lib import costs, spec, vector_store

from conftest import ROOT, tiny_cell

TEXTS = ["word1 word2 word3", "word7 " * 30, "word4095", "",
         " ".join(f"word{i}" for i in range(60))]


# The first four features of each row as the parent commit's reference
# returned them (39c794b ``benchmark/reference/bert.py``
# ``embed(params, ids, lengths, heads=4, eps=1e-12)``, run from a
# ``git archive`` of that commit on the CPU), over ``weights(config, 11)`` at
# the tiny cell's sizes and the token ids below: pinned, so that an edit to
# the forward pass cannot move both sides of a comparison at once.
PARENT_IDS = np.random.default_rng(27).integers(1, 8192, (5, 48))
PARENT_LENGTHS = [5, 32, 3, 3, 48]
PARENT_ROWS = [
    [0.059748899191617966, 0.3713288903236389, -0.0744720846414566,
     -0.08517124503850937],
    [-0.061959683895111084, 0.20684956014156342, -0.07932472229003906,
     -0.11926839500665665],
    [0.10727254301309586, 0.16412785649299622, 0.017670467495918274,
     -0.09226527065038681],
    [0.010384022258222103, 0.24851974844932556, -0.021121827885508537,
     -0.012337780557572842],
    [0.1954098641872406, 0.18901507556438446, 0.06653567403554916,
     -0.053655728697776794]]


def test_the_bert_reference_by_configuration_is_the_parent_s_by_keywords():
    """``embed(params, ids, lengths, config)`` returns what the parent's
    ``embed(..., heads=, eps=)`` returned: equal to the last bit where it
    was pinned, to 1e-6 on a CPU that rounds a sum otherwise."""
    cell = tiny_cell("bge-small-10m.query-steady")
    weights = cell.reference.weights(cell.config, 11)
    got = cell.reference.embed(weights, PARENT_IDS.astype(np.int32),
                               np.array(PARENT_LENGTHS, np.int32),
                               cell.config)
    assert got.dtype == np.float32 and got.shape == (5, 64)
    np.testing.assert_allclose(got[:, :4], PARENT_ROWS, rtol=0, atol=1e-6)
    assert np.allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)


def test_the_reference_s_weights_are_its_own_and_the_program_holds_them():
    """The weights come from the seed and the configuration alone, anew at
    every call; ``build`` loads those arrays, in the program's tree and
    type, and the program's tokens are what the reference is fed."""
    cell = tiny_cell("bge-small-10m.query-steady")
    weights = cell.reference.weights(cell.config, 11)
    again = cell.reference.weights(cell.config, 11)
    other = cell.reference.weights(cell.config, 12)
    token = weights["embeddings"]["token"]
    assert token.dtype == np.float32 and token.shape == (8192, 64)
    assert token is not again["embeddings"]["token"]
    assert np.array_equal(token, again["embeddings"]["token"])
    assert not np.array_equal(token, other["embeddings"]["token"])
    assert abs(float(token.std()) - 0.02) < 1e-3
    embedder = cell.model.build(cell.config, weights)
    import jax

    held = jax.tree_util.tree_leaves_with_path(embedder.params)
    made = jax.tree_util.tree_leaves_with_path(weights)
    assert [p for p, _ in held] == [p for p, _ in made]
    assert all(h.dtype == np.float32 and np.array_equal(np.asarray(h), m)
               for (_, h), (_, m) in zip(held, made))
    ids, lengths = cell.model.tokens(embedder, cell.config, TEXTS)
    assert ids.shape == (len(TEXTS), cell.config["serving"]["max_len"])
    assert lengths.tolist()[:4] == [5, 32, 3, 3]      # [CLS] ... [SEP]
    assert cell.reference.MIN_COS == 0.99988


# (flops, bytes) of benchmark/lib/costs.py before the BERT functions moved
# to benchmark/models/bert.py, at bge-small-10m's published sizes
COSTS_BEFORE = {
    ((8, 128), True): (45902462976.0, 194248704.0),
    ((4, 128), True): (22951231488.0, 118358016.0),
    ((1, 128), True): (5737807872.0, 61440000.0),
    ((32, 64), False): (89389006848.0, 195035136.0),
    ((17, 128), False): (97542733824.0, 204570624.0),
}


@pytest.mark.parametrize("shape, ragged", list(COSTS_BEFORE))
def test_a_dispatch_costs_what_it_cost_before_the_move(shape, ragged):
    """The same ``least`` for the same shapes: ``encoder_roofline`` divides
    byte-identical operations and bytes."""
    cell = spec.load(ROOT).cell("bge-small-10m.ingest-backlog")
    assert cell.model.dispatch_cost(cell.config, shape, ragged) \
        == COSTS_BEFORE[shape, ragged]


def test_the_scan_costs_what_it_cost_before():
    assert costs.knn_search_cost(2, 10485760, 384, 2) \
        == (16106127360.0, 8053066752.0)


def test_the_fill_s_chunk_is_bounded_in_bytes_at_today_s_size():
    rows = vector_store._FILL_CHUNK_BYTES // (2 * 384)
    assert rows == 1 << 19           # 20 chunks for ten million rows
    assert vector_store._FILL_CHUNK_BYTES // (2 * 2048) == 98304


class _Partial:
    """An embedder that lacks part of the protocol."""

    params, ragged = {}, False
    tokenizer = types.SimpleNamespace(batch=lambda texts, max_len: None)

    def get_embedding_dimension(self):
        return 8


def test_a_missing_part_of_the_embedder_protocol_is_named(tmp_path):
    cell = tiny_cell("bge-small-10m.query-steady")
    model = types.SimpleNamespace(__name__="benchmark.models.partial",
                                  build=lambda config, weights: _Partial())
    system = vector_store.System(
        types.SimpleNamespace(config=cell.config, model=model,
                              reference=cell.reference), 0, str(tmp_path))
    with pytest.raises(TypeError, match=r"benchmark.models.partial.build\(\) "
                       r"returned \(_Partial\) lacks encode_batch_device"):
        system.make_embedder()
    system.embedder = _Partial()
    with pytest.raises(TypeError, match=r"lacks pack_tokens"):
        system.instrument({})

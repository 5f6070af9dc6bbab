"""What a test of the benchmark's own suite asserted of the tree it was
written on, and an added cell ends: the test files that are there may be
edited by a ``benchmark`` PR alone, so the test is marked here, strictly (it
fails the suite again the day it passes), and restated beside the cell that
ended it. A ``benchmark`` PR folds the restatement into the file and deletes
this one."""

from __future__ import annotations

import pytest

SUPERSEDED = {
    "tests/test_spec.py::test_the_repo_s_own_benchmark_resolves":
        "pins the benchmark's cells to the two of bge-small-10m and every "
        "model to bert; restated over every cell in test_qwen3_next.py::"
        "test_the_repo_s_own_benchmark_resolves_with_every_cell",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for suffix, reason in SUPERSEDED.items():
            if item.nodeid.endswith(suffix):
                item.add_marker(pytest.mark.xfail(reason=reason, strict=True))

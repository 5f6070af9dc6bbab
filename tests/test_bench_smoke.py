"""bench.py must stay runnable — the driver executes it on real hardware
at round end; a silent import/shape regression there would void the
round's measurements. CPU-sized smoke of each leg's machinery."""

from __future__ import annotations

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clear():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


def test_bench_imports_and_docs():
    sys.path.insert(0, str(REPO))
    import bench

    docs = bench.make_docs(64)
    assert len(docs) == 64 and all(isinstance(d, str) for d in docs)


def test_bench_etl_leg_small():
    import bench

    out = bench.bench_etl(4000)
    assert out["etl_rows_per_s_1w"] > 0
    assert out["etl_rows_per_s_8w"] > 0
    assert out["etl_n_cores"] >= 1


def _run_bench(**env_over):
    import json
    import os
    import subprocess

    env = dict(os.environ, **env_over)
    proc = subprocess.run(
        [sys.executable, "-u", str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, json.loads(lines[-1])


def test_bench_fails_loudly_when_backend_is_dead():
    """A dead backend with a device leg requested is a NON-ZERO exit with
    the error named on the JSON line — never ``value: null`` with rc=0,
    which is how two rounds of records were lost unnoticed."""
    proc, out = _run_bench(JAX_PLATFORMS="bogus", BENCH_SKIP="etl")
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "backend init failed" in out["error"]
    assert out["value"] is None and out["unit"] == "docs/s"
    assert {"platform", "device_kind", "n_devices"} <= set(out)


def test_bench_refuses_device_legs_off_the_chip():
    """The CPU backend is alive, but a device leg measured there would be
    a CPU number under a device metric's name: refuse before running
    anything, and name the platform found."""
    proc, out = _run_bench(JAX_PLATFORMS="cpu")
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "need a TPU" in out["error"] and "'cpu'" in out["error"]
    assert out["platform"] == "cpu" and out["n_devices"] >= 1
    assert out["value"] is None
    assert not any(k.startswith("etl_") for k in out)  # nothing ran


def test_bench_stamps_the_device_on_host_only_runs():
    """With every device leg skipped the host legs run anywhere — and the
    line still says which device JAX had."""
    skip = ("embed,framework,knn,serving,autojit,scaleout,"
            "durability,recovery,replica,qos,semantic_cache,etl")
    proc, out = _run_bench(JAX_PLATFORMS="cpu", BENCH_SKIP=skip)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert out["platform"] == "cpu" and out["device_kind"]
    assert "error" not in out


def test_graft_dryrun_provisions_cpu_before_device_touch():
    """_provision_devices must force the CPU platform BEFORE its first
    device touch — the dryrun runs on virtual CPU devices by definition
    and must never claim a real chip."""
    src = (REPO / "__graft_entry__.py").read_text()
    body = src.split("def _provision_devices", 1)[1].split("\ndef ", 1)[0]
    body = body.split('"""')[2]  # code after the docstring
    assert body.index("jax.config.update") < body.index("jax.devices()")


def test_bench_tokenizer_and_encoder_shapes():
    """The embed leg's host-side pieces: WordPiece batch + bucketing pack
    produce shapes the jitted encoder accepts."""
    import numpy as np

    import bench
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)

    tok = WordPieceTokenizer(
        make_synthetic_vocab([f"word{i}" for i in range(512)],
                             vocab_size=30522), max_len=bench.SEQ)
    docs = bench.make_docs(8)
    ids, mask = tok.batch(docs, pad_to=bench.SEQ)
    assert ids.shape == (8, bench.SEQ) and mask.shape == ids.shape
    lens = mask.sum(axis=1)
    assert (lens > 0).all()
    # pack() logic: int16 ids + bucket width multiple of 16
    width = min(bench.SEQ, max(16, int(-(-int(lens.max()) // 16) * 16)))
    assert width % 16 == 0 and ids[:, :width].astype(np.int16).dtype == \
        np.int16

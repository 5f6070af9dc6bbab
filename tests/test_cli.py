"""CLI: spawn / replay / record (reference: python/pathway/cli.py:53-280)."""

from __future__ import annotations

import csv
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)


def _run_cli(*args, env=None, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu", *args],
        env=env or _ENV, capture_output=True, text=True, timeout=timeout)


def test_help_and_version():
    res = _run_cli("--help")
    assert res.returncode == 0
    assert "spawn" in res.stdout and "replay" in res.stdout
    res = _run_cli("--version")
    assert "pathway-tpu" in res.stdout


_PROGRAM = textwrap.dedent("""
    import os
    import pathway_tpu as pw

    out = os.environ["TEST_OUT"] + os.environ.get("PATHWAY_PROCESS_ID", "?")
    t = pw.io.fs.read(os.environ["TEST_IN"], format="plaintext", mode="batch",
                      autocommit_duration_ms=20, persistent_id="src")
    counts = t.groupby(t.data).reduce(word=t.data, c=pw.reducers.count())
    pw.io.fs.write(counts, out, format="csv")
    pw.run()
""")


def _counts(path) -> dict[str, int]:
    state: dict[str, int] = {}
    with open(path, newline="") as f:
        for row in csv.DictReader(f):
            if int(row["diff"]) > 0:
                state[row["word"]] = int(row["c"])
            elif state.get(row["word"]) == int(row["c"]):
                del state[row["word"]]
    return state


def test_spawn_multi_process_env(tmp_path):
    (tmp_path / "in").mkdir()
    (tmp_path / "in" / "a.txt").write_text("x\ny\nx\n")
    prog = tmp_path / "prog.py"
    prog.write_text(_PROGRAM)
    env = dict(_ENV, TEST_IN=str(tmp_path / "in"),
               TEST_OUT=str(tmp_path / "out"))
    res = _run_cli("spawn", "-n", "2", sys.executable, str(prog), env=env)
    assert res.returncode == 0, res.stderr
    # -n forks a true process cluster (TCP exchange, engine/multiproc.py):
    # each process owns a worker block and writes ITS shard of the result;
    # the union of the shards equals the single-process answer and the
    # shards are disjoint (state actually partitioned across processes)
    assert "2 processes (2 total workers)" in res.stderr
    c0 = _counts(tmp_path / "out0")
    c1 = _counts(tmp_path / "out1")
    assert not (set(c0) & set(c1))
    assert {**c0, **c1} == {"x": 2, "y": 1}


def test_record_then_replay(tmp_path):
    (tmp_path / "in").mkdir()
    (tmp_path / "in" / "a.txt").write_text("p\nq\n")
    prog = tmp_path / "prog.py"
    prog.write_text(_PROGRAM)
    record = str(tmp_path / "rec")
    env = dict(_ENV, TEST_IN=str(tmp_path / "in"),
               TEST_OUT=str(tmp_path / "out"))

    res = _run_cli("spawn", "--record", "--record-path", record,
                   sys.executable, str(prog), env=env)
    assert res.returncode == 0, res.stderr
    assert _counts(tmp_path / "out0") == {"p": 1, "q": 1}
    assert os.path.isdir(os.path.join(record, "streams"))

    # replay against an EMPTY input dir: rows must come from the recording
    for f in (tmp_path / "in").iterdir():
        f.unlink()
    env2 = dict(env, TEST_OUT=str(tmp_path / "replay_out"))
    res = _run_cli("replay", "--record-path", record, "--mode", "batch",
                   sys.executable, str(prog), env=env2)
    assert res.returncode == 0, res.stderr
    assert _counts(tmp_path / "replay_out0") == {"p": 1, "q": 1}

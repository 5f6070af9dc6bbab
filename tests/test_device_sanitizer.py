"""Steady-state device sanitizer (engine/device_sanitizer.py): the
env-armed lifecycle (off → armed → steady → suspended), the compile-miss
hook raising/recording on post-warmup compiles, the transfer guard
blocking implicit host→device operand transfers, the bench-facing
compile counter, and the warmup compile-count pins the PWT4xx family
gates at runtime — mirrors tests/test_lock_sanitizer.py for the
env-armed-instrument pattern."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from pathway_tpu.engine import device_sanitizer as ds  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    monkeypatch.delenv("PATHWAY_DEVICE_SANITIZER", raising=False)
    ds._reset_for_tests()
    yield
    ds._reset_for_tests()


def _fresh_jit(salt: float):
    """A jitted fn no other test has compiled (the salt lands in the
    executable, so jax's in-process cache can't serve it)."""
    return jax.jit(lambda x: x * 2.0 + salt)


# ---------------------------------------------------------------------------
# off by default — everything is a no-op
# ---------------------------------------------------------------------------

def test_disabled_sanitizer_is_inert():
    assert not ds.sanitizer_enabled()
    assert ds.arm() is False
    assert ds.declare_steady_state() is False
    assert not ds.in_steady_state()
    # dispatching fresh code is nobody's business when off
    f = _fresh_jit(0.125)
    f(jax.device_put(np.ones((4,), np.float32)))
    assert ds.violations() == []


@pytest.mark.parametrize("val,enabled,raises", [
    ("1", True, True), ("true", True, True), ("on", True, True),
    ("report", True, False), ("warn", True, False), ("", False, False),
    ("0", False, False)])
def test_env_contract(monkeypatch, val, enabled, raises):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", val)
    assert ds.sanitizer_enabled() is enabled
    if enabled:
        assert ds._raise_on_violation() is raises


# ---------------------------------------------------------------------------
# armed lifecycle
# ---------------------------------------------------------------------------

def test_warmup_window_counts_compiles_without_violating(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    assert ds.arm() is True
    assert not ds.in_steady_state()
    f = _fresh_jit(0.25)
    f(jax.device_put(np.ones((4,), np.float32)))
    assert ds.warmup_compiles() > 0
    assert ds.post_warmup_compiles() == 0
    assert ds.violations() == []


def test_post_warmup_compile_raises_and_cached_dispatch_is_free(
        monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    ds.arm()
    f = _fresh_jit(0.375)
    x = jax.device_put(np.ones((4,), np.float32))
    f(x)  # warm
    ds.declare_steady_state()
    assert ds.in_steady_state()
    f(x)  # cache hit: silent
    assert ds.post_warmup_compiles() == 0
    g = _fresh_jit(0.4375)
    with pytest.raises(ds.DeviceDisciplineViolation,
                       match="steady-state serving window"):
        g(x)
    assert ds.post_warmup_compiles() == 1
    assert [v["kind"] for v in ds.violations()] == ["post-warmup-compile"]
    # the violation names the remediation path
    assert "suspend_steady_state" in ds.violations()[0]["message"]


def test_steady_state_blocks_implicit_transfer(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    ds.arm()
    f = _fresh_jit(0.5)
    host = np.ones((4,), np.float32)
    f(jax.device_put(host))  # warm at this shape
    ds.declare_steady_state()
    # explicit residency establishment stays legal — that is the fix
    f(jax.device_put(host))
    with pytest.raises(Exception, match="[Tt]ransfer"):
        f(host)  # implicit numpy operand transfer


def test_suspend_steady_state_reopens_warmup_window(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    ds.arm()
    x = jax.device_put(np.ones((4,), np.float32))
    _fresh_jit(0.625)(x)
    ds.declare_steady_state()
    before = ds.warmup_compiles()
    with ds.suspend_steady_state("slab growth"):
        assert not ds.in_steady_state()
        _fresh_jit(0.6875)(x)  # legal maintenance compile
        _fresh_jit(0.6875)(np.ones((4,), np.float32))  # transfers too
    assert ds.in_steady_state()  # restored on exit
    assert ds.warmup_compiles() > before
    assert ds.post_warmup_compiles() == 0
    assert ds.violations() == []


def test_report_mode_records_without_raising(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "report")
    ds.arm()
    x = jax.device_put(np.ones((4,), np.float32))
    _fresh_jit(0.75)(x)
    ds.declare_steady_state()
    _fresh_jit(0.8125)(x)  # would raise in enforce mode
    assert ds.post_warmup_compiles() >= 1
    assert any(v["kind"] == "post-warmup-compile"
               for v in ds.violations())


def test_install_compile_counter_needs_no_env():
    count = ds.install_compile_counter()
    before = count()
    _fresh_jit(0.875)(jax.device_put(np.ones((4,), np.float32)))
    assert count() > before
    assert ds.violations() == []  # counter never enforces


# ---------------------------------------------------------------------------
# pw.warmup integration + compile-count pins
# ---------------------------------------------------------------------------

def _tiny_cfg(max_len=64):
    from pathway_tpu.models.encoder import EncoderConfig

    return EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                         intermediate=32, max_len=max_len)


def test_warmup_declares_steady_state(monkeypatch):
    import pathway_tpu as pw

    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    pw.warmup()  # no embedder: still brackets the window
    assert ds.in_steady_state()
    assert ds.post_warmup_compiles() == 0


def test_rewarmup_of_armed_process_is_not_a_violation(monkeypatch):
    import pathway_tpu as pw

    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    pw.warmup()
    assert ds.in_steady_state()
    pw.warmup()  # re-warm: suspends, never violates
    assert ds.in_steady_state()
    assert ds.violations() == []


@pytest.mark.slow
def test_ragged_encoder_ladder_pin_under_sanitizer(monkeypatch):
    """The ragged compile set stays ≤ 6 ladder entries, and re-dispatching
    a warmed bucket in steady state compiles NOTHING."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    emb = JaxEncoderEmbedder(config=_tiny_cfg(), ragged=True, max_len=64)
    out = pw.warmup(emb)
    assert ds.in_steady_state()
    ladder = [e for e in out["compiled"] if e[0] != "autojit"]
    assert 0 < len(ladder) <= 6, out["compiled"]
    assert ds.warmup_compiles() > 0
    # steady state: the exact warmed (bucket, width) dispatch is free
    bucket = emb.ragged_buckets()[0]
    ops, _n_docs = emb.ragged_warmup_operands(bucket)
    emb.encode_ragged_chunk(ops)
    assert ds.post_warmup_compiles() == 0
    assert ds.violations() == []


@pytest.mark.slow
def test_paged_multi_extent_search_zero_compiles_in_steady_state(
        monkeypatch):
    """After warmup walks the search fan-out over a MULTI-extent paged
    slab, a same-bucket query compiles nothing and transfers nothing
    implicitly — the steady-state serving contract, end to end."""
    import pathway_tpu as pw
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    monkeypatch.setenv("PATHWAY_DEVICE_SANITIZER", "1")
    idx = BruteForceKnnIndex(8, metric=KnnMetric.COS, page_rows=128)
    rng = np.random.default_rng(7)
    vecs = rng.normal(size=(300, 8)).astype(np.float32)  # 3 extents
    idx.add_batch([Pointer(i) for i in range(300)], vecs)
    idx.drain()
    pw.warmup(index=idx, ks=(3,))
    assert ds.in_steady_state()
    res1 = idx.search([(Pointer(10 ** 6), vecs[5], 3, None)])
    assert res1[0][0][0] == Pointer(5)
    first = ds.post_warmup_compiles()
    # the second same-bucket query must be compile-free even if the
    # first touched a shape warmup missed
    res2 = idx.search([(Pointer(10 ** 6 + 1), vecs[9], 3, None)])
    assert res2[0][0][0] == Pointer(9)
    assert ds.post_warmup_compiles() == first == 0, ds.violations()
    assert ds.violations() == []

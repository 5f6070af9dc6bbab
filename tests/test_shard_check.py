"""Sharding/placement analyzer (static_check/shard_check.py): one
true-positive and one true-negative per PWT101–PWT110 code, the UDF
classifier, the iterate integration, and the CLI's ``--tpu-mesh`` /
``--json`` front door."""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import pathway_tpu as pw
import pathway_tpu.internals.schema as sch
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.static_check import (MeshSpec, Severity,
                                                classify_udf,
                                                parse_mesh_spec)
from pathway_tpu.internals.static_check.shard_check import (
    check_attention_sharding,
    check_mesh_fits,
    check_pipeline_layout,
    check_shard_specs,
    check_sharded_dim,
)
from tests.utils import T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear():
    G.clear()
    yield
    G.clear()


def codes(diags):
    return [d.code for d in diags]


def _streaming_table(tmp_path, **types):
    types = types or {"a": int}
    return pw.io.fs.read(str(tmp_path), format="json", mode="streaming",
                         schema=sch.schema_from_types(**types))


def _bind(table):
    pw.io.subscribe(table, lambda *a, **k: None)


def _knn_pipeline(tmp_path, *, mesh="auto", reserved_space=1024,
                  embedder=None, dimensions=16, dtype="float32",
                  tenant_quotas=None):
    """Streaming docs -> sharded KNN index -> bound query results."""
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index)

    docs = _streaming_table(tmp_path, doc=str)
    data = docs.select(vec=pw.apply_with_type(
        lambda d: np.zeros(16, dtype=np.float32), np.ndarray, docs.doc))
    index = default_brute_force_knn_document_index(
        data.vec, data, dimensions=dimensions, reserved_space=reserved_space,
        mesh=mesh, embedder=embedder, dtype=dtype,
        tenant_quotas=tenant_quotas)
    hits = index.query_as_of_now(data.vec, number_of_matches=1)
    _bind(hits)
    return hits


# ---------------------------------------------------------------------------
# mesh spec parsing
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_forms():
    assert parse_mesh_spec("4x2") == MeshSpec(4, 2)
    assert parse_mesh_spec("4×2") == MeshSpec(4, 2)
    assert parse_mesh_spec("8") == MeshSpec(8, 1)
    assert parse_mesh_spec(None) is None
    assert parse_mesh_spec(MeshSpec(2, 2)) == MeshSpec(2, 2)
    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh

    assert parse_mesh_spec(MeshConfig(data=4, model=2)) == MeshSpec(4, 2)
    assert parse_mesh_spec(make_mesh(MeshConfig(2, 1))) == MeshSpec(2, 1)
    with pytest.raises(ValueError, match="mesh spec"):
        parse_mesh_spec("4xbanana")


# ---------------------------------------------------------------------------
# PWT101 — mesh axes do not fit the device count
# ---------------------------------------------------------------------------

def test_pwt101_oversubscribed_mesh_is_error():
    diags = check_mesh_fits(3, 2, 4)
    assert codes(diags) == ["PWT101"]
    assert diags[0].is_error


def test_pwt101_non_dividing_mesh_is_error():
    # same severity as the runtime: MeshConfig.from_env refuses to build
    # this topology, so the checker must not wave it through
    diags = check_mesh_fits(3, 2, 8)
    assert codes(diags) == ["PWT101"]
    assert diags[0].is_error


def test_pwt101_malformed_mesh_value_is_a_diagnostic_not_a_crash(tmp_path):
    # a typo'd PATHWAY_STATIC_CHECK_MESH must not abort a warn-mode run
    t = _streaming_table(tmp_path)
    _bind(t.select(b=t.a * 2))
    diags = pw.static_check(mesh="4,2")
    assert "PWT101" in codes(diags)
    [d] = [d for d in diags if d.code == "PWT101"]
    assert "mesh spec" in d.message


def test_pwt101_negative_fitting_meshes():
    assert check_mesh_fits(4, 2, 8) == []
    assert check_mesh_fits(4, 1, 8) == []  # dividing submesh is fine


def test_pwt101_env_override_vs_analysis_mesh(tmp_path, monkeypatch):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=t.a * 2))
    monkeypatch.setenv("PATHWAY_DATA_PARALLEL", "3")
    diags = pw.static_check(mesh="4x2")
    assert "PWT101" in codes(diags)
    monkeypatch.delenv("PATHWAY_DATA_PARALLEL")
    assert "PWT101" not in codes(pw.static_check(mesh="4x2"))


# ---------------------------------------------------------------------------
# PWT102 — sharded leading dim not divisible by the axis
# ---------------------------------------------------------------------------

def test_pwt102_non_divisible_knn_reservation(tmp_path):
    _knn_pipeline(tmp_path, reserved_space=1001)
    diags = pw.static_check(mesh="8x1")
    pwt102 = [d for d in diags if d.code == "PWT102"]
    assert len(pwt102) == 1 and pwt102[0].is_error
    assert "1001" in pwt102[0].message
    assert "rows/shard" in pwt102[0].message  # layout-accurate padding info


def test_pwt102_negative_divisible_reservation(tmp_path):
    _knn_pipeline(tmp_path, reserved_space=1024)
    assert "PWT102" not in codes(pw.static_check(mesh="8x1"))


def test_pwt102_pure_helpers():
    assert codes(check_sharded_dim(30, 8, what="x")) == ["PWT102"]
    assert check_sharded_dim(32, 8, what="x") == []
    assert check_sharded_dim(None, 8, what="x") == []
    assert codes(check_pipeline_layout(10, 4)) == ["PWT102"]
    assert check_pipeline_layout(12, 4) == []


# ---------------------------------------------------------------------------
# PWT103 — shard_map specs vs operand ranks / mesh axes
# ---------------------------------------------------------------------------

def test_pwt103_spec_longer_than_operand_rank():
    diags = check_shard_specs({"data": 8}, [("data", None, "data")], [2])
    assert codes(diags) == ["PWT103"]
    assert diags[0].is_error


def test_pwt103_spec_names_unknown_axis():
    diags = check_shard_specs({"data": 8, "model": 1}, [("tensor",)], [3])
    assert codes(diags) == ["PWT103"]
    assert "tensor" in diags[0].message


def test_pwt103_negative_kernel_layout_is_consistent(tmp_path):
    # the sharded-KNN search kernel's own spec/rank contract (propagated
    # from the plan's factory dtype into the kernel wrapper layout) must
    # be clean for every slab dtype
    from pathway_tpu.parallel.sharded_knn import search_operand_layout

    for dtype in ("float32", "bfloat16", "int8"):
        layout = search_operand_layout(dtype)
        assert check_shard_specs(
            {"data": 8, "model": 1},
            [spec for spec, _ in layout],
            [rank for _, rank in layout]) == []
    _knn_pipeline(tmp_path, dtype="int8")
    assert "PWT103" not in codes(pw.static_check(mesh="8x1"))


def test_shard_map_rejects_unknown_axis_eagerly():
    from jax.sharding import PartitionSpec as P

    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh, shard_map

    mesh = make_mesh(MeshConfig(2, 1))
    with pytest.raises(ValueError, match="PWT103"):
        shard_map(lambda x: x, mesh=mesh, in_specs=(P("bogus"),),
                  out_specs=P())


# ---------------------------------------------------------------------------
# PWT104 — slab pinned to a different topology than the pipeline
# ---------------------------------------------------------------------------

def test_pwt104_index_mesh_differs_from_analysis_mesh(tmp_path):
    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh

    _knn_pipeline(tmp_path, mesh=make_mesh(MeshConfig(2, 1)))
    diags = pw.static_check(mesh="8x1")
    pwt104 = [d for d in diags if d.code == "PWT104"]
    assert len(pwt104) == 1
    assert pwt104[0].severity is Severity.WARNING


def test_pwt104_negative_auto_and_matching_meshes(tmp_path):
    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh

    _knn_pipeline(tmp_path, mesh="auto")
    assert "PWT104" not in codes(pw.static_check(mesh="8x1"))
    G.clear()
    _knn_pipeline(tmp_path, mesh=make_mesh(MeshConfig(2, 1)))
    assert "PWT104" not in codes(pw.static_check(mesh="2x1"))


def test_pwt104_runtime_counterpart_warns(caplog):
    from pathway_tpu.engine.index_ops import ExternalIndexOperator
    from pathway_tpu.parallel.mesh import MeshConfig, make_mesh, use_mesh
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    idx = ShardedKnnIndex(8, mesh=make_mesh(MeshConfig(2, 1)))
    with use_mesh(make_mesh(MeshConfig(8, 1))):
        with caplog.at_level("WARNING", logger="pathway_tpu.shard_check"):
            ExternalIndexOperator(
                index=idx, data_vec_pos=0, data_filter_pos=None,
                query_vec_pos=0, query_limit_pos=None,
                query_filter_pos=None)
    assert any("PWT104" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# PWT105 — host-device sync point on a per-batch path
# ---------------------------------------------------------------------------

def _syncy(x):
    return np.asarray(x).item() * 2.0


def test_pwt105_item_sync_on_streaming_path(tmp_path):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(_syncy, t.a)))
    diags = pw.static_check()
    assert "PWT105" in codes(diags)
    [d] = [d for d in diags if d.code == "PWT105"]
    assert ".item()" in d.message


def test_pwt105_negative_static_pipeline_or_pure_udf(tmp_path):
    t = T("""
    a
    1
    """)
    assert "PWT105" not in codes(pw.static_check(t.select(
        b=pw.apply(_syncy, t.a))))
    G.clear()
    s = _streaming_table(tmp_path)
    _bind(s.select(b=s.a * 2))
    assert "PWT105" not in codes(pw.static_check())


# ---------------------------------------------------------------------------
# PWT106 — ulysses heads not divisible by the axis
# ---------------------------------------------------------------------------

def test_pwt106_heads_not_divisible():
    diags = check_attention_sharding((2, 32, 6, 8), "4x1", scheme="ulysses")
    assert codes(diags) == ["PWT106"]
    assert diags[0].is_error


def test_pwt106_negative_divisible_heads_or_ring():
    assert check_attention_sharding((2, 32, 8, 8), "4x1",
                                    scheme="ulysses") == []
    # ring attention never re-shards heads
    assert check_attention_sharding((2, 32, 6, 8), "4x1",
                                    scheme="ring") == []


def test_ulysses_runtime_error_mentions_code():
    import jax.numpy as jnp

    from pathway_tpu.parallel import MeshConfig, make_mesh, ulysses_attention

    mesh = make_mesh(MeshConfig(4, 1))
    q = jnp.zeros((1, 16, 6, 4))
    with pytest.raises(ValueError, match="PWT106"):
        ulysses_attention(q, q, q, mesh=mesh)


def test_ring_runtime_error_on_non_divisible_seq():
    import jax.numpy as jnp

    from pathway_tpu.parallel import MeshConfig, make_mesh, ring_attention

    mesh = make_mesh(MeshConfig(4, 1))
    q = jnp.zeros((1, 18, 4, 4))
    with pytest.raises(ValueError, match="PWT102"):
        ring_attention(q, q, q, mesh=mesh)


# ---------------------------------------------------------------------------
# PWT107 — model axis configured but unused
# ---------------------------------------------------------------------------

def test_pwt107_model_axis_unused(tmp_path):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=t.a * 2))
    diags = pw.static_check(mesh="4x2")
    pwt107 = [d for d in diags if d.code == "PWT107"]
    assert len(pwt107) == 1
    assert pwt107[0].severity is Severity.INFO


def test_pwt107_negative_model_1_or_device_embedder(tmp_path):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=t.a * 2))
    assert "PWT107" not in codes(pw.static_check(mesh="8x1"))
    G.clear()

    class DeviceEmbedder:
        def encode_batch_device(self, texts):  # model-parallel capable
            raise NotImplementedError

        def get_embedding_dimension(self):
            return 16

    _knn_pipeline(tmp_path, mesh=None, embedder=DeviceEmbedder())
    assert "PWT107" not in codes(pw.static_check(mesh="4x2"))


# ---------------------------------------------------------------------------
# PWT111 — paged-store reservation / tenant quota layout
# ---------------------------------------------------------------------------

def test_pwt111_unaligned_reservation(tmp_path, monkeypatch):
    monkeypatch.delenv("PATHWAY_PAGE_ROWS", raising=False)
    _knn_pipeline(tmp_path, mesh=None, reserved_space=1500)
    diags = pw.static_check()
    pwt = [d for d in diags if d.code == "PWT111"]
    assert len(pwt) == 1
    assert pwt[0].severity is Severity.WARNING
    assert "1500" in pwt[0].message and "2048" in pwt[0].message


def test_pwt111_unaligned_tenant_quota(tmp_path):
    _knn_pipeline(tmp_path, mesh=None, reserved_space=1024,
                  tenant_quotas={"acme": 1500, "globex": 2048})
    diags = pw.static_check()
    pwt = [d for d in diags if d.code == "PWT111"]
    assert len(pwt) == 1  # only acme's quota is unaligned
    assert "acme" in pwt[0].message and "2048" in pwt[0].message


def test_pwt111_quotas_past_device_hbm(tmp_path, monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_HBM_GB", "1")
    # 16 B/row f32 rows: 2^27 rows/tenant x 4 tenants = 8 GiB >> 1 GiB
    quotas = {f"t{i}": (1 << 27) for i in range(4)}
    _knn_pipeline(tmp_path, mesh=None, reserved_space=1024,
                  tenant_quotas=quotas)
    diags = pw.static_check()
    over = [d for d in diags if d.code == "PWT111" and d.is_error]
    assert len(over) == 1
    assert "HBM" in over[0].message


def test_pwt111_negative_cases(tmp_path):
    # page-aligned reservation + aligned, HBM-fitting quotas: clean
    _knn_pipeline(tmp_path, mesh=None, reserved_space=2048,
                  tenant_quotas={"acme": 4096})
    assert "PWT111" not in codes(pw.static_check())


# ---------------------------------------------------------------------------
# PWT109 — host-only UDF on a streaming hot path
# ---------------------------------------------------------------------------

def _hosty(x):
    out = 0.0
    for tok in str(x).split(","):
        out += float(tok)
    return out


def test_pwt109_host_udf_on_streaming_path(tmp_path):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(_hosty, t.a)))
    diags = pw.static_check()
    pwt109 = [d for d in diags if d.code == "PWT109"]
    assert len(pwt109) == 1
    assert pwt109[0].severity is Severity.WARNING
    assert "loop" in pwt109[0].message


def test_pwt109_negative_static_source_or_traceable_udf(tmp_path):
    t = T("""
    a
    1
    """)
    assert "PWT109" not in codes(pw.static_check(
        t.select(b=pw.apply(_hosty, t.a))))
    G.clear()
    s = _streaming_table(tmp_path)
    _bind(s.select(b=pw.apply(lambda x: x * 2, s.a)))
    assert "PWT109" not in codes(pw.static_check())


# ---------------------------------------------------------------------------
# PWT110 — traceable UDF dispatched row-by-row
# ---------------------------------------------------------------------------

def test_pwt110_traceable_udf_rowwise_on_streaming_path(tmp_path):
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(lambda x: x * 2 + 1, t.a)))
    diags = pw.static_check()
    pwt110 = [d for d in diags if d.code == "PWT110"]
    assert len(pwt110) == 1
    assert pwt110[0].severity is Severity.INFO
    assert "batch=True" in pwt110[0].message


def test_pwt110_negative_batch_udf_or_static_source(tmp_path):
    t = _streaming_table(tmp_path)
    doubler = pw.udf(lambda xs: [x * 2 for x in xs], batch=True,
                     deterministic=True)
    _bind(t.select(b=doubler(t.a)))
    assert "PWT110" not in codes(pw.static_check())
    G.clear()
    s = T("""
    a
    1
    """)
    assert "PWT110" not in codes(pw.static_check(
        s.select(b=pw.apply(lambda x: x * 2, s.a))))


def test_pwt110_wording_tracks_autojit_state(tmp_path, monkeypatch):
    """With auto-jit on (the default) PWT110 is informational — the
    runtime fuses the UDF, so the message must NOT send the user off to a
    manual batch=True rewrite; with PATHWAY_AUTO_JIT=0 the manual rewrite
    is the suggestion again."""
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(lambda x: x * 2 + 1, t.a)))
    d, = [d for d in pw.static_check() if d.code == "PWT110"]
    assert "auto-jitted" in d.message
    assert "no change needed" in d.message
    G.clear()
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "0")
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(lambda x: x * 2 + 1, t.a)))
    d, = [d for d in pw.static_check() if d.code == "PWT110"]
    assert "auto-jitted" not in d.message
    assert "fix: pw.udf(batch=True)" in d.message
    G.clear()
    # a body the fused tier will refuse (math.exp has no IEEE-exact
    # vector counterpart) must keep the actionable manual advice even
    # with auto-jit on — "will be auto-jitted" would be an overclaim
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(lambda y: math.exp(y), t.a)))
    d, = [d for d in pw.static_check() if d.code == "PWT110"]
    assert "auto-jitted" not in d.message
    assert "fix: pw.udf(batch=True)" in d.message


def test_pwt109_wording_gains_overlap_caveat(tmp_path, monkeypatch):
    """Host-only-on-hot-path keeps its warning either way, but with
    auto-jit on it names the WindVE-style host/device overlap the split
    lowering provides."""
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(_hosty, t.a)))
    d, = [d for d in pw.static_check() if d.code == "PWT109"]
    assert "overlapped with the device leg" in d.message
    G.clear()
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "0")
    t = _streaming_table(tmp_path)
    _bind(t.select(b=pw.apply(_hosty, t.a)))
    d, = [d for d in pw.static_check() if d.code == "PWT109"]
    assert "overlapped" not in d.message


# ---------------------------------------------------------------------------
# UDF classifier
# ---------------------------------------------------------------------------

def test_classifier_traceable_vmappable_host():
    assert classify_udf(lambda x: x * 2 + 1).kind == "traceable"
    branchy = classify_udf(lambda x: x * 2 if x > 0 else -x)
    assert branchy.kind == "vmappable"
    assert classify_udf(_hosty).kind == "host"
    sync = classify_udf(_syncy)
    assert sync.jit_eligible and sync.sync_points


def test_classifier_async_and_sourceless():
    async def aget(x):
        return x

    assert classify_udf(aget).kind == "host"
    # builtins have no source or bytecode: conservative host
    assert classify_udf(len).kind == "host"


def test_classifier_bytecode_fallback_sees_control_flow():
    # a pure-local loop has an empty co_names: the bytecode fallback must
    # still classify it host (FOR_ITER/jumps), never traceable
    ns: dict = {}
    exec(textwrap.dedent("""
        def loopy(xs):
            t = 0
            for v in xs:
                t += v * v
            return t

        def straight(x):
            return x * 2 + 1
    """), ns)
    assert classify_udf(ns["loopy"]).kind == "host"
    assert classify_udf(ns["straight"]).kind == "traceable"


def test_classification_is_recorded_for_run_py(tmp_path):
    # the hook run.py will use to auto-jit the traceable class: the
    # analyzer stamps _shard_class on the plan's apply expressions and
    # aggregates them by function name
    from pathway_tpu.internals import expression as ex
    from pathway_tpu.internals.static_check import Analyzer

    t = _streaming_table(tmp_path)
    out = t.select(b=pw.apply(lambda x: x * 2, t.a))
    _bind(out)
    analyzer = Analyzer()
    analyzer.run()
    # keys carry the definition site so two lambdas never collide
    lambdas = {k: c for k, c in analyzer.udf_classifications.items()
               if k.startswith("<") or "<lambda>" in k}
    assert lambdas and any("test_shard_check.py" in k for k in lambdas)
    assert all(c.kind == "traceable" for c in lambdas.values())
    stamped = [
        sub
        for node in analyzer._nodes.values()
        for e in node.exprs
        for sub in ex.walk(e)
        if isinstance(sub, ex.ApplyExpression)
        and getattr(sub, "_shard_class", None) is not None
    ]
    assert stamped and all(s._shard_class.kind == "traceable"
                           for s in stamped)


# ---------------------------------------------------------------------------
# pw.iterate integration
# ---------------------------------------------------------------------------

def test_iterate_deep_body_does_not_hit_recursion_limit():
    t = T("""
    a
    1
    """)

    def body(t):
        for _ in range(1200):
            t = t.select(a=pw.this.a)
        return t

    result = pw.iterate(body, t=t)
    assert pw.static_check(result) == []


def test_iterate_body_codes_not_double_reported(tmp_path):
    # the body executes once per iteration at runtime, but the analyzer
    # sees ONE body graph: a diagnostic inside it must appear exactly once
    s = _streaming_table(tmp_path)

    def body(t):
        return t.select(a=pw.apply_with_type(_hosty, float, t.a))

    result = pw.iterate(body, t=s)
    _bind(result)
    diags = pw.static_check()
    assert codes(diags).count("PWT109") == 1


def test_iterate_body_dtype_errors_are_found():
    t = T("""
    a | b
    1 | x
    """)

    def body(t):
        return t.select(a=t.a + 1, b=t.b)

    bad = pw.iterate(body, t=t.select(a=t.a, b=t.b))
    # seed a dtype error inside the body of a second iterate
    def bad_body(t):
        return t.select(a=t.a + t.b, b=t.b)

    worse = pw.iterate(bad_body, t=t)
    diags = pw.static_check(bad, worse)
    assert codes(diags).count("PWT001") == 1


# ---------------------------------------------------------------------------
# MeshConfig.from_env eager validation (parallel/mesh.py)
# ---------------------------------------------------------------------------

def test_from_env_rejects_oversubscription(monkeypatch):
    from pathway_tpu.parallel.mesh import MeshConfig

    monkeypatch.setenv("PATHWAY_DATA_PARALLEL", "5")
    monkeypatch.setenv("PATHWAY_MODEL_PARALLEL", "2")
    with pytest.raises(ValueError) as e:
        MeshConfig.from_env(8)
    assert "PATHWAY_DATA_PARALLEL" in str(e.value)
    assert "PATHWAY_MODEL_PARALLEL" in str(e.value)


def test_from_env_rejects_non_dividing_product(monkeypatch):
    from pathway_tpu.parallel.mesh import MeshConfig

    monkeypatch.setenv("PATHWAY_DATA_PARALLEL", "3")
    monkeypatch.delenv("PATHWAY_MODEL_PARALLEL", raising=False)
    with pytest.raises(ValueError, match="does not divide"):
        MeshConfig.from_env(8)


def test_from_env_rejects_non_integer(monkeypatch):
    from pathway_tpu.parallel.mesh import MeshConfig

    monkeypatch.setenv("PATHWAY_DATA_PARALLEL", "lots")
    with pytest.raises(ValueError, match="positive integers"):
        MeshConfig.from_env(8)


def test_from_env_accepts_valid_and_default(monkeypatch):
    from pathway_tpu.parallel.mesh import MeshConfig

    monkeypatch.setenv("PATHWAY_DATA_PARALLEL", "4")
    monkeypatch.setenv("PATHWAY_MODEL_PARALLEL", "2")
    assert MeshConfig.from_env(8) == MeshConfig(4, 2)
    monkeypatch.delenv("PATHWAY_DATA_PARALLEL")
    monkeypatch.delenv("PATHWAY_MODEL_PARALLEL")
    assert MeshConfig.from_env(8) == MeshConfig(8, 1)


# ---------------------------------------------------------------------------
# CLI: --tpu-mesh / --json
# ---------------------------------------------------------------------------

def _run_check(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "check", *args],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=REPO)


NEGATIVE_EXAMPLE = os.path.join(
    os.path.dirname(__file__), "shard_check_negative_example.py")


def test_cli_tpu_mesh_flags_seeded_bad_slab():
    proc = _run_check("--tpu-mesh", "8x1", NEGATIVE_EXAMPLE)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PWT102" in proc.stdout


def test_cli_tpu_mesh_json_output():
    proc = _run_check("--tpu-mesh", "8x1", "--json", NEGATIVE_EXAMPLE)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    entries = json.loads(proc.stdout)
    pwt102 = [e for e in entries if e["code"] == "PWT102"]
    assert pwt102 and pwt102[0]["severity"] == "error"
    assert pwt102[0]["file"].endswith("shard_check_negative_example.py")
    assert isinstance(pwt102[0]["line"], int)
    assert pwt102[0]["script"].endswith("shard_check_negative_example.py")


def test_cli_without_mesh_passes_the_fixture():
    # the seeded misconfiguration is mesh-relative: without a topology the
    # slab stays unsharded and the script is clean of errors
    proc = _run_check(NEGATIVE_EXAMPLE)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rejects_malformed_mesh(tmp_path):
    script = tmp_path / "empty.py"
    script.write_text("")
    proc = _run_check("--tpu-mesh", "4xbanana", str(script))
    assert proc.returncode != 0
    assert "mesh spec" in proc.stderr


def test_cli_json_clean_script_emits_empty_list(tmp_path):
    script = tmp_path / "clean.py"
    script.write_text(textwrap.dedent("""
        import pathway_tpu as pw
        t = pw.debug.table_from_markdown('''
        a
        1
        ''')
        pw.debug.compute_and_print(t.select(c=t.a * 2))
    """))
    proc = _run_check("--json", str(script))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout) == []

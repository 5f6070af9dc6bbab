"""The trace reducer: its interval arithmetic on hand-made events, and the
whole reduction against small recorded profiles kept under data/."""

from __future__ import annotations

import json
import os
import types

import pytest

from benchmark.lib import readers, trace

from conftest import load_tool

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_time_is_the_union_of_operation_intervals():
    assert trace._union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [
        (0, 3), (5, 6)]


def test_self_time_takes_the_children_out_of_a_nested_operation():
    # a while-loop of 10 s holding two bodies of 3 s and 4 s, then a lone op
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "body"), (5.0, 9.0, "body"),
              (12.0, 13.0, "copy")]
    assert trace._self_seconds(events) == {"while": 3.0, "body": 7.0,
                                           "copy": 1.0}


def test_a_device_is_reduced_inside_the_window_only():
    ops = [(0.0, 1.0, "a"), (1.5, 2.5, "b"), (4.0, 6.0, "a"),
           (9.0, 11.0, "c")]
    modules = [(0.0, 2.5, "jit_search(111)"), (4.0, 6.0, "jit_step(222)"),
               (9.0, 11.0, "jit_search(111)")]
    dev = trace._reduce_device("d0", ops, modules, 0.5, 10.0)
    # a is clipped at the window's start, c at its end
    assert dev.busy_s == pytest.approx(0.5 + 1.0 + 2.0 + 1.0)
    assert dev.gaps == [(1.0, 1.5), (2.5, 4.0), (6.0, 9.0)]
    assert dev.ops == pytest.approx({
        "jit_search/a": 0.5, "jit_search/b": 1.0, "jit_step/a": 2.0,
        "jit_search/c": 1.0})
    # only executions that lie whole inside the window are timed
    assert dev.modules == {"jit_step": [2.0]}


def test_operations_are_kept_by_scope_and_without_one_by_module():
    ops = [(0.0, 4.0, "%while = (s32[], f32[8]{0}) while(%tuple)"),
           (1.0, 2.0, "%fusion.1 = f32[8]{0} x"),
           (2.0, 3.5, "%fusion.2 = f32[8]{0} y"),
           (5.0, 6.0, "%copy-done = f32[8]{0} copy-done(%start)"),
           (6.0, 7.0, "%fusion.3 = f32[8]{0} w")]
    modules = [(0.0, 7.0, "jit_step(1)")]
    paths = {(1, "%fusion.1 = f32[8]{0} x"):
             "while/body/encoder.mlp/dot_general",
             (1, "%fusion.2 = f32[8]{0} y"):
             "while/body/encoder.attention/exp",
             # the same line of another program: not this one's path
             (2, "%copy-done = f32[8]{0} copy-done(%start)"): "other.scope/x",
             (1, "%fusion.3 = f32[8]{0} w"): "mul"}   # a primitive, no scope
    dev = trace._reduce_device("d0", ops, modules, 0.0, 10.0, paths)
    assert dev.ops == pytest.approx({
        "jit_step/while (tuple)": 1.5,
        "jit_step/while/body/encoder.mlp/dot_general/fusion.1 f32[8]": 1.0,
        "jit_step/while/body/encoder.attention/exp/fusion.2 f32[8]": 1.5,
        "jit_step/copy-done f32[8]": 1.0,
        "jit_step/mul/fusion.3 f32[8]": 1.0})
    assert dev.scopes == pytest.approx({
        "jit_step": 3.5, "jit_step/while/body/encoder.mlp": 1.0,
        "jit_step/while/body/encoder.attention": 1.5})
    assert sum(dev.scopes.values()) == pytest.approx(dev.busy_s)
    r = trace.Reduced(0.0, 10.0, [dev], [], None)
    # a scope is found wherever it stands, by whole names
    assert r.scope_seconds("fused_ingest", "encoder.mlp") == [1.0]
    assert r.scope_seconds("fused_ingest", "while/body") == [2.5]
    assert r.scope_seconds("fused_ingest", "encoder") == [0.0]
    assert r.scope_seconds("scan", "encoder.mlp") == [0.0]
    assert r.scope_seconds(r"^jit_st", "body/encoder.attention") == [1.5]
    run = types.SimpleNamespace(trace=r)
    assert readers.scope_seconds(run, "fused_ingest", "encoder.mlp") == 1.0
    assert readers.scope_seconds(run, "fused_ingest", "nothing") is None
    assert readers.scope_seconds(types.SimpleNamespace(trace=None),
                                 "fused_ingest", "encoder.mlp") is None


@pytest.mark.parametrize("tf_op, path", [
    ("jit(step)/jit(main)/encoder.mlp/dot_general:", "encoder.mlp/dot_general"),
    ("jit(search)/while/body/closed_call/top_k:", "while/body/closed_call/top_k"),
    ("jit(search)/jit(norm)/reduce_sum:", "jit(norm)/reduce_sum"),
    ("jit(iota)/iota:", "iota"), ("", ""),
])
def test_a_path_is_the_name_stack_without_the_module_s_function(tf_op, path):
    assert trace._path(tf_op) == path


def _reduced(devices):
    return trace.Reduced(0.0, 10.0, devices, [
        ("t1", "bench.pack", 2.4, 4.1)], clock_offset_s=100.0)


def test_idle_share_is_the_worst_chip_s_and_busy_the_average():
    d0 = trace.DeviceTrace("d0", 6.0, {}, {"jit_search": [1.0, 3.0]}, [])
    d1 = trace.DeviceTrace("d1", 2.0, {}, {"jit_step": [2.0]}, [])
    r = _reduced([d0, d1])
    assert r.busy_s == 4.0 and r.idle_share() == pytest.approx(0.8)
    assert r.module_seconds("scan") == [[1.0, 3.0], []]
    assert r.module_seconds("fused_ingest") == [[], [2.0]]


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    dev = trace.DeviceTrace("d0", 5.0, {}, {}, [
        (1.0, 1.0005), (2.5, 4.0), (6.0, 9.0)])
    # host samples are on time.perf_counter(): the profile's clock - 100 s
    samples = [(-93.5, "bridge:knn.add_batch"), (-92.0, "loop:wait"),
               (-91.5, "loop:wait")]
    gaps = trace.idle_gaps_by_host(_reduced([dev]), samples)
    assert gaps == [["loop:wait", 3.0],
                    # no sample fell into 2.5..4.0: the overlapping host span
                    ["t1:bench.pack", 1.5],
                    ["gaps under 1 ms", pytest.approx(0.0005)]]


def test_module_patterns_name_the_program_s_jitted_functions():
    import re

    for kernel, names in {"scan": ["jit_search"],
                          "fused_ingest": ["jit_step", "jit_step_i8"],
                          "encoder": ["jit_ragged_device_producer",
                                      "jit_device_producer"]}.items():
        for name in names:
            assert re.match(trace.MODULE_PATTERNS[kernel], name)
    assert not re.match(trace.MODULE_PATTERNS["scan"], "jit_local_search")


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A profile recorded here, on the CPU backend: three searches between
    the benchmark's marks."""
    import glob
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def search(q, v):
        return jax.lax.top_k(q @ v.T, 3)

    v, q = jnp.ones((4096, 64)), jnp.ones((2, 64))
    search(q, v)[0].block_until_ready()
    out = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace.CLOCK_MARK,
                                      perf_counter_ns=time.perf_counter_ns()):
        pass
    with jax.profiler.TraceAnnotation(trace.BEGIN_MARK):
        pass
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.index.search"):
            search(q, v)[0].block_until_ready()
        time.sleep(0.02)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.END_MARK):
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return path, t0, t1


def test_a_cpu_profile_reduces_under_the_plane_xla_cpu(cpu_profile):
    path, t0, t1 = cpu_profile
    r = trace.reduce_xplane(path)
    assert [d.name for d in r.devices] == ["xla-cpu"]
    assert r.window_s == pytest.approx(t1 - t0, abs=5e-3)
    # the marks carry the host clock into the profile
    assert r.t0 - r.clock_offset_s == pytest.approx(t0, abs=5e-3)
    dev = r.devices[0]
    assert len(dev.modules["jit_search"]) == 3
    assert 0 < dev.busy_s < r.window_s
    # three sleeps of 20 ms are three idle gaps of at least that
    assert sum(1 for s, e in dev.gaps if e - s >= 0.019) == 3
    assert any(name == "bench.index.search" for _t, name, _s, _e
               in r.host_spans)
    assert trace.top_ops(r)[0][0].startswith("jit_search/")


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "tpu_small.xplane.pb")),
                    reason="no recorded TPU profile")
def test_the_recorded_tpu_profile_reduces_to_the_numbers_read_by_hand():
    import json

    with open(os.path.join(DATA, "tpu_small.expected.json")) as f:
        want = json.load(f)
    r = trace.reduce_xplane(os.path.join(DATA, "tpu_small.xplane.pb"))
    assert [d.name for d in r.devices] == want["devices"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    for kernel, runs in want["module_runs"].items():
        got = r.module_seconds(kernel)[0]
        assert len(got) == runs["count"]
        assert sum(got) == pytest.approx(runs["seconds"], rel=1e-9)
    assert trace.top_ops(r, 3)[0][0] == want["top_op"]


def _module(key: str) -> str:
    return key.split("/")[0]


def test_the_recorded_scopes_reduce_to_what_the_compiled_proto_read():
    """Scopes read from the wire format and reduced by ``_self_seconds``,
    against TensorFlow's compiled xplane.proto and a union of intervals
    (tools/record_fixture.py expect), to the profile's nanosecond."""
    with open(os.path.join(DATA, "tpu_scopes.expected.json")) as f:
        want = json.load(f)
    r = trace.reduce_xplane(os.path.join(DATA, "tpu_scopes.xplane.pb"))
    (dev,) = r.devices
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert set(dev.scopes) == set(want["scope_self_s"])
    for key, seconds in want["scope_self_s"].items():
        assert dev.scopes[key] == pytest.approx(seconds, rel=2e-3, abs=2e-8)
    # self time by scope adds up to the module's, and all of it to busy
    for module, seconds in want["module_ops_self_s"].items():
        assert sum(s for k, s in dev.scopes.items() if _module(k) == module) \
            == pytest.approx(seconds, rel=2e-3, abs=2e-8)
        assert sum(s for k, s in dev.ops.items() if _module(k) == module) \
            == pytest.approx(seconds, rel=2e-3, abs=2e-8)
    assert sum(dev.scopes.values()) == pytest.approx(dev.busy_s)
    # the toy's scopes, the one inside the loop under the loop's own
    step = "jit_toy_step"
    inner = f"{step}/toy.blocks/while/body/closed_call/toy.mlp"
    assert {k for k in dev.scopes if "toy." in k} == {
        f"{step}/toy.embed", f"{step}/toy.pool", f"{step}/toy.blocks", inner}
    mlp = r.scope_seconds(f"^{step}$", "toy.mlp")[0]
    assert mlp == pytest.approx(dev.scopes[inner])
    assert r.scope_seconds(f"^{step}$", "toy.blocks")[0] > mlp > 0
    assert r.scope_seconds(f"^{step}$", "toy.absent") == [0.0]
    # an operation without a scope is kept under its module alone: the
    # scaling outside every scope, and what the compiler added
    assert dev.scopes[step] > 0
    assert any(k.startswith(f"{step}/mul/") for k in dev.ops)
    for op in want["no_tf_op"]:
        assert any(k.split(" ")[0] == f"{step}/{op}" for k in dev.ops), op
    # the breakdown names an operation by module/scope/primitive/op
    assert trace.top_ops(r, 1)[0][0].startswith(f"{step}/toy.")


def test_the_cpu_twin_of_the_recorded_scopes_has_modules_alone(tmp_path):
    """The same toy program traced here: the CPU backend's events carry no
    name stack, so every operation stays under its module and no scope's
    reader finds anything."""
    import glob

    import jax

    toy = load_tool("record_fixture")  # which recorded tpu_scopes
    step = jax.jit(toy.toy_step)
    operands = toy.toy_operands()
    step(*operands).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for _ in range(3):
        step(*operands).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    r = trace.reduce_xplane(path)
    (dev,) = r.devices
    assert dev.name == "xla-cpu"
    assert len(dev.modules["jit_toy_step"]) == 3
    assert set(dev.scopes) == {"jit_toy_step"}
    assert dev.scopes["jit_toy_step"] == pytest.approx(
        sum(dev.ops.values()))
    assert r.scope_seconds("^jit_toy_step$", "toy.mlp") == [0.0]
    assert readers.scope_seconds(types.SimpleNamespace(trace=r),
                                 "^jit_toy_step$", "toy.mlp") is None

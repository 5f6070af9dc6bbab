"""The builder's recorder of the small profile the trace reducer's scope
test reads (``benchmark/tests/data/tpu_scopes.xplane.pb``) and of the
numbers it is held to (``tpu_scopes.expected.json``).

    python3 benchmark/tools/record_fixture.py record <out.xplane.pb>
    python3 benchmark/tools/record_fixture.py expect <in.xplane.pb> <out.json>

``record`` runs on the chip (it refuses any other platform): three executions
of :func:`toy_step` between the benchmark's marks. The toy has what a
kernel inside a larger program has: named scopes, one of them around a loop
(so its operations nest under a ``while``), an operation outside every
scope, and whatever the compiler adds of its own. ``expect`` needs no chip
and no JAX: it reads the profile with the compiled ``xplane.proto`` that
TensorFlow ships, not with benchmark/lib/trace.py's reading of the wire
format, and sums every operation's own time by scope with arithmetic of its
own. The driver runs neither.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SHAPE = (512, 256)   # tokens x width of the toy


def toy_step(table, w, ids):
    """An embedding bag with three blocks in a loop: ``toy.embed``,
    ``toy.blocks`` around ``toy.mlp`` (under the loop's ``while/body``),
    ``toy.pool``, and a scaling outside every scope."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("toy.embed"):
        x = table[ids]

    def block(_i, x):
        with jax.named_scope("toy.mlp"):
            return jnp.tanh(x @ w)

    with jax.named_scope("toy.blocks"):
        x = jax.lax.fori_loop(0, 3, block, x)
    with jax.named_scope("toy.pool"):
        x = x.mean(axis=0)
    return x * 2.0


def toy_operands(seed: int = 0):
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens, width = SHAPE
    return (jax.random.normal(k1, (4096, width), jnp.bfloat16),
            jax.random.normal(k2, (width, width), jnp.bfloat16) / 16,
            jax.random.randint(k3, (tokens,), 0, 4096))


def record(out: str) -> int:
    import jax

    from benchmark.lib import trace

    if jax.devices()[0].platform != "tpu":
        print(f"record: JAX runs on {jax.devices()[0].platform!r}, the "
              f"fixture is a TPU's", file=sys.stderr)
        return 1
    step = jax.jit(toy_step)
    operands = toy_operands()
    step(*operands).block_until_ready()
    directory = tempfile.mkdtemp(prefix="fixture_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=options)
    with jax.profiler.TraceAnnotation(
            trace.CLOCK_MARK, perf_counter_ns=time.perf_counter_ns()):
        pass
    with jax.profiler.TraceAnnotation(trace.BEGIN_MARK):
        pass
    time.sleep(0.005)     # the device's clock leads the host's by about 1 ms
    for _ in range(3):
        step(*operands).block_until_ready()
        time.sleep(0.002)
    time.sleep(0.005)
    with jax.profiler.TraceAnnotation(trace.END_MARK):
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(directory, ignore_errors=True)
    print(f"recorded {out}: {os.path.getsize(out)} bytes")
    return 0


def expect(path: str, out: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == "/host:CPU")
    marks = {}
    for line in host.lines:
        for ev in line.events:
            name = host.event_metadata[ev.metadata_id].name
            if name.startswith("bench.trace."):
                start = line.timestamp_ns * 1000 + ev.offset_ps
                marks[name] = (start, start + ev.duration_ps)
    t0, t1 = marks["bench.trace.begin"][1], marks["bench.trace.end"][0]
    (plane,) = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    mods = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    runs = [(mods.timestamp_ns * 1000 + ev.offset_ps,
             mods.timestamp_ns * 1000 + ev.offset_ps + ev.duration_ps,
             plane.event_metadata[ev.metadata_id].name.split("(")[0])
            for ev in mods.events]
    events = []
    for ev in ops.events:
        meta = plane.event_metadata[ev.metadata_id]
        tf_op = next((s.str_value or stat_names.get(s.ref_value, "")
                      for s in meta.stats
                      if stat_names[s.metadata_id] == "tf_op"), "")
        start = ops.timestamp_ns * 1000 + ev.offset_ps
        events.append((start, start + ev.duration_ps, tf_op, meta.name))
    # an operation's own time: its interval less the union of the
    # operations that lie inside it, both cut to the window
    def clip(s, e):
        return max(s, t0), min(e, t1)

    scopes: dict[str, int] = {}
    unscoped = []
    for s, e, tf_op, name in events:
        cs, ce = clip(s, e)
        own, edge = max(0, ce - cs), cs
        for s2, e2 in sorted(clip(s2, e2) for s2, e2, _t, _n in events
                             if s <= s2 and e2 <= e and (s2, e2) != (s, e)):
            if e2 > max(edge, s2):
                own -= e2 - max(edge, s2)
                edge = e2
        module = next((m for ms, me, m in runs if ms <= s < me), "?")
        names = tf_op.split(":")[0].split("/")[1:-1]   # between jit() and
        key = "/".join([module] + names)               # the primitive
        scopes[key] = scopes.get(key, 0) + own
        if not tf_op and own:
            unscoped.append(name.split(" = ")[0].lstrip("%"))
    result = {
        "window_s": (t1 - t0) / 1e12,
        "scope_self_s": {k: v / 1e12 for k, v in sorted(scopes.items())
                         if v},
        "module_ops_self_s": {m: sum(v for k, v in scopes.items()
                                     if k.split("/")[0] == m) / 1e12
                              for m in {k.split("/")[0] for k in scopes}},
        "no_tf_op": sorted(set(unscoped)),
        "how": "Recorded on a TPU v5e by benchmark/tools/record_fixture.py "
               "record (PR 27): three executions of its toy_step between "
               "the benchmark's marks. These numbers by the same file's "
               "expect: the profile parsed with TensorFlow's compiled "
               "xplane.proto, each operation's own time (its interval less the "
               "union of those inside it, cut to the window) summed by the "
               "names between jit(...) and the primitive in its tf_op "
               "stat; not with benchmark/lib/trace.py.",
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "record":
        sys.exit(record(sys.argv[2]))
    if len(sys.argv) == 4 and sys.argv[1] == "expect":
        sys.exit(expect(sys.argv[2], sys.argv[3]))
    print(__doc__, file=sys.stderr)
    sys.exit(2)

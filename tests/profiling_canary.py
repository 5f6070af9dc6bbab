"""Profiling canary: the continuous profiling plane's three load-bearing
promises, proven end to end (same pattern as trace_canary.py).

1. **Flamegraph gate** — drive ``examples/streaming_etl.py``'s real graph
   with ``PATHWAY_PROFILER=1``: the host sampler must produce non-empty
   collapsed-flamegraph text whose lines parse (``role;frame;... count``),
   with at least one sample attributed to an in-flight DEVICE leg (the
   ``[device:...]`` synthetic leaf the flight recorder tags), and the
   sampler's own rolling overhead accounting must stay under the 2%
   contract.

2. **Roofline gate** — a tiny-config run dispatches every kernel family
   the cost model knows (knn_search, ingest_scatter, encoder_forward,
   segment_attention); each dispatched family must carry a roofline
   classification (arithmetic intensity vs machine balance → compute- or
   bandwidth-bound) with sane numbers.

3. **Overhead guard** — per-tick wall time with the profiler SAMPLING
   must stay within 2% of profiler-off on the same join + sliding window
   + groupby shape trace_canary measures, min-of-K interleaved, with the
   retry-3 rule (a wall-clock ratio on a shared runner can blip on
   correlated noise; a real regression fails every attempt).

The gate numbers are written as a CI artifact (``PROFILING_BENCH_ARTIFACT``)
and checkpointed into ``BENCH_LASTGOOD.json`` per the evidence rule.

Exits 0 iff all hold. Run: ``python tests/profiling_canary.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

_RESULT: dict = {}

_COLLAPSED_LINE = re.compile(r"^[^; ][^;]*(;[^;]+)* \d+$")


def check_flamegraph() -> str | None:
    """Run the streaming example with the profiler forced on; return an
    error string or None."""
    from tests.pipelining_canary import _write_feed

    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "2"
    os.environ["PATHWAY_PROFILER"] = "1"
    # production default interval: the 2% self-overhead contract is
    # stated (and measured) at this cadence
    os.environ.pop("PATHWAY_PROFILER_SAMPLE_MS", None)
    os.environ["PATHWAY_FLIGHT_RECORDER"] = "1"  # in-flight op tagging
    import pathway_tpu as pw
    from examples.streaming_etl import build
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.engine.profiler import current_profiler
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    with tempfile.TemporaryDirectory() as td:
        root = pathlib.Path(td)
        orders_dir, cats_csv = _write_feed(root)
        out_csv = str(root / "out.csv")
        build(orders_dir, cats_csv, out_csv)
        import threading

        th = threading.Thread(target=pw.run, daemon=True)
        th.start()
        deadline = time.monotonic() + 60.0
        prof = None
        while time.monotonic() < deadline and prof is None:
            prof = current_profiler()
            time.sleep(0.05)
        if prof is None:
            _streaming.stop_all()
            th.join(15.0)
            return "profiler never installed (PATHWAY_PROFILER=1 ignored)"
        # run until the sampler caught a device leg in flight (the first
        # device-leg XLA compile alone is hundreds of sampler intervals)
        while time.monotonic() < deadline:
            if prof.device_attributed_samples >= 1 \
                    and prof.samples_total >= 50:
                break
            time.sleep(0.1)
        text = prof.collapsed()
        samples = prof.samples_total
        device_samples = prof.device_attributed_samples
        overhead = prof.overhead_ratio()
        stats = prof.stats()
        _streaming.stop_all()
        th.join(15.0)
        G.clear()
    os.environ.pop("PATHWAY_PROFILER", None)
    os.environ.pop("PATHWAY_FLIGHT_RECORDER", None)
    lines = text.strip().splitlines() if text.strip() else []
    if not lines:
        return "flamegraph is empty: the sampler collected nothing"
    for ln in lines:
        if not _COLLAPSED_LINE.match(ln):
            return f"malformed collapsed-stack line: {ln!r}"
    if device_samples < 1:
        return (f"no device-leg-attributed sample after {samples} samples "
                f"— in-flight tagging is broken")
    if not any("[device:" in ln for ln in lines):
        return "device-attributed samples counted but no [device:...] leaf"
    if overhead >= 0.02:
        return f"sampler self-overhead {overhead:.4f} >= the 2% contract"
    roles = {ln.split(";", 1)[0] for ln in lines}
    _RESULT.update({
        "profiling_flamegraph_stacks": len(lines),
        "profiling_samples_total": samples,
        "profiling_device_attributed_samples": device_samples,
        "profiling_sampler_overhead_ratio": round(overhead, 6),
        "profiling_thread_roles": sorted(roles),
        "profiling_mfu_rolling": stats["mfu_rolling"],
    })
    print(f"flamegraph gate OK: {len(lines)} folded stacks over "
          f"{samples} samples, {device_samples} device-attributed, "
          f"sampler overhead {overhead:.4%}, roles {sorted(roles)}")
    return None


def check_rooflines() -> str | None:
    """Dispatch every kernel family at tiny shapes; each must come back
    roofline-classified."""
    import numpy as np

    import jax.numpy as jnp
    from pathway_tpu.engine.profiler import (KERNEL_FAMILIES, Profiler,
                                             install_profiler,
                                             machine_params)
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    # device side only; classified against the v5e row by hand — the
    # canary checks the cost model's arithmetic, not this host
    prof = Profiler(sample_interval_ms=1e6,
                    machine=machine_params("TPU v5 lite"))
    install_profiler(prof)
    try:
        # knn_search + ingest_scatter
        rng = np.random.default_rng(11)
        vecs = rng.normal(size=(64, 16)).astype(np.float32)
        idx = BruteForceKnnIndex(16, metric=KnnMetric.L2SQ)
        idx.add_batch([Pointer(i) for i in range(64)], vecs)
        idx.search([(Pointer(900), vecs[3], 4, None)])
        # encoder_forward (packed) + segment_attention (ragged)
        cfg = EncoderConfig.tiny(compute_dtype=jnp.float32)
        texts = ["tiny text", "a longer piece of text for packing",
                 "mid", "several words here"] * 3
        JaxEncoderEmbedder(config=cfg, ragged=False,
                           max_len=32).encode_batch_device(texts)
        JaxEncoderEmbedder(config=cfg, ragged=True,
                           max_len=32).encode_batch_device(texts)
        fams = prof.family_stats()
    finally:
        install_profiler(None)
    missing = [f for f in KERNEL_FAMILIES if f not in fams]
    if missing:
        return f"families never dispatched: {missing}"
    rooflines = {}
    for fam in KERNEL_FAMILIES:
        st = fams[fam]
        if st["dispatches"] < 1:
            return f"{fam}: zero dispatches recorded"
        rf = st["roofline"]
        if rf["bound_by"] not in ("compute", "bandwidth"):
            return f"{fam}: bad roofline verdict {rf['bound_by']!r}"
        if rf["arithmetic_intensity"] <= 0.0:
            return f"{fam}: non-positive arithmetic intensity"
        if not 0.0 < rf["attainable_mfu"] <= 1.0:
            return f"{fam}: attainable MFU {rf['attainable_mfu']} out of range"
        if st["device_ms_total"] <= 0.0:
            return f"{fam}: no device time recorded"
        rooflines[fam] = rf["bound_by"]
    # the slab scan and the scatter are bandwidth all the way down on
    # any real machine balance — a "compute" verdict here means the
    # bytes model lost its slab term
    if rooflines["knn_search"] != "bandwidth":
        return f"knn_search classified {rooflines['knn_search']}-bound"
    if rooflines["ingest_scatter"] != "bandwidth":
        return f"ingest_scatter classified {rooflines['ingest_scatter']}-bound"
    _RESULT["profiling_rooflines"] = rooflines
    _RESULT["profiling_family_dispatches"] = {
        f: fams[f]["dispatches"] for f in KERNEL_FAMILIES}
    print(f"roofline gate OK: {rooflines}")
    return None


def check_overhead(attempts: int = 3) -> str | None:
    """Profiler SAMPLING must add < 2% per-tick wall time vs off.

    Retry-3 rule: the gate passes on the first attempt under budget and
    only reports failure after ``attempts`` independent measurements all
    exceed it (correlated wall-clock noise on a shared runner)."""
    last = None
    for i in range(attempts):
        last = _measure_overhead()
        if last is None:
            return None
        print(f"overhead attempt {i + 1}/{attempts} over budget: {last}")
    return last


def _measure_overhead() -> str | None:
    from tests.trace_canary import _etl_like_graph

    from pathway_tpu.engine.profiler import Profiler, install_profiler
    from pathway_tpu.internals.parse_graph import G

    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "1"  # no bridge-thread noise
    os.environ.pop("PATHWAY_PROFILER", None)
    n_rows, n_ticks, trials = 4000, 120, 5

    def run_once(with_profiler: bool) -> float:
        runner = _etl_like_graph(n_rows, n_ticks)
        prof = None
        if with_profiler:
            prof = Profiler()  # default 25ms sampling, like production
            install_profiler(prof)
            prof.start()
        t0 = time.perf_counter()
        try:
            runner.run_batch(n_workers=1)
        finally:
            if prof is not None:
                prof.stop()
                install_profiler(None)
        dt = time.perf_counter() - t0
        G.clear()
        return dt

    run_once(False)  # warm caches/imports off the record
    run_once(True)
    # interleaved trials: thermal / allocator drift must hit both modes
    # equally, or the guard measures the machine, not the sampler
    base_ts, prof_ts = [], []
    for _ in range(trials):
        base_ts.append(run_once(False))
        prof_ts.append(run_once(True))
    base, profiled = min(base_ts), min(prof_ts)
    ratio = profiled / base
    print(f"overhead guard: baseline {base * 1e3:.1f}ms, "
          f"profiler-sampling {profiled * 1e3:.1f}ms over {n_ticks} ticks "
          f"(ratio {ratio:.4f})")
    _RESULT["profiling_overhead_ratio_wall"] = round(ratio, 4)
    if ratio > 1.02:
        return (f"profiler-on per-tick overhead {ratio:.4f}x exceeds "
                f"the 2% budget")
    return None


def _write_artifacts() -> None:
    import bench

    bench._write_lastgood(_RESULT)  # evidence rule: checkpoint immediately
    artifact = os.environ.get("PROFILING_BENCH_ARTIFACT")
    if artifact:
        with open(artifact, "w") as f:
            json.dump(_RESULT, f, indent=1)


def main() -> int:
    for name, check in (("flamegraph", check_flamegraph),
                        ("roofline", check_rooflines),
                        ("overhead", check_overhead)):
        err = check()
        if err:
            print(f"FAIL [{name}]: {err}", file=sys.stderr)
            return 1
    _write_artifacts()
    print("OK: flamegraph + roofline + overhead gates all hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Auto-jit canary: the framework-vs-raw throughput gate + the trace
artifact (internals/autojit.py, round-5 verdict #5).

One gate, evidence-first:

**bench autojit leg** (bench.bench_autojit): the SAME doc-scoring
pipeline — traceable/vmappable scalar UDF chain + host-only formatter +
batch device embed payload — measured three ways in interleaved
best-of-3 trials: raw hand-written kernels, Table path with auto-jit ON,
Table path with auto-jit OFF. Gates:

- ``framework_vs_raw_ratio`` (ON) >= 0.85 — the ROADMAP target;
- the OFF ratio reproduces today's gap (strictly below the ON ratio —
  the artifact carries both numbers from the same run);
- the three paths are byte-identical (asserted inside the leg);
- the fused tier really ran: programs >= 1, dispatches > 0, ZERO
  demotions, and warmup walked the bucket ladder (first-tick compiles
  out of serving latency);
- the flight-recorder per-stage breakdown for BOTH modes ships in the
  trace artifact (``AUTOJIT_TRACE_ARTIFACT``) — the "where the
  Table-path tax went" evidence, uploaded by CI.

The leg's JSON is checkpointed into ``BENCH_LASTGOOD.json`` per the
evidence rule. The ratio gate retries once: on a 2-core shared runner a
neighbor-load episode can straddle even interleaved trials (the r05
lesson — trace_canary's overhead guard retries for the same reason).

Exits 0 iff all hold. Run: ``python tests/autojit_canary.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("PATHWAY_AUTO_JIT", None)  # the default-on path is the DUT
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

RATIO_GATE = float(os.environ.get("AUTOJIT_RATIO_GATE", "0.85"))


def run_leg() -> dict:
    import bench

    artifact = os.environ.get("AUTOJIT_TRACE_ARTIFACT")
    if artifact:
        os.environ["BENCH_AUTOJIT_TRACE_ARTIFACT"] = artifact
    out = bench.bench_autojit()
    bench._write_lastgood(out)  # evidence rule: checkpoint immediately
    json_artifact = os.environ.get("AUTOJIT_BENCH_ARTIFACT")
    if json_artifact:
        with open(json_artifact, "w") as f:
            json.dump(out, f, indent=1)
    return out


def gate(out: dict) -> None:
    ratio = out["framework_vs_raw_ratio"]
    nojit = out["framework_vs_raw_ratio_nojit"]
    assert out["autojit_programs"] >= 1, out
    assert (out["autojit_device_dispatches"]
            + out["autojit_vector_dispatches"]) > 0, \
        "fused tier never dispatched — the gate would be vacuous"
    assert out["autojit_demotions"] == 0, (
        f"{out['autojit_demotions']} demotion(s) during the bench leg — "
        f"a chain the static gates admitted failed on real data")
    assert out["autojit_warmup_compiles"] >= 1, \
        "pw.warmup walked no auto-jit buckets"
    assert nojit < ratio, (
        f"auto-jit OFF ({nojit}) did not reproduce the gap below ON "
        f"({ratio}) — the comparison is not measuring the tier")
    assert ratio >= RATIO_GATE, (
        f"framework_vs_raw_ratio {ratio} < {RATIO_GATE} "
        f"(nojit ratio {nojit})")


def main() -> None:
    out = run_leg()
    try:
        gate(out)
    except AssertionError as first:
        # one retry for runner-noise resilience; both artifacts kept
        print(f"[autojit-canary] first attempt failed ({first}); retrying "
              f"once for shared-runner noise", flush=True)
        out = run_leg()
        gate(out)
    trace = os.environ.get("AUTOJIT_TRACE_ARTIFACT")
    if trace:
        with open(trace) as f:
            t = json.load(f)
        assert t["per_stage_ms"]["on"] and t["per_stage_ms"]["off"], t
    print(f"[autojit-canary] OK: framework_vs_raw_ratio "
          f"{out['framework_vs_raw_ratio']} (gate {RATIO_GATE}), "
          f"nojit {out['framework_vs_raw_ratio_nojit']}, "
          f"{out['autojit_programs']} program(s), "
          f"{out['autojit_device_dispatches']} device + "
          f"{out['autojit_vector_dispatches']} vector dispatches, "
          f"{out['autojit_warmup_compiles']} warmup compiles, "
          f"0 demotions")


if __name__ == "__main__":
    main()

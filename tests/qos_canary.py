"""QoS control-plane canary: the closed SLO loop, proven end to end
(same pattern as serving_canary.py / durability_canary.py). Two gates:

1. **byte-identity + deferral** (in-process) — a deterministic counts
   pipeline streamed under a deliberately tiny ingest budget
   (``PATHWAY_QOS_ALWAYS_BUDGET`` + clamped partition) must produce
   consolidated outputs IDENTICAL to the QoS-off run while the
   controller demonstrably deferred ingest across ticks: deferral moves
   timestamps, never content, and exactly-once is untouched.

2. **bench qos leg** (subprocess) — the real serving workload (KNN
   index under heavy live ingest + closed-loop rest queries) run
   QoS-off then QoS-on, gating:

   - >=1 observed ingest deferral and >=1 shed under the induced
     overload burst, with every shed counted in ``qos_shed_total``
     (never silent — the 503s carried ``Retry-After``, asserted inside
     the leg);
   - >=2 queries coalesced into shared kernel dispatches;
   - the controller's trade, both directions: QoS-on lowers query p50
     AND measurably defers ingest (lower ingest rate); QoS-off is the
     inverse — full ingest rate, blown-out latency;
   - ``BENCH_LASTGOOD.json`` checkpointed + JSON artifact written (the
     ROADMAP evidence rule).

   The ABSOLUTE bar — ``knn_p50_e2e_ms < 20`` under live ingest — arms
   via ``QOS_CANARY_REQUIRE_SLO=1`` (device-capable runners: the
   ROADMAP done-bar rides the driver's device artifact). On CPU-only
   runners the number is REPORTED loudly instead: this container's
   no-ingest serving floor measured ~30 ms (jax-on-CPU dispatch + 2
   cores), the same reason the PR-6 serving canary reports rather than
   thresholds — gating an unreachable bar would only teach CI to
   ignore red.

Exits 0 iff all armed gates hold. Run: ``python tests/qos_canary.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

SLO_GATE_MS = float(os.environ.get("QOS_CANARY_P50_GATE_MS", "20"))
REQUIRE_SLO = os.environ.get("QOS_CANARY_REQUIRE_SLO", "") not in ("", "0")

# calibration for the bench child: heavy-but-sustainable ingest pressure
# (beyond-capacity overload measures nothing but the backlog) at the
# production defaults — pipelined device dispatch, default budget
# floor/deadline — sized down only for canary wall-clock. Measured on
# this container: p50 ~547ms -> ~45ms (12x) while ingest halves; the
# relative-trade gates have an order-of-magnitude margin.
_BENCH_ENV = {
    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
    "BENCH_SKIP": ",".join(sorted(
        {"etl", "autojit", "scaleout", "durability", "recovery", "replica",
         "embed", "framework", "knn", "serving"})),
    "BENCH_QOS_N": os.environ.get("BENCH_QOS_N", "8000"),
    "BENCH_QOS_QUERIES": os.environ.get("BENCH_QOS_QUERIES", "16"),
    "BENCH_QOS_WARMUP": os.environ.get("BENCH_QOS_WARMUP", "4"),
    "BENCH_QOS_BURST": os.environ.get("BENCH_QOS_BURST", "16"),
}


def gate_identity_and_deferral() -> str | None:
    """Deterministic pipeline, QoS-off vs QoS-on with a clamped ingest
    partition: consolidated outputs must be byte-identical while the
    controller demonstrably deferred rows to later ticks."""
    import pathway_tpu as pw
    from pathway_tpu.engine.qos import current_controller, install_controller
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.testing.faults import flaky_subject

    words = [f"w{i % 101}" for i in range(2000)]

    def run_counts(qos_on: bool) -> tuple[dict, dict]:
        G.clear()
        install_controller(None)
        env = {
            "PATHWAY_QOS": "1" if qos_on else "0",
            "PATHWAY_QOS_ALWAYS_BUDGET": "1" if qos_on else "",
            "PATHWAY_QOS_MIN_INGEST_ROWS": "32",
            "PATHWAY_QOS_MAX_INGEST_ROWS": "32",
        }
        old = {k: os.environ.get(k) for k in env}
        for k, v in env.items():
            if v:
                os.environ[k] = v
            else:
                os.environ.pop(k, None)
        try:
            t = pw.io.python.read(
                flaky_subject([{"word": w} for w in words], fail_after=0,
                              fail_attempts=0),
                schema=pw.schema_from_types(word=str),
                autocommit_duration_ms=5)
            counts = t.groupby(t.word).reduce(word=t.word,
                                              c=pw.reducers.count())
            state: dict = {}
            captured: list = []

            def on_change(key, row, time, is_addition):
                if not captured:
                    ctl = current_controller()
                    if ctl is not None:
                        captured.append(ctl)
                if is_addition:
                    state[row["word"]] = row["c"]
                elif state.get(row["word"]) == row["c"]:
                    del state[row["word"]]

            pw.io.subscribe(counts, on_change)
            pw.run()
            return state, (captured[0].summary() if captured else {})
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            G.clear()
            install_controller(None)

    base, _ = run_counts(qos_on=False)
    qos, stats = run_counts(qos_on=True)
    if sum(base.values()) != len(words):
        return f"baseline dropped rows: {sum(base.values())}/{len(words)}"
    if qos != base:
        missing = {k: v for k, v in base.items() if qos.get(k) != v}
        return (f"IDENTITY VIOLATION: QoS-on consolidated outputs differ "
                f"from QoS-off on {len(missing)} key(s): "
                f"{dict(list(missing.items())[:5])}")
    if stats.get("ingest_deferrals", 0) < 1:
        return (f"no ingest deferral observed under a 32-row/tick clamp "
                f"(stats: {stats})")
    if stats.get("shed_total", 0) != 0:
        return f"ingest-only run shed queries?! {stats}"
    print(f"identity gate OK: {len(base)} keys identical, "
          f"{stats['ingest_deferrals']} deferrals "
          f"({stats['deferred_rows_total']} rows rode later ticks)")
    return None


def gate_bench_before_after() -> str | None:
    root = pathlib.Path(__file__).resolve().parent.parent
    artifact = pathlib.Path(os.environ.get("QOS_CANARY_ARTIFACT",
                                           root / "qos_canary_artifact.json"))
    lastgood = root / pathlib.Path(
        os.environ.get("BENCH_LASTGOOD_PATH", "BENCH_LASTGOOD.json"))
    env = dict(os.environ)
    env.update(_BENCH_ENV)
    proc = subprocess.run(
        [sys.executable, str(root / "bench.py")], cwd=str(root),
        env=env, capture_output=True, text=True, timeout=1500)
    last = None
    for line in (proc.stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                last = json.loads(line)
            except json.JSONDecodeError:
                continue
    if last is None:
        tail = (proc.stderr or "").strip().splitlines()[-5:]
        return f"bench emitted no JSON (rc={proc.returncode}): {tail}"
    if "qos_error" in last:
        return f"qos leg failed: {last['qos_error']}"
    artifact.write_text(json.dumps(last, indent=1))
    required = ("qos_off_knn_p50_e2e_ms", "qos_on_knn_p50_e2e_ms",
                "qos_off_ingest_rate_rps", "qos_on_ingest_rate_rps",
                "qos_shed_total", "qos_ingest_deferrals",
                "qos_coalesced_queries")
    for field in required:
        if field not in last:
            return f"bench JSON missing {field}: {sorted(last)}"
    # -- mechanisms: visible shedding, deferral, coalescing ---------------
    if last["qos_shed_total"] < 1:
        return (f"no shed under the induced overload burst "
                f"(qos_shed_total={last['qos_shed_total']})")
    if last["qos_ingest_deferrals"] < 1:
        return (f"no ingest deferral under budget pressure "
                f"(qos_ingest_deferrals={last['qos_ingest_deferrals']})")
    if last["qos_coalesced_queries"] < 2:
        return (f"no cross-request coalescing observed "
                f"(qos_coalesced_queries={last['qos_coalesced_queries']})")
    # -- the trade, both directions ---------------------------------------
    on_p50 = last["qos_on_knn_p50_e2e_ms"]
    off_p50 = last["qos_off_knn_p50_e2e_ms"]
    on_rate = last["qos_on_ingest_rate_rps"]
    off_rate = last["qos_off_ingest_rate_rps"]
    if not on_p50 < off_p50:
        return (f"QoS-on did not lower query p50: on={on_p50}ms vs "
                f"off={off_p50}ms")
    if not on_rate < off_rate:
        return (f"QoS-on did not defer ingest: on={on_rate} rows/s vs "
                f"off={off_rate} rows/s")
    # -- the absolute bar --------------------------------------------------
    if on_p50 < SLO_GATE_MS:
        slo_note = f"MEETS the {SLO_GATE_MS}ms target"
    elif REQUIRE_SLO:
        return (f"qos_on_knn_p50_e2e_ms={on_p50}ms misses the "
                f"{SLO_GATE_MS}ms bar (QOS_CANARY_REQUIRE_SLO armed)")
    else:
        slo_note = (f"reported, not gated: {on_p50}ms vs the "
                    f"{SLO_GATE_MS}ms device bar (CPU runner — no-ingest "
                    f"serving floor is above the bar here; arm with "
                    f"QOS_CANARY_REQUIRE_SLO=1 on capable runners)")
    # -- evidence rule -----------------------------------------------------
    if not lastgood.exists():
        return "BENCH_LASTGOOD.json was not written"
    good = json.loads(lastgood.read_text())["result"]
    if good.get("qos_on_knn_p50_e2e_ms") != on_p50:
        return f"lastgood diverged from bench JSON: {good}"
    print(f"bench qos gate OK: p50 {off_p50}ms -> {on_p50}ms "
          f"({last.get('qos_p50_speedup', '?')}x) while ingest "
          f"{off_rate} -> {on_rate} rows/s; shed={last['qos_shed_total']} "
          f"deferrals={last['qos_ingest_deferrals']} "
          f"coalesced={last['qos_coalesced_queries']}q/"
          f"{last['qos_coalesced_dispatches']}d; {slo_note}")
    return None


def main() -> int:
    for name, gate in (("identity+deferral", gate_identity_and_deferral),
                       ("bench-before-after", gate_bench_before_after)):
        err = gate()
        if err:
            print(f"QOS CANARY FAILED [{name}]: {err}", file=sys.stderr)
            return 1
        print(f"gate {name}: OK", flush=True)
    print("qos canary: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())

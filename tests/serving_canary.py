"""Serving-latency canary: the request-scoped SLO path, proven end to end
(same pattern as pipelining_canary.py / trace_canary.py). Three gates:

1. **streaming_etl + rest_connector** — mount a scoring route (the
   example's own ``demand_score`` device UDF) next to
   ``examples/streaming_etl.py``'s real graph, keep the order feed
   ingesting WHILE queries run, and assert every completed request span
   carries a full, positive stage decomposition that sums to its e2e
   total, with the new metric families live on ``/metrics`` and the
   serving snapshot on ``/status``.

2. **sanitized serving** — warm a paged text index under
   ``PATHWAY_DEVICE_SANITIZER=1`` (engine/device_sanitizer.py), then
   serve queries in steady state and gate ZERO post-warmup compiles and
   zero implicit host→device transfers (any violation raises).

3. **bench serving leg** — call ``bench.bench_serving()`` at a CPU-sized
   slab (the function, not ``python bench.py``: the command line runs
   device legs on a TPU only) and assert ``knn_p50_e2e_ms`` and every
   ``serving_stage_*_p50_ms`` field is present and positive in the bench
   JSON, and that ``BENCH_LASTGOOD.json`` captured the same numbers
   (values are REPORTED, not thresholded — CPU runners don't meet the
   20 ms target).

Exits 0 iff both hold. Run: ``python tests/serving_canary.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

STAGE_FIELDS = ("ingress_wait", "admission_wait", "queue", "host",
                "device", "response_write")


def gate_streaming_etl() -> str | None:
    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "2"
    os.environ["PATHWAY_MONITORING_HTTP_PORT"] = "0"  # ephemeral
    from tests.pipelining_canary import _write_feed

    import pathway_tpu as pw
    from examples.streaming_etl import build, demand_score
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.http import PathwayWebserver, rest_connector

    G.clear()
    with tempfile.TemporaryDirectory() as td:
        root = pathlib.Path(td)
        orders_dir, cats_csv = _write_feed(root)
        build(orders_dir, cats_csv, str(root / "out.csv"))
        ws = PathwayWebserver(host="127.0.0.1", port=0)
        qschema = sch.schema_from_types(qty=int, price=float)
        queries, writer = rest_connector(
            webserver=ws, route="/score", schema=qschema,
            methods=("POST",), delete_completed_queries=True,
            autocommit_duration_ms=10)
        writer(queries.select(
            score=demand_score(queries.qty, queries.price)))

        errors: list[BaseException] = []

        def _run():
            try:
                # with_http_server auto-enables the flight recorder (and
                # with it the request tracker) — the canary rides the
                # production wiring, no explicit env needed
                pw.run(with_http_server=True)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        th = threading.Thread(target=_run, daemon=True)
        th.start()
        stop_feed = threading.Event()

        def _keep_ingesting():
            # live ingest: new order files land while queries are served
            i = 0
            while not stop_feed.is_set():
                rows = [{"item": f"i{j % 4}", "qty": 1 + j % 3,
                         "price": 2.5, "ts": 6000 + 60 * (i * 8 + j)}
                        for j in range(8)]
                (pathlib.Path(orders_dir) / f"more_{i}.jsonl").write_text(
                    "\n".join(json.dumps(r) for r in rows) + "\n")
                i += 1
                stop_feed.wait(0.2)

        feeder = threading.Thread(target=_keep_ingesting, daemon=True)
        try:
            deadline = time.monotonic() + 60.0
            rt = None
            while time.monotonic() < deadline and rt is None:
                live = list(_streaming._ACTIVE_RUNTIMES)
                if live and ws._started.is_set() and ws.port:
                    rt = live[0]
                if errors:
                    return f"pipeline failed at startup: {errors[0]!r}"
                time.sleep(0.05)
            if rt is None:
                return "runtime never started"
            feeder.start()
            rids = set()
            for i in range(6):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{ws.port}/score",
                    data=json.dumps({"qty": 2 + i, "price": 3.5}).encode(),
                    method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                    rids.add(resp.headers.get("X-Pathway-Request-Id"))
            if len(rids) != 6 or None in rids:
                return f"request ids not unique/present: {rids}"
            tracker = rt.recorder.requests if rt.recorder else None
            if tracker is None:
                return "request tracker not armed under with_http_server"
            spans = tracker.trace_spans()
            if len(spans) < 6:
                return f"expected >= 6 completed spans, got {len(spans)}"
            for rec in spans[-6:]:
                stages = rec["stages"]
                if set(stages) != set(STAGE_FIELDS):
                    return f"stage set mismatch: {sorted(stages)}"
                if any(v < 0.0 for v in stages.values()):
                    return f"negative stage in {rec}"
                if abs(sum(stages.values()) - rec["e2e_ms"]) > 0.05:
                    return (f"stages do not sum to e2e: {stages} vs "
                            f"{rec['e2e_ms']}")
                # queue (commit-tick wait) and response write must have
                # genuinely elapsed; compute lives in host+device
                if stages["queue"] <= 0.0 or \
                        stages["response_write"] <= 0.0 or \
                        stages["host"] + stages["device"] <= 0.0:
                    return f"implausible decomposition: {stages}"
            mport = rt.http_server.port
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=10
            ).read().decode()
            for fam in ("pathway_tpu_query_e2e_latency_ms",
                        "pathway_tpu_query_stage_ms",
                        "pathway_tpu_slo_burn_rate",
                        "pathway_tpu_query_slo_violations"):
                if fam not in metrics:
                    return f"/metrics missing family {fam}"
            status = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/status", timeout=10).read())
            if status.get("serving", {}).get("requests", 0) < 6:
                return f"/status.serving incomplete: {status.get('serving')}"
            if "slow_queries" not in status:
                return "/status.slow_queries missing"
            print(f"etl serving gate OK: {len(spans)} spans, e2e p50 "
                  f"{status['serving']['e2e_ms']['p50']:.1f}ms, stages "
                  f"{status['serving'].get('stages')}")
            return None
        finally:
            stop_feed.set()
            _streaming.stop_all()
            th.join(15.0)
            G.clear()


def gate_sanitized_serving() -> str | None:
    """Device-discipline gate (PWT4xx's runtime twin): the warmed text
    serving path — packed encode + paged multi-extent search — completes
    under ``PATHWAY_DEVICE_SANITIZER=1`` with ZERO post-warmup compiles
    and zero implicit host→device transfers. Any violation raises, so
    this gate fails loudly the day a dispatch shape drifts off the
    warmed ladder."""
    os.environ["PATHWAY_DEVICE_SANITIZER"] = "1"
    try:
        import jax

        import pathway_tpu as pw
        from pathway_tpu.engine import device_sanitizer as ds
        from pathway_tpu.internals.keys import Pointer
        from pathway_tpu.models.encoder import EncoderConfig, init_params
        from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                         DeviceEmbeddingKnnIndex,
                                         KnnMetric)
        from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

        cfg = EncoderConfig.tiny(max_len=64)
        emb = JaxEncoderEmbedder(
            config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
            max_len=64, max_batch_size=1)
        idx = DeviceEmbeddingKnnIndex(
            emb, BruteForceKnnIndex(cfg.hidden, metric=KnnMetric.COS,
                                    page_rows=128))
        # population is pre-steady-state work: compiles here are warmup
        texts = [f"document number {i} with content {i * 7}"
                 for i in range(300)]  # 3 extents at page_rows=128
        idx.add_batch([Pointer(i) for i in range(300)], texts)
        idx.drain()
        pw.warmup(emb, index=idx, ks=(3,))
        if not ds.in_steady_state():
            return "pw.warmup did not declare steady state"
        if ds.warmup_compiles() == 0:
            return "no compiles landed in the warmup window"
        # steady-state serving: every query must reuse warmed executables
        # (a violation raises DeviceDisciplineViolation out of this loop)
        for i in range(8):
            res = idx.search(
                [(Pointer(10 ** 6 + i), texts[17 + i], 3, None)])
            if Pointer(17 + i) not in [k for k, _ in res[0]]:
                return f"query {i} returned {res[0]}"
        if ds.post_warmup_compiles() != 0:
            return (f"{ds.post_warmup_compiles()} post-warmup compile(s): "
                    f"{ds.violations()}")
        if ds.violations():
            return f"violations recorded: {ds.violations()}"
        print(f"sanitized serving gate OK: {ds.warmup_compiles()} warmup "
              "compiles, 0 post-warmup, 8 queries served under the "
              "transfer guard")
        return None
    finally:
        os.environ.pop("PATHWAY_DEVICE_SANITIZER", None)


def gate_bench_serving() -> str | None:
    repo = pathlib.Path(__file__).resolve().parent.parent
    with tempfile.TemporaryDirectory() as td:
        lastgood = pathlib.Path(td) / "BENCH_LASTGOOD.json"
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            BENCH_SERVING_N="2000", BENCH_SERVING_QUERIES="12",
            BENCH_SERVING_WARMUP="4",
            BENCH_LASTGOOD_PATH=str(lastgood))
        # the bench child re-warms mid-run with engine-driven (unpinned)
        # batch shapes — its compile/transfer-count COLUMNS watch that
        # leg; the sanitizer's raise-on-compile contract is gated by
        # gate_sanitized_serving above, on the pinned-shape path
        env.pop("PATHWAY_DEVICE_SANITIZER", None)
        # the leg's function, not `python bench.py`: the command line
        # runs device legs on a TPU only, and this is the CPU rehearsal
        proc = subprocess.run(
            [sys.executable, "-c",
             "import json, bench; out = bench.bench_serving(); "
             "bench._write_lastgood(out); print(json.dumps(out))"],
            env=env, cwd=repo, capture_output=True, text=True, timeout=540)
        last = None
        for ln in reversed((proc.stdout or "").splitlines()):
            if ln.strip().startswith("{"):
                try:
                    last = json.loads(ln)
                    break
                except json.JSONDecodeError:
                    continue
        if last is None:
            tail = (proc.stderr or "").strip().splitlines()[-5:]
            return f"bench emitted no JSON (rc={proc.returncode}): {tail}"
        required = ["knn_p50_e2e_ms", "knn_p95_e2e_ms", "knn_p99_e2e_ms",
                    "serving_n_queries"] + \
                   [f"serving_stage_{s}_p50_ms" for s in STAGE_FIELDS]
        for field in required:
            if field not in last:
                return f"bench JSON missing {field}: {sorted(last)}"
            # admission_wait is legitimately ~0 when QoS is off (the
            # stamp sits flush against the enqueue); every other stage
            # must have genuinely elapsed
            if field == "serving_stage_admission_wait_p50_ms":
                if last[field] < 0:
                    return f"bench JSON field {field} negative: {last[field]}"
            elif not last[field] > 0:
                return f"bench JSON field {field} not positive: {last[field]}"
        if not lastgood.exists():
            return "BENCH_LASTGOOD.json was not written"
        good = json.loads(lastgood.read_text())["result"]
        if good.get("knn_p50_e2e_ms") != last["knn_p50_e2e_ms"]:
            return f"lastgood diverged from bench JSON: {good}"
        print("bench serving gate OK: knn_p50_e2e_ms="
              f"{last['knn_p50_e2e_ms']}ms (reported, not thresholded); "
              "stages " + ", ".join(
                  f"{s}={last[f'serving_stage_{s}_p50_ms']}ms"
                  for s in STAGE_FIELDS))
        return None


def main() -> int:
    for name, gate in (("streaming-etl", gate_streaming_etl),
                       ("sanitized-serving", gate_sanitized_serving),
                       ("bench-serving", gate_bench_serving)):
        err = gate()
        if err:
            print(f"FAIL [{name}]: {err}", file=sys.stderr)
            return 1
    print("OK: serving-latency canary holds")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    sys.exit(main())

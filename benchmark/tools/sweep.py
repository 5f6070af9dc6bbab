"""The builder's sweep for the knee: one set-up of a cell, then its mix's
open-loop traffic at each of several fixed query rates, a segment each.

    python3 benchmark/tools/sweep.py --workload <cell> --seed <n>
        --rates 5,10,20,40,80 [--segment-s 12] [--settle-s 3]

The driver never runs this: a cell offers load at a rate fixed in its mix,
found once by a sweep like this one and recorded in PERF.md. Like the
benchmark it runs on a TPU only. It prints one line per rate: latency percentiles from
the due time, generator lateness, the share of ticks' searches that held
more queries than warm-up covered, and how far the last response trailed
the last request (a backlog that grew).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sweep(cell, *, seed: int, rates: list[float], segment_s: float,
          settle_s: float, expected_platform: str, log=print) -> list[dict]:
    from benchmark.lib import runner, stats, traffic
    from benchmark.lib.loadgen import OpenLoop

    rows = []
    with runner.session(cell, seed=seed, expected_platform=expected_platform,
                        t_start=T_START, log=log) as s:
        system, jit = s.system, s.jit
        # trace=True: the benchmark's spans, for the searches' batch sizes
        ready = runner.prepare(cell, system, seed=seed, trace=True,
                               phases=s.phases, jit=jit, workdir=s.workdir,
                               log=log)
        log(f"setup_s {time.perf_counter() - T_START:.2f}")
        warm_batch = cell.traffic["warm"]["query_batch_max"]
        for i, rate in enumerate(rates):
            mix = copy.deepcopy(cell.traffic)
            mix["queries"]["arrivals"]["rate_per_s"] = rate
            events = traffic.open_loop_schedule(
                mix, seed + i, settle_s + segment_s, ready.corpus,
                cell.config["guarantees"]["visible_within_ms"] / 1e3,
                prefix=f"sweep{i}-")
            origin = time.perf_counter() + 0.25
            n_before = len(jit.programs())
            gen = OpenLoop(system.base_url, events, origin, system.live_dir,
                           system.stage_dir)
            gen.start()
            gen.join()
            w0, w1 = origin + settle_s, origin + settle_s + segment_s
            qs = [r for r in gen.results if r.event.kind == "query"
                  and w0 <= r.due < w1]
            ok = [r for r in qs if r.error is None]
            ms = [(r.done - r.due) * 1e3 for r in ok]
            late = [(r.sent - r.due) * 1e3 for r in qs]
            searches = [m["queries"] for s, _e, m in
                        ready.spans.get("index.search", ()) if s >= w0]
            ryw = [r for r in ok if r.event.doc is not None]
            row = {
                "rate_per_s": rate, "queries": len(qs),
                "failed": len(qs) - len(ok),
                "p50_ms": stats.percentile(ms, 50) if ms else None,
                "p95_ms": stats.percentile(ms, 95) if ms else None,
                "p99_ms": stats.percentile(ms, 99) if ms else None,
                "late_p99_ms": stats.percentile(late, 99) if late else None,
                "search_batch_mean": sum(searches) / max(len(searches), 1),
                "search_batch_max": max(searches, default=0),
                "searches_over_warm": sum(1 for b in searches
                                          if b > warm_batch),
                "read_your_write_missed": sum(
                    1 for r in ryw if r.hits[:1] != (r.event.doc,)),
                "drain_s": max((r.done for r in ok), default=w1) - w1,
                "compiles": len(jit.programs()) - n_before,
            }
            rows.append(row)
            log("sweep " + json.dumps(row))
    return rows


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rates", default="5,10,20,40,80")
    parser.add_argument("--segment-s", type=float, default=12.0)
    parser.add_argument("--settle-s", type=float, default=3.0)
    args = parser.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark.lib import spec

    cell = spec.load(ROOT).cell(args.workload)
    rows = sweep(cell, seed=args.seed,
                 rates=[float(r) for r in args.rates.split(",")],
                 segment_s=args.segment_s, settle_s=args.settle_s,
                 expected_platform="tpu",
                 log=lambda msg: print(msg, flush=True))
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sweep.{cell.name}.json"), "a") as f:
        f.write(json.dumps({"rows": rows}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

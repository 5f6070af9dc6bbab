"""Scale-out canary: the exchange plane must carry an honest multi-worker
speedup, on both transports, without changing a single output byte.

Two gates (the gate is trusted because a seeded property is proven end to
end):

1. **bench scaleout leg** (bench.bench_scaleout): the WordCount+join ETL
   pipeline at 1 process vs 4 SPMD processes over BOTH transports (shm
   slab ring and raw tcp). Always gated: byte-identity of the merged
   consolidated outputs per transport, both transports actually used,
   and the coalesced exchange round count. Conditionally gated:
   ``etl_scaleout_efficiency`` ≥ 0.7 — ONLY when the runner exposes
   ≥ 4 cores (the cores-vs-workers honesty rule, bench_etl: a 4-process
   figure on fewer cores measures timesharing, not scaling; the leg then
   reports the number and flags ``scaleout_oversubscribed`` instead).
   The leg's JSON is written as a CI artifact AND checkpointed into
   ``BENCH_LASTGOOD.json`` per the evidence rule.

2. **codec absolute budget**: best-of-5 encode+decode of the r05 payload
   shape through the columnar wire format must stay ≤ 3.0 µs/row (vs
   6.495 at the r05 incident) — the same bound
   tests/test_exchange_perf.py pins, re-proven here against the bench's
   own measurement path so the artifact and the gate cannot drift apart.

Exits 0 iff all hold. Run: ``python tests/scaleout_canary.py``.
Knobs: BENCH_SCALEOUT_ROWS, SCALEOUT_MIN_EFFICIENCY (default 0.7),
SCALEOUT_BENCH_ARTIFACT (JSON path), BENCH_LASTGOOD_PATH.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

MIN_EFFICIENCY = float(os.environ.get("SCALEOUT_MIN_EFFICIENCY", 0.7))
ABS_BUDGET_US = 3.0


def gate_bench_leg() -> dict:
    import bench

    out = bench.bench_scaleout()
    bench._write_lastgood(out)  # evidence rule: checkpoint immediately
    artifact = os.environ.get("SCALEOUT_BENCH_ARTIFACT")
    if artifact:
        with open(artifact, "w") as f:
            json.dump(out, f, indent=1)
    for transport in ("shm", "tcp"):
        assert out[f"scaleout_identical_{transport}"] is True, (
            f"{transport}: 4-process consolidated outputs diverged from "
            f"the 1-process run — the exchange plane changed results")
        assert out[f"scaleout_transport_used_{transport}"] == [transport], (
            f"forced transport {transport} was not the one used: "
            f"{out[f'scaleout_transport_used_{transport}']}")
        assert out[f"scaleout_exchange_rounds_{transport}"] > 0, out
    assert out["scaleout_shm_slab_bytes"] > 0, (
        "shm run moved no slab bytes — payloads fell back to sockets")
    cores = out["scaleout_n_cores"]
    eff = out.get("etl_scaleout_efficiency")
    assert eff is not None, "no transport produced an identical run"
    if cores >= out["scaleout_workers"]:
        assert eff >= MIN_EFFICIENCY, (
            f"etl_scaleout_efficiency {eff} < {MIN_EFFICIENCY} on a "
            f"{cores}-core host: scale-out is not honest yet "
            f"(1p {out['scaleout_rows_per_s_1p']} rows/s vs best 4p "
            f"{max(out['scaleout_rows_per_s_4p_shm'], out['scaleout_rows_per_s_4p_tcp'])})")
        print(f"[gate1] efficiency {eff} >= {MIN_EFFICIENCY} at "
              f"{out['scaleout_workers']} workers on {cores} cores "
              f"(best transport: {out['scaleout_best_transport']})")
    else:
        print(f"[gate1] identity holds on both transports; efficiency "
              f"{eff} reported NOT gated ({cores} cores < "
              f"{out['scaleout_workers']} workers — timesharing, the "
              f"honesty rule)")
    return out


def gate_codec_budget() -> None:
    import gc
    import time

    from pathway_tpu.engine import wire
    from pathway_tpu.internals.keys import hash_values

    n = 20_000
    ents = [(hash_values("row", i), (f"w{i % 5000}", int(i % 9 + 1)), 1)
            for i in range(n)]
    payload = {"rows": {0: {0: ents}}, "wm": None, "bcast": None}
    best = float("inf")
    # freeze the long-lived heap so a gen-2 GC pass over unrelated
    # objects cannot land inside a trial (the r05 noise class); the
    # codec's own allocations still pay their GC cost
    gc.collect()
    gc.freeze()
    for _ in range(5):
        t0 = time.perf_counter()
        blob = b"".join(wire.encode_frame(("x", 1, 0), payload)[0])
        wire.decode_frame(blob)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    gc.unfreeze()
    assert best <= ABS_BUDGET_US, (
        f"columnar enc+dec best-of-5 {best:.3f} µs/row > {ABS_BUDGET_US} "
        f"(r05 was 6.495): absolute regression")
    print(f"[gate2] columnar enc+dec best-of-5 {best:.3f} µs/row "
          f"<= {ABS_BUDGET_US}")


def main() -> int:
    gate_bench_leg()
    gate_codec_budget()
    print("scaleout canary: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

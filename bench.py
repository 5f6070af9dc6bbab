"""Headline benchmark: RAG embed+index throughput + p50 KNN latency @10M.

Measures BOTH halves of the north-star metric from BASELINE.md:

1. documents → tokenize → flagship encoder forward (BGE-small shape,
   bfloat16, jit) → KNN index add (HBM slab scatter). Target: ≥50k
   docs/sec on v5e-8 ⇒ 6250 docs/sec/chip.
2. brute-force KNN query latency against a 10M x 384 bf16 slab resident
   in one chip's HBM (7.7 GB; the search is HBM-bandwidth-bound, chunked
   lax.scan kernel in ops/knn.py). Target: p50 < 20 ms.

Prints JSON lines (the last one is the result); the KNN figures ride
along as knn_* fields. Every line carries the device it ran on
(``platform``, ``device_kind``, ``n_devices``).

One process for the chip: the device legs (embed, framework, knn, serving)
run in THIS process, before any other leg touches JAX, and only on a TPU.
A backend that is not a TPU, or a device leg that raises, is a non-zero
exit with the error named — never a null value with exit 0. The CPU
rehearsal of a device leg is a test calling its ``bench_*`` function, not
this command line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_DOCS_PER_SEC_PER_CHIP = 50_000 / 8
KNN_TARGET_P50_MS = 20.0
KNN_N = int(os.environ.get("BENCH_KNN_N", 10_000_000))
KNN_DIM = 384
# docs/dispatch: one fused encode+scatter dispatch per batch, so the
# batch amortizes per-dispatch host work. Which size is best on the
# current machine is not measured; 2048 is the value every record so far
# was taken at.
BATCH = int(os.environ.get("BENCH_BATCH", 2048))
SKIP = set(os.environ.get("BENCH_SKIP", "").split(","))
# the legs that need the chip, in the order they run
_DEVICE_LEG_NAMES = ("embed", "framework", "knn", "serving")
SEQ = 128
WORDS_PER_DOC = 90


def make_docs(n: int, seed: int = 0) -> list[str]:
    rng = np.random.default_rng(seed)
    vocab = [f"word{i}" for i in range(4096)]
    idx = rng.integers(0, len(vocab), size=(n, WORDS_PER_DOC))
    return [" ".join(vocab[j] for j in row) for row in idx]


def _peak_flops() -> float | None:
    """Peak bf16 FLOP/s of the device JAX runs on, from the shared table
    keyed by ``device_kind`` (engine/profiler.DEVICE_PEAKS), so the bench
    and the live roofline gauges describe the same chip — or None for a
    device with no row: its MFU is then "not measured", never rated
    against another chip's peak."""
    from pathway_tpu.engine.profiler import machine_params

    mp = machine_params()
    return None if mp is None else mp["peak_tflops"] * 1e12


def _encoder_flops_per_token(config, seq: int = SEQ) -> float:
    """Forward FLOPs/token for the encoder — resolved through the SHARED
    cost model (engine/profiler.py): the profiler's MFU gauges and the
    bench's MFU numbers are the same formula by construction, which
    tests/test_profiler.py pins (no drift between copies)."""
    from pathway_tpu.engine.profiler import encoder_flops_per_token

    return encoder_flops_per_token(config.hidden, config.intermediate,
                                   config.layers, seq)


_LEG_FNS = {
    "embed": lambda: bench_embed(),
    "framework": lambda: bench_embed_framework(),
    "knn": lambda: bench_knn(),
    "serving": lambda: bench_serving(),
}


class _DeviceEventCounter:
    """Per-leg XLA compile + implicit host→device transfer counts.

    Compiles come from the device sanitizer's monitoring listener
    (engine/device_sanitizer.install_compile_counter — a plain counter,
    no env gate). Transfers ride JAX's transfer guard in ``log`` mode,
    whose per-transfer lines come out of C++ (guard_lib.cc) on fd 2 —
    invisible to Python-level stderr hooks — so the guard window
    captures fd 2 into a temp file, counts the marker lines, and replays
    the bytes to the real stderr so nothing is swallowed. The counts
    join BENCH_HISTORY.jsonl as ``{leg}_compile_count`` /
    ``{leg}_transfer_count`` with lower-is-better pins in
    ``_BENCH_DIRECTIONS``: a recompile zoo or a new per-tick upload then
    fails ``--check-regression`` numerically even with the sanitizer
    off."""

    def __init__(self):
        from pathway_tpu.engine.device_sanitizer import \
            install_compile_counter

        self._compiles = install_compile_counter()

    def count(self, leg: str, fn):
        """Run ``fn()`` and return (its result, the events dict)."""
        import tempfile

        import jax

        c0 = self._compiles()
        tmp = tempfile.TemporaryFile()
        saved = os.dup(2)
        # restore whatever mode was active (the device sanitizer may
        # hold "disallow" in steady state — don't weaken it for good)
        prev = jax.config.jax_transfer_guard_host_to_device or "allow"
        jax.config.update("jax_transfer_guard_host_to_device", "log")
        os.dup2(tmp.fileno(), 2)
        try:
            out = fn()
        finally:
            os.dup2(saved, 2)
            os.close(saved)
            jax.config.update("jax_transfer_guard_host_to_device", prev)
            tmp.seek(0)
            data = tmp.read()
            tmp.close()
            if data:
                try:
                    os.write(2, data)  # replay: keep stderr observable
                except OSError:
                    pass
        events = {
            f"{leg}_compile_count": self._compiles() - c0,
            f"{leg}_transfer_count": sum(
                b"host-to-device transfer" in line
                for line in data.splitlines()),
        }
        return out, events

# serving-path SLO leg (bench_serving): slab size / dim / query count
SERVING_N = int(os.environ.get("BENCH_SERVING_N", 100_000))
SERVING_DIM = int(os.environ.get("BENCH_SERVING_DIM", KNN_DIM))
SERVING_QUERIES = int(os.environ.get("BENCH_SERVING_QUERIES", 48))
SERVING_WARMUP = int(os.environ.get("BENCH_SERVING_WARMUP", 8))

# QoS leg (bench_qos): same workload QoS-off vs QoS-on — the before/after
# artifact for "the controller actively trades ingest throughput for
# query latency" (engine/qos.py; ROADMAP "close the SLO control loop")
QOS_N = int(os.environ.get("BENCH_QOS_N", 20_000))
QOS_DIM = int(os.environ.get("BENCH_QOS_DIM", 64))
QOS_QUERIES = int(os.environ.get("BENCH_QOS_QUERIES", 32))
QOS_WARMUP = int(os.environ.get("BENCH_QOS_WARMUP", 6))
QOS_INGEST_CHUNK = int(os.environ.get("BENCH_QOS_INGEST_CHUNK", 1024))
QOS_INGEST_PERIOD_S = float(os.environ.get("BENCH_QOS_INGEST_PERIOD_S",
                                           0.05))
QOS_BURST = int(os.environ.get("BENCH_QOS_BURST", 32))
QOS_K = int(os.environ.get("BENCH_QOS_K", 10))
QOS_COMMIT_MS = int(os.environ.get("BENCH_QOS_COMMIT_MS", 5))

# Semantic result-cache leg (bench_semantic_cache): the SAME router-
# fronted serving fleet under a Zipf query stream with live ingest,
# cache-off vs cache-on (operator cache + router fleet cache). The
# Zipf head repeats, so the leg measures what the cache is FOR:
# identical (method, path, body) requests served at the router without
# touching a replica, and repeated query vectors served from the
# operator cache without a kernel dispatch.
SEM_POOL = int(os.environ.get("BENCH_SEM_POOL", 96))
SEM_ZIPF_S = float(os.environ.get("BENCH_SEM_ZIPF_S", 1.1))
SEM_SECONDS = float(os.environ.get("BENCH_SEM_SECONDS", 10.0))
SEM_WARMUP_S = float(os.environ.get("BENCH_SEM_WARMUP_S", 1.5))
SEM_CLIENTS = int(os.environ.get("BENCH_SEM_CLIENTS", 8))
SEM_COST_MS = float(os.environ.get("BENCH_SEM_COST_MS", 30.0))
SEM_VECS = int(os.environ.get("BENCH_SEM_VECS", 512))
# live-ingest cadence for BOTH phases: slow enough that the watermark
# holds across a forward (so router fills commit), fast enough that
# invalidations/tick stays a live number in the snapshot
SEM_TRICKLE_S = float(os.environ.get("BENCH_SEM_TRICKLE_S", 4.0))

# evidence rule (ROADMAP): every completed leg's numbers are checkpointed
# into BENCH_LASTGOOD.json at once, so a crash or a kill in a later leg
# cannot erase them
_LASTGOOD_STATE: dict = {}


def _write_lastgood(snapshot: dict) -> None:
    path = os.environ.get("BENCH_LASTGOOD_PATH", "BENCH_LASTGOOD.json")
    try:
        from pathway_tpu.engine.flight_recorder import atomic_write_json

        if not _LASTGOOD_STATE and os.path.exists(path):
            # seed from the on-disk checkpoint so a single-leg run (the
            # CI jobs call one bench_* fn directly) REFINES the evidence
            # file instead of erasing every other leg's captured numbers
            try:
                with open(path) as f:
                    prior = json.load(f).get("result")
                if isinstance(prior, dict):
                    _LASTGOOD_STATE.update(prior)
            except Exception:  # noqa: BLE001 — a torn file must not block
                pass
        _LASTGOOD_STATE.update(
            {k: v for k, v in snapshot.items() if not k.endswith("error")})
        atomic_write_json(path, {"updated_at": time.time(),
                                 "result": dict(_LASTGOOD_STATE)})
    except Exception:  # noqa: BLE001 — evidence must never kill a leg
        pass


# -- perf-trajectory watch ----------------------------------------------------
# BENCH_LASTGOOD.json is a last-good SNAPSHOT; the trajectory lives in
# BENCH_HISTORY.jsonl (one row per leg metric per run: leg, metric, value,
# git sha, timestamp — engine/fleet_observability.py). Every leg appends
# its rows, and `bench.py --check-regression` compares each series'
# newest point against the trailing median of its prior points with
# per-metric tolerance bands — a CI-checkable time series instead of an
# empty trajectory (ROADMAP evidence rule).

def _append_bench_history(leg: str, metrics: dict) -> None:
    try:
        from pathway_tpu.engine.fleet_observability import \
            append_bench_history

        append_bench_history(leg, metrics)
    except Exception:  # noqa: BLE001 — evidence must never kill a leg
        pass
    _maybe_profile_epoch(leg)


# --profile: one cost-model + host-flamegraph snapshot per completed leg
# (engine/profiler.py profile_epoch), embedded as the "profile" key of
# the emitted BENCH_*.json line — the input `python -m pathway_tpu
# profdiff A.json B.json` compares when --check-regression flags a leg
_PROFILE_EPOCHS: list = []


def _maybe_profile_epoch(leg: str) -> None:
    try:
        from pathway_tpu.engine.profiler import current_profiler

        prof = current_profiler()
        if prof is not None and "--profile" in sys.argv:
            _PROFILE_EPOCHS.append({"leg": leg, **prof.profile_epoch()})
    except Exception:  # noqa: BLE001 — evidence must never kill a leg
        pass


# per-metric direction overrides for series the name heuristics cannot
# judge (engine/fleet_observability.metric_direction). The qos leg's
# series need them: "qos_shed_total" carries no marker at all (fewer
# sheds is better), and the ingest-rate pair is deliberately split —
# the OFF series is a plain throughput number (higher is better; a drop
# means the workload itself regressed) while the ON series is the
# CONTROLLER'S trade and moves with load, so it stays unwatched
# (reported, never gated) rather than coin-flipped.
_BENCH_DIRECTIONS = {
    "qos_shed_total": "lower",
    "qos_off_ingest_rate_rps": "higher",
    "qos_p50_speedup": "higher",
    # recovery leg: the bounded-restart contract is "smaller is better"
    # across the board. The ratio carries no unit marker at all (a bare
    # max/min quotient — growth means snapshot restart is no longer flat
    # in history size), and the restart series are pinned explicitly so
    # the suffix heuristic's `_s_<n>` match is a backstop, not the only
    # thing watching the recovery trajectory.
    "recovery_snapshot_ratio_maxmin": "lower",
    "recovery_walonly_restart_s_1000": "lower",
    "recovery_walonly_restart_s_10000": "lower",
    "recovery_walonly_restart_s_100000": "lower",
    "recovery_snapshot_restart_s_1000": "lower",
    "recovery_snapshot_restart_s_10000": "lower",
    "recovery_snapshot_restart_s_100000": "lower",
    # device-discipline columns (_DeviceEventCounter): bare counts carry
    # no unit marker the name heuristic could judge, and both are
    # strictly lower-is-better — a rising compile count is a recompile
    # zoo and a rising transfer count a new per-tick host→device upload,
    # caught numerically here even when PATHWAY_DEVICE_SANITIZER is off
    "embed_compile_count": "lower",
    "embed_transfer_count": "lower",
    "framework_compile_count": "lower",
    "framework_transfer_count": "lower",
    "knn_compile_count": "lower",
    "knn_transfer_count": "lower",
    "serving_compile_count": "lower",
    "serving_transfer_count": "lower",
    # failover leg (bench_replica): promotion wall-clock is the
    # write-unavailability window (smaller is better), and the fenced
    # zombie's write count is a bare counter — each one is a split-brain
    # write REFUSED; more of them means the zombie raced longer before
    # noticing its demotion
    "replica_failover_promotion_s": "lower",
    "replica_fenced_writes": "lower",
    # semantic result-cache leg: the speedup and both hit rates are the
    # headline (higher is better); router invalidations are watermark
    # moves observed by the cache — a climb means the fleet cache is
    # churning instead of serving. The `lost` counters are plain counts
    # with no unit marker: any rise is dropped queries.
    "semantic_cache_qps_speedup": "higher",
    "semantic_cache_router_hit_rate": "higher",
    "semantic_cache_op_hit_ratio": "higher",
    "semantic_cache_router_invalidations": "lower",
    "semantic_cache_off_lost": "lower",
    "semantic_cache_on_lost": "lower",
}


def check_regression_main(argv: list[str]) -> int:
    """``bench.py --check-regression``: gate the newest BENCH_HISTORY
    point of every watched series against its trailing median. Exit 0
    when the trajectory holds (or is too young to judge), 1 naming each
    regression otherwise. Knobs: ``--history PATH``
    (BENCH_HISTORY_PATH), ``--window N``, ``--min-prior N``,
    ``--tolerance F`` (BENCH_REGRESSION_TOLERANCE, default 0.35).
    Direction overrides for heuristic-blind series live in
    ``_BENCH_DIRECTIONS``."""
    from pathway_tpu.engine.fleet_observability import (
        bench_history_rows, check_regressions, history_path)

    opts = {"--history": None, "--window": "8", "--min-prior": "3",
            "--tolerance": None}
    profdiff_args: list[str] = []
    i = 0
    while i < len(argv):
        if argv[i] in opts and i + 1 < len(argv):
            opts[argv[i]] = argv[i + 1]
            i += 2
        elif argv[i] == "--profdiff" and i + 2 < len(argv):
            # name the dominant frame/kernel delta between a baseline
            # --profile artifact and the flagged run's (profdiff below
            # runs only when a regression actually fires)
            profdiff_args = [argv[i + 1], argv[i + 2]]
            i += 3
        else:
            i += 1
    path = history_path(opts["--history"])
    rows = bench_history_rows(path)
    if not rows:
        print(json.dumps({"check": "regression", "history": path,
                          "rows": 0, "regressions": [],
                          "note": "no trajectory yet"}), flush=True)
        return 0
    regs = check_regressions(
        path, window=int(opts["--window"]),
        min_prior=int(opts["--min-prior"]),
        tolerance=(float(opts["--tolerance"])
                   if opts["--tolerance"] is not None else None),
        directions=_BENCH_DIRECTIONS)
    series = {(r.get("leg"), r["metric"]) for r in rows}
    print(json.dumps({"check": "regression", "history": path,
                      "rows": len(rows), "series": len(series),
                      "regressions": regs}), flush=True)
    for r in regs:
        direction = ">" if r["direction"] == "lower" else "<"
        print(f"REGRESSION {r['leg']}/{r['metric']}: {r['value']} "
              f"{direction} trailing median {r['median']} beyond the "
              f"{r['tolerance']:.0%} band (ratio {r['ratio']}, "
              f"{r['n_prior']} prior points)", file=sys.stderr)
    if regs and profdiff_args:
        # a regression fired and two --profile artifacts were offered:
        # name the dominant frame/kernel delta (engine/profiler.py)
        try:
            from pathway_tpu.engine.profiler import diff_profiles

            with open(profdiff_args[0]) as f:
                a = json.load(f)
            with open(profdiff_args[1]) as f:
                b = json.load(f)
            diff = diff_profiles(a, b)
            dk, df = diff["dominant_kernel"], diff["dominant_frame"]
            if dk is not None:
                print(f"PROFDIFF dominant kernel: {dk['family']} "
                      f"{dk['device_ms_per_dispatch_a']} -> "
                      f"{dk['device_ms_per_dispatch_b']} ms/dispatch "
                      f"({dk['bound_by']}-bound)", file=sys.stderr)
            if df is not None:
                print(f"PROFDIFF dominant frame: {df['frame']} "
                      f"share {df['share_a']} -> {df['share_b']}",
                      file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — attribution is advisory
            print(f"PROFDIFF unavailable: {type(e).__name__}: {e}",
                  file=sys.stderr)
    return 1 if regs else 0


def _device_stamp() -> dict:
    """The device this process runs on, as JAX reports it — stamped on
    every emitted line. First backend touch: raises when JAX finds no
    usable backend."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def _run_device_legs(legs: list[str], result: dict, errors: dict) -> None:
    """Run the device legs in this process, in order. A leg that raises
    is recorded as ``{leg}_error`` (traceback on stderr) and the rest
    still run — main() turns any such key into a non-zero exit."""
    import traceback

    counter = _DeviceEventCounter()
    for leg in legs:
        try:
            leg_out, events = counter.count(leg, _LEG_FNS[leg])
        except Exception as e:  # noqa: BLE001 — reported, then exit != 0
            traceback.print_exc()
            errors[f"{leg}_error"] = f"{type(e).__name__}: {str(e)[:300]}"
            continue
        leg_out.update(events)
        result.update(leg_out)
        if "framework_docs_per_s" in result and "docs_per_s" in result:
            # the round-5 verdict's headline (#5) on the REAL device
            # legs: framework-path throughput over the raw-kernel leg's,
            # SAME run — target >= 0.85. Suffixed _device: the gated CPU
            # autojit leg owns the bare `framework_vs_raw_ratio` key, and
            # a full bench run must not let one leg clobber the other's
            # number in result/BENCH_LASTGOOD.json
            result["framework_vs_raw_ratio_device"] = round(
                result["framework_docs_per_s"] / result["docs_per_s"], 3)
        _write_lastgood(result)
        _append_bench_history(leg, leg_out)


def main() -> None:
    if "--check-regression" in sys.argv:
        # perf-trajectory watch: judge BENCH_HISTORY.jsonl instead of
        # running any leg (engine/fleet_observability.py)
        sys.exit(check_regression_main(sys.argv[1:]))

    # persistent XLA cache: repeat bench runs on one machine skip every
    # warmup compile
    from pathway_tpu.warmup import enable_compilation_cache

    enable_compilation_cache()

    if "--profile" in sys.argv:
        # continuous profiler ON for the whole run: cost-model hooks in
        # the legs feed the per-family aggregates; one profile epoch is
        # snapped per completed leg (_maybe_profile_epoch) and embedded
        # under the "profile" key of the emitted artifact
        from pathway_tpu.engine.profiler import (Profiler, current_profiler,
                                                 install_profiler)

        if current_profiler() is None:
            _prof = Profiler()
            install_profiler(_prof)
            _prof.start()
        os.environ.setdefault("PATHWAY_PROFILER", "1")  # child processes

    result: dict = {}
    errors: dict = {}
    stamp = {"platform": None, "device_kind": None, "n_devices": None}

    def emit() -> None:
        # value/vs_baseline are null — not a real-looking 0.0 — when the
        # embed leg produced no measurement (skipped, or failed: then an
        # error key says so and the exit code is non-zero)
        docs_per_sec = result.get("docs_per_s")
        extra = {}
        if _PROFILE_EPOCHS:
            # --profile: per-leg cost-model + flamegraph epochs, the
            # `python -m pathway_tpu profdiff` input
            extra["profile"] = _PROFILE_EPOCHS
        print(json.dumps({
            "metric": "RAG docs/sec/chip (embed+index); p50 KNN @10M",
            "value": None if docs_per_sec is None else round(docs_per_sec, 1),
            "unit": "docs/s",
            "vs_baseline": None if docs_per_sec is None else round(
                docs_per_sec / BASELINE_DOCS_PER_SEC_PER_CHIP, 3),
            **stamp,
            **{k: v for k, v in result.items() if k != "docs_per_s"},
            **extra,
            **errors,
        }), flush=True)

    try:
        stamp.update(_device_stamp())
    except Exception as e:  # noqa: BLE001 — no backend: named, exit != 0
        errors["error"] = (f"backend init failed: {type(e).__name__}: "
                           f"{str(e)[:300]}")
        emit()
        sys.exit(2)

    # the device legs first, in this process: a chip belongs to one process,
    # and the host-only legs below also touch JAX (autojit)
    device_legs = [leg for leg in _DEVICE_LEG_NAMES if leg not in SKIP]
    if device_legs:
        if stamp["platform"] != "tpu":
            errors["error"] = (
                f"device legs {device_legs} need a TPU, but JAX runs on "
                f"{stamp['platform']!r} — not measuring a fallback")
            emit()
            sys.exit(2)
        _run_device_legs(device_legs, result, errors)
        emit()  # the device numbers are out before the long host legs run

    if "etl" not in SKIP:
        try:
            leg_out = bench_etl()
            result.update(leg_out)
            _append_bench_history("etl", leg_out)
        except Exception as e:  # noqa: BLE001
            errors["etl_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "autojit" not in SKIP:
        # auto-jit leg (CPU-runnable): framework-vs-raw on the doc-scoring
        # pipeline, auto-jit on/off in the same artifact + the per-stage
        # flight-recorder breakdown (where the Table-path tax went)
        try:
            leg_out = bench_autojit()
            result.update(leg_out)
            _append_bench_history("autojit", leg_out)
            _write_lastgood({k: v for k, v in result.items()
                             if k.startswith(("autojit_", "framework_vs_"))})
        except Exception as e:  # noqa: BLE001
            errors["autojit_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "scaleout" not in SKIP:
        # exchange-plane scale-out leg (CPU-runnable): 4-process SPMD
        # cluster vs 1 process over both transports (shm slab ring / raw
        # tcp), etl_scaleout_efficiency under the cores-vs-workers
        # honesty rule, byte-identity, per-transport encdec cost
        try:
            leg_out = bench_scaleout()
            result.update(leg_out)
            _append_bench_history("scaleout", leg_out)
        except Exception as e:  # noqa: BLE001
            errors["scaleout_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "durability" not in SKIP:
        # watermark-durability leg (CPU-runnable): bridge overlap with
        # persistence ON at inflight 1 vs 4 + checkpoint cadence — the
        # evidence that durability no longer prices pipelining at depth 1
        try:
            leg_out = bench_durability()
            result.update(leg_out)
            _append_bench_history("durability", leg_out)
        except Exception as e:  # noqa: BLE001
            errors["durability_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "recovery" not in SKIP:
        # bounded-recovery leg (CPU-runnable): restart wall-clock at
        # 1k/10k/100k-row histories, WAL-only (linear) vs snapshot+suffix
        # (~flat) — the evidence that compaction bounds restart by data
        # size, not stream age
        try:
            leg_out = bench_recovery()
            result.update(leg_out)
            _append_bench_history("recovery", leg_out)
        except Exception as e:  # noqa: BLE001
            errors["recovery_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "replica" not in SKIP:
        # replica-fleet leg (CPU-runnable): hydration time-to-ready vs
        # history size (WAL-only vs snapshot), end-to-end p50/p95 through
        # the router at 1 vs 2 replicas, staleness lag exported on
        # /metrics, and the kill-under-load failover count
        try:
            leg_out = bench_replica()
            result.update(leg_out)
            _append_bench_history("replica", leg_out)
        except Exception as e:  # noqa: BLE001
            errors["replica_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "qos" not in SKIP:
        # QoS leg (CPU-runnable): the same heavy-ingest serving workload
        # QoS-off vs QoS-on — the before/after artifact for "the
        # controller trades ingest throughput for query latency"
        # (engine/qos.py), plus visible-shedding / deferral / coalescing
        # counters from the induced overload phase
        try:
            leg_out = bench_qos()
            result.update(leg_out)
            _append_bench_history("qos", leg_out)
            _write_lastgood({k: v for k, v in leg_out.items()
                             if k.startswith("qos_")})
        except Exception as e:  # noqa: BLE001
            errors["qos_error"] = f"{type(e).__name__}: {str(e)[:300]}"

    if "semantic_cache" not in SKIP:
        # semantic result-cache leg (CPU-runnable): the same Zipf query
        # stream through the router cache-off vs cache-on — served QPS,
        # p95, hit rates at both layers, invalidations/tick under live
        # ingest (engine/result_cache.py)
        try:
            leg_out = bench_semantic_cache()
            result.update(leg_out)
            _append_bench_history("semantic_cache", leg_out)
            _write_lastgood({k: v for k, v in leg_out.items()
                             if k.startswith("semantic_cache_")})
        except Exception as e:  # noqa: BLE001
            errors["semantic_cache_error"] = \
                f"{type(e).__name__}: {str(e)[:300]}"

    emit()
    if any(f"{leg}_error" in errors for leg in device_legs):
        sys.exit(1)


def bench_embed() -> dict:
    """The docs/sec leg: tokenize → encoder forward → fused index add."""
    import jax

    from pathway_tpu.models.encoder import EncoderConfig, encode, init_params
    from pathway_tpu.models.hf_loader import find_local_checkpoint, load_model
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    # real BGE weights + vocab when the checkpoint is on disk; otherwise
    # random weights at the exact BGE shape and a synthetic vocab — the
    # tokenizer still runs the real WordPiece algorithm (native C++ batch
    # kernel), so the host-side cost is representative either way
    if find_local_checkpoint("BAAI/bge-small-en-v1.5"):
        params, config, tokenizer = load_model("BAAI/bge-small-en-v1.5")
        tokenizer.max_len = SEQ
    else:
        config = EncoderConfig.bge_small()
        params = init_params(jax.random.PRNGKey(0), config)
        tokenizer = WordPieceTokenizer(
            make_synthetic_vocab([f"word{i}" for i in range(4096)],
                                 vocab_size=config.vocab_size),
            max_len=SEQ)
    # fused ingest donates the slab, so capacity is pinned — reserve enough
    # for the whole timed window (bf16: 1M x 384 = 0.8 GB)
    index = BruteForceKnnIndex(config.hidden, reserved_space=1 << 20,
                               metric=KnnMetric.COS, dtype="bfloat16")

    import jax.numpy as jnp

    encode_fn = jax.jit(
        lambda p, ids, mask: encode(p, ids, mask, config=config))

    # ONE dispatch per batch: encode fused with the slab scatter, slab
    # donated — embeddings never leave the chip and nothing blocks.
    # Host→device payload is minimized: int16 token ids (vocab < 32768)
    # and per-row lengths instead of a (B, S) mask — the mask is rebuilt
    # on device with iota < len.
    def producer(p, ids_i16, lens):
        ids32 = ids_i16.astype(jnp.int32)
        mask = jnp.arange(ids32.shape[1])[None, :] < lens[:, None]
        return encode(p, ids32, mask, config=config)

    ingest = index.make_fused_ingest(producer)

    def pack(ids, mask):
        # bucket-pad to a multiple of 16 (bounded by SEQ): real docs do not
        # fill the max context, and MXU time scales with padded tokens —
        # a few shape buckets bound recompilation
        lens = mask.sum(axis=1).astype(np.int32)
        width = min(SEQ, max(16, int(-(-int(lens.max()) // 16) * 16)))
        return ids[:, :width].astype(np.int16), lens

    docs = make_docs(BATCH * 4)

    def run_batch(batch_docs, key_base):
        ids, mask = tokenizer.batch(batch_docs, pad_to=SEQ)
        ids16, lens = pack(ids, mask)
        ingest([Pointer(key_base + i) for i in range(len(batch_docs))],
               params, ids16, lens)

    # warmup (compile + device clock ramp) + correctness probe: a doc must
    # retrieve itself. Several post-compile batches: the first dispatches of
    # a fresh process run measurably slower.
    run_batch(docs[:BATCH], 0)
    for w in range(3):
        run_batch(docs[:BATCH], 0)
    ids, mask = tokenizer.batch(docs[:8], pad_to=SEQ)
    probe = np.asarray(encode_fn(params, ids, mask))
    res = index.search([(Pointer(10**9), probe[3], 1, None)])
    assert res and res[0] and res[0][0][0] == Pointer(3), \
        f"self-retrieval failed: {res}"

    # timed: pipeline host tokenization against device compute — submit the
    # encode for batch i, tokenize batch i+1 while the TPU works, then drain.
    # Metric = sustained docs/sec over the timed window (first timed batch
    # dropped: it straddles the warmup boundary). Sustained, not per-batch
    # median — the number must be comparable to BASELINE.md's sustained
    # target, stalls included.
    n_batches = 0
    key_base = BATCH
    start = time.perf_counter()
    batch_times = []
    batch_tokens = []
    batch_flops = []
    last_t = start
    ids16, lens = pack(*tokenizer.batch(docs[:BATCH], pad_to=SEQ))
    while True:
        ingest([Pointer(key_base + i) for i in range(BATCH)],
               params, ids16, lens)  # async: one fused dispatch
        batch_tokens.append(ids16.shape[0] * ids16.shape[1])
        batch_flops.append(batch_tokens[-1] * _encoder_flops_per_token(
            config, seq=ids16.shape[1]))
        next_docs = docs[((n_batches + 1) % 4) * BATCH:][:BATCH]
        ids16, lens = pack(*tokenizer.batch(next_docs, pad_to=SEQ))
        now = time.perf_counter()
        batch_times.append(now - last_t)
        last_t = now
        n_batches += 1
        key_base += BATCH
        elapsed = time.perf_counter() - start
        if (elapsed > 15.0 and len(batch_times) >= 8) or \
                key_base + BATCH > index.capacity:
            break
    # drain the async dispatch queue before the final stamp: sustained
    # throughput must include all queued device work, not just dispatches
    index.drain()
    now = time.perf_counter()
    batch_times[-1] += now - last_t
    sustained = batch_times[1:]  # drop the warmup-straddling first batch
    docs_per_sec = BATCH * len(sustained) / float(np.sum(sustained))
    tokens_per_sec = float(np.sum(batch_tokens[1:]) / np.sum(sustained))
    # MFU from per-batch flops at the ACTUAL padded width (not SEQ):
    # sustained MFU counts host stalls against the device
    peak = _peak_flops()
    flops_per_s = float(np.sum(batch_flops[1:]) / np.sum(sustained))
    flops_per_s_dev = _device_only_flops_per_s(params, config)

    # free the embed leg's device state (slab + donated buffers) before the
    # 10M KNN leg claims most of HBM
    del index, ingest
    import gc

    gc.collect()

    return {
        "docs_per_s": docs_per_sec,
        "tokens_per_s": round(tokens_per_sec, 0),
        # null where the device has no row in DEVICE_PEAKS: not measured
        "mfu_est": None if peak is None else round(flops_per_s / peak, 3),
        "mfu_device_only": None if peak is None else round(
            flops_per_s_dev / peak, 3),
        "mfu_peak_tflops": None if peak is None else peak / 1e12,
    }


def _device_only_flops_per_s(params, config, B: int = 2048, W: int = 128,
                             reps: int = 8) -> float:
    """Encoder FLOP/s with NO host in the loop (reps forwards inside one
    jitted fori_loop): the program's device ceiling, reported (over the
    chip's peak) next to sustained MFU so host-stall time is
    attributable. Not measured on the current machine yet."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import encode

    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, config.vocab_size, (B, W)).astype(np.int32))
    lens = jnp.full((B,), W - 5, jnp.int32)

    @jax.jit
    def loop(params, ids, lens):
        def body(i, acc):
            mask = jnp.arange(ids.shape[1])[None, :] < lens[:, None]
            out = encode(params, ids + i, mask, config=config)
            return acc + jnp.sum(out).astype(jnp.float32)

        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    float(loop(params, ids, lens))  # compile + warm
    # best of 3: this reports the program's device CEILING, and anything
    # else on the host can only subtract from it
    dt = min(_timed(lambda: float(loop(params, ids, lens)))
             for _ in range(3))
    return reps * B * W * _encoder_flops_per_token(config, seq=W) / dt


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_embed_framework(n_docs: int | None = None) -> dict:
    """BASELINE config 2 measured through the ACTUAL framework: a docs
    Table streamed tick-by-tick through VectorStoreServer's graph
    (parse UDF → flatten → split UDF → flatten → JaxEncoderEmbedder
    batch-UDF → engine external index add) under GraphRunner, with one
    retrieval query answered against the built index.

    Reference counterpart: xpacks/llm/vector_store.py:214-292
    (sources→parse→split→embed→index). ``framework_docs_per_s`` vs the
    raw-kernel ``docs_per_s`` is the engine overhead this round is
    shrinking; both ride the same encoder shape + WordPiece tokenizer.
    """
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.json import Json
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    if n_docs is None:
        n_docs = int(os.environ.get("BENCH_FRAMEWORK_DOCS", BATCH * 8))
    n_ticks = max(1, n_docs // BATCH)

    emb = _make_framework_embedder(JaxEncoderEmbedder)

    G.clear()
    schema = sch.schema_from_types(data=str, _metadata=pw.Json)
    docs_rows = [(doc, Json({"path": f"/d{i}.txt"}),
                  (i * n_ticks) // n_docs * 2, 1)
                 for i, doc in enumerate(make_docs(n_docs))]
    docs = table_from_rows(schema, docs_rows, is_stream=True)

    store = VectorStoreServer(
        docs, embedder=emb,
        index_builder=lambda chunks: default_brute_force_knn_document_index(
            chunks.text, chunks, embedder=emb,
            dimensions=emb.get_embedding_dimension(),
            reserved_space=n_docs + 64, dtype="bfloat16"))
    qschema = sch.schema_from_types(
        query=str, k=int, metadata_filter=type(None),
        filepath_globpattern=type(None))
    queries = table_from_rows(
        qschema, [("word1 word2 word3", 3, None, None)])
    res = store.retrieve_query(queries)
    runner = GraphRunner()
    cap = runner.capture(res)

    # pre-compile the kernels at the exact shapes the timed run will use,
    # so the measurement is throughput, not XLA compile time (the raw leg
    # equally excludes its warmup dispatches). The fused encode+scatter
    # step is a separate jit function from the plain encoder, so warm it
    # through the BUILT engine index (then retract the warmup rows).
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import DeviceEmbeddingKnnIndex

    warm = make_docs(BATCH, seed=1)
    emb.embed_batch(["word1 word2 word3"])  # the (1, bucket) query shape
    # the timed ticks are contiguous BATCH-doc slices whose packed widths
    # can straddle a bucket boundary (48 vs 64): warm the fused kernel at
    # EVERY width the run will dispatch, or a ~0.75 s XLA compile lands
    # inside the timed window (measured r5: 2 in-window compiles cost
    # 1.48 s of a 2.76 s window)
    all_texts = [r[0] for r in docs_rows]
    widths = sorted({emb.pack_tokens(all_texts[t * BATCH:(t + 1) * BATCH])[0]
                     .shape[1] for t in range(n_ticks)})
    warmed_fused = False
    for node in runner.graph.nodes:
        idx = getattr(node.op, "index", None)
        if isinstance(idx, DeviceEmbeddingKnnIndex):
            wkeys = [Pointer((1 << 62) + i) for i in range(BATCH)]
            idx.add_batch(wkeys, warm)
            for w in widths:
                idx._fused(wkeys, emb.params,
                           np.zeros((BATCH, w), np.int16),
                           np.full(BATCH, max(1, w - 2), np.int32))
            # warm the top-k search kernel at the query fanout (k=3) —
            # the retrieval answer otherwise compiles it in-window
            idx.search([(Pointer((1 << 62) + BATCH),
                         "word1 word2 word3", 3, None)])
            for k in wkeys:
                idx.remove(k)
            # push the removal invalidations now: they sit in the dirty
            # set, and the first timed ingest would otherwise flush them
            # through the plain scatter — compiling it in-window (0.74 s)
            idx.inner.flush_device()
            warmed_fused = True
    if not warmed_fused:
        emb.embed_batch(warm)
        emb.embed_batch(warm)

    t0 = time.perf_counter()
    runner.run_batch(n_workers=1)
    # drain the async dispatch queue before the stamp (same contract as
    # the raw leg): the last ticks' fused ingests may still be queued
    for node in runner.graph.nodes:
        idx = getattr(node.op, "index", None)
        if isinstance(idx, DeviceEmbeddingKnnIndex):
            idx.inner.drain()
    dt = time.perf_counter() - t0
    bridge = runner._scheduler.bridge_stats()
    G.clear()

    final = [row for _, row, _, diff in cap.events if diff > 0]
    assert final, "framework retrieval produced no output rows"
    reply = final[-1][0]
    matches = reply.value if hasattr(reply, "value") else reply
    assert matches, f"framework retrieval produced no matches: {reply!r}"
    from pathway_tpu.engine.device_bridge import device_inflight_from_env

    out = {
        "framework_docs_per_s": round(n_docs / dt, 1),
        "framework_n_docs": n_docs,
        "framework_ticks": n_ticks,
        # pipelined-execution instrumentation (engine/device_bridge.py):
        # legs > 0 proves the async path ran; overlap_ratio counts legs
        # that fully overlapped host work of later ticks. Same tolerant
        # parse as the runtime, so the label matches the mode measured.
        "framework_device_inflight": device_inflight_from_env(),
    }
    if bridge is not None:
        out["framework_bridge_legs"] = bridge["legs_resolved"]
        out["framework_bridge_overlap_ratio"] = round(
            bridge["overlap_ratio"], 3)
        out["framework_bridge_queue_wait_ms"] = bridge["queue_wait_ms"]
    try:
        # auto-jit tier counters for THIS run (internals/autojit.py):
        # fused programs, XLA bucket compiles, demotions, dispatch mix
        from pathway_tpu.internals.autojit import autojit_stats

        ajs = autojit_stats()
        out["framework_autojit_enabled"] = ajs["enabled"]
        out["framework_autojit_programs"] = ajs["programs"]
        out["framework_autojit_compiles"] = ajs["compiles"]
        out["framework_autojit_demotions"] = ajs["demotions"]
        out["framework_autojit_bucket_count"] = ajs["bucket_count"]
    except Exception:  # noqa: BLE001
        pass
    return out


def _make_framework_embedder(cls):
    """JaxEncoderEmbedder at the flagship shape: real BGE checkpoint when
    on disk, otherwise random weights at the exact BGE shape with the real
    WordPiece algorithm over a synthetic vocab (same policy as
    bench_embed). max_batch_size pins the per-dispatch shape so one
    compile serves the whole run."""
    import jax

    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.models.hf_loader import find_local_checkpoint
    from pathway_tpu.models.tokenizer import (WordPieceTokenizer,
                                              make_synthetic_vocab)

    if find_local_checkpoint("BAAI/bge-small-en-v1.5"):
        return cls(model="BAAI/bge-small-en-v1.5", max_len=SEQ,
                   max_batch_size=BATCH)
    config = EncoderConfig.bge_small()
    return cls(
        config=config,
        params=init_params(jax.random.PRNGKey(0), config),
        tokenizer=WordPieceTokenizer(
            make_synthetic_vocab([f"word{i}" for i in range(4096)],
                                 vocab_size=config.vocab_size),
            max_len=SEQ),
        max_len=SEQ, max_batch_size=BATCH)


def bench_serving() -> dict:
    """Serving-path SLO leg: the BASELINE ``knn_p50_e2e_ms`` measured as
    a *serving* latency for the first time.

    Queries enter through a real ``rest_connector`` (HTTP POST), ride
    the commit tick into ``query_as_of_now`` against a KNN index that is
    ingesting vectors CONCURRENTLY, and resolve back through the
    response writer. The request tracker (engine/request_tracker.py)
    stamps every hand-off, so the reported e2e quantiles come with the
    full per-stage decomposition (ingress wait / queue / host leg /
    device leg / response write) — the input signal for the PR-7
    latency-aware admission scheduler.
    """
    import threading
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.internals import dtype as dt
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.http import PathwayWebserver, rest_connector
    from pathway_tpu.io.python import ConnectorSubject
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )

    os.environ.setdefault("PATHWAY_FLIGHT_RECORDER", "1")  # tracker on
    G.clear()
    dim, n_vecs = SERVING_DIM, SERVING_N
    loaded = threading.Event()

    class IngestSubject(ConnectorSubject):
        """Bulk-load the slab, then keep trickling inserts so every
        timed query is answered under live ingest. Owns its generator —
        numpy Generators are not thread-safe, and this runs on the
        reader thread concurrently with the query thread's draws."""

        def run(self):
            rng = np.random.default_rng(1)
            chunk = 4096
            pushed = 0
            while pushed < n_vecs:
                m = min(chunk, n_vecs - pushed)
                for v in rng.random((m, dim), np.float32) * 2.0 - 1.0:
                    self.next(v=v)
                pushed += m
                if not self._session.sleep(0.002):
                    return
            loaded.set()
            while not self._session.stop_requested:
                for v in rng.random((64, dim), np.float32) * 2.0 - 1.0:
                    self.next(v=v)
                if not self._session.sleep(0.02):
                    return

    data = pw.io.python.read(
        IngestSubject(), schema=sch.schema_from_types(v=np.ndarray),
        autocommit_duration_ms=10, name="serving_ingest")
    index = default_brute_force_knn_document_index(
        data.v, data, dimensions=dim, reserved_space=n_vecs + (64 << 10),
        dtype="bfloat16")

    ws = PathwayWebserver(host="127.0.0.1", port=0)
    qschema = sch.schema_from_types(vec=dt.ANY, k=int)
    queries, writer = rest_connector(
        webserver=ws, route="/query", schema=qschema, methods=("POST",),
        delete_completed_queries=True, autocommit_duration_ms=5)
    qv = queries.select(
        qv=pw.apply(lambda v: np.asarray(v, dtype=np.float32),
                    queries.vec),
        k=queries.k)
    res = index.query_as_of_now(qv.qv, number_of_matches=qv.k)
    writer(res.select(
        n_matches=pw.apply(len, res._pw_index_reply_id)))

    errors: list[BaseException] = []

    def _run():
        try:
            pw.run()
        except Exception as e:  # noqa: BLE001 — reported in the leg JSON
            errors.append(e)

    th = threading.Thread(target=_run, daemon=True, name="bench-serving")
    th.start()
    try:
        deadline = time.monotonic() + 600.0
        rt = None
        while time.monotonic() < deadline and rt is None:
            live = list(_streaming._ACTIVE_RUNTIMES)
            if live and ws._started.is_set() and ws.port:
                rt = live[0]
            if errors:
                raise errors[0]
            time.sleep(0.05)
        assert rt is not None, "serving runtime never started"
        if not loaded.wait(timeout=max(60.0, deadline - time.monotonic())):
            raise TimeoutError(
                f"serving slab never finished loading ({n_vecs} vecs)")

        url = f"http://127.0.0.1:{ws.port}/query"

        def ask(vec) -> float:
            body = json.dumps({"vec": [float(x) for x in vec],
                               "k": 10}).encode()
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as resp:
                resp.read()
            return (time.perf_counter() - t0) * 1e3

        qvecs = np.random.default_rng(2).random(
            (SERVING_WARMUP + SERVING_QUERIES, dim),
            np.float32) * 2.0 - 1.0
        tracker = rt.recorder.requests
        for i in range(SERVING_WARMUP):  # compile + slab upload
            ask(qvecs[i])
        n_warm = tracker.count  # completions before the timed window
        client_ms = [ask(qvecs[SERVING_WARMUP + i])
                     for i in range(SERVING_QUERIES)]
        # count-based slice: the completed ring is bounded, so indexing
        # from its front would misalign once warmup spans are evicted —
        # take exactly the timed window's completions off the tail
        n_timed = tracker.count - n_warm
        spans = tracker.trace_spans()[-n_timed:] if n_timed else []
        assert spans, "no timed request spans completed"
        if len(spans) < n_timed:
            print(f"serving: completed-span ring kept {len(spans)} of "
                  f"{n_timed} timed spans (raise "
                  "PATHWAY_REQUEST_TRACE_SPANS for larger windows)",
                  flush=True)
        ingested = sum(
            st.get("insertions", 0)
            for nid, st in rt.scheduler.stats.items()
            if rt.runner.graph.nodes[nid].name == "serving_ingest")
    finally:
        _streaming.stop_all()
        th.join(15.0)
        G.clear()
    if errors:
        raise errors[0]

    e2e = np.array([r["e2e_ms"] for r in spans])
    # SLO accounting over the TIMED window only — the run-wide tracker
    # also counted the warmup queries (XLA compile, slab upload), which
    # would misstate the serving result in the headline fields
    over_budget = int(np.sum(e2e > tracker.slo_ms))
    out = {
        # exact quantiles over the timed window (warmup excluded)
        "knn_p50_e2e_ms": round(float(np.percentile(e2e, 50)), 2),
        "knn_p95_e2e_ms": round(float(np.percentile(e2e, 95)), 2),
        "knn_p99_e2e_ms": round(float(np.percentile(e2e, 99)), 2),
        "serving_client_p50_ms": round(float(np.percentile(client_ms, 50)),
                                       2),
        "serving_n_queries": len(spans),
        "serving_n_vectors": n_vecs,
        "serving_ingested_rows": int(ingested),
        "serving_dim": dim,
        "serving_slo_ms": tracker.slo_ms,
        "serving_slo_burn_rate": round(
            (over_budget / len(e2e)) / tracker.error_budget, 3),
        "serving_over_budget": over_budget,
    }
    from pathway_tpu.engine.request_tracker import STAGES

    for stage in STAGES:
        vals = np.array([r["stages"][stage] for r in spans])
        out[f"serving_stage_{stage}_p50_ms"] = round(
            float(np.percentile(vals, 50)), 3)
    return out


def _qos_serving_phase(qos_on: bool) -> dict:
    """One phase of the QoS before/after: a KNN index under HEAVY live
    ingest (large chunks per commit tick, so the device leg is dominated
    by maintenance work) serving closed-loop rest queries. Returns the
    phase's query quantiles, the ingest rate observed DURING the timed
    query window, and — QoS on — the controller's counters."""
    import concurrent.futures
    import threading
    import urllib.error
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.internals import dtype as dt
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.io.http import PathwayWebserver, rest_connector
    from pathway_tpu.io.python import ConnectorSubject
    from pathway_tpu.stdlib.indexing import (
        default_brute_force_knn_document_index,
    )

    os.environ["PATHWAY_FLIGHT_RECORDER"] = "1"
    os.environ.setdefault("PATHWAY_SLO_E2E_MS", "20")
    if qos_on:
        os.environ["PATHWAY_QOS"] = "1"
        # a small admission queue so the induced overload burst below
        # actually sheds (visible-shedding evidence, never silent)
        os.environ.setdefault("PATHWAY_QOS_ADMISSION_QUEUE", "8")
    else:
        os.environ["PATHWAY_QOS"] = "0"
    G.clear()
    dim, n_vecs, chunk = QOS_DIM, QOS_N, QOS_INGEST_CHUNK
    loaded = threading.Event()

    class HeavyIngest(ConnectorSubject):
        """Bulk-load the slab, then keep pushing LARGE chunks at a
        heavy-but-sustainable rate — big enough that an unbudgeted tick
        spends tens of ms on maintenance (queries blow the SLO), small
        enough that the engine can keep up (an overload beyond machine
        capacity grows the backlog without bound and measures nothing
        but the backlog)."""

        def run(self):
            rng = np.random.default_rng(7)
            pushed = 0
            while pushed < n_vecs:
                m = min(chunk, n_vecs - pushed)
                for v in rng.random((m, dim), np.float32) * 2.0 - 1.0:
                    self.next(v=v)
                pushed += m
                if not self._session.sleep(0.002):
                    return
            loaded.set()
            while not self._session.stop_requested:
                for v in rng.random((chunk, dim), np.float32) * 2.0 - 1.0:
                    self.next(v=v)
                if not self._session.sleep(QOS_INGEST_PERIOD_S):
                    return

    data = pw.io.python.read(
        HeavyIngest(), schema=sch.schema_from_types(v=np.ndarray),
        autocommit_duration_ms=QOS_COMMIT_MS, name="qos_ingest")
    index = default_brute_force_knn_document_index(
        data.v, data, dimensions=dim, reserved_space=n_vecs + (256 << 10))
    ws = PathwayWebserver(host="127.0.0.1", port=0)
    qschema = sch.schema_from_types(vec=dt.ANY, k=int)
    queries, writer = rest_connector(
        webserver=ws, route="/query", schema=qschema, methods=("POST",),
        delete_completed_queries=True,
        autocommit_duration_ms=QOS_COMMIT_MS)
    qv = queries.select(
        qv=pw.apply(lambda v: np.asarray(v, dtype=np.float32),
                    queries.vec),
        k=queries.k)
    res = index.query_as_of_now(qv.qv, number_of_matches=qv.k)
    writer(res.select(
        n_matches=pw.apply(len, res._pw_index_reply_id)))

    errors: list[BaseException] = []

    def _run():
        try:
            pw.run()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    th = threading.Thread(target=_run, daemon=True,
                          name=f"bench-qos-{'on' if qos_on else 'off'}")
    th.start()
    out: dict = {}
    try:
        deadline = time.monotonic() + 300.0
        rt = None
        while time.monotonic() < deadline and rt is None:
            live = list(_streaming._ACTIVE_RUNTIMES)
            if live and ws._started.is_set() and ws.port:
                rt = live[0]
            if errors:
                raise errors[0]
            time.sleep(0.05)
        assert rt is not None, "qos runtime never started"
        assert (rt.qos is not None) == qos_on
        if not loaded.wait(timeout=max(60.0,
                                       deadline - time.monotonic())):
            raise TimeoutError(f"qos slab never loaded ({n_vecs} vecs)")
        url = f"http://127.0.0.1:{ws.port}/query"

        def ask(vec, timeout=120.0, retries=8):
            body = json.dumps({"vec": [float(x) for x in vec],
                               "k": QOS_K}).encode()
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={"Content-Type": "application/json"})
            for _attempt in range(retries + 1):
                try:
                    with urllib.request.urlopen(req,
                                                timeout=timeout) as resp:
                        resp.read()
                    return
                except urllib.error.HTTPError as e:
                    e.read()
                    if e.code != 503 or _attempt == retries:
                        raise
                    # the shed contract: back off per Retry-After (capped
                    # — a closed-loop bench client is exactly who the
                    # hint is for)
                    try:
                        after = float(e.headers.get("Retry-After") or 1)
                    except ValueError:
                        after = 1.0
                    time.sleep(min(after, 1.0))

        def ingested_rows() -> int:
            return sum(
                st.get("insertions", 0)
                for nid, st in rt.scheduler.stats.items()
                if rt.runner.graph.nodes[nid].name == "qos_ingest")

        qvecs = np.random.default_rng(11).random(
            (QOS_WARMUP + QOS_QUERIES, dim), np.float32) * 2.0 - 1.0
        tracker = rt.recorder.requests
        for i in range(QOS_WARMUP):  # compile + slab upload
            ask(qvecs[i])
        # -- timed closed-loop window (sequential, under live ingest) ----
        n_warm = tracker.count
        rows0 = ingested_rows()
        t0 = time.perf_counter()
        for i in range(QOS_QUERIES):
            ask(qvecs[QOS_WARMUP + i])
        window_s = time.perf_counter() - t0
        rows1 = ingested_rows()
        n_timed = tracker.count - n_warm
        spans = tracker.trace_spans()[-n_timed:] if n_timed else []
        assert spans, "no timed qos request spans completed"
        e2e = np.array([r["e2e_ms"] for r in spans])
        tag = "on" if qos_on else "off"
        out[f"qos_{tag}_knn_p50_e2e_ms"] = round(
            float(np.percentile(e2e, 50)), 2)
        out[f"qos_{tag}_knn_p95_e2e_ms"] = round(
            float(np.percentile(e2e, 95)), 2)
        out[f"qos_{tag}_ingest_rate_rps"] = round(
            (rows1 - rows0) / max(window_s, 1e-9), 1)
        out[f"qos_{tag}_n_queries"] = len(spans)
        # -- induced overload: a concurrent burst past the queue cap -----
        def burst_one(i):
            """(got_503, retry_after_present) — summed on the main
            thread so concurrent increments cannot race."""
            try:
                ask(qvecs[i % len(qvecs)], timeout=60.0)
                return (0, False)
            except urllib.error.HTTPError as e:
                e.read()
                if e.code == 503:
                    return (1, bool(e.headers.get("Retry-After")))
                return (0, False)

        with concurrent.futures.ThreadPoolExecutor(
                max_workers=QOS_BURST) as pool:
            burst = list(pool.map(burst_one, range(QOS_BURST)))
        shed_503 = sum(b for b, _ra in burst)
        retry_after_seen = any(ra for _b, ra in burst)
        out[f"qos_{tag}_burst_503s"] = shed_503
        if qos_on:
            q = rt.qos.summary()
            out["qos_shed_total"] = q["shed_total"]
            out["qos_ingest_deferrals"] = q["ingest_deferrals"]
            out["qos_deferred_rows_total"] = q["deferred_rows_total"]
            out["qos_coalesced_dispatches"] = q["coalesced_dispatches"]
            out["qos_coalesced_queries"] = q["coalesced_queries"]
            out["qos_query_budget_ms"] = q["query_budget_ms"]
            assert retry_after_seen or shed_503 == 0, \
                "503 without Retry-After violates the shed contract"
    finally:
        _streaming.stop_all()
        th.join(15.0)
        G.clear()
        os.environ.pop("PATHWAY_QOS", None)
    if errors:
        raise errors[0]
    return out


def bench_qos() -> dict:
    """QoS before/after leg: the SAME heavy-ingest serving workload with
    the controller off, then on. The artifact shows the trade the
    ROADMAP item demands: QoS-on lowers query p50 (budgeted device time,
    admission control, coalescing) at the cost of measurably deferred
    ingest; QoS-off runs ingest at full rate while query latency blows
    out. Plus the shed evidence: the induced overload burst sheds
    visibly (503 + Retry-After + shed_total), never silently."""
    out = _qos_serving_phase(qos_on=False)
    out.update(_qos_serving_phase(qos_on=True))
    if out.get("qos_off_knn_p50_e2e_ms"):
        out["qos_p50_speedup"] = round(
            out["qos_off_knn_p50_e2e_ms"]
            / max(out["qos_on_knn_p50_e2e_ms"], 1e-9), 3)
    if out.get("qos_off_ingest_rate_rps"):
        out["qos_ingest_trade_ratio"] = round(
            out["qos_on_ingest_rate_rps"]
            / max(out["qos_off_ingest_rate_rps"], 1e-9), 3)
    return out


def _semantic_cache_phase(cache_on: bool) -> dict:
    """One phase of the semantic-cache before/after: a router-fronted
    single-member fleet (the _ReplicaFleet harness) under a Zipf query
    stream with the member's trickle ingest live. Cache-on enables BOTH
    layers — the operator cache in the serving process
    (PATHWAY_RESULT_CACHE) and the router's fleet cache on the query
    route (PATHWAY_ROUTER_CACHE_ROUTES) — because that is the shipped
    configuration; the router layer serves repeated bodies without
    touching the member, the operator layer serves repeated vectors
    without a kernel dispatch."""
    import http.client
    import tempfile
    import threading as _threading

    tag = "on" if cache_on else "off"
    prior = {k: os.environ.get(k)
             for k in ("PATHWAY_RESULT_CACHE",
                       "PATHWAY_ROUTER_CACHE_ROUTES")}
    os.environ["PATHWAY_RESULT_CACHE"] = "1" if cache_on else "0"
    if cache_on:
        os.environ["PATHWAY_ROUTER_CACHE_ROUTES"] = "/q"
    else:
        os.environ.pop("PATHWAY_ROUTER_CACHE_ROUTES", None)
    out: dict = {}
    try:
        with tempfile.TemporaryDirectory() as tmp:
            fleet = _ReplicaFleet(tmp, vecs=SEM_VECS,
                                  query_cost_ms=SEM_COST_MS)
            fleet.base_env["PATHWAY_RESULT_CACHE"] = \
                os.environ["PATHWAY_RESULT_CACHE"]
            fleet.base_env["REPLICA_BENCH_TRICKLE_S"] = str(SEM_TRICKLE_S)
            try:
                fleet.start_router()
                fleet.start_primary(register=True)
                ep = None
                deadline = time.monotonic() + 120.0
                while time.monotonic() < deadline and ep is None:
                    eps = [e for e in fleet.router.endpoints() if e.port]
                    ep = eps[0] if eps else None
                    time.sleep(0.05)
                assert ep is not None, "primary never registered"
                fleet._warm(ep)
                if cache_on:
                    # the watermark needs a version-carrying heartbeat
                    # before the router can serve (or fill) a single hit
                    deadline = time.monotonic() + 30.0
                    while time.monotonic() < deadline \
                            and fleet.router._fleet_watermark() is None:
                        time.sleep(0.05)
                    assert fleet.router._fleet_watermark() is not None, \
                        "index-version watermark never went live"
                # pre-encoded Zipf pool: identical bodies byte-for-byte,
                # which is exactly what the router cache keys on
                rng = np.random.default_rng(23)
                pool = rng.random((SEM_POOL, 16), np.float32) * 2 - 1
                bodies = [json.dumps({"vec": [float(x) for x in v],
                                      "k": 3}).encode() for v in pool]
                samples: list[tuple[float, float, bool]] = []
                lock = _threading.Lock()
                stop_at = time.monotonic() + SEM_WARMUP_S + SEM_SECONDS

                def client(seed: int):
                    crng = np.random.default_rng(1000 + seed)
                    while time.monotonic() < stop_at:
                        body = bodies[min(int(crng.zipf(SEM_ZIPF_S)) - 1,
                                          SEM_POOL - 1)]
                        t0 = time.monotonic()
                        ok = False
                        try:
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", fleet.router.port,
                                timeout=60)
                            try:
                                conn.request(
                                    "POST", "/q", body=body,
                                    headers={"Content-Type":
                                             "application/json"})
                                resp = conn.getresponse()
                                resp.read()
                                ok = resp.status == 200
                            finally:
                                conn.close()
                        except OSError:
                            ok = False
                        with lock:
                            samples.append(
                                (t0, (time.monotonic() - t0) * 1e3, ok))

                threads = [_threading.Thread(target=client, args=(i,),
                                             daemon=True)
                           for i in range(SEM_CLIENTS)]
                t_start = time.monotonic()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=SEM_WARMUP_S + SEM_SECONDS + 120)
                cut = t_start + SEM_WARMUP_S
                timed = [(t0, ms, ok) for t0, ms, ok in samples
                         if t0 >= cut]
                lat = sorted(ms for _t0, ms, ok in timed if ok)
                assert lat, f"semantic-cache {tag} phase served nothing"
                window_s = max(t0 for t0, _ms, _ok in timed) - cut
                out[f"semantic_cache_{tag}_served_qps"] = round(
                    len(lat) / max(window_s, 1e-9), 1)
                out[f"semantic_cache_{tag}_p95_ms"] = round(
                    float(np.percentile(lat, 95)), 3)
                out[f"semantic_cache_{tag}_p50_ms"] = round(
                    float(np.percentile(lat, 50)), 3)
                out[f"semantic_cache_{tag}_queries"] = len(lat)
                out[f"semantic_cache_{tag}_lost"] = sum(
                    1 for _t0, _ms, ok in samples if not ok)
                if cache_on:
                    rc = fleet.router.response_cache.stats()
                    total = rc["hits"] + rc["misses"]
                    out["semantic_cache_router_hit_rate"] = round(
                        rc["hits"] / max(total, 1), 4)
                    out["semantic_cache_router_invalidations"] = \
                        rc["invalidations"]
                    # operator-layer stats ride the last heartbeat
                    opstats = ep.result_cache or {}
                    out["semantic_cache_op_hit_ratio"] = \
                        opstats.get("hit_ratio")
                    out["semantic_cache_invalidations_per_tick"] = \
                        opstats.get("invalidations_per_tick")
            finally:
                fleet.stop()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def bench_semantic_cache() -> dict:
    """Semantic result-cache leg (engine/result_cache.py): the same
    Zipf-distributed query stream against the same router-fronted
    serving member, cache-off then cache-on. The artifact the ROADMAP
    item demands: served QPS up at equal-or-better p95 (router hits
    never touch the member; operator hits never touch the device),
    with the hit/invalidation economics — hit rates at both layers and
    the member's invalidations-per-tick under its live trickle ingest
    — in the same snapshot."""
    out = _semantic_cache_phase(cache_on=False)
    out.update(_semantic_cache_phase(cache_on=True))
    if out.get("semantic_cache_off_served_qps"):
        out["semantic_cache_qps_speedup"] = round(
            out["semantic_cache_on_served_qps"]
            / max(out["semantic_cache_off_served_qps"], 1e-9), 3)
    return out


def bench_etl(n_rows: int = 100_000) -> dict:
    """Streaming ETL rows/sec: WordCount + dimension join over 50 ticks
    (the reference's headline WordCount benchmark shape, README.md:244-250),
    at n_workers ∈ {1, 8}.

    Measured finding (updated r4): the columnar stateful path took 1w from
    ~38k to ~190k rows/s on this box — dictionary-encoded group keys +
    int64 array reducer state (ColumnarGroupByOperator), raw-value join
    keys, and native (C, Python-C-API) passes for the join bilinear update
    and the groupby gather/emit loops (native/fastjoin.cpp,
    native/fastgroup.cpp). True multi-process execution
    (engine/multiproc.py — columnar wire frames over tcp or same-host
    shared memory, PATHWAY_PROCESSES xT) is correctness-tested
    (tests/test_sharded.py, tests/test_cli.py) and has its own
    ``scaleout`` leg (bench_scaleout) measuring etl_scaleout_efficiency
    under the cores-vs-workers honesty rule; this leg's in-process
    n_workers figures measure sharded scheduling on one interpreter,
    where wall-clock scaling is unobservable on a 1-core container
    (etl_n_cores below).
    """
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    n_ticks, vocab = 50, 5000
    rng = np.random.default_rng(0)
    words = rng.integers(0, vocab, size=n_rows)
    qtys = rng.integers(1, 10, size=n_rows)
    ticks = np.sort(rng.integers(0, n_ticks, size=n_rows))

    def bench_exchange() -> dict:
        """Serialization microbench of the multiprocess exchange plane
        (engine/wire.py): bytes/row and enc+dec cost of the columnar wire
        format actually sent between cluster processes.

        Methodology note — the r04→r05 "regression" (1.453 → 6.495
        µs/row) was this microbench timing ONE encode+decode: decode
        allocates tens of thousands of objects, so whenever a
        generational GC pass (gen-2 scans the whole live heap, huge
        after the earlier bench legs) landed inside the single timed
        window the number exploded. Best-of-5 is immune to that class;
        the single-trial figure is still reported for contrast, and
        tests/test_exchange_perf.py pins the best-of-5 ≤ 3.0 absolute."""
        from pathway_tpu.engine import wire
        from pathway_tpu.internals.keys import hash_values

        n = min(20_000, n_rows)
        ents = [(hash_values("row", i), (f"w{words[i]}", int(qtys[i])), 1)
                for i in range(n)]
        payload = {"rows": {0: {0: ents}}, "wm": None, "bcast": None}
        trials = []
        blob = b""
        for _ in range(5):
            t0 = time.perf_counter()
            chunks, _total, _rows = wire.encode_frame(("x", 1, 0), payload)
            blob = b"".join(chunks)
            mid = time.perf_counter()
            wire.decode_frame(blob)
            trials.append((mid - t0, time.perf_counter() - mid))
        best = min(trials, key=sum)
        sums_us = [(e + d) / n * 1e6 for e, d in trials]
        return {
            "exchange_bytes_per_row": round(len(blob) / n, 1),
            "exchange_encode_us_per_row": round(best[0] / n * 1e6, 3),
            "exchange_decode_us_per_row": round(best[1] / n * 1e6, 3),
            "exchange_encdec_us_per_row": round(min(sums_us), 3),
            # the old (r05) methodology and the spread, kept so the
            # artifact itself shows why single-trial numbers were noise
            "exchange_encdec_us_per_row_single_trial": round(
                sums_us[0], 3),
            "exchange_encdec_us_per_row_worst": round(max(sums_us), 3),
        }

    def run_once(n_workers: int) -> tuple[float, int]:
        G.clear()

        class S(pw.Schema):
            word: str
            qty: int

        class L(pw.Schema):
            word: str
            cat: str

        events = table_from_rows(
            S, [(f"w{words[i]}", int(qtys[i]), int(ticks[i]) * 2, 1)
                for i in range(n_rows)], is_stream=True)
        lex = table_from_rows(
            L, [(f"w{i}", f"cat{i % 7}") for i in range(vocab)])
        counts = events.groupby(events.word).reduce(
            events.word, n=pw.reducers.count(),
            total=pw.reducers.sum(events.qty))
        joined = counts.join(lex, counts.word == lex.word).select(
            counts.word, counts.n, counts.total, lex.cat)
        runner = GraphRunner()
        runner.capture(joined)
        t0 = time.perf_counter()
        runner.run_batch(n_workers=n_workers)
        dt = time.perf_counter() - t0
        # coalesced BSP rounds a cluster would pay per tick (the batched
        # exchange groups per-node barriers by topological level)
        rounds = runner._scheduler.exchange_rounds_per_tick()
        G.clear()
        return n_rows / dt, rounds

    def run_windowed() -> float:
        """Tumbling-window aggregation throughput (temporal hot path:
        arithmetic window assignment + columnar groupby)."""
        G.clear()

        class S(pw.Schema):
            sensor: str
            v: int
            at: int

        at_col = np.sort(rng.integers(0, n_rows // 10, size=n_rows))
        t = table_from_rows(
            S, [(f"s{words[i] % 200}", int(qtys[i]), int(at_col[i]),
                 int(ticks[i]) * 2, 1) for i in range(n_rows)],
            is_stream=True)
        win = pw.temporal.windowby(
            t, t.at, window=pw.temporal.tumbling(100), instance=t.sensor,
        ).reduce(sensor=pw.this._pw_instance,
                 start=pw.this._pw_window_start,
                 s=pw.reducers.sum(pw.this.v), c=pw.reducers.count())
        runner = GraphRunner()
        runner.capture(win)
        t0 = time.perf_counter()
        runner.run_batch(n_workers=1)
        dt = time.perf_counter() - t0
        G.clear()
        return n_rows / dt

    cores = os.cpu_count() or 1
    r1, exchange_rounds = run_once(1)
    r8, _ = run_once(8)
    # honest scaling presentation: an 8-worker figure on fewer than 8
    # cores measures timesharing, not scaling — label it so (round-4
    # reviewer note), and report a per-core figure from a fit run
    fit_workers = min(8, cores)
    out = {
        "etl_rows_per_s_1w": round(r1, 0),
        "etl_rows_per_s_8w": round(r8, 0),
        "etl_8w_oversubscribed": cores < 8,
        "etl_windowed_rows_per_s": round(run_windowed(), 0),
        "etl_n_rows": n_rows,
        "etl_ticks": n_ticks,
        "etl_n_cores": cores,
        # cluster barrier count per tick AFTER coalescing (BSP rounds;
        # was = exchanged nodes before the batched exchange landed)
        "etl_exchange_rounds_per_tick": exchange_rounds,
        **bench_exchange(),
    }
    if fit_workers > 1:
        rN, _ = run_once(fit_workers) if fit_workers != 8 else (r8, 0)
        out[f"etl_rows_per_s_{fit_workers}w"] = round(rN, 0)
        out["etl_rows_per_s_per_core"] = round(rN / fit_workers, 0)
    else:
        out["etl_rows_per_s_per_core"] = round(r1, 0)
    return out


_SCALEOUT_PROGRAM = """
import json, os, sys, time
import numpy as np
import pathway_tpu as pw
from pathway_tpu.debug import table_from_rows
from pathway_tpu.engine.multiproc import get_cluster
from pathway_tpu.internals.runner import GraphRunner

n_rows = int(os.environ["BENCH_SCALEOUT_ROWS"])
n_ticks = int(os.environ["BENCH_SCALEOUT_TICKS"])
vocab = 5000
rng = np.random.default_rng(0)
words = rng.integers(0, vocab, size=n_rows)
qtys = rng.integers(1, 10, size=n_rows)
ticks = np.sort(rng.integers(0, n_ticks, size=n_rows))

class S(pw.Schema):
    word: str
    qty: int

class L(pw.Schema):
    word: str
    cat: str

events = table_from_rows(
    S, [(f"w{words[i]}", int(qtys[i]), int(ticks[i]) * 2, 1)
        for i in range(n_rows)], is_stream=True)
lex = table_from_rows(
    L, [(f"w{i}", f"cat{i % 7}") for i in range(vocab)])
counts = events.groupby(events.word).reduce(
    events.word, n=pw.reducers.count(),
    total=pw.reducers.sum(events.qty))
joined = counts.join(lex, counts.word == lex.word).select(
    counts.word, counts.n, counts.total, lex.cat)
runner = GraphRunner()
cap = runner.capture(joined)
cl = get_cluster()
t0 = time.perf_counter()
runner.run_batch(cluster=cl)
dt = time.perf_counter() - t0
events_out = sorted((int(k), repr(r), t, d)
                    for k, r, t, d in cap.consolidated_events())
doc = {
    "dt_s": dt,
    "events": events_out,
    "rounds_per_tick": runner._scheduler.exchange_rounds_per_tick(),
    "stats": cl.stats if cl is not None else None,
    "by_transport": cl.stats_by_transport if cl is not None else None,
    "transports": cl.transport_counts() if cl is not None else {},
}
with open(sys.argv[1], "w") as f:
    json.dump(doc, f)
"""


# -- auto-jit leg (CPU-runnable) --------------------------------------------
# Per-doc "embed" payload for the framework-vs-raw comparison: a jitted
# id-embedding + 2-layer MLP + L2 norm, calibrated into the flagship
# raw-kernel budget's band (BASELINE 15k docs/s/chip ~ 66 us/doc; these
# dims measure ~57 us/doc on this container's CPU) so the ratio gates the
# SAME regime the round-5 verdict's framework-vs-raw gap (#5) was seen in.
# A near-zero payload would gate pure dispatch overhead (a regime the real
# pipeline never runs in); an oversized one would hide any framework tax —
# the per-stage breakdown below keeps the tax itself visible either way.
AUTOJIT_DOCS = int(os.environ.get("BENCH_AUTOJIT_DOCS", 16 * 2048))
AUTOJIT_TICK = 2048
_AUTOJIT_VOCAB, _AUTOJIT_EMB, _AUTOJIT_H1, _AUTOJIT_H2 = \
    4096, 768, 1536, 1280


def _autojit_payload():
    """(embed_fn(ids int32[n]) -> float64[n], params) — the jitted raw
    kernel both sides of the comparison dispatch per tick."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(12)
    params = tuple(
        np.asarray(rng.standard_normal(s), np.float32) / np.sqrt(s[0])
        for s in ((_AUTOJIT_VOCAB, _AUTOJIT_EMB),
                  (_AUTOJIT_EMB, _AUTOJIT_H1), (_AUTOJIT_H1, _AUTOJIT_H2)))

    @jax.jit
    def fwd(ids, emb, w1, w2):
        h = jnp.tanh(emb[ids] @ w1)
        o = h @ w2
        return jnp.sqrt((o * o).sum(axis=1))

    def embed(ids: np.ndarray) -> np.ndarray:
        return np.asarray(fwd(jnp.asarray(ids), *params), np.float64)

    return embed


def bench_autojit(n_docs: int | None = None) -> dict:
    """Framework-vs-raw on CPU: the SAME doc-scoring pipeline measured as
    (a) raw kernels + a thin hand-written loop, (b) the Table path with
    auto-jit ON, (c) the Table path with auto-jit OFF (today's behavior).

    The pipeline carries every workload class the auto-jit tier targets:
    a chain of traceable/vmappable scalar UDFs (fused into one dispatch;
    interpreted per-row when OFF), a host-only UDF (split out and stepped
    on the host thread while the device leg is in flight, WindVE-style),
    and a batch device UDF payload (the jitted embed kernel) riding the
    pipelined bridge. The raw comparator dispatches the IDENTICAL jitted
    kernel and vectorized numpy score math per tick, with the host-only
    formatting as a plain Python loop — i.e. what a user would hand-write
    without the framework, including the row<->column conversions both
    sides must do.

    ``framework_vs_raw_ratio`` (round-5 verdict #5, target >= 0.85) is the ON
    ratio; ``framework_vs_raw_ratio_nojit`` reproduces today's gap in the
    same artifact. Per-stage flight-recorder breakdowns for both modes
    ship inline (`autojit_stage_breakdown`) and as a standalone artifact
    when ``BENCH_AUTOJIT_TRACE_ARTIFACT`` names a path — the "where the
    Table-path tax went" evidence the ROADMAP asks for. Best-of-3 per
    mode: single-trial numbers on shared CI runners catch GC pauses and
    neighbor load (the r05 encdec lesson).
    """
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals import autojit
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    if n_docs is None:
        n_docs = AUTOJIT_DOCS
    n_docs -= n_docs % AUTOJIT_TICK
    n_ticks = n_docs // AUTOJIT_TICK
    embed_kernel = _autojit_payload()

    # the scoring chain: six sync scalar UDFs spanning every class the
    # tier compiles (jit-traceable int/conditional float -> XLA group;
    # compounding-float / math.sqrt / integer-division bodies -> numpy
    # group) — interpreted per row per UDF when auto-jit is off, exactly
    # the per-doc host tax the real framework leg pays around its
    # embedder (parse/split/metadata UDFs)
    import math

    @pw.udf
    def boost(x: int) -> int:
        return x * 3 + 7

    @pw.udf
    def gate(y: float) -> float:
        return y if y < 0.75 else 0.75

    @pw.udf
    def mix(x: int, y: float) -> float:
        return x * 0.0001 + y * 0.5

    @pw.udf
    def norm(y: float) -> float:
        return math.sqrt(y) + 1.0

    @pw.udf
    def damp(y: float) -> float:
        return y * 0.5 + 0.25

    @pw.udf
    def step(x: int) -> int:
        return (x % 7) + (x // 3)

    @pw.udf(deterministic=True)
    def tag(x: int) -> str:
        return f"doc-{x % 97}"

    @pw.udf(batch=True, device=True, deterministic=True, return_type=float)
    def embed(xs):
        ids = np.asarray(xs, np.int64) % _AUTOJIT_VOCAB
        return embed_kernel(ids.astype(np.int32)).tolist()

    rng = np.random.default_rng(3)
    xs = rng.integers(0, 1_000_000, size=n_docs)
    ys = rng.random(size=n_docs)
    rows = [(int(x), float(y), i // AUTOJIT_TICK, 1)
            for i, (x, y) in enumerate(zip(xs, ys))]
    schema = sch.schema_from_types(x=int, y=float)

    def run_framework() -> tuple[float, list, dict, dict]:
        G.clear()
        autojit.reset_stats()
        t = table_from_rows(schema, rows, is_stream=True)
        t1 = t.select(sb=boost(t.x), sg=gate(t.y), sm=mix(t.x, t.y),
                      sn=norm(t.y), sd=damp(t.y), st=step(t.x),
                      tg=tag(t.x))
        t2 = t1.select(emb=embed(t1.sb), tg=t1.tg, sg=t1.sg, sm=t1.sm,
                       sn=t1.sn, sd=t1.sd, st=t1.st)
        runner = GraphRunner()
        cap = runner.capture(t2)
        # first-tick compiles belong in warmup, not the timed window:
        # walk the fused programs' bucket ladders (satellite contract —
        # pw.warmup after building the runner) and prime the embed kernel
        # at the tick shape
        warm = pw.warmup()
        embed_kernel(np.zeros(AUTOJIT_TICK, np.int32))
        rec = FlightRecorder()
        rec.enabled = True
        t0 = time.perf_counter()
        runner.run_batch(n_workers=1, recorder=rec)
        dt = time.perf_counter() - t0
        bridge = runner._scheduler.bridge_stats()
        stages = [
            {"op": s["name"], "op_class": s["op_class"],
             "ms": round(s["sum_ms"], 1), "steps": s["count"],
             "rows_in": s["rows_in"]}
            for s in sorted(rec.op_stats(), key=lambda s: -s["sum_ms"])]
        out_rows = [r for _, r, _, d in cap.events if d > 0]
        G.clear()
        meta = {
            "bridge": bridge,
            "warmup_autojit_compiles": sum(
                1 for kind, _ in warm["compiled"] if kind == "autojit"),
            "stats": autojit.autojit_stats(),
        }
        return dt, out_rows, meta, {"stages": stages}

    def run_raw() -> tuple[float, list]:
        t0 = time.perf_counter()
        out = []
        for tk in range(n_ticks):
            lo = tk * AUTOJIT_TICK
            chunk = rows[lo:lo + AUTOJIT_TICK]
            xa = np.fromiter((r[0] for r in chunk), np.int64, len(chunk))
            ya = np.fromiter((r[1] for r in chunk), np.float64, len(chunk))
            sb = xa * 3 + 7
            sg = np.minimum(ya, 0.75)
            sm = xa * 0.0001 + ya * 0.5
            sn = np.sqrt(ya) + 1.0
            sd = ya * 0.5 + 0.25
            st = (xa % 7) + (xa // 3)
            tg = [f"doc-{int(v) % 97}" for v in xa.tolist()]
            emb = embed_kernel((sb % _AUTOJIT_VOCAB).astype(np.int32))
            out.extend(zip(emb.tolist(), tg, sg.tolist(), sm.tolist(),
                           sn.tolist(), sd.tolist(), st.tolist()))
        dt = time.perf_counter() - t0
        return dt, out

    prev = os.environ.get("PATHWAY_AUTO_JIT")
    try:
        # wake the jit once outside every timed window
        embed_kernel(np.zeros(AUTOJIT_TICK, np.int32))
        # INTERLEAVED best-of-3 (the r05 lesson, round 2): the three modes
        # run round-robin so a neighbor-load / GC episode on a shared
        # runner lands on all of them, not on whichever phase it straddles
        # — phase-sequential trials measured ratio swings of ±0.3 on this
        # container with an unchanged binary
        raw_best = on_best = off_best = None
        for _ in range(3):
            trial = run_raw()
            if raw_best is None or trial[0] < raw_best[0]:
                raw_best = trial
            os.environ["PATHWAY_AUTO_JIT"] = "1"
            trial = run_framework()
            if on_best is None or trial[0] < on_best[0]:
                on_best = trial
            os.environ["PATHWAY_AUTO_JIT"] = "0"
            trial = run_framework()
            if off_best is None or trial[0] < off_best[0]:
                off_best = trial
            if prev is None:
                os.environ.pop("PATHWAY_AUTO_JIT", None)
            else:
                os.environ["PATHWAY_AUTO_JIT"] = prev
        raw_dt, raw_out = raw_best
        on_dt, on_rows, on_meta, on_stages = on_best
        off_dt, off_rows, off_meta, off_stages = off_best
    finally:
        if prev is None:
            os.environ.pop("PATHWAY_AUTO_JIT", None)
        else:
            os.environ["PATHWAY_AUTO_JIT"] = prev

    # byte-identity across all three paths is part of the leg's contract:
    # a fast-but-wrong fused tier must fail the bench, not ship a number
    # (sorted: the source's consolidation pass may reorder within a tick)
    assert sorted(on_rows) == sorted(off_rows), \
        "auto-jit changed the framework output"
    assert sorted(on_rows) == sorted(raw_out), \
        "framework output diverged from the raw comparator"

    on_stats = on_meta["stats"]
    out = {
        "autojit_n_docs": n_docs,
        "autojit_raw_docs_per_s": round(n_docs / raw_dt, 1),
        "autojit_framework_docs_per_s": round(n_docs / on_dt, 1),
        "autojit_framework_docs_per_s_nojit": round(n_docs / off_dt, 1),
        "framework_vs_raw_ratio": round(raw_dt / on_dt, 3),
        "framework_vs_raw_ratio_nojit": round(raw_dt / off_dt, 3),
        "autojit_programs": on_stats["programs"],
        "autojit_compiles": on_stats["compiles"],
        "autojit_demotions": on_stats["demotions"],
        "autojit_bucket_count": on_stats["bucket_count"],
        "autojit_device_dispatches": on_stats["device_dispatches"],
        "autojit_vector_dispatches": on_stats["vector_dispatches"],
        "autojit_fallback_batches": on_stats["fallback_batches"],
        "autojit_warmup_compiles": on_meta["warmup_autojit_compiles"],
        "autojit_bridge_overlap_ratio": round(
            on_meta["bridge"]["overlap_ratio"], 3)
        if on_meta["bridge"] else None,
        "autojit_stage_breakdown": {
            "on": on_stages["stages"][:8], "off": off_stages["stages"][:8]},
    }
    trace_path = os.environ.get("BENCH_AUTOJIT_TRACE_ARTIFACT")
    if trace_path:
        from pathway_tpu.engine.flight_recorder import atomic_write_json

        atomic_write_json(trace_path, {
            "leg": "autojit", "n_docs": n_docs,
            "summary": {k: v for k, v in out.items()
                        if k != "autojit_stage_breakdown"},
            "per_stage_ms": {"on": on_stages["stages"],
                             "off": off_stages["stages"]},
        })
    return out


def bench_scaleout() -> dict:
    """Honest multi-worker scale-out leg: the WordCount+join ETL pipeline
    run as ONE process and as FOUR OS processes (SPMD cluster,
    engine/multiproc.py) over both transports, reporting

    * ``etl_scaleout_efficiency`` = (4-process rate / 1-process rate) /
      min(4, cores) — the cores-vs-workers honesty rule from bench_etl: on
      fewer than 4 cores the 4-process figure measures timesharing, so
      the denominator only credits cores that exist and
      ``scaleout_oversubscribed`` flags the run (CI gates ≥ 0.7 only on
      ≥ 4-core runners — tests/scaleout_canary.py);
    * byte-identity: the union of the 4 shards' consolidated outputs must
      equal the 1-process events exactly, per transport;
    * per-transport exchange cost from the live cluster counters (the
      same numbers /metrics exports as pathway_tpu_exchange_*{transport=}).
    """
    import subprocess
    import sys as _sys
    import tempfile

    n_rows = int(os.environ.get("BENCH_SCALEOUT_ROWS", 100_000))
    n_ticks = int(os.environ.get("BENCH_SCALEOUT_TICKS", 20))
    first_port = int(os.environ.get("BENCH_SCALEOUT_PORT", 19600))
    workers = 4
    cores = os.cpu_count() or 1

    tmp = tempfile.mkdtemp(prefix="bench_scaleout_")
    prog = os.path.join(tmp, "scaleout_prog.py")
    with open(prog, "w") as f:
        f.write(_SCALEOUT_PROGRAM)
    # several processes on one host: a chip belongs to one process, and
    # this leg measures the host-only exchange plane — children stay on
    # the CPU backend so none of them claims the chip
    base_env = dict(os.environ, JAX_PLATFORMS="cpu",
                    BENCH_SCALEOUT_ROWS=str(n_rows),
                    BENCH_SCALEOUT_TICKS=str(n_ticks))
    base_env.setdefault("PYTHONPATH", os.path.dirname(
        os.path.abspath(__file__)))

    def run_procs(n: int, port: int, transport: str) -> list[dict]:
        handles = []
        for pid in range(n):
            env = dict(base_env, PATHWAY_PROCESSES=str(n),
                       PATHWAY_PROCESS_ID=str(pid), PATHWAY_THREADS="1",
                       PATHWAY_FIRST_PORT=str(port),
                       PATHWAY_RUN_ID=f"scaleout-{transport}",
                       PATHWAY_EXCHANGE_TRANSPORT=transport)
            out_path = os.path.join(tmp, f"out_{transport}_{n}_{pid}")
            handles.append((out_path, subprocess.Popen(
                [_sys.executable, prog, out_path], env=env,
                stderr=subprocess.PIPE, text=True)))
        docs = []
        try:
            for out_path, h in handles:
                _, err = h.communicate(timeout=600)
                if h.returncode != 0:
                    raise RuntimeError(
                        f"scaleout child failed (rc={h.returncode}): "
                        f"{err[-500:]}")
                with open(out_path) as f:
                    docs.append(json.load(f))
        except BaseException:
            # one child failing/timing out must not orphan its siblings:
            # bench's main() absorbs this error and runs more legs, and a
            # leaked 4-process cluster spins in exchange retries (recv
            # timeout 300 s), distorting every later timing in the artifact
            # and squatting on the ports for the next transport's run.
            for _, h in handles:
                if h.poll() is None:
                    h.kill()
            for _, h in handles:
                try:
                    h.communicate(timeout=10)
                except Exception:
                    pass
            raise
        return docs

    [single] = run_procs(1, first_port, "tcp")
    rate_1p = n_rows / single["dt_s"]
    out: dict = {
        "scaleout_rows": n_rows,
        "scaleout_ticks": n_ticks,
        "scaleout_workers": workers,
        "scaleout_n_cores": cores,
        "scaleout_oversubscribed": cores < workers,
        "scaleout_rows_per_s_1p": round(rate_1p, 0),
        "scaleout_rounds_per_tick": single["rounds_per_tick"],
    }
    expect = sorted(map(tuple, single["events"]))
    best_rate, best_transport = 0.0, None
    for transport in ("shm", "tcp"):
        docs = run_procs(workers, first_port + 20
                         + (0 if transport == "shm" else 20), transport)
        # collective run: the slowest process bounds the wall-clock
        rate = n_rows / max(d["dt_s"] for d in docs)
        merged = sorted(tuple(e) for d in docs for e in d["events"])
        identical = merged == expect
        used = {t for d in docs for t in d["transports"]}
        st = docs[0]["stats"]
        t_st = docs[0]["by_transport"][transport]
        enc_us = (t_st["encode_s"] * 1e6 / t_st["rows_out"]
                  if t_st["rows_out"] else 0.0)
        dec_us = (t_st["decode_s"] * 1e6 / t_st["rows_in"]
                  if t_st["rows_in"] else 0.0)
        out.update({
            f"scaleout_rows_per_s_4p_{transport}": round(rate, 0),
            f"scaleout_identical_{transport}": identical,
            f"scaleout_transport_used_{transport}": sorted(used),
            f"scaleout_exchange_encode_us_per_row_{transport}": round(
                enc_us, 3),
            f"scaleout_exchange_decode_us_per_row_{transport}": round(
                dec_us, 3),
            f"scaleout_exchange_rounds_{transport}": st["rounds"],
        })
        if transport == "shm":
            out["scaleout_shm_slab_bytes"] = (st["shm_bytes_out"]
                                              + st["shm_bytes_in"])
        if identical and rate > best_rate:
            best_rate, best_transport = rate, transport
    if best_transport is not None:
        out["etl_scaleout_efficiency"] = round(
            (best_rate / rate_1p) / min(workers, cores), 3)
        out["scaleout_best_transport"] = best_transport
    return out


def _dispatch_floor_ms() -> float:
    """Per-dispatch host↔device overhead — measured so the reported e2e
    numbers are interpretable."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def trivial(x):
        return x + 1.0

    x = jnp.zeros((8, 8), jnp.float32)
    np.asarray(trivial(x))
    lat = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(trivial(x))
        lat.append((time.perf_counter() - t0) * 1e3)
    return float(np.percentile(lat, 50))


def _bench_knn_int8(n, gen, chunk, queries, bf16_top) -> dict:
    """int8-slab leg (half of bf16's bytes): p50 at the same scale, plus
    an overlap@10 probe vs the bf16 results over IDENTICAL vectors (the
    generator chunks are re-created from the same PRNG keys)."""
    import gc

    import jax

    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    gc.collect()
    index = BruteForceKnnIndex(KNN_DIM, reserved_space=n,
                               metric=KnnMetric.COS, dtype="int8")
    for ci, base in enumerate(range(0, n, chunk)):
        m = min(chunk, n - base)
        vecs = gen(jax.random.PRNGKey(ci))
        index.add_batch_device(
            [Pointer(base + i) for i in range(m)], vecs[:m])
    res = index.search([(Pointer(10**9 + i), queries[i], 10, None)
                        for i in range(8)])
    overlap = float(np.mean(
        [len(set(k for k, _ in res[i]) & set(bf16_top[i])) / 10.0
         for i in range(8)]))
    p50 = index.latency_probe(batch_size=1, k=10, reps=64)
    b64 = index.latency_probe(batch_size=64, k=10, reps=16)
    del index
    gc.collect()
    return {
        "knn_int8_p50_ms": round(p50, 2),
        "knn_int8_batch64_ms": round(b64, 2),
        "knn_int8_overlap10_vs_bf16": round(overlap, 3),
    }


def bench_durability() -> dict:
    """Checkpoint cadence vs pipeline depth (resolved-prefix commit
    watermark, engine/device_bridge.py + engine/persistence.py).

    Runs one paced streaming graph — python connector → device-leg batch
    UDF (a fixed per-leg device stand-in delay on CPU; the mechanics
    under test are the bridge/commit interactions, not kernel speed) →
    groupby — three ways: inflight=4 with persistence ON, inflight=4
    with persistence OFF, inflight=1 with persistence ON. Reports the
    bridge overlap ratio of each plus ticks-per-commit and watermark lag,
    so the acceptance bar "persistence-on overlap within 10% of
    persistence-off at inflight=4" is a captured number, not a claim.
    """
    import tempfile

    import pathway_tpu as pw
    from pathway_tpu.engine.streaming import StreamingRuntime
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    n_rows = int(os.environ.get("BENCH_DURABILITY_ROWS", 40))
    leg_ms = float(os.environ.get("BENCH_DURABILITY_LEG_MS", 20.0))

    def run_once(inflight: int, persist_dir: str | None) -> dict:
        os.environ["PATHWAY_DEVICE_INFLIGHT"] = str(inflight)
        G.clear()

        @pw.udf(batch=True, device=True, deterministic=True,
                return_type=int)
        def dev_score(qty: list) -> list:
            time.sleep(leg_ms / 1e3)
            return [int(q) * 2 for q in qty]

        class _Feed(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(n_rows):
                    time.sleep(0.004)
                    self.next(item=f"i{i % 5}", qty=1 + i % 3)

        t = pw.io.python.read(
            _Feed(), schema=pw.schema_from_types(item=str, qty=int),
            autocommit_duration_ms=10, persistent_id="bench-durability")
        t = t.select(item=t.item, score=dev_score(t.qty))
        agg = t.groupby(t.item).reduce(item=t.item,
                                       s=pw.reducers.sum(t.score))
        pw.io.subscribe(agg, lambda *a, **k: None)
        cfg = None
        if persist_dir is not None:
            cfg = pw.persistence.Config.simple_config(
                pw.persistence.Backend.filesystem(persist_dir))
        runner = GraphRunner()
        for binder in G.output_binders:
            binder(runner)
        rt = StreamingRuntime(runner, persistence_config=cfg)
        t0 = time.perf_counter()
        rt.run()
        wall_s = time.perf_counter() - t0
        bridge = rt.scheduler.bridge_stats() or {}
        pstats = rt.persistence.stats() if rt.persistence else {}
        G.clear()
        return {"wall_s": wall_s, "bridge": bridge, "pstats": pstats}

    out: dict = {}
    prior_inflight = os.environ.get("PATHWAY_DEVICE_INFLIGHT")
    try:
        with tempfile.TemporaryDirectory() as td:
            p4 = run_once(4, os.path.join(td, "p4"))
            nop4 = run_once(4, None)
            p1 = run_once(1, os.path.join(td, "p1"))
    finally:
        # later legs (and the device-phase child env) must see the
        # caller's pipelining depth, not this leg's last override
        if prior_inflight is None:
            os.environ.pop("PATHWAY_DEVICE_INFLIGHT", None)
        else:
            os.environ["PATHWAY_DEVICE_INFLIGHT"] = prior_inflight
    out["durability_overlap_inflight4_persist"] = round(
        p4["bridge"].get("overlap_ratio", 0.0), 3)
    out["durability_overlap_inflight4_nopersist"] = round(
        nop4["bridge"].get("overlap_ratio", 0.0), 3)
    out["durability_bridge_max_depth_persist"] = \
        p4["bridge"].get("max_depth", 0)
    for tag, leg in (("inflight4", p4), ("inflight1", p1)):
        ps = leg["pstats"]
        commits = max(1, ps.get("commits_with_data", 0))
        out[f"durability_commits_{tag}"] = ps.get("commits_with_data", 0)
        out[f"durability_ticks_per_commit_{tag}"] = round(
            ps.get("watermark", 0) / commits, 2)
        out[f"durability_wall_s_{tag}"] = round(leg["wall_s"], 3)
    out["durability_watermark_lag_ticks"] = p4["pstats"].get(
        "lag_ticks", 0)
    return out


def bench_recovery() -> dict:
    """Bounded-time crash recovery (PR 10): restart wall-clock vs history
    size, WAL-only vs snapshot+suffix (engine/persistence.py operator-state
    snapshots + compaction).

    For each history size H: synthesize a WAL of H rows directly through
    the durable log API (the on-disk format a real run writes), then
    measure a restart three ways — (1) full-WAL replay, (2) one more
    replay with snapshots ON (its teardown writes the generation and
    compacts), (3) the snapshot-restored restart. WAL-only restart grows
    linearly with H; the snapshot restart must stay ~flat: the acceptance
    bar is restart(100k) <= 2x restart(1k) with snapshots on, reported as
    ``recovery_snapshot_ratio_maxmin``.
    """
    import tempfile

    import pathway_tpu as pw
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.internals.parse_graph import G

    sizes = [int(s) for s in os.environ.get(
        "BENCH_RECOVERY_ROWS", "1000,10000,100000").split(",")]
    chunk = 500  # rows per WAL record (one commit's worth)

    class _Closed(pw.io.python.ConnectorSubject):
        def run(self):
            return  # nothing live: the restart is pure recovery

    def run_restart(pdir: str) -> float:
        G.clear()
        t = pw.io.python.read(
            _Closed(), schema=pw.schema_from_types(word=str),
            autocommit_duration_ms=10, persistent_id="bench-recovery")
        counts = t.groupby(t.word).reduce(word=t.word,
                                          c=pw.reducers.count())
        pw.io.subscribe(counts, lambda *a, **k: None)
        cfg = pw.persistence.Config.simple_config(
            pw.persistence.Backend.filesystem(pdir))
        t0 = time.perf_counter()
        pw.run(persistence_config=cfg)
        wall = time.perf_counter() - t0
        G.clear()
        return wall

    out: dict = {}
    prior = {k: os.environ.get(k) for k in
             ("PATHWAY_SNAPSHOT_EVERY_TICKS", "PATHWAY_DEVICE_INFLIGHT")}
    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "1"
    snap_restarts: dict[int, float] = {}
    try:
        for n in sizes:
            with tempfile.TemporaryDirectory() as td:
                pdir = os.path.join(td, "p")
                driver = PersistenceDriver(
                    pw.persistence.Config.simple_config(
                        pw.persistence.Backend.filesystem(pdir)))
                log = driver._log_for("bench-recovery")
                # fixed 1000-word vocabulary at every history size: the
                # aggregation STATE stays constant while the input log
                # grows — exactly the regime where an input-WAL restart
                # is O(stream age) and a state snapshot is O(state)
                tick = 0
                for base in range(0, n, chunk):
                    tick += 1
                    log.append(tick, [
                        (Pointer(i), (f"w{i % 1000}",), 1, None)
                        for i in range(base, min(base + chunk, n))])
                log.close()
                os.environ.pop("PATHWAY_SNAPSHOT_EVERY_TICKS", None)
                # min of two: first-run import/compile noise must not
                # masquerade as replay cost (both restarts are pure
                # recovery over the identical root)
                wal_s = min(run_restart(pdir), run_restart(pdir))
                # snapshot-prep replay: teardown writes the generation
                # covering the whole history and compacts the WAL
                os.environ["PATHWAY_SNAPSHOT_EVERY_TICKS"] = "1000000000"
                run_restart(pdir)
                snap_s = min(run_restart(pdir), run_restart(pdir))
                out[f"recovery_walonly_restart_s_{n}"] = round(wal_s, 3)
                out[f"recovery_snapshot_restart_s_{n}"] = round(snap_s, 3)
                snap_restarts[n] = snap_s
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if snap_restarts:
        lo, hi = min(sizes), max(sizes)
        out["recovery_snapshot_ratio_maxmin"] = round(
            snap_restarts[hi] / max(snap_restarts[lo], 1e-9), 3)
    return out


_REPLICA_PROGRAM = """
# One member of the replica-fleet bench/canary (bench_replica): the
# SAME KNN-serving program run as the PRIMARY (ingests the seeded vector
# feed under persistence, then trickles so staleness stays a live
# number) or as a READ REPLICA (PATHWAY_REPLICA_OF, hydrates + tails;
# registers with the router through PATHWAY_ROUTER_CONTROL). A fixed
# per-query sleep in the post-KNN UDF stands in for per-query device
# cost (rerank/fetch): the router's load spreading is only measurable
# if a query COSTS something, and a sleep costs wall-clock without
# needing a core — so the 1-vs-2-replica p95 drop is honest even on a
# 1-core runner.
import json, os, sys, threading, time
import numpy as np
import pathway_tpu as pw
from pathway_tpu.engine import streaming as _streaming
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import schema as sch
from pathway_tpu.io.http import PathwayWebserver, rest_connector
from pathway_tpu.stdlib.indexing import (
    default_brute_force_knn_document_index)

DIM = 16
ROLE = os.environ["REPLICA_BENCH_ROLE"]
ROOT = os.environ["REPLICA_BENCH_ROOT"]
N = int(os.environ.get("REPLICA_BENCH_VECS", "256"))
COST_MS = float(os.environ.get("REPLICA_BENCH_QUERY_COST_MS", "4"))
# trickle cadence: how often a fresh vector lands after the seed load.
# The semantic-cache leg stretches this (ingest stays LIVE, but the
# index-version watermark holds long enough for router fills to commit
# — a fill is discarded when the watermark moves mid-forward)
TRICKLE_S = float(os.environ.get("REPLICA_BENCH_TRICKLE_S", "0.5"))
READY = os.environ.get("REPLICA_BENCH_READY_FILE")
# fleet-observability mode (tests/fleet_trace_canary.py): each process
# runs its monitoring HTTP server (ephemeral port, announced over the
# control-channel heartbeat) so the router can scrape /metrics and
# /trace?format=chrome for the /fleet/* surfaces
HTTP = os.environ.get("REPLICA_BENCH_HTTP") == "1"
# write-path mode (tests/failover_canary.py): a durable-ack /w route on
# every member — the primary serves it, replicas tail its WAL so a
# promoted replica owns the full write history
WRITES = os.environ.get("REPLICA_BENCH_WRITES") == "1"
# crash-mid-promotion mode: die (rc 3) INSIDE the promotion, after the
# epoch bump but before connector readers start — the router must
# re-elect a survivor
if os.environ.get("REPLICA_BENCH_PROMOTE_CRASH") == "1":
    from pathway_tpu.testing import faults as _faults
    _faults.arm_point("replica.promote.crash",
                      lambda _p, _c: os._exit(3))


class Subject(pw.io.python.ConnectorSubject):
    def run(self):
        rng = np.random.default_rng(11)
        for i in range(N):
            self.next(v=rng.random(DIM, np.float32) * 2 - 1)
            if i % 32 == 31 and not self._session.sleep(0.05):
                return
        while True:  # trickle: keep the WAL (and staleness) live
            if not self._session.sleep(TRICKLE_S):
                return
            self.next(v=rng.random(DIM, np.float32) * 2 - 1)


ws = PathwayWebserver(host="127.0.0.1", port=0)
data = pw.io.python.read(
    Subject(), schema=sch.schema_from_types(v=np.ndarray),
    autocommit_duration_ms=25, name="vecs", persistent_id="vecs")
index = default_brute_force_knn_document_index(
    data.v, data, dimensions=DIM, reserved_space=4096)
qschema = sch.schema_from_types(vec=dt.ANY, k=int)
queries, writer = rest_connector(
    webserver=ws, route="/q", schema=qschema, methods=("POST",),
    delete_completed_queries=True, autocommit_duration_ms=10)
qv = queries.select(
    qv=pw.apply(lambda v: np.asarray(v, dtype=np.float32), queries.vec),
    k=queries.k)
res = index.query_as_of_now(qv.qv, number_of_matches=qv.k)


def _ids(ids):
    time.sleep(COST_MS / 1e3)  # the per-query device-cost stand-in
    return [str(i) for i in ids]


writer(res.select(
    ids=pw.apply(_ids, res._pw_index_reply_id),
    scores=pw.apply(lambda ds: [float(d) for d in ds],
                    res._pw_index_reply_score)))

if WRITES:
    # the write path: durable-ack ingestion with an IDEMPOTENT aggregate
    # (key -> max value), so a client retrying an un-acked POST after
    # failover cannot corrupt state — the 200 means the row is fsynced
    # in the primary root's WAL
    wrows, wack = rest_connector(
        webserver=ws, route="/w",
        schema=sch.schema_from_types(wkey=str, wval=int),
        methods=("POST",), persistent_id="writes",
        autocommit_duration_ms=10, durable_ack=True)
    agg = wrows.groupby(wrows.wkey).reduce(
        wkey=wrows.wkey, wval=pw.reducers.max(wrows.wval))
    pw.io.subscribe(agg, lambda *a, **k: None)
    wack(wrows.select(ok=wrows.wval))


def _announce():
    while not ws._started.is_set():
        time.sleep(0.02)
    def write(doc):
        if not READY:
            return
        with open(READY + ".tmp", "w") as f:
            json.dump(doc, f)
        os.replace(READY + ".tmp", READY)
    write({"port": ws.port, "pid": os.getpid(), "seeded": False})
    if ROLE == "primary":
        while True:  # flip `seeded` once the initial N vectors are durable
            rts = list(_streaming._ACTIVE_RUNTIMES)
            if rts and rts[0].persistence is not None \\
                    and rts[0].persistence.entries_committed >= N:
                write({"port": ws.port, "pid": os.getpid(),
                       "seeded": True})
                return
            time.sleep(0.05)


threading.Thread(target=_announce, daemon=True).start()

if ROLE == "primary":
    pw.run(persistence_config=pw.persistence.Config(
        backend=pw.persistence.Backend.filesystem(ROOT)),
        with_http_server=HTTP)
else:
    pw.run(replica_of=ROOT, with_http_server=HTTP)
"""


class _ReplicaFleet:
    """Multi-process replica-fleet harness shared by bench_replica and
    tests/replica_canary.py: an in-process QueryRouter fronting a primary
    + N read replicas, each a real OS process running _REPLICA_PROGRAM.
    The parent generates closed-loop query load against the router's
    front port and measures end-to-end latency — the numbers a client of
    the fleet would see."""

    def __init__(self, tmp: str, *, vecs: int = 256,
                 query_cost_ms: float = 25.0,
                 observability: bool = False, writes: bool = False):
        import sys as _sys

        self.tmp = tmp
        # fleet-observability mode (tests/fleet_trace_canary.py): every
        # member runs its monitoring HTTP server on an ephemeral port
        # with the flight recorder on, and the PRIMARY also registers
        # with the router (read-serving last resort) so /fleet/* covers
        # the whole fleet
        self.observability = observability
        self.root = os.path.join(tmp, "primary-root")
        self.prog = os.path.join(tmp, "replica_prog.py")
        with open(self.prog, "w") as f:
            f.write(_REPLICA_PROGRAM)
        self._py = _sys.executable
        # several fleet members on one host: a chip belongs to one
        # process, so every member stays on the CPU backend
        self.base_env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            PATHWAY_RUN_ID="replica-bench",
            REPLICA_BENCH_ROOT=self.root,
            REPLICA_BENCH_VECS=str(vecs),
            REPLICA_BENCH_QUERY_COST_MS=str(query_cost_ms))
        self.base_env.setdefault("PYTHONPATH", os.path.dirname(
            os.path.abspath(__file__)))
        # children must not inherit replica/monitoring config from the
        # parent's environment
        for k in ("PATHWAY_REPLICA_OF", "PATHWAY_ROUTER_CONTROL",
                  "PATHWAY_REPLICA_ID", "PATHWAY_SNAPSHOT_EVERY_TICKS",
                  "PATHWAY_MONITORING_HTTP_PORT", "PATHWAY_PROCESSES"):
            self.base_env.pop(k, None)
        if observability:
            self.base_env.update(
                REPLICA_BENCH_HTTP="1",
                PATHWAY_MONITORING_HTTP_PORT="0",  # ephemeral, in the hb
                PATHWAY_FLIGHT_RECORDER="1")
        if writes:
            self.base_env["REPLICA_BENCH_WRITES"] = "1"
        self.vecs = vecs
        self.router = None
        self.procs: dict[str, object] = {}  # name -> Popen

    # -- lifecycle ---------------------------------------------------------
    def start_router(self, *, write_paths=None,
                     election_timeout_ms: int | None = None):
        from pathway_tpu.engine.router import QueryRouter

        prior = os.environ.get("PATHWAY_RUN_ID")
        os.environ["PATHWAY_RUN_ID"] = "replica-bench"  # shared authkey
        prior_et = os.environ.get("PATHWAY_ROUTER_ELECTION_TIMEOUT_MS")
        if election_timeout_ms is not None:
            os.environ["PATHWAY_ROUTER_ELECTION_TIMEOUT_MS"] = str(
                election_timeout_ms)
        try:
            self.router = QueryRouter(port=0, control_port=0,
                                      write_paths=write_paths)
            self.router.start()
        finally:
            if election_timeout_ms is not None:
                if prior_et is None:
                    os.environ.pop("PATHWAY_ROUTER_ELECTION_TIMEOUT_MS",
                                   None)
                else:
                    os.environ["PATHWAY_ROUTER_ELECTION_TIMEOUT_MS"] = \
                        prior_et
            if prior is None:
                os.environ.pop("PATHWAY_RUN_ID", None)
            else:
                os.environ["PATHWAY_RUN_ID"] = prior
        return self.router

    def _spawn(self, name: str, env: dict):
        import subprocess

        err = open(os.path.join(self.tmp, f"{name}.stderr"), "w")
        h = subprocess.Popen([self._py, self.prog], env=env,
                             stderr=err, stdout=subprocess.DEVNULL)
        h._err_file = err  # noqa: SLF001 — closed in stop()
        self.procs[name] = h
        return h

    def _check_alive(self, name: str) -> None:
        h = self.procs[name]
        if h.poll() is not None:
            with open(os.path.join(self.tmp, f"{name}.stderr")) as f:
                tail = f.read()[-800:]
            raise RuntimeError(
                f"fleet member {name} died (rc={h.returncode}): {tail}")

    def start_primary(self, *, snapshot_ticks: int = 4,
                      timeout_s: float = 120.0, register: bool = False):
        ready = os.path.join(self.tmp, "primary.ready")
        env = dict(self.base_env, REPLICA_BENCH_ROLE="primary",
                   REPLICA_BENCH_READY_FILE=ready,
                   PATHWAY_SNAPSHOT_EVERY_TICKS=str(snapshot_ticks))
        if register and self.router is not None:
            # failover mode: the primary joins the control plane so the
            # router can detect its death and run an election
            env.update(PATHWAY_REPLICA_ID="primary",
                       PATHWAY_ROUTER_CONTROL=(
                           f"127.0.0.1:{self.router.control_port}"))
        if self.observability and self.router is not None:
            # the primary registers too (role "primary", routed only as
            # a last resort) so /fleet/metrics//fleet/trace cover it
            env.update(PATHWAY_REPLICA_ID="primary",
                       PATHWAY_ROUTER_CONTROL=(
                           f"127.0.0.1:{self.router.control_port}"))
        self._spawn("primary", env)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self._check_alive("primary")
            if os.path.exists(ready):
                with open(ready) as f:
                    doc = json.load(f)
                if doc.get("seeded"):
                    return doc
            time.sleep(0.1)
        raise TimeoutError("primary never finished seeding its WAL")

    def start_replica(self, rid: str, *, max_staleness: int = 4,
                      timeout_s: float = 120.0,
                      promote_crash: bool = False):
        env = dict(self.base_env, REPLICA_BENCH_ROLE="replica",
                   PATHWAY_REPLICA_OF=self.root, PATHWAY_REPLICA_ID=rid,
                   PATHWAY_ROUTER_CONTROL=(
                       f"127.0.0.1:{self.router.control_port}"))
        if promote_crash:
            env["REPLICA_BENCH_PROMOTE_CRASH"] = "1"
        self._spawn(rid, env)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            self._check_alive(rid)
            for ep in self.router.endpoints():
                if ep.replica_id == rid and ep.port \
                        and ep.applied_tick > 0 \
                        and ep.staleness_ticks <= max_staleness:
                    self._warm(ep)
                    return ep
            time.sleep(0.05)
        raise TimeoutError(f"replica {rid} never caught up / registered")

    def _warm(self, ep, n: int = 3) -> None:
        """Warm a fresh replica DIRECTLY (bypassing the router) before it
        takes fleet traffic: its first queries pay the one-off KNN
        compile, and a measurement window that includes them measures
        warmup, not serving."""
        import http.client

        body = json.dumps({"vec": [0.1] * 16, "k": 3}).encode()
        for _ in range(n):
            conn = http.client.HTTPConnection(ep.host, ep.port,
                                              timeout=60)
            try:
                conn.request("POST", "/q", body=body,
                             headers={"Content-Type": "application/json"})
                conn.getresponse().read()
            finally:
                conn.close()

    def kill_replica(self, rid: str) -> None:
        self.procs[rid].kill()  # SIGKILL: death, not a graceful drain

    def sigstop(self, name: str) -> None:
        """Freeze a member: its sockets stay open but it goes silent —
        the router's staleness detector (not EOF) must declare it."""
        import signal

        os.kill(self.procs[name].pid, signal.SIGSTOP)

    def sigcont(self, name: str) -> None:
        import signal

        os.kill(self.procs[name].pid, signal.SIGCONT)

    def wait_promoted(self, n: int = 1, timeout_s: float = 120.0) -> str:
        """Wait until the router has completed ``n`` promotions; returns
        the promoted member's id."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.router.promotions_total >= n \
                    and self.router._write_primary_id is not None:
                return self.router._write_primary_id
            time.sleep(0.05)
        raise TimeoutError(
            f"router never completed promotion #{n} "
            f"(promotions={self.router.promotions_total}, "
            f"election={self.router._election})")

    def stderr_text(self, name: str) -> str:
        with open(os.path.join(self.tmp, f"{name}.stderr")) as f:
            return f.read()

    def wait_deregistered(self, rid: str, timeout_s: float = 30.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if all(e.replica_id != rid for e in self.router.endpoints()):
                return
            time.sleep(0.05)
        raise TimeoutError(f"router never dropped dead replica {rid}")

    # -- load --------------------------------------------------------------
    def run_load(self, seconds: float, *, clients: int = 8,
                 warmup_s: float = 1.0,
                 kill_at_s: float | None = None,
                 kill_rid: str | None = None) -> dict:
        """Closed-loop load from ``clients`` threads against the router
        front door for ``seconds``; optionally SIGKILL ``kill_rid`` at
        ``kill_at_s`` into the window. Returns latency quantiles over
        the post-warmup samples and the FULL-window failure count (a
        lost query is a lost query, warm or not)."""
        import http.client
        import threading as _threading

        body = json.dumps({"vec": [0.1] * 16, "k": 3}).encode()
        samples: list[tuple[float, float, bool]] = []
        lock = _threading.Lock()
        stop_at = time.monotonic() + seconds

        def client():
            while time.monotonic() < stop_at:
                t0 = time.monotonic()
                ok = False
                try:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self.router.port, timeout=30)
                    try:
                        conn.request(
                            "POST", "/q", body=body,
                            headers={"Content-Type": "application/json"})
                        resp = conn.getresponse()
                        resp.read()
                        ok = resp.status == 200
                    finally:
                        conn.close()
                except OSError:
                    ok = False
                with lock:
                    samples.append(
                        (t0, (time.monotonic() - t0) * 1e3, ok))

        threads = [_threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        t_start = time.monotonic()
        for t in threads:
            t.start()
        if kill_at_s is not None and kill_rid is not None:
            time.sleep(kill_at_s)
            self.kill_replica(kill_rid)
        for t in threads:
            t.join(timeout=seconds + 60)
        lost = sum(1 for _t, _ms, ok in samples if not ok)
        lat = sorted(ms for t0, ms, ok in samples
                     if ok and t0 >= t_start + warmup_s)
        out = {"queries": len(samples), "lost": lost}
        if lat:
            out["p50_ms"] = round(float(np.percentile(lat, 50)), 3)
            out["p95_ms"] = round(float(np.percentile(lat, 95)), 3)
        return out

    def stop(self) -> None:
        for name, h in self.procs.items():
            if h.poll() is None:
                h.kill()
        for name, h in self.procs.items():
            try:
                h.wait(timeout=10)
            except Exception:  # noqa: BLE001 — teardown must finish
                pass
            err = getattr(h, "_err_file", None)
            if err is not None:
                err.close()
        if self.router is not None:
            self.router.stop()


def _bench_replica_ready_sweep() -> dict:
    """Hydration wall-clock vs history size: for each history H,
    synthesize a WAL of H rows, then measure replica time-to-ready (start
    -> applied tick == primary watermark) twice — WAL-only (tail replay,
    O(stream age)) and snapshot-hydrated (PR-10 restore + empty suffix,
    O(state)). The snapshot path must stay ~flat across histories."""
    import tempfile
    import threading as _threading

    import pathway_tpu as pw
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.internals.parse_graph import G

    sizes = [int(s) for s in os.environ.get(
        "BENCH_REPLICA_ROWS", "1000,10000,50000").split(",")]
    chunk = 500

    class _Closed(pw.io.python.ConnectorSubject):
        def run(self):
            return

    def build():
        G.clear()
        t = pw.io.python.read(
            _Closed(), schema=pw.schema_from_types(word=str),
            autocommit_duration_ms=10, persistent_id="bench-replica")
        counts = t.groupby(t.word).reduce(word=t.word,
                                          c=pw.reducers.count())
        pw.io.subscribe(counts, lambda *a, **k: None)

    def replica_ready_s(pdir: str, target_tick: int) -> tuple[float, dict]:
        build()
        errs: list[BaseException] = []

        def _r():
            try:
                pw.run(replica_of=pdir)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        t0 = time.perf_counter()
        th = _threading.Thread(target=_r, daemon=True)
        th.start()
        ready = None
        deadline = time.monotonic() + 300
        stats = {}
        while time.monotonic() < deadline:
            if errs:
                raise RuntimeError(f"replica run failed: {errs[0]!r}")
            for rt in list(_streaming._ACTIVE_RUNTIMES):
                if rt.replica is not None \
                        and rt.replica.applied_tick >= target_tick:
                    ready = time.perf_counter() - t0
                    stats = rt.replica.stats()
            if ready is not None:
                break
            time.sleep(0.02)
        _streaming.stop_all()
        th.join(timeout=60)
        G.clear()
        if ready is None:
            raise TimeoutError(
                f"replica never reached tick {target_tick} over {pdir}")
        return ready, stats

    out: dict = {}
    prior = os.environ.get("PATHWAY_SNAPSHOT_EVERY_TICKS")
    snap_ready: dict[int, float] = {}
    try:
        for n in sizes:
            with tempfile.TemporaryDirectory() as td:
                pdir = os.path.join(td, "p")
                driver = PersistenceDriver(
                    pw.persistence.Config.simple_config(
                        pw.persistence.Backend.filesystem(pdir)))
                log = driver._log_for("bench-replica")
                tick = 0
                for base in range(0, n, chunk):
                    tick += 1
                    log.append(tick, [
                        (Pointer(i), (f"w{i % 1000}",), 1, None)
                        for i in range(base, min(base + chunk, n))])
                log.close()
                os.environ.pop("PATHWAY_SNAPSHOT_EVERY_TICKS", None)
                # min of two: first-run import/compile noise must not
                # masquerade as tail-replay cost (same rule as
                # bench_recovery's restarts)
                wal_s = min(replica_ready_s(pdir, tick)[0],
                            replica_ready_s(pdir, tick)[0])
                # snapshot prep: one primary restart with snapshots ON —
                # its teardown writes the generation and compacts, so the
                # next replica hydrates O(state) with an empty suffix
                os.environ["PATHWAY_SNAPSHOT_EVERY_TICKS"] = "1000000000"
                build()
                pw.run(persistence_config=pw.persistence.Config
                       .simple_config(
                           pw.persistence.Backend.filesystem(pdir)))
                G.clear()
                snap_s, st = min(replica_ready_s(pdir, tick),
                                 replica_ready_s(pdir, tick),
                                 key=lambda r: r[0])
                out[f"replica_ready_walonly_s_{n}"] = round(wal_s, 3)
                out[f"replica_ready_snapshot_s_{n}"] = round(snap_s, 3)
                out[f"replica_hydrate_s_{n}"] = (
                    None if st.get("hydrate_wall_s") is None
                    else round(st["hydrate_wall_s"], 3))
                snap_ready[n] = snap_s
    finally:
        if prior is None:
            os.environ.pop("PATHWAY_SNAPSHOT_EVERY_TICKS", None)
        else:
            os.environ["PATHWAY_SNAPSHOT_EVERY_TICKS"] = prior
    if snap_ready:
        lo, hi = min(sizes), max(sizes)
        out["replica_snapshot_ready_ratio_maxmin"] = round(
            snap_ready[hi] / max(snap_ready[lo], 1e-9), 3)
    return out


def bench_replica() -> dict:
    """Elastic replica fleet (engine/replica.py + engine/router.py):

    * hydration time-to-ready vs history size, WAL-only (linear) vs
      snapshot-hydrated (~flat) — _bench_replica_ready_sweep;
    * a LIVE fleet: primary + read replicas as separate OS processes
      behind the in-process router — end-to-end p50/p95 through the
      router front door with 1 vs 2 replicas (the elasticity evidence),
      per-replica request spread, exported staleness lag (scraped from
      the router's real /metrics HTTP surface), and a SIGKILL of one
      replica under live load (zero lost queries = the failover
      evidence). tests/replica_canary.py gates all of it in CI.
    """
    import tempfile
    import urllib.request

    out = _bench_replica_ready_sweep()
    # 10s windows: the elasticity gate compares phase p95s, and with
    # ~20 qps of closed-loop traffic a 6s window leaves ~100 post-warmup
    # samples — p95 is then set by ~5 queue-alignment outliers and the
    # 1-vs-2-replica comparison flakes. 10s windows + 2s warmup keep the
    # estimate inside the phases' true separation (~2x).
    load_s = float(os.environ.get("BENCH_REPLICA_LOAD_S", 10.0))
    clients = int(os.environ.get("BENCH_REPLICA_CLIENTS", 8))
    tmp = tempfile.mkdtemp(prefix="bench_replica_")
    fleet = _ReplicaFleet(tmp)
    try:
        fleet.start_router()
        fleet.start_primary()
        fleet.start_replica("r1")
        one = fleet.run_load(load_s, clients=clients, warmup_s=2.0)
        fleet.start_replica("r2")
        r1_before = {e.replica_id: e.requests
                     for e in fleet.router.endpoints()}.get("r1", 0)
        two = fleet.run_load(load_s, clients=clients, warmup_s=2.0)
        eps = {e.replica_id: e for e in fleet.router.endpoints()}
        out.update({
            "replica_fleet_clients": clients,
            "replica_query_cost_ms": float(
                fleet.base_env["REPLICA_BENCH_QUERY_COST_MS"]),
            "replica_p50_ms_1": one.get("p50_ms"),
            "replica_p95_ms_1": one.get("p95_ms"),
            "replica_p50_ms_2": two.get("p50_ms"),
            "replica_p95_ms_2": two.get("p95_ms"),
            # phase-2 spread: requests each replica served while BOTH
            # were up (r1's phase-1 traffic subtracted out)
            "replica_requests_r1": eps["r1"].requests - r1_before,
            "replica_requests_r2": eps["r2"].requests,
            "replica_max_staleness_ticks": max(
                e.staleness_ticks for e in eps.values()),
        })
        if one.get("p95_ms") and two.get("p95_ms"):
            out["replica_p95_ratio_2v1"] = round(
                two["p95_ms"] / one["p95_ms"], 3)
        # the exported surface itself: per-replica staleness must be on
        # the router's real /metrics endpoint (acceptance criterion)
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{fleet.router.port}/metrics",
            timeout=10).read().decode()
        out["replica_staleness_exported"] = (
            'pathway_tpu_replica_staleness_ticks{replica="r1"}' in metrics
            and 'pathway_tpu_replica_staleness_ticks{replica="r2"}'
            in metrics)
        # failover: SIGKILL r1 mid-window; the router must fail its
        # in-flight queries over to r2 — zero lost end to end
        kill = fleet.run_load(load_s, clients=clients,
                              kill_at_s=load_s / 3, kill_rid="r1")
        fleet.wait_deregistered("r1")
        out.update({
            "replica_kill_queries": kill["queries"],
            "replica_lost_queries": kill["lost"],
            "replica_failovers": fleet.router.failovers_total,
            "replica_p95_ms_after_kill": kill.get("p95_ms"),
            "replica_fleet_after_kill": sorted(
                e.replica_id for e in fleet.router.endpoints()),
        })
    finally:
        fleet.stop()
    out.update(_bench_replica_failover())
    return out


def _bench_replica_failover() -> dict:
    """Write-path failover wall-clock (PR 18): a registered primary +
    one caught-up replica; SIGSTOP the primary (a zombie, not a corpse:
    its sockets stay open, so only the heartbeat-staleness detector can
    declare it) and measure death-declaration -> promoted-primary
    heartbeat on the router's clock. Then SIGCONT the zombie: its next
    commit must refuse with FencedPrimaryError (counted from its
    stderr — each one is a split-brain write that did NOT land)."""
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_failover_")
    fleet = _ReplicaFleet(tmp)
    out: dict = {}
    try:
        fleet.start_router(write_paths=("/w",),
                           election_timeout_ms=1500)
        fleet.start_primary(register=True)
        fleet.start_replica("r1")
        fleet.sigstop("primary")
        promoted = fleet.wait_promoted(1)
        out["replica_failover_promotion_s"] = (
            None if fleet.router.failover_seconds is None
            else round(fleet.router.failover_seconds, 3))
        out["replica_promoted_member"] = promoted
        # wake the zombie: fencing, not luck, keeps the timeline single
        fleet.sigcont("primary")
        deadline = time.monotonic() + 60
        fenced = 0
        while time.monotonic() < deadline:
            # the error MESSAGE appears once per refused write; the bare
            # class name also shows up in traceback frames (over-counts)
            fenced = fleet.stderr_text("primary").count(
                "fenced primary: this writer holds fencing epoch")
            if fenced and fleet.procs["primary"].poll() is not None:
                break
            time.sleep(0.25)
        out["replica_fenced_writes"] = fenced
    finally:
        fleet.stop()
    return out


def bench_knn() -> dict:
    """Query latency against the largest slab that fits one chip.

    ``knn_p50_ms`` is DEVICE execution time per single-query search
    (measured by index.latency_probe: many searches in one dispatch — the
    number the <20 ms target is about). ``knn_e2e_*`` are end-to-end
    through this environment's dispatch path, with the measured dispatch
    floor reported next to them.
    """

    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    import jax
    import jax.numpy as jnp

    n = KNN_N
    while True:
        try:
            index = BruteForceKnnIndex(KNN_DIM, reserved_space=n,
                                       metric=KnnMetric.COS,
                                       dtype="bfloat16")
            rng = np.random.default_rng(0)
            ingest_start = time.perf_counter()
            chunk = min(1 << 19, n)
            # ingest through the DEVICE path (the production embed+index
            # route: vectors are born on-chip): per-chunk on-device RNG +
            # add_batch_device scatter — no 7.7 GB host→device transfer
            gen = jax.jit(
                lambda key: jax.random.uniform(
                    key, (chunk, KNN_DIM), jnp.bfloat16, -1.0, 1.0))
            for ci, base in enumerate(range(0, n, chunk)):
                m = min(chunk, n - base)
                vecs = gen(jax.random.PRNGKey(ci))
                index.add_batch_device(
                    [Pointer(base + i) for i in range(m)], vecs[:m])
            queries = rng.random((64, KNN_DIM), dtype=np.float32) * 2.0 - 1.0

            def run(batch, k=10):
                qs = [(Pointer(10**9 + i), batch[i], k, None)
                      for i in range(len(batch))]
                return index.search(qs)

            # first search uploads the slab + compiles the (1, N) kernel
            res = run(queries[:1])
            assert res[0] and len(res[0]) == 10
            ingest_s = time.perf_counter() - ingest_start

            dev_single = index.latency_probe(batch_size=1, k=10, reps=64)
            dev_batch64 = index.latency_probe(batch_size=64, k=10, reps=16)
            floor = _dispatch_floor_ms()
            lat = []
            for i in range(20):
                t0 = time.perf_counter()
                run(queries[i % 64:i % 64 + 1])
                lat.append((time.perf_counter() - t0) * 1e3)
            # bf16 top-10 for the int8 overlap probe (same vectors: the
            # int8 slab re-ingests identical PRNGKey chunks)
            bf16_top = [tuple(k for k, _ in r) for r in run(queries[:8])]
            out = {
                "knn_n_vectors": n,
                "knn_dim": KNN_DIM,
                "knn_dtype": "bfloat16",
                "knn_p50_ms": round(dev_single, 2),
                "knn_batch64_ms": round(dev_batch64, 2),
                "knn_vs_target": round(KNN_TARGET_P50_MS / dev_single, 3),
                "knn_e2e_p50_ms": round(float(np.percentile(lat, 50)), 2),
                "knn_e2e_p99_ms": round(float(np.percentile(lat, 99)), 2),
                "knn_dispatch_floor_ms": round(floor, 2),
                "knn_ingest_s": round(ingest_s, 1),
            }
            del index
            try:
                out.update(_bench_knn_int8(n, gen, chunk, queries, bf16_top))
            except Exception as e:  # noqa: BLE001 - int8 leg is additive
                out["knn_int8_error"] = f"{type(e).__name__}: {str(e)[:200]}"
            return out
        except (RuntimeError, MemoryError) as e:
            # HBM too small for this slab — release EVERYTHING the failed
            # attempt pinned on device (slab, chunk buffer, jitted gen)
            # before retrying, then halve
            index = vecs = gen = None  # noqa: F841
            import gc

            gc.collect()
            if n <= 1 << 20:
                return {"knn_error": str(e)[:200]}
            n //= 2


if __name__ == "__main__":
    main()

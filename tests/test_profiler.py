"""Continuous profiling plane (engine/profiler.py):

- the analytic cost model pins: FLOPs and bytes per kernel family match
  hand-computed values at known shapes, and bench.py's MFU math goes
  through the SAME encoder formula (no drift between the live gauges
  and the benchmark);
- roofline classification: arithmetic intensity vs machine balance
  decides compute- vs bandwidth-bound, honoring the BENCH_* env
  overrides bench.py honors;
- leg attribution: dispatches buffered inside a bridge leg are
  re-scaled pro-rata to the leg's MEASURED execute time (and a failed
  leg falls back to call-site walls, unattributed);
- the host sampler emits well-formed collapsed-flamegraph text with
  thread roles from the uniform pathway-tpu-* inventory, tags samples
  with the flight recorder's in-flight operator, and windowed baselines
  subtract correctly;
- the knn hooks record search/scatter dispatches without perturbing
  results — profiler-on output equals profiler-off output exactly;
- per-tenant serving metrics: attribute_tenant + tenant_summary expose
  per-tenant p50/p95 and an SLO burn rate per tenant;
- profdiff names the dominant kernel/frame delta between two profiles.
"""

from __future__ import annotations

import os
import re
import threading
import time

import numpy as np
import pytest

from pathway_tpu.engine.profiler import (Profiler, current_profiler,
                                         diff_profiles, encoder_cost,
                                         encoder_flops_per_token,
                                         ingest_scatter_cost,
                                         install_profiler, knn_search_cost,
                                         live_profiler_stats,
                                         machine_balance, machine_params,
                                         segment_attention_cost)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the v5e row, passed in by hand: these tests check the arithmetic against
# stated peaks — they do not rate the CPU they run on
V5E = machine_params("TPU v5 lite")


@pytest.fixture(autouse=True)
def _fresh_profiler():
    install_profiler(None)
    yield
    install_profiler(None)


# ---------------------------------------------------------------------------
# analytic cost model: hand-computed pins
# ---------------------------------------------------------------------------

def test_encoder_flops_per_token_pin():
    # h=64 f=128 L=2 S=16:
    #   per_layer = 2*(4*64*64 + 2*64*128) = 2*(16384+16384) = 65536
    #   attn      = 2*4*16*64 = 8192
    #   total     = 2*65536 + 8192 = 139264
    assert encoder_flops_per_token(64, 128, 2, 16) == 139264.0


def test_encoder_cost_pin():
    # B=1 S=4 h=8 f=16 L=1:
    #   fpt   = 2*(4*64 + 2*8*16) + 1*4*4*8 = 1024 + 128 = 1152
    #   flops = 1*4*1152 = 4608
    #   param = 2*(4*64 + 2*8*16) = 1024 bytes (bf16)
    #   act   = 8*1*(2*1*4*8) = 512;  emb = 2*1*4*8 = 64
    flops, nbytes = encoder_cost(1, 4, hidden=8, intermediate=16, layers=1)
    assert flops == 4608.0
    assert nbytes == 1024.0 + 512.0 + 64.0


def test_segment_attention_adds_score_tensor_bytes():
    base_f, base_b = encoder_cost(1, 4, hidden=8, intermediate=16, layers=1)
    seg_f, seg_b = segment_attention_cost(1, 4, hidden=8, intermediate=16,
                                          layers=1)
    assert seg_f == base_f  # same matmul tree, mask changes nothing
    # score tensor: 2 (write+read) * L * 2 bytes * B * S * S = 64
    assert seg_b == base_b + 64.0


def test_knn_search_cost_pin():
    # Q=4 N=1024 D=64 f32: flops = 2*4*1024*64 = 524288
    #   bytes = 1024*64*4 (slab) + 4*64*4 (queries) = 262144 + 1024
    assert knn_search_cost(4, 1024, 64) == (524288.0, 263168.0)
    # int8 slab carries f32 scales+vsq side columns (8 B/row)
    flops, nbytes = knn_search_cost(2, 100, 32, itemsize=1,
                                    extra_row_bytes=8)
    assert flops == 2.0 * 2 * 100 * 32
    assert nbytes == 100 * (32 + 8) + 2 * 32 * 4


def test_ingest_scatter_cost_pin():
    # read f32 in + write slab row at storage width
    assert ingest_scatter_cost(8, 16) == (256.0, 8 * 16 * 8.0)
    assert ingest_scatter_cost(8, 16, itemsize=1)[1] == 8 * 16 * 5.0


def test_machine_params_keyed_by_device_kind():
    assert V5E == {"device_kind": "TPU v5 lite", "peak_tflops": 197.0,
                   "hbm_gbps": 819.0}
    assert machine_balance(V5E) == pytest.approx(197e12 / 819e9)
    assert machine_balance({"peak_tflops": 100.0, "hbm_gbps": 1000.0}) \
        == pytest.approx(100.0)  # 100e12 / 1000e9
    # a device without a row has no peaks — never another chip's
    assert machine_params("some future chip") is None
    assert machine_params() is None  # the suite runs on the CPU


def test_unrated_device_reports_no_utilization():
    prof = Profiler(sample_interval_ms=1e6)  # machine from the device: cpu
    prof.record_dispatch("knn_search", 5e8, 1e6, 1.0)
    assert prof.machine is None
    assert prof.rolling_mfu() is None and prof.rolling_hbm_bw_util() is None
    fam = prof.family_stats()["knn_search"]
    assert fam["device_ms_total"] == 1.0  # what was observed stays
    assert fam["roofline"]["arithmetic_intensity"] == 500.0
    assert fam["mfu"] is None and fam["rolling"]["mfu"] is None
    assert fam["roofline"]["bound_by"] is None
    st = prof.stats()
    assert st["machine"] is None and st["mfu_rolling"] is None


def test_bench_mfu_uses_shared_encoder_formula():
    """bench.py's per-token FLOPs must be THE shared formula — a drift
    here silently decouples the live MFU gauge from the benchmark."""
    import sys

    sys.path.insert(0, REPO)
    import bench

    from pathway_tpu.models.encoder import EncoderConfig
    cfg = EncoderConfig(hidden=64, intermediate=128, layers=2)
    assert bench._encoder_flops_per_token(cfg, seq=16) == \
        encoder_flops_per_token(64, 128, 2, 16)


def test_encoder_cost_helper_routes_ragged():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.encoder import encoder_cost as model_cost

    cfg = EncoderConfig(hidden=8, intermediate=16, layers=1)
    assert model_cost(cfg, 1, 4) == encoder_cost(
        1, 4, hidden=8, intermediate=16, layers=1)
    assert model_cost(cfg, 1, 4, ragged=True) == segment_attention_cost(
        1, 4, hidden=8, intermediate=16, layers=1)


# ---------------------------------------------------------------------------
# roofline classification + rolling gauges
# ---------------------------------------------------------------------------

def test_roofline_classification():
    prof = Profiler(sample_interval_ms=1e6, machine=V5E)
    # knn search: AI = 2Q/itemsize ≈ 2 FLOP/byte at Q=4 — far below
    # machine balance → bandwidth-bound
    f, b = knn_search_cost(4, 1024, 64)
    prof.record_dispatch("knn_search", f, b, 2.0)
    # synthetic compute-bound family: AI far above balance
    prof.record_dispatch("encoder_forward", 1e12, 1e6, 5.0)
    fams = prof.family_stats()
    knn = fams["knn_search"]["roofline"]
    assert knn["bound_by"] == "bandwidth"
    assert knn["arithmetic_intensity"] == pytest.approx(f / b, rel=1e-3)
    assert 0.0 < knn["attainable_mfu"] < 1.0
    enc = fams["encoder_forward"]["roofline"]
    assert enc["bound_by"] == "compute"
    assert enc["attainable_mfu"] == 1.0
    # rolling gauges aggregate across families
    assert prof.rolling_mfu() > 0.0
    assert prof.rolling_hbm_bw_util() > 0.0


def test_rolling_mfu_matches_hand_computation():
    prof = Profiler(sample_interval_ms=1e6,  # 1e12 FLOP/s peak
                    machine={"peak_tflops": 1.0, "hbm_gbps": 819.0})
    prof.record_dispatch("knn_search", 5e8, 1e6, 1.0)  # 5e8 FLOP in 1ms
    # 5e8 / 1e-3 s = 5e11 FLOP/s → 50% of the 1e12 peak
    assert prof.rolling_mfu() == pytest.approx(0.5, rel=1e-6)


# ---------------------------------------------------------------------------
# leg attribution: measured bridge time re-scales buffered dispatches
# ---------------------------------------------------------------------------

def test_leg_attribution_rescales_to_measured_time():
    prof = Profiler(sample_interval_ms=1e6)
    prof.begin_leg(tick=3)
    # two async dispatches that "returned" in ~0 host ms: the leg's
    # measured 10ms must be split by analytic bytes (3:1)
    prof.record_dispatch("knn_search", 100.0, 3000.0, 0.001)
    prof.record_dispatch("ingest_scatter", 50.0, 1000.0, 0.001)
    prof.end_leg(10.0)
    fams = prof.family_stats()
    assert fams["knn_search"]["device_ms_total"] == pytest.approx(7.5)
    assert fams["ingest_scatter"]["device_ms_total"] == pytest.approx(2.5)
    assert fams["knn_search"]["attributed_dispatches"] == 1
    assert fams["ingest_scatter"]["attributed_dispatches"] == 1
    total = sum(f["device_ms_total"] for f in fams.values())
    assert total == pytest.approx(10.0)  # sums exactly to the leg


def test_leg_attribution_prefers_meaningful_walls():
    prof = Profiler(sample_interval_ms=1e6)
    prof.begin_leg(tick=0)
    # blocking call sites: their own walls carry the signal (8ms vs 2ms)
    prof.record_dispatch("knn_search", 1.0, 1.0, 8.0)
    prof.record_dispatch("ingest_scatter", 1.0, 1.0, 2.0)
    prof.end_leg(20.0)
    fams = prof.family_stats()
    assert fams["knn_search"]["device_ms_total"] == pytest.approx(16.0)
    assert fams["ingest_scatter"]["device_ms_total"] == pytest.approx(4.0)


def test_failed_leg_keeps_callsite_walls_unattributed():
    prof = Profiler(sample_interval_ms=1e6)
    prof.begin_leg(tick=0)
    prof.record_dispatch("knn_search", 10.0, 10.0, 1.25)
    prof.end_leg(None)  # leg raised
    fams = prof.family_stats()
    assert fams["knn_search"]["device_ms_total"] == pytest.approx(1.25)
    assert fams["knn_search"]["attributed_dispatches"] == 0


def test_record_outside_leg_commits_immediately():
    prof = Profiler(sample_interval_ms=1e6)
    prof.record_dispatch("encoder_forward", 10.0, 10.0, 4.0)
    fams = prof.family_stats()
    assert fams["encoder_forward"]["dispatches"] == 1
    assert fams["encoder_forward"]["attributed_dispatches"] == 0
    assert fams["encoder_forward"]["device_ms_total"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# host sampler: collapsed grammar, roles, in-flight tags, baselines
# ---------------------------------------------------------------------------

_COLLAPSED_LINE = re.compile(r"^[^; ][^;]*(;[^;]+)* \d+$")


def _busy_engine_thread(stop: threading.Event):
    def _inner_hot_loop():
        x = 0.0
        while not stop.is_set():
            x += 1.0
        return x

    _inner_hot_loop()


def test_sampler_collapsed_grammar_and_thread_roles():
    from pathway_tpu.engine import threads

    stop = threading.Event()
    t = threads.spawn(_busy_engine_thread, args=(stop,), name="test-busy")
    prof = Profiler(sample_interval_ms=2.0)
    try:
        prof.start()
        deadline = time.monotonic() + 5.0
        while prof.samples_total < 10 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        prof.stop()
        stop.set()
        t.join(5.0)
    assert prof.samples_total >= 10
    text = prof.collapsed()
    lines = text.strip().splitlines()
    assert lines, "no folded stacks collected"
    for ln in lines:
        assert _COLLAPSED_LINE.match(ln), f"bad collapsed line: {ln!r}"
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts, reverse=True)
    roles = {ln.split(";", 1)[0] for ln in lines}
    assert "test-busy" in roles  # pathway-tpu- prefix stripped to role
    # the busy thread's hot frame is in its folded stack
    busy = [ln for ln in lines if ln.startswith("test-busy;")]
    assert any("_inner_hot_loop" in ln for ln in busy)
    # the sampler never profiles itself into the profile
    assert "profiler-sampler" not in roles
    assert prof.top_host_frame() is not None


def test_sampler_tags_inflight_device_leg(monkeypatch):
    from pathway_tpu.engine import threads
    from pathway_tpu.engine import flight_recorder as fr

    stop = threading.Event()
    t = threads.spawn(_busy_engine_thread, args=(stop,), name="device-bridge")
    try:
        deadline = time.monotonic() + 2.0
        while t.ident is None and time.monotonic() < deadline:
            time.sleep(0.005)
        ident = t.ident
        monkeypatch.setattr(fr, "live_inflight_by_thread",
                            lambda: {ident: ("device", "knn_q")})
        prof = Profiler(sample_interval_ms=2.0)
        try:
            prof.start()
            deadline = time.monotonic() + 5.0
            while (prof.device_attributed_samples < 3
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            prof.stop()
    finally:
        stop.set()
        t.join(5.0)
    assert prof.device_attributed_samples >= 3
    assert "[device:knn_q]" in prof.collapsed()


def test_collapsed_baseline_subtracts_prior_samples():
    prof = Profiler(sample_interval_ms=1e6)
    with prof._lock:
        prof._stacks[("worker", ("f (a.py:1)",))] = 7
    baseline = prof.stack_counts()
    with prof._lock:
        prof._stacks[("worker", ("f (a.py:1)",))] = 9
        prof._stacks[("worker", ("g (a.py:2)",))] = 1
    diff = prof.collapsed(baseline)
    assert "worker;f (a.py:1) 2" in diff
    assert "worker;g (a.py:2) 1" in diff
    assert "7" not in diff  # absolute counts never leak into the window


def test_stack_table_overflow_folds_into_other_bucket():
    from pathway_tpu.engine import profiler as mod

    prof = Profiler(sample_interval_ms=1e6)
    with prof._lock:
        for i in range(mod._MAX_DISTINCT_STACKS):
            prof._stacks[("worker", (f"f{i} (x.py:{i})",))] = 1
    # simulate the sampler seeing a brand-new stack past the bound
    key = ("worker", ("fresh (y.py:1)",))
    with prof._lock:
        if key in prof._stacks:
            prof._stacks[key] += 1
        elif len(prof._stacks) < mod._MAX_DISTINCT_STACKS:
            prof._stacks[key] = 1
        else:
            other = (key[0], ("(other)",))
            prof._stacks[other] = prof._stacks.get(other, 0) + 1
    assert prof.stack_counts().get(("worker", ("(other)",))) == 1


def test_live_profiler_stats_roundtrip():
    assert live_profiler_stats() is None
    prof = Profiler(sample_interval_ms=1e6, machine=V5E)
    install_profiler(prof)
    assert current_profiler() is prof
    st = live_profiler_stats()
    assert st is not None
    assert set(st) >= {"host", "machine", "mfu_rolling", "hbm_bw_util",
                       "families", "capture"}
    assert st["host"]["sampling"] is False
    assert st["machine"]["balance_flop_per_byte"] == pytest.approx(
        machine_balance(V5E), abs=1e-3)


# ---------------------------------------------------------------------------
# knn hooks: dispatches recorded, outputs byte-identical on/off
# ---------------------------------------------------------------------------

def _knn_roundtrip(n=48, dim=8, q=3):
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    rng = np.random.default_rng(7)
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    idx = BruteForceKnnIndex(dim, metric=KnnMetric.L2SQ)
    idx.add_batch([Pointer(i) for i in range(n)], vecs)
    queries = [(Pointer(1000 + i), vecs[i * 5], 4, None) for i in range(q)]
    return idx.search(queries)


@pytest.mark.slow
def test_knn_outputs_identical_with_profiler_on_and_off():
    off = _knn_roundtrip()
    prof = Profiler(sample_interval_ms=1e6, machine=V5E)
    install_profiler(prof)
    on = _knn_roundtrip()
    assert on == off  # the profiler only observes shapes and clocks
    fams = prof.family_stats()
    assert fams["ingest_scatter"]["dispatches"] >= 1
    assert fams["knn_search"]["dispatches"] >= 1
    assert fams["knn_search"]["roofline"]["bound_by"] == "bandwidth"
    assert fams["knn_search"]["device_ms_total"] > 0.0
    # search bytes follow the scan model exactly: N*D*4 + Q*D*4 per
    # dispatch, with N the (power-of-two) rows of the established extents
    from pathway_tpu.engine.profiler import knn_search_cost as cost

    per = fams["knn_search"]["bytes_total"] / \
        fams["knn_search"]["dispatches"]
    caps = [cost(3, 1 << p, 8)[1] for p in range(4, 12)]
    assert per in caps


# ---------------------------------------------------------------------------
# per-tenant serving metrics (engine/request_tracker.py)
# ---------------------------------------------------------------------------

def _finish_query(tr, rid, key, ms, tenant=None):
    # finish() stamps t_responded with the real clock, so the synthetic
    # span must live on it too: e2e ends up ≈ ms (normalized_stamps
    # snaps the response stamp up to t_resolved)
    base = time.perf_counter()
    span = tr.start(rid, "/q", t_ingress=base)
    span.key = key
    tr._by_key[key] = span
    span.t_enqueued = base
    if tenant is not None:
        tr.attribute_tenant([key], tenant)
    span.t_resolved = base + ms / 1e3
    tr.finish(span)


def test_tenant_summary_tracks_per_tenant_quantiles_and_burn():
    from pathway_tpu.engine.request_tracker import RequestTracker

    tr = RequestTracker(slo_ms=50.0)
    for i in range(40):
        _finish_query(tr, f"a{i}", ("a", i), 10.0, tenant="acme")
    for i in range(40):
        _finish_query(tr, f"b{i}", ("b", i), 100.0, tenant="bigco")
    for i in range(5):
        _finish_query(tr, f"n{i}", ("n", i), 10.0)  # unattributed
    ts = tr.tenant_summary()
    assert set(ts) == {"acme", "bigco"}
    assert ts["acme"]["count"] == 40
    assert ts["acme"]["p50_ms"] <= ts["acme"]["p95_ms"]
    # acme is inside SLO, bigco burns budget every query
    assert ts["acme"]["burn_rate"] == 0.0
    assert ts["bigco"]["burn_rate"] > 1.0
    assert tr.summary()["tenants"] == ts


def test_attribute_tenant_first_attribution_wins():
    from pathway_tpu.engine.request_tracker import RequestTracker

    tr = RequestTracker(slo_ms=50.0)
    span = tr.start("r1", "/q", t_ingress=0.0)
    span.key = "k1"
    tr._by_key["k1"] = span
    tr.attribute_tenant(["k1", "missing-key"], "first")
    tr.attribute_tenant(["k1"], "second")
    assert span.tenant == "first"


def test_knn_search_attributes_tenant_to_live_trackers():
    from pathway_tpu.engine.request_tracker import RequestTracker
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex, KnnMetric

    tr = RequestTracker(slo_ms=50.0)  # registers itself in _LIVE
    qkey = Pointer(501)
    span = tr.start("r1", "/q", t_ingress=0.0)
    span.key = qkey
    tr._by_key[qkey] = span
    idx = BruteForceKnnIndex(4, metric=KnnMetric.L2SQ, tenant="acme")
    idx.add_batch([Pointer(0)], np.ones((1, 4), np.float32))
    idx.search([(qkey, np.ones(4, np.float32), 1, None)])
    assert span.tenant == "acme"


# ---------------------------------------------------------------------------
# profdiff: naming the dominant regressor
# ---------------------------------------------------------------------------

def _epoch(knn_ms, frame_share, samples=100):
    return {
        "mfu_rolling": 0.1,
        "families": {
            "knn_search": {"dispatches": 10,
                           "device_ms_total": knn_ms * 10,
                           "roofline": {"bound_by": "bandwidth"}},
            "encoder_forward": {"dispatches": 10, "device_ms_total": 50.0,
                                "roofline": {"bound_by": "compute"}},
        },
        "host": {
            "samples_total": samples,
            "top_frames": [
                {"frame": "search (knn.py:900)",
                 "samples": int(samples * frame_share)},
                {"frame": "step (graph.py:100)",
                 "samples": samples - int(samples * frame_share)},
            ],
        },
    }


def test_diff_profiles_names_dominant_kernel_and_frame():
    d = diff_profiles(_epoch(2.0, 0.2), _epoch(6.0, 0.7))
    assert d["dominant_kernel"]["family"] == "knn_search"
    assert d["dominant_kernel"]["delta_ms_per_dispatch"] == pytest.approx(4.0)
    assert d["dominant_kernel"]["ratio"] == pytest.approx(3.0)
    assert d["dominant_kernel"]["bound_by"] == "bandwidth"
    assert d["dominant_frame"]["frame"] == "search (knn.py:900)"
    assert d["dominant_frame"]["delta_share"] == pytest.approx(0.5)
    assert d["mfu_rolling_delta"] == 0.0


def test_diff_profiles_accepts_bench_artifacts():
    a = {"unit": "docs/s", "profile": [_epoch(1.0, 0.1), _epoch(2.0, 0.2)]}
    b = {"unit": "docs/s", "profile": [_epoch(3.0, 0.2)]}
    d = diff_profiles(a, b)  # last epoch of each artifact wins
    assert d["dominant_kernel"]["device_ms_per_dispatch_a"] == 2.0
    assert d["dominant_kernel"]["device_ms_per_dispatch_b"] == 3.0


def test_diff_profiles_rejects_profile_free_artifacts():
    with pytest.raises(ValueError, match="--profile"):
        diff_profiles({"unit": "docs/s"}, _epoch(1.0, 0.1))


def test_profile_epoch_embeds_host_and_families():
    prof = Profiler(sample_interval_ms=1e6)
    prof.record_dispatch("knn_search", 100.0, 1000.0, 1.0)
    with prof._lock:
        prof._stacks[("worker", ("f (a.py:1)", "g (a.py:2)"))] = 5
        prof.samples_total = 5
    ep = prof.profile_epoch()
    assert ep["families"]["knn_search"]["dispatches"] == 1
    frames = {e["frame"]: e["samples"] for e in ep["host"]["top_frames"]}
    assert frames == {"f (a.py:1)": 5, "g (a.py:2)": 5}
    # an epoch is diffable against itself (zero deltas)
    d = diff_profiles(ep, ep)
    assert d["dominant_kernel"]["delta_ms_per_dispatch"] == 0.0

"""Continuous profiling plane: host flamegraphs + device cost attribution.

The flight recorder (engine/flight_recorder.py) answers "what is each
operator doing" and the request tracker "where did each query spend its
time"; this module answers the two questions left between them when the
perf-trajectory watch flags a regression:

1. **Which host frames got slower?** A low-overhead sampling profiler
   periodically walks ``sys._current_frames()`` for the engine thread
   inventory (every engine thread carries a uniform ``pathway-tpu-*``
   name, engine/threads.py), aggregates folded stacks per thread role,
   and tags each sample with the flight recorder's in-flight operator
   when one is live — so a sample of the device-bridge worker mid-leg
   reads ``device-bridge;...;[device:knn_search]``. Collapsed-flamegraph
   text is served at ``/profile/host?seconds=N`` (engine/http_server.py)
   and the sampler keeps rolling self-overhead accounting against the
   <2% per-tick contract tests/profiling_canary.py enforces.

2. **Which kernels, and are they compute- or bandwidth-bound?** An
   analytic cost model (FLOPs + bytes moved) per kernel family —
   ``knn_search``, ``ingest_scatter``, ``encoder_forward``,
   ``segment_attention`` — is fed measured per-leg device time by the
   ``DeviceBridge`` (dispatches recorded inside a leg are re-scaled
   pro-rata to the leg's measured execute time), producing live
   ``pathway_tpu_mfu_rolling`` / ``pathway_tpu_hbm_bw_util`` /
   ``pathway_tpu_kernel_device_ms{family=}`` gauges and a per-family
   roofline classification (arithmetic intensity vs machine balance) in
   ``/status.profiler``. ``bench.py`` computes MFU through this same
   model — one copy of the math, exported everywhere.

Cost model: **disabled costs one module-global load + None check per
hook** (``current_profiler()`` returns None and every call site
short-circuits); pipeline outputs are byte-identical with profiling on
or off — the profiler only ever *observes* shapes and clocks.

On-demand XLA capture: ``/profile/device/start`` / ``stop`` drive
``jax.profiler.start_trace`` into an artifact directory, for the deep
dives the analytic model only points at.

Machine peaks come from ONE table keyed by the ``device_kind`` JAX
reports (``DEVICE_PEAKS``, shared with bench.py, each row with its
source). A device that is not in the table has no MFU, bandwidth
utilization or roofline verdict: the gauges are absent and the status
fields are None ("not measured") — never another chip's numbers.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time

__all__ = [
    "Profiler", "current_profiler", "install_profiler", "live_profiler_stats",
    "DEVICE_PEAKS", "machine_params", "machine_balance",
    "encoder_flops_per_token", "encoder_cost", "segment_attention_cost",
    "knn_search_cost", "ingest_scatter_cost",
    "diff_profiles",
]

# ---------------------------------------------------------------------------
# machine parameters (shared with bench.py)
# ---------------------------------------------------------------------------

#: Peak rates by ``jax.devices()[0].device_kind``; one row per chip this
#: repo has been run on. A kind that is not here yields no MFU/roofline
#: figure — add the row (with its source) rather than a default.
DEVICE_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "peak_tflops": 197.0,   # bf16
        "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 and 819 GB/s of HBM bandwidth per chip",
    },
}


def machine_params(device_kind: str | None = None) -> dict | None:
    """``{"device_kind", "peak_tflops", "hbm_gbps"}`` for ``device_kind``
    (default: the first device JAX reports — this initialises the
    backend), or None when the table has no row for it."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    row = DEVICE_PEAKS.get(device_kind)
    if row is None:
        return None
    return {"device_kind": device_kind, "peak_tflops": row["peak_tflops"],
            "hbm_gbps": row["hbm_gbps"]}


def machine_balance(machine: dict) -> float:
    """Machine balance in FLOP/byte: the arithmetic intensity at which
    the roofline's compute and bandwidth ceilings intersect. A kernel
    family whose AI sits below this is bandwidth-bound on this chip."""
    return (machine["peak_tflops"] * 1e12) / (machine["hbm_gbps"] * 1e9)


# ---------------------------------------------------------------------------
# analytic cost model, per kernel family
# ---------------------------------------------------------------------------
# One formula per family, pure python over plain shape ints — importable
# without touching jax. tests/test_profiler.py pins each against
# hand-computed values at known shapes.

def encoder_flops_per_token(hidden: int, intermediate: int, layers: int,
                            seq: int) -> float:
    """Forward FLOPs per token for the BERT-family encoder
    (models/encoder.py): 2*(non-embedding matmul params) per token —
    QKV + out-proj (4*h*h) and FFN up+down (2*h*f) per layer — plus the
    attention score/value term (4*S*h per token per layer; scores and
    weighted values are 2*S*h each). This is THE encoder FLOPs formula:
    bench.py's MFU and the profiler's cost model both call it."""
    per_layer = 2 * (4 * hidden * hidden + 2 * hidden * intermediate)
    attn = layers * 4 * seq * hidden
    return float(layers * per_layer + attn)


def encoder_cost(batch: int, seq: int, *, hidden: int, intermediate: int,
                 layers: int, vocab: int = 0,
                 param_bytes: int | None = None) -> tuple[float, float]:
    """(flops, bytes_moved) for one dense encoder forward of
    ``batch x seq`` tokens.

    Bytes: every non-embedding parameter is read once per dispatch
    (2 bytes, bf16 compute) plus the residual-stream activations
    traversing each layer boundary — ~4 reads + writes of the (B, S, H)
    bf16 stream per block (attention in/out, MLP in/out). The embedding
    gather reads one (H,) row per token. Deliberately first-order: the
    roofline verdict needs the right decade, not the exact coefficient.
    """
    flops = batch * seq * encoder_flops_per_token(hidden, intermediate,
                                                  layers, seq)
    if param_bytes is None:
        per_layer = 4 * hidden * hidden + 2 * hidden * intermediate
        param_bytes = 2 * layers * per_layer  # bf16 view of the matmul tree
    stream = 2 * batch * seq * hidden  # one bf16 (B, S, H) residual pass
    act_bytes = 8 * layers * stream    # ~4 in + 4 out stream touches/layer
    emb_bytes = 2 * batch * seq * hidden
    return flops, float(param_bytes + act_bytes + emb_bytes)


def segment_attention_cost(batch: int, seq: int, *, hidden: int,
                           intermediate: int,
                           layers: int) -> tuple[float, float]:
    """(flops, bytes_moved) for one ragged-packed forward
    (models/encoder.py encode_ragged): same matmul tree as the dense
    encoder — the block-diagonal segment mask changes which scores
    survive, not how many are computed — PLUS the (B, H_heads, S, S)
    score tensor the segment-attention softmax materializes in HBM
    twice per layer (write + read), which is the term that makes long
    packed sequences bandwidth-bound."""
    flops, base_bytes = encoder_cost(batch, seq, hidden=hidden,
                                     intermediate=intermediate,
                                     layers=layers)
    score_bytes = 2.0 * layers * 2 * batch * seq * seq  # bf16, write+read
    return flops, base_bytes + score_bytes


def knn_search_cost(queries: int, rows: int, dim: int,
                    itemsize: int = 4, extra_row_bytes: int = 0
                    ) -> tuple[float, float]:
    """(flops, bytes_moved) for one brute-force slab search
    (ops/knn.py): the (Q, D) x (D, N) score matmul is 2*Q*N*D FLOPs;
    bytes are dominated by the full slab scan — N*D*itemsize (int8=1,
    bf16=2, f32=4) plus per-row side columns (int8 carries f32
    scales+vsq: extra_row_bytes=8) plus the query upload. The slab term
    is why search latency tracks slab bytes, not FLOPs — AI = 2*Q/
    itemsize FLOP/byte is far below machine balance at serving Q."""
    flops = 2.0 * queries * rows * dim
    bytes_moved = (rows * (dim * itemsize + extra_row_bytes)
                   + queries * dim * 4.0)
    return flops, float(bytes_moved)


def ingest_scatter_cost(rows: int, dim: int,
                        itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes_moved) for one slab scatter / fused-ingest write
    (ops/knn.py _scatter): per row, read the incoming f32 vector and
    write the slab row at its storage width; int8 additionally computes
    the per-row symmetric scale (one max + one multiply per element,
    ~2*D FLOPs/row — counted for every width, it is the right order for
    bf16 casts too). Scatters are bandwidth all the way down."""
    flops = 2.0 * rows * dim
    bytes_moved = rows * dim * (4.0 + itemsize)
    return flops, float(bytes_moved)


KERNEL_FAMILIES = ("knn_search", "ingest_scatter", "encoder_forward",
                   "segment_attention")


# ---------------------------------------------------------------------------
# the profiler singleton
# ---------------------------------------------------------------------------

_PROFILER = None  # module global: current_profiler() is one load + check

_DEFAULT_SAMPLE_MS = 25.0
_DEFAULT_WINDOW_S = 60.0
_MAX_DISTINCT_STACKS = 512
_MAX_STACK_DEPTH = 48
_ROLLING_EVENTS = 4096


def current_profiler():
    """The installed profiler, or None (the hooks' zero-overhead-off
    branch: one module-global load + None check per call site)."""
    return _PROFILER


def install_profiler(profiler) -> None:
    """Install/clear the process-wide profiler (None clears). The
    streaming runtime owns the lifecycle; tests install directly."""
    global _PROFILER
    _PROFILER = profiler


def live_profiler_stats() -> dict | None:
    """Snapshot of the installed profiler for the dashboard panel and
    the HTTP endpoints (None when no profiler is live)."""
    prof = _PROFILER
    if prof is None:
        return None
    return prof.stats()


class _FamilyStats:
    """Per-kernel-family aggregate + rolling window of dispatches."""

    __slots__ = ("dispatches", "flops_total", "bytes_total",
                 "device_ms_total", "attributed", "window")

    def __init__(self):
        self.dispatches = 0
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.device_ms_total = 0.0
        self.attributed = 0  # dispatches re-timed by a measured bridge leg
        # (monotonic, flops, bytes, device_ms)
        self.window: collections.deque = collections.deque(
            maxlen=_ROLLING_EVENTS)


class _LegBuffer:
    """Thread-local buffer of dispatches recorded inside one device leg
    (the bridge worker wraps leg execution in begin_leg/end_leg)."""

    __slots__ = ("tick", "records")

    def __init__(self, tick: int):
        self.tick = tick
        self.records: list[list] = []  # [family, flops, bytes, wall_ms]


class Profiler:
    """Two-sided profiling plane (see module doc). One per process,
    installed via :func:`install_profiler`; every hook goes through
    :func:`current_profiler` so the uninstalled state costs a branch."""

    def __init__(self, sample_interval_ms: float | None = None,
                 window_s: float | None = None,
                 machine: dict | None = None):
        """``machine``: ``{"peak_tflops", "hbm_gbps"}`` to rate dispatches
        against; by default the ``DEVICE_PEAKS`` row of the device the
        first recorded dispatch ran on (None when it has no row)."""
        from pathway_tpu.internals.config import _env_float

        if sample_interval_ms is None:
            sample_interval_ms = _env_float("PATHWAY_PROFILER_SAMPLE_MS",
                                            _DEFAULT_SAMPLE_MS)
        self.sample_interval_s = max(0.001, sample_interval_ms / 1e3)
        if window_s is None:
            window_s = _env_float("PATHWAY_PROFILER_WINDOW_S",
                                  _DEFAULT_WINDOW_S)
        self.window_s = max(1.0, window_s)
        from pathway_tpu.engine.locking import create_lock

        self._lock = create_lock("Profiler._lock")
        # -- device side ---------------------------------------------------
        self._machine = machine
        # resolved from the device at the first dispatch: by then this
        # process has touched JAX, so the lookup starts no backend
        self._machine_resolved = machine is not None
        self._families: dict[str, _FamilyStats] = {}
        self._leg_local = threading.local()  # .buf: _LegBuffer | None
        # -- host sampler --------------------------------------------------
        # (role, folded-stack tuple) -> count; bounded, overflow -> (other)
        self._stacks: dict[tuple, int] = {}
        self.samples_total = 0
        self.device_attributed_samples = 0
        self._sample_cost_s = 0.0   # time spent inside the sample pass
        self._sampler_started = None  # monotonic of sampler start
        self._stop = threading.Event()
        self._thread = None
        # -- on-demand XLA capture ----------------------------------------
        self._capture_dir: str | None = None
        self.captures_total = 0

    # -- construction ------------------------------------------------------
    @classmethod
    def from_env(cls, auto_on: bool = False) -> "Profiler | None":
        """The run-level profiler, or None when profiling is off.

        Mirrors FlightRecorder.from_env: ``PATHWAY_PROFILER=0``
        force-disables, ``=1`` force-enables, otherwise on iff the
        caller's surface makes the data observable (``auto_on``: http
        server / live dashboard)."""
        flag = os.environ.get("PATHWAY_PROFILER", "").strip().lower()
        if flag in ("0", "false", "off", "no"):
            return None
        forced = flag in ("1", "true", "on", "yes")
        if not forced and not auto_on:
            return None
        return cls()

    # -- host sampling profiler --------------------------------------------
    def start(self) -> None:
        """Start the sampling thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._sampler_started = time.monotonic()
        from pathway_tpu.engine.threads import spawn

        self._thread = spawn(self._sample_loop, name="profiler-sampler")

    def stop(self) -> None:
        """Stop the sampler and any in-flight XLA capture."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(2.0)
            self._thread = None
        if self._capture_dir is not None:
            try:
                self.stop_device_capture()
            except Exception:
                pass

    def _sample_loop(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(self.sample_interval_s):
            t0 = time.perf_counter()
            try:
                self._sample_once(me)
            except Exception:
                # sampling must never take the run down; one bad pass is
                # a lost sample, not a crash (excepthook would log it as
                # a dead engine thread otherwise)
                pass
            self._sample_cost_s += time.perf_counter() - t0

    def _sample_once(self, self_ident: int) -> None:
        from pathway_tpu.engine.threads import thread_role

        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        try:
            from pathway_tpu.engine.flight_recorder import \
                live_inflight_by_thread

            inflight = live_inflight_by_thread()
        except Exception:
            inflight = {}
        new: list[tuple[tuple, bool]] = []
        for ident, frame in frames.items():
            if ident == self_ident:
                continue  # never profile the profiler into the profile
            name = names.get(ident)
            if name is None:
                continue
            role = thread_role(name)
            if role is None:
                continue  # non-engine threads are out of contract
            stack = []
            f = frame
            while f is not None and len(stack) < _MAX_STACK_DEPTH:
                code = f.f_code
                stack.append(
                    f"{code.co_name} "
                    f"({os.path.basename(code.co_filename)}:"
                    f"{f.f_lineno})")
                f = f.f_back
            stack.reverse()  # outermost first: collapsed-stack order
            device_leg = False
            op = inflight.get(ident)
            if op is not None:
                leg, op_name = op
                device_leg = leg == "device"
                stack.append(f"[{leg}:{op_name}]")
            new.append(((role, tuple(stack)), device_leg))
        if not new:
            return
        with self._lock:
            for key, device_leg in new:
                self.samples_total += 1
                if device_leg:
                    self.device_attributed_samples += 1
                if key in self._stacks:
                    self._stacks[key] += 1
                elif len(self._stacks) < _MAX_DISTINCT_STACKS:
                    self._stacks[key] = 1
                else:
                    # bounded memory: the long tail folds into one bucket
                    other = (key[0], ("(other)",))
                    self._stacks[other] = self._stacks.get(other, 0) + 1

    def stack_counts(self) -> dict[tuple, int]:
        """Snapshot of the folded-stack counters (for windowed diffs)."""
        with self._lock:
            return dict(self._stacks)

    def collapsed(self, baseline: dict | None = None) -> str:
        """Collapsed-flamegraph text: ``role;frame;frame count`` per
        line, descending count — feed straight to flamegraph.pl /
        speedscope. ``baseline`` (a prior :meth:`stack_counts` snapshot)
        restricts output to samples taken since it."""
        counts = self.stack_counts()
        rows = []
        for (role, stack), n in counts.items():
            if baseline is not None:
                n -= baseline.get((role, stack), 0)
            if n <= 0:
                continue
            rows.append((";".join((role,) + stack), n))
        rows.sort(key=lambda r: (-r[1], r[0]))
        return "\n".join(f"{stack} {n}" for stack, n in rows) + (
            "\n" if rows else "")

    def top_host_frame(self) -> str | None:
        """The leaf frame with the most samples (dashboard one-liner)."""
        leaf: dict[str, int] = {}
        with self._lock:
            for (_role, stack), n in self._stacks.items():
                if stack:
                    leaf[stack[-1]] = leaf.get(stack[-1], 0) + n
        if not leaf:
            return None
        return max(leaf.items(), key=lambda kv: kv[1])[0]

    def overhead_ratio(self) -> float:
        """Rolling self-overhead: seconds spent inside sample passes over
        sampler wall time. The contract is < 0.02 (2%)."""
        if self._sampler_started is None:
            return 0.0
        wall = time.monotonic() - self._sampler_started
        if wall <= 0.0:
            return 0.0
        return self._sample_cost_s / wall

    # -- device-side dispatch recording ------------------------------------
    def record_dispatch(self, family: str, flops: float, bytes_moved: float,
                        wall_ms: float) -> None:
        """Record one kernel dispatch: analytic (flops, bytes) from the
        cost model + call-site wall ms. Inside a bridge leg
        (begin_leg/end_leg wraps the worker) the record is buffered and
        re-timed to the leg's MEASURED execute time on end_leg; outside
        a leg (sync mode, or a blocking call site like the search's
        np.asarray) the call-site wall time stands."""
        buf = getattr(self._leg_local, "buf", None)
        if buf is not None:
            buf.records.append([family, flops, bytes_moved, wall_ms])
            return
        self._commit(family, flops, bytes_moved, wall_ms, attributed=False)

    def begin_leg(self, tick: int) -> None:
        """Bridge worker: start buffering this thread's dispatches (they
        belong to the device leg whose execute time is being measured)."""
        self._leg_local.buf = _LegBuffer(tick)

    def end_leg(self, exec_ms: float | None) -> None:
        """Bridge worker: leg finished after ``exec_ms`` measured ms (None
        = leg failed; the buffered records keep their call-site wall
        times). Buffered dispatch times are re-scaled pro-rata — by their
        own wall share when it is meaningful, by analytic bytes otherwise
        (async dispatches all return in ~0 host ms) — so per-family
        device time sums exactly to the bridge's measured leg time."""
        buf = getattr(self._leg_local, "buf", None)
        self._leg_local.buf = None
        if buf is None or not buf.records:
            return
        records = buf.records
        if exec_ms is None:
            for family, flops, nbytes, wall_ms in records:
                self._commit(family, flops, nbytes, wall_ms,
                             attributed=False)
            return
        wall_sum = sum(r[3] for r in records)
        if wall_sum > exec_ms * 0.05:
            weights = [r[3] / wall_sum for r in records]
        else:
            cost_sum = sum(r[2] for r in records) or float(len(records))
            weights = [(r[2] / cost_sum if cost_sum else 1.0 / len(records))
                       for r in records]
        for (family, flops, nbytes, _wall), w in zip(records, weights):
            self._commit(family, flops, nbytes, exec_ms * w,
                         attributed=True)

    @property
    def machine(self) -> dict | None:
        """The peaks in use, or None: no dispatch seen yet, or a device
        with no ``DEVICE_PEAKS`` row."""
        return self._machine

    def _commit(self, family: str, flops: float, bytes_moved: float,
                device_ms: float, attributed: bool) -> None:
        if not self._machine_resolved:
            self._machine = machine_params()
            self._machine_resolved = True
        now = time.monotonic()
        with self._lock:
            st = self._families.get(family)
            if st is None:
                st = self._families[family] = _FamilyStats()
            st.dispatches += 1
            st.flops_total += flops
            st.bytes_total += bytes_moved
            st.device_ms_total += device_ms
            if attributed:
                st.attributed += 1
            st.window.append((now, flops, bytes_moved, device_ms))

    # -- device-side read side ---------------------------------------------
    def _rolling(self, st: _FamilyStats, now: float) -> tuple:
        cutoff = now - self.window_s
        flops = nbytes = ms = 0.0
        n = 0
        for t, f, b, m in st.window:
            if t >= cutoff:
                flops += f
                nbytes += b
                ms += m
                n += 1
        return flops, nbytes, ms, n

    def family_stats(self) -> dict[str, dict]:
        """Per-family totals + rolling window, and — where the machine's
        peaks are known — utilization and the roofline classification
        (None otherwise: not measured)."""
        mp = self._machine
        peak_fps = peak_bps = balance = None
        if mp is not None:
            peak_fps = mp["peak_tflops"] * 1e12
            peak_bps = mp["hbm_gbps"] * 1e9
            balance = peak_fps / peak_bps

        def util(amount: float, ms: float, peak: float | None):
            if peak is None:
                return None
            return round(amount / (ms / 1e3) / peak, 6) if ms > 0 else 0.0

        now = time.monotonic()
        out: dict[str, dict] = {}
        with self._lock:
            items = list(self._families.items())
        for family, st in items:
            r_flops, r_bytes, r_ms, r_n = self._rolling(st, now)
            ai = (st.flops_total / st.bytes_total
                  if st.bytes_total > 0 else 0.0)
            roofline = {"arithmetic_intensity": round(ai, 4),
                        "machine_balance": None, "bound_by": None,
                        "attainable_mfu": None}
            if balance is not None:
                roofline.update({
                    "machine_balance": round(balance, 4),
                    "bound_by": ("compute" if ai >= balance
                                 else "bandwidth"),
                    # attainable fraction of peak at this AI — the
                    # roofline ceiling the family could reach at best
                    "attainable_mfu": round(min(1.0, ai / balance), 6),
                })
            out[family] = {
                "dispatches": st.dispatches,
                "attributed_dispatches": st.attributed,
                "flops_total": st.flops_total,
                "bytes_total": st.bytes_total,
                "device_ms_total": round(st.device_ms_total, 3),
                "rolling": {
                    "dispatches": r_n,
                    "device_ms": round(r_ms, 3),
                    "mfu": util(r_flops, r_ms, peak_fps),
                    "hbm_bw_util": util(r_bytes, r_ms, peak_bps),
                },
                "mfu": util(st.flops_total, st.device_ms_total, peak_fps),
                "hbm_bw_util": util(st.bytes_total, st.device_ms_total,
                                    peak_bps),
                "roofline": roofline,
            }
        return out

    def _rolling_util(self, column: int, peak_key: str,
                      scale: float) -> float | None:
        mp = self._machine
        if mp is None:
            return None
        now = time.monotonic()
        amount = ms = 0.0
        with self._lock:
            fams = list(self._families.values())
        for st in fams:
            rolled = self._rolling(st, now)
            amount += rolled[column]
            ms += rolled[2]
        if ms <= 0.0:
            return 0.0
        return amount / (ms / 1e3) / (mp[peak_key] * scale)

    def rolling_mfu(self) -> float | None:
        """Rolling model-FLOPs utilization across every family: window
        FLOPs over window device-seconds, against peak. None when the
        machine's peaks are not known."""
        return self._rolling_util(0, "peak_tflops", 1e12)

    def rolling_hbm_bw_util(self) -> float | None:
        """Rolling HBM bandwidth utilization across every family (None
        when the machine's peaks are not known)."""
        return self._rolling_util(1, "hbm_gbps", 1e9)

    # -- on-demand XLA capture ---------------------------------------------
    def start_device_capture(self, out_dir: str | None = None) -> str:
        """Start a jax.profiler trace into ``out_dir`` (default: a fresh
        ``pathway-profile-<pid>-<n>`` under PATHWAY_PROFILE_DIR or the
        tmpdir). Returns the artifact directory. One capture at a time."""
        if self._capture_dir is not None:
            raise RuntimeError(
                f"device capture already running -> {self._capture_dir}")
        if out_dir is None:
            import tempfile

            base = os.environ.get("PATHWAY_PROFILE_DIR",
                                  tempfile.gettempdir())
            out_dir = os.path.join(
                base, f"pathway-profile-{os.getpid()}"
                      f"-{self.captures_total}")
        os.makedirs(out_dir, exist_ok=True)
        import jax

        jax.profiler.start_trace(out_dir)
        self._capture_dir = out_dir
        return out_dir

    def stop_device_capture(self) -> str:
        """Stop the running capture; returns the artifact directory."""
        if self._capture_dir is None:
            raise RuntimeError("no device capture running")
        out_dir = self._capture_dir
        self._capture_dir = None
        import jax

        jax.profiler.stop_trace()
        self.captures_total += 1
        return out_dir

    # -- snapshots ----------------------------------------------------------
    def stats(self) -> dict:
        """The /status.profiler section (and the dashboard panel feed)."""
        with self._lock:
            distinct = len(self._stacks)
        return {
            "host": {
                "sampling": self._thread is not None
                and self._thread.is_alive(),
                "sample_interval_ms": round(
                    self.sample_interval_s * 1e3, 3),
                "samples_total": self.samples_total,
                "device_attributed_samples":
                    self.device_attributed_samples,
                "distinct_stacks": distinct,
                "overhead_ratio": round(self.overhead_ratio(), 6),
                "top_frame": self.top_host_frame(),
            },
            "machine": None if self._machine is None else {
                **self._machine,
                "balance_flop_per_byte": round(
                    machine_balance(self._machine), 4)},
            "mfu_rolling": _round6(self.rolling_mfu()),
            "hbm_bw_util": _round6(self.rolling_hbm_bw_util()),
            "families": self.family_stats(),
            "capture": {
                "running": self._capture_dir is not None,
                "dir": self._capture_dir,
                "captures_total": self.captures_total,
            },
        }

    def profile_epoch(self) -> dict:
        """One embeddable profile snapshot (bench.py --profile writes a
        list of these into BENCH_*.json; profdiff compares two)."""
        counts = self.stack_counts()
        frames: dict[str, int] = {}
        for (_role, stack), n in counts.items():
            for fr in stack:
                frames[fr] = frames.get(fr, 0) + n
        top = sorted(frames.items(), key=lambda kv: -kv[1])[:40]
        return {
            "at": time.time(),
            "machine": self._machine,
            "mfu_rolling": _round6(self.rolling_mfu()),
            "hbm_bw_util": _round6(self.rolling_hbm_bw_util()),
            "families": self.family_stats(),
            "host": {
                "samples_total": self.samples_total,
                "overhead_ratio": round(self.overhead_ratio(), 6),
                "top_frames": [{"frame": f, "samples": n} for f, n in top],
            },
        }


def _round6(x: float | None) -> float | None:
    return None if x is None else round(x, 6)


# ---------------------------------------------------------------------------
# profdiff: name the dominant frame/kernel delta between two profiles
# ---------------------------------------------------------------------------

def _profile_of(doc: dict) -> dict | None:
    """Accept a bare profile epoch, a {"profile": [...]} bench artifact
    (last epoch wins — it saw the most work), or None."""
    if not isinstance(doc, dict):
        return None
    if "families" in doc or "host" in doc:
        return doc
    epochs = doc.get("profile")
    if isinstance(epochs, list) and epochs:
        return epochs[-1]
    if isinstance(epochs, dict):
        return epochs
    return None


def diff_profiles(a: dict, b: dict) -> dict:
    """Compare two profile snapshots (A = baseline/median, B = flagged
    run): per-kernel-family device-ms deltas and per-host-frame sample-
    share deltas, each naming its dominant regressor. Pure function over
    the JSON bench.py --profile embeds; ``python -m pathway_tpu profdiff
    A.json B.json`` and ``bench.py --check-regression`` both call it."""
    pa, pb = _profile_of(a), _profile_of(b)
    if pa is None or pb is None:
        raise ValueError(
            "no profile data found — run bench.py --profile so "
            "BENCH_*.json embeds profile epochs")
    out: dict = {"kernel_deltas": [], "frame_deltas": []}
    fams = set(pa.get("families", {})) | set(pb.get("families", {}))
    for fam in sorted(fams):
        fa = pa.get("families", {}).get(fam, {})
        fb = pb.get("families", {}).get(fam, {})
        ma = float(fa.get("device_ms_total", 0.0))
        mb = float(fb.get("device_ms_total", 0.0))
        da = max(1, int(fa.get("dispatches", 0) or 0))
        db = max(1, int(fb.get("dispatches", 0) or 0))
        per_a, per_b = ma / da, mb / db
        out["kernel_deltas"].append({
            "family": fam,
            "device_ms_per_dispatch_a": round(per_a, 4),
            "device_ms_per_dispatch_b": round(per_b, 4),
            "delta_ms_per_dispatch": round(per_b - per_a, 4),
            "ratio": round(per_b / per_a, 4) if per_a > 0 else None,
            "bound_by": fb.get("roofline", {}).get("bound_by")
            or fa.get("roofline", {}).get("bound_by"),
        })
    out["kernel_deltas"].sort(key=lambda d: -abs(d["delta_ms_per_dispatch"]))

    def shares(p: dict) -> dict[str, float]:
        host = p.get("host", {})
        total = max(1, int(host.get("samples_total", 0) or 0))
        return {e["frame"]: e["samples"] / total
                for e in host.get("top_frames", [])}

    sa, sb = shares(pa), shares(pb)
    for frame in sorted(set(sa) | set(sb)):
        d = sb.get(frame, 0.0) - sa.get(frame, 0.0)
        out["frame_deltas"].append({
            "frame": frame,
            "share_a": round(sa.get(frame, 0.0), 4),
            "share_b": round(sb.get(frame, 0.0), 4),
            "delta_share": round(d, 4),
        })
    out["frame_deltas"].sort(key=lambda d: -abs(d["delta_share"]))
    out["dominant_kernel"] = (out["kernel_deltas"][0]
                              if out["kernel_deltas"] else None)
    out["dominant_frame"] = (out["frame_deltas"][0]
                             if out["frame_deltas"] else None)
    mfu_a = pa.get("mfu_rolling")
    mfu_b = pb.get("mfu_rolling")
    if mfu_a is not None and mfu_b is not None:
        out["mfu_rolling_delta"] = round(float(mfu_b) - float(mfu_a), 6)
    return out

"""Diagnostic objects for the static pipeline analyzer.

Each finding is a :class:`Diagnostic` with a stable code (``PWT001``…),
a severity, a human message, and — whenever the offending operator captured
one — the user stack frame from the plan's build-time trace
(internals/trace.py), so a diagnostic points at the user's line, exactly
like runtime operator errors do.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from pathway_tpu.internals.trace import Trace


class Severity(enum.Enum):
    ERROR = "error"
    WARNING = "warning"
    INFO = "info"

    def __str__(self) -> str:  # "error" in rendered diagnostics
        return self.value


#: code -> (default severity, one-line summary). The single source of truth
#: for what the analyzer can emit; README's "Static checks" section mirrors it.
CODES: dict[str, tuple[Severity, str]] = {
    "PWT000": (Severity.ERROR,
               "pipeline script failed to import / collect"),
    "PWT001": (Severity.ERROR,
               "binary operation on incompatible column dtypes"),
    "PWT002": (Severity.ERROR,
               "cast/convert between incompatible dtypes"),
    "PWT003": (Severity.ERROR,
               "join/groupby key columns have incompatible dtypes"),
    "PWT004": (Severity.WARNING,
               "dead dataflow: table computed but never reaches a sink"),
    "PWT005": (Severity.WARNING,
               "streaming source never reaches an output binder"),
    "PWT006": (Severity.WARNING,
               "non-deterministic or async UDF feeds a persisted pipeline"),
    "PWT007": (Severity.ERROR,
               "universe mismatch the solver would reject at runtime"),
    "PWT008": (Severity.WARNING,
               "get()/ix default dtype silently widens the column"),
    "PWT009": (Severity.WARNING,
               "sink schema incompatible with the connector's format"),
    "PWT010": (Severity.INFO,
               "redundant cast: expression already has the target dtype"),
    "PWT011": (Severity.ERROR,
               "ix key expression is not a pointer type"),
    "PWT012": (Severity.WARNING,
               "streaming source with max_retries=0 under "
               "terminate_on_error=False: a crash silently drops the "
               "source"),
    "PWT013": (Severity.WARNING,
               "SLO target configured (PATHWAY_SLO_E2E_MS) but the "
               "pipeline serves with QoS disabled: latency is measured "
               "but nothing acts on it"),
    # -- PWT1xx: sharding / placement (static_check/shard_check.py) --------
    "PWT101": (Severity.ERROR,
               "mesh axis sizes do not fit the device count"),
    "PWT102": (Severity.ERROR,
               "sharded leading dimension not divisible by the mesh axis "
               "(silent replication/padding)"),
    "PWT103": (Severity.ERROR,
               "shard_map in/out specs inconsistent with operand rank or "
               "mesh axes"),
    "PWT104": (Severity.WARNING,
               "operands placed on different meshes: every batch pays an "
               "implicit cross-topology gather"),
    "PWT105": (Severity.WARNING,
               "host-device sync point inside a per-batch path"),
    "PWT106": (Severity.ERROR,
               "head-parallel attention: heads not divisible by the axis "
               "size"),
    "PWT107": (Severity.INFO,
               "model axis configured but nothing in the pipeline is "
               "model-parallel (silent weight replication)"),
    "PWT109": (Severity.WARNING,
               "host-only UDF on a streaming hot path"),
    "PWT110": (Severity.INFO,
               "jit-traceable UDF dispatched row-by-row: auto-jitted at "
               "runtime when PATHWAY_AUTO_JIT=1, else a batch=True "
               "candidate"),
    "PWT111": (Severity.WARNING,
               "paged store reservation/tenant quota not page-aligned, or "
               "tenant quotas sum past device HBM"),
    # -- PWT2xx: concurrency (static_check/concurrency_check.py) -----------
    # Source-level AST analysis over the multi-threaded engine itself
    # (engine/, io/, parallel/), not the plan DAG: thread inventory, lock
    # inventory, lock-order graph. Runtime twin: PATHWAY_LOCK_SANITIZER
    # (engine/locking.py).
    "PWT201": (Severity.ERROR,
               "lock-order inversion: a cycle in the global lock "
               "acquisition-order graph (some interleaving deadlocks)"),
    "PWT202": (Severity.ERROR,
               "attribute written from two or more thread roots with no "
               "common lock guard"),
    "PWT203": (Severity.WARNING,
               "lock held across a known-blocking call (fsync, socket "
               "send/recv, bridge submit, device dispatch)"),
    "PWT204": (Severity.WARNING,
               "daemon thread spawned with no stop/join path (its handle "
               "is dropped; nothing can ever wait it out)"),
    "PWT205": (Severity.ERROR,
               "Condition.wait outside a predicate re-check loop (misses "
               "spurious wake-ups and missed-notify races)"),
    "PWT206": (Severity.WARNING,
               "sleep-polling loop where an Event exists (use Event.wait: "
               "immediate wake-up, no poll latency)"),
    "PWT207": (Severity.WARNING,
               "thread or lock primitive constructed bare instead of "
               "through the engine factories (threads.py spawn / "
               "locking.py create_*: excepthook, inventory and sanitizer "
               "coverage)"),
    "PWT208": (Severity.ERROR,
               "Condition.notify/notify_all outside the condition's "
               "`with` block (raises RuntimeError at runtime)"),
    # -- PWT3xx: durability / crash-recovery (static_check/
    # durability_check.py). Source-level AST analysis over the
    # persistence plane (engine/, io/): snapshot coverage, atomic-write
    # discipline, fault-point coverage, restore-path safety. Runtime
    # twin: PATHWAY_SNAPSHOT_SANITIZER (engine/snapshot_sanitizer.py).
    "PWT301": (Severity.WARNING,
               "stateful operator mutates state on step/drain paths but "
               "defines no snapshot_state/restore_state pair (silent "
               "degradation to full-WAL replay on recovery)"),
    "PWT302": (Severity.ERROR,
               "capture/restore asymmetry: a snapshot state key captured "
               "but never restored, or restored but never captured"),
    "PWT303": (Severity.ERROR,
               "hash()/id()/fingerprint-keyed container in snapshotted "
               "state restored without a stable re-key (keys from the "
               "writer process are meaningless in the restorer)"),
    "PWT304": (Severity.ERROR,
               "write to a persistence-root-derived path bypassing the "
               "atomic tmp+fsync+rename discipline (a crash mid-write "
               "leaves a torn file where a checkpoint should be)"),
    "PWT305": (Severity.WARNING,
               "blocking persistence I/O (fsync/truncate/put) with no "
               "named fault point in the enclosing function — the crash "
               "edge is not injectable by testing/faults.py"),
    "PWT306": (Severity.ERROR,
               "unrestricted pickle.load/loads/Unpickler on a restore "
               "path (use persistence._safe_loads: arbitrary-code "
               "execution from a corrupt or hostile snapshot)"),
    "PWT307": (Severity.ERROR,
               "Session.drain outside the atomic seal_drain helper on a "
               "persisted streaming path (drained rows can be lost "
               "between drain and seal on crash)"),
    "PWT308": (Severity.WARNING,
               "nondeterminism source (time.time, random, os.urandom, "
               "uuid4) feeds snapshotted state — restored replicas "
               "diverge from the writer"),
    # -- PWT4xx: device-path perf discipline (static_check/
    # perf_check.py). Source-level AST analysis over the serving hot
    # path (engine/, ops/, models/, parallel/): recompile zoos, hidden
    # host-device syncs, per-row dispatch, residency and donation
    # discipline. Runtime twin: PATHWAY_DEVICE_SANITIZER
    # (engine/device_sanitizer.py).
    "PWT401": (Severity.ERROR,
               "jitted callable dispatched with an unbucketed data-"
               "dependent shape (every distinct length compiles a fresh "
               "executable — a recompile zoo on the serving path)"),
    "PWT402": (Severity.ERROR,
               "host-device sync point (.item()/.tolist()/int()/float()/"
               "np.asarray/bare block_until_ready) on a per-batch path "
               "outside instrumentation code"),
    "PWT403": (Severity.WARNING,
               "per-row device dispatch inside a Python loop where a "
               "batched/vmapped kernel exists in the same module"),
    "PWT404": (Severity.WARNING,
               "implicit host→device transfer per tick: numpy operand "
               "fed to a jitted callable with no device residency or "
               "device_put upstream"),
    "PWT405": (Severity.ERROR,
               "float64/weak-type promotion reaching kernel code (TPUs "
               "emulate f64 at ~1/10 throughput; one stray dtype "
               "contaminates every downstream op)"),
    "PWT406": (Severity.ERROR,
               "donated buffer read after donation (XLA may have reused "
               "the memory: garbage values or a crash, backend-"
               "dependent)"),
    "PWT407": (Severity.WARNING,
               "jitted serving entry point absent from pw.warmup's "
               "bucket registry (the cold compile lands on the first "
               "real query instead of warmup)"),
    "PWT408": (Severity.WARNING,
               "blocking host I/O (file/socket/log flush) inside a "
               "device-leg function (stalls the dispatch pipeline for "
               "host I/O time)"),
}


@dataclass(frozen=True)
class Diagnostic:
    """One static-analysis finding."""

    code: str
    message: str
    severity: Severity | None = None
    trace: Trace | None = None
    table: str | None = None
    # secondary provenance (e.g. the other table of a universe mismatch)
    related: tuple[Trace, ...] = field(default=())

    def __post_init__(self):
        if self.code not in CODES:
            raise ValueError(f"unknown diagnostic code {self.code!r}")
        if self.severity is None:
            object.__setattr__(self, "severity", CODES[self.code][0])

    @property
    def is_error(self) -> bool:
        return self.severity is Severity.ERROR

    def to_dict(self) -> dict:
        """Flat machine-readable form (CLI ``--json`` / CI annotations)."""
        return {
            "code": self.code,
            "severity": str(self.severity),
            "message": self.message,
            "table": self.table,
            "file": self.trace.file_name if self.trace else None,
            "line": self.trace.line_number if self.trace else None,
        }

    def __str__(self) -> str:
        where = f" [{self.table}]" if self.table else ""
        out = f"{self.code} {self.severity}{where}: {self.message}"
        if self.trace is not None:
            out += f"\n{self.trace}"
        for t in self.related:
            out += f"\n  related:\n{t}"
        return out


class StaticCheckError(RuntimeError):
    """Raised by ``pw.run(static_check='error')`` when the analyzer finds
    error-severity diagnostics. Carries the full diagnostic list."""

    def __init__(self, diagnostics: list[Diagnostic]):
        self.diagnostics = diagnostics
        errors = [d for d in diagnostics if d.is_error]
        lines = "\n\n".join(str(d) for d in errors)
        super().__init__(
            f"static check failed with {len(errors)} error(s):\n{lines}")


def render(diagnostics: list[Diagnostic]) -> str:
    """Multi-line human rendering, errors first."""
    order = {Severity.ERROR: 0, Severity.WARNING: 1, Severity.INFO: 2}
    ranked = sorted(diagnostics, key=lambda d: order[d.severity])
    return "\n\n".join(str(d) for d in ranked)

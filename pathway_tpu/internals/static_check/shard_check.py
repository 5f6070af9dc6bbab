"""Static sharding/placement analysis — the PWT1xx diagnostic family.

PR 1's analyzer validates the logical plan (dtypes, dead dataflow,
formats); this pass validates the layer where pod-scale outages actually
live: mesh/topology misconfiguration, slab shapes that silently replicate
or pad over the ``data`` axis, shard_map specs inconsistent with their
operands, index slabs placed on a different topology than the pipeline,
and Python UDFs that force host round-trips on per-batch paths.

Three check layers, mirroring the runtime stack:

1. **mesh/topology** — the analysis mesh (``--tpu-mesh data×model`` on the
   CLI, ``mesh=`` on :func:`pw.static_check`, ``PATHWAY_STATIC_CHECK_MESH``
   for ``pw.run``) is validated against env-var overrides (PWT101); slab
   reservations and kernel operand shapes are checked for data-axis
   divisibility (PWT102) using the SAME layout helpers the kernels size
   themselves with (parallel/sharded_knn.py ``slab_cap_per_shard`` /
   ``search_operand_layout``); shard_map in/out specs are checked against
   operand ranks and mesh axes (PWT103).
2. **placement/comms** — external-index slabs pinned to a mesh other than
   the analysis mesh flag the implicit per-batch cross-topology gather
   (PWT104); UDFs containing host-device sync points — ``.item()``,
   ``np.asarray`` on device values, Python-loop reductions — on per-batch
   paths flag PWT105.
3. **UDF traceability** — an AST (bytecode fallback) classifier tags every
   sync ``pw.udf`` as jit-traceable / vmappable / host-only. Host-only UDFs
   on a streaming hot path flag PWT109; traceable ones dispatched row-by-row
   flag PWT110. The classification is recorded on the expression
   (``expr._shard_class``) and in ``Analyzer.udf_classifications``; the
   auto-jit tier (internals/autojit.py) consumes it at compile time to fuse
   the traceable/vmappable classes into vectorized device dispatches, so
   with auto-jit enabled PWT110 is informational ("will be auto-jitted")
   rather than a manual-rewrite prompt.

Everything here is metadata-only: no device is touched, jax is never
imported — a hypothetical topology can be analyzed on a laptop that owns
no hardware.
"""

from __future__ import annotations

import ast
import inspect
import os
import textwrap
from dataclasses import dataclass

from pathway_tpu.internals import expression as ex
from pathway_tpu.internals.static_check.diagnostics import Diagnostic
from pathway_tpu.internals.trace import Trace

# axis names mirror parallel/mesh.py (not imported: that module pulls jax
# at mesh-construction time; the checker must stay importable without it)
DATA_AXIS = "data"
MODEL_AXIS = "model"


# ---------------------------------------------------------------------------
# mesh topology
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshSpec:
    """A (data, model) topology to analyze against — real or hypothetical."""

    data: int
    model: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.model

    def __str__(self) -> str:
        return f"{self.data}x{self.model}"


def parse_mesh_spec(value) -> MeshSpec | None:
    """Coerce any mesh-ish value to a :class:`MeshSpec` (or None).

    Accepts ``None``, a MeshSpec, a ``parallel.mesh.MeshConfig``, a
    ``jax.sharding.Mesh`` (its shape dict is read, jax is not imported),
    or a string ``"4x2"`` / ``"4×2"`` / ``"8"`` (model defaults to 1).
    """
    if value is None:
        return None
    if isinstance(value, MeshSpec):
        return value
    if isinstance(value, str):
        text = value.strip().lower().replace("×", "x").replace("*", "x")
        parts = [p for p in text.split("x") if p]
        try:
            dims = [int(p) for p in parts]
        except ValueError:
            dims = []
        if len(dims) == 1:
            dims.append(1)
        if len(dims) != 2 or any(d < 1 for d in dims):
            raise ValueError(
                f"cannot parse mesh spec {value!r}: expected 'DATAxMODEL' "
                "with positive integers, e.g. '4x2'")
        return MeshSpec(data=dims[0], model=dims[1])
    shape = getattr(value, "shape", None)
    if shape is not None and hasattr(shape, "get"):  # jax Mesh / Mapping
        return MeshSpec(data=int(shape.get(DATA_AXIS, 1)),
                        model=int(shape.get(MODEL_AXIS, 1)))
    data = getattr(value, "data", None)
    model = getattr(value, "model", None)
    if isinstance(data, int):  # parallel.mesh.MeshConfig (duck-typed)
        return MeshSpec(data=data, model=model if isinstance(model, int) else 1)
    raise ValueError(f"cannot interpret {value!r} as a mesh spec")


def check_mesh_fits(data: int, model: int, n_devices: int, *,
                    source: str = "mesh") -> list[Diagnostic]:
    """PWT101: axis sizes must fit — and tile — the device count.

    Delegates to ``MeshConfig.validate`` (parallel/mesh.py), the same rule
    ``MeshConfig.from_env`` enforces eagerly at runtime — a topology the
    checker flags is exactly one the runtime would refuse to build.
    """
    from pathway_tpu.parallel.mesh import MeshConfig

    return [
        Diagnostic(
            "PWT101",
            f"{source}: {problem} — fix: choose axis sizes whose product "
            f"divides {n_devices}")
        for problem in MeshConfig(data=data, model=model).validate(n_devices)
    ]


def check_sharded_dim(size: int | None, axis_size: int, *,
                      axis: str = DATA_AXIS,
                      what: str = "sharded operand") -> list[Diagnostic]:
    """PWT102: a dim sharded over ``axis`` must be divisible by its size."""
    if size is None or axis_size <= 1:
        return []
    if size % axis_size != 0:
        per = -(-size // axis_size)  # ceil
        pad = per * axis_size - size
        return [Diagnostic(
            "PWT102",
            f"{what}: leading dimension {size} is not divisible by the "
            f"{axis!r} axis size {axis_size} — each shard pads to {per} "
            f"rows ({pad} rows of silent replication/padding, skewed "
            f"shards) — fix: make it a multiple of {axis_size}")]
    return []


def check_shard_specs(mesh_axes: dict, in_specs, in_ranks,
                      out_specs=(), out_ranks=()) -> list[Diagnostic]:
    """PWT103: shard_map specs must match operand ranks and mesh axes.

    ``in_specs``/``out_specs`` are symbolic: each spec is a tuple with one
    entry per leading operand dim — ``None`` (replicated) or an axis name
    (see ``parallel.sharded_knn.search_operand_layout``). A real
    ``jax.sharding.PartitionSpec`` also works (it iterates the same way).
    """
    out: list[Diagnostic] = []

    def _check(kind, specs, ranks):
        for i, (spec, rank) in enumerate(zip(specs, ranks)):
            entries = tuple(spec)
            if len(entries) > rank:
                out.append(Diagnostic(
                    "PWT103",
                    f"{kind}[{i}]: spec {entries!r} names "
                    f"{len(entries)} dims but the operand has rank {rank} — "
                    f"fix: drop spec entries or pass a higher-rank operand"))
            for entry in entries:
                axes = entry if isinstance(entry, tuple) else (entry,)
                for a in axes:
                    if a is None:
                        continue
                    if a not in mesh_axes:
                        out.append(Diagnostic(
                            "PWT103",
                            f"{kind}[{i}]: spec names mesh axis {a!r} but "
                            f"the mesh only has axes "
                            f"{sorted(mesh_axes)} — fix: use one of those "
                            f"or add the axis to the mesh"))

    _check("in_specs", in_specs, in_ranks)
    _check("out_specs", out_specs, out_ranks)
    return out


def check_attention_sharding(shape, mesh: MeshSpec | str, *,
                             scheme: str = "ring",
                             axis: str = DATA_AXIS) -> list[Diagnostic]:
    """Shape pre-check for the sequence-parallel attention kernels.

    ``shape`` is the global (batch, seq, heads, head_dim). Ring attention
    shards ``seq`` over the axis (PWT102 on non-divisibility); Ulysses
    additionally re-shards to head-parallel and needs
    ``heads % axis_size == 0`` (PWT106).
    """
    spec = parse_mesh_spec(mesh)
    n = spec.data if axis == DATA_AXIS else spec.model
    _b, s, h, _d = shape
    out = check_sharded_dim(
        s, n, axis=axis,
        what=f"{scheme} attention sequence (shape {tuple(shape)})")
    if scheme == "ulysses" and n > 1 and h % n != 0:
        out.append(Diagnostic(
            "PWT106",
            f"ulysses attention: {h} heads not divisible by the {axis!r} "
            f"axis size {n} — the all_to_all re-shard to head-parallel "
            f"cannot split the head dim — fix: pad heads to a multiple of "
            f"{n} or use ring attention"))
    return out


def check_pipeline_layout(n_layers: int, n_stages: int) -> list[Diagnostic]:
    """PWT102 for the GPipe layer stack (parallel/pipeline.py): the stacked
    layer axis is sharded over the pipe axis."""
    return check_sharded_dim(
        n_layers, n_stages, axis="pipe",
        what=f"pipeline layer stack ({n_layers} layers over "
             f"{n_stages} stages)")


# ---------------------------------------------------------------------------
# UDF classifier: jit-traceable / vmappable / host-only
# ---------------------------------------------------------------------------

_KIND_ORDER = {"traceable": 0, "vmappable": 1, "host": 2}

# module aliases whose attribute calls trace into XLA
_NUMERIC_MODULES = {"np", "numpy", "jnp", "jax", "lax", "math"}
# math.* works per-scalar: vmap-able after a jnp rewrite, not jit-batchable
_SCALAR_MODULES = {"math"}
# attribute calls that force a device→host copy / synchronization
_SYNC_ATTRS = {"item", "tolist", "numpy", "block_until_ready",
               "copy_to_host_async"}
# numpy-namespace calls that materialize a host ndarray from their operand
_SYNC_NP_FNS = {"asarray", "array", "ascontiguousarray", "frombuffer"}
# per-scalar builtins a vmap rewrite can express
_VMAP_BUILTINS = {"abs", "min", "max", "round", "float", "int", "bool",
                  "divmod", "pow"}
# builtins that pin execution to the Python interpreter
_HOST_BUILTINS = {"open", "print", "input", "eval", "exec", "compile",
                  "len", "sum", "sorted", "list", "dict", "set", "tuple",
                  "str", "repr", "format", "zip", "enumerate", "map",
                  "filter", "iter", "next", "isinstance", "getattr",
                  "setattr", "hash", "id", "type", "vars", "globals"}


@dataclass(frozen=True)
class UdfClassification:
    """Outcome of :func:`classify_udf`.

    ``kind``: ``"traceable"`` (jit directly over batched columns),
    ``"vmappable"`` (per-row scalar code a vmap rewrite can batch) or
    ``"host"`` (must run on the Python interpreter). ``sync_points`` lists
    host-device synchronization constructs found regardless of kind.
    """

    kind: str
    reasons: tuple[str, ...] = ()
    sync_points: tuple[str, ...] = ()

    @property
    def jit_eligible(self) -> bool:
        return self.kind in ("traceable", "vmappable")


class _UdfVisitor(ast.NodeVisitor):
    def __init__(self):
        self.kind = "traceable"
        self.reasons: list[str] = []
        self.sync_points: list[str] = []

    def _bump(self, kind: str, reason: str) -> None:
        if _KIND_ORDER[kind] > _KIND_ORDER[self.kind]:
            self.kind = kind
        if reason not in self.reasons:
            self.reasons.append(reason)

    def _sync(self, what: str) -> None:
        if what not in self.sync_points:
            self.sync_points.append(what)

    # control flow ----------------------------------------------------------
    def visit_If(self, node):
        self._bump("host", "data-dependent `if` statement (jit cannot "
                           "trace Python branches)")
        self.generic_visit(node)

    def visit_While(self, node):
        self._bump("host", "data-dependent `while` loop")
        self.generic_visit(node)

    def visit_For(self, node):
        self._bump("host", "Python `for` loop over row values")
        if any(isinstance(n, ast.AugAssign) for n in ast.walk(node)):
            self._sync("Python-loop reduction (accumulates element by "
                       "element on the host)")
        self.generic_visit(node)

    def visit_IfExp(self, node):
        self._bump("vmappable", "scalar conditional expression "
                                "(jnp.where under vmap)")
        self.generic_visit(node)

    # interpreter-only constructs -------------------------------------------
    def visit_Try(self, node):
        self._bump("host", "try/except block")
        self.generic_visit(node)

    def visit_With(self, node):
        self._bump("host", "context manager")
        self.generic_visit(node)

    visit_AsyncWith = visit_With

    def visit_Raise(self, node):
        self._bump("host", "raise statement")
        self.generic_visit(node)

    def visit_Await(self, node):
        self._bump("host", "await (event-loop bound)")
        self.generic_visit(node)

    def visit_Yield(self, node):
        self._bump("host", "generator")
        self.generic_visit(node)

    visit_YieldFrom = visit_Yield

    def visit_ListComp(self, node):
        self._bump("host", "Python comprehension")
        self.generic_visit(node)

    visit_SetComp = visit_ListComp
    visit_DictComp = visit_ListComp
    visit_GeneratorExp = visit_ListComp

    def visit_JoinedStr(self, node):
        self._bump("host", "string formatting")
        self.generic_visit(node)

    # calls -----------------------------------------------------------------
    def visit_Call(self, node):
        func = node.func
        if isinstance(func, ast.Name):
            name = func.id
            if name in _VMAP_BUILTINS:
                if name in ("int", "float", "bool") and node.args \
                        and not isinstance(node.args[0], ast.Constant):
                    # the cast's implicit .item() blocks until the device
                    # flushes — the sync form PWT105's original list
                    # missed (PWT402 widened the contract; this keeps
                    # classify_udf's view consistent with it)
                    self._sync(f"{name}() cast on a device value blocks "
                               "on an implicit .item()")
                self._bump("vmappable",
                           f"scalar builtin {name}() (vmap-able)")
            elif name in _HOST_BUILTINS:
                self._bump("host", f"host builtin {name}()")
            elif name not in ("jit", "vmap"):
                self._bump("host", f"call to {name}() (not a traceable "
                                   "numeric primitive)")
        elif isinstance(func, ast.Attribute):
            owner = func.value
            attr = func.attr
            if isinstance(owner, ast.Name) and owner.id in _NUMERIC_MODULES:
                if owner.id in ("np", "numpy") and attr in _SYNC_NP_FNS:
                    self._sync(f"{owner.id}.{attr}() on a device value "
                               "forces a device→host transfer")
                if owner.id in _SCALAR_MODULES:
                    self._bump("vmappable",
                               f"{owner.id}.{attr}() is per-scalar "
                               "(vmap-able after a jnp rewrite)")
                # numeric-namespace call: traceable, keep walking args
            elif attr in _SYNC_ATTRS:
                self._sync(f".{attr}() forces a device→host sync")
                self._bump("vmappable",
                           f".{attr}() yields a Python scalar")
            else:
                self._bump("host",
                           f"method call .{attr}() on a row value "
                           "(untraceable)")
        self.generic_visit(node)


def _function_node(fn):
    """The ast FunctionDef/Lambda for ``fn``, or None."""
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return None
    try:
        tree = ast.parse(src)
    except SyntaxError:
        # a lambda sharing its line with surrounding code: retry just the
        # fragment from the first `lambda` keyword
        i = src.find("lambda")
        if i < 0:
            return None
        frag = src[i:].rstrip().rstrip("),]}")
        try:
            tree = ast.parse(frag, mode="eval")
        except SyntaxError:
            return None
    name = getattr(fn, "__name__", "<lambda>")
    candidates = [n for n in ast.walk(tree)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and n.name == name]
    if candidates:
        return candidates[0]
    lambdas = [n for n in ast.walk(tree) if isinstance(n, ast.Lambda)]
    if lambdas:
        return lambdas[0]
    return None


def _classify_bytecode(fn) -> UdfClassification:
    """Source-less fallback: judge by the globals the code object touches
    and its control-flow opcodes (co_names alone misses pure-local
    loops/branches, which would mis-classify them traceable)."""
    import dis

    code = getattr(fn, "__code__", None)
    if code is None:
        return UdfClassification(
            "host", ("no Python source or bytecode available — "
                     "classified host-only",))
    names = set(code.co_names)
    host = sorted((names - _NUMERIC_MODULES) & (_HOST_BUILTINS | {
        "os", "sys", "time", "random", "requests", "socket", "subprocess",
        "pickle", "json", "re", "hashlib", "urllib", "logging"}))
    if host:
        return UdfClassification(
            "host", tuple(f"bytecode touches host global {n!r}"
                          for n in host))
    branchy = any(
        ins.opname == "FOR_ITER" or "JUMP" in ins.opname
        for ins in dis.get_instructions(code))
    if branchy:
        return UdfClassification(
            "host", ("source unavailable; bytecode contains data-dependent "
                     "control flow — classified host-only",))
    if names <= _NUMERIC_MODULES | {"jit", "vmap"}:
        return UdfClassification(
            "traceable", ("straight-line bytecode touching only numeric "
                          "modules",))
    return UdfClassification(
        "host", ("source unavailable; bytecode references "
                 f"{sorted(names)[:4]!r} — classified host-only",))


def classify_udf(fn) -> UdfClassification:
    """Tag a UDF as jit-traceable / vmappable / host-only.

    AST-based when the source is retrievable, bytecode heuristics
    otherwise. Conservative by design: anything not provably expressible
    as traced numeric code classifies ``host``.
    """
    fn = inspect.unwrap(fn)
    if inspect.iscoroutinefunction(fn):
        return UdfClassification("host", ("async (event-loop bound)",))
    node = _function_node(fn)
    if node is None:
        return _classify_bytecode(fn)
    visitor = _UdfVisitor()
    body = node.body if isinstance(node.body, list) else [node.body]
    for stmt in body:
        visitor.visit(stmt)
    return UdfClassification(visitor.kind, tuple(visitor.reasons),
                             tuple(visitor.sync_points))


_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _udf_def_trace(fn) -> Trace | None:
    """Where the UDF is *defined* (vs. where it is applied, which the
    diagnostic's main trace carries). PWT105 attaches this as a related
    trace so ``check --all`` can tell whether the definition lives in a
    tree the PWT4xx device-path lint already scanned — and defer to
    PWT402 there instead of double-reporting the same sync."""
    code = getattr(fn, "__code__", None)
    if code is None:
        code = getattr(getattr(fn, "func", None), "__code__", None)
    if code is None:
        return None
    return Trace(code.co_filename, code.co_firstlineno,
                 getattr(fn, "__name__", "<udf>"), "")


def _is_framework_fn(fn) -> bool:
    """True for callables defined inside pathway_tpu itself — their
    placement is the framework's concern, not a user diagnostic."""
    code = getattr(inspect.unwrap(fn), "__code__", None)
    if code is None:
        return False
    return os.path.abspath(code.co_filename).startswith(_PKG_ROOT + os.sep)


def _udf_key(fn) -> str:
    """Stable registry key for a UDF: qualname plus definition site, so two
    lambdas (or same-named functions in different modules) never collide."""
    base = getattr(fn, "__qualname__",
                   getattr(fn, "__name__", repr(fn)))
    code = getattr(inspect.unwrap(fn), "__code__", None)
    if code is None:
        return base
    return f"{base} [{code.co_filename}:{code.co_firstlineno}]"


# ---------------------------------------------------------------------------
# plan-level shard checker (driven by the Analyzer)
# ---------------------------------------------------------------------------

class ShardChecker:
    """Second-family pass over an already-walked plan DAG.

    Consumes the base :class:`Analyzer`'s node map and reporting helpers so
    PWT1xx diagnostics carry the same trace/dedup machinery as PWT0xx.
    ``analyzer.mesh`` (a :class:`MeshSpec` or None) is the topology under
    analysis; mesh-independent checks (UDF traceability, fused-slab
    hazards) run either way.
    """

    def __init__(self, analyzer):
        self.a = analyzer
        self.mesh: MeshSpec | None = analyzer.mesh

    # -- entry --------------------------------------------------------------
    def run(self, checked_ids: set[int] | None) -> None:
        """``checked_ids``: node ids to analyze (None = all nodes)."""
        nodes = [n for n in self.a._nodes.values()
                 if checked_ids is None or id(n.table) in checked_ids]
        if self.a.mesh_error is not None:
            self.a._report(
                "PWT101",
                f"analysis mesh is unusable: {self.a.mesh_error} — the "
                f"mesh-dependent checks were skipped")
        self._check_env_mesh()
        streaming = self._streaming_downstream()
        saw_model_parallel = False
        for node in nodes:
            plan = node.table._plan
            if plan.kind == "external_index":
                saw_model_parallel |= self._check_external_index(node)
            hot = id(node.table) in streaming
            for e in node.exprs:
                for sub in ex.walk(e):
                    if isinstance(sub, ex.ApplyExpression):
                        self._check_udf_placement(node, sub, hot=hot)
        if (self.mesh is not None and self.mesh.model > 1
                and not saw_model_parallel):
            self.a._report(
                "PWT107",
                f"analysis mesh {self.mesh} has model={self.mesh.model} but "
                f"nothing in the pipeline is model-parallel — model-axis "
                f"chips only replicate state ({self.mesh.model}x HBM for "
                f"zero speedup) — fix: run with model=1 (all "
                f"{self.mesh.n_devices} chips on the data axis) unless an "
                f"embedder forward uses tensor parallelism")

    # -- mesh/topology ------------------------------------------------------
    def _check_env_mesh(self) -> None:
        """PWT101: env-var topology overrides vs the analysis mesh."""
        if self.mesh is None:
            return
        data_env = os.environ.get("PATHWAY_DATA_PARALLEL")
        model_env = os.environ.get("PATHWAY_MODEL_PARALLEL")
        if data_env is None and model_env is None:
            return
        try:
            model = int(model_env) if model_env is not None else 1
            data = (int(data_env) if data_env is not None
                    else max(1, self.mesh.n_devices // model))
        except ValueError:
            self.a._report(
                "PWT101",
                f"PATHWAY_DATA_PARALLEL={data_env!r} / "
                f"PATHWAY_MODEL_PARALLEL={model_env!r} are not integers — "
                f"fix: set both to positive axis sizes")
            return
        for d in check_mesh_fits(
                data, model, self.mesh.n_devices,
                source=f"env topology (PATHWAY_DATA_PARALLEL={data_env}, "
                       f"PATHWAY_MODEL_PARALLEL={model_env}) vs analysis "
                       f"mesh {self.mesh}"):
            self.a._report(d.code, d.message, severity=d.severity)

    # -- external index: slab shape, specs, placement, growth ---------------
    def _check_external_index(self, node) -> bool:
        """All factory-derived checks. Returns True when the index is
        model-parallel-aware (an embedder forward can use the model axis)."""
        factory = node.table._plan.params.get("index_factory")
        if factory is None:
            return False
        slab_data = self._resolved_data_size(factory)
        embedder = getattr(factory, "embedder", None)
        device_embedder = hasattr(embedder, "encode_batch_device")

        # PWT104: slab pinned to a topology other than the analysis mesh
        explicit = self._explicit_mesh_spec(factory)
        if (explicit is not None and self.mesh is not None
                and explicit.data != self.mesh.data):
            self.a._report(
                "PWT104",
                f"index slab is pinned to a {explicit} mesh while the "
                f"pipeline is analyzed against {self.mesh} — every query "
                f"batch crosses topologies (implicit gather of "
                f"queries/results over DCN instead of ICI) — fix: build "
                f"the index with mesh='auto' or the pipeline's mesh",
                node)

        # PWT102: slab reservation must tile the data axis
        if slab_data is not None and slab_data > 1:
            from pathway_tpu.parallel.sharded_knn import (
                search_operand_layout, slab_cap_per_shard)

            reserved = getattr(factory, "reserved_space", None)
            if isinstance(reserved, int) and reserved > 0:
                # layout-accurate message: the store page-aligns each
                # shard's slab, so the predicted cost must use the same
                # page_rows the runtime will
                from pathway_tpu.engine.paged_store import page_rows

                try:
                    cap = slab_cap_per_shard(slab_data, reserved,
                                             page_rows())
                    costs = (f"; the slab allocates {cap} rows/shard "
                             f"({cap * slab_data} total)")
                except ValueError:
                    costs = ""  # reported separately as PWT111
                for d in check_sharded_dim(
                        reserved, slab_data,
                        what=f"KNN slab reservation (reserved_space="
                             f"{reserved} over {slab_data} shards)"):
                    self.a._report(d.code, d.message + costs, node,
                                   severity=d.severity)

            # PWT103: the search kernel's spec/rank contract on this mesh
            layout = search_operand_layout(getattr(factory, "dtype",
                                                   "float32"))
            axes = {DATA_AXIS: slab_data,
                    MODEL_AXIS: self.mesh.model if self.mesh else 1}
            for d in check_shard_specs(
                    axes, [spec for spec, _ in layout],
                    [rank for _, rank in layout]):
                self.a._report(d.code, d.message, node, severity=d.severity)

        reserved = getattr(factory, "reserved_space", None)
        self._check_paged_layout(node, factory, reserved, slab_data)
        return device_embedder

    def _check_paged_layout(self, node, factory, reserved,
                            slab_data) -> None:
        """PWT111: paged-store reservations and tenant quotas. Alignment
        findings are warnings (the allocator rounds UP, silently
        over-reserving); quotas summing past device HBM are errors."""
        from pathway_tpu.engine.paged_store import page_rows
        from pathway_tpu.internals.static_check.diagnostics import Severity

        try:
            pr = page_rows()
        except ValueError as e:
            self.a._report("PWT111", f"invalid paged-store config: {e}",
                           node, severity=Severity.ERROR)
            return
        if isinstance(reserved, int) and reserved > 0 and reserved % pr:
            rounded = -(-reserved // pr) * pr
            self.a._report(
                "PWT111",
                f"reserved_space={reserved} is not page-aligned "
                f"(PATHWAY_PAGE_ROWS={pr}): the paged store rounds the "
                f"reservation up to {rounded} rows "
                f"({rounded // pr} pages), silently over-reserving "
                f"{rounded - reserved} rows of HBM — fix: reserve whole "
                f"pages",
                node)
        quotas = getattr(factory, "tenant_quotas", None)
        if not isinstance(quotas, dict) or not quotas:
            return
        total_pages = 0
        for tenant, rows in quotas.items():
            if not isinstance(rows, int) or rows <= 0:
                self.a._report(
                    "PWT111",
                    f"tenant {tenant!r} quota {rows!r} is not a positive "
                    f"row count",
                    node, severity=Severity.ERROR)
                continue
            pages = -(-rows // pr)
            total_pages += pages
            if rows % pr:
                self.a._report(
                    "PWT111",
                    f"tenant {tenant!r} quota of {rows} rows is not "
                    f"page-aligned (PATHWAY_PAGE_ROWS={pr}): the allocator "
                    f"grants whole pages, so the quota silently becomes "
                    f"{pages * pr} rows ({pages} pages) — fix: quota in "
                    f"multiples of {pr}",
                    node)
        dim = getattr(factory, "dimensions", None)
        if not isinstance(dim, int) or dim <= 0:
            return
        dtype = getattr(factory, "dtype", "float32")
        bytes_per_val = {"int8": 1, "bfloat16": 2}.get(dtype, 4)
        # int8 carries f32 scale+vsq side columns per row
        row_bytes = dim * bytes_per_val + (8 if dtype == "int8" else 0)
        hbm_bytes = int(float(os.environ.get(
            "PATHWAY_DEVICE_HBM_GB", "16")) * (1 << 30))
        n_dev = max(1, slab_data or 1)
        need = total_pages * pr * row_bytes
        if need > hbm_bytes * n_dev:
            self.a._report(
                "PWT111",
                f"tenant quotas sum to {total_pages} pages "
                f"({total_pages * pr} rows x {row_bytes} B/row = "
                f"{need / (1 << 30):.1f} GiB as {dtype}) but the device "
                f"has {hbm_bytes * n_dev / (1 << 30):.0f} GiB HBM "
                f"({n_dev} dev x PATHWAY_DEVICE_HBM_GB"
                f"={os.environ.get('PATHWAY_DEVICE_HBM_GB', '16')}) — "
                f"admitting every tenant at "
                f"quota OOMs the slab — fix: lower quotas or shard the "
                f"store over more chips",
                node, severity=Severity.ERROR)

    def _explicit_mesh_spec(self, factory) -> MeshSpec | None:
        """The factory's mesh when explicitly pinned (not None/'auto')."""
        mesh = getattr(factory, "mesh", None)
        if mesh is None or mesh == "auto":
            return None
        try:
            return parse_mesh_spec(mesh)
        except ValueError:
            return None

    def _resolved_data_size(self, factory) -> int | None:
        """Data-axis size the factory's slab will shard over (1 = single
        slab, None = unknown: mesh='auto' with no analysis mesh)."""
        mesh = getattr(factory, "mesh", None)
        if mesh is None:
            return 1
        if mesh == "auto":
            return self.mesh.data if self.mesh is not None else None
        spec = self._explicit_mesh_spec(factory)
        return spec.data if spec is not None else None

    # -- placement: streaming reachability ----------------------------------
    def _streaming_downstream(self) -> set[int]:
        """Ids of tables downstream of a streaming source — the per-batch
        hot path where host round-trips cost every tick."""
        out: set[int] = set()
        stack = []
        for node in self.a._nodes.values():
            plan = node.table._plan
            if plan.kind != "input":
                continue
            source = plan.params.get("datasource")
            if getattr(source, "mode", "streaming") != "static":
                stack.append(node.table)
        while stack:
            t = stack.pop()
            if id(t) in out:
                continue
            out.add(id(t))
            node = self.a._nodes.get(id(t))
            if node is not None:
                stack.extend(node.consumers)
            if t._plan.kind == "iterate_result":
                # the loop body re-executes every batch: a hot iterate
                # makes its body hot too (placeholders flow to the body
                # tables through the normal consumer edges)
                shared = t._plan.params.get("shared")
                if shared is not None:
                    stack.extend(shared.iterated_placeholders)
                    stack.extend(shared.extra_placeholders)
        return out

    # -- UDF traceability ----------------------------------------------------
    def _check_udf_placement(self, node, expr: ex.ApplyExpression, *,
                             hot: bool) -> None:
        if isinstance(expr, ex.AsyncApplyExpression):
            return  # async UDFs are concurrency tools, not compute kernels
        cls = getattr(expr, "_shard_class", None)
        if cls is None:
            cls = classify_udf(expr._fn)
            expr._shard_class = cls  # recorded for run.py's future auto-jit
        fn_name = getattr(expr._fn, "__name__", repr(expr._fn))
        self.a.udf_classifications[_udf_key(expr._fn)] = cls
        if not hot or _is_framework_fn(expr._fn):
            # framework-internal glue (index plumbing, rank projection) is
            # classified but never reported — the user cannot act on it
            return
        if getattr(expr, "_batch", False):
            # batch=True already amortizes dispatch to one call per engine
            # batch — exactly the fix PWT109/PWT110 would suggest
            return
        from pathway_tpu.internals.autojit import autojit_enabled
        from pathway_tpu.internals.autojit import \
            body_fusable as _autojit_body_fusable

        if cls.sync_points and cls.kind != "host":
            self.a._report(
                "PWT105",
                f"UDF {fn_name!r} contains a host-device sync point on a "
                f"per-batch streaming path: {'; '.join(cls.sync_points)} — "
                f"every engine batch stalls the dispatch queue — fix: keep "
                f"values on device (jnp ops) or move the conversion off "
                f"the hot path",
                node, expr=expr,
                related=(t,) if (t := _udf_def_trace(expr._fn)) else ())
        elif cls.kind == "host":
            detail = "; ".join(cls.reasons[:3]) or "unclassifiable"
            sync = (f" (also: {'; '.join(cls.sync_points)})"
                    if cls.sync_points else "")
            overlap = (
                " (with auto-jit on, host-only work in a select that also "
                "carries traceable UDFs is split out and overlapped with "
                "the device leg instead of serializing before it)"
                if autojit_enabled() else "")
            self.a._report(
                "PWT109",
                f"host-only UDF {fn_name!r} sits on a streaming hot path: "
                f"{detail}{sync} — each batch round-trips device→host→"
                f"device — fix: rewrite with jnp/np primitives, or batch "
                f"the work (pw.udf(batch=True)) to amortize the dispatch"
                f"{overlap}",
                node, expr=expr)
        elif autojit_enabled() and _autojit_body_fusable(expr._fn):
            # informational: the runtime is expected to fuse this UDF
            # automatically (internals/autojit.py) — suggesting a manual
            # batch=True rewrite would send the user to do the compiler's
            # job. The body passed the tier's static hazard screen; the
            # compiler still applies dtype/int-overflow gates, hence
            # "expected", never "guaranteed".
            self.a._report(
                "PWT110",
                f"UDF {fn_name!r} is {cls.kind} and is expected to be "
                f"auto-jitted into a fused vectorized dispatch at runtime "
                f"(PATHWAY_AUTO_JIT=1; byte-identical to the interpreted "
                f"path, demotes loudly if untraceable on real data) — no "
                f"change needed; pw.udf(batch=True) remains the manual "
                f"override, PATHWAY_AUTO_JIT=0 the escape hatch",
                node, expr=expr)
        else:
            # auto-jit off, or the body carries a hazard the fused tier
            # refuses (truthiness, inexact math.*, pow) — the manual
            # batch=True rewrite is the actionable advice
            self.a._report(
                "PWT110",
                f"UDF {fn_name!r} is {cls.kind} but dispatched row-by-row "
                f"on the host — eligible for vectorized TPU dispatch — "
                f"fix: pw.udf(batch=True) (columns in, column out)"
                + ("" if autojit_enabled() else
                   ", or re-enable auto-jit (PATHWAY_AUTO_JIT=1) to fuse "
                   "it automatically"),
                node, expr=expr)

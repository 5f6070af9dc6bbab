"""Static device-path perf analysis — the PWT4xx diagnostic family.

PWT0xx/1xx fence semantics, PWT2xx threads, PWT3xx crash recovery; this
pass fences the contract the ROADMAP's MFU line lives or dies by:
**device-path discipline on the serving hot path**. A recompile zoo, a
hidden ``.item()`` sync, or a per-row dispatch loop lands silently today
and only surfaces as bench noise three PRs later — exactly the failure
modes Ragged Paged Attention's shape-bucket design and WindVE's
host/device-overlap split exist to avoid (PAPERS.md). Each is mechanical
enough for an AST pass to catch at authoring time.

Like its PWT2xx/3xx siblings it analyzes **source files** — the four
device-leg directories (``engine/``, ``ops/``, ``models/``,
``parallel/``) — parsed, never imported, over the same corpus model
(durability_check.build_corpus) and waiver machinery (``pwt-ok``
comments, ``check --list-waivers``).

====== ======================================================== =========
code   finding                                                  severity
====== ======================================================== =========
PWT401 jit dispatch with unbucketed data-dependent shape        error
PWT402 host-device sync point on a per-batch path               error
PWT403 per-row device dispatch in a loop; batched kernel exists warning
PWT404 numpy operand fed to jit with no device residency        warning
PWT405 float64/weak-type promotion reaching kernel code         error
PWT406 donated buffer read after donation                       error
PWT407 jitted serving entry point absent from warmup registry   warning
PWT408 blocking host I/O inside a device-leg function           warning
====== ======================================================== =========

**Hot paths.** Every check except PWT405/406/407 is scoped to the
*per-batch/per-tick* reachability set: methods whose names carry a hot
token (``search``, ``ingest``, ``step``, ``drain``, ``encode`` …) plus
everything they reach through ``self`` calls and same-module function
calls, minus cold-named slow paths (``__init__``, ``_grow``,
``snapshot``/``restore``, ``warmup``) and instrumentation modules
(flight recorder, request tracker, metrics exposition) — a sync inside
a post-mortem dump is the tool working, not a footgun.

**Device residency.** Locals assigned from ``jnp.*`` / jitted calls /
``device_put`` — and attrs assigned one anywhere in their class, or
named with a ``dev``/``device`` segment (``_DEVICE_ATTR_RE``) — are
device-resident; a sync construct only fires on a device-resident operand,
so the host-side ``slots.tolist()`` bookkeeping the KNN index does every
batch stays silent. PWT402 *supersedes and widens* PWT105's narrower sync list
(which missed ``.tolist()`` and ``int()``/``float()`` casts): when both
families run in one ``check --all`` invocation, PWT105 defers to this
family for any UDF defined in a file this pass scanned.

The runtime twin is the device sanitizer (engine/device_sanitizer.py,
``PATHWAY_DEVICE_SANITIZER=1``): what this pass proves about the source
— no post-warmup compile, no implicit transfer — the sanitizer asserts
about the execution, tick by tick, once ``pw.warmup()`` declares steady
state.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

from pathway_tpu.internals.static_check.concurrency_check import _waived
from pathway_tpu.internals.static_check.diagnostics import Diagnostic
from pathway_tpu.internals.static_check.durability_check import (
    _ClassInfo, _Corpus, _ModuleInfo, _self_attr, _units, _walk_unit,
    build_corpus)
from pathway_tpu.internals.trace import Trace

# -- hot-path model ----------------------------------------------------------
# name segments that seed the per-batch/per-tick reachability set
_HOT_TOKENS = {
    "search", "query", "queries", "ingest", "encode", "embed", "forward",
    "dispatch", "drain", "tick", "scatter", "establish", "score", "lookup",
    "step", "serve", "batch", "flush", "submit", "apply", "exchange",
}
# name segments that mark a unit cold even when reached from a hot one:
# construction, growth/realloc, recovery, warmup and teardown run outside
# the steady-state serving window
_COLD_TOKENS = {
    "init", "grow", "snapshot", "restore", "warmup", "warm", "reserve",
    "close", "shutdown", "stop", "rebuild", "hydrate", "recover",
}
# modules whose entire job is measurement/post-mortem — a sync there is
# the instrument working, not a hot-path footgun
_INSTRUMENTATION_STEMS = {
    "flight_recorder", "request_tracker", "http_server", "telemetry",
    "fleet_observability", "locking", "snapshot_sanitizer",
    "device_sanitizer", "qos", "threads", "supervisor",
}
# function-name fragments that mark instrumentation/debug units inside
# otherwise-hot modules
_INSTRUMENTATION_FN_RE = re.compile(
    r"metric|trace|dump|summary|beacon|post_mortem|probe|debug|repr|"
    r"status|describe|mortem")

# -- sync / residency vocabulary ---------------------------------------------
_SYNC_METHOD_ATTRS = {"item", "tolist", "numpy", "copy_to_host_async"}
_SYNC_NP_FNS = {"asarray", "array", "ascontiguousarray", "frombuffer"}
_CAST_BUILTINS = {"int", "float", "bool"}
_DEVICE_NS = {"jnp", "lax"}
_DEVICE_ATTR_RE = re.compile(r"(^|_)dev(ice)?(_|$)")
_HOST_ATTR_RE = re.compile(r"(^|_)host(_|$)")
# evidence that a function disciplines its dispatch shapes: any call whose
# name mentions bucketing/padding/power-of-two rounding
_BUCKET_EVIDENCE_RE = re.compile(
    r"bucket|pad|pow2|power_of_two|next_pow|round_up|ladder")
# PWT407: a jit definition whose name carries one of these segments is a
# serving entry point and must appear in pw.warmup's bucket registry
_SERVING_ENTRY_TOKENS = {"search", "encode", "ingest", "scatter", "score",
                         "lookup", "extent"}
# PWT408: blocking host I/O constructs
_BLOCKING_IO_ATTRS = {"fsync", "sendall", "send_bytes", "recv_bytes",
                      "flush"}
_BLOCKING_IO_RECV_RE = re.compile(r"file|fh|sock|log|handle|stream|conn")


def _name_tokens(name: str) -> set[str]:
    return {t for t in name.lower().strip("_").split("_") if t}


def _is_hot_name(name: str) -> bool:
    toks = _name_tokens(name)
    return bool(toks & _HOT_TOKENS) and not (toks & _COLD_TOKENS)


def _is_cold_name(name: str) -> bool:
    return bool(_name_tokens(name) & _COLD_TOKENS) \
        or name.startswith("__")


def _is_instrumentation(mod: _ModuleInfo, fn_name: str) -> bool:
    return mod.stem in _INSTRUMENTATION_STEMS \
        or bool(_INSTRUMENTATION_FN_RE.search(fn_name.lower()))


# -- jit inventory -----------------------------------------------------------

@dataclass(frozen=True)
class JitDef:
    """One jitted callable: a decorated def or a ``X = jax.jit(...)``
    assignment. ``donate`` holds absolute positional indices from
    ``donate_argnums`` (empty = nothing donated)."""

    name: str           # callable name at the call site (attr or local)
    file: str
    line: int
    donate: tuple[int, ...] = ()
    wrapped: str | None = None   # jax.jit(fn) target name, if a plain Name


def _donate_from_call(call: ast.Call) -> tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(e.value for e in v.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, int))
    return ()


def _is_jit_ref(node: ast.expr) -> bool:
    """``jax.jit`` / bare ``jit`` reference."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return isinstance(node, ast.Attribute) and node.attr == "jit" \
        and isinstance(node.value, ast.Name) and node.value.id == "jax"


def _jit_call_info(value: ast.expr) -> tuple[bool, tuple[int, ...],
                                             str | None]:
    """(is_jit, donate_argnums, wrapped fn name) for a value expression
    ``jax.jit(f, ...)`` / ``functools.partial(jax.jit, ...)(f)`` — or the
    partial itself when used as a decorator."""
    if not isinstance(value, ast.Call):
        return False, (), None
    if _is_jit_ref(value.func):
        wrapped = value.args[0].id if value.args \
            and isinstance(value.args[0], ast.Name) else None
        return True, _donate_from_call(value), wrapped
    # functools.partial(jax.jit, donate_argnums=..., static_argnames=...)
    fn = value.func
    is_partial = (isinstance(fn, ast.Name) and fn.id == "partial") or (
        isinstance(fn, ast.Attribute) and fn.attr == "partial")
    if is_partial and value.args and _is_jit_ref(value.args[0]):
        return True, _donate_from_call(value), None
    return False, (), None


def _decorated_jit(fn: ast.FunctionDef) -> tuple[bool, tuple[int, ...]]:
    for dec in fn.decorator_list:
        if _is_jit_ref(dec):
            return True, ()
        is_jit, donate, _w = _jit_call_info(dec)
        if is_jit:
            return True, donate
    return False, ()


class _JitInventory:
    """Every jitted callable in a module, resolvable at call sites:
    module-level names, ``self.attr`` assignments per class, and
    unit-local names (including nested decorated defs)."""

    def __init__(self, mod: _ModuleInfo):
        self.module: dict[str, JitDef] = {}
        self.by_class: dict[tuple[str, str], JitDef] = {}
        for name, fn in mod.functions.items():
            is_jit, donate = _decorated_jit(fn)
            if is_jit:
                self.module[name] = JitDef(name, mod.path, fn.lineno,
                                           donate)
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                is_jit, donate, wrapped = _jit_call_info(node.value)
                if is_jit:
                    name = node.targets[0].id
                    self.module[name] = JitDef(name, mod.path,
                                               node.lineno, donate,
                                               wrapped)
        for cls in mod.classes.values():
            for m in cls.methods.values():
                for sub in _walk_unit(m):
                    if isinstance(sub, ast.Assign) \
                            and len(sub.targets) == 1 \
                            and _self_attr(sub.targets[0]):
                        is_jit, donate, wrapped = _jit_call_info(sub.value)
                        if is_jit:
                            attr = _self_attr(sub.targets[0])
                            self.by_class[(cls.name, attr)] = JitDef(
                                attr, mod.path, sub.lineno, donate,
                                wrapped)

    def local_jits(self, fn: ast.AST) -> dict[str, JitDef]:
        out: dict[str, JitDef] = {}
        for node in _walk_unit(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                is_jit, donate, wrapped = _jit_call_info(node.value)
                if is_jit:
                    name = node.targets[0].id
                    out[name] = JitDef(name, "", node.lineno, donate,
                                       wrapped)
            elif isinstance(node, ast.FunctionDef) and node is not fn:
                is_jit, donate = _decorated_jit(node)
                if is_jit:
                    out[node.name] = JitDef(node.name, "", node.lineno,
                                            donate)
        return out

    def resolve_call(self, call: ast.Call, cls: _ClassInfo | None,
                     local: dict[str, JitDef]) -> JitDef | None:
        fn = call.func
        if isinstance(fn, ast.Name):
            return local.get(fn.id) or self.module.get(fn.id)
        if isinstance(fn, ast.Attribute) and cls is not None:
            attr = _self_attr(fn)
            if attr is not None:
                return self.by_class.get((cls.name, attr))
        return None

    def all_defs(self):
        yield from self.module.values()
        yield from self.by_class.values()


# -- hot-path reachability ---------------------------------------------------

def _self_calls(fn: ast.AST) -> set[str]:
    out = set()
    for node in _walk_unit(fn):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and (a := _self_attr(node.func)) is not None:
            out.add(a)
    return out


def _name_calls(fn: ast.AST) -> set[str]:
    return {node.func.id for node in _walk_unit(fn)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)}


def hot_units(mod: _ModuleInfo) -> set[tuple[str | None, str]]:
    """``(class_name | None, fn_name)`` units on the per-batch path:
    hot-named seeds closed over same-class ``self`` calls and same-module
    function calls, minus cold-named units."""
    hot: set[tuple[str | None, str]] = set()
    for cls, fn in _units(mod):
        if _is_hot_name(fn.name):
            hot.add((cls.name if cls else None, fn.name))
    changed = True
    while changed:
        changed = False
        for cls, fn in _units(mod):
            key = (cls.name if cls else None, fn.name)
            if key not in hot:
                continue
            callees: set[tuple[str | None, str]] = set()
            if cls is not None:
                callees |= {(cls.name, m) for m in _self_calls(fn)
                            if m in cls.methods}
            callees |= {(None, m) for m in _name_calls(fn)
                        if m in mod.functions}
            for ck in callees:
                if ck not in hot and not _is_cold_name(ck[1]):
                    hot.add(ck)
                    changed = True
    return hot


# -- device / host residency -------------------------------------------------

def _device_attrs(cls: _ClassInfo, jits: _JitInventory) -> set[str]:
    """Attrs device-resident anywhere in the class: named like device
    state, or assigned from ``jnp.*`` / ``device_put`` / a jitted call."""
    out = {a for a in _class_attr_names(cls) if _DEVICE_ATTR_RE.search(a)}
    for m in cls.methods.values():
        local = jits.local_jits(m)
        for node in _walk_unit(m):
            if not isinstance(node, ast.Assign):
                continue
            if not _is_device_producer(node.value, cls, jits, local,
                                       set(), set()):
                continue
            for tgt in node.targets:
                targets = tgt.elts if isinstance(
                    tgt, (ast.Tuple, ast.List)) else [tgt]
                for t in targets:
                    if (a := _self_attr(t)) is not None:
                        out.add(a)
    return out


def _class_attr_names(cls: _ClassInfo) -> set[str]:
    out = set()
    for m in cls.methods.values():
        for node in _walk_unit(m):
            if (a := _self_attr(node)) is not None:
                out.add(a)
    return out


def _is_device_producer(value: ast.expr, cls, jits, local,
                        device_names: set[str],
                        device_attrs: set[str]) -> bool:
    """Does evaluating ``value`` yield a device-resident array?"""
    if isinstance(value, ast.Call):
        fn = value.func
        if isinstance(fn, ast.Attribute):
            if isinstance(fn.value, ast.Name) \
                    and fn.value.id in _DEVICE_NS:
                return True
            if fn.attr in ("device_put", "device_put_sharded"):
                return True
            # method chains on device values (x.at[...].set(...), x.sum())
            if _mentions_device(fn.value, device_names, device_attrs):
                return True
        if jits.resolve_call(value, cls, local) is not None:
            return True
        return False
    if isinstance(value, (ast.Subscript, ast.Attribute, ast.BinOp,
                          ast.UnaryOp)):
        return _mentions_device(value, device_names, device_attrs)
    if isinstance(value, ast.Name):
        return value.id in device_names
    return False


def _mentions_device(expr: ast.expr, device_names: set[str],
                     device_attrs: set[str]) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in device_names:
            return True
        if (a := _self_attr(n)) is not None and (
                a in device_attrs or _DEVICE_ATTR_RE.search(a)):
            return True
    return False


def _unit_residency(fn: ast.AST, cls, jits, local, device_attrs
                    ) -> tuple[set[str], set[str]]:
    """(device-resident local names, host-resident local names) by a
    forward dataflow sweep over the unit's assignments."""
    device: set[str] = set()
    host: set[str] = set()
    nodes = sorted(
        (n for n in _walk_unit(fn) if isinstance(n, ast.Assign)),
        key=lambda n: n.lineno)
    for node in nodes:
        is_dev = _is_device_producer(node.value, cls, jits, local,
                                     device, device_attrs)
        is_host = _is_host_producer(node.value, host)
        for tgt in node.targets:
            targets = tgt.elts if isinstance(
                tgt, (ast.Tuple, ast.List)) else [tgt]
            for t in targets:
                if isinstance(t, ast.Name):
                    if is_dev:
                        device.add(t.id)
                        host.discard(t.id)
                    elif is_host:
                        host.add(t.id)
                        device.discard(t.id)
    return device, host


def _is_host_producer(value: ast.expr, host_names: set[str]) -> bool:
    if isinstance(value, ast.Call):
        fn = value.func
        if isinstance(fn, ast.Attribute) and isinstance(
                fn.value, ast.Name) and fn.value.id in ("np", "numpy"):
            return True
    if isinstance(value, ast.Name):
        return value.id in host_names
    if isinstance(value, (ast.Subscript, ast.BinOp)):
        return any(isinstance(n, ast.Name) and n.id in host_names
                   for n in ast.walk(value)) \
            or any((a := _self_attr(n)) is not None
                   and _HOST_ATTR_RE.search(a)
                   for n in ast.walk(value))
    return False


# -- warmup registry (PWT407) ------------------------------------------------

def load_warmup_registry(paths) -> set[str] | None:
    """The ``WARMED_ENTRY_POINTS`` name set parsed (never imported) from
    the package's warmup.py, located relative to the scanned trees; None
    when no warmup.py is reachable — PWT407 then stays silent."""
    import pathlib

    seen: set[pathlib.Path] = set()
    for p in paths:
        d = pathlib.Path(p).resolve()
        if d.is_file():
            d = d.parent
        for candidate in (d, *d.parents[:3]):
            if candidate in seen:
                continue
            seen.add(candidate)
            w = candidate / "warmup.py"
            if w.is_file():
                reg = _parse_registry(w)
                if reg is not None:
                    return reg
    return None


def _parse_registry(path) -> set[str] | None:
    try:
        tree = ast.parse(path.read_text())
    except (OSError, SyntaxError):
        return None
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        if not any(isinstance(t, ast.Name)
                   and t.id == "WARMED_ENTRY_POINTS" for t in targets):
            continue
        if isinstance(value, ast.Call) and value.args:
            value = value.args[0]  # frozenset({...})
        if isinstance(value, (ast.Set, ast.Tuple, ast.List)):
            return {e.value for e in value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
    return None


# ---------------------------------------------------------------------------
# checker
# ---------------------------------------------------------------------------

class PerfChecker:
    """Runs every PWT4xx check over a parsed corpus."""

    def __init__(self, corpus: _Corpus,
                 warmup_registry: set[str] | None = None):
        self.corpus = corpus
        self.registry = warmup_registry
        self.diagnostics: list[Diagnostic] = []
        self._sources = {m.path: m.source_lines for m in corpus.modules}

    def _report(self, code: str, message: str, file: str, line: int,
                function: str = "") -> None:
        lines = self._sources.get(file, [])
        if _waived(lines, line, code):
            return
        src = lines[line - 1].strip() if 0 < line <= len(lines) else ""
        self.diagnostics.append(Diagnostic(
            code=code, message=message,
            trace=Trace(file, line, function, src)))

    def run(self) -> list[Diagnostic]:
        for path, err in self.corpus.parse_failures:
            self.diagnostics.append(Diagnostic(
                code="PWT000", message=f"cannot analyze {path}: {err}"))
        for mod in self.corpus.modules:
            self._check_module(mod)
        return self.diagnostics

    # -- per-module driver ---------------------------------------------------
    def _check_module(self, mod: _ModuleInfo) -> None:
        jits = _JitInventory(mod)
        hot = hot_units(mod)
        module_has_batched = self._module_has_batched_kernel(mod)
        dev_attrs_by_class = {
            cls.name: _device_attrs(cls, jits)
            for cls in mod.classes.values()}
        self._check_entry_registry(mod, jits)              # PWT407
        for cls, fn in _units(mod):
            owner = f"{cls.name}.{fn.name}" if cls else fn.name
            local = jits.local_jits(fn)
            self._check_donation(mod, cls, fn, owner, jits, local)  # 406
            self._check_f64(mod, cls, fn, owner)                    # 405
            key = (cls.name if cls else None, fn.name)
            if key not in hot or _is_instrumentation(mod, fn.name):
                continue
            dev_attrs = dev_attrs_by_class.get(cls.name, set()) \
                if cls else set()
            device, host = _unit_residency(fn, cls, jits, local,
                                           dev_attrs)
            self._check_syncs(mod, cls, fn, owner, device, dev_attrs)  # 402
            flagged_401 = self._check_unbucketed(
                mod, cls, fn, owner, jits, local, host)                # 401
            self._check_loop_dispatch(mod, cls, fn, owner, jits,
                                      local, module_has_batched)       # 403
            self._check_host_operands(mod, cls, fn, owner, jits,
                                      local, host, flagged_401)        # 404
            self._check_blocking_io(mod, cls, fn, owner, jits, local)  # 408

    @staticmethod
    def _module_has_batched_kernel(mod: _ModuleInfo) -> bool:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "vmap":
                return True
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and "batch" in _name_tokens(node.name):
                return True
        return False

    # -- PWT402 --------------------------------------------------------------
    def _check_syncs(self, mod, cls, fn, owner, device: set[str],
                     dev_attrs: set[str]) -> None:
        def is_dev(expr: ast.expr) -> bool:
            return _mentions_device(expr, device, dev_attrs)

        for node in _walk_unit(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in _SYNC_METHOD_ATTRS and is_dev(f.value):
                    self._report(
                        "PWT402",
                        f"{owner} calls .{f.attr}() on device value "
                        f"{ast.unparse(f.value)} on a per-batch path: "
                        f"every batch stalls the dispatch queue for a "
                        f"device→host round-trip — keep the value on "
                        f"device (jnp ops) or move the read to an "
                        f"output boundary",
                        mod.path, node.lineno, owner)
                elif f.attr == "block_until_ready":
                    self._report(
                        "PWT402",
                        f"{owner} blocks on device completion "
                        f"(.block_until_ready()) on a per-batch path "
                        f"outside instrumentation code: the host idles "
                        f"for the full device leg instead of "
                        f"overlapping — let the consumer's read "
                        f"synchronize, or move the barrier to the "
                        f"output boundary",
                        mod.path, node.lineno, owner)
                elif isinstance(f.value, ast.Name) \
                        and f.value.id in ("np", "numpy") \
                        and f.attr in _SYNC_NP_FNS \
                        and node.args and is_dev(node.args[0]):
                    self._report(
                        "PWT402",
                        f"{owner} materializes device value "
                        f"{ast.unparse(node.args[0])} on the host "
                        f"(np.{f.attr}) on a per-batch path: a full "
                        f"device→host transfer every batch — keep the "
                        f"compute in jnp, or hoist the read out of the "
                        f"hot path",
                        mod.path, node.lineno, owner)
            elif isinstance(f, ast.Name):
                if f.id == "block_until_ready":
                    self._report(
                        "PWT402",
                        f"{owner} blocks on device completion "
                        f"(block_until_ready) on a per-batch path "
                        f"outside instrumentation code",
                        mod.path, node.lineno, owner)
                elif f.id in _CAST_BUILTINS and node.args \
                        and is_dev(node.args[0]):
                    self._report(
                        "PWT402",
                        f"{owner} casts device value "
                        f"{ast.unparse(node.args[0])} to a Python "
                        f"{f.id} on a per-batch path: the implicit "
                        f".item() blocks until the device flushes — "
                        f"PWT105's old list missed exactly this form; "
                        f"keep it a 0-d array or read at the output "
                        f"boundary",
                        mod.path, node.lineno, owner)

    # -- PWT401 --------------------------------------------------------------
    def _check_unbucketed(self, mod, cls, fn, owner, jits, local,
                          host: set[str]) -> set[int]:
        """Flag jit dispatches whose operand's leading dim is raw data
        length with no bucketing evidence in the unit. Returns flagged
        call linenos (PWT404 skips those sites)."""
        has_bucketing = any(
            isinstance(n, ast.Call) and _BUCKET_EVIDENCE_RE.search(
                n.func.attr if isinstance(n.func, ast.Attribute)
                else n.func.id if isinstance(n.func, ast.Name) else "")
            for n in _walk_unit(fn))
        flagged: set[int] = set()
        if has_bucketing:
            return flagged
        params = _param_names(fn)
        ragged = _data_dependent_names(fn, params)
        for node in _walk_unit(fn):
            if not isinstance(node, ast.Call) \
                    or jits.resolve_call(node, cls, local) is None:
                continue
            for arg in node.args:
                bad = None
                if isinstance(arg, ast.Name) and arg.id in ragged:
                    bad = arg.id
                elif _conversion_of_param(arg, params | ragged):
                    bad = ast.unparse(arg)
                if bad is None:
                    continue
                self._report(
                    "PWT401",
                    f"{owner} dispatches jitted callable "
                    f"{ast.unparse(node.func)} with data-dependent "
                    f"shape ({bad}): every distinct batch length "
                    f"compiles a fresh executable — bucket the leading "
                    f"dim (pad to a power-of-two width) before the "
                    f"dispatch site, as the encoder's bucket ladder "
                    f"does",
                    mod.path, node.lineno, owner)
                flagged.add(node.lineno)
                break
        return flagged

    # -- PWT403 --------------------------------------------------------------
    def _check_loop_dispatch(self, mod, cls, fn, owner, jits, local,
                             module_has_batched: bool) -> None:
        if not module_has_batched:
            return
        for node in _walk_unit(fn):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) \
                        and jits.resolve_call(sub, cls, local) \
                        is not None:
                    self._report(
                        "PWT403",
                        f"{owner} dispatches jitted callable "
                        f"{ast.unparse(sub.func)} per row inside a "
                        f"Python loop while this module has a batched/"
                        f"vmapped kernel: ~100 µs dispatch overhead "
                        f"per row instead of one amortized launch — "
                        f"stack the rows and dispatch once",
                        mod.path, node.lineno, owner)
                    break

    # -- PWT404 --------------------------------------------------------------
    def _check_host_operands(self, mod, cls, fn, owner, jits, local,
                             host: set[str], flagged_401: set[int]
                             ) -> None:
        has_device_put = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "device_put"
            for n in _walk_unit(fn))
        if has_device_put:
            return
        for node in _walk_unit(fn):
            if not isinstance(node, ast.Call) \
                    or node.lineno in flagged_401 \
                    or jits.resolve_call(node, cls, local) is None:
                continue
            for arg in node.args:
                hosty = None
                if isinstance(arg, ast.Name) and arg.id in host:
                    hosty = arg.id
                elif (a := _self_attr(arg)) is not None \
                        and _HOST_ATTR_RE.search(a):
                    hosty = f"self.{a}"
                if hosty is None:
                    continue
                self._report(
                    "PWT404",
                    f"{owner} feeds numpy operand {hosty} to jitted "
                    f"callable {ast.unparse(node.func)}: an implicit "
                    f"host→device transfer every tick — device_put it "
                    f"once upstream (or keep it device-resident) so "
                    f"steady-state dispatches reuse the on-device "
                    f"buffer",
                    mod.path, node.lineno, owner)
                break

    # -- PWT405 --------------------------------------------------------------
    def _check_f64(self, mod, cls, fn, owner) -> None:
        for node in _walk_unit(fn):
            bad = None
            if isinstance(node, ast.Attribute) \
                    and node.attr == "float64" \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in ("np", "numpy", "jnp"):
                bad = f"{node.value.id}.float64"
            elif isinstance(node, ast.Constant) \
                    and node.value == "float64":
                bad = "'float64'"
            elif isinstance(node, ast.keyword) and node.arg == "dtype" \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "float":
                bad = "dtype=float (Python float is float64)"
            if bad is None:
                continue
            if not self._near_device_code(fn):
                continue
            self._report(
                "PWT405",
                f"{owner} lets {bad} reach kernel code: TPUs emulate "
                f"f64 at ~1/10 throughput and one stray dtype "
                f"contaminates every downstream op through promotion — "
                f"pin float32 (or the slab dtype) explicitly",
                mod.path, node.lineno, owner)

    @staticmethod
    def _near_device_code(fn: ast.AST) -> bool:
        """float64 only matters where arrays can reach a device op: the
        unit touches jnp/jax/lax or numpy array constructors."""
        for n in _walk_unit(fn):
            if isinstance(n, ast.Name) and n.id in ("jnp", "jax", "lax"):
                return True
            if isinstance(n, ast.Attribute) and isinstance(
                    n.value, ast.Name) and n.value.id in ("np", "numpy"):
                return True
        return False

    # -- PWT406 --------------------------------------------------------------
    def _check_donation(self, mod, cls, fn, owner, jits, local) -> None:
        stmts = [n for n in _walk_unit(fn)
                 if isinstance(n, (ast.Assign, ast.Expr, ast.AugAssign,
                                   ast.Return, ast.If, ast.For))]
        for node in _walk_unit(fn):
            if not isinstance(node, ast.Call):
                continue
            jd = jits.resolve_call(node, cls, local)
            if jd is None or not jd.donate:
                continue
            donated: set[str] = set()
            for idx in jd.donate:
                if idx < len(node.args):
                    arg = node.args[idx]
                    if isinstance(arg, ast.Name):
                        donated.add(arg.id)
                    elif (a := _self_attr(arg)) is not None:
                        donated.add(f"self.{a}")
            if not donated:
                continue
            rebound = self._assignment_targets_for_call(fn, node)
            live = donated - rebound
            if not live:
                continue
            for read_line, name in self._reads_after(
                    fn, node.lineno, live):
                self._report(
                    "PWT406",
                    f"{owner} reads {name} after donating it to "
                    f"{ast.unparse(node.func)} (donate_argnums) at "
                    f"line {node.lineno}: XLA may already have reused "
                    f"the buffer — rebind the result over the donated "
                    f"name, or drop the read",
                    mod.path, read_line, owner)
                break  # one report per donation site

    @staticmethod
    def _assignment_targets_for_call(fn, call: ast.Call) -> set[str]:
        """Names/attrs rebound from the call's result (``x, y = f(...)``)."""
        out: set[str] = set()
        for node in _walk_unit(fn):
            if isinstance(node, ast.Assign) and node.value is call:
                for tgt in node.targets:
                    targets = tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt]
                    for t in targets:
                        if isinstance(t, ast.Name):
                            out.add(t.id)
                        elif (a := _self_attr(t)) is not None:
                            out.add(f"self.{a}")
        return out

    @staticmethod
    def _reads_after(fn, call_line: int, names: set[str]):
        """(lineno, name) for reads of ``names`` after the call, skipping
        names rebound in between."""
        rebinds: dict[str, int] = {}
        for node in _walk_unit(fn):
            if isinstance(node, ast.Assign) and node.lineno > call_line:
                for tgt in node.targets:
                    targets = tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt]
                    for t in targets:
                        key = t.id if isinstance(t, ast.Name) else \
                            (f"self.{_self_attr(t)}"
                             if _self_attr(t) else None)
                        if key in names:
                            rebinds[key] = min(
                                rebinds.get(key, node.lineno),
                                node.lineno)
        reads = []
        for node in _walk_unit(fn):
            if node is None or getattr(node, "lineno", 0) <= call_line:
                continue
            key = None
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load) \
                    and node.id in names:
                key = node.id
            elif isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load) \
                    and (a := _self_attr(node)) is not None \
                    and f"self.{a}" in names:
                key = f"self.{a}"
            if key is None:
                continue
            if key in rebinds and node.lineno >= rebinds[key]:
                continue
            reads.append((node.lineno, key))
        return sorted(reads)

    # -- PWT407 --------------------------------------------------------------
    def _check_entry_registry(self, mod: _ModuleInfo,
                              jits: _JitInventory) -> None:
        if self.registry is None:
            return
        for jd in jits.all_defs():
            names = {jd.name}
            if jd.wrapped:
                names.add(jd.wrapped)
            if not any(_name_tokens(n) & _SERVING_ENTRY_TOKENS
                       for n in names):
                continue
            if names & self.registry:
                continue
            self._report(
                "PWT407",
                f"jitted serving entry point {jd.name!r} is absent "
                f"from pw.warmup's bucket registry "
                f"(warmup.WARMED_ENTRY_POINTS): its cold compile lands "
                f"inside the first real query instead of the warmup "
                f"window — walk it in warmup() and register the name",
                jd.file or mod.path, jd.line, jd.name)

    # -- PWT408 --------------------------------------------------------------
    def _check_blocking_io(self, mod, cls, fn, owner, jits, local
                           ) -> None:
        dispatches = any(
            isinstance(n, ast.Call) and (
                jits.resolve_call(n, cls, local) is not None
                or (isinstance(n.func, ast.Attribute)
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id in _DEVICE_NS))
            for n in _walk_unit(fn))
        if not dispatches:
            return
        for node in _walk_unit(fn):
            if not isinstance(node, ast.Call):
                continue
            what = None
            f = node.func
            if isinstance(f, ast.Name) and f.id in ("open", "print"):
                what = f"{f.id}()"
            elif isinstance(f, ast.Attribute):
                recv = ast.unparse(f.value).lower()
                if f.attr == "fsync" and recv == "os":
                    what = "os.fsync"
                elif f.attr in ("sendall", "send_bytes", "recv_bytes"):
                    what = f".{f.attr}()"
                elif f.attr == "flush" \
                        and _BLOCKING_IO_RECV_RE.search(recv):
                    what = f"{recv}.flush()"
                elif f.attr == "sleep" and recv == "time":
                    what = "time.sleep"
            if what is None:
                continue
            self._report(
                "PWT408",
                f"{owner} performs blocking host I/O ({what}) inside a "
                f"device-leg function: the dispatch pipeline stalls for "
                f"host I/O time every batch — queue the I/O to a "
                f"worker thread or move it off the device leg",
                mod.path, node.lineno, owner)


def _param_names(fn: ast.AST) -> set[str]:
    args = getattr(fn, "args", None)
    if args is None:
        return set()
    names = {a.arg for a in (*args.posonlyargs, *args.args,
                             *args.kwonlyargs)}
    names.discard("self")
    return names


def _data_dependent_names(fn: ast.AST, params: set[str]) -> set[str]:
    """Locals whose leading dim is raw data length: array constructors
    shaped by ``len(<param>)``."""
    out: set[str] = set()
    for node in _walk_unit(fn):
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        uses_len = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id == "len" and n.args
            and isinstance(n.args[0], ast.Name)
            and n.args[0].id in params
            for n in ast.walk(node.value))
        if not uses_len:
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                out.add(tgt.id)
    return out


def _conversion_of_param(arg: ast.expr, data_names: set[str]) -> bool:
    """``jnp.asarray(p)`` / ``jnp.stack(p)`` / ``np.asarray(p)`` where
    ``p`` carries raw data length."""
    if not isinstance(arg, ast.Call) \
            or not isinstance(arg.func, ast.Attribute):
        return False
    f = arg.func
    if not (isinstance(f.value, ast.Name)
            and f.value.id in ("jnp", "np", "numpy")
            and f.attr in ("asarray", "array", "stack")):
        return False
    return any(isinstance(n, ast.Name) and n.id in data_names
               for n in ast.walk(arg.args[0])) if arg.args else False


# ---------------------------------------------------------------------------
# front doors
# ---------------------------------------------------------------------------

def check_perf(paths, *, corpus: _Corpus | None = None,
               warmup_registry: set[str] | None = None
               ) -> list[Diagnostic]:
    """Run the PWT4xx family over ``paths`` (files or directories of
    Python source). Nothing is imported or executed. ``warmup_registry``
    overrides the ``WARMED_ENTRY_POINTS`` set normally parsed from the
    package's warmup.py (tests; None = autodiscover, and PWT407 stays
    silent when no registry is reachable)."""
    if warmup_registry is None:
        warmup_registry = load_warmup_registry(paths)
    return PerfChecker(corpus or build_corpus(paths),
                       warmup_registry=warmup_registry).run()


def perf_inventory(paths, *, corpus: _Corpus | None = None) -> dict:
    """The device-path inventory as plain data — every jitted callable
    (with its donation signature), the hot-unit reachability set per
    module, and the warmup registry — for ``check --perf --json``
    artifacts."""
    corpus = corpus or build_corpus(paths)
    jit_defs = []
    hot: list[str] = []
    for mod in corpus.modules:
        jits = _JitInventory(mod)
        for jd in jits.all_defs():
            jit_defs.append({
                "name": jd.name, "file": jd.file or mod.path,
                "line": jd.line, "donate_argnums": list(jd.donate),
            })
        for cls_name, fn_name in sorted(
                hot_units(mod), key=lambda k: (k[0] or "", k[1])):
            hot.append(f"{mod.stem}:{cls_name + '.' if cls_name else ''}"
                       f"{fn_name}")
    registry = load_warmup_registry(paths)
    return {
        "jit_entry_points": sorted(jit_defs, key=lambda d: (d["file"],
                                                            d["line"])),
        "hot_units": sorted(hot),
        "warmup_registry": sorted(registry) if registry else [],
    }

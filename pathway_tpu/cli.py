"""``pathway-tpu`` command line interface
(reference: python/pathway/cli.py:53-280 — spawn / replay / spawn-from-env).

``spawn -t T -n N program.py`` forks N processes of the user program with
``PATHWAY_THREADS/PROCESSES/PROCESS_ID/FIRST_PORT/RUN_ID`` set (the
reference's timely cluster topology): the processes shard the HOST
dataflow and exchange rows over TCP/shm. It assigns no accelerator — there
is no ``jax.distributed`` set-up and no per-child visible-chip variable —
so every child that touches JAX claims all local chips, and on a TPU host
the second one fails. With a chip in the pipeline, run one process (it can
drive all chips of the host through a mesh), or force the children onto
the CPU backend (``JAX_PLATFORMS=cpu``). ``replay`` re-runs a program against
a recorded snapshot directory with batch/speedrun timing, optionally
continuing live afterwards. Recording/replay wiring rides the persistence
env vars consumed by ``pw.run`` (internals/run.py)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import uuid

import click

import pathway_tpu as pw


def _plural(n: int, singular: str, plural: str) -> str:
    return f"{n} {singular if n == 1 else plural}"


def spawn_program(*, threads: int, processes: int, first_port: int,
                  program: str, arguments: tuple[str, ...], env_base: dict):
    """Fork N processes of the user program, each owning T logical workers
    (reference: cli.py:53-110,166 — PATHWAY_THREADS/PROCESSES/PROCESS_ID/
    FIRST_PORT envs; processes cluster over TCP at FIRST_PORT+i,
    engine/multiproc.py). Host dataflow only: no child is given a chip
    (module docstring)."""
    click.echo(
        f"Preparing {_plural(processes, 'process', 'processes')} "
        f"({_plural(processes * threads, 'total worker', 'total workers')})",
        err=True)
    run_id = str(uuid.uuid4())
    handles = []
    for pid in range(processes):
        env = dict(env_base)
        env["PATHWAY_THREADS"] = str(threads)
        env["PATHWAY_PROCESSES"] = str(processes)
        env["PATHWAY_FIRST_PORT"] = str(first_port)
        env["PATHWAY_PROCESS_ID"] = str(pid)
        env["PATHWAY_RUN_ID"] = run_id
        handles.append(subprocess.Popen([program, *arguments], env=env))
    rc = 0
    try:
        for handle in handles:
            rc = handle.wait() or rc
    finally:
        for handle in handles:
            if handle.poll() is None:
                handle.terminate()
    sys.exit(rc)


@click.group()
@click.version_option(version=pw.__version__, prog_name="pathway-tpu")
def cli() -> None:
    pass


_spawn_opts = [
    click.option("-t", "--threads", metavar="N", type=int, default=1,
                 help="number of threads per process"),
    click.option("-n", "--processes", metavar="N", type=int, default=1,
                 help="number of processes"),
    click.option("--first-port", type=int, metavar="PORT", default=10000,
                 help="first port to use for communication"),
]


def _apply(opts, f):
    for opt in reversed(opts):
        f = opt(f)
    return f


@cli.command(context_settings={"allow_interspersed_args": False,
                               "show_default": True})
@click.option("--record", is_flag=True,
              help="record data from connectors while running")
@click.option("--record-path", type=str, default="record",
              help="directory in which recording is stored")
@click.argument("program")
@click.argument("arguments", nargs=-1)
@click.pass_context
def spawn(ctx, record, record_path, program, arguments,
          threads=1, processes=1, first_port=10000):
    env = os.environ.copy()
    if record:
        env["PATHWAY_REPLAY_STORAGE"] = record_path
        env["PATHWAY_SNAPSHOT_ACCESS"] = "record"
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    spawn_program(threads=threads, processes=processes,
                  first_port=first_port, program=program,
                  arguments=arguments, env_base=env)


spawn = _apply(_spawn_opts, spawn)


@cli.command(context_settings={"allow_interspersed_args": False,
                               "show_default": True})
@click.option("--record-path", type=str, default="record",
              help="directory in which recording is stored")
@click.option("--mode",
              type=click.Choice(["batch", "speedrun"], case_sensitive=False),
              help="mode of replaying data")
@click.option("--continue", "continue_after_replay", is_flag=True,
              help="continue with realtime data after the recording replays")
@click.argument("program")
@click.argument("arguments", nargs=-1)
def replay(record_path, mode, continue_after_replay, program, arguments,
           threads=1, processes=1, first_port=10000):
    env = os.environ.copy()
    env["PATHWAY_REPLAY_STORAGE"] = record_path
    env["PATHWAY_SNAPSHOT_ACCESS"] = "replay"
    if mode:
        env["PATHWAY_PERSISTENCE_MODE"] = (
            "batch" if mode.lower() == "batch" else "speedrun_replay")
    if continue_after_replay:
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    spawn_program(threads=threads, processes=processes,
                  first_port=first_port, program=program,
                  arguments=arguments, env_base=env)


replay = _apply(_spawn_opts, replay)


@cli.command()
@click.option("--strict", is_flag=True,
              help="treat warnings as errors (info stays informational)")
@click.option("--require-pipeline", is_flag=True,
              help="fail scripts that build no tables and register no "
                   "sinks (catches graphs hidden behind __main__ guards)")
@click.option("--tpu-mesh", "tpu_mesh", metavar="DATAxMODEL", default=None,
              help="analyze against a hypothetical device topology "
                   "(e.g. 4x2) — arms the PWT1xx sharding/placement "
                   "checks without owning the hardware")
@click.option("--json", "as_json", is_flag=True,
              help="emit machine-readable diagnostics (code, severity, "
                   "file, line, message) on stdout for CI annotation; "
                   "exit-code semantics unchanged")
@click.option("--concurrency", "concurrency", is_flag=True,
              help="run the PWT2xx concurrency lint instead: an AST pass "
                   "over the given source files/directories (thread "
                   "inventory, lock inventory, lock-order graph) — "
                   "nothing is imported or executed")
@click.option("--durability", "durability", is_flag=True,
              help="run the PWT3xx durability lint instead: an AST pass "
                   "over the given source files/directories (snapshot "
                   "capture/restore contracts, atomic persistence writes, "
                   "restricted unpickling) — nothing is imported or "
                   "executed")
@click.option("--perf", "perf", is_flag=True,
              help="run the PWT4xx device-discipline lint instead: an AST "
                   "pass over the given source files/directories "
                   "(recompile zoos, hidden host-device syncs, per-row "
                   "dispatch, donation/residency discipline, warmup "
                   "registry coverage) — nothing is imported or executed")
@click.option("--all", "all_families", is_flag=True,
              help="run every check family in one pass: script analysis "
                   "(PWT0xx expression + PWT1xx shard) over .py file "
                   "arguments, source lints (PWT2xx concurrency + PWT3xx "
                   "durability + PWT4xx perf) over directory arguments; "
                   "--json emits a versioned per-family payload and the "
                   "exit code is a bitmask (expression=1, shard=2, "
                   "concurrency=4, durability=8, perf=16)")
@click.option("--list-waivers", "list_waivers", is_flag=True,
              help="report every inline 'pwt-ok' waiver under the given "
                   "source trees (code, file:line, justification) instead "
                   "of linting; --json emits a machine-readable list for "
                   "CI audit artifacts")
@click.argument("paths", nargs=-1, required=True)
def check(paths, strict, require_pipeline, tpu_mesh, as_json, concurrency,
          durability, perf, all_families, list_waivers):
    """Statically analyze pipeline scripts without running them.

    Imports each script (or every ``*.py`` under a directory) with
    ``pw.run`` disabled, collects the Table plan DAG it builds, and runs
    the static analyzer (internals/static_check/) over it. Scripts are
    imported with ``__name__ == "__pathway_check__"``, so pipelines built
    only under ``if __name__ == "__main__":`` are skipped (reported as
    "no pipeline collected"; an error under ``--require-pipeline``) — add
    an ``if __name__ == "__pathway_check__":`` branch building the graph
    with placeholder inputs to have it checked. Exits nonzero on any
    error-severity diagnostic.

    With ``--concurrency``, ``--durability`` or ``--perf`` the paths are
    treated as SOURCE trees instead: the PWT2xx concurrency lint (thread
    inventory, lock inventory, lock-order graph), the PWT3xx durability
    lint (snapshot coverage, capture/restore symmetry, atomic
    persistence) or the PWT4xx device-discipline lint (recompile zoos,
    hidden host-device syncs, donation/residency discipline) — all
    internals/static_check/ AST passes — run over them without importing
    anything; ``--json`` adds the inventories to the payload.

    ``--all`` runs every family in one invocation; ``--list-waivers``
    audits inline ``pwt-ok`` suppressions instead of linting."""
    import json as _json
    import pathlib

    from pathway_tpu.internals.static_check import (Severity,
                                                    parse_mesh_spec)

    modes = [name for flag, name in (
        (concurrency, "--concurrency"), (durability, "--durability"),
        (perf, "--perf"), (all_families, "--all"),
        (list_waivers, "--list-waivers"),
    ) if flag]
    if len(modes) > 1:
        raise click.UsageError(
            f"{' and '.join(modes)} are mutually exclusive")
    if modes and (tpu_mesh is not None or require_pipeline):
        raise click.UsageError(
            f"{modes[0]} does not compose with "
            "--tpu-mesh/--require-pipeline")
    if concurrency:
        _check_concurrency_cli(paths, strict=strict, as_json=as_json)
        return
    if durability:
        _check_durability_cli(paths, strict=strict, as_json=as_json)
        return
    if perf:
        _check_perf_cli(paths, strict=strict, as_json=as_json)
        return
    if list_waivers:
        _list_waivers_cli(paths, as_json=as_json)
        return
    if all_families:
        _check_all_cli(paths, strict=strict, as_json=as_json)
        return

    mesh = None
    if tpu_mesh is not None:
        try:
            mesh = parse_mesh_spec(tpu_mesh)
        except ValueError as e:
            raise click.UsageError(str(e))

    scripts: list[pathlib.Path] = []
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            # directory mode only gates pipeline entry points: helper
            # modules (_*.py, __init__.py) and hidden dirs (.venv, .git)
            # are skipped — pass a file path explicitly to force a check
            scripts.extend(
                f for f in sorted(path.rglob("*.py"))
                if not f.name.startswith("_")
                and not any(part.startswith(".")
                            for part in f.relative_to(path).parts))
        elif path.suffix == ".py":
            scripts.append(path)
        else:
            raise click.UsageError(f"not a python script or directory: {p}")
    if not scripts:
        raise click.UsageError("no python scripts found under given paths")

    n_errors = 0
    json_out: list[dict] = []
    for script in scripts:
        diagnostics, collected = _collect_and_check(script, mesh=mesh)
        bad = [d for d in diagnostics
               if d.severity is Severity.ERROR
               or (strict and d.severity is Severity.WARNING)]
        if not collected and require_pipeline and not bad:
            n_errors += 1
            click.echo(f"[FAIL] {script} — no pipeline collected "
                       "(--require-pipeline)", err=True)
        elif not collected and not bad:
            click.echo(f"[ok] {script} — no pipeline collected", err=True)
        else:
            n_errors += len(bad)
            status = "FAIL" if bad else "ok"
            click.echo(f"[{status}] {script} — "
                       f"{len(diagnostics)} diagnostic(s)", err=True)
        for d in diagnostics:
            if as_json:
                json_out.append({"script": str(script), **d.to_dict()})
            else:
                click.echo(str(d))
    if as_json:
        click.echo(_json.dumps(json_out, indent=2))
    if n_errors:
        click.echo(f"static check failed: {n_errors} blocking "
                   f"diagnostic(s)", err=True)
        sys.exit(1)


def _check_concurrency_cli(paths, *, strict: bool, as_json: bool) -> None:
    """``check --concurrency``: the PWT2xx source-level lint. Exit-code
    semantics mirror the pipeline check — nonzero on any error-severity
    diagnostic (warnings too under ``--strict``). ``--json`` emits the
    diagnostics plus the thread/lock inventory for CI artifacts."""
    import json as _json

    from pathway_tpu.internals.static_check import (Severity,
                                                    check_concurrency,
                                                    concurrency_inventory)
    from pathway_tpu.internals.static_check.concurrency_check import \
        build_corpus

    try:
        corpus = build_corpus(paths)  # one parse serves check + inventory
        diagnostics = check_concurrency(paths, corpus=corpus)
    except ValueError as e:
        raise click.UsageError(str(e))
    bad = [d for d in diagnostics
           if d.severity is Severity.ERROR
           or (strict and d.severity is Severity.WARNING)]
    if as_json:
        payload = {
            "diagnostics": [d.to_dict() for d in diagnostics],
            "inventory": concurrency_inventory(paths, corpus=corpus),
        }
        click.echo(_json.dumps(payload, indent=2))
    else:
        for d in diagnostics:
            click.echo(str(d))
    status = "FAIL" if bad else "ok"
    click.echo(f"[{status}] concurrency check over {', '.join(paths)} — "
               f"{len(diagnostics)} diagnostic(s)", err=True)
    if bad:
        click.echo(f"concurrency check failed: {len(bad)} blocking "
                   f"diagnostic(s)", err=True)
        sys.exit(1)


def _check_durability_cli(paths, *, strict: bool, as_json: bool) -> None:
    """``check --durability``: the PWT3xx source-level lint. Same
    exit-code semantics as ``--concurrency``; ``--json`` adds the
    stateful-operator/fault-point inventory for CI artifacts."""
    import json as _json

    from pathway_tpu.internals.static_check import (Severity,
                                                    check_durability,
                                                    durability_inventory)
    from pathway_tpu.internals.static_check.durability_check import \
        build_corpus

    try:
        corpus = build_corpus(paths)  # one parse serves check + inventory
        diagnostics = check_durability(paths, corpus=corpus)
    except ValueError as e:
        raise click.UsageError(str(e))
    bad = [d for d in diagnostics
           if d.severity is Severity.ERROR
           or (strict and d.severity is Severity.WARNING)]
    if as_json:
        payload = {
            "diagnostics": [d.to_dict() for d in diagnostics],
            "inventory": durability_inventory(paths, corpus=corpus),
        }
        click.echo(_json.dumps(payload, indent=2))
    else:
        for d in diagnostics:
            click.echo(str(d))
    status = "FAIL" if bad else "ok"
    click.echo(f"[{status}] durability check over {', '.join(paths)} — "
               f"{len(diagnostics)} diagnostic(s)", err=True)
    if bad:
        click.echo(f"durability check failed: {len(bad)} blocking "
                   f"diagnostic(s)", err=True)
        sys.exit(1)


def _check_perf_cli(paths, *, strict: bool, as_json: bool) -> None:
    """``check --perf``: the PWT4xx device-discipline lint. Same
    exit-code semantics as ``--concurrency``; ``--json`` adds the jit /
    hot-unit / warmup-registry inventory for CI artifacts."""
    import json as _json

    from pathway_tpu.internals.static_check import (Severity, check_perf,
                                                    perf_inventory)
    from pathway_tpu.internals.static_check.durability_check import \
        build_corpus

    try:
        corpus = build_corpus(paths)  # one parse serves check + inventory
        diagnostics = check_perf(paths, corpus=corpus)
    except ValueError as e:
        raise click.UsageError(str(e))
    bad = [d for d in diagnostics
           if d.severity is Severity.ERROR
           or (strict and d.severity is Severity.WARNING)]
    if as_json:
        payload = {
            "diagnostics": [d.to_dict() for d in diagnostics],
            "inventory": perf_inventory(paths, corpus=corpus),
        }
        click.echo(_json.dumps(payload, indent=2))
    else:
        for d in diagnostics:
            click.echo(str(d))
    status = "FAIL" if bad else "ok"
    click.echo(f"[{status}] perf check over {', '.join(paths)} — "
               f"{len(diagnostics)} diagnostic(s)", err=True)
    if bad:
        click.echo(f"perf check failed: {len(bad)} blocking "
                   f"diagnostic(s)", err=True)
        sys.exit(1)


def _list_waivers_cli(paths, *, as_json: bool) -> None:
    """``check --list-waivers``: audit inline ``pwt-ok`` suppressions.
    Always exits 0 — waivers are sanctioned, the point is visibility
    (the CI durability-lint job archives the JSON as an audit artifact)."""
    import json as _json

    from pathway_tpu.internals.static_check import (render_waivers,
                                                    scan_waivers)

    try:
        waivers = scan_waivers(paths)
    except ValueError as e:
        raise click.UsageError(str(e))
    if as_json:
        click.echo(_json.dumps(waivers, indent=2))
    elif waivers:
        click.echo(render_waivers(waivers))
    click.echo(f"[ok] {_plural(len(waivers), 'waiver', 'waivers')} under "
               f"{', '.join(paths)}", err=True)


# ``check --all`` exit code is a bitmask so CI can tell which family
# regressed from the code alone (and --json mirrors it as "exit_code")
_FAMILY_BITS = {"expression": 1, "shard": 2, "concurrency": 4,
                "durability": 8, "perf": 16}


def _defer_pwt105(shard_diags: list, trees) -> list:
    """PWT105 defers to PWT402 when both families run in one invocation:
    drop PWT105 findings whose UDF *definition* (the related trace
    shard_check attaches) lives under a tree the PWT4xx pass scanned —
    the wider device-path lint already covers that source, and keeping
    both would double-report every sync site."""
    import pathlib

    roots = [pathlib.Path(t).resolve() for t in trees]

    def _covered(d) -> bool:
        if d.code != "PWT105" or not d.related:
            return False
        f = pathlib.Path(d.related[0].file_name).resolve()
        return any(root == f or root in f.parents for root in roots)

    return [d for d in shard_diags if not _covered(d)]


def _check_all_cli(paths, *, strict: bool, as_json: bool) -> None:
    """``check --all``: every family in one invocation. ``.py`` file
    arguments get the script analysis (PWT0xx expression / PWT1xx shard,
    split per diagnostic code); directory arguments get the source lints
    (PWT2xx concurrency, PWT3xx durability, PWT4xx perf). The JSON
    payload is versioned (``schema_version``) so CI consumers can evolve
    with it."""
    import json as _json
    import pathlib

    from pathway_tpu.internals.static_check import (Severity,
                                                    check_concurrency,
                                                    check_durability,
                                                    check_perf)

    scripts = [p for p in paths if pathlib.Path(p).suffix == ".py"]
    trees = [p for p in paths if p not in scripts]
    for p in trees:
        if not pathlib.Path(p).is_dir():
            raise click.UsageError(
                f"not a python script or directory: {p}")

    families: dict[str, list] = {
        "expression": [], "shard": [], "concurrency": [],
        "durability": [], "perf": []}
    for script in scripts:
        diagnostics, _collected = _collect_and_check(
            pathlib.Path(script), mesh=None)
        for d in diagnostics:
            fam = "shard" if d.code.startswith("PWT1") else "expression"
            families[fam].append(d)
    if trees:
        try:
            families["concurrency"] = check_concurrency(trees)
            families["durability"] = check_durability(trees)
            families["perf"] = check_perf(trees)
        except ValueError as e:
            raise click.UsageError(str(e))
        families["shard"] = _defer_pwt105(families["shard"], trees)

    exit_code = 0
    for fam, diagnostics in families.items():
        bad = [d for d in diagnostics
               if d.severity is Severity.ERROR
               or (strict and d.severity is Severity.WARNING)]
        if bad:
            exit_code |= _FAMILY_BITS[fam]
        if not as_json:
            for d in diagnostics:
                click.echo(str(d))
        click.echo(f"[{'FAIL' if bad else 'ok'}] {fam} — "
                   f"{len(diagnostics)} diagnostic(s)", err=True)
    if as_json:
        click.echo(_json.dumps({
            # v2: adds the "perf" family (PWT4xx, exit bit 16) and the
            # PWT105→PWT402 deference over shared trees
            "schema_version": 2,
            "families": {fam: [d.to_dict() for d in diagnostics]
                         for fam, diagnostics in families.items()},
            "exit_code": exit_code,
        }, indent=2))
    if exit_code:
        click.echo(f"static check failed (family bitmask {exit_code})",
                   err=True)
        sys.exit(exit_code)


def _collect_and_check(script, mesh=None):
    """Import one script in collect-only mode and analyze its graph.

    Returns ``(diagnostics, collected)`` where ``collected`` is False when
    the script built no tables and registered no sinks — indistinguishable
    from "clean" otherwise, which would make directory gates vacuous."""
    import runpy

    from pathway_tpu.internals import run as _run_module
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.static_check import Diagnostic, analyze

    def _collect_only(**kwargs):
        return None

    def _register_as_sink(table, **kwargs):
        # debug prints count as the pipeline's intended outputs, but must
        # not execute the engine during a static check
        G.add_output(lambda runner: None, table=table, sink="debug")

    patched = [(pw, "run", _collect_only), (pw, "run_all", _collect_only),
               (_run_module, "run", _collect_only),
               (_run_module, "run_all", _collect_only),
               (pw.debug, "compute_and_print", _register_as_sink),
               (pw.debug, "compute_and_print_update_stream",
                _register_as_sink)]
    saved = [getattr(mod, name) for mod, name, _ in patched]

    # the graph registry holds Tables only weakly; pin every table the
    # script constructs so the DAG survives until analyze() even if the
    # module globals are gone (e.g. the script calls sys.exit(0))
    keep_alive: list = []
    _real_register = G.register_table

    def _register_pinned(table):
        keep_alive.append(table)
        _real_register(table)

    G.clear()
    script_dir = os.path.dirname(os.path.abspath(str(script)))
    sys.path.insert(0, script_dir)
    G.register_table = _register_pinned
    # scripts in one directory may share helper modules with import-time
    # side effects; drop helpers this script imports afterwards so every
    # script's collection runs against a cold import cache
    modules_before = set(sys.modules)

    def _is_local_helper(name: str) -> bool:
        f = getattr(sys.modules.get(name), "__file__", None)
        return bool(f) and os.path.abspath(f).startswith(
            script_dir + os.sep)
    try:
        for mod, name, stub in patched:
            setattr(mod, name, stub)
        try:
            runpy.run_path(str(script), run_name="__pathway_check__")
        except KeyboardInterrupt:
            raise  # Ctrl-C must abort the whole check, not log a PWT000
        except SystemExit as e:
            if e.code not in (None, 0):
                return [Diagnostic(
                    code="PWT000",
                    message="script exited with status "
                            f"{e.code} during collection")], True
            # clean exit: analyze what was collected
        except BaseException as e:  # noqa: BLE001 — report, do not crash
            return [Diagnostic(
                code="PWT000",
                message=f"script failed during collection: {e!r}")], True
        collected = bool(G.tables() or G.outputs)
        from pathway_tpu.engine.qos import qos_enabled_from_env

        # PWT013 arming from the CLI: the script's run-time qos= argument
        # is unknowable here, but an explicit PATHWAY_QOS decision in the
        # environment (1 = enabled, 0 = the documented waiver) must be
        # honored the same way pw.run honors it
        diagnostics = analyze(graph=G, mesh=mesh,
                              qos_enabled=qos_enabled_from_env())
        return diagnostics, collected
    finally:
        for (mod, name, _), fn in zip(patched, saved):
            setattr(mod, name, fn)
        del G.register_table  # drop the instance shadow of the class method
        sys.path.remove(script_dir)
        for name in set(sys.modules) - modules_before:
            # framework/third-party modules stay cached: re-executing them
            # repeats registration side effects (and C extensions such as
            # jaxlib do not survive partial re-import at all)
            if _is_local_helper(name):
                del sys.modules[name]
        G.clear()


@cli.command("trace-merge")
@click.option("--out", "out_path", type=str, default=None,
              help="where to write the merged trace "
                   "(default: <dir>/fleet_trace.json)")
@click.argument("paths", nargs=-1, required=True)
def trace_merge(paths, out_path):
    """Merge per-process Chrome trace files into ONE clock-aligned
    fleet timeline (engine/fleet_observability.py).

    PATHS are trace JSON files — or directories scanned for ``*.json``
    files that look like Chrome traces (a ``traceEvents`` list). Each
    process's ``pathway_meta`` block (written by the flight recorder:
    pid, role, process label, monotonic↔wall clock anchor) places its
    events on the shared wall-clock timeline; request ids that appear in
    several processes get cross-process flow arrows, so a failover
    renders as an arrow from the router into the rescuing replica's
    track. The merged file opens directly in Perfetto."""
    import pathlib

    from pathway_tpu.engine.fleet_observability import merge_traces
    from pathway_tpu.engine.flight_recorder import atomic_write_json

    files: list[pathlib.Path] = []
    first_dir: pathlib.Path | None = None
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            if first_dir is None:
                first_dir = path
            files.extend(sorted(path.glob("*.json")))
        elif path.is_file():
            files.append(path)
        else:
            raise click.UsageError(f"no such file or directory: {p}")
    if out_path is None:
        out_path = str((first_dir or pathlib.Path("."))
                       / "fleet_trace.json")
    payloads = []
    for f in files:
        if os.path.abspath(str(f)) == os.path.abspath(out_path):
            continue  # re-running over a dir must not merge its own output
        try:
            with open(f) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            continue
        if isinstance(data, dict) and isinstance(
                data.get("traceEvents"), list):
            payloads.append(data)
        else:
            click.echo(f"[skip] {f} — not a Chrome trace payload",
                       err=True)
    if not payloads:
        raise click.UsageError(
            "no Chrome trace payloads found under the given paths "
            "(run with PATHWAY_TRACE_PATH set on each process, or point "
            "at the router's /fleet/trace output)")
    merged = merge_traces(payloads)
    atomic_write_json(out_path, merged)
    fleet = merged["pathway_fleet"]
    click.echo(
        f"merged {len(payloads)} process trace(s) -> {out_path}: "
        f"{len(merged['traceEvents'])} events, "
        f"{len(fleet['cross_process_request_ids'])} request id(s) "
        f"spanning processes "
        f"({', '.join(p['role'] + ':' + p['process'] for p in fleet['processes'])})",
        err=True)


@cli.command("profdiff")
@click.option("--json", "as_json", is_flag=True,
              help="emit the full structured diff as JSON on stdout")
@click.argument("baseline", type=str)
@click.argument("flagged", type=str)
def profdiff(baseline, flagged, as_json):
    """Name the dominant frame/kernel delta between two profiled runs.

    BASELINE and FLAGGED are ``bench.py --profile`` artifacts
    (BENCH_*.json with embedded ``profile`` epochs) or bare profile
    epochs; the comparison (engine/profiler.py diff_profiles) ranks
    per-kernel-family device-ms-per-dispatch deltas and per-host-frame
    sample-share deltas, so a flagged ``--check-regression`` run gets a
    culprit name instead of just a number."""
    from pathway_tpu.engine.profiler import diff_profiles

    docs = []
    for p in (baseline, flagged):
        try:
            with open(p) as f:
                docs.append(json.load(f))
        except (OSError, ValueError) as e:
            raise click.UsageError(f"cannot read {p}: {e}")
    try:
        diff = diff_profiles(docs[0], docs[1])
    except ValueError as e:
        raise click.UsageError(str(e))
    if as_json:
        click.echo(json.dumps(diff, indent=2))
        return
    dk = diff["dominant_kernel"]
    if dk is not None:
        click.echo(
            f"dominant kernel delta: {dk['family']} "
            f"{dk['device_ms_per_dispatch_a']} -> "
            f"{dk['device_ms_per_dispatch_b']} ms/dispatch"
            + (f" (x{dk['ratio']})" if dk.get("ratio") else "")
            + (f", {dk['bound_by']}-bound" if dk.get("bound_by") else ""))
    df = diff["dominant_frame"]
    if df is not None:
        click.echo(f"dominant host frame delta: {df['frame']} "
                   f"sample share {df['share_a']} -> {df['share_b']}")
    if "mfu_rolling_delta" in diff:
        click.echo(f"rolling MFU delta: {diff['mfu_rolling_delta']:+}")
    for row in diff["kernel_deltas"][:6]:
        click.echo(f"  kernel {row['family']}: "
                   f"{row['delta_ms_per_dispatch']:+} ms/dispatch",
                   err=True)
    for row in diff["frame_deltas"][:6]:
        click.echo(f"  frame {row['frame']}: {row['delta_share']:+} share",
                   err=True)


@cli.command()
def spawn_from_env():
    """Run ``spawn`` with arguments taken from PATHWAY_SPAWN_ARGS
    (reference cli.py:125 — the container entrypoint hook)."""
    args = os.environ.get("PATHWAY_SPAWN_ARGS")
    if args:
        cli.main(args=["spawn", *args.split(" ")],
                 prog_name="pathway-tpu", standalone_mode=True)


def main() -> None:
    cli.main(prog_name="pathway-tpu")


if __name__ == "__main__":
    main()

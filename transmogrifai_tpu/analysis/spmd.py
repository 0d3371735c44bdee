"""SPMD contract auditor (``TPS0xx``) — the seventh analyser.

The ``parallel/`` plane is the repo's only subsystem whose correctness
depends on N processes executing the SAME program in the SAME order: a
``psum`` is a rendezvous, and a host that reaches it late, never, or out
of order deadlocks the mesh (or silently merges the wrong statistics).
None of the six existing analysers can see that contract — TPA walks the
user DAG, TPX/TPJ audit the serving plan and its programs, TPL/TPC lint
single-process invariants. This module audits the parallel plane in
three legs, mirroring the TPC/TPJ architecture:

* **Static AST pass** (:func:`analyze_paths`) over the SPMD surface
  (``parallel/``, ``models/trees.py``, ``resilience/distributed.py``):

  - **TPS001** — python control flow conditioned on a *host-varying*
    value (``process_index()``, host row slices, wall-clock readings,
    retry/failover state) guarding a collective: hosts may issue
    collectives in different orders or different counts — the classic
    SPMD deadlock (the PR-3 ``FailoverController`` re-entry shape). A
    branch predicate that is itself the result of a collective is
    host-invariant by construction (all hosts agreed on it), so the
    barrier-fixed twin of a divergent branch scans clean.
  - **TPS002** — a ``shard_map`` body using an axis name its wrapping
    ``mesh``/``in_specs``/``out_specs`` never bind (the compat-shim
    break class: the kernel traces, then dies on the first real mesh).
  - **TPS003** — a ``PartitionSpec`` whose axis names are not in the
    sharded mesh's vocabulary, or whose entry count disagrees with the
    statically-known rank of the array it shards.
  - **TPS004** — a non-commutative or dtype-unstable op inside a
    shard_map reduction kernel: subtraction of two collective-reduced
    values (the raw-moment variance shape — catastrophic f32
    cancellation) or a 64-bit dtype in the kernel body. Both break the
    ``_guarded`` contract of commutative bit-identical merges.
  - **TPS005** — a collective issued while holding a lock: host A waits
    in the collective holding the lock, host B needs the lock to reach
    its collective — a cross-host ABBA that bridges into the TPC lock
    graph.
  - **TPS007** — a host-dependent shape (unpadded host row block)
    feeding a placement/dispatch primitive: every host compiles its own
    program (recompile storm), and shape-divergent collectives hang.

* **IR leg** (:func:`static_collective_census`): every shard_map kernel
  registered through ``program_trace_specs()`` (``parallel/reductions``,
  ``multihost``, ``ring``, ``segments`` — the PR-6 registry, extended)
  traces to its jaxpr over a device-free ``AbstractMesh`` and yields a
  **static collective census**: count + primitive + axes of every
  collective in the program. The lowered StableHLO is then reconciled
  against it — **TPS006** flags an HLO collective kind the jaxpr census
  never declared (hidden resharding: exactly what ROADMAP item 3's
  explicit-PartitionSpec acceptance needs to refuse).

* **Dynamic reconciler** (:func:`reconcile_collective_orders`): under
  ``TPTPU_COLLECTIVE_TRACE=1`` the canonical seam
  (``parallel/guarded.py`` — every collective already funnels through
  it) records each simulated host's ``(sequence#, name)`` collective
  tape, through failovers (a lost host's tape freezes). The reconciler
  asserts every survivor's tape is IDENTICAL, every lost host's tape is
  a prefix of it, and every issued name is explained by the static seam
  census — **TPS008** otherwise. The third static-vs-runtime reconciler
  after the transfer census and the lock-order graph.

Entry points: ``python -m transmogrifai_tpu lint --spmd`` (gated on the
committed ``spmd_baseline.json`` — same (code, path, line-text) keying
and exit-3 contract as TPL/TPC/TPJ), ``--all`` includes the family, and
``summary_json()["analysis"]["spmd"]`` carries the compact package
summary. ``bench.py multichip`` stamps the ``collectiveAudit`` verdict
into the MULTICHIP artifact.
"""
from __future__ import annotations

import ast
import functools
import os
from typing import Any, Iterable, Sequence

from .findings import Report, Severity, attr_chain, suppressed

__all__ = [
    "DEFAULT_SPMD_PATHS",
    "analyze_paths",
    "analyze_source",
    "audit_spmd",
    "default_spmd_paths",
    "hlo_collective_kinds",
    "package_summary",
    "reconcile_collective_orders",
    "reconcile_hlo_census",
    "seam_collective_census",
    "static_collective_census",
]

#: the SPMD surface: every module that builds or drives shard_map
#: kernels / cross-host collectives
DEFAULT_SPMD_PATHS = (
    "transmogrifai_tpu/parallel",
    "transmogrifai_tpu/models/trees.py",
    "transmogrifai_tpu/resilience/distributed.py",
    # the sharded-sweep driver: workflow CV routes GLM lanes through the
    # SweepLayout pjit path (parallel/sweep.py registers the programs;
    # this entry keeps the DRIVING code on the static TPS surface too)
    "transmogrifai_tpu/workflow/cv.py",
)

# ---- vocabularies ---------------------------------------------------------
#: call names that ISSUE a collective (directly or through the guarded
#: seam) — reaching one is a cross-host rendezvous
_LAX_COLLECTIVES = {
    "psum", "pmin", "pmax", "pmean", "ppermute", "all_gather",
    "all_to_all", "pshuffle", "pbroadcast", "psum_scatter",
}
_REDUCTION_ENTRIES = {
    "pcolumn_stats", "pcentered_gram", "pxtx", "phistogram",
    "pcontingency", "global_column_stats", "ring_gram", "ring_corr",
    "psegment_reduce", "aggregate_events_on_device",
}
_SEAM_ENTRIES = {"guarded_collective", "_guarded"}
#: cross-host sync points that every host must reach (global-array
#: assembly blocks until all processes call it)
_SYNC_ENTRIES = {"make_global_array", "make_array_from_process_local_data",
                 "ingest_global_array", "sync_global_devices"}
COLLECTIVE_CALLS = (
    _LAX_COLLECTIVES | _REDUCTION_ENTRIES | _SEAM_ENTRIES | _SYNC_ENTRIES
)

#: calls whose RESULT varies per host (taint seeds for TPS001/TPS007)
_HOST_VARYING_CALLS = {
    "process_index", "host_row_slice", "read_host_block",
    "dead_hosts", "live_hosts",
    # wall-clock readings: per-host timing is the retry/failover
    # divergence channel (the CollectiveGuard re-entry shape)
    "time", "monotonic", "perf_counter", "perf_counter_ns", "clock",
}
#: parameter / attribute terminal names treated as host-varying state
_HOST_VARYING_NAMES = {
    "host", "host_id", "host_index", "process_id", "lost", "lost_hosts",
}

#: jaxpr primitives that are collectives (the census vocabulary).
#: ``psum2`` is shard_map's replication-checked rewrite of ``psum``
#: (check_rep=True re-expresses psum as pbroadcast + psum2).
COLLECTIVE_PRIMITIVES = {
    "psum", "psum2", "pmin", "pmax", "ppermute", "all_gather",
    "all_to_all", "reduce_scatter", "pbroadcast", "psum_scatter",
    "pgather",
}

#: lowered-HLO collective kinds -> the jaxpr primitives that declare them
HLO_KIND_SOURCES = {
    "all_reduce": ("psum", "psum2", "pmin", "pmax", "psum_scatter"),
    "collective_permute": ("ppermute",),
    "all_gather": ("all_gather",),
    "all_to_all": ("all_to_all", "pgather"),
    "reduce_scatter": ("reduce_scatter", "psum_scatter"),
    "collective_broadcast": ("pbroadcast",),
}

#: axis-name constants of the parallel plane (module-qualified names are
#: resolved per-file too; these cover cross-module imports)
_AXIS_CONSTANTS = {"DATA_AXIS": "data", "MODEL_AXIS": "model",
                   "DCN_AXIS": "dcn"}

#: known mesh constructors -> the axis vocabulary they bind
_MESH_CTOR_AXES = {
    "make_mesh": {"data", "model"},
    "auto_mesh": {"data", "model"},
    "default_execution_mesh": {"data", "model"},
    "make_multihost_mesh": {"dcn", "data", "model"},
}

#: spec-helper functions -> the axis names their PartitionSpec binds
_SPEC_HELPER_AXES = {"_data_spec": {"data"}, "dcn_data_spec": {"dcn", "data"}}


def _call_name(node: ast.AST) -> str:
    chain = attr_chain(node.func) if isinstance(node, ast.Call) else []
    return chain[-1] if chain else ""


def _expr_names(expr: ast.AST) -> set[str]:
    return {
        n.id for n in ast.walk(expr)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


# ==========================================================================
# axis / PartitionSpec resolution (TPS002 / TPS003)
# ==========================================================================
class _AxisEnv:
    """Resolves expressions to axis-name sets: string constants, module
    axis constants, local assignments of strings/tuples, P(...) specs and
    the per-module spec helpers. Unresolvable -> None (never guess)."""

    def __init__(self, module_consts: dict[str, Any], helpers: dict[str, set]):
        self.consts = dict(module_consts)
        self.helpers = dict(helpers)
        self.local: dict[str, Any] = {}

    def bind_local(self, name: str, value: Any) -> None:
        self.local[name] = value

    def axis_of(self, expr: ast.AST) -> set[str] | None:
        """Axis names an axis-argument expression denotes, or None."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return {expr.value}
        if isinstance(expr, (ast.Tuple, ast.List)):
            out: set[str] = set()
            for el in expr.elts:
                sub = self.axis_of(el)
                if sub is None:
                    return None
                out |= sub
            return out
        if isinstance(expr, ast.Name):
            val = self.local.get(expr.id, self.consts.get(expr.id))
            if isinstance(val, str):
                return {val}
            if isinstance(val, (set, frozenset)):
                return set(val)
            return None
        chain = attr_chain(expr)
        if chain and chain[-1] in _AXIS_CONSTANTS:
            return {_AXIS_CONSTANTS[chain[-1]]}
        return None

    def spec_axes(self, expr: ast.AST) -> tuple[set[str], int] | None:
        """(axis names, entry count) of a PartitionSpec-building
        expression: ``P(...)`` literals, spec-helper calls, or names
        bound to one. None when unresolvable."""
        if isinstance(expr, ast.Call):
            name = _call_name(expr)
            if name in ("P", "PartitionSpec"):
                axes: set[str] = set()
                for a in expr.args:
                    if isinstance(a, ast.Constant) and a.value is None:
                        continue
                    sub = self.axis_of(a)
                    if sub is None:
                        return None
                    axes |= sub
                if any(isinstance(a, ast.Starred) for a in expr.args):
                    return None
                return axes, len(expr.args)
            if name in self.helpers:
                # helper(*trailing): 1 leading sharded entry + trailing
                return set(self.helpers[name]), 1 + len(expr.args)
        if isinstance(expr, ast.Name):
            val = self.local.get(expr.id)
            if isinstance(val, tuple) and len(val) == 2 and \
                    isinstance(val[0], set):
                return val
        return None


def _module_axis_consts(tree: ast.Module) -> dict[str, str]:
    """Module-level ``NAME = "axis"`` string constants (plus the shared
    cross-module axis names)."""
    out = dict(_AXIS_CONSTANTS)
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, str):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = stmt.value.value
    return out


def _module_spec_helpers(tree: ast.Module, consts: dict) -> dict[str, set]:
    """Functions whose body returns a single ``P(...)`` — the local spec
    helpers (``_data_spec``); their bound axis names by helper name."""
    helpers = dict(_SPEC_HELPER_AXES)
    env = _AxisEnv(consts, {})
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Return) and isinstance(
                stmt.value, ast.Call
            ) and _call_name(stmt.value) in ("P", "PartitionSpec"):
                axes: set[str] = set()
                ok = True
                for a in stmt.value.args:
                    if isinstance(a, ast.Starred):
                        continue
                    if isinstance(a, ast.Constant) and a.value is None:
                        continue
                    sub = env.axis_of(a)
                    if sub is None:
                        ok = False
                        break
                    axes |= sub
                if ok and axes:
                    helpers[node.name] = axes
    return helpers


def _is_shard_map_decorated(fn: ast.FunctionDef) -> ast.Call | None:
    """The ``partial(shard_map, ...)`` / ``shard_map(...)`` decorator
    Call of a kernel def, else None."""
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        name = _call_name(dec)
        if name == "shard_map":
            return dec
        if name == "partial" and dec.args and \
                attr_chain(dec.args[0])[-1:] == ["shard_map"]:
            return dec
    return None


def _collect_local_axis_bindings(fn: ast.AST, env: _AxisEnv) -> None:
    """Resolve simple local assigns (``axes = (DCN_AXIS, DATA_AXIS)``,
    ``spec = P("data", None)``) so axis args and specs passed by name
    resolve. Encountered in source order; last bind wins."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        t = node.targets[0]
        if not isinstance(t, ast.Name):
            continue
        axes = env.axis_of(node.value)
        if axes is not None:
            env.bind_local(t.id, axes if len(axes) > 1 else next(iter(axes)))
            continue
        spec = env.spec_axes(node.value)
        if spec is not None:
            env.bind_local(t.id, spec)


#: axis-consuming calls -> which positional arg names the axis
_AXIS_ARG_POS = {
    "psum": 1, "pmin": 1, "pmax": 1, "pmean": 1, "ppermute": 1,
    "all_gather": 1, "all_to_all": 1, "pbroadcast": 1, "pshuffle": 1,
    "psum_scatter": 1, "axis_index": 0,
}


def _kernel_used_axes(fn: ast.FunctionDef, env: _AxisEnv):
    """(axis name, call name, lineno) for every resolvable axis-consuming
    call in a shard_map body; unresolvable axis args are skipped."""
    out: list[tuple[set, str, int]] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        pos = _AXIS_ARG_POS.get(name)
        if pos is None:
            continue
        axis_expr = None
        if len(node.args) > pos:
            axis_expr = node.args[pos]
        else:
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis"):
                    axis_expr = kw.value
        if axis_expr is None:
            continue
        axes = env.axis_of(axis_expr)
        if axes:
            out.append((axes, name, node.lineno))
    return out


def _shard_map_bound_axes(dec: ast.Call, env: _AxisEnv) -> tuple[set, bool]:
    """(bound axis names, resolved?) from the decorator's mesh/in_specs/
    out_specs kwargs. resolved=False when NOTHING resolved (judging used
    axes against an empty guess would be noise, not analysis)."""
    bound: set[str] = set()
    resolved = False
    for kw in dec.keywords:
        if kw.arg == "mesh":
            if isinstance(kw.value, ast.Call):
                ctor = _call_name(kw.value)
                if ctor in _MESH_CTOR_AXES:
                    bound |= _MESH_CTOR_AXES[ctor]
                    resolved = True
        elif kw.arg in ("in_specs", "out_specs"):
            exprs = (
                kw.value.elts
                if isinstance(kw.value, (ast.Tuple, ast.List))
                else [kw.value]
            )
            for e in exprs:
                spec = env.spec_axes(e)
                if spec is not None:
                    bound |= spec[0]
                    resolved = True
    return bound, resolved


# ==========================================================================
# host-varying taint (TPS001) + host-shaped taint (TPS007)
# ==========================================================================
def _is_host_varying_expr(expr: ast.AST, tainted: set[str]) -> list[str]:
    """The host-varying sources an expression consumes: tainted local
    names, host-varying calls, host-state attribute reads. A value that
    came out of a collective is host-INVARIANT (all hosts agreed), so
    collective-call results never taint — that is the barrier-fixed twin."""
    hits: list[str] = []
    skip: set[int] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name in COLLECTIVE_CALLS:
                for inner in ast.walk(node):
                    skip.add(id(inner))
    for node in ast.walk(expr):
        if id(node) in skip:
            continue
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name in _HOST_VARYING_CALLS:
                hits.append(f"{name}()")
        elif isinstance(node, ast.Attribute) and \
                node.attr in _HOST_VARYING_NAMES:
            hits.append(node.attr)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id in tainted:
            hits.append(node.id)
    return hits


def _collective_calls_in(body: Iterable[ast.stmt]) -> list[tuple[str, int]]:
    out: list[tuple[str, int]] = []
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name in COLLECTIVE_CALLS:
                    out.append((name, node.lineno))
    return out


def _scan_order_divergence(fn: ast.AST, hits: list) -> None:
    """TPS001 over one function: in-order taint of host-varying values,
    then (a) a tainted branch/loop guarding a collective, (b) a loop
    containing both a collective and a tainted early exit — different
    iteration counts issue different collective counts per host."""
    tainted: set[str] = {
        p.arg for p in (
            list(fn.args.posonlyargs) + list(fn.args.args)
            + list(fn.args.kwonlyargs)
        )
        if p.arg in _HOST_VARYING_NAMES
    }

    def visit(stmts: Iterable[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs get their own pass
            if isinstance(stmt, ast.Assign):
                sources = _is_host_varying_expr(stmt.value, tainted)
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        if sources:
                            tainted.add(t.id)
                        else:
                            tainted.discard(t.id)
            elif isinstance(stmt, ast.AugAssign) and isinstance(
                stmt.target, ast.Name
            ):
                if _is_host_varying_expr(stmt.value, tainted):
                    tainted.add(stmt.target.id)
            elif isinstance(stmt, (ast.If, ast.While)):
                sources = _is_host_varying_expr(stmt.test, tainted)
                if sources:
                    for name, lineno in _collective_calls_in(
                        stmt.body + stmt.orelse
                    ):
                        kind = "if" if isinstance(stmt, ast.If) else "while"
                        hits.append((
                            "TPS001", lineno,
                            f"collective {name}() guarded by a python "
                            f"`{kind}` on host-varying value(s) "
                            f"{sorted(set(sources))} — hosts may issue "
                            "collectives in different orders/counts "
                            "(derive the predicate from an agreeing "
                            "collective, or hoist the collective out of "
                            "the branch)",
                        ))
            elif isinstance(stmt, ast.For):
                sources = _is_host_varying_expr(stmt.iter, tainted)
                if sources:
                    for name, lineno in _collective_calls_in(stmt.body):
                        hits.append((
                            "TPS001", lineno,
                            f"collective {name}() inside a loop over "
                            f"host-varying {sorted(set(sources))} — hosts "
                            "iterate different counts and issue different "
                            "collective sequences",
                        ))
            # loops whose EXIT depends on host-varying state while the
            # body issues collectives: the retry/failover re-entry shape
            if isinstance(stmt, (ast.While, ast.For)):
                colls = _collective_calls_in(stmt.body)
                if colls:
                    # pre-taint the loop body's own assignments (in
                    # source order): the exit predicate usually consumes
                    # a value the SAME iteration computed (`took =
                    # clock() - start`)
                    body_taint = set(tainted)
                    for node in sorted(
                        (n for inner in stmt.body
                         for n in ast.walk(inner)
                         if isinstance(n, (ast.Assign, ast.AugAssign))),
                        key=lambda n: n.lineno,
                    ):
                        value = node.value
                        targets = (
                            node.targets
                            if isinstance(node, ast.Assign)
                            else [node.target]
                        )
                        varying = _is_host_varying_expr(value, body_taint)
                        for t in targets:
                            if isinstance(t, ast.Name):
                                if varying:
                                    body_taint.add(t.id)
                                elif isinstance(node, ast.Assign):
                                    body_taint.discard(t.id)
                    for inner in stmt.body:
                        for node in ast.walk(inner):
                            if isinstance(node, ast.If) and any(
                                isinstance(x, (ast.Break, ast.Continue,
                                               ast.Return))
                                for b in (node.body, node.orelse)
                                for x in b
                            ):
                                sources = _is_host_varying_expr(
                                    node.test, body_taint
                                )
                                if sources:
                                    name, lineno = colls[0]
                                    hits.append((
                                        "TPS001", node.lineno,
                                        "loop re-issues collective "
                                        f"{name}() (line {lineno}) but "
                                        "exits on host-varying "
                                        f"{sorted(set(sources))} — hosts "
                                        "retry different numbers of times "
                                        "(the failover re-entry shape); "
                                        "agree on the retry decision "
                                        "collectively first",
                                    ))
            # recurse into nested bodies in source order
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, attr, None)
                if sub:
                    visit(sub)
            for handler in getattr(stmt, "handlers", ()):
                visit(handler.body)

    visit(list(getattr(fn, "body", ())))


#: shape-fixing producers that clear the host-shaped taint (TPS007)
_SHAPE_FIXERS = {"pad_rows", "pad_cols", "zeros", "ones", "full", "empty",
                 "concatenate"}
#: placement/dispatch sinks a host-shaped value must not reach
_PLACEMENT_SINKS = {"make_global_array", "shard_rows", "shard_cols",
                    "device_put", "shard_rows_if_active"}


def _scan_host_shapes(fn: ast.AST, hits: list) -> None:
    """TPS007 over one function: values whose SHAPE derives from this
    host's real-row block (``read_host_block``, ``x[host_row_slice(...)]``)
    must be padded to the host-invariant block before they reach a
    placement primitive — otherwise every host compiles its own program
    and shape-divergent collectives hang."""
    shaped: set[str] = set()   # names carrying a host-dependent shape
    slices: set[str] = set()   # names bound to a host_row_slice result

    def value_shaped(expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name in ("read_host_block",):
                    return True
                if name == "host_row_slice":
                    return False  # the slice itself; subscripting taints
            elif isinstance(node, ast.Subscript):
                sl = node.slice
                if isinstance(sl, ast.Name) and sl.id in slices:
                    return True
                if isinstance(sl, ast.Call) and \
                        _call_name(sl) == "host_row_slice":
                    return True
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load) and node.id in shaped:
                return True
        return False

    def visit(stmts: Iterable[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(stmt, ast.Assign):
                val = stmt.value
                fixer = isinstance(val, ast.Call) and \
                    _call_name(val) in _SHAPE_FIXERS
                is_slice = isinstance(val, ast.Call) and \
                    _call_name(val) == "host_row_slice"
                tainted_val = not fixer and value_shaped(val)
                for t in stmt.targets:
                    names = [t] if isinstance(t, ast.Name) else [
                        e for e in getattr(t, "elts", ()) if
                        isinstance(e, ast.Name)
                    ]
                    for n in names:
                        if is_slice:
                            slices.add(n.id)
                            shaped.discard(n.id)
                        elif tainted_val:
                            shaped.add(n.id)
                        else:
                            shaped.discard(n.id)
                            slices.discard(n.id)
            for node in ast.walk(stmt) if not isinstance(
                stmt, (ast.If, ast.While, ast.For, ast.With, ast.Try)
            ) else ():
                if isinstance(node, ast.Call) and \
                        _call_name(node) in _PLACEMENT_SINKS and node.args:
                    # the array argument: device_put/shard_* take it at
                    # position 0 or 1 (mesh-first helpers)
                    name = _call_name(node)
                    idx = 1 if name in ("shard_rows", "shard_cols") and \
                        len(node.args) > 1 else 0
                    arg = node.args[idx]
                    if value_shaped(arg):
                        hits.append((
                            "TPS007", node.lineno,
                            f"host-dependent shape feeds {name}() — this "
                            "host's real-row block has a different shape "
                            "on every host, so each compiles its own "
                            "program (recompile storm) and shape-"
                            "divergent collectives hang; pad to the "
                            "host-invariant block first (pad_rows / "
                            "zeros-block copy)",
                        ))
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, attr, None)
                if sub:
                    visit(sub)
            for handler in getattr(stmt, "handlers", ()):
                visit(handler.body)

    visit(list(getattr(fn, "body", ())))


# ==========================================================================
# locks (TPS005) and kernel-body stability (TPS004)
# ==========================================================================
from .findings import lock_guarded_expr as _lock_guarded  # noqa: E402 — shared


def _scan_collective_under_lock(tree: ast.Module, hits: list) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        if not any(_lock_guarded(i.context_expr) for i in node.items):
            continue
        for name, lineno in _collective_calls_in(node.body):
            hits.append((
                "TPS005", lineno,
                f"collective {name}() issued while holding a lock — if "
                "any other host needs this lock to reach its own "
                f"{name}(), the mesh deadlocks (snapshot under the lock, "
                "issue the collective outside it); this edge bridges "
                "into the TPC lock-order graph",
            ))


def _scan_kernel_stability(fn: ast.FunctionDef, hits: list) -> None:
    """TPS004 inside one shard_map kernel body: (a) subtraction whose
    operands BOTH derive from collective reductions — the raw-moment
    variance shape, catastrophic f32 cancellation under reordering;
    (b) 64-bit dtypes — f64 math silently degrades (or refuses to
    lower) on TPU, so merges stop being bit-identical."""
    reduced: set[str] = set()

    def from_reduce(expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) and \
                    _call_name(node) in _LAX_COLLECTIVES:
                return True
            if isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Load
            ) and node.id in reduced:
                return True
        return False

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and from_reduce(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    reduced.add(t.id)
    for node in ast.walk(fn):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            if from_reduce(node.left) and from_reduce(node.right):
                hits.append((
                    "TPS004", node.lineno,
                    f"subtraction of two collective-reduced values in "
                    f"kernel {fn.name}() — the raw-moment shape "
                    "catastrophically cancels in f32 and its rounding is "
                    "reduction-order-sensitive, breaking the guarded "
                    "seam's bit-identical commutative-merge contract "
                    "(center first, then reduce — see pcolumn_stats)",
                ))
        chain = attr_chain(node) if isinstance(node, ast.Attribute) else []
        if chain and chain[-1] in ("float64", "int64", "uint64",
                                   "complex128"):
            hits.append((
                "TPS004", node.lineno,
                f"64-bit dtype in shard_map kernel {fn.name}() — TPU has "
                "no f64 ALU, so the op silently falls to different "
                "rounding (or refuses to lower) and merges stop being "
                "bit-identical across mesh shapes",
            ))
        if isinstance(node, ast.Call) and _call_name(node) == "astype":
            for a in node.args:
                if isinstance(a, ast.Constant) and \
                        str(a.value).endswith("64"):
                    hits.append((
                        "TPS004", node.lineno,
                        f"64-bit cast in shard_map kernel {fn.name}()",
                    ))


# ==========================================================================
# per-file driver
# ==========================================================================
def analyze_source(source: str, rel_path: str) -> Report:
    """The static TPS pass over one file. ``rel_path`` (posix,
    repo-relative) keys findings for the baseline."""
    report = Report()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        report.add(
            "TPS000",
            f"file does not parse: {e}",
            subject=f"{rel_path}:{e.lineno or 0}",
            severity=Severity.WARNING,
            path=rel_path, line=e.lineno or 0, context="",
        )
        return report
    lines = source.splitlines()
    hits: list[tuple[str, int, str]] = []

    consts = _module_axis_consts(tree)
    helpers = _module_spec_helpers(tree, consts)
    seams: dict[str, list[int]] = {}
    kernels = 0

    funcs = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn in funcs:
        _scan_order_divergence(fn, hits)
        _scan_host_shapes(fn, hits)
        dec = _is_shard_map_decorated(fn) if isinstance(
            fn, ast.FunctionDef
        ) else None
        if dec is None:
            continue
        kernels += 1
        env = _AxisEnv(consts, helpers)
        _collect_local_axis_bindings(fn, env)
        bound, resolved = _shard_map_bound_axes(dec, env)
        if resolved:
            for axes, call, lineno in _kernel_used_axes(fn, env):
                missing = axes - bound
                if missing:
                    hits.append((
                        "TPS002", lineno,
                        f"shard_map kernel {fn.name}() issues {call}() "
                        f"over axis {sorted(missing)} but the wrapping "
                        f"mesh/in_specs bind only {sorted(bound)} — the "
                        "kernel traces, then dies with an unbound-axis "
                        "error on the first real mesh (the compat-shim "
                        "break class)",
                    ))
        _scan_kernel_stability(fn, hits)
        # ---- TPS003(a): spec axes vs a resolvable mesh vocabulary
        mesh_axes: set[str] | None = None
        for kw in dec.keywords:
            if kw.arg == "mesh" and isinstance(kw.value, ast.Call):
                ctor = _call_name(kw.value)
                mesh_axes = _MESH_CTOR_AXES.get(ctor)
        if mesh_axes is not None:
            env2 = _AxisEnv(consts, helpers)
            _collect_local_axis_bindings(fn, env2)
            for kw in dec.keywords:
                if kw.arg not in ("in_specs", "out_specs"):
                    continue
                exprs = (
                    kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value]
                )
                for e in exprs:
                    spec = env2.spec_axes(e)
                    if spec and spec[0] - mesh_axes:
                        hits.append((
                            "TPS003", e.lineno,
                            f"PartitionSpec axes "
                            f"{sorted(spec[0] - mesh_axes)} are not in "
                            f"the mesh's vocabulary {sorted(mesh_axes)}",
                        ))

    # ---- TPS003(b): literal-spec placement with statically-known ranks
    _scan_spec_ranks(tree, consts, helpers, hits)
    _scan_collective_under_lock(tree, hits)

    # ---- seam census: names issued through the guarded seam
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) in _SEAM_ENTRIES:
            if node.args and isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                seams.setdefault(node.args[0].value, []).append(node.lineno)

    rel = rel_path.replace(os.sep, "/")
    for code, lineno, message in sorted(hits, key=lambda h: (h[1], h[0])):
        context = lines[lineno - 1].strip() if 0 < lineno <= len(lines) else ""
        if suppressed(context, code):
            continue
        report.add(
            code, message,
            subject=f"{rel}:{lineno}",
            severity=Severity.WARNING,
            path=rel, line=lineno, context=context,
        )
    if seams:
        report.data["spmdSeams"] = {
            rel: {name: lns for name, lns in sorted(seams.items())}
        }
    if kernels:
        report.data["shardMapKernels"] = {rel: kernels}
    return report


def _literal_rank(expr: ast.AST) -> int | None:
    """Rank of an array-building call with a literal shape tuple
    (``np.zeros((a, b))``, ``rng.normal(size=(a, b))``, ``x.reshape``)."""
    if not isinstance(expr, ast.Call):
        return None
    name = _call_name(expr)
    shape_expr = None
    if name in ("zeros", "ones", "full", "empty", "reshape"):
        if expr.args:
            shape_expr = expr.args[0]
    elif name in ("normal", "uniform", "integers", "standard_normal"):
        for kw in expr.keywords:
            if kw.arg == "size":
                shape_expr = kw.value
    if shape_expr is None:
        return None
    if isinstance(shape_expr, (ast.Tuple, ast.List)):
        return len(shape_expr.elts)
    if isinstance(shape_expr, ast.Constant) and isinstance(
        shape_expr.value, int
    ):
        return 1
    return None


def _scan_spec_ranks(tree, consts, helpers, hits: list) -> None:
    """TPS003(b): ``device_put(x, NamedSharding(mesh, SPEC))`` where both
    the spec's entry count and x's rank are statically known and
    disagree — a mis-ranked PartitionSpec either errors at placement or
    silently shards the wrong axis."""
    env = _AxisEnv(consts, helpers)
    ranks: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            r = _literal_rank(node.value)
            if r is not None:
                ranks[node.targets[0].id] = r
            else:
                ranks.pop(node.targets[0].id, None)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and
                _call_name(node) == "device_put" and len(node.args) >= 2):
            continue
        arr, shd = node.args[0], node.args[1]
        rank = None
        if isinstance(arr, ast.Name):
            rank = ranks.get(arr.id)
        else:
            rank = _literal_rank(arr)
        if rank is None:
            continue
        spec_expr = None
        if isinstance(shd, ast.Call) and _call_name(shd) == "NamedSharding" \
                and len(shd.args) >= 2:
            spec_expr = shd.args[1]
        if spec_expr is None:
            continue
        spec = env.spec_axes(spec_expr)
        if spec is not None and spec[1] > rank:
            hits.append((
                "TPS003", node.lineno,
                f"PartitionSpec has {spec[1]} entries but the array it "
                f"shards has rank {rank} — the spec names more axes than "
                "the array has dimensions",
            ))


def analyze_paths(
    paths: Iterable[str] | None = None,
    root: str = ".",
    restrict: bool = True,
) -> Report:
    """The static TPS pass over every ``.py`` under ``paths``; with
    ``restrict`` (the default) only files on the SPMD surface are read —
    single-device code has no collective order to get wrong."""
    if paths is None:
        paths, root = default_spmd_paths()
    files: list[str] = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [
                d for d in dirnames
                if d not in ("__pycache__", ".git", "node_modules")
            ]
            files.extend(
                os.path.join(dirpath, f)
                for f in filenames if f.endswith(".py")
            )
    report = Report()
    seams: dict[str, Any] = {}
    kernels: dict[str, int] = {}
    for path in sorted(set(files)):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        if restrict and not _in_scope(rel):
            continue
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            continue
        sub = analyze_source(source, rel)
        seams.update(sub.data.pop("spmdSeams", {}))
        kernels.update(sub.data.pop("shardMapKernels", {}))
        report.extend(sub)
    report.data["spmdSeams"] = seams
    report.data["shardMapKernels"] = kernels
    return report


def _in_scope(rel: str) -> bool:
    rel = rel.replace(os.sep, "/")
    return (
        "/parallel/" in rel or rel.startswith("parallel/")
        or rel.endswith("models/trees.py")
        or rel.endswith("resilience/distributed.py")
    )


def default_spmd_paths() -> tuple[list[str], str]:
    """(paths, root) mirroring ``concurrency.default_concurrency_paths``:
    a repo checkout analyzes the SPMD surface with repo-relative keys; an
    installed package analyzes itself."""
    if os.path.isdir("transmogrifai_tpu"):
        return [p for p in DEFAULT_SPMD_PATHS if os.path.exists(p)], "."
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(pkg)
    return (
        [
            os.path.join(pkg, "parallel"),
            os.path.join(pkg, "models", "trees.py"),
            os.path.join(pkg, "resilience", "distributed.py"),
        ],
        root,
    )


def seam_collective_census(
    paths: Iterable[str] | None = None, root: str = "."
) -> dict[str, Any]:
    """{collective name -> [site, ...]} of every name issued through the
    guarded seam (the vocabulary the dynamic tapes must be explained by)."""
    report = analyze_paths(paths, root=root)
    out: dict[str, list[str]] = {}
    for rel, names in (report.data.get("spmdSeams") or {}).items():
        for name, linenos in names.items():
            out.setdefault(name, []).extend(
                f"{rel}:{ln}" for ln in linenos
            )
    return out


# ==========================================================================
# IR leg: the static collective census (TPS006)
# ==========================================================================
def hlo_collective_kinds(text: str) -> set[str]:
    """Collective kinds present in a lowered StableHLO/HLO text dump
    (underscore and hyphen spellings both occur across jax versions)."""
    kinds: set[str] = set()
    for kind in HLO_KIND_SOURCES:
        if kind in text or kind.replace("_", "-") in text:
            kinds.add(kind)
    return kinds


def reconcile_hlo_census(
    name: str, declared_prims: set[str], hlo_kinds: set[str]
) -> Report:
    """TPS006 for every lowered collective kind none of the program's
    jaxpr-census primitives declare — lowering inserted a collective the
    trace never showed (hidden resharding)."""
    report = Report()
    for kind in sorted(hlo_kinds):
        if not set(HLO_KIND_SOURCES[kind]) & declared_prims:
            report.add(
                "TPS006",
                f"program '{name}' lowers to HLO containing "
                f"'{kind}' but its jaxpr collective census declares "
                f"{sorted(declared_prims) or 'no collectives'} — XLA "
                "inserted a collective the trace never showed (hidden "
                "resharding); make the resharding explicit in the "
                "program or fix the specs",
                subject=f"program:{name}",
                severity=Severity.WARNING,
                path=f"program:{name}", line=0,
                context=f"{name} hlo:{kind}",
            )
    return report


def jaxpr_collectives(closed) -> list[dict[str, Any]]:
    """The collective census of one (closed) jaxpr: count + primitive +
    axes of every collective primitive, recursed through scan/cond/pjit
    bodies. The unit both census legs share."""
    from . import program as PJ

    counts: dict[tuple[str, str], int] = {}
    for jaxpr, _consts in PJ._walk(closed):
        for eqn in jaxpr.eqns:
            pname = eqn.primitive.name
            if pname not in COLLECTIVE_PRIMITIVES:
                continue
            axes = eqn.params.get("axes") or eqn.params.get(
                "axis_name"
            ) or ()
            if isinstance(axes, (str, int)):
                axes = (axes,)
            key = (pname, ",".join(str(a) for a in axes))
            counts[key] = counts.get(key, 0) + 1
    return [
        {"primitive": p, "axes": a, "count": c}
        for (p, a), c in sorted(counts.items())
    ]


def _parallel_specs(errors: list | None = None):
    from . import program as PJ

    specs = PJ.collect_specs(errors=errors)
    return [
        s for s in specs
        if s.module.startswith("transmogrifai_tpu.parallel")
    ]


def static_collective_census(specs=None) -> Report:
    """Trace every registered parallel-plane shard_map kernel and derive
    its collective census (count + primitive + axes per collective in
    the jaxpr), then reconcile the lowered HLO against it (TPS006).
    Programs that fail to trace degrade to TPS000 findings. The census
    rides ``report.data["collectiveCensus"]``."""
    from . import program as PJ

    report = Report()
    errors: list = []
    if specs is None:
        specs = _parallel_specs(errors=errors)
    for mod_name, err in errors:
        report.add(
            "TPS000",
            f"program registration in '{mod_name}' failed — its kernels "
            f"are MISSING from the collective census: {err}",
            subject=f"module:{mod_name}",
            severity=Severity.WARNING,
            path=f"module:{mod_name}", line=0, context=f"{mod_name} collect",
        )
    census: dict[str, Any] = {}
    for spec in specs:
        bucket = spec.buckets[0]
        try:
            args, statics = spec.build(bucket)
            closed = PJ._trace_closed(spec.fn, args, statics)
        except Exception as e:
            report.add(
                "TPS000",
                f"program '{spec.name}' failed to trace for the "
                f"collective census: {e}",
                subject=f"program:{spec.name}",
                severity=Severity.WARNING,
                path=f"program:{spec.name}", line=0,
                context=f"{spec.name} trace",
            )
            continue
        collectives = jaxpr_collectives(closed)
        prims = {c["primitive"] for c in collectives}
        hlo_kinds: set[str] = set()
        try:
            fn = spec.fn
            if not hasattr(fn, "lower"):
                import jax

                fn = jax.jit(  # tp: disable=TPL003 — lower-only
                    fn, static_argnames=tuple(statics)
                )
            import warnings

            with warnings.catch_warnings():
                # donating programs (the sharded sweep) warn per-lower
                # about buffers whose shapes can't alias an output —
                # expected, and TPJ003 audits the aliasing separately
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers.*"
                )
                text = fn.lower(*args, **statics).as_text()
            hlo_kinds = hlo_collective_kinds(text)
            report.extend(reconcile_hlo_census(spec.name, prims, hlo_kinds))
        except Exception as e:
            report.add(
                "TPS000",
                f"program '{spec.name}' failed to lower for the HLO "
                f"reconciliation: {e}",
                subject=f"program:{spec.name}",
                severity=Severity.WARNING,
                path=f"program:{spec.name}", line=0,
                context=f"{spec.name} lower",
            )
        census[spec.name] = {
            "collectives": collectives,
            "hloKinds": sorted(hlo_kinds),
        }
    report.data["collectiveCensus"] = census
    return report


def audit_spmd(
    paths: Iterable[str] | None = None,
    root: str = ".",
    include_ir: bool = True,
) -> Report:
    """The full TPS pass: static AST analysis over the SPMD surface plus
    (by default) the jaxpr/HLO collective census of every registered
    parallel kernel — the CLI ``lint --spmd`` entry."""
    report = analyze_paths(paths, root=root)
    if include_ir:
        report.extend(static_collective_census())
    return report


# ==========================================================================
# dynamic leg: the per-host collective-tape reconciler (TPS008)
# ==========================================================================
def reconcile_collective_orders(
    tapes: dict[str, Any],
    census: dict[str, Any] | None = None,
) -> Report:
    """Assert the per-host collective tapes agree and are explained.

    ``tapes`` is ``parallel.guarded.collective_tapes()``'s shape (live or
    loaded from a ``TPTPU_COLLECTIVE_TRACE_OUT`` dump). Invariants:

    * every SURVIVOR host's tape is identical — same names, same order,
      same sequence numbers (the commutative-reduce contract only holds
      when every host joins every collective);
    * a LOST host's tape is a strict prefix of the survivors' (it
      stopped at the failover point, it never diverged);
    * with ``census`` (:func:`seam_collective_census`'s shape, or any
      ``{name: ...}``), every issued name is statically declared.

    One TPS008 WARNING per violation plus a ``reconciliation`` data
    attachment; CI gates on ``len(report)``."""
    report = Report()
    hosts = {
        int(h): [(int(s), str(n)) for s, n in tape]
        for h, tape in (tapes.get("hosts") or {}).items()
    }
    lost = {int(h) for h in tapes.get("lost") or ()}
    n_hosts = int(tapes.get("nHosts") or (max(hosts) + 1 if hosts else 0))
    survivors = sorted(h for h in range(n_hosts) if h not in lost)
    divergent: list[int] = []

    reference: list[tuple[int, str]] | None = None
    ref_host = None
    for h in survivors:
        tape = hosts.get(h, [])
        if reference is None:
            reference, ref_host = tape, h
            continue
        if tape != reference:
            divergent.append(h)
            where = next(
                (i for i, (a, b) in enumerate(zip(reference, tape))
                 if a != b),
                min(len(reference), len(tape)),
            )
            report.add(
                "TPS008",
                f"host {h}'s collective tape diverges from host "
                f"{ref_host}'s at sequence {where}: "
                f"{tape[where] if where < len(tape) else '<ended>'} vs "
                f"{reference[where] if where < len(reference) else '<ended>'}"
                " — hosts issued collectives in different orders/counts "
                "(the deadlock precursor TPS001 exists to catch "
                "statically)",
                subject=f"host:{h}",
                severity=Severity.WARNING,
                path="tape:reconcile", line=0,
                context=f"host {h} diverges",
            )
    if reference is None and hosts:
        # every host was lost (a long multi-failover suite can exhaust
        # the host set): the LONGEST frozen tape is the reference and
        # every other tape must be a prefix of it — tapes only ever
        # freeze, so lockstep ordering still proves out
        ref_host = max(hosts, key=lambda h: len(hosts[h]))
        reference = hosts[ref_host]
    for h in sorted(lost):
        if h == ref_host:
            continue
        tape = hosts.get(h, [])
        if reference is not None and tape != reference[: len(tape)]:
            divergent.append(h)
            report.add(
                "TPS008",
                f"lost host {h}'s tape is not a prefix of the survivors' "
                "— it diverged BEFORE the failover, not because of it",
                subject=f"host:{h}",
                severity=Severity.WARNING,
                path="tape:reconcile", line=0,
                context=f"lost host {h} not a prefix",
            )
    issued = {n for tape in hosts.values() for _s, n in tape}
    unexplained = sorted(
        issued - set(census)
    ) if census is not None else []
    for name in unexplained:
        report.add(
            "TPS008",
            f"collective '{name}' was issued at runtime but the static "
            "seam census never declared it — a collective flows outside "
            "the guarded seam's vocabulary (route it through "
            "parallel.guarded.guarded_collective)",
            subject=f"collective:{name}",
            severity=Severity.WARNING,
            path="tape:census", line=0,
            context=f"{name} unexplained",
        )
    report.data["reconciliation"] = {
        "hosts": n_hosts,
        "lostHosts": sorted(lost),
        # reference length, or the longest frozen tape when every host
        # was lost (a long multi-failover suite can exhaust the host set)
        "tapeLength": len(reference or ()) or max(
            (len(t) for t in hosts.values()), default=0
        ),
        "divergentHosts": sorted(set(divergent)),
        "issuedNames": sorted(issued),
        "unexplainedNames": unexplained,
        "tapesAgree": not divergent,
        "explained": not unexplained,
    }
    return report


# ==========================================================================
# summary surface
# ==========================================================================
@functools.lru_cache(maxsize=1)
def package_summary() -> dict[str, Any]:
    """Compact cached summary for ``summary_json()["analysis"]["spmd"]``
    — the TPS family riding beside the TPA/TPX/TPC reports. Cached per
    process: the package's source does not change under a running
    train. Static AST leg only (the IR census traces jax programs —
    too heavy for a summary side-channel)."""
    paths, root = default_spmd_paths()
    report = analyze_paths(paths, root=root)
    codes: dict[str, int] = {}
    for f in report.findings:
        codes[f.code] = codes.get(f.code, 0) + 1
    seams = report.data.get("spmdSeams") or {}
    seam_names = sorted({n for names in seams.values() for n in names})
    return {
        "findings": len(report.findings),
        "codes": codes,
        "seamCollectives": seam_names,
        "shardMapKernels": sum(
            (report.data.get("shardMapKernels") or {}).values()
        ),
    }

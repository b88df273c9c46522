#!/usr/bin/env python3
"""tpu_lint — repo-directed AST lint for TPU tracing hazards.

The engine's hot path is XLA-traced JAX: the classic regressions are a
host sync smuggled into a per-batch loop (``.item()``, a stray
``jax.device_get``), python control flow on a traced value inside a
jitted function (silent recompiles or trace errors), and jit cache keys
that churn (a fresh lambda per call compiles every batch). They all
look innocent in review — this lint makes them CI failures instead.

Rules
-----
TPU001  device→host pull outside the sanctioned sync helpers
        (exec/base.py host_pull/host_fence): ``jax.device_get``,
        ``jax.block_until_ready``, or ``<expr>.item()`` anywhere in
        ``spark_rapids_tpu/{exec,ops,expr}/``. One batched pull through
        the helper costs one host round trip and is auditable; scattered raw
        pulls are how per-batch RTTs regress.
TPU002  unstable jit cache key: ``jax.jit(lambda ...)`` (a fresh lambda
        can never hit the executable cache), ``jax.jit`` called inside a
        function without storing the result in a cache (subscript
        assignment or an lru_cache'd enclosing function), or ``id(...)``
        inside a cache-key tuple (ids are reused after GC).
TPU003  traced-value hazard inside a jit region: within a function
        passed to ``jax.jit`` (and its nested defs) — ``float()`` /
        ``int()`` / ``bool()`` / ``np.asarray()`` applied to a traced
        parameter, ``.item()``, or an ``if``/``while`` whose test reads
        a traced parameter (python control flow cannot branch on traced
        values).
TPU005  raw ``jax.jit`` / ``pjit`` outside the guarded pipeline-cache
        layer: every engine executable must be built inside a builder
        handed to ``exec/base.cached_pipeline`` (or
        ``exec/mesh._cached_program``) so the program participates in
        the AOT program cache, the compile-cost harvest, and the
        donation-mask key fold — a raw jit is invisible to all three.
        ``exec/base.py`` is exempt (it IS the layer); the two AOT
        export-probe compiles in serve/program_cache.py are the
        documented allowlisted exceptions.
TPU004  capacity decision outside the sanctioned layer: a direct
        ``bucket_rows``/``round_up_pow2`` call, or hand-rolled
        power-of-two arithmetic (``1 << (...).bit_length()``), anywhere
        in ``spark_rapids_tpu/`` outside ``columnar/``,
        ``utils/bucketing.py``, and the static plan analyzer
        (``plugin/plananalysis.py``). Batch/byte-pool capacities must
        route through ``columnar.column.choose_capacity`` so the
        analyzer can reproduce the exact buckets the runtime will
        allocate — a hand-rolled bucket is invisible to the plan-time
        layout/footprint/signature forecast.

Allowlist
---------
``tools/tpu_lint_allow.txt`` (path configurable via the
``spark.rapids.tpu.tools.lint.allowlistPath`` conf entry): one
``relpath::qualname::RULE`` per line for the documented legitimate
sites; ``#`` comments. The sanctioned helpers themselves (exec/base.py)
are exempt from TPU001 by construction.

Exit status: 0 clean, 1 findings, 2 usage/IO error.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from lint_common import (  # noqa: E402 — path bootstrap above
    Finding,
    REPO_ROOT,
    attr_chain as _attr_chain,
    default_allowlist_path,
    enclosing_function as _enclosing_function,
    function_defs as _function_defs,
    iter_py_files,
    load_allowlist,
    parents_map as _parents,
    run_tool,
)

DEFAULT_TARGET = os.path.join(REPO_ROOT, "spark_rapids_tpu")
#: dirs where ANY raw host-sync primitive is a finding (TPU001); the rest
#: of the package is host-boundary code where pulls are the point
SYNC_STRICT_DIRS = ("exec", "ops", "expr")
SANCTIONED_FILES = (os.path.join("exec", "base.py"),)

JAX_MODULE_ALIASES = {"jax", "_jax", "_jx"}
NUMPY_ALIASES = {"np", "numpy"}

#: dirs/files where raw bucket arithmetic is the implementation itself
#: (TPU004 exempt): the columnar layer OWNS choose_capacity, bucketing.py
#: defines the primitive, and the plan analyzer mirrors the rules
CAPACITY_SANCTIONED = (
    os.path.join("spark_rapids_tpu", "columnar") + os.sep,
    os.path.join("spark_rapids_tpu", "utils", "bucketing.py"),
    os.path.join("spark_rapids_tpu", "utils", "__init__.py"),
    os.path.join("spark_rapids_tpu", "plugin", "plananalysis.py"),
)


def _default_allowlist_path() -> str:
    return default_allowlist_path(
        "LINT_ALLOWLIST_PATH", os.path.join("tools", "tpu_lint_allow.txt"))


def _is_jit_call(call: ast.Call) -> bool:
    chain = _attr_chain(call.func)
    return chain is not None and chain.split(".")[0] in JAX_MODULE_ALIASES \
        and chain.endswith(".jit")


def _is_jit_like(call: ast.Call) -> bool:
    """jax.jit OR pjit under any import spelling (TPU005 scope)."""
    chain = _attr_chain(call.func)
    if chain is None:
        return False
    if chain.split(".")[-1] == "pjit":
        return True
    return _is_jit_call(call)


def _jit_regions(tree: ast.AST, parents) -> Set[ast.AST]:
    """Function defs passed to jax.jit — resolved by NAME within the
    jit call's enclosing function (then module) scope."""
    regions: Set[ast.AST] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jit_call(node)):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Lambda):
            regions.add(arg)
            continue
        if not isinstance(arg, ast.Name):
            continue
        scope = _enclosing_function(node, parents)
        while True:
            # a Lambda scope has an expression body, never statement
            # defs — look straight through it to the outer function
            # (e.g. ``cached_pipeline(..., lambda: jax.jit(run))``)
            if scope is None:
                body = tree.body
            elif isinstance(scope, ast.Lambda):
                body = []
            else:
                body = scope.body
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and stmt.name == arg.id:
                    regions.add(stmt)
                    break
            else:
                if scope is None:
                    break
                scope = _enclosing_function(scope, parents)
                continue
            break
    return regions


def _region_nodes(region: ast.AST):
    """All nodes inside a jit region, including nested defs."""
    yield from ast.walk(region)


def _traced_params(region: ast.AST) -> Set[str]:
    """Parameter names of the jit entry and every nested def (all are
    trace-time values when the region runs under jax.jit)."""
    names: Set[str] = set()
    for node in ast.walk(region):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            a = node.args
            for p in (list(a.posonlyargs) + list(a.args)
                      + list(a.kwonlyargs)):
                names.add(p.arg)
            if a.vararg:
                names.add(a.vararg.arg)
            if a.kwarg:
                names.add(a.kwarg.arg)
    names.discard("self")
    return names


def _refs_any(node: ast.AST, names: Set[str]) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in names for n in ast.walk(node))


#: the sanctioned guarded-cache helpers (exec/base.cached_pipeline and
#: exec/mesh._cached_program): a builder function handed to one of these
#: has its jit result stored in the keyed cache BY the helper, under the
#: pipeline-cache lock — that IS the cache store
_CACHED_BUILDER_FUNCS = ("cached_pipeline", "_cached_program")


def _is_cached_builder_call(call: ast.Call) -> bool:
    chain = _attr_chain(call.func)
    return chain is not None \
        and chain.split(".")[-1] in _CACHED_BUILDER_FUNCS


def _passed_to_cached_builder(name: str, tree: ast.AST) -> bool:
    """Is a def of this name used as an argument to cached_pipeline /
    _cached_program anywhere in the module?"""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_cached_builder_call(node):
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, ast.Name) and a.id == name:
                    return True
    return False


def _routes_through_cached_builder(call: ast.Call, parents,
                                   tree: ast.AST) -> bool:
    """Does this jit/pjit call's result reach the guarded cache layer —
    i.e. is it (part of) the return value of a builder handed to
    cached_pipeline/_cached_program, or inside a lambda passed to one
    directly? (Tuple wrapping — ``return jax.jit(fn), aux`` — is the
    mesh builders' shape and counts.)"""
    cur = call
    while True:
        parent = parents.get(cur)
        if parent is None:
            return False
        if isinstance(parent, ast.Lambda):
            outer = parents.get(parent)
            return isinstance(outer, ast.Call) \
                and _is_cached_builder_call(outer)
        if isinstance(parent, ast.Return):
            fn = _enclosing_function(parent, parents)
            return isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and _passed_to_cached_builder(fn.name, tree)
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Module)):
            return False
        cur = parent


def _in_cache_store(call: ast.Call, parents, tree: ast.AST) -> bool:
    """jax.jit(...) whose result lands in a subscript store
    (``_CACHE[key] = jax.jit(run)``), is returned from an
    lru_cache-decorated function, or is returned from / wrapped in a
    builder handed to the guarded cache helpers (cached_pipeline)."""
    cur = call
    while True:
        parent = parents.get(cur)
        if parent is None:
            return False
        if isinstance(parent, ast.Assign):
            return any(isinstance(t, ast.Subscript) for t in parent.targets)
        if isinstance(parent, ast.Lambda):
            # ``cached_pipeline(..., lambda: jax.jit(run))``
            outer = parents.get(parent)
            return isinstance(outer, ast.Call) \
                and _is_cached_builder_call(outer)
        if isinstance(parent, ast.Return):
            fn = _enclosing_function(parent, parents)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in fn.decorator_list:
                    chain = _attr_chain(dec) or (
                        _attr_chain(dec.func)
                        if isinstance(dec, ast.Call) else None)
                    if chain and ("lru_cache" in chain or chain.endswith(
                            ".cache") or chain == "cache"):
                        return True
                if _passed_to_cached_builder(fn.name, tree):
                    return True
            return False
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Module)):
            return False
        cur = parent


def lint_file(path: str, relpath: str) -> List[Finding]:
    with open(path, "rb") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(relpath, e.lineno or 0, "TPU000", "<module>",
                        f"syntax error: {e.msg}")]
    parents = _parents(tree)
    qualnames = _function_defs(tree)
    regions = _jit_regions(tree, parents)
    region_node_sets = {r: set(ast.walk(r)) for r in regions}

    def qual_of(node) -> str:
        fn = node if node in qualnames else _enclosing_function(node, parents)
        while fn is not None and fn not in qualnames:
            fn = _enclosing_function(fn, parents)
        return qualnames.get(fn, "<module>")

    findings: List[Finding] = []
    strict_sync = (
        any(f"spark_rapids_tpu{os.sep}{d}{os.sep}" in relpath
            for d in SYNC_STRICT_DIRS)
        and not any(relpath.endswith(s) for s in SANCTIONED_FILES)
    )
    jit_strict = (
        f"spark_rapids_tpu{os.sep}" in relpath
        and not any(relpath.endswith(s) for s in SANCTIONED_FILES)
    )
    capacity_strict = (
        f"spark_rapids_tpu{os.sep}" in relpath
        and not any(s in relpath for s in CAPACITY_SANCTIONED)
    )

    in_any_region = set()
    for s in region_node_sets.values():
        in_any_region |= s

    for node in ast.walk(tree):
        if (capacity_strict and isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.LShift)):
            # hand-rolled power-of-two bucket: 1 << (...).bit_length()
            if any(isinstance(n, ast.Call)
                   and isinstance(n.func, ast.Attribute)
                   and n.func.attr == "bit_length"
                   for n in ast.walk(node)):
                findings.append(Finding(
                    relpath, node.lineno, "TPU004", qual_of(node),
                    "hand-rolled power-of-two capacity arithmetic — use "
                    "columnar.column.choose_capacity"))
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        root = chain.split(".")[0] if chain else None

        # --- TPU001: raw host syncs in the strict dirs -------------------
        if strict_sync:
            if chain and root in JAX_MODULE_ALIASES and chain.endswith(
                    (".device_get", ".block_until_ready")):
                findings.append(Finding(
                    relpath, node.lineno, "TPU001", qual_of(node),
                    f"raw {chain.split('.', 1)[1]} — batch it through "
                    "exec/base.py host_pull()/host_fence()"))
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                findings.append(Finding(
                    relpath, node.lineno, "TPU001", qual_of(node),
                    ".item() is a per-value device sync — pull once via "
                    "exec/base.py host_pull()"))

        # --- TPU002: unstable jit cache keys -----------------------------
        if _is_jit_call(node):
            if node.args and isinstance(node.args[0], ast.Lambda):
                findings.append(Finding(
                    relpath, node.lineno, "TPU002", qual_of(node),
                    "jax.jit(lambda ...): a fresh lambda never hits the "
                    "executable cache — jit a module-level def"))
            elif _enclosing_function(node, parents) is not None \
                    and not _in_cache_store(node, parents, tree):
                findings.append(Finding(
                    relpath, node.lineno, "TPU002", qual_of(node),
                    "jax.jit(...) inside a function without a cache "
                    "store — every call retraces; keep compiled fns in "
                    "a keyed cache or an lru_cache'd builder"))
        # --- TPU005: raw jit/pjit outside the guarded cache layer --------
        if jit_strict and _is_jit_like(node) \
                and not _routes_through_cached_builder(node, parents, tree):
            findings.append(Finding(
                relpath, node.lineno, "TPU005", qual_of(node),
                "raw jax.jit/pjit outside exec/base.cached_pipeline — "
                "build programs inside a builder handed to the guarded "
                "cache so they join the AOT program cache, the cost "
                "harvest, and the donation-mask key fold"))
        # --- TPU004: capacity decisions outside the sanctioned layer -----
        if capacity_strict:
            callee = (node.func.id if isinstance(node.func, ast.Name)
                      else (chain.rsplit(".", 1)[-1] if chain else None))
            if callee in ("bucket_rows", "round_up_pow2"):
                findings.append(Finding(
                    relpath, node.lineno, "TPU004", qual_of(node),
                    f"direct {callee}() — capacity decisions must go "
                    "through columnar.column.choose_capacity so the plan "
                    "analyzer can reproduce the bucket"))

        if (isinstance(node.func, ast.Name) and node.func.id == "id"
                and node.args):
            parent = parents.get(node)
            if isinstance(parent, ast.Tuple):
                holder = parents.get(parent)
                tgt = getattr(holder, "targets", None)
                names = [t.id for t in (tgt or [])
                         if isinstance(t, ast.Name)]
                if any("key" in n.lower() for n in names):
                    findings.append(Finding(
                        relpath, node.lineno, "TPU002", qual_of(node),
                        "id(...) in a cache key: ids are reused after GC "
                        "and silently alias entries — key on values"))

    # --- TPU003: traced-value hazards inside jit regions -----------------
    for region in regions:
        traced = _traced_params(region)
        qn = qualnames.get(region, "<lambda>")
        for node in region_node_sets[region]:
            if isinstance(node, (ast.If, ast.While)):
                if _refs_any(node.test, traced):
                    findings.append(Finding(
                        relpath, node.lineno, "TPU003", qn,
                        "python if/while on a traced value inside a jit "
                        "region — use jnp.where/lax.cond"))
            elif isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args):
                    findings.append(Finding(
                        relpath, node.lineno, "TPU003", qn,
                        ".item() inside a jit region is a trace error / "
                        "hidden sync"))
                elif (isinstance(node.func, ast.Name)
                      and node.func.id in ("float", "int", "bool")
                      and node.args and _refs_any(node.args[0], traced)):
                    findings.append(Finding(
                        relpath, node.lineno, "TPU003", qn,
                        f"{node.func.id}() on a traced value inside a jit "
                        "region — trace error; use astype/jnp casts"))
                elif (chain and chain.split(".")[0] in NUMPY_ALIASES
                      and chain.endswith(".asarray") and node.args
                      and _refs_any(node.args[0], traced)):
                    findings.append(Finding(
                        relpath, node.lineno, "TPU003", qn,
                        "np.asarray(traced value) pulls to host inside a "
                        "jit region — use jnp.asarray"))
    return findings


def main(argv: List[str]) -> int:
    return run_tool("tpu_lint", argv, DEFAULT_TARGET,
                    _default_allowlist_path(), lint_file)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

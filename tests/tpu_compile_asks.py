"""What the ``test_tpu_compile*.py`` files share: asking the TPU's compiler,
without a chip, for programs the benchmark's cells run.

The v5e compiler is installed with jax and compiles for a chip that is
DESCRIBED, not attached (on-chip-measurement guide, section 2): what it
refuses here costs no chip time. Nothing runs, so the asks say nothing
about results or times — a compile that passes is not a chip run.

Rules the asks keep (the suite runs under six xdist workers, each of which
imports every test file): the topology is described inside a module-scoped
fixture that skips when it cannot be — never at import (this module
describes nothing when imported), never in conftest, never autouse; no
child process; the persistent compile cache is off around the compiles (a
TPU executable written there cannot be read back without a chip). One file
a program family, so that ``--dist loadfile`` gives each its own worker,
and ``conftest.py`` collects the three first: they are the suite's longest
files and must not start last. Three workers then hold the TPU's library at
once, which the driver's command allows (``ALLOW_MULTIPLE_LIBTPU_LOAD=1``,
never set by this repo); without it the second and third file skip.

Engine code that asks ``jax.default_backend()`` while planning or tracing
would take its CPU branch here, so the asks patch that answer to ``"tpu"``
for the capture — in the test, never through a program option. Data, conf
and query are the cell's own, read from ``benchmarks/`` and not edited.
"""
import contextlib
import math
import os
import re
import sys
import time
from unittest import mock

import numpy as np
import pytest

import jax

os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: rows of one row group of the one-chip cells
CAP = 1 << 21
#: one v5e chip's HBM
HBM_BYTES = 16 << 30
#: aggregate lowerings (``sql.agg.strategy`` values) the v5e compiler is
#: known to refuse, each with the ask that showed it: what AUTO on the
#: ``tpu`` backend must never resolve (``test_full_width_plan.py``)
REFUSED_ON_V5E = {
    "RADIX": "ops/radix_bin._tile_diffs' 64-bit cumsum at capacity 256: "
             "'Scoped allocation with size 19.14M and limit 16.00M' "
             "(test_tpu_compile_full.py keeps the ask as a strict xfail); "
             "at 2048 it compiles (its float sums were NaN on the chip "
             "until combine_float_sum's rescale was guarded, PR 35)",
    "PALLAS": "Mosaic refuses every kernel (test_tpu_compile.py's strict "
              "xfails)",
}


def load_cell(name):
    """A cell's config, generator and query modules, through the
    benchmark's own ``loader`` (``benchmarks/`` on the path, the way
    ``run.py`` and ``tests/benchmarks/benchmark_testlib`` have it)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import loader

    return loader.load_cell(name)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "shape") else x, tree)


def compile_all(programs, sharding):
    """Compile every captured dispatch once per distinct signature;
    returns [(seconds, memory_analysis)]."""
    done = {}
    for fn, args, kw in programs:
        sargs, skw = on(sharding, (args, kw))
        sig = (id(fn), str(sargs), str(skw))
        if sig in done:
            continue
        t0 = time.perf_counter()
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = fn.lower(*sargs, **skw).compile()
        done[sig] = (time.perf_counter() - t0, compiled.memory_analysis())
    return list(done.values())


def compile_mesh_program_for_four_chips(topo, collect, patches=()):
    """Run ``collect()`` on the virtual CPU devices with the engine's
    ``shard_map`` spied: the SPMD program is captured at its dispatch (it
    never runs), re-targeted at four DESCRIBED chips and compiled there.
    Returns (the dispatch's argument shapes, the compiled program)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.exec import mesh as XM
    from spark_rapids_tpu.parallel.mesh import (
        AXIS, mesh_jit_kwargs, shard_map)

    class Captured(Exception):
        pass

    cap = {}

    def spy_shard_map(f, mesh, in_specs, out_specs, **kw):
        def stop_at_dispatch(*args):
            cap.update(f=f, in_specs=in_specs, out_specs=out_specs,
                       shapes=[(a.shape, a.dtype) for a in args])
            raise Captured()

        return stop_at_dispatch

    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(XM, "shard_map", spy_shard_map))
        stack.enter_context(
            mock.patch.object(jax, "default_backend", lambda: "tpu"))
        for patch in patches:
            stack.enter_context(patch)
        with pytest.raises(Captured):
            collect()
        chips = Mesh(np.array(topo.devices[:4]), (AXIS,))
        rows_on_chips = NamedSharding(chips, P(AXIS))
        fn = jax.jit(
            shard_map(cap["f"], mesh=chips, in_specs=cap["in_specs"],
                      out_specs=cap["out_specs"]), **mesh_jit_kwargs())
        compiled = fn.lower(*[
            jax.ShapeDtypeStruct(s, dt, sharding=rows_on_chips)
            for s, dt in cap["shapes"]]).compile()
    return cap["shapes"], compiled


def row_sized_scatters(text, rows):
    """(indices' elements, op_name) of every ``scatter`` instruction of a
    compiled program's text whose indices operand has ``rows`` elements or
    more. A scatter of N arrays has 2N+1 operands: the indices are the
    middle one."""
    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.-]+) = \(?\w+\[([\d,]*)\]", text, re.M))
    found = []
    for line in text.splitlines():
        m = re.search(r" scatter\(([^)]*)\)", line)
        if not m:
            continue
        operands = [o.strip().split(" ")[-1] for o in m.group(1).split(",")]
        dims = shape_of[operands[len(operands) // 2]]
        n = math.prod(int(d) for d in dims.split(",") if d)
        name = re.search(r'op_name="([^"]*)"', line)
        if n >= rows:
            found.append((n, name.group(1) if name else ""))
    return found


def _by_computation(text):
    """(computation, line) of every instruction line of a compiled
    program's text."""
    name = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
        elif name is not None:
            yield name, line


def computations_under_a_conditional(text):
    """Names of the computations of a compiled program's text that run
    only inside a branch of a ``conditional``: the branch computations
    and whatever they call (fusions, loops, reducers, nested branches)."""
    calls, branches = {}, set()
    for name, line in _by_computation(text):
        taken = re.findall(
            r"(?:true_computation|false_computation)=%?([\w.-]+)", line)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            taken += [b.strip().lstrip("%") for b in group.split(",")]
        branches.update(taken)
        calls.setdefault(name, set()).update(taken, re.findall(
            r"(?:calls|to_apply|body|condition)=%?([\w.-]+)", line))
    under, todo = set(), list(branches)
    while todo:
        c = todo.pop()
        if c not in under:
            under.add(c)
            todo.extend(calls.get(c, ()))
    return under


def instructions_named(text, pattern):
    """(computation, op_name) of every instruction of a compiled program's
    text whose ``op_name`` (its name stack) matches ``pattern``."""
    found = []
    for name, line in _by_computation(text):
        op_name = re.search(r'op_name="([^"]*)"', line)
        if op_name and re.search(pattern, op_name.group(1)):
            found.append((name, op_name.group(1)))
    return found


#: the name stack of an operation of a chunk's update in the mesh
#: aggregate's loop (``exec/mesh.chunked_partials``): the branch of the
#: loop's conditional comes before ``agg_update``
IN_CHUNK_BRANCH = re.compile(
    r"\bwhile/body/.*\bcond/branch_\d+_fun/agg_update/")


def chunk_updates(text):
    """The scatters and matmuls of the mesh aggregate's update in a
    compiled program's text, and those of them that do NOT sit in the
    branch a chunk with no live row skips: by the name stack, and by the
    computations the compiler kept under a conditional."""
    update = instructions_named(text, r"/agg_update/.*(scatter|dot_general)")
    under = computations_under_a_conditional(text)
    return update, [(c, n) for c, n in update
                    if c not in under or not IN_CHUNK_BRANCH.search(n)]

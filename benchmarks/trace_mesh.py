"""What the per-layer metrics of a cell on several chips read, beside
``trace_programs``: device seconds under a scope that only a program across
chips has (``mesh_exchange``, which ``trace_programs.SCOPE_WORDS`` does not
list, so its operations fall under their program's name there), the engine's
counts on the mesh stage's and the cached relation's spans, and the chips'
published interconnect peak (``ici_peaks.json``: ``peaks.json`` is not this
file's to edit).

A reader returns nothing where the program under test has no such span or
scope: under the parent of the PR that brought them the metric is left out
of the line."""
from __future__ import annotations

import bisect
import json
import os
from typing import Dict, List, Optional

import trace_programs as TP
from trace_reduce import DEVICE_PLANE, OPS_LINE

HERE = os.path.dirname(os.path.abspath(__file__))
#: the mesh stage whose spans the readers look for
MESH_AGG = "TpuMeshAggregateExec"
CACHED_SCAN = "TpuInMemoryTableScanExec"
EXCHANGE_SCOPE = "mesh_exchange"


def span(ctx: dict, name: str) -> Optional[dict]:
    """The summary of one engine span in the traced slice, or None."""
    reduced = TP.for_ctx(ctx)
    if not TP.has_engine_names(reduced):
        return None
    return reduced["spans"].get(name)


def count_per_query(ctx: dict, name: str, count: str) -> Optional[float]:
    """The sum of one count over a span's occurrences in the slice, over
    the slice's queries; None where no such span carries the count."""
    rec = span(ctx, name)
    queries = (ctx.get("trace") or {}).get("queries")
    if not rec or not queries or count not in rec["counts"]:
        return None
    return rec["counts"][count] / queries


def scope_seconds(planes: List[dict], word: str) -> Dict[str, float]:
    """Device seconds of the slice whose name stack holds ``word``,
    averaged over the chips that ran anything: ``{"scope": s, "chips": n}``
    (each operation's self time, and its name stack by the program that
    contains it, as ``trace_programs`` finds them)."""
    lo, hi = TP._slice_bounds(planes)
    total, chips = 0.0, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        if not ops:
            continue
        chips += 1
        modules = sorted(
            (ev for line in plane["lines"] if line["name"] == TP.MODULES_LINE
             for ev in line["events"]), key=lambda ev: ev[1])
        starts = [m[1] for m in modules]
        for ev, _, own in TP.self_times(ops, lo, hi):
            if own <= 0.0:
                continue
            i = bisect.bisect_right(starts, ev[1]) - 1
            module_id = None
            if i >= 0 and ev[1] < modules[i][1] + modules[i][2]:
                m = TP._MODULE_ID.search(modules[i][0])
                module_id = int(m.group(1)) if m else None
            stack = TP._op_names(ev[3], module_id) or ""
            if word in (part.rstrip(":") for part in stack.split("/")[1:]):
                total += own / 1e9
    return {"scope": total / max(1, chips), "chips": chips}


def exchange_seconds(ctx: dict) -> Optional[float]:
    """Device seconds a chip spent under ``mesh_exchange`` in the slice;
    None where no operation carries the scope (one chip, or a program from
    before the scope). Read once a run and kept in ``ctx``."""
    if "mesh_exchange_s" not in ctx:
        seconds = None
        if ctx.get("trace") and TP.has_engine_names(TP.for_ctx(ctx)):
            planes = ctx.get("mesh_planes")  # a test hands planes in
            if planes is None:
                path = TP.newest_trace()
                planes = TP.read_xplane(path) if path else []
            found = scope_seconds(planes, EXCHANGE_SCOPE)
            seconds = found["scope"] if found["scope"] > 0.0 else None
        ctx["mesh_exchange_s"] = seconds
    return ctx["mesh_exchange_s"]


def ici_peak(ctx: dict, root: str = HERE) -> Optional[dict]:
    """The published interconnect and memory bandwidth of the chips the
    run holds (``ctx["device_kind"]`` where a test names them); None for a
    kind the table does not have."""
    kind = ctx.get("device_kind")
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    with open(os.path.join(root, "ici_peaks.json")) as f:
        return json.load(f).get(kind)

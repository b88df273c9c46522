"""What the per-layer metrics of a cell that scans a file in several
partitions read, beside ``trace_programs``: the scan's per-split spans and
the one-host shuffle's spans, by the names ``docs/tuning.md`` lists.

A reader returns nothing where the program under test has no such span or
count: under the parent of the PR that brought them the metric is left out
of the line."""
from __future__ import annotations

from typing import List, Optional

import trace_mesh
import trace_programs as TP

SCAN = "TpuFileSourceScanExec"
EXCHANGE = "TpuShuffleExchangeExec"
#: the scope of the one-host shuffle's three ``jit_exchange*`` programs
#: (``exec/base.EXCHANGE_SCOPE_WORDS``), which ``trace_programs.SCOPE_WORDS``
#: does not list: their operations fall under their program's name there
EXCHANGE_SCOPE = "shuffle_exchange"


def reduced_with_queries(ctx: dict):
    """(the slice's reduction, its queries), or (None, 0) where the
    program carries no engine names or the slice holds no query."""
    reduced = TP.for_ctx(ctx)
    queries = (ctx.get("trace") or {}).get("queries")
    if not TP.has_engine_names(reduced) or not queries:
        return None, 0
    return reduced, queries


def split_spans(reduced: dict) -> List[dict]:
    """The scan's per-split spans that carry the ``splits`` count: ``plan``
    (a split the fused stage takes) and ``decode`` (one decoded on its
    own)."""
    return [rec for name, rec in reduced["spans"].items()
            if name in (SCAN + ".plan", SCAN + ".decode")
            and "splits" in rec["counts"]]


def exchange_spans(reduced: dict) -> List[dict]:
    """The shuffle exchange's spans: its map side and its reduce side."""
    return [rec for name, rec in reduced["spans"].items()
            if name == EXCHANGE or name.startswith(EXCHANGE + ".")]


def exchange_device_seconds(ctx: dict) -> Optional[float]:
    """Device seconds of the slice under ``shuffle_exchange``; None where
    the plan ran no exchange (the trace is not read again for it) or no
    operation carries the scope (a program from before it). Read once a
    run and kept in ``ctx``."""
    if "shuffle_exchange_s" not in ctx:
        seconds = None
        reduced, _ = reduced_with_queries(ctx)
        if reduced and exchange_spans(reduced):
            planes = ctx.get("scan_planes")  # a test hands planes in
            if planes is None:
                path = TP.newest_trace()
                planes = TP.read_xplane(path) if path else []
            found = trace_mesh.scope_seconds(planes, EXCHANGE_SCOPE)
            seconds = found["scope"] if found["scope"] > 0.0 else None
        ctx["shuffle_exchange_s"] = seconds
    return ctx["shuffle_exchange_s"]

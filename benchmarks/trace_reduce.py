"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics
read: the device's busy intervals, the device operations that took most
time, and the idle gaps, each under the host annotation that covers it.

The reduction works on a plain structure (``read_xplane`` makes it from the
file, tests build it by hand)::

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Ops",
                 "events": [(name, start_ns, duration_ns), ...]}]}]

What a v5e trace holds (one looked at by hand, PR 25; PERF.md section 5): a
plane ``/device:TPU:<n>`` for each chip, whose line ``XLA Ops`` carries one
event for each operation the chip ran, named by the operation's whole HLO
text (``%while.387 = (u32[]{...}, ...) while(...)``) and nested where a
loop's body runs inside the loop's event; its line ``XLA Modules`` carries
one event for each program (``jit_run(<fingerprint>)``) and ``Async XLA
Ops`` the copies that overlap them. The plane ``/host:CPU`` has a line for
each host thread, on which ``jax.profiler.TraceAnnotation`` spans appear
under their names among the runtime's own. All planes share one clock.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start ns, duration ns
Interval = Tuple[float, float]

SLICE_MARK = "bench.slice"
QUERY_MARK = "bench.query"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: the engine names an exec's hot section <NodeName>[.<section>[.<part>]]
#: (exec/base.py op_timed): TpuHashAggregateExec.stage, ...merge.concat
EXEC_SPAN = re.compile(r"^(Tpu|Cpu)\w+Exec(\.\w+)*$")
BETWEEN = "between queries"
IN_QUERY = "collect() outside any exec span"
UNATTRIBUTED = "unattributed"


# ---------------------------------------------------------------------------
# taking the trace
# ---------------------------------------------------------------------------
class Tracer:
    """Wraps the steady slice of a window in ``jax.profiler``'s trace."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir

    def run_slice(self, driver, n_queries: int) -> dict:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from annotations
        options.host_tracer_level = 2
        first = len(driver.done)
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SLICE_MARK):
                for _ in range(n_queries):
                    with jax.profiler.TraceAnnotation(QUERY_MARK):
                        driver.one()
            wall_s = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        return {"wall_s": wall_s,
                "query_indices": [r[0] for r in driver.done[first:]]}

    def reduce(self, slice_info: dict) -> dict:
        paths = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"the profiler left no trace in {self.out_dir}")
        out = reduce_planes(read_xplane(paths[-1]), slice_info["wall_s"])
        out["query_indices"] = slice_info["query_indices"]
        out["queries"] = len(slice_info["query_indices"])
        return out


def read_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of the disjoint sorted ``busy`` within [lo, hi]."""
    out = []
    at = lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_key(name: str) -> str:
    """A short name for a device operation: the instruction's kind (its
    name without the number XLA gives it) and its first result type, so
    that the fourteen unrolled copies of one loop add up under one name:
    ``%while.387 = (u32[]{:T(128)}, ...`` -> ``while u32[]``."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"[.\d]+$", "", head.strip().lstrip("%")) or head
    m = re.search(r"[a-z]+\d*\[[\d,]*\]", rest)
    return f"{kind} {m.group(0)}" if m else kind


def self_seconds(ops: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds by ``op_key``, each operation's time less that of the
    operations nested in it (a loop's body is counted under the body's
    operations, once), clipped to [lo, hi]."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [key, end, self ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            key, _, own = stack.pop()
            out[key] = out.get(key, 0.0) + max(own, 0.0) / 1e9

    for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([op_key(name), e, e - s])
    close(float("inf"))
    return out


def host_spans(planes: List[dict]) -> List[Event]:
    """The spans that say what the host was doing: the engine's exec spans
    and the benchmark's own marks, from every host thread."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if (ev[0] in (SLICE_MARK, QUERY_MARK)
                        or EXEC_SPAN.match(ev[0])):
                    out.append(ev)
    return out


def attribute(idle: Sequence[Interval], spans: Sequence[Event]) -> Dict[str, float]:
    """Seconds of idle time by what the host was doing. Each idle gap is cut
    at the spans' edges; a piece goes to the innermost span that covers it
    (the one that started last), an exec span before the benchmark's marks;
    a piece inside a query but no exec span, between two queries, or outside
    every span is named so."""
    out: Dict[str, float] = {}
    for g0, g1 in idle:
        covering = [(n, s, s + d) for n, s, d in spans
                    if s < g1 and s + d > g0]
        cuts = sorted({g0, g1, *(t for _, s, e in covering
                                 for t in (s, e) if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            over = [(s, n) for n, s, e in covering if s <= mid < e]
            execs = [x for x in over if x[1] not in (SLICE_MARK, QUERY_MARK)]
            if execs:
                name = max(execs)[1]
            elif any(n == QUERY_MARK for _, n in over):
                name = IN_QUERY
            elif any(n == SLICE_MARK for _, n in over):
                name = BETWEEN
            else:
                name = UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def reduce_planes(planes: List[dict], wall_s: Optional[float] = None) -> dict:
    """``busy_s``: seconds in which an operation ran on the device, averaged
    over the chips that ran any; ``window_s``: the traced slice's length (the
    ``bench.slice`` span, else ``wall_s``, else the device events' extent);
    ``device_ops`` and ``idle_gaps``: [[name, seconds], ...], longest first."""
    spans = host_spans(planes)
    marks = [(s, s + d) for n, s, d in spans if n == SLICE_MARK]
    per_chip = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        if ops:
            per_chip.append(ops)
    all_ops = [ev for ops in per_chip for ev in ops]
    if marks:
        lo, hi = min(m[0] for m in marks), max(m[1] for m in marks)
    elif all_ops:
        lo = min(s for _, s, _ in all_ops)
        hi = (lo + wall_s * 1e9 if wall_s
              else max(s + d for _, s, d in all_ops))
    else:
        lo, hi = 0.0, (wall_s or 0.0) * 1e9
    busy_ns = 0.0
    idle_by_name: Dict[str, float] = {}
    op_seconds: Dict[str, float] = {}
    for ops in per_chip:
        busy = union(clip(((s, s + d) for _, s, d in ops), lo, hi))
        busy_ns += length(busy)
        for name, sec in attribute(gaps(busy, lo, hi), spans).items():
            idle_by_name[name] = idle_by_name.get(name, 0.0) + sec
        for name, sec in self_seconds(ops, lo, hi).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + sec
    chips = max(1, len(per_chip))

    def ranked(by_name: Dict[str, float]) -> List[list]:
        return [[n, sec / chips] for n, sec in
                sorted(by_name.items(), key=lambda kv: -kv[1])]

    return {"busy_s": busy_ns / 1e9 / chips, "window_s": (hi - lo) / 1e9,
            "chips_traced": len(per_chip),
            "device_ops": ranked(op_seconds), "idle_gaps": ranked(idle_by_name)}

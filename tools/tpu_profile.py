#!/usr/bin/env python
"""Offline query profiler over structured event logs.

The rapids-4-spark profiling-tool analog: consume one or more JSONL event
logs produced by ``spark.rapids.tpu.eventLog.dir`` (spark_rapids_tpu/
events.py) and answer "where did this query's time and memory actually go,
and did it regress since last run?" without re-running anything.

Report sections:
  * queries           — per-query duration, rows, plan digest, fallbacks
  * top ops           — top-N operators by device time (host time when no
                        deviceSync lane was recorded), batches/rows/bytes
  * compile misses    — per-site counts, storm flag at/over the threshold
  * roofline          — per compile site: harvested XLA cost
                        (program_cost events) joined against the op_span
                        device lane into achieved GB/s and FLOP/s versus
                        the backend's declared peaks, a bandwidth- vs
                        compute-limited classification, the program
                        furthest below roofline, and the analyzer-bound
                        vs XLA-bytes delta (XLA above the bound means the
                        kernel materializes intermediates the layout
                        model doesn't know about — the roofline-push
                        lead, not a violation)
  * hlo               — per-fusion byte attribution of each harvested
                        program (hlo_summary events): per compile site,
                        the top-bytes fusion with its idiom
                        classification (scatter-add / one-hot dot /
                        gather / transpose-copy / collective) and its
                        share of the site's XLA bytes-accessed — the
                        instruction-level culprit behind a byte
                        amplification, plus parse coverage
  * transfers         — host-link bytes each way + sync-point count
  * shuffle           — pieces/bytes/rows each way, per codec
  * spill timeline    — every spill/unspill with the live device-byte
                        watermark, plus the peak
  * resilience        — OOM recovery actions (oom_retry events by
                        op/kind: retry, split, requeue, fused-plan
                        fallback) and split-and-retry halvings
                        (batch_split events with max depth) — how often
                        forecasts were wrong and what recovery cost;
                        plus the shuffle section's fetch-retry line
  * scan cache        — hit/miss/evict counts and bytes
  * forecast vs actual— the static plan analyzer's bounds (plan_analysis
                        events) diffed against measured compile misses and
                        per-op bytes; any measured value above its bound is
                        a VIOLATION (the offline twin of the test
                        harness's analysis cross-check) and makes the exit
                        code nonzero so CI catches emitter/analyzer drift

Diff mode (``--diff A B``): compare two event logs (per-op host/device
time and bytes, per-site XLA bytes/temp, per-site top-fusion bytes and
scatter counts from hlo_summary events). Regressions beyond
``--threshold`` (default 20%) are flagged and make the exit code
nonzero. When the two runs' ``env`` provenance blocks name different
hardware (backend/device kind), a loud ENVIRONMENTS DIFFER banner
prints first — structural gates stay meaningful, time ratios do not.

Alert replay (``--alerts``): run the LIVE watchdog's rules
(obs/watchdog.py — stall, hbm_pressure, recompile_storm) over a recorded
log, so thresholds are tuned against production recordings instead of
guesses: lower ``--stall-ms`` until the known-slow op fires, check the
pressure fraction against a run that actually spilled. The HBM budget
comes from the log's plan_analysis events unless ``--budget`` overrides.

Usage:
  python tools/tpu_profile.py LOG.jsonl [LOG2.jsonl ...] [--top N]
  python tools/tpu_profile.py --diff OLD NEW [--threshold 0.2]
  python tools/tpu_profile.py LOG.jsonl --alerts [--stall-ms 30000]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEFAULT_STORM_THRESHOLD = 8
#: time deltas under this (ns) are measurement noise, never a regression
#: (also applied to harvested compile-time deltas in --diff: trace/
#: compile jitter below the floor is never flagged)
DIFF_MIN_NS = 1_000_000

#: per-backend (peak HBM GB/s, peak TFLOP/s) used when --peak-hbm-gbps /
#: --peak-tflops are not given; MUST mirror
#: spark_rapids_tpu.xla_cost.BACKEND_PEAKS (tests/test_program_cost.py
#: pins the two in sync — duplicated here so the offline tool never
#: needs to import jax just to read a constant)
BACKEND_PEAKS = {
    "tpu": (819.0, 197.0),
    "cpu": (100.0, 1.0),
}


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def load_events(paths: List[str]) -> List[dict]:
    """Events from JSONL files (directories expand to their *.jsonl),
    merged and sorted by timestamp."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(
                os.path.join(p, f) for f in os.listdir(p)
                if f.endswith(".jsonl")))
        else:
            files.append(p)
    out: List[dict] = []
    for f in files:
        with open(f) as fh:
            for i, line in enumerate(fh):
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError as e:
                    raise SystemExit(
                        f"{f}:{i + 1}: not a JSONL event log ({e})")
    out.sort(key=lambda r: r.get("ts", 0))
    return out


def _ms(ns: Optional[float]) -> str:
    return "-" if ns is None else f"{ns / 1e6:.1f}ms"


def _mb(b: Optional[float]) -> str:
    return "-" if b is None else f"{b / 1e6:.2f}MB"


# ---------------------------------------------------------------------------
# environment provenance (envinfo.environment_info blocks riding on
# query_start events)
# ---------------------------------------------------------------------------
def _env_of(events: List[dict]) -> Optional[dict]:
    """The first query_start env block in a log (None for pre-provenance
    logs — the session stamps every query_start, so one is enough)."""
    for r in events:
        if r.get("event") == "query_start" and r.get("env"):
            return r["env"]
    return None


def _env_str(env: Optional[dict]) -> str:
    if not env:
        return "backend=?"
    return (f"backend={env.get('backend')} "
            f"device={env.get('device_kind')} "
            f"x{env.get('device_count')} "
            f"jax={env.get('jax_version')}")


def _envs_differ(a: Optional[dict], b: Optional[dict]) -> bool:
    """Same rule as spark_rapids_tpu.envinfo.environments_differ (kept
    local so the offline tool stays import-free; tests/test_hlo.py pins
    the two in agreement): different backend or device kind means
    absolute times and HBM fractions are NOT comparable. Missing blocks
    (pre-provenance logs) never differ — no evidence, no warning."""
    if not a or not b:
        return False
    return (a.get("backend") != b.get("backend")
            or a.get("device_kind") != b.get("device_kind"))


def _env_warning(old_env: Optional[dict], new_env: Optional[dict]
                 ) -> List[str]:
    """Loud comparability banner for --diff when the two runs name
    different hardware (the recurring CPU-fallback-vs-device confusion:
    a 10x 'regression' between a device round and a CPU-fallback
    round is an environment change, not a kernel change)."""
    if not _envs_differ(old_env, new_env):
        return []
    return [
        "  !!! ENVIRONMENTS DIFFER — timings are NOT comparable !!!",
        f"  !!! old: {_env_str(old_env)}",
        f"  !!! new: {_env_str(new_env)}",
        "  !!! trust structural gates only (strategy/lowering/scatter "
        "counts), not time or HBM-fraction ratios",
    ]


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------
class OpStats:
    __slots__ = ("host_ns", "device_ns", "batches", "rows", "bytes")

    def __init__(self):
        self.host_ns = 0
        self.device_ns = 0
        self.batches = 0
        self.rows = 0
        self.bytes = 0


def aggregate_ops(events: List[dict]) -> Dict[str, OpStats]:
    ops: Dict[str, OpStats] = defaultdict(OpStats)
    for r in events:
        ev = r.get("event")
        if ev == "op_span":
            s = ops[r["op"]]
            if r.get("lane") == "device":
                s.device_ns += r["dur"]
            else:
                s.host_ns += r["dur"]
        elif ev == "op_batch":
            s = ops[r["op"]]
            s.batches += 1
            s.rows += r.get("rows") or 0
            s.bytes += r.get("bytes") or 0
    return dict(ops)


def _query_windows(events: List[dict]) -> List[dict]:
    """One record per query: start/end ts, duration, rows, tagging and
    analysis payloads, and the events inside its window.

    Concurrency-aware: under the serving scheduler, sessions interleave,
    so (a) queries are keyed by (emitting thread, query_id) — per-session
    query counters collide across sessions in a merged log — and (b)
    when a query's ts window overlaps another's, its events are filtered
    to the records its own drain thread emitted (every record carries
    ``tid``; the same by-thread attribution the live progress tracker
    uses). Serial single-session logs behave exactly as before."""
    queries: Dict[object, dict] = {}
    order: List[dict] = []

    def qkey(r: dict) -> tuple:
        return (r.get("tid"), r.get("query_id"))

    def _fallback(r: dict) -> Optional[dict]:
        """query_end drained on a different thread than planning (the
        writer path): match the open query with this query_id."""
        for q in order:
            if q["query_id"] == r.get("query_id") and q["end"] is None:
                return q
        return None

    for r in events:
        ev = r.get("event")
        if ev == "query_start":
            q = {"query_id": r.get("query_id"), "start": r["ts"],
                 "end": None, "dur": None, "rows": None,
                 "tid": r.get("tid"),
                 "plan_digest": r.get("plan_digest"),
                 "tagged": None, "analysis": None}
            queries[qkey(r)] = q
            order.append(q)
        elif ev == "plan_tagged":
            q = queries.get(qkey(r)) or _fallback(r)
            if q is not None:
                q["tagged"] = r
        elif ev == "plan_analysis":
            q = queries.get(qkey(r)) or _fallback(r)
            if q is not None:
                q["analysis"] = r
        elif ev == "query_end":
            q = queries.get(qkey(r))
            if q is None or q["end"] is not None:
                q = _fallback(r)
            if q is not None:
                q["end"] = r["ts"]
                q["dur"] = r.get("dur")
                q["rows"] = r.get("rows")
    for q in order:
        lo, hi = q["start"], q["end"] if q["end"] is not None else float("inf")
        overlaps = any(
            o is not q and q["start"] <= (o["end"] or float("inf"))
            and o["start"] <= hi for o in order)
        q["events"] = [
            r for r in events
            if lo <= r.get("ts", 0) <= hi
            and (not overlaps or q["tid"] is None
                 or r.get("tid") in (None, q["tid"]))
        ]
    return order


def roofline_section(events: List[dict], queries: List[dict],
                     peak_gbps: Optional[float] = None,
                     peak_tflops: Optional[float] = None,
                     ops: Optional[Dict[str, "OpStats"]] = None
                     ) -> List[str]:
    """Join each compile site's harvested XLA cost (program_cost events)
    against its op's measured device lane: achieved GB/s and FLOP/s vs
    the declared peaks, limiter classification, the program furthest
    below roofline, and the analyzer-bound vs XLA-bytes delta.

    Honest accounting: ``bytes_accessed``/``flops`` are PER-INVOCATION
    figures of each distinct compiled program, summed once each — so the
    achieved numbers are lower bounds that are exact for a cold
    single-dispatch run (the bench/CI case) and conservative when
    programs re-dispatched. An op's measured lane is ONE denominator:
    sites sharing an op (the aggregate compiles at agg_update AND
    agg_plan inside the same op_timed scope) get one combined
    ``op=...`` achieved line over the group's summed bytes instead of
    each dividing by the op's whole lane (which would double-count time
    and understate every row). Sites whose backend reported partial
    cost keys (the CPU fallback) degrade to partial rows, never
    errors."""
    costs = [r for r in events if r.get("event") == "program_cost"]
    lines = ["== roofline =="]
    if not costs:
        lines.append("  no program_cost events (cost plane saw no compile"
                     " misses — warm caches, or the log predates it)")
        return lines
    backend = next((r.get("backend") for r in costs if r.get("backend")),
                   None)
    dg, dt = BACKEND_PEAKS.get(backend or "", BACKEND_PEAKS["cpu"])
    # peak resolution: CLI flag > conf-declared peaks riding in the
    # events (spark.rapids.tpu.roofline.* at harvest time — the only
    # channel a session conf has to this offline tool) > backend default
    logged_g = next((r.get("peak_hbm_gbps") for r in costs
                     if r.get("peak_hbm_gbps")), None)
    logged_t = next((r.get("peak_tflops") for r in costs
                     if r.get("peak_tflops")), None)
    peak_gbps = peak_gbps or logged_g or dg
    peak_tflops = peak_tflops or logged_t or dt
    if ops is None:
        ops = aggregate_ops(events)
    # analyzer comparison is PER QUERY: each query's own (site, op) XLA
    # traffic against ITS analyzer bound — a merged multi-query log must
    # not sum ten queries' bytes against one query's bound, and an op
    # must not be charged a site-mate's bytes
    per_q: Dict[Tuple[str, str], List[Tuple[float, int]]] = defaultdict(list)
    for q in queries:
        qb = (q.get("analysis") or {}).get("bytes_by_op") or {}
        acc: Dict[Tuple[str, str], float] = defaultdict(float)
        for r in q.get("events", []):
            if (r.get("event") == "program_cost" and r.get("op")
                    and r.get("bytes_accessed") is not None):
                acc[(r.get("site"), r["op"])] += r["bytes_accessed"]
        for (site, op), xb in acc.items():
            if qb.get(op) is not None:
                per_q[(site, op)].append((xb, qb[op]))
    sites: Dict[str, dict] = {}
    for r in costs:
        s = sites.setdefault(r.get("site"), {
            "programs": 0, "bytes": 0.0, "flops": 0.0, "temp": 0,
            "compile_ms": 0.0, "ops": set(), "partial": False,
            "by_op": {}})
        s["programs"] += 1
        s["compile_ms"] += (r.get("trace_ms") or 0) + (r.get("compile_ms")
                                                       or 0)
        if r.get("bytes_accessed") is None:
            s["partial"] = True
        else:
            s["bytes"] += r["bytes_accessed"]
        if r.get("flops") is not None:
            s["flops"] += r["flops"]
        if r.get("temp_bytes") is not None:
            s["temp"] = max(s["temp"], r["temp_bytes"])
        if r.get("op"):
            s["ops"].add(r["op"])
            d = s["by_op"].setdefault(r["op"], {"bytes": 0.0, "flops": 0.0})
            d["bytes"] += r.get("bytes_accessed") or 0
            d["flops"] += r.get("flops") or 0
    lines.append(f"  peaks: {peak_gbps:.0f} GB/s, {peak_tflops:.1f} "
                 f"TFLOP/s (backend {backend or '?'}; override with "
                 "spark.rapids.tpu.roofline.peakHbmGBps/.peakTflops or "
                 "--peak-hbm-gbps/--peak-tflops)")
    cached_n = sum(1 for r in costs if r.get("from_cache"))
    if cached_n:
        # AOT program cache (serve/program_cache.py): these programs'
        # bytes/flops are the ORIGINAL harvest re-emitted on a
        # deserialize hit; their compile_ms is this process's near-zero
        # warm cost, so per-site compile seconds read honestly
        lines.append(f"  {cached_n}/{len(costs)} program(s) served "
                     "from the AOT cache (bytes/flops persisted at "
                     "original compile; compile ms = warm deserialize "
                     "cost)")
    # which sites claim each op: ops claimed by >1 site get ONE combined
    # achieved line (the op's lane is one denominator, not one per site)
    op_claims: Dict[str, set] = {}
    for site, s in sites.items():
        for o in s["ops"]:
            op_claims.setdefault(o, set()).add(site)
    shared_ops = {o for o, cl in op_claims.items() if len(cl) > 1}
    by_shared_op: Dict[str, dict] = {}
    for r in costs:
        o = r.get("op")
        if o in shared_ops:
            d = by_shared_op.setdefault(o, {"bytes": 0.0, "flops": 0.0})
            d["bytes"] += r.get("bytes_accessed") or 0
            d["flops"] += r.get("flops") or 0

    def achieved(t_ns: float, lane: str, nbytes: float, nflops: float
                 ) -> Tuple[str, float, str]:
        gbps = nbytes / t_ns          # bytes/ns == GB/s
        tflops = nflops / t_ns / 1e3  # flops/ns == GFLOP/s
        bw_frac = gbps / peak_gbps if peak_gbps else 0.0
        fl_frac = tflops / peak_tflops if peak_tflops else 0.0
        limiter = ("bandwidth-limited" if bw_frac >= fl_frac
                   else "compute-limited")
        return (f"achieved[{lane}]={gbps:.3f}GB/s "
                f"({bw_frac * 100:.2f}% of peak) "
                f"{tflops * 1e3:.3f}GFLOP/s "
                f"({fl_frac * 100:.2f}%) -> {limiter}",
                max(bw_frac, fl_frac), limiter)

    worst: Optional[Tuple[float, str, str]] = None
    for site, s in sorted(sites.items()):
        opl = ",".join(sorted(s["ops"])) or "?"
        row = (f"  site={site} op={opl} programs={s['programs']} "
               f"compile={s['compile_ms']:.1f}ms "
               f"xla_bytes={_mb(s['bytes']) if s['bytes'] else '-'}")
        if s["temp"]:
            row += f" peak_temp={_mb(s['temp'])}"
        # a site's own achieved figure covers only the ops it owns
        # EXCLUSIVELY (shared ops render on the combined lines below);
        # a mixed site still gets a row for its exclusive share
        excl = [o for o in s["ops"] if o not in shared_ops]
        ex_bytes = s["bytes"] - sum(s["by_op"][o]["bytes"]
                                    for o in s["ops"] if o in shared_ops)
        ex_flops = s["flops"] - sum(s["by_op"][o]["flops"]
                                    for o in s["ops"] if o in shared_ops)
        dev_ns = sum(ops[o].device_ns for o in excl if o in ops)
        host_ns = sum(ops[o].host_ns for o in excl if o in ops)
        t_ns, lane = (dev_ns, "device") if dev_ns else (host_ns, "host")
        if t_ns and (ex_bytes or ex_flops):
            txt, score, limiter = achieved(t_ns, lane, ex_bytes, ex_flops)
            row += " " + txt
            if worst is None or score < worst[0]:
                worst = (score, site, limiter)
        elif s["partial"] and not s["bytes"]:
            row += " (backend reported no byte/flop cost keys)"
        lines.append(row)
        for o in sorted(s["ops"]):
            pairs = per_q.get((site, o))
            if not pairs:
                continue
            # show the worst single query (largest overshoot)
            xb, b = max(pairs, key=lambda t: t[0] - t[1])
            if xb > b:
                lines.append(
                    f"    {o}: XLA touches {_mb(xb)} > analyzer "
                    f"bound {_mb(b)} (+{_mb(xb - b)} materialized "
                    "intermediates — roofline-push lead)")
            else:
                lines.append(
                    f"    {o}: XLA touches {_mb(xb)} <= analyzer "
                    f"bound {_mb(b)}")
    for o in sorted(shared_ops):
        st = ops.get(o)
        d = by_shared_op.get(o, {})
        if st is None or not (d.get("bytes") or d.get("flops")):
            continue
        t_ns, lane = ((st.device_ns, "device") if st.device_ns
                      else (st.host_ns, "host"))
        if not t_ns:
            continue
        group = "+".join(sorted(op_claims[o]))
        txt, score, limiter = achieved(t_ns, lane, d["bytes"], d["flops"])
        lines.append(f"  op={o} sites={group} {txt}")
        if worst is None or score < worst[0]:
            worst = (score, f"{o} ({group})", limiter)
    if worst is not None:
        lines.append(f"  furthest below roofline: {worst[1]} at "
                     f"{worst[0] * 100:.2f}% of peak ({worst[2]})")
    return lines


def hlo_section(events: List[dict]) -> List[str]:
    """``== hlo ==``: per-fusion byte attribution joined to its compile
    site (hlo_summary events, emitted beside each program_cost twin by
    spark_rapids_tpu/hlo.py). Per site: programs parsed, the summed
    shape-level byte attribution, worst parse coverage, module scatter
    count, and the AMPLIFICATION CULPRIT — the single top-bytes fusion
    with its idiom classification and its share of the site's XLA
    bytes-accessed ("agg_update: fusion.7 [scatter-add] accounts for
    12.1MB of 19.4MB"). Coverage < 1 or a low accounted fraction means
    the text parse explains only part of the compiler's figure (XLA
    utilization-weights bytes inside fusions/loop bodies) — reported,
    never an error."""
    sums = [r for r in events if r.get("event") == "hlo_summary"]
    lines = ["== hlo =="]
    if not sums:
        lines.append("  no hlo_summary events (cost plane saw no compile"
                     " misses, or the log predates per-fusion attribution)")
        return lines
    # the program_cost twin's compiler-reported bytes, by (site, digest)
    xla: Dict[Tuple[str, str], float] = defaultdict(float)
    for r in events:
        if (r.get("event") == "program_cost"
                and r.get("bytes_accessed") is not None):
            xla[(r.get("site"), r.get("digest"))] += r["bytes_accessed"]
    sites: Dict[str, dict] = {}
    for r in sums:
        s = sites.setdefault(r.get("site"), {
            "programs": 0, "bytes": 0, "xla": 0.0, "cov": 1.0,
            "scatters": 0, "ops": set(), "top": None})
        s["programs"] += 1
        s["bytes"] += r.get("total_bytes") or 0
        s["xla"] += xla.get((r.get("site"), r.get("digest")), 0.0)
        if r.get("coverage") is not None:
            s["cov"] = min(s["cov"], r["coverage"])
        s["scatters"] += r.get("scatter_count") or 0
        if r.get("op"):
            s["ops"].add(r["op"])
        for f in r.get("top_fusions") or []:
            if s["top"] is None or (f.get("bytes") or 0) > s["top"]["bytes"]:
                s["top"] = {"name": f.get("name"), "class": f.get("class"),
                            "bytes": f.get("bytes") or 0}
    worst: Optional[Tuple[float, str]] = None
    for site, s in sorted(sites.items()):
        opl = ",".join(sorted(s["ops"]))
        lines.append(
            f"  site={site}" + (f" op={opl}" if opl else "")
            + f" programs={s['programs']} attributed={_mb(s['bytes'])}"
            + f" coverage={s['cov']:.2f}"
            + (f" scatters={s['scatters']}" if s["scatters"] else ""))
        top = s["top"]
        if top is None:
            continue
        # the culprit line: the fusion the bytes live in, named against
        # the compiler's own figure for the site when it reported one
        denom = s["xla"] or s["bytes"]
        denom_kind = "XLA bytes" if s["xla"] else "attributed bytes"
        share = (f" ({top['bytes'] / denom * 100:.0f}% of site "
                 f"{denom_kind})") if denom else ""
        lines.append(
            f"    {site}: {top['name']} [{top['class']}] accounts for "
            f"{_mb(top['bytes'])} of {_mb(denom)}{share}")
        if worst is None or top["bytes"] > worst[0]:
            worst = (top["bytes"],
                     f"{site}: {top['name']} [{top['class']}] "
                     f"{_mb(top['bytes'])}")
    if worst is not None:
        lines.append(f"  largest single fusion: {worst[1]}")
    return lines


def forecast_vs_actual(queries: List[dict]) -> Tuple[List[str], int]:
    """Per bounded query: measured compile misses per site vs the
    analyzer's forecast, and measured per-op bytes vs the byte bound.
    Mirrors tests/harness.py::_assert_analysis_cross_check semantics —
    warm caches may miss LESS than forecast, never more."""
    lines: List[str] = []
    violations = 0
    for q in queries:
        an = q.get("analysis")
        if an is None:
            continue
        qid = q["query_id"]
        if not an.get("bounded"):
            lines.append(f"  query {qid}: not statically bounded "
                         "(layouts reported, forecasts omitted)")
            continue
        actual_sites: Dict[str, int] = defaultdict(int)
        actual_bytes: Dict[str, int] = defaultdict(int)
        recovery = 0
        for r in q["events"]:
            if r.get("event") == "compile_miss":
                actual_sites[r["site"]] += 1
            elif r.get("event") == "op_batch":
                actual_bytes[r["op"]] += r.get("bytes") or 0
            elif r.get("event") in ("oom_retry", "batch_split"):
                recovery += 1
        forecast = an.get("site_forecast") or {}
        bounds = an.get("bytes_by_op") or {}
        if recovery:
            # OOM recovery degraded this query to half-capacity (or
            # fallback-path) programs the STATIC plan never forecast:
            # the compile bound is honestly waived — that's degradation
            # doing its job, not emitter/analyzer drift (the resilience
            # section reports the actions themselves)
            lines.append(
                f"  query {qid}: compile forecast waived — {recovery} "
                "OOM recovery action(s) compiled degraded-capacity "
                "programs (see == resilience ==)")
        for site in sorted(set(actual_sites) | set(forecast)):
            got, exp = actual_sites.get(site, 0), forecast.get(site, 0)
            bad = got > exp and not recovery
            violations += bad
            if recovery and got > exp:
                lines.append(
                    f"  query {qid} compile[{site}]: actual {got} > "
                    f"forecast {exp} (waived: OOM recovery)")
                continue
            lines.append(
                f"  query {qid} compile[{site}]: actual {got} <= "
                f"forecast {exp}" if not bad else
                f"  query {qid} compile[{site}]: VIOLATION actual {got} > "
                f"forecast {exp}")
        for op in sorted(actual_bytes):
            got = actual_bytes[op]
            bound = bounds.get(op)
            bad = bound is None or got > bound
            violations += bad
            if bound is None:
                lines.append(f"  query {qid} bytes[{op}]: VIOLATION "
                             f"measured {_mb(got)} has no analyzer bound")
            elif bad:
                lines.append(f"  query {qid} bytes[{op}]: VIOLATION "
                             f"measured {_mb(got)} > bound {_mb(bound)}")
            else:
                lines.append(f"  query {qid} bytes[{op}]: measured "
                             f"{_mb(got)} <= bound {_mb(bound)}")
        # analyzer bound vs XLA's compiler-reported bytes: the layout
        # model bounds what rows REQUIRE; XLA reports what the compiled
        # kernel TOUCHES (temp-inflated). XLA above the bound is the
        # interesting signal — the kernel materializes intermediates the
        # layout model doesn't know about — and a lead, NOT a violation.
        xla_by_op: Dict[str, float] = defaultdict(float)
        for r in q["events"]:
            if (r.get("event") == "program_cost" and r.get("op")
                    and r.get("bytes_accessed") is not None):
                xla_by_op[r["op"]] += r["bytes_accessed"]
        for op in sorted(xla_by_op):
            bound = bounds.get(op)
            if bound is None:
                continue
            got = xla_by_op[op]
            if got > bound:
                lines.append(
                    f"  query {qid} xla[{op}]: XLA bytes {_mb(got)} "
                    f"exceed analyzer bound {_mb(bound)} "
                    f"(+{_mb(got - bound)} materialized intermediates — "
                    "roofline-push lead, not a violation)")
            else:
                lines.append(
                    f"  query {qid} xla[{op}]: XLA bytes {_mb(got)} "
                    f"within analyzer bound {_mb(bound)}")
    if not lines:
        lines.append("  no plan_analysis events in log (enable "
                     "sql.analysis.enabled with the event log on)")
    lines.append(f"  {violations} violation(s)")
    return lines, violations


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------
def build_report(events: List[dict], top_n: int = 10,
                 storm_threshold: int = DEFAULT_STORM_THRESHOLD,
                 peak_gbps: Optional[float] = None,
                 peak_tflops: Optional[float] = None) -> Tuple[str, int]:
    """(report text, violation count) for one merged event stream."""
    lines: List[str] = []
    queries = _query_windows(events)

    lines.append("== queries ==")
    env = _env_of(events)
    if env:
        lines.append("  env: " + _env_str(env))
    if not queries:
        lines.append("  none recorded")
    for q in queries:
        fb = q.get("tagged") or {}
        nfb = len(fb.get("fallbacks") or [])
        lines.append(
            f"  query {q['query_id']} plan={q.get('plan_digest')} "
            f"dur={_ms(q['dur'])} rows={q['rows']}"
            + (f" fallbacks={nfb}" if nfb else ""))
        for f in (fb.get("fallbacks") or []):
            lines.append(f"    !{f['op']}: {'; '.join(f['reasons'])}")

    ops = aggregate_ops(events)
    have_device = any(s.device_ns for s in ops.values())
    lane = "device" if have_device else "host"
    lines.append(f"== top ops by {lane} time ==")
    ranked = sorted(
        ops.items(),
        key=lambda kv: (kv[1].device_ns if have_device else kv[1].host_ns),
        reverse=True)[:top_n]
    if not ranked:
        lines.append("  no op spans recorded")
    for name, s in ranked:
        gbps = (s.bytes / s.device_ns if s.device_ns else None)
        lines.append(
            f"  {name}: device={_ms(s.device_ns) if s.device_ns else '-'} "
            f"host={_ms(s.host_ns)} batches={s.batches} rows={s.rows} "
            f"bytes={_mb(s.bytes)}"
            + (f" hbm_gbps={gbps:.2f}" if gbps else ""))
    if not have_device and ranked:
        lines.append("  (no device lane: run with "
                     "spark.rapids.tpu.metrics.deviceSync.enabled for "
                     "device-accurate ranking)")

    sites: Dict[str, int] = defaultdict(int)
    for r in events:
        if r.get("event") == "compile_miss":
            sites[r["site"]] += 1
    lines.append("== compile cache misses ==")
    if not sites:
        lines.append("  none (steady state)")
    for site, n in sorted(sites.items(), key=lambda kv: -kv[1]):
        storm = " <-- COMPILE STORM" if n >= storm_threshold else ""
        lines.append(f"  {site}: {n}{storm}")

    lines.extend(roofline_section(events, queries, peak_gbps, peak_tflops,
                                  ops=ops))

    lines.extend(hlo_section(events))

    xfer: Dict[str, List[int]] = defaultdict(lambda: [0, 0])
    for r in events:
        if r.get("event") == "transfer":
            t = xfer[r["direction"]]
            t[0] += 1
            t[1] += r.get("bytes") or 0
    lines.append("== transfers ==")
    if not xfer:
        lines.append("  none recorded")
    for d, (n, b) in sorted(xfer.items()):
        lines.append(f"  {d}: {n} transfer(s), {_mb(b)}")

    sh: Dict[Tuple[str, str], List[int]] = defaultdict(lambda: [0, 0, 0])
    for r in events:
        if r.get("event") in ("shuffle_write", "shuffle_fetch"):
            t = sh[(r["event"], r.get("codec", "none"))]
            t[0] += 1
            t[1] += r.get("bytes") or 0
            t[2] += r.get("rows") or 0
    lines.append("== shuffle ==")
    if not sh:
        lines.append("  none recorded")
    for (ev, codec), (n, b, rows) in sorted(sh.items()):
        lines.append(f"  {ev}[{codec}]: {n} piece(s), {_mb(b)}, "
                     f"{rows} row(s)")
    fetch_retries = sum(
        r.get("retries") or 0 for r in events
        if r.get("event") == "shuffle_fetch")
    if fetch_retries:
        lines.append(f"  fetch retries: {fetch_retries} transient "
                     "failure(s) recovered by backoff "
                     "(shuffle/network.py)")

    spills = [r for r in events if r.get("event") == "spill"]
    lines.append("== spill timeline ==")
    if not spills:
        lines.append("  none (working set fit the budget)")
    else:
        base = events[0]["ts"]
        peak = 0
        for r in spills:
            peak = max(peak, r["device_bytes"])
            lines.append(
                f"  +{(r['ts'] - base) / 1e6:.1f}ms {r['kind']} "
                f"{_mb(r['bytes'])} (device watermark "
                f"{_mb(r['device_bytes'])})")
        lines.append(f"  peak device watermark: {_mb(peak)}")

    # OOM recovery plane (memory/retry.py): how often forecasts were
    # wrong and what the recovery cost — retries (spill + backoff),
    # split-and-retry halvings (half-capacity recompiles, see the
    # resilience markers beside the compile track in Perfetto), and
    # serve requeues. A nonzero steady-state rate here means the HBM
    # budget or the analyzer's forecasts need attention (the live twin
    # is the watchdog's retry_storm alert).
    lines.append("== resilience ==")
    retries_by: Dict[Tuple[str, str], int] = defaultdict(int)
    for r in events:
        if r.get("event") == "oom_retry":
            retries_by[(r.get("op", "?"), r.get("kind", "retry"))] += 1
    splits_by: Dict[str, List[int]] = defaultdict(lambda: [0, 0])
    for r in events:
        if r.get("event") == "batch_split":
            t = splits_by[r.get("op", "?")]
            t[0] += 1
            t[1] = max(t[1], r.get("depth") or 0)
    if not retries_by and not splits_by:
        lines.append("  none (no OOM recovery activity)")
    for (op, kind), n in sorted(retries_by.items()):
        lines.append(f"  {op}: {n} {kind} action(s)")
    for op, (n, maxd) in sorted(splits_by.items()):
        lines.append(f"  {op}: {n} batch split(s), max depth {maxd} "
                     f"(completed at 1/{1 << maxd} capacity)")

    sc: Dict[str, List[int]] = defaultdict(lambda: [0, 0])
    for r in events:
        if r.get("event") == "scan_cache":
            t = sc[r["op"]]
            t[0] += 1
            t[1] += r.get("bytes") or 0
    lines.append("== scan cache ==")
    if not sc:
        lines.append("  no activity")
    for op, (n, b) in sorted(sc.items()):
        lines.append(f"  {op}: {n} ({_mb(b)})")

    # persistent AOT program cache (serve/program_cache.py): lifecycle
    # counts per op, warm compile cost actually paid, and the
    # compile-seconds-avoided estimate from the persisted cost payloads
    # riding the from_cache program_cost events. A warm serving process
    # should read hits ~= deserializes, zero compile misses above, and
    # avoided >> paid.
    pc_ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for r in events:
        if r.get("event") == "program_cache":
            t = pc_ops[r["op"]]
            t[0] += 1
            t[1] += r.get("bytes") or 0
    warm_paid_ms = 0.0
    saved_ms = 0.0
    from_cache_n = 0
    for r in events:
        if r.get("event") == "program_cost" and r.get("from_cache"):
            from_cache_n += 1
            warm_paid_ms += ((r.get("trace_ms") or 0)
                             + (r.get("compile_ms") or 0))
            saved_ms += r.get("saved_ms") or 0
    lines.append("== program cache ==")
    if not pc_ops:
        lines.append("  no activity (spark.rapids.tpu.aotCache off)")
    else:
        lines.append("  " + ", ".join(
            f"{op}={int(n)}" for op, (n, _) in sorted(pc_ops.items())))
        for op in ("hit", "put"):
            if op in pc_ops and pc_ops[op][1]:
                lines.append(f"  {op} bytes: {_mb(pc_ops[op][1])}")
        if from_cache_n:
            lines.append(
                f"  {from_cache_n} program(s) served from cache: paid "
                f"{warm_paid_ms / 1e3:.2f}s (deserialize + cached "
                f"compile), avoided ~{saved_ms / 1e3:.2f}s of original "
                "trace+compile (persisted payload estimate)")
        corrupt = int(pc_ops.get("corrupt", [0, 0])[0])
        if corrupt:
            lines.append(f"  NOTE: {corrupt} poisoned entr"
                         f"{'y' if corrupt == 1 else 'ies'} deleted "
                         "(fell through to plain compiles)")

    # aggregation strategy choices (one 'agg_strategy' event per exec per
    # capacity): the chooser on the record — compare against the top-ops
    # table above to see whether the pick was right
    strat: Dict[Tuple[str, str, int], Tuple[int, str]] = {}
    for r in events:
        if r.get("event") == "agg_strategy":
            k = (r.get("op"), r.get("strategy"), r.get("cap"))
            n, _ = strat.get(k, (0, ""))
            strat[k] = (n + 1, r.get("reason", ""))
    lines.append("== agg strategy ==")
    if not strat:
        lines.append("  none recorded (no grouped aggregates ran)")
    for (op, s, cap), (n, reason) in sorted(strat.items()):
        times = f" x{n}" if n > 1 else ""
        lines.append(f"  {op}[cap={cap}]: {s}{times} — {reason}")

    # join strategy choices (one 'join_strategy' event per exec per
    # BUILD capacity): the probe-lowering twin of the section above
    jstrat: Dict[Tuple[str, str, int], Tuple[int, str]] = {}
    for r in events:
        if r.get("event") == "join_strategy":
            k = (r.get("op"), r.get("strategy"), r.get("build_cap"))
            n, _ = jstrat.get(k, (0, ""))
            jstrat[k] = (n + 1, r.get("reason", ""))
    lines.append("== join strategy ==")
    if not jstrat:
        lines.append("  none recorded (no equi-joins ran)")
    for (op, s, cap), (n, reason) in sorted(jstrat.items()):
        times = f" x{n}" if n > 1 else ""
        lines.append(f"  {op}[build_cap={cap}]: {s}{times} — {reason}")

    # pipelined parquet decode stages: per-stage totals; overlapping
    # decode/upload spans are visible in the Perfetto export
    pipe: Dict[str, List[int]] = defaultdict(lambda: [0, 0, 0])
    for r in events:
        if r.get("event") == "pq_pipeline":
            t = pipe[r["stage"]]
            t[0] += 1
            t[1] += r.get("bytes") or 0
            t[2] += r.get("dur") or 0
    lines.append("== parquet pipeline ==")
    if not pipe:
        lines.append("  no activity")
    for stage, (n, b, dur) in sorted(pipe.items()):
        lines.append(f"  {stage}: {n} ({_mb(b)}, {_ms(dur)} host)")

    # serving layer: admission verdicts, queue balance + wait quantiles
    # (serve/scheduler.py events; absent in non-serving logs)
    adm: Dict[str, int] = defaultdict(int)
    for r in events:
        if r.get("event") == "admission":
            adm[r["verdict"]] += 1
    qops: Dict[str, int] = defaultdict(int)
    waits: List[int] = []
    max_depth = 0
    for r in events:
        if r.get("event") == "queue":
            qops[r["op"]] += 1
            max_depth = max(max_depth, r.get("depth") or 0)
            if r["op"] == "dequeue":
                waits.append(r.get("wait_ns") or 0)
    serving_violations = 0
    lines.append("== serving ==")
    if not adm and not qops:
        lines.append("  no serving activity "
                     "(spark.rapids.tpu.serve.enabled off)")
    else:
        lines.append("  admissions: " + ", ".join(
            f"{v}={n}" for v, n in sorted(adm.items())))
        if qops:
            waits.sort()

            def pct(p: float) -> str:
                return _ms(waits[min(len(waits) - 1,
                                     int(p * len(waits)))]) if waits else "-"
            lines.append(
                f"  queue: {qops.get('enqueue', 0)} enqueued, "
                f"{qops.get('dequeue', 0)} dequeued, "
                f"{qops.get('timeout', 0)} timed out, "
                f"max depth {max_depth}, wait p50={pct(0.5)} "
                f"p95={pct(0.95)}")
            if qops.get("enqueue", 0) != (qops.get("dequeue", 0)
                                          + qops.get("timeout", 0)):
                serving_violations += 1
                lines.append(
                    "  VIOLATION: queue events unbalanced — "
                    f"{qops.get('enqueue', 0)} enqueue(s) vs "
                    f"{qops.get('dequeue', 0)} dequeue(s) + "
                    f"{qops.get('timeout', 0)} timeout(s) (a query "
                    "entered the queue and never left)")

    lines.append("== forecast vs actual ==")
    fa_lines, violations = forecast_vs_actual(queries)
    lines.extend(fa_lines)
    return "\n".join(lines), violations + serving_violations


# ---------------------------------------------------------------------------
# alert replay (--alerts): the live watchdog's rules over a recorded log
# ---------------------------------------------------------------------------
def run_alerts(events: List[dict], stall_ms: int, pressure_fraction: float,
               storm_threshold: int, storm_window_ms: int,
               budget: Optional[int]) -> Tuple[str, int]:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from spark_rapids_tpu.obs.watchdog import WatchdogRules, replay_alerts

    rules = WatchdogRules(
        stall_ns=stall_ms * 1_000_000,
        pressure_fraction=pressure_fraction,
        storm_threshold=storm_threshold,
        storm_window_ns=storm_window_ms * 1_000_000,
    )
    alerts = replay_alerts(events, rules, budget=budget)
    base = events[0].get("ts", 0) if events else 0
    lines = ["== watchdog alert replay =="]
    lines.append(
        f"  rules: stall>={stall_ms}ms, "
        f"pressure>={pressure_fraction:.2f}x budget, "
        f"storm>={storm_threshold} misses/{storm_window_ms}ms")
    if not alerts:
        lines.append("  no alerts at these thresholds")
    for a in alerts:
        lines.append(f"  +{(a.ts - base) / 1e6:.1f}ms {a.describe()}")
    lines.append(f"  {len(alerts)} alert(s)")
    return "\n".join(lines), len(alerts)


# ---------------------------------------------------------------------------
# diff mode
# ---------------------------------------------------------------------------
def diff_logs(old_events: List[dict], new_events: List[dict],
              threshold: float) -> Tuple[str, int]:
    lines: List[str] = []
    regressions = 0
    # environment provenance first: when the two logs name different
    # hardware, every time/byte ratio below is apples-to-oranges — warn
    # loudly (a warning, not a regression: the structural gates below
    # still hold across environments)
    lines.extend(_env_warning(_env_of(old_events), _env_of(new_events)))
    a, b = aggregate_ops(old_events), aggregate_ops(new_events)
    for op in sorted(set(a) | set(b)):
        sa, sb = a.get(op), b.get(op)
        if sa is None or sb is None:
            lines.append(f"  {op}: only in {'new' if sa is None else 'old'} "
                         "log")
            continue
        for field in ("device_ns", "host_ns"):
            va, vb = getattr(sa, field), getattr(sb, field)
            if va <= 0 or vb <= 0:
                continue
            ratio = vb / va
            # ignore sub-millisecond deltas — host scheduling noise
            if ratio > 1.0 + threshold and vb - va > DIFF_MIN_NS:
                regressions += 1
                lines.append(
                    f"  {op}.{field[:-3]}: REGRESSION {_ms(va)} -> "
                    f"{_ms(vb)} ({ratio:.2f}x)")
            else:
                lines.append(f"  {op}.{field[:-3]}: ok {_ms(va)} -> "
                             f"{_ms(vb)}")
        if sb.bytes > sa.bytes * (1.0 + threshold) and sa.bytes > 0:
            regressions += 1
            lines.append(f"  {op}.bytes: REGRESSION {_mb(sa.bytes)} -> "
                         f"{_mb(sb.bytes)}")
    # roofline gates over harvested program costs: a site whose XLA
    # bytes_accessed or peak temp allocation GREW beyond the threshold is
    # a silent intermediate-materialization regression — exactly what the
    # cost plane exists to catch. Compile-TIME deltas stay subject to the
    # 1ms noise floor (trace/compile jitter is never a regression).
    ca, cb = _site_costs(old_events), _site_costs(new_events)
    for site in sorted(set(ca) & set(cb)):
        a_c, b_c = ca[site], cb[site]
        for field, label in (("bytes", "xla_bytes"), ("temp", "peak_temp")):
            va, vb = a_c[field], b_c[field]
            if va <= 0 or vb <= va * (1.0 + threshold):
                if va > 0 and vb > 0:
                    lines.append(f"  {site}.{label}: ok {_mb(va)} -> "
                                 f"{_mb(vb)}")
                continue
            regressions += 1
            lines.append(f"  {site}.{label}: REGRESSION {_mb(va)} -> "
                         f"{_mb(vb)} (intermediate materialization?)")
        va, vb = a_c["compile_ns"], b_c["compile_ns"]
        if (va > 0 and vb > va * (1.0 + threshold)
                and vb - va > DIFF_MIN_NS):
            regressions += 1
            lines.append(f"  {site}.compile: REGRESSION {_ms(va)} -> "
                         f"{_ms(vb)}")
    # per-fusion HLO gates (hlo_summary events): a site whose largest
    # single-fusion byte attribution grew beyond the threshold, or that
    # gained scatter-classified programs, regressed STRUCTURALLY — this
    # is the gate the item-1 kernel rewrite is judged by (bytes per
    # fusion must shrink; a new scatter lowering must not sneak in), and
    # it holds even across environments (shape-derived, not timed)
    # union of sites, not intersection: the appears-at-any-size scatter
    # gate must fire even when the new run compiled the scatter at a
    # compile site the old log never harvested (exactly the rewrite-
    # introduces-a-new-site scenario); byte-growth gates still need a
    # nonzero old-side figure to compute growth against
    ha, hb = _site_hlo(old_events), _site_hlo(new_events)
    empty = {"bytes": 0, "top": 0, "scatters": 0}
    for site in sorted(set(ha) | set(hb)):
        a_h, b_h = ha.get(site, empty), hb.get(site, empty)
        for field, label in (("top", "top_fusion_bytes"),
                             ("bytes", "hlo_bytes")):
            va, vb = a_h[field], b_h[field]
            if va > 0 and vb > va * (1.0 + threshold):
                regressions += 1
                note = (" (one fusion owns more traffic?)"
                        if field == "top" else "")
                lines.append(f"  {site}.{label}: REGRESSION {_mb(va)} -> "
                             f"{_mb(vb)}{note}")
            elif va > 0 and vb > 0:
                lines.append(f"  {site}.{label}: ok {_mb(va)} -> "
                             f"{_mb(vb)}")
        if b_h["scatters"] > a_h["scatters"]:
            regressions += 1
            lines.append(
                f"  {site}.scatter_count: REGRESSION {a_h['scatters']} -> "
                f"{b_h['scatters']} (a scatter lowering appeared)")
        elif a_h["scatters"] or b_h["scatters"]:
            lines.append(f"  {site}.scatter_count: ok {a_h['scatters']} "
                         f"-> {b_h['scatters']}")
    lines.append(f"  {regressions} regression(s)")
    return "\n".join(lines), regressions


def _site_hlo(events: List[dict]) -> Dict[str, dict]:
    """Per-site hlo_summary aggregates for --diff: summed shape-level
    byte attribution, the largest single-fusion byte figure, and the
    summed scatter count across the site's harvested programs."""
    per: Dict[str, dict] = {}
    for r in events:
        if r.get("event") != "hlo_summary":
            continue
        d = per.setdefault(r.get("site"),
                           {"bytes": 0, "top": 0, "scatters": 0})
        d["bytes"] += r.get("total_bytes") or 0
        d["scatters"] += r.get("scatter_count") or 0
        for f in r.get("top_fusions") or []:
            d["top"] = max(d["top"], f.get("bytes") or 0)
    return per


def _site_costs(events: List[dict]) -> Dict[str, dict]:
    """Per-site program_cost aggregates for --diff: summed bytes, peak
    temp, summed trace+compile ns (fields the backend omitted count 0)."""
    per: Dict[str, dict] = {}
    for r in events:
        if r.get("event") != "program_cost":
            continue
        d = per.setdefault(r.get("site"),
                           {"bytes": 0.0, "temp": 0, "compile_ns": 0})
        d["bytes"] += r.get("bytes_accessed") or 0
        d["temp"] = max(d["temp"], r.get("temp_bytes") or 0)
        d["compile_ns"] += int(((r.get("trace_ms") or 0)
                                + (r.get("compile_ms") or 0)) * 1e6)
    return per


def run_diff(old_path: str, new_path: str, threshold: float
             ) -> Tuple[str, int]:
    head = f"== diff (event logs) {old_path} -> {new_path} =="
    body, n = diff_logs(load_events([old_path]),
                        load_events([new_path]), threshold)
    return head + "\n" + body, n


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Offline profiler for spark_rapids_tpu event logs "
                    "(see module docstring)")
    ap.add_argument("paths", nargs="+",
                    help="event-log files/dirs; with --diff, exactly two "
                         "logs (old new)")
    ap.add_argument("--top", type=int, default=10,
                    help="operators to show in the top-ops table")
    ap.add_argument("--diff", action="store_true",
                    help="compare two logs; nonzero exit on regressions "
                         "beyond --threshold")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="relative regression threshold for --diff "
                         "(0.2 = 20%%)")
    ap.add_argument("--storm-threshold", type=int,
                    default=DEFAULT_STORM_THRESHOLD,
                    help="compile misses per site that flag a storm")
    ap.add_argument("--peak-hbm-gbps", type=float, default=None,
                    help="roofline peak HBM bandwidth (GB/s); default: "
                         "per-backend from the log's program_cost events")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="roofline peak compute (TFLOP/s); default: "
                         "per-backend from the log's program_cost events")
    ap.add_argument("--alerts", action="store_true",
                    help="replay the live watchdog rules over the log(s) "
                         "to tune thresholds offline (obs/watchdog.py)")
    ap.add_argument("--stall-ms", type=int, default=30000,
                    help="--alerts: op span duration that counts as a "
                         "stall")
    ap.add_argument("--pressure-fraction", type=float, default=0.85,
                    help="--alerts: HBM watermark fraction of the budget "
                         "that counts as pressure")
    ap.add_argument("--storm-window-ms", type=int, default=10000,
                    help="--alerts: sliding window for the per-site "
                         "compile-miss storm (count: --storm-threshold)")
    ap.add_argument("--budget", type=int, default=None,
                    help="--alerts: HBM budget bytes override (default: "
                         "the log's plan_analysis budget)")
    args = ap.parse_args(argv)

    if args.alerts:
        events = load_events(args.paths)
        if not events:
            print("no events found", file=sys.stderr)
            return 1
        text, _n = run_alerts(
            events, args.stall_ms, args.pressure_fraction,
            args.storm_threshold, args.storm_window_ms, args.budget)
        print(text)
        # a threshold-tuning tool, not a gate: alerts are the point, so
        # finding some is success (exit 0)
        return 0

    if args.diff:
        if len(args.paths) != 2:
            ap.error("--diff takes exactly two paths (old new)")
        text, bad = run_diff(args.paths[0], args.paths[1], args.threshold)
        print(text)
        return 1 if bad else 0

    events = load_events(args.paths)
    if not events:
        print("no events found", file=sys.stderr)
        return 1
    text, violations = build_report(events, args.top, args.storm_threshold,
                                    peak_gbps=args.peak_hbm_gbps,
                                    peak_tflops=args.peak_tflops)
    print(text)
    # forecast violations mean the analyzer's bounds or the emitters
    # drifted — CI runs this on a fresh log so the drift can't land
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())

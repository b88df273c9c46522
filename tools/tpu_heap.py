#!/usr/bin/env python3
"""Offline HBM heap profiler over the per-buffer ledger's event stream.

Companion to tools/tpu_profile.py (op spans, rooflines) — this tool
answers the MEMORY questions a recorded run leaves behind: who held the
bytes at the watermark, which call sites allocate, what churned through
the spiller, what donation gave back, and whether anything leaked. It
consumes the ``buffer_alloc``/``buffer_free``/``heap_snapshot`` events
the HBM ledger (spark_rapids_tpu/memory/ledger.py) emits, plus the
bid-stamped ``spill`` events that move ledger buffers across tiers and
the ``donation`` events from the donation plane.

Modes::

    tpu_heap.py LOG...                  # full heap report
    tpu_heap.py LOG --at NS             # live-heap snapshot at timestamp
    tpu_heap.py --diff OLD NEW          # per-op peak growth gate

CI gates (used by the ``heap`` workflow job)::

    --fail-on-leaks        nonzero exit if the sentinel flagged buffers
                           (heap_snapshot leaked>0) or non-exempt
                           buffers are still live at end of log
    --max-unattributed F   nonzero exit if more than fraction F of the
                           peak's live bytes carry no owning op

No spark_rapids_tpu imports: like the other tools/ scripts this runs
standalone on any machine holding a log (tests load it via importlib).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

#: per-op peak growth below this many bytes is allocator jitter, not a
#: regression (mirrors tpu_profile's DIFF_MIN_* noise-floor convention)
DIFF_MIN_BYTES = 1 << 20

#: ledger record kinds that never count as device residency or leaks
#: (must mirror memory/ledger.py: reservations are bookkeeping, not
#: buffers; scan-cache entries and cached relations outlive queries by
#: design)
NON_DEVICE_KINDS = ("reservation",)
LEAK_EXEMPT_KINDS = ("reservation", "scan_cache", "plan_state",
                     "cached_relation")


# ---------------------------------------------------------------------------
# loading (same shape as tpu_profile.load_events — duplicated so the
# tool stays standalone)
# ---------------------------------------------------------------------------
def load_events(paths: List[str]) -> List[dict]:
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


def _mb(b: Optional[float]) -> str:
    return "-" if b is None else f"{b / 1e6:.2f}MB"


# ---------------------------------------------------------------------------
# timeline reconstruction
# ---------------------------------------------------------------------------
class HeapTimeline:
    """The whole heap story of one log, replayed buffer by buffer.

    ``live`` tracks device-resident ledger buffers (bid -> record);
    spilled-to-host buffers stay tracked but leave the device tally
    until their unspill. The peak is the device-byte watermark of the
    ATTRIBUTED heap — by construction every byte in it has a record, so
    "unattributed" means owned by no op (op absent at alloc), not
    invisible to the ledger.
    """

    def __init__(self) -> None:
        self.live: Dict[object, dict] = {}       # bid -> record
        self.off_device: set = set()             # spilled bids
        self.live_bytes = 0
        self.peak_bytes = 0
        self.peak_ts = 0
        self.peak_by_op: Dict[str, int] = {}
        self.op_peak: Dict[str, int] = {}        # per-op own watermark
        self.alloc_by_op: Dict[str, int] = {}    # cumulative alloc bytes
        self.alloc_count_by_op: Dict[str, int] = {}
        self.site_bytes: Dict[str, int] = {}     # cumulative alloc bytes
        self.site_count: Dict[str, int] = {}
        self.churn_by_op: Dict[str, int] = {}    # spilled-off bytes
        self.donated_by_site: Dict[str, int] = {}
        self.free_reasons: Dict[str, int] = {}
        self.snapshots: List[dict] = []          # heap_snapshot events
        self.sentinel_leaks = 0                  # sum of snapshot leaked

    def _by_op(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for bid, r in self.live.items():
            if bid in self.off_device:
                continue
            out[r["op"]] = out.get(r["op"], 0) + r["bytes"]
        return out

    def _bump(self, op: str, delta: int, ts: int) -> None:
        self.live_bytes += delta
        if delta > 0:
            cur = self._by_op().get(op, 0)
            if cur > self.op_peak.get(op, 0):
                self.op_peak[op] = cur
            if self.live_bytes > self.peak_bytes:
                self.peak_bytes = self.live_bytes
                self.peak_ts = ts
                self.peak_by_op = self._by_op()

    def feed(self, r: dict) -> None:
        ev = r.get("event")
        ts = r.get("ts", 0)
        if ev == "buffer_alloc":
            if r.get("kind") in NON_DEVICE_KINDS:
                return
            op = r.get("op") or "(unattributed)"
            site = r.get("site") or "?"
            nbytes = int(r.get("bytes") or 0)
            self.live[r.get("bid")] = {
                "op": op, "site": site, "bytes": nbytes,
                "kind": r.get("kind"), "query_id": r.get("query_id"),
                "ts": ts}
            self.alloc_by_op[op] = self.alloc_by_op.get(op, 0) + nbytes
            self.alloc_count_by_op[op] = \
                self.alloc_count_by_op.get(op, 0) + 1
            self.site_bytes[site] = self.site_bytes.get(site, 0) + nbytes
            self.site_count[site] = self.site_count.get(site, 0) + 1
            self._bump(op, nbytes, ts)
        elif ev == "buffer_free":
            rec = self.live.pop(r.get("bid"), None)
            reason = r.get("reason") or "?"
            self.free_reasons[reason] = self.free_reasons.get(reason, 0) + 1
            if rec is None:
                return
            if r.get("bid") in self.off_device:
                self.off_device.discard(r.get("bid"))
            else:
                self._bump(rec["op"], -rec["bytes"], ts)
        elif ev == "spill":
            bid = r.get("bid")
            rec = self.live.get(bid) if bid is not None else None
            if rec is None:
                return
            if r.get("kind") == "device_to_host" \
                    and bid not in self.off_device:
                self.off_device.add(bid)
                self._bump(rec["op"], -rec["bytes"], ts)
                self.churn_by_op[rec["op"]] = \
                    self.churn_by_op.get(rec["op"], 0) + rec["bytes"]
            elif r.get("kind") == "unspill" and bid in self.off_device:
                self.off_device.discard(bid)
                self._bump(rec["op"], rec["bytes"], ts)
        elif ev == "donation":
            site = r.get("site") or "?"
            self.donated_by_site[site] = \
                self.donated_by_site.get(site, 0) + int(r.get("bytes") or 0)
        elif ev == "heap_snapshot":
            self.snapshots.append(r)
            self.sentinel_leaks += int(r.get("leaked") or 0)

    # -- derived views ------------------------------------------------------
    def end_leaks(self) -> List[dict]:
        """Non-exempt buffers still live when the log ends — the offline
        twin of the sentinel (catches buffers whose query never swept)."""
        return [dict(r, bid=bid) for bid, r in self.live.items()
                if r.get("kind") not in LEAK_EXEMPT_KINDS]

    def unattributed_fraction(self) -> float:
        """Share of the peak's live bytes owned by no op."""
        if not self.peak_bytes:
            return 0.0
        return self.peak_by_op.get("(unattributed)", 0) / self.peak_bytes


def build_timeline(events: List[dict]) -> HeapTimeline:
    t = HeapTimeline()
    for r in events:
        t.feed(r)
    return t


def snapshot_at(events: List[dict], at_ns: int) -> HeapTimeline:
    """The heap as it stood at ``at_ns`` (feed stops at the timestamp)."""
    t = HeapTimeline()
    for r in events:
        if r.get("ts", 0) > at_ns:
            break
        t.feed(r)
    return t


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
def _table(rows: List[Tuple[str, ...]], header: Tuple[str, ...]
           ) -> List[str]:
    widths = [len(h) for h in header]
    for row in rows:
        for i, c in enumerate(row):
            widths[i] = max(widths[i], len(c))
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    out = [fmt.format(*header), fmt.format(*("-" * w for w in widths))]
    out.extend(fmt.format(*row) for row in rows)
    return out


def build_report(t: HeapTimeline, top_n: int = 10) -> str:
    lines: List[str] = ["== HBM heap report =="]
    base = t.peak_ts
    lines.append(
        f"peak device-live (attributed): {_mb(t.peak_bytes)}"
        + (f" at ts {base}" if base else ""))
    top = sorted(t.peak_by_op.items(), key=lambda kv: -kv[1])[:3]
    if top:
        lines.append("top owners at peak: " + ", ".join(
            f"{op} {_mb(b)}" for op, b in top))
    unatt = t.unattributed_fraction()
    lines.append(f"unattributed at peak: {unatt * 100:.2f}%")
    lines.append(f"live at end of log: {_mb(t.live_bytes)} "
                 f"({len(t.live)} buffer(s))")

    if t.op_peak:
        lines.append("")
        lines.append("-- per-op attribution --")
        rows = [(op,
                 _mb(t.op_peak.get(op, 0)),
                 _mb(t.alloc_by_op.get(op, 0)),
                 str(t.alloc_count_by_op.get(op, 0)),
                 _mb(t.churn_by_op.get(op, 0)) if op in t.churn_by_op
                 else "-")
                for op, _ in sorted(t.op_peak.items(),
                                    key=lambda kv: -kv[1])[:top_n]]
        lines.extend(_table(
            rows, ("op", "peak", "allocated", "allocs", "spill churn")))

    if t.site_bytes:
        lines.append("")
        lines.append("-- per-site allocation --")
        rows = [(site, _mb(b), str(t.site_count.get(site, 0)))
                for site, b in sorted(t.site_bytes.items(),
                                      key=lambda kv: -kv[1])[:top_n]]
        lines.extend(_table(rows, ("site", "allocated", "allocs")))

    churn = sum(t.churn_by_op.values())
    if churn:
        lines.append("")
        lines.append(f"spill churn: {_mb(churn)} left the device "
                     "(re-upload paid on each unspill)")
    if t.donated_by_site:
        total = sum(t.donated_by_site.values())
        lines.append("")
        lines.append(f"donation savings: {_mb(total)} of output aliased "
                     "over donated inputs")
        for site, b in sorted(t.donated_by_site.items(),
                              key=lambda kv: -kv[1])[:top_n]:
            lines.append(f"  {site}: {_mb(b)}")
    if t.free_reasons:
        lines.append("")
        lines.append("free reasons: " + ", ".join(
            f"{k}={v}" for k, v in sorted(t.free_reasons.items())))

    leaks = t.end_leaks()
    lines.append("")
    if t.sentinel_leaks or leaks:
        lines.append(f"LEAKS: sentinel flagged {t.sentinel_leaks}, "
                     f"{len(leaks)} non-exempt buffer(s) live at end")
        for r in leaks[:top_n]:
            lines.append(
                f"  bid={r['bid']} {r['op']} {_mb(r['bytes'])} "
                f"site={r['site']} query={r.get('query_id')}")
    else:
        lines.append("no leaks: sentinel clean, nothing non-exempt "
                     "live at end of log")
    return "\n".join(lines)


def build_snapshot_report(t: HeapTimeline, at_ns: int) -> str:
    lines = [f"== heap at ts {at_ns} =="]
    lines.append(f"device-live: {_mb(t.live_bytes)} "
                 f"({len(t.live) - len(t.off_device)} buffer(s) on "
                 f"device, {len(t.off_device)} spilled)")
    for op, b in sorted(t._by_op().items(), key=lambda kv: -kv[1]):
        lines.append(f"  {op}: {_mb(b)}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# diff: per-op peak growth gate
# ---------------------------------------------------------------------------
def diff_heap(old: HeapTimeline, new: HeapTimeline, threshold: float
              ) -> Tuple[str, int]:
    """Per-op peak growth between two logs. A regression is an op whose
    peak grew more than ``threshold`` relative AND more than
    DIFF_MIN_BYTES absolute (allocator jitter floor); brand-new ops
    count from zero but still need the absolute floor."""
    lines: List[str] = ["== heap diff (per-op peak) =="]
    regressions = 0
    ops = sorted(set(old.op_peak) | set(new.op_peak))
    for op in ops:
        o, n = old.op_peak.get(op, 0), new.op_peak.get(op, 0)
        if n - o <= DIFF_MIN_BYTES:
            continue
        if o and (n - o) / o <= threshold:
            continue
        regressions += 1
        lines.append(
            f"REGRESSION {op}: peak {_mb(o)} -> {_mb(n)} "
            + (f"({(n - o) / o * 100:+.0f}%)" if o else "(new op)"))
    dp, dn = old.peak_bytes, new.peak_bytes
    lines.append(f"total peak: {_mb(dp)} -> {_mb(dn)}")
    lo, ln = len(old.end_leaks()), len(new.end_leaks())
    if ln > lo:
        regressions += 1
        lines.append(f"REGRESSION leaks: {lo} -> {ln} non-exempt "
                     "buffer(s) live at end")
    if regressions == 0:
        lines.append("no per-op peak regressions")
    return "\n".join(lines), regressions


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Offline HBM heap profiler over ledger event logs "
                    "(see module docstring)")
    ap.add_argument("paths", nargs="+",
                    help="event-log files/dirs; with --diff, exactly two "
                         "(old new)")
    ap.add_argument("--top", type=int, default=10,
                    help="rows per attribution table")
    ap.add_argument("--at", type=int, default=None,
                    help="render the live heap at this ts (ns) instead "
                         "of the full report")
    ap.add_argument("--diff", action="store_true",
                    help="compare two logs; nonzero exit on per-op peak "
                         "growth beyond --threshold")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="relative per-op peak growth threshold for "
                         "--diff (0.2 = 20%%)")
    ap.add_argument("--fail-on-leaks", action="store_true",
                    help="nonzero exit if the sentinel flagged leaks or "
                         "non-exempt buffers are live at end of log")
    ap.add_argument("--max-unattributed", type=float, default=None,
                    help="nonzero exit if more than this fraction of "
                         "peak bytes carries no owning op (CI: 0.01)")
    args = ap.parse_args(argv)

    if args.diff:
        if len(args.paths) != 2:
            ap.error("--diff takes exactly two paths (old new)")
        old = build_timeline(load_events([args.paths[0]]))
        new = build_timeline(load_events([args.paths[1]]))
        text, bad = diff_heap(old, new, args.threshold)
        print(text)
        return 1 if bad else 0

    events = load_events(args.paths)
    if not events:
        print("no events found", file=sys.stderr)
        return 1

    if args.at is not None:
        print(build_snapshot_report(snapshot_at(events, args.at), args.at))
        return 0

    t = build_timeline(events)
    print(build_report(t, args.top))
    rc = 0
    if args.fail_on_leaks and (t.sentinel_leaks or t.end_leaks()):
        print("FAIL: leaked buffers (see report)", file=sys.stderr)
        rc = 1
    if args.max_unattributed is not None:
        frac = t.unattributed_fraction()
        if frac > args.max_unattributed:
            print(f"FAIL: {frac * 100:.2f}% of peak bytes unattributed "
                  f"(limit {args.max_unattributed * 100:.2f}%)",
                  file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())

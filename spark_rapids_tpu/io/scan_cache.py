"""Device scan cache: an HBM buffer pool for hot file scans.

Reference analog: the columnar cache serializer
(shims/spark311/.../ParquetCachedBatchSerializer.scala) gives cached
dataframes a GPU-columnar representation; on TPU the engine caches the
POST-LINK artifact (uploaded+decodable column payloads) because the host
link — not decode — is the scarce resource (orders slower than HBM). The CPU engine's repeated scans get the same effect
for free from the OS page cache.

Keys carry (path, mtime, size), so a rewritten file never serves stale
data. Values are opaque (the scanner stores whatever it rebuilds per row
group); byte accounting is supplied by the caller. Eviction is LRU under
a conf byte budget, with one exception: a split's puts never evict what
ANOTHER split of the same query has asked for (``by`` = the query and the
part of it that asks). A scan that comes round again is the opposite of
what LRU is for: a table past the budget, scanned a split at a time,
evicted in one split what the other had just been handed and would ask
for again, and kept 11 of 29 row groups where the budget holds 20. Within
one split (and for a caller that names no query) the order is plain LRU.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from .. import events as _events
from .. import obs as _obs
from ..utils.locks import ordered_lock


def _ledger():
    """The HBM ledger riding the process catalog: cache entries hold
    device arrays the catalog watermark never sees, so the ledger is
    where their residency gets an owner tag. Entries are exempt from the
    leak sentinel (kind=scan_cache — outliving queries is the point)."""
    from ..memory.catalog import BufferCatalog

    return BufferCatalog.get().ledger


class DeviceScanCache:
    _instance: Optional["DeviceScanCache"] = None
    _instance_lock = threading.Lock()

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        # declared: put/evict feed the HBM ledger + leaf sinks while held
        self._lock = ordered_lock("io.scan_cache")
        #: key -> (value, nbytes, ledger id) — lid is None while the
        #: HBM ledger is unarmed (the zero-overhead-off path)
        self._entries: "OrderedDict[tuple, Tuple[Any, int, Any]]" = \
            OrderedDict()
        #: key -> (query, part of it) that last asked for the entry or
        #: put it; None where the caller named no query
        self._asked_by: Dict[tuple, Any] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @classmethod
    def get_instance(cls, conf) -> Optional["DeviceScanCache"]:
        from ..conf import SCAN_DEVICE_CACHE, SCAN_DEVICE_CACHE_MAX_BYTES

        if not conf.get(SCAN_DEVICE_CACHE):
            return None
        budget = int(conf.get(SCAN_DEVICE_CACHE_MAX_BYTES))
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = DeviceScanCache(budget)
                return cls._instance
            inst = cls._instance
        # a later session's budget governs: the singleton resizes instead
        # of silently pinning the first session's value. Outside the latch
        # — resize takes the declared cache lock and calls into the
        # ledger, which must not nest under a raw singleton latch; two
        # concurrent sessions racing here both resize, idempotently.
        if inst.max_bytes != budget:
            inst.resize(budget)
        return inst

    def resize(self, max_bytes: int) -> None:
        """Adopt a new byte budget, evicting LRU entries if it shrank."""
        with self._lock:
            self.max_bytes = int(max_bytes)
            while self._bytes > self.max_bytes and self._entries:
                self._evict(next(iter(self._entries)))

    def _evict(self, key: tuple) -> None:
        """Drop one entry as an eviction (under ``self._lock``)."""
        _, sz, lid = self._entries.pop(key)
        self._asked_by.pop(key, None)
        self._bytes -= sz
        self.evictions += 1
        if lid is not None:
            _ledger().note_free(lid, reason="evict")
        if _events.enabled():
            _events.emit("scan_cache", op="evict", bytes=sz)
        if _obs.enabled():
            self._obs_note("evict", sz)

    def _obs_note(self, op: str, nbytes: int) -> None:
        """Mirror one cache op into the live registry (called under
        self._lock; the registry lock is a leaf — no inversion)."""
        _obs.inc("tpu_scan_cache_ops", 1, op=op)
        if op in ("hit", "miss"):
            seen = self.hits + self.misses
            _obs.set_gauge("tpu_scan_cache_hit_ratio",
                           self.hits / seen if seen else 0.0)
        _obs.set_gauge("tpu_scan_cache_resident_bytes", self._bytes)

    def stats(self) -> Dict[str, int]:
        """Cache-effectiveness counters (previously unobservable): a hot
        workload should show hits dominating misses and zero evictions; a
        nonzero eviction rate means the working set exceeds
        scan.deviceCache.maxBytes and uploads are being re-paid."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "entries": len(self._entries), "bytes": self._bytes,
                    "max_bytes": self.max_bytes}

    @classmethod
    def reset(cls) -> None:
        with cls._instance_lock:
            cls._instance = None

    def get(self, key: tuple, by: Any = None) -> Optional[Any]:
        """``by`` = (query, part) names who asks (the query of
        ``exec.base.current_query`` and the split of its scan): the puts
        of the query's other parts will not evict what is handed here."""
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self.misses += 1
                if _events.enabled():
                    _events.emit("scan_cache", op="miss", bytes=0)
                if _obs.enabled():
                    self._obs_note("miss", 0)
                return None
            self._entries.move_to_end(key)
            self._asked_by[key] = by
            self.hits += 1
            if _events.enabled():
                _events.emit("scan_cache", op="hit", bytes=hit[1])
            if _obs.enabled():
                self._obs_note("hit", hit[1])
            return hit[0]

    def put(self, key: tuple, value: Any, nbytes: int,
            by: Any = None) -> None:
        with self._lock:
            if key in self._entries:
                _, old, old_lid = self._entries.pop(key)
                self._asked_by.pop(key, None)
                self._bytes -= old
                if old_lid is not None:
                    _ledger().note_free(old_lid, reason="replace")
            # one oversized entry must not wedge the pool
            if nbytes > self.max_bytes:
                return
            # what this put may evict, oldest first: everything but what
            # another part of the same query has asked for. Where that is
            # not enough the entry is not admitted
            victims, over = [], self._bytes + nbytes - self.max_bytes
            for k, (_, sz, _) in self._entries.items():
                if over <= 0:
                    break
                asked = self._asked_by.get(k)
                if (by is None or asked is None or asked[0] != by[0]
                        or asked == by):
                    victims.append(k)
                    over -= sz
            if over > 0:
                return
            led = _ledger()
            lid = led.note_alloc(nbytes, kind="scan_cache") \
                if led.armed() else None
            self._entries[key] = (value, nbytes, lid)
            self._asked_by[key] = by
            self._bytes += nbytes
            if _events.enabled():
                _events.emit("scan_cache", op="put", bytes=nbytes)
            if _obs.enabled():
                self._obs_note("put", nbytes)
            for k in victims:
                self._evict(k)

    def drop_under_pressure(self) -> int:
        """Drop EVERY resident entry (OOM recovery, memory/retry.py):
        cached scan columns are pure re-derivable HBM residency, so under
        device memory exhaustion they are the first thing to give back.
        Returns bytes released. Entries re-fill lazily on the next scan."""
        with self._lock:
            freed = self._bytes
            n = len(self._entries)
            for _, _, lid in self._entries.values():
                if lid is not None:
                    _ledger().note_free(lid, reason="pressure_drop")
            self._entries.clear()
            self._asked_by.clear()
            self._bytes = 0
            self.evictions += n
            if freed and _events.enabled():
                _events.emit("scan_cache", op="pressure_drop", bytes=freed)
            if freed and _obs.enabled():
                self._obs_note("evict", freed)
            return freed

    def invalidate_path(self, path: str) -> None:
        """Drop every entry of one file (the writers' commit protocol
        calls this, io/commit.py — reads stay correct either way via the
        mtime/size key; this just frees the HBM promptly). Paths are
        realpath-normalized to match ``file_key``, so a writer committing
        through a symlink still hits the scanner's entries."""
        path = _real(path)
        with self._lock:
            dead = [k for k in self._entries if k and k[0] == path]
            for k in dead:
                _, sz, lid = self._entries.pop(k)
                self._asked_by.pop(k, None)
                self._bytes -= sz
                if lid is not None:
                    _ledger().note_free(lid, reason="invalidate")


_REALPATH_CACHE: dict = {}


def _real(path: str) -> str:
    """``os.path.realpath`` with a process-lifetime memo: symlink
    resolution lstat()s every path component, which is pathologically slow
    on some overlay/FUSE filesystems (measured multiple SECONDS per call in
    sandboxed containers), and scan keys hit this once per row group. A
    symlink retargeted mid-process misses the memo, but the mtime/size in
    the key already guarantees no stale reads either way."""
    r = _REALPATH_CACHE.get(path)
    if r is None:
        import os

        if len(_REALPATH_CACHE) > 65536:
            _REALPATH_CACHE.clear()
        r = _REALPATH_CACHE[path] = os.path.realpath(path)
    return r


def file_key(path: str, rg: int, columns, cap_hint=None) -> tuple:
    """Cache key pinned to file identity (mtime+size catch rewrites).
    realpath-normalized so the same file reached via symlink / relative
    path shares one entry (and invalidate_path finds it). The stat runs
    on the LIVE path, not the memoized resolution: a symlink retargeted
    after the memo was taken then sees the new target's mtime/size — a
    different key — so the memo can never serve stale data (and never
    turns a valid symlink read into a stat of a deleted old target)."""
    import os

    st = os.stat(path)
    return (_real(path), int(st.st_mtime_ns), st.st_size, rg,
            tuple(columns), cap_hint)

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
a conf byte budget.
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
                _, (_, sz, lid) = self._entries.popitem(last=False)
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

    def get(self, key: tuple) -> Optional[Any]:
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
            self.hits += 1
            if _events.enabled():
                _events.emit("scan_cache", op="hit", bytes=hit[1])
            if _obs.enabled():
                self._obs_note("hit", hit[1])
            return hit[0]

    def put(self, key: tuple, value: Any, nbytes: int) -> None:
        with self._lock:
            if key in self._entries:
                _, old, old_lid = self._entries.pop(key)
                self._bytes -= old
                if old_lid is not None:
                    _ledger().note_free(old_lid, reason="replace")
            # one oversized entry must not wedge the pool
            if nbytes > self.max_bytes:
                return
            led = _ledger()
            lid = led.note_alloc(nbytes, kind="scan_cache") \
                if led.armed() else None
            self._entries[key] = (value, nbytes, lid)
            self._bytes += nbytes
            if _events.enabled():
                _events.emit("scan_cache", op="put", bytes=nbytes)
            if _obs.enabled():
                self._obs_note("put", nbytes)
            while self._bytes > self.max_bytes and self._entries:
                _, (_, sz, elid) = self._entries.popitem(last=False)
                self._bytes -= sz
                self.evictions += 1
                if elid is not None:
                    _ledger().note_free(elid, reason="evict")
                if _events.enabled():
                    _events.emit("scan_cache", op="evict", bytes=sz)
                if _obs.enabled():
                    self._obs_note("evict", sz)

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

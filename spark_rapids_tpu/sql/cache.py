"""``DataFrame.cache()``: the session's cached relations.

Reference analog: Spark's ``CacheManager`` + ``InMemoryRelation``, served on
the accelerator through ``ParquetCachedBatchSerializer`` and the
``InMemoryTableScanExec`` replacement (SURVEY.md). ``cache()`` marks a
logical plan; every later plan of the session that contains it reads the
cached relation in its place (``sql/session._lower``), whichever DataFrame
object it was built from. Nothing runs until the first action: the exec that
serves the relation (``exec/basic.TpuInMemoryTableScanExec``) fills it from
the child plan once, as device batches on one device or as one shard of
global planes on every device of a mesh, and books the bytes with the
``BufferCatalog``. There is no conf key, as Spark's ``cache()`` has none, and
no eviction: a fill that does not fit fails by name, ``unpersist()`` frees.

A relation remembers the identity of the files under its plan (path, mtime,
size: ``io/scan_cache.file_key``'s rule); a plan over a rewritten file drops
what was cached and refills.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..utils.locks import ordered_lock


def file_scan_paths(plan) -> List[str]:
    """The paths of the ``file_scan`` leaves under a logical plan."""
    if plan.kind == "file_scan":
        return [plan.args[1]]
    return [p for c in plan.children for p in file_scan_paths(c)]


def files_identity(plan) -> tuple:
    """(realpath, mtime ns, size) of every file under the ``file_scan``
    leaves of a logical plan, from a fresh listing."""
    from ..io.parquet import discover_files
    from ..io.scan_cache import file_key

    return tuple(sorted(
        file_key(path, None, ())[:3]
        for scanned in file_scan_paths(plan)
        for path, _ in discover_files(scanned)))


class CachedRelation:
    """One cached plan: what is resident, where, and for which files.
    Filled and read by ``TpuInMemoryTableScanExec`` under ``lock``."""

    def __init__(self, plan):
        self.plan = plan
        #: held across a fill; every reader takes it to find the fill done
        self.lock = ordered_lock("sql.cache", reentrant=True)
        self.files_key: Optional[tuple] = None
        #: one device: the child plan's batches by partition, and each
        #: partition's (rows, bytes)
        self.batches: Optional[List[list]] = None
        self.part_sizes: List[tuple] = []
        #: a mesh: ``io/mesh_stage.StagedPlanes``, one shard a device
        self.planes: Any = None
        self.rows = 0
        self.bytes = 0
        self.per_device: Dict[int, int] = {}
        self.fills = 0
        self.hits = 0
        self._rid: Optional[int] = None

    @property
    def filled(self) -> bool:
        return self.batches is not None or self.planes is not None

    @property
    def shards(self) -> int:
        return len(self.per_device)

    def store(self, *, batches=None, part_sizes=(), planes=None, rows: int,
              per_device: Dict[int, int], files_key: tuple) -> None:
        """Keep a fill's result and book its bytes on each device."""
        from ..memory.catalog import BufferCatalog

        self.batches, self.planes = batches, planes
        self.part_sizes = list(part_sizes)
        self.rows = int(rows)
        self.per_device = dict(per_device)
        self.bytes = sum(per_device.values())
        self.files_key = files_key
        self.fills += 1
        self._rid = BufferCatalog.get().register_resident(
            per_device, label=self.plan.kind)

    def release(self) -> int:
        """Drop what is resident (the device memory goes with the last
        reference) and return the catalog's bytes. Returns bytes freed."""
        with self.lock:
            freed = self.bytes if self.filled else 0
            if self._rid is not None:
                from ..memory.catalog import BufferCatalog

                BufferCatalog.get().unregister_resident(self._rid)
                self._rid = None
            self.batches = self.planes = None
            self.part_sizes = []
            self.rows = self.bytes = 0
            self.per_device = {}
            self.files_key = None
            return freed

    def describe(self) -> str:
        if not self.filled:
            return "not filled yet"
        return (f"{self.rows} rows, {self.bytes} bytes resident on "
                f"{self.shards} device(s)")


class CacheManager:
    """The session's marked plans. ``lookup`` is asked for every node of
    every plan while any plan is marked, and for none otherwise."""

    def __init__(self):
        self._relations: Dict[Any, CachedRelation] = {}

    def __bool__(self) -> bool:
        return bool(self._relations)

    def mark(self, plan) -> CachedRelation:
        rel = self._relations.get(plan)
        if rel is None:
            rel = self._relations[plan] = CachedRelation(plan)
        return rel

    def lookup(self, plan) -> Optional[CachedRelation]:
        return self._relations.get(plan) if self._relations else None

    def drop(self, plan) -> int:
        rel = self._relations.pop(plan, None)
        return rel.release() if rel is not None else 0

    def clear(self) -> int:
        freed = 0
        for plan in list(self._relations):
            freed += self.drop(plan)
        return freed

    def relations(self) -> List[CachedRelation]:
        return list(self._relations.values())

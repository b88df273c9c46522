"""Buffer catalog with tiered DEVICE -> HOST -> DISK spill.

Reference analog: RapidsBufferCatalog.scala:34-109 (central registry of
spillable buffers keyed by id), RapidsBufferStore.scala:40 (store chain with
synchronous spill on allocation pressure), SpillPriorities.scala:26, and
DeviceMemoryEventHandler.scala:33 (allocation-failure callback draining the
stores). There is no RMM on TPU — XLA owns the allocator — so pressure is
tracked by *accounting*: every registered buffer adds its byte size to the
device-tier total, and `request()` (called before large materializations)
drains lowest-priority buffers to host/disk until the configured budget
holds. jax arrays whose last reference drops are freed by XLA, so "spill"
here means: copy to host numpy (or an .npz on disk), drop the device
reference, and rematerialize on demand.
"""
from __future__ import annotations

import logging
import os
import tempfile
import threading
from typing import Dict, List, Optional

from .. import events as _events
from .. import obs as _obs
from ..conf import (
    HBM_POOL_FRACTION,
    HBM_RESERVE,
    HOST_SPILL_STORAGE_SIZE,
    MEMORY_DEBUG,
    RapidsConf,
    SPILL_ENABLED,
    conf,
)
from ..utils.locks import ordered_lock
from .ledger import KIND_CACHED_RELATION, KIND_RESERVATION, Ledger

log = logging.getLogger("spark_rapids_tpu.memory")

HBM_BUDGET_BYTES = conf(
    "spark.rapids.tpu.memory.hbm.budgetBytes", 0,
    "Explicit spill budget for catalog-tracked device buffers; 0 derives "
    "it from allocFraction * device memory (or unlimited when the backend "
    "reports no memory stats).")

# tier ordering (reference: RapidsBuffer.scala:54-61 StorageTier)
TIER_DEVICE = 0
TIER_HOST = 1
TIER_DISK = 2

# spill priorities (reference: SpillPriorities.scala:26)
HOST_MEMORY_BUFFER_SPILL_PRIORITY = -100
INPUT_FROM_SHUFFLE_PRIORITY = -50
ACTIVE_BATCHING_PRIORITY = 0


def derive_hbm_budget(conf_: RapidsConf) -> Optional[int]:
    """The device-tier spill budget: explicit hbm.budgetBytes, else
    allocFraction * device memory, else None (unlimited / accounting
    only). ONE derivation shared by the catalog and the static plan
    analyzer (plugin/plananalysis.py), so the plan-time OOM warning and
    the runtime spill trigger can never disagree on the budget."""
    explicit = conf_.get(HBM_BUDGET_BYTES)
    if explicit:
        return int(explicit)
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        limit = stats.get("bytes_limit") if stats else None
    except Exception:  # pragma: no cover - backend-dependent
        limit = None
    if not limit:
        return None
    frac = conf_.get(HBM_POOL_FRACTION)
    reserve = conf_.get(HBM_RESERVE)
    return max(int(limit * frac) - reserve, 1 << 20)


class SpillMetrics:
    def __init__(self):
        self.device_to_host = 0
        self.host_to_disk = 0
        self.spilled_bytes = 0
        #: buffers re-materialized on device after a spill (each one paid
        #: a host->device upload the plan didn't ask for)
        self.unspills = 0
        #: high-water mark of catalog-tracked device bytes — the figure
        #: to compare against the HBM budget when sizing a deployment
        self.peak_device_bytes = 0


class BufferCatalog:
    """Process-wide registry of spillable buffers.

    Buffers register with a byte size and spill priority; `request(bytes)`
    synchronously spills lowest-priority device buffers until the budget
    accommodates the new allocation (reference:
    RapidsBufferStore.synchronousSpill)."""

    _instance: Optional["BufferCatalog"] = None
    _instance_lock = threading.Lock()

    def __init__(self, conf_: Optional[RapidsConf] = None):
        self.conf = conf_ or RapidsConf({})
        self._lock = ordered_lock("memory.catalog", reentrant=True)
        self._buffers: Dict[int, "SpillableHandle"] = {}
        self._next_id = 0
        self._device_bytes = 0
        self._host_bytes = 0
        self.metrics = SpillMetrics()
        self._spill_dir: Optional[str] = None
        self._budget = self._derive_budget()
        # admission reservations (serve/scheduler.py): rid -> (bytes,
        # label). An admitted query's forecast counts against the budget
        # from admission until release, so the scheduler's admit decision
        # and the spiller can never over-commit the same headroom.
        self._reservations: Dict[int, tuple] = {}
        self._reserved_bytes = 0
        self._next_rid = 0
        #: per-buffer lifecycle book (owner attribution, leak sentinel,
        #: observed-peak admission feed) — armed only while events/obs
        #: are on (or force-armed by bench/tests)
        self.ledger = Ledger(self.conf)
        # cached relations (DataFrame.cache()): rid -> ({device id:
        # bytes}, label, ledger id). Resident for a session, a share on
        # each device of a mesh, never spilled: the only bytes the
        # catalog books per device (every spillable buffer lives on the
        # default device, id 0).
        self._resident: Dict[int, tuple] = {}
        self._resident_bytes: Dict[int, int] = {}

    # -- singleton (reference: RapidsBufferCatalog.singleton) --------------
    @classmethod
    def get(cls) -> "BufferCatalog":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = BufferCatalog()
            return cls._instance

    @classmethod
    def reset(cls, conf_: Optional[RapidsConf] = None) -> "BufferCatalog":
        """Re-initialize (tests / executor restart)."""
        with cls._instance_lock:
            cls._instance = BufferCatalog(conf_)
            return cls._instance

    def _derive_budget(self) -> Optional[int]:
        return derive_hbm_budget(self.conf)

    @property
    def budget(self) -> Optional[int]:
        """The live spill budget (None = unlimited) — read by the
        watchdog's pressure rule and the /status HBM block so they can
        never disagree with the spiller."""
        return self._budget

    def _obs_watermark(self) -> None:
        """Mirror the device-byte watermark into the live registry (a
        leaf-lock callee: safe under self._lock)."""
        _obs.set_gauge("tpu_hbm_device_bytes", self._device_bytes)
        _obs.set_gauge("tpu_hbm_peak_device_bytes",
                       self.metrics.peak_device_bytes)
        if self._budget is not None:
            # keep the budget gauge tracking the LIVE catalog (a reset
            # with new memory confs would otherwise leave the plane
            # advertising the first session's stale derivation)
            _obs.set_gauge("tpu_hbm_budget_bytes", self._budget)

    # -- registration ------------------------------------------------------
    def register(self, handle: "SpillableHandle") -> int:
        with self._lock:
            bid = self._next_id
            self._next_id += 1
            self._buffers[bid] = handle
            self._device_bytes += handle.size
            if self._device_bytes > self.metrics.peak_device_bytes:
                self.metrics.peak_device_bytes = self._device_bytes
            if self.conf.get(MEMORY_DEBUG):
                log.info("register buffer %d (%d B, prio %d): device=%d B",
                         bid, handle.size, handle.priority, self._device_bytes)
            if _obs.enabled():
                self._obs_watermark()
            if self.ledger.armed():
                handle._lid = self.ledger.note_alloc(
                    handle.size,
                    kind=getattr(handle, "ledger_kind", "spillable"))
        self.request(0)
        return bid

    def unregister(self, bid: int, reason: str = "close") -> None:
        with self._lock:
            h = self._buffers.pop(bid, None)
            if h is None:
                return
            if h.tier == TIER_DEVICE:
                self._device_bytes -= h.size
            elif h.tier == TIER_HOST:
                self._host_bytes -= h.size
            if _obs.enabled():
                self._obs_watermark()
            self.ledger.note_free(getattr(h, "_lid", None), reason)

    def on_unspill(self, h: "SpillableHandle", from_host: bool) -> None:
        with self._lock:
            if from_host:
                self._host_bytes -= h.size
            self._device_bytes += h.size
            self.metrics.unspills += 1
            if self._device_bytes > self.metrics.peak_device_bytes:
                self.metrics.peak_device_bytes = self._device_bytes
            if _events.enabled():
                _events.emit("spill", kind="unspill", bytes=h.size,
                             device_bytes=self._device_bytes,
                             bid=getattr(h, "_lid", None))
            if _obs.enabled():
                _obs.inc("tpu_spills", 1, kind="unspill")
                _obs.inc("tpu_spill_bytes", h.size, kind="unspill")
                self._obs_watermark()
            self.ledger.note_unspill(getattr(h, "_lid", None))
        # the just-materialized buffer is the one in use: spill OTHERS to
        # make room (the reference pins via addReference during access)
        self.request(0, exclude=h)

    # -- pressure ----------------------------------------------------------
    def _account_device_spill(self, freed: int, emergency: bool,
                              handle: Optional["SpillableHandle"] = None
                              ) -> None:
        """THE device->host spill bookkeeping (byte counters, metrics,
        spill event, obs twins, debug log) — one body shared by the
        proactive path (:meth:`request`) and the OOM-recovery path
        (:meth:`ensure_headroom`) so the two sets of books can never
        diverge. Called after a successful ``spill_to_host``."""
        lid = getattr(handle, "_lid", None)
        with self._lock:
            self._device_bytes -= freed
            self._host_bytes += freed
            self.metrics.device_to_host += 1
            self.metrics.spilled_bytes += freed
            if _events.enabled():
                _events.emit("spill", kind="device_to_host",
                             bytes=freed,
                             device_bytes=self._device_bytes,
                             bid=lid)
            if _obs.enabled():
                _obs.inc("tpu_spills", 1, kind="device_to_host")
                _obs.inc("tpu_spill_bytes", freed,
                         kind="device_to_host")
                self._obs_watermark()
            self.ledger.note_spill(lid)
        if self.conf.get(MEMORY_DEBUG):
            log.info("%sspilled %d B to host (device=%d B)",
                     "emergency " if emergency else "", freed,
                     self._device_bytes)

    def _drain_host_overage(self) -> None:
        """Push host-tier buffers to disk while the tier exceeds
        host.spillStorageSize. The victim list is snapshotted under the
        lock, but the loop re-reads the LIVE byte count under the lock
        each iteration so concurrent spillers stop as soon as the tier
        is under cap instead of each pushing the full overage to disk.
        Deliberately budget-independent: a budget-less catalog's
        emergency spills must still respect the HOST cap."""
        host_cap = self.conf.get(HOST_SPILL_STORAGE_SIZE)
        with self._lock:
            hosts = sorted(
                (h for h in self._buffers.values()
                 if h.tier == TIER_HOST),
                key=lambda h: h.priority,
            ) if self._host_bytes > host_cap else []
        for h in hosts:
            with self._lock:
                if self._host_bytes <= host_cap:
                    break
            freed = h.spill_to_disk(self._disk_dir())
            if freed:
                with self._lock:
                    self._host_bytes -= freed
                    self.metrics.host_to_disk += 1
                    if _events.enabled():
                        _events.emit("spill", kind="host_to_disk",
                                     bytes=freed,
                                     device_bytes=self._device_bytes)
                    if _obs.enabled():
                        _obs.inc("tpu_spills", 1, kind="host_to_disk")
                        _obs.inc("tpu_spill_bytes", freed,
                                 kind="host_to_disk")

    def request(self, nbytes: int, exclude: Optional["SpillableHandle"] = None
                ) -> None:
        """Make room for an upcoming allocation of ``nbytes`` (the
        DeviceMemoryEventHandler analog, invoked proactively)."""
        if self._budget is None or not self.conf.get(SPILL_ENABLED):
            return
        # victims are picked under the catalog lock but spilled OUTSIDE it:
        # each spill takes the handle's own lock, and materialize() takes
        # handle-then-catalog — never holding one while acquiring the other
        # in the opposite order avoids a lock-order inversion
        with self._lock:
            need = (self._device_bytes + self._resident_bytes.get(0, 0)
                    + nbytes - self._budget)
            victims = sorted(
                (h for h in self._buffers.values()
                 if h.tier == TIER_DEVICE and not h.pinned
                 and h is not exclude),
                key=lambda h: h.priority,
            ) if need > 0 else []
        for h in victims:
            if need <= 0:
                break
            freed = h.spill_to_host()
            if freed:
                self._account_device_spill(freed, emergency=False,
                                           handle=h)
                need -= freed
        self._drain_host_overage()

    def ensure_headroom(self, nbytes: Optional[int] = None,
                        exclude: Optional["SpillableHandle"] = None) -> int:
        """EMERGENCY spill for OOM recovery (memory/retry.py): drain
        unpinned device-tier buffers to host until ``nbytes`` have been
        freed — or ALL of them when ``nbytes`` is None (a real backend
        OOM means XLA's allocator is full regardless of what the
        accounting thinks, so the recovery path empties what it can).
        Unlike :meth:`request` this ignores the device budget (a
        budget-less catalog still frees memory) but keeps the same
        victim order, lock discipline, and spill accounting — and the
        HOST-tier cap still applies (the overage drain below runs
        unconditionally, not behind the budget guard). Returns bytes
        freed."""
        if not self.conf.get(SPILL_ENABLED):
            return 0
        with self._lock:
            victims = sorted(
                (h for h in self._buffers.values()
                 if h.tier == TIER_DEVICE and not h.pinned
                 and h is not exclude),
                key=lambda h: h.priority,
            )
        total = 0
        for h in victims:
            if nbytes is not None and total >= nbytes:
                break
            freed = h.spill_to_host()
            if not freed:
                continue
            total += freed
            self._account_device_spill(freed, emergency=True, handle=h)
        # unconditional (not gated on total): a recovery pass that freed
        # nothing itself must still drain an overage a concurrent
        # spiller left — the host cap holds on every exit path
        self._drain_host_overage()
        return total

    def largest_spillable(self) -> int:
        """Size of the largest unpinned device-tier buffer (0 when none)
        — reported by TpuOutOfDeviceMemory so an OOM error names what a
        spill could still have freed."""
        with self._lock:
            return max(
                (h.size for h in self._buffers.values()
                 if h.tier == TIER_DEVICE and not h.pinned), default=0)

    def _disk_dir(self) -> str:
        # under the catalog lock: concurrent host-overage drains
        # otherwise both see None and mkdtemp twice, scattering spill
        # files across two directories (one leaked on cleanup)
        with self._lock:
            if self._spill_dir is None:
                self._spill_dir = tempfile.mkdtemp(prefix="srtpu_spill_")
            return self._spill_dir

    @property
    def device_bytes(self) -> int:
        return self._device_bytes

    # -- cached relations (exec/basic.TpuInMemoryTableScanExec) ------------
    def resident_bytes(self, device: Optional[int] = None):
        """Bytes cached relations hold on device ``device``, or the
        ``{device id: bytes}`` map of every device that holds any."""
        with self._lock:
            if device is None:
                return dict(self._resident_bytes)
            return self._resident_bytes.get(device, 0)

    def check_resident_fit(self, per_device: Dict[int, int],
                           op: str) -> None:
        """Before a cached relation fills: would ``per_device`` more
        resident bytes fit the budget on every device, beside what is
        resident already (and, on the default device, the spillable
        buffers)? Cached shards are never spilled, so a fill that does
        not fit fails here, by name, before anything is uploaded."""
        from .retry import TpuOutOfDeviceMemory

        with self._lock:
            if self._budget is None:
                return
            for dev, nbytes in sorted(per_device.items()):
                held = self._resident_bytes.get(dev, 0) + (
                    self._device_bytes if dev == 0 else 0)
                if held + nbytes > self._budget:
                    raise TpuOutOfDeviceMemory(
                        f"cached relation does not fit device {dev} in "
                        f"{op}: {nbytes} B to make resident beside "
                        f"{held} B held, budget {self._budget} B "
                        "(cached shards are not spilled: unpersist() a "
                        "relation or raise memory.hbm.budgetBytes)",
                        op=op, watermark=held, budget=self._budget)

    def register_resident(self, per_device: Dict[int, int],
                          label: str = "") -> int:
        """Book a filled cached relation: ``per_device`` bytes stay on
        each device until :meth:`unregister_resident`."""
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            total = sum(per_device.values())
            lid = self.ledger.note_alloc(
                total, kind=KIND_CACHED_RELATION,
                site=f"cached:{label}" if label else "cached",
            ) if self.ledger.armed() else None
            self._resident[rid] = (dict(per_device), label, lid)
            for dev, nbytes in per_device.items():
                self._resident_bytes[dev] = (
                    self._resident_bytes.get(dev, 0) + nbytes)
            if self.conf.get(MEMORY_DEBUG):
                log.info("cached relation %s resident: %s", label,
                         self._resident_bytes)
        # what is resident on the default device narrows the room of
        # the spillable buffers there
        self.request(0)
        return rid

    def unregister_resident(self, rid: int) -> None:
        with self._lock:
            entry = self._resident.pop(rid, None)
            if entry is None:
                return
            for dev, nbytes in entry[0].items():
                left = self._resident_bytes.get(dev, 0) - nbytes
                if left > 0:
                    self._resident_bytes[dev] = left
                else:
                    self._resident_bytes.pop(dev, None)
            self.ledger.note_free(entry[2], reason="unpersist")

    # -- admission reservations (serve/scheduler.py) -----------------------
    def observed_query_peak(self, query_id: Optional[str]
                            ) -> Optional[int]:
        """Ledger-observed device-byte peak of one query — the figure
        the PR 13 requeue inflates its forecast to (replacing the raw
        global watermark the typed OOM carries)."""
        return self.ledger.query_peak(query_id)

    def reserve(self, nbytes: int, label: str = "") -> int:
        """Charge an admitted query's peak-HBM forecast against the
        budget until :meth:`release_reservation`. Accounting only — no
        allocation happens; the reservation narrows what the scheduler
        will admit next. Deliberately conservative: a running query's
        ACTUAL buffers also register in ``device_bytes``, so headroom is
        double-counted toward safety (queueing, never OOM)."""
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            lid = self.ledger.note_alloc(
                int(nbytes), kind=KIND_RESERVATION,
                site=f"reservation:{label}" if label else "reservation",
            ) if self.ledger.armed() else None
            self._reservations[rid] = (int(nbytes), label, lid)
            self._reserved_bytes += int(nbytes)
            if _obs.enabled():
                _obs.set_gauge("tpu_hbm_reserved_bytes",
                               self._reserved_bytes)
            if self.conf.get(MEMORY_DEBUG):
                log.info("reserve %d B (%s): reserved=%d B", nbytes, label,
                         self._reserved_bytes)
            return rid

    def release_reservation(self, rid: int) -> None:
        with self._lock:
            entry = self._reservations.pop(rid, None)
            if entry is None:
                return
            self._reserved_bytes -= entry[0]
            self.ledger.note_free(entry[2], reason="release")
            if _obs.enabled():
                _obs.set_gauge("tpu_hbm_reserved_bytes",
                               self._reserved_bytes)

    @property
    def reserved_bytes(self) -> int:
        return self._reserved_bytes

    def admission_state(self) -> tuple:
        """(budget, device_bytes, reserved_bytes) read atomically under
        the catalog lock — the scheduler derives its admission headroom
        from one consistent snapshot, never from separate property reads
        that could interleave with a concurrent register/reserve."""
        with self._lock:
            return self._budget, self._device_bytes, self._reserved_bytes


class SpillableHandle:
    """One spillable buffer set: named jax arrays that can round-trip
    DEVICE -> HOST (numpy) -> DISK (.npz) and back (reference:
    RapidsBuffer.scala:63-140 acquire/addReference/free + the per-tier
    RapidsBuffer implementations)."""

    def __init__(self, arrays: Dict[str, "object"], priority: int = 0,
                 catalog: Optional[BufferCatalog] = None,
                 ledger_kind: str = "spillable"):
        self._catalog = catalog or BufferCatalog.get()
        #: HBM-ledger record kind. Sites whose buffers DELIBERATELY
        #: outlive the creating query (join build sides, broadcast
        #: batches — reused with the cached plan) declare "plan_state"
        #: so the leak sentinel doesn't flag designed retention.
        self.ledger_kind = ledger_kind
        self._device: Optional[Dict[str, object]] = dict(arrays)
        self._host: Optional[Dict[str, object]] = None
        self._disk_path: Optional[str] = None
        self.tier = TIER_DEVICE
        self.priority = priority
        self.pinned = False
        self.size = sum(a.size * a.dtype.itemsize for a in arrays.values())
        self._closed = False
        #: ledger record id — assigned by register() when the ledger is
        #: armed, None otherwise (the zero-overhead-off path)
        self._lid: Optional[int] = None
        # guards tier transitions; "memory.spillable" ranks just above
        # the catalog — close() unregisters while holding it
        self._tlock = ordered_lock("memory.spillable", reentrant=True)
        self._id = self._catalog.register(self)

    # -- tier transitions (each holds the handle lock; the catalog never
    # holds ITS lock while calling in here — see BufferCatalog.request) ----
    def spill_to_host(self) -> int:
        with self._tlock:
            if self.tier != TIER_DEVICE or self._closed:
                return 0
            import jax
            import numpy as np

            self._host = {
                k: np.asarray(jax.device_get(v))
                for k, v in self._device.items()
            }
            self._device = None
            self.tier = TIER_HOST
            return self.size

    def spill_to_disk(self, dirpath: str) -> int:
        with self._tlock:
            if self.tier != TIER_HOST or self._closed:
                return 0
            import numpy as np

            self._disk_path = os.path.join(dirpath, f"buf{self._id}.npz")
            np.savez(self._disk_path, **self._host)
            self._host = None
            self.tier = TIER_DISK
            return self.size

    def materialize(self) -> Dict[str, object]:
        """Bring the arrays back on device (re-registering the device
        bytes); the reference analog is SpillableColumnarBatch
        .getColumnarBatch re-materializing from whatever tier."""
        with self._tlock:
            if self._closed:
                raise ValueError("buffer already closed")
            if self.tier == TIER_DEVICE:
                return self._device
            import jax.numpy as jnp
            import numpy as np

            from_disk = self.tier == TIER_DISK
            if from_disk:
                with np.load(self._disk_path) as z:
                    self._host = {k: z[k] for k in z.files}
                os.unlink(self._disk_path)
                self._disk_path = None
            dev = {k: jnp.asarray(v) for k, v in self._host.items()}
            self._device = dev
            self._host = None
            self.tier = TIER_DEVICE
        self._catalog.on_unspill(self, from_host=not from_disk)
        return dev

    # -- lifecycle (Arm idiom: with_resource(SpillableHandle(...))) --------
    def close(self, reason: str = "close") -> None:
        # taken under the tier lock so a close can't interleave with an
        # in-flight spill: unregister() reads self.tier to pick which byte
        # counter to decrement, and the spill loop decrements the same
        # counter when spill_to_* returns nonzero — serializing the two
        # keeps the accounting single-entry either way
        with self._tlock:
            if self._closed:
                return
            self._closed = True
            self._catalog.unregister(self._id, reason=reason)
            self._device = None
            self._host = None
            if self._disk_path and os.path.exists(self._disk_path):
                os.unlink(self._disk_path)

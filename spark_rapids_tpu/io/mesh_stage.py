"""Sharded ingestion: place scan partitions directly on mesh devices.

The generic mesh staging path (exec/mesh._MeshStage._stage_child) executes
every child partition on the default device, pulls the batches to host,
splices global planes and re-uploads them with a row sharding — a host
GATHER standing between the scan and the SPMD stage. This module is the
data-parallel alternative for sources whose partitions are host-decodable:
partition i is decoded on the host and uploaded STRAIGHT to mesh shard
``i % n`` as that device's slice of a ``NamedSharding``-committed global
array (``jax.make_array_from_single_device_arrays`` — no cross-device
reshard, no host round trip of already-placed data), with the host decode
of shard k+1 overlapping the staged upload of shard k (the cross-device
extension of io/parquet_device.read_row_groups_pipelined's decode→upload
pipeline).

Fixed-width columns only: a string column's byte pool needs a global
re-bucketing decision that defeats per-shard streaming; scans with string
output keep the generic staging path (exec/mesh.py docstring).

Reference analog: the multi-threaded cloud reader feeding the shuffle
transport directly (MultiFileCloudParquetPartitionReader,
GpuParquetScan.scala:1299) — here the "transport" is device placement.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..types import StructType
from ..utils.bucketing import bucket_rows


class ShardPayload(NamedTuple):
    """One shard's decoded host columns: ``arrays[j]`` = (data, validity)
    numpy pair for column j, ``rows`` = live row count."""

    arrays: List[Tuple[np.ndarray, np.ndarray]]
    rows: int


class StagedPlanes(NamedTuple):
    """The contract ``exec/mesh._MeshStage`` consumes: flat global planes
    (data+validity per column, each a NamedSharding row-sharded array),
    per-shard live row counts, the common per-shard capacity, the column
    layout/smls tuples of the generic staging path, and per-shard staged
    byte counts for the transfer events + the plananalysis cross-check.
    ``source`` says where the planes came from: ``"sharded_scan"`` staged
    for this stage, ``"cached"`` a cached relation's resident planes
    (exec/basic.TpuInMemoryTableScanExec); ``uploaded_bytes`` what THIS
    hand-over sent to the devices (0 when the planes were resident)."""

    cols: List[object]
    counts: np.ndarray
    cap: int
    layout: tuple
    smls: tuple
    staged_bytes: tuple
    source: str = "sharded_scan"
    uploaded_bytes: int = 0


def mesh_shard_cap(rows_per_shard: Sequence[int], bucket_min: int) -> int:
    """The common per-shard row capacity: the bucketed max shard row
    count. ONE home for this rule — the runtime staging paths and the
    plananalysis per-shard forecast both call it, so the forecast can
    only drift from the actual by a code change both sides see."""
    return bucket_rows(max(max(rows_per_shard, default=0), 1), bucket_min)


def shard_plane_bytes(cap: int, fields) -> int:
    """Per-shard staged bytes for a fixed-width schema at capacity
    ``cap``: data plane + 1-byte validity plane per column (the exact
    nbytes the staging paths upload — shared with the forecast)."""
    total = 0
    for f in fields:
        total += cap * (np.dtype(f.dataType.to_numpy()).itemsize + 1)
    return total


def stageable_schema(schema: StructType) -> bool:
    return all(T.is_fixed_width(f.dataType) for f in schema.fields)


def forecast_staging(items: Sequence[int], n_shards: int, bucket_min: int,
                     fields) -> dict:
    """What staging ``items`` (row counts, item i -> shard i % n) will
    place on each shard: rows, the common capacity and — for a
    fixed-width schema — the plane bytes. The mesh stages' forecast
    (exec/mesh.forecast_mesh_staging) and a cached relation's check
    before its fill both read this, so neither can drift from
    ``stage_sharded``."""
    per_shard = [sum(items[i] for i in idxs)
                 for idxs in round_robin(len(items), n_shards)]
    cap = mesh_shard_cap(per_shard, bucket_min)
    fixed = all(T.is_fixed_width(f.dataType) for f in fields)
    return {
        "cap": cap,
        "per_shard_rows": per_shard,
        "staged_bytes": ([shard_plane_bytes(cap, fields)] * n_shards
                         if fixed else None),
    }


def stage_sharded(
    mesh,
    n_shards: int,
    schema: StructType,
    decode_shard: Callable[[int], ShardPayload],
    rows_per_shard: Sequence[int],
    bucket_min: int,
    on_shard: Optional[Callable[[int, int, int, float], None]] = None,
) -> StagedPlanes:
    """Decode + place each shard's rows on its own mesh device.

    ``decode_shard(s)`` runs on a worker thread (host decode — pyarrow /
    numpy work that releases the GIL); the caller thread pads the decoded
    columns into planes and uploads them to device ``s`` while the worker
    decodes shard ``s+1``. ``rows_per_shard`` must be known up front
    (parquet metadata / batch row counts) because the common capacity is
    a global max. ``on_shard(s, rows, bytes, seconds)`` fires after each
    shard's upload is dispatched (the per-shard transfer lane).
    """
    import jax

    from ..parallel.mesh import row_sharding

    fields = schema.fields
    if not stageable_schema(schema):
        raise ValueError("stage_sharded is fixed-width only")
    cap = mesh_shard_cap(rows_per_shard, bucket_min)
    devices = list(mesh.devices.reshape(-1))
    sharding = row_sharding(mesh)

    # per column: per-shard single-device pieces, assembled at the end
    pieces: List[List[List[object]]] = [
        [[] for _ in range(n_shards)] for _ in range(2 * len(fields))
    ]
    counts = np.zeros(n_shards, np.int32)
    staged_bytes = [0] * n_shards

    def upload_shard(s: int, payload: ShardPayload) -> None:
        from ..exec.base import phase
        from ..memory.retry import named_oom

        t0 = time.perf_counter()
        n = int(payload.rows)
        counts[s] = n
        nbytes = 0
        # a device allocation failure placing a shard's planes surfaces
        # as TpuOutOfDeviceMemory naming the shard, never a raw XLA
        # traceback mid-pipeline
        # the h2d boundary of the scan that stages (one span a shard,
        # like io/arrow_convert.packed_upload's one a batch): padding +
        # transfer, sized by the bytes that cross the link
        with phase("upload") as span, \
                named_oom(f"mesh_stage[shard {s}]"):
            for j, f in enumerate(fields):
                dt = f.dataType.to_numpy()
                d = np.zeros(cap, dt)
                v = np.zeros(cap, bool)
                if n:
                    data, valid = payload.arrays[j]
                    d[:n] = data[:n]
                    v[:n] = valid[:n]
                dd = jax.device_put(d, devices[s])
                vv = jax.device_put(v, devices[s])
                pieces[2 * j][s] = dd
                pieces[2 * j + 1][s] = vv
                nbytes += d.nbytes + v.nbytes
            span.set(bytes=int(nbytes), shard=s)
        staged_bytes[s] = nbytes
        if on_shard is not None:
            on_shard(s, n, nbytes, time.perf_counter() - t0)

    # the 1-deep pipeline: worker decodes shard k+1 while this thread
    # pads + uploads shard k
    with ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="srtpu-meshdec") as pool:
        from ..exec.base import carry

        # the worker's spans belong to the exec and query open here
        decode = carry(decode_shard)
        nxt = pool.submit(decode, 0) if n_shards else None
        for s in range(n_shards):
            payload = nxt.result()
            nxt = (pool.submit(decode, s + 1)
                   if s + 1 < n_shards else None)
            upload_shard(s, payload)

    cols: List[object] = []
    for plane in pieces:
        cols.append(jax.make_array_from_single_device_arrays(
            (n_shards * cap,), sharding, list(plane)))
    layout = tuple(("f",) for _ in fields)
    smls = tuple(0 for _ in fields)
    return StagedPlanes(cols, counts, cap, layout, smls,
                        tuple(staged_bytes),
                        uploaded_bytes=sum(staged_bytes))


def round_robin(num_items: int, n_shards: int) -> List[List[int]]:
    """Item index lists per shard: item i -> shard i % n (the placement
    contract of the sharded scan — partition i lands on mesh shard
    i mod n)."""
    out: List[List[int]] = [[] for _ in range(n_shards)]
    for i in range(num_items):
        out[i % n_shards].append(i)
    return out

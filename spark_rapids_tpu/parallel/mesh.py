"""Device mesh construction for the SPMD exchange path.

Reference analog: GpuShuffleEnv / the UCX transport bring-up
(GpuShuffleEnv.scala:26-107, shuffle-plugin UCX.scala:53-130) — on TPU the
"transport" is the mesh itself: one jax.sharding.Mesh over the local
devices, collectives riding ICI. There is no connection establishment, no
management port, no bounce-buffer pool to size; XLA owns the wire.

This module also owns the engine's ``shard_map`` wrapper (replication
check off) — every caller (exec/mesh.py, the tests, the dryrun) imports
it from here.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax import shard_map as _shard_map_impl

AXIS = "shards"

_MESH_CACHE: dict = {}


def shard_map(f, mesh, in_specs, out_specs, **_ignored):
    """``jax.shard_map`` with the replication check off (row counts vary
    per shard; the static check can't see through the sort/segment
    kernels). Extra kwargs are ignored."""
    return _shard_map_impl(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)


def mesh_jit_kwargs() -> dict:
    """Extra ``jax.jit`` keywords for a ``shard_map`` program. On the TPU
    the compiler's ``conditional-code-motion`` pass rewrites a conditional
    operand that carries a 64-bit value (a u32 pair after the x64
    rewrite) into a 4-tuple its branch computation does not take, and the
    next ``HloReplicationAnalysis`` CHECK-fails — the whole process
    aborts (found compiling ``TpuMeshAggregateExec`` for four v5e chips,
    jax 0.9.0 / libtpu 0.0.34, PR 23; single-device programs run no such
    analysis). The pass is an optimisation; with it off the program
    compiles."""
    if jax.default_backend() == "tpu":
        return {"compiler_options": {
            "xla_disable_hlo_passes": "conditional-code-motion"}}
    return {}


def device_count() -> int:
    return jax.local_device_count()


def configured_mesh_devices(conf) -> int:
    """The shard count the conf asks for: ``mesh.devices`` caps/forces the
    global mesh width, ``shuffle.meshSize`` (the legacy per-exchange knob)
    still applies when mesh.devices is unset. 0 = all local devices."""
    from ..conf import MESH_DEVICES, SHUFFLE_MESH_SIZE

    n = conf.get(MESH_DEVICES)
    if n == 0:
        n = conf.get(SHUFFLE_MESH_SIZE)
    return n


def get_mesh(n: Optional[int] = None, conf=None) -> "jax.sharding.Mesh":
    """A 1-D mesh over the first ``n`` local devices.

    ``n`` = None/0 consults ``conf`` (``spark.rapids.tpu.mesh.devices``,
    falling back to ``shuffle.meshSize``); still unset means all local
    devices. A request exceeding the visible device count is a conf error
    named after the key, not a silent truncation. Meshes are memoized per
    (count, device identity) so every stage at the same width shares one
    Mesh object (jit caches key on mesh identity)."""
    devs = jax.devices()
    if not n and conf is not None:
        n = configured_mesh_devices(conf)
    n = n or len(devs)
    if n > len(devs):
        raise ValueError(
            f"spark.rapids.tpu.mesh.devices={n} but only {len(devs)} "
            "device(s) are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            "initializes for a virtual CPU mesh)")
    if n < 1:
        raise ValueError(f"mesh of {n} devices makes no sense")
    key = (n, tuple(id(d) for d in devs[:n]))
    m = _MESH_CACHE.get(key)
    if m is None:
        m = jax.sharding.Mesh(np.array(devs[:n]), (AXIS,))
        _MESH_CACHE[key] = m
    return m


def shard_spec() -> "jax.sharding.PartitionSpec":
    return jax.sharding.PartitionSpec(AXIS)


def row_sharding(mesh) -> "jax.sharding.NamedSharding":
    """Rows split over the shard axis (leading dim)."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(AXIS))

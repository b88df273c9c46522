"""Environment provenance: which hardware produced these numbers.

Recorded results used to carry a prose caveat ("CPU fallback, not
comparable") because nothing machine-readable recorded WHAT backend a
run measured. This helper is the one home for that record: the session
rides it on ``query_start`` events, ``/status`` serves it live, and
``tpu_profile --diff`` warns
loudly when two runs' backends or device kinds differ — numbers from
different hardware compare structure, not speed.

Memoized after the first call: ``jax.devices()`` is cheap once the
backend exists, but this is called on every query_start with events on,
and the answer cannot change within a process (jax pins its backend at
first use).
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

_CACHED: Optional[Dict[str, Any]] = None


def environment_info() -> Dict[str, Any]:
    """{backend, device_kind, device_count, jax_version, host_cores} —
    plain JSON, safe to embed in events and bench payloads."""
    global _CACHED
    if _CACHED is None:
        import jax

        devs = jax.devices()
        _CACHED = {
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind if devs else None,
            "device_count": len(devs),
            "jax_version": jax.__version__,
            "host_cores": os.cpu_count(),
        }
    return dict(_CACHED)


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at THE one place this
    repo keeps it and return that path. ``JAX_COMPILATION_CACHE_DIR``
    wins untouched (jax reads it itself; no code sets another
    directory); otherwise ``<checkout>/.jax_compile_cache`` — a FIXED
    path, because the path is part of the cache key: a directory named
    after a pid, the time or ``tempfile`` never hits. Call before the
    first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class MosaicRefused(NotImplementedError):
    """A Pallas kernel the TPU's Mosaic compiler is known to refuse was
    asked to run on the chip. Raised BY NAME instead of running anything
    else in the kernel's place."""


def pallas_interpret(kernel: str, refused: Optional[str] = None) -> bool:
    """``interpret=`` for every ``pallas_call`` in the engine: the Pallas
    interpreter on the CPU backend (tests), Mosaic on the TPU, and a
    named error anywhere else — an unknown platform must not quietly run
    the interpreter and report its timings as a kernel's. ``refused`` is
    what Mosaic said when asked to compile ``kernel`` for the chip
    (tests/test_tpu_compile.py keeps the ask as a strict xfail): while it
    is set, the kernel fails by name on 'tpu' (ROADMAP A6)."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernel {kernel} runs compiled on 'tpu' and "
            f"interpreted on 'cpu'; backend {backend!r} is neither")
    if refused:
        raise MosaicRefused(
            f"Pallas kernel {kernel} does not compile for the TPU "
            f"(Mosaic: {refused}); pick another strategy tier until "
            "ROADMAP A6 repairs it")
    return False


def describe(env: Optional[Dict[str, Any]]) -> str:
    """One operator-readable line ("backend=cpu device=TFRT_CPU x2
    jax=0.4.37") shared by /status consumers (tpu_top) and bench
    stderr."""
    if not env:
        return "backend=?"
    return (f"backend={env.get('backend')} "
            f"device={env.get('device_kind')} "
            f"x{env.get('device_count')} "
            f"jax={env.get('jax_version')}")


def environments_differ(a: Optional[Dict[str, Any]],
                        b: Optional[Dict[str, Any]]) -> bool:
    """True when two provenance blocks name different hardware (backend
    or device kind) — the condition under which absolute times and HBM
    fractions are NOT comparable. Missing blocks (pre-provenance logs)
    never differ: no evidence, no warning."""
    if not a or not b:
        return False
    return (a.get("backend") != b.get("backend")
            or a.get("device_kind") != b.get("device_kind"))

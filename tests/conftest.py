"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax initializes.

Multi-chip hardware is not available in CI; sharding/collective paths are
validated on a virtual device mesh exactly as the driver's dryrun does.

ON-TPU MODE (reference: the GPU differential suites run on the real
device, SURVEY §4 tier 2/3): setting SRTPU_TEST_TPU=1 keeps the real
backend so the differential suites validate Spark-exactness ON the chip
(f32 accumulation, x64 emulation, TPU fusion quirks) instead of only
against the CPU backend. Usage:
    SRTPU_TEST_TPU=1 python -m pytest tests/ -q -m "not cpu_only"
"""
import collections
import os
import sys

import pytest

ON_TPU = os.environ.get("SRTPU_TEST_TPU", "") == "1"

if not ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"  # whatever the shell presets
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# jax may already be imported (a sitecustomize, a plugin) before this
# conftest runs — the env var alone is then too late. The config update
# below still wins as long as no backend has been initialized.
import jax  # noqa: E402

if not ON_TPU:
    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is compile-dominated (~500 XLA
# programs); caching compiled executables across runs cuts the full-suite
# wall time (SURVEY §4 test-strategy analog of the reference's reuse of
# warmed Spark sessions across its pytest modules). The directory is
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_compile_cache —
# envinfo.use_compile_cache is the one helper that decides
# (benchmarks/run.py uses it too).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.envinfo import use_compile_cache  # noqa: E402

use_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


#: Under ``--dist loadfile`` a file is one worker's, so the run cannot end
#: before its longest file does. The compile asks are the longest by far
#: (minutes each, one compile that no cache can hold) and have the fewest
#: tests, so they are collected, and so handed out, first.
_COLLECT_FIRST = (
    "test_tpu_compile_sf100.py", "test_tpu_compile.py",
    "test_tpu_compile_mesh4.py", "test_tpu_compile_full.py",
    "test_tpu_compile_lineitem_full.py", "test_tpu_compile_merge.py",
)


def pytest_configure(config):
    # xdist hands files out by their number of tests, most first, unless
    # told not to: a compile ask is one test and would start last. The
    # order is made in pytest_collection_modifyitems instead.
    config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "cpu_only: needs the multi-device virtual CPU mesh; "
        "skipped when SRTPU_TEST_TPU=1 runs the suite on the real chip")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run "
        "(-m 'not slow'); dedicated CI jobs run these files unfiltered")


@pytest.fixture(autouse=True)
def _hbm_leak_guard():
    """Harness teardown twin of the HBM ledger's leak sentinel: any test
    whose queries left sentinel-flagged buffers live fails HERE, by
    name, instead of poisoning a later test's catalog state. Peeks only
    (no catalog is conjured for tests that never touched memory); a test
    that DELIBERATELY leaks must reset the BufferCatalog itself."""
    yield
    from spark_rapids_tpu.memory.catalog import BufferCatalog

    cat = BufferCatalog._instance
    if cat is None:
        return
    leaked = cat.ledger.stats()["leaked_live"]
    if leaked:
        leaks = cat.ledger.live_leaks()
        BufferCatalog.reset()  # don't cascade into the next test
        raise AssertionError(
            f"HBM leak sentinel: {leaked} buffer(s) outlived their "
            "owning query: " + ", ".join(
                f"{r.get('op') or '(unattributed)'} {r['bytes']}B "
                f"from {r['site']} (query {r.get('query_id')})"
                for r in leaks[:5]))


def pytest_collection_modifyitems(config, items):
    # _COLLECT_FIRST, then xdist's own order: the files with most tests
    # first. Deterministic: every xdist worker must collect the same order.
    rank = {name: i for i, name in enumerate(_COLLECT_FIRST)}
    tests_in = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: (rank.get(item.path.name, len(rank)),
                                 -tests_in[item.path]))
    if not ON_TPU:
        return
    skip = pytest.mark.skip(reason="needs 8-device CPU mesh (on-TPU run)")
    for item in items:
        if "cpu_only" in item.keywords or item.fspath.basename in (
            "test_mesh.py", "test_multichip.py", "test_shuffle.py",
        ):
            item.add_marker(skip)

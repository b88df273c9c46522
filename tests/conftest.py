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
import os
import sys

import pytest

from placement import order

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

# No persistent compilation cache, even where a test points jax at one (the
# benchmark's harness does, run in-process). In a run from a fresh checkout
# it hits only between xdist workers (a sixth of the suite's CPU seconds),
# and every store calls jaxlib 0.9.0's CPU ``serialize()``, which has
# crashed workers (SIGSEGV in test_merge_at_rows_held.py), failing the run.
jax.config.update("jax_enable_compilation_cache", False)

# the package, from this checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # under --dist loadfile xdist hands files out by their number of
    # tests, most first, unless told not to: a compile ask is one test and
    # would start last. placement.order makes the order instead.
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


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    # xdist resets a worker's own --dist and -n; it collects, so it is
    # handed the mode its tests are scheduled under
    node.workerinput["dist"] = node.config.option.dist


@pytest.hookimpl(trylast=True)  # after -m and --deselect: the count is xdist's
def pytest_collection_modifyitems(config, items):
    # the same on every xdist worker, or xdist aborts the run
    worker = getattr(config, "workerinput", {})
    place = {t: i for i, t in enumerate(order(
        [item.nodeid for item in items], worker.get("dist", "no"),
        worker.get("workercount", 1),
        config.getoption("maxschedchunk", None)))}
    items.sort(key=lambda item: place[item.nodeid])
    if not ON_TPU:
        return
    skip = pytest.mark.skip(reason="needs 8-device CPU mesh (on-TPU run)")
    for item in items:
        if "cpu_only" in item.keywords or item.fspath.basename in (
            "test_mesh.py", "test_multichip.py", "test_shuffle.py",
        ):
            item.add_marker(skip)

"""Donation-safety analyzer (tools/tpu_donate.py), the certification
table + batch-exclusivity protocol (plugin/donation.py), and the
runtime witness.

Five layers, mirroring the ISSUE 19 acceptance criteria:

  1. analyzer contract — the must-catch fixture corpus (each
     use-after-donation shape in tests/donation_fixtures/ is flagged by
     its matching rule, the safe variants are not), the repo itself is
     clean under --strict-allowlist, stale entries fail strict mode,
     TPU202 stays warn-level, and the manifest the tool reads from
     donation.py's AST matches the live DONATION_SPECS table;
  2. protocol semantics — mark_exclusive / claim / batch_donatable and
     every gate of dispatch_mask (conf off, uncertified site, shared
     batch, dict columns, snapshot-mode exclusion);
  3. guard semantics — deleted-plane accounting against a real donating
     dispatch (declined aliases count zero bytes, truthfully), plane
     restore on failure, and the witness's two typed violations
     (mask-with-no-effect, use-after-donation) plus the retry-layer
     re-typing;
  4. the differential matrix — donation on vs off bit-exact across the
     five agg strategies and five join tiers, under forced batch
     splits, with donated_bytes > 0 on every donating run and zero on
     every donation-off run;
  5. cache identity — the donate mask folds into the structural key AND
     the AOT program-cache entry: a warm same-mask run compiles nothing
     and still donates (the export probes re-declare donate_argnums),
     while flipping donation off recompiles instead of being served a
     donating executable.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64 enable)
import jax

from spark_rapids_tpu import events as EV
from spark_rapids_tpu import faults
from spark_rapids_tpu import obs
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import ColumnarBatch, schema_of
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import base as B
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.plugin import donation
from spark_rapids_tpu.serve import program_cache as PC
from spark_rapids_tpu.sql import TpuSession

from harness import compare_rows

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "tpu_donate.py")
FIXTURES = os.path.join(REPO, "tests", "donation_fixtures")

AGG_STRATEGIES = ("SCATTER", "MATMUL", "RADIX", "PALLAS")
JOIN_STRATEGIES = ("AUTO", "SEARCH", "DIRECT", "RADIX", "PALLAS")

NO_BACKOFF = {"spark.rapids.tpu.memory.oomRetry.backoffMs": 0}


@pytest.fixture(autouse=True)
def clean_planes():
    """Every test starts and ends with events/obs/faults/program-cache
    uninstalled, the witness off, and the donated-bytes counters
    zeroed (they are process-global, like the pipeline caches)."""
    EV.uninstall()
    obs.uninstall()
    faults.uninstall()
    PC.uninstall()
    donation.uninstall_witness()
    donation.reset_counters()
    yield
    EV.uninstall()
    obs.uninstall()
    faults.uninstall()
    PC.uninstall()
    donation.uninstall_witness()
    donation.reset_counters()


def _run_tool(*args):
    return subprocess.run([sys.executable, TOOL, *args],
                          capture_output=True, text=True, cwd=REPO)


def _findings(out: str):
    """(basename, rule, qualname) triples from analyzer stdout —
    warnings (TPU202) carry a 'warning: ' prefix the parser strips."""
    got = set()
    for raw in out.splitlines():
        line = raw[len("warning: "):] if raw.startswith("warning: ") \
            else raw
        if ": TPU2" not in line:
            continue
        loc, rest = line.split(": TPU", 1)
        rule = "TPU" + rest.split(" ", 1)[0]
        qual = rest.split("[", 1)[1].split("]", 1)[0]
        got.add((os.path.basename(loc.rsplit(":", 1)[0]), rule, qual))
    return got


# ---------------------------------------------------------------------------
# 1. analyzer contract
# ---------------------------------------------------------------------------
def test_fixture_corpus_must_catch():
    """Every donation hazard shape is flagged by its matching rule."""
    r = _run_tool(FIXTURES, "--allowlist=/dev/null")
    assert r.returncode == 1, r.stdout + r.stderr
    got = _findings(r.stdout)
    must_catch = {
        ("fx_use_after_donation.py", "TPU201", "read_after_guard"),
        ("fx_use_after_donation.py", "TPU201", "rows_after_guard"),
        ("fx_certified_not_donating.py", "TPU202", "build_without_mask"),
        ("fx_donation_outside_cache.py", "TPU203", "jit_donating_loose"),
        ("fx_donation_outside_cache.py", "TPU203", "pjit_donating_loose"),
    }
    missing = must_catch - got
    assert not missing, f"rules failed to catch: {missing}\n{r.stdout}"


def test_fixture_corpus_safe_variants_not_flagged():
    """The safe shapes sitting next to each hazard stay quiet — in
    particular the engine's ``if mask: with guard: ... else: ...``
    idiom, whose else arm is textually after the with but an execution
    ALTERNATIVE."""
    r = _run_tool(FIXTURES, "--allowlist=/dev/null")
    quals = {q for (_, _, q) in _findings(r.stdout)}
    for clean in ("metadata_after_guard", "else_arm_dispatch",
                  "build_with_mask", "build_uncertified",
                  "jit_donating_routed", "jit_plain"):
        assert clean not in quals, f"false positive on {clean}:\n{r.stdout}"


def test_tpu202_is_warning_level(tmp_path):
    """A certified-but-not-donating site prints a warning and exits 0 —
    the omission must be visible but can never fail the build."""
    d = tmp_path / "only202"
    d.mkdir()
    shutil.copy(os.path.join(FIXTURES, "fx_certified_not_donating.py"),
                str(d))
    r = _run_tool(str(d), "--allowlist=/dev/null")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "warning:" in r.stdout and "TPU202" in r.stdout
    assert "clean with 1 warning(s)" in r.stdout


def test_repo_clean_under_strict_allowlist():
    """The acceptance gate: zero TPU201/TPU203 and zero TPU202 warnings
    on the engine tree, no stale allowlist entries."""
    r = _run_tool("--strict-allowlist")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout
    assert "warning" not in r.stdout, r.stdout


def test_stale_allowlist_entry_fails_strict(tmp_path):
    r = _run_tool(FIXTURES, "--allowlist=/dev/null")
    keys = [f"tests/donation_fixtures/{b}::{q}::{rule}"
            for (b, rule, q) in _findings(r.stdout)]
    allow = tmp_path / "allow.txt"
    allow.write_text("\n".join(keys) + "\nbogus.py::gone::TPU201  # stale\n")
    ok = _run_tool(FIXTURES, f"--allowlist={allow}")
    assert ok.returncode == 0, ok.stdout + ok.stderr
    strict = _run_tool(FIXTURES, f"--allowlist={allow}",
                       "--strict-allowlist")
    assert strict.returncode == 1
    assert "stale allowlist entry" in strict.stderr


def _tool_module():
    spec = importlib.util.spec_from_file_location("tpu_donate", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_manifest_read_from_ast_matches_live_table():
    """The tool parses DONATION_SPECS out of donation.py's AST (it must
    run without jax); the parse must agree with the imported module on
    every site's argnums and retry contract."""
    rows = _tool_module().load_manifest()
    assert set(rows) == set(donation.DONATION_SPECS)
    for site, spec in donation.DONATION_SPECS.items():
        assert rows[site].argnums == spec.argnums, site
        assert rows[site].retry == spec.retry, site
        assert rows[site].certified == spec.certified, site
        assert spec.reason.startswith(rows[site].reason[:20]), site


def test_explain_prints_certification_table():
    r = _run_tool("--explain")
    assert r.returncode == 0, r.stderr
    for site, spec in donation.DONATION_SPECS.items():
        assert f"{site}: " in r.stdout
        verdict = "CERTIFIED" if spec.certified else "NOT CERTIFIED"
        line = next(ln for ln in r.stdout.splitlines()
                    if ln.startswith(f"{site}: "))
        assert verdict in line, line


# ---------------------------------------------------------------------------
# 2. the exclusivity protocol and dispatch_mask's gates
# ---------------------------------------------------------------------------
def _batch(n=256):
    schema = schema_of(k=T.INT, v=T.LONG)
    return ColumnarBatch.from_pydict(
        {"k": [i % 7 for i in range(n)],
         "v": [None if i % 11 == 0 else i for i in range(n)]}, schema)


def test_exclusivity_mark_claim_roundtrip():
    b = _batch()
    assert not donation.is_exclusive(b)
    assert not donation.batch_donatable(b)
    donation.mark_exclusive(b)
    assert donation.batch_donatable(b)
    donation.claim(b)  # a retainer takes shared ownership
    assert not donation.is_exclusive(b)
    assert not donation.batch_donatable(b)


def test_dict_columns_never_donatable():
    class _Col:
        is_dict = True

    class _B:
        exclusive = True
        columns = [_Col()]

    assert not donation.batch_donatable(_B())


def test_dispatch_mask_gates():
    b = donation.mark_exclusive(_batch())
    # the happy path: donation on (default), certified site, exclusive
    assert donation.dispatch_mask("project", b) == (0,)
    assert donation.dispatch_mask("fused_chain", [b]) == (0,)
    # uncertified / unknown sites never donate
    assert donation.dispatch_mask("sort", b) == ()
    assert donation.dispatch_mask("no_such_site", b) == ()
    # a shared batch poisons the whole dispatch
    assert donation.dispatch_mask("agg_plan", [b, _batch()]) == ()
    # empty batch list: nothing to donate
    assert donation.dispatch_mask("agg_plan", []) == ()
    # conf off: copy semantics everywhere
    off = RapidsConf({"spark.rapids.tpu.sql.donation.enabled": False})
    assert donation.dispatch_mask("project", b, off) == ()
    # snapshot-mode off excludes every retry-covered site (all the
    # certified sites declare retry="snapshot")
    nosnap = RapidsConf(
        {"spark.rapids.tpu.sql.donation.retrySnapshot.enabled": False})
    assert donation.dispatch_mask("project", b, nosnap) == ()


def test_session_conf_arms_witness():
    assert not donation.witness_enabled()
    TpuSession({"spark.rapids.tpu.tools.donation.witness.enabled": True})
    try:
        assert donation.witness_enabled()
    finally:
        donation.uninstall_witness()


# ---------------------------------------------------------------------------
# 3. guard semantics
# ---------------------------------------------------------------------------
def test_guard_accounts_only_deleted_planes():
    """A real donating dispatch on the CPU backend deletes the aliased
    data planes; the counters (and a per-op Metric handed in) must see
    exactly those bytes — declined aliases count zero."""
    b = donation.mark_exclusive(_batch(1024))
    planes = [c.data for c in b.columns]
    want = sum(int(a.nbytes) for a in planes)
    fn = jax.jit(lambda vals: [v + 1 for v in vals], donate_argnums=(0,))
    fn([p + 0 for p in planes])  # warm the cache outside the guard
    snap = donation.snapshot_counters()
    m = B.Metric("donatedBytes")
    with donation.guard("project", b, op="T", snapshot=False, metric=m):
        out = fn(planes)
    delta = donation.counters_since(snap)
    assert 0 < delta.get("project", 0) <= want
    assert m.value == delta["project"]
    assert m.kind == "bytes"
    # the outputs are real — donation reused the planes, not the values
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.arange(1024) % 7 + 1)


def test_guard_restores_planes_on_failure():
    """The retry contract: on a failed donating dispatch the guard puts
    the snapshotted planes back so split-and-retry re-reads the input
    it is contractually owed."""
    b = donation.mark_exclusive(_batch(64))
    before = [np.asarray(c.data) for c in b.columns]
    fn = jax.jit(lambda v: v * 2, donate_argnums=(0,))
    with pytest.raises(RuntimeError, match="boom"):
        with donation.guard("project", b, snapshot=True):
            fn(b.columns[1].data)  # really donates the plane
            raise RuntimeError("boom")
    after = [np.asarray(c.data) for c in b.columns]
    for want, got in zip(before, after):
        np.testing.assert_array_equal(want, got)


def test_witness_flags_mask_with_no_effect():
    """A donate mask the program never aliased (zero planes deleted) is
    a certification bug; the witness turns it into a typed violation."""
    donation.install_witness()
    b = donation.mark_exclusive(_batch(64))
    with pytest.raises(donation.TpuDonationViolation,
                       match="no donated plane was deleted"):
        with donation.guard("project", b, op="BadMask", snapshot=False):
            pass  # the dispatch ignored the mask entirely
    # without the witness the same dispatch is only a zero in the
    # counters — never an error
    donation.uninstall_witness()
    snap = donation.snapshot_counters()
    with donation.guard("project", b, snapshot=False):
        pass
    assert donation.counters_since(snap) == {}


def test_witness_types_use_after_donation():
    donation.install_witness()
    b = donation.mark_exclusive(_batch(64))
    with pytest.raises(donation.TpuDonationViolation) as ei:
        with donation.guard("join", b, op="ProbeOp", snapshot=False):
            raise RuntimeError(
                "INTERNAL: Array has been deleted with shape=int64[64]")
    assert ei.value.site == "join" and ei.value.op == "ProbeOp"
    assert ei.value.__cause__ is not None
    # witness off: the raw backend error passes through untyped
    donation.uninstall_witness()
    with pytest.raises(RuntimeError) as raw:
        with donation.guard("join", b, snapshot=False):
            raise RuntimeError("Array has been deleted")
    assert not isinstance(raw.value, donation.TpuDonationViolation)


def test_retry_layer_retypes_use_after_donation():
    """memory/retry.py re-types a deleted-array error crossing the
    retry boundary, attributing the op — anything else re-raises
    untouched."""
    from spark_rapids_tpu.memory import retry as R

    with pytest.raises(donation.TpuDonationViolation,
                       match="retry attempt"):
        R._raise_if_donation_uaf(
            RuntimeError("Array has been deleted"), "TpuProjectExec")
    # non-donation errors and already-typed violations pass through
    assert R._raise_if_donation_uaf(ValueError("nope"), "Op") is None
    v = donation.TpuDonationViolation("join", "Op", "already typed")
    assert R._raise_if_donation_uaf(v, "Op") is None


def test_obs_rebase_gauge_clears_all_labeled_rows():
    """bench's per-shape memory snapshot rebases the program-temp
    high-water gauge; rebase_gauge must drop every labeled row of that
    gauge and nothing else."""
    from spark_rapids_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    reg.set_gauge_max("tpu_program_temp_bytes", 100, site="a")
    reg.set_gauge_max("tpu_program_temp_bytes", 70, site="b")
    reg.inc("tpu_donated_bytes", 42, site="a")
    reg.rebase_gauge("tpu_program_temp_bytes")
    assert reg.value("tpu_program_temp_bytes", site="a") == 0
    assert reg.value("tpu_donated_bytes", site="a") == 42
    reg.set_gauge_max("tpu_program_temp_bytes", 9, site="a")
    assert reg.value("tpu_program_temp_bytes", site="a") == 9


# ---------------------------------------------------------------------------
# 4. the differential matrix: donation on == donation off, bit for bit
# ---------------------------------------------------------------------------
def _donating(extra=None):
    """Session settings for a donating run: host-resident scans make
    every upload exclusive, so certified downstream sites donate."""
    return {"spark.rapids.tpu.sql.inMemoryScan.hostResident": True,
            **(extra or {})}


def _copying(extra=None):
    return {"spark.rapids.tpu.sql.inMemoryScan.hostResident": True,
            "spark.rapids.tpu.sql.donation.enabled": False,
            **(extra or {})}


def _msort(rows):
    """Order-insensitive bit-exact comparison key (rows carry Nones)."""
    return sorted(rows, key=repr)


def _collect_with_counters(build, settings):
    sess = TpuSession(settings)
    snap = donation.snapshot_counters()
    rows = build(sess).collect()
    return rows, donation.counters_since(snap), sess


@pytest.mark.parametrize("strategy", AGG_STRATEGIES)
def test_agg_matrix_donation_differential(strategy):
    n = 900
    data = {"k": [i % 17 for i in range(n)],
            "a": [None if i % 13 == 0 else i * 3 for i in range(n)],
            "b": [i / 7.0 - 20.0 for i in range(n)]}
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)

    def build(s):
        return (s.create_dataframe(data, schema)
                .where(E.GreaterThanOrEqual(col("a"), lit(0)))
                .group_by("k")
                .agg(A.agg(A.Sum(col("a")), "sa"),
                     A.agg(A.Min(col("a")), "mn"),
                     A.agg(A.Max(col("b")), "mx"),
                     A.agg(A.Count(col("b")), "cb")))

    st = {"spark.rapids.tpu.sql.agg.strategy": strategy}
    on_rows, on_don, _ = _collect_with_counters(build, _donating(st))
    off_rows, off_don, _ = _collect_with_counters(build, _copying(st))
    # bit-exact: identical program modulo aliasing, so == not approx
    assert _msort(on_rows) == _msort(off_rows), strategy
    assert sum(on_don.values()) > 0, (strategy, on_don)
    assert off_don == {}, (strategy, off_don)


@pytest.mark.parametrize("strategy", JOIN_STRATEGIES)
def test_join_matrix_donation_differential(strategy):
    n = 700
    ldata = {"k": [i % 29 for i in range(n)],
             "a": [None if i % 19 == 0 else i for i in range(n)]}
    rdata = {"k2": [i % 11 for i in range(29)],
             "b": [i * 10 for i in range(29)]}
    lsch = schema_of(k=T.INT, a=T.LONG)
    rsch = schema_of(k2=T.INT, b=T.LONG)

    def build(s):
        return s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")],
            how="inner")

    st = {"spark.rapids.tpu.sql.join.strategy": strategy}
    on_rows, on_don, _ = _collect_with_counters(build, _donating(st))
    off_rows, off_don, _ = _collect_with_counters(build, _copying(st))
    assert _msort(on_rows) == _msort(off_rows), strategy
    assert sum(on_don.values()) > 0, (strategy, on_don)
    assert off_don == {}, (strategy, off_don)


def test_donation_under_forced_splits_agg():
    """Injected OOM forces split-and-retry through a donating dispatch:
    the guard's snapshot/restore must hand the retry bit-identical
    input planes (diffed against the CPU oracle)."""
    n = 1200
    data = {"k": [i % 13 for i in range(n)],
            "a": [None if i % 9 == 0 else i for i in range(n)]}
    schema = schema_of(k=T.INT, a=T.LONG)

    def build(s):
        return (s.create_dataframe(data, schema).group_by("k")
                .agg(A.agg(A.Sum(col("a")), "sa"),
                     A.agg(A.Count(None), "c")))

    want = build(
        TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    got, don, _ = _collect_with_counters(build, _donating({
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec>256",
        **NO_BACKOFF}))
    compare_rows(want, got)
    inj = faults.active()
    assert inj is not None and inj.fired(), \
        "fault never fired — the split path was not exercised"
    assert sum(don.values()) > 0, don


def test_donation_under_forced_splits_join():
    n = 800
    ldata = {"k": [i % 23 for i in range(n)],
             "a": [None if i % 17 == 0 else i for i in range(n)]}
    rdata = {"k2": [i % 9 for i in range(23)],
             "b": [i * 10 for i in range(23)]}
    lsch = schema_of(k=T.INT, a=T.LONG)
    rsch = schema_of(k2=T.INT, b=T.LONG)

    def build(s):
        return s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")],
            how="inner")

    want = build(
        TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    got, don, _ = _collect_with_counters(build, _donating({
        "spark.rapids.tpu.test.faults.oom":
            "TpuShuffledHashJoinExec*>256",
        **NO_BACKOFF}))
    compare_rows(want, got, ignore_order=True)
    inj = faults.active()
    assert inj is not None and inj.fired()
    assert sum(don.values()) > 0, don


def test_donation_surfaces_events_and_explain_metrics():
    """Every donating dispatch lands in the event log (site/op/bytes),
    the obs counter mapping, and the per-operator donatedBytes metric
    explain_metrics() renders."""
    n = 600
    data = {"k": [i % 7 for i in range(n)],
            "v": [i * 2 for i in range(n)]}
    schema = schema_of(k=T.INT, v=T.LONG)
    rows, don, sess = _collect_with_counters(
        lambda s: (s.create_dataframe(data, schema)
                   .where(E.GreaterThanOrEqual(col("v"), lit(10)))
                   .select(col("k"),
                           E.Alias(E.Multiply(col("v"), lit(3)), "w"))),
        _donating({"spark.rapids.tpu.eventLog.enabled": True}))
    assert len(rows) == n - 5
    assert sum(don.values()) > 0
    evs = [r for r in sess.events.records() if r["event"] == "donation"]
    assert evs, "donating dispatches emitted no donation events"
    assert sum(r["bytes"] for r in evs) == sum(don.values())
    assert all(r["site"] in donation.DONATION_SPECS for r in evs)
    assert all(r["op"] for r in evs)
    rep = sess.explain_metrics()
    assert "donatedBytes" in rep, rep


# ---------------------------------------------------------------------------
# 5. cache identity: the donate mask is part of the program's name
# ---------------------------------------------------------------------------
def _cache_conf(tmp_path, on=True, hi=2381, mult=5):
    base = {"spark.rapids.tpu.aotCache.dir": str(tmp_path / "aot"),
            "spark.rapids.tpu.sql.inMemoryScan.hostResident": True}
    if not on:
        base["spark.rapids.tpu.sql.donation.enabled"] = False
    return base


def _cache_query(sess, hi, mult):
    data = {"k": [i % 7 for i in range(hi)],
            "v": [i for i in range(hi)]}
    schema = schema_of(k=T.INT, v=T.LONG)
    df = (sess.create_dataframe(data, schema)
          .where(E.GreaterThanOrEqual(col("v"), lit(hi % 97)))
          .select(col("k"),
                  E.Alias(E.Multiply(col("v"), lit(mult)), "w"))
          .group_by("k").agg(A.agg(A.Sum(col("w")), "s")))
    return sorted(df.collect())


def test_warm_aot_zero_miss_and_donating_warm_hit(tmp_path):
    """Warm runs with the same donate mask compile nothing AND still
    donate — jax.export strips donate_argnums, so both AOT probes must
    re-declare the mask the entry key carries. A donation-off caller
    must recompile instead of being served the donating executable."""
    s1 = TpuSession(_cache_conf(tmp_path))
    r1 = _cache_query(s1, 2381, 5)
    st = PC.stats()
    assert st["puts"] >= 1, st
    # simulate the fresh process: empty in-memory pipeline caches
    B.clear_pipeline_caches()
    m0 = B.compile_miss_count()
    snap = donation.snapshot_counters()
    s2 = TpuSession(_cache_conf(tmp_path))
    r2 = _cache_query(s2, 2381, 5)
    assert r2 == r1
    assert B.compile_miss_count() == m0, \
        "warm same-mask run must not compile"
    assert sum(donation.counters_since(snap).values()) > 0, \
        "the deserialized program lost its donation mask"
    # a donation-off caller has a DIFFERENT key: never served the
    # donating entry, so it compiles (and still matches bit-for-bit)
    B.clear_pipeline_caches()
    m1 = B.compile_miss_count()
    s3 = TpuSession(_cache_conf(tmp_path, on=False))
    r3 = _cache_query(s3, 2381, 5)
    assert r3 == r1
    assert B.compile_miss_count() > m1, \
        "donation-off run was served a donating executable"


def test_warm_aot_cross_process_zero_miss(tmp_path):
    """The real cross-process acceptance run: a child process over the
    same AOT dir serves every donating program from disk — zero
    compile misses — and still reports donated bytes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    prog = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "sys.path.insert(0, %r)\n"
        "import spark_rapids_tpu\n"
        "from spark_rapids_tpu.exec import base as B\n"
        "from spark_rapids_tpu.plugin import donation\n"
        "from test_donation import _cache_conf, _cache_query\n"
        "from spark_rapids_tpu.sql import TpuSession\n"
        "import pathlib\n"
        "tmp = pathlib.Path(%r)\n"
        "sess = TpuSession(_cache_conf(tmp))\n"
        "rows = _cache_query(sess, 2381, 5)\n"
        "print(json.dumps({'misses': B.compile_miss_count(),\n"
        "                  'donated': sum(donation"
        ".snapshot_counters().values()),\n"
        "                  'nrows': len(rows)}))\n"
    ) % (REPO, os.path.join(REPO, "tests"), str(tmp_path))
    # the parent seeds the cache dir
    s1 = TpuSession(_cache_conf(tmp_path))
    r1 = _cache_query(s1, 2381, 5)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    import json
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["misses"] == 0, got
    assert got["donated"] > 0, got
    assert got["nrows"] == len(r1)


# ---------------------------------------------------------------------------
# 6. witness-on serve stress (the CI chaos gate rides this test)
# ---------------------------------------------------------------------------
def test_witness_serve_stress_zero_violations():
    """4 sessions x 4 donating queries with the runtime witness armed
    via the conf entry: every dispatch's donation really happened (the
    witness raises into the query otherwise) and results stay exact."""
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.serve import QueryScheduler, SharedPlanCache

    settings = _donating({
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.tools.donation.witness.enabled": True,
    })
    QueryScheduler.reset(RapidsConf(settings))
    SharedPlanCache.reset()
    BufferCatalog.reset(RapidsConf(settings))

    n = 1024
    data = {"k": [i % 7 for i in range(n)],
            "v": [i for i in range(n)]}
    schema = schema_of(k=T.INT, v=T.LONG)

    def q(sess, mult):
        return (sess.create_dataframe(data, schema)
                .where(E.GreaterThanOrEqual(col("v"), lit(100)))
                .select(col("k"),
                        E.Alias(E.Multiply(col("v"), lit(mult)), "w"))
                .group_by("k").agg(A.agg(A.Sum(col("w")), "s")))

    want = {m: sorted(q(TpuSession(
        {"spark.rapids.tpu.sql.enabled": False}), m).collect())
        for m in range(2, 7)}
    errors, lock = [], threading.Lock()
    snap = donation.snapshot_counters()

    def worker(ti):
        try:
            sess = TpuSession(settings)
            for qi in range(4):
                m = 2 + (ti * 4 + qi) % 5
                got = sorted(q(sess, m).collect())
                assert got == want[m]
        except Exception as e:  # pragma: no cover - the failure mode
            with lock:
                errors.append((ti, repr(e)))

    try:
        ths = [threading.Thread(target=worker, args=(ti,),
                                name=f"donation-stress-{ti}")
               for ti in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(180)
        assert not errors, errors
        assert donation.witness_enabled(), \
            "the conf entry did not arm the witness"
        assert sum(donation.counters_since(snap).values()) > 0, \
            "stress never donated — the witness gate proved nothing"
    finally:
        donation.uninstall_witness()
        QueryScheduler.reset()
        SharedPlanCache.reset()
        BufferCatalog.reset()
        EV.uninstall()
        obs.shutdown()

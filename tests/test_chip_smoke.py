"""chip_smoke.py off the chip: the ``--rehearse`` phases in-process at
``1 << 16`` rows on the CPU backend, and the refusal of a non-TPU
platform without ``--rehearse``. The chip run itself is the driver's."""
import json

import pytest

import chip_smoke


@pytest.fixture
def fresh_engine_state():
    """The smoke asserts on process-global state (scan cache, pipeline
    caches hold what earlier tests left); start and end clean."""
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache

    DeviceScanCache.reset()
    yield
    DeviceScanCache.reset()


def test_rehearsal_runs_every_phase(capsys, fresh_engine_state):
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    text = "\n".join(lines)
    assert f"CUT: {chip_smoke.REHEARSE_ROWS} rows" in text
    assert "4 row group(s)" in text
    # three runs, the third without a compile
    run3 = next(ln for ln in lines if ln.startswith("run 3:"))
    assert " 0 compile miss(es)" in run3
    # the whole plan on the device, no fallback reason, rows equal pandas
    assert "TpuHashAggregateExec" in text and "TpuFileSourceScanExec" in text
    assert "all on cpu" in text
    assert "groups equal pandas" in text
    assert "agg strategy:" in text and "native decoder served:" in text


def test_check_rows_is_exact_on_integers_and_toleranced_on_floats():
    want = [(1, 100.0, 7, 3), (2, 50.0, 9, 4)]
    assert chip_smoke.check_rows(list(reversed(want)), want, "t") == 0.0
    near = [(1, 100.0 * (1 + 1e-12), 7, 3), (2, 50.0, 9, 4)]
    assert 0 < chip_smoke.check_rows(near, want, "t") < 1e-11
    for bad in ([(1, 100.0, 8, 3), (2, 50.0, 9, 4)],      # integer sum
                [(1, 100.0, 7, 3), (2, 50.0, 9, 5)],      # count
                [(1, 100.0, 7, 3), (3, 50.0, 9, 4)],      # key
                [(1, 100.0, 7, 3)],                       # missing group
                [(1, 100.0 * (1 + 1e-4), 7, 3), (2, 50.0, 9, 4)]):
        with pytest.raises(AssertionError):
            chip_smoke.check_rows(bad, want, "t")


def test_refuses_a_non_tpu_platform_without_rehearse(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    out, err = capsys.readouterr()
    assert "'cpu'" in err and "not 'tpu'" in err
    assert '"ok"' not in out  # no result line off the chip


def test_chip_rows_never_cut_below_bench_parquet_shape():
    assert chip_smoke.MIN_CHIP_ROWS == 1 << 23
    assert chip_smoke.FULL_ROWS == 28_800_991


def test_compile_cache_is_the_env_var_else_the_checkout(monkeypatch):
    import os

    import jax

    from spark_rapids_tpu.envinfo import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/operator/dir")
    assert use_compile_cache() == "/some/operator/dir"
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    try:
        assert use_compile_cache() == os.path.join(
            checkout, ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            checkout, ".jax_compile_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

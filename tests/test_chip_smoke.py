"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The chip run itself needs a TPU; these tests keep the script's phase
functions working between chip runs, and hold the fused plane to the
interpreted one through ``Engine.run`` — a live run with free-running
asynchronous I/O, not the quiesced protocol of tests/test_fused.py.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

TINY = dict(n_slots=256, rate=2_000.0, duration=2.0, warmup=1.0, batch=64)


def test_q5_fused_matches_interpreted_through_engine_run():
    run = chip_smoke.parity("q5", chip_smoke.run_q5, 7, **TINY)
    # the tiny plane must exercise the cold paths the chip run gates on
    assert run["live_panes_peak"] > TINY["n_slots"]
    assert run["evictions"] > 0 and run["prefetch_staged"] > 0
    assert run["batches"] > 0 and run["device_misses"] > 0
    assert any("'count'" in e[2] for e in run["emits"])


def test_ysb_fused_matches_interpreted_through_engine_run():
    run = chip_smoke.parity("ysb", chip_smoke.run_ysb, 11, n_ads=5_000,
                            **TINY)
    assert run["backend_reads"] > 0 and run["evictions"] > 0
    assert len(run["emits"]) == run["lanes"]     # one enrichment per view


def test_compile_fused_reports_every_program():
    res, hlo = chip_smoke.compile_fused(64, batch=8)
    assert "fused_step" in hlo
    assert set(res) == {"fused_step[sum]", "fused_step[read]",
                        "fused_admit[64]", "gather_rows[1]",
                        "drop_slots[32]"}
    # lowered for the CPU: reference ops, no Pallas kernel
    assert all(secs > 0 and n == 0 for secs, n in res.values())


def test_main_refuses_without_a_tpu(capsys):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""                          # no phase, no result
    assert "needs a TPU" in out.err


def test_compile_cache_is_the_env_dir_or_one_fixed_checkout_dir(
        monkeypatch):
    import jax

    from repro.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        assert use_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert use_compile_cache() == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(checkout, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

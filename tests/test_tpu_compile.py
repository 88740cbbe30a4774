"""The fused hot path compiles for the TPU at deployment width.

These tests compile for a DESCRIBED v5e topology, with no chip attached:
the TPU compiler refuses what interpret mode accepts (blocks that break
the (8, 128) tiling, kernels that outgrow VMEM, programs that outgrow
HBM).  Each program is lowered as the engine calls it, so the Pallas
kernels are chosen by the platform the program is lowered for, not by
any flag.  Widths: the planes ``chip_smoke.py`` runs, the campaign
count's, and 2^20 slots.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro.core import tac_jax  # noqa: E402
from repro.kernels.tac_probe.ops import tac_probe_gather  # noqa: E402

B = chip_smoke.BATCH
# the campaign count's plane of YSB as published
# (bench/configs/ysb-campaign.json)
WINDOW_SLOTS = 1024
WIDTHS = sorted({chip_smoke.Q5_SLOTS, chip_smoke.YSB_SLOTS, WINDOW_SLOTS,
                 2 ** 20})
HBM = 16 * 2 ** 30                       # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _shapes(one_chip, W):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = tac_jax.TACState(S((1, W), jnp.int32), S((1, W), jnp.float32),
                             S((1, W, 1), jnp.float32), S((1, W), bool))
    return S, state, S((W + 1, 1, 2), jnp.float32)


def _compiled(lowered):
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert total < HBM, f"{total} bytes do not fit one chip"
    return compiled.as_text()


@pytest.mark.parametrize("W", WIDTHS)
@pytest.mark.parametrize("kind", ["sum", "max", "read"])
def test_fused_step_compiles_with_kernels(one_chip, kind, W):
    S, state, pages = _shapes(one_chip, W)
    lowered = tac_jax.fused_step.lower(
        state, pages, S((B,), jnp.int32), S((B,), jnp.float32),
        S((B, 1), jnp.float32), S((B,), bool), S((B,), bool), kind=kind)
    if kind == "sum":
        # the in-batch composition matmul must not round f32 to bf16
        dots = [ln for ln in lowered.as_text().splitlines()
                if "dot_general" in ln]
        assert dots and all("HIGHEST, HIGHEST" in ln for ln in dots)
    text = _compiled(lowered)
    # probe + gather, plus the scatter write-back unless read-only
    assert text.count("tpu_custom_call") == (2 if kind == "read" else 3)


@pytest.mark.parametrize("W", WIDTHS)
def test_fused_admit_compiles_with_kernels(one_chip, W):
    S, state, pages = _shapes(one_chip, W)
    n = 64
    text = _compiled(tac_jax.fused_admit.lower(
        state, pages, S((n,), jnp.int32), S((n,), jnp.int32),
        S((n,), jnp.float32), S((n, 1), jnp.float32), S((n,), bool),
        S((n,), bool)))
    assert text.count("tpu_custom_call") == 2      # victim gather + scatter


@pytest.mark.parametrize("W", WIDTHS)
def test_gather_rows_compiles_with_kernel(one_chip, W):
    S, _, pages = _shapes(one_chip, W)
    text = _compiled(tac_jax.gather_rows.lower(pages, S((1,), jnp.int32)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("W", WIDTHS)
def test_tac_probe_gather_compiles_with_kernels(one_chip, W):
    S, state, pages = _shapes(one_chip, W)
    text = _compiled(tac_probe_gather.lower(
        S((B,), jnp.int32), state.keys, pages))
    assert text.count("tpu_custom_call") == 2      # probe + page gather

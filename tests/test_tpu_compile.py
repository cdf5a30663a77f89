"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot show what the chip's
compiler refuses: unsupported casts, vector layouts Mosaic cannot build,
broadcasts it does not implement.  These tests hand each kernel to the v5e
compiler for a chip that is described, not attached, and check that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs, so
results are the interpret-mode tests' business.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.params import AFMTJ_PARAMS
from repro.kernels.bitline_mac import bitline_mac_pallas
from repro.kernels.fake_analog import (AUX_ROWS, fake_analog_grouped_pallas,
                                       fake_analog_mac_pallas)
from repro.kernels.llg_rk4 import VAR_ROWS, llg_rk4_pallas
from repro.kernels.xnor_gemm import xnor_gemm_pallas

CELLS = 32768                     # 64 cell tiles
M, K, N = 128, 896, 4864          # qwen2-0.5b d_model x d_ff projection


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs under /tmp
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("variant", ["deterministic", "thermal",
                                     "thermal_variation"])
def test_llg_rk4_compiles_for_v5e(one_chip, no_persistent_cache, variant):
    p, dt, n_steps = AFMTJ_PARAMS, 0.1e-12, 4096
    lanes = [((8, CELLS), jnp.float32)]
    if variant == "deterministic":
        def fn(st):
            return llg_rk4_pallas(st, p, dt, n_steps)
    else:
        lanes += [((CELLS,), jnp.uint32), ((CELLS,), jnp.float32),
                  ((CELLS,), jnp.float32)]
        if variant == "thermal_variation":
            lanes.append(((VAR_ROWS, CELLS), jnp.float32))

        def fn(st, sd, sg, bd, lp=None):
            return llg_rk4_pallas(st, p, dt, n_steps, thermal_sigma=sg,
                                  seeds=sd, step_budget=bd, chunk=64,
                                  lane_params=lp)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *lanes)


def test_fake_analog_compiles_for_v5e(one_chip, no_persistent_cache):
    def fn(v, wn, fail, aux):
        return fake_analog_mac_pallas(v, wn, fail, aux, adc_bits=8,
                                      apply_fet=True, use_fail=True)

    text = _compiled_text(fn, one_chip, ((M, K), jnp.float32),
                          ((K, N), jnp.float32), ((K, N), jnp.float32),
                          ((AUX_ROWS, N), jnp.float32))
    assert "tpu_custom_call" in text


def test_fake_analog_grouped_compiles_for_v5e(one_chip, no_persistent_cache):
    """moonlight-16b-a3b's expert up-projection on one chip's share: 16
    experts of 2048 x 1408 over the (token, choice) pairs of 8 x 512
    tokens at top-6, a traced grid bound and a scalar-prefetched map."""
    g, d, f, rows = 16, 2048, 1408, 8 * 512 * 6

    def fn(v, wn, fail, aux, sizes):
        return fake_analog_grouped_pallas(v, wn, fail, aux, sizes,
                                          adc_bits=8, use_fail=True,
                                          name="fake_analog_moe_w_up")

    text = _compiled_text(fn, one_chip, ((rows, d), jnp.float32),
                          ((g, d, f), jnp.float32), ((g, d, f), jnp.float32),
                          ((g, AUX_ROWS, f), jnp.float32),
                          ((g,), jnp.int32))
    assert "tpu_custom_call" in text and "fake_analog_moe_w_up" in text


def test_bitline_mac_compiles_for_v5e(one_chip, no_persistent_cache):
    def fn(v, g):
        return bitline_mac_pallas(v, g, adc_bits=8, i_max=1e-3)

    text = _compiled_text(fn, one_chip, ((M, K), jnp.float32),
                          ((K, N), jnp.float32))
    assert "tpu_custom_call" in text


def test_xnor_gemm_compiles_for_v5e(one_chip, no_persistent_cache):
    def fn(a, w):
        return xnor_gemm_pallas(a, w, binarize=True)

    text = _compiled_text(fn, one_chip, ((M, K), jnp.bfloat16),
                          ((K, N), jnp.bfloat16))
    assert "tpu_custom_call" in text

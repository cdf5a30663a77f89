"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # property tests skip; allclose sweeps still run
    from _hypothesis_stub import given, settings, st

from repro.core import llg
from repro.core.params import AFMTJ_PARAMS
from repro.kernels import ops, ref
from repro.kernels.llg_rk4 import CELL_TILE


def _states(cells, seed=0, vmin=0.3, vmax=1.2):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    th = jax.random.uniform(k1, (cells,), minval=0.05, maxval=0.25)
    ph = jax.random.uniform(k2, (cells,), minval=0.0, maxval=6.28)
    m0 = jax.vmap(lambda t, f: llg.initial_state(AFMTJ_PARAMS, t, f))(th, ph)
    v = jnp.linspace(vmin, vmax, cells)
    return ops.pack_states(m0, v)


@pytest.mark.parametrize("cells", [CELL_TILE, 2 * CELL_TILE])
@pytest.mark.parametrize("n_steps", [50, 400])
def test_llg_rk4_matches_ref(cells, n_steps):
    state = _states(cells)
    out_k = ops.llg_rk4(state, AFMTJ_PARAMS, 0.1e-12, n_steps)
    out_r = ref.ref_llg_rk4(state, AFMTJ_PARAMS, 0.1e-12, n_steps)
    np.testing.assert_allclose(np.asarray(out_k[:6]), np.asarray(out_r[:6]),
                               atol=2e-5)
    # switching-step rows agree exactly
    assert np.array_equal(np.asarray(out_k[7]), np.asarray(out_r[7]))


def test_llg_rk4_param_sweep():
    """Kernel must track the oracle across device-parameter variations."""
    for alpha, bes in [(0.005, 1.0), (0.02, 0.5), (0.01, 2.0)]:
        p = dataclasses.replace(AFMTJ_PARAMS, alpha=alpha,
                                b_exchange=AFMTJ_PARAMS.b_exchange * bes)
        state = _states(CELL_TILE, seed=3)
        out_k = ops.llg_rk4(state, p, 0.1e-12, 100)
        out_r = ref.ref_llg_rk4(state, p, 0.1e-12, 100)
        np.testing.assert_allclose(np.asarray(out_k[:6]), np.asarray(out_r[:6]),
                                   atol=2e-5)


def test_llg_rk4_norm_invariant():
    out = ops.llg_rk4(_states(CELL_TILE), AFMTJ_PARAMS, 0.1e-12, 200)
    n1 = np.linalg.norm(np.asarray(out[0:3]), axis=0)
    n2 = np.linalg.norm(np.asarray(out[3:6]), axis=0)
    np.testing.assert_allclose(n1, 1.0, atol=1e-5)
    np.testing.assert_allclose(n2, 1.0, atol=1e-5)


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 256)])
@pytest.mark.parametrize("adc_bits", [0, 4, 8])
def test_bitline_mac_matches_ref(shape, adc_bits):
    m, k, n = shape
    v = jax.random.uniform(jax.random.PRNGKey(0), (m, k))
    g = jax.random.uniform(jax.random.PRNGKey(1), (k, n)) * 3.4e-4
    out_k = ops.bitline_mac(v, g, adc_bits, i_max=0.05)
    out_r = ref.ref_bitline_mac(v, g, adc_bits, i_max=0.05)
    if adc_bits == 0:
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-8)
    else:
        # tiled-K accumulation can land a float-ulp away from the oracle at a
        # quantizer bin edge: allow <=1 LSB there, on <1% of elements
        lsb = 0.05 / (2 ** (adc_bits - 1) - 1)
        diff = np.abs(np.asarray(out_k) - np.asarray(out_r))
        assert diff.max() <= lsb * 1.001, diff.max()
        assert (diff > lsb * 1e-3).mean() < 0.01


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 128, 128), (128, 512, 256)])
def test_xnor_gemm_matches_ref(shape, dtype):
    m, k, n = shape
    a = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (m, k))).astype(dtype)
    w = jnp.sign(jax.random.normal(jax.random.PRNGKey(3), (k, n))).astype(dtype)
    out_k = ops.xnor_gemm(a, w)
    out_r = ref.ref_xnor_gemm(a, w)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=0)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_xnor_popcount_identity(seed):
    """Property: pm1 dot == K - 2*popcount(xor) for random bit matrices."""
    rng = np.random.default_rng(seed)
    a_bits = rng.integers(0, 2, (16, 64))
    w_bits = rng.integers(0, 2, (16, 64))
    pm = lambda b: (2 * b - 1).astype(np.float32)
    expect = pm(a_bits) @ pm(w_bits).T
    got = ref.ref_xnor_popcount(jnp.asarray(a_bits), jnp.asarray(w_bits.T))
    np.testing.assert_allclose(np.asarray(got), expect)


def test_pack_unpack_roundtrip():
    m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.1))(
        jnp.linspace(0.01, 0.3, 100))
    v = jnp.linspace(0.2, 1.0, 100)
    state = ops.pack_states(m0, v)
    assert state.shape == (8, CELL_TILE)    # padded to CELL_TILE
    m, cross = ops.unpack_states(state, 100)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m0), atol=1e-6)

"""Functional analog read path: signed ADC, shape padding, tie conventions,
differential programming exactness, nonideality ordering, and the BNN
density accounting."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.params import CORNER_SS, VariationSpec
from repro.imc.analog_pipeline import (AnalogConfig, analog_matmul,
                                       binary_matmul, mvm_accuracy,
                                       program_weights)
from repro.kernels import ops, ref

REPO = Path(__file__).resolve().parents[1]


# --- satellite: signed ADC ---------------------------------------------------

def test_adc_preserves_negative_currents():
    """Regression for the clip(0,1) bug: signed bit-line currents must pass
    the ADC with a non-zero negative contribution."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    v = jax.random.normal(k1, (16, 128))            # signed drives
    g = jax.random.normal(k2, (128, 32)) * 1e-4     # signed differential G
    ideal = np.asarray(v @ g)
    out = np.asarray(ops.bitline_mac(v, g, adc_bits=6, i_max=2e-3))
    assert (out < 0).any(), "ADC zeroed every negative current"
    # negative entries must track the ideal sign, not be clipped to zero
    neg = ideal < -1e-4
    assert neg.any()
    assert np.mean(np.sign(out[neg]) == -1) > 0.99
    # and agree with the jnp oracle
    np.testing.assert_allclose(out, np.asarray(
        ref.ref_bitline_mac(v, g, adc_bits=6, i_max=2e-3)),
        rtol=1e-5, atol=2e-3 / 31 * 1.001)


def test_adc_symmetric_transfer():
    """Quantizer is odd: q(-i) == -q(i) (symmetric full scale, no 0/1 bias)."""
    from repro.kernels.bitline_mac import adc_quantize

    i = jnp.linspace(0.0, 2.0, 201)
    np.testing.assert_allclose(np.asarray(adc_quantize(-i, 5, 1.0)),
                               -np.asarray(adc_quantize(i, 5, 1.0)), atol=0)
    q = adc_quantize(jnp.asarray([-5.0, 5.0]), 5, 1.0)
    assert float(q[0]) == -1.0 and float(q[1]) == 1.0


# --- padding: non-128-multiple shapes ---------------------------------------

@pytest.mark.parametrize("shape", [(3, 200, 77), (65, 130, 190), (1, 1, 1),
                                   (129, 127, 128)])
def test_bitline_mac_padded_parity(shape):
    m, k, n = shape
    v = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    g = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 3.4e-4
    out_k = np.asarray(ops.bitline_mac(v, g))
    out_r = np.asarray(ref.ref_bitline_mac(v, g))
    assert out_k.shape == (m, n)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("shape", [(3, 200, 77), (130, 190, 65)])
def test_xnor_gemm_padded_parity(shape):
    m, k, n = shape
    a = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (m, k)))
    w = jnp.sign(jax.random.normal(jax.random.PRNGKey(3), (k, n)))
    out_k = np.asarray(ops.xnor_gemm(a, w, binarize=True))
    out_r = np.asarray(ref.ref_xnor_gemm(a, w, binarize=True))
    assert out_k.shape == (m, n)
    np.testing.assert_allclose(out_k, out_r, atol=0)


# --- satellite: XNOR tie convention -----------------------------------------

@pytest.mark.parametrize("tie", [1, -1])
def test_xnor_binarize_tie(tie):
    """Even-K exact ties must land on the requested side, kernel == oracle."""
    k = 128                                   # even: a @ w can be exactly 0
    a = jnp.concatenate([jnp.ones((8, k // 2)), -jnp.ones((8, k // 2))], 1)
    w = jnp.ones((k, 16))                     # every output is an exact tie
    out_k = np.asarray(ops.xnor_gemm(a, w, binarize=True, tie=tie))
    out_r = np.asarray(ref.ref_xnor_gemm(a, w, binarize=True, tie=tie))
    assert (out_k == tie).all(), out_k
    np.testing.assert_allclose(out_k, out_r, atol=0)


def test_xnor_default_tie_matches_seed_convention():
    """Default tie=+1 keeps the seed's ``acc >= 0 -> +1`` behavior."""
    a = jnp.asarray([[1.0, -1.0]])
    w = jnp.asarray([[1.0], [1.0]])
    assert float(ops.xnor_gemm(a, w, binarize=True)[0, 0]) == 1.0


# --- tentpole: differential programming + analog MVM -------------------------

def _wx(k=200, n=150, m=7, seed=0):
    kw, kx = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kw, (k, n)) / k**0.5,
            jax.random.normal(kx, (m, k)))


def test_ideal_path_is_exact():
    """No ADC, no IR drop, no variation: the differential encoding + decode
    chain must reproduce x @ w to float tolerance (odd shapes included)."""
    w, x = _wx()
    arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=0, ir_drop=False))
    y = np.asarray(analog_matmul(arr, x))
    y_ref = np.asarray(x @ w)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5,
                               atol=2e-5 * np.abs(y_ref).max())


def test_programming_is_differential_and_physical():
    """Per-cell conductances stay within the device span; negative weights
    live on the negative cell (g_diff < 0 there)."""
    w, _ = _wx()
    arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=0, ir_drop=False))
    sign_match = np.sign(np.asarray(arr.g_diff)) == np.sign(np.asarray(w))
    assert sign_match.mean() > 0.999
    assert np.abs(np.asarray(arr.g_diff)).max() <= arr.g_fs * (1 + 1e-6)


def test_signed_activations_through_fixed_adc():
    """Acceptance: signed activations pass the fixed ADC with a verified
    non-zero negative-current contribution in the *quantized* output."""
    w, x = _wx()
    arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=6, ir_drop=False))
    y = np.asarray(analog_matmul(arr, x))
    y_ref = np.asarray(x @ w)
    assert (y < 0).sum() > 0.3 * y.size          # negatives survive the ADC
    assert np.corrcoef(y.ravel(), y_ref.ravel())[0, 1] > 0.99


def test_adc_bits_monotonic():
    w, x = _wx()
    nmse = {b: mvm_accuracy(w, x, cfg=AnalogConfig(adc_bits=b)).nmse
            for b in (4, 6, 8)}
    assert nmse[4] > nmse[6] > nmse[8], nmse


def test_higher_tmr_tolerates_variation_better():
    """At fixed D2D variation the wider conductance span (higher TMR) must
    give a lower relative error — the paper's TMR-matters claim."""
    from repro.core.params import VariationSpec

    w, x = _wx()
    var = VariationSpec.from_g_sigma(0.05)     # DESIGN.md §9 D2D spec
    lo = mvm_accuracy(w, x, cfg=AnalogConfig(adc_bits=8, tmr=0.8,
                                             variation=var))
    hi = mvm_accuracy(w, x, cfg=AnalogConfig(adc_bits=8, tmr=5.0,
                                             variation=var))
    assert hi.nmse < lo.nmse / 2, (lo.nmse, hi.nmse)


def test_ir_drop_is_column_gain_error():
    """IR drop on its own (no ADC/variation) leaves a small per-column gain
    spread after mean calibration — bounded, not catastrophic."""
    w, x = _wx()
    arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=0, ir_drop=True))
    assert arr.att_mean < 1.0
    r = mvm_accuracy(w, x, cfg=AnalogConfig(adc_bits=0, ir_drop=True))
    assert r.nmse < 0.05 and r.cosine > 0.97, (r.nmse, r.cosine)


@pytest.mark.parametrize("path", ["analog", "bnn"])
def test_mvm_kernel_matches_oracle_on_its_operands(path):
    """At a non-128-multiple shape (the padding path), each read kernel
    equals its jnp oracle on the exact operands the pipeline feeds it:
    the bit-line MAC behind ``analog_matmul`` (6-bit ADC, within one ADC
    level) and the XNOR popcount behind ``binary_matmul`` (exact)."""
    from repro.imc.analog_pipeline import kernel_operands
    from repro.kernels.xnor_gemm import binarize_acc

    m, k, n = 48, 200, 144
    kw, kx = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(kw, (k, n), jnp.float32) / (k ** 0.5)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    if path == "analog":
        arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=6))
        v, i_max, _ = kernel_operands(arr, x)
        np.testing.assert_allclose(
            np.asarray(ops.bitline_mac(v, arr.g_diff, 6, i_max=i_max)),
            np.asarray(ref.ref_bitline_mac(v, arr.g_diff, 6, i_max=i_max)),
            rtol=1e-5, atol=i_max / 31 * 1.001)
    else:
        xb, wb = binarize_acc(x, 1), binarize_acc(w, 1)
        np.testing.assert_array_equal(np.asarray(ops.xnor_gemm(xb, wb)),
                                      np.asarray(ref.ref_xnor_gemm(xb, wb)))


def test_bnn_mode_correlates():
    w, x = _wx()
    y = np.asarray(binary_matmul(x, w))
    y_ref = np.asarray(x @ w)
    assert np.corrcoef(y.ravel(), y_ref.ravel())[0, 1] > 0.5


# --- tentpole: fused fake-analog path vs the device path ---------------------
# The fake kernel replays programming inside the matmul tiles (DESIGN.md
# §12); these pins keep it numerically indistinguishable from the
# program_weights -> kernel_operands -> analog_matmul chain.

def _device_fake_pair(w, x, cfg, **fake_kw):
    """(device output, fake output, i_max) with the device path's exact ADC
    full scale fed to the fake kernel — isolates cell math from the
    decimal-vs-binary 2-significant-digit rounding."""
    from repro.imc.analog_pipeline import kernel_operands
    from repro.imc.model_analog import fake_analog_matmul

    arr = program_weights(w, "afmtj", cfg)
    _, i_max, _ = kernel_operands(arr, x)
    y_dev = np.asarray(analog_matmul(arr, x))
    y_fake = np.asarray(fake_analog_matmul(w, x, cfg=cfg, i_max=i_max,
                                           **fake_kw))
    return y_dev, y_fake, i_max


@pytest.mark.parametrize("shape", [(5, 200, 77), (3, 130, 190)])
@pytest.mark.parametrize("bits", [4, 6, 8])
def test_fake_analog_parity(shape, bits):
    """Odd shapes x ADC resolutions: decoded outputs agree to f32-vs-f64
    decode rounding (the only remaining difference in the chain)."""
    m, k, n = shape
    w, x = _wx(k=k, n=n, m=m, seed=bits)
    y_dev, y_fake, _ = _device_fake_pair(w, x, AnalogConfig(adc_bits=bits))
    np.testing.assert_allclose(y_fake, y_dev, rtol=1e-5,
                               atol=1e-5 * np.abs(y_dev).max())


def test_fake_analog_default_fullscale_parity():
    """With the fake path sizing its own ADC full scale (traceable
    2-significant-digit rounding vs the device's string round-trip) the
    decoded outputs still agree tightly on random data."""
    from repro.imc.model_analog import fake_analog_matmul

    w, x = _wx()
    cfg = AnalogConfig(adc_bits=6)
    y_dev = np.asarray(analog_matmul(program_weights(w, "afmtj", cfg), x))
    y_fake = np.asarray(fake_analog_matmul(w, x, cfg=cfg))
    np.testing.assert_allclose(y_fake, y_dev, rtol=1e-4,
                               atol=1e-4 * np.abs(y_dev).max())


def test_fake_analog_raw_currents_bit_equal():
    """Acceptance pin: at zero IR drop with a shared ADC full scale the
    *quantized bit-line currents* are bit-equal between the two paths."""
    from repro.imc.analog_pipeline import kernel_operands
    from repro.imc.model_analog import fake_analog_matmul

    w, x = _wx()
    cfg = AnalogConfig(adc_bits=6, ir_drop=False)
    arr = program_weights(w, "afmtj", cfg)
    v, i_max, _ = kernel_operands(arr, x)
    i_dev = np.asarray(ops.bitline_mac(v, arr.g_diff, 6, i_max=i_max))
    i_fake = np.asarray(fake_analog_matmul(w, x, cfg=cfg, i_max=i_max,
                                           decode=False))
    assert np.array_equal(i_fake, i_dev)


def test_fake_analog_signed_currents():
    """Signed activations keep their negative contributions through the
    fused quantize -> decode chain."""
    from repro.imc.model_analog import fake_analog_matmul

    w, x = _wx()
    y = np.asarray(fake_analog_matmul(w, x, cfg=AnalogConfig(adc_bits=6)))
    y_ref = np.asarray(x @ w)
    assert (y < 0).sum() > 0.3 * y.size
    assert np.corrcoef(y.ravel(), y_ref.ravel())[0, 1] > 0.99


def test_fake_analog_write_ber_parity():
    """Residual write faults draw the identical Bernoulli stream on both
    paths (same fold_in salt), so faulty cells land identically."""
    w, x = _wx(k=130, n=100, m=5)
    cfg = AnalogConfig(adc_bits=6, write_ber=0.02, seed=3)
    y_dev, y_fake, _ = _device_fake_pair(w, x, cfg)
    np.testing.assert_allclose(y_fake, y_dev, rtol=1e-5,
                               atol=1e-5 * np.abs(y_dev).max())


@pytest.mark.parametrize("corner", ["ss", "ff"])
def test_fake_analog_corner_parity(corner):
    """Systematic process corners round-trip through the access FET exactly
    as the device path's lane factors do."""
    from repro.core.params import PROCESS_CORNERS, VariationSpec

    w, x = _wx(k=130, n=100, m=5, seed=7)
    cfg = AnalogConfig(adc_bits=6, variation=VariationSpec(
        corners=(PROCESS_CORNERS[corner],)))
    y_dev, y_fake, _ = _device_fake_pair(w, x, cfg)
    np.testing.assert_allclose(y_fake, y_dev, rtol=1e-5,
                               atol=1e-5 * np.abs(y_dev).max())


def test_fake_analog_d2d_raises():
    """Per-cell D2D spreads are device-path-only; the fake path must refuse
    rather than silently drop the variation."""
    from repro.core.params import VariationSpec
    from repro.imc.model_analog import fake_analog_matmul

    w, x = _wx(k=64, n=32, m=2)
    cfg = AnalogConfig(adc_bits=6,
                       variation=VariationSpec.from_g_sigma(0.05))
    with pytest.raises(NotImplementedError):
        fake_analog_matmul(w, x, cfg=cfg)


def test_fake_kernel_matches_oracle():
    """Kernel vs jnp oracle on raw operands (odd shape, FET + fail planes
    active): the Pallas tile replay equals the whole-array reference."""
    from repro.kernels.fake_analog import (AUX_ROWS, ROW_ATT_NEG, ROW_ATT_POS,
                                           ROW_DECODE, ROW_G_AP, ROW_G_FS,
                                           ROW_G_SCALE, ROW_I_MAX,
                                           ROW_R_ACCESS, fake_analog_mac_pallas)

    m, k, n = 5, 150, 70
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    v = jax.random.normal(ks[0], (m, k)) * 0.1
    wn = jnp.tanh(jax.random.normal(ks[1], (k, n)))
    fail = jax.random.randint(ks[2], (k, n), 0, 4).astype(jnp.float32)
    att = 0.9 + 0.1 * jax.random.uniform(ks[3], (2, n))
    aux = jnp.zeros((AUX_ROWS, n), jnp.float32)
    aux = aux.at[ROW_ATT_POS].set(att[0]).at[ROW_ATT_NEG].set(att[1])
    aux = aux.at[ROW_I_MAX].set(2e-3).at[ROW_DECODE].set(1234.5)
    aux = aux.at[ROW_G_AP].set(2e-4).at[ROW_G_FS].set(3e-4)
    aux = aux.at[ROW_G_SCALE].set(1.05).at[ROW_R_ACCESS].set(1e3)
    kw = dict(adc_bits=5, apply_fet=True, use_fail=True)
    out_k = np.asarray(fake_analog_mac_pallas(v, wn, fail, aux,
                                              interpret=True, **kw))
    out_r = np.asarray(ref.ref_fake_analog(v, wn, fail, aux, **kw))
    assert out_k.shape == (m, n)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-6, atol=1e-6 * 1234.5)


@pytest.mark.parametrize("cfg", [
    AnalogConfig(adc_bits=8),
    AnalogConfig(adc_bits=5, write_ber=0.02,
                 variation=VariationSpec(corners=(CORNER_SS,))),
], ids=["adc8", "adc5-ber-ss"])
def test_fake_matmul_matches_oracle_on_its_operands(cfg):
    """``fake_kernel_operands`` hands the oracle exactly what
    ``fake_analog_matmul`` feeds the kernel (the chip smoke run's
    projection check): the two agree to within one ADC level, on a few
    outputs at most."""
    from repro.imc.model_analog import fake_analog_matmul, fake_kernel_operands
    from repro.kernels.fake_analog import ROW_DECODE, ROW_I_MAX

    kx, kw = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (6, 150))
    w = jax.random.normal(kw, (150, 70)) * 0.05
    out = np.asarray(fake_analog_matmul(w, x, cfg=cfg))
    operands, flags = fake_kernel_operands(w, x, cfg=cfg)
    assert flags["adc_bits"] == cfg.adc_bits
    assert flags["use_fail"] == (cfg.write_ber > 0.0)
    want = np.asarray(ref.ref_fake_analog(*operands, **flags))
    aux = np.asarray(operands[3])
    lsb = aux[ROW_I_MAX] * aux[ROW_DECODE] / (2 ** (cfg.adc_bits - 1) - 1)
    levels = np.rint(np.abs(out - want) / lsb)
    assert levels.max() <= 1 and (levels > 0).mean() <= 0.01


# --- mapping wiring ----------------------------------------------------------

def test_accuracy_surface_shape():
    from repro.imc.mapping import accuracy_surface

    surf = accuracy_surface(ARCHS["qwen2-0.5b"], adc_bits=(4, 8), tmrs=(0.8,),
                            cap_k=128, cap_n=64, batch=4)
    assert set(surf) == {(4, 0.8), (8, 0.8)}
    for r in surf.values():
        assert r.arch == "qwen2-0.5b" and 0.0 < r.cosine <= 1.0


def test_bnn_tiles_8x_fewer():
    """Satellite: 8-bit weights occupy 8 cells, binarized 1 — the BNN map
    must use exactly 8x fewer crossbar tiles."""
    from repro.imc.hierarchy import build_hierarchy
    from repro.imc.mapping import map_arch_decode

    hier = build_hierarchy("afmtj")
    for name in ("qwen2-0.5b", "gemma2-2b"):
        r = map_arch_decode(ARCHS[name], hier)
        assert r.tiles == pytest.approx(8.0 * r.tiles_bnn)
        assert r.t_imc_bnn < r.t_imc        # denser + ADC-free => faster


# --- sharded batch axis ------------------------------------------------------

def test_sharded_mvm_matches_single_device():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.imc.analog_pipeline import AnalogConfig, program_weights, analog_matmul
kw, kx = jax.random.split(jax.random.PRNGKey(0))
w = jax.random.normal(kw, (200, 150)) / 200**0.5
x = jax.random.normal(kx, (7, 200))          # odd batch: pad + shard
arr = program_weights(w, "afmtj", AnalogConfig(adc_bits=6))
y4 = np.asarray(analog_matmul(arr, x, devices=4))
y1 = np.asarray(analog_matmul(arr, x, devices=1))
print("SHARDED_OK", np.allclose(y4, y1, rtol=1e-5, atol=1e-7))
"""
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")},
                       capture_output=True, text=True, timeout=300)
    assert "SHARDED_OK True" in r.stdout, r.stderr[-2000:]


# --- satellite: 3-row logic energy -------------------------------------------

def test_logic3_energy_exceeds_logic2():
    """3-row majority conducts through three cells: its per-bit energy must
    exceed the 2-row ops', and by less than the naive 2x."""
    from repro.circuit.subarray import make_subarray

    for kind in ("afmtj", "mtj"):
        tm = make_subarray(kind, rows=8, cols=4).timings
        assert tm.e_logic3_bit > tm.e_logic_bit, kind
        assert tm.e_logic3_bit < 2.0 * tm.e_logic_bit, kind

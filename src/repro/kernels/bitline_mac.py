"""Pallas TPU kernel: analog bit-line MAC (in-memory GEMV/GEMM) with ADC.

Functional model of the paper's multi-row charge-sharing compute: activated
word-lines drive read voltages V (batch, rows);每 column's bit-line sums the
cell currents I = V @ G (G = per-cell conductance from the stored bit and
the device TMR); a flash ADC quantizes the analog column current.

ADC transfer function: a *signed* symmetric mid-tread quantizer.  With the
differential 2-cell weight encoding (``imc.analog_pipeline``) the sense node
sees I+ - I-, which is negative for negative partial sums, so the full scale
is [-i_max, +i_max] with 2^(bits-1)-1 levels per side (one code is shared by
+-0).  Currents beyond the full scale clip — choosing ``i_max`` is part of
the read-driver co-design (see DESIGN.md §6).

Shaped as a tiled MXU matmul with an epilogue:
  grid (M/BM, N/BN, K/BK); f32 VMEM accumulator scratch; on the last K step
  the accumulator passes through the ADC model (clip + uniform quantize)
  and is written out.  BM=BN=BK=128 keeps the MXU dims hardware-aligned;
  non-128-multiple operands are zero-padded (zero voltage drives no current,
  so padding is exact) and the result is sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BM = BN = BK = 128
# Bit-line currents are f32 sums.  An f32 dot on a TPU defaults to one bf16
# pass (inside Mosaic as in XLA), which rounds every conductance to 8
# mantissa bits; HIGHEST keeps f32.  The CPU computes f32 either way.
F32_DOT = jax.lax.Precision.HIGHEST


def adc_quantize(i_bl: jnp.ndarray, adc_bits: int, i_max: float) -> jnp.ndarray:
    """Signed symmetric mid-tread ADC: clip to [-i_max, i_max], quantize to
    2^(bits-1)-1 uniform levels per side.  Shared by the kernel epilogue and
    the jnp oracle (``ref.ref_bitline_mac``) so they cannot drift."""
    if adc_bits <= 0:
        return i_bl
    assert adc_bits >= 2, f"signed ADC needs >= 2 bits, got {adc_bits}"
    half = float(2 ** (adc_bits - 1) - 1)
    x = jnp.clip(i_bl / i_max, -1.0, 1.0)
    return jnp.round(x * half) / half * i_max


def _pad2(x: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    pm, pn = -x.shape[0] % m, -x.shape[1] % n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _mac_kernel(v_ref, g_ref, o_ref, acc_ref, *, nk: int, adc_bits: int,
                i_max: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        v_ref[...], g_ref[...], precision=F32_DOT,
        preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        i_bl = adc_quantize(acc_ref[...], adc_bits, i_max)
        o_ref[...] = i_bl.astype(o_ref.dtype)


def bitline_mac_pallas(
    v: jnp.ndarray,               # (M, K) read voltages (batch x rows)
    g: jnp.ndarray,               # (K, N) cell conductances (rows x cols)
    adc_bits: int = 0,            # 0 = ideal (no quantization)
    i_max: float = 1.0,           # ADC full-scale current (per side)
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = v.shape
    K2, N = g.shape
    assert K == K2, (v.shape, g.shape)
    assert adc_bits == 0 or adc_bits >= 2, adc_bits
    from jax.experimental.pallas import tpu as pltpu

    v = _pad2(v, BM, BK)
    g = _pad2(g, BK, BN)
    mp, kp = v.shape
    _, np_ = g.shape
    nk = kp // BK
    kern = functools.partial(_mac_kernel, nk=nk, adc_bits=adc_bits, i_max=i_max)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        grid=(mp // BM, np_ // BN, nk),
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
        name="bitline_mac",
    )(v, g)
    if (mp, np_) != (M, N):
        out = out[:M, :N]
    return out

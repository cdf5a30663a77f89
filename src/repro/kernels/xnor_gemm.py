"""Pallas TPU kernel: XNOR-popcount GEMM (the paper's *bnn* workload).

out[m, n] = sum_k xnor(a[m,k], w[k,n]) counted over +-1 encodings
          = K - 2 * popcount(a XOR w)  ==  dot(a_pm1, w_pm1)

The +-1 dot-product identity lets the MXU do the popcount: inputs are +-1
(stored bf16), the accumulator is f32, and the epilogue optionally
re-binarizes (sign) — exactly the functional behavior of the AFMTJ
XNOR array + popcount tree modeled in repro.imc.

Tie convention: with even K the popcount can land exactly on zero, and the
sense amp must break the tie one way.  ``tie`` (+1 default, matching the
seed's ``acc >= 0 -> +1``) selects the output for acc == 0; it is threaded
through the jnp oracle (``ref.ref_xnor_gemm``) so kernel and reference agree
bit-for-bit at ties.

Non-128-multiple operands are zero-padded (a 0 contributes nothing to the
+-1 dot product) and the result is sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitline_mac import _pad2

BM = BN = BK = 128


def binarize_acc(acc: jnp.ndarray, tie: int) -> jnp.ndarray:
    """Sign with an explicit tie convention for acc == 0 (shared with ref)."""
    sign = jnp.where(acc > 0.0, 1.0, -1.0)
    return jnp.where(acc == 0.0, float(tie), sign)


def _xnor_kernel(a_ref, w_ref, o_ref, acc_ref, *, nk: int, binarize: bool,
                 tie: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if binarize:
            acc = binarize_acc(acc, tie)
        o_ref[...] = acc.astype(o_ref.dtype)


def xnor_gemm_pallas(
    a: jnp.ndarray,               # (M, K) in {-1, +1}
    w: jnp.ndarray,               # (K, N) in {-1, +1}
    binarize: bool = False,
    tie: int = 1,                 # sign assigned to an exact popcount tie
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = a.shape
    K2, N = w.shape
    assert K == K2, (a.shape, w.shape)
    assert tie in (1, -1), tie
    from jax.experimental.pallas import tpu as pltpu

    a = _pad2(a, BM, BK)
    w = _pad2(w, BK, BN)
    mp, kp = a.shape
    _, np_ = w.shape
    nk = kp // BK
    kern = functools.partial(_xnor_kernel, nk=nk, binarize=binarize, tie=tie)
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
        name="xnor_gemm",
    )(a, w)
    if (mp, np_) != (M, N):
        out = out[:M, :N]
    return out

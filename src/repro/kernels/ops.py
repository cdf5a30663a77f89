"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile through Mosaic.  On any other backend
``interpret`` defaults to True, so the CPU test suite runs the same kernels
under the Pallas interpreter and checks them against ref.py; what the
chip's compiler accepts is checked by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import DeviceParams
from repro.kernels.bitline_mac import bitline_mac_pallas
from repro.kernels.llg_rk4 import CELL_TILE, ROWS, llg_rk4_pallas
from repro.kernels.xnor_gemm import xnor_gemm_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# p is static: the kernel closes over the device constants at compile time
@functools.partial(jax.jit, static_argnames=("p", "dt", "n_steps", "switch_threshold"))
def llg_rk4(state, p: DeviceParams, dt: float, n_steps: int,
            switch_threshold: float = 0.9):
    """Advance a (8, cells) state block n_steps; see llg_rk4.py for layout."""
    return llg_rk4_pallas(state, p, dt, n_steps, switch_threshold,
                          interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=(
    "p", "dt", "n_steps", "switch_threshold", "chunk"))
def llg_rk4_thermal(state, seeds, p: DeviceParams, dt: float, n_steps: int,
                    thermal_sigma, switch_threshold: float = 0.9,
                    step_budget=None, chunk: int = 0, lane_params=None):
    """Thermal (Langevin) variant: per-cell counter-RNG streams in ``seeds``
    ((cells,) uint32, see kernels/noise.cell_seeds).  Brown's sigma is
    *traced data* — a scalar or a (cells,) per-lane row — so campaigns
    spanning several temperatures (or write-verify retry rounds at any
    seed) share one compile.  ``step_budget`` (traced, per-lane) caps each
    lane's horizon below the compiled ``n_steps``; ``chunk > 0`` (static)
    turns on chunked early exit — see kernels/llg_rk4.py.  ``lane_params``
    ((3, cells) f32: alpha, B_k, g_scale — also traced) switches on the
    per-lane device-variation plane (DESIGN.md §9)."""
    return llg_rk4_pallas(state, p, dt, n_steps, switch_threshold,
                          interpret=_default_interpret(),
                          thermal_sigma=thermal_sigma, seeds=seeds,
                          step_budget=step_budget, chunk=chunk,
                          lane_params=lane_params)


def pack_states(m0: jnp.ndarray, voltages: jnp.ndarray) -> jnp.ndarray:
    """(cells, 2, 3) initial states + (cells,) drives -> (8, cells) SoA."""
    assert m0.ndim == 3 and m0.shape[1] == 2, (
        f"SoA layout is dual-sublattice (AFMTJ) only, got {m0.shape}; "
        "single-sublattice (FM/MTJ) states pack via repro.campaign.grid."
        "pack_soa and ride the engine's scan tile instead of this kernel")
    cells = m0.shape[0]
    pad = (-cells) % CELL_TILE
    m0 = jnp.pad(m0, ((0, pad), (0, 0), (0, 0)))
    voltages = jnp.pad(voltages, (0, pad))
    rows = [m0[:, 0, 0], m0[:, 0, 1], m0[:, 0, 2],
            m0[:, 1, 0], m0[:, 1, 1], m0[:, 1, 2],
            voltages, jnp.zeros_like(voltages)]
    return jnp.stack(rows).astype(jnp.float32)


def unpack_states(state: jnp.ndarray, cells: int):
    m = jnp.stack([state[0:3, :cells].T, state[3:6, :cells].T], axis=1)
    crossing_step = state[7, :cells]
    return m, crossing_step


@functools.partial(jax.jit, static_argnames=("adc_bits", "i_max"))
def bitline_mac(v, g, adc_bits: int = 0, i_max: float = 1.0):
    return bitline_mac_pallas(v, g, adc_bits, i_max,
                              interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("binarize", "tie"))
def xnor_gemm(a, w, binarize: bool = False, tie: int = 1):
    return xnor_gemm_pallas(a, w, binarize, tie=tie,
                            interpret=_default_interpret())

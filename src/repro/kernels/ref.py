"""Pure-jnp oracles for every kernel (the allclose targets in tests/).

``ref_llg_rk4`` reuses the *production* physics from ``repro.core`` — the
kernel must agree with the same code the device layer runs, not a private
re-implementation.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import llg, tmr
from repro.core.integrator import rk4_step
from repro.core.params import DeviceParams
from repro.kernels import noise


def ref_llg_rk4(
    state: jnp.ndarray,           # (8, cells) SoA layout (see llg_rk4.py)
    p: DeviceParams,
    dt: float,
    n_steps: int,
    switch_threshold: float = 0.9,
    thermal_sigma=0.0,            # scalar or (cells,) per-lane Brown sigma
    seeds: jnp.ndarray | None = None,   # (cells,) uint32 per-lane streams
    step_budget=None,             # optional (cells,) f32 per-lane step budget
    chunk: int = 0,               # >0: early-exit chunk size (steps)
    lane_params=None,             # optional (3, cells) f32 variation rows:
                                  # alpha, B_k [T], g_scale (DESIGN.md §9)
) -> jnp.ndarray:
    """Both device families: ``p.n_sublattices`` picks dual-sublattice
    (AFMTJ — the Pallas kernel's allclose target) or single-sublattice
    (FM/MTJ — the campaign engine's production tile; rows 3:6 stay zero
    and only the first thermal triple of each per-lane counter is drawn,
    so padded lanes and RNG streams behave identically across kinds).

    Mirrors the kernel's campaign contract (same chunked early exit, same
    per-lane sigma/budget semantics): a lane past ``step_budget`` is frozen
    and records no crossings; with ``chunk > 0`` the whole block exits as
    soon as every lane is done.  Crossing rows are bit-identical to the
    fixed-horizon path either way.

    ``lane_params`` mirrors the kernel's variation plane by replacing the
    scalar ``p.alpha`` / ``p.b_aniso`` with ``(cells, 1, 1)`` rows inside
    the *production* ``llg.llg_rhs`` (broadcasting does the rest) and
    scaling the self-consistent drive by the per-lane junction conductance
    factor — same ops, same order, so the per-lane kernel stays
    allclose-testable against this oracle."""
    cells = state.shape[1]
    n_sub = p.n_sublattices
    g_scale = None
    p_lane = p
    if lane_params is not None:
        lp = jnp.asarray(lane_params, jnp.float32)
        assert lp.shape == (3, cells), (lp.shape, cells)
        p_lane = dataclasses.replace(
            p, alpha=lp[0].reshape(cells, 1, 1),
            b_aniso=lp[1].reshape(cells, 1, 1))
        g_scale = lp[2]
    if n_sub == 1:
        m = state[0:3].T[:, None, :]               # (cells, 1, 3)
    else:
        m = jnp.stack(
            [state[0:3].T, state[3:6].T], axis=1
        )                          # (cells, 2, 3)
    v = state[6]
    use_noise = seeds is not None
    if use_noise:
        seeds = seeds.reshape(cells).astype(jnp.uint32)
        sigma = jnp.broadcast_to(
            jnp.asarray(thermal_sigma, jnp.float32), (cells,)
        ).reshape(cells, 1, 1)
    else:
        assert isinstance(thermal_sigma, (int, float)) and thermal_sigma == 0.0, \
            "thermal path needs per-cell stream seeds"
    budget = None
    if step_budget is not None or chunk > 0:
        budget = (jnp.full((cells,), float(n_steps), jnp.float32)
                  if step_budget is None else
                  jnp.broadcast_to(jnp.asarray(step_budget, jnp.float32),
                                   (cells,)))

    def step(i, m, crossed):
        nz = llg.order_parameter_z(m)
        g = tmr.conductance_from_cos(nz, p)
        aj = p.stt_prefactor * v * g / p.area
        if g_scale is not None:
            aj = aj * g_scale
        if use_noise:
            # identical stream to the Pallas kernel: (cells, n_sub, 3) field
            # from the same per-lane counters (see kernels/noise.py)
            d1, d2 = noise.thermal_draws(seeds, i)
            triples = [jnp.stack(d1, axis=-1), jnp.stack(d2, axis=-1)]
            b_th = sigma * jnp.stack(triples[:n_sub], axis=1)
        else:
            b_th = None
        m_next = rk4_step(lambda mm, tt: llg.llg_rhs(mm, p_lane, aj, b_th),
                          m, 0.0, dt)
        nz_new = llg.order_parameter_z(m_next)
        newly = (nz_new < -switch_threshold) & (crossed >= float(n_steps))
        if budget is not None:
            active = jnp.asarray(i, jnp.float32) < budget
            newly = newly & active
            m_next = jnp.where(active[:, None, None], m_next, m)
        crossed = jnp.where(newly, jnp.asarray(i + 1, jnp.float32), crossed)
        return m_next, crossed

    crossed0 = jnp.full((cells,), float(n_steps), jnp.float32)
    if chunk <= 0:
        def body(carry, i):
            m, crossed = carry
            return step(i, m, crossed), None

        (m, crossed), _ = jax.lax.scan(body, (m, crossed0),
                                       jnp.arange(n_steps))
    else:
        n_chunks = -(-n_steps // chunk)

        def cond(carry):
            c, m, crossed = carry
            done = (crossed < float(n_steps)) | (
                jnp.asarray(c * chunk, jnp.float32) >= budget)
            return (c < n_chunks) & ~jnp.all(done)

        def chunk_body(carry):
            c, m, crossed = carry

            def inner(j, mc):
                return step(c * chunk + j, *mc)

            m, crossed = jax.lax.fori_loop(0, chunk, inner, (m, crossed))
            return c + 1, m, crossed

        _, m, crossed = jax.lax.while_loop(cond, chunk_body,
                                           (0, m, crossed0))
    sub2 = m[:, 1, :].T if n_sub == 2 else jnp.zeros_like(m[:, 0, :].T)
    return jnp.concatenate(
        [m[:, 0, :].T, sub2, v[None], crossed[None]], axis=0
    )


def ref_bitline_mac(v, g, adc_bits: int = 0, i_max: float = 1.0):
    from repro.kernels.bitline_mac import F32_DOT, adc_quantize

    i_bl = jnp.dot(v.astype(jnp.float32), g.astype(jnp.float32),
                   precision=F32_DOT)
    return adc_quantize(i_bl, adc_bits, i_max)


def ref_fake_analog(v, wn, fail, aux, adc_bits: int = 0,
                    apply_fet: bool = False, use_fail: bool = False):
    """jnp oracle for ``fake_analog.fake_analog_mac_pallas``: same fused
    conductance replay (shared ``_tile_g_diff`` — the tile math cannot
    drift), full-array dot, shared ADC, decode gain."""
    from repro.kernels.bitline_mac import F32_DOT, adc_quantize
    from repro.kernels.fake_analog import ROW_DECODE, ROW_I_MAX, _tile_g_diff

    g_diff = _tile_g_diff(jnp.asarray(wn, jnp.float32),
                          jnp.asarray(fail, jnp.float32),
                          jnp.asarray(aux, jnp.float32),
                          apply_fet=apply_fet, use_fail=use_fail)
    i_bl = jnp.dot(v.astype(jnp.float32), g_diff, precision=F32_DOT)
    i_max = aux[ROW_I_MAX:ROW_I_MAX + 1, :]
    return adc_quantize(i_bl, adc_bits, i_max) * aux[ROW_DECODE:ROW_DECODE + 1, :]


def ref_xnor_gemm(a, w, binarize: bool = False, tie: int = 1):
    from repro.kernels.xnor_gemm import binarize_acc

    out = a.astype(jnp.float32) @ w.astype(jnp.float32)
    if binarize:
        out = binarize_acc(out, tie)
    return out


def ref_xnor_popcount(a_bits: jnp.ndarray, w_bits: jnp.ndarray):
    """Bit-domain identity check: a,w in {0,1}; result == pm1 dot product."""
    K = a_bits.shape[-1]
    xnor = 1 - jnp.bitwise_xor(a_bits[:, None, :], w_bits.T[None, :, :])
    pop = jnp.sum(xnor, axis=-1)
    return 2 * pop - K

"""Pallas TPU kernel: dual-sublattice LLG RK4 array simulation.

The paper's hot loop — integrating the coupled sublattice ODEs for every
cell of a subarray (and every Monte-Carlo sample) — restructured for TPU:

* SoA layout ``(8, cells)``: rows 0-2 = m1, rows 3-5 = m2, row 6 = per-cell
  drive voltage, row 7 = first-crossing step (written by the kernel).
  Lane dimension = cells.  The thermal kernel carries every per-lane
  quantity as a ``(1, CELL_TILE)`` row, which Mosaic tiles ``(1, 128)``:
  one vreg holds 8 x 128 = 1,024 lanes of such a row.  ``CELL_TILE`` is
  1,024, so each quantity of the step fills exactly one vreg — a 512-lane
  row fills half of one for the same vector ops per step, a 2,048-lane
  row doubles the ops (``tests/test_tpu_compile.py`` counts them).
* One grid step owns a ``(8, CELL_TILE)`` VMEM-resident tile and advances it
  up to ``n_steps`` — HBM traffic is O(cells), compute O(cells * steps):
  arithmetic intensity ~ 60 flops/step/cell keeps the tile compute-bound
  for any realistic step count.
* Device constants (gamma, alpha, B_E, B_k, RK4 dt, transport constants for
  the self-consistent a_J(theta) drive) are closed over as compile-time
  scalars by default — fixed per device kind.  With a **variation plane**
  (``lane_params``, DESIGN.md §9) the aux input grows from ``(2, cells)``
  to ``(5, cells)`` and per-lane alpha / B_k / junction-conductance-scale
  rows override the scalars: process corners and D2D parameter draws are
  then campaign *data*, so an (corner x temperature x voltage x sample)
  grid rides one launch with one compile.
* Thermal field (``seeds`` given): Brown's Langevin term, sampled per step
  per sublattice component from the stateless counter-based generator in
  ``kernels/noise.py``.  Each lane carries its own uint32 stream seed and
  its own **per-lane sigma** (second input plane, row 0) — temperature is
  campaign *data*, not a compile-time scalar, so a whole
  (temperature x voltage x sample) grid rides one launch with one compile.
* Per-lane **step budget** (second input plane, row 1): lane ``i``
  integrates only while ``step < budget[i]`` — past its budget a lane is
  frozen (state held, no crossings recorded).  Padded lanes get budget 0
  and cost nothing; campaigns whose true horizon is shorter than the
  compiled ``n_steps`` (shape-bucketed launches) stop at the budget.
* Chunked early exit (``chunk > 0``): the step loop is a ``while_loop``
  over chunks of ``chunk`` steps; after each chunk the tile exits as soon
  as every lane is done (crossed or out of budget).  Crossing-step results
  are bit-identical to the fixed-horizon path (the per-step update order
  is unchanged — early exit only skips steps no lane needed), which
  ``tests/test_fused_engine.py`` pins against the ref oracle.

Hardware adaptation note (DESIGN.md §2, §8): this replaces the scalar SPICE
inner loop; the physics is bit-identical to ``repro.core`` (ref.py is the
pure-jnp oracle and tests sweep shapes/dtypes against it, including the
thermal stream at a fixed seed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.params import GAMMA, DeviceParams
from repro.kernels import noise

CELL_TILE = 1024  # lanes per grid step: one (1,128)-tiled vreg per row
ROWS = 8
AUX_ROWS = 2     # aux plane: row 0 = per-lane sigma [T], row 1 = step budget
# Variation plane (DESIGN.md §9): the aux input grows three per-lane device
# parameter rows, so process corners and D2D draws are campaign *data* —
# rows 2-4 = Gilbert alpha, anisotropy B_k [T], junction conductance factor
# g_scale (= 1/r_factor; scales the self-consistent a_J drive).  Exchange
# B_E, the field-like ratio and the transport prefactor stay compile-time
# (not varied — see core.params.ProcessCorner).
VAR_ROWS = 3
VAR_AUX_ROWS = AUX_ROWS + VAR_ROWS


def _rhs(m1, m2, aj, p: DeviceParams, bth1=None, bth2=None,
         alpha=None, bk=None):
    """Vectorized dual-sublattice LLG RHS on (3, n) component stacks.

    ``bth1``/``bth2``: optional per-sublattice thermal field component
    triples [T], added to the deterministic effective field (Brown's
    Langevin term, held constant across the RK4 substages of one step —
    same convention as ``core.montecarlo``).

    ``alpha``/``bk``: optional per-lane rows overriding the compile-time
    device constants (the variation plane).  ``None`` keeps the scalar
    closure — the legacy compiled graph, bit-for-bit.
    """
    be, beta = p.b_exchange, p.beta_flt
    alpha = p.alpha if alpha is None else alpha
    bk = p.b_aniso if bk is None else bk

    def cross(a, b):
        return (
            a[1] * b[2] - a[2] * b[1],
            a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0],
        )

    def one(m, mo, sign, bth):
        # B_eff = B_k m_z z_hat - B_E m_other (+ B_thermal)
        b = (-be * mo[0], -be * mo[1], bk * m[2] - be * mo[2])
        if bth is not None:
            b = tuple(bc + tc for bc, tc in zip(b, bth))
        # p_i = sign * z_hat (staggered Neel STT)
        pvec = (jnp.zeros_like(m[0]), jnp.zeros_like(m[0]),
                jnp.full_like(m[0], sign))
        t_prec = tuple(-GAMMA * c for c in cross(m, b))
        mxp = cross(m, pvec)
        mxmxp = cross(m, mxp)
        t_stt = tuple(GAMMA * aj * c for c in mxmxp)
        t_flt = tuple(-GAMMA * beta * aj * c for c in mxp)
        t = tuple(a + b_ + c for a, b_, c in zip(t_prec, t_stt, t_flt))
        mxt = cross(m, t)
        return tuple((a + alpha * b_) / (1.0 + alpha**2) for a, b_ in zip(t, mxt))

    d1 = one(m1, m2, 1.0, bth1)
    d2 = one(m2, m1, -1.0, bth2)
    return d1, d2


def _renorm(m):
    inv = jax.lax.rsqrt(m[0] * m[0] + m[1] * m[1] + m[2] * m[2])
    return (m[0] * inv, m[1] * inv, m[2] * inv)


def _aj_from_v(v, nz, p: DeviceParams, g_scale=None):
    """Self-consistent STT drive: a_J = pref * V * G(n_z) / A (Julliere).

    ``g_scale``: optional per-lane junction conductance factor (RA/TMR
    resistance corner, variation plane row 4)."""
    g_p = 1.0 / p.r_parallel
    g_ap = 1.0 / p.r_antiparallel
    g = 0.5 * (g_p + g_ap) + 0.5 * (g_p - g_ap) * nz
    aj = p.stt_prefactor * v * g / p.area
    return aj if g_scale is None else aj * g_scale


def _make_body(p: DeviceParams, dt: float, n_steps: int,
               switch_threshold: float, sigma, seeds, v, budget=None,
               lane_params=None):
    """Build the per-step body; ``seeds`` is None for the deterministic
    path (keeps the compiled graph identical to the pre-thermal kernel).
    ``sigma`` is a scalar or per-lane row; ``budget`` (per-lane step
    budget, f32) masks updates for lanes past their horizon — with
    ``budget == n_steps`` everywhere the masked graph computes the exact
    same values as the unmasked one.  ``lane_params`` is the optional
    (alpha, B_k, g_scale) row triple of the variation plane."""
    alpha = bk = g_scale = None
    if lane_params is not None:
        alpha, bk, g_scale = lane_params

    def body(i, carry):
        m1, m2, crossed = carry
        nz = 0.5 * (m1[2] - m2[2])
        aj = _aj_from_v(v, nz, p, g_scale)

        if seeds is not None:
            d1, d2 = noise.thermal_draws(seeds, i)
            bth1 = tuple(sigma * c for c in d1)
            bth2 = tuple(sigma * c for c in d2)
        else:
            bth1 = bth2 = None

        def f(m1, m2):
            return _rhs(m1, m2, aj, p, bth1, bth2, alpha=alpha, bk=bk)

        k1a, k1b = f(m1, m2)
        m1h = tuple(a + 0.5 * dt * k for a, k in zip(m1, k1a))
        m2h = tuple(a + 0.5 * dt * k for a, k in zip(m2, k1b))
        k2a, k2b = f(m1h, m2h)
        m1h = tuple(a + 0.5 * dt * k for a, k in zip(m1, k2a))
        m2h = tuple(a + 0.5 * dt * k for a, k in zip(m2, k2b))
        k3a, k3b = f(m1h, m2h)
        m1f = tuple(a + dt * k for a, k in zip(m1, k3a))
        m2f = tuple(a + dt * k for a, k in zip(m2, k3b))
        k4a, k4b = f(m1f, m2f)
        m1n = tuple(
            a + dt / 6.0 * (x + 2 * y + 2 * z + w)
            for a, x, y, z, w in zip(m1, k1a, k2a, k3a, k4a)
        )
        m2n = tuple(
            a + dt / 6.0 * (x + 2 * y + 2 * z + w)
            for a, x, y, z, w in zip(m2, k1b, k2b, k3b, k4b)
        )
        m1n = _renorm(m1n)
        m2n = _renorm(m2n)
        nz_new = 0.5 * (m1n[2] - m2n[2])
        newly = (nz_new < -switch_threshold) & (crossed >= float(n_steps))
        if budget is not None:
            active = jnp.asarray(i, jnp.float32) < budget
            newly = newly & active
            m1n = tuple(jnp.where(active, a, b) for a, b in zip(m1n, m1))
            m2n = tuple(jnp.where(active, a, b) for a, b in zip(m2n, m2))
        crossed = jnp.where(newly, jnp.asarray(i + 1, jnp.float32), crossed)
        return m1n, m2n, crossed

    return body


def _llg_kernel(state_ref, out_ref, *, p: DeviceParams, dt: float,
                n_steps: int, switch_threshold: float):
    s = state_ref[...]
    m1 = (s[0], s[1], s[2])
    m2 = (s[3], s[4], s[5])
    v = s[6]
    crossed = jnp.full_like(v, float(n_steps))  # first-crossing step (f32)

    body = _make_body(p, dt, n_steps, switch_threshold, 0.0, None, v)
    m1, m2, crossed = jax.lax.fori_loop(0, n_steps, body, (m1, m2, crossed))
    out = jnp.stack([m1[0], m1[1], m1[2], m2[0], m2[1], m2[2], v, crossed])
    out_ref[...] = out


def _llg_thermal_kernel(state_ref, seeds_ref, aux_ref, out_ref, *,
                        p: DeviceParams, dt: float, n_steps: int,
                        switch_threshold: float, chunk: int,
                        variation: bool = False):
    """Thermal kernel: per-lane sigma (aux row 0), per-lane step budget
    (aux row 1), optional chunked early exit (``chunk > 0``).  With
    ``variation`` the aux plane carries three more per-lane device rows
    (2 = alpha, 3 = B_k, 4 = g_scale) and the RK4 body reads those instead
    of the compile-time scalars — process corners become launch data."""
    # every lane quantity stays a (1, CELL_TILE) row: Mosaic cannot lay
    # out the 1-D vectors that ``ref[k]`` would give the uint32 hash
    def row(ref, k):
        return ref[k:k + 1, :]

    m1 = (row(state_ref, 0), row(state_ref, 1), row(state_ref, 2))
    m2 = (row(state_ref, 3), row(state_ref, 4), row(state_ref, 5))
    v = row(state_ref, 6)
    seeds = seeds_ref[...]
    sigma = row(aux_ref, 0)
    budget = row(aux_ref, 1)
    lane_params = ((row(aux_ref, 2), row(aux_ref, 3), row(aux_ref, 4))
                   if variation else None)
    crossed = jnp.full_like(v, float(n_steps))

    body = _make_body(p, dt, n_steps, switch_threshold, sigma, seeds, v,
                      budget=budget, lane_params=lane_params)
    if chunk <= 0:
        m1, m2, crossed = jax.lax.fori_loop(0, n_steps, body,
                                            (m1, m2, crossed))
    else:
        n_chunks = -(-n_steps // chunk)

        def cond(carry):
            c, m1, m2, crossed = carry
            done = (crossed < float(n_steps)) | (
                jnp.asarray(c * chunk, jnp.float32) >= budget)
            return (c < n_chunks) & ~jnp.all(done)

        def chunk_body(carry):
            c, m1, m2, crossed = carry

            def inner(j, cc):
                return body(c * chunk + j, cc)

            m1, m2, crossed = jax.lax.fori_loop(0, chunk, inner,
                                                (m1, m2, crossed))
            return c + 1, m1, m2, crossed

        _, m1, m2, crossed = jax.lax.while_loop(
            cond, chunk_body, (0, m1, m2, crossed))
    out_ref[...] = jnp.concatenate([*m1, *m2, v, crossed], axis=0)


def llg_rk4_pallas(
    state: jnp.ndarray,           # (8, cells) f32 — see module docstring
    p: DeviceParams,
    dt: float,
    n_steps: int,
    switch_threshold: float = 0.9,
    interpret: bool = False,
    thermal_sigma=0.0,            # scalar or (cells,) f32 per-lane Brown sigma
    seeds: jnp.ndarray | None = None,   # (cells,) or (1, cells) uint32
    step_budget=None,             # optional (cells,) f32 per-lane step budget
    chunk: int = 0,               # >0: early-exit chunk size (steps)
    lane_params=None,             # optional (VAR_ROWS, cells) f32 rows:
                                  # alpha, B_k [T], g_scale — the variation
                                  # plane (DESIGN.md §9)
) -> jnp.ndarray:
    rows, cells = state.shape
    assert rows == ROWS and cells % CELL_TILE == 0, state.shape

    if seeds is None:
        # deterministic path: no noise inputs, fixed horizon — the compiled
        # graph is identical to the pre-thermal kernel
        assert isinstance(thermal_sigma, (int, float)) and thermal_sigma == 0.0, \
            "thermal path needs per-cell stream seeds"
        assert step_budget is None, "step budgets ride the thermal kernel"
        assert lane_params is None, "the variation plane rides the thermal kernel"
        kern = functools.partial(
            _llg_kernel, p=p, dt=dt, n_steps=n_steps,
            switch_threshold=switch_threshold,
        )
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((ROWS, cells), jnp.float32),
            grid=(cells // CELL_TILE,),
            in_specs=[pl.BlockSpec((ROWS, CELL_TILE), lambda i: (0, i))],
            out_specs=pl.BlockSpec((ROWS, CELL_TILE), lambda i: (0, i)),
            interpret=interpret,
            name="llg_rk4_deterministic",
        )(state)

    seeds = seeds.reshape(1, cells).astype(jnp.uint32)
    sigma = jnp.broadcast_to(
        jnp.asarray(thermal_sigma, jnp.float32), (cells,))
    if step_budget is None:
        budget = jnp.full((cells,), float(n_steps), jnp.float32)
    else:
        budget = jnp.broadcast_to(
            jnp.asarray(step_budget, jnp.float32), (cells,))
    variation = lane_params is not None
    if variation:
        lp = jnp.asarray(lane_params, jnp.float32)
        assert lp.shape == (VAR_ROWS, cells), (lp.shape, cells)
        aux = jnp.concatenate([jnp.stack([sigma, budget]), lp])
        aux_rows = VAR_AUX_ROWS
    else:
        aux = jnp.stack([sigma, budget])                 # (AUX_ROWS, cells)
        aux_rows = AUX_ROWS
    kern = functools.partial(
        _llg_thermal_kernel, p=p, dt=dt, n_steps=n_steps,
        switch_threshold=switch_threshold, chunk=int(chunk),
        variation=variation,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((ROWS, cells), jnp.float32),
        grid=(cells // CELL_TILE,),
        in_specs=[
            pl.BlockSpec((ROWS, CELL_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, CELL_TILE), lambda i: (0, i)),
            pl.BlockSpec((aux_rows, CELL_TILE), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((ROWS, CELL_TILE), lambda i: (0, i)),
        interpret=interpret,
        name="llg_rk4",
    )(state, seeds, aux)

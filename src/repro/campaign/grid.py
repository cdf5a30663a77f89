"""Campaign grids: (voltage x pulse x temperature x sample) -> SoA tiles.

A *campaign* is the Monte-Carlo experiment the paper's reliability story
needs: sweep write voltage, pulse width and temperature, run many thermal
samples per point, and reduce to WER / latency-percentile surfaces.

Key packing insights (DESIGN.md §8):

* Pulse width does **not** need its own simulation axis.  The kernel
  records the *first-crossing step* per cell, so one integration to
  ``max(pulse)/dt`` steps yields WER at every shorter pulse by
  thresholding the crossing time — the pulse axis is pure post-processing.
* Temperature does **not** need its own launch axis either.  Brown's sigma
  is a per-lane kernel input (aux plane row 0), so the whole
  (temperature x voltage x sample) grid packs into the cells plane:
  ``cells = n_T * n_V * n_S`` lanes, each an independent thermal stream
  (per-lane counter-RNG seed), one launch, one compile
  (``pack_campaign``).
* Process corners don't either (DESIGN.md §9).  Per-lane device-parameter
  rows (alpha, B_k, junction conductance factor — plus sigma/tilt derived
  from the varied volume) ride the kernel's variation plane, so a
  ``VariationSpec``'s corner axis packs corner-major ahead of the
  temperature slices: ``cells = n_C * n_T * n_V * n_S``, still one launch
  (``pack_variation``), corners sharing the nominal packing's thermal
  streams and tilt draws (common random numbers).
* Lane counts are padded to **shape buckets** — power-of-two multiples of
  ``CELL_TILE`` (``bucket_cells``) — so ragged workloads (write-verify
  retry rounds over a shrinking cell set) re-land on a handful of compiled
  shapes instead of one XLA compile per round.  Padded lanes carry a step
  budget of 0 (aux plane row 1): they are frozen before the first step and
  the early-exit loop skips them entirely.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import llg
from repro.core.device import theta0_of_stability
from repro.core.params import DeviceParams, VariationSpec
from repro.kernels import noise
from repro.kernels.llg_rk4 import CELL_TILE
from repro.kernels.ops import pack_states
from repro.runtime import telemetry


@dataclasses.dataclass(frozen=True)
class CampaignGrid:
    """Axes of one Monte-Carlo campaign (all hashable -> usable as jit
    statics and as the on-disk cache key).

    ``variation`` adds the process-corner axis (DESIGN.md §9): each corner
    of the spec gets its own group of temperature slices in the packed
    cells plane, with per-lane device-parameter rows carrying the corner
    factors and D2D draws — corner count and values are campaign *data*
    (they never enter a compile key), and the corner axis shares thermal
    streams and tilt draws with the other corners (common random numbers,
    so corner comparisons are paired per lane)."""

    voltages: Tuple[float, ...]
    pulse_widths: Tuple[float, ...]          # [s], post-processing axis
    temperatures: Tuple[float, ...] = (300.0,)
    n_samples: int = 64
    dt: float = 0.1e-12
    seed: int = 0
    switch_threshold: float = 0.9
    variation: Optional[VariationSpec] = None

    def __post_init__(self):
        object.__setattr__(self, "voltages", tuple(float(v) for v in self.voltages))
        # pulse axis is normalized ascending: it is pure post-processing
        # (surfaces index through grid.pulse_widths) and pulse_for_wer's
        # "smallest qualifying pulse" contract depends on the order
        object.__setattr__(self, "pulse_widths",
                           tuple(sorted(float(t) for t in self.pulse_widths)))
        object.__setattr__(self, "temperatures",
                           tuple(float(t) for t in self.temperatures))
        assert self.voltages and self.pulse_widths and self.temperatures
        assert self.n_samples > 0

    @property
    def n_steps(self) -> int:
        """Integration length covering the longest pulse, plus one step so
        the kernel's never-crossed sentinel (crossing_step == n_steps, i.e.
        crossing_time == n_steps*dt) strictly exceeds every pulse width —
        otherwise lanes that never switch would satisfy ``crossing_time <=
        max(pulse)`` and be miscounted as successful writes."""
        return int(math.ceil(max(self.pulse_widths) / self.dt)) + 1

    @property
    def cells(self) -> int:
        """Real (unpadded) lanes in the packed (voltage x sample) plane."""
        return len(self.voltages) * self.n_samples

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        """(n_T, n_V, n_P, n_S) — the result surface axes (the optional
        corner axis, ``n_corners``, prepends these for variation grids)."""
        return (len(self.temperatures), len(self.voltages),
                len(self.pulse_widths), self.n_samples)

    @property
    def n_corners(self) -> int:
        return 1 if self.variation is None else self.variation.n_corners


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` — the shared rounding rule behind
    both lane bucketing (``bucket_cells``) and the engine's compiled-horizon
    quantization (``engine._quantize_steps``); tune them together."""
    assert n > 0, n
    return 1 << (n - 1).bit_length()


def bucket_cells(cells: int) -> int:
    """Smallest power-of-two multiple of ``CELL_TILE`` >= ``cells``.

    The campaign engine pads every launch to a bucket so ragged cell counts
    (write-verify retry rounds, arbitrary ensembles) reuse a logarithmic
    number of compiled shapes.  Bucket-pad lanes ride with a step budget of
    0, so the extra lanes are frozen at step 0 and (being SIMD lanes of
    otherwise-occupied tiles, or whole tiles that exit before their first
    chunk) cost essentially nothing.
    """
    assert cells > 0, cells
    return CELL_TILE * next_pow2(-(-cells // CELL_TILE))


HORIZON_RUNGS_PER_DECADE = 2


def log_horizon_bucket(n_steps: int,
                       per_decade: int = HORIZON_RUNGS_PER_DECADE) -> int:
    """Smallest rung of a geometric step-count ladder >= ``n_steps``.

    Rungs sit at ``round(10**(k/per_decade))`` for integer k >= 0.  The
    pow2 quantizer (``next_pow2``) is right for write campaigns, whose
    horizons span at most a factor of a few — but retention sweeps span
    *decades* of integration horizon, and pow2 rungs would cost ~3.3
    compiles per decade.  A log ladder caps that at ``per_decade`` compiles
    per decade while never over-integrating by more than one rung (the
    per-lane budget row stops real lanes at the true horizon either way, so
    crossing rows are unaffected — only compile-cache granularity changes).

    Monotone by construction (minimal k with rung >= n_steps), which the
    grid property tests pin alongside ``bucket_cells``.
    """
    assert n_steps > 0, n_steps
    assert per_decade > 0, per_decade
    k = max(0, math.ceil(per_decade * math.log10(n_steps)))
    while k > 0 and round(10 ** ((k - 1) / per_decade)) >= n_steps:
        k -= 1
    while round(10 ** (k / per_decade)) < n_steps:
        k += 1
    return int(round(10 ** (k / per_decade)))


def log_pulses(t_min: float, t_max: float, per_decade: int = 4
               ) -> Tuple[float, ...]:
    """Log-spaced pulse-width ladder [s], endpoints included.

    The natural pulse axis for retention campaigns: the first-crossing row
    gives the survival fraction at *every* rung from one integration, so a
    decade-spanning ladder is free once the horizon covers ``t_max``.
    """
    assert 0 < t_min < t_max, (t_min, t_max)
    n = max(2, int(round(per_decade * math.log10(t_max / t_min))) + 1)
    return tuple(float(t) for t in np.geomspace(t_min, t_max, n))


def pack_soa(m0: jnp.ndarray, voltages: jnp.ndarray) -> jnp.ndarray:
    """(cells, n_sub, 3) states + (cells,) drives -> padded ``(8, cells)`` SoA.

    Dual-sublattice states go through ``kernels.ops.pack_states`` (the Pallas
    kernel's layout contract).  Single-sublattice (FM/MTJ) states keep rows
    0-2 for m and zero rows 3-5 — the engine routes those tiles through the
    ``kernels.ref.ref_llg_rk4`` scan path, never the Pallas kernel, but the
    campaign semantics (padding, seeds, first-crossing row 7) are
    identical.  Lane padding goes to the ``bucket_cells`` shape bucket, not
    just the next ``CELL_TILE`` multiple — see the module docstring.
    """
    cells = m0.shape[0]
    target = bucket_cells(cells)
    if m0.shape[1] == 2:
        state = pack_states(m0, jnp.asarray(voltages, jnp.float32))
        extra = target - state.shape[1]
        if extra:
            state = jnp.pad(state, ((0, 0), (0, extra)))
        return state
    assert m0.shape[1] == 1, m0.shape
    pad = target - cells
    m0 = jnp.pad(m0, ((0, pad), (0, 0), (0, 0)))
    v = jnp.pad(jnp.asarray(voltages, jnp.float32), (0, pad))
    z = jnp.zeros_like(v)
    rows = [m0[:, 0, 0], m0[:, 0, 1], m0[:, 0, 2], z, z, z, v, z]
    return jnp.stack(rows).astype(jnp.float32)


def pack_plane(grid: CampaignGrid, p: DeviceParams, t_index: int):
    """Pack the (voltage x sample) plane for one temperature slice.

    Returns ``(state, seeds)``: the ``(8, cells_padded)`` SoA block and the
    matching ``(cells_padded,)`` uint32 per-lane thermal stream seeds.
    Sample ``s`` of voltage ``v_i`` lands at lane ``i * n_samples + s``.

    Initial states follow ``core.montecarlo``: |N(0,1)| * theta_eq + 0.01
    tilt, uniform azimuth — the Boltzmann spread of the idle cell.  The tilt
    RNG is ``jax.random`` off ``grid.seed``; the *per-step* thermal field
    streams are counter-RNG seeds derived from ``grid.seed`` and the
    temperature index so every (T, V, S) lane is an independent
    realization.  This is the one-slice view of ``pack_campaign``'s
    program: slice ``t_index`` of a campaign whose nominal device is ``p``
    at that slice's temperature packs to the same bits.
    """
    state, seeds, _, _ = _run_pack(grid, p, [_slice_inputs(grid, p, t_index)])
    return state, seeds


def _tilt_draws(key, t_index, cells: int):
    """The Boltzmann tilt normals and azimuths of one (V x S) plane, off
    the campaign's threefry ``key`` (``t_index`` may be traced)."""
    k_th, k_ph = jax.random.split(jax.random.fold_in(key, t_index))
    zs = jnp.abs(jax.random.normal(k_th, (cells,)))
    ph = jax.random.uniform(k_ph, (cells,), maxval=2 * jnp.pi)
    return zs, ph


def _plane_tilt_draws(grid: CampaignGrid, t_index: int, cells: int):
    """``_tilt_draws`` of ``grid.seed`` — shared by the pack program and the
    variation packer, so a variation campaign's slices reuse exactly the
    draws the nominal packing would (the per-lane tilt then differs only
    through the corner's own ``theta0``: common random numbers across
    corners)."""
    return _tilt_draws(jax.random.PRNGKey(grid.seed), t_index, cells)


def _slice_inputs(grid: CampaignGrid, p: DeviceParams, t_index: int):
    """Host-side inputs of slice ``t_index`` at device ``p``'s temperature:
    (slice index, stream base seed, barrier Delta, Brown sigma)."""
    from repro.core.montecarlo import thermal_sigma

    return (t_index, noise.slice_base(grid.seed, t_index),
            p.thermal_stability, thermal_sigma(p, grid.dt))


@functools.partial(jax.jit, static_argnames=("p", "n_s", "n_dev"))
def _pack_program(seed, t_index, base, delta, sigma, voltages, n_steps, *,
                  p: DeviceParams, n_s: int, n_dev: int):
    """The whole fused ``(state, seeds, sigma, budget)`` block of
    ``len(t_index)`` temperature slices in one program (DESIGN.md §8).

    Statics fix shapes and code paths only: the nominal device ``p`` (its
    ``n_sublattices`` picks ``pack_soa``'s branch), the samples per
    voltage, the slice count and voltage count (through the array
    shapes), and the devices the block is laid out on.  Seeds, slice
    indices, per-slice stream bases, Delta and sigma, the voltages and the
    step budget are traced, so a new campaign of the same shape runs
    without tracing; ``campaign.pack_traces`` counts the traces.  With
    ``n_dev > 1`` the block comes out dealt by tile (``deal_tiles``) and
    sharded along its lanes as ``_integrate_sharded`` takes it.
    """
    telemetry.count("campaign.pack_traces")
    key = jax.random.PRNGKey(seed)
    cells = voltages.shape[0] * n_s
    v = jnp.repeat(voltages, n_s)
    lane = jnp.arange(bucket_cells(cells))
    states, seed_rows, sigma_rows, budget_rows = [], [], [], []
    for i in range(t_index.shape[0]):
        zs, ph = _tilt_draws(key, t_index[i], cells)
        # max(., 0) changes no value (both factors are >= 0) but rounds the
        # product on its own: XLA:CPU would contract the multiply-add into
        # one FMA, an ulp off the tilt ``pack_variation`` computes eagerly
        th = jnp.maximum(zs * theta0_of_stability(delta[i]), 0.0) + 0.01
        m0 = jax.vmap(lambda t, f: llg.initial_state(p, t, f))(th, ph)
        states.append(pack_soa(m0, v))                  # pads to the bucket
        seed_rows.append(noise.cell_seeds(base[i], lane.shape[0]))
        sigma_rows.append(jnp.full(lane.shape, sigma[i]))
        budget_rows.append(jnp.where(lane < cells, n_steps, 0.0))
    out = (jnp.concatenate(states, axis=1), jnp.concatenate(seed_rows),
           jnp.concatenate(sigma_rows), jnp.concatenate(budget_rows))
    if n_dev > 1:
        # every device builds the whole block (a few hundred microseconds
        # of device work), deals its tiles round-robin and keeps its own
        # lanes: no collective at all
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("cells",))
        out = tuple(jax.lax.with_sharding_constraint(
            deal_tiles(jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())), n_dev),
            NamedSharding(mesh, P(*(None,) * (x.ndim - 1), "cells")))
            for x in out)
    return out


def deal_tiles(x, n_dev: int):
    """Deal the lane axis (the last) out to ``n_dev`` devices by whole
    ``CELL_TILE`` tiles, round-robin: tile ``k`` of the packed order lands
    in device ``k % n_dev``'s contiguous share, at position ``k // n_dev``
    there.  A campaign's (T, V) blocks then spread evenly over the devices
    however slow each block is.  Whole tiles, never single lanes: the
    kernel exits a tile early once all its lanes are done (DESIGN.md §14),
    and a tile keeps exactly the lanes, hence the cost, it had.  Takes a
    numpy or a jax array whose lane count is a multiple of
    ``n_dev * CELL_TILE``; ``undeal_tiles`` is its inverse."""
    lead, cols = x.shape[:-1], x.shape[-1]
    assert cols % (n_dev * CELL_TILE) == 0, (cols, n_dev)
    tiles = x.reshape(lead + (cols // (n_dev * CELL_TILE), n_dev, CELL_TILE))
    return tiles.swapaxes(-3, -2).reshape(lead + (cols,))


def undeal_tiles(x, n_dev: int):
    """The lane axis of a ``deal_tiles(., n_dev)`` block back in packed
    order."""
    lead, cols = x.shape[:-1], x.shape[-1]
    assert cols % (n_dev * CELL_TILE) == 0, (cols, n_dev)
    tiles = x.reshape(lead + (n_dev, cols // (n_dev * CELL_TILE), CELL_TILE))
    return tiles.swapaxes(-3, -2).reshape(lead + (cols,))


def _run_pack(grid: CampaignGrid, p: DeviceParams, slices, n_dev: int = 1):
    """Call ``_pack_program`` on the host-side inputs of ``slices``."""
    t_index, base, delta, sigma = zip(*slices)
    return _pack_program(
        np.uint32(grid.seed & 0xFFFFFFFF), np.asarray(t_index, np.int32),
        np.asarray(base, np.uint32), np.asarray(delta, np.float32),
        np.asarray(sigma, np.float32),
        np.asarray(grid.voltages, np.float32), np.float32(grid.n_steps),
        p=p, n_s=grid.n_samples, n_dev=n_dev)


def pack_campaign(grid: CampaignGrid, p: DeviceParams, n_dev: int = 1):
    """Fuse the temperature axis into the cells plane: one SoA block for the
    whole (T x V x S) grid.

    Each temperature slice is packed exactly as ``pack_plane`` would pack it
    standalone — same initial-state draws, same per-lane counter-RNG
    streams, same bucket padding — and the padded slices are concatenated
    along the cells axis.  A fused launch therefore produces *bit-identical*
    crossing rows to the old one-launch-per-temperature loop (pinned by
    ``tests/test_fused_engine.py``); what changes is that Brown's sigma
    becomes a per-lane row (slice ``ti`` carries ``thermal_sigma(p @ T_ti,
    dt)``) and the padded lanes carry a step budget of 0.

    The host derives each slice's scalars (inside its ``pack_slice`` span),
    then one call of ``_pack_program`` builds the block on the device;
    ``n_dev > 1`` lays it out over that many devices, its tiles dealt
    round-robin (``deal_tiles``) and its lanes sharded.

    Returns ``(state, seeds, sigma, budget, spans)``: the ``(8, cells)``
    SoA block, per-lane uint32 streams, per-lane sigma row [T], per-lane
    step-budget row (``grid.n_steps`` on real lanes, 0 on padding), and
    ``spans[ti] = (start, stop)`` — the real-lane slice of temperature
    ``ti`` in the packed plane (before any deal: ``undeal_tiles`` first).
    """
    padded = bucket_cells(grid.cells)
    slices, spans = [], []
    for ti, temp in enumerate(grid.temperatures):
        with telemetry.span("campaign.pack_slice", slice=ti):
            p_t = (p if temp == p.temperature
                   else dataclasses.replace(p, temperature=float(temp)))
            slices.append(_slice_inputs(grid, p_t, ti))
        spans.append((ti * padded, ti * padded + grid.cells))
    state, seeds, sigma, budget = _run_pack(grid, p, slices, n_dev)
    return state, seeds, sigma, budget, spans


def pack_variation(grid: CampaignGrid, p: DeviceParams):
    """Fuse the process-corner axis into the cells plane alongside
    temperature: one SoA block for the whole (corner x T x V x S) grid
    (DESIGN.md §9).

    Layout is corner-major: slice ``ci * n_T + ti`` holds corner ``ci`` at
    temperature ``ti``, packed exactly as a single-corner campaign would
    pack it — same tilt normals (``_plane_tilt_draws``), same thermal
    streams (``noise.slice_seeds(seed, ti)``, *shared across corners*:
    common random numbers make corner comparisons paired per lane and the
    fused launch bit-identical to per-corner launches), and D2D parameter
    draws from the spec's own counter streams (salted by temperature index,
    not corner position — ``VariationSpec.lane_factors``).

    Returns ``(state, seeds, sigma, budget, lane_params, spans)``: the
    ``(8, cells)`` SoA block, per-lane uint32 streams, per-lane Brown sigma
    [T] (now a function of the varied alpha/volume), per-lane step budgets,
    the ``(3, cells)`` variation rows (alpha, B_k, g_scale) the kernel's
    aux plane carries, and ``spans[ci * n_T + ti] = (start, stop)`` real-
    lane slices.  Bucket-pad lanes carry nominal parameter rows (never NaN
    physics), sigma 0 and budget 0.
    """
    spec = grid.variation
    assert spec is not None, "pack_variation needs grid.variation"
    n_t = len(grid.temperatures)
    n_steps = float(grid.n_steps)
    cells = grid.cells
    states, seed_rows, sigma_rows, budget_rows, lane_rows_, spans = (
        [], [], [], [], [], [])
    offset = 0
    for ci, corner in enumerate(spec.corners):
        for ti, temp in enumerate(grid.temperatures):
            with telemetry.span("campaign.pack_slice", slice=ci * n_t + ti):
                rows = spec.lane_rows(p, corner, cells, grid.dt,
                                      temperature=temp, stream=ti)
                zs, ph = _plane_tilt_draws(grid, ti, cells)
                th = zs * jnp.asarray(rows.theta0, jnp.float32) + 0.01
                m0 = jax.vmap(lambda t, f: llg.initial_state(p, t, f))(th, ph)
                v = jnp.repeat(jnp.asarray(grid.voltages, jnp.float32),
                               grid.n_samples)
                st = pack_soa(m0, v)
                padded = st.shape[1]
                pad = padded - cells

                def _row(vals, fill):
                    return np.pad(np.asarray(vals, np.float64), (0, pad),
                                  constant_values=fill).astype(np.float32)

                states.append(st)
                seed_rows.append(noise.slice_seeds(grid.seed, ti, padded))
                sigma_rows.append(_row(rows.sigma, 0.0))
                budget_rows.append(_row(np.full(cells, n_steps), 0.0))
                lane_rows_.append(np.stack([
                    _row(rows.alpha, p.alpha),
                    _row(rows.b_aniso, p.b_aniso),
                    _row(rows.g_scale, 1.0),
                ]))
            spans.append((offset, offset + cells))
            offset += padded
    return (jnp.concatenate(states, axis=1),
            jnp.concatenate(seed_rows),
            jnp.asarray(np.concatenate(sigma_rows)),
            jnp.asarray(np.concatenate(budget_rows)),
            jnp.asarray(np.concatenate(lane_rows_, axis=1)),
            spans)

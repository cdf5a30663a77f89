"""Monte-Carlo campaign engine: one kernel launch for the whole campaign.

Replaces the per-sample host-visible scan in ``core.montecarlo`` (O(steps)
XLA while-loop per sample, threefry keys split per step) with the Pallas
thermal LLG kernel — and packs *every* campaign axis that isn't pure
post-processing into the kernel's cells plane:

* voltage x sample ride the lanes (PR 1);
* pulse width falls out of the recorded first-crossing steps (PR 1);
* temperature rides the lanes too: Brown's sigma is a per-lane kernel
  input (aux plane), so a (T x V x S) grid is **one launch, one compile**
  instead of a host-level loop with one sigma-specialized recompile per
  temperature (``grid.pack_campaign``);
* process corners ride the lanes as well (DESIGN.md §9): per-lane
  alpha / B_k / conductance-factor rows on the kernel's variation plane
  make a (corner x T x V x S) grid one launch too, with corner count and
  values as pure data (``grid.pack_variation``).

No wasted steps either: the kernel integrates in chunks and exits a tile
as soon as every lane has crossed or exhausted its per-lane step budget
(``EARLY_EXIT_CHUNK``), and the compiled horizon is quantized to a power
of two (``_quantize_steps``) so campaigns with different pulse ladders
share compiles — the per-lane budget row stops the integration at the
*true* horizon, and crossing rows stay bit-identical to a fixed-horizon
run (``tests/test_fused_engine.py`` pins this).

Scaling: the cells axis is embarrassingly parallel, so the engine shards
cell tiles across every visible device with ``shard_map`` — each device
integrates its own ``cells / n_dev`` lanes (a multiple of the kernel's
CELL_TILE, padded with budget-0 lanes when the tiles don't divide the
mesh — ``_device_plan``), no cross-device communication at all.  A
single launch packed straight onto its devices deals its tiles to them
round-robin (``grid.deal_tiles``), so every device gets its share of
each (T, V) block's slow or fast lanes; the crossing row is put back in
packed order before anything reads it.  Launches
above ``max_cells_per_launch`` split along temperature-slice boundaries
and are all dispatched asynchronously before the first
``block_until_ready`` — the host never serializes device work against
transfers.  Results are reduced host-side into WER / latency-percentile
surfaces and cached on disk (``cache.py``) keyed by the full campaign
content hash.

Past one host (DESIGN.md §14): ``reduce="stream"`` keeps even the
reduction on device — each launch returns exact WER counts and a
first-crossing histogram instead of its dense lane plane, so host
transfers are O(grid points) regardless of sample count; and a
``launch.mesh.CampaignMesh`` partitions whole launches across processes,
which rendezvous lockless-ly through the
content-addressed store (claims + slice checkpoints in ``cache.py``) —
no collectives, so a mesh of hosts needs nothing but a shared cache
directory.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.campaign import cache as _cache
from repro.campaign.grid import (CampaignGrid, bucket_cells,
                                 log_horizon_bucket, next_pow2, pack_campaign,
                                 pack_soa, pack_variation, undeal_tiles)
from repro.core.montecarlo import thermal_sigma
from repro.core.params import DeviceParams
from repro.kernels import noise, ref
from repro.kernels.llg_rk4 import CELL_TILE, llg_rk4_pallas
from repro.kernels.ops import _default_interpret
from repro.runtime import telemetry

# Early-exit granularity [steps]: the kernel checks "is every lane done?"
# once per chunk.  Small enough that a finished tile wastes < chunk steps,
# large enough that the all-lane reduction is noise next to the ~60
# flops/step/lane RK4 body.
EARLY_EXIT_CHUNK = 64


def brown_sigma(p: DeviceParams, dt: float, temperature: Optional[float] = None
                ) -> float:
    """Brown's thermal-field std per component per step [T] — canonical
    formula lives in ``core.montecarlo.thermal_sigma``."""
    if temperature is not None and temperature != p.temperature:
        p = dataclasses.replace(p, temperature=float(temperature))
    return thermal_sigma(p, dt)


def _quantize_steps(n_steps: int, horizon: str = "pow2") -> int:
    """Round the compiled horizon up to a shared rung.

    The per-lane step-budget row stops every lane at the *true* horizon,
    and the chunked loop exits a tile within one chunk of its slowest
    lane's budget — so the masked tail costs ~nothing at runtime while
    campaigns over different pulse ladders (write-verify sweeps, margin
    ladders) land on a logarithmic number of compiled step counts.

    ``horizon`` picks the ladder: ``"pow2"`` (default — every existing
    write-path compile pin) or ``"log"`` — the geometric
    ``grid.log_horizon_bucket`` ladder, ~2 rungs per decade, for retention
    campaigns whose horizons span decades (DESIGN.md §10).
    """
    if horizon == "log":
        return log_horizon_bucket(n_steps)
    assert horizon == "pow2", horizon
    return next_pow2(n_steps)


def _integrate_impl(state, seeds, sigma, budget, lane_params=None, *,
                    p: DeviceParams, dt: float, n_steps: int,
                    switch_threshold: float, backend: str, n_dev: int,
                    chunk: int):
    """Advance a (8, cells) block on ``n_dev`` devices (cells sharded).

    Everything that varies *within* a campaign — or between retry rounds
    of a write-verify schedule — is traced data: per-lane Brown sigma,
    per-lane step budgets, per-lane RNG stream seeds, initial states,
    drive voltages, and (variation campaigns, DESIGN.md §9) the per-lane
    device-parameter rows ``lane_params`` — so process-corner count and
    values never recompile.  The only compile keys left are the nominal
    device physics ``p``, the step size, the (quantized) horizon, the
    launch shape (bucketed by ``grid.bucket_cells``), and whether the
    variation plane is present at all.
    """

    def tile_fn(st, sd, sg, bd, lp=None):
        # the SoA Pallas kernel is dual-sublattice by construction
        # (staggered Neel STT); single-sublattice FM/MTJ devices integrate
        # the same production physics through the oracle's lane-vectorized
        # scan — same grids, padding, RNG streams, first-crossing row 7
        if p.n_sublattices == 1 or backend == "ref":
            return ref.ref_llg_rk4(st, p, dt, n_steps, switch_threshold,
                                   thermal_sigma=sg, seeds=sd,
                                   step_budget=bd, chunk=chunk,
                                   lane_params=lp)
        return llg_rk4_pallas(st, p, dt, n_steps, switch_threshold,
                              interpret=_default_interpret(),
                              thermal_sigma=sg, seeds=sd,
                              step_budget=bd, chunk=chunk, lane_params=lp)

    if n_dev == 1:
        return tile_fn(state, seeds, sigma, budget, lane_params)
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("cells",))
    # check_vma=False: pallas_call has no varying-axes rule; every output
    # is fully sharded along cells anyway
    specs = (P(None, "cells"), P("cells"), P("cells"), P("cells"))
    if lane_params is None:
        fn = jax.shard_map(tile_fn, mesh=mesh, in_specs=specs,
                           out_specs=P(None, "cells"), check_vma=False)
        return fn(state, seeds, sigma, budget)
    fn = jax.shard_map(tile_fn, mesh=mesh,
                       in_specs=specs + (P(None, "cells"),),
                       out_specs=P(None, "cells"), check_vma=False)
    return fn(state, seeds, sigma, budget, lane_params)


_INTEGRATE_STATICS = ("p", "dt", "n_steps", "switch_threshold", "backend",
                      "n_dev", "chunk")
_integrate_sharded = jax.jit(_integrate_impl,
                             static_argnames=_INTEGRATE_STATICS)


def _device_count(devices: Optional[int]) -> int:
    """Devices a launch shards over: all visible ones by default, never
    more than are visible."""
    return (jax.device_count() if devices is None
            else max(1, min(int(devices), jax.device_count())))


def _device_plan(span_cells: int, devices: Optional[int]) -> Tuple[int, int]:
    """Device count + padded lane width for one launch span.

    Never demotes the device count: when the span's CELL_TILE tiles don't
    divide the requested count (pow2 shape buckets vs 3/5/6-device
    meshes), the span is padded with budget-0 lanes up to the next
    tiles-per-device boundary (``launch.sharding.plan_cell_tiles``).  The
    pre-PR-10 ``_usable_devices`` instead walked ``n`` down until the
    tiles divided — silently serializing exactly the uneven meshes
    multi-host fleets produce (tests/test_scale.py pins the fix at 3, 5
    and 6 host devices).  Pad lanes are frozen at step 0 (budget 0) and
    trimmed before any reduction, so crossing rows stay bit-identical to
    the 1-device launch."""
    n = _device_count(devices)
    tiles = -(-span_cells // CELL_TILE)
    from repro.launch.sharding import plan_cell_tiles

    _, padded_tiles = plan_cell_tiles(tiles, n)
    return n, padded_tiles * CELL_TILE


def _pad_lanes(st, sd, sg, bd, lp, pad: int, p: DeviceParams):
    """Append ``pad`` frozen lanes (zero state/seed/sigma/budget, nominal
    variation rows) so a span fills its device plan exactly."""
    if pad == 0:
        return st, sd, sg, bd, lp
    st = jnp.pad(st, ((0, 0), (0, pad)))
    sd = jnp.pad(sd, (0, pad))
    sg = jnp.pad(sg, (0, pad))
    bd = jnp.pad(bd, (0, pad))
    if lp is not None:
        fill = np.broadcast_to(
            np.array([[p.alpha], [p.b_aniso], [1.0]], np.float32), (3, pad))
        lp = jnp.concatenate([lp, jnp.asarray(fill)], axis=1)
    return st, sd, sg, bd, lp


# ------------------------------------------------- streaming reduction
# DESIGN.md §14: billion-sample campaigns cannot round-trip dense lane
# planes to the host (32 B/lane for the (8, cells) block).  In streaming
# mode every launch is reduced ON DEVICE to exactly what the surfaces
# need — WER counts per (slice, V, pulse) and a fixed-bin first-crossing
# histogram per (slice, V) — so the host transfer per launch is O(grid
# points), independent of the sample count.  WER counts are *bit-exact*
# by construction: the dense surface compares f64(crossing_step)*dt >
# pulse, and ``_wer_threshold_steps`` precomputes (in f64, on the host)
# the smallest integer step satisfying that per pulse, so the device
# only ever runs an exact integer comparison.  Latency percentiles come
# from the histogram: exact (bit-identical reconstruction of
# np.nanpercentile's linear interpolation) while bins resolve single
# steps, within two bin widths otherwise — the sketch-error budget
# ``CampaignResult.sketch_tolerance`` documents and tests pin.

# WER campaigns record crossing steps in the kernel's f32 row — exact
# integers only below 2**24, which streaming mode relies on for its
# integer compares (dense mode has the same representational limit).
_STREAM_MAX_STEPS = 1 << 24


def _wer_threshold_steps(pulse_widths, dt: float, n_steps: int) -> np.ndarray:
    """Smallest integer step count per pulse with ``f64(k)*dt > pulse`` —
    counting ``crossing_step >= k`` on device then reproduces the dense
    f64 comparison bit-for-bit."""
    out = []
    for pl in pulse_widths:
        k = int(math.ceil(pl / dt))
        while np.float64(k) * dt <= pl:
            k += 1
        while k > 0 and np.float64(k - 1) * dt > pl:
            k -= 1
        assert k <= n_steps, (k, n_steps, pl)   # grid.n_steps covers pulses
        out.append(k)
    return np.asarray(out, np.int32)


def _hist_step_values(n_steps: int, n_bins: int) -> np.ndarray:
    """Lower-edge crossing *step* of every histogram bin (f64).  With
    ``n_bins >= n_steps`` a bin is a single step and reconstruction is
    exact; otherwise bin ``b`` spans steps ``[ceil(b*n_steps/n_bins),
    ceil((b+1)*n_steps/n_bins))`` and its lower edge stands in for every
    sample inside."""
    if n_bins >= n_steps:
        return np.arange(n_bins, dtype=np.float64)
    return np.ceil(np.arange(n_bins, dtype=np.float64) * n_steps / n_bins)


@functools.partial(jax.jit, static_argnames=(
    "n_slices", "slice_cells", "n_v", "n_s", "n_steps", "n_bins", "deal"))
def _reduce_rows(out, kmin, *, n_slices: int, slice_cells: int, n_v: int,
                 n_s: int, n_steps: int, n_bins: int, deal: int = 1):
    """On-device reduction of one launch's crossing row.

    ``deal > 1`` marks a launch whose lanes were dealt by tile over that
    many devices (``grid.deal_tiles``): the crossing row alone is put back
    in packed order before it is read.

    Returns ``(wer_counts, hist)``: int32 ``(n_slices, n_v, n_p)`` counts
    of samples NOT switched by each pulse (exact — see module comment)
    and the int32 ``(n_slices, n_v, n_bins)`` first-crossing histogram
    over *switched* samples.  Only these reduced tensors ever reach the
    host; bucket padding, device-plan padding and never-crossed sentinels
    are all excluded on device."""
    row7 = out[7, : n_slices * slice_cells]
    if deal > 1:
        # gather the one row, as an undealt launch does, then put it back in
        # packed order on every device (a dealt launch has no plan pad)
        mesh = Mesh(np.asarray(jax.devices()[:deal]), ("cells",))
        row7 = undeal_tiles(jax.lax.with_sharding_constraint(
            row7, NamedSharding(mesh, P())), deal)
    row7 = row7.reshape(n_slices, slice_cells)
    ki = jnp.minimum(row7[:, : n_v * n_s], float(n_steps)).astype(jnp.int32)
    ki = ki.reshape(n_slices, n_v, n_s)
    wer = (ki[:, :, None, :] >= kmin[None, None, :, None]).sum(
        axis=-1).astype(jnp.int32)
    switched = ki < n_steps
    if n_bins >= n_steps:                       # one bin per step: exact
        bins = ki
    else:
        # f32 scale can misplace a boundary value by one bin — covered by
        # the two-bin sketch_tolerance
        bins = jnp.floor(ki.astype(jnp.float32)
                         * (float(n_bins) / float(n_steps))).astype(jnp.int32)
        bins = jnp.clip(bins, 0, n_bins - 1)
    cell = jnp.arange(n_slices * n_v, dtype=jnp.int32).reshape(
        n_slices, n_v, 1)
    flat = jnp.where(switched, cell * n_bins + bins,
                     n_slices * n_v * n_bins)   # unswitched -> spill bin
    hist = jnp.zeros((n_slices * n_v * n_bins + 1,), jnp.int32
                     ).at[flat.reshape(-1)].add(1)
    return wer, hist[:-1].reshape(n_slices, n_v, n_bins)


def _percentiles_from_hist(hist: np.ndarray, values: np.ndarray,
                           qs) -> np.ndarray:
    """Percentiles over switched samples from per-bin counts — the exact
    linear-interpolation rule ``np.nanpercentile`` applies to the sorted
    dense samples, reconstructed from cumulative counts (the sorted array
    is fully determined by them).  All-unswitched cells report NaN, like
    the dense all-NaN slice."""
    qs = np.asarray(qs, dtype=float)
    flat = hist.reshape(-1, hist.shape[-1])
    out = np.full((flat.shape[0], len(qs)), np.nan)
    for i, h in enumerate(flat):
        n = int(h.sum())
        if n == 0:
            continue
        cum = np.cumsum(h)
        pos = (qs / 100.0) * (n - 1)
        lo = np.floor(pos).astype(int)
        hi = np.ceil(pos).astype(int)
        v_lo = values[np.searchsorted(cum, lo, side="right")]
        v_hi = values[np.searchsorted(cum, hi, side="right")]
        # np.percentile's _lerp flips the anchor at t >= 0.5 (monotonicity
        # fix-up); reproduce it exactly or single-ULP drift breaks the
        # bit-identity claim for per-step bins
        t = pos - lo
        lerp = v_lo + t * (v_hi - v_lo)
        flip = t >= 0.5
        lerp[flip] = v_hi[flip] - (v_hi[flip] - v_lo[flip]) * (1 - t[flip])
        out[i] = lerp
    return out.reshape(hist.shape[:-1] + (len(qs),))


@dataclasses.dataclass(frozen=True)
class EnsembleResult:
    """One thermal ensemble integration (a single campaign tile)."""
    final_state: np.ndarray      # (8, cells) SoA at loop exit
    crossing_steps: np.ndarray   # (cells,) first crossing (== n_steps: none)
    n_steps: int
    dt: float
    elapsed_s: float

    @property
    def crossing_time(self) -> np.ndarray:
        return self.crossing_steps * self.dt

    @property
    def switched(self) -> np.ndarray:
        return self.crossing_steps < self.n_steps


def run_ensemble(
    p: DeviceParams,
    m0: jnp.ndarray,                 # (cells, n_sub, 3) initial states
    voltages: jnp.ndarray,           # (cells,) per-cell drive
    dt: float,
    n_steps: int,
    *,
    seed: int = 0,
    temperature: Optional[float] = None,
    backend: str = "pallas",
    switch_threshold: float = 0.9,
    devices: Optional[int] = None,
    chunk: int = 0,
    lane_params=None,                # optional (3, cells) variation rows
    sigma_lanes=None,                # optional (cells,) per-lane Brown sigma
    horizon: str = "pow2",           # compiled-horizon ladder (chunk > 0)
) -> EnsembleResult:
    """Integrate an arbitrary thermal ensemble through the kernel path.

    The general entry point (used by ``examples/array_mc_sim.py`` for
    per-cell IR-drop voltage maps); ``run_campaign`` packs structured
    (T x V x S) grids on top of the same kernel.  ``temperature=None``
    uses ``p.temperature``; ``temperature=0`` (or alpha/volume making
    sigma 0) zeroes the per-lane thermal field (numerically identical to
    the deterministic kernel).  Single-sublattice devices
    (``p.n_sublattices == 1``, the MTJ baseline) integrate through the
    ``kernels.ref.ref_llg_rk4`` scan — same API, grids and reductions, no
    Pallas kernel (the SoA kernel is dual-sublattice only).

    ``chunk > 0`` turns on chunked early exit: crossing rows are
    bit-identical to the fixed-horizon default, but ``final_state`` then
    holds the at-exit state (lanes stop within one chunk of the last
    crossing) rather than the state after the full horizon — and the
    *compiled* horizon is quantized to a power of two (the per-lane budget
    row stops real lanes at the true ``n_steps``), so callers sweeping
    horizons (write-verify retry rounds) share compiles.

    ``lane_params`` ((3, cells): alpha, B_k, g_scale) switches on the
    kernel's per-lane device-variation plane; ``sigma_lanes`` overrides
    the scalar Brown sigma with a per-lane row (the two usually travel
    together — a varied alpha/volume changes sigma; see
    ``VariationSpec.lane_rows``).

    Never-switched lanes report ``crossing_steps == n_steps`` (so
    ``crossing_time == n_steps*dt``); when thresholding crossings against a
    pulse width, choose ``n_steps`` with ``n_steps*dt`` strictly beyond the
    longest pulse (``CampaignGrid`` does this automatically).
    """
    cells = m0.shape[0]
    state = pack_soa(m0, jnp.asarray(voltages, jnp.float32))
    padded = state.shape[1]
    if sigma_lanes is not None:
        sigma = jnp.pad(jnp.asarray(sigma_lanes, jnp.float32),
                        (0, padded - cells))
    else:
        sigma_t = brown_sigma(p, dt, temperature)
        sigma = jnp.full((padded,), float(sigma_t), jnp.float32)
    budget = jnp.where(jnp.arange(padded) < cells, float(n_steps),
                       0.0).astype(jnp.float32)
    if lane_params is not None:
        lp = np.asarray(lane_params, np.float64)
        assert lp.shape == (3, cells), (lp.shape, cells)
        fill = np.array([[p.alpha], [p.b_aniso], [1.0]])
        lane_params = jnp.asarray(np.concatenate(
            [lp, np.broadcast_to(fill, (3, padded - cells))],
            axis=1).astype(np.float32))
    seeds = noise.cell_seeds(seed, padded)
    n_dev, plan_cols = _device_plan(padded, devices)
    state, seeds, sigma, budget, lane_params = _pad_lanes(
        state, seeds, sigma, budget, lane_params, plan_cols - padded, p)
    n_static = _quantize_steps(n_steps, horizon) if chunk > 0 else n_steps

    t0 = time.time()
    out = _integrate_sharded(
        state, seeds, sigma, budget, lane_params, p=p, dt=dt,
        n_steps=n_static, switch_threshold=float(switch_threshold),
        backend=backend, n_dev=n_dev, chunk=int(chunk))
    out = np.asarray(jax.block_until_ready(out))
    elapsed = time.time() - t0
    return EnsembleResult(
        final_state=out[:, :cells],
        crossing_steps=np.minimum(out[7, :cells].astype(np.float64),
                                  float(n_steps)),
        n_steps=n_steps, dt=dt, elapsed_s=elapsed)


@dataclasses.dataclass(frozen=True)
class CampaignResult:
    """WER / latency surfaces over the (T, V, pulse) axes of a grid — with
    a leading process-corner axis when the grid carries a
    ``VariationSpec`` (``crossing_time`` is then (n_C, n_T, n_V, n_S) and
    every surface reduction grows the same leading axis).

    ``reduced=True`` is the streaming-reduction variant (DESIGN.md §14):
    ``crossing_time`` is None — the dense lane planes never left the
    devices — and the surfaces come from ``wer_counts`` (bit-exact) and
    the ``latency_hist`` sketch (exact while bins resolve single steps,
    within ``sketch_tolerance`` otherwise)."""
    grid: CampaignGrid
    backend: str
    crossing_time: Optional[np.ndarray]  # (n_T, n_V, n_S) s; variation
                                         # grids prepend the corner axis
                                         # (n_C, ...); None when reduced
    elapsed_s: float                 # integration wall-clock (0 on cache hit)
    from_cache: bool = False
    n_launches: int = 1              # kernel launches this result took
    n_resumed: int = 0               # launches restored from slice checkpoints
    reduced: bool = False            # streaming on-device reduction ran
    wer_counts: Optional[np.ndarray] = None    # (..., n_T, n_V, n_P) int64
    latency_hist: Optional[np.ndarray] = None  # (..., n_T, n_V, n_bins)
    hist_values: Optional[np.ndarray] = None   # (n_bins,) bin lower edge [s]
    host_bytes: int = 0              # result bytes transferred device->host
    n_computed: int = 0              # launches integrated by THIS process

    @property
    def n_samples_total(self) -> int:
        if self.crossing_time is not None:
            return int(self.crossing_time.size)
        n_t, n_v, _, n_s = self.grid.shape
        return self.grid.n_corners * n_t * n_v * n_s

    @property
    def sketch_tolerance(self) -> float:
        """Latency-percentile error bound of the streaming sketch [s]: 0
        when bins resolve single steps (the histogram then determines the
        sorted sample array exactly), else two bin widths — one for the
        floor quantization onto bin lower edges, one for the f32 bin-index
        rounding (``_reduce_rows``).  Dense results are exact."""
        if not self.reduced:
            return 0.0
        n_bins = self.latency_hist.shape[-1]
        if n_bins >= self.grid.n_steps:
            return 0.0
        return 2.0 * self.grid.n_steps * self.grid.dt / n_bins

    @property
    def corners(self) -> Optional[Tuple[str, ...]]:
        """Corner names of the leading axis (None for nominal grids)."""
        return (None if self.grid.variation is None
                else self.grid.variation.corner_names)

    def wer_surface(self) -> np.ndarray:
        """(..., n_T, n_V, n_P) write-error rate: fraction of thermal
        samples NOT switched by the end of each pulse width (leading axis =
        process corners for variation grids).  Identical — bit-for-bit —
        between dense and reduced results: the on-device counts use the
        host-precomputed integer thresholds of ``_wer_threshold_steps``,
        and an exact integer count divided by ``n_samples`` in f64 is the
        same number the dense boolean ``.mean`` produces."""
        if self.reduced:
            return (self.wer_counts.astype(np.float64)
                    / np.float64(self.grid.n_samples))
        pulses = np.asarray(self.grid.pulse_widths)
        # crossing_time == n_steps*dt marks "never crossed" and exceeds
        # every pulse in the grid by construction
        ct = self.crossing_time[..., None, :]             # (..., V, 1, S)
        return (ct > pulses[:, None]).mean(axis=-1)

    def wer(self, t_index: int = 0, corner_index: int = 0) -> np.ndarray:
        """(n_V, n_P) slice at one temperature (and corner, if any)."""
        w = self.wer_surface()
        return w[corner_index, t_index] if w.ndim == 4 else w[t_index]

    def latency_percentiles(self, qs: Sequence[float] = (50.0, 99.0)
                            ) -> np.ndarray:
        """(..., n_T, n_V, len(qs)) switching-latency percentiles over
        *switched* samples (NaN where no sample switched; leading corner
        axis for variation grids).  One masked ``np.nanpercentile`` over
        the whole tensor — never-crossed samples become NaN and drop out
        per (T, V) cell."""
        if self.reduced:
            return _percentiles_from_hist(self.latency_hist,
                                          self.hist_values, qs)
        horizon = self.grid.n_steps * self.grid.dt
        ct = np.where(self.crossing_time < horizon, self.crossing_time,
                      np.nan)
        with warnings.catch_warnings():
            # (T, V) cells where nothing switched are *expected* to be NaN
            warnings.filterwarnings("ignore", "All-NaN slice encountered")
            out = np.nanpercentile(ct, np.asarray(qs, dtype=float), axis=-1)
        return np.moveaxis(out, 0, -1)

    def pulse_for_wer(self, wer_target: float, t_index: int = 0,
                      v_index: Optional[int] = None,
                      corner_index: Optional[int] = None) -> float:
        """Smallest grid pulse width whose WER <= target (the write-margin
        query the IMC controller binds against).  ``v_index=None`` (default)
        evaluates at the *lowest* grid voltage — the worst-case drive, so a
        controller pulse sized from the default covers every cell — not at
        whatever voltage happens to be listed last.  On a variation grid,
        ``corner_index=None`` (default) takes the worst corner at every
        pulse — the margined pulse then covers the whole process spread.
        Raises if no grid pulse qualifies — callers must widen the grid
        rather than silently build timing models on a pulse that misses
        the WER target."""
        if v_index is None:
            v_index = int(np.argmin(self.grid.voltages))
        surface = self.wer_surface()
        if surface.ndim == 4:
            surface = (surface.max(axis=0) if corner_index is None
                       else surface[corner_index])
        w = surface[t_index][v_index]
        pulses = np.asarray(self.grid.pulse_widths)
        ok = np.nonzero(w <= wer_target)[0]
        if not ok.size:
            raise ValueError(
                f"no grid pulse meets WER<={wer_target:g} (best WER "
                f"{w.min():.3g} at {pulses[-1]*1e12:.0f} ps); widen "
                "pulse_widths or raise the drive voltage")
        return float(pulses[ok[0]])


def _launch_spans(n_slices: int, slice_cells: int,
                  max_cells: Optional[int]) -> List[Tuple[int, int]]:
    """Group whole temperature slices into launches of <= max_cells lanes
    (one launch when ``max_cells`` is None)."""
    if max_cells is None:
        return [(0, n_slices)]
    per = max(1, int(max_cells) // slice_cells)
    return [(a, min(a + per, n_slices)) for a in range(0, n_slices, per)]


def _slice_key(key: str, a: int, b: int, chunk: int, horizon: str,
               kind: str = "slice-row7") -> str:
    """Content key of one launch span's checkpoint payload (resume
    protocol, DESIGN.md §13): derived from the whole-campaign key plus
    everything that shapes the launch decomposition, so a resume with a
    different split/horizon never matches a stale slice.  ``kind`` keeps
    payload flavors apart — ``"slice-row7"`` is the dense raw crossing
    row (unchanged since PR 9, so existing checkpoints stay resumable);
    streaming launches store ``"slice-reduced-<n_bins>"`` entries."""
    return _cache.content_key({"campaign": key, "span": [int(a), int(b)],
                               "chunk": int(chunk), "horizon": horizon,
                               "kind": kind})


def run_campaign(
    p: DeviceParams,
    grid: CampaignGrid,
    *,
    backend: str = "pallas",
    use_cache: bool = True,
    cache_dir: Optional[str] = None,
    devices: Optional[int] = None,
    chunk: int = EARLY_EXIT_CHUNK,
    max_cells_per_launch: Optional[int] = None,
    horizon: str = "pow2",
    checkpoint: Optional[bool] = None,
    max_retries: int = 2,
    retry_backoff_s: float = 0.25,
    on_slice_complete=None,
    reduce: str = "dense",
    n_bins: int = 512,
    mesh=None,
) -> CampaignResult:
    """Run (or cache-load) a full Monte-Carlo campaign.

    The whole (temperature x voltage x sample) grid rides the packed cells
    plane of **one** kernel launch (per-lane sigma carries the temperature
    axis); pulse width is post-processing.  ``backend`` is "pallas"
    (production) or "ref" (pure-jnp oracle — same noise streams, used for
    parity checks and throughput baselines).

    ``chunk`` sets the early-exit granularity (0 disables early exit and
    step quantization — the exact fixed-horizon launch); ``horizon``
    selects the compiled-horizon ladder ("pow2" default, "log" for
    decade-spanning retention sweeps — see ``_quantize_steps``).  Crossing
    rows are ladder-independent (the budget row stops real lanes at the
    true horizon), so results cache under the same key.  Campaigns larger
    than ``max_cells_per_launch`` lanes split along (corner x temperature)
    slice boundaries into multiple launches, all dispatched before the
    first device sync, so transfers overlap integration.

    With ``grid.variation`` set, the process-corner axis fuses into the
    cells plane too (DESIGN.md §9): per-lane device-parameter rows ride
    the kernel's variation plane, the whole (corner x T x V x S) grid is
    still one launch, and the returned ``crossing_time`` grows a leading
    corner axis.  Single-launch variation campaigns additionally pad the
    *total* plane to a power-of-two bucket, so the corner count enters
    the compile key only through that logarithmic bucket.

    Crash resume (DESIGN.md §13): multi-launch campaigns checkpoint each
    completed launch's raw crossing row through the content-keyed cache
    (``checkpoint=None`` means "on whenever caching is on and there is
    more than one launch"), so a killed process re-runs only the launches
    it never finished — and because the stored row is the kernel's f32
    output verbatim, the resumed assembly is bit-identical to an
    uninterrupted run.  Slice checkpoints are retired once the
    whole-campaign entry is durable.  Each launch's program is compiled
    before it is dispatched, and a compiler refusal raises at once; a
    launch that then fails to dispatch or sync is retried up to
    ``max_retries`` times with exponential backoff (``retry_backoff_s``
    base).  ``on_slice_complete(i, n_launches)`` fires
    after each freshly-integrated launch is checkpointed — the hook the
    kill/resume tests use to die at a deterministic point.

    Scaling knobs (DESIGN.md §14):

    * ``reduce="stream"`` turns on the streaming on-device reduction: each
      launch is reduced to WER counts + a first-crossing histogram on the
      devices (``_reduce_rows``) and only those O(grid-points) tensors
      reach the host — ``CampaignResult.reduced`` is then True, WER
      surfaces are bit-identical to dense mode and latency percentiles are
      within ``sketch_tolerance`` (exact when ``n_bins >= grid.n_steps``).
      Streaming results cache under their own derived key, so dense and
      reduced entries never shadow each other.
    * ``mesh`` (a ``launch.mesh.CampaignMesh``) scales past one process:
      ``mesh.n_devices`` shards each launch's cells plane, and with
      ``mesh.process_count > 1`` whole launches are partitioned across
      processes through the content-addressed store — each process claims
      launches lockless-ly (``cache.try_claim``), polls peers' slice
      checkpoints, and steals claims older than ``mesh.claim_ttl_s`` from
      dead peers.  Requires ``use_cache`` (the store is the rendezvous);
      every process returns the identical assembled result.

    The campaign runs inside the host span ``repro.campaign.run`` (attributes
    ``lanes``, ``launches``, ``devices``, ``seed``), its stages inside the
    spans DESIGN.md §15 lists.
    """
    n_slices = grid.n_corners * len(grid.temperatures)
    n_launches = len(_launch_spans(n_slices, bucket_cells(grid.cells),
                                   max_cells_per_launch))
    n_dev = _device_count(mesh.n_devices if mesh is not None else devices)
    with telemetry.span("campaign.run", lanes=n_slices * grid.cells,
                        launches=n_launches, devices=n_dev, seed=grid.seed):
        return _run_campaign(
            p, grid, backend=backend, use_cache=use_cache,
            cache_dir=cache_dir, devices=devices, chunk=chunk,
            max_cells_per_launch=max_cells_per_launch, horizon=horizon,
            checkpoint=checkpoint, max_retries=max_retries,
            retry_backoff_s=retry_backoff_s,
            on_slice_complete=on_slice_complete, reduce=reduce,
            n_bins=n_bins, mesh=mesh)


def _run_campaign(p: DeviceParams, grid: CampaignGrid, *, backend: str,
                  use_cache: bool, cache_dir: Optional[str],
                  devices: Optional[int], chunk: int,
                  max_cells_per_launch: Optional[int], horizon: str,
                  checkpoint: Optional[bool], max_retries: int,
                  retry_backoff_s: float, on_slice_complete, reduce: str,
                  n_bins: int, mesh) -> CampaignResult:
    """``run_campaign`` inside its ``repro.campaign.run`` span."""
    assert backend in ("pallas", "ref"), backend
    assert reduce in ("dense", "stream"), reduce
    streaming = reduce == "stream"
    if mesh is not None:
        devices = mesh.n_devices
    multi = mesh is not None and mesh.process_count > 1
    spec = grid.variation
    n_t, n_v, n_p, n_s = grid.shape
    n_c = grid.n_corners
    expect_shape = ((n_c, n_t, n_v, n_s) if spec is not None
                    else (n_t, n_v, n_s))
    key = _cache.campaign_key(p, grid, backend)
    n_steps = grid.n_steps
    if streaming:
        assert int(n_bins) >= 1, n_bins
        assert n_steps <= _STREAM_MAX_STEPS, (
            "streaming WER relies on exact integer steps in the kernel's "
            f"f32 crossing row: n_steps={n_steps} > {_STREAM_MAX_STEPS}")
        # streaming entries live under their own derived key: the payload
        # is a different tensor family (counts + histogram, n_bins-shaped)
        # and must never shadow — or be shadowed by — a dense entry
        red_key = _cache.content_key({"campaign": key, "kind": "reduced",
                                      "n_bins": int(n_bins), "v": 1})
        lead = (n_c, n_t) if spec is not None else (n_t,)
        expect_wer = lead + (n_v, n_p)
        expect_hist = lead + (n_v, int(n_bins))
        hist_values = _hist_step_values(n_steps, int(n_bins)) * grid.dt
        kmin_dev = jnp.asarray(
            _wer_threshold_steps(grid.pulse_widths, grid.dt, n_steps))

        def _reduced_result(wer, hist, **kw):
            return CampaignResult(
                grid=grid, backend=backend, crossing_time=None,
                reduced=True, wer_counts=np.asarray(wer).astype(np.int64),
                latency_hist=np.asarray(hist), hist_values=hist_values,
                **kw)

    def _load_whole():
        """This mode's durable whole-campaign entry, or None on miss."""
        with telemetry.span("campaign.cache_load"):
            if streaming:
                hit = _cache.load_arrays(red_key, cache_dir)
                if (hit is not None and "wer" in hit and "hist" in hit
                        and hit["wer"].shape == expect_wer
                        and hit["hist"].shape == expect_hist):
                    return hit
                return None
            hit = _cache.load(key, cache_dir)
        return hit if (hit is not None and hit.shape == expect_shape) else None

    if use_cache:
        whole = _load_whole()
        if whole is not None:
            if streaming:
                return _reduced_result(whole["wer"], whole["hist"],
                                       elapsed_s=0.0, from_cache=True,
                                       n_launches=0)
            return CampaignResult(grid=grid, backend=backend,
                                  crossing_time=whole, elapsed_s=0.0,
                                  from_cache=True, n_launches=0)

    n_static = _quantize_steps(n_steps, horizon) if chunk > 0 else n_steps
    n_slices = n_c * n_t
    slice_cells = bucket_cells(grid.cells)
    launches = _launch_spans(n_slices, slice_cells, max_cells_per_launch)
    # a single launch whose device plan needs no pad lanes takes the packed
    # block whole (a full slice is the array itself): pack it straight onto
    # the launch's devices, its tiles dealt round-robin over them, and undo
    # the deal on the crossing row (``_reduce_rows``, ``_fetch``)
    deal = 1
    if spec is None and len(launches) == 1:
        plan_dev, plan_cols = _device_plan(n_slices * slice_cells, devices)
        if plan_cols == n_slices * slice_cells:
            deal = plan_dev

    def _pack_inputs():
        """Pack the campaign's device inputs."""
        with telemetry.span("campaign.pack"):
            if spec is None:
                st, sd, sg, bd, sp = pack_campaign(grid, p, n_dev=deal)
                lp = None
            else:
                st, sd, sg, bd, lp, sp = pack_variation(grid, p)
        return st, sd, sg, bd, lp, sp

    def _bucket_pad(st, sd, sg, bd, lp):
        # total-plane pow2 bucket: corner count reaches the compile key
        # only through this logarithmic bucket (3 vs 4 corners usually
        # share a compiled shape; pinned by tests/test_variation.py)
        total = st.shape[1]
        pad = bucket_cells(total) - total
        if pad:
            st = jnp.pad(st, ((0, 0), (0, pad)))
            sd = jnp.pad(sd, (0, pad))
            sg = jnp.pad(sg, (0, pad))
            bd = jnp.pad(bd, (0, pad))
            fill = np.broadcast_to(
                np.array([[p.alpha], [p.b_aniso], [1.0]], np.float32),
                (3, pad))
            lp = jnp.concatenate([lp, jnp.asarray(fill)], axis=1)
        return st, sd, sg, bd, lp

    state, seeds, sigma, budget, lane_params, spans = _pack_inputs()
    single_variation = spec is not None and len(launches) == 1
    if single_variation:
        state, seeds, sigma, budget, lane_params = _bucket_pad(
            state, seeds, sigma, budget, lane_params)
        launches = [(0, n_slices)]

    ckpt = ((use_cache and len(launches) > 1) if checkpoint is None
            else bool(checkpoint))
    if multi:
        assert use_cache, ("multi-process campaigns rendezvous through the "
                           "content-addressed store; use_cache=False has "
                           "no channel to exchange slices")
        ckpt = True                # slice entries ARE the exchange channel
    skind = f"slice-reduced-{int(n_bins)}" if streaming else "slice-row7"

    def span_cols(a: int, b: int) -> Tuple[int, int]:
        c0, c1 = a * slice_cells, b * slice_cells
        if single_variation:
            c1 = state.shape[1]              # include the total-bucket pad
        return c0, c1

    def launch_plan(a: int, b: int):
        c0, c1 = span_cols(a, b)
        n_dev, plan_cols = _device_plan(c1 - c0, devices)
        statics = dict(p=p, dt=grid.dt, n_steps=n_static,
                       switch_threshold=float(grid.switch_threshold),
                       backend=backend, n_dev=n_dev, chunk=int(chunk))
        return c0, c1, plan_cols, statics

    def compile_launch(i: int) -> None:
        """Compile launch ``i``'s program before it is dispatched.  A
        compiler refusal is deterministic, so it raises here, outside every
        retry ladder; the dispatch then reuses the compiled executable
        (repeat calls hit the jit caches)."""
        _, _, cols, statics = launch_plan(*launches[i])
        shapes = [jax.ShapeDtypeStruct(x.shape[:-1] + (cols,), x.dtype)
                  for x in (state, seeds, sigma, budget)]
        lp = (None if lane_params is None else
              jax.ShapeDtypeStruct((lane_params.shape[0], cols),
                                   lane_params.dtype))
        with telemetry.span("campaign.compile", launch=i):
            _integrate_sharded.lower(*shapes, lp, **statics).compile()

    def dispatch(i: int):
        a, b = launches[i]
        dealt = {"deal": deal} if deal > 1 else {}
        with telemetry.span("campaign.dispatch", launch=i, **dealt):
            c0, c1, plan_cols, statics = launch_plan(a, b)
            st, sd, sg, bd, lp = _pad_lanes(
                state[:, c0:c1], seeds[c0:c1], sigma[c0:c1], budget[c0:c1],
                None if lane_params is None else lane_params[:, c0:c1],
                plan_cols - (c1 - c0), p)
            out = _integrate_sharded(st, sd, sg, bd, lp, **statics)
            if streaming:
                out = _reduce_rows(out, kmin_dev, n_slices=b - a,
                                   slice_cells=slice_cells, n_v=n_v, n_s=n_s,
                                   n_steps=n_steps, n_bins=int(n_bins),
                                   deal=deal)
        telemetry.count("campaign.launches")
        if dealt:
            telemetry.count("campaign.dealt_launches")
        telemetry.count("campaign.lanes", (b - a) * grid.cells)
        return out

    host_bytes = 0
    n_computed = 0

    def _fetch(out, i: int) -> Dict[str, np.ndarray]:
        """Sync launch ``i`` and pull its payload to host — the ONLY
        device-to-host transfer of the campaign, which ``host_bytes``
        meters (dense: the full (8, cells) block; streaming: the reduced
        counts + histogram, O(grid points))."""
        nonlocal host_bytes
        c0, c1 = span_cols(*launches[i])
        nbytes = sum(x.nbytes for x in (out if streaming else (out,)))
        with telemetry.span("campaign.sync", launch=i, bytes=nbytes):
            if streaming:
                wer_d, hist_d = out
                payload = {"wer": np.asarray(jax.block_until_ready(wer_d)),
                           "hist": np.asarray(jax.block_until_ready(hist_d))}
            else:
                row7 = np.asarray(jax.block_until_ready(out))[7]
                if deal > 1:
                    row7 = undeal_tiles(row7, deal)
                payload = {"row7": row7[: c1 - c0]}   # trim device-plan pad
        host_bytes += nbytes
        telemetry.count("campaign.host_bytes", nbytes)
        return payload

    def _payload_ok(hit, a: int, b: int) -> bool:
        if hit is None:
            return False
        if streaming:
            return ("wer" in hit and "hist" in hit
                    and hit["wer"].shape == (b - a, n_v, n_p)
                    and hit["hist"].shape == (b - a, n_v, int(n_bins)))
        c0, c1 = span_cols(a, b)
        return "row7" in hit and hit["row7"].shape == (c1 - c0,)

    def _store_slice(a: int, b: int, payload) -> None:
        with telemetry.span("campaign.cache_store"):
            _cache.store_arrays(
                _slice_key(key, a, b, chunk, horizon, skind), payload,
                header={"campaign": key, "span": [int(a), int(b)],
                        "kind": skind},
                cache_dir=cache_dir)

    def _load_slice(i: int):
        a, b = launches[i]
        with telemetry.span("campaign.cache_load", launch=i):
            hit = _cache.load_arrays(
                _slice_key(key, a, b, chunk, horizon, skind), cache_dir)
        return hit if _payload_ok(hit, a, b) else None

    def _compute(i: int, out=None) -> Dict[str, np.ndarray]:
        """Dispatch (if not already in flight) + sync launch ``i``, with the
        retry ladder."""
        nonlocal n_computed
        compile_launch(i)
        attempt = 0
        while True:
            try:
                if out is None:
                    out = dispatch(i)
                payload = _fetch(out, i)
                n_computed += 1
                return payload
            except Exception:
                out = None
                if attempt >= max_retries:
                    raise
                time.sleep(retry_backoff_s * (2.0 ** attempt))
                attempt += 1

    t0 = time.time()
    payloads: List[Optional[Dict[str, np.ndarray]]] = [None] * len(launches)
    n_resumed = 0
    whole = None

    if not multi:
        # dispatch every launch before syncing on any of them: jax dispatch
        # is async, so device compute and D2H transfers pipeline across
        # launches.  Checkpointed launches restore their stored payload
        # instead of dispatching at all; a failed dispatch is deferred to
        # the sync loop's retry ladder rather than aborting the other
        # launches' overlap.
        outs: List[Optional[object]] = [None] * len(launches)
        for i, (a, b) in enumerate(launches):
            if ckpt:
                hit = _load_slice(i)
                if hit is not None:
                    payloads[i] = hit
                    n_resumed += 1
                    continue
            compile_launch(i)
            try:
                outs[i] = dispatch(i)
            except Exception:                # retried in the sync loop
                outs[i] = None
        for i, (a, b) in enumerate(launches):
            if payloads[i] is not None:
                continue
            payloads[i] = _compute(i, out=outs[i])
            if ckpt:
                _store_slice(a, b, payloads[i])
            if on_slice_complete is not None:
                on_slice_complete(i, len(launches))
    else:
        owner = f"proc{mesh.process_index}"
        skeys = [_slice_key(key, a, b, chunk, horizon, skind)
                 for a, b in launches]

        def _claim(i: int, steal: bool = False) -> bool:
            with telemetry.span("campaign.cache_claim", launch=i):
                if steal:
                    return _cache.steal_claim(skeys[i], mesh.claim_ttl_s,
                                              cache_dir, owner=owner)
                return _cache.try_claim(skeys[i], cache_dir, owner=owner)

        def _release(i: int) -> None:
            with telemetry.span("campaign.cache_claim", launch=i):
                _cache.release_claim(skeys[i], cache_dir)

        def _claim_and_run(i: int) -> None:
            # holding the claim, re-check the whole-campaign entry: a peer
            # that already assembled retires the slice checkpoints, and
            # retirement is strictly ordered AFTER its whole store — so a
            # vanished slice is always covered by this check and a launch
            # is never integrated twice (absent a TTL steal)
            nonlocal whole
            whole = _load_whole()
            if whole is not None:
                _release(i)
                return
            try:
                payload = _compute(i)
            except Exception:
                _release(i)
                raise
            _store_slice(*launches[i], payload)
            _release(i)
            payloads[i] = payload
            if on_slice_complete is not None:
                on_slice_complete(i, len(launches))

        # pass A: each process walks the launch ring from its own offset,
        # claiming and integrating whatever no peer has started — with P
        # processes over L launches the fleet first-touches disjoint arcs,
        # so claims rarely collide and work splits ~L/P per process.
        start = (len(launches) * mesh.process_index) // mesh.process_count
        for j in range(len(launches)):
            if whole is not None:
                break
            i = (start + j) % len(launches)
            hit = _load_slice(i)
            if hit is not None:
                payloads[i] = hit
                n_resumed += 1
            elif _claim(i):
                _claim_and_run(i)

        # pass B: poll the store for peers' slices; steal claims older
        # than the mesh TTL (dead peer — the store's atomicity makes a
        # double-compute after a steal wasteful, never wrong); bail to the
        # whole-campaign entry if a peer already assembled and retired the
        # slice checkpoints (the retirement race, DESIGN.md §14).
        deadline = time.time() + max(10.0 * mesh.claim_ttl_s, 30.0)
        while whole is None and any(pl is None for pl in payloads):
            whole = _load_whole()
            if whole is not None:
                break
            for i in range(len(launches)):
                if whole is not None or payloads[i] is not None:
                    continue
                hit = _load_slice(i)
                if hit is not None:
                    payloads[i] = hit
                    n_resumed += 1
                elif _cache.claim_age_s(skeys[i], cache_dir) is None:
                    if _claim(i):
                        _claim_and_run(i)
                elif _claim(i, steal=True):
                    _claim_and_run(i)
            if whole is None and any(pl is None for pl in payloads):
                if time.time() > deadline:
                    raise RuntimeError(
                        f"campaign {key[:12]}: timed out waiting on peer "
                        f"slices (ttl {mesh.claim_ttl_s}s)")
                time.sleep(mesh.poll_s)
    elapsed = time.time() - t0

    if whole is not None:
        # a peer won the assembly; adopt its durable entry verbatim
        common = dict(elapsed_s=elapsed, from_cache=True,
                      n_launches=len(launches), n_resumed=n_resumed,
                      host_bytes=host_bytes, n_computed=n_computed)
        if streaming:
            return _reduced_result(whole["wer"], whole["hist"], **common)
        return CampaignResult(grid=grid, backend=backend,
                              crossing_time=whole, **common)

    with telemetry.span("campaign.assemble"):
        if streaming:
            wer_cat = np.concatenate([pl["wer"] for pl in payloads])
            hist_cat = np.concatenate([pl["hist"] for pl in payloads])
            if spec is not None:
                wer_cat = wer_cat.reshape(n_c, n_t, n_v, n_p)
                hist_cat = hist_cat.reshape(n_c, n_t, n_v, int(n_bins))
            if use_cache:
                with telemetry.span("campaign.cache_store"):
                    _cache.store_arrays(
                        red_key, {"wer": wer_cat, "hist": hist_cat},
                        header={"campaign": key, "kind": "reduced",
                                "n_bins": int(n_bins), "backend": backend},
                        cache_dir=cache_dir)
            result = _reduced_result(wer_cat, hist_cat, elapsed_s=elapsed,
                                     n_launches=len(launches),
                                     n_resumed=n_resumed,
                                     host_bytes=host_bytes,
                                     n_computed=n_computed)
        else:
            # clip the quantized-horizon sentinel (n_static) back to the
            # grid's horizon: real crossings are <= budget == n_steps and
            # pass unchanged.  float64 before the dt multiply — in f32 the
            # sentinel n_steps*dt rounds below the f64 horizon and
            # never-crossed lanes would leak into the switched-only
            # latency reductions
            row7 = np.minimum(
                np.concatenate([pl["row7"] for pl in payloads]
                               ).astype(np.float64),
                float(n_steps))
            crossing = np.empty(expect_shape)
            for si, (lo, hi) in enumerate(spans):
                plane = row7[lo:hi].reshape(n_v, n_s) * grid.dt
                if spec is None:
                    crossing[si] = plane
                else:
                    crossing[si // n_t, si % n_t] = plane
            if use_cache:
                with telemetry.span("campaign.cache_store"):
                    _cache.store(key, crossing,
                                 header={"params": dataclasses.asdict(p),
                                         "grid": dataclasses.asdict(grid),
                                         "backend": backend},
                                 cache_dir=cache_dir)
            result = CampaignResult(
                grid=grid, backend=backend, crossing_time=crossing,
                elapsed_s=elapsed, n_launches=len(launches),
                n_resumed=n_resumed, host_bytes=host_bytes,
                n_computed=n_computed)
        if ckpt:
            # the whole-campaign entry is durable (or caching is off and
            # the result is in hand) — retire the per-slice resume
            # checkpoints
            with telemetry.span("campaign.cache_store"):
                for a, b in launches:
                    _cache.drop_arrays(
                        _slice_key(key, a, b, chunk, horizon, skind),
                        cache_dir)
    return result

"""Stochastic write path: write-verify programming through LLG transients.

The read path got functional in PR 2 (``imc.analog_pipeline``); this module
does the same for *writes* — the side the paper's headline claims are about
(~8x lower write latency, ~9x lower write energy than MTJs).  Instead of
assuming every write succeeds in one nominal pulse (the closed-form
``t_write``/``e_write`` constants in ``circuit.subarray``), a write-verify
scheduler programs arrays through actual thermal LLG transients:

  1. issue one fixed-width pulse per cell (a single-point Monte-Carlo
     campaign through ``campaign.run_campaign`` — the Pallas thermal kernel
     for the AFMTJ, the engine's FM scan tile for the MTJ baseline),
  2. read switching success back from the kernel's first-crossing row
     (crossed within the pulse <=> the verify read sees the new state),
  3. re-pulse only the failed cells (bit-selective rewrite: per-column
     write drivers mask passing bits) with fresh thermal samples, up to
     ``max_attempts`` rounds.

What comes out is *measured*: per-cell write latency / energy
distributions (mean + tail percentiles), retry histograms, and residual
bit-error rates as a function of pulse voltage, width and temperature —
the quantities a pipelined IMC controller actually schedules against
(``circuit.subarray.make_subarray(..., write_percentile=...)`` consumes
them; ``imc.mapping.write_energy_accuracy_surface`` turns the residual
BER into an accuracy-vs-write-energy surface).  See DESIGN.md §7.

Modeling conventions (documented, not hidden):

* **Independent attempts** — the verify interval re-thermalizes a failed
  cell inside its unswitched well, so each retry is an independent thermal
  trial (fresh Boltzmann initial tilt + fresh noise stream per round).
  Attempt counts are then geometric in the single-pulse WER, which the
  retry tests pin.
* **Two-state energy** — per-attempt energy integrates V^2 G(t) with the
  junction at G_P until the recorded crossing and at G_AP for the pulse
  remainder (failed attempts: G_P for the full pulse), plus the driver
  line-charge overhead ``t_rc`` at G_P.  This reproduces the deterministic
  ``simulate_write`` energies to a few percent (the reversal itself is fast
  compared to the incubation) and needs only the first-crossing row.
* **Verify cost** — ``t_verify``/``e_verify`` default to 0: in the
  pipelined controller the verify sense overlaps the next attempt's line
  charge (paper Sec. III-B).  Both are explicit policy knobs for
  non-pipelined accounting.

Process variation (DESIGN.md §9): ``WritePolicy.variation`` programs the
array as *sampled devices* — a single-corner ``VariationSpec`` draws each
cell's alpha/B_k/volume/RA once, holds the draw across that cell's
retries (a retry re-pulses the same junction with fresh thermal history),
and scales both the STT drive and the energy accounting by the cell's own
conductance; ``write_verify_corners`` sweeps a multi-corner spec into
per-corner measured distributions on paired random numbers.

Performance note (DESIGN.md §8): retry rounds are recompile-free.  The
engine pads each round's shrinking cell set to a power-of-two shape bucket
(``campaign.bucket_cells`` — extra lanes carry a zero step budget and cost
nothing), the per-round seed and Brown sigma are traced kernel inputs, and
the pulse horizon rides the per-lane step-budget row under a
power-of-two-quantized compiled horizon — so a ``max_attempts``-round
schedule compiles O(log cells) times, not once per round, and a
``write_surface`` sweep over (temperature x voltage x pulse) reuses those
same compiles across its whole grid.  ``ArrayWriteResult.rounds`` records
the rounds actually run; ``tests/test_fused_engine.py`` pins rounds vs XLA
compiles.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro.campaign.engine import EARLY_EXIT_CHUNK, run_campaign, run_ensemble
from repro.campaign.grid import CampaignGrid
from repro.core.params import (AFMTJ_PARAMS, MTJ_PARAMS, DeviceParams,
                               VariationSpec)
from repro.imc.write_margin import DEVICE_DT


def _params_for(kind: str) -> DeviceParams:
    assert kind in ("afmtj", "mtj"), kind
    return AFMTJ_PARAMS if kind == "afmtj" else MTJ_PARAMS


@functools.lru_cache(maxsize=None)
def nominal_pulse(kind: str, v_write: float = 1.0) -> float:
    """Device-nominal per-attempt pulse [s]: the deterministic mean switching
    time x the 2% pulse margin (``circuit.subarray._characterize_write``).
    Thermal retries cover the tail the deterministic solve cannot see."""
    from repro.circuit.subarray import _characterize_write

    t_sw, _ = _characterize_write(kind, float(v_write))
    return float(t_sw)


@dataclasses.dataclass(frozen=True)
class WritePolicy:
    """Write-verify scheduling knobs (hashable -> usable as a cache key)."""

    v_write: float = 1.0
    pulse: Optional[float] = None     # per-attempt pulse [s]; None = nominal
    pulse_margin: float = 1.5         # x nominal when pulse is None: per-
                                      # attempt thermal margin (wer1 ~5% for
                                      # the AFMTJ at 1 V; retries mop up the
                                      # tail instead of a 2x worst-case pulse)
    max_attempts: int = 8
    t_rc: float = 40e-12              # driver line-charge overhead / attempt
    t_verify: float = 0.0             # verify read latency / attempt
    e_verify: float = 0.0             # verify read energy / attempt [J]
    temperature: Optional[float] = None   # None = device default (300 K)
    dt: Optional[float] = None        # None = per-device campaign step
    seed: int = 0
    backend: str = "pallas"
    use_cache: bool = True
    # Optional single-corner process-variation spec (DESIGN.md §9): D2D
    # parameter draws are per *device* and persist across retry rounds — a
    # retry re-pulses the same junction with fresh thermal history.  Use
    # ``write_verify_corners`` to sweep the corners of a multi-corner spec.
    variation: Optional[VariationSpec] = None

    def resolved_pulse(self, kind: str) -> float:
        if self.pulse is not None:
            return float(self.pulse)
        return float(nominal_pulse(kind, self.v_write) * self.pulse_margin)

    def resolved_dt(self, kind: str) -> float:
        return float(self.dt if self.dt is not None else DEVICE_DT[kind])

    @property
    def cycle_overhead(self) -> float:
        return self.t_rc + self.t_verify


@dataclasses.dataclass(frozen=True)
class ArrayWriteResult:
    """Measured write-verify statistics for one batch of cell writes."""

    kind: str
    policy: WritePolicy
    pulse: float                  # resolved per-attempt pulse [s]
    dt: float
    attempts: np.ndarray          # (cells,) pulses issued (1..max_attempts)
    success: np.ndarray           # (cells,) bool — verified within budget
    crossing_time: np.ndarray     # (cells,) [s] within the successful
                                  # attempt; NaN where the cell never wrote
    energy: np.ndarray            # (cells,) total write energy [J]
    elapsed_s: float              # simulation wall-clock
    rounds: int = 0               # retry rounds actually integrated

    @property
    def cycle(self) -> float:
        """One attempt's latency slot: line charge + pulse + verify."""
        return self.policy.cycle_overhead + self.pulse

    @property
    def latency(self) -> np.ndarray:
        """(cells,) total per-cell write latency [s]."""
        return self.attempts * self.cycle

    @property
    def attempts_mean(self) -> float:
        return float(self.attempts.mean()) if self.attempts.size else 0.0

    @property
    def residual_ber(self) -> float:
        """Fraction of cells still holding the wrong state after the
        attempt budget — the bit-error rate the read path inherits.
        Zero-cell batches (nothing to flip) report 0 errors, not NaN."""
        return float(1.0 - self.success.mean()) if self.success.size else 0.0

    @property
    def single_pulse_wer(self) -> float:
        """First-attempt failure fraction (the per-pulse WER the geometric
        retry statistics are built on).  Counts cells that did *not* verify
        on their first pulse — robust at any attempt budget (with
        ``max_attempts == 1`` a failed cell still shows ``attempts == 1``)."""
        if not self.success.size:
            return 0.0
        return float(1.0 - (self.success & (self.attempts == 1)).mean())

    def latency_percentile(self, q) -> np.ndarray:
        return np.percentile(self.latency, q)

    def energy_mean(self) -> float:
        return float(self.energy.mean()) if self.energy.size else 0.0

    def retry_histogram(self) -> np.ndarray:
        """(max_attempts + 1,) count of cells by attempts used (index 0
        unused — every written cell takes at least one pulse)."""
        return np.bincount(self.attempts,
                           minlength=self.policy.max_attempts + 1)

    def row_attempts(self, cols: int) -> np.ndarray:
        """(rows,) attempts a *row-granular* controller pays per row: failed
        bits re-pulse bit-selectively, but the row op retires only when its
        slowest bit verifies — the row cost is the max over its cells."""
        cells = self.attempts.size
        assert cells % cols == 0, (cells, cols)
        return self.attempts.reshape(cells // cols, cols).max(axis=1)

    def row_latency_percentile(self, cols: int, q: float) -> float:
        """Row write time [s] at percentile ``q`` over sampled rows — the
        stage time a pipelined controller should schedule (resolution is
        limited by the number of sampled rows)."""
        return float(np.percentile(self.row_attempts(cols), q) * self.cycle)


def write_verify(kind: str, n_cells: int,
                 policy: WritePolicy = WritePolicy()) -> ArrayWriteResult:
    """Write ``n_cells`` cells (P -> AP) through the retry scheduler.

    Each round is one single-point campaign over the still-unwritten cells:
    fresh Boltzmann initial states and fresh counter-RNG thermal streams
    (``CampaignGrid.seed`` folds in the round index), horizon = one pulse.
    Success is read off the first-crossing row; failures re-enter the next
    round.  Deterministic at a fixed ``policy.seed``.

    With ``policy.variation`` (a single-corner spec) each cell is a
    *sampled device*: corner/D2D parameter rows ride the kernel's
    variation plane, stay fixed across that cell's retries, and scale the
    two-state energy accounting by the cell's own conductance — slow-
    corner arrays retry more and pay more energy per attempt
    (``_write_verify_variation``).
    """
    if policy.variation is not None:
        return _write_verify_variation(kind, n_cells, policy)
    p = _params_for(kind)
    v = float(policy.v_write)
    pulse = policy.resolved_pulse(kind)
    dt = policy.resolved_dt(kind)
    temp = float(policy.temperature if policy.temperature is not None
                 else p.temperature)
    g_p = 1.0 / p.r_parallel
    g_ap = 1.0 / p.r_antiparallel
    e_rc = v * v * g_p * policy.t_rc

    attempts = np.zeros(n_cells, dtype=np.int64)
    success = np.zeros(n_cells, dtype=bool)
    crossing = np.full(n_cells, np.nan)
    energy = np.zeros(n_cells)
    remaining = np.arange(n_cells)

    t0 = time.time()
    rounds = 0
    for rnd in range(policy.max_attempts):
        if remaining.size == 0:
            break
        rounds += 1
        grid = CampaignGrid(
            voltages=(v,), pulse_widths=(pulse,), temperatures=(temp,),
            n_samples=int(remaining.size), dt=dt,
            seed=policy.seed * 1009 + rnd)
        res = run_campaign(p, grid, backend=policy.backend,
                           use_cache=policy.use_cache)
        ct = res.crossing_time[0, 0]                  # (remaining,)
        ok = ct <= pulse

        attempts[remaining] += 1
        # two-state energy: G_P up to the crossing, G_AP for the remainder;
        # failed attempts sit at G_P the whole pulse
        e_att = np.where(ok,
                         v * v * (g_p * ct + g_ap * (pulse - ct)),
                         v * v * g_p * pulse)
        energy[remaining] += e_att + e_rc + policy.e_verify
        done = remaining[ok]
        success[done] = True
        crossing[done] = ct[ok]
        remaining = remaining[~ok]
    elapsed = time.time() - t0

    return ArrayWriteResult(kind=kind, policy=policy, pulse=pulse, dt=dt,
                            attempts=attempts, success=success,
                            crossing_time=crossing, energy=energy,
                            elapsed_s=elapsed, rounds=rounds)


def _write_verify_variation(kind: str, n_cells: int,
                            policy: WritePolicy) -> ArrayWriteResult:
    """Write-verify under per-device process variation (DESIGN.md §9).

    One D2D draw up front fixes every cell's device sample (alpha, B_k,
    volume -> Brown sigma / Boltzmann tilt, and the RA factor -> drive and
    energy conductances); each retry round then integrates the surviving
    cells through ``run_ensemble`` with the sampled rows on the kernel's
    variation plane — the lanes renumber per round but index back into the
    same per-device rows, so a cell's parameters persist across its
    retries while its thermal history is fresh (round-folded seed).
    Rounds stay recompile-free exactly like the nominal path: shape
    buckets + pow2-quantized horizon under a per-lane budget.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import llg

    p = _params_for(kind)
    spec = policy.variation
    assert spec is not None and spec.n_corners == 1, (
        "write_verify programs one corner's array; sweep corners with "
        "write_verify_corners")
    v = float(policy.v_write)
    pulse = policy.resolved_pulse(kind)
    dt = policy.resolved_dt(kind)
    temp = float(policy.temperature if policy.temperature is not None
                 else p.temperature)
    # horizon: one step past the pulse so the never-crossed sentinel
    # strictly exceeds it (same rule as CampaignGrid.n_steps)
    n_steps = int(math.ceil(pulse / dt)) + 1

    rows = spec.lane_rows(p, spec.corners[0], n_cells, dt, temperature=temp)
    kernel_rows = rows.kernel_rows                      # (3, n_cells) f32
    g_p = (1.0 / p.r_parallel) * rows.g_scale           # per-cell [S]
    g_ap = (1.0 / p.r_antiparallel) * rows.g_scale
    e_rc = v * v * g_p * policy.t_rc

    attempts = np.zeros(n_cells, dtype=np.int64)
    success = np.zeros(n_cells, dtype=bool)
    crossing = np.full(n_cells, np.nan)
    energy = np.zeros(n_cells)
    remaining = np.arange(n_cells)

    t0 = time.time()
    rounds = 0
    for rnd in range(policy.max_attempts):
        if remaining.size == 0:
            break
        rounds += 1
        m = int(remaining.size)
        seed_r = policy.seed * 1009 + rnd
        # fresh Boltzmann tilt per round, scaled by each survivor's own
        # theta0 (mirrors grid._plane_tilt_draws at t_index 0)
        key = jax.random.fold_in(jax.random.PRNGKey(seed_r), 0)
        k_th, k_ph = jax.random.split(key)
        zs = jnp.abs(jax.random.normal(k_th, (m,)))
        ph = jax.random.uniform(k_ph, (m,), maxval=2 * jnp.pi)
        th = zs * jnp.asarray(rows.theta0[remaining], jnp.float32) + 0.01
        m0 = jax.vmap(lambda t, f: llg.initial_state(p, t, f))(th, ph)
        res = run_ensemble(
            p, m0, jnp.full((m,), v, jnp.float32), dt, n_steps,
            seed=seed_r, backend=policy.backend, chunk=EARLY_EXIT_CHUNK,
            lane_params=kernel_rows[:, remaining],
            sigma_lanes=rows.sigma[remaining])
        ct = res.crossing_time                          # (m,) [s]
        ok = ct <= pulse

        attempts[remaining] += 1
        gp_r, gap_r = g_p[remaining], g_ap[remaining]
        e_att = np.where(ok,
                         v * v * (gp_r * ct + gap_r * (pulse - ct)),
                         v * v * gp_r * pulse)
        energy[remaining] += e_att + e_rc[remaining] + policy.e_verify
        done = remaining[ok]
        success[done] = True
        crossing[done] = ct[ok]
        remaining = remaining[~ok]
    elapsed = time.time() - t0

    return ArrayWriteResult(kind=kind, policy=policy, pulse=pulse, dt=dt,
                            attempts=attempts, success=success,
                            crossing_time=crossing, energy=energy,
                            elapsed_s=elapsed, rounds=rounds)


def write_verify_corners(
    kind: str, n_cells: int,
    policy: WritePolicy = WritePolicy(),
    spec: Optional[VariationSpec] = None,
) -> Dict[str, ArrayWriteResult]:
    """Measured per-corner write distributions: one retry schedule per
    process corner of ``spec`` (default: ``policy.variation``).

    Corners share D2D draws and per-round tilt/thermal streams (common
    random numbers — ``VariationSpec.lane_factors`` is salted by stream,
    not corner position), so corner-to-corner retry/latency/energy deltas
    are paired per cell.  Returns ``{corner_name: ArrayWriteResult}``.
    """
    spec = spec if spec is not None else policy.variation
    assert spec is not None, "write_verify_corners needs a VariationSpec"
    return {
        corner.name: write_verify(
            kind, n_cells,
            dataclasses.replace(policy, variation=spec.at_corner(ci)))
        for ci, corner in enumerate(spec.corners)
    }


def program_bits(target: np.ndarray, kind: str = "afmtj",
                 policy: WritePolicy = WritePolicy(),
                 current: Optional[np.ndarray] = None,
                 ) -> Tuple[ArrayWriteResult, np.ndarray]:
    """Program a (rows, cols) bit matrix; returns the write statistics of
    the flipped cells plus the residual bit-error map.

    Only cells whose target differs from ``current`` (default: all-zeros
    erased array) get pulses; both switching directions are modeled by the
    same P -> AP transient (symmetric wells to first order).  The error map
    marks cells still holding stale data after ``policy.max_attempts`` —
    the map ``imc.analog_pipeline`` injects into weight programming.
    """
    target = np.asarray(target)
    assert target.ndim == 2, target.shape
    cur = (np.zeros_like(target) if current is None
           else np.asarray(current))
    flip = target != cur
    res = write_verify(kind, int(flip.sum()), policy)
    error_map = np.zeros(target.shape, dtype=bool)
    error_map[flip] = ~res.success
    return res, error_map


# --------------------------------------------------------------------------
# Measured subarray write timings — the circuit-layer client
# (``circuit.subarray.make_subarray(..., write_percentile=...)``).

@dataclasses.dataclass(frozen=True)
class MeasuredWrite:
    """Distribution summary the subarray timing model consumes."""

    t_write: float            # row write time at ``percentile`` [s]
    e_write_bit: float        # mean per-cell write energy [J]
    attempts_mean: float      # per-cell mean pulses
    attempts_row_mean: float  # mean over rows of the per-row max
    single_pulse_wer: float
    residual_ber: float
    pulse: float              # per-attempt pulse [s]
    percentile: float


@functools.lru_cache(maxsize=None)
def measured_write_timings(
    kind: str,
    v_write: float = 1.0,
    cols: int = 256,
    percentile: float = 99.0,
    t_rc: float = 40e-12,
    pulse: Optional[float] = None,
    max_attempts: int = 8,
    n_rows: int = 16,
    seed: int = 0,
    use_cache: bool = True,
    variation: Optional[VariationSpec] = None,
) -> MeasuredWrite:
    """Row-granular write timing from the measured retry distribution.

    Samples ``n_rows`` rows of ``cols`` cells through ``write_verify`` and
    reduces to the ``percentile`` row write time (max-over-row attempts x
    cycle) and the mean per-bit energy.  lru-cached in process; the
    underlying campaigns hit the on-disk cache, so hierarchy rebuilds pay
    only the reduction.  Percentile resolution is bounded by ``n_rows``.
    ``variation`` (hashable, single-corner) sizes the timings against a
    process corner's measured distribution instead of the nominal device.
    """
    policy = WritePolicy(v_write=float(v_write), pulse=pulse, t_rc=float(t_rc),
                         max_attempts=int(max_attempts), seed=int(seed),
                         use_cache=use_cache, variation=variation)
    res = write_verify(kind, int(cols) * int(n_rows), policy)
    row_att = res.row_attempts(int(cols))
    return MeasuredWrite(
        t_write=res.row_latency_percentile(int(cols), float(percentile)),
        e_write_bit=res.energy_mean(),
        attempts_mean=res.attempts_mean,
        attempts_row_mean=float(row_att.mean()),
        single_pulse_wer=res.single_pulse_wer,
        residual_ber=res.residual_ber,
        pulse=res.pulse,
        percentile=float(percentile),
    )


# --------------------------------------------------------------------------
# Sweep helper: residual-BER / latency / energy surfaces over the write
# operating point (pulse voltage, width, temperature).

@dataclasses.dataclass(frozen=True)
class WriteSurface:
    """Measured write statistics over (temperature x voltage x pulse)."""

    kind: str
    voltages: Tuple[float, ...]
    pulses: Tuple[float, ...]
    temperatures: Tuple[float, ...]
    residual_ber: np.ndarray     # (n_T, n_V, n_P)
    attempts_mean: np.ndarray    # (n_T, n_V, n_P)
    latency_mean: np.ndarray     # (n_T, n_V, n_P) [s]
    energy_mean: np.ndarray      # (n_T, n_V, n_P) [J]


def write_surface(
    kind: str,
    voltages: Tuple[float, ...] = (1.0,),
    pulses: Optional[Tuple[float, ...]] = None,
    temperatures: Optional[Tuple[float, ...]] = None,
    n_cells: int = 256,
    policy: WritePolicy = WritePolicy(),
) -> WriteSurface:
    """Residual bit-error / retry / cost maps vs the write operating point.

    ``pulses=None`` uses the device-nominal pulse only; axes ride the
    scheduler cell-by-cell (one retry ladder per grid point), so keep the
    grid small on CPU-interpret runs.
    """
    p = _params_for(kind)
    pulses = tuple(float(x) for x in (
        pulses if pulses is not None else (policy.resolved_pulse(kind),)))
    temperatures = tuple(float(x) for x in (
        temperatures if temperatures is not None else (p.temperature,)))
    voltages = tuple(float(x) for x in voltages)
    shape = (len(temperatures), len(voltages), len(pulses))
    ber = np.zeros(shape)
    att = np.zeros(shape)
    lat = np.zeros(shape)
    en = np.zeros(shape)
    for ti, temp in enumerate(temperatures):
        for vi, v in enumerate(voltages):
            for pi, pw in enumerate(pulses):
                pol = dataclasses.replace(policy, v_write=v, pulse=pw,
                                          temperature=temp)
                r = write_verify(kind, n_cells, pol)
                ber[ti, vi, pi] = r.residual_ber
                att[ti, vi, pi] = r.attempts_mean
                lat[ti, vi, pi] = float(r.latency.mean())
                en[ti, vi, pi] = r.energy_mean()
    return WriteSurface(kind=kind, voltages=voltages, pulses=pulses,
                        temperatures=temperatures, residual_ber=ber,
                        attempts_mean=att, latency_mean=lat, energy_mean=en)

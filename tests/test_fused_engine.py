"""Fused campaign-engine tests (DESIGN.md §8): one-launch temperature
packing, compile budgets, shape buckets, chunked early exit.

The §8 restructure has two invariants worth pinning hard:

* **bit-compatibility** — fusing the temperature axis, bucketing lane
  counts, quantizing the compiled horizon and exiting tiles early must not
  change a single crossing step relative to the old fixed-horizon,
  one-launch-per-temperature engine;
* **compile economy** — a multi-temperature campaign costs one XLA
  compile, and a shrinking write-verify retry schedule stays within its
  shape-bucket budget instead of compiling once per round.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import (CampaignGrid, bucket_cells, pack_campaign,
                            pack_plane, run_campaign, run_ensemble)
from repro.campaign.engine import _integrate_sharded, brown_sigma
from repro.core import llg
from repro.core.params import AFMTJ_PARAMS, MTJ_PARAMS
from repro.kernels import noise, ops, ref
from repro.kernels.llg_rk4 import CELL_TILE
from repro.runtime import telemetry

TEMPS = (260.0, 300.0, 340.0)


@pytest.fixture(scope="module")
def fused_grid():
    # 0.6 V lanes mostly never cross, 1.2 V lanes all do — the fixture
    # exercises both the crossing and the sentinel paths of every reduction
    return CampaignGrid(voltages=(0.6, 1.2), pulse_widths=(120e-12, 250e-12),
                        temperatures=TEMPS, n_samples=24, dt=0.1e-12, seed=0)


@pytest.fixture(scope="module")
def fused_result(fused_grid):
    return run_campaign(AFMTJ_PARAMS, fused_grid, use_cache=False)


# ------------------------------------------------------------ shape buckets
def test_bucket_cells_power_of_two_tiles():
    assert bucket_cells(1) == CELL_TILE
    assert bucket_cells(CELL_TILE) == CELL_TILE
    assert bucket_cells(CELL_TILE + 1) == 2 * CELL_TILE
    assert bucket_cells(3 * CELL_TILE) == 4 * CELL_TILE
    assert bucket_cells(4 * CELL_TILE) == 4 * CELL_TILE
    # buckets are monotone and cover every count
    for n in (1, 100, 513, 1500, 5000):
        b = bucket_cells(n)
        assert b >= n and b % CELL_TILE == 0
        assert (b // CELL_TILE) & (b // CELL_TILE - 1) == 0  # pow2 tiles


def test_pack_campaign_layout(fused_grid):
    state, seeds, sigma, budget, spans = pack_campaign(fused_grid,
                                                       AFMTJ_PARAMS)
    n_t = len(TEMPS)
    per = state.shape[1] // n_t
    assert per == bucket_cells(fused_grid.cells)
    assert seeds.shape == sigma.shape == budget.shape == (state.shape[1],)
    assert spans == [(ti * per, ti * per + fused_grid.cells)
                     for ti in range(n_t)]
    sig = np.asarray(sigma)
    bud = np.asarray(budget)
    for ti, t in enumerate(TEMPS):
        lo = ti * per
        # the whole slice carries that temperature's Brown sigma ...
        np.testing.assert_allclose(
            sig[lo:lo + per], brown_sigma(AFMTJ_PARAMS, fused_grid.dt, t))
        # ... real lanes get the full horizon, bucket padding gets 0
        assert (bud[lo:lo + fused_grid.cells] == fused_grid.n_steps).all()
        assert (bud[lo + fused_grid.cells:lo + per] == 0.0).all()
    # hotter slices fluctuate harder
    assert sig[0] < sig[-1]


# ------------------------------------------------ the jitted pack program
def _eager_pack_campaign(grid, p):
    """The pack as it ran before it became one program: eager ``jax.random``
    and ``jnp`` ops, slice by slice, concatenated on the host side."""
    from repro.campaign.grid import pack_soa
    from repro.core.device import thermal_theta0
    from repro.core.montecarlo import thermal_sigma

    states, seed_rows, sigma_rows, budget_rows, spans = [], [], [], [], []
    offset = 0
    for ti, temp in enumerate(grid.temperatures):
        p_t = (p if temp == p.temperature
               else dataclasses.replace(p, temperature=float(temp)))
        key = jax.random.fold_in(jax.random.PRNGKey(grid.seed), ti)
        k_th, k_ph = jax.random.split(key)
        zs = jnp.abs(jax.random.normal(k_th, (grid.cells,)))
        ph = jax.random.uniform(k_ph, (grid.cells,), maxval=2 * jnp.pi)
        th = zs * thermal_theta0(p_t) + 0.01
        m0 = jax.vmap(lambda t, f: llg.initial_state(p_t, t, f))(th, ph)
        v = jnp.repeat(jnp.asarray(grid.voltages, jnp.float32),
                       grid.n_samples)
        st = pack_soa(m0, v)
        padded = st.shape[1]
        lane = jnp.arange(padded)
        base = (grid.seed * 0x9E3779B1 + ti * 0x85EBCA6B) & 0xFFFFFFFF
        states.append(st)
        seed_rows.append(noise.cell_seeds(base, padded))
        sigma_rows.append(jnp.full((padded,), thermal_sigma(p_t, grid.dt),
                                   jnp.float32))
        budget_rows.append(jnp.where(lane < grid.cells, float(grid.n_steps),
                                     0.0).astype(jnp.float32))
        spans.append((offset, offset + grid.cells))
        offset += padded
    return (jnp.concatenate(states, axis=1), jnp.concatenate(seed_rows),
            jnp.concatenate(sigma_rows), jnp.concatenate(budget_rows), spans)


def _cell_grid(seed, n_samples=700):
    """The WER cell's axes (bench/traffic/wer_campaign.json) at a CPU size;
    700 samples leave bucket padding in every slice."""
    return CampaignGrid(voltages=(0.6, 1.2), pulse_widths=(120e-12, 250e-12),
                        temperatures=(300.0, 350.0, 400.0),
                        n_samples=n_samples, dt=0.1e-12, seed=seed)


@pytest.mark.parametrize("p", [AFMTJ_PARAMS, MTJ_PARAMS],
                         ids=["afmtj", "mtj"])
@pytest.mark.parametrize("seed", [0, 3100000063])
def test_pack_program_matches_eager_pack(seed, p):
    """One compiled pack program gives the eager pack's bits: seeds, sigma
    and budget rows, and the state block too (the tilt's multiply-add is
    kept from contracting into an FMA), with the same spans."""
    grid = _cell_grid(seed)
    got = pack_campaign(grid, p)
    want = _eager_pack_campaign(grid, p)
    for g, w in zip(got[:4], want[:4]):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert got[4] == want[4]


def test_pack_program_traces_once_per_shape():
    """A new seed reuses the traced and compiled pack; a new sample count
    is a new shape, traced once."""
    pack_campaign(_cell_grid(1, n_samples=300), AFMTJ_PARAMS)
    before = telemetry.snapshot()
    pack_campaign(_cell_grid(2, n_samples=300), AFMTJ_PARAMS)
    mid = telemetry.snapshot()
    pack_campaign(_cell_grid(2, n_samples=301), AFMTJ_PARAMS)
    after = telemetry.snapshot()
    for name in ("campaign.pack_traces", "xla.compiles"):
        assert mid.get(name, 0) == before.get(name, 0), name
    assert (after["campaign.pack_traces"]
            == mid.get("campaign.pack_traces", 0) + 1)


# ----------------------------------------------- fused-T bit-compatibility
def test_fused_campaign_bit_identical_to_per_temperature_launches(
        fused_grid, fused_result):
    """The pre-§8 engine: one fixed-horizon launch per temperature, Brown's
    sigma a compile-time scalar.  Reproduce it literally (pack_plane +
    scalar-sigma kernel, no budgets, no early exit) and demand the fused
    one-launch result match every crossing step bit-for-bit."""
    n_v, n_s = len(fused_grid.voltages), fused_grid.n_samples
    for ti, temp in enumerate(TEMPS):
        p_t = dataclasses.replace(AFMTJ_PARAMS, temperature=temp)
        state, seeds = pack_plane(fused_grid, p_t, ti)
        sigma = brown_sigma(AFMTJ_PARAMS, fused_grid.dt, temp)
        out = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, fused_grid.dt,
                                  fused_grid.n_steps, sigma)
        old = np.asarray(out[7, :fused_grid.cells], np.float64) \
            .reshape(n_v, n_s) * fused_grid.dt
        np.testing.assert_array_equal(fused_result.crossing_time[ti], old)


def test_early_exit_and_quantization_bit_identical(fused_grid, fused_result):
    """chunk=0 disables early exit AND horizon quantization — the exact
    fixed-horizon launch.  Crossing times must agree bit-for-bit."""
    exact = run_campaign(AFMTJ_PARAMS, fused_grid, use_cache=False, chunk=0)
    np.testing.assert_array_equal(fused_result.crossing_time,
                                  exact.crossing_time)
    # the fixture grid must actually exercise both outcomes
    horizon = fused_grid.n_steps * fused_grid.dt
    assert (fused_result.crossing_time < horizon).any()
    assert (fused_result.crossing_time >= horizon).any()


def test_pipelined_launch_split_matches_single_launch(fused_grid,
                                                      fused_result):
    """max_cells_per_launch splits along temperature slices; all launches
    dispatch before the first sync and the surface is unchanged."""
    per = bucket_cells(fused_grid.cells)
    split = run_campaign(AFMTJ_PARAMS, fused_grid, use_cache=False,
                         max_cells_per_launch=per)
    assert split.n_launches == len(TEMPS)
    assert fused_result.n_launches == 1
    np.testing.assert_array_equal(split.crossing_time,
                                  fused_result.crossing_time)


# ------------------------------------------------------------ compile pins
def test_multi_temperature_campaign_compiles_once(fused_grid):
    _integrate_sharded._clear_cache()
    res = run_campaign(AFMTJ_PARAMS, fused_grid, use_cache=False)
    assert res.n_launches == 1
    assert _integrate_sharded._cache_size() == 1
    # a second campaign at different seed/temperatures reuses the compile:
    # sigma, seeds and initial states are all traced data now
    grid2 = dataclasses.replace(fused_grid, seed=7,
                                temperatures=(250.0, 310.0, 370.0))
    run_campaign(AFMTJ_PARAMS, grid2, use_cache=False)
    assert _integrate_sharded._cache_size() == 1


def test_write_verify_stays_within_bucket_compile_budget():
    """A shrinking retry schedule (1280 -> ~600 -> ~280 -> ...) touches two
    shape buckets (2 and 1 ``CELL_TILE`` tiles): compiles must stay below
    the round count."""
    from repro.imc.write_path import WritePolicy, write_verify

    _integrate_sharded._clear_cache()
    pol = WritePolicy(v_write=1.0, pulse=130e-12, max_attempts=3, seed=5,
                      use_cache=False)
    r = write_verify("afmtj", CELL_TILE + CELL_TILE // 4, pol)
    assert r.rounds == 3                      # short pulse: retries happen
    assert _integrate_sharded._cache_size() <= 2 < r.rounds


# ------------------------------------------------- kernel-level invariants
def _packed_states(cells, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    th = jax.random.uniform(k1, (cells,), minval=0.05, maxval=0.25)
    ph = jax.random.uniform(k2, (cells,), minval=0.0, maxval=6.28)
    m0 = jax.vmap(lambda t, f: llg.initial_state(AFMTJ_PARAMS, t, f))(th, ph)
    return ops.pack_states(m0, jnp.linspace(0.8, 1.3, cells))


def test_kernel_early_exit_crossings_bit_identical():
    """Chunked early exit must reproduce the fixed-horizon crossing row
    bit-for-bit, and leave never-crossed lanes' magnetization untouched."""
    cells, dt, n_steps = CELL_TILE, 0.1e-12, 1600
    state = _packed_states(cells)
    sigma = brown_sigma(AFMTJ_PARAMS, dt)
    seeds = noise.cell_seeds(3, cells)
    fixed = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                sigma)
    assert (np.asarray(fixed[7]) < n_steps).any()     # crossings do occur
    for chunk in (64, 100):
        early = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                    sigma, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(early[7]),
                                      np.asarray(fixed[7]))
        still = np.asarray(fixed[7]) >= n_steps
        np.testing.assert_array_equal(np.asarray(early[:6])[:, still],
                                      np.asarray(fixed[:6])[:, still])


def test_crossing_step_does_not_depend_on_tile_neighbours():
    """Lanes share a tile only through its early exit: a lane crosses at
    the same step beside neighbours that run the whole horizon (slow 0.6 V
    lanes that never cross) as beside budget-0 pads (the tile exits once
    the lane is done).  Probes sit at both ends of the tile and in
    different 128-lane rows of it, driven hard enough to cross early."""
    cells, dt, n_steps, chunk = CELL_TILE, 0.1e-12, 1600, 64
    probe = np.array([0, 129, cells // 2 + 7, cells - 1])
    state = np.array(_packed_states(cells, seed=4))
    state[6] = 0.6
    state[6, probe] = 2.0
    state = jnp.asarray(state)
    seeds = noise.cell_seeds(9, cells)
    sigma = brown_sigma(AFMTJ_PARAMS, dt)
    alone = np.zeros(cells, np.float32)
    alone[probe] = n_steps
    crossing = {}
    for name, budget in (("beside_slow", np.full(cells, n_steps, np.float32)),
                         ("beside_pads", alone)):
        out = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                  sigma, step_budget=jnp.asarray(budget),
                                  chunk=chunk)
        crossing[name] = np.asarray(out[7])
    # the probes cross chunks before the horizon, while most of their
    # neighbours are still integrating at its end
    assert crossing["beside_pads"][probe].max() < n_steps - 2 * chunk
    assert (crossing["beside_slow"] >= n_steps).mean() > 0.5
    np.testing.assert_array_equal(crossing["beside_slow"][probe],
                                  crossing["beside_pads"][probe])


def test_kernel_per_lane_sigma_matches_ref_two_temperatures():
    """Two temperatures in one launch: the Pallas kernel and the jnp oracle
    consume identical per-lane sigma rows and identical streams."""
    cells, dt, n_steps = CELL_TILE, 0.1e-12, 200
    state = _packed_states(cells, seed=1)
    seeds = noise.cell_seeds(11, cells)
    sig = np.empty(cells, np.float32)
    sig[:cells // 2] = brown_sigma(AFMTJ_PARAMS, dt, 260.0)
    sig[cells // 2:] = brown_sigma(AFMTJ_PARAMS, dt, 340.0)
    sig = jnp.asarray(sig)
    out_k = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                sig, chunk=32)
    out_r = ref.ref_llg_rk4(state, AFMTJ_PARAMS, dt, n_steps,
                            thermal_sigma=sig, seeds=seeds, chunk=32)
    np.testing.assert_allclose(np.asarray(out_k[:6]), np.asarray(out_r[:6]),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out_k[7]),
                                  np.asarray(out_r[7]))
    # the two sigma halves must actually behave differently on identical
    # lanes: hotter lanes spread more (statistical, generous margin)
    assert float(sig[0]) < float(sig[-1])


def test_kernel_step_budget_clips_like_shorter_horizon():
    """Integrating to a quantized horizon with a per-lane budget must equal
    (after sentinel clipping) integrating exactly to the budget — the §8
    recompile-free pulse-horizon contract."""
    cells, dt = CELL_TILE, 0.1e-12
    state = _packed_states(cells, seed=2)
    sigma = brown_sigma(AFMTJ_PARAMS, dt)
    seeds = noise.cell_seeds(7, cells)
    n_budget, n_static = 1500, 2048
    budget = jnp.full((cells,), float(n_budget), jnp.float32)
    quant = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_static,
                                sigma, step_budget=budget, chunk=64)
    exact = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_budget,
                                sigma)
    clipped = np.minimum(np.asarray(quant[7]), float(n_budget))
    np.testing.assert_array_equal(clipped, np.asarray(exact[7]))
    assert (clipped < n_budget).any()


# ----------------------------------------------------- engine entry points
def test_run_ensemble_chunked_crossings_match():
    n = 100
    m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.2))(
        jnp.linspace(0.05, 0.15, n))
    v = jnp.linspace(0.9, 1.1, n)
    r0 = run_ensemble(AFMTJ_PARAMS, m0, v, 0.1e-12, 300, seed=0)
    r1 = run_ensemble(AFMTJ_PARAMS, m0, v, 0.1e-12, 300, seed=0, chunk=50)
    np.testing.assert_array_equal(r0.crossing_steps, r1.crossing_steps)


def test_latency_percentiles_vectorization_matches_loop(fused_result):
    """The masked-nanpercentile reduction must agree with the explicit
    per-(T, V) loop it replaced."""
    qs = (50.0, 90.0, 99.0)
    lp = fused_result.latency_percentiles(qs)
    grid = fused_result.grid
    n_t, n_v, _, _ = grid.shape
    horizon = grid.n_steps * grid.dt
    expect = np.full((n_t, n_v, len(qs)), np.nan)
    for t in range(n_t):
        for v in range(n_v):
            ct = fused_result.crossing_time[t, v]
            ok = ct < horizon
            if ok.any():
                expect[t, v] = np.percentile(ct[ok], qs)
    np.testing.assert_allclose(lp, expect)
    assert np.isfinite(lp).any()


def test_wer_margined_pulse_over_temperature_range():
    """The operating-range margin is the worst case over the corners — at
    least as long as the nominal-temperature pulse, from one fused
    launch."""
    from repro.imc.write_margin import wer_margined_pulse

    kw = dict(v_write=1.0, wer_target=5e-2, n_samples=64, use_cache=False)
    nominal = wer_margined_pulse("afmtj", **kw)
    ranged = wer_margined_pulse("afmtj", temperatures=(260.0, 300.0, 340.0),
                                **kw)
    assert ranged >= nominal

"""Per-lane device-variation plane tests (DESIGN.md §9).

The variation refactor has three contracts worth pinning hard:

* **kernel = oracle** — the Pallas kernel consuming per-lane alpha / B_k /
  g_scale rows must track the jnp oracle (which routes the same rows
  through the *production* ``llg.llg_rhs``) at a fixed thermal seed,
  across shapes and chunking modes;
* **corner axis is data** — a multi-corner campaign is one launch / one
  compile, per-corner crossing rows are bit-identical to separate
  single-corner launches (shared thermal streams: common random numbers),
  and changing corner values / D2D sigmas / corner count (within a total
  shape bucket) never recompiles;
* **consumers agree** — the scalar ``simulate_write`` baseline, the
  write-verify scheduler, the analog programmer and the margin solver all
  derive their corner semantics from the same ``VariationSpec``, with
  exact nominal-corner parity where the math allows it.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import (CampaignGrid, bucket_cells, pack_variation,
                            run_campaign, run_ensemble)
from repro.campaign import cache as _cache
from repro.campaign.engine import _integrate_sharded
from repro.core import llg
from repro.core.params import (AFMTJ_PARAMS, CORNER_FF, CORNER_SS, CORNER_TT,
                               MTJ_PARAMS, PROCESS_CORNERS, VariationSpec)
from repro.kernels import noise, ops, ref

SPEC3 = VariationSpec(corners=(
    CORNER_FF, CORNER_TT,
    dataclasses.replace(CORNER_SS, sigma_alpha=0.05, sigma_r=0.08)))


@pytest.fixture(scope="module")
def var_grid():
    # low-V lanes mostly never cross, high-V lanes do; three corners with
    # D2D spread on the slow one — exercises every surface reduction path
    return CampaignGrid(voltages=(0.8, 1.2), pulse_widths=(120e-12, 250e-12),
                        temperatures=(280.0, 320.0), n_samples=16, seed=0,
                        variation=SPEC3)


@pytest.fixture(scope="module")
def var_result(var_grid):
    return run_campaign(AFMTJ_PARAMS, var_grid, use_cache=False)


# ----------------------------------------------------------- spec semantics
def test_spec_hashable_and_cache_serializable():
    assert hash(SPEC3) != hash(VariationSpec())
    payload = dataclasses.asdict(SPEC3)
    json.dumps(payload)                       # cache key payload round-trips
    assert SPEC3.corner_names == ("ff", "tt", "ss")
    assert VariationSpec().is_nominal and not SPEC3.is_nominal
    assert set(PROCESS_CORNERS) == {"tt", "ss", "ff"}


def test_lane_factors_reproducible_and_corner_paired():
    c = dataclasses.replace(CORNER_SS, sigma_alpha=0.1, sigma_r=0.1)
    a = SPEC3.lane_factors(c, 256, stream=1)
    b = SPEC3.lane_factors(c, 256, stream=1)
    np.testing.assert_array_equal(a, b)       # pure function of the spec
    assert not np.array_equal(a, SPEC3.lane_factors(c, 256, stream=2))
    assert not np.array_equal(
        a, dataclasses.replace(SPEC3, seed=1).lane_factors(c, 256, stream=1))
    # common random numbers: corners share z draws — at sigma=0 factors are
    # exactly the corner centers, and two corners' draws are paired
    f_tt = SPEC3.lane_factors(CORNER_TT, 64)
    np.testing.assert_array_equal(f_tt, np.ones((4, 64)))
    f_ss = SPEC3.lane_factors(CORNER_SS, 64)
    np.testing.assert_allclose(f_ss[0], 1.15)   # sigma 0 -> center exactly


def test_lane_rows_physics():
    rows = SPEC3.lane_rows(AFMTJ_PARAMS, CORNER_SS, 32, dt=0.1e-12)
    nom = SPEC3.lane_rows(AFMTJ_PARAMS, CORNER_TT, 32, dt=0.1e-12)
    assert (rows.alpha > nom.alpha).all()       # more damping
    assert (rows.g_scale < nom.g_scale).all()   # higher RA -> less drive
    assert (rows.sigma > nom.sigma).all()       # alpha up + volume down
    assert (rows.theta0 < nom.theta0).all()     # taller barrier -> tighter
    np.testing.assert_array_equal(nom.g_scale, 1.0)
    assert rows.kernel_rows.shape == (3, 32)
    assert rows.kernel_rows.dtype == np.float32


# ------------------------------------------------- kernel-vs-oracle parity
def _packed(cells, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    th = jax.random.uniform(k1, (cells,), minval=0.05, maxval=0.25)
    ph = jax.random.uniform(k2, (cells,), minval=0.0, maxval=6.28)
    m0 = jax.vmap(lambda t, f: llg.initial_state(AFMTJ_PARAMS, t, f))(th, ph)
    return ops.pack_states(m0, jnp.linspace(0.8, 1.3, cells))


@pytest.mark.parametrize("cells,chunk", [(512, 0), (512, 32), (1024, 64)])
def test_variation_kernel_matches_ref(cells, chunk):
    """Per-lane parameter rows: the Pallas kernel and the jnp oracle consume
    identical (alpha, B_k, g_scale) rows and identical thermal streams —
    magnetization rows allclose, crossing row bit-equal, across shapes and
    early-exit modes at a fixed seed."""
    dt, n_steps = 0.1e-12, 160
    state = _packed(cells, seed=cells + chunk)
    seeds = noise.cell_seeds(11, cells)
    rng = np.random.default_rng(5)
    lp = jnp.asarray(np.stack([
        AFMTJ_PARAMS.alpha * rng.uniform(0.8, 1.2, cells),
        AFMTJ_PARAMS.b_aniso * rng.uniform(0.9, 1.1, cells),
        rng.uniform(0.8, 1.2, cells)]).astype(np.float32))
    sigma = jnp.full((cells,), 0.02, jnp.float32)
    out_k = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                sigma, chunk=chunk, lane_params=lp)
    out_r = ref.ref_llg_rk4(state, AFMTJ_PARAMS, dt, n_steps,
                            thermal_sigma=sigma, seeds=seeds, chunk=chunk,
                            lane_params=lp)
    np.testing.assert_allclose(np.asarray(out_k[:6]), np.asarray(out_r[:6]),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out_k[7]), np.asarray(out_r[7]))
    # nominal rows reproduce the scalar-closure kernel to float tolerance
    # (different rounding of 1 + alpha^2, so allclose — not bitwise)
    lp0 = jnp.asarray(np.stack([
        np.full(cells, AFMTJ_PARAMS.alpha),
        np.full(cells, AFMTJ_PARAMS.b_aniso),
        np.ones(cells)]).astype(np.float32))
    out_v = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                sigma, chunk=chunk, lane_params=lp0)
    out_s = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps,
                                sigma, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out_v[:6]), np.asarray(out_s[:6]),
                               atol=2e-5)
    # and the varied rows actually change the dynamics
    assert not np.allclose(np.asarray(out_k[:6]), np.asarray(out_v[:6]))


# ------------------------------------------- fused corner axis bit-compat
def test_pack_variation_layout(var_grid):
    state, seeds, sigma, budget, lane_params, spans = pack_variation(
        var_grid, AFMTJ_PARAMS)
    n_c, n_t = var_grid.n_corners, len(var_grid.temperatures)
    per = state.shape[1] // (n_c * n_t)
    assert per == bucket_cells(var_grid.cells)
    assert lane_params.shape == (3, state.shape[1])
    assert spans == [(si * per, si * per + var_grid.cells)
                     for si in range(n_c * n_t)]
    seeds = np.asarray(seeds)
    for ci in range(n_c):
        for ti in range(n_t):
            lo = (ci * n_t + ti) * per
            # thermal streams are shared across corners (common random
            # numbers) and distinct across temperature slices
            np.testing.assert_array_equal(seeds[lo:lo + per],
                                          seeds[ti * per:(ti + 1) * per])
    bud = np.asarray(budget)
    assert (bud[:var_grid.cells] == var_grid.n_steps).all()
    assert (bud[var_grid.cells:per] == 0.0).all()
    # the slow corner's lanes carry a hotter Brown sigma than nominal
    sig = np.asarray(sigma)
    assert sig[2 * n_t * per] > sig[1 * n_t * per]


def test_fused_corners_bit_identical_to_single_corner_launches(var_grid,
                                                               var_result):
    """The acceptance pin: each corner's crossing rows from the fused
    (corner x T x V x S) launch equal a separate single-corner campaign at
    the same lane seeds, bit-for-bit — corners share tilt draws and
    thermal streams, so fusing the axis changes nothing but the launch
    count."""
    assert var_result.crossing_time.shape == (3, 2, 2, 16)
    assert var_result.n_launches == 1
    for ci in range(var_grid.n_corners):
        single = run_campaign(
            AFMTJ_PARAMS,
            dataclasses.replace(var_grid,
                                variation=var_grid.variation.at_corner(ci)),
            use_cache=False)
        np.testing.assert_array_equal(var_result.crossing_time[ci],
                                      single.crossing_time[0])
    # corners must actually differ (FF faster than SS at the same streams)
    lat = var_result.latency_percentiles((50.0,))
    ff, ss = lat[0, 0, 1, 0], lat[2, 0, 1, 0]
    assert np.isfinite(ff) and np.isfinite(ss) and ff < ss


def test_slow_corner_binds_wer(var_result):
    """The slow corner is the reliability binder: at every (V, pulse)
    rung its worst-temperature WER is at least the fast corner's, and
    above it somewhere."""
    worst = var_result.wer_surface().max(axis=1)      # (C, V, P)
    ff, ss = worst[0], worst[2]
    assert (ss >= ff).all(), (ss, ff)
    assert (ss > ff).any(), (ss, ff)


def test_nominal_corner_statistically_matches_legacy_engine(var_grid):
    """An all-nominal variation campaign rides the per-lane parameter rows
    (different rounding path than the scalar closure -> chaotic divergence
    per lane), so parity with the legacy engine is statistical, not
    bitwise: WER within Monte-Carlo error, same qualitative surface."""
    nom_var = dataclasses.replace(var_grid, n_samples=64,
                                  variation=VariationSpec())
    legacy = dataclasses.replace(nom_var, variation=None)
    r_var = run_campaign(AFMTJ_PARAMS, nom_var, use_cache=False)
    r_leg = run_campaign(AFMTJ_PARAMS, legacy, use_cache=False)
    assert r_var.crossing_time.shape == (1,) + r_leg.crossing_time.shape
    w_var, w_leg = r_var.wer_surface()[0], r_leg.wer_surface()
    np.testing.assert_allclose(w_var, w_leg, atol=0.2)    # ~3 sigma @ n=64
    # 1.2 V long-pulse writes succeed, 0.8 V short-pulse writes fail, in both
    assert w_var[:, 1, 1].max() < 0.2 and w_leg[:, 1, 1].max() < 0.2
    assert w_var[:, 0, 0].min() > 0.8 and w_leg[:, 0, 0].min() > 0.8


# ------------------------------------------------------------ compile pins
def test_corner_count_and_values_do_not_enter_compile_key(var_grid):
    """One compile for a 3-corner campaign; new corner values, new D2D
    sigmas, new seeds reuse it; and a 4-corner campaign lands in the same
    total shape bucket -> still no recompile."""
    _integrate_sharded._clear_cache()
    res = run_campaign(AFMTJ_PARAMS, var_grid, use_cache=False)
    assert res.n_launches == 1
    assert _integrate_sharded._cache_size() == 1
    spec_b = VariationSpec(corners=(
        dataclasses.replace(CORNER_SS, alpha_factor=1.3, sigma_volume=0.1),
        CORNER_TT, CORNER_FF), seed=17)
    run_campaign(AFMTJ_PARAMS,
                 dataclasses.replace(var_grid, variation=spec_b, seed=3),
                 use_cache=False)
    assert _integrate_sharded._cache_size() == 1
    # 4 corners x 2 T x 512-lane slices = 4096 lanes — same pow2 total
    # bucket as 3 x 2 x 512 = 3072 -> 4096: corner count is data too
    spec_c = VariationSpec(corners=(CORNER_TT, CORNER_SS, CORNER_FF,
                                    dataclasses.replace(CORNER_SS, name="sf",
                                                        r_factor=1.3)))
    r4 = run_campaign(AFMTJ_PARAMS,
                      dataclasses.replace(var_grid, variation=spec_c),
                      use_cache=False)
    assert r4.crossing_time.shape[0] == 4
    assert _integrate_sharded._cache_size() == 1


# ------------------------------------------------------- cache v4 behavior
def test_cache_v4_migration_ignores_stale_entries(tmp_path, var_grid):
    grid = dataclasses.replace(var_grid, n_samples=8,
                               pulse_widths=(60e-12,),
                               temperatures=(300.0,))
    cache_dir = str(tmp_path)
    # a v3-keyed entry (old layout, no variation field) must never match
    v3_payload = {"v": 3, "layout": "fused-T/bucket-pow2",
                  "params": dataclasses.asdict(AFMTJ_PARAMS),
                  "grid": {"voltages": list(grid.voltages)},
                  "backend": "pallas"}
    import hashlib
    v3_key = hashlib.sha256(
        json.dumps(v3_payload, sort_keys=True, default=float).encode()
    ).hexdigest()[:32]
    _cache.store(v3_key, np.zeros((1, 2, 8)), header={}, cache_dir=cache_dir)
    v4_key = _cache.campaign_key(AFMTJ_PARAMS, grid, "pallas")
    assert v4_key != v3_key
    # a corrupt file AT the v4 key is a miss, not a crash
    (tmp_path / f"{v4_key}.npz").write_bytes(b"not an npz")
    assert _cache.load(v4_key, cache_dir) is None
    r1 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=cache_dir)
    assert not r1.from_cache
    # the recomputed 4-D surface round-trips through the cache
    r2 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=cache_dir)
    assert r2.from_cache
    np.testing.assert_array_equal(r1.crossing_time, r2.crossing_time)
    # a wrong-shape v4 entry (e.g. written before a grid edit) is ignored
    _cache.store(v4_key, np.zeros((2, 2, 2)), header={}, cache_dir=cache_dir)
    r3 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=cache_dir)
    assert not r3.from_cache


# ---------------------------------------------------------- consumer layers
def test_run_ensemble_lane_params_drive_scale():
    """g_scale=0 removes the STT drive entirely: no lane may cross; at
    g_scale=1 (same seeds) the high-voltage lanes do."""
    n = 128
    m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.2))(
        jnp.full((n,), 0.1))
    v = jnp.full((n,), 1.2)
    lp_on = np.stack([np.full(n, AFMTJ_PARAMS.alpha),
                      np.full(n, AFMTJ_PARAMS.b_aniso),
                      np.ones(n)]).astype(np.float32)
    lp_off = lp_on.copy()
    lp_off[2] = 0.0
    kw = dict(dt=0.1e-12, n_steps=1800, seed=4, chunk=64)
    r_on = run_ensemble(AFMTJ_PARAMS, m0, v, lane_params=lp_on, **kw)
    r_off = run_ensemble(AFMTJ_PARAMS, m0, v, lane_params=lp_off, **kw)
    assert r_on.switched.any()
    assert not r_off.switched.any()


def test_simulate_write_nominal_sample_parity():
    """variation=0 (the nominal-corner sample) is *exactly* the baseline:
    every factor is literally 1.0, so the scalar path and the engine agree
    on nominal-corner semantics bit-for-bit."""
    from repro.core.device import simulate_write

    s = VariationSpec().sample_device(AFMTJ_PARAMS)
    r0 = simulate_write(AFMTJ_PARAMS, 1.0, n_steps=3000, dt=0.1e-12)
    r1 = simulate_write(AFMTJ_PARAMS, 1.0, n_steps=3000, dt=0.1e-12,
                        variation=s)
    assert float(r0.t_switch) == float(r1.t_switch)
    assert float(r0.energy) == float(r1.energy)
    # the slow corner really is slower, for both device families
    for p, steps, dt in ((AFMTJ_PARAMS, 5000, 0.1e-12),):
        ss = VariationSpec(corners=(CORNER_SS,)).sample_device(p)
        r2 = simulate_write(p, 1.0, n_steps=steps, dt=dt, variation=ss)
        assert float(r2.t_switch) > float(r0.t_switch)


def test_write_verify_corner_retry_asymmetry():
    """Slow-corner devices fail the per-attempt pulse more often: the
    measured retry distribution orders FF < TT-ish < SS, with shared D2D /
    thermal streams making the comparison paired."""
    from repro.imc.write_path import WritePolicy, write_verify_corners

    pol = WritePolicy(v_write=1.0, pulse=130e-12, max_attempts=4, seed=3,
                      use_cache=False)
    out = write_verify_corners("afmtj", 192, pol,
                               VariationSpec(corners=(CORNER_FF, CORNER_SS)))
    assert set(out) == {"ff", "ss"}
    assert out["ss"].attempts_mean > out["ff"].attempts_mean
    assert out["ss"].energy_mean() > 0 and out["ff"].energy_mean() > 0
    assert out["ss"].rounds >= out["ff"].rounds >= 1


def test_write_verify_variation_rounds_stay_in_compile_budget():
    """Variation retry rounds ride the same shape-bucket + quantized-horizon
    compile economy as the nominal scheduler: a multi-round shrinking
    schedule compiles fewer graphs than it runs rounds."""
    from repro.imc.write_path import WritePolicy, write_verify

    _integrate_sharded._clear_cache()
    pol = WritePolicy(v_write=1.0, pulse=130e-12, max_attempts=3, seed=5,
                      use_cache=False,
                      variation=VariationSpec(corners=(CORNER_SS,)))
    r = write_verify("afmtj", 640, pol)
    assert r.rounds == 3
    assert _integrate_sharded._cache_size() <= 2 < r.rounds


def test_analog_g_sigma_deprecated_alias():
    """g_sigma warns and constructs the equivalent spec: bit-identical
    programmed conductances, warning-free when the spec is passed
    explicitly."""
    from repro.imc.analog_pipeline import AnalogConfig, program_weights

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    with pytest.warns(DeprecationWarning, match="g_sigma is deprecated"):
        old = program_weights(w, "afmtj", AnalogConfig(g_sigma=0.05, seed=2))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        new = program_weights(w, "afmtj", AnalogConfig(
            variation=VariationSpec.from_g_sigma(0.05, seed=2), seed=2))
    np.testing.assert_array_equal(np.asarray(old.g_diff),
                                  np.asarray(new.g_diff))
    # variation really perturbs programming vs the ideal target
    ideal = program_weights(w, "afmtj", AnalogConfig())
    assert not np.allclose(np.asarray(old.g_diff), np.asarray(ideal.g_diff))


def test_wer_margined_pulse_covers_process_corners():
    """The corner-margined pulse is the worst case over (corner x T): at
    least the nominal pulse, from one fused launch per device kind."""
    from repro.imc.write_margin import wer_margined_pulse

    kw = dict(v_write=1.0, wer_target=5e-2, n_samples=64, use_cache=False)
    nominal = wer_margined_pulse("afmtj", **kw)
    spec = VariationSpec(corners=(CORNER_FF, CORNER_SS))
    ranged = wer_margined_pulse("afmtj", variation=spec, **kw)
    assert ranged >= nominal


def test_mtj_variation_rides_the_scan_tile():
    """The single-sublattice (MTJ) engine tile honors the variation plane
    too: the slow corner's WER at a marginal pulse exceeds the fast
    corner's on the same thermal streams."""
    grid = CampaignGrid(voltages=(1.0,), pulse_widths=(1400e-12,),
                        temperatures=(300.0,), n_samples=32, dt=0.2e-12,
                        seed=1,
                        variation=VariationSpec(corners=(CORNER_FF,
                                                         CORNER_SS)))
    res = run_campaign(MTJ_PARAMS, grid, use_cache=False)
    w = res.wer_surface()                     # (2, 1, 1, 1)
    assert w.shape == (2, 1, 1, 1)
    assert w[1, 0, 0, 0] >= w[0, 0, 0, 0]
    assert w[1, 0, 0, 0] > 0.1                # slow corner misses the pulse

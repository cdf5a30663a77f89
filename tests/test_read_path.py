"""Read-path scenario-family tests (DESIGN.md §10): read-disturb,
accelerated retention, sense-margin yield, and the refresh policy charged
into the system model.

The load-bearing pins:

* **offset_sigma dead-knob regression** — ``SenseAmpParams.offset_sigma``
  used to be stored and never read; now that it drives the sense MC, the
  ``offset_sigma=0`` / ``offset=None`` paths must stay *bit-identical* to
  the deterministic circuit model.
* **kernel-vs-oracle parity in the read regimes** — the campaign engine
  was only ever parity-tested in the write regime (strong over-threshold
  drive, short horizons).  Sub-threshold drive and zero-drive long-horizon
  integration hit different numerics (marginal crossings, ~10^4-step
  trajectories), so the Pallas path is pinned against ``kernels.ref``
  there too, including the log-horizon ladder and the MTJ
  single-sublattice routing.
* **one launch, one compile** per kernel-backed scenario.
* **refresh charging** — nominal Fig. 4 numbers must be bit-identical
  with the refresh knobs off, and strictly degrade with a finite scrub
  interval.
"""
import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign.engine import _integrate_sharded
from repro.circuit.bitline import BitlineParams, multi_row_current
from repro.circuit.senseamp import (SenseAmpParams, resolve_logic,
                                    sa_offsets, sense_delay)
from repro.core.params import (AFMTJ_PARAMS, CORNER_FF, CORNER_SS, CORNER_TT,
                               VariationSpec)
from repro.imc.read_path import (DisturbModel, accumulated_disturb,
                                 read_disturb_campaign, reads_between_refresh,
                                 retention_campaign, sense_margin_yield,
                                 _censored_tau)

TT_ONLY = VariationSpec(corners=(CORNER_TT,))


# ------------------------------------------------ offset_sigma regression
def test_sa_offsets_zero_sigma_is_exact_zero():
    sa = SenseAmpParams(offset_sigma=0.0)
    assert (np.asarray(sa_offsets(sa, 257)) == 0.0).all()


def test_sa_offsets_population_and_crn():
    sa = SenseAmpParams(offset_sigma=5e-3)
    a = np.asarray(sa_offsets(sa, 4096, seed=3))
    b = np.asarray(sa_offsets(sa, 4096, seed=3))
    c = np.asarray(sa_offsets(sa, 4096, seed=4))
    np.testing.assert_array_equal(a, b)          # stateless: same seed, same pop
    assert not np.array_equal(a, c)
    assert abs(a.std() - 5e-3) / 5e-3 < 0.1
    assert abs(a.mean()) < 5e-4


def test_sense_delay_offset_none_bit_identical_to_zero_offset():
    """|di*r + 0| == |di|*r exactly in IEEE arithmetic — the offset=None
    fast path and an explicit zero offset must agree bit-for-bit."""
    sa = SenseAmpParams()
    di = jnp.asarray(np.linspace(-2e-5, 2e-5, 101), jnp.float32)
    t_none = np.asarray(sense_delay(di, sa))
    t_zero = np.asarray(sense_delay(di, sa, offset=jnp.zeros_like(di)))
    np.testing.assert_array_equal(t_none, t_zero)


@pytest.mark.parametrize("op", ["and", "nand", "or", "nor", "xor", "xnor"])
def test_resolve_logic_offset_none_bit_identical(op):
    sa, bl = SenseAmpParams(), BitlineParams()
    bits = jnp.asarray([[i >> 1 & 1, i & 1] for i in range(4)], jnp.float32)
    out0, d0 = resolve_logic(bits, op, AFMTJ_PARAMS, bl, sa)
    outz, dz = resolve_logic(bits, op, AFMTJ_PARAMS, bl, sa,
                             offset=jnp.zeros((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(outz))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(dz))


def test_resolve_logic_large_offset_flips_decision():
    """An offset past the reference gap is exactly the sense-yield failure
    mode: the resolved bit flips relative to the deterministic path."""
    sa, bl = SenseAmpParams(), BitlineParams()
    bits = jnp.asarray([[1.0, 1.0]])
    out0, _ = resolve_logic(bits, "and", AFMTJ_PARAMS, bl, sa)
    gap = float(multi_row_current(bits, AFMTJ_PARAMS, bl)[0]) * sa.r_trans
    big = jnp.asarray([-2.0 * gap], jnp.float32)
    out1, _ = resolve_logic(bits, "and", AFMTJ_PARAMS, bl, sa, offset=big)
    assert bool(out0[0]) and not bool(out1[0])


def test_sense_yield_deterministic_limit_is_perfect():
    """sigma_r=0 corners + offset_sigma=0 removes every noise source: the
    MC must report yield exactly 1.0 with a strictly positive margin."""
    sy = sense_margin_yield("afmtj", v_reads=(0.1,),
                            sa=SenseAmpParams(offset_sigma=0.0),
                            variation=TT_ONLY, n_samples=512)
    assert (sy.yield_surface == 1.0).all()
    assert sy.margin_min.min() > 0.0


# --------------------------------------------- kernel-vs-oracle parity
@pytest.fixture(scope="module")
def retention_pair():
    """Zero-drive campaign on the log-horizon ladder, Pallas vs the jnp
    oracle, plus the compile count of the Pallas run.  The horizon stays
    at 0.6 ns (6001 steps): strict bit-equality holds there; past ~10^4
    steps marginal crossings drift by one step (see the disturb test)."""
    kw = dict(accel_factors=(0.05,), temperatures=(300.0,),
              horizons=(0.2e-9, 0.6e-9), n_samples=32,
              variation=TT_ONLY, use_cache=False)
    _integrate_sharded._clear_cache()
    rp = retention_campaign("afmtj", backend="pallas", **kw)
    compiles = _integrate_sharded._cache_size()
    rr = retention_campaign("afmtj", backend="ref", **kw)
    return rp, rr, compiles


def test_retention_zero_drive_long_horizon_bit_equal(retention_pair):
    rp, rr, _ = retention_pair
    ctp = rp.result.crossing_time
    np.testing.assert_array_equal(ctp, rr.result.crossing_time)
    horizon = max(rp.grid.pulse_widths)
    assert (ctp < horizon).any()                 # escapes actually happened
    assert (ctp >= horizon).any()                # and the sentinel path too


def test_retention_one_launch_one_compile(retention_pair):
    rp, _, compiles = retention_pair
    assert rp.n_launches == 1
    assert compiles == 1


def test_retention_log_horizon_ladder_independent(retention_pair):
    """The log-horizon quantizer only changes the *compiled* horizon; the
    per-lane budget row stops real lanes at the true horizon, so crossing
    rows must match the unquantized (chunk=0) integration bit-for-bit."""
    from repro.campaign.engine import run_campaign

    rp, _, _ = retention_pair
    exact = run_campaign(AFMTJ_PARAMS, rp.grid, use_cache=False, chunk=0)
    np.testing.assert_array_equal(rp.result.crossing_time,
                                  exact.crossing_time)


@pytest.fixture(scope="module")
def retention_stats():
    """Two measurable acceleration rungs for the MLE/Arrhenius stack
    (both rungs flip >= min_flips lanes within the 1.2 ns window at
    n=96)."""
    from repro.campaign.grid import log_pulses

    return retention_campaign(
        "afmtj", accel_factors=(0.05, 0.10), temperatures=(300.0,),
        horizons=log_pulses(0.15e-9, 1.2e-9, per_decade=3),
        n_samples=96, variation=TT_ONLY, use_cache=False)


def test_retention_mle_and_extrapolation(retention_stats):
    """Measured escape times must order by barrier, the Arrhenius
    cross-check must land in the activated-escape band, and the pinned
    slope extrapolation must put operating retention far beyond the
    simulated horizon."""
    rp = retention_stats
    tau = rp.tau_acc[0, 0]                       # (n_accel,)
    assert rp.n_flips[0, 0].min() >= rp.min_flips
    assert tau[0] < tau[1]                       # Delta_eff 2 escapes faster
    slope, _ = rp.arrhenius_fit(0, 0)
    assert 0.3 < slope < 3.0
    assert rp.tau0(0, 0) > 0.0
    t_op = rp.worst_tau_op()
    assert t_op > 1e3                            # seconds, vs a ns horizon
    q = rp.retention_percentiles(qs=(1e-9, 1e-6))[0, 0]
    assert 0 < q[0] < q[1]                       # tighter quantile is sooner


def test_disturb_sub_threshold_crossings_match_oracle():
    """Sub-threshold drive at elevated T: marginal thermally-assisted
    crossings ~10^4 steps in.  Crossed/uncrossed sets must match the
    oracle exactly; crossing steps may land one step apart (ulp-level
    trajectory divergence between the fused kernel and the jnp scan over
    that many steps), never more."""
    kw = dict(voltages=(0.10, 0.24), pulses=(1.0e-9,),
              temperatures=(400.0,), n_samples=48, use_cache=False)
    dp = read_disturb_campaign("afmtj", backend="pallas", **kw)
    dr = read_disturb_campaign("afmtj", backend="ref", **kw)
    dt = dp.grid.dt
    sp = np.round(dp.result.crossing_time / dt)
    sr = np.round(dr.result.crossing_time / dt)
    horizon = dp.grid.n_steps
    np.testing.assert_array_equal(sp >= horizon, sr >= horizon)
    assert (sp < horizon).any()                  # disturb flips occurred
    assert np.abs(sp - sr).max() <= 1.0
    # sub-threshold bias must not disturb the low rung at this horizon
    assert (sp[0, 0] >= horizon).all()


def test_mtj_single_sublattice_path_parity():
    """MTJ campaigns route through the ref scan for both backends — the
    routing itself plus crossing extraction must agree bit-for-bit, with
    the over-threshold rung crossing and the sub-threshold rung not."""
    kw = dict(voltages=(0.2, 1.0), pulses=(2.5e-9,), temperatures=(300.0,),
              n_samples=24, use_cache=False)
    dp = read_disturb_campaign("mtj", backend="pallas", **kw)
    dr = read_disturb_campaign("mtj", backend="ref", **kw)
    ct = dp.result.crossing_time
    np.testing.assert_array_equal(ct, dr.result.crossing_time)
    horizon = 2.5e-9
    assert (ct[0, 1] < horizon).all()            # 1.0 V writes
    assert (ct[0, 0] >= horizon).all()           # 0.2 V holds


@pytest.fixture(scope="module")
def disturb_surface():
    return read_disturb_campaign("afmtj", voltages=(0.10, 0.24),
                                 pulses=(0.2e-9, 2.0e-9),
                                 temperatures=(300.0, 400.0), n_samples=128,
                                 use_cache=False)


@pytest.mark.parametrize("t_index", [0, 1], ids=["300K", "400K"])
def test_disturb_onset_grows_with_bias(disturb_surface, t_index):
    """Over the longest read pulse the higher sub-threshold bias disturbs
    more cells than the lower one, at each temperature."""
    p_lo = disturb_surface.p1(v_index=0, p_index=-1, t_index=t_index)
    p_hi = disturb_surface.p1(v_index=1, p_index=-1, t_index=t_index)
    assert p_hi > p_lo, (p_lo, p_hi)


def test_disturb_campaign_one_launch_one_compile():
    _integrate_sharded._clear_cache()
    res = read_disturb_campaign("afmtj", voltages=(0.10, 0.24),
                                pulses=(0.2e-9,), temperatures=(300.0, 400.0),
                                n_samples=32, use_cache=False)
    assert res.n_launches == 1
    assert _integrate_sharded._cache_size() == 1


# --------------------------------------------------- disturb model math
def test_accumulated_disturb_and_refresh_roundtrip():
    assert accumulated_disturb(0.0, 1e9) == 0.0
    p1 = 3e-7
    assert abs(accumulated_disturb(p1, 1000) - (1 - (1 - p1) ** 1000)) < 1e-12
    n = reads_between_refresh(p1, 1e-4)
    assert abs(accumulated_disturb(p1, n) - 1e-4) / 1e-4 < 1e-9
    assert math.isinf(reads_between_refresh(0.0, 1e-9))


def test_disturb_model_suppression_shape():
    m = DisturbModel(kind="afmtj", v_c=0.2, beta=1.5, accel_factor=0.1,
                     delta_acc=4.0, tau0_acc=1e-9, voltages=(0.0,),
                     tau_meas=(1e-9,), sse=0.0)
    assert m.suppression(0.0) == 1.0
    assert m.suppression(0.25) == 0.0            # clipped above V_c
    vs = np.linspace(0.0, 0.19, 20)
    s = np.array([m.suppression(v) for v in vs])
    assert (np.diff(s) < 0).all()                # monotone suppression
    p = np.array([m.p1(v, 1e-9, 40.0, 0.25e-9) for v in vs])
    assert (np.diff(p) > 0).all()                # disturb grows with bias
    assert m.p1(0.0, 1e-9, 40.0, 0.25e-9) < 1e-15


def test_censored_tau_mle():
    # all escaped: plain mean
    tau, n = _censored_tau(np.array([1.0, 3.0]), horizon=10.0)
    assert n == 2 and tau == 2.0
    # half censored: survivors contribute their censored horizon
    tau, n = _censored_tau(np.array([2.0, 20.0]), horizon=10.0)
    assert n == 1 and tau == 12.0
    # nothing escaped
    tau, n = _censored_tau(np.array([20.0, 20.0]), horizon=10.0)
    assert n == 0 and math.isinf(tau)


# ------------------------------------------------------ sense-margin MC
@pytest.fixture(scope="module")
def sense_surface():
    return sense_margin_yield("afmtj", n_samples=2048, seed=0)


def test_sense_yield_ladders_with_read_voltage(sense_surface):
    sy = sense_surface
    y = sy.yield_surface                         # (n_corners, n_V)
    assert y.shape == (3, len(sy.v_reads))
    assert (np.diff(y, axis=1) >= 0).all()       # more bias, more margin
    v = sy.v_read_for_yield(0.999)
    assert v in sy.v_reads
    wi = int(np.argmin(y[:, -1]))
    assert y[wi, list(sy.v_reads).index(v)] >= 0.999


def test_sense_yield_target_beyond_ladder_raises(sense_surface):
    with pytest.raises(ValueError):
        sense_surface.v_read_for_yield(1.0 + 1e-9)


def test_sense_yield_nominal_trim_exposes_systematic_corner_loss():
    """Without per-corner reference trimming the r_factor=1.15 slow corner
    pushes part of its D2D tail across the nominal reference — a yield
    ceiling raising v_read cannot fix.  Corner trimming removes it."""
    kw = dict(v_reads=(0.1, 0.2), n_samples=2048, seed=0)
    trimmed = sense_margin_yield("afmtj", ref_trim="corner", **kw)
    untrimmed = sense_margin_yield("afmtj", ref_trim="nominal", **kw)
    si = list(trimmed.corner_names).index("ss")
    assert untrimmed.yield_surface[si].max() < 0.995
    assert trimmed.yield_surface[si].max() > 0.999


def test_sense_time_budget_costs_yield(sense_surface):
    tight = sense_margin_yield("afmtj", n_samples=2048, seed=0,
                               t_budget=float(sense_surface.t_sense.min()))
    assert tight.yield_surface.min() < sense_surface.yield_surface.min()


def test_measured_read_timings_thread_into_subarray():
    from repro.circuit.subarray import make_subarray
    from repro.imc.read_path import measured_read_timings

    det = make_subarray("afmtj", rows=64, cols=64)
    meas = make_subarray("afmtj", rows=64, cols=64, read_percentile=99.0,
                         sa=SenseAmpParams(offset_sigma=5e-3))
    assert det.timings.read_percentile is None
    assert det.timings.read_yield == 1.0
    assert meas.timings.read_percentile == 99.0
    assert 0.9 < meas.timings.read_yield <= 1.0
    # p99 over (corner x D2D x offset) must be slower than the nominal path
    assert meas.timings.t_read > det.timings.t_read
    # lru-cached characterization: identical args, identical object
    mr = measured_read_timings("afmtj", v_read=0.1, percentile=99.0)
    assert mr is measured_read_timings("afmtj", v_read=0.1, percentile=99.0)


# ----------------------------------------------------- refresh charging
def test_system_nominal_refresh_fields_inert():
    from repro.imc.evaluate import evaluate_system

    res = evaluate_system("afmtj")
    for r in res.values():
        assert r.t_refresh == 0.0 and r.e_refresh == 0.0
        assert math.isinf(r.refresh_interval)


def test_refresh_policy_charging_monotone():
    from repro.imc.evaluate import evaluate_system
    from repro.imc.read_path import RefreshPolicy

    def pol(interval):
        return RefreshPolicy(interval=interval, limited_by="disturb",
                             tau_retention=1e7, p1_read=1e-10,
                             reads_max=10.0, ber_budget=1e-9,
                             reads_per_cell_s=1e6)

    base = evaluate_system("afmtj")
    inert = evaluate_system("afmtj", refresh=pol(math.inf))
    for name, r in base.items():
        assert inert[name].t_imc == r.t_imc      # inf interval: bit-identical
        assert inert[name].e_imc == r.e_imc
    slow = evaluate_system("afmtj", refresh=pol(1e-4))
    fast = evaluate_system("afmtj", refresh=pol(1e-5))
    for name, r in base.items():
        assert slow[name].t_refresh > 0.0
        assert fast[name].t_refresh > slow[name].t_refresh
        assert fast[name].e_imc > slow[name].e_imc > r.e_imc
        assert slow[name].t_imc == pytest.approx(
            r.t_imc + slow[name].t_refresh)
        assert slow[name].speedup < r.speedup


def test_refresh_policy_is_hashable_pure_data():
    from repro.imc.read_path import RefreshPolicy

    p = RefreshPolicy(interval=1e-4, limited_by="retention",
                      tau_retention=1e7, p1_read=0.0, reads_max=math.inf,
                      ber_budget=1e-9, reads_per_cell_s=1e6)
    assert hash(p) == hash(dataclasses.replace(p))

"""Campaign engine tests: thermal kernel parity, WER physics, caching,
crash-safe cache writes, and crash-resumable multi-launch campaigns."""
import dataclasses
import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import (CampaignGrid, brown_sigma, pack_plane,
                            run_campaign, run_ensemble)
from repro.core import llg
from repro.core.params import AFMTJ_PARAMS
from repro.kernels import noise, ops, ref


# ------------------------------------------------------------ noise streams
def test_noise_stream_statistics():
    """Counter-RNG normals: ~N(0,1), decorrelated across lanes and steps."""
    seeds = noise.cell_seeds(0, 2048)
    zs = []
    for step in range(8):                       # 8 x 6 x 2048 ~ 100k draws
        d1, d2 = noise.thermal_draws(seeds, jnp.int32(step))
        zs.append(np.stack([np.asarray(c) for c in d1 + d2]))
    z = np.stack(zs)
    assert abs(z.mean()) < 0.015                # ~5 sigma of the MC error
    assert abs(z.std() - 1.0) < 0.02
    # consecutive steps must decorrelate
    r = np.corrcoef(z[0, 0], z[1, 0])[0, 1]
    assert abs(r) < 0.1


def test_noise_stream_deterministic():
    seeds = noise.cell_seeds(7, 512)
    a, _ = noise.thermal_draws(seeds, jnp.int32(11))
    b, _ = noise.thermal_draws(seeds, jnp.int32(11))
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


# ---------------------------------------------------- kernel-vs-oracle parity
def _states(cells, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    th = jax.random.uniform(k1, (cells,), minval=0.05, maxval=0.25)
    ph = jax.random.uniform(k2, (cells,), minval=0.0, maxval=6.28)
    m0 = jax.vmap(lambda t, f: llg.initial_state(AFMTJ_PARAMS, t, f))(th, ph)
    return ops.pack_states(m0, jnp.linspace(0.3, 1.2, cells))


@pytest.mark.parametrize("n_steps", [50, 200])
def test_thermal_kernel_matches_ref_exact_stream(n_steps):
    """Pallas-with-noise vs ref.py oracle at a fixed seed: the counter-RNG
    is stateless, so both consume the *identical* thermal stream and the
    trajectories must agree to float tolerance (not just statistically)."""
    cells, dt = 512, 0.1e-12
    state = _states(cells)
    sigma = brown_sigma(AFMTJ_PARAMS, dt)
    seeds = noise.cell_seeds(42, cells)
    out_k = ops.llg_rk4_thermal(state, seeds, AFMTJ_PARAMS, dt, n_steps, sigma)
    out_r = ref.ref_llg_rk4(state, AFMTJ_PARAMS, dt, n_steps,
                            thermal_sigma=sigma, seeds=seeds)
    np.testing.assert_allclose(np.asarray(out_k[:6]), np.asarray(out_r[:6]),
                               atol=2e-5)
    assert np.array_equal(np.asarray(out_k[7]), np.asarray(out_r[7]))


def test_thermal_zero_sigma_reduces_to_deterministic():
    state = _states(512, seed=2)
    out_t = ops.llg_rk4_thermal(state, noise.cell_seeds(0, 512),
                                AFMTJ_PARAMS, 0.1e-12, 100, 0.0)
    out_d = ops.llg_rk4(state, AFMTJ_PARAMS, 0.1e-12, 100)
    # the thermal kernel adds an exact 0.0 field, but XLA fuses the add
    # differently than the deterministic kernel — rounding can differ by
    # a ulp per step, so pin to a few f32 ulps rather than bit equality
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_d),
                               rtol=0, atol=5e-7)


def test_thermal_seeds_decorrelate_lanes():
    """Same initial state on every lane + noise => lanes must diverge."""
    m0 = jnp.broadcast_to(llg.initial_state(AFMTJ_PARAMS, 0.1, 0.3), (512, 2, 3))
    state = ops.pack_states(m0, jnp.full((512,), 1.0))
    sigma = brown_sigma(AFMTJ_PARAMS, 0.1e-12)
    out = ops.llg_rk4_thermal(state, noise.cell_seeds(1, 512),
                              AFMTJ_PARAMS, 0.1e-12, 200, sigma)
    nz = np.asarray(0.5 * (out[2] - out[5]))
    assert nz.std() > 1e-3


# ------------------------------------------------------------- WER physics
@pytest.fixture(scope="module")
def campaign_result():
    grid = CampaignGrid(voltages=(0.8, 1.0, 1.2),
                        pulse_widths=(120e-12, 200e-12, 300e-12),
                        n_samples=48, dt=0.1e-12, seed=0)
    return run_campaign(AFMTJ_PARAMS, grid, use_cache=False)


def test_wer_monotone_in_pulse_and_voltage(campaign_result):
    """WER must be non-increasing along both the pulse and voltage axes."""
    w = campaign_result.wer()                      # (n_V, n_P)
    assert (np.diff(w, axis=1) <= 0).all(), f"not monotone in pulse:\n{w}"
    assert (np.diff(w, axis=0) <= 1e-9).all(), f"not monotone in voltage:\n{w}"
    # end-member sanity: strong long pulse writes reliably, weak short doesn't
    assert w[-1, -1] <= 0.05
    assert w[0, 0] >= w[-1, -1]


def test_wer_counts_unswitched_at_longest_pulse():
    """Regression: the never-crossed sentinel must exceed every grid pulse,
    or unswitched lanes are miscounted as successful writes at the longest
    pulse (WER 0.0 where the scan oracle says ~0.5)."""
    grid = CampaignGrid(voltages=(0.6,), pulse_widths=(250e-12,),
                        n_samples=32, dt=0.1e-12, seed=0)
    assert grid.n_steps * grid.dt > max(grid.pulse_widths)
    res = run_campaign(AFMTJ_PARAMS, grid, use_cache=False)
    assert res.wer()[0, -1] > 0.1, res.wer()


def test_pulse_for_wer_raises_when_unreachable():
    from repro.campaign import CampaignResult
    grid = CampaignGrid(voltages=(0.5,), pulse_widths=(50e-12,),
                        n_samples=4, dt=0.1e-12)
    never = np.full((1, 1, 4), grid.n_steps * grid.dt)   # nobody switched
    res = CampaignResult(grid=grid, backend="pallas", crossing_time=never,
                         elapsed_s=0.0)
    with pytest.raises(ValueError, match="widen"):
        res.pulse_for_wer(1e-2)


def test_pack_states_rejects_single_sublattice():
    from repro.core.params import MTJ_PARAMS
    m0 = jax.vmap(lambda t: llg.initial_state(MTJ_PARAMS, t, 0.1))(
        jnp.linspace(0.01, 0.2, 8))
    with pytest.raises(AssertionError, match="dual-sublattice"):
        ops.pack_states(m0, jnp.ones(8))


# ------------------------------------------- single-sublattice (FM/MTJ) path
def test_pack_soa_single_sublattice_layout():
    """FM states pack with m in rows 0-2, zero rows 3-5, CELL_TILE padding."""
    from repro.campaign import pack_soa
    from repro.core.params import MTJ_PARAMS
    m0 = jax.vmap(lambda t: llg.initial_state(MTJ_PARAMS, t, 0.1))(
        jnp.linspace(0.01, 0.2, 8))
    state = pack_soa(m0, jnp.linspace(0.8, 1.2, 8))
    assert state.shape[0] == 8 and state.shape[1] % 512 == 0
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(state[0:3, :8]), axis=0), 1.0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state[3:6]), 0.0)


def test_fm_campaign_matches_scan_statistics():
    """The engine's FM scan tile and the independently-seeded
    ``write_error_rate_scan`` baseline must agree on MTJ WER within
    Monte-Carlo error (two RNG implementations, same physics)."""
    from repro.core.montecarlo import write_error_rate, write_error_rate_scan
    from repro.core.params import MTJ_PARAMS
    pulse, n, dt = 1400e-12, 48, 0.2e-12
    w_engine = write_error_rate(MTJ_PARAMS, 1.0, pulse, n_samples=n, dt=dt)
    w_scan = float(write_error_rate_scan(MTJ_PARAMS, 1.0, pulse,
                                         n_samples=n, dt=dt))
    # binomial std at p~0.5, n=48 is ~0.07; allow ~3 sigma both ways
    assert abs(w_engine - w_scan) < 0.25, (w_engine, w_scan)


def test_fm_wer_monotone_in_pulse():
    from repro.core.params import MTJ_PARAMS
    grid = CampaignGrid(voltages=(1.0,),
                        pulse_widths=(900e-12, 1400e-12, 2000e-12),
                        n_samples=32, dt=0.2e-12, seed=0)
    res = run_campaign(MTJ_PARAMS, grid, use_cache=False)
    w = res.wer()[0]
    assert (np.diff(w) <= 0).all(), w
    assert w[0] > w[-1]           # short pulses must actually fail more


def test_wer_pulse_axis_is_postprocessing(campaign_result):
    """WER at the longest grid pulse == fraction not crossed by then."""
    ct = campaign_result.crossing_time[0]          # (n_V, n_S) at T0
    pulse = campaign_result.grid.pulse_widths[-1]
    expect = (ct > pulse).mean(axis=-1)
    np.testing.assert_allclose(campaign_result.wer()[:, -1], expect)


def test_latency_percentiles(campaign_result):
    lp = campaign_result.latency_percentiles((50.0, 99.0))
    ok = ~np.isnan(lp)
    assert ok.any()
    # p99 >= p50 wherever defined; higher voltage switches faster at p50
    assert (lp[..., 1][ok[..., 1]] >= lp[..., 0][ok[..., 0]]).all()
    p50 = lp[0, :, 0]
    assert p50[-1] <= p50[0]


def test_engine_agrees_with_scan_statistics():
    """Two independent RNG implementations of the same physics must agree
    on WER within Monte-Carlo error."""
    from repro.core.montecarlo import write_error_rate, write_error_rate_scan
    pulse, n = 200e-12, 64
    w_engine = write_error_rate(AFMTJ_PARAMS, 1.0, pulse, n_samples=n)
    w_scan = float(write_error_rate_scan(AFMTJ_PARAMS, 1.0, pulse, n_samples=n))
    # binomial std at p~0.1, n=64 is ~0.04; allow 3 sigma both ways
    assert abs(w_engine - w_scan) < 0.15, (w_engine, w_scan)


# ------------------------------------------------------------------ caching
def test_campaign_cache_roundtrip(tmp_path):
    grid = CampaignGrid(voltages=(1.0,), pulse_widths=(60e-12,),
                        n_samples=8, dt=0.1e-12, seed=3)
    r1 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path))
    assert not r1.from_cache
    r2 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path))
    assert r2.from_cache and r2.elapsed_s == 0.0
    np.testing.assert_array_equal(r1.crossing_time, r2.crossing_time)
    # any input change must miss: different device params -> new key
    p2 = dataclasses.replace(AFMTJ_PARAMS, alpha=0.02)
    r3 = run_campaign(p2, grid, cache_dir=str(tmp_path))
    assert not r3.from_cache


def test_campaign_cache_corrupt_entry_is_miss(tmp_path):
    from repro.campaign.cache import campaign_key
    grid = CampaignGrid(voltages=(1.0,), pulse_widths=(60e-12,),
                        n_samples=8, dt=0.1e-12, seed=4)
    key = campaign_key(AFMTJ_PARAMS, grid, "pallas")
    (tmp_path / f"{key}.npz").write_bytes(b"not an npz")
    r = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path))
    assert not r.from_cache           # corrupt entry read as miss, re-run


# ------------------------------------------------- crash safety / resume
REPO = Path(__file__).resolve().parents[1]
_ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def _resume_grid():
    return CampaignGrid(voltages=(0.6, 1.2), pulse_widths=(120e-12, 250e-12),
                        temperatures=(300.0, 350.0, 400.0), n_samples=16,
                        dt=0.1e-12, seed=0)


def test_store_arrays_kill_mid_write_never_corrupts(tmp_path):
    """A process SIGKILLed mid-``store_arrays`` leaves only a ``.tmp``
    dropping — the atomic rename never ran, so loads stay clean misses and
    the stale-tmp sweep reclaims the disk."""
    from repro.campaign.cache import gc_stale_tmp, load_arrays, store_arrays

    child = textwrap.dedent("""
        import os, signal, sys
        import numpy as np
        from repro.campaign import cache

        def killer(f, **kw):
            f.write(b"partial write, then the lights go out")
            f.flush()
            os.kill(os.getpid(), signal.SIGKILL)

        np.savez_compressed = killer
        cache.store_arrays("deadbeef", {"a": np.ones(8)}, {},
                           cache_dir=sys.argv[1])
    """)
    r = subprocess.run([sys.executable, "-c", child, str(tmp_path)],
                       env=_ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == -signal.SIGKILL, r.stderr
    leftovers = sorted(p.name for p in tmp_path.iterdir())
    assert leftovers and all(n.endswith(".tmp") for n in leftovers), leftovers
    assert load_arrays("deadbeef", cache_dir=str(tmp_path)) is None
    # fresh droppings survive the default age guard (a live writer may own
    # them); max_age_s=0 reclaims them
    assert gc_stale_tmp(str(tmp_path)) == 0
    assert gc_stale_tmp(str(tmp_path), max_age_s=0.0) == len(leftovers)
    assert not any(tmp_path.iterdir())
    # and the store works normally afterwards
    store_arrays("deadbeef", {"a": np.arange(3.0)}, {"k": 1},
                 cache_dir=str(tmp_path))
    got = load_arrays("deadbeef", cache_dir=str(tmp_path))
    np.testing.assert_array_equal(got["a"], np.arange(3.0))
    assert not list(tmp_path.glob("*.tmp"))


def test_campaign_kill_resume_bit_identical(tmp_path):
    """Acceptance pin: a campaign SIGKILLed after its first launch resumes
    from the slice checkpoints and assembles the crossing tensor
    bit-identically to an uninterrupted run (subprocess kill, real files)."""
    from repro.campaign.grid import bucket_cells

    grid = _resume_grid()
    per = bucket_cells(grid.cells)
    child = textwrap.dedent("""
        import os, signal, sys
        from repro.campaign.engine import run_campaign
        from repro.campaign.grid import CampaignGrid, bucket_cells
        from repro.core.params import AFMTJ_PARAMS

        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0, 400.0),
                            n_samples=16, dt=0.1e-12, seed=0)

        def killer(i, n):
            if i == 0:
                os.kill(os.getpid(), signal.SIGKILL)

        run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                     cache_dir=sys.argv[1],
                     max_cells_per_launch=bucket_cells(grid.cells),
                     on_slice_complete=killer)
    """)
    r = subprocess.run([sys.executable, "-c", child, str(tmp_path)],
                       env=_ENV, capture_output=True, text=True, timeout=560)
    assert r.returncode == -signal.SIGKILL, r.stderr
    assert list(tmp_path.glob("*.npz")), "no slice checkpoint survived"

    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                         max_cells_per_launch=per)
    resumed = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                           cache_dir=str(tmp_path), max_cells_per_launch=per)
    assert not resumed.from_cache
    assert resumed.n_launches == 3 and resumed.n_resumed == 1
    np.testing.assert_array_equal(resumed.crossing_time, fresh.crossing_time)
    # slice checkpoints retired once the whole-campaign entry is durable
    cached = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                          cache_dir=str(tmp_path), max_cells_per_launch=per)
    assert cached.from_cache
    assert len(list(tmp_path.glob("*.npz"))) == 1


def test_campaign_resume_in_process_hook(tmp_path):
    """The ``on_slice_complete`` hook fires after each checkpointed launch;
    aborting through it leaves resumable state (no subprocess needed)."""
    from repro.campaign.grid import bucket_cells

    grid = _resume_grid()
    per = bucket_cells(grid.cells)

    class Abort(Exception):
        pass

    def die_after_two(i, n):
        assert n == 3
        if i == 1:
            raise Abort

    with pytest.raises(Abort):
        run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                     cache_dir=str(tmp_path), max_cells_per_launch=per,
                     on_slice_complete=die_after_two)
    res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                       cache_dir=str(tmp_path), max_cells_per_launch=per)
    assert res.n_resumed == 2 and not res.from_cache
    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                         max_cells_per_launch=per)
    np.testing.assert_array_equal(res.crossing_time, fresh.crossing_time)


def test_campaign_launch_retry_bounded(monkeypatch):
    """Transient launch failures retry with backoff and still produce the
    exact result; a persistent failure raises after max_retries."""
    from repro.campaign import engine

    grid = _resume_grid()
    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False)

    real = engine._integrate_sharded
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient device loss")
        return real(*a, **kw)

    # the launches still compile through the real program
    flaky.lower = real.lower
    monkeypatch.setattr(engine, "_integrate_sharded", flaky)
    res = engine.run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                              use_cache=False, max_retries=1,
                              retry_backoff_s=0.0)
    np.testing.assert_array_equal(res.crossing_time, fresh.crossing_time)

    def always_fails(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("dead device")

    always_fails.lower = real.lower
    calls["n"] = 0
    monkeypatch.setattr(engine, "_integrate_sharded", always_fails)
    with pytest.raises(RuntimeError, match="dead device"):
        engine.run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                            use_cache=False, max_retries=2,
                            retry_backoff_s=0.0)
    assert calls["n"] == 4          # 1 dispatch + 1 sync + 2 bounded retries


def test_campaign_compile_error_raises_without_retry(monkeypatch):
    """A launch the compiler refuses fails identically on every attempt:
    it raises at once, before any dispatch or back-off."""
    from repro.campaign import engine

    calls = {"n": 0}

    class Refused:
        def __call__(self, *a, **kw):
            calls["n"] += 1
            raise AssertionError("a refused launch must never dispatch")

        def lower(self, *a, **kw):
            return self

        def compile(self):
            raise NotImplementedError("Unsupported cast: uint32 -> float32")

    monkeypatch.setattr(engine, "_integrate_sharded", Refused())
    with pytest.raises(NotImplementedError, match="Unsupported cast"):
        engine.run_campaign(AFMTJ_PARAMS, _resume_grid(), backend="ref",
                            use_cache=False, max_retries=3,
                            retry_backoff_s=60.0)
    assert calls["n"] == 0


def test_campaign_key_differs_by_platform(monkeypatch):
    """A surface integrated on one platform is never served as another's:
    the first device's platform is part of the key."""
    from repro.campaign.cache import campaign_key

    grid = _resume_grid()
    here = campaign_key(AFMTJ_PARAMS, grid, "pallas")
    assert campaign_key(AFMTJ_PARAMS, grid, "pallas") == here

    class Other:
        platform = "cpu" if jax.devices()[0].platform == "tpu" else "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Other()])
    assert campaign_key(AFMTJ_PARAMS, grid, "pallas") != here


# ------------------------------------------------------------- grid/packing
def test_pack_plane_layout():
    grid = CampaignGrid(voltages=(0.5, 1.0), pulse_widths=(100e-12,),
                        n_samples=10, dt=0.1e-12)
    state, seeds = pack_plane(grid, AFMTJ_PARAMS, 0)
    assert state.shape[0] == 8 and state.shape[1] % 512 == 0
    assert seeds.shape == (state.shape[1],) and seeds.dtype == jnp.uint32
    # voltage row: sample s of voltage i at lane i*n_samples + s
    v = np.asarray(state[6, :grid.cells])
    np.testing.assert_allclose(v, np.repeat([0.5, 1.0], 10), rtol=1e-6)
    # all real lanes hold unit-norm antiparallel sublattice pairs
    m1 = np.asarray(state[0:3, :grid.cells])
    np.testing.assert_allclose(np.linalg.norm(m1, axis=0), 1.0, atol=1e-6)


def test_run_ensemble_per_cell_voltages():
    """The general entry point (array_mc_sim path): per-cell drives."""
    n = 100
    m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.2))(
        jnp.linspace(0.05, 0.15, n))
    v = jnp.linspace(0.9, 1.1, n)
    res = run_ensemble(AFMTJ_PARAMS, m0, v, 0.1e-12, 300, seed=0)
    assert res.crossing_steps.shape == (n,)
    assert res.switched.dtype == bool

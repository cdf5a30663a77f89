"""Scaling-path tests (DESIGN.md §14): streaming on-device reduction,
pad-don't-demote device planning, lockless claims, multi-process
campaign dedupe, and elastic device-count resume.

Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count`` (the flag must precede the
child's first jax import); everything the children integrate is compared
bit-for-bit against this process's single-device run.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from repro.campaign import CampaignGrid, run_campaign
from repro.campaign.engine import (_hist_step_values, _percentiles_from_hist,
                                   _wer_threshold_steps)
from repro.campaign.grid import bucket_cells
from repro.core.params import AFMTJ_PARAMS
from repro.launch.mesh import CampaignMesh, host_device_flag
from repro.launch.sharding import plan_cell_tiles

REPO = Path(__file__).resolve().parents[1]
_ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def _forced_env(n_devices: int) -> dict:
    env = dict(_ENV)
    old = env.get("XLA_FLAGS", "").strip()
    flag = host_device_flag(n_devices)
    env["XLA_FLAGS"] = f"{old} {flag}".strip() if old else flag
    return env


def _grid(**kw):
    base = dict(voltages=(0.6, 1.2), pulse_widths=(120e-12, 250e-12),
                temperatures=(300.0, 350.0, 400.0), n_samples=16,
                dt=0.1e-12, seed=0)
    base.update(kw)
    return CampaignGrid(**base)


# ------------------------------------------------- streaming reduction
@pytest.fixture(scope="module")
def dense_result():
    return run_campaign(AFMTJ_PARAMS, _grid(), use_cache=False)


def test_streaming_wer_bit_identical(dense_result):
    """Acceptance pin: reduce="stream" never round-trips lane planes, yet
    the WER surface is bit-identical to the dense reduction (host-side f64
    thresholds -> exact on-device integer compares)."""
    grid = _grid()
    res = run_campaign(AFMTJ_PARAMS, grid, use_cache=False, reduce="stream")
    assert res.reduced and res.crossing_time is None
    np.testing.assert_array_equal(res.wer_surface(),
                                  dense_result.wer_surface())
    assert res.n_samples_total == dense_result.n_samples_total
    assert res.wer_counts.shape == (3, 2, 2)
    # the whole point: result transfer is O(grid points) vs O(lane plane)
    assert 0 < res.host_bytes < dense_result.host_bytes


def test_streaming_percentiles_exact_with_per_step_bins(dense_result):
    """With n_bins >= n_steps the histogram resolves single steps, so the
    sketch reconstructs np.nanpercentile's output bit-for-bit."""
    grid = _grid()
    res = run_campaign(AFMTJ_PARAMS, grid, use_cache=False, reduce="stream",
                       n_bins=4096)
    assert 4096 >= grid.n_steps
    assert res.sketch_tolerance == 0.0
    qs = (10.0, 50.0, 90.0, 99.0)
    np.testing.assert_array_equal(res.latency_percentiles(qs),
                                  dense_result.latency_percentiles(qs))


def test_streaming_sketch_within_documented_tolerance(dense_result):
    """Coarse bins trade exactness for footprint; the error must stay
    inside the two-bin-width budget ``sketch_tolerance`` documents."""
    grid = _grid()
    res = run_campaign(AFMTJ_PARAMS, grid, use_cache=False, reduce="stream",
                       n_bins=128)
    tol = res.sketch_tolerance
    assert tol == 2.0 * grid.n_steps * grid.dt / 128
    lp_d = dense_result.latency_percentiles((50.0, 99.0))
    lp_s = res.latency_percentiles((50.0, 99.0))
    assert np.isnan(lp_d).sum() == np.isnan(lp_s).sum()
    err = np.nanmax(np.abs(lp_d - lp_s))
    assert err <= tol, (err, tol)
    # WER stays bit-exact at ANY bin count, and at 128 bins the transfer
    # shrinks by well over the 4x acceptance floor
    np.testing.assert_array_equal(res.wer_surface(),
                                  dense_result.wer_surface())
    assert res.host_bytes * 4 <= dense_result.host_bytes


def test_streaming_cache_separate_from_dense(tmp_path):
    """Streaming entries live under their own derived key: a dense entry
    never satisfies a streaming request (different payload family) and
    vice versa; the second streaming call is a pure cache hit."""
    grid = _grid(seed=11)
    d1 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path))
    s1 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path),
                      reduce="stream")
    assert not s1.from_cache                 # dense entry didn't shadow
    s2 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path),
                      reduce="stream")
    assert s2.from_cache and s2.reduced
    np.testing.assert_array_equal(s1.wer_counts, s2.wer_counts)
    np.testing.assert_array_equal(s1.latency_hist, s2.latency_hist)
    d2 = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path))
    assert d2.from_cache                     # dense entry still intact
    np.testing.assert_array_equal(d1.crossing_time, d2.crossing_time)


def test_streaming_variation_grid():
    """The reduced surfaces grow the leading corner axis exactly like the
    dense ones (corner-major slice layout)."""
    from repro.core.params import CORNER_SS, CORNER_TT, VariationSpec
    spec = VariationSpec(corners=(CORNER_TT, CORNER_SS), seed=7)
    grid = _grid(variation=spec, temperatures=(300.0, 350.0))
    dense = run_campaign(AFMTJ_PARAMS, grid, use_cache=False)
    res = run_campaign(AFMTJ_PARAMS, grid, use_cache=False, reduce="stream",
                       n_bins=4096)
    assert res.wer_counts.shape == (2, 2, 2, 2)
    np.testing.assert_array_equal(res.wer_surface(), dense.wer_surface())
    np.testing.assert_array_equal(res.latency_percentiles((50.0,)),
                                  dense.latency_percentiles((50.0,)))


def test_streaming_multilaunch_checkpoint_resume(tmp_path, dense_result):
    """Streaming launches checkpoint their reduced payloads under the
    ``slice-reduced-*`` kind and resume bit-identically."""
    grid = _grid()
    per = bucket_cells(grid.cells)

    class Abort(Exception):
        pass

    def die_after_two(i, n):
        assert n == 3
        if i == 1:
            raise Abort

    with pytest.raises(Abort):
        run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path),
                     max_cells_per_launch=per, reduce="stream",
                     on_slice_complete=die_after_two)
    res = run_campaign(AFMTJ_PARAMS, grid, cache_dir=str(tmp_path),
                       max_cells_per_launch=per, reduce="stream")
    assert res.n_resumed == 2 and not res.from_cache
    np.testing.assert_array_equal(res.wer_surface(),
                                  dense_result.wer_surface())


def test_wer_threshold_steps_reproduces_f64_compare():
    """The streamed threshold k is the *smallest* integer step whose f64
    time strictly exceeds the pulse — the exact dense comparison."""
    dt = 0.1e-12
    pulses = (100e-12, 123.4e-12, 250e-12, 399.9e-12)
    n_steps = 4001
    ks = _wer_threshold_steps(pulses, dt, n_steps)
    for k, pl in zip(ks, pulses):
        assert np.float64(k) * dt > pl
        assert np.float64(k - 1) * dt <= pl


def test_percentiles_from_hist_matches_numpy():
    """Per-step bins determine the sorted sample multiset, so the sketch
    percentile must equal np.percentile of the reconstructed samples."""
    rng = np.random.default_rng(0)
    n_steps = 50
    steps = rng.integers(0, n_steps, size=400)
    hist = np.bincount(steps, minlength=n_steps)[None, :]
    values = _hist_step_values(n_steps, n_steps) * 1e-12
    qs = (5.0, 50.0, 95.0)
    got = _percentiles_from_hist(hist, values, qs)[0]
    want = np.percentile(steps.astype(np.float64) * 1e-12, qs)
    np.testing.assert_array_equal(got, want)


def test_percentiles_from_hist_all_unswitched_is_nan():
    hist = np.zeros((2, 3, 8), dtype=np.int64)
    out = _percentiles_from_hist(hist, np.arange(8.0), (50.0,))
    assert np.isnan(out).all() and out.shape == (2, 3, 1)


# ------------------------------------------------- device planning (pad)
def test_plan_cell_tiles_units():
    assert plan_cell_tiles(4, 1) == (4, 4)
    assert plan_cell_tiles(4, 3) == (2, 6)     # pad 2 tiles, keep 3 devices
    assert plan_cell_tiles(4, 5) == (1, 5)
    assert plan_cell_tiles(4, 6) == (1, 6)
    assert plan_cell_tiles(8, 8) == (1, 8)
    assert plan_cell_tiles(1, 4) == (1, 4)
    with pytest.raises(AssertionError):
        plan_cell_tiles(0, 4)


@pytest.mark.parametrize("n_dev", [3, 5, 6])
def test_uneven_device_counts_pad_not_demote(n_dev, tmp_path):
    """Regression (pre-PR-10 ``_usable_devices``): a 2048-cell span on a
    3/5/6-device mesh must keep ALL devices (padding the lane plane) and
    produce crossing rows bit-identical to the single-device launch."""
    child = textwrap.dedent("""
        import sys
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.campaign.engine import _device_plan, run_ensemble
        from repro.core import llg
        from repro.core.params import AFMTJ_PARAMS
        from repro.kernels.llg_rk4 import CELL_TILE

        n_dev = int(sys.argv[2])
        assert jax.device_count() == n_dev, jax.devices()
        got_n, plan_cols = _device_plan(2048, None)
        assert got_n == n_dev, (got_n, n_dev)       # padded, NOT demoted
        assert plan_cols % (CELL_TILE * n_dev) == 0 and plan_cols >= 2048

        m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.2))(
            jnp.linspace(0.05, 0.15, 2048))
        res = run_ensemble(AFMTJ_PARAMS, m0, jnp.full((2048,), 1.0),
                           0.1e-12, 200, seed=3, backend="ref")
        np.save(sys.argv[1], res.crossing_steps)
    """)
    out = tmp_path / f"steps{n_dev}.npy"
    r = subprocess.run([sys.executable, "-c", child, str(out), str(n_dev)],
                       env=_forced_env(n_dev), capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, r.stderr

    import jax
    import jax.numpy as jnp
    from repro.campaign.engine import run_ensemble
    from repro.core import llg
    m0 = jax.vmap(lambda t: llg.initial_state(AFMTJ_PARAMS, t, 0.2))(
        jnp.linspace(0.05, 0.15, 2048))
    ref = run_ensemble(AFMTJ_PARAMS, m0, jnp.full((2048,), 1.0),
                       0.1e-12, 200, seed=3, backend="ref")
    np.testing.assert_array_equal(np.load(out), ref.crossing_steps)


def test_four_device_pack_lands_sharded_and_bit_identical(tmp_path):
    """A single-launch campaign whose lanes divide over 4 devices packs its
    block straight onto them, its tiles dealt round-robin — no collective
    in the pack program — and the block equals the 1-device one dealt by
    tile, while the streamed WER counts and histogram and the dense
    crossing times equal the 1-device ones bit for bit."""
    child = textwrap.dedent("""
        import re
        import numpy as np
        import jax
        from repro.campaign import CampaignGrid, engine, run_campaign
        from repro.campaign.engine import _device_plan
        from repro.campaign.grid import _pack_program, deal_tiles, pack_campaign
        from repro.core.params import AFMTJ_PARAMS

        assert jax.device_count() == 4, jax.devices()
        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0, 400.0),
                            n_samples=1200, dt=0.1e-12, seed=3100000063)
        assert _device_plan(3 * 4096, 4) == (4, 3 * 4096)   # no pad lanes
        one = pack_campaign(grid, AFMTJ_PARAMS)
        four = pack_campaign(grid, AFMTJ_PARAMS, n_dev=4)
        for a, b in zip(one[:4], four[:4]):
            assert len(b.sharding.device_set) == 4, b.sharding
            assert b.sharding.spec[-1] == "cells", b.sharding
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_array_equal(deal_tiles(a, 4), b)
            assert not np.array_equal(a, b)        # the deal moved lanes
        hlo = _pack_program.lower(
            np.uint32(0), np.arange(3, dtype=np.int32),
            np.zeros(3, np.uint32), np.ones(3, np.float32),
            np.ones(3, np.float32), np.ones(2, np.float32), np.float32(9),
            p=AFMTJ_PARAMS, n_s=1200, n_dev=4).compile().as_text()
        assert not re.search(
            r"all-gather|all-to-all|all-reduce|collective-permute", hlo)
        seen = []                     # the layout the engine asks for
        real = engine.pack_campaign
        engine.pack_campaign = lambda g, p, n_dev=1: (
            seen.append(n_dev) or real(g, p, n_dev=n_dev))
        kw = dict(use_cache=False, reduce="stream", n_bins=32)
        r1 = run_campaign(AFMTJ_PARAMS, grid, devices=1, **kw)
        r4 = run_campaign(AFMTJ_PARAMS, grid, devices=4, **kw)
        assert seen == [1, 4], seen
        np.testing.assert_array_equal(r4.wer_counts, r1.wer_counts)
        np.testing.assert_array_equal(r4.latency_hist, r1.latency_hist)
        # lanes switch and lanes fail, so the counts compare something
        assert r1.latency_hist.sum() > 0 and r1.wer_counts.max() > 0
        d1 = run_campaign(AFMTJ_PARAMS, grid, devices=1, use_cache=False)
        d4 = run_campaign(AFMTJ_PARAMS, grid, devices=4, use_cache=False)
        assert seen == [1, 4, 1, 4], seen
        np.testing.assert_array_equal(d4.crossing_time, d1.crossing_time)
        np.testing.assert_array_equal(d1.wer_surface(), r1.wer_surface())
    """)
    r = subprocess.run([sys.executable, "-c", child], env=_forced_env(4),
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------ lockless claims
def test_claim_protocol(tmp_path):
    from repro.campaign import cache
    d = str(tmp_path)
    assert cache.try_claim("k1", d, owner="a")
    assert not cache.try_claim("k1", d, owner="b")   # exclusive
    age = cache.claim_age_s("k1", d)
    assert age is not None and age >= 0.0
    assert cache.claim_age_s("nope", d) is None
    # fresh claims are not stealable; stale ones are
    assert not cache.steal_claim("k1", ttl_s=60.0, cache_dir=d, owner="b")
    old = time.time() - 120.0
    os.utime(cache.claim_path("k1", d), (old, old))
    assert cache.steal_claim("k1", ttl_s=60.0, cache_dir=d, owner="b")
    assert cache.release_claim("k1", d)
    assert not cache.release_claim("k1", d)          # second unlink no-ops
    # gc sweeps only stale droppings
    cache.try_claim("k2", d)
    assert cache.gc_stale_claims(d, max_age_s=3600.0) == 0
    assert cache.gc_stale_claims(d, max_age_s=0.0) == 1
    assert cache.claim_age_s("k2", d) is None


def test_multiprocess_mesh_lone_process_completes(tmp_path):
    """A process_count=2 mesh with no peer must still finish: pass B claims
    and integrates everything the absent peer never started."""
    grid = _grid(seed=21)
    per = bucket_cells(grid.cells)
    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                         max_cells_per_launch=per)
    mesh = CampaignMesh(n_devices=1, process_index=0, process_count=2,
                        claim_ttl_s=5.0, poll_s=0.01)
    res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                       cache_dir=str(tmp_path), max_cells_per_launch=per,
                       mesh=mesh)
    assert res.n_computed == res.n_launches == 3
    np.testing.assert_array_equal(res.crossing_time, fresh.crossing_time)
    assert not list(tmp_path.glob("*.claim"))        # all claims retired
    # a late-arriving peer adopts the whole-campaign entry
    late = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                        cache_dir=str(tmp_path), max_cells_per_launch=per,
                        mesh=CampaignMesh(n_devices=1, process_index=1,
                                          process_count=2))
    assert late.from_cache and late.n_computed == 0
    np.testing.assert_array_equal(late.crossing_time, fresh.crossing_time)


def test_multiprocess_mesh_requires_cache():
    mesh = CampaignMesh(n_devices=1, process_index=0, process_count=2)
    with pytest.raises(AssertionError, match="store"):
        run_campaign(AFMTJ_PARAMS, _grid(), use_cache=False, mesh=mesh)


def test_multiprocess_dedupe_two_processes(tmp_path):
    """Acceptance pin: two concurrent processes sharing one cache dir split
    a 3-launch campaign without integrating any launch twice, and both
    assemble the crossing tensor bit-identically to a lone run.

    A file barrier releases both children together (after their jax
    imports), so the claim protocol is exercised under real concurrency.
    """
    grid = _grid(seed=33)
    per = bucket_cells(grid.cells)
    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                         max_cells_per_launch=per)

    child = textwrap.dedent("""
        import hashlib, json, os, sys, time
        import numpy as np
        from repro.campaign import CampaignGrid, run_campaign
        from repro.campaign.grid import bucket_cells
        from repro.core.params import AFMTJ_PARAMS
        from repro.launch.mesh import CampaignMesh

        root, pi = sys.argv[1], int(sys.argv[2])
        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0, 400.0),
                            n_samples=16, dt=0.1e-12, seed=33)
        open(os.path.join(root, f"ready{pi}"), "w").close()
        while not os.path.exists(os.path.join(root, "go")):
            time.sleep(0.005)
        mesh = CampaignMesh(n_devices=1, process_index=pi, process_count=2,
                            claim_ttl_s=120.0, poll_s=0.01)
        res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                           cache_dir=os.path.join(root, "cache"),
                           max_cells_per_launch=bucket_cells(grid.cells),
                           mesh=mesh)
        ct = (res.crossing_time if res.crossing_time is not None else None)
        json.dump({"n_computed": res.n_computed,
                   "n_launches": res.n_launches,
                   "sha": hashlib.sha256(ct.tobytes()).hexdigest()},
                  open(os.path.join(root, f"out{pi}.json"), "w"))
    """)
    procs = [subprocess.Popen(
        [sys.executable, "-c", child, str(tmp_path), str(i)],
        env=_ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)]
    deadline = time.time() + 300
    while not all((tmp_path / f"ready{i}").exists() for i in range(2)):
        assert time.time() < deadline, "children never became ready"
        for pr in procs:
            assert pr.poll() is None, pr.communicate()[1]
        time.sleep(0.01)
    (tmp_path / "go").touch()
    errs = [pr.communicate(timeout=560)[1] for pr in procs]
    assert all(pr.returncode == 0 for pr in procs), errs

    outs = [json.load(open(tmp_path / f"out{i}.json")) for i in range(2)]
    sha = __import__("hashlib").sha256(
        fresh.crossing_time.tobytes()).hexdigest()
    assert all(o["sha"] == sha for o in outs), outs
    assert all(o["n_launches"] == 3 for o in outs)
    total = sum(o["n_computed"] for o in outs)
    assert total == 3, outs                  # every launch integrated once


# ------------------------------------------------- elastic resume (N->M)
def test_elastic_kill_at_4_resume_at_2_devices(tmp_path):
    """Acceptance pin: a campaign SIGKILLed on a 4-device mesh resumes on
    2 devices from the same slice checkpoints (keys are device-count-free)
    and assembles bit-identically to a single-device run."""
    grid = _grid(seed=44)
    per = bucket_cells(grid.cells)
    killer = textwrap.dedent("""
        import os, signal, sys
        import jax
        from repro.campaign import CampaignGrid, run_campaign
        from repro.campaign.grid import bucket_cells
        from repro.core.params import AFMTJ_PARAMS

        assert jax.device_count() == 4, jax.devices()
        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0, 400.0),
                            n_samples=16, dt=0.1e-12, seed=44)

        def die(i, n):
            if i == 0:
                os.kill(os.getpid(), signal.SIGKILL)

        run_campaign(AFMTJ_PARAMS, grid, backend="ref", cache_dir=sys.argv[1],
                     max_cells_per_launch=bucket_cells(grid.cells),
                     on_slice_complete=die)
    """)
    r = subprocess.run([sys.executable, "-c", killer, str(tmp_path)],
                       env=_forced_env(4), capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == -signal.SIGKILL, r.stderr
    assert list(tmp_path.glob("*.npz")), "no slice checkpoint survived"

    resumer = textwrap.dedent("""
        import sys
        import numpy as np
        import jax
        from repro.campaign import CampaignGrid, run_campaign
        from repro.campaign.grid import bucket_cells
        from repro.core.params import AFMTJ_PARAMS
        from repro.launch.mesh import build_campaign_mesh

        assert jax.device_count() == 2, jax.devices()
        mesh = build_campaign_mesh(elastic_from=4)
        assert mesh.n_devices == 2
        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0, 400.0),
                            n_samples=16, dt=0.1e-12, seed=44)
        res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                           cache_dir=sys.argv[1],
                           max_cells_per_launch=bucket_cells(grid.cells),
                           mesh=mesh)
        assert res.n_resumed == 1, res.n_resumed
        np.save(sys.argv[2], res.crossing_time)
    """)
    out = tmp_path / "resumed.npy"
    r = subprocess.run(
        [sys.executable, "-c", resumer, str(tmp_path), str(out)],
        env=_forced_env(2), capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr

    fresh = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                         max_cells_per_launch=per)
    np.testing.assert_array_equal(np.load(out), fresh.crossing_time)


def test_plan_campaign_devices_ladder():
    from repro.runtime.elastic import plan_campaign_devices
    full = plan_campaign_devices(8, 8)
    assert full.mesh_shape == (8,) and full.microbatch_scale == 1
    more = plan_campaign_devices(12, 8)          # extra devices: keep plan
    assert more.mesh_shape == (8,)
    degraded = plan_campaign_devices(3, 8)       # halving ladder: 8->2
    assert degraded.mesh_shape == (2,) and degraded.microbatch_scale == 4
    floor = plan_campaign_devices(0, 4)
    assert floor.mesh_shape == (1,) and floor.microbatch_scale == 4
    assert all(p.axis_names == ("cells",)
               for p in (full, more, degraded, floor))

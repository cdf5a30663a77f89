"""launch.mesh / launch.sharding / runtime.xla_flags tests (DESIGN.md §14).

Device-count-dependent cases run in subprocesses whose ``XLA_FLAGS`` force
1/4/8 host devices (the flag must precede the child's first jax import);
the 8-device streaming campaign is compared bit-for-bit against this
process's single-device run — the ISSUE's multi-device acceptance pin.
"""
import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.launch.mesh import CampaignMesh, host_device_flag
from repro.runtime import xla_flags

REPO = Path(__file__).resolve().parents[1]
_ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def _forced_env(n_devices: int) -> dict:
    env = dict(_ENV)
    old = env.get("XLA_FLAGS", "").strip()
    flag = host_device_flag(n_devices)
    env["XLA_FLAGS"] = f"{old} {flag}".strip() if old else flag
    return env


def _run_child(src: str, *argv: str, env: dict, timeout: float = 560.0):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(src), *argv],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr
    return r


# --------------------------------------------------------------- meshes
def test_host_device_flag():
    assert host_device_flag(8) == "--xla_force_host_platform_device_count=8"


def test_campaign_mesh_validation():
    m = CampaignMesh(n_devices=4)
    assert m.process_count == 1 and m.process_index == 0
    for bad in (dict(n_devices=0), dict(n_devices=1, process_count=0),
                dict(n_devices=1, process_index=2, process_count=2),
                dict(n_devices=1, claim_ttl_s=0.0),
                dict(n_devices=1, poll_s=0.0)):
        with pytest.raises(AssertionError):
            CampaignMesh(**bad)


@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_mesh_construction_forced_devices(n_dev):
    """make_local_mesh and build_campaign_mesh see exactly the forced
    device count, and the campaign mesh clamps requests to it."""
    child = textwrap.dedent("""
        import sys
        import jax
        from repro.launch.mesh import (build_campaign_mesh, data_axes,
                                       make_local_mesh)

        n = int(sys.argv[1])
        assert jax.device_count() == n, jax.devices()
        mesh = make_local_mesh()
        assert mesh.devices.shape == (n, 1)
        assert mesh.axis_names == ("data", "model")
        assert data_axes(mesh) == ("data",)
        if n % 2 == 0:
            mesh2 = make_local_mesh(model=2)
            assert mesh2.devices.shape == (n // 2, 2)

        cm = build_campaign_mesh()
        assert cm.n_devices == n and cm.process_count == 1
        assert build_campaign_mesh(devices=2 * n).n_devices == n   # clamp
        assert build_campaign_mesh(devices=1).n_devices == 1
    """)
    _run_child(child, str(n_dev), env=_forced_env(n_dev))


def test_resolve_pspec_roundtrip_four_devices():
    """Sharding-rule resolution on a real (data=2, model=2) mesh: dividing
    dims map to their mesh axes, non-dividing dims drop to replicated, and
    a device_put through the resolved spec round-trips the array."""
    child = textwrap.dedent("""
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.sharding import resolve_pspec

        assert jax.device_count() == 4
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        rules = {"embed": ("data",), "ffn": ("model",), "both": ("data",
                 "model")}

        assert resolve_pspec((8, 6), ("embed", "ffn"), rules, mesh) == \\
            P("data", "model")
        # 7 % 2 != 0: the embed axis drops, ffn still shards
        assert resolve_pspec((7, 6), ("embed", "ffn"), rules, mesh) == \\
            P(None, "model")
        # multi-axis rule needs divisibility by the axis product
        assert resolve_pspec((8,), ("both",), rules, mesh) == \\
            P(("data", "model"))
        assert resolve_pspec((6,), ("both",), rules, mesh) == P(None)
        # an axis already used by another dim is not reused
        assert resolve_pspec((8, 8), ("embed", "embed"), rules, mesh) == \\
            P("data", None)

        x = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)
        spec = resolve_pspec(x.shape, ("embed", "ffn"), rules, mesh)
        y = jax.device_put(x, NamedSharding(mesh, spec))
        assert len(y.sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(y), x)   # round-trip
    """)
    _run_child(child, env=_forced_env(4))


def test_eight_device_streaming_campaign_matches_one_device(tmp_path):
    """ISSUE acceptance: an 8-host-device smoke campaign — streaming
    reduction sharded over all 8 — produces WER counts and latency
    histograms bit-identical to this process's 1-device run."""
    child = textwrap.dedent("""
        import sys
        import numpy as np
        import jax
        from repro.campaign import CampaignGrid, run_campaign
        from repro.core.params import AFMTJ_PARAMS
        from repro.launch.mesh import build_campaign_mesh

        assert jax.device_count() == 8, jax.devices()
        mesh = build_campaign_mesh()
        assert mesh.n_devices == 8
        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(120e-12, 250e-12),
                            temperatures=(300.0, 350.0), n_samples=16,
                            dt=0.1e-12, seed=9)
        res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                           use_cache=False, reduce="stream", n_bins=128,
                           mesh=mesh)
        assert res.reduced
        np.savez(sys.argv[1], wer=res.wer_counts, hist=res.latency_hist)
    """)
    out = tmp_path / "eight.npz"
    _run_child(child, str(out), env=_forced_env(8))

    from repro.campaign import CampaignGrid, run_campaign
    from repro.core.params import AFMTJ_PARAMS
    grid = CampaignGrid(voltages=(0.6, 1.2), pulse_widths=(120e-12, 250e-12),
                        temperatures=(300.0, 350.0), n_samples=16,
                        dt=0.1e-12, seed=9)
    ref = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                       reduce="stream", n_bins=128, devices=1)
    got = np.load(out)
    np.testing.assert_array_equal(got["wer"], ref.wer_counts)
    np.testing.assert_array_equal(got["hist"], ref.latency_hist)


# ------------------------------------------------------------ xla flags
def test_gpu_scaling_profile_keeps_wer_bit_identical(tmp_path):
    """A campaign run in a child process under the ``gpu-scaling``
    profile's XLA_FLAGS (parsed, inert on CPU) gives WER counts and a
    latency histogram bit-identical to this process's run."""
    from repro.campaign import CampaignGrid, run_campaign
    from repro.core.params import AFMTJ_PARAMS

    child = textwrap.dedent("""
        import sys
        import numpy as np
        from repro.campaign import CampaignGrid, run_campaign
        from repro.core.params import AFMTJ_PARAMS

        grid = CampaignGrid(voltages=(0.6, 1.2),
                            pulse_widths=(20e-12, 40e-12),
                            temperatures=(300.0,), n_samples=512,
                            dt=0.1e-12, seed=0)
        res = run_campaign(AFMTJ_PARAMS, grid, backend="ref",
                           use_cache=False, reduce="stream", n_bins=128)
        np.savez(sys.argv[1], wer=res.wer_counts, hist=res.latency_hist)
    """)
    out = tmp_path / "tuned.npz"
    _run_child(child, str(out), env=xla_flags.apply_profile("gpu-scaling",
                                                            _ENV))
    grid = CampaignGrid(voltages=(0.6, 1.2), pulse_widths=(20e-12, 40e-12),
                        temperatures=(300.0,), n_samples=512, dt=0.1e-12,
                        seed=0)
    ref = run_campaign(AFMTJ_PARAMS, grid, backend="ref", use_cache=False,
                       reduce="stream", n_bins=128)
    got = np.load(out)
    np.testing.assert_array_equal(got["wer"], ref.wer_counts)
    np.testing.assert_array_equal(got["hist"], ref.latency_hist)


def test_flags_for_gpu_scaling_profile():
    s = xla_flags.flags_for("gpu-scaling")
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in s
    assert "--xla_gpu_all_reduce_combine_threshold_bytes=134217728" in s
    assert len(s.split()) == len(xla_flags.PROFILES["gpu-scaling"])


def test_flags_for_host_devices_formats_n():
    assert xla_flags.flags_for("host-devices", n=8) == host_device_flag(8)


def test_flags_for_unknown_profile_raises():
    with pytest.raises(KeyError, match="unknown XLA profile"):
        xla_flags.flags_for("nope")


def test_apply_profile_merges_preserving_existing_flags():
    env = {"XLA_FLAGS": "--xla_abc=1", "OTHER": "x"}
    out = xla_flags.apply_profile("host-devices", env, n=4)
    assert out["XLA_FLAGS"] == f"--xla_abc=1 {host_device_flag(4)}"
    assert out["OTHER"] == "x"
    assert env["XLA_FLAGS"] == "--xla_abc=1"      # input env not mutated
    out2 = xla_flags.apply_profile("gpu-scaling", {})
    assert out2["XLA_FLAGS"] == xla_flags.flags_for("gpu-scaling")


def test_apply_profile_refuses_live_process():
    """jax is initialized in this test process (campaign imports), so an
    env=None apply must warn and leave XLA_FLAGS unmerged."""
    import jax

    jax.devices()                                  # ensure backend is up
    assert xla_flags.jax_initialized()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = xla_flags.apply_profile("gpu-scaling")
    assert any(issubclass(x.category, RuntimeWarning) for x in w)
    assert out.get("XLA_FLAGS", "") == os.environ.get("XLA_FLAGS", "")

"""The campaign cells at a tiny size on the CPU: a sound run is correct;
the bfloat16 control and faults planted in the timed path are not."""
import jax
import jax.numpy as jnp
import pytest

from bench_tiny import assert_result_shape, control_fails, run_tiny

CELL = "afmtj.wer_campaign"


@pytest.fixture(autouse=True)
def fresh_programs():
    # planted faults live in traced code: no executable may outlive a test
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct():
    res = run_tiny(CELL)
    assert_result_shape(res, "llg_samples_per_s")
    assert res["correct"], res["compared"]


def test_bfloat16_control_fails():
    readings, fails = control_fails(CELL)
    assert fails, readings


def test_half_of_the_lanes_left_out_fails(monkeypatch):
    from repro.campaign import engine

    orig = engine._reduce_rows

    def half(out, kmin, *, n_slices, slice_cells, n_v, n_s, **kw):
        # the second half of every (T, V) block repeats the first half: the
        # counts then come from half the samples, scaled up
        row = out[7, : n_slices * slice_cells].reshape(n_slices, slice_cells)
        real = row[:, : n_v * n_s].reshape(n_slices, n_v, n_s)
        h = n_s // 2
        real = real.at[:, :, h:2 * h].set(real[:, :, :h])
        row = row.at[:, : n_v * n_s].set(real.reshape(n_slices, n_v * n_s))
        out = out.at[7, : n_slices * slice_cells].set(row.reshape(-1))
        return orig(out, kmin, n_slices=n_slices, slice_cells=slice_cells,
                    n_v=n_v, n_s=n_s, **kw)

    monkeypatch.setattr(engine, "_reduce_rows", half)
    assert not run_tiny(CELL)["correct"]


def test_crossings_altered_in_the_kernel_fails(monkeypatch):
    from repro.campaign import engine

    orig = engine.llg_rk4_pallas

    def late(state, p, dt, n_steps, *a, **kw):
        out = orig(state, p, dt, n_steps, *a, **kw)
        row = out[7]
        return out.at[7].set(jnp.where(row < n_steps, row + 64.0, row))

    monkeypatch.setattr(engine, "llg_rk4_pallas", late)
    assert not run_tiny(CELL)["correct"]


def test_kernel_that_returns_its_state_unchanged_fails(monkeypatch):
    from repro.campaign import engine

    monkeypatch.setattr(engine, "llg_rk4_pallas",
                        lambda state, *a, **kw: state)
    assert not run_tiny(CELL)["correct"]

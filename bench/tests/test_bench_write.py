"""The write-verify cell at a tiny size on the CPU: a sound run is correct;
the bfloat16 control and faults planted under the ladder are not."""
import dataclasses

import numpy as np

from bench_tiny import assert_result_shape, control_fails, run_tiny

CELL = "afmtj.write_verify"


def test_sound_run_is_correct():
    res = run_tiny(CELL)
    assert_result_shape(res, "write_ladder_ms")
    assert res["correct"], res["compared"]


def test_bfloat16_control_fails():
    readings, fails = control_fails(CELL)
    assert fails, readings


def _patch_rounds(monkeypatch, alter):
    """Alter every round's crossing times where the campaign returns them."""
    from repro.imc import write_path

    orig = write_path.run_campaign

    def patched(p, grid, **kw):
        res = orig(p, grid, **kw)
        ct = np.array(res.crossing_time)
        return dataclasses.replace(res, crossing_time=alter(ct, grid))

    monkeypatch.setattr(write_path, "run_campaign", patched)


def test_half_of_the_cells_left_out_fails(monkeypatch):
    def half(ct, grid):
        n = ct.shape[-1]
        ct[..., n // 2: 2 * (n // 2)] = ct[..., : n // 2]
        return ct

    _patch_rounds(monkeypatch, half)
    assert not run_tiny(CELL)["correct"]


def test_crossings_altered_fails(monkeypatch):
    _patch_rounds(monkeypatch, lambda ct, grid: ct + 64 * grid.dt)
    assert not run_tiny(CELL)["correct"]

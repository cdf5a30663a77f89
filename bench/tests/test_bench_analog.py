"""The analog forward cell at a tiny size on the CPU: a sound run is
correct, the control at ``high`` reads far above it, and faults planted
under the forward are not correct."""
import jax
import pytest

from bench_tiny import TINY_MODEL, assert_result_shape, control_fails, run_tiny

CELL = "qwen2-0.5b.analog_eval"


@pytest.fixture
def fresh_forward():
    from repro.imc import model_analog

    def clear():
        model_analog._jitted_fake_forward.cache_clear()
        jax.clear_caches()

    clear()
    yield
    clear()


def test_sound_run_is_correct():
    res = run_tiny(CELL)
    assert_result_shape(res, "analog_forward_ms")
    assert res["correct"], res["compared"]


def test_high_precision_control_reads_far_above_a_sound_run():
    # at this size float32 association moves the logits by ~5e-7 of their
    # scale and three bfloat16 products by ~1e-5; the cell's limits are set
    # from chip readings at full depth and width, where the control reads
    # ~1e-2 (PERF.md), so the test asserts the separation itself
    sound = run_tiny(CELL, seed=7)["compared"]
    readings, _ = control_fails(CELL, seed=7)
    for name, c in sound.items():
        assert readings[name] > 10 * c["value"], (name, readings, sound)


def _patch_linears(monkeypatch, alter):
    """Alter every crossbar read where the fused kernel produces it."""
    from repro.imc import model_analog

    orig = model_analog._fake_mvm_body

    def patched(x, w, bl, scal, **kw):
        return alter(orig(x, w, bl, scal, **kw), w)

    monkeypatch.setattr(model_analog, "_fake_mvm_body", patched)


def test_half_of_the_batch_left_out_fails(monkeypatch, fresh_forward):
    def half(y, w):
        m = y.shape[0] // 2
        return y.at[m:2 * m].set(y[:m])

    _patch_linears(monkeypatch, half)
    assert not run_tiny(CELL)["correct"]


def test_head_output_altered_fails(monkeypatch, fresh_forward):
    vocab = TINY_MODEL["vocab_size"]

    def altered(y, w):
        # one logit of one position moved by a tenth of its row's scale
        if w.shape[1] != vocab:
            return y
        return y.at[0, 0].add(0.1 * abs(y[0]).max())

    _patch_linears(monkeypatch, altered)
    assert not run_tiny(CELL)["correct"]

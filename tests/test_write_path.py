"""Stochastic write path tests: scheduler determinism, retry physics,
accounting invariants, and the circuit/system threading (DESIGN.md §7)."""
import dataclasses

import numpy as np
import pytest

from repro.imc.write_path import (WritePolicy, measured_write_timings,
                                  nominal_pulse, program_bits, write_verify)

# Short pulse (below the AFMTJ mean switching time at 1.0 V) so the retry
# machinery is actually exercised; shared across tests to share compiles.
PULSE = 130e-12
N = 96


def _policy(**kw):
    base = dict(v_write=1.0, pulse=PULSE, max_attempts=3, seed=5,
                use_cache=False)
    base.update(kw)
    return WritePolicy(**base)


@pytest.fixture(scope="module")
def afmtj_result():
    return write_verify("afmtj", N, _policy())


# ------------------------------------------------------------- determinism
def test_deterministic_at_fixed_seed(afmtj_result):
    r2 = write_verify("afmtj", N, _policy())
    np.testing.assert_array_equal(afmtj_result.attempts, r2.attempts)
    np.testing.assert_array_equal(afmtj_result.success, r2.success)
    np.testing.assert_array_equal(afmtj_result.crossing_time,
                                  r2.crossing_time)
    np.testing.assert_array_equal(afmtj_result.energy, r2.energy)


def test_seed_changes_the_draw(afmtj_result):
    r2 = write_verify("afmtj", N, _policy(seed=6))
    assert not np.array_equal(afmtj_result.crossing_time, r2.crossing_time)


# ------------------------------------------------------------ retry physics
def test_retries_grow_as_voltage_drops():
    """Lower drive eats the STT overdrive: at a fixed pulse the per-attempt
    WER rises, so the scheduler pays monotonically more attempts."""
    means = [write_verify("afmtj", N, _policy(v_write=v,
                                              max_attempts=4)).attempts_mean
             for v in (1.15, 1.0, 0.85)]
    assert means[0] <= means[1] <= means[2], means
    assert means[2] > means[0], means


def test_mtj_needs_more_retries_at_equal_pulse(afmtj_result):
    """At the AFMTJ's (picosecond) pulse width the FM baseline virtually
    never verifies — the retry counts carry the device asymmetry."""
    r_mtj = write_verify("mtj", N, _policy())
    assert r_mtj.attempts_mean > afmtj_result.attempts_mean
    assert r_mtj.residual_ber >= afmtj_result.residual_ber
    assert r_mtj.residual_ber > 0.9            # ~every cell fails


def test_single_pulse_wer_matches_histogram(afmtj_result):
    r = afmtj_result
    hist = r.retry_histogram()
    assert hist[0] == 0 and hist.sum() == N
    assert r.single_pulse_wer == pytest.approx(1.0 - hist[1] / N)
    # short pulse: retries must actually occur
    assert r.attempts_mean > 1.0


# ------------------------------------------------------- accounting invariants
def test_latency_and_energy_accounting(afmtj_result):
    r = afmtj_result
    pol = r.policy
    np.testing.assert_allclose(
        r.latency, r.attempts * (pol.t_rc + r.pulse + pol.t_verify))
    # two-state energy bounds per attempt: G_AP * pulse <= e <= G_P *
    # (pulse + t_rc) at v^2 (e_verify = 0 here)
    from repro.core.params import AFMTJ_PARAMS as P
    v2 = pol.v_write**2
    lo = r.attempts * v2 / P.r_antiparallel * r.pulse
    hi = r.attempts * v2 / P.r_parallel * (r.pulse + pol.t_rc) * (1 + 1e-9)
    assert (r.energy >= lo).all() and (r.energy <= hi).all()
    # crossing times are only defined for verified cells, inside the pulse
    ok = r.success
    assert np.isnan(r.crossing_time[~ok]).all()
    assert (r.crossing_time[ok] <= r.pulse).all()


def test_row_granular_stats(afmtj_result):
    r = afmtj_result
    rows = r.row_attempts(cols=8)
    assert rows.shape == (N // 8,)
    np.testing.assert_array_equal(
        rows, r.attempts.reshape(-1, 8).max(axis=1))
    assert r.row_latency_percentile(8, 100.0) == pytest.approx(
        rows.max() * r.cycle)


def test_program_bits_error_map():
    rng = np.random.default_rng(0)
    target = (rng.random((8, 8)) < 0.5).astype(np.uint8)
    res, err = program_bits(target, "afmtj", _policy(max_attempts=2))
    assert res.attempts.size == int(target.sum())
    assert err.shape == target.shape
    assert err[target == 0].sum() == 0          # unwritten cells never err
    assert err.sum() == int((~res.success).sum())


# ------------------------------------------------- circuit/system threading
def test_subarray_measured_write_path():
    from repro.circuit.subarray import make_subarray

    closed = make_subarray("afmtj", rows=8, cols=8).timings
    assert closed.write_attempts == 1.0
    assert closed.write_residual_ber == 0.0
    assert closed.write_percentile is None

    measured = make_subarray("afmtj", rows=8, cols=8,
                             write_percentile=99.0).timings
    assert measured.write_percentile == 99.0
    assert measured.write_attempts >= 1.0
    # the percentile row time covers at least one full attempt cycle and
    # sits above the closed-form single-pulse time (retry + margin tail)
    assert measured.t_write > closed.t_write
    assert measured.e_write_bit > 0.0


def test_system_result_threads_write_stats():
    from repro.circuit.subarray import make_subarray
    from repro.imc.evaluate import evaluate_workload
    from repro.imc.hierarchy import IMCHierarchy, IMCLevel, LEVELS
    from repro.imc.workloads import WORKLOADS

    sub = make_subarray("afmtj", rows=8, cols=8, write_percentile=99.0)
    hier = IMCHierarchy("afmtj", {s.name: IMCLevel(spec=s, timings=sub.timings)
                                  for s in LEVELS})
    r = evaluate_workload(WORKLOADS["mat_add"], hier)
    assert r.t_write_op == sub.timings.t_write
    assert r.write_attempts == sub.timings.write_attempts
    assert r.write_residual_ber == sub.timings.write_residual_ber


def test_evaluate_system_defaults_are_single_pulse():
    from repro.imc.evaluate import evaluate_system

    for r in evaluate_system("afmtj").values():
        assert r.write_attempts == 1.0 and r.t_write_op > 0.0


# ------------------------------------------------ read-path BER injection
def test_write_ber_degrades_analog_accuracy():
    import jax
    import jax.numpy as jnp

    from repro.imc.analog_pipeline import AnalogConfig, mvm_accuracy

    kw, kx = jax.random.split(jax.random.PRNGKey(1))
    w = jax.random.normal(kw, (64, 48), jnp.float32) / 8.0
    x = jax.random.normal(kx, (4, 64), jnp.float32)
    base = AnalogConfig(adc_bits=0, ir_drop=False)
    clean = mvm_accuracy(w, x, cfg=base)
    dirty = mvm_accuracy(w, x, cfg=dataclasses.replace(base, write_ber=0.2))
    assert clean.nmse < 1e-9                    # ideal path stays exact
    assert dirty.nmse > 100 * max(clean.nmse, 1e-12)
    assert dirty.write_ber == 0.2


def test_write_energy_accuracy_surface_tradeoff():
    from repro.configs.registry import ARCHS
    from repro.imc.mapping import write_energy_accuracy_surface

    surf = write_energy_accuracy_surface(
        ARCHS["gemma2-2b"], kind="afmtj", wer_targets=(3e-1, 1e-2),
        policy=_policy(max_attempts=1), n_cells=64,
        cap_k=64, cap_n=32, batch=2)
    loose, tight = surf[3e-1], surf[1e-2]
    assert tight.attempts_budget > loose.attempts_budget
    assert tight.write_ber < loose.write_ber
    assert tight.e_write_bit > loose.e_write_bit
    assert tight.report.nmse < loose.report.nmse


@pytest.fixture(scope="module")
def nominal_pair():
    """AFMTJ and MTJ write-verify at 1.0 V, each at its own nominal pulse."""
    pol = WritePolicy(v_write=1.0, max_attempts=4, seed=0, use_cache=False)
    return {kind: write_verify(kind, 64, pol) for kind in ("afmtj", "mtj")}


@pytest.mark.parametrize("stat", ["latency", "energy"])
def test_measured_write_ratio_at_nominal_pulse(nominal_pair, stat):
    """The paper's headline write ratios (~8x latency, ~9x energy at
    1.0 V) come out of the measured write-verify path, retries included,
    within 5-13x."""
    mean = {k: (r.latency.mean() if stat == "latency" else r.energy_mean())
            for k, r in nominal_pair.items()}
    assert 5.0 < mean["mtj"] / mean["afmtj"] < 13.0, mean


# ------------------------------------------------------------ pulse policy
def test_nominal_pulse_ordering():
    assert nominal_pulse("mtj", 1.0) > 4 * nominal_pulse("afmtj", 1.0)
    pol = WritePolicy(v_write=1.0)
    assert pol.resolved_pulse("afmtj") == pytest.approx(
        nominal_pulse("afmtj", 1.0) * pol.pulse_margin)
    explicit = WritePolicy(v_write=1.0, pulse=PULSE)
    assert explicit.resolved_pulse("afmtj") == PULSE

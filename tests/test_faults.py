"""Hard-fault injection and graceful degradation (DESIGN.md §13): fault-code
planes through the fused kernel, CRN pairing across repair policies, repair
semantics, the repair-capacity yield model, and cost/system charging.

The acceptance pins live here: fault-free paths stay bit-identical when a
zero-rate spec is present, kernel and oracle agree bit-for-bit on raw
currents with fault planes active, and a fault-rate sweep adds zero XLA
compiles (rates are data; the repair policy is the compile key)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.imc import faults as hf
from repro.imc.analog_pipeline import (AnalogConfig, analog_matmul,
                                       kernel_operands, program_weights)
from repro.imc.faults import (FaultSpec, REPAIR_NONE, REPAIR_SPARE,
                              REPAIR_SPARE_ECC, apply_repair,
                              column_ok_plane, fault_code_plane)
from repro.imc.model_analog import fake_analog_matmul
from repro.kernels import ops, ref
from repro.kernels.fake_analog import (FAULT_DEAD, FAULT_NEG_OFF,
                                       FAULT_NEG_ON, FAULT_POS_OFF,
                                       FAULT_POS_ON, fail_bit)


def _wx(k=200, n=150, m=7, seed=0):
    kw, kx = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kw, (k, n)) / k**0.5,
            jax.random.normal(kx, (m, k)))


# --- defect planes -----------------------------------------------------------

def test_zero_rate_plane_is_empty():
    """Uniforms live in (0, 1], so ``u <= 0`` is never true: a zero-rate
    spec draws the exactly-empty defect map."""
    code = fault_code_plane(64, 48, seed=np.uint32(0), stuck_on=0.0,
                            stuck_off=0.0, dead_row=0.0)
    col = column_ok_plane(48, seed=np.uint32(0), dead_col=0.0)
    assert np.array_equal(np.asarray(code), np.zeros((64, 48), np.float32))
    assert np.array_equal(np.asarray(col), np.ones((48,), np.float32))


def test_monotone_coupling_across_rates():
    """The u <= rate threshold test shares uniforms across rates, so the
    defective set at a lower rate is a subset of the set at a higher one
    (a defect never heals when the rate goes up)."""
    lo, hi = FaultSpec.at_rate(3e-3, seed=5), FaultSpec.at_rate(3e-2, seed=5)
    c_lo, k_lo = (np.asarray(a) for a in lo.planes(256, 128))
    c_hi, k_hi = (np.asarray(a) for a in hi.planes(256, 128))
    assert ((c_lo > 0) <= (c_hi > 0)).all()
    assert (k_hi <= k_lo).all()              # dead columns only accumulate
    assert (c_hi > 0).sum() > (c_lo > 0).sum()


def test_crn_invariance_across_policies():
    """The defect draw depends only on (seed, stream, lane) — never on the
    repair policy — and ``apply_repair`` consumes no RNG: every policy
    transforms the IDENTICAL map, and repair only ever *removes* or
    *reclassifies* defects (repaired defect positions are a subset)."""
    spec = FaultSpec.at_rate(1e-2, seed=3)
    code, col = spec.planes(256, 128)
    code2, col2 = spec.planes(256, 128)
    assert np.array_equal(np.asarray(code), np.asarray(code2))
    assert np.array_equal(np.asarray(col), np.asarray(col2))
    for pol in (REPAIR_SPARE, REPAIR_SPARE_ECC):
        rc, rk = apply_repair(code, col, pol)
        assert ((np.asarray(rc) > 0) <= (np.asarray(code) > 0)).all()
        assert (np.asarray(rk) >= np.asarray(col)).all()   # revive only


def test_apply_repair_semantics_hand_built():
    """ECC clears the first stuck pair per row, masking converts remaining
    stuck-ON shorts to dead pairs, the worst row is remapped to a spare,
    and one dead column is revived."""
    code = np.zeros((4, 4), np.float32)
    code[0, 0] = FAULT_POS_ON                 # short: ECC eats it (1st/row)
    code[1, 0] = FAULT_NEG_OFF                # ECC eats it
    code[1, 2] = FAULT_POS_ON                 # 2nd stuck in row -> masked
    code[2, :] = FAULT_DEAD                   # dead row: worst row
    col = np.array([1.0, 0.0, 1.0, 0.0], np.float32)
    pol = hf.RepairPolicy(name="t", spare_rows=1, spare_cols=1,
                          mask_pairs=True, ecc_cells_per_row=1)
    rc, rk = (np.asarray(a) for a in
              apply_repair(jnp.asarray(code), jnp.asarray(col), pol))
    assert rc[0, 0] == 0.0 and rc[1, 0] == 0.0        # ECC corrections
    assert rc[1, 2] == FAULT_DEAD                     # masked short
    assert (rc[2] == 0.0).all()                       # spare-row remap
    assert rk[1] == 1.0 and rk[3] == 0.0              # one column revived
    # REPAIR_NONE / None are strict passthroughs
    for pol0 in (None, REPAIR_NONE):
        pc, pk = apply_repair(jnp.asarray(code), jnp.asarray(col), pol0)
        assert np.array_equal(np.asarray(pc), code)
        assert np.array_equal(np.asarray(pk), col)


def test_endurance_wear_folds_into_stuck_off():
    s = FaultSpec(wear_per_cycle=1e-6, write_cycles=1e5)
    assert s.wear_rate == pytest.approx(1.0 - (1.0 - 1e-6) ** 1e5)
    assert s.stuck_off_effective == pytest.approx(s.wear_rate)
    assert s.any_faults
    both = FaultSpec(stuck_off_rate=0.01, wear_per_cycle=1e-6,
                     write_cycles=1e5)
    assert both.stuck_off_effective > max(0.01, s.wear_rate)
    assert not FaultSpec().any_faults


# --- kernel vs oracle with fault codes ---------------------------------------

def test_fault_codes_kernel_matches_oracle():
    """The full 7-bit fault alphabet (write-ber floors + stuck-at + dead)
    through the Pallas kernel equals the jnp oracle on raw operands."""
    from repro.kernels.fake_analog import (AUX_ROWS, ROW_ATT_NEG, ROW_ATT_POS,
                                           ROW_DECODE, ROW_G_AP, ROW_G_FS,
                                           ROW_G_SCALE, ROW_I_MAX,
                                           ROW_R_ACCESS, FAIL_CODE_MAX,
                                           fake_analog_mac_pallas)

    m, k, n = 5, 150, 70
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    v = jax.random.normal(ks[0], (m, k)) * 0.1
    wn = jnp.tanh(jax.random.normal(ks[1], (k, n)))
    fail = jax.random.randint(ks[2], (k, n), 0,
                              int(FAIL_CODE_MAX) + 1).astype(jnp.float32)
    aux = jnp.zeros((AUX_ROWS, n), jnp.float32)
    aux = aux.at[ROW_ATT_POS].set(0.95).at[ROW_ATT_NEG].set(0.93)
    aux = aux.at[ROW_I_MAX].set(2e-3).at[ROW_DECODE].set(1234.5)
    aux = aux.at[ROW_G_AP].set(2e-4).at[ROW_G_FS].set(3e-4)
    aux = aux.at[ROW_G_SCALE].set(1.0).at[ROW_R_ACCESS].set(1e3)
    kw = dict(adc_bits=5, apply_fet=False, use_fail=True)
    out_k = np.asarray(fake_analog_mac_pallas(v, wn, fail, aux,
                                              interpret=True, **kw))
    out_r = np.asarray(ref.ref_fake_analog(v, wn, fail, aux, **kw))
    np.testing.assert_allclose(out_k, out_r, rtol=1e-6, atol=1e-6 * 1234.5)


def test_fail_bit_decode_alphabet():
    """``fail_bit`` reads every bit of every representable code exactly."""
    bits = (1.0, 2.0, FAULT_POS_OFF, FAULT_NEG_OFF, FAULT_POS_ON,
            FAULT_NEG_ON, FAULT_DEAD)
    codes = jnp.arange(128.0)
    for b in bits:
        expect = (np.arange(128) & int(b)) > 0
        got = np.asarray(fail_bit(codes, b))
        assert np.array_equal(got, expect), b


# --- fault-free bit-identity -------------------------------------------------

def test_fake_zero_rate_spec_bit_identical():
    """Presence of an all-zero-rate spec traces the fault machinery in but
    produces the empty defect map — outputs bit-identical to faults=None."""
    w, x = _wx()
    base = AnalogConfig(adc_bits=6)
    zero = dataclasses.replace(base, faults=FaultSpec.at_rate(0.0))
    y0 = np.asarray(fake_analog_matmul(w, x, cfg=base))
    yz = np.asarray(fake_analog_matmul(w, x, cfg=zero))
    assert np.array_equal(y0, yz)


def test_device_zero_rate_spec_bit_identical():
    """Same pin on the device programming path, IR drop included (the
    live-column calibration keeps the no-fault association exactly)."""
    w, x = _wx(k=130, n=100, m=5)
    base = AnalogConfig(adc_bits=6)
    zero = dataclasses.replace(base, faults=FaultSpec.at_rate(0.0),
                               repair=REPAIR_SPARE)
    a0 = program_weights(w, "afmtj", base)
    az = program_weights(w, "afmtj", zero)
    assert np.array_equal(np.asarray(a0.g_diff), np.asarray(az.g_diff))
    assert a0.att_mean == az.att_mean
    y0 = np.asarray(analog_matmul(a0, x))
    yz = np.asarray(analog_matmul(az, x))
    assert np.array_equal(y0, yz)


# --- device vs fake parity with faults active --------------------------------

def _fault_cfg(rate=1e-2, repair=None, **kw):
    return AnalogConfig(adc_bits=6, faults=FaultSpec.at_rate(rate, seed=2),
                        repair=repair, **kw)


def test_device_fake_fault_raw_currents_bit_equal():
    """With stuck-at + dead-line planes active (no IR drop, shared full
    scale) the quantized bit-line currents are bit-equal between the device
    programming path and the fused fake kernel."""
    w, x = _wx()
    for repair in (None, REPAIR_SPARE):
        cfg = _fault_cfg(ir_drop=False, repair=repair)
        arr = program_weights(w, "afmtj", cfg)
        v, i_max, _ = kernel_operands(arr, x)
        i_dev = np.asarray(ops.bitline_mac(v, arr.g_diff, 6, i_max=i_max))
        i_fake = np.asarray(fake_analog_matmul(w, x, cfg=cfg, i_max=i_max,
                                               decode=False))
        assert np.array_equal(i_fake, i_dev), repair


def test_device_fake_fault_decoded_parity():
    """Decoded outputs with faults + repair + IR drop agree to f32 decode
    rounding — the dead-column live-mean calibration matches on both paths."""
    w, x = _wx(k=130, n=100, m=5, seed=4)
    cfg = _fault_cfg(repair=REPAIR_SPARE)
    arr = program_weights(w, "afmtj", cfg)
    _, i_max, _ = kernel_operands(arr, x)
    y_dev = np.asarray(analog_matmul(arr, x))
    y_fake = np.asarray(fake_analog_matmul(w, x, cfg=cfg, i_max=i_max))
    np.testing.assert_allclose(y_fake, y_dev, rtol=1e-5,
                               atol=1e-5 * np.abs(y_dev).max())


def test_repair_reduces_error_on_same_defect_map():
    """CRN pairing makes the comparison honest: on the identical defect
    map, spare-line repair must reduce the MVM error vs no repair."""
    w, x = _wx(k=130, n=100, m=5, seed=6)
    ideal = np.asarray(x @ w)
    y_none = np.asarray(fake_analog_matmul(w, x, cfg=_fault_cfg(3e-2)))
    y_rep = np.asarray(fake_analog_matmul(
        w, x, cfg=_fault_cfg(3e-2, repair=REPAIR_SPARE)))
    mse_none = float(np.mean((y_none - ideal) ** 2))
    mse_rep = float(np.mean((y_rep - ideal) ** 2))
    assert mse_rep < mse_none, (mse_rep, mse_none)


def test_drift_is_device_path_only():
    w, x = _wx(k=64, n=32, m=2)
    cfg = AnalogConfig(adc_bits=6,
                       faults=FaultSpec(drift_sigma=0.1))
    with pytest.raises(NotImplementedError):
        fake_analog_matmul(w, x, cfg=cfg)
    # device path: mean-preserving lognormal perturbation of the cells
    a0 = program_weights(w, "afmtj", AnalogConfig(adc_bits=6))
    ad = program_weights(w, "afmtj", cfg)
    g0, gd = np.asarray(a0.g_diff), np.asarray(ad.g_diff)
    assert not np.array_equal(g0, gd)
    assert abs(gd.mean() - g0.mean()) < 5.0 * np.abs(g0).mean() * 0.1


# --- compile discipline ------------------------------------------------------

def test_fault_rate_sweep_adds_zero_compiles():
    """Fault rates and seeds are traced data: a whole rate sweep under one
    repair policy reuses ONE executable.  Changing the policy re-keys."""
    from repro.imc.model_analog import _jitted_fake_mvm

    w, x = _wx(k=96, n=64, m=3)
    args = (6, False, False, True, False, True, True, True)
    _jitted_fake_mvm(*args, REPAIR_SPARE)._clear_cache()
    _jitted_fake_mvm(*args, None)._clear_cache()
    for r in (0.0, 1e-3, 3e-3, 1e-2):
        fake_analog_matmul(
            w, x, cfg=AnalogConfig(adc_bits=6,
                                   faults=FaultSpec.at_rate(r, seed=1),
                                   repair=REPAIR_SPARE))
    assert _jitted_fake_mvm(*args, REPAIR_SPARE)._cache_size() == 1
    assert _jitted_fake_mvm(*args, None)._cache_size() == 0


# --- repair-capacity yield + cost charging -----------------------------------

def test_repair_yield_bounds_and_ordering():
    from repro.imc.mapping import repair_yield

    for rate in (1e-4, 1e-3, 1e-2):
        f = FaultSpec.at_rate(rate)
        ys = [repair_yield(f, pol) for pol in (None, REPAIR_SPARE,
                                               REPAIR_SPARE_ECC)]
        assert all(0.0 <= y <= 1.0 for y in ys)
        assert ys[1] >= ys[0] and ys[2] >= ys[0]
    # yield falls monotonically with rate under every policy
    for pol in (None, REPAIR_SPARE):
        ys = [repair_yield(FaultSpec.at_rate(r), pol)
              for r in (1e-5, 1e-4, 1e-3, 1e-2)]
        assert all(a >= b for a, b in zip(ys, ys[1:])), (pol, ys)


def test_fault_cost_factors_inert_and_active():
    from repro.imc.mapping import fault_cost_factors

    assert fault_cost_factors(None) == (1.0, 1.0, 1.0)
    assert fault_cost_factors(FaultSpec.at_rate(0.0)) == (1.0, 1.0, 1.0)
    y, ovh, stretch = fault_cost_factors(FaultSpec.at_rate(1e-3),
                                         REPAIR_SPARE)
    assert 0.0 < y <= 1.0 and ovh > 1.0 and stretch >= ovh


def test_cost_model_fault_charging():
    """Nominal prices are bit-for-bit unchanged without faults; with them,
    no-repair stretches latency far more than spare-line repair."""
    from repro.imc.cost_model import imc_cost_model

    nom = imc_cost_model("afmtj")
    assert dataclasses.asdict(nom) == dataclasses.asdict(
        imc_cost_model("afmtj", faults=None))
    f = FaultSpec.at_rate(1e-3)
    bare = imc_cost_model("afmtj", faults=f)
    rep = imc_cost_model("afmtj", faults=f, repair=REPAIR_SPARE)
    assert bare.t_mac > nom.t_mac
    assert nom.t_mac < rep.t_mac < bare.t_mac
    assert rep.array_yield > bare.array_yield
    assert rep.e_mac > nom.e_mac          # spare/ECC area is not free


def test_evaluate_system_fault_charging():
    """Fig. 4 numbers stay bit-for-bit with defaults off; charging faults
    stretches t_imc and repair recovers most of it."""
    from repro.imc.evaluate import evaluate_system

    nom = evaluate_system("afmtj")
    nom2 = evaluate_system("afmtj", faults=None)
    for k in nom:
        assert dataclasses.asdict(nom[k]) == dataclasses.asdict(nom2[k])
        assert nom[k].array_yield == 1.0
    f = FaultSpec.at_rate(1e-3)
    bare = evaluate_system("afmtj", faults=f)
    rep = evaluate_system("afmtj", faults=f, repair=REPAIR_SPARE)
    assert bare["mac"].t_imc > nom["mac"].t_imc
    assert rep["mac"].t_imc < bare["mac"].t_imc
    assert rep["mac"].array_yield > bare["mac"].array_yield


# --- serving degradation curve -----------------------------------------------

def test_fault_slo_curve_degrades_monotonically():
    from repro.launch.simulate import fault_slo_curve

    pts = fault_slo_curve(rates=(0.0, 3e-4, 1e-3),
                          policies=(None, REPAIR_SPARE), n_requests=400)
    none = [p for p in pts if p.repair == "none"]
    spare = [p for p in pts if p.repair == "spare"]
    # same healthy starting point, monotone decay, repair extends the knee
    assert none[0].slo_attainment == spare[0].slo_attainment
    assert all(a.slo_attainment >= b.slo_attainment
               for a, b in zip(none, none[1:]))
    assert spare[-1].slo_attainment >= none[-1].slo_attainment


# --- model degradation curves ------------------------------------------------

@pytest.fixture(scope="module")
def degradation_curves():
    """``model_degradation_curves`` at its defaults (qwen2-0.5b smoke
    forward, batch 2 x seq 64, five rates, no repair vs spare rows), with
    the number of forward executables each policy compiled."""
    from repro.imc import model_analog as ma

    cfg, *_ = ma._setup("qwen2-0.5b", True, 2, 64, 0)
    fns = []
    for pol in (None, REPAIR_SPARE):
        acfg = AnalogConfig(adc_bits=6, seed=0,
                            faults=FaultSpec.at_rate(1e-3, seed=0),
                            repair=pol)
        apply_fet, _ = ma._systematic_g_scale(acfg)
        # the positional arguments ``analog_model_logits`` passes
        fn = ma._jitted_fake_forward(cfg, 6, apply_fet, False, acfg.ir_drop,
                                     ma._default_interpret(),
                                     ma._fake_faults_mode(acfg), pol, False)
        fn._clear_cache()
        fns.append(fn)
    reports = ma.model_degradation_curves("qwen2-0.5b")
    by_pol = {}
    for r in reports:
        by_pol.setdefault(r.repair, []).append(r)
    return reports, by_pol, [fn._cache_size() for fn in fns]


@pytest.mark.parametrize("check", ["monotone", "repair_extends_knee",
                                   "one_compile_per_policy"])
def test_model_degradation_curves(degradation_curves, check):
    """Accuracy degrades monotonically with the fault rate under each
    policy; spare-row repair extends the knee (or lowers the top-rate KL);
    and each policy's whole rate sweep runs one compiled forward."""
    from repro.imc.model_analog import degradation_knee

    reports, by_pol, compiles = degradation_curves
    assert set(by_pol) == {"none", REPAIR_SPARE.name}
    if check == "monotone":
        for rs in by_pol.values():
            for a, b in zip(rs, rs[1:]):
                assert a.kl <= b.kl + 1e-9, (a, b)
                assert a.token_match >= b.token_match - 1e-9, (a, b)
    elif check == "repair_extends_knee":
        bar = 0.8 * by_pol["none"][0].token_match
        knees = degradation_knee(reports, min_token_match=bar)
        top_none, top_spare = by_pol["none"][-1], by_pol[REPAIR_SPARE.name][-1]
        assert (knees[REPAIR_SPARE.name] > knees["none"]
                or top_spare.kl < top_none.kl), knees
    else:
        assert compiles == [1, 1], compiles


# --- degradation-knee reduction ----------------------------------------------

def test_degradation_knee_reduction():
    from repro.imc.model_analog import ModelAccuracyReport, degradation_knee

    def rep(rate, repair, match):
        return ModelAccuracyReport(
            arch="a", kind="afmtj", mode="fake", adc_bits=6, tmr=0.0,
            corner="tt", write_ber=0.0, kl=0.0, token_match=match,
            ppl_analog=1.0, ppl_ref=1.0, batch=1, seq_len=1,
            fault_rate=rate, repair=repair)

    reports = [rep(0.0, "none", 0.95), rep(1e-3, "none", 0.85),
               rep(1e-2, "none", 0.40),
               rep(0.0, "spare", 0.95), rep(1e-3, "spare", 0.94),
               rep(1e-2, "spare", 0.90)]
    knees = degradation_knee(reports, min_token_match=0.8)
    assert knees == {"none": 1e-3, "spare": 1e-2}

"""Serving-subsystem tests: scheduler edge cases against a stub engine (no
JAX compile), cost-model pricing identities, traffic generation, the
event-driven simulator against the step-granular scheduler reference, and
the report/SLO layer.  Only the cost-model-from-hierarchy tests touch JAX;
a subprocess test pins that the whole scheduler/traffic/simulator stack
imports and runs with JAX blocked."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.imc.cost_model import (StepCounts, TokenCounts, TokenPrices,
                                  decode_step_counts, per_token_counts,
                                  prefill_step_counts)
from repro.launch.engine import StubEngine
from repro.launch.report import SLO, build_report
from repro.launch.scheduler import ContinuousBatchScheduler, Request
from repro.launch.simulate import simulate_serving
from repro.launch.traffic import (CHAT_OUTPUTS, CHAT_PROMPTS, LengthMixture,
                                  PoissonTraffic, Trace, mean_request_time,
                                  poisson_at_load, rate_for_load)

REPO = Path(__file__).resolve().parents[1]

# synthetic affine prices: big constant term, small position term
PRICES = TokenPrices("synthetic", t_tok=1e-6, t_pos=1e-8,
                     e_tok=1e-12, e_pos=1e-14)


def run_loop(sched, engine, now=0.0):
    """The documented serve-loop contract (see launch.scheduler)."""
    while not sched.finished:
        sched.admit(now)
        tok, _ = engine.prefill(sched.histories(), sched.frontends())
        while True:
            out = sched.commit(tok, now)
            if sched.finished or (out.freed and sched.has_waiting(now)):
                break
            tok, _ = engine.decode_step(tok, sched.slot_positions())
    return sched.stats()


def make_sched(n_slots, max_new, n_requests, prompt_len=6, eos_id=-1):
    sched = ContinuousBatchScheduler(n_slots, max_new, eos_id=eos_id)
    for rid in range(n_requests):
        sched.submit(Request(rid=rid,
                             prompt=np.arange(1, prompt_len + 1, dtype=np.int32)))
    return sched


# --------------------------------------------------------------------------
# scheduler edge cases (stub engine -- no JAX, no compile)
# --------------------------------------------------------------------------

def test_five_requests_through_two_slots_token_split():
    """The satellite accounting fix, pinned: 5 requests x 4 tokens through 2
    slots is 5 prefill-produced tokens + 15 decode tokens, never 20/0."""
    stats = run_loop(make_sched(2, 4, 5), StubEngine())
    assert stats["served"] == 5
    assert stats["prefill_tokens"] == 5
    assert stats["decode_tokens"] == 15
    assert stats["generated_tokens"] == 20
    assert stats["prefills"] >= 3                 # at least two join waves
    assert [len(c) for c in stats["completions"]] == [4] * 5


def test_queue_empties_mid_wave():
    """Fewer requests than slots: the wave runs with idle slots, and idle
    slots must contribute zero tokens to the accounting."""
    stats = run_loop(make_sched(4, 3, 3), StubEngine())
    assert stats["served"] == 3
    assert stats["prefills"] == 1                 # single wave, no re-joins
    assert stats["prefill_tokens"] == 3
    assert stats["decode_tokens"] == 3 * 2        # no dead-slot tokens


def test_eos_same_step_as_max_new_completes_once():
    """EOS arriving exactly on the max_new step must finish the request
    exactly once (no double completion, no double free)."""
    plen, cap = 4, 3
    eos = 42
    # stub emits EOS exactly when the history holds plen + cap - 1 tokens,
    # i.e. the generated token that is BOTH the EOS and the max_new-th
    engine = StubEngine(token_fn=lambda s, n: eos if n == plen + cap - 1
                        else 7)
    sched = make_sched(1, cap, 1, prompt_len=plen, eos_id=eos)
    stats = run_loop(sched, engine)
    assert stats["served"] == 1
    assert stats["completions"] == [[7, 7, eos]]
    assert stats["generated_tokens"] == cap


def test_eos_frees_slot_early():
    eos = 9
    engine = StubEngine(token_fn=lambda s, n: eos)
    stats = run_loop(make_sched(2, 5, 3, eos_id=eos), engine)
    assert stats["served"] == 3
    assert stats["completions"] == [[eos]] * 3
    assert stats["prefill_tokens"] == 3 and stats["decode_tokens"] == 0


def test_zero_request_run():
    sched = make_sched(2, 4, 0)
    assert sched.finished                          # nothing to do
    stats = run_loop(sched, StubEngine())
    assert stats["served"] == 0
    assert stats["generated_tokens"] == 0
    assert stats["completions"] == []


def test_fifo_starvation_freedom():
    """Admission must follow submission order exactly: with more requests
    than slots no late request can jump an earlier one (FIFO => no
    starvation)."""
    n = 11
    stats_sched = make_sched(3, 2, n)
    run_loop(stats_sched, StubEngine())
    assert stats_sched.admission_order == list(range(n))
    assert stats_sched.served == n


def test_submit_out_of_arrival_order_rejected():
    sched = ContinuousBatchScheduler(1, 2)
    sched.submit(Request(rid=0, prompt=np.ones(2, np.int32), arrival=5.0))
    with pytest.raises(ValueError):
        sched.submit(Request(rid=1, prompt=np.ones(2, np.int32), arrival=1.0))


def test_admission_respects_arrival_time():
    sched = ContinuousBatchScheduler(2, 2)
    sched.submit(Request(rid=0, prompt=np.ones(2, np.int32), arrival=0.0))
    sched.submit(Request(rid=1, prompt=np.ones(2, np.int32), arrival=10.0))
    assert sched.has_waiting(0.0) and not sched.finished
    joined = sched.admit(now=0.0)
    assert len(joined) == 1                       # rid 1 has not arrived yet
    assert sched.next_arrival() == 10.0


# --------------------------------------------------------------------------
# cost model: counting identities (JAX-free)
# --------------------------------------------------------------------------

def test_prefill_counts_triangle():
    tc = TokenCounts(mac_weights=10.0, kv_elems=2.0)
    c = prefill_step_counts(tc, [4, 1])
    assert c.tokens == 2
    assert c.mac_weights == 10.0 * 5
    assert c.kv_write_elems == 2.0 * 5
    assert c.kv_read_elems == 2.0 * (4 * 3 / 2)   # len-1 history adds 0


def test_decode_counts_positions():
    tc = TokenCounts(mac_weights=10.0, kv_elems=2.0)
    c = decode_step_counts(tc, [7, 3])
    assert c.tokens == 2
    assert c.mac_weights == 20.0
    assert c.kv_write_elems == 4.0
    assert c.kv_read_elems == 2.0 * 10


def test_token_prices_match_step_cost():
    """The affine coefficients must reproduce step_cost exactly: that is
    what lets the event simulator integrate in closed form."""
    from repro.imc.cost_model import DeviceCostModel

    m = DeviceCostModel(kind="synthetic", t_mac=3e-12, e_mac=1e-15,
                        t_kv_write=5e-11, e_kv_write=2e-15,
                        t_kv_read=7e-12, e_kv_read=3e-15)
    tc = TokenCounts(mac_weights=1000.0, kv_elems=16.0)
    pr = m.token_prices(tc)
    for p in (0, 1, 17, 301):
        direct = m.step_cost(decode_step_counts(tc, [p]))
        affine = pr.decode_token(p)
        assert direct.t == pytest.approx(affine.t, rel=1e-12)
        assert direct.e == pytest.approx(affine.e, rel=1e-12)
    for L in (1, 2, 33):
        direct = m.step_cost(prefill_step_counts(tc, [L]))
        affine = pr.prefill(L)
        assert direct.t == pytest.approx(affine.t, rel=1e-12)
        assert direct.e == pytest.approx(affine.e, rel=1e-12)


def test_unknown_technology_rejected():
    from repro.imc.cost_model import device_cost_model

    with pytest.raises(ValueError):
        device_cost_model("sram")


# --------------------------------------------------------------------------
# cost model from the measured hierarchy (pulls JAX)
# --------------------------------------------------------------------------

def test_afmtj_kv_writes_cheaper_than_mtj():
    """The case-study claim at the price level: KV appends ride the write
    path, where AFMTJ's picosecond switching beats MTJ's nanosecond
    writes; read-side prices stay comparable."""
    from repro.imc.cost_model import device_cost_model

    af = device_cost_model("afmtj")
    mtj = device_cost_model("mtj")
    assert af.t_kv_write < mtj.t_kv_write / 5.0
    assert af.t_kv_read == pytest.approx(mtj.t_kv_read, rel=0.5)
    tc = TokenCounts(mac_weights=1e6, kv_elems=2048.0)
    assert af.token_prices(tc).t_tok < mtj.token_prices(tc).t_tok


def test_refresh_pricing_needs_resident_bytes():
    from repro.imc.cost_model import imc_cost_model

    refresh = SimpleNamespace(interval=1e-3)
    with pytest.raises(ValueError):
        imc_cost_model("afmtj", refresh=refresh)
    priced = imc_cost_model("afmtj", refresh=refresh, resident_bytes=1e6)
    base = imc_cost_model("afmtj")
    assert priced.t_mac > base.t_mac              # scrub duty-cycle stretch
    assert priced.e_standing_rate > 0.0


def test_measured_percentile_knobs_move_prices():
    from repro.imc.cost_model import device_cost_model

    base = device_cost_model("afmtj")
    tail = device_cost_model("afmtj", write_percentile=99.0,
                             read_percentile=99.0)
    assert tail.t_kv_write >= base.t_kv_write     # p99 write is no faster


def test_per_token_counts_attention_kv():
    from repro.configs.registry import smoke_config

    cfg = smoke_config("qwen2-0.5b")
    tc = per_token_counts(cfg)
    attn_layers = sum(cfg.n_pattern_repeats for mixer, _ in cfg.pattern
                      if mixer.startswith("attn"))
    assert tc.kv_elems == 2.0 * cfg.n_kv_heads * cfg.d_head * attn_layers
    assert tc.mac_weights == float(cfg.active_param_count())


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def test_poisson_rate_and_determinism():
    tr = PoissonTraffic(rate=1000.0, n_requests=20000, seed=3).trace()
    emp = len(tr) / tr.arrival_s[-1]
    assert emp == pytest.approx(1000.0, rel=0.05)
    tr2 = PoissonTraffic(rate=1000.0, n_requests=20000, seed=3).trace()
    assert np.array_equal(tr.arrival_s, tr2.arrival_s)
    assert np.array_equal(tr.prompt_tokens, tr2.prompt_tokens)


def test_length_mixture_moments():
    mix = LengthMixture(((1.0, 64.0, 0.5),), lo=1, hi=100000)
    rng = np.random.default_rng(0)
    s = mix.sample(rng, 200000).astype(np.float64)
    assert s.mean() == pytest.approx(mix.mean(), rel=0.02)
    assert (s ** 2).mean() == pytest.approx(mix.mean_sq(), rel=0.05)
    assert s.min() >= 1 and s.max() <= 100000


def test_trace_roundtrip(tmp_path):
    tr = PoissonTraffic(rate=10.0, n_requests=64, seed=1).trace()
    for name in ("t.npz", "t.jsonl"):
        path = tmp_path / name
        tr.save(path)
        back = Trace.load(path)
        assert np.allclose(back.arrival_s, tr.arrival_s)
        assert np.array_equal(back.prompt_tokens, tr.prompt_tokens)
        assert np.array_equal(back.output_tokens, tr.output_tokens)


def test_rate_for_load_scales_linearly():
    r1 = rate_for_load(PRICES, 0.5, 8)
    r2 = rate_for_load(PRICES, 1.0, 8)
    assert r2 == pytest.approx(2.0 * r1, rel=1e-12)
    assert mean_request_time(PRICES, CHAT_PROMPTS, CHAT_OUTPUTS, 8) > \
        mean_request_time(PRICES, CHAT_PROMPTS, CHAT_OUTPUTS, 1)


# --------------------------------------------------------------------------
# simulator: closed-form events vs the scheduler-driven reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rho,n_slots", [(0.5, 8), (1.5, 8), (0.8, 1),
                                         (0.8, 3)])
def test_events_match_steps(rho, n_slots):
    """The event-driven fast path must agree with the real scheduler driven
    step by step — token counts and wave counts exactly, clocks to float
    tolerance."""
    tr = poisson_at_load(PRICES, rho, 400, n_slots, seed=7).trace()
    ev = simulate_serving(PRICES, tr, n_slots=n_slots, method="events")
    st = simulate_serving(PRICES, tr, n_slots=n_slots, method="steps")
    assert ev.prefill_tokens == st.prefill_tokens == len(tr)
    assert ev.decode_tokens == st.decode_tokens
    assert ev.waves == st.waves
    assert ev.wave_tokens == st.wave_tokens
    assert ev.sim_time_s == pytest.approx(st.sim_time_s, rel=1e-9)
    assert ev.busy_s == pytest.approx(st.busy_s, rel=1e-9)
    assert ev.energy_j == pytest.approx(st.energy_j, rel=1e-9)
    np.testing.assert_allclose(ev.ttft_s, st.ttft_s, rtol=1e-9)
    fe, fs = np.isfinite(ev.tpot_s), np.isfinite(st.tpot_s)
    assert np.array_equal(fe, fs)
    np.testing.assert_allclose(ev.tpot_s[fe], st.tpot_s[fs], rtol=1e-9)


def test_saturation_blows_up_ttft():
    """Past offered load 1 the queue grows without bound; p99 TTFT must be
    orders of magnitude above the sub-critical value."""
    lo = simulate_serving(PRICES,
                          poisson_at_load(PRICES, 0.3, 2000, 8, seed=1)
                          .trace(), n_slots=8)
    hi = simulate_serving(PRICES,
                          poisson_at_load(PRICES, 3.0, 2000, 8, seed=1)
                          .trace(), n_slots=8)
    assert np.percentile(hi.ttft_s, 99) > 10 * np.percentile(lo.ttft_s, 99)
    # below capacity the device idles between arrivals; above it barely does
    # (the analytic capacity estimate is conservative, so nominal rho=3 may
    # sit just above the true knee -- utilization, not equality, is the pin)
    assert hi.busy_s / hi.sim_time_s > 0.95
    assert lo.busy_s / lo.sim_time_s < hi.busy_s / hi.sim_time_s


def test_empty_trace():
    tr = Trace(np.zeros(0), np.zeros(0, np.int64), np.zeros(0, np.int64))
    r = simulate_serving(PRICES, tr, n_slots=4)
    assert r.sim_time_s == 0.0 and r.prefill_tokens == 0


def test_decode_tokens_conservation():
    """Every output token beyond the first is a decode token."""
    tr = poisson_at_load(PRICES, 0.7, 300, 4, seed=2).trace()
    r = simulate_serving(PRICES, tr, n_slots=4)
    assert r.prefill_tokens == len(tr)
    assert r.decode_tokens == int((tr.output_tokens - 1).sum())


# --------------------------------------------------------------------------
# report / SLO
# --------------------------------------------------------------------------

def test_report_excludes_nan_tpot_but_slo_checks_ttft():
    ttft = np.array([1.0, 1.0, 100.0])
    tpot = np.array([1.0, np.nan, 1.0])       # single-token request in slot 1
    rep = build_report("x", ttft, tpot, sim_time_s=10.0, energy_j=2.0,
                       prefill_tokens=3, decode_tokens=5,
                       slo=SLO(ttft_s=2.0, tpot_s=2.0), busy_s=5.0)
    assert np.isfinite(rep.tpot_p99_s)
    assert rep.slo_attainment == pytest.approx(2.0 / 3.0)
    assert rep.utilization == pytest.approx(0.5)
    assert rep.generated_tokens == 8
    assert rep.tokens_per_joule == pytest.approx(4.0)
    assert "slo_attainment" in rep.row_dict()


def test_slo_normalized_attainable_below_capacity():
    """The policy-normalized SLO must be mostly met below capacity and
    mostly missed deep in saturation — that is the curve the case study
    sweeps."""
    slo = SLO.normalized(PRICES, CHAT_PROMPTS, CHAT_OUTPUTS, 8)
    reps = {}
    for rho in (0.5, 2.0):
        tr = poisson_at_load(PRICES, rho, 2000, 8, seed=1).trace()
        r = simulate_serving(PRICES, tr, n_slots=8)
        reps[rho] = build_report("x", r.ttft_s, r.tpot_s, r.sim_time_s,
                                 r.energy_j, r.prefill_tokens,
                                 r.decode_tokens, offered_load=rho, slo=slo)
    assert reps[0.5].slo_attainment > 0.9
    assert reps[2.0].slo_attainment < 0.5


@pytest.fixture(scope="module")
def tpot_p99():
    """p99 time per output token of qwen2-0.5b served at offered load 0.8
    on 8 slots, 20,000 Poisson requests per technology at nominal
    prices."""
    from repro.configs.registry import ARCHS
    from repro.imc.cost_model import device_cost_model

    tc = per_token_counts(ARCHS["qwen2-0.5b"])
    out = {}
    for tech in ("afmtj", "mtj", "cpu"):
        prices = device_cost_model(tech).token_prices(tc)
        trace = poisson_at_load(prices, 0.8, 20_000, 8, seed=11).trace()
        slo = SLO.normalized(prices, CHAT_PROMPTS, CHAT_OUTPUTS, 8)
        r = simulate_serving(prices, trace, n_slots=8)
        out[tech] = build_report(
            tech, r.ttft_s, r.tpot_s, r.sim_time_s, r.energy_j,
            r.prefill_tokens, r.decode_tokens, offered_load=0.8, slo=slo,
            busy_s=r.busy_s).tpot_p99_s
    return out


@pytest.mark.parametrize("baseline", ["mtj", "cpu"])
def test_afmtj_beats_baseline_p99_tpot(tpot_p99, baseline):
    """The serving case study: every generated token pays its KV append
    on the write path, so the AFMTJ's faster writes show in the p99 tail
    against the MTJ and the CPU baseline alike."""
    assert tpot_p99["afmtj"] < tpot_p99[baseline], tpot_p99


# --------------------------------------------------------------------------
# evaluate: geometric-mean summary (satellite)
# --------------------------------------------------------------------------

def test_summarize_geomean_vs_arithmetic():
    from repro.imc.evaluate import summarize, summarize_geomean

    results = {"a": SimpleNamespace(speedup=10.0, energy_saving=10.0),
               "b": SimpleNamespace(speedup=1000.0, energy_saving=1000.0)}
    sp_a, es_a = summarize(results)
    sp_g, es_g = summarize_geomean(results)
    assert sp_a == pytest.approx(505.0)
    assert es_a == pytest.approx(505.0)
    assert sp_g == pytest.approx(100.0)
    assert es_g == pytest.approx(100.0)


# --------------------------------------------------------------------------
# the stack must work with JAX blocked (subprocess)
# --------------------------------------------------------------------------

def test_serving_stack_runs_without_jax():
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"           # any 'import jax' now fails
        "sys.modules['jax.numpy'] = None\n"
        "import numpy as np\n"
        "from repro.imc.cost_model import TokenPrices\n"
        "from repro.launch.engine import StubEngine\n"
        "from repro.launch.scheduler import ContinuousBatchScheduler\n"
        "from repro.launch.traffic import PoissonTraffic\n"
        "from repro.launch.simulate import simulate_serving\n"
        "from repro.launch.report import build_report\n"
        "pr = TokenPrices('syn', 1e-6, 1e-8, 1e-12, 1e-14)\n"
        "tr = PoissonTraffic(rate=2000.0, n_requests=60, seed=0).trace()\n"
        "for m in ('events', 'steps'):\n"
        "    r = simulate_serving(pr, tr, n_slots=4, method=m)\n"
        "    assert r.prefill_tokens == 60\n"
        "rep = build_report('syn', r.ttft_s, r.tpot_s, r.sim_time_s,\n"
        "                   r.energy_j, r.prefill_tokens, r.decode_tokens)\n"
        "assert rep.throughput_tok_s > 0\n"
        "print('NOJAX_OK')\n"
    )
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "NOJAX_OK" in out.stdout

"""Spans and counters (``repro.runtime.telemetry``, DESIGN.md §15): the
campaign engine and the analog forward record their stages as
``repro.*`` host spans in a profiler trace, device ops carry their layer
in ``op_name``, every Pallas kernel has a name, and the counters see
JAX's compiles."""
import glob
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import CampaignGrid, run_campaign
from repro.circuit.bitline import BitlineParams
from repro.core import llg
from repro.core.params import AFMTJ_PARAMS
from repro.imc.analog_pipeline import AnalogConfig
from repro.imc.model_analog import (_fake_scalars, _jitted_fake_forward,
                                    _setup, analog_model_logits)
from repro.kernels.llg_rk4 import llg_rk4_pallas
from repro.kernels.ops import pack_states
from repro.runtime import telemetry

GRID = CampaignGrid(voltages=(0.8,), pulse_widths=(20e-12,),
                    temperatures=(300.0, 350.0), n_samples=64, seed=11)
STAGES = ("pack", "compile", "dispatch", "sync", "assemble")
REPO = Path(__file__).resolve().parents[1]


def _traced(fn, trace_dir):
    """(fn's result, the trace's ``repro.*`` host spans as (name, start,
    end, stats), sorted by start)."""
    with jax.profiler.trace(str(trace_dir)):
        out = fn()
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = [(ev.name, ev.start_ns, ev.end_ns, {k: v for k, v in ev.stats})
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(telemetry.SPAN_PREFIX)]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _campaign(**kw):
    return run_campaign(AFMTJ_PARAMS, GRID, reduce="stream", n_bins=16,
                        use_cache=False, **kw)


def test_span_carries_prefix_and_attributes(tmp_path):
    def probe():
        with telemetry.span("probe", a=3, b="x"):
            pass

    _, spans = _traced(probe, tmp_path)
    assert [(n, st) for n, _, _, st in spans] == [
        ("repro.probe", {"a": 3, "b": "x"})]


def test_campaign_stages_nest_under_the_run_span(tmp_path):
    _campaign()                                   # warm: compile outside
    res, spans = _traced(_campaign, tmp_path)
    run, = [s for s in spans if s[0] == "repro.campaign.run"]
    assert run[3] == {"lanes": 128, "launches": 1, "devices": 1,
                      "seed": GRID.seed}
    by = {st: [s for s in spans if s[0] == f"repro.campaign.{st}"]
          for st in STAGES + ("pack_slice",)}
    assert all(by[st] for st in STAGES), sorted({s[0] for s in spans})
    assert all(_inside(s, run) for st in STAGES for s in by[st])
    # one pack, one child per temperature slice inside it
    pack, = by["pack"]
    assert [s[3] for s in by["pack_slice"]] == [{"slice": 0}, {"slice": 1}]
    assert all(_inside(s, pack) for s in by["pack_slice"])
    # each launch compiles twice (before dispatch, and in the sync loop)
    assert [s[3] for s in by["compile"]] == [{"launch": 0}, {"launch": 0}]
    assert [s[3] for s in by["dispatch"]] == [{"launch": 0}]
    assert [s[3] for s in by["sync"]] == [
        {"launch": 0, "bytes": res.host_bytes}]
    # the stages follow each other in this order
    firsts = [by[st][0][1] for st in STAGES]
    assert firsts == sorted(firsts)


def test_campaign_cache_spans(tmp_path):
    kw = dict(reduce="stream", n_bins=16, cache_dir=str(tmp_path / "c"))
    _, cold = _traced(lambda: run_campaign(AFMTJ_PARAMS, GRID, **kw),
                      tmp_path / "t1")
    hit, warm = _traced(lambda: run_campaign(AFMTJ_PARAMS, GRID, **kw),
                        tmp_path / "t2")
    names = [s[0] for s in cold]
    assert "repro.campaign.cache_load" in names
    assert "repro.campaign.cache_store" in names
    # a cache hit loads and returns: no pack, no launch
    assert hit.from_cache
    assert [s[0] for s in warm] == ["repro.campaign.run",
                                    "repro.campaign.cache_load"]


def test_second_identical_campaign_adds_no_compile():
    _campaign()
    before = telemetry.snapshot()
    res = _campaign()
    after = telemetry.snapshot()
    diff = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert diff.get("xla.compiles", 0) == 0
    assert diff.get("xla.lowerings", 0) == 0
    # the two compile_launch calls re-trace; the pack program does not
    assert 2 <= diff["xla.traces"] <= 4
    assert diff.get("campaign.pack_traces", 0) == 0
    assert diff["campaign.launches"] == 1
    assert diff["campaign.lanes"] == 128
    assert diff["campaign.host_bytes"] == res.host_bytes > 0


def test_listener_counts_trace_lowering_and_compile():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(17.0)
    before = telemetry.snapshot()
    f(x).block_until_ready()
    mid = telemetry.snapshot()
    f(x).block_until_ready()
    after = telemetry.snapshot()
    for name in ("xla.traces", "xla.lowerings", "xla.compiles"):
        assert mid.get(name, 0) > before.get(name, 0), name
        assert after.get(name, 0) == mid.get(name, 0), name


def test_count_and_snapshot():
    before = telemetry.snapshot().get("test.items", 0)
    telemetry.count("test.items")
    telemetry.count("test.items", 4)
    snap = telemetry.snapshot()
    assert snap["test.items"] == before + 5
    snap["test.items"] = -1                  # a copy, not the registry
    assert telemetry.snapshot()["test.items"] == before + 5


@pytest.fixture(scope="module")
def tiny_model():
    cfg, params, tokens, _ = _setup("qwen2-0.5b", True, 2, 32, 0)
    return cfg, params, tokens


def test_analog_forward_spans(tiny_model, tmp_path):
    cfg, params, tokens = tiny_model
    acfg = AnalogConfig(adc_bits=6)
    analog_model_logits(params, cfg, tokens, acfg).block_until_ready()
    _, spans = _traced(lambda: analog_model_logits(
        params, cfg, tokens, acfg).block_until_ready(), tmp_path)
    assert [(n, st) for n, _, _, st in spans] == [
        ("repro.analog.prepare", {}),
        ("repro.analog.dispatch", {"batch": 2, "seq": 32, "adc_bits": 6})]
    assert spans[0][2] <= spans[1][1]


def test_analog_forward_ops_carry_their_layer(tiny_model):
    cfg, params, tokens = tiny_model
    fn = _jitted_fake_forward(cfg, 6, False, False, True, True)
    scal = _fake_scalars("afmtj", AnalogConfig(adc_bits=6), BitlineParams(),
                         1.0, None)
    text = fn.lower(params, tokens, scal).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("unembed/", "block0/attn/", "block0/ffn/",
                  f"block{cfg.n_layers - 1}/ffn/",
                  # each kernel is named after its linear site
                  "unembed/fake_analog_unembed",
                  "block0/ffn/fake_analog_w_up"):
        assert any(scope in n for n in names), scope


@pytest.fixture(scope="module")
def moe_share():
    """moonlight-16b-a3b's smoke config holding experts 1-2 of 4, with one
    analog forward already dispatched (and the counters it moved)."""
    import dataclasses
    from repro.configs.registry import smoke_config
    from repro.models import model as M

    cfg = smoke_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            held=(1, 2)))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    acfg = AnalogConfig(adc_bits=0)
    before = telemetry.snapshot()
    analog_model_logits(params, cfg, tokens, acfg).block_until_ready()
    after = telemetry.snapshot()
    moved = {k: after[k] - before.get(k, 0) for k in after}
    return cfg, params, tokens, moved


def test_moe_forward_counts_grouped_launches_and_rows(moe_share):
    """Three grouped launches per MoE layer, each handed the layer's
    (token, choice) pair buffer; the host counts them at dispatch."""
    cfg, _, tokens, moved = moe_share
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert moved["analog.grouped_launches"] == 3 * n_moe
    assert moved["analog.grouped_rows"] == (3 * n_moe * tokens.size
                                            * cfg.moe.top_k)


def test_moe_forward_ops_carry_their_layer(moe_share):
    """Latent attention and expert layers name their scopes ``mla`` and
    ``moe``; each fake-analog kernel is named after its site."""
    cfg, params, tokens, _ = moe_share
    fn = _jitted_fake_forward(cfg, 0, False, False, True, True)
    scal = _fake_scalars("afmtj", AnalogConfig(adc_bits=0), BitlineParams(),
                         1.0, None)
    text = fn.lower(params, tokens, scal).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    last = cfg.n_layers - 1
    for scope in ("block0/mla/", "block0/ffn/fake_analog_w_up",
                  f"block{last}/mla/fake_analog_wkv_b",
                  f"block{last}/moe/fake_analog_router",
                  f"block{last}/moe/fake_analog_moe_w_gate",
                  f"block{last}/moe/fake_analog_moe_w_down",
                  f"block{last}/moe/fake_analog_shared_w_up",
                  "unembed/fake_analog_unembed"):
        assert any(scope in n for n in names), scope


@pytest.mark.parametrize("kernel", ["llg_rk4", "fake_analog", "bitline_mac",
                                    "xnor_gemm"])
def test_every_pallas_kernel_has_its_name(kernel):
    src = (REPO / "src" / "repro" / "kernels" / f"{kernel}.py").read_text()
    calls = src.split("pl.pallas_call(")[1:]
    assert calls
    for call in calls:
        assert "name=" in call[:call.index(")(")], kernel
    assert re.search(rf'"{kernel}(_\w+)?"', src)


def test_llg_kernel_name_reaches_the_program():
    m0 = jax.vmap(lambda t, f: llg.initial_state(AFMTJ_PARAMS, t, f))(
        jnp.full((8,), 0.1), jnp.zeros((8,)))
    state = pack_states(m0, jnp.full((8,), 0.8))
    jaxpr = jax.make_jaxpr(lambda s: llg_rk4_pallas(
        s, AFMTJ_PARAMS, 1e-13, 16, 0.9, interpret=True,
        thermal_sigma=0.0, seeds=np.zeros(s.shape[1], np.uint32)))(state)
    assert "llg_rk4" in str(jaxpr)


def test_design_lists_every_span_and_counter():
    """DESIGN.md §15 is the operator-facing list: every name the program
    records appears there."""
    design = (REPO / "DESIGN.md").read_text()
    section = design[design.index("## §15"):]
    src = "\n".join(p.read_text() for p in (REPO / "src").rglob("*.py"))
    spans = set(re.findall(r'telemetry\.span\(\s*"([\w.]+)"', src))
    counters = set(re.findall(r'telemetry\.count\(\s*"([\w.]+)"', src))
    counters |= set(telemetry._JAX_EVENTS.values())
    assert len(spans) >= 10 and len(counters) >= 7, (spans, counters)
    for n in spans:
        assert f"`repro.{n}`" in section, n
    for n in counters:
        assert f"`{n}`" in section, n

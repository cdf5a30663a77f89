"""The stage reduction of ``bench/stages.py`` on tiny traced windows of the
campaign and analog cells recorded on the CPU, and the readers that the
benchmark already has, which it leaves as they read."""
import math
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import run, stages  # noqa: E402
from bench import trace as bt  # noqa: E402
from bench_tiny import tiny_cell  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "cpu_small.xplane.pb"
CPU = dict(plane_filter=lambda n: n == "/host:CPU",
           line_filter=lambda n: n.startswith("tf_XLA"))
METRICS = ("campaign.host_pack_ms", "campaign.host_compile_ms",
           "campaign.host_dispatch_ms", "xla.traces_per_call",
           "xla.compiles_per_call", "device_idle.unattributed",
           "analog_forward.head_share")


def _window(cell, tmp_path):
    wl, cfg = tiny_cell(cell)
    entry = run.load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, 5, jax.devices()[: wl["chips"]])
    records, counters = stages.traced_window(entry, state, 5, 0.0,
                                             str(tmp_path))
    path = bt.find_xplane(str(tmp_path))
    return stages.reduce_trace(path, len(records), counters, **CPU), path


@pytest.fixture(scope="module")
def campaign(tmp_path_factory):
    return _window("afmtj.wer_campaign", tmp_path_factory.mktemp("c"))


@pytest.fixture(scope="module")
def analog(tmp_path_factory):
    return _window("qwen2-0.5b.analog_eval", tmp_path_factory.mktemp("a"))


@pytest.mark.parametrize("cell", ["campaign", "analog"])
def test_every_metric_reads_a_finite_value(cell, request):
    out, _ = request.getfixturevalue(cell)
    assert set(out["metrics"]) == set(METRICS)
    assert all(math.isfinite(v) and v >= 0.0
               for v in out["metrics"].values()), out["metrics"]
    assert 0.0 <= out["metrics"]["device_idle.unattributed"] <= 100.0


def test_campaign_stages_and_counters(campaign):
    out, _ = campaign
    m = out["metrics"]
    assert m["campaign.host_pack_ms"] > 0.0
    assert m["campaign.host_compile_ms"] > 0.0
    assert m["campaign.host_dispatch_ms"] > 0.0
    # the warm call compiled everything: the window only re-traces
    assert m["xla.compiles_per_call"] == 0.0
    assert m["xla.traces_per_call"] >= 2.0
    assert out["counters"]["campaign.launches"] == out["calls"]
    for stage in ("run", "pack", "pack_slice", "compile", "dispatch", "sync",
                  "assemble"):
        assert out["stage_ms"][f"repro.campaign.{stage}"] > 0.0, stage
    # a campaign's stages lie inside it, the slices inside the pack
    st = out["stage_ms"]
    assert st["repro.campaign.pack_slice"] <= st["repro.campaign.pack"]
    assert sum(st[f"repro.campaign.{s}"] for s in (
        "pack", "compile", "dispatch", "sync", "assemble")) <= (
            st["repro.campaign.run"] * (1 + 1e-9))


def test_analog_stages(analog):
    out, _ = analog
    assert set(out["stage_ms"]) == {"repro.analog.prepare",
                                    "repro.analog.dispatch"}
    assert out["metrics"]["campaign.host_pack_ms"] == 0.0


@pytest.mark.parametrize("cell", ["campaign", "analog"])
def test_idle_split_by_span_adds_up_to_the_idle_share(cell, request):
    out, path = request.getfixturevalue(cell)
    summ = bt.summarize(path, **CPU)
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        100.0 * summ.idle_share, rel=1e-9)
    # one device: the unattributed idle time is the split's outside part
    assert summ.n_devices == 1
    unattributed = sum(v for k, v in out["idle_by_span"].items()
                       if not k.startswith("repro."))
    assert out["metrics"]["device_idle.unattributed"] == pytest.approx(
        unattributed, rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("cell", ["campaign", "analog"])
def test_named_gaps_keep_the_breakdowns_gaps(cell, request):
    out, path = request.getfixturevalue(cell)
    summ = bt.summarize(path, **CPU)
    assert [g for _, g in out["idle_gaps"]] == [
        g for _, g in summ.idle_gaps(10)]


def test_idle_split_averages_over_devices():
    # window 0-100 ns; device 0 busy 10-60 (packing), device 1 busy 60-90
    # (the kernel); the host packs in 10-60 and syncs in 60-95
    spans = [("bench.call", 0, 100), ("repro.pack", 10, 60),
             ("repro.sync", 60, 95)]
    summ = bt.TraceSummary(
        window_s=1e-7, busy_s=[5e-8, 3e-8],
        ops=[[bt.Op("a", 10, 60, "a")], [bt.Op("k", 60, 90, "k")]],
        spans=[spans[0]])
    split = stages.mean_idle_by_span(summ, spans)
    assert split == pytest.approx({"bench.call": 15.0, "repro.pack": 25.0,
                                   "repro.sync": 20.0})
    assert sum(split.values()) == pytest.approx(100 * summ.idle_share)


def test_named_gaps_with_only_bench_spans_equal_the_breakdown():
    summ = bt.summarize(str(DATA), plane_filter=lambda n: n == "/host:CPU",
                        line_filter=lambda n: n.startswith("tf_XLAPjRt"))
    assert stages.named_gaps(summ, summ.spans) == summ.idle_gaps(10)


def _reader_values():
    summ = bt.summarize(str(DATA), plane_filter=lambda n: n == "/host:CPU",
                        line_filter=lambda n: n.startswith("tf_XLAPjRt"))
    ctx = {"trace": summ, "calls": 3, "span_s": 0.05,
           "work": {"lane_steps": 3_000_000, "model_flops": 2.0e9},
           "peaks": bt.peaks_for("TPU v5 lite")}
    return {name: run.load_module("metrics", name).read(ctx) for name in (
        "llg_rk4.lane_steps_per_s", "device_idle.campaign",
        "device_idle.analog", "fake_analog_roofline", "analog_forward_mfu")}


def test_existing_readers_read_what_they_read_before():
    """The readers of the accepted metrics, on the committed CPU trace:
    the values they read before the program had spans, bit for bit, and
    the same after the stage reduction has read the trace."""
    pinned = {"llg_rk4.lane_steps_per_s": None,
              "device_idle.campaign": 80.96948307230699,
              "device_idle.analog": 80.96948307230699,
              "fake_analog_roofline": None,
              "analog_forward_mfu": 0.06091370558375635}
    assert _reader_values() == pinned
    stages.reduce_trace(str(DATA), 3, {}, **CPU)
    assert _reader_values() == pinned

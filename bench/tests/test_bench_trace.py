"""The trace reduction, on a small trace recorded on the CPU, and the peak
table."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import trace as bt  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "cpu_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    # the CPU runs XLA's operations on the PjRt client's thread of the
    # host plane; a TPU trace keeps them on each device's "XLA Ops" line
    return bt.summarize(str(DATA),
                        plane_filter=lambda n: n == "/host:CPU",
                        line_filter=lambda n: n.startswith("tf_XLAPjRt"))


def test_busy_is_the_union_of_operations_inside_the_window(summary):
    # three calls of two programs each, 4 ms and 6 ms of host sleep apart
    assert summary.n_devices == 1
    assert summary.kernel_count(lambda t: "llg_probe" in t) == 3
    assert summary.kernel_count(lambda t: "other_op" in t) == 3
    ops = sum(o.end - o.start for o in summary.ops[0]) * 1e-9
    assert 0.0 < summary.busy_s[0] <= ops + 1e-12
    assert summary.busy_s[0] < summary.window_s
    assert 0.5 < summary.idle_share < 1.0


def test_kernel_time_by_stable_name(summary):
    t = summary.kernel_seconds(lambda t: "llg_probe" in t)
    assert 0.0 < t < summary.busy_s[0]
    assert summary.kernel_seconds(lambda t: "no_such_kernel" in t) == 0.0


def test_idle_gaps_are_labelled_with_bench_spans(summary):
    gaps = summary.idle_gaps(10)
    assert gaps and all(label in ("bench.call", "bench.window")
                        for label, _ in gaps)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    # the 6 ms sleeps between calls are the longest gaps, outside any call
    assert gaps[0][0] == "bench.window" and gaps[0][1] > 0.005


def test_op_kind_keeps_opcode_and_result_shape():
    name = ("%run.337 = f32[4096,151936]{1,0:T(8,128)} custom-call(f32[4096,"
            "896]{1,0:T(8,128)S(1)} %a), custom_call_target=\"tpu_custom_call\"")
    assert bt.op_kind(name) == "custom-call f32[4096,151936]"
    assert bt.op_kind("multiply_add_fusion") == "multiply_add_fusion"
    tup = ("%fusion.2 = (f32[151936]{0:T(1024)S(1)}, f32[896,151936]{1,0:"
           "T(8,128)}) fusion(f32[]{:T(128)S(6)} %copy.706), kind=kLoop")
    assert bt.op_kind(tup) == "fusion (f32[151936], f32[896,151936])"


def test_union_merges_overlaps():
    ops = [bt.Op("a", 0, 10, "a"), bt.Op("b", 5, 20, "b"),
           bt.Op("c", 30, 40, "c")]
    assert bt._union(ops) == [(0, 20), (30, 40)]


def test_peaks_known_and_unknown_device():
    p = bt.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bt.peaks_for("TPU v99")

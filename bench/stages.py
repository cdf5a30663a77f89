#!/usr/bin/env python3
"""Where a cell's host and device time goes, by the program's own spans
and counters.

    python3 bench/stages.py --workload <name> --seed <n> --seconds <s>

Runs one cell as ``bench/run.py --trace 1`` does (set-up with its warm
call, then whole calls for ``--seconds`` inside the spans ``bench.window``
and ``bench.call`` under the profiler), skips the correctness check, and
reduces the trace by the ``repro.*`` host spans and the counters that the
program records (``repro.runtime.telemetry``).  The last line of standard
output is one JSON object:

  metrics      per call or per window, under the names a per-layer metric
               would take (PERF.md, Open questions):
                 campaign.host_{pack,compile,dispatch}_ms  ms per call in
                   ``repro.campaign.{pack,compile,dispatch}`` (campaigns)
                 xla.traces_per_call, xla.compiles_per_call
                 device_idle.unattributed  % of the window in which device
                   0 is idle and no ``repro.*`` span is open
                 analog_forward.head_share  % of device-op time in ops
                   whose text holds ``unembed``: the output head's scope, or
                   on the TPU, whose ops carry no scope, the name of its
                   kernel (``fake_analog_unembed``)
  stage_ms     ms per call inside each ``repro.*`` span name (the union of
               its spans; child spans included)
  per_call_ms  the same, call by call, and ``idle``: device 0's idle time
               inside each ``bench.call``
  idle_by_span % of the window in which a device is idle, by the innermost
               open span of either family, averaged over the devices (on
               four chips the host packs on device 0 while the others
               idle)
  idle_pct     1 - busy / window, as the ``device_idle.*`` metrics read it
  counters     telemetry counter differences over the window
  kernel_s     device seconds of the ops named after each Pallas kernel, by
               name (a fake-analog kernel is named after its linear site)
  idle_gaps    the ten longest idle gaps of device 0 (the benchmark's
               ``breakdown``), each named by the innermost span of either
               family around its midpoint
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path.pop(0)
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402
from bench import trace as bench_trace  # noqa: E402

Span = Tuple[str, float, float]          # (name, start ns, end ns)

# an op named after a Pallas kernel: ``%fake_analog_wq.12 = f32[...] ...``
KERNEL_OP = re.compile(
    r"%?((?:llg_rk4|fake_analog|bitline_mac|xnor_gemm)\w*)\.\d+ = ")
STAGE_METRICS = {"campaign.host_pack_ms": "repro.campaign.pack",
                 "campaign.host_compile_ms": "repro.campaign.compile",
                 "campaign.host_dispatch_ms": "repro.campaign.dispatch"}


def host_spans(path: str, prefixes: Sequence[str]) -> List[Span]:
    """Host spans whose name starts with one of ``prefixes``, by start."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tuple(prefixes)):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _covered(intervals: Sequence[Tuple[float, float]]) -> float:
    ops = [bench_trace.Op("", a, b, "") for a, b in intervals if b > a]
    return sum(b - a for a, b in bench_trace._union(ops))


def _clip(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def idle_intervals(summ, device: int = 0) -> List[Tuple[float, float]]:
    """A device's idle intervals inside the window, which is the extent of
    the ``bench.*`` spans (``bench.trace.summarize``)."""
    lo = min(s for _, s, _ in summ.spans)
    hi = max(e for _, _, e in summ.spans)
    out, t = [], lo
    for a, b in bench_trace._union(summ.ops[device]):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Span], t: float) -> str:
    """The innermost span open at ``t`` (spans sorted by start, nested),
    labelled as ``bench.trace`` labels a gap outside every span."""
    label = "outside bench spans"
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            label = name
    return label


def idle_by_span(idle: Sequence[Tuple[float, float]],
                 spans: Sequence[Span]) -> Dict[str, float]:
    """Idle nanoseconds by the innermost open span: every idle interval is
    cut at the span boundaries inside it."""
    out: Dict[str, float] = {}
    for a, b in idle:
        cuts = sorted({a, b} | {x for _, s, e in spans for x in (s, e)
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            label = innermost(spans, 0.5 * (lo + hi))
            out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def mean_idle_by_span(summ, spans: Sequence[Span]) -> Dict[str, float]:
    """``idle_by_span`` of every device, averaged over the devices."""
    out: Dict[str, float] = {}
    for d in range(summ.n_devices):
        for name, ns in idle_by_span(idle_intervals(summ, d), spans).items():
            out[name] = out.get(name, 0.0) + ns / summ.n_devices
    return out


def named_gaps(summ, spans: Sequence[Span], k: int = 10):
    """``summ.idle_gaps(k)`` with each gap named by the innermost span of
    ``spans`` around its midpoint: the same gaps, in the same order."""
    merged = bench_trace._union(summ.ops[0])
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in
            zip(merged, merged[1:]) if b_start > a_end]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(innermost(spans, 0.5 * (lo + hi)), (hi - lo) * 1e-9)
            for lo, hi in gaps[:k]]


def kernel_seconds(summ) -> Dict[str, float]:
    """Device seconds by kernel name, summed over devices."""
    out: Dict[str, float] = {}
    for ops in summ.ops:
        for o in ops:
            m = KERNEL_OP.match(o.name)
            if m:
                out[m.group(1)] = out.get(m.group(1), 0.0) + (
                    o.end - o.start) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def per_call_ms(summ, repro: Sequence[Span],
                idle: Sequence[Tuple[float, float]]) -> Dict[str, list]:
    """Milliseconds in each ``repro.*`` span name, and device 0's idle
    time (``idle``), inside each ``bench.call``."""
    calls = [(s, e) for n, s, e in summ.spans if n == "bench.call"]
    out: Dict[str, list] = {n: [0.0] * len(calls)
                            for n in sorted({n for n, _, _ in repro})}
    out["idle"] = [1e-6 * _covered([(max(a, s), min(b, e)) for a, b in idle])
                   for s, e in calls]
    for name, s, e in repro:
        for i, (cs, ce) in enumerate(calls):
            if cs <= s and e <= ce:
                out[name][i] += 1e-6 * (e - s)
    return out


def reduce_trace(path: str, calls: int, counters: Dict[str, int],
                 **filters) -> dict:
    """The whole reduction of one traced window (``filters`` go to
    ``bench.trace.summarize``: a CPU trace keeps its ops elsewhere)."""
    summ = bench_trace.summarize(path, **filters)
    lo = min(s for _, s, _ in summ.spans)
    hi = max(e for _, _, e in summ.spans)
    repro = _clip(host_spans(path, ("repro.",)), lo, hi)
    both = sorted(repro + list(summ.spans), key=lambda s: (s[1], -s[2]))
    window_ns = hi - lo
    idle = idle_intervals(summ)

    stage_ms = {}
    for name in sorted({n for n, _, _ in repro}):
        ns = _covered([(s, e) for n, s, e in repro if n == name])
        stage_ms[name] = 1e-6 * ns / calls
    unattributed = sum(ns for name, ns in idle_by_span(idle, both).items()
                       if not name.startswith("repro."))
    op_s = summ.kernel_seconds(lambda t: True)
    split = mean_idle_by_span(summ, both)
    metrics = {m: stage_ms.get(name, 0.0) for m, name in
               STAGE_METRICS.items()}
    metrics.update({
        "xla.traces_per_call": counters.get("xla.traces", 0) / calls,
        "xla.compiles_per_call": counters.get("xla.compiles", 0) / calls,
        "device_idle.unattributed": 100.0 * unattributed / window_ns,
        "analog_forward.head_share": 100.0 * summ.kernel_seconds(
            lambda t: "unembed" in t) / op_s,
    })
    return {
        "calls": calls, "window_s": summ.window_s,
        "idle_pct": 100.0 * summ.idle_share,
        "metrics": metrics, "stage_ms": stage_ms,
        "idle_by_span": {n: 100.0 * v / window_ns for n, v in
                         sorted(split.items(), key=lambda kv: -kv[1])},
        "per_call_ms": per_call_ms(summ, repro, idle),
        "counters": counters,
        "kernel_s": kernel_seconds(summ),
        "idle_gaps": [list(g) for g in named_gaps(summ, both)],
    }


def _counters() -> Dict[str, int]:
    """The program's counters, or none where it has no telemetry."""
    try:
        from repro.runtime import telemetry
    except ImportError:
        return {}
    return telemetry.snapshot()


def traced_window(entry, state, seed: int, seconds: float, trace_dir: str):
    """Whole calls for ``seconds`` under the profiler, inside the spans
    ``bench/run.py`` opens; returns (records, counter differences)."""
    import jax

    records = []
    before = _counters()
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t_start = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    records.append(entry.call(state, len(records),
                                              run.call_seed(seed,
                                                            len(records))))
                if time.perf_counter() - t_start >= seconds:
                    break
    finally:
        jax.profiler.stop_trace()
    after = _counters()
    return records, {k: v - before.get(k, 0) for k, v in after.items()
                     if v != before.get(k, 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    wl, cfg = run.load_cell(args.workload)
    try:
        devices = run.check_devices(int(wl["chips"]))
    except run.NoChip as e:
        print(f"stages: {e}", file=sys.stderr)
        return 3
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = run.load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, args.seed, devices)
    print(f"stages: setup {time.perf_counter() - _T0:.3f} s",
          file=sys.stderr, flush=True)
    trace_dir = tempfile.mkdtemp(prefix="stages-trace-")
    try:
        records, counters = traced_window(entry, state, args.seed,
                                          args.seconds, trace_dir)
        path = bench_trace.find_xplane(trace_dir)
        out = reduce_trace(path, len(records), counters)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["workload"] = args.workload
    assert all(math.isfinite(v) for v in out["metrics"].values())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

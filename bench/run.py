#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data, found by name:

  bench/workloads/<workload>.json   configuration, traffic, entry, chips,
                                    end-to-end metric, per-layer metrics and
                                    the limits of the correctness check
  bench/traffic/<traffic>.json      the traffic's parameters
  bench/configs/<config>.json       the configuration as it is run
  bench/entries/<entry>.py          the driver of one entry point of the
                                    program: set-up, one call, the check
  bench/metrics/<metric>.py         the reader of one per-layer metric

A run sets up (imports, device, compile cache, inputs made on the device
from the seed, one warm call of the cell's shapes: ``setup_s``), then runs
whole calls in a closed loop until ``--seconds`` have passed, each call
with its own seed derived from ``--seed`` and its index.  After the
window it reads the device's peak memory, frees the program's state and
compares a sample of the window's outputs, drawn from the seed, with the
plain reference under ``bench/reference``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last the numbers
compared with their limits (``compared``), which also close standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
# the script's own directory would shadow stdlib modules (``trace``)
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path.pop(0)
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(workload with its traffic parameters, configuration) of a cell."""
    wl = load_json("workloads", name)
    wl["traffic"] = load_json("traffic", wl["traffic"])
    return wl, load_json("configs", wl["config"])


def call_seed(seed: int, index: int) -> int:
    """Seed of call ``index`` of a run: distinct per call, below 2**20 so
    every seed the program derives from it stays in 31 bits."""
    x = (int(seed) * 0x9E3779B1 + (index + 2) * 0x85EBCA6B) & 0xFFFFFFFFFFFF
    x ^= x >> 23
    x = (x * 0x2545F4914F6CDD1D) & 0xFFFFFFFFFFFF
    return int(x % ((1 << 20) - 1)) + 1


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip("no TPU found: JAX sees only "
                     f"{sorted({d.platform for d in devices})}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def end_to_end(spec: dict, work: dict, n_calls: int, span_s: float) -> float:
    """The cell's end-to-end metric over the whole window: a rate of work
    units over all calls, or milliseconds per call."""
    if spec["kind"] == "rate":
        return work[spec["units"]] * n_calls / span_s
    if spec["kind"] == "ms_per_call":
        return 1e3 * span_s / n_calls
    raise ValueError(f"unknown end-to-end kind {spec['kind']!r}")


def run_cell(wl: dict, cfg: dict, seed: int, seconds: float, trace: bool, *,
             devices, t0: float, log=print):
    """Set up, run the window, check; returns the result object."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, seed, devices)
    setup_s = time.perf_counter() - t0
    log(f"setup {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    records, call_s = [], []
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t_start = t_end = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    records.append(entry.call(state, len(records),
                                              call_seed(seed, len(records))))
                call_s.append(time.perf_counter() - t_end)
                t_end += call_s[-1]
                if t_end - t_start >= seconds:
                    break
    finally:
        if trace:
            jax.profiler.stop_trace()
    span_s = t_end - t_start
    log(f"window {span_s:.3f} s, {len(records)} calls, fastest "
        f"{min(call_s):.3f} s, slowest {max(call_s):.3f} s")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    work = entry.work(state)
    metrics = {}
    breakdown = None
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from bench import trace as bench_trace

        summ = bench_trace.summarize(bench_trace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summ.mean_busy_s
        device["window_s"] = summ.window_s
        ctx = {"trace": summ, "calls": len(records), "work": work,
               "peaks": bench_trace.peaks_for(dev0.device_kind),
               "span_s": span_s}
        for name in wl["per_layer"]:
            reader = load_module("metrics", name)
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
        breakdown = {"device_ops": [list(x) for x in summ.top_ops(10)],
                     "idle_gaps": [list(x) for x in summ.idle_gaps(10)]}
    else:
        e2e = wl["end_to_end"]
        metrics[e2e["name"]] = {"value": end_to_end(e2e, work, len(records),
                                                    span_s),
                                "unit": e2e["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    compared = {k: float(v) for k, v in
                entry.check(state, records, seed).items()}
    limits = wl["limits"]
    ok = bool(compared) and all(
        math.isfinite(v) and v <= limits[name] for name, v in compared.items())
    result = {"correct": ok, "attempted": len(records), "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {name: {"value": v, "limit": limits[name]}
                          for name, v in compared.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    wl, cfg = load_cell(args.workload)
    try:
        devices = check_devices(int(wl["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    def log(msg):
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    result = run_cell(wl, cfg, args.seed, args.seconds, bool(args.trace),
                      devices=devices, t0=_T0, log=log)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

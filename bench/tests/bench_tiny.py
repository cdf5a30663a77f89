"""Tiny versions of the benchmark's cells for CPU tests: the same files,
entries and limits, at sizes a test run holds (Pallas interpreted)."""
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402

from bench import run  # noqa: E402

TINY_TRAFFIC = {
    "campaign": {"samples_per_point": 256,
                 "pulse_widths_s": [6e-11, 1.2e-10]},
    "write_verify": {"cells": 4096},
    "analog_forward": {"batch": 2, "seq": 64},
}
TINY_MODEL = {"hidden_size": 128, "intermediate_size": 256,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 512}


def tiny_cell(name: str):
    wl, cfg = run.load_cell(name)
    wl["traffic"].update(TINY_TRAFFIC[wl["entry"]])
    if "model" in cfg:
        cfg["model"].update(TINY_MODEL)
    return wl, cfg


def run_tiny(name: str, seed: int = 12345, devices=None):
    """One run of the cell past the chip check: set-up, a window of one
    call, the check; returns the result object."""
    wl, cfg = tiny_cell(name)
    devices = devices or jax.devices()[: wl["chips"]]
    return run.run_cell(wl, cfg, seed, 0.0, False, devices=devices,
                        t0=time.perf_counter(), log=lambda *a: None)


def control_fails(name: str, seed: int = 7):
    """The control's readings and whether the cell's limits reject it."""
    wl, cfg = tiny_cell(name)
    entry = run.load_module("entries", wl["entry"])
    state = entry.setup(cfg, wl, seed, jax.devices()[: wl["chips"]])
    rec = entry.call(state, 0, run.call_seed(seed, 0))
    readings = entry.control(state, [rec], seed)
    return readings, any(readings[k] > v for k, v in wl["limits"].items())


def assert_result_shape(res, e2e: str):
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert list(res)[-1] == "compared"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {e2e, "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert all(set(c) == {"value", "limit"} for c in res["compared"].values())

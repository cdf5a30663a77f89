"""Entry: ``campaign.engine.run_campaign`` with the streaming reduction.

One call is one whole Monte-Carlo campaign over the workload's
(temperature x voltage x sample) grid with a fresh seed: the Pallas thermal
LLG kernel integrates every lane, and the reduction on the device leaves
WER counts per (T, V, pulse) and a first-crossing histogram per (T, V).
The result cache is off, so every call integrates.

The check takes one campaign of the window, drawn from the run's seed,
and integrates the same lanes with the plain reference
(``bench/reference/llg.py``), temperature slice by slice, split over the
cell's chips.  It compares:

  wer_gap     the largest |difference| of a WER count, over all
              (T, V, pulse), as a share of the samples per (T, V);
  hist_moved  the share of all lanes whose latency bin differs
              (half the L1 distance of the two histograms).
"""
from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from bench import program
from bench.reference import llg as ref_llg


def _grid(t: dict, seed: int):
    from repro.campaign import CampaignGrid

    return CampaignGrid(voltages=tuple(t["voltages"]),
                        pulse_widths=tuple(t["pulse_widths_s"]),
                        temperatures=tuple(t["temperatures_k"]),
                        n_samples=int(t["samples_per_point"]),
                        dt=float(t["dt_s"]), seed=int(seed),
                        switch_threshold=float(t["switch_threshold"]))


def setup(cfg, wl, seed, devices):
    state = {"cfg": cfg, "t": wl["traffic"], "p": program.afmtj_params(cfg),
             "devices": devices}
    call(state, -1, 0)                   # the warm call: compiles or loads
    return state


def call(state, index, seed):
    from repro.campaign import run_campaign

    t = state["t"]
    res = run_campaign(state["p"], _grid(t, seed), reduce="stream",
                       n_bins=int(t["n_bins"]), use_cache=False,
                       devices=len(state["devices"]))
    return {"seed": seed, "wer": np.asarray(res.wer_counts),
            "hist": np.asarray(res.latency_hist)}


def work(state):
    t = state["t"]
    lanes = (len(t["temperatures_k"]) * len(t["voltages"])
             * int(t["samples_per_point"]))
    n_steps = ref_llg.horizon_steps(t["pulse_widths_s"], t["dt_s"])
    return {"lanes": lanes, "n_steps": n_steps,
            "lane_steps": lanes * n_steps}


def reference(state, seed, dtype=jnp.float32):
    """(wer_counts, hist) of one campaign by the plain reference: every
    temperature slice split evenly over the cell's chips, all parts
    dispatched before any is read."""
    t = state["t"]
    dt = float(t["dt_s"])
    n_s = int(t["samples_per_point"])
    n_steps = ref_llg.horizon_steps(t["pulse_widths_s"], dt)
    volts = np.repeat(np.asarray(t["voltages"], np.float32), n_s)
    edges = np.linspace(0, volts.size, len(state["devices"]) + 1).astype(int)
    parts = [[ref_llg.slice_crossings(
        state["cfg"]["device"], seed=seed, slice_index=ti,
        temperature=float(temp), volts=volts, dt=dt, n_steps=n_steps,
        threshold=float(t["switch_threshold"]), lo=int(lo), hi=int(hi),
        dtype=dtype, device=dev)
        for dev, lo, hi in zip(state["devices"], edges[:-1], edges[1:])]
        for ti, temp in enumerate(t["temperatures_k"])]
    steps = np.stack([np.concatenate([np.asarray(x) for x in row])
                      for row in parts])
    steps = steps.reshape(len(t["temperatures_k"]), len(t["voltages"]), n_s)
    return ref_llg.reduce_crossings(steps, t["pulse_widths_s"], dt, n_steps,
                                    int(t["n_bins"]))


def compare(rec, wer, hist, n_samples: int) -> dict:
    lanes = hist.shape[0] * hist.shape[1] * n_samples
    return {
        "wer_gap": float(np.abs(rec["wer"].astype(np.int64) - wer).max()
                         / n_samples),
        "hist_moved": float(np.abs(rec["hist"].astype(np.int64) - hist).sum()
                            / (2.0 * lanes)),
    }


def sample(records, seed):
    return records[random.Random(seed).randrange(len(records))]


def check(state, records, seed):
    rec = sample(records, seed)
    wer, hist = reference(state, rec["seed"])
    return compare(rec, wer, hist, int(state["t"]["samples_per_point"]))


def control(state, records, seed):
    """The check's numbers with the reference in bfloat16 put in the
    program's place."""
    rec = sample(records, seed)
    wer, hist = reference(state, rec["seed"])
    w16, h16 = reference(state, rec["seed"], dtype=jnp.bfloat16)
    fake = {"wer": w16, "hist": h16}
    return compare(fake, wer, hist, int(state["t"]["samples_per_point"]))

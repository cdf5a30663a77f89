"""Entry: ``imc.write_path.write_verify`` — one write-verify ladder per call.

One call programs every cell of one subarray (P -> AP) through the retry
ladder: a thermal LLG pulse per still-unwritten cell, the success test on
the crossing row, compaction of the survivors, again up to the policy's
attempt budget; it returns per-cell attempts, crossing time and energy.
The result cache is off, so every round integrates.

The check takes one ladder of the window, drawn from the run's seed, and
replays it with the plain reference (``bench/reference/llg.py``): the same
rounds, seeds, pulse, success test, compaction and energy accounting.  It
compares:

  first_pulse_mismatch  the share of cells whose first pulse succeeds in
                        one and fails in the other;
  attempts_moved        the share of cells whose attempt count differs
                        (half the L1 distance of the two retry
                        histograms);
  energy_gap            |mean energy difference| over the reference's
                        mean energy per cell.
"""
from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from bench import program
from bench.reference import llg as ref_llg


def _policy(t: dict, seed: int):
    from repro.imc.write_path import WritePolicy

    return WritePolicy(v_write=float(t["v_write"]), pulse=float(t["pulse_s"]),
                       max_attempts=int(t["max_attempts"]),
                       t_rc=float(t["t_rc_s"]), seed=int(seed),
                       use_cache=False)


def setup(cfg, wl, seed, devices):
    from repro.imc.write_path import DEVICE_DT

    t = wl["traffic"]
    program.afmtj_params(cfg)
    if float(t["dt_s"]) != DEVICE_DT["afmtj"]:
        raise ValueError("workload dt differs from the program's AFMTJ step")
    state = {"cfg": cfg, "t": t, "devices": devices}
    call(state, -1, 0)                   # the warm call: compiles or loads
    return state


def call(state, index, seed):
    from repro.imc.write_path import write_verify

    t = state["t"]
    res = write_verify("afmtj", int(t["cells"]), _policy(t, seed))
    return {"seed": seed, "attempts": res.attempts.astype(np.int8),
            "energy": res.energy, "rounds": res.rounds}


def work(state):
    return {"cells": int(state["t"]["cells"])}


def reference(state, seed, dtype=jnp.float32):
    """(attempts, energy) per cell of one ladder by the plain reference."""
    t = state["t"]
    dev = state["cfg"]["device"]
    n = int(t["cells"])
    v, pulse, dt = float(t["v_write"]), float(t["pulse_s"]), float(t["dt_s"])
    temp = float(dev["temperature"])
    d = ref_llg.derived(dev, temp, dt)
    n_steps = ref_llg.horizon_steps([pulse], dt)
    e_rc = v * v * d["g_p"] * float(t["t_rc_s"])
    attempts = np.zeros(n, np.int64)
    energy = np.zeros(n)
    remaining = np.arange(n)
    for rnd in range(int(t["max_attempts"])):
        if remaining.size == 0:
            break
        k = np.asarray(ref_llg.slice_crossings(
            dev, seed=seed * 1009 + rnd, slice_index=0, temperature=temp,
            volts=np.full(remaining.size, v, np.float32), dt=dt,
            n_steps=n_steps, threshold=float(t["switch_threshold"]),
            dtype=dtype, device=state["devices"][0]))
        ct = np.minimum(k.astype(np.float64), float(n_steps)) * dt
        ok = ct <= pulse
        attempts[remaining] += 1
        energy[remaining] += np.where(
            ok, v * v * (d["g_p"] * ct + d["g_ap"] * (pulse - ct)),
            v * v * d["g_p"] * pulse) + e_rc
        remaining = remaining[~ok]
    return attempts, energy


def compare(rec, attempts, energy, max_attempts: int) -> dict:
    a = rec["attempts"].astype(np.int64)
    n = a.size
    h_prog = np.bincount(a, minlength=max_attempts + 1)
    h_ref = np.bincount(attempts, minlength=max_attempts + 1)
    return {
        "first_pulse_mismatch": float(((a == 1) != (attempts == 1)).mean()),
        "attempts_moved": float(np.abs(h_prog - h_ref).sum() / (2.0 * n)),
        "energy_gap": float(abs(rec["energy"].mean() - energy.mean())
                            / energy.mean()),
    }


def sample(records, seed):
    return records[random.Random(seed).randrange(len(records))]


def check(state, records, seed):
    rec = sample(records, seed)
    attempts, energy = reference(state, rec["seed"])
    return compare(rec, attempts, energy, int(state["t"]["max_attempts"]))


def control(state, records, seed):
    """The check's numbers with the reference in bfloat16 put in the
    program's place."""
    rec = sample(records, seed)
    attempts, energy = reference(state, rec["seed"])
    a16, e16 = reference(state, rec["seed"], dtype=jnp.bfloat16)
    return compare({"attempts": a16, "energy": e16}, attempts, energy,
                   int(state["t"]["max_attempts"]))

"""Opt-in XLA tuning profiles (DESIGN.md §14).

Campaign launches at fleet scale are dominated by two things XLA controls
but does not default well for collective-heavy programs: how eagerly the
scheduler hides collective latency under compute, and how aggressively
small collectives are combined into fewer, larger ones.  The MLPerf-style
recipes in SNIPPETS.md §2 tune exactly those knobs; this module packages
them as *named profiles* so a campaign driver (or a test child process)
opts in with one env merge instead of a hand-maintained flag string.

XLA reads ``XLA_FLAGS`` once, at backend initialization — so profiles are
applied to the environment of a *future* process (tests spawn
children; fleet launchers export before exec), never mutated into a live
one.  ``apply_profile`` refuses (warns and returns the env unchanged)
when JAX is already initialized in-process, because the flags would
silently not take effect.

Profiles:

* ``gpu-scaling`` — the SNIPPETS.md §2 set: latency-hiding scheduler,
  per-collective combine thresholds, pipelined all-gather/reduce-scatter/
  all-reduce, while-loop double buffering.  GPU-backend flags parse (and
  no-op) on CPU builds, so the same profile string is safe to stage in CI.
* ``host-devices`` — the CI / smoke stand-in for a device mesh:
  ``--xla_force_host_platform_device_count=N`` (``n=`` format key).
"""
from __future__ import annotations

import os
import sys
import warnings
from typing import Dict, Optional, Tuple

# flag tuples, not strings, so tests can assert per-flag and callers can
# subset; combine thresholds follow SNIPPETS.md §2 (all-reduce 128 MiB,
# all-gather 1 GiB, reduce-scatter 32 MiB)
PROFILES: Dict[str, Tuple[str, ...]] = {
    "gpu-scaling": (
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
        "--xla_gpu_all_reduce_combine_threshold_bytes=134217728",
        "--xla_gpu_all_gather_combine_threshold_bytes=1073741824",
        "--xla_gpu_reduce_scatter_combine_threshold_bytes=33554432",
        "--xla_gpu_enable_pipelined_all_gather=true",
        "--xla_gpu_enable_pipelined_reduce_scatter=true",
        "--xla_gpu_enable_pipelined_all_reduce=true",
        "--xla_gpu_enable_while_loop_double_buffering=true",
        "--xla_gpu_enable_all_gather_combine_by_dim=false",
        "--xla_gpu_enable_reduce_scatter_combine_by_dim=false",
    ),
    "host-devices": (
        "--xla_force_host_platform_device_count={n}",
    ),
}


def flags_for(profile: str, **fmt) -> str:
    """The profile's flag string (space-joined), with ``{key}`` format
    fields substituted (``host-devices`` needs ``n=...``)."""
    if profile not in PROFILES:
        raise KeyError(
            f"unknown XLA profile {profile!r}; have {sorted(PROFILES)}")
    return " ".join(f.format(**fmt) for f in PROFILES[profile])


def jax_initialized() -> bool:
    """Whether this process's JAX backend is already up (flags applied now
    would be ignored).  Checked without importing jax: an un-imported jax
    trivially hasn't initialized."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        from jax._src import xla_bridge

        return xla_bridge.backends_are_initialized()
    except Exception:
        return True                      # can't prove it's safe: assume up


def apply_profile(profile: str, env: Optional[Dict[str, str]] = None,
                  **fmt) -> Dict[str, str]:
    """Merge a profile into ``env``'s ``XLA_FLAGS`` and return the env.

    ``env=None`` copies ``os.environ`` — the common case of building a
    child-process environment.  Existing ``XLA_FLAGS`` content is kept
    (profile flags append, so an explicit user flag still wins XLA's
    last-one-parses semantics for duplicated options).  Mutating the
    *current* process after JAX initialized is a silent no-op at the XLA
    level, so that case warns and returns the env unmerged.
    """
    if env is None:
        if jax_initialized():
            warnings.warn(
                f"XLA profile {profile!r} not applied: jax is already "
                "initialized in this process; spawn a child with this env "
                "instead", RuntimeWarning, stacklevel=2)
            return dict(os.environ)
        env = dict(os.environ)
    else:
        env = dict(env)
    new = flags_for(profile, **fmt)
    old = env.get("XLA_FLAGS", "").strip()
    env["XLA_FLAGS"] = f"{old} {new}".strip() if old else new
    return env

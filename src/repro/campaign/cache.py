"""On-disk campaign result cache (content-addressed npz).

A campaign is expensive (minutes of kernel time for production grids) and
perfectly reproducible: the result is a pure function of (device params,
grid axes, backend, kernel version).  So results are cached under a sha256
content key — re-running a benchmark or re-building an IMC hierarchy with
WER-margined pulses hits the cache instead of re-integrating.

Layout: ``<cache_dir>/<key>.npz`` holding the crossing-time tensor plus a
json header echoing the inputs (for `ls`-ability / debugging).  Writes are
atomic (tmp + rename) so concurrent campaign processes never observe a
torn file.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from repro.core.params import DeviceParams

# bump when the kernel's noise stream or integration scheme changes — old
# cached surfaces are then silently invalidated (different key).
# v3: fused-temperature launch layout (per-lane sigma + step-budget aux
# plane, bucketed lane padding, chunked early exit).  Crossing tensors are
# designed to be bit-identical to v2 (the per-lane streams and per-step
# update order are unchanged — tests/test_fused_engine.py pins the fused
# vs per-T equality), but the launch layout changed enough that a
# conservative invalidation is cheaper than any risk of a stale surface.
# v4: per-lane device-variation plane (DESIGN.md §9) — grids grew an
# optional ``variation`` axis (``CampaignGrid.variation`` lands in the
# key payload via asdict) and variation results store a 4-D
# (corner x T x V x S) tensor.  Nominal grids are numerically unchanged,
# but v3 entries were keyed without the variation field, so they are
# orphaned rather than risked: a v3 file simply never matches a v4 key
# (the version is in the hash) and loads of malformed/stale files stay
# misses — tests/test_variation.py pins the ignored-not-crashed behavior.
KERNEL_VERSION = 4
# covered by the key so future packing changes (lane order, bucket rule)
# can invalidate independently of the physics version
CELLS_LAYOUT = "fused-CT/bucket-pow2"

DEFAULT_CACHE_DIR = os.environ.get(
    "REPRO_CAMPAIGN_CACHE", os.path.join(os.path.expanduser("~"),
                                         ".cache", "repro-campaigns"))


# ---------------------------------------------------------------- generic
# Content-keyed named-array store — the campaign crossing-time cache below
# and the analog weight-programming cache (``imc.model_analog``) are both
# thin layers over these three primitives.

def content_key(payload: dict) -> str:
    """sha256 content key of a json-able payload (sorted keys, so dict
    insertion order never leaks into the key)."""
    blob = json.dumps(payload, sort_keys=True, default=float)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def load_arrays(key: str, cache_dir: Optional[str] = None
                ) -> Optional[dict]:
    """All named arrays of a cached entry (header excluded), or None on
    miss.  Corrupt / torn / stale-format files are misses, never errors."""
    path = Path(cache_dir or DEFAULT_CACHE_DIR) / f"{key}.npz"
    if not path.exists():
        return None
    try:
        with np.load(path) as z:
            return {k: z[k] for k in z.files if k != "header"}
    except (OSError, KeyError, ValueError):
        return None                      # corrupt entry == miss


def gc_stale_tmp(cache_dir: Optional[str] = None,
                 max_age_s: float = 86400.0) -> int:
    """Remove ``*.tmp`` droppings older than ``max_age_s`` seconds.

    A process SIGKILLed mid-``store_arrays`` leaves its mkstemp file behind
    (the atomic rename never ran, so no ``.npz`` is ever torn — but the tmp
    bytes still occupy disk).  The age guard keeps the sweep safe against
    *live* writers in other processes: a concurrent store's tmp file is
    seconds old, far under any sane ``max_age_s``.  Returns the number of
    files removed; every error is best-effort-ignored (a racing writer may
    rename or unlink first).
    """
    import time

    d = Path(cache_dir or DEFAULT_CACHE_DIR)
    if not d.is_dir():
        return 0
    cutoff = time.time() - max_age_s
    removed = 0
    for tmp in d.glob("*.tmp"):
        try:
            if tmp.stat().st_mtime <= cutoff:
                tmp.unlink()
                removed += 1
        except OSError:
            continue
    return removed


def store_arrays(key: str, arrays: dict, header: dict,
                 cache_dir: Optional[str] = None) -> Path:
    """Atomically persist named arrays + a json header under ``key``."""
    assert "header" not in arrays, "reserved entry name"
    d = Path(cache_dir or DEFAULT_CACHE_DIR)
    d.mkdir(parents=True, exist_ok=True)
    gc_stale_tmp(cache_dir, max_age_s=86400.0)
    final = d / f"{key}.npz"
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(
                f, **arrays,
                header=np.frombuffer(
                    json.dumps(header, default=float).encode(), dtype=np.uint8),
            )
        os.replace(tmp, final)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return final


def drop_arrays(key: str, cache_dir: Optional[str] = None) -> bool:
    """Remove a cached entry (best-effort); True if a file was deleted.
    Used by the campaign engine to retire per-slice resume checkpoints
    once the whole-campaign entry is durable."""
    path = Path(cache_dir or DEFAULT_CACHE_DIR) / f"{key}.npz"
    try:
        path.unlink()
        return True
    except OSError:
        return False


# ----------------------------------------------------------------- claims
# Lockless work claims over the content-addressed store (DESIGN.md §14).
# A fleet of campaign processes sharing one cache directory dedupes work
# by *claiming* a content key before integrating it: ``O_CREAT | O_EXCL``
# on ``<key>.claim`` is atomic on every POSIX filesystem (including NFS
# for local excl semantics we rely on), so exactly one process wins each
# key without any lock server.  A claim is advisory — the npz store stays
# last-writer-wins-atomic regardless — its only job is to keep N processes
# from integrating the same slice N times.  Crashed claimants are handled
# by age: a claim older than ``ttl_s`` is presumed orphaned and may be
# *stolen* (unlinked + re-claimed); the store's atomicity makes a rare
# double-compute after a steal merely wasteful, never wrong.

def claim_path(key: str, cache_dir: Optional[str] = None) -> Path:
    return Path(cache_dir or DEFAULT_CACHE_DIR) / f"{key}.claim"


def try_claim(key: str, cache_dir: Optional[str] = None,
              owner: str = "") -> bool:
    """Atomically claim ``key`` for this process; False if already claimed."""
    d = Path(cache_dir or DEFAULT_CACHE_DIR)
    d.mkdir(parents=True, exist_ok=True)
    try:
        fd = os.open(claim_path(key, cache_dir),
                     os.O_CREAT | os.O_EXCL | os.O_WRONLY)
    except FileExistsError:
        return False
    with os.fdopen(fd, "w") as f:
        f.write(json.dumps({"pid": os.getpid(), "owner": owner}))
    return True


def release_claim(key: str, cache_dir: Optional[str] = None) -> bool:
    """Drop this (or any) claim on ``key`` — best-effort, True on unlink."""
    try:
        claim_path(key, cache_dir).unlink()
        return True
    except OSError:
        return False


def claim_age_s(key: str, cache_dir: Optional[str] = None) -> Optional[float]:
    """Seconds since ``key`` was claimed, or None when unclaimed."""
    import time

    try:
        return max(0.0, time.time() - claim_path(key, cache_dir).stat().st_mtime)
    except OSError:
        return None


def steal_claim(key: str, ttl_s: float, cache_dir: Optional[str] = None,
                owner: str = "") -> bool:
    """Take over a claim older than ``ttl_s`` (a crashed claimant).

    Unlink-then-reclaim: two stealers can both unlink, but only one wins
    the ``O_EXCL`` re-create — the loser retreats to polling the store.
    """
    age = claim_age_s(key, cache_dir)
    if age is None or age < ttl_s:
        return False
    release_claim(key, cache_dir)
    return try_claim(key, cache_dir, owner=owner)


def gc_stale_claims(cache_dir: Optional[str] = None,
                    max_age_s: float = 3600.0) -> int:
    """Sweep orphaned ``*.claim`` files older than ``max_age_s`` (claims of
    processes that died without ``release_claim``); returns files removed."""
    import time

    d = Path(cache_dir or DEFAULT_CACHE_DIR)
    if not d.is_dir():
        return 0
    cutoff = time.time() - max_age_s
    removed = 0
    for c in d.glob("*.claim"):
        try:
            if c.stat().st_mtime <= cutoff:
                c.unlink()
                removed += 1
        except OSError:
            continue
    return removed


# --------------------------------------------------------------- campaigns
def campaign_key(p: DeviceParams, grid, backend: str) -> str:
    """Content hash of everything the crossing-time tensor depends on.

    The platform (``"cpu"``, ``"tpu"``) is part of the key, so a surface
    integrated on one platform is never served as another's; the
    resume-slice and streaming keys derive from this one and inherit it."""
    return content_key({
        "v": KERNEL_VERSION,
        "layout": CELLS_LAYOUT,
        "params": dataclasses.asdict(p),
        "grid": dataclasses.asdict(grid),
        "backend": backend,
        "platform": jax.devices()[0].platform,
    })


def load(key: str, cache_dir: Optional[str] = None) -> Optional[np.ndarray]:
    """Cached (n_T, n_V, n_S) crossing-time tensor, or None on miss."""
    arrays = load_arrays(key, cache_dir)
    if arrays is None or "crossing_time" not in arrays:
        return None
    return arrays["crossing_time"]


def store(key: str, crossing_time: np.ndarray, header: dict,
          cache_dir: Optional[str] = None) -> Path:
    return store_arrays(key, {"crossing_time": crossing_time}, header,
                        cache_dir)

"""Counter-based Gaussian noise shared by the Pallas kernel and the oracle.

The thermal field inside ``llg_rk4.py`` cannot use ``jax.random`` (threefry
needs key state threaded through the fori_loop and is ~20x the flops of the
RK4 update itself), so we use a stateless counter-based generator: every
draw is ``mix(cell_seed + counter)`` where ``mix`` is a full-avalanche
32-bit integer hash (lowbias32 constants) and the counter encodes
(step, draw-index).  Properties that matter here:

* **stateless** — noise at step ``i`` is a pure function of (seed, i), so
  the kernel's ``fori_loop`` carries no RNG state and the pure-jnp oracle in
  ``ref.py`` can reproduce the *identical* stream: thermal trajectories are
  testable with ``allclose`` at a fixed seed, not just statistically.
* **per-lane independent** — each cell (lane) owns a distinct uint32 seed
  (``cell_seeds``), so every Monte-Carlo sample in a packed campaign tile is
  an independent thermal realization.
* **cheap on the VPU** — a normal pair costs 2 integer hashes (~12 int ops)
  + one Box-Muller (log/sqrt/sincos), all element-wise 32-bit ops, vs
  threefry's 20 rounds + key management.

Statistical quality: lowbias32 passes full-avalanche tests; this is thermal
noise for a Langevin integrator, not cryptography — what matters is that
per-(seed, counter) outputs are decorrelated, which a full-avalanche mixer
guarantees to well below the sigma of the physics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_GOLD = np.uint32(0x9E3779B9)       # 2^32 / phi — Weyl counter increment
_M1 = np.uint32(0x21F0AAAD)         # lowbias32 (Degski / TheIronBorn) v2
_M2 = np.uint32(0x735A2D97)
_TWO_PI = 6.283185307179586
_INV_2_24 = float(2.0**-24)


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Full-avalanche 32-bit mixer (lowbias32). x: uint32 array."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def cell_seeds(base_seed, cells: int) -> jnp.ndarray:
    """(cells,) uint32 — one independent stream seed per cell/lane.

    splitmix-style: mix a Weyl sequence off the base seed so consecutive
    cells land in decorrelated regions of counter space.  ``base_seed`` is
    a Python int (low 32 bits used) or a uint32 array scalar, which a
    jitted caller passes as traced data.
    """
    idx = jnp.arange(cells, dtype=jnp.uint32)
    base = (base_seed.astype(jnp.uint32) if isinstance(base_seed, jax.Array)
            else np.uint32(base_seed & 0xFFFFFFFF))
    return mix32(mix32(base + idx * _GOLD))


_SLICE_GOLD = 0x9E3779B1        # odd Weyl constants: campaign seed ...
_SLICE_OFF = 0x85EB_CA6B        # ... and per-temperature-slice offset


def slice_seeds(base_seed: int, slice_index: int, cells: int) -> jnp.ndarray:
    """(cells,) uint32 streams for slice ``slice_index`` of a campaign.

    Offsets the base seed by a per-slice Weyl constant before the per-lane
    split, so (for the campaign engine) the temperature slices of a fused
    (T x V x S) plane never share counters — and a fused launch consumes
    exactly the streams the old per-temperature launches did (the packing
    bit-compat ``tests/test_fused_engine.py`` pins)."""
    return cell_seeds(slice_base(base_seed, slice_index), cells)


def slice_base(base_seed: int, slice_index: int) -> int:
    """The base seed ``slice_seeds`` splits into lanes, as a host int."""
    return (base_seed * _SLICE_GOLD + slice_index * _SLICE_OFF) & 0xFFFFFFFF


def _uniform24(h: jnp.ndarray) -> jnp.ndarray:
    """uint32 hash -> f32 uniform in (0, 1] using the top 24 bits.

    The 24-bit value goes through int32 on its way to f32: exact (it is
    below 2**24), and Mosaic lowers int32 -> f32 but not uint32 -> f32."""
    top = (h >> np.uint32(8)).astype(jnp.int32)
    return (top.astype(jnp.float32) + 1.0) * _INV_2_24


def normal_pair(seed: jnp.ndarray, counter: jnp.ndarray):
    """Two independent standard normals per lane via Box-Muller.

    seed: (n,) uint32 per-lane stream seeds; counter: scalar uint32 draw
    counter (same for all lanes).  Returns (z0, z1) f32 arrays of shape (n,).

    The counter is avalanche-mixed *before* combining with the lane seed:
    with a plain Weyl offset (``seed + counter*GOLD``), two lanes whose
    seeds differ by k*GOLD would consume time-shifted copies of the same
    stream.  Hashing the counter first makes persistent cross-lane overlap
    require mix32 collisions, not arithmetic coincidence.
    """
    base = seed ^ mix32(counter * _GOLD + np.uint32(1))
    h1 = mix32(base)
    h2 = mix32(base ^ _M2)
    u1 = _uniform24(h1)
    u2 = _uniform24(h2)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    ang = _TWO_PI * u2
    return r * jnp.cos(ang), r * jnp.sin(ang)


def thermal_draws(seed: jnp.ndarray, step: jnp.ndarray):
    """Six standard normals per lane for one LLG step.

    Returns ((x1, y1, z1), (x2, y2, z2)) — the per-component thermal field
    directions for sublattice 1 and 2 (scale by sigma at the call site).
    ``step`` may be a traced loop index (any integer dtype).
    """
    step_u = (jnp.asarray(step).astype(jnp.uint32)) * np.uint32(3)
    a0, b0 = normal_pair(seed, step_u)
    a1, b1 = normal_pair(seed, step_u + np.uint32(1))
    a2, b2 = normal_pair(seed, step_u + np.uint32(2))
    return (a0, a1, a2), (b0, b1, b2)

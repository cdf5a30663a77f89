"""Plain reference of the thermal dual-sublattice LLG campaign.

Written from the model's equations (paper Sec. II) and the campaign's
stated semantics, in straightforward ``jax.numpy`` over lane vectors; it
imports nothing of the system under test.  What it reproduces:

* the counter-based thermal stream (lowbias32 hash, Box-Muller), one
  uint32 seed per lane, three normal pairs per step;
* the Boltzmann tilt of the idle state, drawn with ``jax.random`` from the
  campaign seed and the temperature index;
* one RK4 step of the implicit-Gilbert LLG with a staggered Neel
  spin-transfer torque, drive a_J(n_z) and thermal field held over the
  step, renormalised after it;
* the first step at which the Neel z component passes below
  ``-switch_threshold``, and the reduction of those steps to WER counts and
  a fixed-bin latency histogram.

``dtype`` sets the precision of the physics (float32 as the configuration
states; bfloat16 is the control that a sound comparison must reject).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GAMMA = 1.760859630e11     # gyromagnetic ratio [rad / (s T)]
KB = 1.380649e-23          # Boltzmann [J / K]
HBAR = 1.054571817e-34     # reduced Planck [J s]
QE = 1.602176634e-19       # elementary charge [C]

_GOLD = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)
_SLICE_GOLD = 0x9E3779B1
_SLICE_OFF = 0x85EB_CA6B


# ------------------------------------------------------------ device numbers
def derived(dev: dict, temperature: float, dt: float) -> dict:
    """Host constants of one device at one temperature (float64)."""
    area = dev["lx"] * dev["ly"]
    volume = area * dev["lz"]
    r_p = dev["ra_product"] / area
    r_ap = r_p * (1.0 + dev["tmr"])
    e_b = 0.5 * dev["b_aniso"] * dev["ms"] * volume
    return {
        "g_p": 1.0 / r_p, "g_ap": 1.0 / r_ap, "area": area,
        "stt": HBAR * dev["polarization"] / (2.0 * QE * dev["ms"] * dev["lz"]),
        "sigma": math.sqrt(2.0 * dev["alpha"] * KB * temperature
                           / (GAMMA * dev["ms"] * volume * dt)),
        "delta": e_b / (KB * temperature),
    }


# ------------------------------------------------------- counter-based RNG
def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def lane_seeds(base_seed: int, slice_index: int, lanes: int):
    """(lanes,) uint32 stream seeds of one temperature slice."""
    base = (base_seed * _SLICE_GOLD + slice_index * _SLICE_OFF) & 0xFFFFFFFF
    idx = jnp.arange(lanes, dtype=jnp.uint32)
    return _mix32(_mix32(np.uint32(base) + idx * _GOLD))


def _uniform(h):
    top = (h >> np.uint32(8)).astype(jnp.int32)
    return (top.astype(jnp.float32) + 1.0) * float(2.0 ** -24)


def _normal_pair(seed, counter):
    base = seed ^ _mix32(counter * _GOLD + np.uint32(1))
    u1 = _uniform(_mix32(base))
    u2 = _uniform(_mix32(base ^ _M2))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    ang = 6.283185307179586 * u2
    return r * jnp.cos(ang), r * jnp.sin(ang)


def thermal_normals(seed, step):
    """Six standard normals per lane at ``step``: sublattice 1 (x, y, z),
    sublattice 2 (x, y, z)."""
    c = jnp.asarray(step).astype(jnp.uint32) * np.uint32(3)
    a0, b0 = _normal_pair(seed, c)
    a1, b1 = _normal_pair(seed, c + np.uint32(1))
    a2, b2 = _normal_pair(seed, c + np.uint32(2))
    return (a0, a1, a2), (b0, b1, b2)


# -------------------------------------------------------------- initial state
@functools.partial(jax.jit, static_argnames=("lanes",))
def tilted_states(seed, slice_index, lanes: int, delta):
    """Neel-mode tilt of the idle cell: |N(0,1)| * theta_eq + 0.01 from +z,
    uniform azimuth; sublattice 2 antiparallel.  Returns (m1, m2) triples."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), slice_index)
    k_th, k_ph = jax.random.split(key)
    zs = jnp.abs(jax.random.normal(k_th, (lanes,)))
    ph = jax.random.uniform(k_ph, (lanes,), maxval=2 * jnp.pi)
    theta_eq = jnp.sqrt(1.0 / (2.0 * jnp.maximum(delta, 1.0)))
    th = zs * theta_eq + 0.01
    m1 = (jnp.sin(th) * jnp.cos(ph), jnp.sin(th) * jnp.sin(ph), jnp.cos(th))
    return m1, tuple(-c for c in m1)


# ---------------------------------------------------------------- dynamics
def _cross(a, b):
    return (a[1] * b[2] - a[2] * b[1],
            a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0])


def _rhs(m, m_other, sign, aj, b_th, dev, dtype):
    """dm/dt of one sublattice: precession in B_eff (anisotropy, exchange
    with the other sublattice, thermal field), damping-like and field-like
    STT along sign * z, implicit Gilbert damping solved exactly."""
    c = functools.partial(jnp.asarray, dtype=dtype)
    zero = jnp.zeros_like(m[0])
    b = (-c(dev["b_exchange"]) * m_other[0] + b_th[0],
         -c(dev["b_exchange"]) * m_other[1] + b_th[1],
         c(dev["b_aniso"]) * m[2] - c(dev["b_exchange"]) * m_other[2]
         + b_th[2])
    pvec = (zero, zero, zero + c(sign))
    mxb = _cross(m, b)
    mxp = _cross(m, pvec)
    mxmxp = _cross(m, mxp)
    g = c(GAMMA)
    t = tuple(-g * x + g * aj * y - g * c(dev["beta_flt"]) * aj * z
              for x, y, z in zip(mxb, mxmxp, mxp))
    mxt = _cross(m, t)
    alpha = c(dev["alpha"])
    return tuple((x + alpha * y) / (1.0 + alpha * alpha)
                 for x, y in zip(t, mxt))


def _rk4(m1, m2, aj, th1, th2, dt, dev, dtype):
    def f(a, b):
        return (_rhs(a, b, 1.0, aj, th1, dev, dtype),
                _rhs(b, a, -1.0, aj, th2, dev, dtype))

    def add(m, k, h):
        return tuple(x + h * y for x, y in zip(m, k))

    h = jnp.asarray(dt, dtype)
    k1 = f(m1, m2)
    k2 = f(add(m1, k1[0], 0.5 * h), add(m2, k1[1], 0.5 * h))
    k3 = f(add(m1, k2[0], 0.5 * h), add(m2, k2[1], 0.5 * h))
    k4 = f(add(m1, k3[0], h), add(m2, k3[1], h))

    def step(m, i):
        new = tuple(x + (h / 6.0) * (a + 2.0 * b + 2.0 * c_ + d)
                    for x, a, b, c_, d in zip(m, k1[i], k2[i], k3[i], k4[i]))
        norm = jnp.sqrt(new[0] * new[0] + new[1] * new[1] + new[2] * new[2])
        return tuple(x / norm for x in new)

    return step(m1, 0), step(m2, 1)


@functools.partial(jax.jit, static_argnames=("n_steps", "dt", "threshold",
                                             "dtype"))
def crossing_steps(m1, m2, volts, seeds, sigma, g_p, g_ap, stt_over_area,
                   dev, *, n_steps: int, dt: float, threshold: float,
                   dtype=jnp.float32):
    """(lanes,) f32 first step (1-based) at which n_z < -threshold, or
    ``n_steps`` where a lane never crosses within ``n_steps`` steps."""
    as_t = functools.partial(jnp.asarray, dtype=dtype)
    m1 = tuple(as_t(x) for x in m1)
    m2 = tuple(as_t(x) for x in m2)
    volts = as_t(volts)
    dev = {k: as_t(v) for k, v in dev.items()}
    g_mid = as_t(0.5 * (g_p + g_ap))
    g_half = as_t(0.5 * (g_p - g_ap))
    pref = as_t(stt_over_area)
    sigma = as_t(sigma)

    def body(i, carry):
        m1, m2, crossed = carry
        nz = 0.5 * (m1[2] - m2[2])
        aj = pref * volts * (g_mid + g_half * nz)
        d1, d2 = thermal_normals(seeds, i)
        th1 = tuple(sigma * as_t(x) for x in d1)
        th2 = tuple(sigma * as_t(x) for x in d2)
        m1, m2 = _rk4(m1, m2, aj, th1, th2, dt, dev, dtype)
        nz = (0.5 * (m1[2] - m2[2])).astype(jnp.float32)
        newly = (nz < -threshold) & (crossed >= float(n_steps))
        crossed = jnp.where(newly, (i + 1).astype(jnp.float32), crossed)
        return m1, m2, crossed

    crossed = jnp.full(volts.shape, float(n_steps), jnp.float32)
    _, _, crossed = jax.lax.fori_loop(0, n_steps, body, (m1, m2, crossed))
    return crossed


def physics_rows(dev: dict) -> dict:
    """The device constants the step reads, as plain floats."""
    return {k: float(dev[k]) for k in ("b_exchange", "b_aniso", "alpha",
                                       "beta_flt")}


def slice_crossings(dev: dict, *, seed: int, slice_index: int,
                    temperature: float, volts: np.ndarray, dt: float,
                    n_steps: int, threshold: float, lo: int = 0,
                    hi: int | None = None, dtype=jnp.float32, device=None):
    """First-crossing steps of lanes ``lo:hi`` of one temperature slice,
    whose lane ``i`` takes drive ``volts[i]``, stream ``i`` of the slice and
    tilt draw ``i`` of the slice.  Dispatched on ``device`` (default: the
    first) without waiting for the result."""
    d = derived(dev, temperature, dt)
    lanes = int(volts.shape[0])
    hi = lanes if hi is None else hi
    # integrate on a power-of-two number of lanes (idle lanes at +z with no
    # drive and no noise) so a ladder's shrinking rounds share compiles
    width = max(512, 1 << (hi - lo - 1).bit_length())
    pad = width - (hi - lo)
    device = device or jax.devices()[0]
    with jax.default_device(device):
        m1, m2 = tilted_states(seed, slice_index, lanes, d["delta"])
        seeds = jnp.pad(lane_seeds(seed, slice_index, lanes)[lo:hi], (0, pad))
        sigma = jnp.pad(jnp.full((hi - lo,), d["sigma"], jnp.float32),
                        (0, pad))
        v = jax.device_put(np.pad(np.asarray(volts[lo:hi], np.float32),
                                  (0, pad)), device)
        rest = lambda c, fill: jnp.pad(c[lo:hi], (0, pad),
                                       constant_values=fill)
        out = crossing_steps(
            tuple(rest(c, f) for c, f in zip(m1, (0.0, 0.0, 1.0))),
            tuple(rest(c, f) for c, f in zip(m2, (0.0, 0.0, -1.0))), v,
            seeds, sigma, d["g_p"], d["g_ap"], d["stt"] / d["area"],
            physics_rows(dev), n_steps=int(n_steps), dt=float(dt),
            threshold=float(threshold), dtype=dtype)
        return out[: hi - lo]


# ---------------------------------------------------------------- reduction
def horizon_steps(pulses, dt: float) -> int:
    """Steps that cover the longest pulse, plus one, so the never-crossed
    value ``n_steps`` lies strictly beyond every pulse."""
    return int(math.ceil(max(pulses) / dt)) + 1


def pulse_steps(pulses, dt: float) -> np.ndarray:
    """Per pulse, the smallest step k with float64(k) * dt > pulse: a lane
    whose crossing step is k or more has not switched within the pulse."""
    out = []
    for pl in pulses:
        k = 0
        while np.float64(k) * dt <= pl:
            k += 1
        out.append(k)
    return np.asarray(out, np.int64)


def reduce_crossings(steps: np.ndarray, pulses, dt: float, n_steps: int,
                     n_bins: int):
    """``steps`` (n_T, n_V, n_S) crossing steps -> (wer_counts (n_T, n_V,
    n_P): lanes not switched within each pulse; hist (n_T, n_V, n_bins):
    switched lanes by bin of crossing step, bin = floor(k * n_bins /
    n_steps) in float32 arithmetic)."""
    k = np.minimum(steps, n_steps).astype(np.int64)
    kmin = pulse_steps(pulses, dt)
    wer = (k[..., None, :] >= kmin[:, None]).sum(-1)
    if n_bins >= n_steps:
        bins = k
    else:
        scale = np.float32(n_bins) / np.float32(n_steps)
        bins = np.floor(k.astype(np.float32) * scale).astype(np.int64)
        bins = np.clip(bins, 0, n_bins - 1)
    hist = np.zeros(k.shape[:-1] + (n_bins,), np.int64)
    sw = k < n_steps
    for idx in np.ndindex(k.shape[:-1]):
        hist[idx] = np.bincount(bins[idx][sw[idx]], minlength=n_bins)
    return wer, hist

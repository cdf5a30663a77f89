"""Junction-level operations: write / read (paper Sec. III-B, Fig. 3).

``simulate_write`` integrates the coupled transport+dynamics system: the
instantaneous conductance G(theta(t)) sets the current density, which sets
the STT amplitude a_J(t) — the self-consistent coupling a SPICE testbench
provides.  Switching time is the first crossing of the order parameter below
-0.9; write latency adds the bit-line RC settle time (circuit layer); write
energy is the integral of V^2 G dt over the pulse.

Everything is jit/vmap-friendly; voltage sweeps are a single vmap.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import llg, tmr
from repro.core.integrator import BASE_DT, Trace, integrate_fixed
from repro.core.params import DeviceParams, DeviceSample

# Default thermal tilt of the initial state: theta_0 = sqrt(1/(2 Delta)),
# the equilibrium Boltzmann spread for a macrospin with barrier Delta kT.
def thermal_theta0(p: DeviceParams) -> jnp.ndarray:
    return theta0_of_stability(p.thermal_stability)


def theta0_of_stability(delta) -> jnp.ndarray:
    """``thermal_theta0`` from the barrier Delta alone (a float or a traced
    float32), for programs that take Delta as data."""
    return jnp.sqrt(1.0 / (2.0 * jnp.maximum(delta, 1.0)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WriteResult:
    t_switch: jnp.ndarray        # intrinsic magnetization reversal time [s]
    write_latency: jnp.ndarray   # t_switch * margin + t_rc  [s]
    energy: jnp.ndarray          # dynamic write energy [J]
    switched: jnp.ndarray        # bool
    final_state: jnp.ndarray


def a_j_from_voltage(v, m: jnp.ndarray, p: DeviceParams) -> jnp.ndarray:
    """Self-consistent STT amplitude [T]: a_J = pref * J = pref * V G(m)/A."""
    g = tmr.conductance(m, p)
    j_density = v * g / p.area
    return p.stt_prefactor * j_density


def simulate_write(
    p: DeviceParams,
    voltage,
    n_steps: int = 30000,
    dt: float = BASE_DT,
    theta0: Optional[float] = None,
    t_rc: float = 40e-12,      # bit-line RC + driver + SA settle (circuit layer)
    pulse_margin: float = 1.02,
    down: bool = True,
    thermal_sigma: float = 0.0,
    rng: Optional[jax.Array] = None,
    variation: Optional[DeviceSample] = None,
) -> WriteResult:
    """Write (switch P -> AP, i.e. order parameter +z -> -z) at ``voltage``.

    The STT amplitude is evaluated self-consistently from the instantaneous
    conductance at every RK4 stage via the time-dependent drive hook below.

    ``variation`` is one sampled device from a process-corner draw
    (``core.params.VariationSpec.sample_device``): its corner/D2D-adjusted
    ``DeviceParams`` replace ``p``, the junction conductance factor scales
    the self-consistent drive, and the default Boltzmann tilt uses the
    volume-adjusted thermal stability — exactly the semantics the campaign
    engine's per-lane variation plane applies (DESIGN.md §9), so the
    scalar baseline and the engine agree on what a corner means.  At the
    nominal corner every factor is literally 1.0 and the result is
    bit-identical to ``variation=None``.
    """
    g_scale = 1.0
    if variation is not None:
        p = variation.params
        g_scale = variation.g_scale
        if theta0 is None:
            theta0 = float(jnp.sqrt(1.0 / (2.0 * jnp.maximum(
                variation.thermal_stability, 1.0))))
    return _simulate_write(p, voltage, g_scale, n_steps=n_steps, dt=dt,
                           theta0=theta0, t_rc=t_rc,
                           pulse_margin=pulse_margin, down=down,
                           thermal_sigma=thermal_sigma, rng=rng)


# thermal_sigma is static: it gates the noise branch with python control
# flow (the wrapper above always forwards it explicitly, so it would
# otherwise be traced — unlike in the pre-variation signature where the
# unpassed default stayed a concrete python float)
@partial(jax.jit, static_argnames=("n_steps", "down", "thermal_sigma"))
def _simulate_write(
    p: DeviceParams,
    voltage,
    g_scale,
    n_steps: int = 30000,
    dt: float = BASE_DT,
    theta0: Optional[float] = None,
    t_rc: float = 40e-12,
    pulse_margin: float = 1.02,
    down: bool = True,
    thermal_sigma: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> WriteResult:
    th0 = thermal_theta0(p) if theta0 is None else theta0
    m0 = llg.initial_state(p, theta0=th0, phi0=0.3, up=down)

    # Self-consistency: fold conductance into the rhs by recomputing a_J from
    # the *current* state each step.  integrate_fixed takes a per-step a_J
    # series; instead we wrap its single-step structure with a custom scan to
    # keep a_J state-dependent.
    def body(carry, key):
        m, t, t_sw, sw, en = carry
        a_j = a_j_from_voltage(voltage, m, p) * g_scale
        if thermal_sigma > 0.0:
            b_th = thermal_sigma * jax.random.normal(key, m.shape)
        else:
            b_th = None
        from repro.core.integrator import rk4_step  # local to avoid cycle

        m_next = rk4_step(lambda mm, tt: llg.llg_rhs(mm, p, a_j, b_th), m, t, dt)
        opz = llg.order_parameter_z(m_next)
        crossed = opz < -0.9 if down else opz > 0.9
        newly = jnp.logical_and(crossed, jnp.logical_not(sw))
        t_sw = jnp.where(newly, t + dt, t_sw)
        sw = jnp.logical_or(sw, crossed)
        g = tmr.conductance(m_next, p) * g_scale
        en = en + jnp.where(sw, 0.0, jnp.asarray(voltage) ** 2 * g * dt)
        return (m_next, t + dt, t_sw, sw, en), None

    if rng is None:
        rng = jax.random.PRNGKey(0)
    keys = jax.random.split(rng, n_steps)
    init = (
        m0,
        jnp.zeros(()),
        jnp.asarray(jnp.inf),
        jnp.asarray(False),
        jnp.zeros(()),
    )
    (m_f, _, t_sw, sw, en), _ = jax.lax.scan(body, init, keys)

    # Write pulse = switching time * margin; energy already integrated up to
    # switch, add the margin tail at the post-switch conductance.
    g_final = tmr.conductance(m_f, p) * g_scale
    tail = (pulse_margin - 1.0) * t_sw
    tail = jnp.where(jnp.isfinite(tail), tail, 0.0)
    # Energy over the full write window: RC/driver overhead at the initial
    # (parallel-state) conductance + the switching pulse + the margin tail.
    g0 = tmr.conductance(m0, p) * g_scale
    energy = (
        en
        + jnp.asarray(voltage) ** 2 * g_final * tail
        + jnp.asarray(voltage) ** 2 * g0 * t_rc
    )
    latency = t_sw * pulse_margin + t_rc
    return WriteResult(
        t_switch=t_sw,
        write_latency=latency,
        energy=energy,
        switched=sw,
        final_state=m_f,
    )


def write_sweep(p: DeviceParams, voltages: jnp.ndarray, **kw) -> WriteResult:
    """Vectorized voltage sweep (paper Fig. 3)."""
    return jax.vmap(lambda v: simulate_write(p, v, **kw))(voltages)


@partial(jax.jit, static_argnames=())
def simulate_read(p: DeviceParams, m: jnp.ndarray, v_read: float = 0.1):
    """Read op: sense current at v_read; returns (current, resistance)."""
    g = tmr.conductance(m, p)
    return v_read * g, 1.0 / g


def read_energy(p: DeviceParams, t_read: float = 1e-9, v_read: float = 0.1) -> float:
    """Worst-case (parallel-state) read energy."""
    return v_read**2 / p.r_parallel * t_read

"""Thermal Monte-Carlo ensembles: write-error rate and retention checks.

Brown's thermal field: per-component std  sigma_B = sqrt(2 alpha k_B T /
(gamma Ms V dt))  [T] — large for the paper's 45x45x0.45 nm cell, which is
why write pulses need margin: WER(pulse) is the MRAM reliability metric a
controller binds against (the paper's pipelined controller assumes a pulse
that covers the thermal tail).

``write_error_rate`` routes through the campaign engine
(``repro.campaign``): the whole thermal ensemble rides one Pallas kernel
launch with in-kernel counter-RNG noise instead of a per-sample
scan-over-steps.  The original pure-jnp path is kept as
``write_error_rate_scan`` — it is the statistical baseline the engine is
tested against (``tests/test_campaign.py``) and a second,
independently-seeded implementation of the same physics.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import llg
from repro.core.device import a_j_from_voltage, thermal_theta0
from repro.core.integrator import rk4_step
from repro.core.params import GAMMA, KB, DeviceParams


def thermal_sigma(p: DeviceParams, dt: float) -> float:
    import math

    return math.sqrt(
        2.0 * p.alpha * KB * p.temperature / (GAMMA * p.ms * p.volume * dt)
    )


def write_error_rate(
    p: DeviceParams,
    voltage: float,
    pulse_s: float,
    n_samples: int = 64,
    dt: float = 0.1e-12,
    n_steps: int = None,
    seed: int = 0,
    backend: str = "pallas",
    use_cache: bool = False,
) -> float:
    """Fraction of thermal samples NOT switched by the end of the pulse.

    Thin wrapper over the campaign engine: builds a single-point (V, pulse)
    grid and reads the WER surface.  ``use_cache=True`` makes repeated
    margin queries (e.g. the IMC write-margin solver) hit the on-disk
    campaign cache.
    """
    # lazy import: campaign builds on core + kernels, so core must not
    # import it at module scope
    from repro.campaign.engine import run_campaign
    from repro.campaign.grid import CampaignGrid

    pulse = float(pulse_s if n_steps is None else n_steps * dt)
    grid = CampaignGrid(voltages=(float(voltage),), pulse_widths=(pulse,),
                        temperatures=(p.temperature,), n_samples=n_samples,
                        dt=dt, seed=seed)
    res = run_campaign(p, grid, backend=backend, use_cache=use_cache)
    return float(res.wer_surface()[0, 0, 0])


@partial(jax.jit, static_argnames=("p", "pulse_s", "n_steps", "n_samples", "dt"))
def write_error_rate_scan(
    p: DeviceParams,
    voltage: float,
    pulse_s: float,
    n_samples: int = 64,
    dt: float = 0.1e-12,
    n_steps: int = None,
    seed: int = 0,
):
    """Reference scan path: per-sample vmap over a scan-over-steps with
    ``jax.random`` (threefry) thermal draws.  O(steps) sequential work per
    sample and ~20x the RNG flops of the kernel's counter-RNG — kept as the
    baseline the campaign engine is measured against, and as an
    independently-seeded cross-check of the WER statistics."""
    n_steps = int(pulse_s / dt) if n_steps is None else n_steps
    sigma = thermal_sigma(p, dt)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_samples)

    def one(key):
        k0, k1, kr = jax.random.split(key, 3)
        th = jnp.abs(jax.random.normal(k0)) * thermal_theta0(p) + 0.01
        ph = jax.random.uniform(k1, maxval=2 * jnp.pi)
        m0 = llg.initial_state(p, theta0=th, phi0=ph)

        def body(carry, step_key):
            m, sw = carry
            aj = a_j_from_voltage(voltage, m, p)
            b_th = sigma * jax.random.normal(step_key, m.shape)
            m = rk4_step(lambda mm, tt: llg.llg_rhs(mm, p, aj, b_th), m, 0.0, dt)
            sw = jnp.logical_or(sw, llg.order_parameter_z(m) < -0.9)
            return (m, sw), None

        (m, sw), _ = jax.lax.scan(body, (m0, jnp.asarray(False)),
                                  jax.random.split(kr, n_steps))
        return sw

    switched = jax.vmap(one)(keys)
    return 1.0 - jnp.mean(switched.astype(jnp.float32))

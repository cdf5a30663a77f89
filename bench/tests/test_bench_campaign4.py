"""The four-chip campaign cell at a tiny size on four virtual CPU devices:
a sound run is correct, and a run whose chips' results after the first
never reach the host is not."""
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

SCRIPT = """
import sys
sys.path.insert(0, {here!r})
import jax, jax.numpy as jnp
from bench_tiny import run_tiny
CELL = "afmtj.wer_campaign.4chip"
assert len(jax.devices()) == 4
print("sound", run_tiny(CELL)["correct"])
from repro.campaign import engine
orig = engine.llg_rk4_pallas
def first_chip_only(state, p, dt, n_steps, *a, **kw):
    out = orig(state, p, dt, n_steps, *a, **kw)
    mine = jax.lax.axis_index("cells") == 0
    return out.at[7].set(jnp.where(mine, out[7], float(n_steps)))
engine.llg_rk4_pallas = first_chip_only
jax.clear_caches()
print("exchange_left_out", run_tiny(CELL)["correct"])
"""


def test_four_chip_campaign_sound_and_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(here=str(HERE))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = dict(line.split() for line in p.stdout.splitlines()
                 if line.startswith(("sound", "exchange_left_out")))
    assert lines == {"sound": "True", "exchange_left_out": "False"}

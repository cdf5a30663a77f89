"""Hard-fault injection for AFMTJ crossbars: stuck-at cells, dead lines,
endurance wear-out, and the repair policies that contain them (DESIGN.md §13).

PR 5/6 model *parametric* non-idealities (corners, disturb, retention); this
module models *hard* defects — the failure modes a production memory ships
defect maps and spare rows for:

  stuck-at-G_off  cell pinned at the G_AP floor (electrode void, open)
  stuck-at-G_on   cell pinned at G_AP + G_FS (dielectric short): the nasty
                  one — a full-scale wrong weight, not a missing weight
  dead row/col    word-line / bit-line driver failures killing a whole line
  endurance wear  per-write-cycle Bernoulli wear-out that folds into an
                  effective stuck-off rate (cells die open as they cycle)
  drift           slow lognormal conductance relaxation (device path only,
                  like D2D sigma — the fake path raises)

Everything is drawn by the same stateless counter-RNG discipline as the
variation planes (``kernels/noise.py``): a draw depends only on
(seed, stream, lane), never on the fault *rate* or the repair policy, so

  * rates are **data** — the fake-analog path feeds them as traced scalars
    and a whole fault-rate sweep reuses ONE XLA compile (pinned in
    ``tests/test_faults.py``), and raising the rate only *adds* defects
    (monotone coupling: the u <= rate threshold test shares uniforms
    across rates);
  * repair policies are CRN-paired — ``apply_repair`` transforms the same
    defect map, so policy A vs policy B comparisons see identical defects.

Fault codes are bit-ORs (``kernels/fake_analog.FAULT_*``) riding the
existing ``fail`` operand of the fused kernel; dead columns ride the aux
attenuation rows.  Masks are planes of data, not compile keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels import noise
from repro.kernels.fake_analog import (
    FAULT_DEAD,
    FAULT_NEG_OFF,
    FAULT_NEG_ON,
    FAULT_POS_OFF,
    FAULT_POS_ON,
    fail_bit,
)

# stream ids of the per-lane uniform draws (disjoint by construction)
_STREAM_POS = 0      # positive-cell defect class
_STREAM_NEG = 1      # negative-cell defect class
_STREAM_ROW = 2      # dead row drivers
_STREAM_COL = 3      # dead column drivers
_STREAM_DRIFT_P = 4  # conductance drift, positive array (device path)
_STREAM_DRIFT_N = 5  # conductance drift, negative array (device path)

_FAULT_GOLD = np.uint32(0x9E3779B1)
_FAULT_STREAM = 0xC2B2AE35


def _lane_seeds(seed, stream: int, count: int) -> jnp.ndarray:
    """(count,) uint32 stream seeds; ``seed`` may be traced (uint32 scalar).

    Mirrors ``noise.cell_seeds`` salted like ``VariationSpec._normals`` —
    but in pure jnp so the fake path can feed the seed as data.
    """
    base = (jnp.asarray(seed).astype(jnp.uint32) * _FAULT_GOLD
            + np.uint32(((stream + 1) * _FAULT_STREAM) & 0xFFFFFFFF))
    idx = jnp.arange(count, dtype=jnp.uint32)
    return noise.mix32(noise.mix32(base + idx * np.uint32(0x9E3779B9)))


def _lane_uniforms(seed, stream: int, count: int) -> jnp.ndarray:
    """(count,) f32 uniforms in (0, 1] — ``u <= rate`` at rate 0 is never
    true, so a zero-rate plane is exactly the empty defect map."""
    return noise._uniform24(_lane_seeds(seed, stream, count))


def fault_code_plane(rows: int, cols: int, *, seed, stuck_on, stuck_off,
                     dead_row) -> jnp.ndarray:
    """(rows, cols) f32 bit-code defect plane.

    ``seed`` and the three rates may be traced scalars (the fake-analog
    path passes them as data) or concrete floats (the device path).  One
    uniform per cell is split into disjoint [0, p_off] stuck-off and
    (p_off, p_off + p_on] stuck-on intervals, so the defect *positions*
    are a pure function of (seed, stream, lane) — CRN across rates and
    repair policies.
    """
    p_off = jnp.asarray(stuck_off, jnp.float32)
    p_on = jnp.asarray(stuck_on, jnp.float32)
    u_pos = _lane_uniforms(seed, _STREAM_POS, rows * cols).reshape(rows, cols)
    u_neg = _lane_uniforms(seed, _STREAM_NEG, rows * cols).reshape(rows, cols)
    u_row = _lane_uniforms(seed, _STREAM_ROW, rows)
    dead = (u_row <= jnp.asarray(dead_row, jnp.float32))[:, None]
    code = ((u_pos <= p_off) * float(FAULT_POS_OFF)
            + (u_neg <= p_off) * float(FAULT_NEG_OFF)
            + ((u_pos > p_off) & (u_pos <= p_off + p_on)) * float(FAULT_POS_ON)
            + ((u_neg > p_off) & (u_neg <= p_off + p_on)) * float(FAULT_NEG_ON)
            + dead * float(FAULT_DEAD))
    return code.astype(jnp.float32)


def column_ok_plane(cols: int, *, seed, dead_col) -> jnp.ndarray:
    """(cols,) f32 column-health plane: 1.0 healthy, 0.0 dead driver."""
    u = _lane_uniforms(seed, _STREAM_COL, cols)
    return (u > jnp.asarray(dead_col, jnp.float32)).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Hard-fault model knobs.  Hashable (rides ``AnalogConfig`` and cache
    keys); all rates are per-cell/per-line Bernoulli probabilities."""

    stuck_on_rate: float = 0.0    # cell pinned at G_on = G_AP + G_FS
    stuck_off_rate: float = 0.0   # cell pinned at the G_AP floor
    dead_row_rate: float = 0.0    # word-line driver dead (whole row)
    dead_col_rate: float = 0.0    # bit-line driver dead (whole column)
    wear_per_cycle: float = 0.0   # per-write-cycle wear-out Bernoulli
    write_cycles: float = 0.0     # cycles endured -> folds into stuck-off
    drift_sigma: float = 0.0      # lognormal conductance drift (device only)
    seed: int = 0
    rate: float = 0.0             # headline knob that sized the component
    #                               rates via ``at_rate`` (reporting only)

    @property
    def wear_rate(self) -> float:
        """P(cell has worn out open) after ``write_cycles`` cycles."""
        if self.wear_per_cycle <= 0.0 or self.write_cycles <= 0.0:
            return 0.0
        return 1.0 - (1.0 - self.wear_per_cycle) ** self.write_cycles

    @property
    def stuck_off_effective(self) -> float:
        """Stuck-off rate with endurance wear folded in (independent OR)."""
        return 1.0 - (1.0 - self.stuck_off_rate) * (1.0 - self.wear_rate)

    @property
    def cell_fault_rate(self) -> float:
        return self.stuck_on_rate + self.stuck_off_effective

    @property
    def any_faults(self) -> bool:
        return (self.cell_fault_rate > 0.0 or self.dead_row_rate > 0.0
                or self.dead_col_rate > 0.0 or self.drift_sigma > 0.0)

    @classmethod
    def at_rate(cls, rate: float, *, seed: int = 0,
                drift_sigma: float = 0.0) -> "FaultSpec":
        """Canonical single-knob mix used by the degradation sweeps:
        35% stuck-on, 35% stuck-off, 20% dead rows, 10% dead columns."""
        r = float(rate)
        return cls(stuck_on_rate=0.35 * r, stuck_off_rate=0.35 * r,
                   dead_row_rate=0.20 * r, dead_col_rate=0.10 * r,
                   drift_sigma=drift_sigma, seed=seed, rate=r)

    def planes(self, rows: int, cols: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Concrete (code, col_ok) defect planes for one array."""
        code = fault_code_plane(
            rows, cols, seed=np.uint32(self.seed & 0xFFFFFFFF),
            stuck_on=self.stuck_on_rate, stuck_off=self.stuck_off_effective,
            dead_row=self.dead_row_rate)
        col_ok = column_ok_plane(
            cols, seed=np.uint32(self.seed & 0xFFFFFFFF),
            dead_col=self.dead_col_rate)
        return code, col_ok


@dataclasses.dataclass(frozen=True)
class RepairPolicy:
    """Array repair knobs.  Hashable — the policy is a *compile key* (it
    restructures the trace); the fault rates stay data."""

    name: str = "none"
    spare_rows: int = 0          # remap capacity: worst rows -> spares
    spare_cols: int = 0          # revive capacity: dead columns -> spares
    mask_pairs: bool = False     # differential-pair-aware masking
    ecc_cells_per_row: int = 0   # lightweight ECC: stuck cells corrected/row


REPAIR_NONE = RepairPolicy()
REPAIR_SPARE = RepairPolicy(name="spare", spare_rows=8, spare_cols=8,
                            mask_pairs=True)
REPAIR_SPARE_ECC = RepairPolicy(name="spare+ecc", spare_rows=8, spare_cols=8,
                                mask_pairs=True, ecc_cells_per_row=1)
REPAIR_POLICIES = (REPAIR_NONE, REPAIR_SPARE, REPAIR_SPARE_ECC)


def apply_repair(code: jnp.ndarray, col_ok: jnp.ndarray,
                 policy: Optional[RepairPolicy]):
    """Transform the defect map the way the repair controller would.

    Fully traced (policy capacities are static ints), and draw-free: repair
    never consumes RNG, so the underlying defect map is identical across
    policies (CRN invariance, pinned in tests/test_faults.py).  Order:

      1. ECC side-table corrects up to ``ecc_cells_per_row`` stuck (not
         dead) pairs per row — their codes clear entirely.
      2. Differential-pair masking converts remaining stuck-ON pairs to
         dead pairs: a short contributes a full-scale wrong weight, a
         masked pair only loses |w| — bounded error.
      3. Spare-row remap clears the ``spare_rows`` worst faulty rows.
      4. Spare columns revive up to ``spare_cols`` dead columns.
    """
    if policy is None or policy == REPAIR_NONE:
        return code, col_ok
    rows = code.shape[0]
    dead = fail_bit(code, FAULT_DEAD)
    if policy.ecc_cells_per_row > 0:
        stuck = (code > 0.0) & ~dead
        cum = jnp.cumsum(stuck.astype(jnp.float32), axis=1)
        clear = stuck & (cum <= float(policy.ecc_cells_per_row))
        code = jnp.where(clear, 0.0, code)
    if policy.mask_pairs:
        stuck_on = ((fail_bit(code, FAULT_POS_ON)
                     | fail_bit(code, FAULT_NEG_ON)) & ~dead)
        code = jnp.where(stuck_on, float(FAULT_DEAD), code)
    if policy.spare_rows > 0:
        row_bad = jnp.sum((code > 0.0).astype(jnp.float32), axis=1)
        sel = jnp.argsort(-row_bad)[: policy.spare_rows]
        is_spare = jnp.zeros((rows,), bool).at[sel].set(True)
        is_spare = is_spare & (row_bad > 0.0)
        code = jnp.where(is_spare[:, None], 0.0, code)
    if policy.spare_cols > 0:
        dead_c = col_ok < 0.5
        cum_c = jnp.cumsum(dead_c.astype(jnp.float32))
        revive = dead_c & (cum_c <= float(policy.spare_cols))
        col_ok = jnp.where(revive, 1.0, col_ok)
    return code, col_ok


def apply_cell_faults(code: jnp.ndarray, g_pos: jnp.ndarray,
                      g_neg: jnp.ndarray, *, g_off, g_on):
    """Overwrite programmed conductances with the stuck/dead fault codes —
    the device-path twin of the decode inside ``pos_neg_conductance``
    (same priority: floor, then stuck-on, then dead)."""
    g_pos = jnp.where(fail_bit(code, FAULT_POS_OFF), g_off, g_pos)
    g_neg = jnp.where(fail_bit(code, FAULT_NEG_OFF), g_off, g_neg)
    g_pos = jnp.where(fail_bit(code, FAULT_POS_ON), g_on, g_pos)
    g_neg = jnp.where(fail_bit(code, FAULT_NEG_ON), g_on, g_neg)
    dead = fail_bit(code, FAULT_DEAD)
    g_pos = jnp.where(dead, 0.0, g_pos)
    g_neg = jnp.where(dead, 0.0, g_neg)
    return g_pos, g_neg


def drift_factors(spec: FaultSpec, rows: int, cols: int, *,
                  negative: bool) -> jnp.ndarray:
    """(rows, cols) mean-preserving lognormal drift multipliers,
    exp(sigma*z - sigma^2/2).  Device path only — the fused fake path
    raises on drift_sigma > 0 (same contract as D2D sigma)."""
    stream = _STREAM_DRIFT_N if negative else _STREAM_DRIFT_P
    lanes = _lane_seeds(np.uint32(spec.seed & 0xFFFFFFFF), stream,
                        rows * cols)
    z, _ = noise.normal_pair(lanes, jnp.uint32(0))
    s = float(spec.drift_sigma)
    return jnp.exp(s * z - 0.5 * s * s).reshape(rows, cols)

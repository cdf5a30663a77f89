"""End-to-end functional analog MVM through the Pallas bitline/XNOR kernels.

This is the read-path counterpart of the write-path campaign engine
(``repro.campaign``): instead of *timing* the crossbar GEMV with closed-form
algebra (``imc.mapping``), it actually **computes** one — programming a
weight matrix into per-cell conductances from the device TMR, driving the
word lines with activation-scaled read voltages, accumulating bit-line
currents in the Pallas MXU kernel (``kernels.bitline_mac``), attenuating
per-column for IR drop (``circuit.bitline.column_ir_drop``), and quantizing
through the signed ADC — so the repo can answer "is the computed result
numerically usable", not just "how fast is it".

Signal chain (DESIGN.md §6):

  1. **Programming** — differential 2-cell encoding.  Weights are normalized
     to [-1, 1] by ``w_scale = max|w|`` and mapped linearly onto the
     *effective* cell conductance span [G_AP, G_P] (junction through the
     access transistor): the positive cell stores max(w, 0), the negative
     cell max(-w, 0), both riding on the G_AP floor.  Programming is
     write-verify pre-compensated (the linear map targets effective
     conductance), so device-to-device variation — a single-corner
     ``core.params.VariationSpec`` whose junction resistance factor
     perturbs the programmed conductance (``g_sigma`` survives as a
     deprecated alias that constructs the equivalent spec) — is the
     residual programming error; cells whose
     write-verify attempt budget ran out (``write_ber``, measured by
     ``imc.write_path`` — DESIGN.md §7) stay at the erased G_AP floor.
  2. **IR drop** — each differential line attenuates by its own column
     factor (heavier-loaded columns sag more).  The *mean* factor is a
     one-point gain calibration (divided out at decode); the per-column and
     pos/neg spread remains as gain error.
  3. **MVM** — I = V @ G_diff on the MXU, where G_diff = G+ - G- is the
     differential conductance the sense node sees (linearity makes one
     kernel pass over G_diff exact for the two-array subtraction).
  4. **ADC** — signed symmetric quantizer, full scale auto-sized to
     ``full_scale_sigmas`` column-current standard deviations (the
     read-driver co-design knob: too small clips, too large wastes codes).

The batch (word-line drive) axis is embarrassingly parallel, so ``cells``
shards across devices with ``shard_map`` exactly like the campaign engine —
weights replicated (they are *resident* in the arrays), activations split.

The 1-bit path (``binary_matmul``) binarizes both operands to +-1 and runs
the XNOR-popcount kernel (``kernels.xnor_gemm``) with per-column |w| scales
— the paper's *bnn* mode applied to a projection.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.circuit.bitline import BitlineParams, cell_conductance, column_ir_drop
from repro.core.params import (AFMTJ_PARAMS, MTJ_PARAMS, DeviceParams,
                               VariationSpec)
from repro.imc import faults as hard_faults
from repro.imc.faults import FaultSpec, RepairPolicy
from repro.kernels.bitline_mac import bitline_mac_pallas
from repro.kernels.ops import _default_interpret
from repro.kernels.xnor_gemm import xnor_gemm_pallas


@dataclasses.dataclass(frozen=True)
class AnalogConfig:
    """Read/write-path non-ideality knobs (the accuracy surface axes)."""

    adc_bits: int = 6              # 0 = ideal ADC (no quantization)
    tmr: Optional[float] = None    # device TMR override (None = device default)
    v_read: float = 0.1            # DAC full-scale read voltage [V]
    g_sigma: float = 0.0           # DEPRECATED alias: lognormal D2D junction
                                   # conductance sigma — internally rewritten
                                   # to ``VariationSpec.from_g_sigma`` (with a
                                   # DeprecationWarning); set ``variation``
    ir_drop: bool = True           # per-column bit-line IR attenuation
    full_scale_sigmas: float = 4.0 # ADC full scale in column-current sigmas
    seed: int = 0                  # programming-variation draw
    write_ber: float = 0.0         # residual write-error rate: probability a
                                   # cell's write-verify budget ran out and it
                                   # still sits at the erased G_AP floor
                                   # (measured by ``imc.write_path``)
    # Single source of truth for D2D / process-corner draws (DESIGN.md §9):
    # a single-corner VariationSpec whose junction resistance factor
    # (systematic r_factor x lognormal sigma_r) perturbs the programmed
    # junction conductance — same spec, same counter-RNG streams as the
    # write-path and campaign-engine variation planes.
    variation: Optional[VariationSpec] = None
    # Hard-defect model (DESIGN.md §13): stuck-at / dead-line / wear fault
    # planes drawn by ``imc.faults`` — presence of a spec switches the
    # fault machinery on (an all-zero-rate spec is the empty defect map,
    # bit-identical to ``None``), and the optional repair policy transforms
    # the defect map the way the array's repair controller would.
    faults: Optional[FaultSpec] = None
    repair: Optional[RepairPolicy] = None


@dataclasses.dataclass(frozen=True)
class ProgrammedArray:
    """A weight matrix resident in a differential crossbar pair."""

    g_diff: jnp.ndarray      # (K, N) effective differential conductance [S]
    w_scale: float           # |w|_max used for normalization
    g_fs: float              # unit-weight differential conductance G_P-G_AP [S]
    att_mean: float          # mean IR-drop factor (decode gain calibration)
    g_rms: float             # rms of g_diff (ADC full-scale sizing)
    dev: DeviceParams
    bl: BitlineParams
    cfg: AnalogConfig

    @property
    def shape(self) -> Tuple[int, int]:
        return self.g_diff.shape


def _device_for(kind: str, cfg: AnalogConfig) -> DeviceParams:
    dev = AFMTJ_PARAMS if kind == "afmtj" else MTJ_PARAMS
    if cfg.tmr is not None:
        dev = dataclasses.replace(dev, tmr=float(cfg.tmr))
    return dev


def _resolved_variation(cfg: AnalogConfig) -> Optional[VariationSpec]:
    """The D2D spec programming actually uses: ``cfg.variation``, or the
    deprecated ``g_sigma`` rewritten to its equivalent spec (the reciprocal
    of the spec's mean-conductance-preserving lognormal resistance draw is
    exactly the old mean-preserving lognormal on the conductance)."""
    if cfg.variation is not None:
        assert cfg.g_sigma == 0.0, (
            "set either AnalogConfig.variation or the deprecated g_sigma, "
            "not both — fold the D2D sigma into the spec's sigma_r")
        assert cfg.variation.n_corners == 1, (
            "read-path programming models one corner's array; sweep corners "
            "by programming one AnalogConfig per corner (spec.at_corner)")
        return cfg.variation
    if cfg.g_sigma > 0.0:
        warnings.warn(
            "AnalogConfig.g_sigma is deprecated; pass variation="
            "VariationSpec.from_g_sigma(g_sigma, seed) instead (single "
            "source of truth for D2D draws, DESIGN.md §9)",
            DeprecationWarning, stacklevel=3)
        return VariationSpec.from_g_sigma(cfg.g_sigma, seed=cfg.seed)
    return None


def program_weights(
    w: jnp.ndarray,                  # (K, N) float weights
    kind: str = "afmtj",
    cfg: AnalogConfig = AnalogConfig(),
    bl: Optional[BitlineParams] = None,
) -> ProgrammedArray:
    """Program ``w`` into a differential conductance pair (steps 1-2 above)."""
    assert w.ndim == 2, w.shape
    k_rows = w.shape[0]
    dev = _device_for(kind, cfg)
    bl = bl or BitlineParams(rows=k_rows)

    g_p_eff = float(cell_conductance(jnp.asarray(1.0 / dev.r_parallel), bl))
    g_ap_eff = float(cell_conductance(jnp.asarray(1.0 / dev.r_antiparallel), bl))
    g_fs = g_p_eff - g_ap_eff

    w = jnp.asarray(w, jnp.float32)
    w_scale = float(jnp.max(jnp.abs(w)))
    if w_scale == 0.0:
        w_scale = 1.0
    wn = w / w_scale
    tgt_pos = g_ap_eff + jnp.maximum(wn, 0.0) * g_fs
    tgt_neg = g_ap_eff + jnp.maximum(-wn, 0.0) * g_fs

    spec = _resolved_variation(cfg)
    if spec is not None:
        # variation lives on the junction (DESIGN.md §9): push the
        # write-verify target back through the access FET, apply the
        # spec's per-junction resistance factor (systematic corner x D2D
        # draw, same counter-RNG streams as the write path), come forward
        # again.  Streams 0/1 decorrelate the pos/neg array.
        corner = spec.corners[0]

        def perturb(tgt, stream):
            g_j = tgt / (1.0 - bl.r_access * tgt)
            r_f = spec.lane_factors(corner, tgt.size, stream=stream)[3]
            g_scale = jnp.asarray(
                (1.0 / r_f).reshape(tgt.shape), jnp.float32)
            return cell_conductance(g_j * g_scale, bl)

        g_pos, g_neg = perturb(tgt_pos, 0), perturb(tgt_neg, 1)
    else:
        g_pos, g_neg = tgt_pos, tgt_neg

    if cfg.faults is not None and cfg.faults.drift_sigma > 0.0:
        # slow conductance relaxation of the programmed targets; hard fault
        # codes and write-verify floors override it below
        g_pos = g_pos * hard_faults.drift_factors(
            cfg.faults, w.shape[0], w.shape[1], negative=False)
        g_neg = g_neg * hard_faults.drift_factors(
            cfg.faults, w.shape[0], w.shape[1], negative=True)

    if cfg.write_ber > 0.0:
        # residual write errors (imc.write_path, DESIGN.md §7): a cell whose
        # write-verify attempt budget ran out never left the erased state,
        # so it reads back at the G_AP floor instead of its target.  The
        # fold_in constant keeps the g_sigma draw stream unchanged.
        kber = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 0x5EB)
        kb1, kb2 = jax.random.split(kber)
        fail_pos = jax.random.bernoulli(kb1, cfg.write_ber, tgt_pos.shape)
        fail_neg = jax.random.bernoulli(kb2, cfg.write_ber, tgt_neg.shape)
        g_pos = jnp.where(fail_pos, g_ap_eff, g_pos)
        g_neg = jnp.where(fail_neg, g_ap_eff, g_neg)

    col_ok = None
    if cfg.faults is not None:
        # hard defects (DESIGN.md §13), applied *before* IR drop so stuck-on
        # shorts load their columns and dead pairs unload theirs — exactly
        # mirroring the fused fake-analog decode order (floor -> stuck-on
        # -> dead inside ``pos_neg_conductance``)
        code, col_ok = cfg.faults.planes(w.shape[0], w.shape[1])
        if cfg.repair is not None:
            code, col_ok = hard_faults.apply_repair(code, col_ok, cfg.repair)
        g_pos, g_neg = hard_faults.apply_cell_faults(
            code, g_pos, g_neg, g_off=g_ap_eff, g_on=g_ap_eff + g_fs)

    att_mean = 1.0
    if cfg.ir_drop:
        att_pos = column_ir_drop(jnp.sum(g_pos, axis=0), bl)
        att_neg = column_ir_drop(jnp.sum(g_neg, axis=0), bl)
        g_pos = g_pos * att_pos[None, :]
        g_neg = g_neg * att_neg[None, :]
        att_mean = float(0.5 * (jnp.mean(att_pos) + jnp.mean(att_neg)))

    if col_ok is not None:
        # dead bit-line drivers: their columns read zero on both arrays and
        # the decode gain calibrates over *live* columns only
        g_pos = g_pos * col_ok[None, :]
        g_neg = g_neg * col_ok[None, :]
        if cfg.ir_drop:
            # same association as the no-fault mean so an all-live plane is
            # bit-identical: 0.5 * (sum_p/live + sum_n/live)
            live = max(float(jnp.sum(col_ok)), 1.0)
            att_mean = float(0.5 * (jnp.sum(att_pos * col_ok) / live
                                    + jnp.sum(att_neg * col_ok) / live))

    g_diff = g_pos - g_neg
    g_rms = float(jnp.sqrt(jnp.mean(g_diff * g_diff)))
    return ProgrammedArray(g_diff=g_diff, w_scale=w_scale, g_fs=g_fs,
                           att_mean=att_mean, g_rms=g_rms, dev=dev, bl=bl,
                           cfg=cfg)


def _usable_devices(m: int, devices: Optional[int]) -> int:
    n = jax.device_count() if devices is None else min(devices, jax.device_count())
    return max(min(n, m), 1)


@functools.partial(jax.jit, static_argnames=(
    "adc_bits", "i_max", "interpret", "n_dev"))
def _mvm_sharded(v, g, *, adc_bits: int, i_max: float, interpret: bool,
                 n_dev: int):
    """V @ G through the bitline kernel, batch rows sharded over devices."""

    def tile(vv, gg):
        return bitline_mac_pallas(vv, gg, adc_bits, i_max, interpret=interpret)

    if n_dev == 1:
        return tile(v, g)
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("batch",))
    # check_vma=False: pallas_call has no varying-axes rule
    fn = jax.shard_map(tile, mesh=mesh,
                       in_specs=(P("batch", None), P(None, None)),
                       out_specs=P("batch", None), check_vma=False)
    return fn(v, g)


def kernel_operands(
    arr: ProgrammedArray, x: jnp.ndarray
) -> Tuple[jnp.ndarray, float, float]:
    """The exact (v, i_max, x_scale) ``analog_matmul`` feeds the kernel —
    exposed so parity checks (``tests/test_analog_pipeline.py``) reconstruct
    the same operands instead of copying the derivation.

    Activations map to bipolar word-line read voltages (``v_read`` full
    scale).  The ADC full scale comes from column-current statistics (an
    independence estimate), rounded to 2 significant digits to bound
    jit-cache churn across sweeps.
    """
    cfg = arr.cfg
    x = jnp.asarray(x, jnp.float32)
    x_scale = float(jnp.max(jnp.abs(x)))
    if x_scale == 0.0:
        x_scale = 1.0
    v = cfg.v_read * x / x_scale
    v_rms = float(jnp.sqrt(jnp.mean(v * v)))
    i_sigma = v_rms * arr.g_rms * math.sqrt(x.shape[1])
    i_max = float(f"{max(cfg.full_scale_sigmas * i_sigma, 1e-30):.2g}")
    return v, i_max, x_scale


def analog_matmul(
    arr: ProgrammedArray,
    x: jnp.ndarray,                  # (M, K) activations (signed)
    devices: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Run ``x @ w`` through the programmed crossbar (steps 3-4).

    The ADC result is decoded back to weight/activation units via the
    programming scales and the mean IR-drop calibration factor.
    """
    assert x.ndim == 2 and x.shape[1] == arr.g_diff.shape[0], (
        x.shape, arr.g_diff.shape)
    cfg = arr.cfg
    m = x.shape[0]
    v, i_max, x_scale = kernel_operands(arr, x)

    n_dev = _usable_devices(m, devices)
    pad = -m % n_dev
    if pad:
        v = jnp.pad(v, ((0, pad), (0, 0)))
    interp = _default_interpret() if interpret is None else interpret
    i_out = _mvm_sharded(v, arr.g_diff, adc_bits=cfg.adc_bits, i_max=i_max,
                         interpret=interp, n_dev=n_dev)
    if pad:
        i_out = i_out[:m]
    return i_out * (x_scale * arr.w_scale) / (
        cfg.v_read * arr.g_fs * arr.att_mean)


def binary_matmul(
    x: jnp.ndarray,                  # (M, K) float activations
    w: jnp.ndarray,                  # (K, N) float weights
    tie: int = 1,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """1-bit (XNOR-popcount) projection: sign-binarize both operands, run the
    XNOR kernel, rescale by per-column mean |w| and scalar mean |x| (the
    standard BNN first-order correction)."""
    x = jnp.asarray(x, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    from repro.kernels.xnor_gemm import binarize_acc

    xb = binarize_acc(x, tie)
    wb = binarize_acc(w, tie)
    interp = _default_interpret() if interpret is None else interpret
    pops = xnor_gemm_pallas(xb, wb, binarize=False, tie=tie, interpret=interp)
    alpha_w = jnp.mean(jnp.abs(w), axis=0)      # (N,)
    alpha_x = jnp.mean(jnp.abs(x))
    return pops * alpha_w[None, :] * alpha_x


@dataclasses.dataclass(frozen=True)
class AccuracyReport:
    """Output error of one analog MVM vs the f32 matmul oracle."""

    arch: str
    kind: str
    mode: str                      # "analog" (bitline+ADC) | "bnn" (xnor)
    adc_bits: int
    tmr: float
    g_sigma: float
    m: int
    k: int
    n: int
    mse: float
    nmse: float                    # mse / mean(y_ref^2)
    cosine: float
    max_abs_err: float
    write_ber: float = 0.0         # injected residual write-error rate


def _report(y, y_ref, *, arch, kind, mode, cfg: AnalogConfig, tmr: float
            ) -> AccuracyReport:
    y = np.asarray(y, np.float64)
    y_ref = np.asarray(y_ref, np.float64)
    err = y - y_ref
    mse = float(np.mean(err**2))
    ref_pw = float(np.mean(y_ref**2))
    cos = float(np.sum(y * y_ref) /
                max(np.linalg.norm(y) * np.linalg.norm(y_ref), 1e-30))
    return AccuracyReport(
        arch=arch, kind=kind, mode=mode, adc_bits=cfg.adc_bits, tmr=tmr,
        g_sigma=cfg.g_sigma, m=y.shape[0], k=0, n=y.shape[1], mse=mse,
        nmse=mse / max(ref_pw, 1e-30), cosine=cos,
        max_abs_err=float(np.max(np.abs(err))), write_ber=cfg.write_ber)


def mvm_accuracy(
    w: jnp.ndarray,
    x: jnp.ndarray,
    kind: str = "afmtj",
    cfg: AnalogConfig = AnalogConfig(),
    mode: str = "analog",
    arch: str = "",
    devices: Optional[int] = None,
) -> AccuracyReport:
    """Program ``w``, run ``x`` through the kernel path, score vs f32."""
    y_ref = jnp.asarray(x, jnp.float32) @ jnp.asarray(w, jnp.float32)
    if mode == "analog":
        arr = program_weights(w, kind, cfg)
        y = analog_matmul(arr, x, devices=devices)
        tmr = arr.dev.tmr
    elif mode == "bnn":
        y = binary_matmul(x, w)
        tmr = _device_for(kind, cfg).tmr
    else:
        raise ValueError(f"unknown mode {mode!r}")
    rep = _report(y, y_ref, arch=arch, kind=kind, mode=mode, cfg=cfg, tmr=tmr)
    return dataclasses.replace(rep, k=int(w.shape[0]))
